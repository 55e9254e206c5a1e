"""Deterministic chaos e2e: with seeded faults on the filer→volume and
s3→filer hops (5% injected 503s + 30ms delays), 200 S3 PUT/GET cycles
must all succeed with zero duplicate writes — the injected 503s carry
X-Sw-Retryable (rejected before any state was touched), so the retry
layer replays them safely.  Also exercises the breaker trip/recover
cycle against a real listener and the EC degraded-read codec pin."""
import contextlib
import socket
import time
import types

import pytest
import requests

from seaweedfs_tpu.rpc.http import ServerThread
from seaweedfs_tpu.rpc.httpclient import session
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.utils import faults, retry

pytestmark = pytest.mark.chaos

CHAOS_SPEC = ("volume:*:error=0.05,filer:*:error=0.05,"
              "volume:*:delay=30ms,filer:*:delay=30ms")
CYCLES = 200


@contextlib.contextmanager
def _chaos(spec, seed=20240817, max_attempts=5):
    """Enable seeded faults + a deeper retry budget for the duration;
    the registries are process-global, so always restore defaults."""
    faults.configure(spec, seed=seed)
    retry.configure(max_attempts=max_attempts)
    retry.reset_breakers()
    try:
        yield
    finally:
        faults.configure(spec=None)
        retry.configure(max_attempts=3)
        retry.reset_breakers()


class TestChaosPutGet:
    def test_200_cycles_all_succeed_no_duplicates(self, tmp_path):
        c = Cluster(str(tmp_path), n_volume_servers=2,
                    volume_size_limit=64 << 20,
                    with_filer=True, with_s3=True)
        base = c.s3_url.rstrip("/")
        try:
            assert requests.put(f"{base}/chaos", timeout=30
                                ).status_code == 200
            with _chaos(CHAOS_SPEC):
                for i in range(CYCLES):
                    body = (f"chaos-{i}-".encode() * 8)[:100 + i]
                    p = requests.put(f"{base}/chaos/obj-{i:03d}", data=body,
                                     timeout=30)
                    assert p.status_code == 200, (i, p.status_code, p.text)
                    g = requests.get(f"{base}/chaos/obj-{i:03d}", timeout=30)
                    assert g.status_code == 200, (i, g.status_code)
                    assert g.content == body, i
                injected = faults.counts()
                # the chaos actually fired on both hop classes
                assert injected.get("filer:error", 0) > 0, injected
                assert injected.get("volume:error", 0) > 0, injected
                assert injected.get("filer:delay", 0) > 0, injected
            # zero duplicate writes: exactly one key per PUT survives
            r = requests.get(f"{base}/chaos?list-type=2&max-keys=1000",
                             timeout=30)
            assert r.status_code == 200
            keys = [seg.split("</Key>")[0] for seg in
                    r.text.split("<Key>")[1:]]
            assert sorted(keys) == [f"obj-{i:03d}" for i in range(CYCLES)]
            assert len(set(keys)) == CYCLES
        finally:
            c.stop()

    def test_edge_deadline_minted_and_propagated(self, tmp_path):
        """The s3 edge mints X-Sw-Deadline when the client sent none;
        an expired client deadline is refused before any work."""
        c = Cluster(str(tmp_path), n_volume_servers=1,
                    volume_size_limit=64 << 20,
                    with_filer=True, with_s3=True)
        base = c.s3_url.rstrip("/")
        try:
            assert requests.put(f"{base}/dl", timeout=30).status_code == 200
            r = requests.put(f"{base}/dl/k", data=b"x", timeout=30,
                             headers={retry.DEADLINE_HEADER:
                                      str(time.time() - 5)})
            assert r.status_code == 504
            assert requests.get(f"{base}/dl/k", timeout=30
                                ).status_code == 404
        finally:
            c.stop()


class TestBreakerTripAndRecover:
    def test_breaker_trips_on_dead_peer_then_recovers(self, tmp_path):
        """Drive real connection-refused failures at a closed port until
        the breaker opens (asserted via the exposed /debug/breakers
        state), then bring a listener up on that same port and watch the
        half-open probe close it."""
        c = Cluster(str(tmp_path), n_volume_servers=1,
                    volume_size_limit=64 << 20)
        # reserve a port, then close it so connects are refused
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        peer = f"127.0.0.1:{port}"
        retry.reset_breakers()
        retry.configure(breaker_failures=3, breaker_reset=0.3)
        try:
            sess = session()
            for _ in range(6):
                with pytest.raises(OSError):
                    sess.get(f"http://{peer}/ping", timeout=2)
            snap = {b["peer"]: b for b in requests.get(
                f"{c.master_url}/debug/breakers", timeout=10
            ).json()["breakers"]}
            assert snap[peer]["state"] == retry.OPEN, snap
            assert snap[peer]["trips"] >= 1
            # while open: fail fast, no connect attempted
            t0 = time.monotonic()
            with pytest.raises(retry.BreakerOpenError):
                sess.get(f"http://{peer}/ping", timeout=2)
            assert time.monotonic() - t0 < 0.5
            # peer comes back on the same port; after reset_timeout the
            # half-open probe succeeds and the breaker closes
            from aiohttp import web

            async def ping(request):
                return web.Response(text="pong")

            app = web.Application()
            app.router.add_get("/ping", ping)
            revived = ServerThread(app, port=port).start()
            try:
                time.sleep(0.35)
                r = sess.get(f"http://{peer}/ping", timeout=5)
                assert r.status_code == 200
                snap = {b["peer"]: b for b in requests.get(
                    f"{c.master_url}/debug/breakers", timeout=10
                ).json()["breakers"]}
                assert snap[peer]["state"] == retry.CLOSED, snap
            finally:
                revived.stop()
            # breaker state also rides the master topology dump
            topo = requests.get(f"{c.master_url}/dir/status",
                                timeout=10).json()["Topology"]
            nodes = [n for dc in topo["datacenters"]
                     for r in dc["racks"] for n in r["nodes"]]
            assert nodes and all(
                n["breaker"] in (retry.CLOSED, retry.OPEN,
                                 retry.HALF_OPEN) for n in nodes), topo
        finally:
            retry.configure(breaker_failures=5, breaker_reset=5.0)
            retry.reset_breakers()
            c.stop()


class TestDegradedReadCodecPin:
    def test_interval_reconstruct_pinned_to_cpu_codec(self, tmp_path):
        """With -ec.backend=pallas forced, single-needle degraded reads
        still reconstruct on the native/CPU codec — a device dispatch
        on a GET's critical path is pure latency."""
        from seaweedfs_tpu.ec.backend import cpu_backend_name
        from seaweedfs_tpu.storage.store import Store

        store = Store([str(tmp_path)], ip="127.0.0.1", port=0,
                      ec_backend="pallas")
        ecv = types.SimpleNamespace(k=10, m=4)
        rs = store._rs_for(ecv, interval=True)
        assert rs.backend.name == cpu_backend_name()
        assert rs.backend.name in ("native", "numpy")
        assert rs.backend.name != "pallas"
        # whole-volume ops keep the configured device backend
        assert store.ec_backend == "pallas"
