"""Degraded-read fan-out: concurrent first-k-wins shard fetch under a
per-read deadline (store_ec.go:349-393 goroutine fan-out equivalent;
round-2 VERDICT item 3 — the serial walk paid >= 10 sequential RTTs and
a single hung peer stalled the read forever).
"""
import threading
import time

import numpy as np
import pytest
import requests

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.operation import verbs
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell import commands_ec
from seaweedfs_tpu.shell.env import CommandEnv


# ---------------------------------------------------------------------
# Store-level: the reconstruct ladder uses the fan-out fetcher contract
# ---------------------------------------------------------------------

def _make_ec_store(tmp_path, n_local=4):
    """A Store holding only `n_local` shards of a 14-shard volume, plus
    the golden shard bytes for the rest."""
    from seaweedfs_tpu.ec.encoder import write_ec_files, write_sorted_ecx
    from seaweedfs_tpu.storage.store import Store

    rng = np.random.default_rng(5)
    base = tmp_path / "77"
    # a tiny needle-shaped volume is unnecessary: reconstruct operates
    # on raw intervals, so raw shard ranges are enough for this layer
    (tmp_path / "77.dat").write_bytes(rng.bytes(geo.SMALL_BLOCK * 10 * 3))
    (tmp_path / "77.idx").write_bytes(b"")  # no needles needed here
    write_ec_files(str(base), backend="numpy")
    write_sorted_ecx(str(base))
    shards = {i: (tmp_path / ("77" + geo.shard_ext(i))).read_bytes()
              for i in range(geo.TOTAL_SHARDS)}
    for i in range(geo.TOTAL_SHARDS):
        if i >= n_local:
            (tmp_path / ("77" + geo.shard_ext(i))).unlink()
    store = Store([str(tmp_path)])
    assert 77 in store.ec_volumes
    return store, shards


def test_reconstruct_uses_fanout_fetcher(tmp_path):
    store, shards = _make_ec_store(tmp_path, n_local=4)
    calls = []

    def fetcher(vid, sids, offset, size, need, deadline):
        calls.append((vid, tuple(sids), need))
        # return exactly `need` shards, as a concurrent fan-out would
        out = {}
        for sid in sids[:need]:
            out[sid] = shards[sid][offset:offset + size]
        return out

    store.remote_shards_fetcher = fetcher
    ecv = store.ec_volumes[77]
    got = store._reconstruct_interval(ecv, 12, 100, 5000)
    assert got == shards[12][100:5100]
    (vid, sids, need) = calls[0]
    assert vid == 77 and need == geo.DATA_SHARDS - 4  # shards 0-3 local
    assert 12 not in sids  # never asks for the shard being rebuilt
    assert all(s >= 4 for s in sids)  # locals aren't re-fetched


def test_reconstruct_fails_cleanly_when_short(tmp_path):
    store, shards = _make_ec_store(tmp_path, n_local=4)
    store.remote_shards_fetcher = \
        lambda vid, sids, off, size, need, dl: {}  # all peers dark
    ecv = store.ec_volumes[77]
    with pytest.raises(IOError, match="only 4 shards reachable"):
        store._reconstruct_interval(ecv, 12, 0, 100)


# ---------------------------------------------------------------------
# Server-level e2e: one hung peer must not stall the degraded read
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(str(tmp_path_factory.mktemp("ec_par")),
                n_volume_servers=3, volume_size_limit=4 << 20,
                max_volumes=40)
    yield c
    c.stop()


def test_degraded_read_with_hung_peer(cluster):
    import secrets

    env = CommandEnv(cluster.master_url)
    env.acquire_lock()
    try:
        col = "hung" + secrets.token_hex(3)
        rng = np.random.default_rng(1)
        a = verbs.assign(cluster.master_url, collection=col)
        vid = int(a.fid.split(",")[0])
        data = rng.bytes(200_000)
        verbs.upload(a, data)
        commands_ec.ec_encode(env, vid)
        locs = env.ec_shard_locations(vid)

        # which shard does this needle's read actually need?
        from seaweedfs_tpu.storage.types import parse_file_id

        _, nid, _ = parse_file_id(a.fid)
        any_srv = cluster.volume_servers[0]
        intervals, _size = \
            any_srv.store.ec_volumes[vid].needle_intervals(nid)
        sid_x, _ = intervals[0].to_shard_and_offset()

        # wedge ONLY that shard on its holder (a wedged-but-connected
        # peer); everything else stays healthy, so reconstruction from
        # the other 13 shards remains possible
        hung_url = locs[sid_x][0]
        hung_srv = next(
            s for s in cluster.volume_servers
            if f"{s.store.ip}:{s.store.port}" == hung_url)
        ecv = hung_srv.store.ec_volumes[vid]
        release = threading.Event()

        class HungShard:
            def __init__(self, inner):
                self._inner = inner

            def read_at(self, *a, **kw):
                release.wait(30)  # wedged until the test releases it
                return self._inner.read_at(*a, **kw)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        saved = dict(ecv.shards)
        patched = dict(saved)
        patched[sid_x] = HungShard(saved[sid_x])
        ecv.shards = patched
        try:
            # read through a DIFFERENT server: the direct fetch of the
            # wedged shard must give up after its small budget slice,
            # and the reconstruction fan-out must win well inside the
            # read deadline
            reader = next(u for urls in locs.values() for u in urls
                          if u != hung_url)
            deadline = 8.0
            for s in cluster.volume_servers:
                s.store.ec_read_deadline = deadline
            t0 = time.monotonic()
            resp = requests.get(f"http://{reader}/{a.fid}", timeout=25)
            dt = time.monotonic() - t0
            assert resp.status_code == 200, resp.text
            assert resp.content == data
            # p50 bound: well under the hung peer's 30s wedge — the
            # direct hop costs <= 2s, the fan-out single-digit seconds
            assert dt < deadline, f"degraded read took {dt:.1f}s"
        finally:
            release.set()
            ecv.shards = saved
    finally:
        env.close()


def test_client_ec_cache_follows_shard_move(cluster):
    """EC per-shard locations live in the client vid cache and the
    KeepConnected ec_updates push invalidates them on a shard move
    (vid_map.go:169-236; VERDICT round-2 item 7)."""
    import secrets

    from seaweedfs_tpu.wdclient.client import MasterClient

    env = CommandEnv(cluster.master_url)
    env.acquire_lock()
    mc = MasterClient(cluster.master_url, subscribe=True)
    try:
        col = "mv" + secrets.token_hex(3)
        rng = np.random.default_rng(2)
        a = verbs.assign(cluster.master_url, collection=col)
        vid = int(a.fid.split(",")[0])
        data = rng.bytes(50_000)
        verbs.upload(a, data)
        commands_ec.ec_encode(env, vid)

        # cache warm: per-shard map served without re-polling
        shards = mc.lookup_ec(vid)
        assert shards and all(urls for urls in shards.values())
        src = shards[0][0]
        dst = next(u for urls in shards.values() for u in urls
                   if u != src)

        # move shard 0: copy to dst, mount there, unmount+delete at src
        env.vs_post(dst, "/admin/ec/copy",
                    {"volume": vid, "collection": col, "shard_ids": [0],
                     "source": src})
        env.vs_post(dst, "/admin/ec/mount",
                    {"volume": vid, "collection": col, "shard_ids": [0]})
        env.vs_post(src, "/admin/ec/unmount",
                    {"volume": vid, "shard_ids": [0]})

        # the push stream must update the SUBSCRIBED cache (no manual
        # invalidation, max_age large so polling can't mask a miss)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            now_shards = mc.lookup_ec(vid, max_age=3600)
            holders = now_shards.get(0, [])
            if dst in holders and src not in holders:
                break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"ec cache still stale after move: {now_shards.get(0)}")

        # and a degraded read through any holder still round-trips
        reader = now_shards[0][0]
        resp = requests.get(f"http://{reader}/{a.fid}", timeout=25)
        assert resp.status_code == 200 and resp.content == data
    finally:
        mc.stop()
        env.close()


def test_single_interval_reconstruct_latency_budget():
    """Degraded-read latency budget (VERDICT r2 item 7): recovering ONE
    1MB interval from k=10 shards through the Store's synchronous codec
    must stay in single-digit-milliseconds territory on the CPU path.
    The budget is deliberately loose (CI VMs share cores) but tight enough
    to catch an accidental O(n^2) or a fallen-off fast path."""
    import time

    import numpy as np

    from seaweedfs_tpu.ec.backend import ReedSolomon
    from seaweedfs_tpu.ops import rs_matrix

    rs = ReedSolomon(10, 4, backend="auto")
    present = [i for i in range(14) if i != 0]
    rows, inputs = rs_matrix.recovery_rows(10, 4, present[:10], [0])
    shards = np.random.default_rng(0).integers(
        0, 256, (10, 1 << 20), dtype=np.uint8)
    rs.backend.coded_matmul(rows, shards)  # warm
    lats = []
    for _ in range(7):
        t0 = time.perf_counter()
        rs.backend.coded_matmul(rows, shards)
        lats.append(time.perf_counter() - t0)
    p50_ms = sorted(lats)[len(lats) // 2] * 1000
    assert p50_ms < 50, f"1MB reconstruct p50 {p50_ms:.1f}ms over budget"
