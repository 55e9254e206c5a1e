"""End-to-end EC workflow over a live cluster: the reference's
ec.encode/ec.rebuild/ec.decode shell flows (SURVEY.md section 3.5) plus
degraded reads through on-the-fly reconstruction (store_ec.go:339)."""
import numpy as np
import pytest
import requests

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.operation import verbs
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell import commands_ec, commands_volume
from seaweedfs_tpu.shell.env import CommandEnv, ShellError


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(str(tmp_path_factory.mktemp("ec_cluster")),
                n_volume_servers=3, volume_size_limit=4 << 20,
                max_volumes=40)
    yield c
    c.stop()


@pytest.fixture(scope="module")
def env(cluster):
    e = CommandEnv(cluster.master_url)
    e.acquire_lock()
    return e


@pytest.fixture()
def sealed_volume(cluster):
    """Upload objects into a fresh collection; return (vid, {fid: data})."""
    import secrets

    col = "seal" + secrets.token_hex(3)
    rng = np.random.default_rng(0)
    payloads = {}
    a0 = verbs.assign(cluster.master_url, collection=col)
    vid = int(a0.fid.split(",")[0])
    verbs.upload(a0, rng.bytes(1000))
    payloads[a0.fid] = None  # replaced below
    payloads = {}
    for i in range(30):
        a = verbs.assign(cluster.master_url, collection=col)
        if int(a.fid.split(",")[0]) != vid:
            continue
        data = rng.bytes(int(rng.integers(100, 50000)))
        verbs.upload(a, data)
        payloads[a.fid] = data
    return vid, payloads


class TestEcEncode:
    def test_encode_spread_read(self, cluster, env, sealed_volume):
        vid, payloads = sealed_volume
        placement = commands_ec.ec_encode(env, vid)
        assert len(placement) == geo.TOTAL_SHARDS
        # original volume is gone from all stores
        assert all(not s.has_volume(vid) for s in cluster.stores)
        # shards spread across all 3 servers
        servers = set(placement.values())
        assert len(servers) == 3
        # every object readable through the EC read path
        for fid, data in payloads.items():
            holders = env.ec_shard_locations(vid)
            any_holder = holders[0][0]
            resp = requests.get(f"http://{any_holder}/{fid}")
            assert resp.status_code == 200, fid
            assert resp.content == data

    def test_degraded_read_after_losing_parity_and_data(
            self, cluster, env, sealed_volume):
        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid)
        locs = env.ec_shard_locations(vid)
        # delete 2 data shards + 2 parity shards (max tolerable)
        for sid in (1, 4, 10, 13):
            for url in locs.get(sid, []):
                env.vs_post(url, "/admin/ec/delete",
                            {"volume": vid, "shard_ids": [sid]})
        locs2 = env.ec_shard_locations(vid)
        remaining = {sid for sid, urls in locs2.items() if urls}
        assert len(remaining) == 10
        fid, data = next(iter(payloads.items()))
        holder = locs2[sorted(remaining)[0]][0]
        resp = requests.get(f"http://{holder}/{fid}")
        assert resp.status_code == 200
        assert resp.content == data

    def test_rebuild_restores_full_set(self, cluster, env, sealed_volume):
        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid)
        locs = env.ec_shard_locations(vid)
        for sid in (0, 7, 12):
            for url in locs.get(sid, []):
                env.vs_post(url, "/admin/ec/delete",
                            {"volume": vid, "shard_ids": [sid]})
        result = commands_ec.ec_rebuild(env, vid)
        assert sorted(result["rebuilt"]) == [0, 7, 12]
        locs2 = env.ec_shard_locations(vid)
        assert sum(1 for urls in locs2.values() if urls) == 14
        # reads still fine
        for fid, data in list(payloads.items())[:3]:
            holder = locs2[0][0]
            resp = requests.get(f"http://{holder}/{fid}")
            assert resp.content == data

    def test_decode_back_to_volume(self, cluster, env, sealed_volume):
        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid)
        out = commands_ec.ec_decode(env, vid)
        server = out["server"]
        # normal volume reads again
        for fid, data in list(payloads.items())[:3]:
            resp = requests.get(f"http://{server}/{fid}")
            assert resp.status_code == 200
            assert resp.content == data

    def test_encode_requires_lock(self, cluster, sealed_volume):
        vid, _ = sealed_volume
        env2 = CommandEnv(cluster.master_url)
        with pytest.raises(ShellError, match="lock"):
            commands_ec.ec_encode(env2, vid)

    def test_encode_missing_volume(self, env):
        with pytest.raises(ShellError, match="not found"):
            commands_ec.ec_encode(env, 424242)


class TestPartialRepairTraffic:
    """Acceptance: rebuilding ONE lost shard through the partial-stripe
    path must demonstrably move fewer bytes than the classic
    borrow-every-shard full rebuild — asserted on the
    repair_read_bytes_total{mode} counters both paths feed."""

    @staticmethod
    def _read_bytes(mode):
        from seaweedfs_tpu.utils import metrics
        return metrics._counters.get(
            ("repair_read_bytes_total", (("mode", mode),)), 0.0)

    @staticmethod
    def _until(fn, what):
        """The master learns of a shard's loss or return from the next
        heartbeat: poll what it lists."""
        import time

        deadline = time.monotonic() + 20
        while not fn():
            assert time.monotonic() < deadline, what
            time.sleep(0.05)

    def _drop_shard(self, env, vid, sid):
        for url in env.ec_shard_locations(vid).get(sid, []):
            env.vs_post(url, "/admin/ec/delete",
                        {"volume": vid, "shard_ids": [sid]})
        # a rebuild planned before the master sees the loss finds
        # nothing missing
        self._until(lambda: not env.ec_shard_locations(vid).get(sid),
                    f"shard {sid} still listed")

    def test_partial_moves_fewer_bytes_than_full(self, cluster, env,
                                                 sealed_volume):
        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid)
        # leg 1: lose shard 3, repair through the partial path
        self._drop_shard(env, vid, 3)
        p0, f0 = self._read_bytes("partial"), self._read_bytes("full")
        out = commands_ec.ec_rebuild(env, vid, partial=True)
        assert out["mode"] == "partial"
        assert out["rebuilt"] == [3]
        partial_bytes = self._read_bytes("partial") - p0
        assert partial_bytes > 0
        assert partial_bytes == out["read_bytes"]
        assert self._read_bytes("full") == f0, \
            "partial repair leaked full-path traffic"
        # leg 2: the SAME single-shard loss repaired the classic way
        self._drop_shard(env, vid, 3)
        f1 = self._read_bytes("full")
        out2 = commands_ec.ec_rebuild(env, vid, partial=False)
        assert out2["mode"] == "full"
        assert 3 in out2["rebuilt"]
        full_bytes = self._read_bytes("full") - f1
        assert full_bytes > 0
        # the headline claim: partial-stripe reads strictly fewer bytes
        assert partial_bytes < full_bytes, \
            f"partial={partial_bytes} full={full_bytes}"
        # the healed volume still serves every object
        locs = env.ec_shard_locations(vid)
        assert sum(1 for urls in locs.values() if urls) == 14
        holder = locs[3][0]
        for fid, data in list(payloads.items())[:3]:
            assert requests.get(f"http://{holder}/{fid}").content == data

    def test_partial_rebuild_survives_dark_planned_shard(
            self, cluster, env, sealed_volume):
        """Regression: a planned remote shard that never answers must
        not abort a structured-code partial rebuild. The server marks
        it dead, re-plans around it (LRCs carry substitutable shards),
        and still heals the lost shard bit-for-bit."""
        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid, codec="lrc-10.2.2")
        col, reg_code, locs = env.ec_full_info(vid)
        assert reg_code.spec == "lrc-10.2.2"
        # golden copy of data shard 1 before losing it everywhere
        holder = next(s for s in cluster.volume_servers
                      if f"{s.store.ip}:{s.store.port}" == locs[1][0])
        shard = holder.store.ec_volumes[vid].shards[1]
        golden = shard.read_at(0, shard.size)
        self._drop_shard(env, vid, 1)
        plan = reg_code.repair_plan(
            [1], [s for s in range(reg_code.total) if s != 1])
        assert plan is not None and plan.kind == "local"
        # pick a rebuilder that must fetch >= 1 planned shard remotely,
        # then black that shard out at its fan-out layer
        rebuilder = dark = None
        for srv in cluster.volume_servers:
            ecv = srv.store.ec_volumes.get(vid)
            mine = set(ecv.shards) if ecv is not None else set()
            short = [s for s in plan.reads if s not in mine]
            if short:
                rebuilder, dark = srv, short[0]
                break
        assert rebuilder is not None
        orig = rebuilder._remote_shards_fetch_sync
        darkened = []

        def no_answer_from_dark(vid_, sids, offset, size, need,
                                deadline, bps=0.0):
            live = [s for s in sids if s != dark]
            if len(live) != len(sids):
                darkened.append(dark)
            if not live:
                return {}
            return orig(vid_, live, offset, size,
                        need=min(need, len(live)), deadline=deadline,
                        bps=bps)

        rebuilder._remote_shards_fetch_sync = no_answer_from_dark
        try:
            out = env.vs_post(
                f"{rebuilder.store.ip}:{rebuilder.store.port}",
                "/admin/ec/rebuild_partial",
                {"volume": vid, "collection": col, "shard_ids": [1]})
        finally:
            rebuilder._remote_shards_fetch_sync = orig
        assert out["rebuilt_shards"] == [1]
        assert darkened, "the dark shard never entered a plan"
        healed = rebuilder.store.ec_volumes[vid].shards[1]
        assert healed.read_at(0, healed.size) == golden
        # the healed volume still serves reads
        self._until(lambda: env.ec_shard_locations(vid).get(1),
                    "rebuilt shard 1 never listed")
        locs2 = env.ec_shard_locations(vid)
        fid, data = next(iter(payloads.items()))
        assert requests.get(
            f"http://{locs2[1][0]}/{fid}").content == data

    def test_partial_rebuild_steps_are_timed_per_chunk(
            self, cluster, env, sealed_volume, monkeypatch):
        """Each chunk of a partial rebuild is one fetch, one
        reconstruct and one write interval (span_seconds); the local
        and remote read counters add up to the repair plan's reads;
        and the rebuild's trace in the ring holds its phases only,
        however many chunks it took."""
        from seaweedfs_tpu.utils import metrics, tracing

        chunk = 256 << 10
        steps = ("ec.rebuild.read_local", "ec.rebuild.fetch",
                 "ec.rebuild.reconstruct", "ec.rebuild.write")

        def count(name):
            return metrics._counters.get(
                ("span_seconds_count", (("name", name),)), 0.0)

        def read_bytes(name, code):
            return sum(v for (n, lab), v in metrics._counters.items()
                       if n == name and ("code", code) in lab)

        vid, payloads = sealed_volume
        commands_ec.ec_encode(env, vid, codec="lrc-10.2.2")
        _col, code, _locs = env.ec_full_info(vid)
        self._drop_shard(env, vid, 1)
        plan = code.repair_plan(
            [1], [s for s in range(code.total) if s != 1])
        assert plan is not None and plan.kind == "local"
        # the shell sends no chunk size: ask for a small one, so the
        # shard takes several chunks
        post = env.vs_post

        def small_chunks(server, path, body, timeout=600):
            if path == "/admin/ec/rebuild_partial":
                body = dict(body, chunk=chunk)
            return post(server, path, body, timeout=timeout)

        monkeypatch.setattr(env, "vs_post", small_chunks)
        before = {s: count(s) for s in steps}
        remote0 = read_bytes("ec_repair_read_bytes_by_code_total",
                             code.spec)
        local0 = read_bytes("ec_repair_local_read_bytes_total", code.spec)
        out = commands_ec.ec_rebuild(env, vid)
        assert out["mode"] == "partial" and out["rebuilt"] == [1]
        shard_size = out["rebuilt_bytes"]
        chunks = -(-shard_size // chunk)
        assert chunks > 1
        for s in steps[1:]:
            assert count(s) - before[s] == chunks, s
        # the rebuilder holds some of the group, never all of it
        assert count(steps[0]) > before[steps[0]]
        remote = read_bytes("ec_repair_read_bytes_by_code_total",
                            code.spec) - remote0
        local = read_bytes("ec_repair_local_read_bytes_total",
                           code.spec) - local0
        assert local > 0 and remote > 0
        assert local + remote == len(plan.reads) * shard_size
        # the ring: the rebuild's phases, no per-chunk step
        root = [s for s in tracing._spans if s["name"] == "ec.rebuild"][-1]
        internal = [s["name"] for s in tracing._spans
                    if s["trace_id"] == root["trace_id"]
                    and s["kind"] == "internal"]
        assert sorted(internal) == sorted(
            ["ec.rebuild", "ec.rebuild.plan", "ec.rebuild.partial",
             "ec.rebuild.wait_registration"])
        # the healed volume still serves every object
        locs = env.ec_shard_locations(vid)
        fid, data = next(iter(payloads.items()))
        assert requests.get(f"http://{locs[1][0]}/{fid}").content == data

    def test_partial_rebuild_rejects_garbage(self, cluster, env,
                                             sealed_volume):
        vid, _ = sealed_volume
        commands_ec.ec_encode(env, vid)
        locs = env.ec_shard_locations(vid)
        url = locs[0][0]
        with pytest.raises(ShellError):
            env.vs_post(url, "/admin/ec/rebuild_partial",
                        {"volume": vid, "shard_ids": []})
        with pytest.raises(ShellError):
            env.vs_post(url, "/admin/ec/rebuild_partial",
                        {"volume": vid, "shard_ids": [0], "chunk": 0})


class TestEcBalance:
    def test_balance_evens_counts(self, cluster, env, sealed_volume):
        vid, _ = sealed_volume
        commands_ec.ec_encode(env, vid)
        moves = commands_ec.ec_balance(env)
        counts = []
        for n in env.data_nodes():
            counts.append(sum(bin(b).count("1")
                              for b in n["ec_volumes"].values()))
        assert max(counts) - min(counts) <= geo.TOTAL_SHARDS // 3 + 2


class TestVolumeMaintenance:
    def test_volume_list_and_cluster_check(self, cluster, env):
        check = commands_volume.cluster_check(env)
        assert check["nodes"] == 3

    def test_fix_replication(self, cluster, env):
        a = verbs.assign(cluster.master_url, collection="fixrep",
                         replication="001")
        verbs.upload(a, b"fix me")
        vid = int(a.fid.split(",")[0])
        # drop one replica
        locs = env.volume_locations(vid)
        assert len(locs) == 2
        env.vs_post(locs[1], "/admin/delete_volume", {"volume": vid})
        import time

        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                len(env.volume_locations(vid)) != 1:
            time.sleep(0.1)
        fixes = commands_volume.volume_fix_replication(env)
        assert any(f["volume"] == vid for f in fixes)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                len(env.volume_locations(vid)) != 2:
            time.sleep(0.1)
        locs2 = env.volume_locations(vid)
        assert len(locs2) == 2
        for url in locs2:
            assert requests.get(
                f"http://{url}/{a.fid}").content == b"fix me"
