"""Hitchhiker-XOR on RS(10,4) (`hh-10.4`, hop-and-couple): the code's
generator, the coupled seal against the plain reference
(benchmark/references/hh10_4.py), the partial rebuild of every shard
with the half ranges it reads, the fallback solve, degraded needle
reads, and every other reader of a volume's code (the full-stripe
rebuild, ec.verify, ec.decode, the watchdog). Seeded random bytes, no
timings."""
import os
import secrets
import threading
import types

import numpy as np
import pytest

from benchmark import reference as rs_reference
from benchmark.references import hh10_4 as hh_reference
from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ec.backend import ReedSolomon
from seaweedfs_tpu.operation import verbs
from seaweedfs_tpu.ops import rs_matrix
from seaweedfs_tpu.rpc.httpclient import session
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell import commands_ec
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.utils import metrics

HH = "hh-10.4"
CODE = geo.parse_code(HH)
REF_CODE = {"k": 10, "local": 0, "global": 4}
MiB = 1 << 20
CHUNK = 128 << 10


def _input_bytes(spec=HH):
    return {sub: metrics._counters.get(
        ("ec_repair_input_bytes_total",
         (("code", spec), ("substripe", sub))), 0.0)
        for sub in ("a", "b", "whole")}


def _seal_file(tmp_path, nbytes, codec=HH, seed=5, name="v"):
    base = str(tmp_path / name)
    dat = np.random.default_rng(seed).integers(0, 256, nbytes,
                                               dtype=np.uint8)
    dat.tofile(base + ".dat")
    encoder.write_ec_files(base, backend="numpy", codec=codec,
                           chunk=3 * MiB)
    return base, dat


def _shard(base, sid):
    return np.fromfile(base + geo.shard_ext(sid), dtype=np.uint8)


# -- the code ---------------------------------------------------------

def test_parse_code_round_trips_through_vif(tmp_path):
    assert CODE.kind == "hh" and (CODE.k, CODE.m) == (10, 4)
    assert geo.parse_codec(HH) == (10, 4)
    assert CODE.substripes == 2 and CODE.rows == 28
    assert CODE.piggyback_groups == ((0, 1, 2), (3, 4, 5), (6, 7, 8, 9))
    base, _ = _seal_file(tmp_path, 3 * MiB)
    assert encoder.code_of(base) == CODE
    from seaweedfs_tpu.ec.volume import EcVolume

    assert EcVolume(str(tmp_path), "", 0).code == geo.parse_code("")
    for bad in ("hh-10", "hh-10.1", "hh-30.4"):
        with pytest.raises(ValueError):
            geo.parse_code(bad)


def test_generator_follows_the_equations():
    """Row h*14+s is shard s's half h over the symbols a_0..a_9,
    b_0..b_9: data rows are unit vectors, both halves carry RS(10,4)'s
    parities, and parity 10+i's b-half adds the XOR of G_i's a-bytes."""
    g = rs_matrix.encode_matrix_for(CODE)
    assert g.shape == (28, 20)
    rs = rs_matrix.encode_matrix(10, 4)
    eye = np.eye(20, dtype=np.uint8)
    for j in range(10):
        assert np.array_equal(g[CODE.halfrow(j, 0)], eye[j])
        assert np.array_equal(g[CODE.halfrow(j, 1)], eye[10 + j])
    for i in range(4):
        a_row = g[CODE.halfrow(10 + i, 0)]
        b_row = g[CODE.halfrow(10 + i, 1)]
        assert np.array_equal(a_row[:10], rs[10 + i])
        assert not a_row[10:].any()
        assert np.array_equal(b_row[10:], rs[10 + i])
        piggy = np.zeros(10, dtype=np.uint8)
        if i:
            piggy[list(CODE.piggyback_groups[i - 1])] = 1
        assert np.array_equal(b_row[:10], piggy)
    assert np.array_equal(rs_matrix.parity_rows_for(CODE),
                          g[[10, 11, 12, 13, 24, 25, 26, 27]])


def test_any_four_losses_are_recoverable():
    """MDS like RS(10,4): every 10 of the 14 shards reach rank 20."""
    from itertools import combinations

    for lost in combinations(range(14), 4):
        assert CODE.recoverable([s for s in range(14) if s not in lost])
    assert not CODE.recoverable([s for s in range(14)
                                 if s not in (0, 3, 5, 11, 12)])


@pytest.mark.parametrize("d", range(10))
def test_single_data_loss_plan_reads_half_ranges(d):
    plan = CODE.repair_plan([d], [s for s in range(14) if s != d])
    assert plan.kind == "piggyback"
    i = next(i for i, g in enumerate(CODE.piggyback_groups) if d in g)
    peers = [s for s in CODE.piggyback_groups[i] if s != d]
    want = sorted([(s, 1) for s in range(11) if s != d] +
                  [(11 + i, 1)] + [(s, 0) for s in peers])
    assert list(plan.reads) == want
    assert plan.fanin == (13 if d < 6 else 14)
    rows, inputs = rs_matrix.recovery_rows_for(
        CODE, [CODE.halfrow(s, h) for s, h in plan.reads],
        [CODE.halfrow(d, 0), CODE.halfrow(d, 1)])
    assert rows.shape == (2, plan.fanin) and len(inputs) == plan.fanin


# -- the seal ---------------------------------------------------------

# (.dat bytes, small rows a shard holds): 24 MB is three 10 MiB rows,
# so h = 1.5 MiB falls mid-block; 2 and 4 rows meet on a row boundary
SEALS = {"24MB_odd_rows": (24_000_000, 3), "16MiB_two_rows": (16 * MiB, 2),
         "tail_four_rows": (31 * MiB + 12345, 4)}


@pytest.mark.parametrize("case", list(SEALS))
def test_seal_matches_reference(tmp_path, case):
    nbytes, rows = SEALS[case]
    base, dat = _seal_file(tmp_path, nbytes)
    ref = hh_reference.shards(dat, REF_CODE, geo.LARGE_BLOCK,
                              geo.SMALL_BLOCK)
    for sid in range(14):
        got = _shard(base, sid)
        assert got.size == rows * MiB
        assert np.array_equal(got, ref[sid]), sid
    assert encoder.verify_ec_files(base, backend="numpy")


def test_shares_rs10_4_bytes(tmp_path):
    """Shards 0-10 and the a-halves of 11-13 are RS(10,4)'s own bytes;
    the b-halves of 11-13 differ by the piggybacks alone."""
    hh, dat = _seal_file(tmp_path, 24_000_000, name="hh")
    rs, _ = _seal_file(tmp_path, 24_000_000, codec="", name="rs")
    half = _shard(hh, 0).size // 2
    for sid in range(14):
        a, b = _shard(hh, sid), _shard(rs, sid)
        if sid <= 10:
            assert np.array_equal(a, b), sid
        else:
            assert np.array_equal(a[:half], b[:half]), sid
            assert not np.array_equal(a[half:], b[half:]), sid
    ref = rs_reference.shards(dat, REF_CODE, geo.LARGE_BLOCK,
                              geo.SMALL_BLOCK, [0])
    assert np.array_equal(_shard(hh, 0), ref[0])


# -- every other reader of a volume's code ----------------------------

@pytest.mark.parametrize("lost", [[4], [9], [10], [13], [0, 4, 8, 12]])
def test_full_stripe_rebuild_is_exact(tmp_path, lost):
    base, dat = _seal_file(tmp_path, 24_000_000)
    want = {s: _shard(base, s) for s in lost}
    for s in lost:
        os.remove(base + geo.shard_ext(s))
    assert encoder.rebuild_ec_files(base, backend="numpy",
                                    chunk=256 << 10) == lost
    for s in lost:
        assert np.array_equal(_shard(base, s), want[s]), s


def test_verify_checks_both_halves(tmp_path):
    base, _ = _seal_file(tmp_path, 16 * MiB)
    assert encoder.verify_ec_files(base, backend="numpy")
    stack = np.stack([_shard(base, s) for s in range(14)])
    rs = ReedSolomon(0, 0, backend="numpy", code=CODE)
    assert rs.verify(stack)
    # parity 12 as RS(10,4) would have it, its piggybacks left out: a
    # check that took the volume as RS(10,4) would pass it
    rs_bytes = rs_reference.shards(
        np.fromfile(base + ".dat", dtype=np.uint8), REF_CODE,
        geo.LARGE_BLOCK, geo.SMALL_BLOCK, [12])[12]
    rs_bytes.tofile(base + geo.shard_ext(12))
    assert not encoder.verify_ec_files(base, backend="numpy")
    stack[12] = rs_bytes
    assert not rs.verify(stack)
    assert commands_ec._locate_corrupt_shard(
        rs, {s: stack[s] for s in range(14)}) == 12


@pytest.mark.parametrize("lost", [[0], [0, 1, 10], [0, 11, 12, 13]])
def test_degraded_range_across_the_seam(tmp_path, lost):
    """A lost data shard's range that crosses h (three rows: h falls
    mid-block) reads back exact, the rest of `lost` dark too: with
    parity 10 or the piggybacked parities gone, the a-halves strip
    what the b-halves carry."""
    from seaweedfs_tpu.storage.store import Store

    base, _ = _seal_file(tmp_path, 24_000_000, name="93")
    open(base + ".idx", "wb").close()
    encoder.write_sorted_ecx(base)
    shards = {s: _shard(base, s).tobytes() for s in range(14)}
    for s in lost:
        os.remove(base + geo.shard_ext(s))
    # shards 5-9 answer over the wire; the rest are local
    for s in range(5, 10):
        os.rename(base + geo.shard_ext(s), base + ".remote%d" % s)
    store = Store([str(tmp_path)])
    ecv = store.ec_volumes[93]
    assert ecv.code == CODE

    def fetcher(vid, sids, offset, size, need, deadline):
        return {s: shards[s][offset:offset + size]
                for s in sids if 5 <= s < 10}

    store.remote_shards_fetcher = fetcher
    half = len(shards[0]) // 2
    assert half % MiB  # mid-block
    for off, size in ((half - 300, 1000), (7, 4096), (half + 5, 70000)):
        got = store._reconstruct_interval(ecv, 0, off, size)
        assert got == shards[0][off:off + size], (off, size)


def test_watchdog_takes_the_code():
    from seaweedfs_tpu.master.watchdog import RedundancyWatchdog

    live = {sid: ["n1:1"] for sid in range(14) if sid not in (0, 4, 8, 12)}
    topo = types.SimpleNamespace(
        lock=threading.Lock(), layouts={},
        ec_locations={7: live, 8: {s: ["n1:1"] for s in range(9)}},
        ec_codecs={7: HH, 8: HH}, ec_collections={})
    wd = RedundancyWatchdog(types.SimpleNamespace(topo=topo))
    _, under = wd.scan()
    got = {u["volume"]: u for u in under}
    assert got[7]["code"] == HH and got[7]["recoverable"] is True
    assert got[7]["want"] == 14 and got[7]["have"] == 10
    assert got[8]["recoverable"] is False


# -- the cluster: partial rebuild, degraded reads, ec.verify, decode --

class _Cluster:
    def __init__(self, root):
        self.cluster = Cluster(
            str(root), n_volume_servers=4, volume_size_limit=64 << 20,
            max_volumes=40,
            topology=[("dc1", f"rack{i}") for i in range(4)])
        self.env = CommandEnv(self.cluster.master_url)
        self.env.acquire_lock()

    def close(self):
        self.env.close()
        self.cluster.stop()

    def seal(self, needles, size, seed):
        """Upload needles into one fresh volume and seal it hh-10.4;
        (vid, {fid: bytes})."""
        rng = np.random.default_rng(seed)
        col = "hh" + secrets.token_hex(3)
        a = verbs.assign(self.cluster.master_url, count=needles,
                         collection=col)
        written = {}
        for i in range(needles):
            fid = a.fid if i == 0 else f"{a.fid}_{i}"
            body = rng.bytes(size)
            verbs.upload(f"http://{a.url}/{fid}", body)
            written[fid] = body
        vid = int(a.fid.split(",")[0])
        commands_ec.ec_encode(self.env, vid, codec=HH)
        return vid, written

    def shard_paths(self, vid):
        out = {}
        for s in self.cluster.volume_servers:
            ecv = s.store.ec_volumes.get(vid)
            for sid, shard in (ecv.shards.items() if ecv else ()):
                out[sid] = (f"{s.store.ip}:{s.store.port}", shard.path)
        return out

    def lose(self, vid, sids):
        import time

        paths = self.shard_paths(vid)
        golden = {s: open(paths[s][1], "rb").read() for s in sids}
        for s in sids:
            self.env.vs_post(paths[s][0], "/admin/ec/delete",
                             {"volume": vid, "shard_ids": [s]})
        deadline = time.monotonic() + 20
        while set(sids) & set(self.env.ec_full_info(vid)[2]):
            assert time.monotonic() < deadline, sids
            time.sleep(0.05)
        return golden

    def rebuilder_without(self, vid, sids):
        held: dict[str, set] = {}
        for sid, (url, _) in self.shard_paths(vid).items():
            held.setdefault(url, set()).add(sid)
        ok = [u for u, mine in held.items() if not mine & set(sids)]
        return max(ok, key=lambda u: len(held[u]))

    def rebuild(self, vid, sids):
        col = self.env.ec_full_info(vid)[0]
        return self.env.vs_post(
            self.rebuilder_without(vid, sids), "/admin/ec/rebuild_partial",
            {"volume": vid, "collection": col, "shard_ids": sids,
             "chunk": CHUNK})


@pytest.fixture(scope="module")
def hh_cluster(tmp_path_factory):
    c = _Cluster(tmp_path_factory.mktemp("hh"))
    # 12 needles of 1 MB: two 10 MiB rows, every data shard holds data
    vid, written = c.seal(12, 1_000_000, seed=31)
    c.vid, c.written = vid, written
    yield c
    c.close()


@pytest.mark.parametrize("sid", range(14))
def test_partial_rebuild_is_exact(hh_cluster, sid):
    c = hh_cluster
    golden = c.lose(c.vid, [sid])
    size = len(golden[sid])
    before = _input_bytes()
    out = c.rebuild(c.vid, [sid])
    after = _input_bytes()
    c.env.wait_for_ec_registration(c.vid, 14)
    assert out["rebuilt_shards"] == [sid]
    assert out["rebuilt_bytes"] == size
    assert open(c.shard_paths(c.vid)[sid][1], "rb").read() == golden[sid]
    got = {k: after[k] - before[k] for k in after}
    assert got["whole"] == 0
    if sid < 10:
        # the plan's half ranges, handed to the codec: 13 for shards
        # 0-5, 14 for 6-9, against RS(10,4)'s 20 halves
        peers = 2 if sid < 6 else 3
        assert got == {"a": peers * size // 2, "b": 11 * size // 2,
                       "whole": 0}
    else:
        # a parity: every data shard whole
        assert got["a"] == got["b"] == 10 * size // 2


def test_fallback_solve_when_a_planned_read_is_lost(hh_cluster):
    """Data shard 4's plan reads parity 10's b-half; with 10 lost too
    the planner solves over every available half, and both shards come
    back exact."""
    c = hh_cluster
    golden = c.lose(c.vid, [4, 10])
    out = c.rebuild(c.vid, [4, 10])
    c.env.wait_for_ec_registration(c.vid, 14)
    assert out["rebuilt_shards"] == [4, 10]
    paths = c.shard_paths(c.vid)
    for sid in (4, 10):
        assert open(paths[sid][1], "rb").read() == golden[sid], sid


def test_degraded_reads_of_a_lost_data_shard(hh_cluster):
    """Shard 0 holds the first MiB of each row: needles there read
    through both halves of the lost shard (and across the seam)."""
    c = hh_cluster
    golden = c.lose(c.vid, [0])
    try:
        half = len(golden[0]) // 2
        url = c.env.ec_full_info(c.vid)[2][1][0]
        ecv = next(s.store.ec_volumes[c.vid]
                   for s in c.cluster.volume_servers
                   if f"{s.store.ip}:{s.store.port}" == url)
        halves = set()
        for key in ecv._keys:
            for iv in ecv.needle_intervals(int(key))[0]:
                sid, off = iv.to_shard_and_offset()
                if sid == 0:
                    halves.add(off // half)
        assert halves == {0, 1}
        for fid, body in c.written.items():
            r = session().get(f"http://{url}/{fid}", timeout=30)
            assert r.status_code == 200, fid
            assert r.content == body, fid
    finally:
        c.rebuild(c.vid, [0])
        c.env.wait_for_ec_registration(c.vid, 14)


def test_ec_verify_checks_the_halves(hh_cluster):
    c = hh_cluster
    out = commands_ec.ec_verify(c.env, c.vid, sample_mb=0,
                                quarantine=False)
    assert out["verified"] is True
    out = commands_ec.ec_verify(c.env, c.vid, sample_mb=1,
                                quarantine=False)
    assert out["verified"] is True


def test_ec_decode_restores_the_volume(tmp_path):
    c = _Cluster(tmp_path)
    try:
        vid, written = c.seal(12, 1_000_000, seed=77)
        c.lose(vid, [2, 7])
        out = commands_ec.ec_decode(c.env, vid)
        for fid, body in written.items():
            r = session().get(f"http://{out['server']}/{fid}", timeout=30)
            assert r.status_code == 200, fid
            assert r.content == body, fid
    finally:
        c.close()
