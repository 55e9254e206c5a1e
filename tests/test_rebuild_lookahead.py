"""The partial rebuild's one-chunk lookahead (`_partial_ec_rebuild_sync`):
the gather of chunk i+1 runs while chunk i is reconstructed and written.
The rebuilt shards stay byte-exact for every code and loss shape, a
fault on either side leaves no torn shard and no gather thread behind,
the positional shard reads it relies on stay exact under concurrency,
and each chunk's input stack lands in a pooled staging buffer that is
safe to refill once reconstruct returns."""
import os
import secrets
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ec.backend import ReedSolomon
from seaweedfs_tpu.ec.volume import EcVolumeShard
from seaweedfs_tpu.operation import verbs
from seaweedfs_tpu.ops import codec_numpy, rs_matrix
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell import commands_ec
from seaweedfs_tpu.shell.env import CommandEnv, ShellError
from seaweedfs_tpu.utils import metrics

CHUNK = 128 << 10
GATHER_THREAD = "ec-rebuild-gather"


def _until(fn, what, limit=20.0):
    deadline = time.monotonic() + limit
    while not fn():
        assert time.monotonic() < deadline, what
        time.sleep(0.05)


def _gather_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(GATHER_THREAD)]


def _staging(outcome):
    return metrics._counters.get(
        ("ec_staging_buffers_total", (("outcome", outcome),)), 0.0)


class _Deployment:
    """A cluster with a locked shell, one rack per server."""

    def __init__(self, root, n_servers):
        self.cluster = Cluster(
            str(root), n_volume_servers=n_servers,
            volume_size_limit=64 << 20, max_volumes=40,
            topology=[("dc1", f"rack{i}") for i in range(n_servers)])
        self.env = CommandEnv(self.cluster.master_url)
        self.env.acquire_lock()

    def close(self):
        self.env.close()
        self.cluster.stop()

    def server(self, url):
        return next(s for s in self.cluster.volume_servers
                    if f"{s.store.ip}:{s.store.port}" == url)

    def seal(self, codec, needles, size):
        """Upload `needles` random needles of `size` bytes into one
        fresh volume and EC-encode it with `codec`; its vid."""
        rng = np.random.default_rng(needles * 7 + size)
        col = "la" + secrets.token_hex(3)
        a = verbs.assign(self.cluster.master_url, count=needles,
                         collection=col)
        for i in range(needles):
            fid = a.fid if i == 0 else f"{a.fid}_{i}"
            verbs.upload(f"http://{a.url}/{fid}", rng.bytes(size))
        vid = int(a.fid.split(",")[0])
        commands_ec.ec_encode(self.env, vid, codec=codec)
        return vid

    def shard_paths(self, vid):
        """{shard id: (holder url, file path)} over every server."""
        out = {}
        for s in self.cluster.volume_servers:
            ecv = s.store.ec_volumes.get(vid)
            for sid, shard in (ecv.shards.items() if ecv else ()):
                out[sid] = (f"{s.store.ip}:{s.store.port}", shard.path)
        return out

    def lose(self, vid, sids):
        """Delete `sids` where they live; their bytes before."""
        paths = self.shard_paths(vid)
        golden = {sid: open(paths[sid][1], "rb").read() for sid in sids}
        for sid in sids:
            self.env.vs_post(paths[sid][0], "/admin/ec/delete",
                             {"volume": vid, "shard_ids": [sid]})
        _until(lambda: not set(sids) & set(self.env.ec_full_info(vid)[2]),
               f"shards {sids} still listed")
        return golden

    def rebuild(self, rebuilder, vid, sids):
        col, _, _ = self.env.ec_full_info(vid)
        return self.env.vs_post(
            rebuilder, "/admin/ec/rebuild_partial",
            {"volume": vid, "collection": col, "shard_ids": sids,
             "chunk": CHUNK})

    def holder_of(self, vid, sid):
        return self.shard_paths(vid)[sid][0]

    def rebuilder_without(self, vid, sids):
        """The server holding most shards of `vid` and none of `sids`:
        some inputs local, the rest over the fan-out."""
        held: dict[str, set] = {}
        for sid, (url, _) in self.shard_paths(vid).items():
            held.setdefault(url, set()).add(sid)
        ok = [u for u, mine in held.items() if not mine & set(sids)]
        return max(ok, key=lambda u: len(held[u]))


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    d = _Deployment(tmp_path_factory.mktemp("lookahead4"), 4)
    yield d
    d.close()


def _assert_rebuilt(dep, vid, code, golden):
    """Every lost shard is back, byte for byte, and the whole set is a
    codeword of the numpy reference codec."""
    paths = dep.shard_paths(vid)
    assert sorted(paths) == list(range(code.total))
    for sid, want in golden.items():
        assert open(paths[sid][1], "rb").read() == want, sid
    stack = np.stack([np.fromfile(paths[sid][1], dtype=np.uint8)
                      for sid in range(code.total)])
    assert ReedSolomon(0, 0, backend="numpy", code=code).verify(stack)


# case: (codec, servers, needles, needle bytes, how the loss is chosen)
CASES = {
    # first-k-wins: the rebuilder asks every remote candidate, keeps
    # k - local of them and abandons the losers
    "rs10_4_single_data_shard": ("", 4, 12, 150_000, "single"),
    # the plan reads the lost shard's 6-shard local group
    "lrc12_2_2_local_group": ("lrc-12.2.2", 4, 12, 150_000, "single"),
    # a dead server: 4 shards, every input remote, zero slack
    "rs10_4_server_loss": ("", 4, 12, 150_000, "server"),
    # eight failure domains, 28 remote ranges a chunk
    "rs28_4_server_loss": ("28.4", 8, 30, 1 << 20, "server"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_pipelined_rebuild_is_byte_exact(case, four, tmp_path):
    codec, n_servers, needles, size, loss = CASES[case]
    dep = four if n_servers == 4 else _Deployment(tmp_path, n_servers)
    try:
        vid = dep.seal(codec, needles, size)
        code = dep.env.ec_full_info(vid)[1]
        if loss == "single":
            lost = [1]
            rebuilder = dep.rebuilder_without(vid, lost)
        else:
            rebuilder = dep.holder_of(vid, 0)
            lost = sorted(s for s, (u, _) in dep.shard_paths(vid).items()
                          if u == rebuilder)
            assert len(lost) > 1
        golden = dep.lose(vid, lost)
        shard_size = len(golden[lost[0]])
        assert shard_size // CHUNK >= 4
        out = dep.rebuild(rebuilder, vid, lost)
        assert out["rebuilt_shards"] == lost
        assert out["rebuilt_bytes"] == shard_size * len(lost)
        _assert_rebuilt(dep, vid, code, golden)
        assert not _gather_threads()
        # the same loss again: the job stacks into the buffer the first
        # one returned to the pool, and rebuilds the same bytes
        dep.lose(vid, lost)
        reused, allocated = _staging("reused"), _staging("allocated")
        dep.rebuild(rebuilder, vid, lost)
        assert _staging("reused") == reused + 1
        assert _staging("allocated") == allocated
        _assert_rebuilt(dep, vid, code, golden)
    finally:
        if dep is not four:
            dep.close()


def test_lookahead_gathers_next_chunk_during_reconstruct(four, monkeypatch):
    """Chunk 0's reconstruct waits for chunk 1's fetch to begin: a
    loop that gathers only after it reconstructs times out here."""
    vid = four.seal("", 12, 150_000)
    code = four.env.ec_full_info(vid)[1]
    golden = four.lose(vid, [2])
    assert len(golden[2]) // CHUNK >= 4
    rebuilder = four.rebuilder_without(vid, [2])
    srv = four.server(rebuilder)
    second_fetch = threading.Event()
    fetch = srv._remote_shards_fetch_sync

    def marked_fetch(vid_, sids, offset, size, need, deadline, bps=0.0):
        if offset == CHUNK:
            second_fetch.set()
        return fetch(vid_, sids, offset, size, need=need,
                     deadline=deadline, bps=bps)

    reconstruct = ReedSolomon.reconstruct
    calls = []

    def waiting_reconstruct(self, shards, missing=None, **kw):
        calls.append(len(calls))
        if calls == [0] and not second_fetch.wait(10):
            raise RuntimeError("chunk 1 was not gathered during chunk "
                               "0's reconstruct")
        return reconstruct(self, shards, missing=missing, **kw)

    monkeypatch.setattr(srv, "_remote_shards_fetch_sync", marked_fetch)
    monkeypatch.setattr(ReedSolomon, "reconstruct", waiting_reconstruct)
    out = four.rebuild(rebuilder, vid, [2])
    assert out["rebuilt_shards"] == [2]
    assert len(calls) == -(-len(golden[2]) // CHUNK)
    monkeypatch.undo()
    _assert_rebuilt(four, vid, code, golden)


def _no_torn_files(dep, rebuilder, vid, lost):
    srv = dep.server(rebuilder)
    for loc in srv.store.locations:
        base = loc.base_name(dep.env.ec_full_info(vid)[0], vid)
        for sid in lost:
            assert not os.path.exists(base + geo.shard_ext(sid)), sid
    ecv = srv.store.ec_volumes.get(vid)
    assert not (set(ecv.shards) & set(lost) if ecv else set())


def test_producer_fault_unlinks_and_joins(four, monkeypatch):
    """Every holder of one needed shard goes away after chunk 1 of a
    zero-slack server loss: the gather raises on its worker, the
    handler answers with the error, and no torn shard or gather thread
    is left."""
    vid = four.seal("", 12, 150_000)
    rebuilder = four.holder_of(vid, 0)
    lost = sorted(s for s, (u, _) in four.shard_paths(vid).items()
                  if u == rebuilder)
    gone = next(s for s in range(14) if s not in lost)
    four.lose(vid, lost)
    srv = four.server(rebuilder)
    fetch_one = srv._fetch_shard_from_holders

    def holders_gone(vid_, sid, holders, offset, size, deadline_t,
                     bps=0.0):
        if sid == gone and offset >= 2 * CHUNK:
            return None
        return fetch_one(vid_, sid, holders, offset, size, deadline_t,
                         bps)

    monkeypatch.setattr(srv, "_fetch_shard_from_holders", holders_gone)
    with pytest.raises(ShellError, match="shard ranges"):
        four.rebuild(rebuilder, vid, lost)
    assert not _gather_threads()
    _no_torn_files(four, rebuilder, vid, lost)


def test_caller_fault_stops_the_gather(four, monkeypatch):
    """The reconstruct of chunk 2 raises: the lookahead's gather of
    chunk 3 is the last, it is joined before the handler answers, and
    no fetch starts afterwards."""
    vid = four.seal("", 12, 150_000)
    golden = four.lose(vid, [5])
    chunks = -(-len(golden[5]) // CHUNK)
    assert chunks >= 6
    rebuilder = four.rebuilder_without(vid, [5])
    srv = four.server(rebuilder)
    fetch = srv._remote_shards_fetch_sync
    offsets = []

    def recorded_fetch(vid_, sids, offset, size, need, deadline, bps=0.0):
        offsets.append(offset)
        return fetch(vid_, sids, offset, size, need=need,
                     deadline=deadline, bps=bps)

    reconstruct = ReedSolomon.reconstruct
    calls = []

    def failing_reconstruct(self, shards, missing=None, **kw):
        calls.append(len(calls))
        if len(calls) == 3:
            raise RuntimeError("codec fault on chunk 2")
        return reconstruct(self, shards, missing=missing, **kw)

    monkeypatch.setattr(srv, "_remote_shards_fetch_sync", recorded_fetch)
    monkeypatch.setattr(ReedSolomon, "reconstruct", failing_reconstruct)
    with pytest.raises(ShellError):
        four.rebuild(rebuilder, vid, [5])
    assert not _gather_threads()
    started = len(offsets)
    assert set(offsets) <= {i * CHUNK for i in range(4)}
    time.sleep(0.3)
    assert len(offsets) == started, "a gather ran after the rebuild"
    _no_torn_files(four, rebuilder, vid, [5])


# ---------------------------------------------------------------------
# positional shard reads
# ---------------------------------------------------------------------

@pytest.fixture()
def shard_file(tmp_path):
    data = np.random.default_rng(5).bytes((1 << 20) + 333)
    path = tmp_path / "1.ec03"
    path.write_bytes(data)
    shard = EcVolumeShard("", 1, 3, str(path))
    yield shard, data
    shard.close()


def test_concurrent_reads_of_one_shard_are_exact(shard_file):
    shard, data = shard_file
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        for _ in range(200):
            off = int(rng.integers(0, len(data)))
            n = int(rng.integers(1, 64 << 10))
            if shard.read_at(off, n) != data[off:off + n]:
                errors.append((off, n))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:5]


def test_read_at_end_of_file(shard_file):
    shard, data = shard_file
    size = len(data)
    assert shard.read_at(size - 10, 100) == data[-10:]
    assert shard.read_at(size, 5) == b""
    assert shard.read_at(size + 7, 5) == b""
    assert shard.read_at(0, 0) == b""


def test_read_at_loops_on_short_positional_reads(shard_file, monkeypatch):
    shard, data = shard_file
    pread = os.pread
    monkeypatch.setattr(os, "pread",
                        lambda fd, n, off: pread(fd, min(n, 4097), off))
    assert shard.read_at(1000, 50_000) == data[1000:51_000]
    assert shard.read_at(len(data) - 9000, 20_000) == data[-9000:]


# ---------------------------------------------------------------------
# staging buffers for the per-chunk input stack
# ---------------------------------------------------------------------

STAGE_W = 4096


def _stripe(code, width, seed):
    data = np.random.default_rng(seed).integers(
        0, 256, (code.k, width), dtype=np.uint8)
    parity = codec_numpy.coded_matmul(rs_matrix.parity_rows_for(code),
                                      data)
    return np.concatenate([data, parity])


class _Recording:
    """The numpy codec, recording each stack it is handed."""
    name = "recording"

    def __init__(self):
        self.stacks = []

    def coded_matmul(self, coef, shards):
        self.stacks.append(shards)
        return codec_numpy.coded_matmul(coef, shards)


# the partial rebuild's loss on each code: one data shard, or a server
LOSSES = {"10.4": [1], "lrc-12.2.2": [1], "28.4": [0, 8, 16, 24]}
# buffer rows against the code's k: exact, more, one short of the inputs
STAGE_CASES = {"full_chunk": (STAGE_W, 0), "short_last_chunk": (1000, 0),
               "more_rows_than_inputs": (STAGE_W, 3),
               "too_small": (STAGE_W, None)}


@pytest.mark.parametrize("case", list(STAGE_CASES))
@pytest.mark.parametrize("spec", list(LOSSES))
def test_staged_reconstruct_matches_np_stack(spec, case):
    code = geo.parse_code(spec)
    width, extra = STAGE_CASES[case]
    stripe = _stripe(code, width, 17)
    lost = LOSSES[spec]
    shards = {s: stripe[s] for s in range(code.total) if s not in lost}
    rec = _Recording()
    rs = ReedSolomon(0, 0, backend=rec, code=code)
    _, inputs = rs_matrix.recovery_rows_for(code, sorted(shards), lost)
    rows = len(inputs) - 1 if extra is None else code.k + extra
    stage = np.empty(rows * STAGE_W, dtype=np.uint8)
    want = rs.reconstruct(shards, missing=lost)
    got = rs.reconstruct(shards, missing=lost, stage=stage)
    assert sorted(got) == lost
    for s in lost:
        assert got[s].tobytes() == want[s].tobytes() == stripe[s].tobytes()
    plain, staged = rec.stacks
    assert np.array_equal(plain, staged)
    assert np.shares_memory(staged, stage) == (extra is not None)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_rows_survive_refilling_the_stage(backend):
    code = geo.parse_code("10.4")
    stripe = _stripe(code, STAGE_W, 3)
    shards = {s: stripe[s] for s in range(code.total) if s != 2}
    rs = ReedSolomon(0, 0, backend=backend, code=code)
    stage = np.empty(code.k * STAGE_W, dtype=np.uint8)
    got = rs.reconstruct(shards, missing=[2], stage=stage)
    before = got[2].copy()
    assert not np.shares_memory(got[2], stage)
    stage.fill(0xA5)
    assert np.array_equal(got[2], before)
    assert np.array_equal(got[2], stripe[2])


def test_staged_chunks_go_through_the_codec_and_are_counted():
    """One coded_matmul per chunk, on the backend (so a fault wrapper
    stays in the path), and ec_codec_bytes_total{op=reconstruct} counts
    every stacked byte."""
    code = geo.parse_code("10.4")
    stripe = _stripe(code, 2 * STAGE_W + 777, 9)
    rec = _Recording()
    rs = ReedSolomon(0, 0, backend=rec, code=code)
    key = ("ec_codec_bytes_total",
           (("backend", "recording"), ("op", "reconstruct")))
    before = metrics._counters.get(key, 0.0)
    stage = np.empty(code.k * STAGE_W, dtype=np.uint8)
    stacked = 0
    for off in range(0, stripe.shape[1], STAGE_W):
        chunk = stripe[:, off:off + STAGE_W]
        shards = {s: chunk[s] for s in range(code.total) if s != 4}
        got = rs.reconstruct(shards, missing=[4], stage=stage)
        assert np.array_equal(got[4], chunk[4])
        stacked += code.k * chunk.shape[1]
    assert len(rec.stacks) == 3
    assert all(np.shares_memory(st, stage) for st in rec.stacks)
    assert metrics._counters.get(key, 0.0) - before == stacked


def test_staging_pool_is_exclusive_bounded_and_reused(monkeypatch):
    monkeypatch.setattr(ecb, "_staging_free", [])
    held, both = [], threading.Barrier(2, timeout=10)

    def hold():
        with ecb.staging_buffer(1 << 16) as buf:
            held.append(buf)
            both.wait()

    threads = [threading.Thread(target=hold) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert not any(t.is_alive() for t in threads)
    assert len(held) == 2 and not np.shares_memory(*held)
    assert held[0].nbytes >= 1 << 16 and held[1].nbytes >= 1 << 16

    # many holders at once: a buffer handed to two of them would see
    # the other's mark, and the free list stays bounded throughout
    clashes = []

    def churn(mark):
        for i in range(300):
            with ecb.staging_buffer(64 + i % 3) as buf:
                buf[:64] = mark
                time.sleep(0)
                if not (buf[:64] == mark).all():
                    clashes.append(mark)
            if len(ecb._staging_free) > ecb.STAGING_KEEP:
                clashes.append("kept")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=churn, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not clashes
    ecb._staging_free[:] = held
    with ecb.staging_buffer(10), ecb.staging_buffer(20), \
            ecb.staging_buffer(1 << 17):
        pass
    assert len(ecb._staging_free) == ecb.STAGING_KEEP == 2
    reused, allocated = _staging("reused"), _staging("allocated")
    with ecb.staging_buffer(1 << 16) as buf:
        assert any(buf is b for b in held)
    assert (_staging("reused"), _staging("allocated")) == \
        (reused + 1, allocated)
    with ecb.staging_buffer(1 << 18) as big:
        assert big.nbytes == 1 << 18
    assert _staging("allocated") == allocated + 1
    assert [b.nbytes for b in ecb._staging_free] == [1 << 17, 1 << 18]
