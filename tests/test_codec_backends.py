"""Codec backend agreement tests: every backend must match numpy bit-for-bit."""
import numpy as np
import pytest

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ops import codec_numpy

BACKENDS = ["numpy", "pallas"]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


@pytest.mark.parametrize("name", BACKENDS)
@pytest.mark.parametrize("shape", [(4, 10, 1024), (4, 10, 1), (2, 3, 777),
                                   (4, 28, 4096), (14, 10, 100)])
def test_coded_matmul_matches_numpy(name, shape, rng):
    m, k, n = shape
    coef = rng.integers(0, 256, (m, k)).astype(np.uint8)
    data = rng.integers(0, 256, (k, n)).astype(np.uint8)
    want = codec_numpy.coded_matmul(coef, data)
    got = ecb.get_backend(name).coded_matmul(coef, data)
    assert np.array_equal(np.asarray(got), want), name


@pytest.mark.parametrize("name", BACKENDS)
def test_encode_reconstruct_roundtrip(name, rng):
    rs = ecb.ReedSolomon(10, 4, backend=name)
    data = rng.integers(0, 256, (10, 2048)).astype(np.uint8)
    parity = rs.encode(data)
    full = np.concatenate([data, parity], axis=0)
    assert rs.verify(full)

    # drop any 4 shards, reconstruct, compare bit-for-bit
    for drop in ([0, 1, 2, 3], [0, 5, 10, 13], [10, 11, 12, 13], [9, 3, 12, 7]):
        shards = {i: full[i] for i in range(14) if i not in drop}
        rec = rs.reconstruct(shards)
        assert sorted(rec) == sorted(drop)
        for sid, row in rec.items():
            assert np.array_equal(row, full[sid]), (name, sid)


@pytest.mark.parametrize("name", BACKENDS)
def test_reconstruct_data_only(name, rng):
    rs = ecb.ReedSolomon(10, 4, backend=name)
    data = rng.integers(0, 256, (10, 512)).astype(np.uint8)
    parity = rs.encode(data)
    full = np.concatenate([data, parity], axis=0)
    shards = {i: full[i] for i in range(14) if i not in (2, 7)}
    rec = rs.reconstruct_data(shards)
    assert sorted(rec) == [2, 7]
    assert np.array_equal(rec[2], full[2])
    assert np.array_equal(rec[7], full[7])


@pytest.mark.parametrize("name", BACKENDS)
def test_too_few_shards_raises(name, rng):
    rs = ecb.ReedSolomon(4, 2, backend=name)
    data = rng.integers(0, 256, (4, 64)).astype(np.uint8)
    parity = rs.encode(data)
    full = np.concatenate([data, parity], axis=0)
    shards = {i: full[i] for i in range(3)}  # < k
    with pytest.raises(ValueError):
        rs.reconstruct(shards)


def test_wide_code_rs28_4(rng):
    """BASELINE.json config 4: wide code RS(28,4)."""
    for name in BACKENDS:
        rs = ecb.ReedSolomon(28, 4, backend=name)
        data = rng.integers(0, 256, (28, 1000)).astype(np.uint8)
        parity = rs.encode(data)
        full = np.concatenate([data, parity], axis=0)
        shards = {i: full[i] for i in range(32) if i not in (0, 15, 28, 31)}
        rec = rs.reconstruct(shards)
        for sid, row in rec.items():
            assert np.array_equal(row, full[sid])


def test_jax_slab_chunking(rng):
    """Columns beyond one slab are processed in chunks with identical bits."""
    from seaweedfs_tpu.ops.codec_pallas import COL_TILE, PallasCodec

    codec = PallasCodec(slab=COL_TILE)
    coef = rng.integers(0, 256, (4, 10)).astype(np.uint8)
    data = rng.integers(0, 256, (10, COL_TILE + 1000)).astype(np.uint8)
    want = codec_numpy.coded_matmul(coef, data)
    assert np.array_equal(codec.coded_matmul(coef, data), want)


def test_backend_registry():
    assert "numpy" in ecb.backend_names()
    assert "pallas" in ecb.backend_names()
    assert "jax" not in ecb.backend_names()
    with pytest.raises(KeyError):
        ecb.get_backend("nope")


@pytest.mark.parametrize("cmd", ["volume", "server"])
def test_ec_backend_flag_rejects_unknown_name(cmd, capsys):
    """-ec.backend takes only registered names: a removed or mistyped
    one fails at start-up and names the known backends, not at a
    server's first EC job."""
    from seaweedfs_tpu import cli

    assert cli.parse_args([cmd, "-ec.backend=pallas"]).ec_backend == \
        "pallas"
    with pytest.raises(SystemExit) as exc:
        cli.parse_args([cmd, "-ec.backend=jax"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "invalid choice: 'jax'" in err
    for name in ecb.backend_names():
        assert name in err


# ---------------------------------------------------------------------
# pipelined device feed + measured-curve router (ISSUE 3)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3, 4, 8])
def test_pipelined_stream_matches_numpy_all_depths(depth, rng):
    """The depth-N staged pipeline is bit-identical to the numpy
    oracle at every depth — including uneven final blocks, a width
    under one lane tile, and an empty block mid-stream."""
    from seaweedfs_tpu.ops.codec_pallas import COL_TILE, PallasCodec

    codec = PallasCodec(slab=COL_TILE)
    coef = rng.integers(0, 256, (4, 10)).astype(np.uint8)
    widths = [1000, 512, 257, 0, 64, 777, 3]
    blocks = [rng.integers(0, 256, (10, w)).astype(np.uint8)
              for w in widths]
    outs = list(codec.coded_matmul_stream(coef, iter(blocks),
                                          depth=depth))
    assert len(outs) == len(blocks)
    for out, blk in zip(outs, blocks):
        want = codec_numpy.coded_matmul(coef, blk)
        assert np.array_equal(np.asarray(out), want), depth


def test_pipelined_encode_feed_matches_oracle(rng):
    """models.ec_pipeline host-feed (BASELINE config #3 path) is
    bit-identical to the jitted batch encode at several depths."""
    from seaweedfs_tpu.models import ec_pipeline as ep

    blocks = [rng.integers(0, 256, (2, 10, 300 + 17 * i))
              .astype(np.uint8) for i in range(4)]
    fn, a_bits = ep.jitted_encode()
    refs = [np.asarray(fn(a_bits, b)) for b in blocks]
    for depth in (1, 2, 4):
        outs = list(ep.pipelined_encode_stream(iter(blocks),
                                               depth=depth))
        for out, want in zip(outs, refs):
            assert np.array_equal(np.asarray(out), want), depth


def _mk_curve(cpu_mbps, rows, device=True):
    import time as _t

    from seaweedfs_tpu.ec import probe

    return {
        "fingerprint": probe.host_fingerprint(),
        "measured_at": _t.time(),
        "rows": rows,
        "cpu_backend": "numpy",
        "cpu_mbps": cpu_mbps,
        "device": ({"platform": "tpu", "kind": "test", "count": 1}
                   if device else None),
        "device_backend": "pallas",
    }


def _rows(rates_by_size_depth):
    return [{"size": s, "depth": d, "e2e_mbps": r}
            for (s, d), r in rates_by_size_depth.items()]


def test_router_interpolates_monotonically():
    """Piecewise-linear in log2(size) over best-depth-per-size,
    clamped at both ends: monotone input -> monotone output, no hump
    the sweep didn't measure."""
    from seaweedfs_tpu.ec import probe

    curve = _mk_curve(50.0, _rows({
        (1 << 20, 1): 10.0, (1 << 20, 2): 8.0,
        (4 << 20, 2): 40.0,
        (16 << 20, 2): 160.0, (16 << 20, 4): 120.0,
        (64 << 20, 4): 320.0}))
    xs = [1 << 18, 1 << 20, 2 << 20, 4 << 20, 11 << 20, 16 << 20,
          40 << 20, 64 << 20, 1 << 30]
    ys = [probe.e2e_mbps_at(curve, x) for x in xs]
    assert ys == sorted(ys)
    assert ys[0] == 10.0 and ys[-1] == 320.0  # clamped, no extrapolation
    # exact at measured points, best depth wins per size
    assert probe.e2e_mbps_at(curve, 16 << 20) == 160.0
    assert probe.depth_at(curve, 16 << 20) == 2
    assert probe.depth_at(curve, 64 << 20) == 4
    assert probe.depth_at(curve, 1 << 20) == 1


def test_router_never_picks_device_below_cpu_rate(monkeypatch):
    """A device whose MEASURED e2e is below the measured CPU rate is
    never selected, at any size — the r05 relay scenario."""
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    slow = _mk_curve(327.0, _rows({
        (1 << 20, 2): 3.0, (4 << 20, 2): 6.0,
        (16 << 20, 2): 9.0, (64 << 20, 4): 9.5}))
    for size in (1 << 18, 1 << 20, 8 << 20, 64 << 20, 1 << 30):
        assert ecb._decide(slow, size) == "numpy", size


def test_router_picks_device_when_measured_faster(monkeypatch):
    """...and a device that measurably beats the CPU rate at bulk
    sizes IS selected there, while small requests still route to the
    CPU codec (per-size decision from the same curve)."""
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    fast = _mk_curve(300.0, _rows({
        (1 << 20, 1): 50.0, (4 << 20, 2): 250.0,
        (16 << 20, 2): 900.0, (64 << 20, 4): 2000.0}))
    assert ecb._decide(fast, 1 << 20) == "numpy"
    assert ecb._decide(fast, 64 << 20) == "pallas"
    from seaweedfs_tpu.ec import probe

    monkeypatch.setattr(probe, "_curves", {"": fast})
    assert ecb.choose_backend_for_size(1 << 20) == "numpy"
    assert ecb.choose_backend_for_size(64 << 20) == "pallas"
    assert ecb.pipeline_depth_for(64 << 20) == 4


def test_probe_cache_roundtrip(tmp_path, monkeypatch):
    from seaweedfs_tpu.ec import probe

    path = str(tmp_path / "ec_probe.json")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_PROBE_CACHE", path)
    curve = _mk_curve(100.0, _rows({(1 << 20, 2): 5.0}))
    probe.save_cache(curve)
    got = probe.load_cached()
    assert got is not None
    assert got["rows"] == curve["rows"]


def test_probe_cache_corrupt_falls_back_to_sweep(tmp_path, monkeypatch):
    """Corrupt cache JSON -> load returns None -> get_curve re-sweeps;
    never a crash, never a half-trusted curve."""
    from seaweedfs_tpu.ec import probe

    path = str(tmp_path / "ec_probe.json")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_PROBE_CACHE", path)
    with open(path, "w") as f:
        f.write('{"rows": [1, 2')  # truncated JSON
    assert probe.load_cached() is None
    sentinel = _mk_curve(1.0, [], device=False)
    monkeypatch.setattr(probe, "run_sweep", lambda **kw: dict(sentinel))
    monkeypatch.setattr(probe, "_curves", {})
    got = probe.get_curve()
    assert got["source"] == "fresh"
    assert got["cpu_mbps"] == 1.0


def test_probe_cache_expired_or_foreign_falls_back(tmp_path,
                                                   monkeypatch):
    from seaweedfs_tpu.ec import probe

    path = str(tmp_path / "ec_probe.json")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_PROBE_CACHE", path)
    expired = _mk_curve(100.0, [])
    expired["measured_at"] -= probe.cache_ttl_s() + 60
    probe.save_cache(expired)
    assert probe.load_cached() is None
    foreign = _mk_curve(100.0, [])
    foreign["fingerprint"] = dict(foreign["fingerprint"],
                                  host="someone-else")
    probe.save_cache(foreign)
    assert probe.load_cached() is None
