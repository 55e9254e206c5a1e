"""Streaming codec pipeline + auto backend selection.

Covers the round-2 production wiring of the TPU codec: the
depth-bounded coded_matmul_stream pipeline (H2D / compute / D2H
overlap), the streaming write/rebuild/verify paths in ec/encoder.py,
and the measured `auto` backend choice (ec/backend.py
choose_auto_backend).
"""
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ec.backend import ReedSolomon, get_backend
from seaweedfs_tpu.ops import rs_matrix


@pytest.fixture(autouse=True)
def _reset_auto_choice():
    before = ecb._auto_choice
    yield
    ecb._auto_choice = before


def test_stream_matches_sync_jax():
    rs_sync = ReedSolomon(10, 4, backend="numpy")
    rs_dev = ReedSolomon(10, 4, backend="pallas")
    assert rs_dev.supports_streaming
    rng = np.random.default_rng(7)
    blocks = [rng.integers(0, 256, (10, w), dtype=np.uint8)
              for w in (1, 300, 4096, 70000, 0, 513)]
    out = list(rs_dev.encode_stream(iter(blocks), depth=3))
    assert len(out) == len(blocks)
    for block, parity in zip(blocks, out):
        assert np.array_equal(parity, rs_sync.encode(block))


def test_stream_fallback_sync_backend():
    # numpy backend has no coded_matmul_stream: matmul_stream must
    # degrade to the synchronous per-block path with identical results
    rs = ReedSolomon(10, 4, backend="numpy")
    assert not rs.supports_streaming
    rng = np.random.default_rng(8)
    blocks = [rng.integers(0, 256, (10, 1000), dtype=np.uint8)
              for _ in range(3)]
    out = list(rs.encode_stream(iter(blocks)))
    for block, parity in zip(blocks, out):
        assert np.array_equal(parity, rs.encode(block))


def test_stream_recovery_rows():
    # the rebuild path streams with a recovery matrix, not parity rows
    rs = ReedSolomon(10, 4, backend="pallas")
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, (10, 5000), dtype=np.uint8)
    parity = ReedSolomon(10, 4, backend="numpy").encode(data)
    full = np.concatenate([data, parity])
    present = [i for i in range(14) if i not in (2, 9)]
    rows, inputs = rs_matrix.recovery_rows(10, 4, present, [2, 9])
    blocks = [np.stack([full[i][c:c + 1024] for i in inputs])
              for c in range(0, 5000, 1024)]
    rec = np.concatenate(list(rs.matmul_stream(rows, iter(blocks))),
                         axis=1)
    assert np.array_equal(rec[0], full[2])
    assert np.array_equal(rec[1], full[9])


def test_auto_env_override(monkeypatch):
    monkeypatch.setenv(ecb._AUTO_ENV, "numpy")
    ecb._auto_choice = None
    assert ecb.choose_auto_backend() == "numpy"


def test_auto_on_cpu_picks_cpu_codec(monkeypatch):
    # tests run with JAX_PLATFORMS=cpu: the probe must refuse the
    # device path and land on the fastest CPU codec present
    monkeypatch.delenv(ecb._AUTO_ENV, raising=False)
    ecb._auto_choice = None
    choice = ecb.choose_auto_backend()
    assert choice in ("native", "numpy")
    assert choice == ecb._probe_cpu_backend()


def test_auto_codec_delegates(monkeypatch):
    monkeypatch.setenv(ecb._AUTO_ENV, "numpy")
    ecb._auto_choice = None
    auto = ecb.AutoCodec()
    coef = rs_matrix.parity_rows(4, 2)
    data = np.arange(4 * 64, dtype=np.uint8).reshape(4, 64)
    want = get_backend("numpy").coded_matmul(coef, data)
    assert np.array_equal(auto.coded_matmul(coef, data), want)
    assert auto.chosen == "numpy"
    # streaming falls back to sync per-block on a sync impl
    outs = list(auto.coded_matmul_stream(coef, iter([data, data])))
    assert all(np.array_equal(o, want) for o in outs)


def test_write_ec_files_auto_streaming(tmp_path, monkeypatch):
    # e2e: write_ec_files default (auto) must equal the numpy golden
    from seaweedfs_tpu.ec.encoder import rebuild_ec_files, \
        verify_ec_files, write_ec_files
    from seaweedfs_tpu.ec.geometry import shard_ext

    rng = np.random.default_rng(11)
    payload = rng.integers(0, 256, 3 << 20, dtype=np.uint8).tobytes()
    for sub, backend in (("a", "numpy"), ("b", "auto"), ("c", "pallas")):
        base = tmp_path / sub / "1"
        os.makedirs(base.parent)
        (base.parent / "1.dat").write_bytes(payload)
        write_ec_files(str(base), backend=backend,
                       large_block=1 << 20, small_block=1 << 14,
                       chunk=1 << 19)
    for i in range(14):
        golden = (tmp_path / "a" / ("1" + shard_ext(i))).read_bytes()
        assert (tmp_path / "b" / ("1" + shard_ext(i))).read_bytes() \
            == golden, f"auto shard {i} diverges"
        assert (tmp_path / "c" / ("1" + shard_ext(i))).read_bytes() \
            == golden, f"pallas streaming shard {i} diverges"

    # streamed rebuild: drop two shards from the pallas copy, rebuild, compare
    base = str(tmp_path / "c" / "1")
    for i in (0, 12):
        os.unlink(base + shard_ext(i))
    assert sorted(rebuild_ec_files(base, backend="pallas",
                                   chunk=1 << 18)) == [0, 12]
    for i in (0, 12):
        golden = (tmp_path / "a" / ("1" + shard_ext(i))).read_bytes()
        assert (tmp_path / "c" / ("1" + shard_ext(i))).read_bytes() \
            == golden
    assert verify_ec_files(base, backend="pallas", chunk=1 << 18)
