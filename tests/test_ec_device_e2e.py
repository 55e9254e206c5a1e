"""Force-device e2e encode/rebuild (VERDICT r2 item 8): the auto
router's DEVICE arm executed through the real production file paths,
golden-bits-checked — not just coded_matmul units.

The device backend is "pallas": under the test conftest
(JAX_PLATFORMS=cpu, 8 virtual devices) its kernel runs interpreted
through the same depth-bounded streaming pipeline (H2D/compute/D2H,
slabbing) it runs compiled on a real accelerator. Either way,
write_ec_files and rebuild_ec_files run their device-streaming arm end
to end.
"""
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ec.encoder import (rebuild_ec_files, verify_ec_files,
                                      write_ec_files)


# interpreted on the CPU test mesh
DEVICE_BACKEND = "pallas"


@pytest.fixture()
def volume(tmp_path):
    base = str(tmp_path / "1")
    rng = np.random.default_rng(42)
    data = rng.integers(0, 256, 3 << 20, dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(data)
    open(base + ".idx", "wb").close()
    return base, data


def _shard_bytes(base):
    out = {}
    for i in range(geo.TOTAL_SHARDS):
        with open(base + geo.shard_ext(i), "rb") as f:
            out[i] = f.read()
    return out


def test_device_encode_golden_bits(volume, tmp_path):
    base, data = volume
    backend = DEVICE_BACKEND
    # small chunk: several streaming pipeline iterations, not one
    write_ec_files(base, backend=backend, chunk=1 << 20,
                   small_block=256 << 10)
    dev_shards = _shard_bytes(base)

    # golden: the CPU reference codec over a fresh copy of the volume
    base2 = str(tmp_path / "2")
    with open(base2 + ".dat", "wb") as f:
        f.write(data)
    open(base2 + ".idx", "wb").close()
    write_ec_files(base2, backend="numpy", chunk=1 << 20,
                   small_block=256 << 10)
    for i in range(geo.TOTAL_SHARDS):
        with open(base2 + geo.shard_ext(i), "rb") as f:
            assert f.read() == dev_shards[i], f"shard {i} diverges"


def test_device_rebuild_golden_bits(volume):
    base, _ = volume
    backend = DEVICE_BACKEND
    write_ec_files(base, backend=backend, chunk=1 << 20,
                   small_block=256 << 10)
    golden = _shard_bytes(base)
    # knock out a data shard and a parity shard, rebuild on device
    for i in (2, 12):
        os.remove(base + geo.shard_ext(i))
    rebuilt = rebuild_ec_files(base, backend=backend, chunk=1 << 20)
    assert sorted(rebuilt) == [2, 12]
    assert _shard_bytes(base) == golden
    assert verify_ec_files(base, backend=backend, chunk=1 << 20)


def test_env_override_routes_auto(volume, monkeypatch):
    """SEAWEEDFS_TPU_EC_BACKEND pins the auto router's choice — the
    production switch the force-device deployment would set."""
    from seaweedfs_tpu.ec import backend as ecb

    base, _ = volume
    backend = DEVICE_BACKEND
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_BACKEND", backend)
    ecb._auto_choice = None
    try:
        assert ecb.choose_auto_backend() == backend
        write_ec_files(base, backend="auto", chunk=1 << 20,
                       small_block=256 << 10)
        assert verify_ec_files(base, backend="numpy", chunk=1 << 20)
    finally:
        ecb._auto_choice = None
