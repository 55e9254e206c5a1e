"""The bring-up repairs: no hidden CPU fallback, one process per chip, a
compile cache placed from outside, native libraries built from the
committed source."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ec import probe
from seaweedfs_tpu.native import build as nbuild
from seaweedfs_tpu.ops import device
from seaweedfs_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _errors(stage: str) -> float:
    return metrics._counters.get(
        ("ec_device_errors_total", (("stage", stage),)), 0.0)


@pytest.mark.parametrize("name", ecb.DEVICE_BACKENDS)
def test_device_backend_refuses_cpu_unless_forced(monkeypatch, name):
    assert jax.devices()[0].platform == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="needs an accelerator"):
        ecb._factories[name]()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert ecb._factories[name]().name == name


def test_router_probe_error_is_an_error_not_a_decision(monkeypatch):
    def broken(**_kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(probe, "get_curve", broken)
    monkeypatch.setattr(ecb, "_auto_choice", None)
    monkeypatch.setattr(ecb, "_auto_probe", None)
    monkeypatch.delenv(ecb._AUTO_ENV, raising=False)
    before = _errors("router")
    assert ecb.choose_auto_backend() == ecb.cpu_backend_name()
    assert _errors("router") == before + 1
    assert "device lost" in ecb._auto_probe["device_error"]
    assert "device lost" in ecb.probe_snapshot()["device_error"]


def test_sweep_device_error_is_recorded_and_not_cached(monkeypatch,
                                                      tmp_path):
    def broken(*_a, **_kw):
        raise RuntimeError("kernel failed")

    monkeypatch.setenv("SEAWEEDFS_TPU_EC_PROBE_CACHE",
                       str(tmp_path / "probe.json"))
    monkeypatch.setattr(probe, "_device",
                        lambda: ("tpu", "TPU v5 lite", 1))
    monkeypatch.setattr(probe, "_measure_e2e_row", broken)
    monkeypatch.setattr(probe, "_curves", {})
    before = _errors("probe")
    curve = probe.get_curve()
    assert "kernel failed" in curve["device_error"]
    assert probe.summary(curve)["device_error"] == curve["device_error"]
    assert _errors("probe") == before + 1
    assert not (tmp_path / "probe.json").exists()
    # the router still routes — to the CPU, with the error on record
    assert ecb._decide(curve, 64 << 20) == curve["cpu_backend"]


def test_probe_snapshot_initialises_no_backend(tmp_path):
    """A process with no codec (master, filer) answers /debug/ec and
    /cluster/status without taking the chip, even with a probe cache
    on disk whose fingerprint check would ask jax for devices."""
    cache = tmp_path / "probe.json"
    cache.write_text(json.dumps({"rows": [], "measured_at": 0,
                                 "fingerprint": {}}))
    code = ("from seaweedfs_tpu.ec import backend\n"
            "from seaweedfs_tpu.ops import device\n"
            "snap = backend.probe_snapshot()\n"
            "assert not device.backends_initialized()\n"
            "print(snap['device'], snap['probe']['state'])\n")
    env = dict(os.environ, PYTHONPATH=REPO,
               SEAWEEDFS_TPU_EC_PROBE_CACHE=str(cache))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "no device in this process unprobed"


def test_compile_cache_follows_env(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: compiles land there and the code
    sets no directory of its own."""
    code = ("import jax, jax.numpy as jnp\n"
            "from seaweedfs_tpu.ops import device\n"
            "print(device.setup_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.jit(lambda x: x * 3)(jnp.ones(4)).block_until_ready()\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path)] * 2
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_fixed_in_tree_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert device.setup_compile_cache() == device.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == device.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
    assert device.CACHE_DIR == os.path.join(REPO, ".jax_cache")


def test_native_build_rebuilds_on_key_mismatch(tmp_path):
    src = tmp_path / "tiny.cc"
    src.write_text('extern "C" int tiny() { return 1; }\n')
    lib = str(tmp_path / "libtiny.so")
    nbuild._compile(str(src), lib, verbose=False)
    first = os.stat(lib).st_ino
    # same source, flags and CPU: the key matches, nothing is rebuilt
    nbuild._compile(str(src), lib, verbose=False)
    assert os.stat(lib).st_ino == first
    # a library whose key does not match (copied in from another
    # machine, or built from other sources) is rebuilt, however new
    with open(lib + ".key", "w") as f:
        f.write("built-elsewhere")
    os.utime(lib, (2 ** 31, 2 ** 31))
    nbuild._compile(str(src), lib, verbose=False)
    assert os.stat(lib).st_ino != first
    with open(lib + ".key") as f:
        assert f.read() == nbuild.build_key(
            str(src), ["-O3", "-march=native", "-shared", "-fPIC",
                       "-std=c++17"])


def test_shell_verify_refuses_device_backend(monkeypatch):
    from seaweedfs_tpu.shell import commands_ec
    from seaweedfs_tpu.shell.env import ShellError

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ShellError, match="belongs to the volume server"):
        commands_ec.ec_verify(None, 1, backend="pallas")


def test_pallas_codec_runs_interpreted_on_forced_cpu():
    from seaweedfs_tpu.ops import codec_numpy, rs_matrix

    coef = rs_matrix.parity_rows(10, 4)
    data = np.random.default_rng(5).integers(0, 256, (10, 9000),
                                             dtype=np.uint8)
    got = ecb._factories["pallas"]().coded_matmul(coef, data)
    assert np.array_equal(got, codec_numpy.coded_matmul(coef, data))
