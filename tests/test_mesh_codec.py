"""Mesh codec (-ec.backend=mesh) tests: bit-for-bit oracle agreement
with the CPU codec on even and uneven shapes, pad_to_mesh round-trips,
and the three-way (cpu / single-chip / mesh) measured-curve router.

All device tests run on the 8-device virtual CPU mesh conftest forces;
they skip themselves (mesh marker) if fewer than 2 devices are visible.
"""
import time as _time

import jax
import numpy as np
import pytest

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ec import probe
from seaweedfs_tpu.ops import codec_numpy, rs_matrix
from seaweedfs_tpu.parallel import mesh as pmesh

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="mesh tests need >= 2 jax devices")

pytestmark = [pytest.mark.mesh, needs_devices]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


@pytest.fixture(scope="module")
def mesh_codec():
    from seaweedfs_tpu.ops.codec_mesh import MeshCodec

    return MeshCodec()


# ---------------------------------------------------------------------
# oracle agreement
# ---------------------------------------------------------------------

@pytest.mark.parametrize("km", [(10, 4), (28, 4)])
@pytest.mark.parametrize("n", [8192, 5000, 777, 8, 1])
def test_mesh_encode_matches_cpu_oracle(mesh_codec, rng, km, n):
    """Even AND uneven column counts: the mesh pad->shard->trim path is
    bit-identical to the numpy codec for narrow and wide codes."""
    k, m = km
    coef = rs_matrix.parity_rows(k, m)
    data = rng.integers(0, 256, (k, n), dtype=np.uint8)
    got = mesh_codec.coded_matmul(coef, data)
    want = codec_numpy.coded_matmul(coef, data)
    assert got.shape == (m, n)
    assert np.array_equal(got, want), (km, n)


@pytest.mark.parametrize("km", [(10, 4), (28, 4)])
def test_mesh_reconstruct_matches_cpu_oracle(mesh_codec, rng, km):
    k, m = km
    rs_mesh = ecb.ReedSolomon(k, m, backend=mesh_codec)
    rs_cpu = ecb.ReedSolomon(k, m, backend="numpy")
    data = rng.integers(0, 256, (k, 3001), dtype=np.uint8)
    parity = rs_mesh.encode(data)
    assert np.array_equal(parity, rs_cpu.encode(data))
    full = np.concatenate([data, parity], axis=0)
    drop = [0, 3, k + 1, k + 3]
    shards = {i: full[i] for i in range(k + m) if i not in drop}
    rec = rs_mesh.reconstruct(shards)
    assert sorted(rec) == sorted(drop)
    for sid, row in rec.items():
        assert np.array_equal(row, full[sid]), (km, sid)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_mesh_stream_matches_oracle_all_depths(mesh_codec, rng, depth):
    """Streaming pipeline: order preserved, uneven widths and an empty
    block mid-stream, bit-identical at every depth."""
    coef = rs_matrix.parity_rows(10, 4)
    widths = [4096, 1000, 0, 257, 8192, 3]
    blocks = [rng.integers(0, 256, (10, w), dtype=np.uint8)
              for w in widths]
    outs = list(mesh_codec.coded_matmul_stream(coef, iter(blocks),
                                               depth=depth))
    assert len(outs) == len(blocks)
    for out, blk in zip(outs, blocks):
        assert np.array_equal(out, codec_numpy.coded_matmul(coef, blk))


def test_mesh_registered_and_constructible():
    assert "mesh" in ecb.backend_names()
    assert "mesh" in ecb.available_backend_names()
    codec = ecb.get_backend("mesh")
    geo = codec.describe()
    assert geo["devices"] == geo["vol"] * geo["col"] >= 2


# ---------------------------------------------------------------------
# pad_to_mesh round-trips (satellite: uneven batch/column oracles)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("km", [(10, 4), (28, 4)])
def test_pad_to_mesh_roundtrip_uneven(rng, km):
    """Uneven batch AND uneven columns: sharded encode over the padded
    tensor, sliced back, equals the single-chip encode bit-for-bit."""
    from seaweedfs_tpu.models import ec_pipeline as ep

    k, m = km
    mesh = pmesh.make_mesh()
    vol, col = mesh.devices.shape
    batch, cols = vol + 1, 100 * col + 3  # both indivisible
    stripes = rng.integers(0, 256, (batch, k, cols), dtype=np.uint8)

    padded, orig = pmesh.pad_to_mesh(stripes, mesh)
    assert orig == (batch, cols)
    assert padded.shape[0] % vol == 0 and padded.shape[2] % col == 0

    step, a_bits, data_sh = ep.sharded_encode_scrub(mesh, k, m)
    dev = jax.device_put(padded, data_sh)
    zeros = jax.device_put(
        np.zeros((padded.shape[0], m, padded.shape[2]), np.uint8),
        data_sh)
    parity, _ = step(a_bits, dev, zeros)
    got = np.asarray(parity)[:batch, :, :cols]

    fn, a1 = ep.jitted_encode(k, m)
    want = np.asarray(fn(a1, stripes))
    assert np.array_equal(got, want), km


def test_pad_to_mesh_even_is_identity(rng):
    mesh = pmesh.make_mesh()
    vol, col = mesh.devices.shape
    arr = rng.integers(0, 256, (2 * vol, 10, 64 * col), dtype=np.uint8)
    padded, orig = pmesh.pad_to_mesh(arr, mesh)
    assert padded is arr
    assert orig == (arr.shape[0], arr.shape[2])


def test_make_mesh_divisibility_error():
    n = len(jax.devices())
    with pytest.raises(ValueError):
        pmesh.make_mesh(n, col_parallel=n + 1)
    if n % 3:
        with pytest.raises(ValueError):
            pmesh.make_mesh(n, col_parallel=3)
    with pytest.raises(ValueError):
        pmesh.make_mesh(n + 1)  # more than the host has


def test_mesh_config_env_parsing(monkeypatch):
    monkeypatch.setenv(pmesh.DEVICES_ENV, "4")
    monkeypatch.setenv(pmesh.COL_ENV, "2")
    assert pmesh.mesh_config() == (4, 2)
    monkeypatch.setenv(pmesh.DEVICES_ENV, "garbage")
    monkeypatch.setenv(pmesh.COL_ENV, "-3")
    assert pmesh.mesh_config() == (None, None)  # ignored, not fatal
    monkeypatch.delenv(pmesh.DEVICES_ENV)
    monkeypatch.delenv(pmesh.COL_ENV)
    assert pmesh.mesh_config() == (None, None)


def test_mesh_codec_respects_env_shape(monkeypatch):
    from seaweedfs_tpu.ops.codec_mesh import MeshCodec

    monkeypatch.setenv(pmesh.DEVICES_ENV, "2")
    monkeypatch.setenv(pmesh.COL_ENV, "1")
    codec = MeshCodec()
    assert (codec.n_devices, codec.vol, codec.col) == (2, 2, 1)


# ---------------------------------------------------------------------
# pipelined feed over the mesh
# ---------------------------------------------------------------------

def test_pipelined_encode_stream_mesh_matches_single(rng):
    from seaweedfs_tpu.models import ec_pipeline as ep

    mesh = pmesh.make_mesh()
    blocks = [rng.integers(0, 256, (3, 10, 300 + 17 * i), dtype=np.uint8)
              for i in range(4)]  # uneven batch and columns throughout
    fn, a_bits = ep.jitted_encode()
    refs = [np.asarray(fn(a_bits, b)) for b in blocks]
    for depth in (1, 2):
        outs = list(ep.pipelined_encode_stream(iter(blocks), depth=depth,
                                               mesh=mesh))
        assert len(outs) == len(blocks)
        for out, want in zip(outs, refs):
            assert out.shape == want.shape
            assert np.array_equal(np.asarray(out), want), depth


def test_pipelined_scrub_mesh_counts_mismatches(rng):
    from seaweedfs_tpu.models import ec_pipeline as ep

    mesh = pmesh.make_mesh()
    fn, a_bits = ep.jitted_encode()
    stripes = rng.integers(0, 256, (3, 10, 501), dtype=np.uint8)
    parity = np.asarray(fn(a_bits, stripes))
    clean, n = ep.pipelined_scrub(iter([(stripes, parity)]), mesh=mesh)
    assert (clean, n) == (0, 1)
    bad = parity.copy()
    bad[0, 0, 0] ^= 0xFF
    dirty, n = ep.pipelined_scrub(iter([(stripes, bad)]), mesh=mesh)
    assert n == 1 and dirty == 1  # exactly the byte we flipped


# ---------------------------------------------------------------------
# three-way router + fingerprint invalidation
# ---------------------------------------------------------------------

def _mk_curve(cpu_mbps, rows=(), mesh_rows=(), device=True):
    curve = {
        "fingerprint": probe.host_fingerprint(),
        "measured_at": _time.time(),
        "rows": list(rows),
        "cpu_backend": "numpy",
        "cpu_mbps": cpu_mbps,
        "device": ({"platform": "tpu", "kind": "test", "count": 8}
                   if device else None),
        "device_backend": "pallas",
    }
    if mesh_rows:
        curve["mesh_rows"] = list(mesh_rows)
        curve["mesh"] = {"devices": 8, "vol": 4, "col": 2,
                         "platform": "tpu"}
    return curve


def _rows(rates):
    return [{"size": s, "depth": d, "e2e_mbps": r}
            for (s, d), r in rates.items()]


def test_router_picks_mesh_when_fastest(monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    curve = _mk_curve(300.0,
                      rows=_rows({(1 << 20, 1): 400.0,
                                  (64 << 20, 2): 900.0}),
                      mesh_rows=_rows({(1 << 20, 1): 100.0,
                                       (64 << 20, 4): 4000.0}))
    # small requests can't amortize the scatter: single-chip wins
    assert ecb._decide(curve, 1 << 20) == "pallas"
    # bulk rides the mesh
    assert ecb._decide(curve, 64 << 20) == "mesh"
    monkeypatch.setattr(probe, "_curves", {"": curve})
    assert ecb.choose_backend_for_size(64 << 20) == "mesh"
    # depth for a mesh-routed size comes from the MESH rows
    assert ecb.pipeline_depth_for(64 << 20) == 4
    assert ecb.pipeline_depth_for(1 << 20) == 1


def test_router_never_picks_mesh_below_cpu(monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_EC_BACKEND", raising=False)
    curve = _mk_curve(500.0,
                      rows=_rows({(64 << 20, 2): 90.0}),
                      mesh_rows=_rows({(64 << 20, 4): 400.0}))
    for size in (1 << 20, 64 << 20, 1 << 30):
        assert ecb._decide(curve, size) == "numpy", size


def test_router_mesh_interpolation_and_buckets():
    curve = _mk_curve(100.0,
                      rows=_rows({(1 << 20, 1): 50.0}),
                      mesh_rows=_rows({(1 << 20, 1): 200.0,
                                       (64 << 20, 4): 800.0}))
    assert probe.mesh_mbps_at(curve, 1 << 20) == 200.0
    assert probe.mesh_mbps_at(curve, 64 << 20) == 800.0
    mid = probe.mesh_mbps_at(curve, 8 << 20)
    assert 200.0 < mid < 800.0
    assert probe.mesh_depth_at(curve, 64 << 20) == 4
    buckets = ecb.router_buckets(curve)
    assert any(b["mesh_e2e_mbps"] for b in buckets)
    assert buckets[-1]["backend"] == "mesh"
    # no mesh rows -> reader degrades to None/default, not a crash
    bare = _mk_curve(100.0, rows=_rows({(1 << 20, 1): 50.0}))
    assert probe.mesh_mbps_at(bare, 4 << 20) is None
    assert probe.mesh_depth_at(bare, 4 << 20) == 2


def test_fingerprint_includes_visible_device_count(monkeypatch):
    """Satellite fix: a curve swept with a different visible device
    set must not be trusted — the fingerprint carries the TOTAL device
    count (any platform) and the mesh shape knobs, so CPU-only hosts
    invalidate too."""
    fp = probe.host_fingerprint()
    assert fp["device_count"] == len(jax.devices())
    assert fp["probe_version"] == probe.PROBE_VERSION >= 2
    assert "mesh_config" in fp

    stale = _mk_curve(100.0, rows=_rows({(1 << 20, 1): 50.0}))
    stale["fingerprint"] = dict(stale["fingerprint"], device_count=1)
    import json as _json
    import os as _os
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        path = _os.path.join(td, "probe.json")
        monkeypatch.setenv("SEAWEEDFS_TPU_EC_PROBE_CACHE", path)
        with open(path, "w", encoding="utf-8") as f:
            _json.dump(stale, f)
        assert probe.load_cached() is None  # stale device set rejected
        fresh = _mk_curve(100.0, rows=_rows({(1 << 20, 1): 50.0}))
        with open(path, "w", encoding="utf-8") as f:
            _json.dump(fresh, f)
        assert probe.load_cached() is not None


def test_fingerprint_changes_with_mesh_knobs(monkeypatch):
    base = probe.host_fingerprint()
    monkeypatch.setenv(pmesh.DEVICES_ENV, "2")
    assert probe.host_fingerprint() != base


def test_mesh_geometry_in_debug_snapshot():
    ecb.get_backend("mesh")  # ensure the instance exists
    snap = ecb.probe_snapshot()
    geo = snap["mesh"]
    assert geo["state"] == "active"
    assert geo["devices"] >= 2
    assert geo["devices"] == geo["vol"] * geo["col"]


def test_summary_includes_mesh_rows():
    curve = _mk_curve(100.0,
                      rows=_rows({(1 << 20, 1): 50.0}),
                      mesh_rows=_rows({(64 << 20, 4): 800.0}))
    s = probe.summary(curve)
    assert s["mesh"]["devices"] == 8
    assert s["mesh_best_by_size_mb"]["64"]["e2e_mbps"] == 800.0
