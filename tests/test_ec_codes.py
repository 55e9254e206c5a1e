"""Pluggable code families (ISSUE 14): LRC beside RS(10,4).

Property tests against the numpy GF(256) oracle: drop-any-1 heals
through the LOCAL plan (group-size fan-in, bit-for-bit), every
recoverable multi-loss pattern heals through the GLOBAL solve, and
unrecoverable patterns are refused — never silently mis-decoded. Plus
the bit-plane scheduling pass oracle: the CSE'd XOR program is
bit-identical to the dense matmul on every backend that runs it.
"""
import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ec import backend as ecb
from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ops import codec_numpy, rs_matrix, schedule

pytestmark = pytest.mark.codes

LRC = "lrc-12.3.2"   # the registered locality code (k=12, 3 locals, 2 globals)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(1309)  # arXiv 1309.0186


def _full_stripe(code: geo.CodeConfig, rng, width: int) -> np.ndarray:
    data = rng.integers(0, 256, (code.k, width), dtype=np.uint8)
    parity = codec_numpy.coded_matmul(rs_matrix.parity_rows_for(code), data)
    return np.concatenate([data, parity], axis=0)


# ---------------------------------------------------------------------
# code registry + geometry structure
# ---------------------------------------------------------------------

def test_parse_code_canonical_identity():
    """'' and '10.4' are ONE code: same spec, equal configs — the probe
    fingerprint, the .vif and the router must never see two names for
    the default."""
    assert geo.parse_code("") == geo.parse_code("10.4")
    assert geo.parse_code("").spec == "10.4"
    assert geo.parse_code("").is_rs
    assert geo.parse_code("28.4").k == 28


def test_parse_code_rejects_bad_specs():
    for bad in ("lrc-12.5.2",      # k not divisible into l groups
                "lrc-12.3",        # missing globals
                "lrc-0.1.1", "lrc-12.3.0",
                "lrc-24.4.6"):     # k+l+g > 32 shard-bit mask
        with pytest.raises(ValueError):
            geo.parse_code(bad)


def test_lrc_geometry_structure():
    code = geo.parse_code(LRC)
    assert (code.k, code.n_local, code.n_global) == (12, 3, 2)
    assert (code.m, code.total) == (5, 17)
    assert code.group_size == 4
    assert code.local_groups == ((0, 1, 2, 3, 12), (4, 5, 6, 7, 13),
                                 (8, 9, 10, 11, 14))
    assert code.global_parities == (15, 16)
    assert code.group_of(5) == (4, 5, 6, 7, 13)
    assert code.group_of(15) is None
    assert code.repair_fanin == 4          # vs 10 for RS(10,4)
    assert code.storage_overhead == pytest.approx(17 / 12)


def test_lrc_local_parity_is_group_xor(rng):
    """Shard k+i of the encode matrix is literally the XOR of group i
    — the structure the local repair path peels."""
    code = geo.parse_code(LRC)
    full = _full_stripe(code, rng, 513)
    for grp in code.local_groups:
        *members, lp = grp
        want = np.bitwise_xor.reduce(full[list(members)], axis=0)
        assert np.array_equal(full[lp], want)


# ---------------------------------------------------------------------
# drop-any-1 -> local repair (bit-for-bit vs oracle, even/uneven widths)
# ---------------------------------------------------------------------

@pytest.mark.parametrize("width", [1, 7, 64, 1000, 4096])
def test_lrc_single_loss_heals_locally(rng, width):
    code = geo.parse_code(LRC)
    rs = ecb.ReedSolomon.for_codec(LRC)
    full = _full_stripe(code, rng, width)
    survivors = lambda sid: [s for s in range(code.total) if s != sid]
    for sid in range(code.total):
        plan = code.repair_plan([sid], survivors(sid))
        assert plan is not None and plan.missing == (sid,)
        if code.group_of(sid) is not None:
            # data or local parity: group peel, fan-in = group size
            assert plan.kind == "local"
            assert plan.fanin == code.group_size
            assert set(plan.reads) <= set(code.group_of(sid))
        else:
            # a lost global parity needs the full-rank solve
            assert plan.kind == "global"
        shards = {s: full[s] for s in plan.reads}
        rec = rs.reconstruct(shards, [sid])
        assert np.array_equal(rec[sid], full[sid]), (sid, width)


def test_rs_single_loss_plan_is_k_wide(rng):
    """RS has no locality: the plan exists but reads k shards — the
    ladder's cost model must see the difference."""
    code = geo.parse_code("10.4")
    plan = code.repair_plan([3], [s for s in range(14) if s != 3])
    assert plan is not None
    assert plan.fanin == code.k


# ---------------------------------------------------------------------
# multi-loss -> global repair; unrecoverable -> refused
# ---------------------------------------------------------------------

def _check_pattern(code, rs, full, missing) -> None:
    present = [s for s in range(code.total) if s not in missing]
    plan = code.repair_plan(missing, present)
    if code.recoverable(present):
        assert plan is not None, missing
        shards = {s: full[s] for s in plan.reads}
        rec = rs.reconstruct(shards, list(missing))
        for sid in missing:
            assert np.array_equal(rec[sid], full[sid]), missing
    else:
        assert plan is None, missing
        with pytest.raises(ValueError):
            rs.reconstruct({s: full[s] for s in present}, list(missing))


def test_lrc_every_triple_loss_recovers(rng):
    """All C(17,1)+C(17,2)+C(17,3) loss patterns: the code's distance
    covers any <= globals+1 = 3 erasures, and every one reconstructs
    bit-for-bit from exactly the plan's read set."""
    code = geo.parse_code(LRC)
    rs = ecb.ReedSolomon.for_codec(LRC)
    full = _full_stripe(code, rng, 64)
    shard_ids = range(code.total)
    n = 0
    for size in (1, 2, 3):
        for missing in itertools.combinations(shard_ids, size):
            present = [s for s in shard_ids if s not in missing]
            assert code.recoverable(present), missing
            _check_pattern(code, rs, full, missing)
            n += 1
    assert n == 17 + 136 + 680


def test_lrc_quad_loss_recoverable_vs_refused(rng):
    """4 erasures exceed the guaranteed distance: SOME patterns still
    solve (and must be bit-exact), others are rank-deficient (and must
    raise, not mis-decode). recoverable() is the single source of
    truth either way."""
    code = geo.parse_code(LRC)
    rs = ecb.ReedSolomon.for_codec(LRC)
    full = _full_stripe(code, rng, 64)
    quads = list(itertools.combinations(range(code.total), 4))
    sample = [quads[i] for i in
              np.random.default_rng(4).choice(len(quads), 120,
                                              replace=False)]
    # both branches must actually occur in the sample
    split = {True: 0, False: 0}
    for missing in sample:
        present = [s for s in range(code.total) if s not in missing]
        split[code.recoverable(present)] += 1
        _check_pattern(code, rs, full, missing)
    assert split[True] > 0 and split[False] > 0, split


def test_lrc_two_losses_one_group_goes_global(rng):
    """Two losses inside ONE group defeat the local XOR; the plan
    escalates to a global solve and still heals bit-for-bit."""
    code = geo.parse_code(LRC)
    rs = ecb.ReedSolomon.for_codec(LRC)
    full = _full_stripe(code, rng, 333)
    missing = [0, 1]                       # same group, same peel
    plan = code.repair_plan(missing, range(2, code.total))
    assert plan is not None and plan.kind == "global"
    rec = rs.reconstruct({s: full[s] for s in plan.reads}, missing)
    for sid in missing:
        assert np.array_equal(rec[sid], full[sid])


def test_lrc_mixed_peel_then_solve(rng):
    """One healable-by-group loss plus an unrelated double loss: the
    peel heals what it can, the solve covers the rest, one plan."""
    code = geo.parse_code(LRC)
    rs = ecb.ReedSolomon.for_codec(LRC)
    full = _full_stripe(code, rng, 100)
    missing = [0, 4, 5]   # group 0 single + group 1 double
    plan = code.repair_plan(missing,
                            [s for s in range(code.total)
                             if s not in missing])
    assert plan is not None and plan.kind == "global"
    rec = rs.reconstruct({s: full[s] for s in plan.reads}, missing)
    for sid in missing:
        assert np.array_equal(rec[sid], full[sid])


def test_lrc_survivor_count_is_not_recoverability():
    """>= k survivors can still be rank-deficient for a structured
    code: lose a whole group's data AND its local parity and the
    remaining 12 shards don't span — the honest check is rank, and
    both recoverable() and the plan say no."""
    code = geo.parse_code(LRC)
    missing = [0, 1, 2, 3, 12]   # group 0 entirely (worse than distance)
    present = [s for s in range(code.total) if s not in missing]
    assert len(present) >= code.k         # the count heuristic would lie
    assert not code.recoverable(present)
    assert code.repair_plan(missing, present) is None


# ---------------------------------------------------------------------
# mesh backend (multi-device): LRC coefficients through the mesh codec
# ---------------------------------------------------------------------

@pytest.mark.mesh
@pytest.mark.parametrize("width", [8192, 777, 1])
def test_lrc_mesh_backend_matches_oracle(rng, width):
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("mesh tests need >= 2 jax devices")
    from seaweedfs_tpu.ops.codec_mesh import MeshCodec

    code = geo.parse_code(LRC)
    coef = rs_matrix.parity_rows_for(code)
    data = rng.integers(0, 256, (code.k, width), dtype=np.uint8)
    got = MeshCodec().coded_matmul(coef, data)
    want = codec_numpy.coded_matmul(coef, data)
    assert np.array_equal(np.asarray(got), want), width


# ---------------------------------------------------------------------
# scheduling pass: XOR program oracle
# ---------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["10.4", LRC, "28.4"])
def test_schedule_program_matches_dense_oracle(rng, spec):
    """The CSE'd bit-plane program computes EXACTLY the dense GF(256)
    matmul, for every registered code's parity block, on even and
    uneven widths — and never uses more XORs than the naive program."""
    code = geo.parse_code(spec)
    coef = rs_matrix.parity_rows_for(code)
    prog = schedule.build_program(coef)
    assert prog.xors <= prog.naive_xors
    for width in (1, 5, 64, 1000):
        data = rng.integers(0, 256, (code.k, width), dtype=np.uint8)
        want = codec_numpy.coded_matmul(coef, data)
        got = schedule.apply_bytes_numpy(prog, data)
        assert np.array_equal(got, want), (spec, width)


def test_schedule_cse_actually_saves():
    """Paar factoring must find shared subexpressions in a dense
    Vandermonde parity block — a no-op pass would silently fall back
    to naive cost everywhere and the never-slower guarantee would be
    vacuous."""
    prog = schedule.plan_for(rs_matrix.parity_rows(10, 4))
    assert prog.saving > 0.25, prog.saving


def test_flattened_oplist_layout():
    coef = rs_matrix.parity_rows(4, 2)
    prog = schedule.build_program(coef)
    flat = schedule.flatten(prog)
    assert flat.dtype == np.int32
    n_in, n_out, n_ops = int(flat[0]), int(flat[1]), int(flat[2])
    assert (n_in, n_out) == (prog.n_in, prog.n_out)
    assert len(flat) == 3 + 3 * n_ops + n_out


def test_native_scheduled_kernel_matches_oracle(rng):
    from seaweedfs_tpu import native

    try:
        native.load()
    except Exception as e:
        pytest.skip(f"native library unavailable: {e}")
    code = geo.parse_code(LRC)
    coef = rs_matrix.parity_rows_for(code)
    flat = schedule.flatten(schedule.build_program(coef))
    for width in (1, 63, 4096, 100_000):
        data = rng.integers(0, 256, (code.k, width), dtype=np.uint8)
        got = native.scheduled_matmul(flat, data, coef.shape[0])
        assert np.array_equal(got, codec_numpy.coded_matmul(coef, data))


@pytest.mark.parametrize("force", ["on", "off"])
def test_native_codec_forced_schedule_modes(rng, monkeypatch, force):
    """SEAWEEDFS_TPU_EC_SCHEDULE on/off both stay bit-identical —
    the mode only moves the work between kernels."""
    from seaweedfs_tpu.ops import codec_native

    try:
        codec = codec_native.NativeCodec()
    except Exception as e:
        pytest.skip(f"native codec unavailable: {e}")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_SCHEDULE", force)
    coef = rs_matrix.parity_rows_for(geo.parse_code(LRC))
    data = rng.integers(0, 256, (12, schedule.MIN_SCHED_BYTES // 12 + 11),
                        dtype=np.uint8)
    got = codec.coded_matmul(coef, data)
    assert np.array_equal(np.asarray(got),
                          codec_numpy.coded_matmul(coef, data))


# ---------------------------------------------------------------------
# inversion LRU + .vif round trip
# ---------------------------------------------------------------------

def test_reconstruction_inversion_cache_hits(rng):
    """A repair storm over one loss pattern pays the k x k inversion
    once: the second stripe chunk with the same surviving set is a
    cache hit."""
    rs_matrix._inv_cache.clear()
    rs = ecb.ReedSolomon(10, 4, backend="numpy")
    code = geo.parse_code("10.4")
    full = _full_stripe(code, rng, 128)
    shards = {s: full[s] for s in range(14) if s not in (2, 7)}
    rs.reconstruct(dict(shards), [2, 7])
    before = rs_matrix.inversion_cache_info()["entries"]
    rs.reconstruct(dict(shards), [2, 7])   # same survivors -> hit
    assert rs_matrix.inversion_cache_info()["entries"] == before > 0


def test_vif_records_code_and_rebuild_uses_plan(rng, tmp_path):
    """write_ec_files with an LRC codec records the code in the .vif
    (even though LRC-10.2.2-style codes can share RS's (k, m)); a
    single lost shard rebuilds bit-for-bit from the sidecar's code."""
    from seaweedfs_tpu.ec import encoder

    base = str(tmp_path / "v1")
    dat = rng.integers(0, 256, 3 * (1 << 12), dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    encoder.write_ec_files(base, backend="numpy", codec=LRC,
                           large_block=1 << 14, small_block=1 << 10)
    code = encoder.code_of(base)
    assert code == geo.parse_code(LRC)
    import os
    with open(base + geo.shard_ext(5), "rb") as f:
        want = f.read()
    os.remove(base + geo.shard_ext(5))
    rebuilt = encoder.rebuild_ec_files(base, backend="numpy")
    assert rebuilt == [5]
    with open(base + geo.shard_ext(5), "rb") as f:
        assert f.read() == want
    assert encoder.verify_ec_files(base, backend="numpy")


def test_degraded_gather_skips_dependent_rows(rng, tmp_path):
    """Regression (store generic gather): with data shard 5 lost, the
    first-k-BY-COUNT local set {0-4, 6-11, 12} has GF(256) rank 11 —
    shard 12 is the XOR of its fully-present group — so a count-based
    gather declared the read dead while an independent global parity
    sat one fetch away. The gather must grow the row SPAN: skip
    dependent shards and keep fetching until rank k."""
    from seaweedfs_tpu.ec.encoder import write_ec_files, write_sorted_ecx
    from seaweedfs_tpu.storage.store import Store

    code = geo.parse_code(LRC)
    base = tmp_path / "91"
    (tmp_path / "91.dat").write_bytes(
        rng.integers(0, 256, code.k * 1024 * 3, dtype=np.uint8).tobytes())
    (tmp_path / "91.idx").write_bytes(b"")
    write_ec_files(str(base), backend="numpy", codec=LRC,
                   large_block=1 << 14, small_block=1 << 10)
    write_sorted_ecx(str(base))
    shards = {s: (tmp_path / ("91" + geo.shard_ext(s))).read_bytes()
              for s in range(code.total)}
    # kept local: 0-4, 6-11 plus BOTH dependent local parities 12 and
    # 14 (each one's data group is fully present). Gone from disk: the
    # lost shard 5, its group parity 13, and the global parities 15/16
    # — of which only 16 answers over the wire
    for s in (5, 13, 15, 16):
        (tmp_path / ("91" + geo.shard_ext(s))).unlink()
    store = Store([str(tmp_path)])
    ecv = store.ec_volumes[91]
    assert ecv.code == code
    asked = []

    def fetcher(vid, sids, offset, size, need, deadline):
        asked.append(tuple(sids))
        if 16 in sids:
            return {16: shards[16][offset:offset + size]}
        return {}

    store.remote_shards_fetcher = fetcher
    got = store._reconstruct_interval(ecv, 5, 64, 2048)
    assert got == shards[5][64:64 + 2048]
    # the planned group read tried (and lost) dark shard 13 first,
    # then the rank-aware fallback went to the independent parity
    assert asked[0] == (13,)
    assert any(16 in sids for sids in asked[1:])


def test_chooser_measures_once_under_concurrent_callers(monkeypatch):
    """A caller that arrives while another thread measures the same
    (matrix, bucket) gets the dense answer at once instead of starting
    a second measurement; the verdict serves everyone afterwards."""
    import threading
    import time

    monkeypatch.setenv("SEAWEEDFS_TPU_EC_SCHEDULE", "auto")
    ch = schedule.Chooser()
    coef = rs_matrix.parity_rows(10, 4)
    started, gate = threading.Event(), threading.Event()
    sched_runs = []

    def run_sched():
        started.set()
        gate.wait(10)
        sched_runs.append(1)

    def run_dense():
        time.sleep(0.002)

    n = schedule.MIN_SCHED_BYTES
    first = []
    t = threading.Thread(target=lambda: first.append(
        ch.use_scheduled(coef, n, run_sched, run_dense)))
    t.start()
    assert started.wait(10)
    assert ch.snapshot()["measuring"] == 1
    assert ch.use_scheduled(coef, n, run_sched, run_dense) is False
    gate.set()
    t.join(10)
    snap = ch.snapshot()
    assert snap["measuring"] == 0 and snap["buckets"] == 1
    # warm + timed = exactly one measurement despite two callers
    assert len(sched_runs) == 2
    # the scheduled closure beat the 2ms dense one
    assert first == [True]
    assert ch.use_scheduled(coef, n, run_sched, run_dense) is True


def test_native_sample_cap_keys_verdict_by_probed_size(rng, monkeypatch):
    """Requests past MEASURE_BYTES_MAX are decided from a byte-capped
    sample and the cached verdict is keyed by the SAMPLE's size — the
    chooser only ever records sizes it actually measured."""
    from seaweedfs_tpu.ops import codec_native

    try:
        codec = codec_native.NativeCodec()
    except Exception as e:
        pytest.skip(f"native codec unavailable: {e}")
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_SCHEDULE", "auto")
    coef = rs_matrix.parity_rows(10, 4)
    width = schedule.MEASURE_BYTES_MAX // 10 * 2  # 2x the sample cap
    data = rng.integers(0, 256, (10, width), dtype=np.uint8)
    got = codec.coded_matmul(coef, data)
    assert np.array_equal(np.asarray(got),
                          codec_numpy.coded_matmul(coef, data))
    keys = list(codec._chooser._won)
    assert len(keys) == 1
    sample_bytes = 10 * (schedule.MEASURE_BYTES_MAX // 10)
    assert keys[0][1] == schedule._bucket(sample_bytes)
    assert keys[0][1] != schedule._bucket(data.nbytes)


def test_probe_fingerprint_differs_per_code():
    from seaweedfs_tpu.ec import probe

    fp_rs = probe.code_fingerprint("")
    fp_lrc = probe.code_fingerprint(LRC)
    assert fp_rs["spec"] == "10.4" and fp_lrc["spec"] == LRC
    assert fp_rs["matrix_hash"] != fp_lrc["matrix_hash"]
    assert probe.cache_path(LRC) != probe.cache_path("")
    # the process-wide -ec.code default must NOT be in the host
    # fingerprint: repointing it would invalidate every cached curve
    assert "default_code" not in probe.host_fingerprint(LRC)


def test_code_table_and_snapshot_surface_codes():
    table = ecb.code_table()
    specs = {row["spec"] for row in table}
    assert {"10.4", LRC} <= specs
    snap = ecb.probe_snapshot()
    assert LRC in snap["code_buckets"]
    assert snap["default_code"] in ("", *ecb.KNOWN_CODES)
