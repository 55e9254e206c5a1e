"""Measured host<->device bandwidth curve for the EC feed router.

Round 5's auto-router decided from ONE synchronous 4MB device_put and
a derived guess (`bw / 1.4`). Both papers the roadmap cites
(arXiv:2108.02692, arXiv:1709.05365) say the same thing about erasure
coding: throughput is decided by data-movement scheduling, so the only
honest router input is the *measured end-to-end rate of the actual
pipelined feed* at the sizes production requests come in. This module
produces that: a size x depth sweep of the real streaming codec
(ops/codec_pallas pipeline — committed device_put upload thread, kernel,
drain thread), each row paired with a shaped transfer-only ceiling
twin (same bytes over the link, codec replaced by a trivial slice), so
a published device number always carries the link bound it ran under.

The sweep result is cached on disk (JSON) with a TTL and a host
fingerprint — serving processes on the same machine read the curve
instead of re-paying the probe; a different host, device, jax version
or probe schema invalidates it, as does corruption (any parse/shape
error -> fresh sweep, never a crash).

Interpolation: `e2e_mbps_at(curve, nbytes)` is piecewise-linear in
log2(size) over the best depth per measured size, clamped at both
ends — monotone between measured points by construction, so the
router can never invent a hump the sweep didn't see.
"""
from __future__ import annotations

import json
import os
import time as _time

import numpy as np

# probe schema version: bump when the sweep method or JSON layout
# changes so stale caches self-invalidate (3: per-code curves + code
# config in the fingerprint)
PROBE_VERSION = 3

SWEEP_SIZES = (1 << 20, 4 << 20, 16 << 20, 64 << 20)
SWEEP_DEPTHS = (1, 2, 4)
# RS(10,4): the codec the production feed runs
_K, _M = 10, 4

_CACHE_ENV = "SEAWEEDFS_TPU_EC_PROBE_CACHE"
_TTL_ENV = "SEAWEEDFS_TPU_EC_PROBE_TTL"
_BUDGET_ENV = "SEAWEEDFS_TPU_EC_PROBE_BUDGET"
DEFAULT_TTL_S = 24 * 3600.0
# wall budget for one full sweep: on a fast link the whole table costs
# well under this; on a slow link the budget is what keeps a serving
# process's first EC op from stalling for minutes — unaffordable rows
# are skipped and marked, and the curve clamps to the largest measured
DEFAULT_BUDGET_S = 45.0

# process cache of the active curves, keyed by code spec ("" = the
# default RS(10,4) production feed)
_curves: dict[str, dict] = {}


def cache_path(code: str = "") -> str:
    p = os.environ.get(_CACHE_ENV, "").strip()
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    if not p:
        p = os.path.join(base, "seaweedfs_tpu", "ec_probe.json")
    if not code:
        return p
    # per-code curve, sibling of the default cache: a mixed-code
    # cluster carries one measured curve per code family
    root, ext = os.path.splitext(p)
    return f"{root}-{code.replace('.', '_')}{ext or '.json'}"


def cache_ttl_s() -> float:
    try:
        return float(os.environ.get(_TTL_ENV, DEFAULT_TTL_S))
    except ValueError:
        return DEFAULT_TTL_S


def _device() -> tuple[str, str, int] | None:
    """(platform, kind, count) of the default jax device, or None when
    jax is absent or only CPU devices exist (no feed to probe)."""
    import importlib.util

    if importlib.util.find_spec("jax") is None:
        return None
    import jax

    try:
        dev = jax.devices()[0]
    except Exception:
        return None
    if dev.platform == "cpu":
        return None
    return (dev.platform, getattr(dev, "device_kind", "") or "",
            len(jax.devices()))


def process_device() -> dict | None:
    """The accelerator this process holds, for /debug/ec: None when it
    runs on the CPU. Only for a process whose JAX backend is already
    up (ops.device.backends_initialized()): asking for devices
    initialises one."""
    dev = _device()
    return ({"platform": dev[0], "kind": dev[1], "count": dev[2]}
            if dev else None)


def _visible_device_count() -> int | None:
    """Total visible jax devices on ANY platform (None when jax is
    absent). The accelerator-only `_device()` is not enough for the
    fingerprint: on a CPU-only host it returns None regardless of how
    many virtual devices are configured, so a curve swept with 1
    device would survive the host growing to 8 — and a mesh curve
    would keep routing after devices vanish."""
    import importlib.util

    if importlib.util.find_spec("jax") is None:
        return None
    import jax

    try:
        return len(jax.devices())
    except Exception:
        return None


def code_fingerprint(spec: str = "") -> dict:
    """The code-config part of the fingerprint: the canonical spec and
    a hash of its encode matrix. A curve swept for one coefficient
    matrix says nothing about another — if the matrix construction ever
    changes (or the operator repoints -ec.code), the hash changes and
    the cache self-invalidates."""
    import hashlib

    from ..ops import rs_matrix
    from . import geometry as geo

    code = geo.parse_code(spec or "")
    mat = rs_matrix.encode_matrix_for(code)
    return {"spec": code.spec,
            "matrix_hash": hashlib.sha256(mat.tobytes()).hexdigest()[:16]}


def host_fingerprint(code: str = "") -> dict:
    """What must match for a cached curve to be trusted: same machine,
    same visible device set behind the same jax, same mesh shape knobs,
    same swept-code config (spec + encode-matrix hash), same probe
    schema. The process-wide -ec.code DEFAULT is deliberately absent:
    the swept code is fully captured by code_fingerprint, and baking
    the default in would invalidate every cached curve — including the
    RS(10,4) one — on an unrelated config repoint, forcing full
    re-sweeps fleet-wide."""
    import platform as _plat

    fp = {"probe_version": PROBE_VERSION,
          "host": _plat.node(),
          "machine": _plat.machine()}
    try:
        fp["code"] = code_fingerprint(code)
    except Exception:  # pragma: no cover - fingerprint must not fatal
        fp["code"] = {"spec": code or "", "matrix_hash": None}
    dev = _device()
    fp["device"] = ({"platform": dev[0], "kind": dev[1], "count": dev[2]}
                    if dev else None)
    fp["device_count"] = _visible_device_count()
    try:
        from ..parallel import mesh as pmesh

        fp["mesh_config"] = list(pmesh.mesh_config())
    except Exception:
        fp["mesh_config"] = None
    try:
        import jax

        fp["jax"] = jax.__version__
    except Exception:
        fp["jax"] = None
    return fp


# ----------------------------------------------------------------------
# measurement
# ----------------------------------------------------------------------

def measure_cpu_mbps(backend, coef: np.ndarray | None = None,
                     k: int = _K) -> float:
    """Steady rate of the CPU-side codec on the encode shape (k x 1MB
    parity matmul, RS(10,4) by default), input bytes per second."""
    from ..ops import rs_matrix

    if coef is None:
        coef = rs_matrix.parity_rows(_K, _M)
    blk = np.random.default_rng(0).integers(
        0, 256, (k, 1 << 20), dtype=np.uint8)
    backend.coded_matmul(coef, blk)  # warm (native lib load, caches)
    t0 = _time.perf_counter()
    backend.coded_matmul(coef, blk)
    return blk.nbytes / (_time.perf_counter() - t0) / 1e6


def _measure_e2e_row(codec, coef, size: int, depth: int,
                     n_blocks: int, k: int = _K, m: int = _M) -> float:
    """Pipelined e2e MB/s at one (size, depth): n_blocks distinct
    (k, size/k) blocks through the staged streaming pipeline; rate is
    input bytes / wall from first pread to last yield. k/m default to
    the production RS(10,4) shape; the mesh rows and per-code sweeps
    pass their own."""
    w = max(1, size // k)
    rng = np.random.default_rng(size ^ depth)
    blocks = [rng.integers(0, 256, (k, w), dtype=np.uint8)
              for _ in range(n_blocks)]
    t0 = _time.perf_counter()
    got = 0
    for out in codec.coded_matmul_stream(coef, iter(blocks), depth=depth):
        got += 1
        assert out.shape == (m, w)
    assert got == n_blocks
    return n_blocks * k * w / (_time.perf_counter() - t0) / 1e6


_slice_rows: dict[int, object] = {}


def _get_slice_rows(m: int = _M):
    """Jitted (k, w) -> (m, w) row slice, one per output-row count:
    one jit cache shared by every ceiling row of that code, so shapes
    compiled during the per-size warm pass stay compiled for the timed
    rows."""
    fn = _slice_rows.get(m)
    if fn is None:
        import jax

        fn = _slice_rows[m] = jax.jit(lambda x: x[:m])
    return fn


def _measure_xfer_ceiling(codec, size: int, depth: int,
                          n_blocks: int, k: int = _K,
                          m: int = _M) -> float:
    """Shaped transfer-only twin of the row above: the same (k, w)
    uint8 blocks cross H2D and an (m, w) slice crosses D2H through the
    same committed placement and the same depth-bounded overlap, but
    the kernel is a free row slice — what the link alone supports for
    this traffic shape."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    slice_rows = _get_slice_rows(m)
    w = max(1, size // k)
    rng = np.random.default_rng(size * 31 + depth)
    blocks = [rng.integers(0, 256, (k, w), dtype=np.uint8)
              for _ in range(n_blocks)]
    depth = max(1, depth)
    t0 = _time.perf_counter()
    with ThreadPoolExecutor(1) as up_ex, ThreadPoolExecutor(1) as down_ex:
        pending: deque = deque()

        def up(b):
            dev = codec._h2d(b)
            dev.block_until_ready()
            return slice_rows(dev)

        def down(fut):
            return np.asarray(fut.result())

        for b in blocks:
            pending.append(down_ex.submit(down, up_ex.submit(up, b)))
            while len(pending) >= depth:
                pending.popleft().result()
        while pending:
            pending.popleft().result()
    return n_blocks * k * w / (_time.perf_counter() - t0) / 1e6


def run_sweep(sizes=SWEEP_SIZES, depths=SWEEP_DEPTHS,
              budget_s: float | None = None,
              with_ceilings: bool = True, code: str = "") -> dict:
    """Measure the curve for one code family (default: the RS(10,4)
    production feed). Always includes the CPU codec rate; device rows
    only when a non-CPU device exists. Never raises: a failed row is
    recorded with its error and the sweep moves on."""
    from ..ops import rs_matrix
    from . import backend as ecb
    from . import geometry as geo

    cfg = geo.parse_code(code or "")
    # the encode's shape: (m, k) parity rows; a sub-striped code's
    # coupled encode takes (2m, 2k)
    coef = rs_matrix.parity_rows_for(cfg)
    m, k = coef.shape
    if budget_s is None:
        try:
            budget_s = float(os.environ.get(_BUDGET_ENV,
                                            DEFAULT_BUDGET_S))
        except ValueError:
            budget_s = DEFAULT_BUDGET_S
    t_start = _time.perf_counter()
    curve: dict = {"fingerprint": host_fingerprint(code),
                   "measured_at": _time.time(),
                   "budget_s": budget_s,
                   "code": cfg.spec,
                   "rows": []}
    cpu_name = ecb.cpu_backend_name()
    curve["cpu_backend"] = cpu_name
    try:
        curve["cpu_mbps"] = round(
            measure_cpu_mbps(ecb.get_backend(cpu_name), coef, k), 1)
    except Exception as e:  # pragma: no cover - probe must never fatal
        curve["cpu_mbps"] = None
        curve["cpu_error"] = repr(e)

    dev = _device()
    curve["device"] = ({"platform": dev[0], "kind": dev[1],
                        "count": dev[2]} if dev else None)
    if dev is None:
        return curve

    try:
        codec = ecb.get_backend("pallas")
    except KeyError as e:
        return _device_failed(curve, repr(e))
    curve["device_backend"] = "pallas"

    try:
        # spin up the path (first device_put, executor machinery)
        # outside every timed row; per-size XLA compiles get their own
        # warm pass below so no (size, depth) row is billed a compile
        _measure_e2e_row(codec, coef, 1 << 18, 1, n_blocks=2, k=k, m=m)
    except Exception as e:
        return _device_failed(curve, repr(e))

    last_rate: float | None = None

    def remaining() -> float:
        return budget_s - (_time.perf_counter() - t_start)

    def affordable(nbytes: int) -> bool:
        # projection from the last measured rate; before any rate is
        # known, only a positive budget is required (the smallest size
        # is the probe's own floor)
        if last_rate:
            return nbytes / 1e6 / last_rate <= remaining()
        return remaining() > 0

    for size in sorted(sizes):
        # one warm block at this exact width compiles the padded-shape
        # kernels (codec + ceiling slice) so depth=1 isn't billed for
        # XLA compile while depth=4 rides its cache
        if not affordable(2 * size):
            for depth in depths:
                curve["rows"].append({"size": int(size),
                                      "depth": int(depth),
                                      "skipped": "budget"})
            continue
        try:
            _measure_e2e_row(codec, coef, size, 1, n_blocks=1, k=k, m=m)
            if with_ceilings:
                _measure_xfer_ceiling(codec, size, 1, n_blocks=1,
                                      k=k, m=m)
        except Exception as e:  # pragma: no cover - keep sweeping
            for depth in depths:
                curve["rows"].append({"size": int(size),
                                      "depth": int(depth),
                                      "error": repr(e)})
            continue
        for depth in depths:
            n_blocks = depth + 2
            row = {"size": int(size), "depth": int(depth),
                   "blocks": n_blocks}
            cost = n_blocks * size * (2 if with_ceilings else 1)
            if not affordable(cost):
                # a row that would blow the remaining budget is skipped
                # and marked — the table says so instead of silently
                # truncating
                row["skipped"] = "budget"
                curve["rows"].append(row)
                continue
            try:
                rate = _measure_e2e_row(codec, coef, size, depth,
                                        n_blocks, k=k, m=m)
                row["e2e_mbps"] = round(rate, 2)
                last_rate = rate
                if with_ceilings:
                    ceil = _measure_xfer_ceiling(codec, size, depth,
                                                 n_blocks, k=k, m=m)
                    row["xfer_ceiling_mbps"] = round(ceil, 2)
                    if ceil > 0:
                        row["vs_ceiling"] = round(rate / ceil, 2)
            except Exception as e:  # pragma: no cover - keep sweeping
                row["error"] = repr(e)
            curve["rows"].append(row)

    # mesh rows: the same protocol against the sharded codec when more
    # than one device is visible — the mesh's scatter/gather overhead
    # is real, so its curve is measured, never derived from the
    # single-chip rows times N
    if dev[2] > 1:
        last_rate = _sweep_mesh_rows(curve, sizes, depths, remaining,
                                     last_rate, coef=coef, k=k, m=m)
    curve["sweep_seconds"] = round(_time.perf_counter() - t_start, 2)
    errors = [r["error"] for key in ("rows", "mesh_rows")
              for r in curve.get(key, []) if "error" in r]
    if curve.get("mesh_error"):
        errors.append(curve["mesh_error"])
    if errors:
        return _device_failed(curve, errors[0])
    return curve


def _device_failed(curve: dict, err: str) -> dict:
    """Record a device that errored during the sweep: `device_error`
    in the curve (and so in /debug/ec), a warning log and
    ec_device_errors_total{stage="probe"}. The router still routes on
    whatever rows did measure, but the failure stays an error on
    record, not a routing decision."""
    from . import backend as ecb

    curve["device_error"] = err
    ecb.record_device_error("probe", err)
    return curve


def _sweep_mesh_rows(curve: dict, sizes, depths, remaining,
                     last_rate: float | None,
                     coef: np.ndarray | None = None, k: int = _K,
                     m: int = _M) -> float | None:
    """size x depth rows for the mesh codec, appended to
    curve["mesh_rows"] with the mesh geometry in curve["mesh"]; shares
    the sweep's wall budget (`remaining`) so a slow link can't make the
    probe cost 2x its cap."""
    from ..ops import rs_matrix
    from . import backend as ecb

    try:
        codec = ecb.get_backend("mesh")
    except KeyError as e:
        curve["mesh_error"] = repr(e)
        return last_rate
    curve["mesh"] = codec.describe()
    if coef is None:
        coef = rs_matrix.parity_rows(_K, _M)

    def affordable(nbytes: int) -> bool:
        if last_rate:
            return nbytes / 1e6 / last_rate <= remaining()
        return remaining() > 0

    try:
        _measure_e2e_row(codec, coef, 1 << 18, 1, n_blocks=2, k=k, m=m)
    except Exception as e:  # pragma: no cover - probe must never fatal
        curve["mesh_error"] = repr(e)
        return last_rate

    rows = curve.setdefault("mesh_rows", [])
    for size in sorted(sizes):
        if not affordable(2 * size):
            for depth in depths:
                rows.append({"size": int(size), "depth": int(depth),
                             "skipped": "budget"})
            continue
        try:
            _measure_e2e_row(codec, coef, size, 1, n_blocks=1, k=k, m=m)
        except Exception as e:  # pragma: no cover - keep sweeping
            for depth in depths:
                rows.append({"size": int(size), "depth": int(depth),
                             "error": repr(e)})
            continue
        for depth in depths:
            n_blocks = depth + 2
            row = {"size": int(size), "depth": int(depth),
                   "blocks": n_blocks}
            if not affordable(n_blocks * size):
                row["skipped"] = "budget"
                rows.append(row)
                continue
            try:
                rate = _measure_e2e_row(codec, coef, size, depth,
                                        n_blocks, k=k, m=m)
                row["e2e_mbps"] = round(rate, 2)
                last_rate = rate
            except Exception as e:  # pragma: no cover - keep sweeping
                row["error"] = repr(e)
            rows.append(row)
    return last_rate


# ----------------------------------------------------------------------
# disk cache
# ----------------------------------------------------------------------

def load_cached(path: str | None = None,
                ttl_s: float | None = None,
                code: str = "") -> dict | None:
    """The cached curve if present, parseable, same-host, same-code
    and fresh — else None. Corruption and expiry both land here as
    None: the caller re-sweeps, it never crashes."""
    path = path or cache_path(code)
    ttl_s = cache_ttl_s() if ttl_s is None else ttl_s
    try:
        with open(path, encoding="utf-8") as f:
            curve = json.load(f)
        if not isinstance(curve, dict):
            return None
        if not isinstance(curve.get("rows"), list):
            return None
        if curve.get("fingerprint") != host_fingerprint(code):
            return None
        age = _time.time() - float(curve.get("measured_at", 0))
        if age < 0 or age > ttl_s:
            return None
        return curve
    except Exception:
        return None


def save_cache(curve: dict, path: str | None = None) -> None:
    """Best-effort atomic write (rename) so a crashed writer leaves
    the old cache intact, not a half-written JSON."""
    path = path or cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(curve, f, indent=1)
        os.replace(tmp, path)
    except Exception:  # pragma: no cover - cache is an optimization
        pass


def get_curve(refresh: bool = False, code: str = "") -> dict:
    """The active curve for one code family: process memo -> disk
    cache -> fresh sweep (persisted only when a device was actually
    measured — a CPU-only probe is cheap enough to redo and says
    nothing about the link)."""
    memo = _curves.get(code)
    if memo is not None and not refresh:
        return memo
    curve = None if refresh else load_cached(code=code)
    if curve is None:
        curve = run_sweep(code=code)
        # a device that errored is re-probed by the next process, not
        # remembered for a TTL as if the CPU had won
        if curve.get("device") is not None and \
                not curve.get("device_error"):
            save_cache(curve, cache_path(code))
        curve["source"] = "fresh"
    else:
        curve["source"] = "cache"
    _curves[code] = curve
    return curve


def peek(code: str = "", touch: bool = True) -> dict | None:
    """The curve if this process already has one (memo or a valid disk
    cache) — never sweeps. Debug surfaces use this so a GET can't
    stall behind the probe budget; they pass touch=False, and then a
    process without a JAX backend does not read the disk cache, whose
    fingerprint check would initialise one."""
    from ..ops import device

    memo = _curves.get(code)
    if memo is not None:
        return memo
    if not touch and not device.backends_initialized():
        return None
    curve = load_cached(code=code)
    if curve is not None:
        curve["source"] = "cache"
        _curves[code] = curve
    return curve


def invalidate() -> None:
    """Drop the process memo, all codes (tests; ops can also just
    delete the cache files and restart)."""
    _curves.clear()


# ----------------------------------------------------------------------
# curve reading
# ----------------------------------------------------------------------

def measured_rows(curve: dict, key: str = "rows") -> list[dict]:
    return [r for r in curve.get(key, [])
            if isinstance(r.get("e2e_mbps"), (int, float))]


def best_by_size(curve: dict,
                 key: str = "rows") -> list[tuple[int, float, int]]:
    """[(size, best_e2e_mbps, best_depth)] ascending by size."""
    best: dict[int, tuple[float, int]] = {}
    for r in measured_rows(curve, key):
        size, rate, depth = int(r["size"]), float(r["e2e_mbps"]), \
            int(r["depth"])
        if size not in best or rate > best[size][0]:
            best[size] = (rate, depth)
    return [(s, best[s][0], best[s][1]) for s in sorted(best)]


def _interp_at(pts: list[tuple[int, float, int]],
               nbytes: int) -> float | None:
    if not pts:
        return None
    nbytes = max(1, int(nbytes))
    if len(pts) == 1 or nbytes <= pts[0][0]:
        return pts[0][1]
    if nbytes >= pts[-1][0]:
        return pts[-1][1]
    xs = np.log2([p[0] for p in pts])
    ys = [p[1] for p in pts]
    return float(np.interp(np.log2(nbytes), xs, ys))


def e2e_mbps_at(curve: dict, nbytes: int) -> float | None:
    """Device e2e MB/s the measured curve predicts for a request of
    `nbytes`: piecewise-linear in log2(size) over the best depth per
    measured size, clamped to the measured range (no extrapolated
    optimism past the largest row that actually ran)."""
    return _interp_at(best_by_size(curve), nbytes)


def mesh_mbps_at(curve: dict, nbytes: int) -> float | None:
    """Mesh-codec e2e MB/s at `nbytes` — same interpolation over the
    mesh rows; None when no mesh was swept (single-device host)."""
    return _interp_at(best_by_size(curve, "mesh_rows"), nbytes)


def _nearest_depth(pts: list[tuple[int, float, int]],
                   nbytes: int) -> int:
    if not pts:
        return 2
    nbytes = max(1, int(nbytes))
    target = np.log2(nbytes)
    best = min(pts, key=lambda p: abs(np.log2(p[0]) - target))
    return best[2]


def depth_at(curve: dict, nbytes: int) -> int:
    """Pipeline depth of the nearest measured size (default 2 when the
    curve is empty): what the feed should run for this request size."""
    return _nearest_depth(best_by_size(curve), nbytes)


def mesh_depth_at(curve: dict, nbytes: int) -> int:
    """Pipeline depth the mesh rows recommend at `nbytes` (2 when no
    mesh row was measured)."""
    return _nearest_depth(best_by_size(curve, "mesh_rows"), nbytes)


def summary(curve: dict) -> dict:
    """Compact view for logs and /debug/ec: per-size best rates plus
    the CPU rate the router compares against."""
    out = {
        "cpu_backend": curve.get("cpu_backend"),
        "cpu_mbps": curve.get("cpu_mbps"),
        "device": curve.get("device"),
        "device_backend": curve.get("device_backend"),
        "best_by_size_mb": {
            str(s >> 20): {"e2e_mbps": round(r, 2), "depth": d}
            for s, r, d in best_by_size(curve)},
        "skipped_rows": sum(1 for r in curve.get("rows", [])
                            if r.get("skipped")),
        "device_error": curve.get("device_error"),
        "measured_at": curve.get("measured_at"),
        "source": curve.get("source"),
    }
    if curve.get("mesh") is not None:
        out["mesh"] = curve["mesh"]
        out["mesh_best_by_size_mb"] = {
            str(s >> 20): {"e2e_mbps": round(r, 2), "depth": d}
            for s, r, d in best_by_size(curve, "mesh_rows")}
    if curve.get("mesh_error"):
        out["mesh_error"] = curve["mesh_error"]
    return out
