"""Codec backend registry for the erasure-coding subsystem.

Mirrors the reference's storage-backend plugin pattern — a factory registry
keyed by a type string (/root/reference/weed/storage/backend/backend.go:
25-45 `BackendStorageFactory` / `BackendStorages`) — applied to the RS
codec, selected via config `ec.backend=numpy|native|pallas|mesh` (the
north-star `-ec.backend=tpu` switch from BASELINE.json).

A backend implements one method:

    coded_matmul(coef: (m,k) uint8, shards: (k,n) uint8) -> (m,n) uint8

computing out[i] = XOR_j coef[i,j]*shards[j] over GF(256). Everything else
(encode, reconstruct, verify) is built on top here, using the systematic
matrices from ops.rs_matrix.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time as _time
from typing import Callable, Iterator, Protocol

import numpy as np

from ..ops import rs_matrix
from ..utils import metrics
from . import geometry as geo


def _codec_label(backend) -> str:
    """Metrics label for a backend; AutoCodec reports what it resolved
    to (or "auto" before first use)."""
    name = getattr(backend, "name", "") or "unknown"
    if name == "auto":
        name = getattr(backend, "chosen", None) or "auto"
    return name


def observe_codec(op: str, backend, seconds: float | None = None,
                  nbytes: int = 0, code: str = "") -> None:
    """Record one codec operation into ec_codec_seconds{op,backend}
    / ec_codec_bytes_total (bytes = input data processed). Either part
    may be skipped (seconds=None / nbytes=0) so streaming paths can
    count bytes at consumption and time at yield without double
    observations. When the caller knows its code family, bytes are
    additionally counted per code (Grafana's "Codes" row charts
    encode/repair throughput by code without exploding the base
    series)."""
    lab = {"op": op, "backend": backend if isinstance(backend, str)
           else _codec_label(backend)}
    if seconds is not None:
        metrics.histogram_observe("ec_codec_seconds", seconds, lab)
    if nbytes:
        metrics.counter_add("ec_codec_bytes_total", nbytes, lab)
        if code:
            lab2 = {"op": op, "backend": lab["backend"], "code": code}
            metrics.counter_add("ec_codec_bytes_by_code_total", nbytes,
                                lab2)


class CodecBackend(Protocol):
    name: str

    def coded_matmul(self, coef: np.ndarray, shards: np.ndarray) -> np.ndarray:
        ...


# the codecs that run on the accelerator (and so need the process
# that owns it)
DEVICE_BACKENDS = ("pallas", "mesh")

_factories: dict[str, Callable[[], CodecBackend]] = {}
_instances: dict[str, CodecBackend] = {}


def register(name: str, factory: Callable[[], CodecBackend]) -> None:
    _factories[name] = factory


def backend_names() -> list[str]:
    return sorted(_factories)


def get_backend(name: str = "numpy") -> CodecBackend:
    inst = _instances.get(name)
    if inst is None:
        try:
            factory = _factories[name]
        except KeyError:
            raise KeyError(
                f"unknown codec backend {name!r}; known: {backend_names()}"
            ) from None
        try:
            inst = factory()
        except ImportError as e:
            raise KeyError(
                f"codec backend {name!r} is registered but unavailable "
                f"in this environment: {e}") from e
        _instances[name] = inst
    return inst


def available_backend_names() -> list[str]:
    """Backends usable in this environment — probed cheaply (module
    lookup), without constructing instances or importing jax."""
    import importlib.util

    deps = {"numpy": "numpy", "mesh": "jax",
            "pallas": "seaweedfs_tpu.ops.codec_pallas",
            "native": "seaweedfs_tpu.ops.codec_native"}
    out = []
    for name in backend_names():
        dep = deps.get(name)
        if dep is None or importlib.util.find_spec(dep) is not None:
            out.append(name)
    return out


def _register_builtins() -> None:
    from ..ops import codec_numpy

    register("numpy", codec_numpy.NumpyCodec)

    def _native_factory():
        from ..ops import codec_native

        return codec_native.NativeCodec()

    register("native", _native_factory)

    def _pallas_factory():
        from ..ops import codec_pallas, device

        device.require_accelerator("pallas")
        return codec_pallas.PallasCodec()

    register("pallas", _pallas_factory)

    def _mesh_factory():
        from ..ops import codec_mesh, device

        device.require_accelerator("mesh")
        return codec_mesh.MeshCodec()

    register("mesh", _mesh_factory)
    register("auto", AutoCodec)


_AUTO_ENV = "SEAWEEDFS_TPU_EC_BACKEND"
_auto_choice: str | None = None
_auto_probe: dict | None = None

# ----------------------------------------------------------------------
# code families: registered specs selectable via -ec.code
# ----------------------------------------------------------------------

_CODE_ENV = "SEAWEEDFS_TPU_EC_CODE"

# the blessed code specs: the RS default, the wide cold-tier RS, and
# the LRC configs (local XOR groups cut single-loss repair fan-in from
# k to the group size at a small storage premium, arXiv 1309.0186).
# Any well-formed spec works with -ec.code; these are the documented,
# probed and benched ones, with Hitchhiker-XOR on RS(10,4) (a
# single data-shard repair reads 13-14 half shards, not 10 whole).
KNOWN_CODES = ("10.4", "lrc-10.2.2", "lrc-12.3.2", "28.4", "hh-10.4")


def default_code_spec() -> str:
    """The `-ec.code` process default (env SEAWEEDFS_TPU_EC_CODE):
    what ec.encode uses when no explicit codec is passed. '' = the
    classic RS(10,4)."""
    spec = os.environ.get(_CODE_ENV, "").strip()
    if not spec:
        return ""
    try:
        geo.parse_code(spec)
        return spec
    except (ValueError, TypeError) as e:
        try:
            from ..utils import glog

            glog.warning("ignoring %s=%r: %s", _CODE_ENV, spec, e)
        except Exception:  # pragma: no cover
            pass
        return ""


def get_code(spec: str = "") -> geo.CodeConfig:
    """Spec string (as recorded in a volume .vif) -> CodeConfig."""
    return geo.parse_code(spec or "")


def code_table() -> list[dict]:
    """The registry view for /debug/ec, README and the shell: each
    known code's structure, storage overhead and repair fan-in. Every
    backend serves every code (the coefficient matrix is a runtime
    argument in all of them)."""
    out = []
    for spec in KNOWN_CODES:
        row = get_code(spec).describe()
        row["backends"] = backend_names()
        row["default"] = spec == (default_code_spec() or "10.4")
        out.append(row)
    return out


def _probe_cpu_backend() -> str:
    """Fastest CPU-side codec present: the C++ AVX2 library when it is
    built, else the numpy table-gather codec."""
    try:
        get_backend("native")
        return "native"
    except KeyError:
        return "numpy"


def cpu_backend_name() -> str:
    """Public alias of the CPU-codec probe: the backend latency-
    sensitive paths (single-needle degraded reads) must use no matter
    what -ec.backend configured — a device dispatch (compile + DMA)
    can put >1s in a GET that reconstructs a few KB."""
    return _probe_cpu_backend()


# the request size the process-wide choice represents: bulk encodes
# stream in multi-MB blocks, so "which backend for big work" is "which
# backend at the top of the measured curve"
_ROUTER_BULK_BYTES = 64 << 20


def _env_override() -> str | None:
    """SEAWEEDFS_TPU_EC_BACKEND, validated; None when unset/auto."""
    env = os.environ.get(_AUTO_ENV, "").strip()
    if not env or env == "auto":
        return None
    # validate at selection time, not deep inside the first EC op
    try:
        get_backend(env)
        return env
    except KeyError as e:
        try:
            from ..utils import glog

            glog.warning("ignoring %s=%r: %s", _AUTO_ENV, env, e)
        except Exception:  # pragma: no cover
            pass
        return None


def _decide(curve: dict, nbytes: int) -> str:
    """Router core: the measured e2e rates interpolated at this
    request size versus the measured CPU-codec rate — a device
    backend (single-chip or mesh) is only ever chosen when its
    *measured end-to-end* feed beats the CPU, never from a derived
    estimate. Three-way since the mesh codec landed: the mesh rows of
    the same sweep compete against the single-chip rows, so small
    requests that can't amortize the scatter stay single-chip while
    bulk streams ride all devices."""
    from . import probe

    cpu_name = curve.get("cpu_backend") or _probe_cpu_backend()
    cpu_rate = curve.get("cpu_mbps")
    candidates = []
    dev_rate = probe.e2e_mbps_at(curve, nbytes)
    dev_name = curve.get("device_backend")
    if dev_rate is not None and dev_name:
        candidates.append((dev_rate, dev_name))
    mesh_rate = probe.mesh_mbps_at(curve, nbytes)
    if mesh_rate is not None:
        candidates.append((mesh_rate, "mesh"))
    for rate, name in sorted(candidates, reverse=True):
        if cpu_rate is not None and rate <= cpu_rate:
            continue
        try:
            get_backend(name)
            return name
        except KeyError:
            continue
    return cpu_name


def _curve_code(code: str) -> str:
    """Probe-curve key for a code spec: the default RS(10,4) rides the
    primary curve ('') every existing caller already pays for; any
    other code gets its own measured curve."""
    return "" if code in ("", "10.4") else code


def choose_backend_for_size(nbytes: int, code: str = "") -> str:
    """Backend for a request of `nbytes` under code `code`, from the
    measured size x depth curve (ec/probe.py): interpolate the device
    e2e rate at this size, compare to the measured CPU rate, pick the
    winner. Per-code curves keep the router honest — an LRC's wider
    local rows move the crossover point, so its decision comes from a
    sweep of ITS coefficient matrix, never the RS one. Override with
    env SEAWEEDFS_TPU_EC_BACKEND. First use pays the sweep (or reads
    the disk cache); after that it is a dict lookup."""
    env = _env_override()
    if env is not None:
        return env
    from . import probe

    return _decide(probe.get_curve(code=_curve_code(code)), nbytes)


def pipeline_depth_for(nbytes: int, code: str = "") -> int:
    """Streaming-pipeline depth the measured curve recommends for
    blocks of `nbytes` (2 when nothing is measured — the classic
    double buffer). When the router would send this size to the mesh,
    the depth comes from the mesh rows — the scatter across N devices
    has its own overlap sweet spot."""
    from . import probe

    curve = probe.peek(code=_curve_code(code))
    if curve is None:
        return 2
    env = _env_override()
    choice = env if env is not None else _decide(curve, nbytes)
    if choice == "mesh":
        return probe.mesh_depth_at(curve, nbytes)
    return probe.depth_at(curve, nbytes)


def choose_auto_backend() -> str:
    """Process-wide codec choice for bulk work, from measurement, not
    faith: the size x depth sweep of the real pipelined feed
    (ec/probe.py) interpolated at the bulk request size. However fast
    the MXU, the device only wins where its host<->device feed
    beats the AVX2 library end to end, and only the measured e2e
    curve can tell. Override with env SEAWEEDFS_TPU_EC_BACKEND.

    A probe that raises is a device error, not a routing decision: the
    process falls back to the CPU codec, but the error is logged at
    warning level, counted in ec_device_errors_total and kept in the
    /debug/ec summary under `device_error`.

    The decision is cached per process; the sweep result is cached on
    disk (TTL + host fingerprint), so across serving processes the
    probe is paid once per host per TTL window.
    """
    global _auto_choice, _auto_probe
    env = _env_override()
    if env is not None:
        metrics.gauge_set("ec_codec_chosen_backend", 1,
                          {"backend": env})
        return env
    if _auto_choice is not None:
        return _auto_choice
    from . import probe

    try:
        curve = probe.get_curve()
        choice = _decide(curve, _ROUTER_BULK_BYTES)
        summary = probe.summary(curve)
    except Exception as e:  # the probe must never take the server down
        choice = _probe_cpu_backend()
        summary = {"device_error": repr(e)}
        record_device_error("router", e)
    _auto_choice = choice
    summary["chosen"] = choice
    _auto_probe = summary
    metrics.gauge_set("ec_codec_chosen_backend", 1, {"backend": choice})
    try:
        from ..utils import glog

        glog.info("ec auto backend: %s", summary)
    except Exception:  # pragma: no cover
        pass
    return choice


def record_device_error(stage: str, err) -> None:
    """A device that errored: warning log plus
    ec_device_errors_total{stage}. Every path that falls back to the
    CPU because the device failed (rather than because the measured
    curve said so) goes through here."""
    metrics.counter_add("ec_device_errors_total", 1, {"stage": stage})
    from ..utils import glog

    glog.warning("ec device error (%s): %s", stage, err)


def router_buckets(curve: dict) -> list[dict]:
    """Per-size-bucket routing table (one row per swept size): what
    the router would pick for a request of that size and the measured
    rates behind the decision — the operator-facing 'why native (or
    device)' answer."""
    from . import probe

    env = _env_override()
    out = []
    for size in probe.SWEEP_SIZES:
        dev_rate = probe.e2e_mbps_at(curve, size)
        mesh_rate = probe.mesh_mbps_at(curve, size)
        backend = env if env is not None else _decide(curve, size)
        depth = (probe.mesh_depth_at(curve, size) if backend == "mesh"
                 else probe.depth_at(curve, size))
        out.append({
            "size_mb": size >> 20,
            "backend": backend,
            "pinned_by_env": env is not None,
            "device_e2e_mbps": (round(dev_rate, 2)
                                if dev_rate is not None else None),
            "mesh_e2e_mbps": (round(mesh_rate, 2)
                              if mesh_rate is not None else None),
            "cpu_mbps": curve.get("cpu_mbps"),
            "depth": depth,
        })
    return out


def mesh_geometry() -> dict | None:
    """Mesh codec geometry for /debug/ec and /cluster/status: the live
    instance's shape when one exists (never constructs one — a debug
    GET must not pay device init), else the configured knobs."""
    inst = _instances.get("mesh")
    if inst is not None and hasattr(inst, "describe"):
        geo = dict(inst.describe())
        geo["state"] = "active"
        return geo
    try:
        from ..parallel import mesh as pmesh

        n_devices, col = pmesh.mesh_config()
    except Exception:  # jax absent: no mesh to describe
        return None
    return {"state": "unbuilt", "devices": n_devices, "col": col}


def probe_snapshot() -> dict:
    """Router state for /debug/ec and /cluster/status: the measured
    curve, where it came from (process sweep vs disk cache), how stale
    it is, and the per-size-bucket decision. Never triggers a sweep —
    an unprobed process says so instead of stalling the debug handler
    for the probe's budget — and never initialises a JAX backend: a
    process that holds no device (master, filer, gateways) reports
    "no device in this process" instead of taking the chip from the
    volume server."""
    import time as _t

    from ..ops import device
    from . import probe

    touch = device.backends_initialized()
    snap: dict = {
        "device": (probe.process_device() if touch
                   else "no device in this process"),
        "env_override": os.environ.get(_AUTO_ENV, "").strip() or None,
        "process_choice": _auto_choice,
        "cpu_backend": _probe_cpu_backend(),
        "cache_path": probe.cache_path(),
        "cache_ttl_s": probe.cache_ttl_s(),
        "mesh": mesh_geometry(),
        "default_code": default_code_spec() or "10.4",
        "codes": code_table(),
    }
    if _auto_probe and _auto_probe.get("device_error"):
        snap["device_error"] = _auto_probe["device_error"]
    # per-code router state: each known code's measured curve (when
    # one exists — peek never sweeps) and the bucket choices it yields
    per_code: dict[str, dict] = {}
    for spec in KNOWN_CODES:
        ckey = _curve_code(spec)
        ccurve = probe.peek(code=ckey, touch=touch)
        if ccurve is None:
            per_code[spec] = {"state": "unprobed"}
        else:
            per_code[spec] = {"state": "measured",
                              "buckets": router_buckets(ccurve)}
    snap["code_buckets"] = per_code
    curve = probe.peek(touch=touch)
    if curve is None:
        snap["probe"] = {"state": "unprobed"}
        return snap
    measured_at = float(curve.get("measured_at") or 0)
    snap["probe"] = {
        "state": "measured",
        "source": curve.get("source"),
        "age_s": round(max(0.0, _t.time() - measured_at), 1),
        "summary": probe.summary(curve),
        "rows": curve.get("rows", []),
    }
    if curve.get("device_error"):
        snap["device_error"] = curve["device_error"]
    snap["buckets"] = router_buckets(curve)
    return snap


async def handle_debug_ec(request):
    """GET /debug/ec — shared route handler for all servers: the
    router's measured curve, cache age and per-bucket decision."""
    from aiohttp import web

    return web.json_response(probe_snapshot())


class AutoCodec:
    """`-ec.backend=auto`: routes each op to the measured-fastest
    backend for its size — the per-request interpolation of the probe
    curve (choose_backend_for_size). Lazy so that constructing a Store
    never pays the probe unless an EC op actually runs. Callers that
    must keep a whole multi-dispatch operation on ONE backend (the
    file encode/rebuild paths) pin it first via resolve_for(total
    request bytes)."""

    name = "auto"

    def __init__(self, code_spec: str = ""):
        self._impl: CodecBackend | None = None
        self._pinned = False
        # the code family this instance routes for: per-code measured
        # curves can move the CPU/device crossover point
        self.code_spec = code_spec

    @property
    def chosen(self) -> str | None:
        return getattr(self._impl, "name", None)

    def _resolve(self) -> CodecBackend:
        """Process-wide (bulk-size) choice, pinned."""
        if not self._pinned:
            if _curve_code(self.code_spec):
                self._impl = get_backend(choose_backend_for_size(
                    _ROUTER_BULK_BYTES, self.code_spec))
            else:
                self._impl = get_backend(choose_auto_backend())
            self._pinned = True
        return self._impl

    def resolve_for(self, nbytes: int) -> CodecBackend:
        """Pin the backend the measured curve picks for a request of
        `nbytes` — the whole operation then rides one backend even as
        it streams through many dispatches."""
        self._impl = get_backend(choose_backend_for_size(
            nbytes, self.code_spec))
        self._pinned = True
        return self._impl

    def _backend_for(self, nbytes: int) -> CodecBackend:
        if self._pinned:
            return self._impl
        self._impl = get_backend(choose_backend_for_size(
            nbytes, self.code_spec))
        return self._impl

    def coded_matmul(self, coef: np.ndarray, shards) -> np.ndarray:
        shards = np.asarray(shards, dtype=np.uint8)
        return self._backend_for(shards.nbytes).coded_matmul(coef,
                                                             shards)

    def coded_matmul_stream(self, coef: np.ndarray, blocks,
                            depth: int = 2):
        # streams are bulk by construction: route like a large request
        impl = (self._impl if self._pinned
                else self._backend_for(_ROUTER_BULK_BYTES))
        stream = getattr(impl, "coded_matmul_stream", None)
        if stream is not None:
            yield from stream(coef, blocks, depth=depth)
        else:
            for block in blocks:
                yield impl.coded_matmul(coef, block)


_register_builtins()


# host staging buffers for reconstruct's input stack, kept across jobs:
# an allocation over glibc's mmap threshold is a fresh mapping whose
# pages fault in on first touch, so a stack that reuses one skips them
STAGING_KEEP = 2
_staging_lock = threading.Lock()
_staging_free: list[np.ndarray] = []


@contextlib.contextmanager
def staging_buffer(nbytes: int) -> Iterator[np.ndarray]:
    """A writable 1-D uint8 buffer of at least `nbytes`, the caller's
    alone until the block ends: the smallest free one that fits, else
    a new one. On exit it goes back to the free list, which keeps the
    STAGING_KEEP largest. Counted in ec_staging_buffers_total{outcome=
    reused|allocated}."""
    with _staging_lock:  # the free list is kept smallest first
        i = next((i for i, b in enumerate(_staging_free)
                  if b.nbytes >= nbytes), None)
        buf = None if i is None else _staging_free.pop(i)
    metrics.counter_add("ec_staging_buffers_total", 1,
                        {"outcome": "allocated" if buf is None
                         else "reused"})
    if buf is None:
        buf = np.empty(nbytes, dtype=np.uint8)
    try:
        yield buf
    finally:
        with _staging_lock:
            _staging_free.append(buf)
            _staging_free.sort(key=lambda b: b.nbytes)
            del _staging_free[:-STAGING_KEEP]


class ReedSolomon:
    """RS(k, m) erasure codec over a pluggable coded-matmul backend.

    API shape follows the reference's codec dependency (Encode /
    Reconstruct / Verify, /root/reference/weed/storage/erasure_coding/
    ec_encoder.go:190,274, store_ec.go:384) but operates on (shards, n)
    numpy arrays so callers can batch arbitrarily many stripes per call.
    """

    def __init__(self, data_shards: int, parity_shards: int,
                 backend: str | CodecBackend = "numpy",
                 code: "geo.CodeConfig | str | None" = None):
        if code is not None:
            if isinstance(code, str):
                code = geo.parse_code(code)
            data_shards, parity_shards = code.k, code.m
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("data_shards and parity_shards must be > 0")
        if data_shards + parity_shards > 256:
            raise ValueError("data+parity shards must be <= 256")
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        # the structural code config: RS unless an LRC (or other
        # structured) spec was passed — repair planning and parity
        # construction consult it instead of assuming k-of-n
        self.code = code if code is not None \
            else geo.CodeConfig(geo.codec_name(data_shards,
                                               parity_shards),
                                "rs", data_shards, 0, parity_shards)
        if backend == "auto" and _curve_code(self.code.spec):
            # a non-default code routes on its own measured curve, so
            # it gets its own AutoCodec instead of the shared singleton
            # (whose pinned choice belongs to the RS(10,4) curve)
            backend = AutoCodec(self.code.spec)
        self.backend = (get_backend(backend) if isinstance(backend, str)
                        else backend)
        self._parity_rows = rs_matrix.parity_rows_for(self.code)

    @classmethod
    def for_codec(cls, codec: str,
                  backend: str | CodecBackend = "numpy"
                  ) -> "ReedSolomon":
        """Construct from a .vif codec spec string ('', 'k.m',
        'lrc-k.l.g') — the one entry point volume readers use, so a
        mixed-code cluster decodes every volume with its own code."""
        return cls(0, 0, backend, code=geo.parse_code(codec or ""))

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(k, n) data shards -> (m, n) parity shards. A sub-striped
        code takes (2k, n), the data shards' a-halves then b-halves,
        and gives (2m, n), the parities' in the same order."""
        data = np.asarray(data, dtype=np.uint8)
        assert data.shape[0] == self._parity_rows.shape[1], data.shape
        t0 = _time.perf_counter()
        out = self.backend.coded_matmul(self._parity_rows, data)
        # label after the call: AutoCodec resolves during its first op
        observe_codec("encode", self.backend,
                      _time.perf_counter() - t0, data.nbytes,
                      code=self.code.spec)
        return out

    def reconstruct(self, shards: dict[int, np.ndarray],
                    missing: list[int] | None = None, *,
                    stage: np.ndarray | None = None
                    ) -> dict[int, np.ndarray]:
        """Recover shards from any >= k present ones.

        shards: {shard_id: (n,) or (n_cols,) uint8 row}; missing: which ids
        to produce (default: all absent ids 0..k+m-1). Returns {id: row}.
        For a sub-striped code the ids are row ids (code.halfrow): a
        half of a shard each, all at the same offset within its half.

        stage: an optional writable 1-D uint8 buffer the caller owns
        (staging_buffer()). When it holds the input rows, they are
        stacked into it instead of a fresh array; else np.stack as
        without it. The returned rows never view it, and coded_matmul
        is synchronous, so the caller may refill it once this returns.
        Never hand such a buffer to coded_matmul_stream: its upload
        runs on another thread after the call returns.
        """
        present = sorted(shards)
        if missing is None:
            missing = [i for i in range(self.code.rows)
                       if i not in shards]
        if not missing:
            return {}
        rows, inputs = rs_matrix.recovery_rows_for(self.code, present,
                                                   missing)
        ins = [np.asarray(shards[i], dtype=np.uint8) for i in inputs]
        # a backend that compiles a kernel per input-row count may ask
        # for the count rounded up (row_multiple): zero rows under zero
        # coefficients, so fan-ins one apart (Hitchhiker's 13 and 14
        # half ranges) share one kernel and neither compiles mid-loop
        pad = -len(ins) % getattr(self.backend, "row_multiple", 1)
        shape = (len(ins) + pad,) + ins[0].shape
        size = shape[0] * ins[0].size
        if stage is not None and stage.size >= size:
            stack = stage[:size].reshape(shape)
        else:
            stack = np.empty(shape, dtype=np.uint8)
        np.stack(ins, out=stack[:len(ins)])
        if pad:
            stack[len(ins):] = 0
            rows = np.concatenate(
                [rows, np.zeros((len(rows), pad), dtype=np.uint8)], axis=1)
        t0 = _time.perf_counter()
        out = self.backend.coded_matmul(rows, stack)
        observe_codec("reconstruct", self.backend,
                      _time.perf_counter() - t0, len(ins) * ins[0].nbytes,
                      code=self.code.spec)
        return {sid: out[i] for i, sid in enumerate(missing)}

    def reconstruct_data(self, shards: dict[int, np.ndarray]) -> dict[int, np.ndarray]:
        """Recover only missing DATA shards (reference ReconstructData,
        /root/reference/weed/storage/store_ec.go:384)."""
        missing = [i for i in range(self.k) if i not in shards]
        return self.reconstruct(shards, missing)

    @property
    def supports_streaming(self) -> bool:
        """True when the backend can pipeline column blocks (device
        codecs overlapping H2D / compute / D2H)."""
        return hasattr(self.backend, "coded_matmul_stream")

    def matmul_stream(self, coef: np.ndarray, blocks, depth: int = 2,
                      op: str = "encode"):
        """Yield coded_matmul(coef, block) per block, pipelined when the
        backend supports it (device in-flight depth `depth`), else
        computed synchronously block-by-block. Each block is recorded
        into ec_codec_seconds{op,backend} (steady-state inter-yield time
        for pipelined backends) and ec_codec_bytes_total."""
        def counted(src):
            for block in src:
                observe_codec(op, self.backend,
                              nbytes=getattr(block, "nbytes", 0))
                yield block

        stream = getattr(self.backend, "coded_matmul_stream", None)
        if stream is not None:
            it = stream(coef, counted(blocks), depth=depth)
        else:
            it = (self.backend.coded_matmul(coef, block)
                  for block in counted(blocks))
        while True:
            t0 = _time.perf_counter()
            try:
                out = next(it)
            except StopIteration:
                return
            observe_codec(op, self.backend, _time.perf_counter() - t0)
            yield out

    def encode_stream(self, blocks, depth: int = 2):
        """Streaming encode: yields (m, w) parity per (k, w) data block."""
        yield from self.matmul_stream(self._parity_rows, blocks,
                                      depth=depth, op="encode")

    def verify(self, shards: np.ndarray) -> bool:
        """(k+m, n) full shard stack -> parity consistency check. A
        sub-striped code's shards are coded in halves, so `n` must be
        whole shards (or matching prefixes of both halves, side by
        side)."""
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.shape[0] == self.n
        subs = self.code.substripes
        if subs > 1:
            if shards.shape[1] % subs:
                return False
            # one row per half, in code.halfrow order
            halves = np.concatenate(np.split(shards, subs, axis=1))
            data = [r for r in range(len(halves)) if r % self.n < self.k]
            par = [r for r in range(len(halves)) if r % self.n >= self.k]
            return bool(np.array_equal(self.encode(halves[data]),
                                       halves[par]))
        expect = self.encode(shards[: self.k])
        return bool(np.array_equal(expect, shards[self.k:]))
