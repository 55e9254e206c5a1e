"""TPU codec: GF(256) coded matmul as a bit-plane matmul on the MXU.

The trick (SURVEY.md section 7 "GF(256) as MXU work"): multiplication by a
GF(256) constant is linear over GF(2)^8, so the whole m x k coefficient
matrix expands to an (8m x 8k) 0/1 matrix A_bits (gf256.expand_to_bits) and

    out_bytes = pack( (A_bits @ unpack(shards)) mod 2 )

where unpack turns (k, n) bytes into (8k, n) bit-planes. The matmul runs in
bf16 on the MXU with f32 accumulation — sums of 8k <= 2048 zeros/ones are
exact in f32 — and `mod 2` is a cheap elementwise op XLA fuses into the
epilogue. One compiled kernel serves encode AND any reconstruction: the
coefficient bit-matrix is a runtime argument, only shapes are static.

Equivalent of the reference's hot loops enc.Encode / enc.Reconstruct
(/root/reference/weed/storage/erasure_coding/ec_encoder.go:190,274), but
batched: callers collapse (batch, k, stripe) into (k, batch*stripe) columns
so thousands of stripes ride one dispatch.

The streaming entry point (coded_matmul_stream) is a depth-N staged
pipeline: a dedicated upload thread commits block k+1 to the device
(jax.device_put with an explicit SingleDeviceSharding, so placement is
decided once, not re-negotiated per call) while the device runs block
k's kernel and a dedicated drain thread reads block k-1 back. Input
device buffers are donated to the kernel on real accelerators so XLA
can reuse them for the bit-plane intermediate, and readback goes
through dlpack when the consumer and producer share an address space
(CPU devices: zero-copy). Every stage is timed into
ec_codec_stage_seconds{stage,backend} — pread (waiting on the block
source), h2d, drain_wait (the drain thread waiting for the kernel,
which overlaps it), d2h, and relay (finished results waiting for the
consumer) — and, but for relay, which no thread is inside, opens a
`swfs.ec.stage.<stage>` profiler annotation, so a trace shows the
kernel's own time beside the host stage around it.
"""
from __future__ import annotations

import time as _time
from collections import OrderedDict
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256, schedule
from ..utils import metrics, tracing

# Column slab each jitted call processes; callers pad up to a multiple.
# 2 MiB columns x 8k bit-rows in bf16 keeps the working set well inside HBM
# while amortizing dispatch overhead.
DEFAULT_SLAB = 1 << 21


def _bit_matmul_body(a_bits: jax.Array, shards: jax.Array) -> jax.Array:
    from .bits import coded_matmul_bits

    return coded_matmul_bits(a_bits, shards)


# a_bits: (8m, 8k) bf16 0/1; shards: (k, n) uint8 -> (m, n) uint8.
_bit_matmul = jax.jit(_bit_matmul_body)
# pipeline variant: the device input block is dead after the kernel, so
# donating it lets XLA reuse the buffer for the (8k, n) bit-plane
# intermediate instead of allocating fresh HBM per in-flight block
_bit_matmul_donated = jax.jit(_bit_matmul_body, donate_argnums=(1,))


def _xor_matmul_body(program, shards: jax.Array) -> jax.Array:
    """The scheduled alternative to the MXU matmul: run the
    CSE-optimized XOR program (ops/schedule.Program, static) over
    uint8 bit-planes. Same byte semantics as coded_matmul_bits — the
    schedule rewrites the program, not the layout — so either kernel
    can serve any dispatch; which one runs is measured, not assumed."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (shards[:, None, :] >> shifts[None, :, None]) & 1
    bits = bits.reshape(shards.shape[0] * 8, shards.shape[1])
    pool = [bits[i] for i in range(program.n_in)]
    for _, a, b in program.ops:
        pool.append(pool[a] ^ pool[b])
    zero = jnp.zeros_like(bits[0])
    rows = jnp.stack([pool[v] if v >= 0 else zero
                      for v in program.outputs])
    from .bits import pack_bits_uint8

    return pack_bits_uint8(rows)


# program is hashable (frozen dataclass of tuples) -> valid static arg
_xor_matmul = jax.jit(_xor_matmul_body, static_argnums=(0,))


def observe_stage(backend: str, stage: str, seconds: float) -> None:
    """Per-stage feed timing (pread/h2d/drain_wait/d2h/relay). Lives
    next to ec_codec_seconds; one extra label dimension, one histogram
    per (stage, backend)."""
    metrics.histogram_observe("ec_codec_stage_seconds", seconds,
                              {"stage": stage, "backend": backend})


@contextmanager
def stage(backend: str, name: str):
    """One feed stage on this thread: the `swfs.ec.stage.<name>`
    annotation over it, its seconds into ec_codec_stage_seconds."""
    with tracing.interval("ec.stage." + name, observe=False) as iv:
        yield
    observe_stage(backend, name, iv.seconds)


def bit_matrix(coef: np.ndarray) -> jax.Array:
    """Host byte matrix -> device bf16 bit-matrix (cacheable by caller)."""
    return jnp.asarray(gf256.expand_to_bits(coef), dtype=jnp.bfloat16)


class JaxCodec:
    """Coded-matmul backend running on the default jax device.

    Caches expanded coefficient bit-matrices keyed by the coefficient
    bytes, and pads the column count to `slab` multiples so XLA compiles a
    handful of shapes no matter the file size.
    """

    name = "jax"

    # bound the per-instance coefficient-matrix cache: reconstruction over
    # wide codes can see tens of thousands of distinct recovery matrices
    BITMAT_CACHE_MAX = 256

    def __init__(self, slab: int = DEFAULT_SLAB):
        self.slab = slab
        self._bitmats: "OrderedDict[bytes, jax.Array]" = OrderedDict()
        self._sharding = None
        self._donate: bool | None = None
        self._chooser = schedule.Chooser()

    def _coef_bits(self, coef: np.ndarray) -> jax.Array:
        key = coef.shape[0].to_bytes(2, "big") + coef.tobytes()
        bm = self._bitmats.get(key)
        if bm is None:
            bm = bit_matrix(coef)
            self._bitmats[key] = bm
            if len(self._bitmats) > self.BITMAT_CACHE_MAX:
                self._bitmats.popitem(last=False)
        else:
            self._bitmats.move_to_end(key)
        return bm

    # ------------------------------------------------------------------
    # placement / transfer / dispatch primitives (shared with PallasCodec)
    # ------------------------------------------------------------------
    def _placement(self):
        """Committed single-device placement: device_put against an
        explicit sharding starts the copy immediately and pins the
        array, so back-to-back uploads from the feed thread queue on
        the DMA engine instead of waiting for lazy placement."""
        if self._sharding is None:
            from jax.sharding import SingleDeviceSharding

            self._sharding = SingleDeviceSharding(jax.devices()[0])
        return self._sharding

    def _h2d(self, chunk: np.ndarray) -> jax.Array:
        return jax.device_put(chunk, self._placement())

    def _pad_width(self, n: int) -> int:
        """Pad sub-slab column counts to power-of-two buckets (>=256) so
        XLA compiles at most log2(slab/256) shapes for sub-slab calls."""
        padded = 256
        while padded < n:
            padded <<= 1
        return min(padded, max(self.slab, n))

    def _split(self, shards: np.ndarray) -> list[tuple[np.ndarray, int]]:
        """Host-side slab split + padding: [(padded_chunk, true_width)].
        Padding happens before H2D so the device never sees a shape it
        has to relayout."""
        n = shards.shape[1]
        slab = self.slab
        if n <= slab:
            return [(_pad_cols(shards, self._pad_width(n)), n)]
        out = []
        for off in range(0, n, slab):
            chunk = shards[:, off:off + slab]
            w = chunk.shape[1]
            out.append((_pad_cols(chunk, self._pad_width(w)), w))
        return out

    def _run(self, mats, dev: jax.Array, plan=None) -> jax.Array:
        """Dispatch the kernel on an already-on-device padded block:
        the scheduled XOR program when the chooser picked it for this
        (matrix, size), else the dense MXU bit-matmul."""
        if plan is not None:
            return _xor_matmul(plan, dev)
        if self._donate is None:
            # donation on the CPU backend logs an unusable-buffer
            # warning per call; only enable where it buys HBM reuse
            self._donate = jax.devices()[0].platform != "cpu"
        fn = _bit_matmul_donated if self._donate else _bit_matmul
        return fn(mats, dev)

    def _plan_for(self, coef: np.ndarray, nbytes: int):
        """The scheduled program when measurement says it beats the
        dense kernel at this (matrix, size bucket); None otherwise.
        Both candidates are timed once per bucket on a slab-width
        sample (after a warm/compile call each), and the verdict is
        keyed by the SAMPLE's byte size — never-slower at the probed
        size by construction, pinnable via SEAWEEDFS_TPU_EC_SCHEDULE.
        The measurement runs on a background thread (the warm calls
        include an XLA compile of the ~10^3-op unrolled XOR program,
        multi-second cold): first sight of a (matrix, bucket) serves
        the dense kernel immediately and upgrades once the verdict
        lands, so a live read/repair never pays the compile spike."""
        k = coef.shape[1]
        w = self._pad_width(
            min(max(1, nbytes // max(1, k)), self.slab))
        sample_bytes = min(nbytes, k * w)
        sample = None
        mats = None
        plan = None

        def prep():
            nonlocal sample, mats, plan
            if sample is None:
                rng = np.random.default_rng(0)
                chunk = rng.integers(0, 256, (k, w), dtype=np.uint8)
                sample = self._h2d(chunk)
                mats = self._coef_bits(coef)
                plan = schedule.plan_for(coef)

        def run_sched():
            prep()
            _xor_matmul(plan, sample).block_until_ready()

        def run_dense():
            prep()
            _bit_matmul(mats, sample).block_until_ready()

        if self._chooser.use_scheduled(coef, sample_bytes, run_sched,
                                       run_dense, background=True):
            return schedule.plan_for(coef)
        return None

    def _dispatch(self, mats, shards: np.ndarray, plan=None) -> list:
        """Issue the async device calls for one (k, n) column block,
        slab-split and bucket-padded; returns [(device_array, width)]
        without forcing any transfer back."""
        return [(self._run(mats, self._h2d(chunk), plan), w)
                for chunk, w in self._split(shards)]

    def coded_matmul(self, coef: np.ndarray, shards) -> np.ndarray:
        coef = np.asarray(coef, dtype=np.uint8)
        m, k = coef.shape
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.ndim == 2 and shards.shape[0] == k
        n = shards.shape[1]
        if n == 0:
            return np.zeros((m, 0), dtype=np.uint8)
        plan = self._plan_for(coef, shards.nbytes)
        mats = self._coef_bits(coef)
        return _collect(self._dispatch(mats, shards, plan))

    def coded_matmul_stream(self, coef: np.ndarray, blocks,
                            depth: int = 2):
        """Streaming pipeline: for each (k, w) uint8 column block from
        the iterable `blocks`, yield the matching (m, w) result, in
        order, with up to `depth` blocks in flight.

        Three stages on three threads so they genuinely overlap (the
        reference streams 256KB buffers through its CPU codec
        synchronously, ec_encoder.go:198-235; a device codec lives or
        dies by hiding transfer latency):

          caller thread   pread   next(blocks) + host pad/split
          upload thread   h2d     committed device_put, blocks until
                                  the copy lands, then issues the
                                  kernel (async under jax dispatch)
          drain thread    drain_wait  block_until_ready on the result
                          d2h     dlpack/np.asarray readback

        While the drain thread reads block k-1 back, the device runs
        block k's kernel and the upload thread pushes block k+1 — the
        double-buffered schedule at depth=2, deeper when asked. Each
        stage records ec_codec_stage_seconds{stage}; `relay` is the
        time a finished block waited for the consumer (writer
        backpressure + queue residence), so
        pread+h2d+drain_wait+d2h+relay accounts for the whole e2e gap
        versus the link ceiling.
        """
        from collections import deque
        from concurrent.futures import Future, ThreadPoolExecutor

        coef = np.asarray(coef, dtype=np.uint8)
        m = coef.shape[0]
        mats = self._coef_bits(coef)
        depth = max(1, int(depth))
        backend = self.name
        # streams are bulk: decide scheduled-vs-dense once at slab size
        plan = self._plan_for(coef, coef.shape[1] * self.slab)

        def upload(block: np.ndarray):
            with stage(backend, "h2d"):
                chunks = self._split(block)
                devs = [(self._h2d(chunk), w) for chunk, w in chunks]
                for d, _ in devs:
                    # wait for the copies, not the compute: the h2d
                    # stage time must be the transfer alone, and issuing
                    # the next upload before the kernel keeps the DMA
                    # engine busy
                    d.block_until_ready()
            return [(self._run(mats, d, plan), w) for d, w in devs]

        def drain(up_fut):
            outs = up_fut.result()
            with stage(backend, "drain_wait"):
                for d, _ in outs:
                    d.block_until_ready()
            with stage(backend, "d2h"):
                arr = _collect(outs)
            return arr, _time.perf_counter()

        up_ex = ThreadPoolExecutor(1, thread_name_prefix="ec-h2d")
        down_ex = ThreadPoolExecutor(1, thread_name_prefix="ec-d2h")

        def finish(fut) -> np.ndarray:
            arr, t_done = fut.result()
            relay = _time.perf_counter() - t_done
            if relay > 0:
                observe_stage(backend, "relay", relay)
            return arr

        try:
            pending: deque = deque()
            it = iter(blocks)
            while True:
                try:
                    with stage(backend, "pread"):
                        block = next(it)
                except StopIteration:
                    break
                block = np.asarray(block, dtype=np.uint8)
                if block.shape[1] == 0:
                    # empty result still rides the queue: yielding it
                    # directly would reorder it ahead of pending blocks
                    f: Future = Future()
                    f.set_result((np.zeros((m, 0), dtype=np.uint8),
                                  _time.perf_counter()))
                    pending.append(f)
                else:
                    up = up_ex.submit(upload, block)
                    pending.append(down_ex.submit(drain, up))
                while len(pending) >= depth:
                    yield finish(pending.popleft())
            while pending:
                yield finish(pending.popleft())
        finally:
            # bounded: at most `depth` blocks in flight, and upload
            # tasks cannot deadlock on drain tasks, so waiting here
            # can't hang; cancel_futures covers generator early-close
            up_ex.shutdown(wait=True, cancel_futures=True)
            down_ex.shutdown(wait=True, cancel_futures=True)


def _readback(dev: jax.Array) -> np.ndarray:
    """D2H for one device result. dlpack first: on CPU devices (and
    any platform sharing the host address space) it aliases the device
    buffer instead of copying — the consumer only reads, so the
    read-only view is fine. Accelerators fall back to np.asarray."""
    try:
        return np.from_dlpack(dev)
    except Exception:
        return np.asarray(dev)


def _collect(devs: list) -> np.ndarray:
    """Force D2H on a _dispatch result and reassemble the (m, n) block
    (shared by the sync path and the streaming drain thread)."""
    if len(devs) == 1:
        dev, w = devs[0]
        out = _readback(dev)
        return out[:, :w] if out.shape[1] != w else out
    return np.concatenate(
        [_readback(dev)[:, :w] for dev, w in devs], axis=1)


def _pad_cols(arr: np.ndarray, n: int) -> np.ndarray:
    if arr.shape[1] == n:
        return arr
    out = np.zeros((arr.shape[0], n), dtype=arr.dtype)
    out[:, : arr.shape[1]] = arr
    return out
