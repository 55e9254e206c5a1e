"""The single-chip device codec: a fused Pallas TPU kernel for the
GF(256) coded matmul, and the staged feed that streams blocks through it.

The trick (SURVEY.md section 7 "GF(256) as MXU work"): multiplication
by a GF(256) constant is linear over GF(2)^8, so the m x k coefficient
matrix expands to an (8m x 8k) 0/1 matrix (gf256.expand_to_bits) and
out_bytes = pack((A_bits @ unpack(shards)) mod 2). One compiled kernel
serves encode AND any reconstruction: the coefficient matrices are
runtime arguments, only shapes are static. Equivalent of upstream
SeaweedFS's enc.Encode / enc.Reconstruct
(weed/storage/erasure_coding/ec_encoder.go:190,274), batched: callers
collapse stripes into (k, n) columns.

The XLA form of that chain (bits.coded_matmul_bits, the mesh codec's
kernel) materializes the (8k, n) bf16 bit-plane expansion — 32x the
input bytes of HBM write+read traffic. This kernel keeps the whole
unpack -> matmul -> pack chain in VMEM per column tile: HBM sees only
the (k, TN) uint8 reads and (m, TN) uint8 writes.

Layout discipline (the first attempt died on this): Mosaic relayouts
across the sublane dimension — the interleaving reshape
(k, 8, n)->(8k, n) or strided sublane slicing — are catastrophically
slow. So the kernel never interleaves: the bit expansion CONCATENATES
the 8 shift masks along sublanes (plane-major order) and the
coefficient matrix's columns are permuted on the host to match
(plane_major_bit_matrix); the byte pack is itself a tiny matmul with
the power-of-two packing matrix P[i, 8i+b] = 2^b — exact in f32.

Bit/byte semantics are EXACTLY bits.coded_matmul_bits (golden tests
run identical vectors through both paths). Two measurement traps this
file's history hit: closing over the data array turns it into a
multi-GB jit constant, and a fori_loop over one slab gets hoisted as
loop-invariant and reports fantasy numbers — time a scan over
distinct slabs. Selected with -ec.backend=pallas.

PallasCodec.coded_matmul_stream is a depth-N staged pipeline: an
upload thread commits block k+1 to the device (jax.device_put with an
explicit SingleDeviceSharding, so placement is decided once) while the
device runs block k's kernel and a drain thread reads block k-1 back.
Input blocks are donated to the kernel on real accelerators; on the
CPU platform the kernel runs interpreted. Every stage is timed and
annotated through ops/feed.py.
"""
from __future__ import annotations

import time as _time
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256
from .feed import _collect, _pad_cols, observe_stage, stage

COL_TILE = 4096  # lanes per grid step
# the kernel's op name in compiled HLO and in a device trace
# ("%ec_coded_matmul.N"), whatever the Python wrapper is called
KERNEL_NAME = "ec_coded_matmul"


def _kernel(a_ref, p_ref, x_ref, o_ref):
    """a_ref: (8m, 8k) bf16 coefficient matrix with PLANE-MAJOR
    columns (see plane_major_bit_matrix); p_ref: (m, 8m) bf16 packing
    matrix; x_ref: (k, TN) uint8; o_ref: (m, TN) uint8.

    The bit expansion concatenates the 8 shift masks along sublanes
    (plane-major: all bit-0 rows, then bit-1 rows, ...) — concat is a
    cheap placement, unlike the interleaving (k,8,TN)->(8k,TN) reshape
    which forces a catastrophic sublane relayout."""
    x = x_ref[:, :].astype(jnp.int32)
    planes = [((x >> s) & 1).astype(jnp.bfloat16) for s in range(8)]
    bits = jnp.concatenate(planes, axis=0)  # (8k, TN) plane-major
    acc = jax.lax.dot_general(
        a_ref[:, :], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    par = (acc.astype(jnp.int32) & 1).astype(jnp.bfloat16)  # (8m, TN)
    packed = jax.lax.dot_general(
        p_ref[:, :], par, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # exact: sums <= 255
    o_ref[:, :] = packed.astype(jnp.int32).astype(jnp.uint8)


def plane_major_bit_matrix(a_bits: np.ndarray | jax.Array) -> jax.Array:
    """(8m, 8k) bit-minor matrix -> (8m, 8k) with columns permuted to
    plane-major order: column s*k + j multiplies bit s of shard j
    (matching the kernel's concatenated expansion). Row order is
    untouched, so the packing matrix stays the same."""
    a = np.asarray(a_bits, dtype=np.float32)
    m8, k8 = a.shape
    k = k8 // 8
    perm = [8 * j + s for s in range(8) for j in range(k)]
    return jnp.asarray(a[:, perm], dtype=jnp.bfloat16)


def packing_matrix(m: int) -> jax.Array:
    """(m, 8m) P with P[i, 8i+b] = 2^b: packs bit rows back to bytes
    via one exact f32 matmul (bit-minor order, matching
    bits.pack_bits_uint8)."""
    p = np.zeros((m, 8 * m), dtype=np.float32)
    for i in range(m):
        for b in range(8):
            p[i, 8 * i + b] = float(1 << b)
    return jnp.asarray(p, dtype=jnp.bfloat16)


def _coded_matmul_pallas_pm_impl(a_pm: jax.Array, pack: jax.Array,
                                 shards: jax.Array,
                                 interpret: bool = False) -> jax.Array:
    """a_pm: (8m, 8k) bf16 plane-major coefficient matrix;
    pack: (m, 8m) bf16; shards: (k, n) uint8 with n % COL_TILE == 0
    -> (m, n) uint8."""
    from jax.experimental import pallas as pl

    m8, k8 = a_pm.shape
    k, n = shards.shape
    assert k8 == 8 * k and n % COL_TILE == 0, (a_pm.shape, shards.shape)
    m = m8 // 8
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.uint8),
        grid=(n // COL_TILE,),
        in_specs=[
            pl.BlockSpec((m8, k8), lambda j: (0, 0)),
            pl.BlockSpec((m, m8), lambda j: (0, 0)),
            pl.BlockSpec((k, COL_TILE), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, COL_TILE), lambda j: (0, j)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(a_pm, pack, shards)


coded_matmul_pallas_pm = jax.jit(_coded_matmul_pallas_pm_impl,
                                 static_argnames=("interpret",))
# pipeline variant: the uploaded block is dead after the kernel —
# donating it lets XLA recycle its HBM for in-flight staging buffers
coded_matmul_pallas_pm_donated = jax.jit(
    _coded_matmul_pallas_pm_impl, static_argnames=("interpret",),
    donate_argnums=(2,))


class PallasCodec:
    """Codec backend running the fused kernel on the default device
    (-ec.backend=pallas). Caches the per-coefficient matrices and pads
    the column count on the host, before H2D, to COL_TILE multiples,
    in slabs of `slab` columns per kernel call."""

    name = "pallas"
    # ReedSolomon.reconstruct rounds the input rows up to a multiple of
    # this with zero rows: one compiled kernel per pair of fan-ins
    row_multiple = 2

    # bound the coefficient-matrix cache: reconstruction over wide
    # codes can see tens of thousands of distinct recovery matrices
    BITMAT_CACHE_MAX = 256

    def __init__(self, slab: int = 8 << 20):
        self.slab = slab
        self._mats: "OrderedDict[bytes, tuple]" = OrderedDict()
        self._sharding = None
        self._donate: bool | None = None

    def _coef_bits(self, coef: np.ndarray):
        key = coef.shape[0].to_bytes(2, "big") + coef.tobytes()
        mats = self._mats.get(key)
        if mats is None:
            bits = gf256.expand_to_bits(coef)
            mats = (plane_major_bit_matrix(bits),
                    packing_matrix(coef.shape[0]))
            self._mats[key] = mats
            if len(self._mats) > self.BITMAT_CACHE_MAX:
                self._mats.popitem(last=False)
        else:
            self._mats.move_to_end(key)
        return mats

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

    def _split(self, shards: np.ndarray) -> list[tuple[np.ndarray, int]]:
        """Host-side slab split + padding to COL_TILE multiples (the
        kernel's grid step): [(padded_chunk, true_width)]. Padding
        happens before H2D so the device never relayouts."""
        slab = self.slab
        out = []
        for off in range(0, max(1, shards.shape[1]), slab):
            chunk = shards[:, off:off + slab]
            w = chunk.shape[1]
            out.append((_pad_cols(chunk, w + (-w) % COL_TILE), w))
        return out

    def _run(self, mats, dev: jax.Array) -> jax.Array:
        """Dispatch the kernel on an already-on-device padded block."""
        a_pm, pack = mats
        if self._donate is None:
            # donation on the CPU backend logs an unusable-buffer
            # warning per call; only enable where it buys HBM reuse
            self._donate = jax.devices()[0].platform != "cpu"
        if self._donate:
            return coded_matmul_pallas_pm_donated(a_pm, pack, dev)
        # the CPU backend (JAX_PLATFORMS=cpu: tests, rehearsals) has no
        # Mosaic compiler; the kernel runs interpreted
        return coded_matmul_pallas_pm(a_pm, pack, dev, interpret=True)

    def coded_matmul(self, coef: np.ndarray, shards) -> np.ndarray:
        coef = np.asarray(coef, dtype=np.uint8)
        m, k = coef.shape
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.ndim == 2 and shards.shape[0] == k
        if shards.shape[1] == 0:
            return np.zeros((m, 0), dtype=np.uint8)
        mats = self._coef_bits(coef)
        return _collect([(self._run(mats, self._h2d(chunk)), w)
                         for chunk, w in self._split(shards)])

    def coded_matmul_stream(self, coef: np.ndarray, blocks,
                            depth: int = 2):
        """Streaming pipeline: for each (k, w) uint8 column block from
        the iterable `blocks`, yield the matching (m, w) result, in
        order, with up to `depth` blocks in flight.

        Three stages on three threads so they genuinely overlap (the
        reference streams 256KB buffers through its CPU codec
        synchronously, ec_encoder.go:198-235; a device codec lives or
        dies by hiding transfer latency):

          caller thread   pread   next(blocks)
          upload thread   h2d     host pad/split + committed
                                  device_put, blocks until the copy
                                  lands, then issues the kernel
                                  (async under jax dispatch)
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
        coef = np.asarray(coef, dtype=np.uint8)
        m = coef.shape[0]
        mats = self._coef_bits(coef)
        depth = max(1, int(depth))
        backend = self.name

        def upload(block: np.ndarray):
            with stage(backend, "h2d"):
                devs = [(self._h2d(chunk), w)
                        for chunk, w in self._split(block)]
                for d, _ in devs:
                    # wait for the copies, not the compute: the h2d
                    # stage time must be the transfer alone, and issuing
                    # the next upload before the kernel keeps the DMA
                    # engine busy
                    d.block_until_ready()
            return [(self._run(mats, d), w) for d, w in devs]

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
