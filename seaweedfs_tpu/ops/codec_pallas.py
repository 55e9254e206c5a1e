"""Fused Pallas TPU kernel for the GF(256) coded matmul.

The XLA path (codec_jax / bits.coded_matmul_bits) materializes the
(8k, n) bf16 bit-plane expansion — 32x the input bytes of HBM write+
read traffic — so at scale it runs HBM-bound far below the MXU's
ceiling. This kernel keeps the whole unpack -> matmul -> pack chain in
VMEM per column tile: HBM sees only the (k, TN) uint8 reads and
(m, TN) uint8 writes.

Layout discipline (the first attempt died on this): Mosaic relayouts
across the sublane dimension — the interleaving reshape
(k, 8, n)->(8k, n) or strided sublane slicing — are catastrophically
slow. So the kernel never interleaves: the bit expansion CONCATENATES
the 8 shift masks along sublanes (plane-major order) and the
coefficient matrix's columns are permuted on the host to match
(plane_major_bit_matrix); the byte pack is itself a tiny matmul with
the power-of-two packing matrix P[i, 8i+b] = 2^b — exact in f32.

Bit/byte semantics are EXACTLY bits.coded_matmul_bits (golden tests
run identical vectors through both paths). Its speed against the XLA
path is not measured on the chip yet. Beware two measurement traps
this file's history hit: closing over the data array turns it into a
multi-GB jit constant, and a fori_loop over one slab gets hoisted as
loop-invariant and reports fantasy numbers — bench.py's
scan-over-distinct-slabs is the honest shape. Selected with
-ec.backend=pallas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


def coded_matmul_pallas(a_bits: jax.Array, shards: jax.Array,
                        interpret: bool = False) -> jax.Array:
    """Drop-in signature match for bits.coded_matmul_bits (a_bits is
    the bit-minor (8m, 8k) matrix); hot paths should precompute the
    plane-major matrix + packing matrix and call the _pm form."""
    a_pm = plane_major_bit_matrix(np.asarray(a_bits, dtype=np.float32))
    pack = packing_matrix(a_pm.shape[0] // 8)
    return coded_matmul_pallas_pm(a_pm, pack, shards,
                                  interpret=interpret)


def _make_pallas_codec_class():
    """Deferred so importing this module never pulls codec_jax/jax
    machinery at module import time (mirrors the lazy backend
    factories in ec/backend.py)."""
    from collections import OrderedDict

    from .codec_jax import JaxCodec

    class PallasCodec(JaxCodec):
        """Codec backend running the fused Pallas kernel
        (-ec.backend=pallas). Reuses JaxCodec's slabbing, committed
        H2D placement and the staged streaming pipeline; only the
        per-coefficient matrices, the column padding (COL_TILE
        multiples, applied host-side before H2D) and the kernel
        dispatch differ."""

        name = "pallas"

        def __init__(self, slab: int = 8 << 20):
            super().__init__(slab=slab)
            self._mats: "OrderedDict[bytes, tuple]" = OrderedDict()

        def _coef_bits(self, coef: np.ndarray):
            key = coef.shape[0].to_bytes(2, "big") + coef.tobytes()
            mats = self._mats.get(key)
            if mats is None:
                from . import gf256

                bits = gf256.expand_to_bits(coef)
                mats = (plane_major_bit_matrix(bits),
                        packing_matrix(coef.shape[0]))
                self._mats[key] = mats
                if len(self._mats) > self.BITMAT_CACHE_MAX:
                    self._mats.popitem(last=False)
            else:
                self._mats.move_to_end(key)
            return mats

        def _pad_width(self, n: int) -> int:
            # the kernel's grid walks COL_TILE lanes per step; padding
            # happens on the host (JaxCodec._split) so the device
            # never relayouts
            return n + (-n) % COL_TILE

        def _plan_for(self, coef, nbytes):
            # the fused kernel is already a bit-plane program executed
            # on-device; the scheduled XOR path never applies here
            return None

        def _run(self, mats, dev: jax.Array, plan=None) -> jax.Array:
            a_pm, pack = mats
            if self._donate is None:
                self._donate = jax.devices()[0].platform != "cpu"
            if self._donate:
                return coded_matmul_pallas_pm_donated(a_pm, pack, dev)
            # the CPU backend (JAX_PLATFORMS=cpu: tests, rehearsals)
            # has no Mosaic compiler; the kernel runs interpreted
            return coded_matmul_pallas_pm(a_pm, pack, dev, interpret=True)

    return PallasCodec


def PallasCodec(slab: int = 8 << 20):
    """Factory kept under the class's name for the backend registry."""
    return _make_pallas_codec_class()(slab=slab)
