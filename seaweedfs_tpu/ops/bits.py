"""Shared jax bit-plane pack/unpack — the device-side counterpart of
gf256.unpack_bits/pack_bits (numpy).

Every XLA codec path (codec_mesh, models.ec_pipeline) MUST use
these two functions: the codecs have to stay bit-identical for shard
interoperability, and divergent hand-rolled copies of the shift/weights
transform are exactly how they'd drift apart.

Bit order: bit s of byte b lands at plane-row 8*i+s for shard-row i
(bit-minor), matching gf256.expand_to_bits block layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unpack_bits_bf16(x: jax.Array) -> jax.Array:
    """(..., k, n) uint8 -> (..., 8k, n) bf16 0/1 bit-planes."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & 1
    shape = x.shape[:-2] + (x.shape[-2] * 8, x.shape[-1])
    return bits.reshape(shape).astype(jnp.bfloat16)


def pack_bits_uint8(bits: jax.Array) -> jax.Array:
    """(..., 8m, n) int 0/1 -> (..., m, n) uint8."""
    m8, n = bits.shape[-2], bits.shape[-1]
    b = bits.reshape(bits.shape[:-2] + (m8 // 8, 8, n)).astype(jnp.uint8)
    w = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    return (b * w).sum(axis=-2, dtype=jnp.uint8)


def coded_matmul_bits(a_bits: jax.Array, shards: jax.Array) -> jax.Array:
    """The core codec op: (8m, 8k) bf16 bit-matrix x (k, n) uint8 shards
    -> (m, n) uint8, GF(256) coded matmul via GF(2) matmul on the MXU."""
    bits = unpack_bits_bf16(shards)
    acc = jax.lax.dot_general(
        a_bits, bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return pack_bits_uint8(acc.astype(jnp.int32) & 1)
