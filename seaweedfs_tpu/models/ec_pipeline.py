"""Flagship pipeline: batched erasure-coding encode + scrub as one
jittable, mesh-shardable step.

This is the framework's "model": the computation the TPU sidecar runs in
steady state (BASELINE.json north_star) — thousands of stripes per
dispatch, RS(10,4) parity generation fused with the parity-consistency
scrub, sharded over a (vol, col) device mesh with psum aggregation.

The step takes a (batch, k, cols) uint8 stripe tensor and the parity
bit-matrix, and returns the (batch, m, cols) parity plus a global scrub
scalar (count of mismatched bytes vs a provided expected-parity tensor;
zero when clean). Encode-only callers pass expected=None logic via the
`encode_step` wrapper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import gf256, rs_matrix


def parity_bit_matrix(k: int = 10, m: int = 4) -> np.ndarray:
    """Host-side (8m, 8k) 0/1 matrix for the systematic parity rows."""
    return gf256.expand_to_bits(rs_matrix.parity_rows(k, m))


def encode_batch(a_bits: jax.Array, stripes: jax.Array) -> jax.Array:
    """(batch, k, n) uint8 -> (batch, m, n) uint8 parity. Pure function,
    jit/shard_map-safe; batch and n dims are embarrassingly parallel."""
    from ..ops.bits import pack_bits_uint8, unpack_bits_bf16

    bits = unpack_bits_bf16(stripes)                      # (B, 8k, n)
    acc = jnp.einsum("st,btn->bsn", a_bits, bits,
                     preferred_element_type=jnp.float32)
    return pack_bits_uint8(acc.astype(jnp.int32) & 1)


def encode_scrub_step(a_bits: jax.Array, stripes: jax.Array,
                      expected_parity: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Full step: encode parity AND count bytes differing from
    expected_parity (the scrub check). Returns (parity, mismatches)."""
    parity = encode_batch(a_bits, stripes)
    mism = jnp.sum((parity != expected_parity).astype(jnp.int64))
    return parity, mism


def jitted_encode(k: int = 10, m: int = 4):
    """-> (fn, a_bits) with fn(a_bits, stripes) jitted."""
    a_bits = jnp.asarray(parity_bit_matrix(k, m), dtype=jnp.bfloat16)
    return jax.jit(encode_batch), a_bits


def sharded_encode_scrub(mesh, k: int = 10, m: int = 4):
    """The multi-chip training-step analogue: jit encode+scrub over a
    (vol, col) mesh. Stripes shard (batch->vol, cols->col); the scrub
    count all-reduces via the sharded sum (XLA inserts the psum).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import COL_AXIS, VOL_AXIS

    a_bits = jnp.asarray(parity_bit_matrix(k, m), dtype=jnp.bfloat16)
    data_sh = NamedSharding(mesh, P(VOL_AXIS, None, COL_AXIS))
    repl = NamedSharding(mesh, P())

    step = jax.jit(
        encode_scrub_step,
        in_shardings=(repl, data_sh, data_sh),
        out_shardings=(data_sh, repl),
    )
    return step, a_bits, data_sh


# ---------------------------------------------------------------------
# Host-feed pipeline (BASELINE configs #3 and #5)
#
# The jitted step above is device-side only; at volume scale the feed
# is the bottleneck. These entry points run the same depth-N staged
# pipeline as ops.codec_jax.JaxCodec.coded_matmul_stream — block j+1's
# H2D overlaps block j's kernel and block j-1's D2H — with the same
# per-stage ec_codec_stage_seconds observations, so Grafana attributes
# batched-encode and scrub time to pread/h2d/kernel/d2h/relay exactly
# like the codec path.
# ---------------------------------------------------------------------


def _staged_feed(blocks, upload, drain, depth: int, backend: str):
    """Shared pipeline skeleton: pread timing around the caller's
    generator, bounded deque of `depth` in-flight blocks, relay = time
    a finished result waited for the consumer. Yields drain results in
    input order."""
    import time
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    from ..ops.codec_jax import observe_stage, stage

    up_ex = ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix="ecfeed-h2d")
    down_ex = ThreadPoolExecutor(max_workers=1,
                                 thread_name_prefix="ecfeed-d2h")
    pending: deque = deque()

    def finish(fut):
        host, t_done = fut.result()
        observe_stage(backend, "relay", time.perf_counter() - t_done)
        return host

    it = iter(blocks)
    try:
        while True:
            try:
                with stage(backend, "pread"):
                    block = next(it)
            except StopIteration:
                break
            pending.append(down_ex.submit(drain, up_ex.submit(upload,
                                                              block)))
            while len(pending) >= max(1, depth):
                yield finish(pending.popleft())
        while pending:
            yield finish(pending.popleft())
    finally:
        up_ex.shutdown(wait=True, cancel_futures=True)
        down_ex.shutdown(wait=True, cancel_futures=True)


def pipelined_encode_stream(stripe_blocks, k: int = 10, m: int = 4,
                            depth: int = 2, mesh=None):
    """Batched-encode feed (config #3: 64x1GB volumes through the
    sidecar). `stripe_blocks` yields (B, k, n) uint8 host arrays;
    yields (B, m, n) np.uint8 parity blocks in order, bit-identical to
    encode_batch on the same input.

    With `mesh` (a parallel.mesh (vol, col) mesh) each block is
    zero-padded to the mesh grain (pad_to_mesh), scattered with one
    sharded device_put (batch over vol, columns over col) and the
    jitted step runs on every device; outputs are trimmed back to the
    caller's shape, so uneven volume tails ride the mesh unchanged."""
    import time

    from jax.sharding import SingleDeviceSharding

    from ..ops.codec_jax import _readback, stage

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import COL_AXIS, VOL_AXIS, pad_to_mesh

        a_bits = jnp.asarray(parity_bit_matrix(k, m), dtype=jnp.bfloat16)
        data_sh = NamedSharding(mesh, P(VOL_AXIS, None, COL_AXIS))
        repl = NamedSharding(mesh, P())
        fn = jax.jit(encode_batch, in_shardings=(repl, data_sh),
                     out_shardings=data_sh)
        a_bits = jax.device_put(a_bits, repl)
        sharding = data_sh
        backend = "ec_pipeline_mesh"
    else:
        fn, a_bits = jitted_encode(k, m)
        sharding = SingleDeviceSharding(jax.devices()[0])
        backend = "ec_pipeline"

    def upload(block):
        with stage(backend, "h2d"):
            block = np.ascontiguousarray(block)
            orig = None
            if mesh is not None:
                block, orig = pad_to_mesh(block, mesh)
            dev = jax.device_put(block, sharding)
            jax.block_until_ready(dev)
        return fn(a_bits, dev), orig

    def drain(up_fut):
        out, orig = up_fut.result()
        with stage(backend, "drain_wait"):
            jax.block_until_ready(out)
        with stage(backend, "d2h"):
            host = _readback(out)
            if orig is not None and \
                    (host.shape[0], host.shape[2]) != orig:
                host = np.ascontiguousarray(host[:orig[0], :, :orig[1]])
        return host, time.perf_counter()

    yield from _staged_feed(stripe_blocks, upload, drain, depth,
                            backend)


def pipelined_scrub(pair_blocks, k: int = 10, m: int = 4,
                    depth: int = 2, mesh=None) -> tuple[int, int]:
    """Cluster-scrub feed (config #5: RS parity verify over a volume
    fleet). `pair_blocks` yields (stripes, expected_parity) uint8 host
    pairs; returns (total_mismatched_bytes, n_blocks). Only the int64
    scrub scalar crosses back over the link per block, so the feed
    stays H2D/kernel bound — the honest shape for a read-mostly scrub.

    With `mesh`, each pair is zero-padded to the mesh grain and both
    tensors scatter over (vol, col); padding stripes encode to zero
    parity and the padded expected parity is also zero, so the psum'd
    mismatch count is untouched — `volume.scrub -all` saturates every
    local device with no caller-visible shape constraints."""
    import time

    from jax.sharding import SingleDeviceSharding

    from ..ops.codec_jax import stage

    if mesh is not None:
        from ..parallel.mesh import pad_to_mesh

        step, a_bits, data_sh = sharded_encode_scrub(mesh, k, m)
        sharding = data_sh
        backend = "ec_scrub_mesh"
    else:
        step = jax.jit(encode_scrub_step)
        a_bits = jnp.asarray(parity_bit_matrix(k, m),
                             dtype=jnp.bfloat16)
        sharding = SingleDeviceSharding(jax.devices()[0])
        backend = "ec_scrub"

    def upload(pair):
        stripes, expected = pair
        with stage(backend, "h2d"):
            stripes = np.ascontiguousarray(stripes)
            expected = np.ascontiguousarray(expected)
            if mesh is not None:
                stripes, _ = pad_to_mesh(stripes, mesh)
                expected, _ = pad_to_mesh(expected, mesh)
            dev_s = jax.device_put(stripes, sharding)
            dev_e = jax.device_put(expected, sharding)
            jax.block_until_ready((dev_s, dev_e))
        return step(a_bits, dev_s, dev_e)

    def drain(up_fut):
        _parity, mism = up_fut.result()
        with stage(backend, "drain_wait"):
            jax.block_until_ready(mism)
        with stage(backend, "d2h"):
            val = int(mism)
        return val, time.perf_counter()

    total = 0
    n = 0
    for val in _staged_feed(pair_blocks, upload, drain, depth, backend):
        total += val
        n += 1
    return total, n


def rebuild_mesh(n_devices: int | None = None):
    """1-D mesh over the `shard` axis: device i holds shard-rows i*k/d
    .. (i+1)*k/d — the layout that mirrors storage reality, where each
    shard lives on a different server/chip."""
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), ("shard",))


def sharded_rebuild(mesh, k: int = 10, m: int = 4,
                    present: list[int] | None = None,
                    missing: list[int] | None = None):
    """Distributed reconstruction with shard rows spread across the
    mesh — the framework's ring/all-to-all sequence-parallel analogue.

    Each device holds a row block of the (8k, n) bit expansion (its
    local shards); it computes the partial parity counts its rows
    contribute, and a reduce-scatter ring (lax.psum_scatter over the
    `shard` axis — XLA lowers it onto ICI as a ring) leaves every
    device with the finished column slice of the rebuilt shards. The
    mod-2 fold happens after the ring: integer partial counts sum
    exactly in int32, and total_count & 1 == XOR.

    Returns (step, a_pm) where step(a_pm, shards_rowsharded) ->
    rebuilt bytes, column-sharded. shards input: (k, n) uint8 with k
    divisible by the mesh size; n divisible by 8*mesh size.
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    if present is None or missing is None:
        missing = list(range(m))
        present = list(range(m, k + m))[:k]
    coef, _ = rs_matrix.recovery_rows(k, len(missing), present, missing)
    a_bits = gf256.expand_to_bits(coef)  # (8m', 8k)
    d = mesh.devices.size
    # granularity is BIT rows: the (8k, n) expansion shards over
    # devices, so 8k (80 for RS(10,4)) must divide — device
    # boundaries may cut across a byte's bit-planes, which is fine
    # because the dot contracts all of them
    assert (8 * k) % d == 0, f"{8 * k} bit rows over {d} devices"

    def step(a, local_bits_rows):
        # a: full (8m', 8k) replicated; local rows: (8k/d, n)
        i = jax.lax.axis_index("shard")
        rows_per = a.shape[1] // d
        a_block = jax.lax.dynamic_slice(
            a, (0, i * rows_per), (a.shape[0], rows_per))
        partial = jax.lax.dot_general(
            a_block, local_bits_rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        # reduce-scatter ring: sum partials, scatter columns
        total = jax.lax.psum_scatter(partial, "shard",
                                     scatter_dimension=1, tiled=True)
        return total & 1

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P("shard", None)),
        out_specs=P(None, "shard"))

    @jax.jit
    def rebuild(a, shards_u8):
        from ..ops.bits import pack_bits_uint8, unpack_bits_bf16

        bits = unpack_bits_bf16(shards_u8)       # (8k, n)
        out_bits = smapped(a, bits)              # (8m', n) col-sharded
        return pack_bits_uint8(out_bits)

    a_dev = jnp.asarray(a_bits, dtype=jnp.bfloat16)
    return rebuild, a_dev, coef
