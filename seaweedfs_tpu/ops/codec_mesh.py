"""Mesh codec: the multichip dryrun promoted to a production backend.

`-ec.backend=mesh` runs the GF(256) bit-plane coded matmul sharded over
every local device: the (k, n) column block a caller hands any codec
backend is split into `vol` column segments (the data-parallel batch
axis of `parallel/mesh.py`) and each segment's columns shard over the
`col` (sequence-parallel) axis, so one jitted dispatch — compiled with
explicit `NamedSharding`s, the pjit pattern from SNIPPETS.md [1]–[3] —
keeps all chips busy. Encode and reconstruction are column-local, so
there are no collectives in the hot path and throughput scales
near-linearly with device count until the host↔device link is the wall
(which the mesh rows of `ec/probe.py` measure rather than assume).

Geometry comes from `parallel.mesh.make_mesh`: `{'vol': 4, 'col': 2}`
on 8 devices by default, overridable with `-ec.mesh.devices` /
`-ec.mesh.col` (env `SEAWEEDFS_TPU_EC_MESH_DEVICES` /
`SEAWEEDFS_TPU_EC_MESH_COL`). Wide codes (RS(28,4)+) are first-class:
the coefficient matrix is a runtime argument exactly as in the
single-chip codec, so `ec.encode -codec=28.4` volumes ride the same
compiled kernel shape and amortize the per-byte transfer cost over
2.8x more data bytes per parity byte.

The streaming entry point mirrors `PallasCodec.coded_matmul_stream`:
a depth-N staged pipeline (upload thread committing the sharded
device_put, kernel, drain thread gathering the result) with the same
ec_codec_stage_seconds{stage,backend="mesh"} attribution.
"""
from __future__ import annotations

import time as _time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics
from . import gf256
from .feed import observe_stage, stage

# Per-vol-segment column widths are padded up to power-of-two buckets
# (>= this) so repeated uneven blocks share a handful of XLA compiles,
# the mesh's counterpart of PallasCodec's COL_TILE padding.
BUCKET_MIN = 256


def _mesh_kernel(a_bits: jax.Array, stripes: jax.Array) -> jax.Array:
    """(8m, 8k) bf16 bit-matrix x (vol, k, w) uint8 -> (vol, m, w)
    uint8. Batch and column dims are embarrassingly parallel, so with
    stripes sharded (vol -> 'vol', w -> 'col') every device computes
    its slice locally — no collectives."""
    from .bits import pack_bits_uint8, unpack_bits_bf16

    bits = unpack_bits_bf16(stripes)                    # (vol, 8k, w)
    acc = jnp.einsum("st,btn->bsn", a_bits, bits,
                     preferred_element_type=jnp.float32)
    return pack_bits_uint8(acc.astype(jnp.int32) & 1)


class MeshCodec:
    """Coded-matmul backend sharded over the local (vol, col) mesh."""

    name = "mesh"

    BITMAT_CACHE_MAX = 256

    def __init__(self, mesh=None, bucket_min: int = BUCKET_MIN):
        from ..parallel import mesh as pmesh

        if mesh is None:
            n_devices, col = pmesh.mesh_config()
            mesh = pmesh.make_mesh(n_devices, col)
        self.mesh = mesh
        self.vol, self.col = (int(x) for x in mesh.devices.shape)
        self.n_devices = int(mesh.devices.size)
        self.bucket_min = max(1, int(bucket_min))
        self._data_sh = pmesh.stripe_sharding(mesh)
        self._repl = pmesh.replicated(mesh)
        self._bitmats: "OrderedDict[bytes, jax.Array]" = OrderedDict()
        self._fn = None
        self._donate = mesh.devices.flat[0].platform != "cpu"
        metrics.gauge_set("ec_mesh_devices", self.n_devices)
        metrics.gauge_set("ec_mesh_vol", self.vol)
        metrics.gauge_set("ec_mesh_col", self.col)

    # -- introspection --------------------------------------------------

    def describe(self) -> dict:
        from ..parallel import mesh as pmesh

        return pmesh.describe(self.mesh)

    # -- compiled step / coefficient cache ------------------------------

    def _step(self):
        if self._fn is None:
            self._fn = jax.jit(
                _mesh_kernel,
                in_shardings=(self._repl, self._data_sh),
                out_shardings=self._data_sh,
                donate_argnums=(1,) if self._donate else ())
        return self._fn

    def _coef_bits(self, coef: np.ndarray) -> jax.Array:
        key = coef.shape[0].to_bytes(2, "big") + coef.tobytes()
        bm = self._bitmats.get(key)
        if bm is None:
            bm = jax.device_put(
                jnp.asarray(gf256.expand_to_bits(coef),
                            dtype=jnp.bfloat16), self._repl)
            self._bitmats[key] = bm
            if len(self._bitmats) > self.BITMAT_CACHE_MAX:
                self._bitmats.popitem(last=False)
        else:
            self._bitmats.move_to_end(key)
        return bm

    # -- host-side layout -----------------------------------------------

    def _seg_width(self, n: int) -> int:
        """Per-vol-segment width for n columns: divides `col` (the
        NamedSharding requirement), bucket-padded to bound compiles."""
        grain = self.vol * self.col
        per = -(-n // grain) * self.col
        bucket = self.bucket_min
        while bucket < per:
            bucket <<= 1
        # re-round after bucketing: a non-power-of-two col axis must
        # still divide the padded width
        return -(-bucket // self.col) * self.col

    def _to_batched(self, shards: np.ndarray) -> tuple[np.ndarray, int]:
        """(k, n) -> (vol, k, per) with zero padding; segment v holds
        columns [v*per, (v+1)*per). Zero columns encode/reconstruct to
        zero columns, sliced off on the way back."""
        k, n = shards.shape
        per = self._seg_width(n)
        total = per * self.vol
        if total != n:
            padded = np.zeros((k, total), dtype=np.uint8)
            padded[:, :n] = shards
        else:
            padded = np.asarray(shards, dtype=np.uint8)
        return np.ascontiguousarray(
            padded.reshape(k, self.vol, per).transpose(1, 0, 2)), per

    def _from_batched(self, out: np.ndarray, n: int) -> np.ndarray:
        """(vol, m, per) device result -> (m, n) host block."""
        vol, m, per = out.shape
        res = out.transpose(1, 0, 2).reshape(m, vol * per)
        return np.ascontiguousarray(res[:, :n]) if vol * per != n \
            else res

    def _h2d(self, batched: np.ndarray) -> jax.Array:
        """Committed sharded placement: one device_put against the
        explicit NamedSharding scatters the host block across every
        device and pins it there."""
        return jax.device_put(batched, self._data_sh)

    # -- codec API ------------------------------------------------------

    def coded_matmul(self, coef: np.ndarray, shards) -> np.ndarray:
        coef = np.asarray(coef, dtype=np.uint8)
        m, k = coef.shape
        shards = np.asarray(shards, dtype=np.uint8)
        assert shards.ndim == 2 and shards.shape[0] == k, shards.shape
        n = shards.shape[1]
        if n == 0:
            return np.zeros((m, 0), dtype=np.uint8)
        mats = self._coef_bits(coef)
        batched, _per = self._to_batched(shards)
        out = self._step()(mats, self._h2d(batched))
        return self._from_batched(np.asarray(out), n)

    def coded_matmul_stream(self, coef: np.ndarray, blocks,
                            depth: int = 2):
        """Depth-N staged pipeline over the mesh: while the drain
        thread gathers block j-1 from all devices, the devices run
        block j's sharded kernel and the upload thread scatters block
        j+1 — the same schedule as the single-chip feed, with the
        whole mesh behind each stage. Stages record
        ec_codec_stage_seconds{stage,backend="mesh"}."""
        from collections import deque
        from concurrent.futures import Future, ThreadPoolExecutor

        coef = np.asarray(coef, dtype=np.uint8)
        m = coef.shape[0]
        mats = self._coef_bits(coef)
        depth = max(1, int(depth))
        backend = self.name

        def upload(block: np.ndarray):
            with stage(backend, "h2d"):
                batched, _per = self._to_batched(block)
                dev = self._h2d(batched)
                dev.block_until_ready()
            return self._step()(mats, dev)

        def drain(up_fut, n: int):
            out = up_fut.result()
            with stage(backend, "drain_wait"):
                out.block_until_ready()
            with stage(backend, "d2h"):
                arr = self._from_batched(np.asarray(out), n)
            return arr, _time.perf_counter()

        up_ex = ThreadPoolExecutor(1, thread_name_prefix="ecmesh-h2d")
        down_ex = ThreadPoolExecutor(1, thread_name_prefix="ecmesh-d2h")

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
                    # empty block still rides the queue so ordering
                    # holds (same contract as PallasCodec's stream)
                    f: Future = Future()
                    f.set_result((np.zeros((m, 0), dtype=np.uint8),
                                  _time.perf_counter()))
                    pending.append(f)
                else:
                    up = up_ex.submit(upload, block)
                    pending.append(
                        down_ex.submit(drain, up, block.shape[1]))
                while len(pending) >= depth:
                    yield finish(pending.popleft())
            while pending:
                yield finish(pending.popleft())
        finally:
            up_ex.shutdown(wait=True, cancel_futures=True)
            down_ex.shutdown(wait=True, cancel_futures=True)
