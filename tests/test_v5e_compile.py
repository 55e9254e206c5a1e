"""Real-width compiles of the EC kernels for a described TPU v5e 2x2.

Nothing runs: the TPU compiler compiles each kernel for a chip that is
described, not attached, so a kernel the chip's compiler would refuse
(an unaligned slice, too much VMEM, a mesh that cannot partition)
fails here instead of on the chip. The topology is described inside a
module fixture, never at import, so every xdist worker collects the
same tests and only the worker that runs this file loads libtpu.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ops import bits, codec_pallas, rs_matrix

# PallasCodec's production slab, and a bulk block for the XLA kernel
PALLAS_COLS = 8 << 20
XLA_COLS = 2 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _parity(spec: str) -> np.ndarray:
    return rs_matrix.parity_rows_for(geo.parse_code(spec))


def _compile_pallas(one_chip, m, k, cols):
    compiled = codec_pallas.coded_matmul_pallas_pm_donated.lower(
        _spec((8 * m, 8 * k), jnp.bfloat16, one_chip),
        _spec((m, 8 * m), jnp.bfloat16, one_chip),
        _spec((k, cols), jnp.uint8, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the op carries the program's own name, which a device trace shows
    assert f"%{codec_pallas.KERNEL_NAME}." in text


# hh-10.4's encode is one (8, 20) coupled matmul over half-rows
@pytest.mark.parametrize("spec", ["10.4", "28.4", "lrc-12.3.2", "hh-10.4"])
def test_pallas_kernel(one_chip, spec):
    m, k = _parity(spec).shape
    _compile_pallas(one_chip, m, k, PALLAS_COLS)


# hh-10.4's single data-shard rebuild: 2 rows out of 13 or 14 half
# ranges, a 4 MiB chunk of each
@pytest.mark.parametrize("fanin", [13, 14])
def test_pallas_hitchhiker_reconstruct(one_chip, fanin):
    _compile_pallas(one_chip, 2, fanin, 4 << 20)


def test_xla_dense_kernel(one_chip):
    m, k = _parity("10.4").shape
    compiled = jax.jit(bits.coded_matmul_bits).lower(
        _spec((8 * m, 8 * k), jnp.bfloat16, one_chip),
        _spec((k, XLA_COLS), jnp.uint8, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_encode_scrub_2x2(topo):
    from seaweedfs_tpu.models.ec_pipeline import sharded_encode_scrub
    from seaweedfs_tpu.parallel.mesh import COL_AXIS, VOL_AXIS

    mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                (VOL_AXIS, COL_AXIS))
    k, m, batch, cols = 10, 4, 8, 8 << 20
    step, a_bits, data_sh = sharded_encode_scrub(mesh, k, m)
    compiled = step.lower(
        _spec(a_bits.shape, jnp.bfloat16, NamedSharding(mesh, P())),
        _spec((batch, k, cols), jnp.uint8, data_sh),
        _spec((batch, m, cols), jnp.uint8, data_sh)).compile()
    assert "all-reduce" in compiled.as_text()
