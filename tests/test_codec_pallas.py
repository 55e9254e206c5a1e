"""Pallas fused codec kernel: bit-exactness against the numpy codec
and the backend plumbing. Runs in pallas interpret mode so it works on
the CPU test mesh; the real-TPU path is exercised by the benchmark and
chip_smoke.py (kernel: seaweedfs_tpu/ops/codec_pallas.py).
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from seaweedfs_tpu.ops import codec_numpy, codec_pallas, gf256, rs_matrix


def pm_mats(coef):
    bits = gf256.expand_to_bits(coef)
    return (codec_pallas.plane_major_bit_matrix(bits),
            codec_pallas.packing_matrix(coef.shape[0]))


class TestKernelExactness:
    def run_pallas(self, coef, data):
        a_pm, pack = pm_mats(coef)
        return np.asarray(codec_pallas.coded_matmul_pallas_pm(
            a_pm, pack, jnp.asarray(data), interpret=True))

    def test_encode_parity_exact(self):
        rng = np.random.default_rng(1)
        coef = rs_matrix.encode_matrix(10, 4)[10:]
        data = rng.integers(0, 256, (10, codec_pallas.COL_TILE),
                            dtype=np.uint8)
        assert np.array_equal(self.run_pallas(coef, data),
                              codec_numpy.coded_matmul(coef, data))

    def test_rebuild_matrix_exact(self):
        rng = np.random.default_rng(2)
        present = [i for i in range(14) if i not in (1, 4, 11, 13)]
        coef, _ = rs_matrix.recovery_rows(10, 4, present,
                                          [1, 4, 11, 13])
        data = rng.integers(0, 256, (10, codec_pallas.COL_TILE),
                            dtype=np.uint8)
        assert np.array_equal(self.run_pallas(coef, data),
                              codec_numpy.coded_matmul(coef, data))

    def test_wide_code(self):
        rng = np.random.default_rng(3)
        coef = rs_matrix.encode_matrix(28, 4)[28:]
        data = rng.integers(0, 256, (28, codec_pallas.COL_TILE),
                            dtype=np.uint8)
        assert np.array_equal(self.run_pallas(coef, data),
                              codec_numpy.coded_matmul(coef, data))

    def test_plane_major_permutation_roundtrip(self):
        coef = rs_matrix.encode_matrix(5, 3)[5:]
        bits = gf256.expand_to_bits(coef)
        pm = np.asarray(codec_pallas.plane_major_bit_matrix(bits),
                        dtype=np.float32)
        k = coef.shape[1]
        # column s*k + j of pm == column 8*j + s of the bit-minor matrix
        for s in range(8):
            for j in range(k):
                assert np.array_equal(pm[:, s * k + j],
                                      bits[:, 8 * j + s].astype(
                                          np.float32))


class TestBackendPlumbing:
    def test_registered(self):
        from seaweedfs_tpu.ec.backend import backend_names
        assert "pallas" in backend_names()

    def test_codec_pads_and_slices(self, monkeypatch):
        # interpret mode so this runs on the CPU mesh
        real = codec_pallas.coded_matmul_pallas_pm

        def interp(a_pm, pack, shards, interpret=False):
            return real(a_pm, pack, shards, interpret=True)

        monkeypatch.setattr(codec_pallas, "coded_matmul_pallas_pm",
                            interp)
        codec = codec_pallas.PallasCodec()
        rng = np.random.default_rng(4)
        coef = rs_matrix.encode_matrix(10, 4)[10:]
        data = rng.integers(0, 256, (10, 1000), dtype=np.uint8)  # !%4096
        out = codec.coded_matmul(coef, data)
        assert out.shape == (4, 1000)
        assert np.array_equal(out,
                              codec_numpy.coded_matmul(coef, data))
