"""EC file-level golden tests — the port of the reference's
TestEncodingDecoding semantics (/root/reference/weed/storage/
erasure_coding/ec_test.go:21): encode a real volume fixture, validate
shard-interval reads against whole-file reads, rebuild lost shards
bit-for-bit, and round-trip decode. Uses small block sizes so the
large/small region transition is exercised without GB files.
"""
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ec.backend import ReedSolomon
from seaweedfs_tpu.ec.decoder import write_dat_file
from seaweedfs_tpu.ec.encoder import (rebuild_ec_files, verify_ec_files,
                                      write_ec_files, write_sorted_ecx)
from seaweedfs_tpu.storage import idx as idxmod
from seaweedfs_tpu.storage import needle as ndl
from seaweedfs_tpu.storage.volume import Volume

LB = 4096   # test large block
SB = 512    # test small block


@pytest.fixture()
def fixture_volume(tmp_path):
    """A real volume with a few hundred needles, as the golden input."""
    v = Volume(str(tmp_path), "", 7, create=True)
    rng = np.random.default_rng(1234)
    for i in range(300):
        size = int(rng.integers(1, 400))
        v.append_needle(ndl.Needle(id=i + 1, cookie=int(rng.integers(0, 2**32)),
                                   data=rng.bytes(size)))
    v.close()
    return str(tmp_path / "7")


def _encode(base, backend="numpy"):
    write_ec_files(base, backend=backend, large_block=LB, small_block=SB,
                   chunk=2048)


class TestRowLayout:
    def test_small_only(self):
        n_large, n_small = geo.row_layout(100, LB, SB)
        assert n_large == 0 and n_small == 1

    def test_exact_small_row(self):
        assert geo.row_layout(SB * 10, LB, SB) == (0, 1)
        assert geo.row_layout(SB * 10 + 1, LB, SB) == (0, 2)

    def test_large_transition(self):
        # == one large row stays small (reference's strict >)
        assert geo.row_layout(LB * 10, LB, SB)[0] == 0
        assert geo.row_layout(LB * 10 + 1, LB, SB)[0] == 1

    def test_shard_size(self):
        dat = LB * 10 + SB * 3 + 17
        n_large, n_small = geo.row_layout(dat, LB, SB)
        assert geo.shard_file_size(dat, LB, SB) == n_large * LB + n_small * SB


class TestLocate:
    """Interval math vs a brute-force shard-layout simulation
    (reference TestLocateData, ec_test.go:192)."""

    @pytest.mark.parametrize("dat_size", [1, 100, SB * 10, SB * 10 + 1,
                                          LB * 10 + 1, LB * 10 + SB * 7 + 99,
                                          LB * 20 + 5])
    def test_locate_against_simulation(self, dat_size):
        rng = np.random.default_rng(dat_size)
        dat = rng.integers(0, 256, dat_size).astype(np.uint8)
        shards = _simulate_shards(dat, LB, SB)
        for _ in range(20):
            off = int(rng.integers(0, dat_size))
            size = int(rng.integers(1, min(3 * SB, dat_size - off) + 1))
            got = bytearray()
            for iv in geo.locate(dat_size, off, size, LB, SB):
                sid, s_off = iv.to_shard_and_offset(LB, SB)
                got += shards[sid][s_off:s_off + iv.size].tobytes()
            assert bytes(got) == dat[off:off + size].tobytes(), (off, size)


def _simulate_shards(dat: np.ndarray, lb: int, sb: int) -> list[np.ndarray]:
    """Brute-force the encode layout: walk rows exactly like the encoder
    and slice blocks into shard buffers."""
    n_large, n_small = geo.row_layout(len(dat), lb, sb)
    shard_len = n_large * lb + n_small * sb
    shards = [np.zeros(shard_len, dtype=np.uint8) for _ in range(10)]
    pos = 0
    out_off = 0
    for block, rows in ((lb, n_large), (sb, n_small)):
        for _ in range(rows):
            for i in range(10):
                chunk = dat[pos:pos + block]
                shards[i][out_off:out_off + len(chunk)] = chunk
                pos += block
            out_off += block
    return shards


class TestEncodeRebuildDecode:
    def test_shard_reads_match_dat(self, fixture_volume):
        base = fixture_volume
        _encode(base)
        dat_size = os.path.getsize(base + ".dat")
        dat = np.fromfile(base + ".dat", dtype=np.uint8)
        shards = [np.fromfile(base + geo.shard_ext(i), dtype=np.uint8)
                  for i in range(10)]
        assert all(len(s) == geo.shard_file_size(dat_size, LB, SB)
                   for s in shards)
        rng = np.random.default_rng(0)
        for _ in range(50):
            off = int(rng.integers(0, dat_size))
            size = int(rng.integers(1, min(2000, dat_size - off) + 1))
            got = bytearray()
            for iv in geo.locate(dat_size, off, size, LB, SB):
                sid, s_off = iv.to_shard_and_offset(LB, SB)
                got += shards[sid][s_off:s_off + iv.size].tobytes()
            assert bytes(got) == dat[off:off + size].tobytes()

    def test_parity_verifies(self, fixture_volume):
        _encode(fixture_volume)
        assert verify_ec_files(fixture_volume, chunk=2048)

    def test_rebuild_bit_for_bit(self, fixture_volume):
        base = fixture_volume
        _encode(base)
        originals = {i: open(base + geo.shard_ext(i), "rb").read()
                     for i in range(14)}
        # destroy 4 shards (2 data, 2 parity)
        for i in (0, 7, 10, 13):
            os.remove(base + geo.shard_ext(i))
        rebuilt = rebuild_ec_files(base, chunk=1536)
        assert sorted(rebuilt) == [0, 7, 10, 13]
        for i in (0, 7, 10, 13):
            assert open(base + geo.shard_ext(i), "rb").read() == originals[i], i

    def test_rebuild_too_many_missing(self, fixture_volume):
        base = fixture_volume
        _encode(base)
        for i in range(5):
            os.remove(base + geo.shard_ext(i))
        # 9 shards left < 10
        with pytest.raises(ValueError):
            rebuild_ec_files(base)

    def test_decode_back_to_dat(self, fixture_volume):
        base = fixture_volume
        _encode(base)
        original = open(base + ".dat", "rb").read()
        os.remove(base + ".dat")
        os.remove(base + geo.shard_ext(3))  # also exercise rebuild-on-decode
        write_dat_file(base, len(original), LB, SB)
        assert open(base + ".dat", "rb").read() == original

    def test_needle_reads_through_shards(self, fixture_volume):
        """End-to-end: locate each indexed needle in the shards and parse
        it — the EC read path's core loop (store_ec.go:136)."""
        base = fixture_volume
        _encode(base)
        write_sorted_ecx(base)
        dat_size = os.path.getsize(base + ".dat")
        shards = [np.fromfile(base + geo.shard_ext(i), dtype=np.uint8)
                  for i in range(10)]
        from seaweedfs_tpu.storage import types as t
        count = 0
        for e in idxmod.iter_entries(base + ".ecx"):
            if not t.size_is_valid(e.size):
                continue
            disk = ndl.disk_size(e.size)
            got = bytearray()
            for iv in geo.locate(dat_size, t.offset_to_actual(e.offset),
                                 disk, LB, SB):
                sid, s_off = iv.to_shard_and_offset(LB, SB)
                got += shards[sid][s_off:s_off + iv.size].tobytes()
            n = ndl.Needle.from_bytes(bytes(got))
            assert n.id == e.key
            count += 1
        assert count == 300

    def test_jax_backend_encode_identical(self, fixture_volume, tmp_path):
        """CPU and device (pallas) backends must produce byte-identical
        shards."""
        base = fixture_volume
        _encode(base, backend="numpy")
        cpu_shards = {i: open(base + geo.shard_ext(i), "rb").read()
                      for i in range(14)}
        for i in range(14):
            os.remove(base + geo.shard_ext(i))
        _encode(base, backend="pallas")
        for i in range(14):
            assert open(base + geo.shard_ext(i), "rb").read() == \
                cpu_shards[i], i
