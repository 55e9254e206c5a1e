"""Wide-code EC tier: RS(28,4) volumes end-to-end (beyond-reference,
BASELINE config #4 / VERDICT round-2 item 8).

The reference hard-codes RS(10,4); here `ec.encode -codec=28.4` encodes
cold volumes at 1/7th the parity overhead, with the same geometry math
parameterized by stripe width and the codec recorded in the .vif
sidecar so every consumer (mount, rebuild, degraded read, decode)
agrees.
"""
import os
import secrets
import time

import numpy as np
import pytest
import requests

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.ec.backend import ReedSolomon
from seaweedfs_tpu.ec.encoder import (codec_of, rebuild_ec_files,
                                      verify_ec_files, write_ec_files)
from seaweedfs_tpu.operation import verbs
from seaweedfs_tpu.server.cluster import Cluster
from seaweedfs_tpu.shell import commands_ec
from seaweedfs_tpu.shell.env import CommandEnv
from seaweedfs_tpu.shell.repl import run_command
from seaweedfs_tpu.utils import metrics


# ---------------------------------------------------------------------
# geometry + file level
# ---------------------------------------------------------------------

def test_parse_codec():
    assert geo.parse_codec("") == (10, 4)
    assert geo.parse_codec("28.4") == (28, 4)
    with pytest.raises(ValueError):
        geo.parse_codec("30.4")  # > uint32 shard mask
    with pytest.raises(ValueError):
        geo.parse_codec("0.4")


def test_wide_locate_round_trip():
    # every byte of a 28-wide stripe maps to the right shard/offset
    k = 28
    dat_size = k * (1 << 14) * 3 + 12345
    small = 1 << 14
    for off in (0, small - 1, small * k, dat_size - 1):
        ivs = geo.locate(dat_size, off, 1, large_block=1 << 20,
                         small_block=small, data_shards=k)
        assert len(ivs) == 1
        sid, shard_off = ivs[0].to_shard_and_offset(
            large_block=1 << 20, small_block=small)
        assert 0 <= sid < k
        # block b of row r belongs to shard b%k at row-offset r*small
        row, block = divmod(off, small * k)
        assert sid == block // small
        assert shard_off == row * small + off % small


def test_wide_write_rebuild_verify_files(tmp_path):
    rng = np.random.default_rng(21)
    base = str(tmp_path / "9")
    payload = rng.bytes(3 << 20)
    (tmp_path / "9.dat").write_bytes(payload)
    write_ec_files(base, backend="numpy", codec="28.4",
                   large_block=1 << 20, small_block=1 << 14,
                   chunk=1 << 18)
    assert codec_of(base) == (28, 4)
    assert all(os.path.exists(base + geo.shard_ext(i)) for i in range(32))
    assert not os.path.exists(base + geo.shard_ext(32))
    # drop 4 shards (max tolerable) and rebuild bit-exact
    golden = {i: open(base + geo.shard_ext(i), "rb").read()
              for i in (0, 13, 29, 31)}
    for i in golden:
        os.unlink(base + geo.shard_ext(i))
    assert sorted(rebuild_ec_files(base, backend="numpy",
                                   chunk=1 << 18)) == [0, 13, 29, 31]
    for i, want in golden.items():
        assert open(base + geo.shard_ext(i), "rb").read() == want
    assert verify_ec_files(base, backend="numpy", chunk=1 << 18)

    # data shards concatenate back to the original bytes
    k = 28
    n_large, n_small = geo.row_layout(len(payload), 1 << 20, 1 << 14, k)
    out = bytearray()
    for r in range(n_small):
        for i in range(k):
            shard = open(base + geo.shard_ext(i), "rb").read()
            out += shard[r << 14:(r + 1) << 14]
    assert bytes(out[:len(payload)]) == payload


def test_wide_code_parity_matches_reed_solomon(tmp_path):
    # the shard files ARE RS(28,4) codewords column-by-column
    rng = np.random.default_rng(22)
    base = str(tmp_path / "5")
    (tmp_path / "5.dat").write_bytes(rng.bytes(1 << 20))
    write_ec_files(base, backend="numpy", codec="28.4",
                   large_block=1 << 20, small_block=1 << 14)
    shards = np.stack([np.frombuffer(
        open(base + geo.shard_ext(i), "rb").read(), dtype=np.uint8)
        for i in range(32)])
    assert ReedSolomon(28, 4, backend="numpy").verify(shards)


# ---------------------------------------------------------------------
# cluster e2e: encode -> spread -> degraded read -> rebuild
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = Cluster(str(tmp_path_factory.mktemp("wide_ec")),
                n_volume_servers=3, volume_size_limit=4 << 20,
                max_volumes=60)
    yield c
    c.stop()


def test_wide_encode_spread_degraded_read(cluster):
    env = CommandEnv(cluster.master_url)
    env.acquire_lock()
    try:
        col = "wide" + secrets.token_hex(3)
        rng = np.random.default_rng(23)
        a = verbs.assign(cluster.master_url, collection=col)
        vid = int(a.fid.split(",")[0])
        payloads = {a.fid: rng.bytes(120_000)}
        verbs.upload(a, payloads[a.fid])
        for _ in range(10):
            b = verbs.assign(cluster.master_url, collection=col)
            if int(b.fid.split(",")[0]) != vid:
                continue
            payloads[b.fid] = rng.bytes(int(rng.integers(500, 60_000)))
            verbs.upload(b, payloads[b.fid])

        placement = run_command(
            env, f"ec.encode -volumeId={vid} -codec=28.4")
        assert len(placement) == 32
        # master learned the codec from the heartbeat
        assert env.ec_codec(vid) == (28, 4)

        # the codec record survives the source-volume delete ON DISK:
        # a restarted server must re-derive (28, 4), not the default
        # (round-2 review: Volume.destroy used to unlink the .vif)
        from seaweedfs_tpu.ec.volume import EcVolume

        srv_ecv = next(s.store.ec_volumes[vid]
                       for s in cluster.volume_servers
                       if vid in s.store.ec_volumes)
        fresh = EcVolume(srv_ecv.dir, srv_ecv.collection, vid)
        assert (fresh.k, fresh.m) == (28, 4)
        fresh.close()

        # reads through any holder (local + remote shard fetch)
        locs = env.ec_shard_locations(vid)
        holder = locs[0][0]
        for fid, data in payloads.items():
            r = requests.get(f"http://{holder}/{fid}", timeout=30)
            assert r.status_code == 200, (fid, r.text)
            assert r.content == data

        # lose 4 shards (max tolerable for m=4) -> degraded reads OK
        for sid in (2, 11, 28, 31):
            for url in locs.get(sid, []):
                env.vs_post(url, "/admin/ec/delete",
                            {"volume": vid, "shard_ids": [sid]})
        for fid, data in payloads.items():
            r = requests.get(f"http://{holder}/{fid}", timeout=60)
            assert r.status_code == 200, (fid, r.text)
            assert r.content == data

        # ec.rebuild restores the full 32-shard set
        out = commands_ec.ec_rebuild(env, vid)
        assert sorted(out["rebuilt"]) == [2, 11, 28, 31]
        assert commands_ec.ec_verify(env, vid)["verified"]
    finally:
        env.close()


# ---------------------------------------------------------------------
# eight failure domains: a whole server lost, rebuilt where it was
# ---------------------------------------------------------------------

def _counter(name):
    return metrics._counters.get((name, ()), 0.0)


def test_wide_server_loss_rebuilt_on_the_server_that_lost_it(tmp_path):
    # 8 servers in 8 racks: ceil(32/4) failure domains, 4 shards each
    cluster = Cluster(str(tmp_path), n_volume_servers=8,
                      volume_size_limit=64 << 20, max_volumes=8,
                      topology=[("dc1", f"rack{i}") for i in range(8)])
    env = CommandEnv(cluster.master_url)
    env.acquire_lock()
    try:
        # 30 MiB of needles: more than one 28 x 1 MiB stripe row, so
        # every data shard holds data
        rng = np.random.default_rng(25)
        a = verbs.assign(cluster.master_url, count=30, collection="cold")
        vid = int(a.fid.split(",")[0])
        for i in range(30):
            fid = a.fid if i == 0 else f"{a.fid}_{i}"
            verbs.upload(f"http://{a.url}/{fid}", rng.bytes(1 << 20))
        run_command(env, f"ec.encode -volumeId={vid} -codec=28.4")

        def shard_paths():
            out = {}
            for sid, urls in env.ec_full_info(vid)[2].items():
                store = next(s for s in cluster.stores
                             if s.public_url == urls[0])
                out[sid] = (urls[0],
                            store.ec_volumes[vid].shards[sid].path)
            return out

        before = shard_paths()
        by_url: dict[str, list[int]] = {}
        for sid, (url, _) in before.items():
            by_url.setdefault(url, []).append(sid)
        assert sorted(len(v) for v in by_url.values()) == [4] * 8
        dead = before[0][0]
        lost = sorted(by_url[dead])
        golden = {sid: open(before[sid][1], "rb").read() for sid in lost}
        shard_size = len(golden[lost[0]])

        env.vs_post(dead, "/admin/ec/delete",
                    {"volume": vid, "shard_ids": lost})
        deadline = time.monotonic() + 30
        while set(lost) & set(env.ec_full_info(vid)[2]):
            assert time.monotonic() < deadline, "the loss never showed"
            time.sleep(0.05)

        ranges0 = _counter("ec_fetch_fanout_ranges_total")
        queued0 = _counter("ec_fetch_fanout_queued_total")
        out = commands_ec.ec_rebuild(env, vid)
        assert out["mode"] == "partial"
        assert out["rebuilder"] == dead
        assert sorted(out["rebuilt"]) == lost

        after = shard_paths()
        assert all(after[sid][0] == dead for sid in lost)
        for sid in lost:
            assert open(after[sid][1], "rb").read() == golden[sid]
        # the whole 32-shard set is an RS(28,4) codeword of the numpy
        # oracle codec
        stack = np.stack([np.fromfile(after[sid][1], dtype=np.uint8)
                          for sid in range(32)])
        assert ReedSolomon(28, 4, backend="numpy").verify(stack)
        # the rebuilder holds none of the volume's shards: each 4 MiB
        # chunk asks all 28 survivors at once, none waiting for a worker
        chunks = -(-shard_size // (4 << 20))
        assert _counter("ec_fetch_fanout_ranges_total") - ranges0 == \
            28 * chunks
        assert _counter("ec_fetch_fanout_queued_total") - queued0 == 0
    finally:
        env.close()
        cluster.stop()


def test_reencode_default_clears_stale_codec(tmp_path):
    # encode wide -> wipe shards (decode analog) -> re-encode default:
    # the stale .vif marker must be cleared (round-2 review finding)
    rng = np.random.default_rng(24)
    base = str(tmp_path / "4")
    (tmp_path / "4.dat").write_bytes(rng.bytes(1 << 20))
    write_ec_files(base, backend="numpy", codec="28.4",
                   large_block=1 << 20, small_block=1 << 14)
    assert codec_of(base) == (28, 4)
    for i in range(32):
        os.unlink(base + geo.shard_ext(i))
    write_ec_files(base, backend="numpy",
                   large_block=1 << 20, small_block=1 << 14)
    assert codec_of(base) == (10, 4)
    assert verify_ec_files(base, backend="numpy")
