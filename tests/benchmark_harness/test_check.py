"""What `correct` compares: every volume the window sealed, and every
shard the harness deleted, whatever the program reports."""
from __future__ import annotations

import os
import types

import numpy as np
import pytest

from benchmark import check
from benchmark.traffic import Traffic

CONFIG = {"code": {"spec": "10.4", "k": 10, "local": 0, "global": 4},
          "large_block_bytes": 1 << 20, "small_block_bytes": 1 << 10}


@pytest.fixture()
def ref(tmp_path):
    dat = tmp_path / "ref.dat"
    np.random.default_rng(3).integers(0, 256, 25_000, dtype=np.uint8) \
        .tofile(dat)
    return str(dat), check.reference_shards(str(dat), CONFIG)


def _sealed(tmp_path, shards, vids) -> dict:
    out = {}
    for vid in vids:
        out[vid] = {}
        for sid, want in shards.items():
            path = tmp_path / f"{vid}.ec{sid:02d}"
            want.tofile(path)
            out[vid][sid] = str(path)
    return out


@pytest.mark.parametrize("bad_vid", [2, 3, 4])
def test_every_sealed_volume_is_compared(tmp_path, ref, bad_vid):
    dat, shards = ref
    t = types.SimpleNamespace(ref_dat=dat, jobs=[],
                              sealed_paths=_sealed(tmp_path, shards,
                                                   [2, 3, 4]))
    assert check.shard_checks(t, CONFIG)[0]["shard_bytes_wrong"] == 0
    path = t.sealed_paths[bad_vid][5]
    b = np.fromfile(path, dtype=np.uint8)
    b[7] ^= 1
    b.tofile(path)
    got, pairs = check.shard_checks(t, CONFIG)
    assert got["shard_bytes_wrong"] == 1
    assert len(pairs) == 3 * 14


def test_lost_shard_left_out_of_the_rebuild_is_wrong(tmp_path, ref):
    """The snapshot follows the shards the harness deleted: one the
    program neither rebuilt nor reported counts whole."""
    dat, shards = ref
    work = tmp_path / "w"
    work.mkdir()
    gen = Traffic({"jobs": "rebuild"}, CONFIG, 1, str(work))
    gen.ref_dat = dat
    holder = tmp_path / "srv"
    holder.mkdir()
    shards[3].tofile(holder / "1.ec03")
    dep = types.SimpleNamespace(
        holders=lambda vid: {3: ["u"]},  # shard 6 was never rebuilt
        shard_path=lambda vid, sid, url: str(holder / f"1.ec{sid:02d}"))
    snap = gen._snapshot(dep, [3, 6], 0)
    assert os.path.exists(snap[3]) and not os.path.exists(snap[6])
    gen.jobs = [{"op": "rebuild", "snap": snap, "rebuilt": [3]}]
    got, _ = check.shard_checks(gen, CONFIG)
    assert got["shard_bytes_wrong"] == shards[6].size


def _counters(by_backend: dict) -> dict:
    return {("ec_codec_bytes_total", (("backend", b), ("op", "encode"))): v
            for b, v in by_backend.items()}


# two encode jobs of 1,000 and 500 bytes in the window, the warm-up job
# outside it; the codec's counters as each case leaves them
SEAL = types.SimpleNamespace(reads=[], jobs=[
    {"op": "encode", "warm": True, "dat_bytes": 700, "end": 0.5},
    {"op": "encode", "dat_bytes": 1000, "end": 1.0},
    {"op": "encode", "dat_bytes": 500, "end": 2.0}])


@pytest.mark.parametrize("backend,coded,want", [
    # a concrete backend: the checks as they always were
    ("pallas", {"pallas": 1500}, {"offdevice_codec_bytes": 0,
                                  "device_short_bytes": 0}),
    ("pallas", {"pallas": 1000, "native": 500},
     {"offdevice_codec_bytes": 500, "device_short_bytes": 500}),
    # auto: any backend the router named moves the bytes
    ("auto", {"pallas": 1500}, {"device_short_bytes": 0}),
    ("auto", {"native": 1500}, {"device_short_bytes": 0}),
    ("auto", {"pallas": 900, "native": 600}, {"device_short_bytes": 0}),
    # bytes left under the unresolved label, and a job the codec skipped
    ("auto", {"auto": 1500}, {"device_short_bytes": 1500}),
    ("auto", {"pallas": 1000}, {"device_short_bytes": 500}),
], ids=["pallas", "pallas-offdevice", "auto-pallas", "auto-native",
        "auto-both", "auto-unresolved", "auto-skipped-job"])
def test_codec_checks(backend, coded, want):
    counters = _counters(coded)
    got = check.codec_checks(SEAL, counters, {"ec_backend": backend})
    assert got == want
    assert check.codec_bytes_by_backend(counters) == {
        b: {"encode": v} for b, v in coded.items()}


def test_offdevice_fault_under_auto_is_refused():
    from benchmark import faults
    from seaweedfs_tpu.ec import backend as ecb

    before = dict(ecb._instances)
    with pytest.raises(ValueError, match="no device guarantee"):
        faults.install("offdevice", {"ec_backend": "auto"})
    assert ecb._instances == before
