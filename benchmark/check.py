"""What decides `correct`: the timed path's answers against the plain
reference, byte for byte, after the window. The reference is
reference.py, or, where a configuration brings its own,
benchmark/references/<config name>.py (found by spec.reference).

Every number compared has the limit 0, because GF(256) is exact and a
read returns the stored bytes or it does not:

- shard_bytes_wrong: bytes of the shards the window produced that
  differ from the reference, a missing byte counting as wrong: every
  shard of every volume the window sealed (the pool's volumes are
  copies of one .dat, so the reference is made once), and each shard
  the harness deleted as the rebuild job after it left it, whether or
  not the program says it rebuilt it;
- shards_unmounted: shards of a volume the window touched that the
  master does not list, or that their server does not hold mounted;
- reads_wrong, reads_failed: reads whose bytes differ from what was
  written, and reads that got no answer;
- jobs_failed: jobs that raised;
- offdevice_codec_bytes: bytes of the jobs' coding done by a codec
  other than the configured device codec;
- device_short_bytes: bytes the device codec was due to move and did
  not, counting as due at least each encoded .dat and each rebuilt
  shard.

Under `ec_backend: auto` the router may rightly choose the CPU codec,
so there is no device guarantee to hold: offdevice_codec_bytes is not
a check, and device_short_bytes counts the due bytes that no concrete
backend moved (a job the codec skipped, or bytes left under the
unresolved label `auto`). The window's coded bytes by backend and op
go in the run's line as information.
"""
from __future__ import annotations

import hashlib
import os

import numpy as np

from . import reference, spec
from .deploy import total


def shard_diff(path: str, want: np.ndarray) -> int:
    """Bytes of the file at `path` that differ from `want`."""
    if not os.path.exists(path):
        return int(want.size)
    got = np.fromfile(path, dtype=np.uint8)
    n = min(got.size, want.size)
    return int(np.count_nonzero(got[:n] != want[:n])) + \
        abs(got.size - want.size)


def reference_shards(dat_path: str, config: dict,
                     which: list[int] | None = None,
                     root: str = spec.ROOT) -> dict:
    """Reference bytes of the shards in `which` of the .dat at
    `dat_path`: the configuration's own reference where it brings one,
    else reference.py's."""
    shards = spec.reference(config.get("name", ""), root) or \
        reference.shards
    dat = np.fromfile(dat_path, dtype=np.uint8)
    return shards(dat, config["code"], config["large_block_bytes"],
                  config["small_block_bytes"], which)


def codec_bytes_by_backend(counters: dict) -> dict:
    """{backend: {op: bytes}} of the window's coding, under the labels
    the codec recorded: information, compared with no limit."""
    out: dict = {}
    for (name, labels), v in counters.items():
        if name == "ec_codec_bytes_total" and v:
            lab = dict(labels)
            by_op = out.setdefault(lab["backend"], {})
            by_op[lab["op"]] = by_op.get(lab["op"], 0.0) + v
    return out


def codec_checks(traffic, counters: dict, config: dict) -> dict:
    """Which codec moved the jobs' bytes, from the window's counters.
    Single-needle reads reconstruct on the CPU codec by design, so in
    a mix with reads the off-device count is not taken for rebuilds.
    Under `auto` the bytes any concrete backend moved count as coded."""
    auto = config["ec_backend"] == "auto"
    ops = {"encode": "encode", "rebuild": "reconstruct"}
    out = {}
    job_ops = {j["op"] for j in traffic.jobs if not j.get("warm")}
    off = short = 0.0
    for op in job_ops:
        name = ops[op]
        moved = total(counters, "ec_codec_bytes_total", op=name)
        if auto:
            coded = moved - total(counters, "ec_codec_bytes_total",
                                  op=name, backend="auto")
        else:
            coded = total(counters, "ec_codec_bytes_total", op=name,
                          backend=config["ec_backend"])
            if not (name == "reconstruct" and traffic.reads):
                off += moved - coded
        due = 0
        for j in traffic.jobs:
            if j.get("warm") or j.get("op") != op or "end" not in j:
                continue
            due += j["rebuilt_bytes"] if op == "rebuild" \
                else j["dat_bytes"]
        short += max(0.0, due - coded)
    if not auto:
        out["offdevice_codec_bytes"] = off
    out["device_short_bytes"] = short
    return out


def read_checks(traffic) -> dict:
    """Every read of the window against the bytes written."""
    want: dict[int, str] = {}
    vol = traffic.volume
    wrong = failed = 0
    for i, row in zip(traffic.read_requests, traffic.reads):
        _due, _sent, _end, status, digest = row
        if status != 200:
            failed += 1
            continue
        if i not in want:
            off, size = vol["offsets"][i], vol["sizes"][i]
            want[i] = hashlib.blake2b(vol["payload"][off:off + size],
                                      digest_size=16).hexdigest()
        wrong += digest != want[i]
    return {"reads_wrong": wrong, "reads_failed": failed}


def shard_checks(traffic, config: dict,
                 root: str = spec.ROOT) -> tuple[dict, list]:
    """Shards the window produced against the reference; returns the
    numbers and the (path, shard id) pairs compared."""
    pairs: list[tuple[str, int]] = []
    for vid, paths in getattr(traffic, "sealed_paths", {}).items():
        pairs += [(p, sid) for sid, p in paths.items()]
    for j in traffic.jobs:
        if j.get("op") == "rebuild" and "snap" in j:
            pairs += [(p, sid) for sid, p in j["snap"].items()]
    which = sorted({sid for _, sid in pairs})
    wrong = 0
    if which:
        from concurrent.futures import ThreadPoolExecutor

        ref = reference_shards(traffic.ref_dat, config, which, root)
        with ThreadPoolExecutor(8) as ex:
            wrong = sum(ex.map(lambda p: shard_diff(p[0], ref[p[1]]),
                               pairs))
    return {"shard_bytes_wrong": wrong}, pairs
