#!/usr/bin/env python3
"""Bring-up smoke for the erasure-coding main path on the chip.

    python chip_smoke.py [--seed N]            one chip (the default)
    python chip_smoke.py --chips 4 [--seed N]  the mesh codec on four

One chip: this process owns the chip and runs the master and the volume
server in-process through `cli.start_server`, the classes and flags of
`python -m seaweedfs_tpu server`, with `-ec.backend=pallas` and a 1 GiB
volume size limit. It uploads about 1 GiB of needles (4 KiB-4 MiB,
log-uniform, from --seed) through the client SDK, runs `ec.encode`,
deletes one data shard and then four shards (two data, two parity) and
runs `ec.rebuild` each time, reading 200 seeded needles back while
shards are missing and again after each rebuild. Every shard is
compared byte for byte with a numpy-codec encode of the same .dat, and
the codec metrics must show that pallas moved the volume's bytes. The
router's size x depth probe then runs once on the chip.

Four chips: only the mesh codec and what it is compared with. The same
1 GiB volume is written through the storage layer, encoded by numpy,
by single-chip pallas and by mesh over the four devices, then rebuilt
by mesh after losing four shards; the mesh output must sit on four
distinct devices, and the sharded_rebuild ring is checked against the
numpy oracle.

Any failed check exits non-zero. Only a run that passed prints, as its
last line, {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import signal
import socket
import sys
import tempfile
import time

VOLUME_MB = 1024
MIN_NEEDLE = 4 << 10
MAX_NEEDLE = 4 << 20
# room left under the size limit for needle headers and padding, so
# the whole load lands in one volume
HEADROOM = 8 << 20
READS = 200
TIME_LIMIT_S = 1100
_COMPARE_CHUNK = 64 << 20


class SmokeError(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class Compiles:
    """Counts XLA compilations and persistent-cache hits from JAX's
    monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = {"xla_compiles": 0, "cache_hits": 0, "cache_misses": 0}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in self.n:
            self.n[name] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["xla_compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.n)


class Phases:
    """Per-phase wall seconds and compile counts, one line per phase."""

    def __init__(self, compiles: Compiles | None):
        self.compiles = compiles
        self.rows: dict[str, dict] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        before = self.compiles.snapshot() if self.compiles else {}
        t0 = time.perf_counter()
        yield
        row = {"seconds": round(time.perf_counter() - t0, 3)}
        if self.compiles:
            after = self.compiles.snapshot()
            row.update({k: after[k] - before[k] for k in after})
        self.rows[name] = row
        say(f"phase {name}: {json.dumps(row)}")


# ----------------------------------------------------------------------
# device
# ----------------------------------------------------------------------

def device_info(chips: int, cache_dir: str) -> dict:
    """Step 1: the default JAX device must be a TPU, with at least
    `chips` of them. Never falls back."""
    import importlib.metadata as md

    import jax
    import jaxlib

    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu",
          f"JAX's default device is {platform!r}, not a TPU: this "
          "smoke runs only on the chip")
    check(len(devs) >= chips,
          f"asked for {chips} chips, JAX sees {len(devs)}")
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    info = {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device " + json.dumps(dict(
        info, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache=cache_dir)))
    return info


def build_native() -> None:
    """Step 2: the native libraries, rebuilt here unless the built
    files carry this source + flags + CPU's key."""
    from seaweedfs_tpu.native import build

    for fn in (build.build, build.build_dataplane):
        path = fn(verbose=False)
        with open(path + ".key", encoding="utf-8") as f:
            key = f.read().strip()
        say(f"native {os.path.basename(path)} key {key[:16]}")


# ----------------------------------------------------------------------
# data
# ----------------------------------------------------------------------

def needle_mix(seed: int, total: int):
    """Needle sizes (log-uniform 4 KiB-4 MiB: most needles small, most
    bytes in large ones) filling `total` bytes, and their payload."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lo, hi = math.log(MIN_NEEDLE), math.log(MAX_NEEDLE)
    sizes = []
    acc = 0
    while True:
        s = int(math.exp(rng.uniform(lo, hi)))
        if acc + s > total:
            break
        sizes.append(s)
        acc += s
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(int)
    payload = memoryview(rng.bytes(acc))
    return sizes, [int(o) for o in offsets], payload


def same_files(a: str, b: str) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            x = fa.read(_COMPARE_CHUNK)
            if x != fb.read(_COMPARE_CHUNK):
                return False
            if not x:
                return True


def compare_shards(base: str, ref_base: str, shard_ids, what: str,
                   ref: str = "numpy") -> None:
    from seaweedfs_tpu.ec import geometry as geo

    for sid in shard_ids:
        ext = geo.shard_ext(sid)
        check(same_files(base + ext, ref_base + ext),
              f"{what}: shard {sid} differs from {ref}")
    say(f"{what}: shards {list(shard_ids)} byte-identical to {ref}")


def numpy_reference(dat_path: str, ref_dir: str, vid: int) -> str:
    """The numpy-codec encode of a copy of `dat_path`: the oracle every
    device-coded shard is compared with."""
    from seaweedfs_tpu.ec.encoder import write_ec_files

    os.makedirs(ref_dir, exist_ok=True)
    ref_base = os.path.join(ref_dir, str(vid))
    shutil.copyfile(dat_path, ref_base + ".dat")
    write_ec_files(ref_base, backend="numpy")
    return ref_base


# ----------------------------------------------------------------------
# metrics
# ----------------------------------------------------------------------

def codec_bytes(metrics_url: str) -> dict:
    """{(op, backend): bytes} from ec_codec_bytes_total as the volume
    server's /metrics exposes it."""
    import re

    from seaweedfs_tpu.rpc.httpclient import session

    text = session().get(metrics_url, timeout=30).text
    out: dict = {}
    for line in text.splitlines():
        if not line.startswith("ec_codec_bytes_total{"):
            continue
        labels = dict(re.findall(r'(\w+)="([^"]*)"', line))
        out[(labels.get("op"), labels.get("backend"))] = \
            float(line.rsplit(" ", 1)[1])
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


# ----------------------------------------------------------------------
# one chip: the served path
# ----------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for(fn, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while True:
        got = fn()
        if got:
            return got
        check(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.2)


def run_single(seed: int, work: str, phase: Phases,
               volume_mb: int = VOLUME_MB) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from seaweedfs_tpu import cli
    from seaweedfs_tpu.ec import geometry as geo
    from seaweedfs_tpu.operation import verbs
    from seaweedfs_tpu.shell.env import CommandEnv
    from seaweedfs_tpu.shell.repl import run_command

    data_dir = os.path.join(work, "cluster")
    with phase("start"):
        args = cli.parse_args([
            "server", "-dir", data_dir, "-ip", "127.0.0.1",
            "-master.port", str(free_port()),
            "-volume.port", str(free_port()),
            "-volumeSizeLimitMB", str(volume_mb),
            "-ec.backend", "pallas"])
        cli.configure(args)
        threads = cli.start_server(args)
        master = threads[0].url
        env = CommandEnv(master)
        nodes = wait_for(env.data_nodes, "the volume server to register")
        vs_url = nodes[0]["url"]
        metrics_url = f"http://{vs_url}/metrics"
    try:
        with phase("load"):
            sizes, offsets, payload = needle_mix(
                seed, (volume_mb << 20) - HEADROOM)

            def put(i: int) -> str:
                blob = bytes(payload[offsets[i]:offsets[i] + sizes[i]])
                return verbs.upload_data(master, blob)

            with ThreadPoolExecutor(8) as ex:
                fids = list(ex.map(put, range(len(sizes))))
            vids = {int(f.split(",")[0]) for f in fids}
            check(len(vids) == 1, f"needles spread over volumes {vids}")
            vid = vids.pop()
            dat = os.path.join(data_dir, "volume", f"{vid}.dat")
            say(f"load: {len(fids)} needles, {sum(sizes)} bytes, volume "
                f"{vid} .dat {os.path.getsize(dat)} bytes")
        with phase("reference"):
            ref_base = numpy_reference(dat, os.path.join(work, "ref"),
                                       vid)
        base = os.path.join(data_dir, "volume", str(vid))
        dat_size = os.path.getsize(ref_base + ".dat")
        shard_ids = range(geo.TOTAL_SHARDS)
        rng = np.random.default_rng(seed + 1)

        def read_back(what: str) -> None:
            before = codec_bytes(metrics_url)
            picks = rng.choice(len(fids), size=min(READS, len(fids)),
                               replace=False)
            for i in picks:
                got = verbs.download(f"http://{vs_url}/{fids[i]}")
                want = payload[offsets[i]:offsets[i] + sizes[i]]
                check(got == want, f"{what}: needle {fids[i]} differs")
            served = delta(codec_bytes(metrics_url), before)
            say(f"{what}: {len(picks)} needles byte-identical; codec "
                f"bytes {json.dumps({'/'.join(k): v for k, v in served.items()})}")

        with phase("encode"):
            run_command(env, "lock")
            before = codec_bytes(metrics_url)
            run_command(env, f"ec.encode -volumeId={vid}")
            moved = delta(codec_bytes(metrics_url), before)
            say(f"encode: codec bytes {moved}")
            check(moved.get(("encode", "pallas"), 0) >= dat_size,
                  f"pallas encoded {moved.get(('encode', 'pallas'), 0)} "
                  f"of {dat_size} bytes")
            check(set(moved) == {("encode", "pallas")},
                  f"a non-device codec took encode work: {moved}")
        with phase("compare encode"):
            compare_shards(base, ref_base, shard_ids, "encode")

        for lost in ([3], [1, 6, 11, 12]):
            tag = f"lost {lost}"
            with phase(f"degraded {lost}"):
                srv = env.vs_post(vs_url, "/admin/ec/delete",
                                  {"volume": vid, "shard_ids": lost})
                check("error" not in srv, f"{tag}: delete {srv}")
                wait_for(lambda: not set(lost) & set(
                    env.ec_full_info(vid)[2]),
                    f"the master to see shards {lost} gone")
                read_back(f"degraded reads, {tag}")
            with phase(f"rebuild {lost}"):
                before = codec_bytes(metrics_url)
                out = run_command(env, f"ec.rebuild -volumeId={vid}")
                moved = delta(codec_bytes(metrics_url), before)
                say(f"rebuild {tag}: {json.dumps(out)}; codec bytes "
                    f"{json.dumps({'/'.join(k): v for k, v in moved.items()})}")
                check(sorted(out["rebuilt"]) == sorted(lost),
                      f"{tag}: rebuilt {out['rebuilt']}")
                check(moved.get(("reconstruct", "pallas"), 0) > 0,
                      f"{tag}: the rebuild did not run on pallas")
                check(set(moved) == {("reconstruct", "pallas")},
                      f"{tag}: a non-device codec took rebuild work")
            with phase(f"compare rebuild {lost}"):
                compare_shards(base, ref_base, lost, f"rebuild {tag}")
            with phase(f"read back {lost}"):
                read_back(f"reads after rebuild, {tag}")
        run_command(env, "unlock")
    finally:
        env.close()
        for t in reversed(threads):
            t.stop()


def run_probe(phase: Phases) -> None:
    """The router's size x depth sweep, once, on the chip: the first
    chip reading of the host<->device feed. Informational; a device
    error fails the smoke."""
    from seaweedfs_tpu.ec import probe

    with phase("probe"):
        curve = probe.run_sweep()
    say("probe device_error: " + json.dumps(curve.get("device_error")))
    say("probe " + json.dumps({
        k: curve.get(k) for k in ("device", "device_backend",
                                  "cpu_backend", "cpu_mbps",
                                  "sweep_seconds")}))
    for row in curve.get("rows", []):
        say("probe row " + json.dumps(row))
    check(not curve.get("device_error"),
          f"probe device_error: {curve.get('device_error')}")


# ----------------------------------------------------------------------
# four chips: the mesh codec only
# ----------------------------------------------------------------------

def write_volume(vol_dir: str, vid: int, seed: int, total: int) -> str:
    """The load phase's needle mix written straight through the
    storage layer (no servers in the four-chip run)."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    os.makedirs(vol_dir, exist_ok=True)
    sizes, offsets, payload = needle_mix(seed, total)
    v = Volume(vol_dir, "", vid, create=True)
    try:
        for i, (off, size) in enumerate(zip(offsets, sizes)):
            v.append_needle(Needle(id=i + 1, cookie=0x5EED,
                                   data=bytes(payload[off:off + size])))
        v.sync()
    finally:
        v.close()
    return v.file_name()


def run_mesh(seed: int, work: str, phase: Phases, chips: int,
             volume_mb: int = VOLUME_MB) -> None:
    import numpy as np

    from seaweedfs_tpu.ec import backend as ecb
    from seaweedfs_tpu.ec import geometry as geo
    from seaweedfs_tpu.ec.encoder import rebuild_ec_files, write_ec_files
    from seaweedfs_tpu.models.ec_pipeline import (rebuild_mesh,
                                                  sharded_rebuild)
    from seaweedfs_tpu.ops import codec_numpy, rs_matrix
    from seaweedfs_tpu.utils import metrics

    def encoded_bytes(op: str, backend: str) -> float:
        key = ("ec_codec_bytes_total",
               tuple(sorted({"op": op, "backend": backend}.items())))
        return metrics._counters.get(key, 0.0)

    vid = 1
    with phase("load"):
        src = write_volume(os.path.join(work, "src"), vid, seed,
                           (volume_mb << 20) - HEADROOM)
        dat_size = os.path.getsize(src + ".dat")
        say(f"load: volume {vid} .dat {dat_size} bytes")
    with phase("reference"):
        ref = numpy_reference(src + ".dat", os.path.join(work, "numpy"),
                              vid)
    bases = {}
    for backend in ("pallas", "mesh"):
        d = os.path.join(work, backend)
        os.makedirs(d)
        bases[backend] = os.path.join(d, str(vid))
        os.link(src + ".dat", bases[backend] + ".dat")
        with phase(f"encode {backend}"):
            before = encoded_bytes("encode", backend)
            write_ec_files(bases[backend], backend=backend)
            moved = encoded_bytes("encode", backend) - before
            check(moved >= dat_size,
                  f"{backend} encoded {moved} of {dat_size} bytes")
        compare_shards(bases[backend], ref, range(geo.TOTAL_SHARDS),
                       f"encode {backend}")
    compare_shards(bases["mesh"], bases["pallas"],
                   range(geo.TOTAL_SHARDS), "encode mesh",
                   ref="single-chip pallas")

    with phase("mesh placement"):
        codec = ecb.get_backend("mesh")
        check(codec.n_devices == chips,
              f"mesh spans {codec.n_devices} devices, not {chips}")
        coef = rs_matrix.parity_rows(geo.DATA_SHARDS, geo.PARITY_SHARDS)
        block = np.stack([np.fromfile(ref + geo.shard_ext(i),
                                      dtype=np.uint8, count=8 << 20)
                          for i in range(geo.DATA_SHARDS)])
        batched, _ = codec._to_batched(block)
        out = codec._kernel_call(codec._coef_bits(coef), None,
                                 codec._h2d(batched))
        holders = {s.device for s in out.addressable_shards}
        say(f"mesh placement: output on {len(holders)} devices "
            f"{sorted(d.id for d in holders)}, mesh {codec.describe()}")
        check(len(holders) == chips,
              f"mesh output sits on {len(holders)} devices, not {chips}")
        got = codec._from_batched(np.asarray(out), block.shape[1])
        check(np.array_equal(got, codec_numpy.coded_matmul(coef, block)),
              "mesh kernel output differs from numpy")

    lost = [1, 6, 11, 12]
    with phase(f"rebuild mesh {lost}"):
        for sid in lost:
            os.remove(bases["mesh"] + geo.shard_ext(sid))
        before = encoded_bytes("reconstruct", "mesh")
        rebuilt = rebuild_ec_files(bases["mesh"], backend="mesh")
        check(sorted(rebuilt) == lost, f"rebuilt {rebuilt}")
        check(encoded_bytes("reconstruct", "mesh") > before,
              "the rebuild did not run on mesh")
    compare_shards(bases["mesh"], ref, lost, f"rebuild mesh {lost}")

    with phase("ring rebuild"):
        rebuild, a_dev, rcoef = sharded_rebuild(rebuild_mesh(chips))
        present = list(range(geo.PARITY_SHARDS, geo.TOTAL_SHARDS))
        n = 4 << 20
        rows = np.stack([np.fromfile(ref + geo.shard_ext(i),
                                     dtype=np.uint8, count=n)
                         for i in present])
        got = np.asarray(rebuild(a_dev, rows))
        want = np.stack([np.fromfile(ref + geo.shard_ext(i),
                                     dtype=np.uint8, count=n)
                         for i in range(geo.PARITY_SHARDS)])
        check(np.array_equal(got, codec_numpy.coded_matmul(rcoef, rows)),
              "ring rebuild differs from the numpy oracle")
        check(np.array_equal(got, want),
              "ring rebuild differs from the encoded shards")
        say(f"ring rebuild: shards 0-3 from 4-13 over {chips} devices, "
            f"{n} columns, byte-identical")


# ----------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    a = ap.parse_args(argv)

    def _expired(signum, frame):
        raise SmokeError(f"over the {TIME_LIMIT_S} s budget")

    signal.signal(signal.SIGALRM, _expired)
    signal.alarm(TIME_LIMIT_S)
    t0 = time.perf_counter()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        from seaweedfs_tpu.ops import device

        cache_dir = device.setup_compile_cache()
        compiles = Compiles()
        phase = Phases(compiles)
        info = device_info(a.chips, cache_dir)
        with phase("native"):
            build_native()
        if a.chips == 1:
            run_single(a.seed, work, phase)
            run_probe(phase)
        else:
            run_mesh(a.seed, work, phase, a.chips)
        n = compiles.snapshot()
        say("compiles " + json.dumps(n))
        say(f"compile cache {cache_dir}: "
            f"{'hit' if n['cache_hits'] else 'no hits'} "
            f"({n['cache_hits']} hits, {n['cache_misses']} misses)")
        say(f"total seconds {time.perf_counter() - t0:.3f}")
    except Exception as e:  # every failure ends here, non-zero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        shutil.rmtree(work, ignore_errors=True)
    say(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # server, commit and native-front threads would keep the
    # interpreter alive; the smoke's verdict is already out
    os._exit(rc)
