#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The process owns the chip. It brings up the cell's deployment (one
master, four volume servers, in this process), makes its data from the
seed, warms the cell's own shapes with a throwaway job, measures the
traffic mix for --seconds, and then, with the window closed, compares
what the window produced with the plain reference. With --trace 1 the
window is traced by the JAX profiler and the per-layer metrics are
printed instead of the end-to-end ones.

The last line of standard output is one JSON object: correct,
attempted, failed, metrics, device, (with --trace 1) breakdown, (under
`ec_backend: auto`) codec_bytes_by_backend, and last `checks`, each
number compared beside its limit; the same checks end standard error.
Off the chip it exits non-zero and prints no result, unless
JAX_PLATFORMS=cpu asks for a CPU rehearsal.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# JAX's persistent compile cache lives at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".bench_work")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_check(chips: int) -> dict:
    """The default device must be a TPU, `chips` of them, unless
    JAX_PLATFORMS=cpu asks for a rehearsal on the CPU."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if platform != "tpu" and not rehearsal:
        raise SystemExit(f"JAX's default device is {platform!r}, not a "
                         "TPU; set JAX_PLATFORMS=cpu for a rehearsal")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def build_native() -> None:
    from seaweedfs_tpu.native import build

    for fn in (build.build, build.build_dataplane):
        fn(verbose=False)


def start_trace(path: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(path, profiler_options=opts)


def probe_sweeps(dep) -> dict:
    """{curve file: sweep seconds} of the codec router's probe curves
    this run's set-up measured and saved (only a curve with a device
    measured is saved)."""
    probe_dir = os.path.dirname(dep.probe_cache)
    if not os.path.isdir(probe_dir):
        return {}
    out = {}
    for name in sorted(os.listdir(probe_dir)):
        with open(os.path.join(probe_dir, name), encoding="utf-8") as f:
            out[name] = json.load(f).get("sweep_seconds")
    return out


def run_cell(args, spec_: dict, work: str) -> dict:
    import jax

    from benchmark import check, spec, trace_reduce
    from benchmark.deploy import Compiles, Deployment, delta

    cell, config = spec_["cell"], spec_["config"]
    mark = [T_START]

    def phase(name: str) -> None:
        now = time.monotonic()
        say(f"setup {name}: {now - mark[0]:.3f} s")
        mark[0] = now

    info = device_check(cell["chips"])
    from seaweedfs_tpu.ops import device

    device.setup_compile_cache()
    compiles = Compiles()
    phase("python, JAX and the device")
    build_native()
    phase("native libraries")
    if args.fault:
        from benchmark import faults

        faults.install(args.fault, config)
    traffic = spec.traffic_class(spec_)(spec_["traffic"], config, args.seed,
                                        work)
    dep = Deployment(os.path.join(work, "cluster"), config)
    traffic.write_data(dep)
    phase("data")
    dep.start(max_volumes=len(traffic.pool) + 16)
    phase("deployment")
    stopped = False
    try:
        traffic.setup(dep)
        phase("sealing, losses and warm-up")
        if config["ec_backend"] == "auto":
            say("probe sweeps " + json.dumps(probe_sweeps(dep)))
        say("setup compiles " + json.dumps(compiles.snapshot()))
        trace_dir = os.path.join(work, "trace")
        if args.trace:
            start_trace(trace_dir)
        before = dep.scrape()
        c0 = compiles.snapshot()
        setup_s = traffic.arm(args.seconds) - T_START
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            win = traffic.window(dep, args.seconds)
        c1 = compiles.snapshot()
        if args.trace:
            jax.profiler.stop_trace()
        peak = memory_peak()
        counters = delta(dep.scrape(), before)
        unmounted = 0
        for vid in traffic.sealed:
            unmounted += len(dep.unmounted(vid, traffic.total))
        # every volume the window sealed, each shard where the master
        # lists it; one the master does not list is compared as absent
        done = [j["vid"] for j in traffic.jobs
                if j.get("op") == "encode" and not j.get("warm")
                and "end" in j]
        traffic.sealed_paths = {}
        for vid in done:
            holders = dep.holders(vid)
            traffic.sealed_paths[vid] = {
                sid: dep.shard_path(vid, sid, holders[sid][0])
                if sid in holders else os.path.join(work, "absent")
                for sid in range(traffic.total)}
        if any(j.get("op") == "rebuild" for j in traffic.jobs):
            unmounted += len(dep.unmounted(1, traffic.total))
        dep.stop()
        stopped = True
    finally:
        if not stopped:
            dep.stop()

    trace = trace_reduce.reduce_dir(trace_dir) if args.trace else None
    checks = {"jobs_failed": sum("error" in j for j in traffic.jobs),
              "shards_unmounted": unmounted}
    checks.update(check.codec_checks(traffic, counters, config))
    t_ref = time.monotonic()
    shard_numbers, pairs = check.shard_checks(traffic, config, args.root)
    checks.update(shard_numbers)
    if traffic.spec.get("reads"):
        checks.update(check.read_checks(traffic))
    say(f"reference: {len(pairs)} shards compared in "
        f"{time.monotonic() - t_ref:.3f} s")
    info["memory_peak_bytes"] = peak
    return {"info": info, "setup_s": setup_s, "window": win,
            "counters": counters, "traffic": traffic, "trace": trace,
            "checks": checks, "compiles_in_window": {
                k: c1[k] - c0[k] for k in c1},
            "config": config}


def report(args, spec_: dict, run: dict) -> dict:
    from benchmark import spec

    traffic = run["traffic"]
    window_jobs = [j for j in traffic.jobs if not j.get("warm")]
    rec = {
        "setup_s": run["setup_s"],
        "window_s": run["window"]["end"] - run["window"]["start"],
        "jobs": window_jobs,
        "reads": [dict(zip(("due", "sent", "end", "status", "digest"), r))
                  for r in traffic.reads],
        "counters": run["counters"],
        "trace": run["trace"],
        "device": run["info"],
        "config": run["config"],
    }
    wanted = spec_["per_layer"] if args.trace else spec_["end_to_end"]
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"], args.root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for j in window_jobs:
        say("job " + json.dumps({k: v for k, v in j.items()
                                 if k not in ("snap",)}))
    if traffic.reads:
        late = sorted(r[1] - r[0] for r in traffic.reads)
        bad = {}
        for r in traffic.reads:
            if r[3] != 200:
                bad[f"{r[3]} {r[4]}"] = bad.get(f"{r[3]} {r[4]}", 0) + 1
        if bad:
            say("failed reads " + json.dumps(bad))
        say(f"load generator lateness: p50 {late[len(late) // 2]:.6f} s, "
            f"max {late[-1]:.6f} s over {len(late)} reads")
    say("compiles in window " + json.dumps(run["compiles_in_window"]))
    t = run["trace"]
    if t and t["busy_s"] and run["info"]["platform"] == "tpu":
        from benchmark import roofline, trace_reduce

        peak = trace_reduce.peaks(run["info"]["kind"])["bf16_flops_per_s"]
        for op in sorted({j["op"] for j in window_jobs if "op" in j}):
            flops = roofline.bitplane_flops(rec, op)
            say(f"bit-plane {op}: {flops:.6e} bf16 FLOP, "
                f"{flops / t['busy_s'] / peak * 100:.4f}% of the bf16 "
                "peak over busy time (information only)")
    out = {"correct": all(v <= 0 for v in run["checks"].values()),
           "attempted": len(window_jobs) + len(traffic.reads),
           "failed": run["checks"]["jobs_failed"] +
           run["checks"].get("reads_failed", 0),
           "metrics": metrics,
           "device": dict(run["info"])}
    if run["trace"]:
        t = run["trace"]
        out["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["compiles_in_window"] = run["compiles_in_window"]["xla_compiles"]
    if run["config"]["ec_backend"] == "auto":
        from benchmark import check

        out["codec_bytes_by_backend"] = check.codec_bytes_by_backend(
            run["counters"])
        say("codec bytes by backend " +
            json.dumps(out["codec_bytes_by_backend"]))
    out["checks"] = {k: {"value": v, "limit": 0}
                     for k, v in run["checks"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json and benchmark/ are read "
                         "from (the tests' tiny copies)")
    ap.add_argument("--fault", default="",
                    help="plant a fault under the timed path (the "
                         "control and its tests; never in a check)")
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 63:
        raise SystemExit("--seed must be a whole number in [0, 2**63)")
    from benchmark import spec

    spec_ = spec.load_cell(args.workload, args.root)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # no size cap: the cap's eviction races between the servers' codec
    # threads on the chip host's file system and loses entries
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.makedirs(WORK_DIR, exist_ok=True)
    work = tempfile.mkdtemp(prefix="run_", dir=WORK_DIR)
    try:
        run = run_cell(args, spec_, work)
        out = report(args, spec_, run)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    for name, c in out["checks"].items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        rc = 2
    except Exception:
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # server and pool threads of the stopped deployment would keep the
    # interpreter alive; every process this run started has ended
    os._exit(rc)
