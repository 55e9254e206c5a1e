"""The program's own spans, as the per-layer metrics and the traced runs
read them.

The program times its phases and per-chunk steps into
`span_seconds{name}` and opens a `swfs.<name>` profiler annotation over
each (seaweedfs_tpu/utils/tracing.py). `span_total` reads the first
from a run's counters; a program without such a span reads None, so a
metric built on it leaves the line instead of reading 0.

Run as a script, this is benchmark/run.py with the idle pieces of the
breakdown named by the innermost `bench.*` or `swfs.*` annotation open
through them, so a traced run says which host stage the device waited
on:

    python3 benchmark/program_spans.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace 1
"""
from __future__ import annotations

import os
import runpy
import sys

PREFIXES = ("bench.", "swfs.")


def span_total(counters: dict, name: str) -> float | None:
    """Seconds the window spent in the program's span or interval
    `name` (Σ span_seconds{name}); None when the program has none."""
    found = [v for (n, lab), v in counters.items()
             if n == "span_seconds_sum" and ("name", name) in lab]
    return sum(found) if found else None


def job_seconds(run: dict, op: str) -> float:
    """Summed wall time of the window's finished `op` jobs."""
    return sum(j["end"] - j["start"] for j in run["jobs"]
               if j.get("op") == op and "end" in j)


def read_events(path: str) -> dict:
    """trace_reduce.read_events, keeping the program's `swfs.`
    annotations beside the benchmark's own."""
    from benchmark import trace_reduce

    saved, trace_reduce.PREFIX = trace_reduce.PREFIX, PREFIXES
    try:
        return trace_reduce.read_events(path)
    finally:
        trace_reduce.PREFIX = saved


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark import trace_reduce

    trace_reduce.PREFIX = PREFIXES
    runpy.run_path(os.path.join(root, "benchmark", "run.py"),
                   run_name="__main__")
