"""A tiny copy of the benchmark's data for CPU tests: the same cells,
traffic and metric readers, with 24 MB volumes (two stripe rows, every data shard holding data), and
two more cells: one that drives the generator's open-loop reads, and
the seal mix on a copy of rs10_4 with `ec_backend: auto`, added as a
configuration would be, by files and entries only. The harness reads
it through `--root`."""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOLUME_MB = 24
READS_CELL = "rs10_4.tiny_reads"
AUTO_CONFIG = "rs10_4_auto"
AUTO_CELL = AUTO_CONFIG + ".seal"


def make_root(dst: str) -> str:
    bench = os.path.join(dst, "benchmark")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(bench, sub))
    for name in os.listdir(os.path.join(bench, "configs")):
        path = os.path.join(bench, "configs", name)
        with open(path, encoding="utf-8") as f:
            cfg = json.load(f)
        cfg["volume_size_limit_mb"] = VOLUME_MB
        with open(path, "w", encoding="utf-8") as f:
            json.dump(cfg, f)
        if cfg["name"] == "rs10_4":
            auto = dict(cfg, name=AUTO_CONFIG, ec_backend="auto")
            with open(os.path.join(bench, "configs", AUTO_CONFIG + ".json"),
                      "w", encoding="utf-8") as f:
                json.dump(auto, f)
    # serial reads: the program's EC shard reads race under concurrency
    # (PERF.md, Open questions), which is not what these tests check
    reads = {"volumes": 1, "sealed": True,
             "lose": {"kind": "server", "each": "run"},
             "reads": {"rate_per_s": 20, "zipf_theta": 0.99,
                       "connections": 1}}
    with open(os.path.join(bench, "traffic", "tiny_reads.json"), "w",
              encoding="utf-8") as f:
        json.dump(reads, f)
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        spec = json.load(f)
    spec["workloads"].append({"name": READS_CELL, "config": "rs10_4",
                              "traffic": "tiny_reads", "chips": 1,
                              "why": "the generator's open-loop reads"})
    spec["workloads"].append({"name": AUTO_CELL, "config": AUTO_CONFIG,
                              "traffic": "seal", "chips": 1,
                              "why": "the seal mix through the router"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "rs10_4.seal" in m.get("workloads", []):
            m["workloads"].append(AUTO_CELL)
    spec["end_to_end"].append({
        "name": "degraded_read_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": [READS_CELL]})
    for name in ("read_p50_ms.degraded", "reconstruct_ms_per_read.degraded"):
        spec["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "service",
            "moves": "degraded_read_p99_ms", "workloads": [READS_CELL]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(spec, f)
    return dst
