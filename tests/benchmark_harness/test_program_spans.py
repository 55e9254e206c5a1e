"""The program's spans as the benchmark reads them: each cell once on
the CPU, traced, at tiny size (24 MB volumes, a 1 s window) through
`benchmark/program_spans.py`, which names idle pieces by the program's
`swfs.` annotations too; and the span fixture recorded on a TPU v5 lite
by record_spans_fixture.py."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark import program_spans, trace_reduce

from . import tiny

CELLS = ["rs10_4.seal", "rs10_4.rebuild1", "lrc12_2_2.rebuild1"]
# the per-layer metrics that read the program's spans and counters
METRICS = ["shell_spread_share.encode",
           "shell_registration_wait_share.encode",
           "encoder_gather_share.encode", "encoder_write_wait_share.encode",
           "rebuild_fetch_share.rebuild", "repair_fetch_MBps.rebuild",
           "rebuild_reconstruct_share.rebuild",
           "rebuild_write_share.rebuild"]
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tpu_spans.xplane.pb")


def _run(root: str, cell: str) -> dict:
    cmd = [sys.executable,
           os.path.join(tiny.REPO, "benchmark", "program_spans.py"),
           "--workload", cell, "--seed", str(2 ** 31 + 29),
           "--seconds", "1", "--trace", "1", "--root", root]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=240)
    lines = p.stdout.strip().splitlines()
    return {"rc": p.returncode, "err": p.stderr[-3000:],
            "line": json.loads(lines[-1]) if lines else None}


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("spans_root")))
    with ThreadPoolExecutor(2) as ex:
        futs = {c: ex.submit(_run, root, c) for c in CELLS}
        return {c: f.result() for c, f in futs.items()}


def _cells_of(metric: str) -> list[str]:
    with open(os.path.join(tiny.REPO, "BENCHMARK.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    return next(m["workloads"] for m in spec["per_layer"]
                if m["name"] == metric)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reads_a_number(results, metric):
    cells = _cells_of(metric)
    assert cells
    for cell in cells:
        res = results[cell]
        assert res["rc"] == 0, res["err"]
        got = res["line"]["metrics"].get(metric)
        assert got is not None, (cell, res["line"]["metrics"])
        assert got["value"] >= 0


@pytest.mark.parametrize("cell", CELLS)
def test_idle_pieces_name_program_stages(results, cell):
    res = results[cell]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert line["correct"] is True, line["checks"]
    names = [n for n, _ in line["breakdown"]["idle_gaps"]]
    assert any(n.startswith("swfs.") for n in names), names


def test_span_total_tells_absent_from_zero():
    counters = {("span_seconds_sum", (("name", "a"),)): 0.0,
                ("span_seconds_sum", (("name", "b"),)): 1.5,
                ("request_trace_seconds_sum",
                 (("handler", "x"), ("service", "volume"))): 9.0}
    assert program_spans.span_total(counters, "a") == 0.0
    assert program_spans.span_total(counters, "b") == 1.5
    assert program_spans.span_total(counters, "x") is None
    assert program_spans.span_total({}, "b") is None


def test_recorded_tpu_spans():
    from seaweedfs_tpu.ops.codec_pallas import KERNEL_NAME

    got = trace_reduce.reduce(program_spans.read_events(FIXTURE))
    with open(FIXTURE.replace(".xplane.pb", ".json"),
              encoding="utf-8") as f:
        recorded = json.load(f)
    assert got["busy_s"] == pytest.approx(recorded["busy_s"])
    assert 0 < got["busy_s"] < got["window_s"]
    # the kernel under the program's own name, not its wrapper's
    ops = [n for n, _ in got["device_ops"]]
    assert any(n.lstrip("%").startswith(KERNEL_NAME + ".") for n in ops)
    # the longest idle pieces are named by the encoder's own stages
    gaps = got["idle_gaps"]
    assert all(n.startswith("swfs.") for n, _ in gaps[:3]), gaps
    # the benchmark's reduction alone still sees only its own spans
    plain = trace_reduce.reduce(trace_reduce.read_events(FIXTURE))
    assert plain["busy_s"] == got["busy_s"]
    assert not any(n.startswith("swfs.") for n, _ in plain["idle_gaps"])
