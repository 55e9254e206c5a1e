"""Each cell once on the CPU (JAX_PLATFORMS=cpu, 24 MB volumes, a 1 s
window), and each fault the cells can have planted under the timed
path: the result line's keys, `correct` true on the sound run, false
under every fault. The runs are subprocesses, a few at a time, started
once for the whole module."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

import pytest

from . import tiny

CELLS = ["rs10_4.seal", "rs10_4.rebuild1", "lrc12_2_2.rebuild1"]
FAULTS = ["flip", "unchanged", "half", "offdevice"]
# the seal mix under `ec_backend: auto`, the router's control
AUTO_FAULTS = [(tiny.AUTO_CELL, "flip")]
RUNS = [(c, "", 1) for c in CELLS] + [(tiny.READS_CELL, "", 0)] + \
    [(tiny.AUTO_CELL, "", 0)] + \
    [(c, f, 0) for c in CELLS for f in FAULTS] + \
    [(c, f, 0) for c, f in AUTO_FAULTS]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _run(root: str, cell: str, fault: str, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(tiny.REPO, "benchmark", "run.py"),
           "--workload", cell, "--seed", str(2 ** 31 + 17),
           "--seconds", "1", "--trace", str(trace), "--root", root]
    if fault:
        cmd += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=240)
    lines = p.stdout.strip().splitlines()
    return {"rc": p.returncode, "err": p.stderr[-3000:],
            "line": json.loads(lines[-1]) if lines else None}


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))
    with ThreadPoolExecutor(2) as ex:
        futs = {r: ex.submit(_run, root, *r) for r in RUNS}
        return {r: f.result() for r, f in futs.items()}


@pytest.mark.parametrize("cell", CELLS + [tiny.READS_CELL, tiny.AUTO_CELL])
def test_sound_run_is_correct(results, cell):
    trace = int(cell in CELLS)
    res = results[(cell, "", trace)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert KEYS <= set(line), line
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        # no TPU here: the device-trace metrics stay out of the line
        assert not any("roofline" in m or "idle" in m
                       for m in line["metrics"])
    else:
        assert "setup_s" in line["metrics"]
        assert len(line["metrics"]) >= 2


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in CELLS for f in FAULTS] +
                         AUTO_FAULTS)
def test_fault_is_not_correct(results, cell, fault):
    res = results[(cell, fault, 0)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items() if c["value"] > 0}
    if fault == "offdevice":
        assert "offdevice_codec_bytes" in failing
    else:
        assert "shard_bytes_wrong" in failing


def test_auto_run_names_the_router_choice(results):
    """Off the chip the router has no device to measure and picks the
    CPU codec: every coded byte under that concrete name, none under
    `auto`, and no device guarantee checked."""
    line = results[(tiny.AUTO_CELL, "", 0)]["line"]
    by_backend = line["codec_bytes_by_backend"]
    assert set(by_backend) <= {"native", "numpy"}, by_backend
    assert sum(b.get("encode", 0) for b in by_backend.values()) > 0
    assert "offdevice_codec_bytes" not in line["checks"]
    assert list(line)[-1] == "checks"
