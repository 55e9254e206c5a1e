"""The Hitchhiker-XOR cell once on the CPU (JAX_PLATFORMS=cpu, 24 MB
volumes, a 1 s window), traced, beside three other rebuild cells for
the repair planner's input ratio, and under the faults that must turn
it not correct. The configuration brings its own reference
(benchmark/references/hh10_4.py), which make_root does not copy, so the
root gets it here. The runs are subprocesses, two at a time, started
once for the whole module."""
from __future__ import annotations

import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import pytest

from . import tiny
from .test_cells_cpu import KEYS, _run

CELL = "hh10_4.rebuild1"
FAULTS = {"flip": "shard_bytes_wrong", "offdevice": "offdevice_codec_bytes"}
# the ranges a job hands the codec per byte rebuilt: k for an RS single
# loss, the local group for LRC, k over the 4 lost for a server loss
INPUT_RATIOS = {"rs10_4.rebuild1": 10.0, "lrc12_2_2.rebuild1": 6.0,
                "rs10_4.server_loss_rebuild": 2.5}
RUNS = [(CELL, "", 1)] + [(c, "", 1) for c in INPUT_RATIOS] + \
    [(CELL, f, 0) for f in FAULTS]
RATIO = "repair_input_ratio.rebuild"


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))
    shutil.copytree(os.path.join(tiny.REPO, "benchmark", "references"),
                    os.path.join(root, "benchmark", "references"))
    with ThreadPoolExecutor(2) as ex:
        futs = {r: ex.submit(_run, root, *r) for r in RUNS}
        return {r: f.result() for r, f in futs.items()}


def test_hh_rebuild_is_correct(results):
    res = results[(CELL, "", 1)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert KEYS <= set(line), line
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # 13 half ranges for shards 0-5, 14 for 6-9: 6.5-7.0 a byte rebuilt
    assert 6.5 <= m[RATIO] <= 7.0
    # the rebuilder holds some inputs itself: fewer remote bytes
    assert 0 < m["repair_read_ratio.rebuild"] < m[RATIO]


@pytest.mark.parametrize("cell", sorted(INPUT_RATIOS))
def test_input_ratio_of_whole_range_codes(results, cell):
    res = results[(cell, "", 1)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert line["correct"] is True, line["checks"]
    assert line["metrics"][RATIO]["value"] == \
        pytest.approx(INPUT_RATIOS[cell])


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_hh_fault_is_not_correct(results, fault):
    res = results[(CELL, fault, 0)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items() if c["value"] > 0}
    assert FAULTS[fault] in failing
