"""The server-loss cells once on the CPU (JAX_PLATFORMS=cpu, a 1 s
window), traced, and under the faults that must turn them not correct:
each job loses every shard of one server, and the harness compares all
of them with the reference after every job. RS(28,4)'s volume is 64 MB
here, as 24 MB is less than one 28 x 1 MiB stripe row and would leave
most data shards empty. The runs are subprocesses, two at a time,
started once for the whole module."""
from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor

import pytest

from . import tiny
from .test_cells_cpu import KEYS, _run

CELLS = {"rs28_4.server_loss_rebuild": ([0, 8, 16, 24], 7.0),
         "rs10_4.server_loss_rebuild": ([0, 4, 8, 12], 2.5)}
FAULTS = {"flip": "shard_bytes_wrong", "offdevice": "offdevice_codec_bytes"}
RUNS = [(c, "", 1) for c in CELLS] + [(c, f, 0) for c in CELLS
                                      for f in FAULTS]
FANOUT = ("fanout_queued_share.rebuild", "fetch_tail_share.rebuild")


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))
    path = os.path.join(root, "benchmark", "configs", "rs28_4.json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg["volume_size_limit_mb"] = 64
    with open(path, "w", encoding="utf-8") as f:
        json.dump(cfg, f)
    with ThreadPoolExecutor(2) as ex:
        futs = {r: ex.submit(_run, root, *r) for r in RUNS}
        return {r: f.result() for r, f in futs.items()}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_server_loss_is_correct(results, cell):
    res = results[(cell, "", 1)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert KEYS <= set(line), line
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert all(c["value"] == 0 for c in line["checks"].values())
    # each job lost one whole server's shards, every one of them remote
    # for the rebuilder, which holds none of the volume
    lost, ratio = CELLS[cell]
    assert f'"lost": {json.dumps(lost)}' in res["err"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["repair_read_ratio.rebuild"] == pytest.approx(ratio,
                                                           rel=1e-3)
    assert set(FANOUT) <= set(m)
    assert m["fanout_queued_share.rebuild"] == 0
    assert 0 <= m["fetch_tail_share.rebuild"] <= 100


@pytest.mark.parametrize("cell,fault",
                         [(c, f) for c in sorted(CELLS) for f in FAULTS])
def test_server_loss_fault_is_not_correct(results, cell, fault):
    res = results[(cell, fault, 0)]
    assert res["rc"] == 0, res["err"]
    line = res["line"]
    assert line["correct"] is False, line["checks"]
    failing = {k for k, c in line["checks"].items() if c["value"] > 0}
    assert FAULTS[fault] in failing
