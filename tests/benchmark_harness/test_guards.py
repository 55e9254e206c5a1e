"""The harness refuses to run where it would measure the wrong thing:
off the chip without JAX_PLATFORMS=cpu, on fewer chips than the cell
asks for, and without the program beside it; and no run reads the
codec router's probe curve that another run left."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run

from . import tiny


def test_cpu_without_rehearsal_is_refused(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit, match="not a TPU"):
        run.device_check(1)


def test_fewer_chips_than_asked_is_refused(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run.device_check(1)["platform"] == "cpu"
    with pytest.raises(SystemExit, match="asks for 64 chips"):
        run.device_check(64)


def test_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.REPO, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rs10_4.seal",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "seaweedfs_tpu" in p.stderr


def test_each_run_keeps_its_own_probe_cache(tmp_path, monkeypatch):
    from seaweedfs_tpu.ec import probe

    from benchmark import deploy

    monkeypatch.setenv(deploy.PROBE_CACHE_ENV, str(tmp_path / "shared"))
    seen = []
    for name in ("run_a", "run_b"):
        work = str(tmp_path / name / "cluster")
        dep = deploy.Deployment(work, {"volume_servers": 1})
        assert os.environ[deploy.PROBE_CACHE_ENV] == dep.probe_cache
        # the default curve and a per-code one both lie in the run's dir
        for code in ("", "12.2.2"):
            assert probe.cache_path(code).startswith(work + os.sep)
        seen.append(os.path.dirname(dep.probe_cache))
    assert seen[0] != seen[1]
