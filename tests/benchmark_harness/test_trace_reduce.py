"""The trace reduction: busy time as the union of device ops inside
the bench.window annotation, idle share, the top ops, idle gaps named
by the benchmark's own spans, and the peaks table. The fixture was
recorded on a TPU v5 lite by record_trace_fixture.py."""
from __future__ import annotations

import json
import os

import pytest

from benchmark import trace_reduce
from benchmark.roofline import hbm_roofline_pct

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "tpu_window.xplane.pb")

MS = 1_000_000


def _events():
    return {
        "devices": {"/device:TPU:0": [
            ("kernel", 10 * MS, 30 * MS),
            ("kernel", 20 * MS, 40 * MS),   # overlaps: counted once
            ("copy", 60 * MS, 70 * MS),
            ("kernel", 95 * MS, 130 * MS),  # runs past the window
        ]},
        "annotations": [
            (trace_reduce.WINDOW, 0, 100 * MS),
            ("bench.encode_job", 0, 50 * MS),
            ("bench.rebuild_job", 55 * MS, 100 * MS),
        ],
    }


def test_busy_is_the_union_inside_the_window():
    got = trace_reduce.reduce(_events())
    assert got["busy_s"] == pytest.approx(0.045)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["idle_share"] == pytest.approx(0.55)
    assert got["device_ops"][0] == ["kernel", pytest.approx(0.045)]
    assert got["device_ops"][1] == ["copy", pytest.approx(0.01)]


def test_idle_gaps_are_split_and_named_by_the_open_bench_span():
    gaps = trace_reduce.reduce(_events())["idle_gaps"]
    assert gaps == [
        ["bench.rebuild_job", pytest.approx(0.025)],
        ["bench.encode_job", pytest.approx(0.01)],
        ["bench.encode_job", pytest.approx(0.01)],
        ["outside any bench span", pytest.approx(0.005)],
        ["bench.rebuild_job", pytest.approx(0.005)],
    ]


def test_one_window_annotation_is_required():
    ev = _events()
    ev["annotations"] = ev["annotations"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(ev)


def test_peaks_table():
    v5e = trace_reduce.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in"):
        trace_reduce.peaks("TPU v99 imaginary")


def test_recorded_tpu_trace():
    got = trace_reduce.reduce(trace_reduce.read_events(FIXTURE))
    with open(FIXTURE.replace(".xplane.pb", ".json"),
              encoding="utf-8") as f:
        recorded = json.load(f)
    assert got["device_planes"] == 1
    assert got["busy_s"] == pytest.approx(recorded["busy_s"])
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["window_s"] > 0.05   # holds the 50 ms bench.idle sleep
    names = [n for n, _ in got["device_ops"]]
    assert any("coded_matmul_pallas" in n for n in names)
    assert any(n == "bench.idle" and s >= 0.045
               for n, s in got["idle_gaps"])
    # the work inside: two 10 x 8 MiB encodes (4 parity rows out) and
    # one rebuild of a shard from 10 (1 row out)
    n = 8 << 20
    run = {"trace": got,
           "device": {"platform": "tpu", "kind": recorded["device_kind"]},
           "config": {"ec_backend": "pallas",
                      "code": {"k": 10, "local": 0, "global": 4}},
           "jobs": [{"op": "encode"}],
           "counters": {("ec_codec_bytes_total",
                         (("backend", "pallas"), ("op", "encode"))):
                        2 * 10 * n}}
    share = hbm_roofline_pct(run, "encode")
    assert 0 < share < 100
