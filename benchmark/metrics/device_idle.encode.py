"""Idle share of the device over the traced seal window: 1 - busy /
window, busy the union of the XLA ops on the TPU (trace_reduce.py)."""
from benchmark.roofline import idle_pct


def read(run):
    return idle_pct(run, "encode")
