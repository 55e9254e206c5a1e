"""The coding work a window did, counted from what the code needs and
not from how a kernel does it, and its share of the chip's peak.

A coded matmul reads its input rows and writes its output rows once:
an encode reads k data rows and writes m parity rows per column; a
rebuild reads its input rows and writes the lost shards. The least
time is those bytes over peak HBM bandwidth; the share is the least
time over the device's busy time in the traced window. That is the
same work whatever kernel implements it. The bit-plane kernel's FLOP
count (2 * 8r * 8k per column, bf16) is printed as information only.
"""
from __future__ import annotations

from .deploy import device_backend, total
from .trace_reduce import peaks

OPS = {"encode": "encode", "rebuild": "reconstruct"}


def coded_bytes(run, op: str) -> tuple[float, float]:
    """(input, output) bytes of the window's device coding of `op`."""
    dev = device_backend(run["config"])
    moved = total(run["counters"], "ec_codec_bytes_total", op=OPS[op],
                  backend=dev)
    if op == "encode":
        code = run["config"]["code"]
        out = moved * (code.get("local", 0) + code["global"]) / code["k"]
    else:
        out = sum(j.get("rebuilt_bytes", 0) for j in run["jobs"]
                  if j.get("op") == "rebuild" and "end" in j)
    return moved, out


def bitplane_flops(run, op: str) -> float:
    """The bit-plane kernel's bf16 FLOP for the window's coding of
    `op`: 2 * 8r * 8k per column, r output and k input rows, so
    128 * r * input bytes. Information only: another kernel (int8, XOR)
    would need other work for the same bytes."""
    inp, _ = coded_bytes(run, op)
    if op == "encode":
        code = run["config"]["code"]
        r = code.get("local", 0) + code["global"]
    else:
        r = max((len(j.get("rebuilt", [])) for j in run["jobs"]
                 if j.get("op") == "rebuild"), default=0)
    return 128.0 * r * inp


def _traced(run, op: str) -> bool:
    t = run["trace"]
    return bool(t and t["busy_s"] > 0 and run["device"]["platform"] == "tpu"
                and any(j.get("op") == op for j in run["jobs"]))


def hbm_roofline_pct(run, op: str):
    if not _traced(run, op):
        return None
    inp, out = coded_bytes(run, op)
    if not inp:
        return None
    least = (inp + out) / peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return least / run["trace"]["busy_s"] * 100


def idle_pct(run, op: str):
    if not _traced(run, op):
        return None
    t = run["trace"]
    return (1 - t["busy_s"] / t["window_s"]) * 100
