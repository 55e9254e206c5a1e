"""From a profiler trace (.xplane.pb) to device busy time, idle share,
the top device ops and the longest idle gaps.

Busy time is the union of the compute-op intervals on each TPU plane's
"XLA Ops" line; transfers sit on other lines and are not counted. The
window is the benchmark's own `bench.window` host annotation. Idle time
is split where a `bench.*` host annotation opens or closes, and each
piece is named by the innermost one open through it, so the breakdown
says what the benchmark was waiting on then. Under
JAX_PLATFORMS=cpu (a rehearsal) the "device" is XLA's CPU client: the
host events that carry an `hlo_op` stat.
"""
from __future__ import annotations

import glob
import json
import os

WINDOW = "bench.window"
PREFIX = "bench."
OP_LINE = "XLA Ops"
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The table row of `device_kind`; an unknown kind is an error."""
    with open(PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS}; add its peaks with their source") \
            from None


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"{trace_dir}: {len(files)} xplane files")
    return files[0]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def read_events(path: str) -> dict:
    """{'devices': {plane: [(name, start, end)]}, 'annotations':
    [(name, start, end)]} in ns, from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    notes: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        tpu = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            for e in line.events:
                # an op's name is its HLO text; keep the part before " = "
                span = (e.name.split(" = ", 1)[0], e.start_ns,
                        e.start_ns + e.duration_ns)
                if tpu:
                    if line.name == OP_LINE:
                        devices.setdefault(plane.name, []).append(span)
                elif plane.name.startswith("/host:"):
                    if e.name.startswith(PREFIX):
                        notes.append(span)
                    elif e.duration_ns and "hlo_op" in dict(e.stats):
                        cpu_ops.append(span)
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return {"devices": devices, "annotations": notes}


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s (mean over device planes), window_s, idle share, the top
    device ops by total time and the longest idle gaps, named."""
    wins = [(a, b) for n, a, b in events["annotations"] if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found "
                         f"{len(wins)}")
    lo, hi = wins[0]
    window_s = (hi - lo) / 1e9
    planes = events["devices"]
    busy = {}
    by_op: dict[str, float] = {}
    for plane, ops in sorted(planes.items()):
        spans = _clip([(a, b) for _, a, b in ops], lo, hi)
        busy[plane] = _union(spans)
        for name, a, b in ops:
            if b > lo and a < hi:
                by_op[name] = by_op.get(name, 0.0) + \
                    (min(b, hi) - max(a, lo)) / 1e9
    busy_s = (sum(sum(b - a for a, b in u) for u in busy.values())
              / len(busy) / 1e9) if busy else 0.0
    gaps = []
    if busy:
        first = busy[sorted(busy)[0]]
        edge = lo
        for a, b in first + [[hi, hi]]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
    notes = [(n, a, b) for n, a, b in events["annotations"]
             if n != WINDOW]

    def name_at(t: float) -> str:
        best = None
        for n, s, e in notes:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        return best[0] if best else "outside any bench span"

    # split each gap where a bench span opens or closes, so that each
    # piece is named by the innermost span open through it
    pieces = []
    for a, b in gaps:
        cuts = sorted({a, b} | {t for _, s, e in notes for t in (s, e)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            name = name_at((x + y) / 2)
            if pieces and pieces[-1][0] == name and pieces[-1][2] == x:
                pieces[-1][2] = y
            else:
                pieces.append([name, x, y])
    pieces.sort(key=lambda p: p[1] - p[2])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s else None,
        "device_planes": len(busy),
        "device_ops": [[n, s] for n, s in sorted(
            by_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, (b - a) / 1e9] for n, a, b in pieces[:top]],
    }


def reduce_dir(trace_dir: str) -> dict:
    return reduce(read_events(find_xplane(trace_dir)))
