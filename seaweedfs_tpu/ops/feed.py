"""Host side of the staged device feed: stage timing and readback.

Shared by the device codecs (codec_pallas, codec_mesh) and the
models.ec_pipeline feeds. Every stage — pread (waiting on the block
source), h2d, drain_wait (the drain thread waiting for the kernel,
which overlaps it), d2h, and relay (finished results waiting for the
consumer) — is timed into ec_codec_stage_seconds{stage,backend} and,
but for relay, which no thread is inside, opens a
`swfs.ec.stage.<stage>` profiler annotation, so a trace shows the
kernel's own time beside the host stage around it.
"""
from __future__ import annotations

from contextlib import contextmanager

import numpy as np

from ..utils import metrics, tracing


def observe_stage(backend: str, stage: str, seconds: float) -> None:
    """Per-stage feed timing (pread/h2d/drain_wait/d2h/relay). Lives
    next to ec_codec_seconds; one extra label dimension, one histogram
    per (stage, backend)."""
    metrics.histogram_observe("ec_codec_stage_seconds", seconds,
                              {"stage": stage, "backend": backend})


@contextmanager
def stage(backend: str, name: str):
    """One feed stage on this thread: the `swfs.ec.stage.<name>`
    annotation over it, its seconds into ec_codec_stage_seconds."""
    with tracing.interval("ec.stage." + name, observe=False) as iv:
        yield
    observe_stage(backend, name, iv.seconds)


def _readback(dev) -> np.ndarray:
    """D2H for one device result. dlpack first: on CPU devices (and
    any platform sharing the host address space) it aliases the device
    buffer instead of copying — the consumer only reads, so the
    read-only view is fine. Accelerators fall back to np.asarray."""
    try:
        return np.from_dlpack(dev)
    except Exception:
        return np.asarray(dev)


def _collect(devs: list) -> np.ndarray:
    """Force D2H on [(device_array, true_width)] and reassemble the
    (m, n) block (shared by the sync path and the streaming drain
    thread)."""
    if len(devs) == 1:
        dev, w = devs[0]
        out = _readback(dev)
        return out[:, :w] if out.shape[1] != w else out
    return np.concatenate(
        [_readback(dev)[:, :w] for dev, w in devs], axis=1)


def _pad_cols(arr: np.ndarray, n: int) -> np.ndarray:
    if arr.shape[1] == n:
        return arr
    out = np.zeros((arr.shape[0], n), dtype=arr.dtype)
    out[:, : arr.shape[1]] = arr
    return out
