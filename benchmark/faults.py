"""Faults planted under the timed path, for the control and the fault
tests: `correct` has to come out false under each. The benchmark's own
runs plant none.

- flip: one byte of every codec output altered where it is produced
  (the control: it breaks the configuration's byte-exact guarantee);
- unchanged: the codec hands back its output buffer unwritten;
- half: the first half of every block's columns left out;
- offdevice: the device codec's work done by the native CPU codec.
"""
from __future__ import annotations

import numpy as np

FAULTS = ("flip", "unchanged", "half", "offdevice")


def _alter(fault: str, out):
    out = np.array(out, dtype=np.uint8, copy=True)
    if fault == "flip":
        out.reshape(-1)[0] ^= 0x5A
    elif fault == "unchanged":
        out[...] = 0
    elif fault == "half":
        out[..., :out.shape[-1] // 2] = 0
    return out


class _Faulty:
    """A codec whose outputs pass through `_alter`."""

    def __init__(self, inner, fault: str):
        self._inner = inner
        self._fault = fault
        self.name = inner.name
        if hasattr(inner, "coded_matmul_stream"):
            self.coded_matmul_stream = self._stream

    def __getattr__(self, attr):
        return getattr(self._inner, attr)

    def coded_matmul(self, coef, shards):
        return _alter(self._fault, self._inner.coded_matmul(coef, shards))

    def _stream(self, coef, blocks, depth: int = 2):
        for out in self._inner.coded_matmul_stream(coef, blocks,
                                                   depth=depth):
            yield _alter(self._fault, out)


def install(fault: str, device_backend: str) -> None:
    """Plant `fault` in this process's codec registry, before any
    server builds a codec."""
    from seaweedfs_tpu.ec import backend as ecb

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault == "offdevice":
        ecb._instances[device_backend] = ecb.get_backend("native")
        return
    for name in {device_backend, ecb.cpu_backend_name()}:
        ecb._instances[name] = _Faulty(ecb.get_backend(name), fault)
