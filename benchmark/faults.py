"""Faults planted under the timed path, for the control and the fault
tests: `correct` has to come out false under each. The benchmark's own
runs plant none.

- flip: one byte of every codec output altered where it is produced
  (the control: it breaks the configuration's byte-exact guarantee);
- unchanged: the codec hands back its output buffer unwritten;
- half: the first half of every block's columns left out;
- offdevice: the device codec's work done by the native CPU codec.

Under `ec_backend: auto` a fault is planted in every concrete codec the
router can hand out on this host, the single-chip device codec and the
CPU codec, so it reaches whichever side the router picks; `offdevice`
has no device guarantee to break there and is refused. Where the file
encoder takes the native codec, it codes the whole file in one native
call (native.ec_encode_file) and not through the codec object: the
fault alters that call's parity files as it returns.
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


def _faulty_file_encode(inner, fault: str):
    """native.ec_encode_file, its parity files passed through `_alter`."""
    def encode(dat_path, shard_paths, coef, k, m, *args, **kw):
        inner(dat_path, shard_paths, coef, k, m, *args, **kw)
        for path in shard_paths[k:]:
            out = np.fromfile(path, dtype=np.uint8)
            if out.size:
                _alter(fault, out).tofile(path)
    return encode


def install(fault: str, config: dict) -> None:
    """Plant `fault` in this process's codec registry, before any
    server builds a codec."""
    from seaweedfs_tpu.ec import backend as ecb

    from .deploy import device_backend

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    device = device_backend(config)
    if fault == "offdevice":
        if config["ec_backend"] == "auto":
            raise ValueError("fault 'offdevice' under ec_backend auto: "
                             "the router may rightly choose the CPU "
                             "codec, so there is no device guarantee "
                             "to break")
        ecb._instances[device] = ecb.get_backend("native")
        return
    for name in {device, ecb.cpu_backend_name()}:
        ecb._instances[name] = _Faulty(ecb.get_backend(name), fault)
    if config["ec_backend"] in ("auto", "native"):
        from seaweedfs_tpu import native

        native.ec_encode_file = _faulty_file_encode(native.ec_encode_file,
                                                    fault)
