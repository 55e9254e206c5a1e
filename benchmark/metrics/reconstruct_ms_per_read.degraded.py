"""CPU-codec reconstruct time per read: the window's
ec_codec_seconds{op=reconstruct} of the CPU codec (single-needle
degraded reads are pinned to it, storage/store.py _rs_for), over the
reads. A program span, host clock."""
from benchmark.deploy import device_backend, total


def read(run):
    reads = run["reads"]
    if not reads:
        return None
    dev = device_backend(run["config"])
    c = run["counters"]
    spent = total(c, "ec_codec_seconds_sum", op="reconstruct") - \
        total(c, "ec_codec_seconds_sum", op="reconstruct", backend=dev)
    return spent / len(reads) * 1e3
