"""The feed pipeline's upload rate: bytes the device codec encoded over
the seconds its stage histogram spent in h2d
(ec_codec_stage_seconds{stage=h2d}; the feed is
PallasCodec.coded_matmul_stream in ops/codec_pallas.py, its stages in
ops/feed.py). GB = 1e9."""
from benchmark.deploy import device_backend, total


def read(run):
    dev = device_backend(run["config"])
    c = run["counters"]
    moved = total(c, "ec_codec_bytes_total", op="encode", backend=dev)
    spent = total(c, "ec_codec_stage_seconds_sum", stage="h2d",
                  backend=dev)
    if not moved or not spent:
        return None
    return moved / spent / 1e9
