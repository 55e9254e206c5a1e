"""The feed pipeline's upload rate: bytes the device codec encoded over
the seconds its stage histogram spent in h2d
(ec_codec_stage_seconds{stage=h2d}, ops/codec_jax.py). GB = 1e9."""
from benchmark.deploy import total


def read(run):
    dev = run["config"]["ec_backend"]
    c = run["counters"]
    moved = total(c, "ec_codec_bytes_total", op="encode", backend=dev)
    spent = total(c, "ec_codec_stage_seconds_sum", stage="h2d",
                  backend=dev)
    if not moved or not spent:
        return None
    return moved / spent / 1e9
