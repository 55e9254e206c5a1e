"""A cell's data, made from the seed: needles written straight through
the storage layer (no HTTP upload), and the list of what was written.

Needle sizes are log-uniform from 4 KiB to 4 MiB (chip_smoke.py's mix:
most needles small, most bytes in large ones). The sizes come from a
fixed layout seed, so every --seed fills the volume with the same
needle sizes in the same places, and --seed draws only the payload
bytes and the order of requests: runs with different seeds do the
same work.
"""
from __future__ import annotations

import math
import os

import numpy as np

LAYOUT_SEED = 20121
COOKIE = 0x5EED
# room under the size limit for needle headers and padding
HEADROOM = 8 << 20


def needle_sizes(total: int, lo: int, hi: int) -> list[int]:
    rng = np.random.default_rng(LAYOUT_SEED)
    llo, lhi = math.log(lo), math.log(hi)
    sizes, acc = [], 0
    while True:
        s = int(math.exp(rng.uniform(llo, lhi)))
        if acc + s > total:
            return sizes
        sizes.append(s)
        acc += s


def payload(seed: int, nbytes: int) -> memoryview:
    return memoryview(np.random.default_rng(seed).bytes(nbytes))


def write_volume(vol_dir: str, vid: int, seed: int,
                 config: dict) -> dict:
    """One volume of needles 1..n; returns its needle table."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    total = (config["volume_size_limit_mb"] << 20) - HEADROOM
    sizes = needle_sizes(total, config["needle_min_bytes"],
                         config["needle_max_bytes"])
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(int)
    blob = payload(seed, int(sum(sizes)))
    os.makedirs(vol_dir, exist_ok=True)
    v = Volume(vol_dir, "", vid, create=True)
    try:
        for i, (off, size) in enumerate(zip(offsets, sizes)):
            v.append_needle(Needle(id=i + 1, cookie=COOKIE,
                                   data=bytes(blob[off:off + size])))
        v.sync()
    finally:
        v.close()
    return {"base": v.file_name(), "sizes": sizes,
            "offsets": [int(o) for o in offsets], "payload": blob}


def fid(vid: int, needle_id: int) -> str:
    return f"{vid},{needle_id:x}{COOKIE:08x}"
