"""The plain reference of Hitchhiker-XOR on RS(10,4) with
hop-and-couple (Rashmi et al., "A 'Hitchhiker's' Guide to Fast and
Efficient Data Reconstruction in Erasure-coded Data Centers", SIGCOMM
2014, sections 3 and 5), written out from the paper and independent of
the program under test.

Each shard of S bytes is two halves of h = S/2 bytes, and shard byte x
is coupled with byte h+x (hop-and-couple). The a-bytes (offset x) and
the b-bytes (offset h+x) of the data shards are two RS(10,4) codewords
of klauspost's matrix, reference.py's. The data shards are the .dat's
stripes as in RS(10,4). Parity 10 is RS(10,4)'s over both halves;
parities 11, 12 and 13 carry in their b-half, on top of RS(10,4)'s
byte, the XOR of the a-bytes of the data groups {0,1,2}, {3,4,5} and
{6,7,8,9}: k data shards in m-1 consecutive groups, the larger last.

So this reference is reference.py's RS shards with those XORs added.
"""
from __future__ import annotations

import numpy as np

from benchmark import reference


def groups(k: int, m: int) -> list[list[int]]:
    """The piggyback groups: k data shards in m-1 consecutive groups,
    sizes as even as they go, the larger ones last."""
    n = m - 1
    base, extra = divmod(k, n)
    out, start = [], 0
    for i in range(n):
        size = base + (1 if i >= n - extra else 0)
        out.append(list(range(start, start + size)))
        start += size
    return out


def shards(dat: np.ndarray, code: dict, large: int, small: int,
           which: list[int] | None = None) -> dict[int, np.ndarray]:
    """Reference bytes of the shards in `which` (all by default)."""
    k, m = code["k"], code["global"]
    which = list(range(k + m)) if which is None else list(which)
    piggy = [s for s in which if s > k]
    need = sorted(set(which) | (set(range(k)) if piggy else set()))
    rs = reference.shards(dat, {"k": k, "local": 0, "global": m},
                          large, small, need)
    out = {s: rs[s] for s in which}
    grps = groups(k, m)
    for s in piggy:
        h = rs[s].size // 2
        row = rs[s].copy()
        for j in grps[s - k - 1]:
            np.bitwise_xor(row[h:], rs[j][:h], out=row[h:])
        out[s] = row
    return out
