"""The plain reference: upstream SeaweedFS's erasure code, written out
from its description and independent of the program under test.

Upstream (weed/storage/erasure_coding/ec_encoder.go) codes with
klauspost/reedsolomon's default matrix over GF(2^8) with the polynomial
x^8+x^4+x^3+x^2+1 (0x11d): the (k+m) x k Vandermonde matrix v[r][c] =
r^c, times the inverse of its top k x k square, so that the top is the
identity and rows k.. are the parity coefficients. A volume's .dat is
striped row by row: while more than one large row (k x 1 GiB) remains,
large rows; then rows of k x 1 MiB small blocks, the last zero-padded.
Data shard i holds block i of every row.

LRC(k, l, g) as this repository defines it (ec/geometry.py docstring,
listed under `assumed` in the configuration): data groups of k/l
consecutive shards, local parity k+i the XOR of group i, and the g
global parities the last g parity rows of RS(k, l+g) above.

Nothing here imports the program: a shard is judged by bytes computed
from the .dat alone.
"""
from __future__ import annotations

import numpy as np

GF_POLY = 0x11D


def _tables():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(256)")
    return int(EXP[255 - LOG[a]])


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        acc = [0] * len(b[0])
        for x, brow in zip(row, b):
            if x:
                acc = [s ^ gf_mul(x, y) for s, y in zip(acc, brow)]
        out.append(acc)
    return out


def mat_inv(a: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(256)."""
    n = len(a)
    w = [list(r) + [int(i == j) for j in range(n)] for i, r in enumerate(a)]
    for col in range(n):
        piv = next(r for r in range(col, n) if w[r][col])
        w[col], w[piv] = w[piv], w[col]
        inv = gf_inv(w[col][col])
        w[col] = [gf_mul(inv, x) for x in w[col]]
        for r in range(n):
            if r != col and w[r][col]:
                f = w[r][col]
                w[r] = [x ^ gf_mul(f, y) for x, y in zip(w[r], w[col])]
    return [r[n:] for r in w]


def rs_parity_rows(k: int, m: int) -> list[list[int]]:
    """klauspost/reedsolomon's default parity coefficients."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return mat_mul(vm, mat_inv(vm[:k]))[k:]


def parity_rows(code: dict) -> list[list[int]]:
    """Parity coefficient rows of a configuration's code."""
    k, local, glob = code["k"], code.get("local", 0), code["global"]
    if not local:
        return rs_parity_rows(k, glob)
    size = k // local
    rows = [[int(g * size <= c < (g + 1) * size) for c in range(k)]
            for g in range(local)]
    return rows + rs_parity_rows(k, local + glob)[local:]


MUL = np.array([[gf_mul(a, b) for b in range(256)] for a in range(256)],
               dtype=np.uint8)


def row_layout(dat_size: int, k: int, large: int, small: int):
    """(large rows, small rows) of a .dat, upstream's rule."""
    n_large, rest = 0, dat_size
    while rest > k * large:
        n_large += 1
        rest -= k * large
    return n_large, -(-rest // (k * small))


def data_shards(dat: np.ndarray, k: int, large: int, small: int,
                which: list[int] | None = None) -> dict[int, np.ndarray]:
    """{i: data shard i} of a .dat given as a uint8 array."""
    n_large, n_small = row_layout(dat.size, k, large, small)
    head = dat[:n_large * k * large].reshape(n_large, k, large)
    rest = dat[n_large * k * large:]
    padded = np.zeros(n_small * k * small, dtype=np.uint8)
    padded[:rest.size] = rest
    tail = padded.reshape(n_small, k, small)
    return {i: np.concatenate([head[:, i].reshape(-1),
                               tail[:, i].reshape(-1)])
            for i in (range(k) if which is None else which)}


def parity(data: np.ndarray, rows: list[list[int]],
           threads: int = 8, step: int = 1 << 20) -> np.ndarray:
    """(len(rows), n) parity of (k, n) data: table look-ups and XOR,
    column blocks on a few threads (np.take releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    n = data.shape[1]
    out = np.zeros((len(rows), n), dtype=np.uint8)

    def block(lo: int) -> None:
        hi = min(lo + step, n)
        tmp = np.empty(hi - lo, dtype=np.uint8)
        for j, row in enumerate(rows):
            acc = out[j, lo:hi]
            for i, c in enumerate(row):
                if c == 1:
                    np.bitwise_xor(acc, data[i, lo:hi], out=acc)
                elif c:
                    np.take(MUL[c], data[i, lo:hi], out=tmp)
                    np.bitwise_xor(acc, tmp, out=acc)

    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(block, range(0, n, step)))
    return out


def shards(dat: np.ndarray, code: dict, large: int, small: int,
           which: list[int] | None = None) -> dict[int, np.ndarray]:
    """Reference bytes of the shards in `which` (all by default)."""
    k = code["k"]
    total = k + code.get("local", 0) + code["global"]
    which = list(range(total)) if which is None else which
    par = [s for s in which if s >= k]
    data = data_shards(dat, k, large, small,
                       None if par else [s for s in which if s < k])
    out = {s: data[s] for s in which if s < k}
    if par:
        rows = parity_rows(code)
        stack = np.stack([data[i] for i in range(k)])
        out.update(zip(par, parity(stack, [rows[s - k] for s in par])))
    return out
