"""Bit-matrix scheduling for the GF(256) coded-matmul hot path.

A GF(256) coefficient matrix expands to an (8m x 8k) 0/1 matrix over
GF(2) (gf256.expand_to_bits); computing the coded matmul is then an
XOR program: output bit-plane i is the XOR of the input bit-planes
where the matrix has ones. The naive program costs popcount(B) - 8m
XORs; the classic program-optimization result (arXiv 2108.02692,
Paar-style greedy factoring) is that shared subexpressions cut that
substantially — RS parity matrices are dense and highly redundant.

This module builds the optimized program once per coefficient matrix:

  - `build_program(coef)` -> a hashable `Program` of (dst, a, b) XOR
    ops over a growing variable pool (inputs are vars [0, 8k)), plus
    the output variable per bit-plane row.
  - `apply_numpy(program, bits)` — the oracle executor tests compare
    against (and the reference semantics of the flattened op list).
  - `flatten(program)` — one int32 array the native C kernel consumes
    (gf256_codec.cc `gf256_scheduled_matmul`).
  - `plan_for(coef)` — bounded memo, shared by every backend so the
    CSE pass runs once per matrix per process.
  - `Chooser` — measured per-(matrix, size-bucket) selection between
    the scheduled kernel and the dense one, so the scheduled path is
    never slower than unscheduled at any probed size: both run once at
    first sight of a bucket, the winner is cached.

Everything here is host-side numpy + pure python; the executor that
serves requests is the C one in native/gf256_codec.cc (codec_native).
The device codecs run the dense bit-plane kernel only.
"""
from __future__ import annotations

import os
import threading
import time as _time
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from . import gf256

# below this many BYTES the dense kernels win on dispatch overhead
# alone; the chooser never even measures the scheduled path there
MIN_SCHED_BYTES = 64 << 10

# measurement sample cap: requests bigger than this are decided from a
# sample of at most this many bytes, and callers key the verdict by the
# SAMPLE's size (the size actually probed) so the never-slower-at-any-
# probed-size guarantee stays honest for large requests
MEASURE_BYTES_MAX = 4 << 20

_SCHED_ENV = "SEAWEEDFS_TPU_EC_SCHEDULE"  # auto (default) | on | off


def mode() -> str:
    v = os.environ.get(_SCHED_ENV, "auto").strip().lower()
    return v if v in ("auto", "on", "off") else "auto"


@dataclass(frozen=True)
class Program:
    """An XOR straight-line program over bit-plane variables.

    Vars [0, n_in) are the input planes (bit s of shard row j is var
    8j+s); op i defines var n_in+i as vars[a] ^ vars[b]. `outputs[r]`
    is the var holding output plane r, or -1 for an all-zero row.
    Hashable (static arg for jitted executors)."""

    n_in: int
    n_out: int
    ops: tuple[tuple[int, int, int], ...]
    outputs: tuple[int, ...]
    naive_xors: int

    @property
    def xors(self) -> int:
        return len(self.ops)

    @property
    def saving(self) -> float:
        """Fraction of naive XORs eliminated by the schedule."""
        if not self.naive_xors:
            return 0.0
        return 1.0 - self.xors / self.naive_xors


def build_program(coef: np.ndarray) -> Program:
    """CSE-schedule the XOR program of a byte coefficient matrix.

    Greedy pair factoring (Paar): while some variable pair co-occurs
    in >= 2 rows, hoist the most frequent pair into a fresh variable;
    then emit per-row XOR chains. Output is bit-identical with the
    dense GF(256) matmul by construction — the pass rewrites the
    program, never the shard byte layout.
    """
    coef = np.asarray(coef, dtype=np.uint8)
    bits = gf256.expand_to_bits(coef)          # (8m, 8k)
    n_out, n_in = bits.shape
    rows: list[set[int]] = [set(np.nonzero(bits[r])[0].tolist())
                            for r in range(n_out)]
    naive = sum(max(0, len(r) - 1) for r in rows)

    # pair -> count over all rows, maintained incrementally
    counts: dict[tuple[int, int], int] = {}

    def add_row_pairs(row: set[int], sign: int) -> None:
        mem = sorted(row)
        for i, a in enumerate(mem):
            for b in mem[i + 1:]:
                key = (a, b)
                c = counts.get(key, 0) + sign
                if c > 0:
                    counts[key] = c
                else:
                    counts.pop(key, None)

    for row in rows:
        add_row_pairs(row, +1)

    ops: list[tuple[int, int, int]] = []
    next_var = n_in
    while counts:
        (a, b), best = max(counts.items(), key=lambda kv: kv[1])
        if best < 2:
            break
        t = next_var
        next_var += 1
        ops.append((t, a, b))
        for row in rows:
            if a in row and b in row:
                add_row_pairs(row, -1)
                row.discard(a)
                row.discard(b)
                row.add(t)
                add_row_pairs(row, +1)

    outputs: list[int] = []
    for row in rows:
        mem = sorted(row)
        if not mem:
            outputs.append(-1)
            continue
        acc = mem[0]
        for v in mem[1:]:
            t = next_var
            next_var += 1
            ops.append((t, acc, v))
            acc = t
        outputs.append(acc)

    return Program(n_in, n_out, tuple(ops), tuple(outputs), naive)


def apply_numpy(program: Program, bits: np.ndarray) -> np.ndarray:
    """Oracle executor: (n_in, n) 0/1 planes -> (n_out, n) 0/1 planes.
    This IS the semantics of the flattened op list the C kernel runs;
    tests diff every other executor against it."""
    n = bits.shape[1]
    vars_: list[np.ndarray] = [bits[i] for i in range(program.n_in)]
    for _, a, b in program.ops:
        vars_.append(vars_[a] ^ vars_[b])
    out = np.zeros((program.n_out, n), dtype=bits.dtype)
    for r, v in enumerate(program.outputs):
        if v >= 0:
            out[r] = vars_[v]
    return out


def apply_bytes_numpy(program: Program, shards: np.ndarray) -> np.ndarray:
    """(k, n) uint8 shards -> (m, n) uint8 via unpack/XOR-program/pack
    — the byte-level oracle (must equal the dense GF(256) matmul)."""
    bits = gf256.unpack_bits(np.asarray(shards, dtype=np.uint8))
    return gf256.pack_bits(apply_numpy(program, bits))


def flatten(program: Program) -> np.ndarray:
    """One contiguous int32 array for the C kernel:
    [n_in, n_out, n_ops, (dst, a, b) * n_ops, outputs * n_out]."""
    head = [program.n_in, program.n_out, len(program.ops)]
    body = [v for op in program.ops for v in op]
    return np.asarray(head + body + list(program.outputs),
                      dtype=np.int32)


# ----------------------------------------------------------------------
# per-process plan memo (the CSE pass is O(ones^2)-ish; run it once
# per coefficient matrix, shared by every backend)
# ----------------------------------------------------------------------

PLAN_CACHE_MAX = 128
_plans: "OrderedDict[bytes, Program]" = OrderedDict()


def coef_key(coef: np.ndarray) -> bytes:
    coef = np.asarray(coef, dtype=np.uint8)
    return coef.shape[0].to_bytes(2, "big") + coef.tobytes()


def plan_for(coef: np.ndarray) -> Program:
    key = coef_key(coef)
    plan = _plans.get(key)
    if plan is None:
        plan = build_program(coef)
        _plans[key] = plan
        while len(_plans) > PLAN_CACHE_MAX:
            _plans.popitem(last=False)
    else:
        _plans.move_to_end(key)
    return plan


def summary_for(coef: np.ndarray) -> dict:
    plan = plan_for(coef)
    return {"naive_xors": plan.naive_xors, "scheduled_xors": plan.xors,
            "saving": round(plan.saving, 3)}


# ----------------------------------------------------------------------
# measured scheduled-vs-dense selection
# ----------------------------------------------------------------------

def _bucket(nbytes: int) -> int:
    return max(0, int(nbytes).bit_length() - 1)


@dataclass
class Chooser:
    """Per-backend winner table: (coef key, log2 size bucket) -> use
    scheduled? `auto` measures both paths once per bucket (after a
    warm call each, so jit/compile is not billed) and caches the
    winner — the guarantee that the scheduled kernel is never slower
    than the dense one at any probed size holds by construction;
    callers pass the nbytes of the sample they actually measure so the
    cached verdict is keyed by a probed size. `on`/`off`
    (SEAWEEDFS_TPU_EC_SCHEDULE) pin the answer for tests and benches.
    A caller that arrives while another thread measures the same key
    gets the dense answer instead of measuring twice."""

    max_keys: int = 256
    _won: "OrderedDict[tuple[bytes, int], bool]" = field(
        default_factory=OrderedDict)
    _pending: set = field(default_factory=set)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def use_scheduled(self, coef: np.ndarray, nbytes: int,
                      run_sched, run_dense) -> bool:
        m = mode()
        if m == "off":
            return False
        if m == "on":
            return True
        if nbytes < MIN_SCHED_BYTES:
            return False
        plan = plan_for(coef)
        if plan.xors >= plan.naive_xors:
            return False
        key = (coef_key(coef), _bucket(nbytes))
        with self._lock:
            hit = self._won.get(key)
            if hit is not None:
                self._won.move_to_end(key)
                return hit
            if key in self._pending:
                return False  # measurement in flight: dense meanwhile
            self._pending.add(key)
        return self._measure(key, run_sched, run_dense)

    def _measure(self, key, run_sched, run_dense) -> bool:
        try:
            run_sched()  # warm: build/compile both paths off the clock
            run_dense()
            t0 = _time.perf_counter()
            run_sched()
            t_s = _time.perf_counter() - t0
            t0 = _time.perf_counter()
            run_dense()
            t_d = _time.perf_counter() - t0
            win = t_s < t_d
        except Exception:
            win = False
        with self._lock:
            self._pending.discard(key)
            self._won[key] = win
            while len(self._won) > self.max_keys:
                self._won.popitem(last=False)
        return win

    def snapshot(self) -> dict:
        with self._lock:
            wins = sum(1 for v in self._won.values() if v)
            return {"buckets": len(self._won), "scheduled_wins": wins,
                    "measuring": len(self._pending)}
