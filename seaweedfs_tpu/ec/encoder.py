"""File-level erasure coding: volume .dat -> .ec00..13 shard files,
rebuild of missing shards, and .idx -> .ecx sorted index generation.

Functional equivalents of the reference's WriteEcFiles / RebuildEcFiles /
WriteSortedFileFromIdx (/root/reference/weed/storage/erasure_coding/
ec_encoder.go:27,57,61), redesigned for a batched accelerator:

* The reference streams 256KB per-shard buffers through the CPU codec one
  stripe-row at a time. Here the .dat is memory-mapped and fed to the
  codec backend as wide (k, W) byte matrices — W spans MANY stripe rows of
  the small-block region at once (a row-group transpose turns contiguous
  file bytes into codec columns), so a single device dispatch covers tens
  of MB and the MXU stays busy.
* The same coded_matmul entry point serves encode (parity rows) and
  rebuild (recovery rows from rs_matrix), so rebuild rides the identical
  batched path instead of a separate Reconstruct loop.

Shard-file byte layout is identical to the reference's, so geometry
(geometry.row_layout / locate) and fixtures interoperate.
"""
from __future__ import annotations

import os
import time as _time

import numpy as np

from ..storage import needle_map
from ..utils import metrics, tracing
from . import geometry as geo
from .backend import ReedSolomon, get_backend

# Default column width per codec dispatch (bytes per shard). Multiple
# small rows are packed per dispatch up to this width.
DEFAULT_CHUNK = 32 << 20


def _contig_view(row: np.ndarray):
    """Zero-copy buffer for file writes (tobytes() copied every shard
    block once more than needed — measured ~2x on the e2e encode)."""
    return memoryview(np.ascontiguousarray(row))


class _AsyncWriter:
    """Background thread pool draining ordered (file, array) queues.

    File writes are the measured bottleneck of the e2e encode
    (page-cache memcpy + write-back vs ~3 GB/s codec); pushing them
    off the producer thread overlaps write-back with gather + codec
    dispatch. Each output file is pinned to ONE thread (first-seen
    round-robin), so per-file write order is the enqueue order while
    different shard files write concurrently — write() drops the GIL,
    so even one core overlaps the page-cache copies with codec work,
    and real disks see >1 outstanding stream."""

    def __init__(self, max_pending_bytes: int = 256 << 20,
                 threads: int = 4):
        import queue
        import threading

        self._qs = [queue.Queue() for _ in range(max(1, threads))]
        self._affinity: dict[int, int] = {}  # id(file) -> queue index
        self._next = 0
        self._err: list[BaseException] = []
        # backpressure is byte-denominated, not item-count: a 16-item
        # bound at 32MB rows would pin ~512MB of blocks alive
        self._max = max_pending_bytes
        self._bytes = 0
        self._cond = threading.Condition()
        self._threads = [
            threading.Thread(target=self._run, args=(q,), daemon=True)
            for q in self._qs]
        for t in self._threads:
            t.start()

    def _run(self, q) -> None:
        while True:
            item = q.get()
            if item is None:
                return
            f, arr = item
            if not self._err:
                try:
                    with tracing.interval("ec.shard_write"):
                        view = _contig_view(arr)
                        # raw (buffering=0) files may short-write (e.g.
                        # ENOSPC partway); loop or the next block lands
                        # at the wrong offset and the shard silently
                        # corrupts
                        while len(view):
                            n = f.write(view)
                            if n is None or n == len(view):
                                break
                            view = view[n:]
                    metrics.counter_add("ec_shard_write_bytes_total",
                                        arr.nbytes)
                except BaseException as e:  # noqa: BLE001 - close re-raises
                    self._err.append(e)
            with self._cond:
                self._bytes -= arr.nbytes
                self._cond.notify_all()

    def put(self, f, arr: np.ndarray) -> None:
        with self._cond:
            if self._bytes >= self._max and not self._err:
                # the producer's backpressure wait, timed only when it
                # happens
                with tracing.interval("ec.encode.write_wait"):
                    while self._bytes >= self._max and not self._err:
                        self._cond.wait()
            self._bytes += arr.nbytes
        qi = self._affinity.get(id(f))
        if qi is None:
            qi = self._affinity[id(f)] = self._next % len(self._qs)
            self._next += 1
        self._qs[qi].put((f, arr))

    def close(self) -> None:
        for q in self._qs:
            q.put(None)
        for t in self._threads:
            t.join()
        if self._err:
            raise self._err[0]


def write_sorted_ecx(base: str, ext: str = ".ecx") -> None:
    """.idx -> sorted .ecx (WriteSortedFileFromIdx, ec_encoder.go:27)."""
    db = needle_map.MemDb()
    db.load_from_idx(base + ".idx")
    db.save_to_idx(base + ext)


def codec_of(base: str) -> tuple[int, int]:
    """(data_shards, parity_shards) of the shard set at `base`, read
    from the .vif sidecar ('' -> the RS(10,4) default)."""
    code = code_of(base)
    return code.k, code.m


def code_of(base: str) -> geo.CodeConfig:
    """Full code config of the shard set at `base` (.vif sidecar) —
    what rebuild and repair must consult: an LRC's recovery rows and
    read fan-in differ from RS even at the same (k, m)."""
    from ..storage import volume_info as vinfo

    vi = vinfo.maybe_load_volume_info(base + ".vif")
    return geo.parse_code(vi.ec_codec if vi else "")


def _record_codec(base: str, codec: str) -> None:
    """Persist a non-default codec in the .vif so every later consumer
    (mount, rebuild, decode, degraded read) agrees on the geometry."""
    from ..storage import volume_info as vinfo

    vi = vinfo.maybe_load_volume_info(base + ".vif") or vinfo.VolumeInfo()
    vi.ec_codec = codec
    vinfo.save_volume_info(base + ".vif", vi)


def write_ec_files(base: str, backend: str = "auto",
                   large_block: int = geo.LARGE_BLOCK,
                   small_block: int = geo.SMALL_BLOCK,
                   chunk: int = DEFAULT_CHUNK,
                   codec: str = "") -> None:
    """Generate .ec00..ecNN from `base`.dat (WriteEcFiles equivalent).
    `codec` selects the code family: "k.m" a (wide) RS, "lrc-k.l.g" an
    LRC; default RS(10,4)."""
    with tracing.span("ec.write_ec_files", kind="internal") as rec:
        _write_ec_files(base, backend, large_block, small_block, chunk,
                        codec, rec)


def _write_ec_files(base: str, backend: str, large_block: int,
                    small_block: int, chunk: int, codec: str,
                    rec: dict) -> None:
    code = geo.parse_code(codec or "")
    k, m = code.k, code.m
    # identity is the CODE, not (k, m): lrc-10.2.2 shares RS(10,4)'s
    # shard count but not its parity bytes, so it must hit the .vif too
    if code != geo.parse_code(""):
        _record_codec(base, codec)
    else:
        # re-encoding at the default codec must CLEAR a stale wide-code
        # marker left by a previous encode/decode cycle, or every later
        # consumer reads 10+4 shard files with k=28 geometry
        from ..storage import volume_info as vinfo

        vi = vinfo.maybe_load_volume_info(base + ".vif")
        if vi is not None and vi.ec_codec:
            vi.ec_codec = ""
            vinfo.save_volume_info(base + ".vif", vi)
    rs = ReedSolomon(k, m, backend=backend, code=code)
    dat_path = base + ".dat"
    dat_size = os.path.getsize(dat_path)
    n_large, n_small = geo.row_layout(dat_size, large_block, small_block,
                                      data_shards=k)

    # resolve `auto` so the dispatch below sees the real backend; the
    # router interpolates the measured bandwidth curve at THIS
    # volume's size, so a small volume can route to the CPU codec
    # while a bulk encode on the same host rides the device
    backend_name = getattr(rs.backend, "name", "")
    if backend_name == "auto":
        resolve_for = getattr(rs.backend, "resolve_for", None)
        if resolve_for is not None:
            resolve_for(dat_size)
        else:
            rs.backend._resolve()
        backend_name = getattr(rs.backend, "chosen", "") or ""
    rec["peer"] = backend_name
    if code.substripes > 1:
        # the coupled encode; never the native whole-file call, which
        # codes each shard offset on its own
        _encode_substriped(rs, base, dat_size, large_block, small_block,
                           chunk)
        return
    if backend_name == "native" and dat_size:
        # the whole read -> parity -> write loop in one native call:
        # no GIL on either the producer or writer side (the measured
        # residual that kept a third of the disk idle). Byte-identical
        # output — same ops/rs_matrix coefficients as rs.encode().
        from .. import native as nat
        from ..ops import rs_matrix
        from .backend import observe_codec

        t0 = _time.perf_counter()
        nat.ec_encode_file(
            dat_path, [base + geo.shard_ext(i) for i in range(k + m)],
            rs_matrix.parity_rows_for(code), k, m, large_block,
            small_block)
        # the bypass skips rs.encode entirely — record it here or the
        # fastest path would be the only uninstrumented one
        observe_codec("encode", "native", _time.perf_counter() - t0,
                      dat_size)
        return

    dat = np.memmap(dat_path, dtype=np.uint8, mode="r") if dat_size else \
        np.zeros(0, dtype=np.uint8)
    # buffering=0: every write here is a full shard block; the default
    # BufferedWriter adds a copy that measured ~2x on this path
    outs = [open(base + geo.shard_ext(i), "wb", buffering=0)
            for i in range(k + m)]
    try:
        _encode_region(rs, dat, 0, n_large, large_block, chunk, outs)
        _encode_region(rs, dat, n_large * large_block * k,
                       n_small, small_block, chunk, outs)
    finally:
        for f in outs:
            f.close()
        if dat_size:
            del dat


def _region_blocks(dat: np.ndarray, start: int, n_rows: int,
                   block: int, chunk: int, k: int = geo.DATA_SHARDS,
                   wide: bool = True):
    """Yield the (k, w) codec input blocks for `n_rows` stripe rows of
    `block`-sized blocks starting at file offset `start`, in shard-file
    write order.

    wide=True packs many rows per dispatch via a transpose gather —
    right for device codecs, whose per-dispatch cost (transfer setup,
    kernel launch) dwarfs the strided copy. wide=False walks one stripe row
    at a time: a full row is a CONTIGUOUS window of the .dat, so the
    codec input is a zero-copy reshape view — no gather at all except
    the zero-padded tail row. Right for CPU codecs, where the
    transpose copy was the measured residual between encode speed and
    the disk ceiling."""
    row_bytes = block * k
    total = dat.shape[0]
    if block >= chunk:
        # large blocks: walk one row at a time, column-chunked
        for r in range(n_rows):
            row_start = start + r * row_bytes
            for c0 in range(0, block, chunk):
                c1 = min(c0 + chunk, block)
                with tracing.interval("ec.encode.gather"):
                    cols = _gather_columns(dat, row_start, block, c0, c1,
                                           k)
                yield cols
        return
    if not wide:
        for r in range(n_rows):
            row_start = start + r * row_bytes
            if row_start + row_bytes <= total:
                yield dat[row_start:row_start + row_bytes] \
                    .reshape(k, block)
            else:  # tail row: zero-pad past EOF
                flat = np.zeros(row_bytes, dtype=np.uint8)
                avail = max(0, total - row_start)
                if avail:
                    flat[:avail] = dat[row_start:row_start + avail]
                yield flat.reshape(k, block)
        return
    # small blocks, wide: pack many rows per dispatch
    rows_per = max(1, chunk // block)
    for r0 in range(0, n_rows, rows_per):
        r1 = min(r0 + rows_per, n_rows)
        span_start = start + r0 * row_bytes
        span_len = (r1 - r0) * row_bytes
        avail = max(0, min(span_len, total - span_start))
        with tracing.interval("ec.encode.gather"):
            if avail == span_len:
                # full span: transpose straight off the memmap — one
                # strided copy instead of flat-copy + transpose-copy
                flat = dat[span_start:span_start + span_len]
            else:
                flat = np.zeros(span_len, dtype=np.uint8)
                if avail:
                    flat[:avail] = dat[span_start:span_start + avail]
            # (rows, k, block) -> (k, rows*block): row-major per shard
            cols = np.ascontiguousarray(
                flat.reshape(r1 - r0, k, block).transpose(1, 0, 2)
                .reshape(k, (r1 - r0) * block))
        yield cols


def _encode_region(rs: ReedSolomon, dat: np.ndarray, start: int, n_rows: int,
                   block: int, chunk: int, outs: list) -> None:
    """Encode a stripe-row region, writing each shard's blocks
    sequentially. Data-shard bytes are written as each block is
    gathered (they never touch the codec); parity arrives through the
    backend's streaming pipeline, which keeps `depth` blocks in flight
    on a device codec so H2D, MXU compute, and D2H overlap instead of
    serializing per block."""
    k = rs.k
    # CPU codecs take narrow zero-copy row views (the transpose gather
    # was their residual overhead); device codecs get wide packed
    # dispatches that amortize transfer/launch latency. `auto` must be
    # RESOLVED first or the production default would silently keep the
    # wide gather on CPU machines — the exact overhead this removes.
    backend_name = getattr(rs.backend, "name", "")
    if backend_name == "auto":
        rs.backend._resolve()
        backend_name = getattr(rs.backend, "chosen", "") or ""
    wide = backend_name not in ("numpy", "native")
    # pipeline depth from the measured curve at this dispatch size
    # (double-buffer default when nothing is measured)
    from .backend import pipeline_depth_for

    depth = pipeline_depth_for(k * chunk)
    w = _AsyncWriter()
    try:
        def gen():
            for data in _region_blocks(dat, start, n_rows, block, chunk,
                                       k, wide=wide):
                for i in range(k):
                    w.put(outs[i], data[i])
                yield data

        for parity in rs.encode_stream(gen(), depth=depth):
            for j in range(rs.m):
                w.put(outs[k + j], parity[j])
    finally:
        w.close()


def _shard_columns(dat: np.ndarray, y0: int, y1: int, n_large: int,
                   large_block: int, small_block: int,
                   out: np.ndarray) -> None:
    """Fill `out` (k, y1-y0) with the data shards' bytes at shard
    offsets [y0, y1), taken from their .dat offsets through the row
    layout; zeros past EOF."""
    k = out.shape[0]
    total = dat.shape[0]
    large_end = n_large * large_block
    y = y0
    while y < y1:
        if y < large_end:
            block = large_block
            row, inner = divmod(y, block)
            row_start = row * k * block
            stop = min(y1, (row + 1) * block)
        else:
            block = small_block
            row, inner = divmod(y - large_end, block)
            row_start = large_end * k + row * k * block
            stop = min(y1, large_end + (row + 1) * block)
        o, w = y - y0, stop - y
        for j in range(k):
            src = row_start + j * block + inner
            got = max(0, min(w, total - src))
            out[j, o:o + got] = dat[src:src + got]
            out[j, o + got:o + w] = 0
        y = stop


def _encode_substriped(rs: ReedSolomon, base: str, dat_size: int,
                       large_block: int, small_block: int,
                       chunk: int) -> None:
    """Hitchhiker's coupled encode (hop-and-couple): for each column x
    of the first half of the shards, the 2k inputs are every data
    shard at x and at h+x, and one (2m, 2k) coded matmul gives the m
    parities at x and at h+x, piggybacks included. Each shard file
    has two sequential writers, one from 0 and one from h."""
    k, m = rs.k, rs.m
    n_large, _ = geo.row_layout(dat_size, large_block, small_block,
                                data_shards=k)
    half = geo.shard_file_size(dat_size, large_block, small_block,
                               data_shards=k) // 2
    dat = np.memmap(base + ".dat", dtype=np.uint8, mode="r") \
        if dat_size else np.zeros(0, dtype=np.uint8)
    paths = [base + geo.shard_ext(i) for i in range(k + m)]
    heads = [open(p, "wb", buffering=0) for p in paths]
    tails = []
    from .backend import pipeline_depth_for

    try:
        for p in paths:
            tails.append(open(p, "r+b", buffering=0))
            tails[-1].seek(half)
        depth = pipeline_depth_for(2 * k * chunk, code=rs.code.spec)
        w = _AsyncWriter()
        try:
            def gen():
                for x0 in range(0, half, chunk):
                    x1 = min(x0 + chunk, half)
                    with tracing.interval("ec.encode.gather"):
                        cols = np.empty((2 * k, x1 - x0), dtype=np.uint8)
                        _shard_columns(dat, x0, x1, n_large, large_block,
                                       small_block, cols[:k])
                        _shard_columns(dat, half + x0, half + x1, n_large,
                                       large_block, small_block, cols[k:])
                    for i in range(k):
                        w.put(heads[i], cols[i])
                        w.put(tails[i], cols[k + i])
                    yield cols

            for parity in rs.encode_stream(gen(), depth=depth):
                for j in range(m):
                    w.put(heads[k + j], parity[j])
                    w.put(tails[k + j], parity[m + j])
        finally:
            w.close()
    finally:
        for f in heads + tails:
            f.close()
        del dat


def _gather_columns(dat: np.ndarray, row_start: int, block: int,
                    c0: int, c1: int,
                    k: int = geo.DATA_SHARDS) -> np.ndarray:
    """(k, c1-c0) data matrix for one stripe row, zero-padded past EOF."""
    w = c1 - c0
    out = np.zeros((k, w), dtype=np.uint8)
    total = dat.shape[0]
    for i in range(k):
        s = row_start + i * block + c0
        e = min(s + w, total)
        if e > s:
            out[i, : e - s] = dat[s:e]
    return out


def rebuild_ec_files(base: str, backend: str = "auto",
                     chunk: int = DEFAULT_CHUNK,
                     only_shards: list[int] | None = None) -> list[int]:
    """Regenerate missing .ecXX files from the present ones
    (RebuildEcFiles, ec_encoder.go:61). Returns rebuilt shard ids.
    `only_shards` restricts which missing shards are produced."""
    code = code_of(base)
    k, m = code.k, code.m
    present, missing = [], []
    for i in range(k + m):
        (present if os.path.exists(base + geo.shard_ext(i)) else
         missing).append(i)
    if only_shards is not None:
        missing = [i for i in missing if i in set(only_shards)]
    if not missing:
        return []
    if not code.recoverable(present):
        raise ValueError(
            f"shards {present} cannot rebuild {code.spec} "
            f"(need rank {k})")

    rs = ReedSolomon(k, m, backend=backend, code=code)
    sizes = {os.path.getsize(base + geo.shard_ext(i)) for i in present}
    if len(sizes) != 1:
        raise ValueError(f"present shards disagree on size: {sizes}")
    shard_size = sizes.pop()
    if code.substripes > 1:
        _rebuild_substriped(base, rs, chunk, shard_size, present, missing)
        return missing

    # one recovery matrix serves every chunk; the code's repair plan
    # picks the inputs (an LRC single-loss reads its group, not k), and
    # only THOSE shards are opened — repair IO equals the plan's fan-in
    from ..ops import rs_matrix

    rows, inputs = rs_matrix.recovery_rows_for(code, present, missing)
    ins = {i: np.memmap(base + geo.shard_ext(i), dtype=np.uint8, mode="r")
           for i in inputs} if shard_size else {i: np.zeros(0, np.uint8)
                                                for i in inputs}
    outs = {i: open(base + geo.shard_ext(i), "wb", buffering=0)
            for i in missing}
    # stream chunks through the backend pipeline (device codecs
    # overlap read + H2D + compute + D2H)
    from .backend import pipeline_depth_for

    depth = pipeline_depth_for(len(inputs) * chunk, code=code.spec)
    try:
        def gen():
            for c0 in range(0, shard_size, chunk):
                c1 = min(c0 + chunk, shard_size)
                yield np.stack([np.asarray(ins[i][c0:c1]) for i in inputs])

        w = _AsyncWriter()
        try:
            for rec in rs.matmul_stream(rows, gen(), depth=depth,
                                        op="reconstruct"):
                for j, i in enumerate(missing):
                    w.put(outs[i], rec[j])
        finally:
            w.close()
    finally:
        for f in outs.values():
            f.close()
    return missing


def _rebuild_substriped(base: str, rs: ReedSolomon, chunk: int,
                        shard_size: int, present: list[int],
                        missing: list[int]) -> None:
    """rebuild_ec_files for a sub-striped code: the repair plan's
    (shard, half) reads, column x of each half at a time, and the
    lost shards' halves written at x and at h+x."""
    from ..ops import rs_matrix
    from .backend import pipeline_depth_for

    code = rs.code
    half = shard_size // 2
    plan = code.repair_plan(missing, present)
    targets = [code.halfrow(s, h) for s in missing for h in range(2)]
    rows, inputs = rs_matrix.recovery_rows_for(
        code, [code.halfrow(s, h) for s, h in plan.reads], targets)
    reads = [code.split_row(r) for r in inputs]
    ins = {s: np.memmap(base + geo.shard_ext(s), dtype=np.uint8,
                        mode="r") for s, _ in reads} if half else {}
    heads = {s: open(base + geo.shard_ext(s), "wb", buffering=0)
             for s in missing}
    tails = {}
    try:
        for s in missing:
            tails[s] = open(base + geo.shard_ext(s), "r+b", buffering=0)
            tails[s].seek(half)

        def gen():
            for x0 in range(0, half, chunk):
                x1 = min(x0 + chunk, half)
                yield np.stack([np.asarray(ins[s][h * half + x0:
                                                  h * half + x1])
                                for s, h in reads])

        depth = pipeline_depth_for(len(inputs) * chunk, code=code.spec)
        w = _AsyncWriter()
        try:
            for rec in rs.matmul_stream(rows, gen(), depth=depth,
                                        op="reconstruct"):
                for j, r in enumerate(targets):
                    s, h = code.split_row(r)
                    w.put((tails if h else heads)[s], rec[j])
        finally:
            w.close()
    finally:
        for f in list(heads.values()) + list(tails.values()):
            f.close()


def verify_ec_files(base: str, backend: str = "auto",
                    chunk: int = DEFAULT_CHUNK) -> bool:
    """Parity-check the full shard set (scrub building block). A
    sub-striped code checks its halves side by side: column x of
    every shard at x and at h+x."""
    code = code_of(base)
    k, m = code.k, code.m
    rs = ReedSolomon(k, m, backend=backend, code=code)
    paths = [base + geo.shard_ext(i) for i in range(k + m)]
    if not all(os.path.exists(p) for p in paths):
        return False
    size = os.path.getsize(paths[0])
    maps = [np.memmap(p, dtype=np.uint8, mode="r") for p in paths]
    for mm in maps:
        if mm.shape[0] != size:
            return False
    subs = code.substripes
    if size % subs:
        return False
    part = size // subs
    # one stack row per code row (code.halfrow order)
    data = [r for r in range(code.rows) if r % code.total < k]
    par = [r for r in range(code.rows) if r % code.total >= k]
    from collections import deque

    expected: deque = deque()

    def gen():
        for c0 in range(0, part, chunk):
            c1 = min(c0 + chunk, part)
            stack = np.stack([np.asarray(mm[h * part + c0:h * part + c1])
                              for h in range(subs) for mm in maps])
            expected.append(stack[par])
            yield stack[data]

    for parity in rs.encode_stream(gen()):
        if not np.array_equal(parity, expected.popleft()):
            return False
    return True
