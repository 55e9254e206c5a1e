"""The shard-range fetch that partial rebuilds and degraded reads share
(VolumeServer._fetch_shard_from_holders), against live holder stubs:
a sized body and a chunked one come back byte-identical from the
one-call read, a short body fails its holder over to the next, and
keep-alive survives the read; and the first-k-wins fan-out over them
(VolumeServer._remote_shards_fetch_sync) has every range of the
widest code in flight at once."""
import asyncio
import time
from types import SimpleNamespace

import numpy as np
import pytest
from aiohttp import web

from seaweedfs_tpu.ec import geometry as geo
from seaweedfs_tpu.rpc.http import ServerThread
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.utils import metrics

RANGE = 4 << 20
DATA = np.random.default_rng(24).bytes(2 * RANGE)


def _range(req: web.Request) -> bytes:
    off = int(req.query["offset"])
    return DATA[off:off + int(req.query["size"])]


class _Holder:
    """One aiohttp holder on its own loop thread that answers
    /admin/ec/shard_read with `handler` and records each request's
    client address (one per TCP connection)."""

    def __init__(self, handler):
        self.peers: list = []

        async def shard_read(req):
            self.peers.append(req.transport.get_extra_info("peername"))
            return await handler(req)

        app = web.Application()
        app.router.add_get("/admin/ec/shard_read", shard_read)
        self.thread = ServerThread(app).start()

    def stop(self):
        self.thread.stop()


async def _whole(req):
    # the shape of handle_ec_shard_read's answer: a sized body
    return web.Response(body=_range(req),
                        content_type="application/octet-stream")


async def _chunked(req):
    resp = web.StreamResponse()
    resp.enable_chunked_encoding()
    await resp.prepare(req)
    body = _range(req)
    for i in range(0, len(body), 1 << 20):
        await resp.write(body[i:i + (1 << 20)])
    await resp.write_eof()
    return resp


async def _short(req):
    # promises the whole range, sends half, hangs up
    body = _range(req)
    resp = web.StreamResponse()
    resp.content_length = len(body)
    await resp.prepare(req)
    await resp.write(body[:len(body) // 2])
    req.transport.close()
    return resp


@pytest.fixture()
def holders():
    made = []

    def make(handler):
        h = _Holder(handler)
        made.append(h)
        return h
    yield make
    for h in made:
        h.stop()


def _fetch(targets, offset=0, size=RANGE):
    vs = VolumeServer.__new__(VolumeServer)  # the fetch reads no state
    return vs._fetch_shard_from_holders(
        7, 3, [h.thread.address for h in targets], offset, size,
        time.monotonic() + 30)


def test_sized_range_read_whole(holders):
    h = holders(_whole)
    got = _fetch([h], offset=RANGE // 3)
    assert isinstance(got, bytes)
    assert got == DATA[RANGE // 3:RANGE // 3 + RANGE]


def test_chunked_range_read_whole(holders):
    h = holders(_chunked)
    assert _fetch([h]) == DATA[:RANGE]


def test_short_body_fails_over_to_next_holder(holders):
    short, good = holders(_short), holders(_whole)
    got = _fetch([short, good], offset=RANGE)
    assert got == DATA[RANGE:2 * RANGE]
    assert len(short.peers) == 1 and len(good.peers) == 1
    # a short body alone is a failed fetch, never a short row
    assert _fetch([short]) is None


def test_sequential_fetches_reuse_one_connection(holders):
    h = holders(_whole)
    assert _fetch([h]) == DATA[:RANGE]
    assert _fetch([h], offset=RANGE) == DATA[RANGE:]
    assert len(h.peers) == 2
    assert h.peers[0] == h.peers[1], "the second fetch dialled anew"


def test_fanout_of_widest_code_is_all_in_flight(holders):
    # each range answers only once every shard of the widest code has
    # asked (or after 5 s): a pool with fewer workers than shards
    # leaves the rest queued, and the peak stays at its worker count
    n = geo.MAX_SHARD_COUNT
    seen = {"now": 0, "peak": 0, "all_in": None}

    async def gated(req):
        if seen["all_in"] is None:
            seen["all_in"] = asyncio.Event()
        seen["now"] += 1
        seen["peak"] = max(seen["peak"], seen["now"])
        if seen["now"] == n:
            seen["all_in"].set()
        try:
            await asyncio.wait_for(seen["all_in"].wait(), 5)
        except asyncio.TimeoutError:
            pass
        return await _whole(req)

    h = holders(gated)
    vs = VolumeServer.__new__(VolumeServer)
    vs.store = SimpleNamespace(ip="127.0.0.1", port=0)
    vs._ec_holders = lambda vid: {str(s): [h.thread.address]
                                  for s in range(n)}

    def counter(name):
        return metrics._counters.get((name, ()), 0.0)

    ranges0 = counter("ec_fetch_fanout_ranges_total")
    queued0 = counter("ec_fetch_fanout_queued_total")
    size = 64 << 10
    try:
        t0 = time.monotonic()
        got = vs._remote_shards_fetch_sync(7, list(range(n)), size, size,
                                           need=n, deadline=30)
        took = time.monotonic() - t0
    finally:
        vs._ec_fetch_pool.shutdown(wait=True)
    assert sorted(got) == list(range(n))
    assert all(v == DATA[size:2 * size] for v in got.values())
    assert seen["peak"] == n
    assert took < 5, "ranges waited for a worker"
    assert counter("ec_fetch_fanout_ranges_total") - ranges0 == n
    assert counter("ec_fetch_fanout_queued_total") - queued0 == 0
