"""The shard-range fetch that partial rebuilds and degraded reads share
(VolumeServer._fetch_shard_from_holders), against live holder stubs:
a sized body and a chunked one come back byte-identical from the
one-call read, a short body fails its holder over to the next, and
keep-alive survives the read."""
import time

import numpy as np
import pytest
from aiohttp import web

from seaweedfs_tpu.rpc.http import ServerThread
from seaweedfs_tpu.server.volume_server import VolumeServer

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
