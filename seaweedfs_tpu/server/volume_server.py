"""Volume server: data-plane HTTP + admin API + master heartbeat loop.

Equivalents: /root/reference/weed/server/volume_server_handlers_read.go:31
(GetOrHeadHandler), _write.go:18 (PostHandler) with replica fan-out
(topology/store_replicate.go:24 ReplicatedWrite), the VolumeServer admin
rpcs (volume_grpc_admin.go, volume_grpc_erasure_coding.go:38-407,
volume_grpc_copy.go file streaming, volume_grpc_vacuum.go), and the
heartbeat loop (volume_grpc_client_to_master.go:50-120).

In-flight byte accounting backpressure (volume_server.go:17-40) is
implemented by InFlightLimiter below (cond-var waits + 429 on
timeout), alongside an asyncio semaphore bounding concurrent disk
writes.
"""
from __future__ import annotations

import asyncio
import contextvars
import json
import os
import time

import aiohttp
from aiohttp import web

from ..ec import geometry as geo
from ..ec.decoder import find_dat_size, write_dat_file, write_idx_from_ecx
from ..storage import backend
from ..storage import needle as ndl
from ..storage import types as t
from ..rpc.http import debug_index_factory
from ..storage.store import Store
from ..utils import faults, glog, httprange, metrics, ratelimit, retry, \
    tracing
from ..utils.security import Guard


# per-peer cap for replica fan-out writes; clipped further by the
# request's remaining X-Sw-Deadline budget
REPLICATE_TIMEOUT = 30.0


class InFlightLimiter:
    """Byte-based in-flight accounting with cond-var backpressure —
    the volume_server.go:24-28 inFlightUpload/DownloadDataSize +
    sync.Cond scheme: a request WAITS while the tally is over the
    limit (so one oversized request can't starve), is admitted as soon
    as it drops below, and 429s after `timeout` seconds of waiting.
    limit<=0 means account-only (no backpressure)."""

    def __init__(self, limit: int, timeout: float = 30.0):
        self.limit = limit
        self.timeout = timeout
        self.value = 0
        self._cond: asyncio.Condition | None = None

    def _c(self) -> asyncio.Condition:
        if self._cond is None:  # bind lazily to the serving loop
            self._cond = asyncio.Condition()
        return self._cond

    async def wait_admit(self) -> bool:
        if self.limit <= 0 or self.value <= self.limit:
            return True
        cond = self._c()
        try:
            async with cond:
                await asyncio.wait_for(
                    cond.wait_for(lambda: self.value <= self.limit),
                    self.timeout)
            return True
        except asyncio.TimeoutError:
            return False

    def add(self, n: int) -> None:
        self.value += n

    async def release(self, n: int) -> None:
        self.value -= n
        if self.limit > 0:
            cond = self._c()
            async with cond:
                cond.notify_all()


class VolumeServer:
    def __init__(self, store: Store, master_url: str,
                 data_center: str = "DefaultDataCenter",
                 rack: str = "DefaultRack",
                 jwt_secret: str = "",
                 pulse_seconds: float = 5.0,
                 max_concurrent_writes: int = 64,
                 tier_backends: dict[str, dict] | None = None,
                 disk_type: str = "hdd",
                 concurrent_upload_limit: int = 256 << 20,
                 concurrent_download_limit: int = 256 << 20,
                 commit_durability: str = "buffered",
                 commit_max_delay: float = 0.002,
                 commit_max_bytes: int = 4 << 20):
        self.store = store
        self.disk_type = disk_type
        # comma-separated list in HA mode; heartbeats follow the raft
        # leader (volume_grpc_client_to_master.go:50 tries all masters)
        self.masters = [
            m if m.startswith("http") else f"http://{m}"
            for m in (s.strip().rstrip("/") for s in master_url.split(","))
            if m]
        self.master_url = self.masters[0]
        self.data_center = data_center
        self.rack = rack
        self.guard = Guard(jwt_secret)
        self.pulse_seconds = pulse_seconds
        # native C++ data plane (native/dataplane.cc): set by
        # enable_native(); None = pure-Python serving
        self.dp = None
        import threading as _threading

        self._dp_maint: dict[int, int] = {}  # vid -> open windows
        self._dp_maint_lock = _threading.Lock()
        self._write_sem = asyncio.Semaphore(max_concurrent_writes)
        # group-commit pipeline (storage/commit.py): runs in every
        # durability mode — buffered rides it for idx/btree commit
        # hygiene (the old COMMIT_EVERY cadence), batch gates acks on
        # the covering fsync, sync is the per-write fsync oracle
        from ..storage.commit import CommitScheduler

        self.commit = CommitScheduler(durability=commit_durability,
                                      max_delay=commit_max_delay,
                                      max_bytes=commit_max_bytes)
        self._upload_flight = InFlightLimiter(concurrent_upload_limit)
        self._download_flight = InFlightLimiter(concurrent_download_limit)
        self._hb_task: asyncio.Task | None = None
        self._hb_wake = asyncio.Event()
        self.store.remote_shard_reader = self._remote_shard_read_sync
        self.store.remote_shards_fetcher = self._remote_shards_fetch_sync
        # tier destinations, e.g. {"s3.default": {"endpoint":..,"bucket":..}}
        # (the reference receives these from master.toml [storage.backend]
        # via the heartbeat response, volume_grpc_client_to_master.go)
        for name, conf in (tier_backends or {}).items():
            backend.configure_storage(name, **conf)
        self.app = self._build_app()
        self.app.on_startup.append(self._on_startup)
        self.app.on_cleanup.append(self._on_cleanup)

    def _build_app(self) -> web.Application:
        @web.middleware
        async def error_mw(request, handler):
            try:
                return await handler(request)
            except web.HTTPException:
                raise
            except (json.JSONDecodeError, KeyError, ValueError,
                    TypeError) as e:
                return web.json_response(
                    {"error": f"bad request: {e}"}, status=400)

        app = web.Application(
            client_max_size=256 << 20,
            middlewares=[tracing.aiohttp_middleware("volume"),
                         retry.aiohttp_middleware("volume"),
                         faults.aiohttp_middleware("volume"), error_mw])
        app.add_routes([
            web.get("/", self.handle_ui),
            web.get("/ui/index.html", self.handle_ui),
            web.get("/status", self.handle_status),
            web.get("/metrics", self.handle_metrics),
            web.get("/debug", debug_index_factory("volume", {
                "/debug/traces": "recent spans recorded in-process",
                "/debug/breakers": "circuit breaker states",
                "/debug/ec": "EC codec router: probe curve + backends",
                "/debug/commit": "group-commit pipeline: window, "
                                 "queue depth, durability mode",
            })),
            web.get("/debug/traces", tracing.handle_debug_traces),
            web.get("/debug/breakers",
                    retry.handle_debug_breakers_factory()),
            web.get("/debug/ec", self.handle_debug_ec),
            web.get("/debug/commit", self.handle_debug_commit),
            web.post("/admin/assign_volume", self.handle_assign_volume),
            web.post("/admin/delete_volume", self.handle_delete_volume),
            web.post("/admin/mark_readonly", self.handle_mark_readonly),
            web.post("/admin/mark_writable", self.handle_mark_writable),
            web.post("/admin/volume_copy", self.handle_volume_copy),
            web.post("/admin/volume_mount", self.handle_volume_mount),
            web.post("/admin/volume_unmount", self.handle_volume_unmount),
            web.get("/admin/needle_ids", self.handle_needle_ids),
            web.get("/admin/needle_read", self.handle_needle_read),
            web.post("/admin/needle_write", self.handle_needle_write),
            web.post("/admin/needle_delete", self.handle_needle_delete),
            web.post("/admin/leave", self.handle_leave),
            web.post("/admin/volume_replication",
                     self.handle_volume_replication),
            web.post("/admin/volume_scrub", self.handle_volume_scrub),
            web.post("/admin/vacuum_check", self.handle_vacuum_check),
            web.post("/admin/vacuum_compact", self.handle_vacuum_compact),
            web.post("/admin/tier_upload", self.handle_tier_upload),
            web.post("/admin/tier_download", self.handle_tier_download),
            web.post("/admin/tier_offload", self.handle_tier_offload),
            web.post("/admin/tier_recall", self.handle_tier_recall),
            web.post("/admin/ec/generate", self.handle_ec_generate),
            web.post("/admin/ec/rebuild", self.handle_ec_rebuild),
            web.post("/admin/ec/rebuild_partial",
                     self.handle_ec_rebuild_partial),
            web.post("/admin/ec/copy", self.handle_ec_copy),
            web.post("/admin/ec/mount", self.handle_ec_mount),
            web.post("/admin/ec/unmount", self.handle_ec_unmount),
            web.post("/admin/ec/delete", self.handle_ec_delete),
            web.post("/admin/ec/to_volume", self.handle_ec_to_volume),
            web.get("/admin/ec/shard_read", self.handle_ec_shard_read),
            web.get("/admin/copy_file", self.handle_copy_file),
            web.get("/admin/volume_sync_status",
                    self.handle_volume_sync_status),
            web.get("/admin/volume_incremental_copy",
                    self.handle_volume_incremental_copy),
            web.get("/admin/volume_tail", self.handle_volume_tail),
            web.post("/admin/volume_tail_receive",
                     self.handle_volume_tail_receive),
            web.get("/admin/volume_info", self.handle_volume_info),
            web.post("/admin/query", self.handle_query),
            # `_N` suffix = assign?count batch slot (ParsePath:121-141)
            web.route("*", "/{fid:[0-9]+,[0-9a-fA-F]+(_[0-9]+)?}",
                      self.handle_fid),
        ])
        return app

    # -- native data plane ---------------------------------------------
    def enable_native(self, public_port: int, backend_port: int,
                      workers: int = 2,
                      listen_ip: str = "0.0.0.0") -> int:
        """Start the C++ HTTP front on `public_port` (0 = ephemeral),
        proxying non-hot-path requests to the Python app listening on
        `backend_port`, and attach every eligible volume. Returns the
        bound public port."""
        from ..native.dataplane import DataPlane

        dp = DataPlane()
        port = dp.start(public_port, backend_port, workers,
                        listen_ip=listen_ip)
        dp.config(self.guard.enabled, self.guard.secret)
        dp.set_commit(self.commit.durability, self.commit.max_delay,
                      self.commit.max_bytes)
        if faults.enabled():
            # mirror this service's share of -fault.spec so requests the
            # front answers natively see the same chaos as relayed ones
            re, we, rd, wd = faults.native_params("volume")
            dp.set_faults(re, we, rd, wd, seed=faults.seed())
        self.dp = dp
        for loc in self.store.locations:
            for v in loc.volumes.values():
                self._dp_attach(v)
        return port

    def disable_native(self) -> None:
        if self.dp is None:
            return
        for loc in self.store.locations:
            for v in loc.volumes.values():
                v.detach_native()
        self.dp.stop()
        self.dp = None

    def _dp_attach(self, v) -> None:
        """Attach one volume to the native plane (no-op when the plane
        is off, the volume isn't a plain local-disk one, or another
        maintenance window still holds it)."""
        if self.dp is None or v is None:
            return
        with self._dp_maint_lock:
            if self._dp_maint.get(v.vid, 0) > 0:
                return  # a concurrent _dp_detached window is still open
            try:
                v.attach_native(self.dp)
            except OSError as e:
                glog.warning(
                    f"native attach of volume {v.vid} failed: {e}")

    def _dp_detached(self, vid: int):
        """Context manager: exclusive Python ownership of a volume for
        maintenance (vacuum, tier, raw segment application);
        reattaches on exit only when the LAST overlapping window
        closes — two concurrent admin ops on one volume must not
        reattach it under each other."""
        server = self

        class _Ctx:
            def __enter__(self):
                with server._dp_maint_lock:
                    server._dp_maint[vid] = \
                        server._dp_maint.get(vid, 0) + 1
                v = server.store.find_volume(vid)
                if v is not None:
                    v.detach_native()
                return v

            def __exit__(self, *exc):
                with server._dp_maint_lock:
                    left = server._dp_maint.get(vid, 1) - 1
                    if left > 0:
                        server._dp_maint[vid] = left
                        return False
                    server._dp_maint.pop(vid, None)
                server._dp_attach(server.store.find_volume(vid))
                return False

        return _Ctx()

    async def _on_startup(self, app) -> None:
        self._hb_task = asyncio.create_task(self._heartbeat_loop())
        self._peer_task = asyncio.create_task(self._peer_refresh_loop())

    PEER_REFRESH_SECONDS = 2.0

    async def _peer_refresh_loop(self) -> None:
        """Keep the native front's replica peer lists fresh so primary
        writes to replicated volumes fan out in C++ (the analogue of the
        reference masterClient vidMap feeding store_replicate.go:191).
        A fan-out failure marks the list stale — the front relays those
        writes to this Python path until the next push here."""
        while True:
            try:
                await asyncio.sleep(self.PEER_REFRESH_SECONDS)
                if self.dp is None:
                    continue
                me = f"{self.store.ip}:{self.store.port}"
                for loc in self.store.locations:
                    for v in list(loc.volumes.values()):
                        if getattr(v, "delegate", None) is None:
                            continue
                        copies = \
                            v.super_block.replica_placement.copy_count
                        if copies <= 1:
                            continue
                        try:
                            if self.dp.peers_stale(v.vid):
                                # a peer died or moved: force a fresh
                                # master lookup instead of the TTL cache
                                self._invalidate_lookup(v.vid)
                        except KeyError:
                            continue  # detached meanwhile
                        urls = await self._lookup_volume_all(v.vid)
                        peers = [u for u in urls if u != me]
                        # only a COMPLETE placement may fan out natively;
                        # anything short relays to Python, which fails
                        # the write rather than under-replicate
                        if len(peers) == copies - 1:
                            try:
                                self.dp.set_peers(v.vid, peers)
                            except KeyError:
                                pass
            except asyncio.CancelledError:
                return
            except Exception as e:
                glog.v(1, "native peer refresh failed: %s", e)
                await asyncio.sleep(1)

    async def handle_leave(self, req: web.Request) -> web.Response:
        """volume.server.leave (command_volume_server_leave.go →
        VolumeServerLeave rpc): stop heartbeating so the master drops
        this node from the topology; the server keeps serving reads
        until the operator shuts it down."""
        if self._hb_task is not None:
            self._hb_task.cancel()
            try:
                await self._hb_task
            except asyncio.CancelledError:
                pass
            self._hb_task = None
        return web.json_response({"left": True})

    async def _on_cleanup(self, app) -> None:
        if self._hb_task is not None:
            self._hb_task.cancel()
            try:
                await self._hb_task
            except asyncio.CancelledError:
                pass
        peer_task = getattr(self, "_peer_task", None)
        if peer_task is not None:
            peer_task.cancel()
            try:
                await peer_task
            except asyncio.CancelledError:
                pass
        sess = getattr(self, "_client_sess", None)
        if sess is not None and not sess.closed:
            await sess.close()
        pool = getattr(self, "_ec_fetch_pool", None)
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        mc = getattr(self, "_ec_master_client", None)
        if mc is not None:
            mc.stop()
        if self.dp is not None:
            await asyncio.to_thread(self.disable_native)
        await asyncio.to_thread(self.commit.stop)
        await asyncio.to_thread(self.store.close)

    # ------------------------------------------------------------------
    # heartbeat (volume_grpc_client_to_master.go:50 doHeartbeat)
    # ------------------------------------------------------------------
    async def _find_leader(self, sess: aiohttp.ClientSession) -> str:
        """Locate the current master leader among self.masters
        (wdclient masterclient.go:160 tryAllMasters analogue)."""
        for m in self.masters:
            try:
                async with sess.get(f"{m}/cluster/leader",
                                    timeout=aiohttp.ClientTimeout(
                                        total=3)) as resp:
                    d = await resp.json()
                    if d.get("IsLeader"):
                        return m
                    if d.get("Leader"):
                        return f"http://{d['Leader']}"
            except Exception:
                continue
        return self.masters[0]

    async def _heartbeat_loop(self) -> None:
        while self.store.port == 0:
            # ephemeral listen port not resolved yet (set by the runner
            # right after the site binds) — don't register as :0
            await asyncio.sleep(0.02)
        while True:
            try:
                async with aiohttp.ClientSession() as sess:
                    self.master_url = await self._find_leader(sess)
                    ws_url = self.master_url.replace(
                        "http", "ws", 1) + "/ws/heartbeat"
                    async with sess.ws_connect(ws_url) as ws:
                        while True:
                            hb = self.store.collect_heartbeat()
                            hb["data_center"] = self.data_center
                            hb["rack"] = self.rack
                            hb["disk_type"] = self.disk_type
                            bw = ratelimit.snapshot().get("repair")
                            if bw is not None:
                                hb["repair_bw"] = bw
                                metrics.gauge_set(
                                    "repair_bw_fill_bytes", bw["fill"])
                                metrics.gauge_set(
                                    "repair_bw_debt_bytes", bw["debt"])
                            tbw = ratelimit.snapshot().get("tier")
                            if tbw is not None:
                                hb["tier_bw"] = tbw
                                metrics.gauge_set(
                                    "tier_bw_fill_bytes", tbw["fill"])
                                metrics.gauge_set(
                                    "tier_bw_debt_bytes", tbw["debt"])
                            await ws.send_json(hb)
                            msg = await ws.receive(
                                timeout=self.pulse_seconds * 4)
                            if msg.type != aiohttp.WSMsgType.TEXT:
                                break
                            try:
                                await asyncio.wait_for(
                                    self._hb_wake.wait(),
                                    timeout=self.pulse_seconds)
                                self._hb_wake.clear()
                            except asyncio.TimeoutError:
                                pass
                # graceful close (e.g. a follower refusing our stream
                # while no leader exists): back off before re-probing
                glog.v(1, "heartbeat stream to %s closed; re-probing",
                       self.master_url)
                await asyncio.sleep(min(1.0, self.pulse_seconds))
            except asyncio.CancelledError:
                return
            except Exception as e:
                glog.v(1, "heartbeat to %s failed: %s; retrying",
                       self.master_url, e)
                await asyncio.sleep(1)

    def poke_heartbeat(self) -> None:
        self._hb_wake.set()

    # ------------------------------------------------------------------
    # repair bandwidth shaping: one node-wide "repair" token bucket
    # shared by every repair role this server plays (copy source via
    # ?bps= on copy_file/shard_read, copy destination via max_bps in
    # volume_copy/ec/copy bodies, partial-rebuild fetcher), so the
    # per-node cap holds no matter how many transfers overlap
    # ------------------------------------------------------------------
    async def _repair_throttle(self, max_bps: float, n: int) -> None:
        """Async-side shaping: debit ``n`` repair bytes and sleep out
        the wait off the event loop."""
        if n <= 0:
            return
        metrics.counter_add("repair_bw_bytes_total", n)
        if max_bps and max_bps > 0:
            wait = ratelimit.bucket("repair", max_bps).reserve(n)
            if wait > 0:
                await asyncio.sleep(wait)

    def _repair_throttle_sync(self, max_bps: float, n: int) -> None:
        """Thread-side shaping (partial rebuild fetch loop)."""
        if n <= 0:
            return
        metrics.counter_add("repair_bw_bytes_total", n)
        if max_bps and max_bps > 0:
            ratelimit.bucket("repair", max_bps).acquire(n)

    # ------------------------------------------------------------------
    # data plane: GET/HEAD/POST/DELETE /<vid>,<fid>
    # ------------------------------------------------------------------
    async def handle_fid(self, req: web.Request) -> web.Response:
        fid = req.match_info["fid"]
        try:
            vid, key, cookie = t.parse_file_id(fid)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        if req.method in ("GET", "HEAD"):
            # byte-based in-flight download backpressure
            # (volume_server.go:25 + handlers.go cond-var wait)
            if not await self._download_flight.wait_admit():
                return web.Response(
                    status=429, text="too many in-flight downloads")
            est = self.store.needle_size(vid, key)
            self._download_flight.add(est)
            try:
                return await self._read_fid(req, vid, key, cookie)
            finally:
                await self._download_flight.release(est)
        if req.method == "POST" or req.method == "PUT":
            if not await self._upload_flight.wait_admit():
                return web.Response(
                    status=429, text="too many in-flight uploads")
            est = req.content_length or 0
            self._upload_flight.add(est)
            try:
                return await self._write_fid(req, fid, vid, key, cookie)
            finally:
                await self._upload_flight.release(est)
        if req.method == "DELETE":
            return await self._delete_fid(req, fid, vid, key)
        return web.Response(status=405)

    async def _inline_or_thread(self, v, inline_ok: bool, fn, *args,
                                **kwargs):
        """Run `fn` inline on the event loop only when it is cheap
        (caller's `inline_ok`) AND the volume's write_lock is free —
        a vacuum commit holds it across the .dat/.idx swap (seconds
        for a btree rebuild), and blocking inline would stall every
        volume on this server, not just this request. Contended or
        heavyweight calls take the worker-thread hop."""
        if inline_ok and v is not None and \
                v.write_lock.acquire(blocking=False):
            try:
                return fn(*args, **kwargs)
            finally:
                v.write_lock.release()
        return await asyncio.to_thread(fn, *args, **kwargs)

    async def _serve_chunked_manifest(self, req, manifest_body: bytes,
                                      is_gzip: bool,
                                      headers: dict) -> web.Response:
        """GET/HEAD of a chunk-manifest needle: fetch ONLY the bytes
        the request asks for — a HEAD reads nothing, a ranged read
        fetches its spans, and a full GET streams span by span so a
        multi-GB legacy chunked file never materializes in memory
        (the reference streams through ChunkedFileReader the same
        way, chunked_file.go:42)."""
        from ..filer.stream import stream_content
        from ..operation.chunked_file import load_chunk_manifest

        cm = load_chunk_manifest(manifest_body, is_gzip)
        chunks = cm.as_file_chunks()
        total = cm.size
        headers["X-File-Store"] = "chunked"
        ct = "application/octet-stream"
        if cm.mime and not cm.mime.startswith(
                "application/octet-stream"):
            ct = cm.mime
        elif cm.name:
            import mimetypes

            ct = mimetypes.guess_type(cm.name)[0] \
                or "application/octet-stream"
        if req.method == "HEAD":
            headers["Content-Length"] = str(total)
            return web.Response(status=200, headers=headers,
                                content_type=ct)

        def _span(off: int, ln: int):
            return asyncio.to_thread(stream_content,
                                     self._lookup_fid_url, chunks,
                                     off, ln)

        rng = req.headers.get("Range")
        if rng:
            ranges = httprange.parse_range_header(rng, total)
            if ranges in (httprange.MALFORMED, httprange.UNSATISFIABLE):
                return web.Response(
                    status=416,
                    headers={"Content-Range": f"bytes */{total}"})
            if ranges and ranges is not httprange.IGNORE:
                if len(ranges) == 1:
                    s, ln = ranges[0]
                    headers["Content-Range"] = httprange.content_range(
                        s, ln, total)
                    return web.Response(status=206,
                                        body=await _span(s, ln),
                                        content_type=ct,
                                        headers=headers)
                spans = await asyncio.gather(
                    *(_span(s, ln) for s, ln in ranges))
                mbody, mct = httprange.multipart_byteranges(
                    [(s, ln, d)
                     for (s, ln), d in zip(ranges, spans)], ct, total)
                headers["Content-Type"] = mct
                return web.Response(status=206, body=mbody,
                                    headers=headers)
        # full GET: stream in bounded windows (O(window) memory)
        headers["Content-Length"] = str(total)
        headers["Content-Type"] = ct
        resp = web.StreamResponse(status=200, headers=headers)
        await resp.prepare(req)
        window = 8 << 20
        for off in range(0, total, window):
            await resp.write(await _span(off, min(window, total - off)))
        await resp.write_eof()
        return resp

    def _lookup_fid_url(self, fid: str) -> str:
        """fid -> url via a lazily-built master client (chunk-manifest
        reassembly + cascade delete need cross-volume lookups)."""
        mc = getattr(self, "_mc", None)
        if mc is None:
            from ..wdclient.client import MasterClient

            mc = self._mc = MasterClient(self.masters)
        return mc.lookup_file_id(fid)

    STREAM_READ_LIMIT = 1 << 20  # PagedReadLimit (volume_read.go:15)

    @staticmethod
    def _needle_headers(n) -> dict:
        """Response headers a needle read always carries: ETag,
        Seaweed-* metadata pairs, Last-Modified — one assembly shared
        by the materialized and streamed read paths."""
        headers = {"Etag": f'"{n.etag()}"'}
        if n.pairs:
            try:
                for k, v in json.loads(n.pairs).items():
                    if k.lower().startswith("seaweed-"):
                        headers[k] = str(v)
            except (json.JSONDecodeError, AttributeError):
                pass
        if n.last_modified:
            headers["Last-Modified"] = time.strftime(
                "%a, %d %b %Y %H:%M:%S GMT",
                time.gmtime(n.last_modified))
        return headers

    async def _maybe_stream_big_needle(self, req, vid, key,
                                       cookie) -> web.Response | None:
        """Serve a large plain needle in pread windows instead of
        materializing it (the reference pages needles past
        PagedReadLimit through streamWriteResponseContent). None =
        not eligible, take the normal path. Compressed/manifest
        needles, image transforms, multi-range, readDeleted and
        remote-backed volumes all fall through — their handling needs
        the whole body or different machinery."""
        if req.method != "GET":
            return None
        if set(req.query) & {"width", "height", "mode", "crop_x1",
                             "crop_y1", "crop_x2", "crop_y2",
                             "readDeleted", "cm"}:
            return None
        v = self.store.find_volume(vid)
        if v is None or getattr(v.dat, "remote", True) \
                or vid in self.store.ec_volumes:
            return None
        try:
            if self.store.needle_size(vid, key) <= self.STREAM_READ_LIMIT:
                return None
        except KeyError:
            return None
        # flags live AFTER the data on disk, so eligibility is only
        # known post-open: remember big compressed/manifest needles so
        # their repeat GETs skip the wasted probe preads
        no_stream = getattr(self, "_no_stream", None)
        if no_stream is None:
            no_stream = self._no_stream = set()
        if (vid, key) in no_stream:
            return None
        try:
            n, data_size, reader = await asyncio.to_thread(
                v.read_needle_streamed, key, cookie)
        except KeyError:
            return web.Response(status=404)
        except PermissionError:
            return web.Response(status=403)
        except (ValueError, IOError):
            return None  # surprises re-run through the checked path
        if n.is_compressed or n.is_chunk_manifest:
            if len(no_stream) >= 4096:
                no_stream.clear()
            no_stream.add((vid, key))
            return None  # needs inflation / reassembly: whole-body path
        headers = self._needle_headers(n)
        ct = n.mime.decode() if n.mime else "application/octet-stream"
        start_i, length = 0, data_size
        rng = req.headers.get("Range")
        status = 200
        if rng:
            ranges = httprange.parse_range_header(rng, data_size)
            if ranges in (httprange.MALFORMED, httprange.UNSATISFIABLE):
                return web.Response(
                    status=416,
                    headers={"Content-Range": f"bytes */{data_size}"})
            if ranges and ranges is not httprange.IGNORE:
                if len(ranges) > 1:
                    return None  # multipart assembly: whole-body path
                start_i, length = ranges[0]
                status = 206
                headers["Content-Range"] = httprange.content_range(
                    start_i, length, data_size)
        headers["Content-Length"] = str(length)
        headers["Content-Type"] = ct
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(req)
        t0 = time.perf_counter()
        window = 4 << 20
        sent = 0
        while sent < length:
            try:
                piece = await asyncio.to_thread(
                    reader, start_i + sent, min(window, length - sent))
            except (ValueError, OSError):
                # vacuum commit closed the captured handle mid-stream:
                # close short (the client sees a truncated body, not a
                # server stack trace) — rare, and a retry reads the
                # compacted volume cleanly
                piece = b""
            if not piece:
                break
            await resp.write(piece)
            sent += len(piece)
        await resp.write_eof()
        metrics.histogram_observe("volume_server_read_seconds",
                                  time.perf_counter() - t0)
        return resp

    async def _read_fid(self, req, vid, key, cookie) -> web.Response:
        start = time.perf_counter()
        if not self.store.has_volume(vid) and \
                vid not in self.store.ec_volumes:
            # not local: redirect via master lookup (handlers_read.go:48)
            url = await self._lookup_volume(vid)
            if url:
                raise web.HTTPMovedPermanently(
                    f"http://{url}/{req.match_info['fid']}")
            return web.Response(status=404, text=f"volume {vid} not found")
        streamed = await self._maybe_stream_big_needle(req, vid, key,
                                                       cookie)
        if streamed is not None:
            return streamed
        try:
            # the needle map gives the size in O(1): small reads are a
            # page-cache pread, cheaper inline than a to_thread hop.
            # NEVER inline a remote-backed (tiered) volume: its read is
            # a network call that would block the event loop — and can
            # deadlock outright when the tier bucket lives on this same
            # cluster (s3 gateway -> filer -> this very server)
            read_deleted = req.query.get("readDeleted") == "true"
            v = self.store.find_volume(vid)
            inline_ok = (
                not read_deleted
                and v is not None and not getattr(v.dat, "remote", True)
                and self.store.needle_size(vid, key) <= (64 << 10)
                and vid not in self.store.ec_volumes)
            n = await self._inline_or_thread(
                v, inline_ok, self.store.read_needle, vid, key, cookie,
                read_deleted=read_deleted)
        except KeyError:
            return web.Response(status=404)
        except PermissionError:
            return web.Response(status=403)
        except (ValueError, IOError) as e:
            return web.Response(status=500, text=str(e))
        metrics.histogram_observe("volume_server_read_seconds",
                                  time.perf_counter() - start)
        headers = self._needle_headers(n)
        body = n.data
        is_gzip = n.is_compressed
        ct = n.mime.decode() if n.mime else "application/octet-stream"
        if n.is_chunk_manifest and req.query.get("cm") != "false":
            # legacy chunked file: the needle body is a manifest of
            # sub-fids; reassemble server-side
            # (volume_server_handlers_read.go:254 tryHandleChunkedFile;
            # ?cm=false serves the raw manifest JSON)
            try:
                return await self._serve_chunked_manifest(
                    req, body, is_gzip, headers)
            except (ValueError, KeyError, LookupError, OSError) as e:
                return web.Response(
                    status=500, text=f"chunked manifest: {e}")
        # image renditions (volume_server_handlers_read.go:294-353);
        # a compressed image must be inflated before PIL sees it.
        # Crop runs BEFORE resize, exactly like the reference's
        # conditionallyCropImages -> conditionallyResizeImages chain
        if "crop_x2" in req.query or "crop_y2" in req.query:
            from .. import images

            try:
                x1 = int(req.query.get("crop_x1", "0") or 0)
                y1 = int(req.query.get("crop_y1", "0") or 0)
                x2 = int(req.query.get("crop_x2", "0") or 0)
                y2 = int(req.query.get("crop_y2", "0") or 0)
            except ValueError:
                x1 = y1 = x2 = y2 = 0
            croppable = ct.split(";")[0].strip().lower() in (
                "image/png", "image/jpeg", "image/gif")
            if x2 > x1 and y2 > y1 and croppable:
                if is_gzip:
                    from ..utils import compression

                    body = await asyncio.to_thread(
                        compression.ungzip, body)
                    is_gzip = False
                body = await asyncio.to_thread(
                    images.cropped, body, ct, x1, y1, x2, y2)
        if ("width" in req.query or "height" in req.query):
            from .. import images

            try:
                want_w = int(req.query.get("width", "0") or 0)
                want_h = int(req.query.get("height", "0") or 0)
            except ValueError:
                want_w = want_h = 0  # reference ignores bad dims
            if images.is_image_mime(ct) and (want_w or want_h):
                if is_gzip:
                    from ..utils import compression

                    body = await asyncio.to_thread(
                        compression.ungzip, body)
                    is_gzip = False
                body = await asyncio.to_thread(
                    images.resized, body, ct, want_w, want_h,
                    req.query.get("mode", ""))
        rng = req.headers.get("Range")
        if is_gzip and (rng or "gzip" not in
                        req.headers.get("Accept-Encoding", "")):
            # ranges address ORIGINAL bytes: slicing the gzip stream
            # would serve garbage, so partial reads always inflate
            # (in a worker thread: a large inflate must not stall the
            # event loop)
            from ..utils import compression

            body = await asyncio.to_thread(compression.ungzip, body)
        elif is_gzip:
            headers["Content-Encoding"] = "gzip"
        if req.method == "HEAD":
            headers["Content-Length"] = str(len(body))
            return web.Response(status=200, headers=headers)
        # range support, incl. multi-range multipart/byteranges
        # (common.go processRangeRequest:306-383)
        if rng:
            ranges = httprange.parse_range_header(rng, len(body))
            if ranges in (httprange.MALFORMED, httprange.UNSATISFIABLE):
                return web.Response(
                    status=416,
                    headers={"Content-Range": f"bytes */{len(body)}"})
            if ranges and ranges is not httprange.IGNORE:
                if len(ranges) == 1:
                    start_i, length = ranges[0]
                    headers["Content-Range"] = httprange.content_range(
                        start_i, length, len(body))
                    return web.Response(
                        status=206, body=body[start_i:start_i + length],
                        content_type=ct, headers=headers)
                parts = [(s, ln, body[s:s + ln]) for s, ln in ranges]
                mbody, mct = httprange.multipart_byteranges(
                    parts, ct, len(body))
                headers["Content-Type"] = mct  # carries the boundary
                return web.Response(status=206, body=mbody,
                                    headers=headers)
        return web.Response(body=body, content_type=ct, headers=headers)

    async def _write_fid(self, req, fid, vid, key, cookie) -> web.Response:
        start = time.perf_counter()
        try:
            self.guard.check(req.headers.get("Authorization"), fid)
        except PermissionError as e:
            return web.Response(status=401, text=str(e))
        if not self.store.has_volume(vid):
            return web.Response(status=404, text=f"volume {vid} not found")
        n = ndl.Needle(id=key, cookie=cookie)
        ctype = req.content_type or ""
        if ctype.startswith("multipart/"):
            reader = await req.multipart()
            part = await reader.next()
            if part is None:
                return web.Response(status=400, text="empty multipart body")
            n.data = bytes(await part.read(decode=False))
            if part.filename:
                n.name = part.filename.encode()
            pct = part.headers.get("Content-Type", "")
            if pct and pct != "application/octet-stream":
                n.mime = pct.encode()
        else:
            n.data = await req.read()
            if ctype and ctype != "application/octet-stream":
                n.mime = ctype.encode()
        from ..utils import compression

        is_replicate = req.query.get("type") == "replicate"
        if req.query.get("name"):
            if is_replicate:
                # server-to-server: latin-1 maps bytes 1:1 so the
                # primary's exact name bytes survive the query string
                n.name = req.query["name"].encode("latin-1", "replace")
            else:
                n.name = req.query["name"].encode()  # client text
        if is_replicate and req.query.get("mime"):
            n.mime = req.query["mime"].encode("latin-1", "replace")
        if req.query.get("ts"):
            n.last_modified = int(req.query["ts"])
        if req.query.get("cm") in ("true", "1"):
            # the body is a chunk manifest of sub-fids
            # (needle_parse_upload.go:186 IsChunkedFile); reads
            # reassemble, deletes cascade
            n.flags |= ndl.FLAG_IS_CHUNK_MANIFEST
        # custom metadata pairs: Seaweed-* headers stored as JSON in
        # the needle (needle_parse_upload.go parsePairs)
        pairs = {k: v for k, v in req.headers.items()
                 if k.lower().startswith("seaweed-")}
        if pairs:
            n.pairs = json.dumps(pairs, separators=(",", ":")).encode()
            n.flags |= ndl.FLAG_HAS_PAIRS
        # transparent compression (needle_parse_upload.go): a client's
        # pre-gzipped body normally arrives already inflated (aiohttp
        # decodes Content-Encoding) and re-compresses below; if it
        # somehow arrives still gzipped, keep it and flag it
        if req.query.get("compressed") == "1" and \
                compression.is_gzipped(n.data):
            # replica fan-out ships the primary's stored bytes verbatim
            # (gzip magic required: the param is client-forgeable and a
            # false flag would make the needle unreadable forever)
            n.flags |= ndl.FLAG_IS_COMPRESSED
        elif "gzip" in req.headers.get("Content-Encoding", "") and \
                compression.is_gzipped(n.data):
            n.flags |= ndl.FLAG_IS_COMPRESSED
        elif compression.is_compressible(
                n.mime.decode("utf-8", "replace"),
                n.name.decode("utf-8", "replace")):
            body, did = await asyncio.to_thread(
                compression.maybe_gzip, n.data)
            if did:
                n.data = body
                n.flags |= ndl.FLAG_IS_COMPRESSED
        durability = self.commit.durability
        want_fsync = req.query.get("fsync") in ("true", "1")
        ticket = None
        async with self._write_sem:
            try:
                # small appends land in the page cache in ~10us: the
                # to_thread hop costs more than the write on the 1-core
                # benchmark; only big bodies leave the event loop
                _, size = await self._inline_or_thread(
                    self.store.find_volume(vid),
                    len(n.data) <= (64 << 10),
                    self.store.write_needle, vid, n)
                v_w = self.store.find_volume(vid)
                if durability == "sync" or want_fsync:
                    # per-write fsync oracle, and the ?fsync=true
                    # contract (the filer forwards its own ?fsync /
                    # filer.conf fsync rule here;
                    # volume_server_handlers_write.go honors the same
                    # param). fsync is per-inode, so this covers
                    # appends made by the native front too.
                    if v_w is not None:
                        await asyncio.to_thread(v_w.sync)
                elif v_w is not None:
                    # enqueue on the group-commit pipeline: in batch
                    # mode the ack below waits for the covering fsync;
                    # buffered mode never waits but still feeds the
                    # batched idx/btree commit cadence
                    ticket = self.commit.submit(
                        v_w, len(n.data),
                        loop=asyncio.get_running_loop()
                        if durability == "batch" else None)
            except KeyError:
                return web.Response(status=404)
            except PermissionError as e:
                return web.Response(status=409, text=str(e))
        # replica fan-out (store_replicate.go:24): skip when this IS
        # the replicated copy (type=replicate marks secondary writes).
        # The peer sends start NOW — right after the page-cache append
        # — while the batch fsync runs; only the ack below waits on
        # local durability, overlapping network and disk.
        repl_task = None
        t_repl = time.perf_counter()
        if req.query.get("type") != "replicate":
            repl_task = asyncio.ensure_future(
                self._replicate(req, fid, n.data, "POST", needle=n))
        if durability == "batch" and ticket is not None:
            await ticket
            if ticket.error is not None:
                if repl_task is not None:
                    await repl_task
                return web.Response(
                    status=500, text=f"commit failed: {ticket.error}")
        if repl_task is not None:
            err = await repl_task
            metrics.histogram_observe(
                "write_commit_seconds",
                time.perf_counter() - t_repl, {"stage": "replicate"})
            if err:
                return web.Response(status=500, text=err)
        self.poke_heartbeat()
        elapsed = time.perf_counter() - start
        metrics.histogram_observe("volume_server_write_seconds", elapsed)
        metrics.histogram_observe("write_commit_seconds", elapsed,
                                  {"stage": "ack"})
        return web.json_response(
            {"name": n.name.decode("utf-8", "replace") if n.name
             else "",
             "size": len(n.data), "eTag": n.etag()}, status=201,
            headers={"X-Sw-Durability":
                     "sync" if want_fsync else durability})

    async def _delete_fid(self, req, fid, vid, key) -> web.Response:
        try:
            self.guard.check(req.headers.get("Authorization"), fid)
        except PermissionError as e:
            return web.Response(status=401, text=str(e))
        manifest_size = 0
        # deleting a chunk manifest deletes its chunks FIRST
        # (volume_server_handlers_write.go:112-124) so the data can't
        # be orphaned by a manifest-only delete. Only the PRIMARY
        # cascades: a ?type=replicate delete is the fan-out of a
        # primary that already did (re-running it per replica would
        # re-delete chunks N times and fail replication on a lookup
        # hiccup)
        if req.query.get("type") != "replicate":
            try:
                n = await asyncio.to_thread(
                    self.store.read_needle, vid, key)
            except (KeyError, PermissionError):
                n = None  # absent needle: plain delete decides
            except (ValueError, IOError):
                n = None  # unreadable: still allow the tombstone
            if n is not None and n.is_chunk_manifest:
                from ..operation.chunked_file import (delete_chunks,
                                                      load_chunk_manifest)

                try:
                    cm = load_chunk_manifest(n.data, n.is_compressed)
                except ValueError as e:
                    return web.json_response(
                        {"error": f"load chunks manifest: {e}"},
                        status=500)
                failed = await asyncio.to_thread(
                    delete_chunks, self._lookup_fid_url, cm)
                if failed:
                    return web.json_response(
                        {"error": f"delete chunks failed: {failed}"},
                        status=500)
                manifest_size = cm.size
        try:
            size = await asyncio.to_thread(
                self.store.delete_needle, vid, key)
        except KeyError:
            return web.Response(status=404)
        size = manifest_size or size
        if req.query.get("type") != "replicate":
            err = await self._replicate(req, fid, b"", "DELETE")
            if err:
                return web.Response(status=500, text=err)
        return web.json_response({"size": size}, status=202)

    async def _replicate(self, req, fid: str, data: bytes,
                         method: str,
                         needle: "ndl.Needle | None" = None) -> str | None:
        """Fan out to replica peers from master lookup, excluding self
        (DistributedOperation, store_replicate.go:171). The secondary
        write must carry the needle's full identity — name, mime,
        mtime, compression — or replicas silently diverge from the
        primary (and a gzipped body would be re-compressed)."""
        vid = int(fid.split(",")[0])
        # single-copy volumes have no peers by definition: skip the
        # master lookup entirely (it would otherwise cost one master
        # round-trip PER WRITE — measured 5x the needle-write time).
        # Same rule as the reference (store_replicate.go:191
        # GetWritableRemoteReplications returns early on copy count 1).
        v = self.store.find_volume(vid)
        if v is not None and \
                v.super_block.replica_placement.copy_count <= 1:
            return None
        locations = await self._lookup_volume_all(vid)
        me = f"{self.store.ip}:{self.store.port}"
        peers = [u for u in locations if u != me]
        if not peers:
            # copy_count > 1 (checked above) means peers are EXPECTED:
            # an empty/failed lookup must fail the write, not silently
            # ack it under-replicated (GetWritableRemoteReplications
            # errors the same way when locations < copy count). Drop
            # any cached self-only list so the next write re-resolves
            # instead of failing for the rest of the TTL.
            self._invalidate_lookup(vid)
            return f"volume {vid}: no replica peers resolvable"
        params = {"type": "replicate"}
        if req.query.get("fsync") in ("true", "1"):
            # an fsync'd write must be durable on EVERY copy before
            # the ack, not just the primary (ReplicatedWrite forwards
            # the same param)
            params["fsync"] = "true"
        headers = {}
        # the secondary ALSO guards writes: forward the client's token
        # (same fid claim, still inside its validity window — the
        # reference forwards the jwt through ReplicatedWrite the same
        # way). Without this, JWT + replication could never coexist.
        auth = req.headers.get("Authorization")
        if auth:
            headers["Authorization"] = auth
        if needle is not None:
            if needle.name:
                # latin-1 maps bytes 1:1 so non-UTF-8 names survive
                params["name"] = needle.name.decode("latin-1")
            if needle.last_modified:
                params["ts"] = str(needle.last_modified)
            if needle.mime:
                # query param, not Content-Type: the header would be
                # re-encoded as UTF-8 on the other side and non-ASCII
                # mime bytes would diverge from the primary
                params["mime"] = needle.mime.decode("latin-1")
            if needle.pairs:
                try:
                    headers.update({
                        k: str(v)
                        for k, v in json.loads(needle.pairs).items()
                        if k.lower().startswith("seaweed-")})
                except (json.JSONDecodeError, AttributeError):
                    pass
            if needle.is_compressed:
                # marker param, NOT Content-Encoding: the receiving
                # server must append these bytes verbatim (inflate +
                # re-gzip would waste CPU and could diverge byte-wise)
                params["compressed"] = "1"
        import urllib.parse

        tracing.inject(headers)
        retry.inject(headers)
        qs = urllib.parse.urlencode(params)
        sess = self._client()
        # replica writes must land on EVERY peer before the ack: bound
        # each hop (deadline-aware) so one dead peer can't hold the
        # client for the session default, and fail fast on a peer whose
        # breaker is already open instead of re-proving it down
        budget = retry.remaining(default=REPLICATE_TIMEOUT) or \
            REPLICATE_TIMEOUT
        timeout = aiohttp.ClientTimeout(
            total=max(0.1, min(REPLICATE_TIMEOUT, budget)), connect=5.0)
        for peer in peers:
            breaker = retry.breaker_for(peer)
            if not breaker.allow():
                self._invalidate_lookup(vid)
                return f"replicate to {peer}: circuit open"
            url = f"http://{peer}/{fid}?{qs}"
            try:
                if method == "POST":
                    async with sess.post(url, data=data, headers=headers,
                                         timeout=timeout) as resp:
                        if resp.status >= 300:
                            self._invalidate_lookup(vid)
                            return (f"replicate to {peer}: "
                                    f"{resp.status}")
                else:
                    async with sess.delete(url, headers=headers,
                                           timeout=timeout) as resp:
                        if resp.status >= 300 and resp.status != 404:
                            self._invalidate_lookup(vid)
                            return (f"replicate delete {peer}: "
                                    f"{resp.status}")
            except aiohttp.ClientConnectorError as e:
                # connect-phase failure: the breaker's trip signal
                breaker.record_failure()
                self._invalidate_lookup(vid)
                return f"replicate to {peer}: {e}"
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                # outcome unproven (timeout / mid-stream drop): settle a
                # held half-open probe so the slot doesn't leak, then
                # re-resolve the cached peer on the next write instead
                # of failing for the whole TTL
                breaker.probe_inconclusive()
                self._invalidate_lookup(vid)
                return f"replicate to {peer}: {e!r}"
            breaker.record_success()
        return None

    async def _lookup_volume(self, vid: int) -> str | None:
        urls = await self._lookup_volume_all(vid)
        if not urls:
            return None
        # redirect clients away from a replica whose breaker is open
        healthy = [u for u in urls
                   if retry.breaker_for(u).state != retry.OPEN]
        return (healthy or urls)[0]

    def _client(self) -> aiohttp.ClientSession:
        """Shared keep-alive client session, bound to the serving loop
        (per-call ClientSessions paid a TCP handshake every time)."""
        sess = getattr(self, "_client_sess", None)
        if sess is None or sess.closed:
            sess = aiohttp.ClientSession()
            self._client_sess = sess
        return sess

    LOOKUP_TTL = 10.0  # matches the wdclient vidMap freshness idea

    async def _lookup_volume_all(self, vid: int) -> list[str]:
        cache = getattr(self, "_lookup_cache", None)
        if cache is None:
            cache = self._lookup_cache = {}
        hit = cache.get(vid)
        now = time.monotonic()
        if hit is not None and now - hit[1] < self.LOOKUP_TTL:
            return hit[0]
        try:
            sess = self._client()
            async with sess.get(
                    f"{self.master_url}/dir/lookup",
                    params={"volumeId": str(vid)}) as resp:
                if resp.status != 200:
                    return []
                body = await resp.json()
                urls = [l["url"] for l in body.get("locations", [])]
                # never cache an empty location list: during that TTL
                # window _replicate would see no peers and "succeed"
                # without replicating, and newly-placed replicas would
                # stay invisible
                if urls:
                    cache[vid] = (urls, now)
                else:
                    cache.pop(vid, None)
                return urls
        except aiohttp.ClientError:
            return []

    def _invalidate_lookup(self, vid: int) -> None:
        """Drop a cached lookup (e.g. after replication to a cached
        peer fails) so the next write re-resolves placement."""
        cache = getattr(self, "_lookup_cache", None)
        if cache is not None:
            cache.pop(vid, None)

    # ------------------------------------------------------------------
    # admin: volume lifecycle
    # ------------------------------------------------------------------
    async def handle_assign_volume(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = int(body["volume"])
        try:
            await asyncio.to_thread(
                self.store.add_volume, vid, body.get("collection", ""),
                body.get("replication", "000"),
                bytes(body.get("ttl", (0, 0))))
        except FileExistsError as e:
            return web.json_response({"error": str(e)}, status=409)
        self._dp_attach(self.store.find_volume(vid))
        self.poke_heartbeat()
        return web.json_response({"volume": vid})

    async def handle_delete_volume(self, req: web.Request) -> web.Response:
        body = await req.json()
        try:
            await asyncio.to_thread(
                self.store.delete_volume, int(body["volume"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_mark_readonly(self, req: web.Request) -> web.Response:
        body = await req.json()
        try:
            self.store.mark_readonly(int(body["volume"]), True)
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_mark_writable(self, req: web.Request) -> web.Response:
        body = await req.json()
        try:
            self.store.mark_readonly(int(body["volume"]), False)
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_volume_copy(self, req: web.Request) -> web.Response:
        """VolumeCopy (volume_grpc_copy.go): pull .dat/.idx from a source
        server and mount the volume locally."""
        body = await req.json()
        vid = int(body["volume"])
        collection = body.get("collection", "")
        source = body["source"]
        max_bps = float(body.get("max_bps", 0) or 0)
        if self.store.has_volume(vid):
            return web.json_response({"error": "volume exists"}, status=409)
        loc = min(self.store.locations, key=lambda l: l.volume_count)
        base = loc.base_name(collection, vid)
        copied = 0
        async with aiohttp.ClientSession() as sess:
            for ext in (".dat", ".idx"):
                async with sess.get(
                        f"http://{source}/admin/copy_file",
                        params={"volume": vid, "collection": collection,
                                "ext": ext, "bps": max_bps},
                        timeout=aiohttp.ClientTimeout(total=None)) as resp:
                    if resp.status != 200:
                        return web.json_response(
                            {"error": f"copy {ext} from {source}: "
                                      f"{resp.status}"}, status=502)
                    with open(base + ext, "wb") as f:
                        async for chunk in resp.content.iter_chunked(1 << 20):
                            # destination-side debit of the shared
                            # repair bucket; the source debits its own
                            # via ?bps=, giving a per-node total cap
                            await self._repair_throttle(max_bps, len(chunk))
                            f.write(chunk)
                            copied += len(chunk)
        from ..storage.volume import Volume

        loc.volumes[vid] = await asyncio.to_thread(
            Volume, loc.dir, collection, vid)
        self._dp_attach(loc.volumes[vid])
        self.poke_heartbeat()
        return web.json_response({"volume": vid, "bytes": copied})

    async def handle_volume_unmount(self, req: web.Request) -> web.Response:
        """VolumeUnmount (volume_grpc_admin.go): close + forget a volume,
        keeping its files — the offline half of volume.move."""
        body = await req.json()
        try:
            await asyncio.to_thread(
                self.store.unmount_volume, int(body["volume"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_volume_mount(self, req: web.Request) -> web.Response:
        body = await req.json()
        try:
            await asyncio.to_thread(
                self.store.mount_volume, int(body["volume"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        self._dp_attach(self.store.find_volume(int(body["volume"])))
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_needle_read(self, req: web.Request) -> web.Response:
        """Raw needle record for replica sync (volume.check.disk)."""
        try:
            blob = await asyncio.to_thread(
                self.store.read_raw_needle, int(req.query["volume"]),
                int(req.query["key"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.Response(body=blob,
                            content_type="application/octet-stream")

    async def handle_needle_write(self, req: web.Request) -> web.Response:
        """Append a raw needle record pulled from a peer replica.
        ?force=1 overwrites an existing live needle (content-divergence
        repair where the newer record wins)."""
        try:
            key = await asyncio.to_thread(
                self.store.append_raw_needle, int(req.query["volume"]),
                await req.read(), req.query.get("force") == "1")
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        except (ValueError, PermissionError) as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response({"key": key})

    async def handle_needle_delete(self, req: web.Request) -> web.Response:
        """Tombstone a needle by key without cookie/replication fan-out
        — tombstone propagation for volume.check.disk."""
        body = await req.json()
        try:
            await asyncio.to_thread(
                self.store.delete_needle, int(body["volume"]),
                int(body["key"]))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        except PermissionError as e:
            return web.json_response({"error": str(e)}, status=403)
        return web.json_response({})

    async def handle_needle_ids(self, req: web.Request) -> web.Response:
        """Live needle-id census of one volume — the server side of
        volume.fsck / volume.check.disk (volume_grpc_admin.go
        VolumeNeedleStatus + fsck's idx walk)."""
        vid = int(req.query["volume"])
        try:
            live, deleted = await asyncio.to_thread(
                self.store.needle_ids, vid)
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response(
            {"volume": vid, "needles": [[k, s] for k, s in live],
             "deleted": deleted})

    async def handle_volume_replication(self, req: web.Request) -> web.Response:
        """GET the replica placement — or rewrite it in the superblock
        when the body carries `replication`, the
        VolumeConfigure rpc behind volume.configure.replication
        (command_volume_configure_replication.go)."""
        body = await req.json()
        v = self.store.find_volume(int(body["volume"]))
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        if "replication" in body:
            from ..storage.super_block import ReplicaPlacement
            try:
                rp = ReplicaPlacement.parse(body["replication"])
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
            v.super_block.replica_placement = rp
            await asyncio.to_thread(
                v.dat.write_at, v.super_block.to_bytes(), 0)
            self.poke_heartbeat()
        return web.json_response(
            {"replication": str(v.super_block.replica_placement)})

    async def handle_volume_scrub(self, req: web.Request) -> web.Response:
        """Full-read needle verification for one local volume (the
        per-volume arm of cluster scrub)."""
        body = await req.json()
        vid = int(body["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            return web.Response(status=404, text=f"volume {vid}")
        out = await asyncio.to_thread(v.scrub, int(body.get("limit", 0)))
        return web.json_response(out)

    async def handle_vacuum_check(self, req: web.Request) -> web.Response:
        body = await req.json()
        v = self.store.find_volume(int(body["volume"]))
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        return web.json_response({"garbage_ratio": v.garbage_ratio()})

    async def handle_vacuum_compact(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = int(body["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            return web.json_response({"error": "not found"}, status=404)

        def _compact_detached():
            # vacuum swaps .dat/.idx wholesale: the native plane must
            # hand the volume back to Python for the duration
            with self._dp_detached(vid):
                v.compact()

        await asyncio.to_thread(_compact_detached)
        self.poke_heartbeat()
        return web.json_response({"size": v.content_size()})

    async def handle_volume_info(self, req: web.Request) -> web.Response:
        vid = int(req.query["volume"])
        v = self.store.find_volume(vid)
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        remote = v.volume_info.remote_file() if v.volume_info else None
        return web.json_response({
            "volume": vid, "size": v.content_size(),
            "file_count": v.nm.file_count,
            "deleted_bytes": v.nm.deleted_bytes,
            "garbage_ratio": v.garbage_ratio(),
            "read_only": v.read_only,
            "remote": ({"backend": remote.backend_name, "key": remote.key,
                        "file_size": remote.file_size}
                       if remote else None),
        })

    # ------------------------------------------------------------------
    # admin: tiering (volume_grpc_tier_upload.go / _download.go)
    # ------------------------------------------------------------------
    async def handle_tier_upload(self, req: web.Request) -> web.Response:
        body = await req.json()
        v = self.store.find_volume(int(body["volume"]))
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        keep = bool(body.get("keepLocalDatFile", False))
        try:
            adopt = body.get("adopt")
            if adopt:
                # another replica already uploaded the object: just
                # record it and drop the local copy
                from ..storage import volume_info as vinfo
                rf = vinfo.RemoteFile(**adopt)
                await asyncio.to_thread(v.tier_adopt, rf, keep)
            else:
                storage = backend.get_storage(
                    body.get("dest", "s3.default"))
                rf = await asyncio.to_thread(v.tier_upload, storage, keep)
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        self.poke_heartbeat()
        return web.json_response({
            "volume": v.vid, "backend": rf.backend_name, "key": rf.key,
            "backend_type": rf.backend_type, "backend_id": rf.backend_id,
            "file_size": rf.file_size, "modified_time": rf.modified_time})

    async def handle_tier_download(self, req: web.Request) -> web.Response:
        body = await req.json()
        v = self.store.find_volume(int(body["volume"]))
        if v is None:
            return web.json_response({"error": "not found"}, status=404)
        try:
            await asyncio.to_thread(
                v.tier_download, bool(body.get("deleteRemote", True)))
        except (ValueError, KeyError) as e:
            return web.json_response({"error": str(e)}, status=400)
        self._dp_attach(v)  # local disk again: back onto the fast path
        self.poke_heartbeat()
        return web.json_response({"volume": v.vid,
                                  "size": v.content_size()})

    # ------------------------------------------------------------------
    # admin: EC-shard cold tier (master/tiering.py offload/recall arms)
    # ------------------------------------------------------------------
    def _tier_throttle_sync(self, max_bps: float, direction: str):
        """Per-shard shaping hook for bulk tier movement: debit the
        node-wide "tier" token bucket (so overlapping offloads and
        recalls share one cap) and account the bytes by direction."""
        def throttle(n: int) -> None:
            if n <= 0:
                return
            metrics.counter_add("tier_bytes_moved_total", n,
                                {"dir": direction})
            if max_bps and max_bps > 0:
                ratelimit.bucket("tier", max_bps).acquire(n)
        return throttle

    async def handle_tier_offload(self, req: web.Request) -> web.Response:
        """Move this server's local shards of one EC volume to the
        remote tier named by `remote` (a remote_storage client conf);
        reads keep flowing through the remote-backed shard objects."""
        body = await req.json()
        vid = int(body["volume"])
        remote_conf = body["remote"]
        if not isinstance(remote_conf, dict) or "type" not in remote_conf:
            return web.json_response(
                {"error": "remote must be a client conf with a type"},
                status=400)
        max_bps = float(body.get("max_bps", 0) or 0)
        try:
            result = await asyncio.to_thread(
                self.store.tier_offload_ec, vid, remote_conf,
                self._tier_throttle_sync(max_bps, "offload"))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        except (ValueError, OSError) as e:
            return web.json_response({"error": str(e)}, status=502)
        self.poke_heartbeat()
        return web.json_response(result)

    async def handle_tier_recall(self, req: web.Request) -> web.Response:
        """Bring this server's offloaded shards back to local disk
        (the first half of a recall; the controller then runs
        ec.decode to re-materialize the plain volume)."""
        body = await req.json()
        vid = int(body["volume"])
        max_bps = float(body.get("max_bps", 0) or 0)
        try:
            result = await asyncio.to_thread(
                self.store.tier_recall_ec, vid,
                self._tier_throttle_sync(max_bps, "recall"),
                bool(body.get("deleteRemote", True)))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        except (ValueError, OSError) as e:
            return web.json_response({"error": str(e)}, status=502)
        self.poke_heartbeat()
        return web.json_response(result)

    # ------------------------------------------------------------------
    # admin: erasure coding (volume_grpc_erasure_coding.go)
    # ------------------------------------------------------------------
    async def handle_ec_generate(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = int(body["volume"])
        try:
            await asyncio.to_thread(self.store.generate_ec_shards, vid,
                                    body.get("codec", ""))
        except KeyError as e:
            return web.json_response({"error": str(e)}, status=404)
        return web.json_response({"volume": vid})

    async def handle_ec_rebuild(self, req: web.Request) -> web.Response:
        body = await req.json()
        vid = int(body["volume"])
        try:
            rebuilt = await asyncio.to_thread(
                self.store.rebuild_ec_shards, vid)
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        rebuilt_bytes = 0
        base = self.store._ec_base(vid)
        if base:
            from ..ec import geometry as geo

            for sid in rebuilt:
                try:
                    rebuilt_bytes += os.path.getsize(
                        base + geo.shard_ext(sid))
                except OSError:
                    pass
        return web.json_response({"rebuilt_shards": rebuilt,
                                  "rebuilt_bytes": rebuilt_bytes})

    async def handle_ec_rebuild_partial(self, req: web.Request) -> web.Response:
        """Traffic-minimal shard reconstruction: instead of borrowing
        every surviving shard file (full stripe, the ec/copy +
        ec/rebuild path), stream only the k shard ranges the codec
        needs through the degraded-read guard's first-k-wins fan-out
        and rebuild the missing shard(s) chunk by chunk — the
        partial-stripe repair the warehouse study (arXiv 1309.0186)
        motivates. Bytes fetched are accounted as
        repair_read_bytes_total{mode="partial"} (the classic path
        counts mode="full"), so the saving is measurable."""
        body = await req.json()
        vid = int(body["volume"])
        collection = body.get("collection", "")
        missing = sorted({int(s) for s in body["shard_ids"]})
        max_bps = float(body.get("max_bps", 0) or 0)
        chunk = int(body.get("chunk", 4 << 20))
        if not missing or chunk <= 0:
            return web.json_response(
                {"error": "need shard_ids and chunk > 0"}, status=400)
        try:
            result = await asyncio.to_thread(
                self._partial_ec_rebuild_sync, vid, collection,
                missing, max_bps, chunk)
        except (KeyError, ValueError) as e:
            return web.json_response({"error": str(e)}, status=400)
        self.store.mount_ec_shards(vid, collection, missing)
        self.poke_heartbeat()
        return web.json_response(result)

    def _fetch_ec_index_sync(self, vid: int, collection: str, base: str,
                             hosts: list[str], max_bps: float) -> int:
        """Copy the .ecx (and the .vif, when any holder has one) of
        `vid` from the first of `hosts` that serves it; the bytes
        copied."""
        from ..rpc.httpclient import session

        copied = 0
        for ext in (".ecx", ".vif"):
            blob = None
            for h in hosts:
                try:
                    r = session().get(
                        f"http://{h}/admin/copy_file",
                        params={"volume": vid,
                                "collection": collection,
                                "ext": ext, "bps": max_bps},
                        timeout=60)
                except Exception:
                    continue
                if r.status_code == 200:
                    blob = r.content
                    break
            if blob is None:
                if ext == ".ecx":
                    raise ValueError(f"vid {vid}: no holder "
                                     f"serves .ecx")
                try:  # no .vif anywhere = default RS(10,4)
                    os.unlink(base + ".vif")
                except FileNotFoundError:
                    pass
                continue
            with open(base + ext, "wb") as f:
                f.write(blob)
            self._repair_throttle_sync(max_bps, len(blob))
            copied += len(blob)
        return copied

    def _partial_ec_rebuild_sync(self, vid: int, collection: str,
                                 missing: list[int], max_bps: float,
                                 chunk: int) -> dict:
        import numpy as np

        from ..ec.backend import ReedSolomon
        from ..ec.encoder import code_of
        from ..rpc.httpclient import session

        # land the rebuilt files beside already-mounted shards so
        # ec.mount finds them (same rule as handle_ec_copy)
        loc = self.store.locations[0]
        ecv = self.store.ec_volumes.get(vid)
        if ecv is not None:
            for cand in self.store.locations:
                if cand.dir == ecv.dir:
                    loc = cand
                    break
        base = loc.base_name(collection, vid)
        me = f"{self.store.ip}:{self.store.port}"
        holders = {int(s): [h for h in urls if h != me]
                   for s, urls in self._ec_holders(vid).items()}
        local_sids = sorted(s for s in (ecv.shards if ecv else {})
                            if s not in missing)
        remote_sids = sorted(s for s, urls in holders.items()
                             if urls and s not in missing
                             and s not in local_sids)
        hosts: list[str] = []
        for urls in holders.values():
            for u in urls:
                if u not in hosts:
                    hosts.append(u)
        net_bytes = 0
        local_bytes = 0
        # the sorted needle index (and codec sidecar) must exist
        # locally before the rebuilt shard can be mounted
        if not os.path.exists(base + ".ecx"):
            with tracing.interval("ec.rebuild.fetch_index"):
                net_bytes += self._fetch_ec_index_sync(
                    vid, collection, base, hosts, max_bps)
        code = code_of(base)
        k, m = code.k, code.m
        avail = sorted(set(local_sids) | set(remote_sids))
        # the code's repair plan picks the read set: an LRC single
        # loss streams its locality group (fan-in k/l), and even a
        # global solve gets an INDEPENDENT input row set — a first-k
        # gather can be rank-deficient for structured codes
        plan = None if code.is_rs else code.repair_plan(missing, avail)
        if code.is_rs:
            if len(avail) < k:
                raise ValueError(
                    f"vid {vid}: {len(avail)} shards reachable, "
                    f"need {k}")
        elif plan is None:
            raise ValueError(
                f"vid {vid}: shards {avail} cannot rebuild "
                f"{code.spec} shards {missing}")
        shard_size = None
        if local_sids:
            shard_size = ecv.shards[local_sids[0]].size
        else:
            for s in remote_sids:
                for h in holders[s]:
                    try:
                        r = session().get(
                            f"http://{h}/admin/ec/shard_read",
                            params={"volume": vid, "shard": s,
                                    "stat": "1"}, timeout=10)
                    except Exception:
                        continue
                    if r.status_code == 200:
                        shard_size = int(r.json()["size"])
                        break
                if shard_size is not None:
                    break
        if not shard_size:
            raise ValueError(f"vid {vid}: cannot stat shard size")
        rs = ReedSolomon(k, m, backend=self.store.ec_backend,
                         code=code)
        # planned reads (structured codes): which shards each chunk
        # actually touches — locals for free, remotes over the wire.
        # A planned remote that times out is marked dead and the plan
        # recomputed without it (structured codes carry substitutable
        # shards); only when no plan survives does the chunk fall back
        # to the generic rank-k gather below — a single slow peer must
        # not abort the whole rebuild the way the RS first-k-wins path
        # never lets it.
        dead: set[int] = set()
        plan_local = plan_remote = None

        def split_plan() -> None:
            nonlocal plan_local, plan_remote
            plan_local = [s for s in plan.reads if s in local_sids]
            plan_remote = [s for s in plan.reads
                           if s not in local_sids]

        if plan is not None:
            split_plan()
        fetch_deadline = max(30.0, self.store.ec_read_deadline)

        def read_local(s: int, off: int, n: int):
            nonlocal local_bytes
            with tracing.interval("ec.rebuild.read_local"):
                row = np.frombuffer(ecv.shards[s].read_at(off, n),
                                    dtype=np.uint8)
            local_bytes += len(row)
            return row

        def fetch(sids: list[int], off: int, n: int, need: int) -> dict:
            nonlocal net_bytes
            with tracing.interval("ec.rebuild.fetch"):
                # pace the loop BEFORE the fan-out so the burst the
                # fetch admits is already paid for
                self._repair_throttle_sync(max_bps, need * n)
                fetched = self._remote_shards_fetch_sync(
                    vid, sids, off, n, need=need,
                    deadline=fetch_deadline, bps=max_bps)
            net_bytes += len(fetched) * n
            return fetched

        def gather_planned(off: int, n: int):
            """Rows for one chunk via the repair plan, re-planning
            around unreachable remotes; None -> use the generic
            gather."""
            nonlocal plan
            while plan is not None:
                rows: dict[int, object] = {}
                for s in plan_local:
                    rows[s] = read_local(s, off, n)
                if not plan_remote:
                    return rows
                fetched = fetch(plan_remote, off, n, len(plan_remote))
                short = [s for s in plan_remote if s not in fetched]
                if not short:
                    for s in plan_remote:
                        rows[s] = np.frombuffer(fetched[s],
                                                dtype=np.uint8)
                    return rows
                dead.update(short)
                plan = code.repair_plan(
                    missing, [s for s in avail if s not in dead])
                if plan is not None:
                    split_plan()
            return None

        def gather_generic(off: int, n: int) -> dict:
            """Span-growing gather over ALL reachable shards (dead
            ones included — they may only have been slow): rank k over
            the code's encode rows, which for RS is plain first-k."""
            from ..ops import rs_matrix

            rows: dict[int, object] = {}
            span: list[int] = []

            def grows(s: int) -> bool:
                if len(span) >= k:
                    return False
                if code.is_rs:
                    return True
                return rs_matrix.rank_of(code, span + [s]) > len(span)

            for s in local_sids:
                if grows(s):
                    rows[s] = read_local(s, off, n)
                    span.append(s)
            cands = list(remote_sids)
            while len(span) < k and cands:
                fetched = fetch(cands, off, n, k - len(span))
                if not fetched:
                    break
                for s in sorted(fetched):
                    if grows(s):
                        rows[s] = np.frombuffer(fetched[s],
                                                dtype=np.uint8)
                        span.append(s)
                cands = [s for s in cands if s not in fetched]
            if len(span) < k:
                raise ValueError(
                    f"vid {vid}: only {len(rows)}/{k} shard "
                    f"ranges at +{off}")
            return rows

        written = 0
        files = {s: open(base + geo.shard_ext(s), "wb")
                 for s in missing}
        try:
            for off in range(0, shard_size, chunk):
                n = min(chunk, shard_size - off)
                rows = gather_planned(off, n) if plan is not None \
                    else None
                if rows is None:
                    rows = gather_generic(off, n)
                with tracing.interval("ec.rebuild.reconstruct"):
                    rec = rs.reconstruct(rows, missing=missing)
                with tracing.interval("ec.rebuild.write"):
                    for s in missing:
                        row = np.asarray(rec[s], dtype=np.uint8).tobytes()
                        files[s].write(row)
                        written += len(row)
        except Exception:
            for s, f in files.items():
                f.close()
                try:  # never leave a torn shard for ec.mount to find
                    os.unlink(base + geo.shard_ext(s))
                except FileNotFoundError:
                    pass
            raise
        for f in files.values():
            f.close()
        metrics.counter_add("repair_read_bytes_total", net_bytes,
                            {"mode": "partial"})
        lab = {"mode": "partial", "code": code.spec}
        metrics.counter_add("ec_repair_read_bytes_by_code_total",
                            net_bytes, lab)
        # the shards the rebuilder holds itself, which the counters
        # above leave out: the two together are the plan's reads
        metrics.counter_add("ec_repair_local_read_bytes_total",
                            local_bytes, {"code": code.spec})
        return {"rebuilt_shards": missing, "rebuilt_bytes": written,
                "read_bytes": net_bytes}

    async def handle_ec_copy(self, req: web.Request) -> web.Response:
        """VolumeEcShardsCopy (:126): pull shard files (and optionally
        .ecx/.ecj) from a source server's copy_file endpoint."""
        body = await req.json()
        vid = int(body["volume"])
        collection = body.get("collection", "")
        shard_ids = body["shard_ids"]
        source = body["source"]
        max_bps = float(body.get("max_bps", 0) or 0)
        # repair=true marks shards borrowed for a FULL-stripe rebuild,
        # so repair_read_bytes_total{mode} can contrast full vs the
        # partial path (handle_ec_rebuild_partial)
        is_repair = bool(body.get("repair", False))
        # if shards of this ec volume are already mounted from another
        # disk location, the new files must land beside them — writing
        # to locations[0] would strand them where ec.mount never looks
        loc = self.store.locations[0]
        ecv = self.store.ec_volumes.get(vid)
        if ecv is not None:
            for cand in self.store.locations:
                if cand.dir == ecv.dir:
                    loc = cand
                    break
        base = loc.base_name(collection, vid)
        exts = [geo.shard_ext(sid) for sid in shard_ids]
        if body.get("copy_ecx", True):
            exts += [".ecx"]
        if body.get("copy_ecj", False):
            exts += [".ecj"]
        # the .vif sidecar names the volume's EC codec: a wide-code
        # shard set copied without it would be misread as RS(10,4)
        exts += [".vif"]
        copied = 0
        async with aiohttp.ClientSession() as sess:
            for ext in exts:
                async with sess.get(
                        f"http://{source}/admin/copy_file",
                        params={"volume": vid, "collection": collection,
                                "ext": ext, "bps": max_bps},
                        timeout=aiohttp.ClientTimeout(total=None)) as resp:
                    if resp.status == 404 and ext in (".ecj", ".vif"):
                        if ext == ".vif":
                            # source has no codec sidecar (default
                            # RS(10,4)): a stale local one from an
                            # earlier wide-code volume would poison
                            # this shard set's geometry
                            try:
                                os.unlink(base + ext)
                            except FileNotFoundError:
                                pass
                        continue
                    if resp.status != 200:
                        return web.json_response(
                            {"error": f"copy {ext} from {source}: "
                                      f"{resp.status}"}, status=502)
                    with open(base + ext, "wb") as f:
                        async for chunk in resp.content.iter_chunked(1 << 20):
                            await self._repair_throttle(max_bps, len(chunk))
                            f.write(chunk)
                            copied += len(chunk)
        if is_repair and copied:
            metrics.counter_add("repair_read_bytes_total", copied,
                                {"mode": "full"})
            # per-code accounting: the .vif just copied in names the
            # code family these borrowed bytes repair
            try:
                from ..ec.encoder import code_of

                spec = code_of(base).spec
            except Exception:
                spec = geo.parse_code("").spec
            lab = {"mode": "full", "code": spec}
            metrics.counter_add("ec_repair_read_bytes_by_code_total",
                                copied, lab)
        return web.json_response({"copied": exts, "bytes": copied})

    async def handle_ec_mount(self, req: web.Request) -> web.Response:
        body = await req.json()
        self.store.mount_ec_shards(int(body["volume"]),
                                   body.get("collection", ""),
                                   body["shard_ids"])
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_ec_unmount(self, req: web.Request) -> web.Response:
        body = await req.json()
        self.store.unmount_ec_shards(int(body["volume"]), body["shard_ids"])
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_ec_delete(self, req: web.Request) -> web.Response:
        body = await req.json()
        self.store.delete_ec_shards(int(body["volume"]),
                                    body.get("shard_ids"))
        self.poke_heartbeat()
        return web.json_response({})

    async def handle_ec_to_volume(self, req: web.Request) -> web.Response:
        """VolumeEcShardsToVolume (:407): decode shards back to .dat/.idx
        and mount as a normal volume."""
        body = await req.json()
        vid = int(body["volume"])
        collection = body.get("collection", "")
        ecv = self.store.ec_volumes.get(vid)
        if ecv is None:
            return web.json_response({"error": "ec volume not mounted"},
                                     status=404)
        base = ecv.base_name()

        def _decode():
            dat_size = find_dat_size(base)
            write_dat_file(base, dat_size, backend=self.store.ec_backend)
            write_idx_from_ecx(base)

        await asyncio.to_thread(_decode)
        self.store.delete_ec_shards(vid, None)
        for loc in self.store.locations:
            if os.path.dirname(base) == loc.dir:
                from ..storage.volume import Volume

                loc.volumes[vid] = Volume(loc.dir, collection, vid)
                self._dp_attach(loc.volumes[vid])
        self.poke_heartbeat()
        return web.json_response({"volume": vid})

    async def handle_ec_shard_read(self, req: web.Request) -> web.StreamResponse:
        """VolumeEcShardRead (:309): stream a byte range of a local
        shard."""
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        offset = int(req.query.get("offset", 0))
        size = int(req.query.get("size", -1))
        ecv = self.store.ec_volumes.get(vid)
        shard = ecv.shards.get(sid) if ecv else None
        if shard is None:
            return web.Response(status=404, text="shard not found")
        if req.query.get("stat") == "1":
            # size probe: the partial rebuilder plans its chunk loop
            # from a peer's shard length without moving shard bytes
            return web.json_response({"volume": vid, "shard": sid,
                                      "size": shard.size})
        if size < 0:
            size = shard.size - offset
        data = await asyncio.to_thread(shard.read_at, offset, size)
        # ?bps= = repair pull: shape the source side too
        bps = float(req.query.get("bps", 0) or 0)
        if bps > 0:
            await self._repair_throttle(bps, len(data))
        return web.Response(body=data,
                            content_type="application/octet-stream")

    # -- server-side query (volume_grpc_query.go, query/json) ----------
    async def handle_query(self, req: web.Request) -> web.StreamResponse:
        """VolumeServer.Query rpc: scan JSON object bodies held locally
        and stream back only the projected/filtered records (NDJSON)."""
        from ..query import Filter, query_json_bytes

        body = await req.json()
        fids = body.get("from", {}).get("file_ids") or body.get("fids")
        if not fids:
            return web.json_response(
                {"error": "query needs fids"}, status=400)
        selections = body.get("selections", [])
        fd = body.get("filter", {})
        filt = Filter(field=fd.get("field", ""),
                      op=fd.get("operand", fd.get("op", "=")),
                      value=str(fd.get("value", "")))
        # validate everything that can raise BEFORE streaming starts:
        # after prepare() the 200 is on the wire and errors can only
        # truncate the stream
        from ..query.json_query import OPS

        if filt.op not in OPS:
            return web.json_response(
                {"error": f"bad operand {filt.op!r}"}, status=400)
        try:
            parsed = [t.parse_file_id(fid) for fid in fids]
        except ValueError as e:
            return web.json_response({"error": str(e)}, status=400)
        resp = web.StreamResponse()
        resp.content_type = "application/x-ndjson"
        await resp.prepare(req)
        for vid, key, cookie in parsed:
            v = self.store.find_volume(vid)
            if v is None:
                continue  # reference queries only local volumes
            try:
                n = await asyncio.to_thread(v.read_needle, key, cookie)
            except (KeyError, PermissionError, ValueError):
                continue
            payload = n.data
            if n.is_compressed:
                from ..utils import compression

                try:
                    payload = await asyncio.to_thread(
                        compression.ungzip, payload)
                except OSError:
                    continue
            out = []
            for doc in query_json_bytes(payload, selections, filt):
                out.append(json.dumps(doc, separators=(",", ":")))
            if out:
                await resp.write(("\n".join(out) + "\n").encode())
        await resp.write_eof()
        return resp

    # -- incremental sync / tail (volume_backup.go, volume_grpc_tail.go)
    async def handle_volume_sync_status(self, req: web.Request) \
            -> web.Response:
        """VolumeSyncStatus rpc: tail offset + compact revision +
        last append stamp, the negotiation for incremental copy."""
        v = self.store.find_volume(int(req.query["volume"]))
        if v is None:
            return web.Response(status=404, text="volume not found")
        await asyncio.to_thread(v.sync)
        return web.json_response(v.sync_status())

    async def handle_volume_incremental_copy(self, req: web.Request) \
            -> web.StreamResponse:
        """VolumeIncrementalCopy rpc: stream raw .dat records appended
        strictly after since_ns."""
        v = self.store.find_volume(int(req.query["volume"]))
        if v is None:
            return web.Response(status=404, text="volume not found")
        since_ns = int(req.query.get("since_ns", "0"))
        await asyncio.to_thread(v.sync)
        offset = await asyncio.to_thread(
            v.offset_for_append_at_ns, since_ns)
        end = v.dat.size()
        resp = web.StreamResponse()
        resp.content_length = end - offset
        await resp.prepare(req)
        while offset < end:
            # cap at the captured end: concurrent appends must not
            # push the body past the declared content length, and a
            # concurrent compact (file swap) must abort, not mis-frame
            chunk = await asyncio.to_thread(
                v.read_segment, offset, min(1 << 20, end - offset))
            if not chunk:
                raise ConnectionResetError(
                    f"volume {v.vid} changed under incremental copy")
            await resp.write(chunk)
            offset += len(chunk)
        await resp.write_eof()
        return resp

    async def handle_volume_tail(self, req: web.Request) \
            -> web.StreamResponse:
        """VolumeTailSender rpc: stream records after since_ns and keep
        following new appends until idle for idle_timeout seconds."""
        v = self.store.find_volume(int(req.query["volume"]))
        if v is None:
            return web.Response(status=404, text="volume not found")
        since_ns = int(req.query.get("since_ns", "0"))
        idle_timeout = float(req.query.get("idle_timeout", "3"))
        offset = await asyncio.to_thread(
            v.offset_for_append_at_ns, since_ns)
        resp = web.StreamResponse()
        await resp.prepare(req)
        idle = 0.0
        while idle < idle_timeout:
            # size() flushes the write buffer — enough for read
            # visibility; fsync per poll would hammer the write path
            end = await asyncio.to_thread(v.dat.size)
            if end < offset:
                break  # compact/truncate rewrote history: end the tail
            if offset < end:
                idle = 0.0
                while offset < end:
                    chunk = await asyncio.to_thread(
                        v.read_segment, offset,
                        min(1 << 20, end - offset))
                    if not chunk:
                        return resp  # volume swapped mid-read
                    await resp.write(chunk)
                    offset += len(chunk)
            else:
                await asyncio.sleep(0.1)
                idle += 0.1
        await resp.write_eof()
        return resp

    async def handle_volume_tail_receive(self, req: web.Request) \
            -> web.Response:
        """VolumeTailReceiver rpc: follow another server's tail stream
        and append its records into the local replica."""
        body = await req.json()
        vid = int(body["volume"])
        source = body["source"]
        v = self.store.find_volume(vid)
        if v is None:
            return web.json_response({"error": "volume not found"},
                                     status=404)
        since_ns = int(body.get("since_ns", v.last_append_at_ns))
        idle_timeout = float(body.get("idle_timeout", 3))
        buf = bytearray()
        # raw segment application needs exclusive Python ownership of
        # the tail (multi-record append + error-path truncate); the
        # maintenance window runs off the loop (detach replays the
        # .idx into a fresh map) and ALWAYS closes — error returns
        # must not strand the volume on the slow path, and the
        # counter keeps a concurrent vacuum's window from being
        # broken by this one's reattach
        ctx = self._dp_detached(vid)
        await asyncio.to_thread(ctx.__enter__)
        try:
            return await self._tail_receive_stream(
                req, v, vid, source, since_ns, idle_timeout, buf)
        finally:
            await asyncio.to_thread(ctx.__exit__, None, None, None)

    async def _tail_receive_stream(self, req, v, vid, source, since_ns,
                                   idle_timeout, buf) -> web.Response:
        applied = 0
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                    f"http://{source}/admin/volume_tail",
                    params={"volume": vid, "since_ns": since_ns,
                            "idle_timeout": idle_timeout},
                    timeout=aiohttp.ClientTimeout(total=None)) as resp:
                if resp.status != 200:
                    return web.json_response(
                        {"error": f"tail from {source}: {resp.status}"},
                        status=502)
                async for chunk in resp.content.iter_chunked(1 << 20):
                    buf.extend(chunk)
                    whole = ndl.whole_records_prefix(buf, v.version)
                    if whole:
                        applied += await asyncio.to_thread(
                            v.append_raw_segment,
                            bytes(memoryview(buf)[:whole]))
                        del buf[:whole]
        if buf:
            return web.json_response(
                {"error": f"tail stream ended mid-record "
                          f"({len(buf)} trailing bytes)",
                 "applied": applied}, status=502)
        self.poke_heartbeat()
        return web.json_response({"applied": applied})

    async def handle_copy_file(self, req: web.Request) -> web.StreamResponse:
        """CopyFile rpc (volume_grpc_copy.go): stream any volume/shard
        file by extension."""
        vid = int(req.query["volume"])
        collection = req.query.get("collection", "")
        ext = req.query["ext"]
        if ext not in {".dat", ".idx", ".ecx", ".ecj", ".vif"} and \
                not (ext.startswith(".ec") and ext[3:].isdigit()):
            return web.Response(status=400, text=f"bad ext {ext}")
        if ext in (".dat", ".idx"):
            v = self.store.find_volume(vid)
            if v is not None:
                await asyncio.to_thread(v.sync)
        path = None
        for loc in self.store.locations:
            cand = loc.base_name(collection, vid) + ext
            if os.path.exists(cand):
                path = cand
                break
        if path is None:
            return web.Response(status=404, text=f"{ext} not found")
        # ?bps= marks a repair pull and shapes the SOURCE side against
        # this node's shared repair bucket
        bps = float(req.query.get("bps", 0) or 0)
        resp = web.StreamResponse()
        resp.content_length = os.path.getsize(path)
        await resp.prepare(req)
        with open(path, "rb") as f:
            while True:
                chunk = await asyncio.to_thread(f.read, 1 << 20)
                if not chunk:
                    break
                if bps > 0:
                    await self._repair_throttle(bps, len(chunk))
                await resp.write(chunk)
        await resp.write_eof()
        return resp

    # ------------------------------------------------------------------
    # degraded reads: fetch remote shard intervals synchronously (called
    # from store threads, store_ec.go:299 readRemoteEcShardInterval)
    # ------------------------------------------------------------------
    EC_HOLDERS_TTL = 10.0

    def _ec_holders(self, vid: int) -> dict:
        """{shard_id_str: [host:port, ...]} from the client vid cache —
        a subscribed MasterClient whose KeepConnected ec_updates stream
        invalidates on shard moves, so degraded reads neither poll the
        master per shard nor serve a stale map after ec.balance
        (vid_map.go:169-236)."""
        mc = getattr(self, "_ec_master_client", None)
        if mc is None:
            import threading

            from ..wdclient.client import MasterClient

            lock = getattr(self, "_ec_mc_lock", None)
            if lock is None:
                lock = self.__dict__.setdefault(
                    "_ec_mc_lock", threading.Lock())
            with lock:
                mc = getattr(self, "_ec_master_client", None)
                if mc is None:
                    # double-checked: concurrent fan-out threads must
                    # not each spawn a subscriber websocket
                    mc = self._ec_master_client = MasterClient(
                        self.masters or [self.master_url],
                        subscribe=True)
        shards = mc.lookup_ec(vid, max_age=self.EC_HOLDERS_TTL)
        return {str(sid): urls for sid, urls in shards.items()}

    def _fetch_shard_from_holders(self, vid: int, sid: int,
                                  holders: list, offset: int, size: int,
                                  deadline_t: float,
                                  bps: float = 0.0) -> bytes | None:
        import requests

        from ..rpc.httpclient import session

        for holder in holders:
            remaining = deadline_t - time.monotonic()
            if remaining <= 0:
                return None
            params = {"volume": vid, "shard": sid,
                      "offset": offset, "size": size}
            if bps > 0:  # repair pull: let the source shape its side
                params["bps"] = bps
            try:
                r = session().get(
                    f"http://{holder}/admin/ec/shard_read",
                    params=params,
                    timeout=min(remaining, 10.0))
                if r.status_code == 200:
                    return r.content
            except requests.RequestException:
                continue
        return None

    def _remote_shard_read_sync(self, vid: int, sid: int, offset: int,
                                size: int) -> bytes | None:
        me = f"{self.store.ip}:{self.store.port}"
        holders = [h for h in self._ec_holders(vid).get(str(sid), [])
                   if h != me]
        return self._fetch_shard_from_holders(
            vid, sid, holders, offset, size,
            time.monotonic() + self.store.ec_read_deadline)

    def _remote_shards_fetch_sync(self, vid: int, sids: list, offset: int,
                                  size: int, need: int,
                                  deadline: float,
                                  bps: float = 0.0) -> dict:
        """Concurrent first-k-wins shard-range fan-out for degraded
        reads (goroutine fan-out in store_ec.go:349-393): every
        candidate shard is requested at once; the call returns as soon
        as `need` of them arrive or the deadline passes, so one hung
        peer costs nothing but its own thread."""
        from concurrent.futures import FIRST_COMPLETED, wait

        me = f"{self.store.ip}:{self.store.port}"
        holders_map = self._ec_holders(vid)
        deadline_t = time.monotonic() + deadline
        pool = getattr(self, "_ec_fetch_pool", None)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = self._ec_fetch_pool = ThreadPoolExecutor(
                max_workers=16, thread_name_prefix="ec-fetch")
        futs = {}
        for sid in sids:
            holders = [h for h in holders_map.get(str(sid), []) if h != me]
            if holders:
                # copy_context: pool.submit (unlike asyncio.to_thread)
                # drops contextvars, which would orphan the fetch spans
                # from the request trace and lose the deadline
                futs[pool.submit(
                    contextvars.copy_context().run,
                    self._fetch_shard_from_holders, vid, sid, holders,
                    offset, size, deadline_t, bps)] = sid
        out: dict[int, bytes] = {}
        pending = set(futs)
        while pending and len(out) < need:
            remaining = deadline_t - time.monotonic()
            if remaining <= 0:
                break
            done, pending = wait(pending, timeout=remaining,
                                 return_when=FIRST_COMPLETED)
            for fut in done:
                data = fut.result()
                if data is not None:
                    out[futs[fut]] = data
        for fut in pending:  # abandoned losers; bounded by timeouts
            fut.cancel()
        return out

    # ------------------------------------------------------------------
    async def handle_debug_ec(self, req: web.Request) -> web.Response:
        from ..ec import backend as ec_backend

        snap = ec_backend.probe_snapshot()
        # per-volume view: which code each mounted EC volume actually
        # runs (k / locals / globals from its .vif), so a mixed-code
        # cluster is inspectable per volume, not just per process
        vols = {}
        for vid, ecv in sorted(self.store.ec_volumes.items()):
            code = ecv.code
            vols[str(vid)] = {
                "code": code.spec, "kind": code.kind, "k": code.k,
                "locals": code.n_local, "globals": code.n_global,
                "shards": sorted(ecv.shards),
            }
        snap["volumes"] = vols
        return web.json_response(snap)

    async def handle_debug_commit(self, req: web.Request) -> web.Response:
        """Group-commit pipeline snapshot: current window, queue depth,
        durability mode, batch-size/bytes distributions — plus the
        native front's commit counters when the C++ plane serves the
        hot path (its commit queue is a separate instance of the same
        design, so both views matter)."""
        snap = self.commit.snapshot()
        if self.dp is not None:
            try:
                snap["native"] = self.dp.commit_stats()
            except Exception:
                pass
        return web.json_response(snap)

    async def handle_status(self, req: web.Request) -> web.Response:
        hb = self.store.collect_heartbeat()
        out = {"Version": "seaweedfs-tpu", **hb}
        if self.dp is not None:
            out["native_dataplane"] = self.dp.http_stats()
            front = self.dp.front_stats()
            if front is not None:
                out["native_front"] = front
        return web.json_response(out)

    async def handle_metrics(self, req: web.Request) -> web.Response:
        # disk gauges recomputed at scrape time (the reference keeps
        # volume/EC size gauges in stats/metrics.go + store_ec.go:41)
        by_col: dict[str, dict] = {}
        for loc in self.store.locations:
            for v in loc.volumes.values():
                s = by_col.setdefault(v.collection,
                                      {"n": 0, "bytes": 0, "files": 0})
                s["n"] += 1
                s["bytes"] += v.content_size()
                s["files"] += v.nm.file_count
        for col, s in by_col.items():
            lab = {"collection": col or "default"}
            metrics.gauge_set("volume_server_volumes", s["n"], lab)
            metrics.gauge_set("volume_server_total_disk_size",
                              s["bytes"], lab)
            metrics.gauge_set("volume_server_file_count", s["files"], lab)
        ec_by_col: dict[str, dict] = {}
        for ecv in self.store.ec_volumes.values():
            s = ec_by_col.setdefault(ecv.collection,
                                     {"shards": 0, "bytes": 0})
            n = ecv.shard_bits().count()
            s["shards"] += n
            try:
                s["bytes"] += n * ecv.shard_size()
            except Exception:
                pass
        for col, s in ec_by_col.items():
            lab = {"collection": col or "default"}
            metrics.gauge_set("volume_server_ec_shards", s["shards"], lab)
            metrics.gauge_set("volume_server_ec_bytes", s["bytes"], lab)
        metrics.gauge_set(
            "volume_server_max_volumes",
            sum(l.max_volumes for l in self.store.locations))
        metrics.gauge_set("volume_server_in_flight_upload_bytes",
                          self._upload_flight.value)
        metrics.gauge_set("volume_server_in_flight_download_bytes",
                          self._download_flight.value)
        cs = self.commit.snapshot()
        metrics.gauge_set("write_commit_queue_depth", cs["queue_depth"])
        text = metrics.render()
        text += self._native_front_exposition()
        text += self._native_commit_exposition()
        return web.Response(text=text, content_type="text/plain")

    def _native_commit_exposition(self) -> str:
        """Native commit-queue counters appended to /metrics — same
        render-direct treatment as _native_front_exposition (monotonic
        snapshots owned by the C library)."""
        if self.dp is None:
            return ""
        try:
            st = self.dp.commit_stats()
        except Exception:
            return ""
        if not st:
            return ""
        lines = []
        for name in ("batches", "fsyncs", "writes", "bytes"):
            if name in st:
                lines.append(
                    f"# TYPE native_commit_{name}_total counter")
                lines.append(
                    f"native_commit_{name}_total {st[name]}")
        if "fsync_seconds" in st:
            lines.append("# TYPE native_commit_fsync_seconds_total "
                         "counter")
            lines.append("native_commit_fsync_seconds_total "
                         f"{st['fsync_seconds']:.6f}")
        if "queue_depth" in st:
            lines.append("# TYPE native_commit_queue_depth gauge")
            lines.append(f"native_commit_queue_depth "
                         f"{st['queue_depth']}")
        return "\n".join(lines) + "\n" if lines else ""

    def _native_front_exposition(self) -> str:
        """Native data-plane front counters appended to /metrics.
        These are monotonic snapshots owned by the C library, so they
        render directly instead of being pumped through the registry
        (counter_add would double-count on every scrape).
        `native_front_*` keeps its historical meaning (the volume
        front); `native_fronts_*{front=...}` breaks all three roles
        out per front for the "Native fronts" dashboard panel."""
        if self.dp is None:
            return ""
        try:
            st = self.dp.front_stats()
        except Exception:
            return ""
        if st is None:
            return ""
        lines = ["# TYPE native_front_requests_total counter"]
        for code in ("2xx", "3xx", "4xx", "5xx"):
            lines.append(
                f'native_front_requests_total{{code="{code}"}} '
                f'{st[code]}')
        lines.append("# TYPE native_front_bytes_total counter")
        for direction in ("in", "out"):
            lines.append(
                f'native_front_bytes_total{{direction="{direction}"}} '
                f'{st["bytes_" + direction]}')
        # per-role families: the S3/filer fronts run in this process
        # (combined-server mode shares the one C library), so their
        # counters federate through this volume server's /metrics
        from ..native import dataplane as dpmod

        per_role = []
        for front, role in (("volume", dpmod.ROLE_VOLUME),
                            ("s3", dpmod.ROLE_S3),
                            ("filer", dpmod.ROLE_FILER)):
            try:
                rst = self.dp.role_front_stats(role)
            except Exception:
                rst = None
            if rst is not None:
                per_role.append((front, rst))
        if per_role:
            lines.append("# TYPE native_fronts_requests_total counter")
            for front, rst in per_role:
                for code in ("2xx", "3xx", "4xx", "5xx"):
                    lines.append(
                        f'native_fronts_requests_total{{front="{front}"'
                        f',code="{code}"}} {rst[code]}')
            lines.append("# TYPE native_fronts_bytes_total counter")
            for front, rst in per_role:
                for direction in ("in", "out"):
                    lines.append(
                        f'native_fronts_bytes_total{{front="{front}"'
                        f',direction="{direction}"}} '
                        f'{rst["bytes_" + direction]}')
        return "\n".join(lines) + "\n"

    async def handle_ui(self, req: web.Request) -> web.Response:
        """Status page (server/volume_server_ui/ equivalent)."""
        import html as _html

        hb = self.store.collect_heartbeat()
        rows = "".join(
            f"<tr><td>{v['id']}</td>"
            f"<td>{_html.escape(v['collection']) or '-'}</td>"
            f"<td>{v['size']:,}</td><td>{v['file_count']}</td>"
            f"<td>{v['delete_count']}</td>"
            f"<td>{'ro' if v['read_only'] else 'rw'}</td>"
            f"<td>{v['replica_placement']}</td></tr>"
            for v in hb["volumes"])
        ec_rows = "".join(
            f"<tr><td>{e['id']}</td>"
            f"<td>{_html.escape(e['collection']) or '-'}</td>"
            f"<td>{e['shard_bits']:014b}</td></tr>"
            for e in hb["ec_shards"])
        return web.Response(
            text=f"<html><body><h1>seaweedfs-tpu volume server</h1>"
                 f"<p>{_html.escape(hb['public_url'])} &middot; master "
                 f"{self.master_url} &middot; "
                 f"{len(hb['volumes'])} volumes, "
                 f"{len(hb['ec_shards'])} ec volumes</p>"
                 f"<table border=1 cellpadding=4><tr><th>id</th>"
                 f"<th>collection</th><th>size</th><th>files</th>"
                 f"<th>deleted</th><th>mode</th><th>rp</th></tr>"
                 f"{rows}</table>"
                 f"<h2>ec shards</h2>"
                 f"<table border=1 cellpadding=4><tr><th>id</th>"
                 f"<th>collection</th><th>shard bits</th></tr>"
                 f"{ec_rows}</table>"
                 f"<p><a href='/metrics'>metrics</a> &middot; "
                 f"<a href='/status'>status</a></p></body></html>",
            content_type="text/html")

