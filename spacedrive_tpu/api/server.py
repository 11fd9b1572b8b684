"""HTTP/websocket host: the framework's `apps/server` equivalent.

Mirrors the reference's axum host (/root/reference/apps/server/src/main.rs:40-63):
- `GET /health` — liveness;
- `GET/WS /rspc` — the RPC transport (websocket JSON frames; HTTP GET/POST
  for one-shot queries/mutations);
- `GET /spacedrive/thumbnail/<cas_id>.webp` and
  `GET /spacedrive/file/<library_id>/<location_id>/<file_path_id>` — the
  custom_uri plane (core/src/custom_uri/mod.rs:149-330) serving
  thumbnails and original files with HTTP Range support.

Wire protocol (JSON frames over the websocket):
  → {"id": 1, "type": "query"|"mutation", "path": "...", "input": {...}}
  ← {"id": 1, "type": "response", "result": ...}
  ← {"id": 1, "type": "error", "code": "...", "message": "..."}
  → {"id": 2, "type": "subscription", "path": "...", "input": {...}}
  ← {"id": 2, "type": "event", "data": ...}   (repeatedly)
  → {"id": 2, "type": "subscriptionStop"}
"""

from __future__ import annotations

import asyncio
import json
import mimetypes
import os
from typing import Any, Dict, Optional

from aiohttp import WSCloseCode, WSMsgType, web

from .. import channels, chaos, tasks, telemetry, threadctx, tracing
from ..locations.paths import IsolatedPath
from ..media.thumbnail import thumbnail_path
from ..telemetry import API_REQUESTS
from ..timeouts import with_timeout
from .router import Router, RpcError, mount_router

RANGE_CHUNK = 1 << 20


class WsSubscriptionPump:
    """One subscription's bounded delivery path: events land in a
    registered `api.ws` channel and ONE supervised drainer task sends
    them under the api.ws.send budget. This is the EventBus's buffered
    edge — in-process subscribers stay synchronous callbacks (cheap
    filters), but delivery to a REMOTE subscriber is where unbounded
    buffering lived: the old shape spawned one emit task per event, so
    a stalled consumer accumulated the node's whole event stream (every
    task parked on its 30s send budget). Now depth is capped by the
    channel contract: TelemetrySnapshot frames coalesce to the newest
    snapshot, and overflow sheds NEW events into
    sd_chan_shed_total{api.ws} — a slow consumer loses events (it was
    going to time out anyway), never wedges the node or its memory."""

    def __init__(self, send, owner: str):
        self._send = send
        self.chan = channels.channel("api.ws")
        self._task = tasks.spawn("ws-pump", self._drain(), owner=owner)

    def offer(self, payload: dict) -> bool:
        """Queue one event frame (loop thread). Returns False when the
        overflow policy shed it."""
        data = payload.get("data")
        key = None
        if isinstance(data, dict) and data.get("type") in (
                "TelemetrySnapshot", "HealthSnapshot",
                "FleetHealthSnapshot"):
            # Snapshot-coalescing (newest wins): only the latest
            # telemetry/health/fleet state matters to a consumer that
            # fell behind — intermediate snapshots are stale by
            # definition.
            key = data["type"]
        return self.chan.put_nowait(payload, key=key)

    async def _drain(self) -> None:
        while True:
            payload = await self.chan.get()
            # Chaos seam: delay = a slow consumer, wedge = a dead one
            # that never reads — the channel above must shed while the
            # drainer is parked (the node and its memory stay bounded;
            # the pump itself is freed by unsubscribe/teardown
            # cancelling this task), drop = a lost frame.
            f = chaos.hit("api.ws.send", only=("delay", "drop", "wedge"))
            if f is not None and await chaos.apply_async(f):
                continue  # dropped
            await self._send(payload)

    async def stop(self) -> None:
        await tasks.cancel_and_gather(self._task)
        # The subscriber is gone: drop its undelivered frames so the
        # per-name depth gauge doesn't freeze at this DEAD instance's
        # depth forever (found by load_bench's wedge gate: a chaos-
        # wedged pump died at full depth and sd_chan_depth{api.ws}
        # read "wedged" long after the consumer was reaped).
        while True:
            try:
                self.chan.get_nowait()
            except asyncio.QueueEmpty:
                break


@web.middleware
async def _count_requests(request: web.Request, handler):
    """Per-route-template request counter (templates, not raw paths, so
    label cardinality stays bounded; unmatched paths share one label)."""
    resource = request.match_info.route.resource  # None for true 404s
    API_REQUESTS.labels(
        route=getattr(resource, "canonical", None) or "unmatched").inc()
    return await handler(request)


class ApiServer:
    def __init__(self, node, router: Optional[Router] = None,
                 http_inflight_cap: Optional[int] = None):
        self.node = node
        self._owner = f"{getattr(node, 'task_owner', 'proc')}/api"
        self.router = router or mount_router(node)
        self.app = web.Application(middlewares=[_count_requests])
        self.app.router.add_get("/", self._index)
        self.app.router.add_get("/static/{name}", self._static)
        self.app.router.add_get("/manifest.webmanifest", self._manifest)
        self.app.router.add_get("/health", self._health)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_get("/rspc", self._rspc_ws)
        self.app.router.add_post("/rspc/{path}", self._rspc_http)
        self.app.router.add_get("/rspc/{path}", self._rspc_http)
        self.app.router.add_get(
            "/spacedrive/thumbnail/{cas_id}.webp", self._thumbnail)
        self.app.router.add_get(
            "/spacedrive/file/{library_id}/{location_id}/{file_path_id}",
            self._file)
        self._runner: Optional[web.AppRunner] = None
        self.port: Optional[int] = None
        # Admission window for rspc HTTP dispatch (declared channel
        # api.http.inflight, policy shed_new): a request past capacity
        # is refused with 503 SHED instead of queueing unbounded
        # behind a saturated backend — the HTTP plane's version of the
        # jobs run-queue's admission refusal. Sheds are the health
        # observatory's named evidence for an API storm.
        # `http_inflight_cap` narrows THIS instance below the declared
        # ceiling (never above) — how the load harness drives the shed
        # edge at bench scale.
        self._inflight = channels.channel(
            "api.http.inflight", capacity_cap=http_inflight_cap)
        # Live /rspc sockets (loop thread only), closed at shutdown.
        self._websockets: set = set()
        self.app.on_shutdown.append(self._close_websockets)

    # -- lifecycle ---------------------------------------------------------

    async def start(self, host: str = "127.0.0.1", port: int = 8080) -> int:
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = self._runner.addresses[0][1]
        return self.port

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    async def _close_websockets(self, _app: web.Application) -> None:
        """Shutdown hook: aiohttp's cleanup waits out every open handler
        (its 60 s shutdown_timeout), so one connected client held stop()
        for minutes. Closing the live sockets first lets each handler's
        teardown run at once."""
        for ws in list(self._websockets):
            await with_timeout("api.ws.send", ws.close(
                code=WSCloseCode.GOING_AWAY, message=b"server shutdown"))

    # -- handlers ----------------------------------------------------------

    async def _health(self, _request: web.Request) -> web.Response:
        return web.Response(text="OK")

    async def _metrics(self, _request: web.Request) -> web.Response:
        """Prometheus text exposition of the node-wide registry — the
        operator-facing face of spacedrive_tpu/telemetry.py (scrape
        this; the webui gets the same data as TelemetrySnapshot
        events)."""
        return web.Response(
            body=telemetry.render_prometheus().encode("utf-8"),
            headers={"Content-Type": telemetry.PROMETHEUS_CONTENT_TYPE})

    async def _index(self, _request: web.Request) -> web.Response:
        """Web explorer entry (apps/web equivalent; assets from
        api/static, the reference's embedded-dist pattern,
        apps/server/src/main.rs:60-63)."""
        from .webui import index_html

        # index_html reads the asset from disk on first render
        html = await asyncio.to_thread(index_html)
        return web.Response(text=html, content_type="text/html")

    async def _static(self, request: web.Request) -> web.Response:
        """Serve the explorer's static assets (no path traversal: the
        name must resolve inside STATIC_DIR)."""
        from .webui import STATIC_DIR

        name = request.match_info["name"]
        path = os.path.realpath(os.path.join(STATIC_DIR, name))
        if not path.startswith(os.path.realpath(STATIC_DIR) + os.sep) \
                or not os.path.isfile(path):
            raise web.HTTPNotFound()
        ctype = (mimetypes.guess_type(path)[0]
                 or "application/octet-stream")
        def _read() -> bytes:
            with open(path, "rb") as f:
                return f.read()

        body = await asyncio.to_thread(_read)
        return web.Response(body=body, content_type=ctype)

    async def _manifest(self, _request: web.Request) -> web.Response:
        """PWA manifest: with the reconnecting websocket client this
        makes the web UI an installable standalone app — the honest
        stand-in for the reference's Tauri desktop shell
        (apps/desktop/src-tauri) in a runtime with no webview toolkit."""
        return web.json_response({
            "name": "Spacedrive TPU",
            "short_name": "sdtpu",
            "start_url": "/",
            "display": "standalone",
            "background_color": "#16161d",
            "theme_color": "#16161d",
            "description": "TPU-native virtual distributed filesystem",
            "icons": [],
        }, content_type="application/manifest+json")

    async def _rspc_http(self, request: web.Request) -> web.Response:
        path = request.match_info["path"]
        if not self._inflight.put_nowait(1):
            # Admission refusal FIRST — before the body read, so a
            # shed request costs zero backend work (a flood of large
            # or slow-trickled bodies must not buy a budgeted read
            # each before being refused). The shed counts into
            # sd_chan_shed_total{api.http.inflight}.
            return web.json_response(
                {"error": {"code": "SHED",
                           "message": "API host at dispatch capacity; "
                                      "retry with backoff"}},
                status=503, headers={"Retry-After": "1"})
        try:
            if request.method == "POST":
                try:
                    # Budgeted body read: a slow-loris client cannot
                    # pin the handler (it occupies its admission slot
                    # for at most the read budget).
                    input = await with_timeout("api.http.read",
                                               request.json())
                except json.JSONDecodeError:
                    input = None
                except asyncio.TimeoutError:
                    # A half-sent body must FAIL the request, not
                    # dispatch the procedure with null input.
                    return web.json_response(
                        {"error": {"code": "TIMEOUT",
                                   "message": "request body read "
                                              "timed out"}},
                        status=408)
            else:
                raw = request.query.get("input")
                input = json.loads(raw) if raw else None
            # Chaos seam (inside the admission window): delay = a slow
            # backend — storms against it drive the shed path above;
            # error = a failing one, reported as 503 so load clients
            # exercise their retry discipline.
            f = chaos.hit("api.http.dispatch", only=("delay", "error"))
            if f is not None:
                try:
                    await chaos.apply_async(f)
                except chaos.ChaosError as e:
                    return web.json_response(
                        {"error": {"code": "UNAVAILABLE",
                                   "message": str(e)}}, status=503)
            # Clients (and the trace_export CLI pulling a live trace)
            # propagate their trace in X-Sdtpu-Trace; the dispatch
            # span then continues it, so an API-triggered sync/job
            # shows up under the caller's id.
            with tracing.continue_trace(
                    request.headers.get("X-Sdtpu-Trace")), \
                    tracing.span(f"rpc/{path}"):
                result = await self.router.dispatch(path, input)
            return web.json_response({"result": result})
        except RpcError as e:
            return web.json_response(
                {"error": {"code": e.code, "message": e.message}},
                status=400 if e.code == "BAD_REQUEST" else 404
                if e.code == "NOT_FOUND" else 500)
        finally:
            try:
                self._inflight.get_nowait()
            except asyncio.QueueEmpty:  # pragma: no cover - defensive
                pass

    async def _rspc_ws(self, request: web.Request) -> web.WebSocketResponse:
        ws = web.WebSocketResponse()
        await with_timeout("api.ws.prepare", ws.prepare(request))
        self._websockets.add(ws)
        subscriptions: Dict[Any, Any] = {}
        loop = asyncio.get_running_loop()

        async def ws_emit(payload: dict) -> None:
            # One subscription event to one subscriber, budgeted; a
            # subscriber that vanished mid-emit (connection gone, send
            # budget fired) is unsubscribe racing us, not an engine
            # error. Anything else — an unserializable payload above
            # all — propagates so the supervisor's done-callback
            # records the task_exception instead of silence.
            try:
                await with_timeout("api.ws.send", ws.send_json(payload))
            except (asyncio.TimeoutError, ConnectionError,
                    RuntimeError):
                # RuntimeError: aiohttp's "websocket connection is
                # closing" shape on a half-closed socket.
                pass

        async def handle(msg: dict) -> None:
            mid = msg.get("id")
            mtype = msg.get("type")
            try:
                if mtype in ("query", "mutation"):
                    try:
                        # The ws envelope's optional "tp" field is the
                        # websocket spelling of X-Sdtpu-Trace.
                        with tracing.continue_trace(msg.get("tp")), \
                                tracing.span(f"rpc/{msg['path']}"):
                            result = await self.router.dispatch(
                                msg["path"], msg.get("input"))
                    except asyncio.TimeoutError as e:
                        # A budget fired INSIDE the procedure (p2p/sync
                        # await): the socket is fine — report it, as
                        # distinct from an api.ws.send wedge below.
                        raise RpcError(
                            "TIMEOUT", f"upstream timeout: {e}") from e
                    await with_timeout("api.ws.send", ws.send_json(
                        {"id": mid, "type": "response", "result": result}))
                elif mtype == "subscription":
                    if mid in subscriptions:
                        # Overwriting the map entry would strand the
                        # prior unsub + pump for the server's lifetime
                        # (nothing would ever tear them down).
                        raise RpcError(
                            "BAD_REQUEST",
                            f"duplicate subscription id {mid!r}")
                    # Bounded per-subscription delivery: events go
                    # through the pump's registered api.ws channel and
                    # one supervised drainer (reaped at shutdown) —
                    # not a task per event.
                    pump = WsSubscriptionPump(ws_emit, owner=self._owner)

                    def emit(data, _mid=mid, _pump=pump):
                        # Thread-safe: event bus callbacks may fire
                        # from worker threads; the channel itself is
                        # loop-thread-only. A loop closed mid-shutdown
                        # drops the frame (counted) instead of
                        # crashing the emitting thread.
                        threadctx.call_threadsafe(
                            loop, _pump.offer,
                            {"id": _mid, "type": "event", "data": data})
                    try:
                        unsub = await self.router.subscribe(
                            msg["path"], msg.get("input"), emit)
                    except asyncio.TimeoutError as e:
                        # Same split as the dispatch branch above: a
                        # budget firing INSIDE the handler is not a
                        # send wedge — the client must hear about it.
                        await pump.stop()
                        raise RpcError(
                            "TIMEOUT", f"upstream timeout: {e}") from e
                    except BaseException:
                        # Failed subscribe (unknown path, bad input):
                        # the pump never reaches the subscriptions map,
                        # so reap its drainer here, then re-raise.
                        await pump.stop()
                        raise
                    subscriptions[mid] = (unsub, pump)
                    await with_timeout("api.ws.send", ws.send_json(
                        {"id": mid, "type": "response", "result": None}))
                elif mtype == "subscriptionStop":
                    unsub, pump = subscriptions.pop(mid, (None, None))
                    if unsub:
                        # Same guard as the disconnect teardown: a
                        # raising unsub must not skip the pump reap
                        # (the entry is already out of the map, so
                        # nothing else would ever stop the drainer).
                        try:
                            unsub()
                        except Exception:
                            pass
                    if pump:
                        await pump.stop()
                else:
                    raise RpcError("BAD_REQUEST",
                                   f"unknown frame type {mtype}")
            except asyncio.TimeoutError:
                # An api.ws.send budget fired: the transport itself is
                # wedged — answering over the same stalled socket would
                # just double the wedge (and report a dispatch error
                # that never happened). Drop the frame; the read side
                # or reap tears the connection down.
                pass
            except RpcError as e:
                await with_timeout("api.ws.send", ws.send_json(
                    {"id": mid, "type": "error",
                     "code": e.code, "message": e.message}))
            except Exception as e:  # noqa: BLE001 — protocol boundary
                await with_timeout("api.ws.send", ws.send_json(
                    {"id": mid, "type": "error",
                     "code": "INTERNAL", "message": str(e)}))

        try:
            async for msg in ws:
                if msg.type == WSMsgType.TEXT:
                    try:
                        frame = json.loads(msg.data)
                    except json.JSONDecodeError:
                        continue
                    await handle(frame)
                elif msg.type == WSMsgType.ERROR:
                    break
        finally:
            self._websockets.discard(ws)
            # Cancellation-safe teardown: every unsub runs before the
            # first await (an await inside the loop would die on the
            # first CancelledError and strand later subscriptions'
            # EventBus callbacks for the node's lifetime), then ALL
            # pumps reap under one shield whose inner gather is waited
            # out to completion even while the handler itself is being
            # cancelled (same intent as sync_net._pull's ingester reap).
            for unsub, _pump in subscriptions.values():
                try:
                    unsub()
                except Exception:
                    pass
            if subscriptions:
                stops = asyncio.gather(
                    *(pump.stop() for _, pump in subscriptions.values()),
                    return_exceptions=True)
                cancelled = False
                while not stops.done():
                    try:
                        await asyncio.shield(stops)
                    except asyncio.CancelledError:
                        cancelled = True
                if cancelled:
                    raise asyncio.CancelledError
        return ws

    async def _thumbnail(self, request: web.Request) -> web.Response:
        cas_id = request.match_info["cas_id"]
        if not cas_id.isalnum():
            raise web.HTTPBadRequest()
        p = thumbnail_path(self.node.data_dir, cas_id)
        if not os.path.exists(p):
            raise web.HTTPNotFound()
        return web.FileResponse(p, headers={"Content-Type": "image/webp"})

    def _location_is_local(self, lib, loc) -> bool:
        """A location is local when it belongs to this library instance
        (or predates instance attribution)."""
        if loc["instance_id"] is None:
            return True
        row = lib.db.run("node.instance_pub_by_row",
                         (loc["instance_id"],))
        return row is None or row["pub_id"] == lib.sync.instance

    async def _file_over_p2p(self, request, lib, loc, row
                             ) -> web.StreamResponse:
        """Proxy a remote node's file over the p2p mesh
        (custom_uri/mod.rs files_over_p2p_flag path).

        Rows cross the wire as synced pub_ids (local autoincrement ids
        diverge between nodes); the client's Range maps onto the
        spaceblock ranged transfer; the fetched bytes stream back in
        RANGE_CHUNK pieces so multi-GB files never sit in RAM or block
        the event loop."""
        import tempfile

        node = self.node
        if ("filesOverP2P" not in node.config.features
                or node.p2p is None or node.p2p.networked is None):
            raise web.HTTPNotFound()
        inst = await asyncio.to_thread(
            lib.db.query_one,
            "SELECT * FROM instance WHERE id = ?", (loc["instance_id"],))
        if inst is None or not inst["identity"]:
            raise web.HTTPNotFound()
        from ..p2p.identity import RemoteIdentity

        route = node.p2p.networked._resolve(RemoteIdentity(inst["identity"]))
        if route is None:
            raise web.HTTPNotFound(text="peer offline")

        range_start = range_end = None
        rng = request.headers.get("Range")
        if rng and rng.startswith("bytes="):
            try:
                start_s, _, end_s = rng[len("bytes="):].partition("-")
                range_start = int(start_s) if start_s else 0
                range_end = int(end_s) + 1 if end_s else None
            except ValueError:
                raise web.HTTPBadRequest()

        with tempfile.NamedTemporaryFile(delete=False) as tmp:
            tmp_path = tmp.name
        try:
            ok = await node.p2p.request_file(
                route[0], route[1], str(lib.id), loc["pub_id"],
                row["pub_id"], tmp_path,
                range_start=range_start, range_end=range_end)
            if not ok:
                raise web.HTTPNotFound(text="remote fetch failed")
            name = (row["name"] or "file") + (
                "." + row["extension"] if row["extension"] else "")
            ctype = mimetypes.guess_type(name)[0] or \
                "application/octet-stream"
            got = os.path.getsize(tmp_path)
            headers = {"Content-Type": ctype, "X-Served-Via": "p2p",
                       "Content-Length": str(got),
                       "Accept-Ranges": "bytes"}
            status = 200
            if range_start is not None:
                end_b = range_start + got - 1
                raw_size = row["size_in_bytes_bytes"]
                size_b = (int.from_bytes(raw_size, "big")
                          if raw_size else "*")
                headers["Content-Range"] = \
                    f"bytes {range_start}-{end_b}/{size_b}"
                status = 206
            resp = web.StreamResponse(status=status, headers=headers)
            await with_timeout("api.http.write", resp.prepare(request))
            with await asyncio.to_thread(open, tmp_path, "rb") as f:
                while True:
                    chunk = await asyncio.to_thread(f.read, RANGE_CHUNK)
                    if not chunk:
                        break
                    # Per-chunk budget: stalled clients release the
                    # handler within one window.
                    await with_timeout("api.http.write", resp.write(chunk))
            await with_timeout("api.http.write", resp.write_eof())
            return resp
        finally:
            try:
                os.unlink(tmp_path)
            except OSError:
                pass

    async def _file(self, request: web.Request) -> web.StreamResponse:
        """Original file serving with Range support
        (custom_uri/mod.rs:149-330)."""
        import uuid as uuidlib
        try:
            lib = self.node.libraries.get(
                uuidlib.UUID(request.match_info["library_id"]))
            location_id = int(request.match_info["location_id"])
            file_path_id = int(request.match_info["file_path_id"])
        except (ValueError, KeyError):
            raise web.HTTPBadRequest()
        if lib is None:
            raise web.HTTPNotFound()
        row = await asyncio.to_thread(
            lib.db.query_one,
            "SELECT * FROM file_path WHERE id = ? AND location_id = ?",
            (file_path_id, location_id))
        loc = await asyncio.to_thread(
            lib.db.query_one,
            "SELECT * FROM location WHERE id = ?", (location_id,))
        if row is None or loc is None:
            raise web.HTTPNotFound()
        if not await asyncio.to_thread(self._location_is_local, lib, loc):
            # Remote location: proxy the bytes over p2p when the
            # FilesOverP2P feature is on (custom_uri/mod.rs:149-330
            # files_over_p2p_flag path).
            return await self._file_over_p2p(request, lib, loc, row)
        if not loc["path"]:
            raise web.HTTPNotFound()
        iso = IsolatedPath.from_db_row(
            location_id, bool(row["is_dir"]), row["materialized_path"],
            row["name"] or "", row["extension"] or "")
        full = iso.join_on(loc["path"])
        if not os.path.isfile(full):
            raise web.HTTPNotFound()
        size = os.path.getsize(full)
        ctype = mimetypes.guess_type(full)[0] or "application/octet-stream"

        rng = request.headers.get("Range")
        if rng and rng.startswith("bytes="):
            try:
                start_s, _, end_s = rng[len("bytes="):].partition("-")
                start = int(start_s) if start_s else 0
                end = int(end_s) if end_s else size - 1
            except ValueError:
                raise web.HTTPBadRequest()
            end = min(end, size - 1)
            if start > end or start >= size:
                raise web.HTTPRequestRangeNotSatisfiable()
            resp = web.StreamResponse(
                status=206,
                headers={
                    "Content-Type": ctype,
                    "Content-Range": f"bytes {start}-{end}/{size}",
                    "Content-Length": str(end - start + 1),
                    "Accept-Ranges": "bytes",
                })
            await with_timeout("api.http.write", resp.prepare(request))
            with await asyncio.to_thread(open, full, "rb") as f:
                f.seek(start)
                remaining = end - start + 1
                while remaining > 0:
                    chunk = await asyncio.to_thread(
                        f.read, min(RANGE_CHUNK, remaining))
                    if not chunk:
                        break
                    await with_timeout("api.http.write", resp.write(chunk))
                    remaining -= len(chunk)
            await with_timeout("api.http.write", resp.write_eof())
            return resp
        return web.FileResponse(full, headers={
            "Content-Type": ctype, "Accept-Ranges": "bytes"})


async def serve(data_dir: str, host: str = "127.0.0.1",
                port: int = 8080) -> None:
    """CLI entry: run a node + API server until cancelled."""
    from ..node import Node
    node = Node(data_dir)
    await node.start()
    p2p_port = await node.start_p2p()
    node.p2p.interactive_spacedrop = True  # offers flow through p2p.events
    server = ApiServer(node)
    actual = await server.start(host, port)
    print(f"spacedrive_tpu server listening on {host}:{actual} "
          f"(p2p on {p2p_port})")
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        # Shielded: serve() exits via cancellation (ctrl-C), and the
        # node must still shut down cleanly through the reap.
        await asyncio.shield(server.stop())
        await asyncio.shield(node.shutdown())
        from ..tracing import stop_profiler

        stop_profiler()  # process exit: flush any SDTPU_PROFILE trace


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    args = ap.parse_args()
    asyncio.run(serve(args.data_dir, args.host, args.port))
