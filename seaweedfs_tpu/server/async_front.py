"""Asyncio front for a role's HTTP funnel (ISSUE 12 tentpole, part 2).

The threaded server (httpd.py) spends a thread — stack, scheduler
churn, GIL convoying — per connection; at gateway concurrency the
recv/route/assign/proxy funnel is host-bound long before the disks
are.  This front multiplexes every connection of a role on ONE event
loop: HTTP framing (request parse, body recv, response write) runs on
the loop, handlers execute on a small bounded thread pool (they are
synchronous by design — sqlite, pooled-client hops), and everything
observable is SHARED with the threaded front: the owner HttpServer's
route tables, guard, QoS admission hook, tracing spans, request-id
propagation, requests_in_flight gauge and request_seconds histogram.
`SEAWEEDFS_TPU_ASYNC_FRONT=1` selects it for the filer gateway
(a comma list names other roles); default stays the threaded server.

Handler-facing requests duck-type httpd.Request: `.method`, `.path`,
`.query`, `.headers` (case-insensitive), `.body` (pre-read on the
loop — the recv is the part worth multiplexing), `.json()`,
`.stream_body()`, `.drain()`, and a `._handler.close_connection` shim
for handlers that poison-pill their connection.

SWFS014 polices this file's contract: an `async def` handler here must
never block the loop — time.sleep, sync pooled-client calls, and
un-executor'd file reads belong on the pool.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import threading
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

from .. import tracing
from ..util.request_id import HEADER as _RID_HEADER
from ..util.request_id import ensure_request_id
from .httpd import normalize_payload

_MAX_HEADER = 64 << 10


class _Headers:
    """Case-insensitive header map preserving original spellings
    (the email.Message surface the handlers actually use)."""

    __slots__ = ("_d",)

    def __init__(self):
        self._d: dict = {}

    def add(self, k: str, v: str) -> None:
        lk = k.lower()
        if lk in self._d:
            # duplicate headers: comma-join (RFC 9110 §5.2), matching
            # what handlers would see from email.Message.get
            self._d[lk] = (self._d[lk][0], self._d[lk][1] + ", " + v)
        else:
            self._d[lk] = (k, v)

    def get(self, k: str, default=None):
        t = self._d.get(k.lower())
        return t[1] if t is not None else default

    def __getitem__(self, k: str):
        t = self._d.get(k.lower())
        if t is None:
            raise KeyError(k)
        return t[1]

    def __contains__(self, k) -> bool:
        return isinstance(k, str) and k.lower() in self._d

    def __iter__(self):
        for orig, _v in self._d.values():
            yield orig

    def keys(self):
        return [orig for orig, _v in self._d.values()]

    def values(self):
        return [v for _o, v in self._d.values()]

    def items(self):
        return [(orig, v) for orig, v in self._d.values()]


class _HandlerShim:
    """Handlers poke `req._handler.close_connection` to poison-pill a
    connection (mid-stream failure injection); the front honors it."""

    __slots__ = ("close_connection",)

    def __init__(self):
        self.close_connection = False


class AsyncRequest:
    """httpd.Request duck-type over a fully-received async request."""

    __slots__ = ("method", "path", "remote_ip", "headers", "_raw_query",
                 "_query", "_body", "_handler")

    def __init__(self, method: str, target: str, headers: _Headers,
                 body: bytes, remote_ip: str):
        path, _, query = target.partition("?")
        if path[:4] == "http" and "://" in path[:8]:
            rest = path.split("://", 1)[1]
            slash = rest.find("/")
            path = rest[slash:] if slash >= 0 else "/"
        self.method = method
        self.path = path
        self.remote_ip = remote_ip
        self.headers = headers
        self._raw_query = query
        self._query = None
        self._body = body
        self._handler = _HandlerShim()

    @property
    def query(self) -> dict:
        if self._query is None:
            self._query = {
                k: v[0] for k, v in urllib.parse.parse_qs(
                    self._raw_query, keep_blank_values=True).items()} \
                if self._raw_query else {}
        return self._query

    @property
    def body(self) -> bytes:
        return self._body

    def json(self) -> dict:
        return json.loads(self._body or b"{}")

    def stream_body(self, chunk_size: int = 4 << 20):
        # the loop already received the body; yield it once (the same
        # fallback httpd.Request.stream_body takes for buffered
        # bodies) — handlers that stream see identical semantics
        if self._body:
            yield self._body

    def drain(self, max_drain: int = 64 << 20) -> None:
        pass   # nothing unread: the loop consumed the framing


class AsyncFront:
    """One event loop + bounded handler pool serving an HttpServer's
    routes (shared guard/admission/metrics/tracing)."""

    def __init__(self, owner, ssl_context=None):
        self.owner = owner
        self.ssl_context = ssl_context
        self._loop: "asyncio.AbstractEventLoop | None" = None
        self._server = None
        self._thread: "threading.Thread | None" = None
        self._transports: set = set()
        try:
            workers = max(1, int(os.environ.get(
                "SEAWEEDFS_TPU_ASYNC_WORKERS", "") or 16))
        except ValueError:
            workers = 16
        self._pool = ThreadPoolExecutor(
            max_workers=workers,
            thread_name_prefix=f"async-{owner.role or 'front'}")
        self._ready = threading.Event()

    # -- lifecycle ------------------------------------------------------

    def start(self, sock) -> None:
        self._thread = threading.Thread(
            target=self._run, args=(sock,), daemon=True)
        self._thread.start()
        self._ready.wait(timeout=10)

    def _run(self, sock) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop

        async def _serve():
            sock.setblocking(False)
            self._server = await asyncio.start_server(
                self._handle_conn, sock=sock, ssl=self.ssl_context,
                limit=_MAX_HEADER)
            self._ready.set()

        loop.run_until_complete(_serve())
        try:
            loop.run_forever()
        finally:
            try:
                loop.run_until_complete(loop.shutdown_asyncgens())
            except RuntimeError:
                pass
            loop.close()

    def stop(self) -> None:
        loop = self._loop
        if loop is None:
            return

        def _shutdown():
            if self._server is not None:
                self._server.close()
            for tr in list(self._transports):
                try:
                    tr.close()
                except (OSError, RuntimeError):
                    pass   # teardown: transport already dead
            loop.stop()

        try:
            loop.call_soon_threadsafe(_shutdown)
        except RuntimeError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=10)
        self._pool.shutdown(wait=False)

    # -- connection handling --------------------------------------------

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        self._transports.add(writer.transport)
        peer = writer.get_extra_info("peername") or ("", 0)
        remote_ip = peer[0] if isinstance(peer, tuple) else ""
        try:
            while True:
                try:
                    head = await reader.readuntil(b"\r\n\r\n")
                except (asyncio.IncompleteReadError,
                        asyncio.LimitOverrunError):
                    return
                lines = head[:-4].decode("latin-1").split("\r\n")
                try:
                    method, target, _version = lines[0].split(" ", 2)
                except ValueError:
                    return
                headers = _Headers()
                for line in lines[1:]:
                    k, sep, v = line.partition(":")
                    if sep:
                        headers.add(k.strip(), v.strip())
                try:
                    body = await self._read_body(reader, headers)
                except (ValueError, asyncio.IncompleteReadError):
                    return
                req = AsyncRequest(method, target, headers, body,
                                   remote_ip)
                keep = await self._dispatch(req, writer)
                want_close = (
                    not keep or req._handler.close_connection or
                    (headers.get("Connection") or "").lower() ==
                    "close")
                if want_close:
                    return
        except (ConnectionError, TimeoutError, OSError):
            pass
        finally:
            self._transports.discard(writer.transport)
            try:
                writer.close()
            except (OSError, RuntimeError):
                pass   # teardown: transport already dead

    async def _read_body(self, reader: asyncio.StreamReader,
                         headers: _Headers) -> bytes:
        te = (headers.get("Transfer-Encoding") or "").lower()
        if "chunked" in te:
            out = bytearray()
            while True:
                line = await reader.readline()
                size = int(line.split(b";")[0], 16)   # ValueError: up
                if size == 0:
                    while True:
                        t = await reader.readline()
                        if t in (b"\r\n", b"\n", b""):
                            break
                    break
                out += await reader.readexactly(size)
                await reader.readexactly(2)
            return bytes(out)
        length = int(headers.get("Content-Length") or 0)
        if length:
            return await reader.readexactly(length)
        return b""

    # -- dispatch -------------------------------------------------------

    def _sync_process(self, req: AsyncRequest):
        """Everything between framing and response write, on a pool
        thread: request-id adoption, server span, QoS admission,
        guard, route — the same ladder as the threaded dispatcher.
        Returns the flight-recorder material (verdict, pool-thread
        CPU, deadline doc, stage summary, notes) alongside, since the
        contextvars it rides live on THIS thread, not the loop's."""
        import time as _time

        from .. import profiling as _prof
        outer = self.owner
        rid = ensure_request_id(req.headers.get(_RID_HEADER, ""))
        # deadline plane (util/deadline): same ingress contract as the
        # threaded front — adopt (or clear a stale binding on this
        # reused pool thread) before any work, 504 an expired budget
        # before admission/guard/route spend anything; the
        # maintenance plane is exempt from the operator DEFAULT
        # (explicit budgets still honored)
        from ..util import deadline as _dl
        dl = _dl.adopt(req.headers.get(_dl.HEADER),
                       site=outer.role or "server",
                       allow_default=not req.path.startswith(
                           ("/admin/", "/debug/")))
        flight_on = _prof.recorder_enabled()
        if flight_on:
            _prof.arm_flight_notes()
        # sampled CPU attribution, same rule as the threaded front:
        # deadline-carrying requests always pay the thread-CPU clock,
        # budget-less ones every Nth — and the k<=0 kill switch
        # gates both (cpu_attr_front)
        _, parent_span = tracing.parse_traceparent(
            req.headers.get(tracing.HEADER, ""))
        cpu0 = _time.thread_time() \
            if _prof.cpu_attr_front(
                dl is not None or bool(parent_span)) else None
        verdict = "ok"
        route = outer.routes.get((req.method, req.path))
        if route is None and outer.prefix_routes:
            route = outer._prefix_route(req.method, req.path)
        sp = tracing.start_span(
            f"{req.method} {req.path}", role=outer.role,
            parent=parent_span, trace_id=rid)
        if dl is not None:
            sp.set("deadlineMs", int(dl.remaining() * 1e3))
        qos_release = None
        try:
            throttled = None
            if dl is not None and dl.expired():
                throttled = _dl.expired_response(
                    f"{outer.role or 'server'}.ingress")
                verdict = "deadline"
            if throttled is None and outer.admission is not None:
                throttled, qos_release = outer.admission(req)
                if throttled is not None:
                    verdict = "shed"
            if throttled is not None:
                status, payload = throttled
            elif (denied := outer.guard(req)
                  if outer.guard else None) is not None:
                status, payload = denied
            elif route is not None:
                status, payload = route(req)
            elif outer.fallback is not None:
                status, payload = outer.fallback(req)
            else:
                status, payload = 404, {"error": "not found"}
        except _dl.DeadlineExceeded as e:
            # budget died mid-handler: 504, matching the threaded front
            status, payload = _dl.handler_exceeded_response()
            verdict = "deadline"
            sp.set_error(e)
        except Exception as e:  # noqa: BLE001 — server must answer
            status, payload = 500, {"error": str(e)}
            verdict = "error"
            sp.set_error(e)
        # cpu rides OUTSIDE the flight dict: the request_cpu_seconds
        # histogram must not vanish when the recorder is disarmed
        # (the threaded front emits it unconditionally).  The summary
        # drain is likewise unconditional — a finished track's
        # summary left behind while disarmed would be attributed to a
        # later request on this reused pool thread after re-arming.
        cpu = (_time.thread_time() - cpu0) if cpu0 is not None \
            else None
        summary = _prof.take_last_summary()
        flight = None
        if flight_on:
            dl_doc = None
            if dl is not None:
                dl_doc = {"budgetMs": int(dl.budget * 1e3),
                          "remainingMs": int(dl.remaining() * 1e3)}
            flight = {"verdict": verdict,
                      "deadline": dl_doc,
                      "stages": summary,
                      "notes": _prof.take_flight_notes()}
        return status, payload, sp, rid, qos_release, cpu, flight

    async def _dispatch(self, req: AsyncRequest,
                        writer: asyncio.StreamWriter) -> bool:
        """Returns True to keep the connection alive."""
        outer = self.owner
        loop = asyncio.get_running_loop()
        with outer._inflight_lock:
            outer._inflight += 1
            inflight = outer._inflight
        if outer.metrics is not None:
            outer.metrics.gauge_set(
                "requests_in_flight", inflight,
                help_text="requests currently being handled")
        sp = None
        status = 0
        sent = 0
        qos_release = None
        stream_body = None
        cpu = None
        flight = None
        keep = True
        try:
            status, payload, sp, rid, qos_release, cpu, flight = \
                await loop.run_in_executor(self._pool,
                                           self._sync_process, req)
            body, ctype, extra_headers = normalize_payload(payload)
            reason = http.client.responses.get(status, "")
            head = [f"HTTP/1.1 {status} {reason}",
                    f"Content-Type: {ctype}",
                    f"{_RID_HEADER}: {rid}"]
            for hk, hv in extra_headers.items():
                head.append(f"{hk}: {hv}")
            if hasattr(body, "read"):
                stream_body = body
                sent = int(extra_headers.get("Content-Length") or 0)
                # file-like bodies must carry Content-Length in
                # extra_headers (the threaded front's rule; these
                # responses are never chunked)
                writer.write(("\r\n".join(head) + "\r\n\r\n")
                             .encode("latin-1"))
                if req.method != "HEAD":
                    while True:
                        chunk = await loop.run_in_executor(
                            self._pool, stream_body.read, 1 << 20)
                        if not chunk:
                            break
                        writer.write(chunk)
                        await writer.drain()
                await writer.drain()
                return keep
            sent = len(body)
            if "Content-Length" not in extra_headers:
                head.append(f"Content-Length: {len(body)}")
            writer.write(("\r\n".join(head) + "\r\n\r\n")
                         .encode("latin-1"))
            if req.method != "HEAD":
                writer.write(body)
            await writer.drain()
            return keep
        except (ConnectionError, TimeoutError, OSError):
            keep = False
            return False
        finally:
            if stream_body is not None:
                try:
                    stream_body.close()
                except OSError:
                    pass
            if qos_release is not None:
                try:
                    qos_release()
                except Exception as e:  # noqa: BLE001 — accounting
                    # must never break a reply
                    from ..util import wlog
                    wlog.warning("qos release failed: %s", e,
                                 component="qos")
            if sp is not None:
                outer.close_server_span(sp, req, status, cpu, sent)
            with outer._inflight_lock:
                outer._inflight -= 1
                inflight = outer._inflight
            if outer.metrics is not None:
                outer.metrics.gauge_set("requests_in_flight",
                                        inflight)
                if sp is not None:
                    outer.metrics.histogram_observe(
                        "request_seconds", sp.duration,
                        help_text="HTTP request handling latency",
                        method=req.method, code=str(status))
                    if cpu is not None:
                        from .. import profiling as _prof
                        outer.metrics.histogram_observe(
                            "request_cpu_seconds", cpu,
                            buckets=_prof.STAGE_BUCKETS,
                            help_text="handler-thread CPU per request"
                                      " (thread_time, sampled — see "
                                      "SEAWEEDFS_TPU_CPU_SAMPLE); "
                                      "request_seconds minus this is "
                                      "GIL/lock/IO wait",
                            method=req.method, code=str(status))
            if flight is not None and sp is not None:
                # after sp.finish(): the capture's span-tree pull must
                # see the server span in the ring.  The wall covers
                # the response write (sp.duration does); the CPU is
                # the pool thread's handler share — the loop's framing
                # cost is the front's, not this request's.
                from .. import profiling as _prof
                try:
                    _prof.flight_recorder().observe(
                        role=outer.role or "server",
                        method=req.method, path=req.path,
                        status=status, wall_s=sp.duration,
                        cpu_s=cpu,
                        verdict=flight["verdict"], trace_id=rid,
                        deadline=flight["deadline"],
                        stages=flight["stages"],
                        notes=flight["notes"])
                except Exception as e:  # noqa: BLE001 —
                    # observability must never break a reply
                    from ..util import wlog
                    wlog.warning("flight capture failed: %s", e,
                                 component="profiling")
