"""Minimal threaded HTTP server + JSON routing used by all roles.

Python-idiomatic stand-in for the reference's mux+gRPC server plumbing
(weed/server/*): handlers are (method, path-prefix) routes returning
(status, payload).  Bodies are JSON for control endpoints and raw bytes
for the data path.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable


class Request:
    def __init__(self, handler: BaseHTTPRequestHandler):
        # hot-path parse: one partition instead of a full urlparse.
        # Targets are origin-form (RFC 9112 §3.2.1) except the
        # absolute-form a forward proxy may send (§3.2.2 requires
        # accepting it) — strip scheme+authority for that rare shape
        path, _, query = handler.path.partition("?")
        if path[:4] == "http" and "://" in path[:8]:
            rest = path.split("://", 1)[1]
            slash = rest.find("/")
            path = rest[slash:] if slash >= 0 else "/"
        self.method = handler.command
        self.path = path
        self.remote_ip = handler.client_address[0]
        self._raw_query = query
        self._query: "dict[str, str] | None" = None
        self.headers = handler.headers
        self._handler = handler
        self._body: bytes | None = None

    @property
    def query(self) -> "dict[str, str]":
        """Parsed query params, lazily: the hot data path (needle
        POSTs, filer PUTs) usually carries none, and parse_qs per
        request was measurable funnel overhead.  keep_blank_values:
        S3-style marker params (?uploads=, ?delete=) must survive
        parsing."""
        if self._query is None:
            self._query = {
                k: v[0] for k, v in urllib.parse.parse_qs(
                    self._raw_query, keep_blank_values=True).items()} \
                if self._raw_query else {}
        return self._query

    @property
    def body(self) -> bytes:
        if self._body is None:
            te = (self.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                # RFC 9112 §7.1 — curl -T and many WebDAV clients
                # stream uploads chunked with no Content-Length
                self._body = self._read_chunked()
            else:
                length = int(self.headers.get("Content-Length") or 0)
                self._body = self._handler.rfile.read(length) \
                    if length else b""
        return self._body

    def _read_chunked(self) -> bytes:
        rfile = self._handler.rfile
        out = bytearray()
        while True:
            size_line = rfile.readline(1024).strip()
            try:
                size = int(size_line.split(b";")[0], 16)
            except ValueError:
                # malformed framing: the stream position is unknown —
                # poison-proof the connection by closing it after this
                # response
                self._handler.close_connection = True
                break
            if size == 0:
                # drain optional trailers up to the blank line
                while True:
                    line = rfile.readline(1024)
                    if line in (b"\r\n", b"\n", b""):
                        break
                break
            out += rfile.read(size)
            rfile.readline(8)  # CRLF after each chunk
        return bytes(out)

    def json(self) -> dict:
        return json.loads(self.body or b"{}")

    def stream_body(self, chunk_size: int = 4 << 20):
        """Yield the request body in chunks without buffering it whole
        (the bulk-data receive path: a 30GB volume file must stream to
        disk, volume_server.proto:69 CopyFile / ReceiveFile), for both
        Content-Length and chunked framing.  After clean exhaustion
        `self.body` is b"" so the dispatcher's drain is a no-op; while
        streaming, the connection is marked close-on-response so a
        handler that fails MID-stream (ENOSPC) can never leave unread
        body bytes to be parsed as the next request on a keep-alive
        connection.  Mutually exclusive with touching `.body` first."""
        if self._body is not None:
            # body already buffered (small request): yield it once
            if self._body:
                yield self._body
            return
        self._body = b""
        # abandoned-generator safety: assume poisoned until proven
        # fully drained
        self._handler.close_connection = True
        te = (self.headers.get("Transfer-Encoding") or "").lower()
        if "chunked" in te:
            yield from self._stream_chunked(chunk_size)
            return
        length = int(self.headers.get("Content-Length") or 0)
        remaining = length
        while remaining > 0:
            chunk = self._handler.rfile.read(min(chunk_size, remaining))
            if not chunk:
                raise IOError(
                    f"short body: {remaining} of {length} bytes missing")
            remaining -= len(chunk)
            yield chunk
        self._handler.close_connection = False

    def _stream_chunked(self, chunk_size: int):
        """Chunk-at-a-time RFC 9112 §7.1 parser: unlike _read_chunked
        (small control bodies) nothing is accumulated, so chunked bulk
        uploads (`curl -T`) stream with bounded memory too."""
        rfile = self._handler.rfile
        while True:
            size_line = rfile.readline(1024).strip()
            try:
                size = int(size_line.split(b";")[0], 16)
            except ValueError:
                raise IOError(f"malformed chunk framing: "
                              f"{size_line[:64]!r}") from None
            if size == 0:
                while True:  # drain optional trailers
                    line = rfile.readline(1024)
                    if line in (b"\r\n", b"\n", b""):
                        break
                break
            remaining = size
            while remaining > 0:
                piece = rfile.read(min(chunk_size, remaining))
                if not piece:
                    raise IOError("short chunked body")
                remaining -= len(piece)
                yield piece
            rfile.readline(8)  # CRLF after each chunk
        self._handler.close_connection = False

    def drain(self, max_drain: int = 64 << 20) -> None:
        """Discard any unread body with bounded memory.  Oversized or
        chunked unread bodies are not read at all — the connection is
        closed instead (cheaper than consuming 30GB to keep one
        keep-alive socket)."""
        if self._body is not None:
            return
        te = (self.headers.get("Transfer-Encoding") or "").lower()
        length = int(self.headers.get("Content-Length") or 0)
        if "chunked" in te or length > max_drain:
            self._body = b""
            self._handler.close_connection = True
            return
        remaining = length
        while remaining > 0:
            chunk = self._handler.rfile.read(min(1 << 20, remaining))
            if not chunk:
                self._handler.close_connection = True
                break
            remaining -= len(chunk)
        self._body = b""


class CopyHalves:
    """Where socket-to-file copies (copy_to_file) spent their time,
    each half apart: `socket` is the reads of the sender's bytes,
    `file` the writes that land them, each [calls, busy seconds on the
    wall clock, seconds on the thread's CPU, bytes].  Copies that share
    one add up (a pull's `.dat` and `.idx`): `start` is the first
    copy's first read on time.time(), `wall` their loops together."""

    __slots__ = ("start", "wall", "socket", "file")

    def __init__(self):
        self.start = 0.0
        self.wall = 0.0
        self.socket = [0, 0.0, 0.0, 0]
        self.file = [0, 0.0, 0.0, 0]

    def spans(self, stage: str) -> "list[dict]":
        """`<stage>.socket` and `<stage>.file` as tracing.emit_span_batch
        items, under the caller's current span: the stage-span form of
        the EC pipeline's `encode.read` / `.codec` / `.write`."""
        return [{"name": f"{stage}.{half}", "start": self.start,
                 "duration": self.wall,
                 "attrs": {"calls": calls, "busySeconds": round(busy, 6),
                           "cpuSeconds": round(cpu, 6), "bytes": n}}
                for half, (calls, busy, cpu, n) in
                (("socket", self.socket), ("file", self.file))]


def copy_to_file(chunks, f, halves: CopyHalves, each=None) -> int:
    """Write every chunk `chunks` yields (an iterator that reads a
    socket: stream_body, a response's reads) to the open file `f`, and
    add to `halves` what each half took: the iterator's next() is the
    socket half, f.write and then `each(chunk)` (a running CRC) the file
    half.  Four clock reads a chunk.  Returns the bytes copied; emits
    no span, so a caller with no span to hang them under leaves none."""
    sock, fil = halves.socket, halves.file
    if not halves.start:
        halves.start = time.time()
    t0 = t = time.perf_counter()
    c = time.thread_time()
    copied = 0
    it = iter(chunks)
    while True:
        chunk = next(it, None)
        t1, c1 = time.perf_counter(), time.thread_time()
        sock[1] += t1 - t
        sock[2] += c1 - c
        if chunk is None:
            break
        f.write(chunk)
        if each is not None:
            each(chunk)
        t, c = time.perf_counter(), time.thread_time()
        n = len(chunk)
        copied += n
        sock[0] += 1
        sock[3] += n
        fil[0] += 1
        fil[1] += t - t1
        fil[2] += c - c1
        fil[3] += n
    halves.wall += t1 - t0
    return copied


Route = Callable[[Request], "tuple[int, object]"]


def normalize_payload(payload) -> "tuple[object, str, dict]":
    """One payload contract for both server fronts (threaded
    dispatcher below, async_front.py): handlers return dict/list
    (json), bytes, str, a (body, headers-dict) or (body, ctype) tuple,
    or a file-like body inside either tuple form.  Returns
    (body_or_stream, content_type, extra_headers)."""
    if isinstance(payload, (dict, list)):
        return json.dumps(payload).encode(), "application/json", {}
    if isinstance(payload, tuple):
        body, second = payload
        if isinstance(second, dict):
            extra = dict(second)
            ctype = extra.pop("Content-Type",
                              "application/octet-stream")
            return body, ctype, extra
        return body, second, {}
    body = payload if isinstance(payload, bytes) else \
        str(payload).encode()
    return body, "application/octet-stream", {}


def async_front_roles() -> "set[str]":
    """Roles served by the asyncio front (SEAWEEDFS_TPU_ASYNC_FRONT):
    "1"/"true" selects the filer gateway (the GIL-bound recv/route/
    assign/proxy funnel the front exists for); a comma list names
    roles explicitly (e.g. "filer,s3").  Empty/0 keeps every role on
    the threaded server."""
    import os
    v = os.environ.get("SEAWEEDFS_TPU_ASYNC_FRONT", "").strip().lower()
    if v in ("", "0", "false"):
        return set()
    if v in ("1", "true"):
        return {"filer"}
    return {r.strip() for r in v.split(",") if r.strip()}


class FileSlice:
    """A file-like over [current position, current position + size) of
    an open file, for streaming byte-range responses; closes the
    underlying file with it."""

    def __init__(self, f, size: int):
        self._f = f
        self._remaining = max(size, 0)

    def read(self, n: int = -1) -> bytes:
        if self._remaining <= 0:
            return b""
        if n < 0 or n > self._remaining:
            n = self._remaining
        chunk = self._f.read(n)
        self._remaining -= len(chunk)
        if not chunk:
            self._remaining = 0
        return chunk

    def close(self) -> None:
        self._f.close()


class HttpServer:
    """Routes: exact-path dict + prefix handlers + fallback.

    `reuse_port=True` binds with SO_REUSEPORT so N sibling processes
    can share one listener (the filer's pre-fork worker mode: the
    kernel distributes connections across the workers' accept
    queues — one gateway address, N GILs)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 reuse_port: bool = False):
        self.routes: dict[tuple[str, str], Route] = {}
        # pre-parsed prefix table, compiled at registration: method ->
        # [(prefix, handler)] longest-first.  Role servers used to
        # re-match their path prefixes inside the fallback on every
        # request; hot-path dispatch now resolves exact -> prefix ->
        # fallback from tables built once at boot.
        self.prefix_routes: dict[str, list] = {}
        self.quiet_routes: set[tuple[str, str]] = set()  # route(quiet=)
        self.job_routes: set[tuple[str, str]] = set()    # route(job=)
        self.fallback: Route | None = None
        # optional auth hook (security/guard.go Guard): returns None to
        # continue or a (status, payload) response to short-circuit
        self.guard: "Callable[[Request], tuple[int, object] | None] | None" \
            = None
        # optional QoS admission hook (qos.install): called before the
        # guard, returns (deny_response | None, release | None) — the
        # deny response carries Retry-After via the (body, headers)
        # payload form; release (in-flight byte accounting) runs when
        # the request finishes, success or failure
        self.admission: "Callable[[Request], tuple] | None" = None
        # observability hooks, set by the owning role server: `role`
        # labels this listener's server spans (tracing.py), `metrics`
        # receives the uniform request_seconds histogram (stats.py) —
        # one middleware, every role (master/volume/filer/s3 alike)
        self.role: str = ""
        self.metrics = None
        # in-flight request count for the cluster.top live view: the
        # gauge that distinguishes "idle" from "every handler thread
        # parked on a slow disk" at a glance
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        # per-(method, code) pre-resolved request histogram observers
        # (stats.Metrics.observer, ROADMAP 1d): the middleware below
        # observes two histograms on EVERY request, and the label-set
        # space is tiny (~methods x codes) — resolve each cell once
        self._req_obs: dict = {}
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # small request/response pairs (1KB needles) must not sit
            # in Nagle's 40ms window behind delayed ACKs
            disable_nagle_algorithm = True

            def _dispatch(self):
                req = Request(self)
                # request-id propagation (util/request_id): adopt the
                # caller's X-Request-ID or mint one at this edge; the
                # contextvar follows this handler thread so outbound
                # hops and log lines inherit it
                from ..util.request_id import HEADER as _RID_HEADER
                from ..util.request_id import ensure_request_id
                from .. import tracing
                from ..util import deadline as _dl
                rid = ensure_request_id(
                    req.headers.get(_RID_HEADER, ""))
                # deadline plane (util/deadline): adopt the caller's
                # remaining budget (or the operator default) BEFORE
                # anything spends time on this request; the adopt
                # also clears any stale deadline this reused handler
                # thread carried from its previous request.  The
                # maintenance plane only ever runs under an EXPLICIT
                # budget — a tenant-facing default must not 504 a
                # multi-minute volume copy or EC rebuild mid-pull.
                dl = _dl.adopt(req.headers.get(_dl.HEADER),
                               site=outer.role or "server",
                               allow_default=not req.path.startswith(
                                   ("/admin/", "/debug/")))
                # flight recorder (profiling.py): arm the per-request
                # notes dict so hedge/QoS/plane verdicts down the
                # handler chain have somewhere to land, and sample
                # this thread's CPU clock — wall − cpu at the end is
                # the request's GIL/lock/syscall wait.  The clock is
                # a trapped syscall on sandboxed kernels, so only
                # deadline-carrying and every-Nth budget-less
                # requests pay it (cpu_sample_every)
                from .. import profiling as _prof
                flight_on = _prof.recorder_enabled()
                if flight_on:
                    _prof.arm_flight_notes()
                # server span: trace id = request id, parent from the
                # caller's X-Trace-Parent (tracing.py); every role's
                # handler is wrapped by this one middleware.  A
                # request that carries a trace parent is one somebody
                # will read the trace of, so it pays the clock too:
                # its span says how much of its wall was this thread's
                # CPU (the receiver's half of a bulk push)
                _, parent_span = tracing.parse_traceparent(
                    req.headers.get(tracing.HEADER, ""))
                cpu0 = time.thread_time() \
                    if _prof.cpu_attr_front(
                        dl is not None or bool(parent_span)) else None
                verdict = "ok"
                route = outer.routes.get((req.method, req.path))
                if route is None and outer.prefix_routes:
                    route = outer._prefix_route(req.method, req.path)
                sp = tracing.start_span(
                    f"{req.method} {req.path}", role=outer.role,
                    parent=parent_span, trace_id=rid,
                    job=(req.method, req.path) in outer.job_routes)
                if dl is not None:
                    sp.set("deadlineMs", int(dl.remaining() * 1e3))
                status = 0
                sent = 0                # response body bytes
                qos_release = None
                stream_cleanup = None   # file-like response body
                with outer._inflight_lock:
                    outer._inflight += 1
                    inflight = outer._inflight
                if outer.metrics is not None:
                    outer.metrics.gauge_set(
                        "requests_in_flight", inflight,
                        help_text="requests currently being handled")
                try:
                    # the span (and request_seconds) covers handler
                    # execution AND the response-body write: for the
                    # bulk serve paths (FileSlice sendfile) the write
                    # IS the dominant cost, and closing the span at
                    # handler return would record a multi-second
                    # stream as ~0ms
                    try:
                        # expired budget: 504 + Retry-After BEFORE
                        # admission spends a rate token, the guard
                        # verifies anything, or the handler queues —
                        # work the client already abandoned is shed
                        # at the cheapest possible point
                        throttled = None
                        if dl is not None and dl.expired():
                            throttled = _dl.expired_response(
                                f"{outer.role or 'server'}.ingress")
                            verdict = "deadline"
                        # QoS admission next (qos.py): an over-limit
                        # tenant is rejected with 503 + Retry-After
                        # BEFORE auth or routing spends anything on it
                        if throttled is None and \
                                outer.admission is not None:
                            throttled, qos_release = \
                                outer.admission(req)
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
                            status, payload = 404, \
                                {"error": "not found"}
                    except _dl.DeadlineExceeded as e:
                        # budget died mid-handler (an outbound hop's
                        # io_timeout raised): the honest status is
                        # 504, not a generic 500
                        status, payload = \
                            _dl.handler_exceeded_response()
                        verdict = "deadline"
                        sp.set_error(e)
                    except Exception as e:  # noqa: BLE001 — server
                        # must answer
                        status, payload = 500, {"error": str(e)}
                        verdict = "error"
                        sp.set_error(e)
                    # drain any unread request body: a handler that
                    # ignores its body (e.g. PROPFIND's XML) would
                    # otherwise leave the bytes in the keep-alive
                    # stream to be parsed as the NEXT request line,
                    # poisoning the connection.  Bounded: an unread
                    # 30GB upload (rejected by the guard or a 400)
                    # closes the connection instead of buffering —
                    # the drain must never re-introduce the
                    # whole-body OOM the streaming path exists to
                    # avoid.
                    try:
                        req.drain()
                    except Exception:  # noqa: BLE001 — close instead
                        self.close_connection = True
                    body, ctype, extra_headers = \
                        normalize_payload(payload)
                    if hasattr(body, "read"):
                        # register for the OUTER finally: a header
                        # write dying on a reset connection would
                        # otherwise skip the stream branch's own
                        # close, leaking the body's resources (fd,
                        # QoS in-flight bytes riding close()) —
                        # close() is idempotent on every body type
                        stream_cleanup = body
                    self.send_response(status)
                    self.send_header("Content-Type", ctype)
                    self.send_header(_RID_HEADER, rid)
                    for hk, hv in extra_headers.items():
                        self.send_header(hk, hv)
                    if hasattr(body, "read"):
                        # file-like payload: stream without buffering
                        # (the bulk-data serve path).  Content-Length
                        # must be in extra_headers — these responses
                        # are never chunked.
                        sent = int(extra_headers.get(
                            "Content-Length") or 0)
                        self.end_headers()
                        try:
                            if req.method == "HEAD":
                                return
                            # sendfile(2) fast path for FileSlice
                            # needle reads: zero-copy kernel transfer
                            # from the .dat fd (the RDMA-sidecar
                            # idea's in-server sibling;
                            # socket.sendfile falls back to a send
                            # loop under TLS).  No mid-stream
                            # fallback: a partial sendfile that then
                            # re-sent bytes would corrupt the
                            # response, so errors close the
                            # connection instead.
                            f = getattr(body, "_f", None)
                            count = getattr(body, "_remaining", 0)
                            if f is not None and count > 0 and \
                                    hasattr(f, "fileno"):
                                try:
                                    self.wfile.flush()
                                    # offset defaults to 0, NOT the
                                    # file position — ranged needle
                                    # reads start mid-.dat
                                    self.connection.sendfile(
                                        f, offset=f.tell(),
                                        count=count)
                                except (OSError, ValueError):
                                    self.close_connection = True
                                return
                            while True:
                                chunk = body.read(1 << 20)
                                if not chunk:
                                    break
                                self.wfile.write(chunk)
                        finally:
                            body.close()
                        return
                    if "Content-Length" not in extra_headers:
                        self.send_header("Content-Length",
                                         str(len(body)))
                    self.end_headers()
                    sent = len(body)
                    if req.method != "HEAD":
                        self.wfile.write(body)
                finally:
                    if stream_cleanup is not None:
                        try:
                            stream_cleanup.close()
                        except OSError:
                            pass   # cleanup must never break a reply
                    if qos_release is not None:
                        try:
                            qos_release()
                        except Exception as e:  # noqa: BLE001 —
                            # accounting must never break a reply
                            from ..util import wlog
                            wlog.warning(
                                "qos release failed: %s", e,
                                component="qos")
                    # this thread's CPU for the whole request —
                    # handler AND response write (the streamed-body
                    # paths run above on this same thread); None when
                    # this request didn't draw the attribution sample
                    cpu = (time.thread_time() - cpu0) \
                        if cpu0 is not None else None
                    outer.close_server_span(sp, req, status, cpu, sent)
                    with outer._inflight_lock:
                        outer._inflight -= 1
                        inflight = outer._inflight
                    if outer.metrics is not None:
                        outer.metrics.gauge_set(
                            "requests_in_flight", inflight)
                        cell = (req.method, status)
                        obs = outer._req_obs.get(cell)
                        if obs is None:
                            obs = outer._req_obs[cell] = (
                                outer.metrics.observer(
                                    "request_seconds",
                                    help_text="HTTP request handling "
                                              "latency",
                                    method=req.method,
                                    code=str(status)),
                                outer.metrics.observer(
                                    "request_cpu_seconds",
                                    buckets=_prof.STAGE_BUCKETS,
                                    help_text="handler-thread CPU per "
                                              "request (thread_time, "
                                              "sampled — see SEAWEED"
                                              "FS_TPU_CPU_SAMPLE); "
                                              "request_seconds minus "
                                              "this is GIL/lock/IO "
                                              "wait",
                                    method=req.method,
                                    code=str(status)))
                        obs[0](sp.duration)
                        if cpu is not None:
                            obs[1](cpu)
                    # ALWAYS drain the finished-track summary: tracks
                    # run whether or not the recorder is armed, and a
                    # summary left behind while disarmed would be
                    # attributed to a later request on this reused
                    # thread after re-arming
                    summary = _prof.take_last_summary()
                    if flight_on:
                        # AFTER sp.finish(): the capture pulls this
                        # trace's spans from the ring, and the server
                        # span must be among them
                        dl_doc = None
                        if dl is not None:
                            dl_doc = {
                                "budgetMs": int(dl.budget * 1e3),
                                "remainingMs":
                                    int(dl.remaining() * 1e3)}
                        try:
                            _prof.flight_recorder().observe(
                                role=outer.role or "server",
                                method=req.method, path=req.path,
                                status=status, wall_s=sp.duration,
                                cpu_s=cpu, verdict=verdict,
                                trace_id=rid, deadline=dl_doc,
                                stages=summary,
                                notes=_prof.take_flight_notes())
                        except Exception as e:  # noqa: BLE001 —
                            # observability must never break a reply
                            from ..util import wlog
                            wlog.warning(
                                "flight capture failed: %s", e,
                                component="profiling")

            do_GET = do_POST = do_PUT = do_DELETE = do_HEAD = _dispatch
            do_OPTIONS = _dispatch  # CORS preflight (S3 gateway)
            # WebDAV verbs (server/webdav_server.go)
            do_PROPFIND = do_MKCOL = do_MOVE = do_COPY = _dispatch
            do_PATCH = _dispatch  # TUS resumable uploads

            def log_message(self, *args):  # quiet
                pass

        class Server(ThreadingHTTPServer):
            daemon_threads = True
            allow_reuse_address = True
            reuse_port = False   # set below before construction
            ssl_context = None  # set by start() when the TLS plane is on

            def server_bind(self):
                if self.reuse_port:
                    import socket as _socket
                    self.socket.setsockopt(_socket.SOL_SOCKET,
                                           _socket.SO_REUSEPORT, 1)
                super().server_bind()

            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                # established keep-alive connections, so stop() can
                # sever them: shutdown() only ends the ACCEPT loop,
                # and with pooled clients a "stopped" server would
                # otherwise keep serving (and acking writes!) over
                # existing sockets — breaking every stop-means-stop
                # invariant (e.g. the MQ broker's stop-then-flush)
                self._conns: set = set()
                self._conns_lock = threading.Lock()

            def finish_request(self, request, client_address):
                # TLS handshake PER CONNECTION in its own handler
                # thread — wrapping the listening socket would
                # handshake inside the single accept loop, letting one
                # silent client stall every role and wedge shutdown.
                # The raw socket joins _conns BEFORE the handshake so
                # stop() can sever a connection parked mid-handshake
                # (previously only handshaken sockets were severable),
                # and a failed handshake is counted but never reaches
                # _dispatch — the requests_in_flight gauge only ever
                # counts handshaken, dispatched requests.
                raw = request
                with self._conns_lock:
                    self._conns.add(raw)
                try:
                    if self.ssl_context is not None:
                        import ssl as _ssl
                        try:
                            request.settimeout(10)
                            request = self.ssl_context.wrap_socket(
                                request, server_side=True)
                            request.settimeout(None)
                        except (_ssl.SSLError, OSError) as e:
                            from ..stats import PROCESS
                            PROCESS.counter_add(
                                "tls_handshake_failures_total", 1.0,
                                help_text="inbound TLS handshakes "
                                          "that never completed",
                                reason=type(e).__name__)
                            try:
                                request.close()
                            except OSError:
                                pass
                            return
                        with self._conns_lock:
                            # track the wrapped socket: close() on it
                            # tears down the TLS layer AND the raw fd
                            self._conns.discard(raw)
                            self._conns.add(request)
                    super().finish_request(request, client_address)
                finally:
                    with self._conns_lock:
                        self._conns.discard(request)
                        self._conns.discard(raw)

            def close_established(self):
                import socket as _socket
                with self._conns_lock:
                    conns = list(self._conns)
                for c in conns:
                    try:
                        c.shutdown(_socket.SHUT_RDWR)
                    except OSError:
                        pass
                    try:
                        c.close()
                    except OSError:
                        pass

            def handle_error(self, request, client_address):
                # a client (or close_established) dropping the socket
                # mid-response is normal teardown, not a stack trace
                import sys as _sys
                exc = _sys.exc_info()[1]
                if isinstance(exc, (BrokenPipeError,
                                    ConnectionResetError,
                                    ConnectionAbortedError)):
                    return
                super().handle_error(request, client_address)

        Server.reuse_port = bool(reuse_port)
        self._httpd = Server((host, port), Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: threading.Thread | None = None
        self._async = None   # asyncio front, when selected (start())

    def route(self, method: str, path: str, fn: Route,
              quiet: bool = False, job: bool = False) -> None:
        """`quiet` marks chatter — status, poll, scrape and heartbeat
        routes: their server spans are recorded only when they err or
        are slow (tracing.Span.quiet), so a minute of polling cannot
        turn the ring over on the trace of the job being polled.
        `job` marks what a maintenance job asks of this role (a pull,
        a push, a mount): its server span and the rest of its trace go
        to the jobs' ring (tracing.start_span), out of reach of the
        client traffic that fills the other."""
        self.routes[(method, path)] = fn
        for flag, routes in ((quiet, self.quiet_routes),
                             (job, self.job_routes)):
            if flag:
                routes.add((method, path))
            else:
                routes.discard((method, path))

    def close_server_span(self, sp, req, status: int,
                          cpu: "float | None", sent: int) -> None:
        """Close a request's server span — both fronts end here.
        `bytes` is the request's body where it has one, else the
        response's (a bulk push or pull is its bytes); `cpuSeconds` is
        the handler thread's CPU where the request paid the clock."""
        sp.set("status", status)
        try:
            nbytes = int(req.headers.get("Content-Length") or 0) or sent
        except ValueError:
            nbytes = sent
        if nbytes:
            sp.set("bytes", nbytes)
        if cpu is not None:
            sp.set("cpuSeconds", round(cpu, 6))
        sp.quiet = status < 500 and \
            (req.method, req.path) in self.quiet_routes
        sp.finish()

    def route_prefix(self, method: str, prefix: str, fn: Route) -> None:
        """Register a handler for every path under `prefix`.  The
        per-method table is kept longest-prefix-first so nested
        prefixes resolve to the most specific handler."""
        table = self.prefix_routes.setdefault(method, [])
        table[:] = [(p, f) for p, f in table if p != prefix]
        table.append((prefix, fn))
        table.sort(key=lambda pf: -len(pf[0]))

    def _prefix_route(self, method: str, path: str) -> "Route | None":
        for prefix, fn in self.prefix_routes.get(method, ()):
            if path.startswith(prefix):
                return fn
        return None

    def start(self) -> None:
        tls = _tls_config()
        if self.role and self.role in async_front_roles():
            # asyncio front (async_front.py): one event loop
            # multiplexes every connection of this role's funnel —
            # same routes, guard, QoS admission, tracing spans and
            # request_seconds, different concurrency substrate.  The
            # already-bound listener socket is handed over so the
            # port the owner advertised stays the port served.
            from .async_front import AsyncFront
            self._async = AsyncFront(
                self, ssl_context=(tls.server_context()
                                   if tls is not None else None))
            self._async.start(self._httpd.socket)
            return
        if tls is not None:
            # TLS plane (weed/security/tls.go); connections handshake
            # in their handler threads (Server.finish_request), with
            # mTLS only CA-signed peers get through
            self._httpd.ssl_context = tls.server_context()
        # poll_interval: serve_forever's shutdown() handshake waits
        # for the accept loop's next selector tick — the 0.5 s
        # default parked EVERY server stop for ~0.25 s on average,
        # which across a tier-1 run's hundreds of role teardowns was
        # tens of seconds of pure sleep.  Accepts use the selector,
        # so a short tick costs ~nothing while serving.
        self._thread = threading.Thread(
            target=lambda: self._httpd.serve_forever(
                poll_interval=0.02), daemon=True)
        self._thread.start()

    def abort(self) -> None:
        """Close a bound listener that never served (owner-constructor
        failure unwind).  stop() is wrong here: shutdown() waits on
        the serve_forever loop's acknowledgement, which never comes
        from a loop that never started."""
        self._httpd.server_close()

    def stop(self) -> None:
        a = getattr(self, "_async", None)
        if a is not None:
            self._async = None
            a.stop()
            try:
                self._httpd.server_close()  # shared socket: idempotent
            except OSError:
                pass
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        # sever established keep-alive connections: in-flight handlers
        # see a dead socket, pooled clients get a connection error and
        # re-dial elsewhere — a stopped server must never ack another
        # request
        self._httpd.close_established()

    @property
    def url(self) -> str:
        return f"{self.host}:{self.port}"


# --- tiny client helpers -------------------------------------------------

def _tls_config():
    from .. import security
    return security.current().tls


def _dial(url: str) -> "tuple[str, object | None]":
    """(full url, ssl context) — https with the cluster CA pinned when
    the TLS plane is on; plain http otherwise.  Single funnel: every
    role's client traffic passes through http_bytes/http_json."""
    tls = _tls_config()
    if url.startswith("http"):
        return url, (tls.client_context() if tls and
                     url.startswith("https") else None)
    if tls is not None:
        return "https://" + url, tls.client_context()
    return "http://" + url, None


def _auth_for(url: str, headers: dict | None) -> dict:
    """Attach the process admin JWT to admin-plane requests — the analog
    of the reference's gRPC client factory applying the global security
    config to every dial (pb/grpc_client_server.go), so call sites don't
    plumb credentials."""
    from .. import security
    sec = security.current()
    if not sec.admin_key:
        return headers or {}
    path = urllib.parse.urlparse(
        url if url.startswith("http") else "http://" + url).path
    if not is_admin_path(path):
        return headers or {}
    headers = dict(headers or {})
    headers.setdefault("Authorization", f"Bearer {sec.admin_jwt()}")
    return headers


def is_admin_path(path: str) -> bool:
    """The admin/maintenance plane: volume+filer /admin/*, master grow /
    lock / raft endpoints, and heartbeats (all gRPC-only surfaces in the
    reference, gated there by grpc credentials — an unauthenticated
    raft RPC would let an outsider depose the leader)."""
    return path.startswith(("/admin/", "/cluster/raft/",
                            "/debug/")) or path in (
        "/vol/grow", "/cluster/lease_admin_token",
        "/cluster/release_admin_token", "/heartbeat")


def http_json(method: str, url: str, payload: dict | None = None,
              timeout: float = 30.0,
              headers: dict | None = None) -> dict:
    """JSON request; non-2xx responses return their parsed error body
    (callers check for an "error" key, mirroring gRPC status handling).
    Explicit `headers` win over the global-config auto-attach (a server
    with a per-instance security override passes its own tokens)."""
    data = json.dumps(payload).encode() if payload is not None else None
    headers = dict(headers or {})
    if data:
        headers.setdefault("Content-Type", "application/json")
    status, body, _ = _pooled_request(method, url, data,
                                      _auth_for(url, headers), timeout)
    try:
        parsed = json.loads(body or b"{}")
    except ValueError:
        parsed = {"error": body.decode(errors="replace")}
    if status >= 300 and isinstance(parsed, dict):
        parsed.setdefault("error", f"HTTP {status}")
    return parsed


def parse_range(header: str, total: int
                ) -> "tuple[int, int] | None | str":
    """One shared parser for `Range: bytes=...` (RFC 9110 §14):
    returns (offset, size), None for absent/malformed (serve the full
    body), or "unsatisfiable" for a well-formed range beyond EOF.
    Handles the suffix form bytes=-N (last N bytes)."""
    if not header.startswith("bytes="):
        return None
    spec = header[6:]
    if "," in spec:
        return None            # multipart ranges: serve full body
    lo, dash, hi = spec.partition("-")
    if not dash:
        return None
    try:
        if lo:
            offset = int(lo)
            if offset >= total > 0 or offset < 0:
                return "unsatisfiable"
            stop = min(int(hi) + 1, total) if hi else total
            if stop <= offset:
                return None
            return offset, stop - offset
        if hi:                 # suffix: last N bytes
            size = min(int(hi), total)
            if size <= 0:
                return None    # bytes=-0 / bytes=--5: not a range
            return total - size, size
    except ValueError:
        return None
    return None


class _RelaySourceError(OSError):
    """http_relay: the SOURCE leg died (real or injected) — the
    destination never answered, so no verdict probe is possible."""


def _trace_headers(headers: dict) -> dict:
    """Forward the active request id + trace parent on every internal
    hop (util/request_id, tracing.py): the receiving server adopts
    both, so one id traces gateway -> filer -> volume in the logs and
    the receiver's server span hangs under this caller's span.  The
    pooled funnel and every bulk path (download, upload, relay,
    stream) stamp through here — a bulk transfer is where an EC job
    spends its time, and a trace cut there loses the receiver's half."""
    from .. import tracing
    from ..util.request_id import HEADER as _RID_HEADER
    from ..util.request_id import get_request_id
    rid = get_request_id()
    if rid and _RID_HEADER not in headers:
        headers = dict(headers)
        headers[_RID_HEADER] = rid
    tp = tracing.traceparent_header()
    if tp and tracing.HEADER not in headers:
        headers = dict(headers)
        headers[tracing.HEADER] = tp
    return headers


def _fire_fault(site: str, key: str = "") -> "str | None":
    """faults.py hook for the client funnel (late import: httpd is on
    every role's startup path).  Returns the directive for
    truncate/drop arms; raises FaultInjected for error arms."""
    from .. import faults
    return faults.fire(site, key=key)


def _open_conn(full_url: str, ctx, timeout: float):
    """(http.client connection, request target) for a `_dial`ed url —
    the bulk client paths drive the connection themselves (chunk
    framing, sendfile), which urllib does not let them."""
    import http.client
    parsed = urllib.parse.urlsplit(full_url)
    target = parsed.path or "/"
    if parsed.query:
        target += "?" + parsed.query
    if parsed.scheme == "https":
        return http.client.HTTPSConnection(
            parsed.netloc, timeout=timeout, context=ctx), target
    return http.client.HTTPConnection(parsed.netloc,
                                      timeout=timeout), target


def _receiver_verdict(conn) -> "tuple[int, bytes, dict] | None":
    """After a body send failed: the receiver may have REJECTED the
    upload mid-body (4xx/5xx + close) — its verdict is the root cause
    the caller needs, not the sender's broken pipe.  (status, body,
    headers) when a response is readable, else None and the caller
    re-raises its send error."""
    import http.client
    try:
        resp = conn.getresponse()
        return resp.status, resp.read(), dict(resp.headers)
    except (OSError, http.client.HTTPException):
        return None


def http_download(url: str, dest_path: str,
                  headers: dict | None = None, timeout: float = 60.0,
                  chunk_size: int = 4 << 20,
                  halves: "CopyHalves | None" = None) -> tuple[int, dict]:
    """GET `url` streaming the response body to `dest_path` in chunks —
    bounded memory no matter the file size (the worker's bulk volume
    pull; the reference streams CopyFile the same way,
    volume_server.proto:69).  Returns (status, response headers); on a
    non-2xx status dest_path is removed and the (small) error body is
    left unconsumed.  The copy's socket and file halves are added to
    `halves` where one is given (copy_to_file).

    `timeout` is a per-socket-operation stall bound, not a transfer
    bound: a 30GB pull may run for hours as long as bytes keep
    arriving, but a peer that goes silent costs 60s, not the old 600s
    (deadline plane satellite: a hung peer must not park a caller for
    minutes even with the plane disabled).  When the request carries a
    deadline the stall bound shrinks to the remaining budget."""
    import os as _os
    from ..util import deadline as _dl
    timeout = _dl.io_timeout(timeout, site="httpd.download")
    full_url, ctx = _dial(url)
    req = urllib.request.Request(
        full_url, headers=_dl.stamp_headers(
            _trace_headers(_auth_for(url, headers))))
    # download into a sibling temp file and os.replace on success: a
    # mid-transfer failure (connection reset at 10GB of a 30GB pull)
    # must never leave a truncated file at dest_path for the store to
    # later mount, and an error must never clobber a pre-existing dest
    import uuid as _uuid
    tmp = f"{dest_path}.download.{_uuid.uuid4().hex}"

    def chunks(resp):
        while True:
            if _fire_fault("httpd.download.chunk",
                           key=full_url) is not None:
                # truncate/drop both mean "the source died mid-body":
                # surface it, never os.replace a short file into place
                raise IOError(
                    f"download {url}: fault-injected mid-body failure")
            chunk = resp.read(chunk_size)
            if not chunk:
                return
            yield chunk

    try:
        with urllib.request.urlopen(req, timeout=timeout,
                                    context=ctx) as resp:
            with open(tmp, "wb") as f:
                copy_to_file(chunks(resp), f, halves or CopyHalves())
            _os.replace(tmp, dest_path)
            return resp.status, dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers)
    finally:
        try:
            _os.remove(tmp)
        except OSError:
            pass


def http_relay(src_url: str, dst_method: str, dst_url: str,
               headers: dict | None = None, timeout: float = 60.0,
               chunk_size: int = 4 << 20
               ) -> "tuple[int, int, bytes]":
    """Stream a GET of `src_url` straight into a chunked-encoded
    `dst_method dst_url` body: the push starts at the first downloaded
    chunk, so the two transfer legs overlap instead of staging the
    whole file through a temp relay, and RAM stays bounded by one
    chunk.  Returns (src_status, dst_status, dst_body); on a non-2xx
    source the upload never starts (dst_status 0).  `timeout` is a
    per-socket-operation stall bound (see http_download), deadline-
    derived when the request carries a budget."""
    from ..util import deadline as _dl
    timeout = _dl.io_timeout(timeout, site="httpd.relay")
    full_src, src_ctx = _dial(src_url)
    req = urllib.request.Request(
        full_src,
        headers=_dl.stamp_headers(
            _trace_headers(_auth_for(src_url, headers))))
    try:
        resp = urllib.request.urlopen(req, timeout=timeout,
                                      context=src_ctx)
    except urllib.error.HTTPError as e:
        e.close()
        return e.code, 0, b""
    with resp:
        if resp.status != 200:
            return resp.status, 0, b""
        full_dst, dst_ctx = _dial(dst_url)
        conn, target = _open_conn(full_dst, dst_ctx, timeout)
        up_headers = dict(_dl.stamp_headers(
            _trace_headers(_auth_for(dst_url, headers))))
        up_headers["Transfer-Encoding"] = "chunked"
        expected = resp.length  # None when the source streams chunked

        def chunks():
            # every SOURCE-side failure (real or fault-injected)
            # raises _RelaySourceError: the destination is then still
            # waiting for chunks, so the caller must NOT probe it for
            # a verdict — only send-socket failures mean the
            # destination spoke first
            sent = 0
            while True:
                try:
                    directive = _fire_fault("httpd.relay.chunk",
                                            key=full_dst)
                except OSError as e:  # armed `error`: source died
                    raise _RelaySourceError(str(e)) from None
                if directive == "truncate":
                    # simulated source death: raising (not returning)
                    # keeps the no-truncated-but-clean-upload rule —
                    # the aborted chunked stream errors on the dest
                    raise _RelaySourceError(
                        f"relay {src_url}: fault-injected "
                        f"truncation at {sent} bytes")
                if directive == "drop":
                    resp.close()
                    raise _RelaySourceError(
                        f"relay {src_url}: fault-injected "
                        f"connection drop at {sent} bytes")
                try:
                    chunk = resp.read(chunk_size)
                except OSError as e:
                    raise _RelaySourceError(
                        f"relay source {src_url} died at {sent} "
                        f"bytes: {e}") from None
                if not chunk:
                    if expected is not None and sent != expected:
                        # a source dying mid-body reads as plain EOF
                        # (no IncompleteRead with sized reads) — raise
                        # instead of finalizing a truncated upload as
                        # success; the aborted chunked stream also
                        # errors on the destination
                        raise _RelaySourceError(
                            f"relay source truncated at {sent} of "
                            f"{expected} bytes")
                    return
                sent += len(chunk)
                yield chunk

        try:
            try:
                conn.request(dst_method, target, body=chunks(),
                             headers=up_headers, encode_chunked=True)
            except _RelaySourceError:
                raise
            except OSError:
                # the send socket failed: the DESTINATION may have
                # spoken first
                verdict = _receiver_verdict(conn)
                if verdict is None:
                    raise
                return 200, verdict[0], verdict[1]
            r = conn.getresponse()
            return 200, r.status, r.read()
        finally:
            conn.close()


def http_stream_request(method: str, url: str, chunks,
                        headers: dict | None = None,
                        timeout: float = 60.0
                        ) -> "tuple[int, bytes]":
    """Send an iterable of byte windows as ONE chunked-encoded request
    body — the producer side of `Request.stream_body`.  The request is
    on the wire from the first window, so a producer that generates
    bytes incrementally (the scatter-encode GF pipeline) streams at
    wire speed with bounded memory instead of staging a whole shard.
    A producer exception tears the connection down mid-body — the
    receiver sees a short chunked stream and errors, never a
    truncated-but-clean upload.  Returns (status, body).  `timeout`
    is a per-socket-operation stall bound (see http_download),
    deadline-derived when the request carries a budget."""
    from ..util import deadline as _dl
    timeout = _dl.io_timeout(timeout, site="httpd.stream")
    full_url, ctx = _dial(url)
    conn, target = _open_conn(full_url, ctx, timeout)
    up_headers = dict(_dl.stamp_headers(
        _trace_headers(_auth_for(url, headers))))
    try:
        # manual chunk framing instead of http.client's encode_chunked:
        # that path CONCATENATES header+chunk+trailer into a fresh
        # buffer per window (one extra multi-MB copy per send on the
        # scatter hot path); three sends straight off the caller's
        # memoryview keep the loop copy-free (sendall releases the GIL)
        conn.putrequest(method, target, skip_accept_encoding=True)
        for hk, hv in up_headers.items():
            conn.putheader(hk, hv)
        conn.putheader("Transfer-Encoding", "chunked")
        conn.endheaders()
        if conn.sock is not None:
            import socket as _socket
            # the per-chunk framing interleaves small sends (size
            # line, CRLF) with multi-MB payload sends — Nagle would
            # park the small ones behind delayed ACKs
            conn.sock.setsockopt(_socket.IPPROTO_TCP,
                                 _socket.TCP_NODELAY, 1)
        from ..faults import FaultInjected as _FaultInjected
        try:
            for chunk in chunks:
                directive = _fire_fault("httpd.stream.chunk",
                                        key=full_url)
                if directive == "truncate":
                    # end the chunked stream EARLY but CLEANLY: the
                    # receiver sees valid framing with fewer bytes
                    # than the producer meant — exactly the case the
                    # CRC/byte-count commit handshake must catch
                    break
                if directive == "drop":
                    conn.sock.close()
                    raise OSError(
                        f"stream to {url}: fault-injected drop")
                n = len(chunk)
                if not n:
                    continue
                conn.send(b"%X\r\n" % n)
                conn.send(chunk)
                conn.send(b"\r\n")
            conn.send(b"0\r\n\r\n")
        except _FaultInjected:
            # an armed `error` fault (here or in the producer) stands
            # in for the WIRE dying, not the receiver answering: skip
            # the receiver-verdict probe below — with both ends alive
            # it would block on a receiver that still wants chunks —
            # and let the finally tear the connection down mid-body
            raise
        except OSError:
            verdict = _receiver_verdict(conn)
            if verdict is None:
                raise
            return verdict[:2]
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class UploadResult(tuple):
    """http_upload's (status, body, headers), which also says in `via`
    how the body went to the socket: "sendfile" or "blocks"."""

    def __new__(cls, status: int, body: bytes, headers: dict,
                via: str):
        self = super().__new__(cls, (status, body, headers))
        self.via = via
        return self


_UPLOAD_BLOCK = 1 << 20


def _send_pieces(sock, pieces, send_some) -> int:
    """`pieces` [(offset, count, zeros)] of an open file to `sock`:
    `send_some(offset, count)` sends some of a range and returns how
    much (0 where the file has ended), then the piece's zero bytes go.
    Returns the bytes sent, fewer than asked for where the file ended
    inside a piece."""
    sent = 0
    for offset, count, zeros in pieces:
        while count:
            n = send_some(offset, count)
            if not n:
                return sent
            offset += n
            count -= n
            sent += n
        fill = memoryview(bytes(min(zeros, _UPLOAD_BLOCK)))
        while zeros:
            part = fill[:zeros]
            sock.sendall(part)
            zeros -= len(part)
            sent += len(part)
    return sent


def _sendfile_to(sock, fd: int):
    """send_some for a plain socket: `os.sendfile`, the kernel copying.
    A socket with a timeout is non-blocking underneath, so a full send
    buffer is waited out, on one poller for the whole body
    (`socket.sendfile` builds a selector, and selects before every
    call, once a range)."""
    import os as _os
    import select as _select
    out = sock.fileno()
    timeout = sock.gettimeout()
    wait_ms = None if timeout is None else timeout * 1e3
    poller = _select.poll()
    poller.register(out, _select.POLLOUT)

    def send_some(offset: int, count: int) -> int:
        while True:
            try:
                return _os.sendfile(out, fd, offset, count)
            except BlockingIOError:
                if not poller.poll(wait_ms):
                    raise TimeoutError("timed out") from None
    return send_some


def _blocks_to(sock, fd: int):
    """send_some for a TLS socket (where sendfile would fall back to
    8 KiB sends): through one reused 1 MiB buffer."""
    import os as _os
    block = memoryview(bytearray(_UPLOAD_BLOCK))

    def send_some(offset: int, count: int) -> int:
        n = _os.preadv(fd, [block[:count]], offset)
        sock.sendall(block[:n])
        return n
    return send_some


def http_upload(method: str, url: str, src_path: str,
                headers: dict | None = None, timeout: float = 60.0,
                pieces: "list[tuple[int, int, int]] | None" = None
                ) -> UploadResult:
    """Send a file as the request body WITHOUT buffering it in memory
    (the worker's bulk shard push): the whole of it, or with `pieces`
    [(offset, count, zeros)] those ranges of it in their order, each
    followed by that many zero bytes (a data shard as the blocks of the
    `.dat` it is made of, ec_locate.data_shard_ranges).  Content-Length
    is the open file's size, or the pieces' sum, and the body goes to a
    plain socket by `os.sendfile` (the kernel copies) and to a TLS one
    through one reused 1 MiB buffer; `via` says which.  A body that
    ends short of its Content-Length raises; a receiver that answers
    mid-body and closes gets its status and body returned, not the
    sender's broken pipe.  `timeout` is a per-socket-operation stall
    bound (see http_download), deadline-derived when a budget is
    armed."""
    import os as _os
    from ..util import deadline as _dl
    timeout = _dl.io_timeout(timeout, site="httpd.upload")
    up_headers = _dl.stamp_headers(
        _trace_headers(_auth_for(url, headers)))
    full_url, ctx = _dial(url)
    conn, target = _open_conn(full_url, ctx, timeout)
    via = "blocks" if full_url.startswith("https") else "sendfile"
    send_to = _blocks_to if via == "blocks" else _sendfile_to
    try:
        with open(src_path, "rb") as f:
            if pieces is None:
                pieces = [(0, _os.fstat(f.fileno()).st_size, 0)]
            size = sum(count + zeros for _, count, zeros in pieces)
            conn.putrequest(method, target, skip_accept_encoding=True)
            for hk, hv in up_headers.items():
                conn.putheader(hk, hv)
            conn.putheader("Content-Length", str(size))
            conn.endheaders()
            try:
                sent = _send_pieces(conn.sock, pieces,
                                    send_to(conn.sock, f.fileno()))
            except TimeoutError:
                raise           # a stalled receiver has said nothing
            except OSError:
                verdict = _receiver_verdict(conn)
                if verdict is None:
                    raise
                return UploadResult(*verdict, via)
        if sent != size:
            raise IOError(f"upload {src_path}: file ended at {sent} "
                          f"of {size} bytes")
        resp = conn.getresponse()
        return UploadResult(resp.status, resp.read(),
                            dict(resp.headers), via)
    finally:
        conn.close()


# --- pooled keep-alive client (the hot data-plane funnel) ----------------
#
# urllib.request opens a fresh TCP connection per call; at benchmark
# concurrency that is 3 syscall round-trips of pure setup per 1KB
# needle, and measured ~30x below the reference's `weed benchmark`
# req/s (README.md:555-605 — its Go http.Client pools keep-alive
# connections).  This pool is PER-THREAD (no cross-thread locking on
# the hot path; a ThreadPool worker reuses its sockets) keyed by
# scheme+netloc.  POSTs are retried once ONLY when a REUSED socket
# died before the request hit the wire (stale keep-alive), never on a
# fresh connection — the same idempotency rule Go's Transport applies.

_thread_pools = threading.local()


def _pool() -> dict:
    p = getattr(_thread_pools, "conns", None)
    if p is None:
        p = _thread_pools.conns = {}
    return p


def _one_pooled_request(method: str, full_url: str, body,
                        headers: dict, timeout: float, ctx):
    """One request over the thread's pooled connection for the url's
    (scheme, netloc); returns (status, data, headers, location)."""
    import http.client

    parsed = urllib.parse.urlsplit(full_url)
    target = parsed.path or "/"
    if parsed.query:
        target += "?" + parsed.query
    key = (parsed.scheme, parsed.netloc)
    # connection-churn counters (the pre-work for the persistent-
    # connection rework, ROADMAP item 1): a healthy funnel reuses ~all
    # of its sockets; opened ~= requests means every call pays the TCP
    # setup tax the pool exists to amortize
    from ..stats import PROCESS as _process_metrics
    for attempt in (0, 1):
        conn = _pool().get(key)
        reused = conn is not None
        if reused:
            _process_metrics.counter_add(
                "pool_connections_reused_total", 1.0,
                help_text="pooled requests served over a kept-alive "
                          "socket", peer=parsed.netloc)
        if conn is None:
            _fire_fault("httpd.pool.connect", key=parsed.netloc)
            _process_metrics.counter_add(
                "pool_connections_opened_total", 1.0,
                help_text="fresh sockets dialed by the pooled client",
                peer=parsed.netloc)
            if parsed.scheme == "https":
                conn = http.client.HTTPSConnection(
                    parsed.netloc, timeout=timeout, context=ctx)
            else:
                conn = http.client.HTTPConnection(
                    parsed.netloc, timeout=timeout)
            _pool()[key] = conn
        if conn.sock is not None:
            conn.sock.settimeout(timeout)
        try:
            _fire_fault("httpd.pool.request",
                        key=f"{parsed.netloc}{target}")
            conn.request(method, target, body=body, headers=headers)
        except (http.client.HTTPException, OSError) as e:
            # send failed: the request never executed — safe to retry
            # any method once on a stale reused socket
            conn.close()
            _pool().pop(key, None)
            if reused and attempt == 0:
                continue
            if isinstance(e, OSError):
                raise
            raise OSError(f"http request failed: {e!r}") from e
        try:
            resp = conn.getresponse()
            data = resp.read()
        except (http.client.HTTPException, OSError) as e:
            # request may have EXECUTED server-side (response lost):
            # transparently retrying a POST here would double-execute
            # non-idempotent operations (publish, delete counters), so
            # only idempotent work (RFC 9110 §9.2.2 methods, or a
            # caller-DECLARED X-Idempotent POST such as truncate-to-
            # size) re-issues — and only for the stale-keep-alive
            # race: a REUSED pooled socket that died with ZERO
            # response bytes is a connection-state artifact, not a
            # peer-health verdict, so it re-issues inline on a fresh
            # dial without feeding the breaker or spending retry
            # budget.  Every other failure (timeout on a hung peer,
            # mid-response reset, fresh-connection death) surfaces to
            # the ONE outer policy in _pooled_request (util/retry),
            # which re-issues idempotent work under backoff + budget —
            # keeping this inner loop from stacking multiplicatively
            # with the outer attempts.  Undeclared POSTs still surface
            # the executed-or-not ambiguity (Go Transport's rule —
            # blind replay would double-publish MQ messages).
            conn.close()
            _pool().pop(key, None)
            if attempt == 0 and reused and \
                    isinstance(e, http.client.RemoteDisconnected) and \
                    (method in ("GET", "HEAD", "PUT", "DELETE",
                                "OPTIONS")
                     or headers.get("X-Idempotent") == "1"):
                continue
            if isinstance(e, OSError):
                raise
            raise OSError(f"http response failed: {e!r}") from e
        if resp.will_close:
            conn.close()
            _pool().pop(key, None)
        return (resp.status, data, dict(resp.headers),
                resp.getheader("Location"))
    raise OSError("unreachable")  # pragma: no cover


def _pooled_request(method: str, url: str, body, headers: dict,
                    timeout: float, max_redirects: int = 3):
    headers = _trace_headers(headers)
    full_url, ctx = _dial(url)
    # unified failure policy (util/retry): consult the peer's circuit
    # breaker before dialing (a tripped peer fails fast instead of
    # burning a timeout), feed every transport outcome back into the
    # health map, and re-issue idempotent requests under the capped
    # jittered backoff + process retry budget.  POSTs keep exactly the
    # seed's semantics: only `_one_pooled_request`'s provably-never-
    # executed send-failed rule re-issues them.
    from ..util import deadline as _dl
    from ..util import retry as _retry
    for _hop in range(max_redirects):
        peer = urllib.parse.urlsplit(full_url).netloc
        idempotent = method in ("GET", "HEAD", "PUT", "DELETE",
                                "OPTIONS") or \
            headers.get("X-Idempotent") == "1"

        def _attempt(u=full_url):
            # deadline plane, per ATTEMPT: the socket timeout is
            # re-derived from the budget remaining NOW (a retry after
            # backoff has less), and the forwarded header carries the
            # fresh remaining ms so the receiver can never out-wait
            # this caller.  An already-spent budget raises before the
            # dial (DeadlineExceeded — retry_call refuses to re-issue
            # it).  Unarmed requests: two contextvar reads, the seed
            # timeout, no header.
            t = _dl.io_timeout(timeout, site="httpd.pool")
            return _one_pooled_request(method, u, body,
                                       _dl.stamp_headers(headers),
                                       t, ctx)

        status, data, rheaders, location = _retry.retry_call(
            _attempt, site="httpd.pool", peer=peer,
            idempotent=idempotent)
        if status in (301, 302, 307, 308) and location and \
                method in ("GET", "HEAD"):
            # urllib-parity redirect following for read paths
            full_url = urllib.parse.urljoin(full_url, location)
            continue
        return status, data, rheaders
    return status, data, rheaders


def http_bytes(method: str, url: str, body: bytes | None = None,
               headers: dict | None = None, timeout: float = 60.0
               ) -> tuple[int, bytes, dict]:
    return _pooled_request(method, url, body,
                           _auth_for(url, headers), timeout)
