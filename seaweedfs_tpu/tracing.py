"""In-process distributed tracer riding the request-id plane.

The request id (util/request_id) already crosses every hop —
gateway -> filer -> volume -> master -> worker — so it IS the trace
id; this module hangs spans on it.  A span records one timed unit of
work (an HTTP handler, a gRPC method, an EC pipeline stage) with
explicit parentage, so `weed shell trace.show <request_id>` can
reassemble one request's cross-node tree and show where the time went
(stage-level timing, not aggregate counters, is what exposes the
bottleneck stage — arXiv:1709.05365 §5, arXiv:1908.01527 §2).

Design constraints, in order:

- always-on and allocation-cheap: the data plane runs with tracing
  enabled, so a span is one small object + one deque append; no
  locks on the hot path beyond the deque's own;
- in-process ring buffer only (`SEAWEEDFS_TPU_TRACE_BUFFER` spans,
  default 4096): no exporter, no background thread — the debug plane
  (`GET /debug/traces`) reads the buffer and `trace.show` fans out;
- context propagation over HTTP via `X-Trace-Parent:
  <trace_id>-<span_id>` next to `X-Request-ID`, over gRPC via
  `x-trace-parent` metadata, and across the worker job boundary via
  the job payload;
- sampling (`SEAWEEDFS_TPU_TRACE_SAMPLE`, 0.0-1.0, default 1.0)
  drops span RECORDING, never id propagation, so a sampled-out parent
  still stitches its children to the same trace;
- spans slower than `SEAWEEDFS_TPU_SLOW_MS` are written through
  util/wlog at WARN with their attrs (the slow-request log);
- chatter does not evict work: a server span of a route registered
  quiet (status, poll, scrape, heartbeat) is recorded only when it
  errs or is slow, so an EC job's trace outlives a minute of polls;
- nor does client traffic: a span opened with `job=True` (a route
  registered as job work, the worker's job span) marks its trace as a
  job's, and every later span of a marked trace, and every span
  `ingest` re-records, goes to a second ring of the same size, so a
  volume role's client GETs cannot turn over the trace of the job it
  served.  The readers merge both rings by start.

API shapes the SWFS007 lint understands:

    with tracing.span("GET /path", role="filer") as sp:
        sp.set("status", 200)          # preferred: leak-proof

    sp = tracing.start_span("job", role="worker")
    try: ...
    finally: sp.finish()               # manual pair — lint enforces

    tracing.emit_span("rebuild.fetch", start, duration, ...)
    # post-hoc emission for work measured elsewhere (pipeline stages)
"""

from __future__ import annotations

import contextvars
import itertools
import os
import random
import secrets
import threading
import time
from collections import deque

from .util.request_id import get_request_id

HEADER = "X-Trace-Parent"
GRPC_METADATA_KEY = "x-trace-parent"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def buffer_size() -> int:
    """SEAWEEDFS_TPU_TRACE_BUFFER: spans kept per process."""
    return max(16, _env_int("SEAWEEDFS_TPU_TRACE_BUFFER", 4096))


def sample_rate() -> float:
    """SEAWEEDFS_TPU_TRACE_SAMPLE in [0, 1]: fraction of spans
    recorded to the ring buffer (propagation is never sampled)."""
    return min(1.0, max(0.0, _env_float("SEAWEEDFS_TPU_TRACE_SAMPLE",
                                        1.0)))


def slow_ms() -> float:
    """SEAWEEDFS_TPU_SLOW_MS: spans at least this slow are logged at
    WARN through wlog; unset or <= 0 disables the slow log."""
    return _env_float("SEAWEEDFS_TPU_SLOW_MS", 0.0)


_buffer: "deque[dict]" = deque(maxlen=buffer_size())
# the jobs' ring, and the traces marked as a job's (insertion-ordered,
# at most buffer_size() of them: the oldest mark goes first)
_job_buffer: "deque[dict]" = deque(maxlen=buffer_size())
_job_traces: "dict[str, None]" = {}
_buffer_lock = threading.Lock()

# (trace_id, span_id, role) of the active span on this context; the
# trace id mirrors the request id so children minted on this thread
# parent correctly even when the request id was set separately
_current: contextvars.ContextVar["tuple[str, str, str] | None"] = \
    contextvars.ContextVar("weed_trace_span", default=None)


# span ids need uniqueness (per process, and across the nodes a
# trace.show merge sees), not unpredictability; secrets.token_hex per
# span was a measurable slice of the filer's write-path CPU profile
# (several spans are minted per request).  6 random hex chars pin the
# process, a C-level counter distinguishes spans.
_SPAN_PREFIX = secrets.token_hex(3)
_span_counter = itertools.count(1)


def new_span_id() -> str:
    return f"{_SPAN_PREFIX}{next(_span_counter) & 0xFFFFFF:06x}"


class Span:
    """One unit of timed work.  Cheap on purpose: plain attributes,
    no dict allocated until an attr is set."""

    __slots__ = ("trace_id", "span_id", "parent_id", "role", "name",
                 "start", "duration", "attrs", "error", "quiet",
                 "_token", "_t0", "_finished")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: str, role: str):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.role = role
        self.start = time.time()
        self.duration = 0.0
        self.attrs: "dict | None" = None
        self.error = False
        # a quiet span (status, poll, scrape and heartbeat routes —
        # HttpServer.route(quiet=True)) still carries its ids to
        # children and outbound hops, but is recorded only when it
        # errs or is slower than SEAWEEDFS_TPU_SLOW_MS: a client
        # polling a job's status must not turn the ring over before
        # anyone asks for the job's trace
        self.quiet = False
        self._token = None
        self._t0 = time.perf_counter()
        self._finished = False

    def set(self, key: str, value) -> "Span":
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def set_error(self, err=None) -> "Span":
        self.error = True
        if err is not None:
            self.set("error", f"{type(err).__name__}: {err}")
        return self

    def finish(self) -> None:
        """Close the span: compute duration, restore the previous
        current-span context, record to the ring buffer (sampled) and
        the slow log.  Idempotent — a double finish is a no-op."""
        if self._finished:
            return
        self._finished = True
        self.duration = time.perf_counter() - self._t0
        if self._token is not None:
            try:
                _current.reset(self._token)
            except ValueError:   # finished on a different context
                pass
            self._token = None
        if self.quiet and not self.error:
            threshold = slow_ms()
            if threshold <= 0 or self.duration * 1e3 < threshold:
                return
        _record(self.to_dict())

    def to_dict(self) -> dict:
        d = {"traceId": self.trace_id, "spanId": self.span_id,
             "parentId": self.parent_id, "role": self.role,
             "name": self.name, "start": self.start,
             "durationMs": round(self.duration * 1e3, 3)}
        if self.error:
            d["error"] = True
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d

    # -- context manager ------------------------------------------------

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.set_error(exc)
        self.finish()


def _slow_log_and_sample(doc: dict, threshold: float,
                         rate: float) -> bool:
    """The per-doc half of recording, shared by _record and
    emit_span_batch: the slow log fires regardless of sampling (a
    dropped-from-buffer span that took 4s is still operator-
    actionable), then the sampling gate decides whether the doc is
    kept."""
    if threshold > 0 and doc["durationMs"] >= threshold:
        from .util import wlog
        wlog.warning(
            "slow span %s (%s) %.1fms trace=%s span=%s attrs=%s",
            doc["name"], doc["role"] or "-", doc["durationMs"],
            doc["traceId"], doc["spanId"], doc.get("attrs") or {},
            component="trace")
    return not (rate < 1.0 and random.random() >= rate)


def _resize_locked() -> None:
    global _buffer, _job_buffer
    size = buffer_size()
    if _buffer.maxlen != size:
        # env knob changed since import (tests): rebuild, keeping the
        # newest spans
        _buffer = deque(_buffer, maxlen=size)
        _job_buffer = deque(_job_buffer, maxlen=size)


def _buffer_extend(docs) -> None:
    with _buffer_lock:
        _resize_locked()
        for doc in docs:
            (_job_buffer if doc["traceId"] in _job_traces
             else _buffer).append(doc)


def _mark_job(trace_id: str) -> None:
    """Record the rest of this trace's spans in the jobs' ring."""
    with _buffer_lock:
        if trace_id in _job_traces:
            return
        _job_traces[trace_id] = None
        if len(_job_traces) > buffer_size():
            del _job_traces[next(iter(_job_traces))]


def _record(doc: dict) -> None:
    if _slow_log_and_sample(doc, slow_ms(), sample_rate()):
        _buffer_extend((doc,))


def start_span(name: str, role: str = "", parent: "str | None" = None,
               trace_id: "str | None" = None, job: bool = False) -> Span:
    """Open a span and make it the context's current span.  The caller
    MUST finish() it (or use span() / the with-statement form); the
    SWFS007 lint flags call sites that do neither.

    Parentage: explicit `parent` wins, else the context's current
    span.  Trace id: explicit wins, else the current span's, else the
    active request id, else a fresh id (a traced unit outside any
    request still gets a coherent trace).  `job` marks the trace as a
    job's: this span and the trace's later spans go to the jobs'
    ring."""
    cur = _current.get()
    if parent is None:
        parent = cur[1] if cur else ""
    if not role and cur:
        role = cur[2]
    if trace_id is None:
        trace_id = (cur[0] if cur else "") or get_request_id() or \
            secrets.token_hex(8)
    if job:
        _mark_job(trace_id)
    sp = Span(name, trace_id, new_span_id(), parent, role)
    sp._token = _current.set((sp.trace_id, sp.span_id, sp.role))
    return sp


def span(name: str, role: str = "", parent: "str | None" = None,
         trace_id: "str | None" = None, job: bool = False) -> Span:
    """Context-manager form (the default way to trace a block)."""
    return start_span(name, role=role, parent=parent,
                      trace_id=trace_id, job=job)


def emit_span(name: str, start: float, duration: float,
              role: str = "", parent: str = "",
              trace_id: str = "", attrs: "dict | None" = None,
              error: bool = False) -> dict:
    """Record an already-measured span (work timed outside the
    tracer — pipeline stages whose lifetime spans threads).  Returns
    the recorded document."""
    cur = _current.get()
    doc = {
        "traceId": trace_id or (cur[0] if cur else "") or
        get_request_id() or secrets.token_hex(8),
        "spanId": new_span_id(),
        "parentId": parent or (cur[1] if cur else ""),
        "role": role or (cur[2] if cur else ""),
        "name": name, "start": start,
        "durationMs": round(duration * 1e3, 3)}
    if error:
        doc["error"] = True
    if attrs:
        doc["attrs"] = dict(attrs)
    _record(doc)
    return doc


def emit_span_batch(items: "list[dict]") -> None:
    """Batch emit_span for a stage track's sibling spans: the
    slow-log / sample-rate / buffer-size knobs are env lookups and
    were read three times PER SPAN through emit_span — on a
    stage-tracked write that made them the tracer's dominant hot-path
    cost.  Each item carries emit_span's kwargs (name, start,
    duration, role, parent, trace_id, attrs, error)."""
    if not items:
        return
    cur = _current.get()
    threshold = slow_ms()
    rate = sample_rate()
    out = []
    for it in items:
        doc = {
            "traceId": it.get("trace_id") or (cur[0] if cur else "")
            or get_request_id() or secrets.token_hex(8),
            "spanId": new_span_id(),
            "parentId": it.get("parent") or (cur[1] if cur else ""),
            "role": it.get("role") or (cur[2] if cur else ""),
            "name": it["name"], "start": it["start"],
            "durationMs": round(it["duration"] * 1e3, 3)}
        if it.get("error"):
            doc["error"] = True
        attrs = it.get("attrs")
        if attrs:
            doc["attrs"] = dict(attrs)
        if _slow_log_and_sample(doc, threshold, rate):
            out.append(doc)
    if out:
        _buffer_extend(out)


def emit_plane_hop(name: str, role: str, trace_id: str,
                   start: float, duration: float,
                   stages: "list[tuple[str, float]]",
                   attrs: "dict | None" = None,
                   error: bool = False) -> dict:
    """Synthesize one native-plane hop as a span tree: a root hop
    span plus one child per non-zero stage (ISSUE 18 — the C++ planes
    record stage ns in their flight ring; the Python drainer calls
    this post-hoc, so plane-served requests stitch into the same
    trace as the Python hops that share the request id).  Stage spans
    are laid out back-to-back from the hop start — the planes measure
    stages as consecutive windows of one event-loop pass."""
    hop = emit_span(name, start, duration, role=role, parent="",
                    trace_id=trace_id, attrs=attrs, error=error)
    items = []
    at = start
    for stage_name, stage_s in stages:
        if stage_s <= 0.0:
            continue
        items.append({"name": f"plane.{stage_name}", "start": at,
                      "duration": stage_s, "role": role,
                      "parent": hop["spanId"], "trace_id": trace_id})
        at += stage_s
    emit_span_batch(items)
    return hop


# -- context / propagation helpers ----------------------------------------

def current_ids() -> "tuple[str, str, str] | None":
    """(trace_id, span_id, role) of the active span, or None.  Capture
    this BEFORE handing work to another thread — contextvars do not
    follow threading.Thread — and pass it back as span(parent=...)."""
    return _current.get()


def traceparent_header() -> str:
    """`<trace_id>-<span_id>` for the outbound X-Trace-Parent header;
    empty when no span is active."""
    cur = _current.get()
    return f"{cur[0]}-{cur[1]}" if cur else ""


def parse_traceparent(value: "str | None") -> "tuple[str, str]":
    """(trace_id, parent_span_id) from an inbound header; ("", "")
    when absent/malformed."""
    if not value or "-" not in value:
        return "", ""
    trace_id, _, span_id = value.rpartition("-")
    if not trace_id or not span_id:
        return "", ""
    return trace_id, span_id


def adopt_remote_parent(header_value: "str | None",
                        role: str = "") -> None:
    """Make an inbound trace-parent the context's current span
    without opening a local span (the worker/gRPC boundary adopts the
    caller's context, then opens its own child spans).  An absent/
    malformed value CLEARS the context instead — a long-lived loop
    thread (the worker) must never leak the previous job's ancestry
    into the next one."""
    trace_id, span_id = parse_traceparent(header_value)
    _current.set((trace_id, span_id, role) if trace_id else None)


# -- buffer access (the /debug/traces feed) -------------------------------

def ingest(spans: "list[dict]") -> int:
    """Re-record span documents produced by ANOTHER process into this
    process's ring buffer (the admin ingests worker job spans from
    completion reports — workers have no HTTP listener of their own
    for trace.show to query): a job's spans, so into the jobs' ring.
    Malformed entries are dropped, span ids already buffered are
    skipped (at-least-once reports must not duplicate); returns how
    many were added."""
    added = 0
    with _buffer_lock:
        _resize_locked()
        have = {d["spanId"] for d in _buffer}
        have.update(d["spanId"] for d in _job_buffer)
        for doc in spans or []:
            if not isinstance(doc, dict):
                continue
            if not (doc.get("traceId") and doc.get("spanId") and
                    doc.get("name")):
                continue
            if doc["spanId"] in have:
                continue
            doc = dict(doc)
            doc.setdefault("parentId", "")
            doc.setdefault("role", "")
            doc.setdefault("start", 0.0)
            doc.setdefault("durationMs", 0.0)
            _job_buffer.append(doc)
            have.add(doc["spanId"])
            added += 1
    return added


def _both_rings(trace_id: "str | None" = None) -> "list[dict]":
    """Both rings' spans (of one trace, where given), merged by start."""
    with _buffer_lock:
        docs = [d for ring in (_buffer, _job_buffer) for d in ring
                if trace_id is None or d["traceId"] == trace_id]
    return sorted(docs, key=lambda d: d["start"])


def spans_for(trace_id: str) -> "list[dict]":
    return [dict(d) for d in _both_rings(trace_id)]


def recent_spans(limit: int = 200) -> "list[dict]":
    return [dict(d) for d in _both_rings()[-max(1, limit):]]


def reset_buffer() -> None:
    """Tests only: empty both rings and forget the jobs' marks."""
    with _buffer_lock:
        _buffer.clear()
        _job_buffer.clear()
        _job_traces.clear()
