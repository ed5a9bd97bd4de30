"""The tracing plane (tracing.py): span mechanics, contextvar
parenting, ring-buffer bounds, sampling/slow-log env knobs, header
codecs, the httpd middleware's server spans + request_seconds
histogram, and the worker job-boundary adoption."""

import logging
import os
import threading
import time

import pytest

from seaweedfs_tpu import tracing
from seaweedfs_tpu.server.httpd import HttpServer, http_bytes
from seaweedfs_tpu.util.request_id import set_request_id


@pytest.fixture(autouse=True)
def clean_buffer():
    tracing.reset_buffer()
    tracing.adopt_remote_parent("")  # clear any stale span context
    set_request_id("")
    yield
    tracing.reset_buffer()
    tracing.adopt_remote_parent("")
    set_request_id("")


def test_span_records_trace_parent_attrs_and_error():
    set_request_id("req-1")
    with tracing.span("outer", role="volume") as outer:
        outer.set("k", "v")
        with tracing.span("inner") as inner:
            pass
        try:
            with tracing.span("bad"):
                raise ValueError("boom")
        except ValueError:
            pass
    spans = {s["name"]: s for s in tracing.spans_for("req-1")}
    assert set(spans) == {"outer", "inner", "bad"}
    assert spans["outer"]["parentId"] == ""
    assert spans["outer"]["attrs"] == {"k": "v"}
    # children inherit trace id, parent id AND role via the contextvar
    assert spans["inner"]["parentId"] == spans["outer"]["spanId"]
    assert spans["inner"]["role"] == "volume"
    assert spans["bad"]["error"] is True
    assert "boom" in spans["bad"]["attrs"]["error"]
    assert all(s["durationMs"] >= 0 for s in spans.values())


def test_span_without_request_id_mints_trace():
    with tracing.span("orphan") as sp:
        pass
    assert sp.trace_id
    assert tracing.spans_for(sp.trace_id)[0]["name"] == "orphan"


def test_manual_start_finish_pair_and_idempotence():
    sp = tracing.start_span("manual", role="worker")
    assert tracing.current_ids() == (sp.trace_id, sp.span_id, "worker")
    sp.finish()
    sp.finish()  # double finish must not double-record
    assert tracing.current_ids() is None
    assert len(tracing.spans_for(sp.trace_id)) == 1


def test_traceparent_header_roundtrip():
    set_request_id("rid-7")
    assert tracing.traceparent_header() == ""  # no active span
    with tracing.span("s") as sp:
        hdr = tracing.traceparent_header()
        assert hdr == f"{sp.trace_id}-{sp.span_id}"
        assert tracing.parse_traceparent(hdr) == (sp.trace_id,
                                                  sp.span_id)
    assert tracing.parse_traceparent("") == ("", "")
    assert tracing.parse_traceparent("nodash") == ("", "")
    assert tracing.parse_traceparent(None) == ("", "")


def test_adopt_remote_parent_links_children():
    tracing.adopt_remote_parent("trace-x-aabbccdd", role="worker")
    with tracing.span("child") as sp:
        pass
    assert sp.trace_id == "trace-x"
    assert sp.parent_id == "aabbccdd"
    assert sp.role == "worker"


def test_ring_buffer_is_bounded(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_BUFFER", "32")
    for i in range(100):
        with tracing.span(f"s{i}", trace_id="bounded"):
            pass
    spans = tracing.spans_for("bounded")
    assert len(spans) == 32
    assert spans[-1]["name"] == "s99"  # newest kept, oldest evicted


def test_sampling_drops_recording_not_propagation(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_SAMPLE", "0.0")
    with tracing.span("invisible", trace_id="sampled") as outer:
        # ids still flow: an unsampled parent must not orphan children
        assert tracing.traceparent_header() == \
            f"sampled-{outer.span_id}"
    assert tracing.spans_for("sampled") == []
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_SAMPLE", "1.0")
    with tracing.span("visible", trace_id="sampled"):
        pass
    assert [s["name"] for s in tracing.spans_for("sampled")] == \
        ["visible"]


def test_slow_span_logged_at_warn(monkeypatch):
    # the "weed" logger does not propagate to root (wlog owns its
    # handlers), so capture with our own handler instead of caplog
    monkeypatch.setenv("SEAWEEDFS_TPU_SLOW_MS", "5")
    lines = []

    class Capture(logging.Handler):
        def emit(self, record):
            lines.append((record.levelno, record.getMessage()))

    h = Capture()
    logging.getLogger("weed").addHandler(h)
    try:
        with tracing.span("fast", trace_id="slowlog"):
            pass
        with tracing.span("slow", trace_id="slowlog"):
            time.sleep(0.02)
    finally:
        logging.getLogger("weed").removeHandler(h)
    warns = [msg for lvl, msg in lines if lvl >= logging.WARNING]
    assert any("slow span slow" in m for m in warns), warns
    assert not any("slow span fast" in m for m in warns), warns


def test_emit_span_for_post_hoc_stage_timing():
    doc = tracing.emit_span("stage", time.time() - 1.0, 0.5,
                            role="volume", trace_id="post-hoc",
                            attrs={"bytes": 42})
    got = tracing.spans_for("post-hoc")
    assert got == [doc]
    assert got[0]["durationMs"] == 500.0
    assert got[0]["attrs"]["bytes"] == 42


# -- httpd middleware -----------------------------------------------------

def _wait_spans(trace_id: str, n: int,
                timeout: float = 2.0) -> "list[dict]":
    """The middleware records the server span AFTER writing the
    response (recording must never delay the client), so an
    in-process client can observe the status before the server
    thread's _record lands — poll briefly instead of racing it."""
    deadline = time.time() + timeout
    spans = tracing.spans_for(trace_id)
    while len(spans) < n and time.time() < deadline:
        time.sleep(0.005)
        spans = tracing.spans_for(trace_id)
    return spans


@pytest.fixture
def little_server():
    http = HttpServer("127.0.0.1", 0)
    http.role = "testrole"
    from seaweedfs_tpu.stats import Metrics
    http.metrics = Metrics("testrole")

    def ok(req):
        return 200, {"ok": True}

    def boom(req):
        raise RuntimeError("kaput")

    def hop(req):
        # server handler making an outbound hop: the funnel must
        # attach X-Trace-Parent pointing at THIS handler's span
        st, _, _ = http_bytes("GET", f"{http.url}/ok")
        return 200, {"hopped": st}

    http.route("GET", "/ok", ok)
    http.route("GET", "/boom", boom)
    http.route("GET", "/hop", hop)
    http.start()
    yield http
    http.stop()


def test_middleware_server_span_and_histogram(little_server):
    set_request_id("mw-1")
    st, _, _ = http_bytes("GET", f"http://{little_server.url}/ok")
    assert st == 200
    spans = _wait_spans("mw-1", 1)
    assert [s["name"] for s in spans] == ["GET /ok"]
    sp = spans[0]
    assert sp["role"] == "testrole"
    assert sp["attrs"]["status"] == 200
    text = little_server.metrics.render()
    assert 'testrole_request_seconds_bucket' in text
    assert 'method="GET"' in text and 'code="200"' in text


def test_middleware_marks_handler_error(little_server):
    set_request_id("mw-2")
    st, _, _ = http_bytes("GET", f"http://{little_server.url}/boom")
    assert st == 500
    sp = _wait_spans("mw-2", 1)[0]
    assert sp["error"] is True and sp["attrs"]["status"] == 500
    assert "kaput" in sp["attrs"]["error"]


def test_cross_hop_parenting(little_server):
    """client -> /hop -> /ok: the /ok server span must be a child of
    the /hop server span (one trace, valid ancestry)."""
    set_request_id("mw-3")
    st, _, _ = http_bytes("GET", f"http://{little_server.url}/hop")
    assert st == 200
    spans = {s["name"]: s for s in _wait_spans("mw-3", 2)}
    assert set(spans) == {"GET /hop", "GET /ok"}
    assert spans["GET /ok"]["parentId"] == spans["GET /hop"]["spanId"]


def test_debug_traces_endpoint(little_server):
    from seaweedfs_tpu.server.debug import install_debug_routes
    install_debug_routes(little_server)
    set_request_id("mw-4")
    http_bytes("GET", f"http://{little_server.url}/ok")
    import json
    st, body, _ = http_bytes(
        "GET",
        f"http://{little_server.url}/debug/traces?request_id=mw-4")
    assert st == 200
    doc = json.loads(body)
    assert doc["requestId"] == "mw-4"
    assert [s["name"] for s in doc["spans"]] == ["GET /ok"]


# -- worker job boundary --------------------------------------------------

def test_worker_execute_joins_submitter_trace(tmp_path, monkeypatch):
    from seaweedfs_tpu.plugin import worker as worker_mod
    from seaweedfs_tpu.plugin.worker import JobHandler, PluginWorker

    reports = []
    monkeypatch.setattr(worker_mod, "_post_with_retry",
                        lambda url, payload, attempts=1:
                        reports.append((url, payload)))

    class Handler(JobHandler):
        job_type = "test_job"

        def execute(self, worker, job_id, params):
            # the handler runs INSIDE the job span with the
            # submitter's request id active
            assert tracing.current_ids() is not None
            from seaweedfs_tpu.util.request_id import get_request_id
            assert get_request_id() == "submitter-rid"
            return "done"

    w = PluginWorker("127.0.0.1:1", "127.0.0.1:1", str(tmp_path),
                     [Handler()])
    w._execute("jobX", "test_job", {},
               request_id="submitter-rid",
               trace_parent="submitter-rid-cafe1234")
    spans = tracing.spans_for("submitter-rid")
    assert [s["name"] for s in spans] == ["job:test_job"]
    sp = spans[0]
    assert sp["role"] == "worker"
    assert sp["parentId"] == "cafe1234"
    assert sp["attrs"]["jobId"] == "jobX"
    assert reports and reports[0][1]["success"] is True
    # the worker has no debug listener: its spans ride the completion
    # report so the admin can ingest them into ITS ring buffer
    shipped = reports[0][1]["spans"]
    assert [s["name"] for s in shipped] == ["job:test_job"]
    # the loop thread's context is RESTORED after the job — a leaked
    # rid would trace every later poll into this finished job
    from seaweedfs_tpu.util.request_id import get_request_id
    assert get_request_id() == ""
    assert tracing.current_ids() is None


def test_ingest_dedupes_and_validates():
    doc = {"traceId": "ing-1", "spanId": "aa11", "name": "job:x",
           "role": "worker", "start": 1.0, "durationMs": 5.0}
    assert tracing.ingest([doc, dict(doc),          # duplicate id
                           {"noTrace": True},       # malformed
                           "not-a-dict"]) == 1
    assert tracing.ingest([doc]) == 0  # at-least-once redelivery
    got = tracing.spans_for("ing-1")
    assert len(got) == 1 and got[0]["parentId"] == ""


def test_worker_execute_without_context_mints_job_trace(tmp_path,
                                                        monkeypatch):
    from seaweedfs_tpu.plugin import worker as worker_mod
    from seaweedfs_tpu.plugin.worker import JobHandler, PluginWorker
    monkeypatch.setattr(worker_mod, "_post_with_retry",
                        lambda *a, **k: None)

    class Failing(JobHandler):
        job_type = "test_job"

        def execute(self, worker, job_id, params):
            raise RuntimeError("handler blew up")

    w = PluginWorker("127.0.0.1:1", "127.0.0.1:1", str(tmp_path),
                     [Failing()])
    w._execute("jobY", "test_job", {})
    spans = tracing.spans_for("job-jobY")
    assert len(spans) == 1
    assert spans[0]["error"] is True


def test_spans_across_threads_with_captured_context():
    """The documented pattern for thread-crossing work: capture
    current_ids() before the thread, pass parent= explicitly."""
    set_request_id("threaded")
    with tracing.span("parent") as parent:
        ctx = tracing.current_ids()

        def work():
            tracing.emit_span("child", time.time(), 0.001,
                              role=ctx[2], parent=ctx[1],
                              trace_id=ctx[0])

        t = threading.Thread(target=work)
        t.start()
        t.join()
    spans = {s["name"]: s for s in tracing.spans_for("threaded")}
    assert spans["child"]["parentId"] == parent.span_id


# -- the jobs' ring -----------------------------------------------------------

def _flood(n, trace_id="client"):
    for i in range(n):
        with tracing.span(f"GET /c{i}", trace_id=trace_id):
            pass


def test_a_job_span_keeps_its_trace_out_of_reach_of_other_spans(
        monkeypatch):
    """Everything recorded on a trace once a `job=True` span opened on
    it, on this thread or (by its ids) on another, outlives a flood of
    other spans that turns the ring over; what came before the mark
    does not."""
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_BUFFER", "64")
    with tracing.span("before", trace_id="job-1"):
        pass
    with tracing.span("POST /admin/receive_file", trace_id="job-1",
                      job=True) as server:
        tracing.emit_span_batch([{"name": "receive.socket",
                                  "start": server.start, "duration": 0.01}])
        ctx = tracing.current_ids()
    t = threading.Thread(target=lambda: tracing.emit_span(
        "stage.h2d", time.time(), 0.001, role=ctx[2], parent=ctx[1],
        trace_id=ctx[0]))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    _flood(200)
    names = [s["name"] for s in tracing.spans_for("job-1")]
    assert sorted(names) == ["POST /admin/receive_file", "receive.socket",
                             "stage.h2d"]
    # the other ring still turns over at its size, the jobs' ring too
    assert len(tracing.spans_for("client")) == 64
    for i in range(100):
        with tracing.span(f"job {i}", trace_id=f"job-{i + 2}", job=True):
            pass
    assert len([s for s in tracing.recent_spans(1000)
                if s["name"].startswith("job ")]) == 64


def test_both_rings_read_merged_by_start():
    with tracing.span("job", trace_id="t-job", job=True):
        pass
    with tracing.span("client", trace_id="t-client"):
        pass
    tracing.emit_span("early", 1.0, 0.0, trace_id="t-client")
    tracing.emit_span("earlier", 0.5, 0.0, trace_id="t-job")
    got = [s["name"] for s in tracing.recent_spans(10)]
    assert got == ["earlier", "early", "job", "client"]
    assert [s["name"] for s in tracing.recent_spans(2)] == ["job",
                                                             "client"]


def test_ingested_spans_go_to_the_jobs_ring(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_BUFFER", "32")
    docs = [{"traceId": "ing-job", "spanId": f"w{i}", "name": "ec.push",
             "start": float(i)} for i in range(20)]
    assert tracing.ingest(docs) == 20
    _flood(100)
    assert len(tracing.spans_for("ing-job")) == 20
    assert tracing.ingest(docs) == 0       # redelivery, either ring


def test_the_marks_are_bounded_and_reset_forgets_them(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_BUFFER", "16")
    for i in range(40):
        with tracing.span("j", trace_id=f"m-{i}", job=True):
            pass
    assert len(tracing._job_traces) == 16
    assert "m-39" in tracing._job_traces and "m-0" not in tracing._job_traces
    tracing.reset_buffer()
    assert not tracing._job_traces and tracing.recent_spans(100) == []


def test_many_threads_lose_no_span_to_the_two_rings(monkeypatch):
    """More threads than cores record on job and client traces at once,
    the switch interval shortened: every span lands, in its ring."""
    import sys
    monkeypatch.setenv("SEAWEEDFS_TPU_TRACE_BUFFER", "100000")
    threads, each = 4 * (os.cpu_count() or 2), 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for i in range(each):
                with tracing.span("s", trace_id=f"w{k}-{i % 3}",
                                  job=(i % 3 == 0)):
                    pass

        ts = [threading.Thread(target=work, args=(k,))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert len(tracing._job_buffer) == threads * len(range(0, each, 3))
    assert len(tracing.recent_spans(10 ** 6)) == threads * each
