"""One whole trace per EC job (ISSUE 25): spans where the job's work
happens, carried across the bulk transfers, kept past the poll chatter;
and the staging ledger's payload bytes, with the hand-off's waits read
from the pipeline's own stage spans (ISSUE 29)."""

import glob
import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import operation, tracing
from seaweedfs_tpu.ops import staging
from seaweedfs_tpu.ops.rs_jax import ReedSolomonJax, gf_apply_matrix_words
from seaweedfs_tpu.ops.rs_jax import pack_words
from seaweedfs_tpu.plugin import AdminServer, PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler
from seaweedfs_tpu.server.httpd import (HttpServer, http_bytes,
                                        http_download, http_json,
                                        http_upload)
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.request_id import (reset_request_id,
                                           set_request_id)

FILES_PUSHED = 14 + 2 * 3      # 14 shards, .ecx and .vif once a target


# -- a three-server cluster runs one erasure_coding job -----------------------

@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """(admin, servers, job detail, merged spans of the job's trace,
    volume dirs): one `erasure_coding` job run to its end."""
    tmp = tmp_path_factory.mktemp("ecjob")
    master = MasterServer(volume_size_limit_mb=1).start()
    servers, dirs = [], []
    for i in range(3):
        d = tmp / f"vol{i}"
        d.mkdir()
        dirs.append(str(d))
        servers.append(VolumeServer([str(d)], master.url,
                                    pulse_seconds=0.3).start())
    admin = AdminServer(master.url, detection_interval=3600).start()
    worker = PluginWorker(
        admin.url, master.url, str(tmp / "worker"),
        handlers=[EcEncodeHandler(fullness_ratio=0.5, backend="jax")],
        poll_wait=0.5).start()
    time.sleep(0.6)
    rng = np.random.default_rng(25)
    for _ in range(12):
        fid = operation.submit(master.url, rng.integers(
            0, 256, 50_000, dtype=np.uint8).tobytes())
    vid = int(fid.split(",")[0])
    job_id = http_json("POST", f"{admin.url}/maintenance/submit_job", {
        "jobType": "erasure_coding",
        "params": {"volumeId": vid, "collection": ""}})["jobId"]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        detail = http_json("GET",
                           f"{admin.url}/maintenance/job?id={job_id}")
        if detail["status"] in ("done", "failed"):
            break
        time.sleep(0.05)
    assert detail["status"] == "done", detail
    yield admin, servers, detail, dirs
    worker.stop()
    admin.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def _trace(admin, servers, detail) -> "list[dict]":
    """The admin's and the volume roles' /debug/traces of the job,
    merged by span id (shell/commands.collect_trace's rule)."""
    merged = {}
    for node in [admin.url] + [vs.url for vs in servers]:
        got = http_json("GET", f"{node}/debug/traces?request_id="
                               f"{detail['requestId']}")["spans"]
        for s in got:
            merged.setdefault(s["spanId"], s)
    return sorted(merged.values(), key=lambda s: s["start"])


def _one(spans, name):
    got = [s for s in spans if s["name"] == name]
    assert len(got) == 1, (name, len(got))
    return got[0]


def test_job_trace_names_every_step_under_the_job_span(job):
    admin, servers, detail, _dirs = job
    spans = _trace(admin, servers, detail)
    root = _one(spans, "job:erasure_coding")
    for name in ("ec.mark_readonly", "ec.pull", "ec.sort_index",
                 "ec.encode", "ec.distribute", "ec.delete_source"):
        s = _one(spans, name)
        assert s["parentId"] == root["spanId"], name
        assert s["role"] == "worker" and s["traceId"] == root["traceId"]
    dist = _one(spans, "ec.distribute")
    mounts = [s for s in spans if s["name"] == "ec.mount"]
    assert len(mounts) == 3
    assert {s["attrs"]["target"] for s in mounts} == \
        {vs.url for vs in servers}
    assert all(s["parentId"] == dist["spanId"] for s in mounts)
    # the steps are laid end to end on the wall clock, inside the job
    order = [_one(spans, n) for n in (
        "ec.mark_readonly", "ec.pull", "ec.sort_index", "ec.encode",
        "ec.distribute", "ec.delete_source")]
    for a, b in zip(order, order[1:]):
        assert a["start"] + a["durationMs"] / 1e3 <= b["start"] + 1e-3
    assert root["start"] <= order[0]["start"]


def test_pull_and_pushes_carry_their_bytes(job):
    admin, servers, detail, dirs = job
    spans = _trace(admin, servers, detail)
    pull = _one(spans, "ec.pull")
    served = [s for s in spans if s["name"] == "GET /admin/volume_file"]
    assert len(served) == 2                       # .dat and .idx
    assert all(s["parentId"] == pull["spanId"] and
               s["traceId"] == pull["traceId"] and s["role"] == "volume"
               for s in served)
    assert pull["attrs"]["bytes"] == \
        sum(s["attrs"]["bytes"] for s in served) > 600_000
    pushes = [s for s in spans if s["name"] == "ec.push"]
    assert len(pushes) == FILES_PUSHED
    on_disk = sum(os.path.getsize(p) for d in dirs for ext in (
        ".ec[0-9][0-9]", ".ecx", ".vif")
        for p in glob.glob(os.path.join(d, "*" + ext)))
    assert sum(s["attrs"]["bytes"] for s in pushes) == on_disk
    dist = _one(spans, "ec.distribute")
    push_seconds = dist["attrs"]["pushSeconds"]
    from_dat = sum(s["attrs"]["bytes"] for s in pushes
                   if s["attrs"]["source"] == "dat")
    assert dist["attrs"] == {"serversAtStart": 3, "servers": 3,
                             "bytes": on_disk, "streams": 3,
                             "pushSeconds": push_seconds,
                             "bytesFromDat": from_dat}
    # the ten data shards are sent out of the .dat (ISSUE 35)
    assert 0 < from_dat == sum(
        os.path.getsize(p) for d in dirs
        for p in glob.glob(os.path.join(d, "*.ec0[0-9]")))
    assert 0 < push_seconds <= dist["durationMs"] / 1e3
    exts = sorted(s["attrs"]["ext"] for s in pushes)
    assert exts == sorted([f".ec{i:02d}" for i in range(14)]
                          + [".ecx", ".vif"] * 3)
    for s in pushes:
        assert s["parentId"] == dist["spanId"]
        assert 0 <= s["attrs"]["cpuSeconds"] <= s["durationMs"] / 1e3 + 0.05
        # what http_upload did on this plain-HTTP cluster (ISSUE 26)
        assert s["attrs"]["via"] == "sendfile"


def test_each_push_holds_the_receivers_span(job):
    admin, servers, detail, _dirs = job
    spans = _trace(admin, servers, detail)
    pushes = {s["spanId"]: s for s in spans if s["name"] == "ec.push"}
    received = [s for s in spans
                if s["name"] == "POST /admin/receive_file"]
    assert len(received) == FILES_PUSHED
    assert {s["parentId"] for s in received} == set(pushes)
    for s in received:
        push = pushes[s["parentId"]]
        assert s["traceId"] == push["traceId"] == detail["requestId"]
        assert s["role"] == "volume"
        assert s["attrs"]["bytes"] == push["attrs"]["bytes"]
        # a request that carries a trace parent pays the clock
        assert s["attrs"]["cpuSeconds"] >= 0
        assert s["attrs"]["status"] == 200


def test_staged_windows_hang_under_the_encode_span(job):
    admin, servers, detail, _dirs = job
    spans = _trace(admin, servers, detail)
    enc = _one(spans, "ec.encode")
    h2d = [s for s in spans if s["name"] == "stage.h2d"]
    d2h = [s for s in spans if s["name"] == "stage.d2h"]
    assert h2d and len(h2d) == len(d2h)
    for s in h2d + d2h:
        assert s["parentId"] == enc["spanId"] and s["role"] == "worker"
        assert s["attrs"]["bytes"] > 0
        assert enc["start"] - 1e-3 <= s["start"] <= \
            enc["start"] + enc["durationMs"] / 1e3 + 1e-3
    assert all(0 <= s["attrs"]["packSeconds"] <= s["durationMs"] / 1e3
               + 1e-6 for s in h2d)


def test_five_thousand_status_polls_do_not_evict_the_jobs_spans(job):
    admin, servers, detail, _dirs = job
    before = {s["spanId"] for s in _trace(admin, servers, detail)}
    assert tracing.buffer_size() < 5000
    for _ in range(5000):
        http_bytes("GET",
                   f"{admin.url}/maintenance/job?id={detail['jobId']}")
    after = {s["spanId"] for s in _trace(admin, servers, detail)}
    assert before <= after
    recent = tracing.recent_spans(100000)
    assert not [s for s in recent if s["name"] == "GET /maintenance/job"]


def test_trace_show_renders_one_tree_down_to_the_receivers(job):
    from seaweedfs_tpu.shell.commands import render_trace
    admin, servers, detail, _dirs = job
    text = render_trace(_trace(admin, servers, detail))
    lines = text.splitlines()
    depth = {}
    for ln in lines[1:]:
        name = ln.split("ms ", 1)[1].split("  ")[0]
        depth.setdefault(name, len(ln) - len(ln.lstrip()))
    assert depth["job:erasure_coding"] < depth["ec.distribute"] < \
        depth["ec.push"] < depth["POST /admin/receive_file"]
    assert depth["ec.encode"] < depth["stage.h2d"]
    pushes = [ln for ln in lines if "ms ec.push  " in ln]
    assert len(pushes) == FILES_PUSHED
    assert all(" via=sendfile " in ln and " ext=." in ln and
               " cpuSeconds=" in ln for ln in pushes)
    (dist,) = [ln for ln in lines if "ms ec.distribute  " in ln]
    assert " streams=3 " in dist and " pushSeconds=" in dist


# -- quiet routes -------------------------------------------------------------

@pytest.fixture
def quiet_server():
    http = HttpServer("127.0.0.1", 0)
    http.role = "testrole"
    seen = {}

    def status(req):
        # ids still propagate: a child opened here hangs under the
        # quiet span, on the caller's trace
        with tracing.span("inner") as sp:
            seen["inner"] = (sp.trace_id, sp.parent_id)
        return 200, {"ok": True}

    def boom(req):
        raise RuntimeError("kaput")

    def refuse(req):
        return 503, {"error": "not now"}

    def slow(req):
        time.sleep(0.03)
        return 200, {"ok": True}

    http.route("GET", "/status", status, quiet=True)
    http.route("GET", "/boom", boom, quiet=True)
    http.route("GET", "/refuse", refuse, quiet=True)
    http.route("GET", "/slow", slow, quiet=True)
    http.route("GET", "/loud", status)
    http.start()
    tracing.reset_buffer()
    yield http, seen
    http.stop()


def _names(trace_id, spans=1):
    """Names recorded under the trace, once `spans` of them are there:
    a server span closes after its reply is on the wire, so the caller
    can be back before it."""
    deadline = time.monotonic() + 2
    while True:
        got = sorted(s["name"] for s in tracing.spans_for(trace_id))
        if len(got) >= spans or time.monotonic() > deadline:
            return got
        time.sleep(0.01)


def _get(http, path, rid):
    token = set_request_id(rid)
    try:
        return http_bytes("GET", f"{http.url}{path}")[0]
    finally:
        reset_request_id(token)


def test_a_quiet_route_propagates_ids_and_records_nothing(quiet_server):
    http, seen = quiet_server
    assert _get(http, "/status", "q-1") == 200
    assert _names("q-1") == ["inner"]
    trace_id, parent = seen["inner"]
    assert trace_id == "q-1" and parent      # under the unrecorded span
    assert _get(http, "/loud", "q-2") == 200
    assert _names("q-2", spans=2) == ["GET /loud", "inner"]


@pytest.mark.parametrize("path,status", [("/boom", 500), ("/refuse", 503)])
def test_a_failing_quiet_route_still_records(quiet_server, path, status):
    http, _seen = quiet_server
    assert _get(http, path, "q-err") == status
    assert _names("q-err") == [f"GET {path}"]
    assert tracing.spans_for("q-err")[0]["attrs"]["status"] == status


def test_a_slow_quiet_route_records_where_a_threshold_is_set(
        quiet_server, monkeypatch):
    http, _seen = quiet_server
    assert _get(http, "/slow", "q-slow-0") == 200
    time.sleep(0.05)
    assert tracing.spans_for("q-slow-0") == []     # no threshold set
    monkeypatch.setenv("SEAWEEDFS_TPU_SLOW_MS", "20")
    assert _get(http, "/slow", "q-slow-1") == 200
    assert _names("q-slow-1") == ["GET /slow"]
    assert _get(http, "/status", "q-fast") == 200
    assert _names("q-fast") == ["inner"]


# -- the bulk transfers carry the trace ---------------------------------------

@pytest.fixture
def file_server(tmp_path):
    http = HttpServer("127.0.0.1", 0)
    http.role = "testrole"
    blob = os.urandom(300_000)
    src = tmp_path / "served.bin"
    src.write_bytes(blob)

    def give(req):
        f = open(src, "rb")
        return 200, (f, {"Content-Length": str(len(blob))})

    def take(req):
        n = sum(len(c) for c in req.stream_body())
        return 200, {"bytes": n}

    http.route("GET", "/give", give)
    http.route("POST", "/take", take)
    http.start()
    tracing.reset_buffer()
    yield http, tmp_path, blob
    http.stop()


@pytest.mark.parametrize("way", ["download", "upload"])
def test_bulk_transfer_hangs_the_servers_span_under_the_callers(
        file_server, way):
    http, tmp_path, blob = file_server
    token = set_request_id(f"bulk-{way}")
    try:
        with tracing.span("caller", role="worker") as caller:
            if way == "download":
                st, _ = http_download(f"{http.url}/give",
                                      str(tmp_path / "got.bin"))
                assert (tmp_path / "got.bin").read_bytes() == blob
            else:
                st, _, _ = http_upload("POST", f"{http.url}/take",
                                       str(tmp_path / "served.bin"))
            assert st == 200
    finally:
        reset_request_id(token)
    name = "GET /give" if way == "download" else "POST /take"
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline and \
            name not in _names(f"bulk-{way}"):
        time.sleep(0.01)
    served = _one(tracing.spans_for(f"bulk-{way}"), name)
    assert served["parentId"] == caller.span_id
    assert served["attrs"]["bytes"] == len(blob)
    assert served["attrs"]["cpuSeconds"] >= 0


# -- the staging ledger -------------------------------------------------------

@pytest.fixture
def small_windows(monkeypatch):
    """Toy rows, three to a window of RS(10,4), one device to be seen."""
    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    monkeypatch.setattr(staging, "WINDOW_BYTES", 32 * 4096)
    monkeypatch.setattr(staging, "encode_shardings",
                        lambda: (None, None, 1))
    monkeypatch.setattr(ec_encoder, "SMALL_BLOCK_SIZE", 4096)
    monkeypatch.setattr(ec_context, "SMALL_BLOCK_SIZE", 4096)
    staging.reset_aggregate()
    return monkeypatch


def _encode_traced(tmp_path, rid: str, progress=None) -> dict:
    """A 13-row toy volume (5 work items, the last of one short row)
    encoded under an `ec.encode` span; the stage spans by name."""
    from seaweedfs_tpu.storage.erasure_coding import ec_encoder
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext
    dat_size = 12 * 10 * 4096 + 12_345
    (tmp_path / "v.dat").write_bytes(np.random.default_rng(3).integers(
        0, 256, dat_size, dtype=np.uint8).tobytes())
    tracing.reset_buffer()
    token = set_request_id(rid)
    try:
        with tracing.span("ec.encode", role="worker"):
            ec_encoder.write_ec_files(str(tmp_path / "v"),
                                      ECContext(backend="jax"),
                                      progress=progress)
    finally:
        reset_request_id(token)
    return {s["name"]: s for s in tracing.spans_for(rid)
            if s["name"].startswith("encode.")}


def test_payload_bytes_are_the_volumes_and_nothing_is_packed(
        small_windows, tmp_path):
    """A short tail: 13 small rows at 3 a window are 5 work items, the
    last of one row sent in the window's shape, and the last row is
    short."""
    stages = _encode_traced(tmp_path, "ledger-1")
    snap = staging.snapshot()
    assert snap["payload_bytes"] == 12 * 10 * 4096 + 12_345
    assert snap["h2d_bytes"] == 15 * 10 * 4096 > snap["payload_bytes"]
    assert snap["pack_seconds"] == 0 < snap["h2d_seconds"]
    assert snap["launches"] == snap["windows"] == 5
    assert all(stages[n]["attrs"]["calls"] == 5 for n in
               ("encode.read", "encode.codec", "encode.write"))


def _launch(data):
    rs = ReedSolomonJax(10, 4)
    flat = pack_words(np.ascontiguousarray(data))
    return staging.WindowedLaunch(rs._parity_rows, flat,
                                  gf_apply_matrix_words, 4,
                                  data.shape[1])


def test_payload_defaults_to_the_batch_less_its_padding(small_windows):
    data = np.random.default_rng(1).integers(
        0, 256, size=(10, 4001), dtype=np.uint8)     # 4001: word padding
    out = _launch(data).materialize()
    assert out.shape == (4, 4001)
    snap = staging.snapshot()
    assert snap["payload_bytes"] == 10 * 4001
    assert snap["h2d_bytes"] == 10 * 4004


def test_a_slow_writer_shows_in_the_write_stage(small_windows, tmp_path):
    """What `slot_wait_seconds` stood for: the consumer is the slower
    side.  The staging ledger has no wait to count; `encode.write` is
    busy its whole length and `encode.codec` is not."""
    stages = _encode_traced(tmp_path, "slow-writer",
                            progress=lambda _d, _t: time.sleep(0.03))
    write, codec = (stages[n] for n in ("encode.write", "encode.codec"))
    assert write["attrs"]["busySeconds"] >= 5 * 0.03
    assert write["attrs"]["busySeconds"] >= 0.8 * write["durationMs"] / 1e3
    assert codec["attrs"]["busySeconds"] < write["attrs"]["busySeconds"]
    snap = staging.snapshot()
    assert snap["slot_wait_seconds"] == snap["ready_wait_seconds"] == 0


def test_a_slow_put_shows_in_the_codec_stage(small_windows, tmp_path):
    """What `ready_wait_seconds` stood for: the staging side is the
    slower one.  The puts run on the compute stage, so a device
    codec's `encode.codec` says its busy seconds, and they hold the
    ledger's h2d seconds."""
    import jax
    put = jax.device_put

    def slow_put(x, *a, **kw):
        time.sleep(0.03)
        return put(x, *a, **kw)
    small_windows.setattr(jax, "device_put", slow_put)
    stages = _encode_traced(tmp_path, "slow-put")
    snap = staging.snapshot()
    assert snap["h2d_seconds"] >= 5 * 0.03
    assert stages["encode.codec"]["attrs"]["busySeconds"] >= \
        snap["h2d_seconds"]
    assert snap["slot_wait_seconds"] == snap["ready_wait_seconds"] == 0


def test_a_launch_emits_its_windows_under_the_callers_span(small_windows):
    tracing.reset_buffer()
    data = np.random.default_rng(4).integers(
        0, 256, size=(10, 4096), dtype=np.uint8)
    token = set_request_id("launch-1")
    try:
        with tracing.span("ec.encode", role="worker") as enc:
            launch = _launch(data)
        # consumed on another thread, as the encoder's writer does
        t = threading.Thread(target=launch.materialize)
        t.start()
        t.join()
    finally:
        reset_request_id(token)
    spans = tracing.spans_for("launch-1")
    windows = staging.snapshot()["windows"]
    for name, per in (("stage.h2d", 10 * 4), ("stage.d2h", 4 * 4)):
        got = [s for s in spans if s["name"] == name]
        assert len(got) == windows
        assert all(s["parentId"] == enc.span_id and s["role"] == "worker"
                   for s in got)
        assert sum(s["attrs"]["bytes"] for s in got) == per * 1024
    # nobody tracing: no span with a made-up trace id
    tracing.reset_buffer()
    _launch(data).materialize()
    assert tracing.recent_spans(1000) == []
