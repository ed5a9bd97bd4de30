"""Cost-attribution & flight-recorder plane (ISSUE 15).

Unit half: StageTrack's per-stage CPU beside wall (thread_time
sampled on whichever thread runs the stage, so the use_track re-bind
charges pool-thread CPU to the request), the FlightRecorder ring
(cap under concurrent load, record schema, error/deadline/shed
capture triggers, slow-threshold self-limiting + rate cap, kill
switch), the scheduler-delay probe, and the /proc process-tree
aggregation behind process_tree_cpu_seconds.

Front half: both HTTP fronts capture into the ring — a handler
exception as verdict=error, an expired ingress budget as
verdict=deadline with the budget doc, a QoS rejection as
verdict=shed — and /debug/slow serves + clears it.
"""

import json
import os
import threading
import time

import pytest

from seaweedfs_tpu import profiling, stats
from seaweedfs_tpu.server.httpd import HttpServer, http_bytes, \
    http_json
from seaweedfs_tpu.util import deadline


def _burn(ms: float) -> None:
    """Burn ~ms of actual CPU on the calling thread."""
    t0 = time.thread_time()
    while (time.thread_time() - t0) * 1e3 < ms:
        sum(i * i for i in range(200))


# -- stage cpu beside wall ------------------------------------------------

def test_stage_cpu_beside_wall_histograms(monkeypatch):
    # pin the attribution sample: this test IS about the cpu clock
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "1")
    m = stats.Metrics("cputest")
    trk = profiling.StageTrack("cputest_write", metrics=m)
    with profiling.use_track(trk):
        with profiling.stage("busy"):
            _burn(8.0)
        with profiling.stage("parked"):
            time.sleep(0.03)
    trk.finish()
    busy = trk.stages["busy"]
    parked = trk.stages["parked"]
    # busy: cpu tracks wall; parked: wall is almost all wait
    assert busy[3] >= 0.004, busy
    assert parked[0] >= 0.025 and parked[3] < 0.010, parked
    txt = m.render()
    assert "cputest_write_stage_seconds_bucket" in txt
    assert "cputest_write_stage_cpu_seconds_bucket" in txt
    assert 'stage="busy"' in txt and 'stage="total"' in txt


def test_thread_time_rebind_charges_pool_thread_cpu(monkeypatch):
    """The upload-pool shape: a stage timed on a FOREIGN thread via
    use_track must charge that thread's CPU to the request — and the
    track total must include it on top of the owner's own burn."""
    # pin the attribution sample: this test IS about the cpu clock
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "1")
    trk = profiling.StageTrack("rebind_write")

    def pool_worker() -> None:
        with profiling.use_track(trk):
            with profiling.stage("upload"):
                _burn(10.0)

    t = threading.Thread(target=pool_worker)
    t.start()
    t.join()
    _burn(5.0)          # owner-thread work between the stages
    trk.finish()
    summary = profiling.take_last_summary()
    up = summary["stages"]["upload"]
    assert up["cpuMs"] >= 5.0, summary
    # total cpu = owner thread-time (>=5ms burned here) + the pool
    # thread's stage cpu (>=10ms) — the whole request's CPU bill
    assert summary["cpuMs"] >= up["cpuMs"] + 4.0, summary


def test_cpu_attribution_sampling(monkeypatch):
    """Budget-less tracks pay the thread-CPU clock only every Nth
    (SEAWEEDFS_TPU_CPU_SAMPLE); deadline-carrying ones always; 0
    disables.  An unsampled summary reports wall with the cpu keys
    ABSENT — never a fake zero."""
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "1000000")
    profiling.cpu_attr_tick()   # burn any aligned tick (fresh proc)
    trk = profiling.StageTrack("sampletest_write")
    with profiling.use_track(trk):
        with profiling.stage("work"):
            _burn(1.0)
    trk.finish()
    s = profiling.take_last_summary()
    assert s["cpuSampled"] is False, s
    assert "cpuMs" not in s and "cpuMs" not in s["stages"]["work"]
    assert s["stages"]["work"]["wallMs"] > 0
    # a deadline-carrying request always draws the sample
    from seaweedfs_tpu.util import deadline
    with deadline.scope(30.0):
        trk = profiling.StageTrack("sampletest_write")
        with profiling.use_track(trk):
            with profiling.stage("work"):
                _burn(1.0)
        trk.finish()
    s = profiling.take_last_summary()
    assert s["cpuSampled"] is True and s["cpuMs"] > 0, s
    assert "cpuMs" in s["stages"]["work"]
    # 0 = attribution off entirely, budget or not
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "0")
    with deadline.scope(30.0):
        trk = profiling.StageTrack("sampletest_write")
        trk.finish()
    assert profiling.take_last_summary()["cpuSampled"] is False
    # the FRONT helper honors the kill switch even for deadline-
    # carrying requests — a deadline-default cluster must not pay
    # the trapped clock syscall under a knob documented as 'never'
    assert profiling.cpu_attr_front(True) is False
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "1")
    assert profiling.cpu_attr_front(True) is True


def test_take_last_summary_clears_on_read():
    trk = profiling.StageTrack("clear_write")
    with profiling.use_track(trk):
        with profiling.stage("s"):
            pass
    trk.finish()
    assert profiling.take_last_summary() is not None
    assert profiling.take_last_summary() is None


def test_flight_note_prefers_track_falls_back_to_armed_notes():
    # no track, no armed notes: a silent no-op
    profiling.flight_note("orphan", 1)
    assert profiling.take_flight_notes() is None
    # front-armed notes dict catches notes without a track
    profiling.arm_flight_notes()
    profiling.flight_note("hedge", {"won": True})
    assert profiling.take_flight_notes() == {"hedge": {"won": True}}
    assert profiling.take_flight_notes() is None   # cleared on read
    # an active track wins over armed notes
    trk = profiling.StageTrack("note_write")
    profiling.arm_flight_notes()
    with profiling.use_track(trk):
        profiling.flight_note("nativePlane", "write")
    assert trk.notes == {"nativePlane": "write"}
    # the armed dict stayed empty (the track won) — normalized to None
    assert profiling.take_flight_notes() is None


# -- flight recorder ring -------------------------------------------------

def test_ring_cap_under_concurrent_load():
    r = profiling.FlightRecorder(size=16)

    def feeder(seed: int) -> None:
        for i in range(200):
            r.observe("filer", "GET", f"/t{seed}/{i}", 500,
                      wall_s=0.001)

    threads = [threading.Thread(target=feeder, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = r.snapshot()
    assert len(snap["records"]) == 16
    assert snap["captured"] == 800
    assert snap["ringSize"] == 16


def test_record_schema_complete():
    r = profiling.FlightRecorder(size=8)
    rec = r.observe(
        "filer", "PUT", "/f/a.bin", 201, wall_s=0.25, cpu_s=0.01,
        verdict="deadline", trace_id="",
        deadline={"budgetMs": 200, "remainingMs": 0},
        stages={"totalMs": 250.0, "cpuMs": 10.0,
                "stages": {"meta": {"wallMs": 240.0, "cpuMs": 2.0,
                                    "calls": 1}}},
        notes={"chunks": 3})
    for key in ("ts", "role", "method", "path", "status", "verdict",
                "wallMs", "cpuMs", "waitMs", "traceId", "deadline",
                "stages", "notes"):
        assert key in rec, key
    assert rec["waitMs"] == pytest.approx(240.0)
    assert json.loads(json.dumps(rec)) == rec     # wire-serializable


def test_error_deadline_shed_capture_while_tracker_cold():
    """The precious verdicts are never threshold- or rate-gated: a
    cold recorder (no latency history) still captures them."""
    r = profiling.FlightRecorder(size=8)
    assert r.threshold() is None
    assert r.observe("s3", "GET", "/e", 500, wall_s=0.001) is not None
    assert r.observe("s3", "GET", "/d", 504, wall_s=0.001,
                     verdict="deadline") is not None
    assert r.observe("s3", "GET", "/s", 503, wall_s=0.001,
                     verdict="shed") is not None
    # a fast ok request is NOT captured while the threshold warms
    assert r.observe("s3", "GET", "/ok", 200, wall_s=0.001) is None
    verdicts = [x["verdict"] for x in r.snapshot()["records"]]
    assert verdicts == ["error", "deadline", "shed"]


def test_slow_threshold_floor_and_capture():
    r = profiling.FlightRecorder(size=8)
    for _ in range(40):
        r.observe("filer", "GET", "/fast", 200, wall_s=0.001)
    # p95 of 1ms traffic clamps to the SLOW_MIN_MS floor (25ms)
    assert r.threshold() == pytest.approx(0.025)
    assert r.observe("filer", "GET", "/slow", 200,
                     wall_s=0.050)["verdict"] == "slow"
    assert r.observe("filer", "GET", "/fast", 200,
                     wall_s=0.001) is None


def test_slow_capture_rate_cap(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_SLOW_CAPTURE_PER_S", "3")
    r = profiling.FlightRecorder(size=64)
    # pin the rate-window clock: on a loaded box the wall-clock 1s
    # window can roll mid-loop and admit a fourth capture
    r._now = lambda: 1000.0
    for _ in range(40):
        r.observe("filer", "GET", "/warm", 200, wall_s=0.001)
    for i in range(10):
        r.observe("filer", "GET", f"/slow{i}", 200, wall_s=0.060)
    snap = r.snapshot()
    slows = [x for x in snap["records"] if x["verdict"] == "slow"]
    assert len(slows) == 3
    assert snap["droppedRateLimited"] == 7
    # error verdicts ignore the cap
    assert r.observe("filer", "GET", "/e", 500,
                     wall_s=0.001) is not None


def test_recorder_kill_switch(monkeypatch):
    assert profiling.recorder_enabled()
    monkeypatch.setenv("SEAWEEDFS_TPU_FLIGHT_RECORDER", "0")
    assert not profiling.recorder_enabled()


def test_reset_forgets_records_and_history():
    r = profiling.FlightRecorder(size=8)
    for _ in range(40):
        r.observe("filer", "GET", "/x", 500, wall_s=0.001)
    assert r.snapshot()["records"]
    r.reset()
    snap = r.snapshot()
    assert snap["records"] == [] and snap["captured"] == 0
    assert snap["thresholdMs"] is None


# -- scheduler probe & process tree ---------------------------------------

def test_sched_probe_ticks_and_ratio():
    p = profiling.SchedProbe(interval_s=0.005)
    p.start()
    try:
        # 12 ticks is ~60ms of ideal probe time; the generous deadline
        # absorbs an oversubscribed box that deschedules the probe
        # thread for whole seconds — the loop exits the moment the
        # ticks land, so the happy path stays fast
        deadline_t = time.monotonic() + 30.0
        while p.ticks < 12 and time.monotonic() < deadline_t:
            time.sleep(0.01)
    finally:
        p.stop()
    assert p.ticks >= 12
    assert p.ratio >= 0.0
    assert "gil_wait_ratio" in stats.PROCESS.render()


@pytest.mark.skipif(not os.path.isdir("/proc"),
                    reason="needs /proc")
def test_process_tree_gauges_cover_children():
    import subprocess
    child = subprocess.Popen(["sleep", "30"])
    try:
        tree = stats._proc_tree_sample()
        assert tree is not None
        cpu, rss, count = tree
        assert cpu > 0 and rss > 0
        assert count >= 2          # self + the sleep child
        txt = stats.render_process()
        assert "process_tree_cpu_seconds" in txt
        assert "process_tree_rss_bytes" in txt
        assert "process_tree_procs" in txt
    finally:
        child.kill()
        child.wait()


def test_process_tree_stale_root_degrades_to_self(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_TREE_ROOT", "999999999")
    tree = stats._proc_tree_sample()
    if tree is None:
        pytest.skip("no /proc")
    assert tree[2] >= 1            # fell back to this process


# -- the fronts capture into the ring -------------------------------------

@pytest.fixture()
def front(monkeypatch):
    # pin a FRESH recorder: the module-global singleton accumulates
    # latency history (and with it a warmed slow threshold) from
    # whatever earlier tests and background drains observed, and
    # these tests assert on exact capture sets
    monkeypatch.setattr(profiling, "_recorder",
                        profiling.FlightRecorder())
    h = HttpServer()
    h.role = "flighttest"

    def boom(req):
        raise RuntimeError("kaboom")

    def ok(req):
        return 200, {"ok": True}

    h.route("GET", "/boom", boom)
    h.route("GET", "/ok", ok)
    h.start()
    yield h
    h.stop()


def _records_for(path: str) -> "list[dict]":
    return [r for r in
            profiling.flight_recorder().snapshot()["records"]
            if r.get("path") == path]


def _wait_records(path: str, timeout: float = 30.0) -> "list[dict]":
    """Poll for a capture: the front observes AFTER the response is
    flushed, so the client can read the snapshot before the handler
    thread reaches the recorder.  The window is deliberately wide —
    it only matters on a degraded box where the handler thread is
    starved; the poll returns as soon as the record appears."""
    deadline = time.time() + timeout
    recs = _records_for(path)
    while not recs and time.time() < deadline:
        time.sleep(0.01)
        recs = _records_for(path)
    return recs


def test_threaded_front_captures_error(front):
    st, _, _ = http_bytes("GET", f"{front.url}/boom", timeout=5)
    assert st == 500
    recs = _wait_records("/boom")
    assert recs and recs[0]["verdict"] == "error"
    assert recs[0]["status"] == 500
    assert recs[0]["wallMs"] > 0
    assert recs[0]["traceId"]


def test_threaded_front_captures_expired_deadline(front):
    st, _, _ = http_bytes("GET", f"{front.url}/ok", None,
                          {deadline.HEADER: "0"}, timeout=5)
    assert st == 504
    recs = _wait_records("/ok")
    assert recs and recs[0]["verdict"] == "deadline"
    assert recs[0]["deadline"]["budgetMs"] == 0


def test_threaded_front_captures_qos_shed(front):
    front.admission = lambda req: ((503, {"error": "qos"}), None)
    try:
        st, _, _ = http_bytes("GET", f"{front.url}/ok", timeout=5)
    finally:
        front.admission = None
    assert st == 503
    recs = [r for r in _wait_records("/ok")
            if r["verdict"] == "shed"]
    assert recs and recs[0]["status"] == 503


def test_front_kill_switch_stops_capture(front, monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_FLIGHT_RECORDER", "0")
    profiling.flight_recorder().reset()
    st, _, _ = http_bytes("GET", f"{front.url}/boom", timeout=5)
    assert st == 500
    time.sleep(0.1)   # give the handler thread its post-flush beat
    assert _records_for("/boom") == []


@pytest.fixture()
def async_front_server(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_ASYNC_FRONT", "filer")
    # fresh recorder for the same reason as the `front` fixture
    monkeypatch.setattr(profiling, "_recorder",
                        profiling.FlightRecorder())
    h = HttpServer()
    h.role = "filer"

    def boom(req):
        raise RuntimeError("async kaboom")

    h.route("GET", "/aboom", boom)
    h.start()
    assert h._async is not None
    yield h
    h.stop()


def test_async_front_captures_error_and_deadline(async_front_server):
    h = async_front_server
    st, _, _ = http_bytes("GET", f"{h.url}/aboom", timeout=5)
    assert st == 500
    recs = _wait_records("/aboom")
    assert recs and recs[0]["verdict"] == "error"
    st, _, _ = http_bytes("GET", f"{h.url}/aboom", None,
                          {deadline.HEADER: "0"}, timeout=5)
    assert st == 504
    deadline_t = time.time() + 30.0
    while not any(r["verdict"] == "deadline"
                  for r in _records_for("/aboom")) \
            and time.time() < deadline_t:
        time.sleep(0.01)
    assert any(r["verdict"] == "deadline"
               for r in _records_for("/aboom"))


def test_debug_slow_serves_and_clears(front):
    from seaweedfs_tpu.server import debug as debug_mod
    debug_mod.install_debug_routes(front)
    http_bytes("GET", f"{front.url}/boom", timeout=5)
    assert _wait_records("/boom")
    doc = http_json("GET", f"{front.url}/debug/slow", timeout=5)
    assert "records" in doc and "thresholdMs" in doc
    assert any(r["path"] == "/boom" for r in doc["records"])
    cleared = http_json("POST", f"{front.url}/debug/slow",
                        {"clear": True}, timeout=5)
    assert cleared["records"] == []
    bad = http_json("POST", f"{front.url}/debug/slow", {},
                    timeout=5)
    assert "error" in bad


def test_capture_includes_span_tree_and_stage_summary(front,
                                                      monkeypatch):
    """The whole record: a handler that runs a stage track produces a
    capture carrying both the stage wall+cpu summary and the server
    span pulled from the trace ring."""
    # pin the attribution sample: the capture must carry stage cpu
    monkeypatch.setenv("SEAWEEDFS_TPU_CPU_SAMPLE", "1")

    def staged(req):
        with profiling.track("flighttest_write", role="flighttest"):
            with profiling.stage("work"):
                _burn(2.0)
        raise RuntimeError("after track")

    front.route("GET", "/staged", staged)
    st, _, _ = http_bytes("GET", f"{front.url}/staged", timeout=5)
    assert st == 500
    recs = _wait_records("/staged")
    assert recs, profiling.flight_recorder().snapshot()
    rec = recs[0]
    assert "work" in rec["stages"]["stages"]
    assert rec["stages"]["stages"]["work"]["cpuMs"] > 0
    spans = rec.get("spans") or []
    assert any(s.get("name") == "GET /staged" for s in spans), spans


def test_attribution_runtime_lever(front):
    """POST /debug/attribution {"disarmed": true} kills stage
    tracks, CPU sampling and flight capture in this process without
    a restart; {"disarmed": false} restores the env-configured
    behavior.  (Also the lever for a within-cluster overhead
    A/B.)"""
    from seaweedfs_tpu.server import debug as debug_mod
    debug_mod.install_debug_routes(front)
    r = http_json("POST", f"{front.url}/debug/attribution",
                  {"disarmed": True}, timeout=5)
    assert r == {"disarmed": True, "scope": "all",
                 "drainEnabled": True}
    try:
        assert profiling.recorder_enabled() is False
        assert profiling.stage_timers_enabled() is False
        assert profiling.cpu_sample_every() == 0
        # even an ERROR verdict is not captured while disarmed
        st, _, _ = http_bytes("GET", f"{front.url}/boom", timeout=5)
        assert st == 500
        time.sleep(0.1)   # post-flush beat, as in the kill switch
        assert not _records_for("/boom")
    finally:
        r = http_json("POST", f"{front.url}/debug/attribution",
                      {"disarmed": False}, timeout=5)
    assert r == {"disarmed": False, "scope": "",
                 "drainEnabled": True}
    assert profiling.recorder_enabled() is True
    # scope=plane disarms only the ISSUE 15 additions — the PR 7
    # wall-stage decomposition stays armed
    r = http_json("POST", f"{front.url}/debug/attribution",
                  {"disarmed": True, "scope": "plane"}, timeout=5)
    assert r == {"disarmed": True, "scope": "plane",
                 "drainEnabled": True}
    try:
        assert profiling.recorder_enabled() is False
        assert profiling.cpu_sample_every() == 0
        assert profiling.stage_timers_enabled() is True
    finally:
        http_json("POST", f"{front.url}/debug/attribution",
                  {"disarmed": False}, timeout=5)
    # scope=drain disarms only the native-plane record drain — the
    # rest of the attribution plane stays armed
    r = http_json("POST", f"{front.url}/debug/attribution",
                  {"disarmed": True, "scope": "drain"}, timeout=5)
    assert r["drainEnabled"] is False
    try:
        assert profiling.plane_drain_enabled() is False
        assert profiling.recorder_enabled() is True
        assert profiling.stage_timers_enabled() is True
    finally:
        http_json("POST", f"{front.url}/debug/attribution",
                  {"disarmed": False, "scope": "drain"}, timeout=5)
    assert profiling.plane_drain_enabled() is True
    st, _, _ = http_bytes("GET", f"{front.url}/boom", timeout=5)
    assert st == 500
    assert _wait_records("/boom")
    assert "error" in http_json(
        "POST", f"{front.url}/debug/attribution", {}, timeout=5)
