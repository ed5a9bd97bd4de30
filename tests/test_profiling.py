"""Performance-observability plane (profiling.py): sampler mechanics
and overhead bound, stage-track decomposition, device telemetry,
prometheus-text client helpers — and the cluster acceptance: every
role uniformly serves /metrics, /debug/health, /debug/traces, and
/debug/pprof, and `cluster.profile` over a proc-cluster under write
load returns merged folded stacks naming the needle-append hot path.
"""

import json
import os
import threading
import time

import pytest

from proc_framework import ProcCluster
from seaweedfs_tpu import profiling, stats
from seaweedfs_tpu.server.httpd import http_bytes, http_json


# -- sampler --------------------------------------------------------------

def _busy(stop: threading.Event) -> None:
    while not stop.is_set():
        sum(i * i for i in range(500))


def test_sampler_start_stop_snapshot():
    s = profiling.Sampler()
    stop = threading.Event()
    t = threading.Thread(target=_busy, args=(stop,), daemon=True)
    t.start()
    try:
        assert s.start(200) is True
        assert s.running
        # the sampler is overhead-self-limiting: on a loaded 1-core
        # box it downshifts below its nominal hz, so wait on the
        # sample COUNT (bounded), not a fixed wall-clock window
        deadline = time.monotonic() + 8.0
        while s.snapshot()["samples"] <= 10 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        s.stop()
        assert not s.running
        snap = s.snapshot()
        assert snap["samples"] > 10
        assert snap["stacks"] > 0
        # the busy thread's stack must be in the folded table,
        # root-first with file:func frames
        assert any("test_profiling.py:_busy" in stack
                   for stack in snap["folded"])
    finally:
        stop.set()
        t.join()


def test_sampler_second_start_keeps_running_window():
    s = profiling.Sampler()
    assert s.start(50) is True
    try:
        # a second operator arming cluster-wide must not reset the
        # first one's window
        assert s.start(500) is False
        assert s.hz == 50
    finally:
        s.stop()


def test_sampler_hz_clamped_and_reset():
    s = profiling.Sampler()
    s.start(1e9)
    try:
        assert s.hz <= 1000.0
        time.sleep(0.05)
    finally:
        s.stop()
    s.reset()
    assert s.snapshot()["samples"] == 0
    assert s.snapshot()["folded"] == {}


def test_sampler_overhead_bounded():
    """The sampler stretches its sleep when a pass overruns its
    budget: self-time must stay around MAX_OVERHEAD of wall."""
    stops = threading.Event()
    threads = [threading.Thread(target=_busy, args=(stops,),
                                daemon=True) for _ in range(4)]
    for t in threads:
        t.start()
    s = profiling.Sampler()
    s.start(1000)   # max rate against 4 busy threads
    try:
        time.sleep(0.6)
    finally:
        s.stop()
        stops.set()
        for t in threads:
            t.join()
    snap = s.snapshot()
    # generous ceiling: the construction bounds it at MAX_OVERHEAD of
    # one core; allow scheduler noise on a loaded 2-core box
    assert snap["overhead"] < profiling.MAX_OVERHEAD * 2.5


def test_sampler_table_cap_counts_drops(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_PROFILE_STACKS", "64")
    s = profiling.Sampler()
    # drive the fold loop directly: cap applies per distinct stack
    cap = profiling.max_stacks()
    with s._lock:
        for i in range(cap + 10):
            key = f"stack{i}"
            if len(s._folded) < cap:
                s._folded[key] = 1
            else:
                s.dropped += 1
    assert len(s._folded) == cap
    assert s.dropped == 10


def test_collapsed_output_is_flamegraph_input():
    s = profiling.Sampler()
    with s._lock:
        s._folded.update({"a;b;c": 3, "a;d": 1})
    text = s.collapsed()
    lines = text.strip().splitlines()
    assert lines[0] == "a;b;c 3"   # most-sampled first
    assert lines[1] == "a;d 1"


def test_merge_folded_sums_and_skips_junk():
    merged = profiling.merge_folded([
        {"a;b": 2, "c": 1}, {"a;b": 3}, None,
        {"c": "junk", "d": 4}])
    assert merged == {"a;b": 5, "c": 1, "d": 4}


def test_maybe_autostart_respects_default_off(monkeypatch):
    monkeypatch.delenv("SEAWEEDFS_TPU_PROFILE_HZ", raising=False)
    assert profiling.default_hz() == 0.0
    monkeypatch.setenv("SEAWEEDFS_TPU_PROFILE_HZ", "250")
    assert profiling.default_hz() == 250.0
    monkeypatch.setenv("SEAWEEDFS_TPU_PROFILE_HZ", "junk")
    assert profiling.default_hz() == 0.0


# -- stage tracks ---------------------------------------------------------

def test_stage_is_shared_noop_without_track():
    assert profiling.current_track() is None
    assert profiling.stage("anything") is profiling._NOOP


def test_track_observes_histogram_and_total():
    m = stats.Metrics("t")
    with profiling.track("write", role="volume", metrics=m) as trk:
        assert trk is not None
        with profiling.stage("append"):
            time.sleep(0.01)
        with profiling.stage("append"):
            pass
        with profiling.stage("flush"):
            pass
    text = m.render()
    assert 't_write_stage_seconds_count{stage="append"} 1' in text
    assert 't_write_stage_seconds_count{stage="total"} 1' in text
    parsed = profiling.parse_prom_text(text)
    append = profiling.prom_histogram(
        parsed, "t_write_stage_seconds", {"stage": "append"})
    total = profiling.prom_histogram(
        parsed, "t_write_stage_seconds", {"stage": "total"})
    # two append stage() blocks accumulate into ONE per-request cell
    assert append["count"] == 1
    assert append["sum"] >= 0.01
    assert total["sum"] >= append["sum"]


def test_use_track_binds_other_thread():
    m = stats.Metrics("x")
    done = threading.Event()

    def worker(trk):
        with profiling.use_track(trk):
            with profiling.stage("upload"):
                pass
        done.set()

    with profiling.track("write", metrics=m) as trk:
        t = threading.Thread(target=worker, args=(trk,))
        t.start()
        assert done.wait(5)
        t.join()
    assert 'stage="upload"' in m.render()


def test_stage_timers_disable_knob(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_STAGE_TIMERS", "0")
    m = stats.Metrics("off")
    with profiling.track("write", metrics=m) as trk:
        assert trk is None
        with profiling.stage("append"):
            pass
    assert "write_stage_seconds" not in m.render()


# -- device telemetry -----------------------------------------------------

def test_device_and_kernel_notes_land_in_process_registry():
    profiling.device_note("h2d", 1 << 20, 0.001)
    profiling.kernel_note("gf_apply_matrix", 0.002)
    text = stats.render_process()
    assert 'device_transfer_bytes_total{dir="h2d"}' in text
    assert 'device_kernel_last_ms{kernel="gf_apply_matrix"}' in text


def test_sample_device_memory_never_raises():
    # CPU mesh: backend has no memory_stats -> empty dict, no gauges
    # required, and above all no exception
    out = profiling.sample_device_memory()
    assert isinstance(out, dict)


# -- prometheus-text client helpers ---------------------------------------

def test_parse_prom_text_roundtrip_with_escaping():
    m = stats.Metrics("ns")
    m.counter_add("hits_total", 2.0, peer='weird"peer\nname')
    m.gauge_set("depth", 3.5)
    m.histogram_observe("lat_seconds", 0.03, buckets=(0.01, 0.1))
    parsed = profiling.parse_prom_text(m.render())
    [(labels, v)] = parsed["ns_hits_total"]
    assert v == 2.0
    assert labels["peer"] == 'weird"peer\nname'
    assert parsed["ns_depth"][0][1] == 3.5
    h = profiling.prom_histogram(parsed, "ns_lat_seconds")
    assert h["count"] == 1
    assert h["sum"] == pytest.approx(0.03)
    assert h["counts"] == [0, 1, 0]   # (…0.01], (0.01–0.1], +Inf


def test_parse_prom_text_unescape_is_single_pass():
    # 'a\nb' (backslash + literal n) escapes to 'a\\\\nb'; a
    # sequential-replace decoder turns it into backslash+newline
    m = stats.Metrics("ns")
    m.counter_add("c_total", 1.0, peer="a\\nb")
    parsed = profiling.parse_prom_text(m.render())
    [(labels, _v)] = parsed["ns_c_total"]
    assert labels["peer"] == "a\\nb"


def test_histogram_quantile_interpolates():
    h = {"buckets": [0.01, 0.1, 1.0],
         "counts": [10, 10, 0, 0], "sum": 1.0, "count": 20}
    assert profiling.histogram_quantile(h, 0.25) == pytest.approx(
        0.005, rel=0.2)
    q90 = profiling.histogram_quantile(h, 0.90)
    assert 0.01 < q90 <= 0.1
    assert profiling.histogram_quantile(None, 0.5) == 0.0
    assert profiling.histogram_quantile(h, 0.0) >= 0.0


def test_histogram_delta_windows_counters():
    before = {"buckets": [1.0], "counts": [5, 0], "sum": 2.0,
              "count": 5}
    after = {"buckets": [1.0], "counts": [8, 1], "sum": 4.0,
             "count": 9}
    d = profiling.histogram_delta(after, before)
    assert d["count"] == 4
    assert d["counts"] == [3, 1]
    # bucket-layout change: the delta degrades to the 'after' snapshot
    assert profiling.histogram_delta(after, {"buckets": [2.0],
                                             "counts": [1, 0],
                                             "sum": 0, "count": 1}) \
        == after
    assert profiling.histogram_delta(None, before) is None


# -- cluster acceptance ---------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    c = ProcCluster(tmp_path_factory.mktemp("prof"), volumes=2).start()
    _wait_writable(c)
    yield c
    c.stop()


def _wait_writable(c, timeout=45):
    from seaweedfs_tpu import operation
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            fid = operation.submit(c.master, b"probe")
            assert operation.read(c.master, fid) == b"probe"
            return
        except Exception as e:  # noqa: BLE001
            last = e
        time.sleep(0.3)
    raise TimeoutError(f"cluster never writable: {last}")


def _role_urls(c) -> "list[tuple[str, str]]":
    return [(name, p.url) for name, p in c.procs.items()]


@pytest.mark.parametrize("endpoint", ["/metrics", "/debug/health",
                                      "/debug/traces", "/debug/pprof",
                                      "/debug/slow"])
def test_every_role_serves_debug_plane(cluster, endpoint):
    """The uniform debug surface: every role answers every endpoint
    with a parseable document."""
    for role, url in _role_urls(cluster):
        # warm the middleware: request_seconds exists only after a
        # node has served at least one request
        http_bytes("GET", f"{url}/debug/health", timeout=10)
        st, body, _ = http_bytes("GET", f"{url}{endpoint}", timeout=10)
        assert st == 200, f"{role} {endpoint} -> {st}"
        text = body.decode()
        if endpoint == "/metrics":
            parsed = profiling.parse_prom_text(text)
            assert any(k.endswith("request_seconds_count")
                       for k in parsed), f"{role}: no request_seconds"
        else:
            doc = json.loads(text)
            if endpoint == "/debug/health":
                assert "peers" in doc, role
            elif endpoint == "/debug/traces":
                assert "spans" in doc, role
            elif endpoint == "/debug/slow":
                assert "records" in doc and "ringSize" in doc, role
            else:
                assert doc["running"] is False, \
                    f"{role}: profiler must be off by default"
                assert "folded" in doc


def test_pprof_post_roundtrip_and_bad_input(cluster):
    url = cluster.procs["volume0"].url
    r = http_json("POST", f"{url}/debug/pprof",
                  {"action": "start", "hz": 200}, timeout=10)
    assert r["running"] is True and r["started"] is True
    try:
        time.sleep(0.3)
        snap = http_json("GET", f"{url}/debug/pprof?top=5", timeout=10)
        assert snap["running"] is True
        assert len(snap["folded"]) <= 5
        st, body, _ = http_bytes(
            "GET", f"{url}/debug/pprof?format=collapsed", timeout=10)
        assert st == 200
        for line in body.decode().strip().splitlines():
            stack, _, count = line.rpartition(" ")
            assert stack and count.isdigit()
    finally:
        stopped = http_json("POST", f"{url}/debug/pprof",
                            {"action": "stop"}, timeout=10)
    assert stopped["running"] is False
    assert stopped["samples"] > 0
    bad = http_json("POST", f"{url}/debug/pprof",
                    {"action": "start", "hz": "junk"}, timeout=10)
    assert "error" in bad
    bad2 = http_json("POST", f"{url}/debug/pprof", {}, timeout=10)
    assert "error" in bad2


def test_cluster_profile_names_needle_append_hot_path(cluster,
                                                     tmp_path):
    """The tentpole acceptance: cluster.profile arms every node,
    merges folded stacks, and the write hot path is IN them."""
    from seaweedfs_tpu import operation
    from seaweedfs_tpu.shell import CommandEnv, run_command

    stop = threading.Event()

    def writer(seed: int) -> None:
        blob = bytes([seed]) * 4096
        while not stop.is_set():
            try:
                # named needles stay on the PYTHON write path (the
                # native write plane 404s them): this test profiles
                # the Python hot path by construction
                operation.submit(cluster.master, blob,
                                 name=f"prof{seed}.bin")
            except OSError:
                time.sleep(0.05)

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    out_path = os.path.join(str(tmp_path), "cluster.folded")
    try:
        env = CommandEnv(cluster.master, filer=cluster.filer)
        out = run_command(
            env, f"cluster.profile -duration=3 -hz=250 "
                 f"-out={out_path}")
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert "distinct stacks" in out
    with open(out_path) as f:
        merged = f.read()
    # the needle-append hot path, by name, in the merged flame view
    # (write_needle when a pass lands mid-append; the handler frame
    # when it lands in recv/response — either names the hot path)
    assert "volume.py:write_needle" in merged or \
        "volume_server.py:_put_needle" in merged, merged[:2000]
    # traffic ran through the whole funnel during the window; the
    # master's assign path shows up too on a healthy merge
    assert "volume_server.py" in merged or "master_server.py" in merged


def test_cluster_top_renders_live_view(cluster):
    from seaweedfs_tpu.shell import CommandEnv, run_command
    env = CommandEnv(cluster.master, filer=cluster.filer)
    out = run_command(env, "cluster.top -interval=0.5")
    assert "cluster.top" in out
    # every node line carries a recognized role tag
    for role, url in _role_urls(cluster):
        assert url in out, f"{role} missing from cluster.top"
    assert "[master]" in out and "[volume_server]" in out \
        and "[filer]" in out
    # the filer's SLO-autopilot loop state renders in its block
    assert "autopilot: on" in out


def test_stage_cpu_and_tree_gauges_exported(cluster):
    """ISSUE 15 acceptance: after real writes, every write-path
    role's /metrics carries the stage-CPU histograms beside the wall
    ones, the per-request CPU histogram, and the /proc process-tree
    gauges."""
    from seaweedfs_tpu import operation
    for i in range(4):
        # named needles stay on the Python volume write path (stage
        # tracks live there); the filer POSTs mint the filer funnel's
        # stages
        operation.submit(cluster.master, b"cpu" * 512,
                         name=f"cpu{i}.bin")
        st, _, _ = http_bytes(
            "POST", f"{cluster.filer}/stagecpu/f{i}.bin", b"c" * 2048,
            timeout=10)
        assert st == 201
    # assignment spreads the writes across the volume fleet: require
    # the stage families on at least one volume server, the request-
    # cpu histogram + tree gauges on every role scraped
    targets = [(cluster.filer, "filer", True)] + [
        (p.url, "volume_server", False)
        for name, p in cluster.procs.items()
        if name.startswith("volume")]
    staged_volumes = 0
    for url, ns, required in targets:
        st, body, _ = http_bytes("GET", f"{url}/metrics", timeout=10)
        assert st == 200
        parsed = profiling.parse_prom_text(body.decode())
        wall = profiling.prom_histogram(
            parsed, f"{ns}_write_stage_seconds", {"stage": "total"})
        cpu = profiling.prom_histogram(
            parsed, f"{ns}_write_stage_cpu_seconds",
            {"stage": "total"})
        if wall and wall["count"] > 0:
            assert cpu and cpu["count"] > 0, f"{ns}: no cpu stages"
            # sanity, not equality: the cpu histogram holds only the
            # SAMPLED subset while wall holds every track, and this
            # sandbox's thread-CPU clock is quantized coarsely enough
            # to overshoot wall on a single short request — the guard
            # here is against unit errors (ns-vs-s), so allow slack
            assert cpu["sum"] <= wall["sum"] * 2.0 + 0.1, \
                (ns, cpu, wall)
            if ns == "volume_server":
                staged_volumes += 1
        elif required:
            raise AssertionError(f"{ns}@{url}: no wall stages")
        assert f"{ns}_request_cpu_seconds_count" in parsed, (ns, url)
        assert "seaweedfs_tpu_process_tree_cpu_seconds" in parsed, ns
        assert "seaweedfs_tpu_process_tree_rss_bytes" in parsed, ns
    assert staged_volumes >= 1, "no volume server minted stage cpu"


def test_cluster_slow_renders_cross_role_tree(cluster):
    """The flight-recorder acceptance path: a deadline-killed write
    is captured on the filer, and cluster.slow renders its record —
    verdict, wall/cpu split, and the merged span tree."""
    from seaweedfs_tpu.shell import CommandEnv, run_command
    from seaweedfs_tpu.util import deadline as dl
    env = CommandEnv(cluster.master, filer=cluster.filer)
    run_command(env, "cluster.slow -clear")
    st, _, _ = http_bytes(
        "POST", f"{cluster.filer}/slowtest/never.bin", b"x" * 1024,
        {dl.HEADER: "0"}, timeout=10)
    assert st == 504
    out = run_command(env, "cluster.slow -top=3")
    assert "cluster.slow" in out
    assert "verdict=deadline" in out, out
    assert "/slowtest/never.bin" in out, out
    assert "deadline=0ms" in out, out
    # a slow-but-ok request joins it after the ring warms; the
    # deadline verdict filter narrows to the incident
    filtered = run_command(env, "cluster.slow -verdict=deadline")
    assert "/slowtest/never.bin" in filtered


def test_cluster_commands_skip_unreachable_node(cluster):
    """Satellite: a node whose scrape fails mid-fan-out costs a
    rendered note, never the whole cluster view."""
    from seaweedfs_tpu.shell import CommandEnv, run_command
    env = CommandEnv(cluster.master, filer=cluster.filer)
    dead = "127.0.0.1:9"        # discard port: nothing listens
    top = run_command(env, f"cluster.top -interval=0.3 -nodes={dead}")
    assert f"{dead}: unreachable" in top
    assert "[filer]" in top     # the live nodes still rendered
    slow = run_command(env, f"cluster.slow -nodes={dead}")
    assert f"{dead}: scrape failed, skipped" in slow


def test_cluster_top_contains_node_render_failure(cluster,
                                                  monkeypatch):
    """A node whose metrics parse but whose render trips (truncated
    scrape, role skew) is skipped with a note."""
    from seaweedfs_tpu.shell import CommandEnv, commands, run_command

    def explode(url, b, a, window):
        raise ValueError("malformed cell")

    monkeypatch.setattr(commands, "_render_node_top", explode)
    env = CommandEnv(cluster.master, filer=cluster.filer)
    out = run_command(env, "cluster.top -interval=0.3")
    assert "render failed: malformed cell" in out
    assert "cluster.top" in out          # header still rendered


def test_cluster_top_renders_cpu_line(cluster):
    """The cost-attribution line: under live traffic the window sees
    request CPU vs wall and the process-tree burn."""
    from seaweedfs_tpu import operation
    from seaweedfs_tpu.shell import CommandEnv, run_command
    stop = threading.Event()

    def writer() -> None:
        i = 0
        while not stop.is_set():
            try:
                operation.submit(cluster.master, b"t" * 2048,
                                 name=f"cpuline{i}.bin")
            except OSError:
                time.sleep(0.02)
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        env = CommandEnv(cluster.master, filer=cluster.filer)
        out = run_command(env, "cluster.top -interval=1.5")
    finally:
        stop.set()
        t.join(timeout=10)
    assert "cpu:" in out, out
    assert "tree=" in out, out
