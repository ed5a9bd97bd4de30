"""Plugin-worker plane tests: the analog of test/plugin_workers/
framework.go:43 NewHarness — a real AdminServer wired to a real
PluginWorker over loopback, against a live mini-cluster."""

import time

import numpy as np
import pytest

from seaweedfs_tpu import operation
from seaweedfs_tpu.plugin import AdminServer, PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler, VacuumHandler
from seaweedfs_tpu.server.httpd import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer


def _csrf_of(html: str) -> str:
    """Scrape the GET-served CSRF token out of a UI page's forms."""
    import re
    m = re.search(r"name='csrf' value='([0-9a-f]+)'", html)
    assert m, "UI page carries no CSRF token"
    return m.group(1)


@pytest.fixture
def harness(tmp_path):
    master = MasterServer(volume_size_limit_mb=1).start()  # tiny: 1MB
    servers = []
    for i in range(4):
        d = tmp_path / f"vol{i}"
        d.mkdir()
        servers.append(VolumeServer([str(d)], master.url,
                                    pulse_seconds=0.3).start())
    admin = AdminServer(master.url, detection_interval=3600).start()
    workdir = tmp_path / "worker"
    worker = PluginWorker(
        admin.url, master.url, str(workdir),
        # jax backend: single-volume encodes AND the mesh-batched
        # multi-volume path both run the TPU kernels (on the virtual
        # CPU mesh in tests)
        handlers=[EcEncodeHandler(fullness_ratio=0.5, backend="jax"),
                  VacuumHandler(garbage_threshold=0.2)],
        poll_wait=0.5).start()
    time.sleep(0.6)
    yield master, servers, admin, worker
    worker.stop()
    admin.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def _wait_jobs_done(admin, timeout=90):
    # 90s, not 30: the jax EC encode shares this box's single core
    # with the rest of the tier-1 run — jobs progress, just slowly
    deadline = time.time() + timeout
    while time.time() < deadline:
        jobs = http_json("GET", f"{admin.url}/maintenance/queue")["jobs"]
        if jobs and all(j["status"] in ("done", "failed") for j in jobs):
            return jobs
        time.sleep(0.2)
    raise TimeoutError(f"jobs not finished: {jobs}")


def test_worker_registration(harness):
    master, servers, admin, worker = harness
    assert worker.worker_id
    caps = admin.workers[worker.worker_id].capabilities
    assert {c["jobType"] for c in caps} == {"erasure_coding", "vacuum"}


def test_ec_detection_and_execution_via_worker(harness):
    """Full plugin EC pipeline (SURVEY §3.4): detection proposes the
    over-full volume, the worker copies it, encodes LOCALLY, distributes
    shards, mounts, deletes the original — then reads still work."""
    master, servers, admin, worker = harness
    rng = np.random.default_rng(5)
    blobs = {}
    # ~0.6MB of data -> exceeds 50% of the 1MB volume size limit
    for _ in range(12):
        data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
        fid = operation.submit(master.url, data)
        blobs[fid] = data
    vid = int(next(iter(blobs)).split(",")[0])
    time.sleep(0.5)  # heartbeat refresh so detection sees the size

    progress = []
    report = worker.report_progress
    worker.report_progress = lambda job_id, frac, message="": (
        progress.append((frac, message)), report(job_id, frac, message))
    r = http_json("POST", f"{admin.url}/maintenance/trigger_detection",
                  {})
    assert worker.worker_id in r["asked"]
    jobs = _wait_jobs_done(admin)
    ec_jobs = [j for j in jobs if j["jobType"] == "erasure_coding"]
    assert ec_jobs, jobs
    assert ec_jobs[0]["status"] == "done", ec_jobs[0]
    assert "distributed" in ec_jobs[0]["message"]
    # the result names where the codec ran (platform + device_kind),
    # in the message and in the job's trace
    assert "(jax on cpu cpu x8)" in ec_jobs[0]["message"]
    job = http_json("GET", f"{admin.url}/maintenance/job"
                           f"?id={ec_jobs[0]['jobId']}")
    spans = http_json(
        "GET", f"{admin.url}/debug/traces?request_id="
        f"{job['requestId'] or 'job-' + job['jobId']}")["spans"]
    enc = next(s for s in spans if s["name"] == "ec.encode")["attrs"]
    assert enc["codec"] == {"backend": "jax", "platform": "cpu",
                            "kind": "cpu", "count": 8}
    assert {"h2d_gbps", "d2h_gbps", "overlap_fraction"} <= \
        set(enc["staging"])
    # liveness across the encode: progress is reported from INSIDE it
    # (the worker cannot poll while it executes, and the admin reaps a
    # worker that stays silent), between "copied" 0.3 and "encoded" 0.6
    inside = [f for f, m in progress if m.startswith("encoding ")]
    assert inside and all(0.3 <= f <= 0.6 for f in inside), progress

    time.sleep(0.5)
    # volume is now EC: shards spread, original gone
    shard_locs = http_json(
        "GET", f"{master.url}/dir/ec_lookup?volumeId={vid}")
    total = sum(len(l["shardIds"])
                for l in shard_locs["shardIdLocations"])
    assert total == 14
    assert len(shard_locs["shardIdLocations"]) == 4  # spread over all
    # data survives, served through the EC read path
    for fid, want in blobs.items():
        assert operation.read(master.url, fid) == want, fid
    # dedupe: re-running detection must not enqueue a second ec job
    http_json("POST", f"{admin.url}/maintenance/trigger_detection", {})
    time.sleep(1.0)
    jobs = http_json("GET", f"{admin.url}/maintenance/queue")["jobs"]
    assert len([j for j in jobs
                if j["jobType"] == "erasure_coding"]) == 1


def test_vacuum_detection(harness):
    master, servers, admin, worker = harness
    rng = np.random.default_rng(6)
    fids = [operation.submit(master.url,
                             rng.integers(0, 256, 30_000,
                                          dtype=np.uint8).tobytes())
            for _ in range(6)]
    for fid in fids[:4]:
        operation.delete(master.url, fid)
    time.sleep(0.5)
    http_json("POST", f"{admin.url}/maintenance/trigger_detection", {})
    jobs = _wait_jobs_done(admin)
    vac = [j for j in jobs if j["jobType"] == "vacuum"]
    assert vac and vac[0]["status"] == "done", jobs
    for fid in fids[4:]:
        assert operation.read(master.url, fid)


def test_batch_ec_job_multi_volume(harness):
    """VERDICT r2 Next #9: a multi-volume batch job (execute_batch)
    runs each volume through the staged encode pipeline
    (ec_encoder.write_parity_files) and leaves every volume EC'd, with
    all data readable."""
    master, servers, admin, worker = harness
    # pre-grow a second volume so uploads spread over >= 2 volumes
    http_json("POST", f"{master.url}/vol/grow",
              {"count": 2, "replication": "000"})
    rng = np.random.default_rng(17)
    blobs = {}
    for _ in range(24):
        data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
        fid = operation.submit(master.url, data)
        blobs[fid] = data
    vids = sorted({int(fid.split(",")[0]) for fid in blobs})
    assert len(vids) >= 2, f"need >=2 volumes, got {vids}"
    time.sleep(0.5)  # heartbeat refresh

    r = http_json("POST", f"{admin.url}/maintenance/submit_job",
                  {"jobType": "erasure_coding",
                   "dedupeKey": f"ec-batch:{vids}",
                   "params": {"volumeIds": vids}})
    job_id = r["jobId"]
    jobs = _wait_jobs_done(admin, timeout=60)
    job = next(j for j in jobs if j["jobId"] == job_id)
    assert job["status"] == "done", job
    assert "batch" in job["message"] and "mesh" in job["message"]

    time.sleep(0.5)
    for vid in vids:
        shard_locs = http_json(
            "GET", f"{master.url}/dir/ec_lookup?volumeId={vid}")
        total = sum(len(l["shardIds"])
                    for l in shard_locs["shardIdLocations"])
        assert total == 14, f"volume {vid}: {total} shards"
    for fid, want in blobs.items():
        assert operation.read(master.url, fid) == want, fid


def test_admin_ui_status_page(harness):
    """The admin's minimal web UI (weed/admin view analog) renders
    topology, workers, and the job queue."""
    import urllib.request
    master, servers, admin, worker = harness
    with urllib.request.urlopen(f"http://{admin.url}/",
                                timeout=10) as r:
        html = r.read().decode()
    assert "seaweedfs-tpu admin" in html
    assert worker.worker_id in html
    assert "erasure_coding" in html
    # all four volume servers listed
    for vs in servers:
        assert vs.url in html


def test_bulk_file_transfer_streams_with_bounded_memory(harness,
                                                        tmp_path):
    """The worker bulk-data path (volume pull + shard push) must stream
    in chunks, never buffering whole files (VERDICT r3 weak #2: a 30GB
    volume would OOM the worker).  Transfers a file much larger than
    the stream chunk size through both directions against a live
    volume server and bounds the client-side Python allocation peak
    well below the file size (the reference streams CopyFile the same
    way, volume_server.proto:69)."""
    import os
    import tracemalloc

    from seaweedfs_tpu.server.httpd import http_download, http_upload

    master, servers, admin, worker = harness
    vs = servers[0]
    size = 48 << 20  # 12x the 4MB stream chunk
    rng = np.random.default_rng(11)
    src = tmp_path / "big.bin"
    blob = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    src.write_bytes(blob)

    tracemalloc.start()
    # push: file -> server (streamed request body)
    status, body, _ = http_upload(
        "POST", f"{vs.url}/admin/receive_file?volumeId=777"
        "&collection=&ext=.dat", str(src))
    assert status == 200, body
    # pull: server -> file (streamed response body)
    dest = tmp_path / "pulled.bin"
    status, hdrs = http_download(
        f"{vs.url}/admin/volume_file?volumeId=777&ext=.dat", str(dest))
    assert status == 200
    _cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    assert dest.read_bytes() == blob
    assert int(hdrs.get("Content-Length", -1)) == size
    # whole-file buffering would show ~size (or 2x) peaks; the streamed
    # path allocates only per-chunk buffers
    assert peak < size // 2, f"peak {peak} suggests whole-file buffering"

    # ranged pull (offset+size) still works and streams
    status, hdrs = http_download(
        f"{vs.url}/admin/volume_file?volumeId=777&ext=.dat"
        "&offset=1048576&size=2097152", str(dest))
    assert status == 200
    assert dest.read_bytes() == blob[1 << 20:(1 << 20) + (2 << 20)]


def test_admin_state_survives_restart(tmp_path):
    """VERDICT r4 #7 done-criterion: jobs, dedupe keys, decision
    traces, worker registry and config survive an admin restart
    (persistence under <dataDir>/plugin/, admin/plugin/DESIGN.md)."""
    from seaweedfs_tpu.plugin.admin import AdminServer

    d = str(tmp_path / "admin")
    master = MasterServer(volume_size_limit_mb=8).start()
    try:
        admin = AdminServer(master.url, detection_interval=3600,
                            data_dir=d).start()
        # register a worker with a schema-bearing descriptor
        r = http_json("POST", f"{admin.url}/worker/register", {
            "capabilities": [{"jobType": "erasure_coding",
                              "canDetect": True,
                              "canExecute": True}],
            "descriptors": [{"jobType": "erasure_coding", "fields": [
                {"name": "fullnessRatio", "type": "float",
                 "default": 0.9}]}],
            "maxConcurrent": 2})
        wid = r["workerId"]
        # set config through the schema-validated store
        r = http_json("POST", f"{admin.url}/maintenance/config",
                      {"jobType": "erasure_coding",
                       "values": {"fullnessRatio": 0.5}})
        assert r["values"]["fullnessRatio"] == 0.5
        # bad field/type rejected
        assert "error" in http_json(
            "POST", f"{admin.url}/maintenance/config",
            {"jobType": "erasure_coding", "values": {"nope": 1}})
        assert "error" in http_json(
            "POST", f"{admin.url}/maintenance/config",
            {"jobType": "erasure_coding",
             "values": {"fullnessRatio": "not-a-number"}})
        # submit a job; have the (fake) worker pick it up
        r = http_json("POST", f"{admin.url}/maintenance/submit_job",
                      {"jobType": "erasure_coding",
                       "params": {"volumeId": 7},
                       "dedupeKey": "ec:7"})
        jid = r["jobId"]
        msg = http_json("POST", f"{admin.url}/worker/poll",
                        {"workerId": wid, "waitSeconds": 2})
        assert msg["type"] == "executeJob" and msg["jobId"] == jid
        detail = http_json("GET",
                           f"{admin.url}/maintenance/job?id={jid}")
        events = [t["event"] for t in detail["trace"]]
        assert any("submitted" in e for e in events)
        assert any("assigned" in e for e in events)
        admin.stop()

        # restart: everything is still there
        admin2 = AdminServer(master.url, detection_interval=3600,
                             data_dir=d).start()
        try:
            detail = http_json(
                "GET", f"{admin2.url}/maintenance/job?id={jid}")
            assert detail["jobType"] == "erasure_coding"
            # live assignment was requeued on recovery, trace says so
            assert detail["status"] == "pending"
            assert any("admin restart" in t["event"]
                       for t in detail["trace"])
            # dedupe key still guards: resubmit dedupes to the old job
            r = http_json("POST",
                          f"{admin2.url}/maintenance/submit_job",
                          {"jobType": "erasure_coding",
                           "params": {"volumeId": 7},
                           "dedupeKey": "ec:7"})
            assert r.get("deduped") and r["jobId"] == jid
            # worker registry survived: a poll from the old worker id
            # is NOT a 404, and the job reassigns to it
            msg = http_json("POST", f"{admin2.url}/worker/poll",
                            {"workerId": wid, "waitSeconds": 2})
            assert msg["type"] == "executeJob" and msg["jobId"] == jid
            # schema + config survived
            cfg = http_json("GET", f"{admin2.url}/maintenance/config")
            ec = cfg["jobTypes"]["erasure_coding"]
            assert ec["values"]["fullnessRatio"] == 0.5
            assert any(f["name"] == "fullnessRatio"
                       for f in ec["fields"])
        finally:
            admin2.stop()
    finally:
        master.stop()


def test_config_reaches_worker_detection(harness):
    """Operator config flows admin -> worker handlers with the next
    RunDetection (SchemaCoordinator -> detector path)."""
    master, servers, admin, worker = harness
    h = worker.handlers["erasure_coding"]
    assert h.fullness_ratio != 0.123
    r = http_json("POST", f"{admin.url}/maintenance/config",
                  {"jobType": "erasure_coding",
                   "values": {"fullnessRatio": 0.123}})
    assert "error" not in r
    http_json("POST", f"{admin.url}/maintenance/trigger_detection", {})
    deadline = time.time() + 10
    while time.time() < deadline and h.fullness_ratio != 0.123:
        time.sleep(0.1)
    assert h.fullness_ratio == 0.123


def test_admin_multi_page_ui_and_config_forms(harness):
    """Round 5: the admin UI grows pages (volumes/ec/jobs/config —
    weed/admin/view/app roles) and schema-driven config FORMS whose
    submissions run the same validation as the JSON API."""
    import urllib.error
    import urllib.parse
    import urllib.request
    master, servers, admin, worker = harness
    from seaweedfs_tpu import operation
    a = operation.assign(master.url)
    operation.upload(a.url, a.fid, b"ui-visible")
    time.sleep(0.6)
    base = f"http://{admin.url}"
    with urllib.request.urlopen(f"{base}/ui/volumes",
                                timeout=10) as r:
        html = r.read().decode()
    vid = a.fid.split(",")[0]
    assert f"<td>{vid}</td>" in html and "garbage" in html
    with urllib.request.urlopen(f"{base}/ui/ec", timeout=10) as r:
        assert "EC volumes" in r.read().decode()
    with urllib.request.urlopen(f"{base}/ui/jobs", timeout=10) as r:
        assert "filter:" in r.read().decode()
    # config page renders the worker's schema as a form, including
    # the CSRF token every UI write must echo back
    with urllib.request.urlopen(f"{base}/ui/config", timeout=10) as r:
        html = r.read().decode()
    assert "erasure_coding" in html and "<form" in html
    csrf = _csrf_of(html)
    # submit a value through the FORM path; it lands in the store
    field = admin.schemas["erasure_coding"][0]["name"]
    data = urllib.parse.urlencode(
        {"jobType": "erasure_coding", field: "123",
         "csrf": csrf}).encode()
    req = urllib.request.Request(f"{base}/ui/config", data=data,
                                 method="POST")
    try:
        urllib.request.urlopen(req, timeout=10)
    except urllib.error.HTTPError as e:
        assert e.code in (302, 303), e.read()
    assert float(admin.config["erasure_coding"][field]) == 123
    # bad job type through the form: validation error page, no crash
    data = urllib.parse.urlencode(
        {"jobType": "nope", "x": "1", "csrf": csrf}).encode()
    req = urllib.request.Request(f"{base}/ui/config", data=data,
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            assert "error" in r.read().decode().lower()
    except urllib.error.HTTPError as e:
        assert e.code in (400, 404)


def test_admin_ui_actions(harness):
    """Round 5: browser-driven maintenance — trigger a detection
    round and submit a job from the jobs page; both share the JSON
    handlers' validation."""
    import urllib.error
    import urllib.parse
    import urllib.request
    master, servers, admin, worker = harness
    base = f"http://{admin.url}"
    with urllib.request.urlopen(f"{base}/ui/jobs", timeout=10) as r:
        csrf = _csrf_of(r.read().decode())

    def post(data):
        req = urllib.request.Request(
            f"{base}/ui/actions",
            data=urllib.parse.urlencode(
                dict(data, csrf=csrf)).encode(),
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    st, _ = post({"action": "detect"})
    assert st in (200, 303)
    # submit a vacuum job from the form path
    st, body = post({"action": "submit", "jobType": "vacuum",
                     "params": "{}"})
    assert st in (200, 303), body
    deadline = time.time() + 10
    while time.time() < deadline:
        with admin.lock:
            if any(j.job_type == "vacuum"
                   for j in admin.jobs.values()):
                break
        time.sleep(0.2)
    with admin.lock:
        assert any(j.job_type == "vacuum"
                   for j in admin.jobs.values())
    # bad params JSON -> error page, no crash
    st, body = post({"action": "submit", "jobType": "vacuum",
                     "params": "{nope"})
    assert st == 200 and b"bad params JSON" in body
    # unknown job type -> validation error surfaced (error PAGE;
    # a silent 303-to-jobs would mean an unrunnable job was minted)
    st, body = post({"action": "submit", "jobType": "bogus",
                     "params": "{}"})
    assert b"Submit error" in body, body[:200]
    with admin.lock:
        assert not any(j.job_type == "bogus"
                       for j in admin.jobs.values())
    st, _ = post({"action": "wat"})
    assert st == 400


def test_admin_ui_writes_require_csrf_and_admin_key(harness):
    """UI write endpoints fail closed: a POST without the GET-served
    CSRF token is 403 (cross-site form protection), and with a
    security.toml admin key configured, a POST without admin
    credentials is 403 even WITH a valid token."""
    import urllib.error
    import urllib.parse
    import urllib.request
    from seaweedfs_tpu import security
    master, servers, admin, worker = harness
    base = f"http://{admin.url}"

    def post(path, data):
        req = urllib.request.Request(
            f"{base}{path}",
            data=urllib.parse.urlencode(data).encode(),
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    # no token -> 403, nothing mutated
    st, body = post("/ui/actions", {"action": "detect"})
    assert st == 403 and b"CSRF" in body
    st, body = post("/ui/config",
                    {"jobType": "erasure_coding",
                     admin.schemas["erasure_coding"][0]["name"]: "7"})
    assert st == 403
    # forged token -> 403
    st, _ = post("/ui/actions", {"action": "detect",
                                 "csrf": "f" * 32})
    assert st == 403
    # valid token, admin key armed, no credentials -> 403
    with urllib.request.urlopen(f"{base}/ui/jobs", timeout=10) as r:
        csrf = _csrf_of(r.read().decode())
    old = security.current()
    try:
        security.configure(
            security.SecurityConfig(admin_key="ui-admin-key"))
        st, body = post("/ui/actions", {"action": "detect",
                                        "csrf": csrf})
        assert st == 403 and b"admin credentials" in body
        # with the admin jwt (?jwt= form a browser bookmark carries)
        # AND the token, the write goes through
        jwt = security.current().admin_jwt()
        st, _ = post(f"/ui/actions?jwt={jwt}",
                     {"action": "detect", "csrf": csrf})
        assert st in (200, 303)
    finally:
        security.configure(old)
