"""Admin server: worker registry + detection scheduling + job dispatch
(weed/admin/maintenance/maintenance_manager.go + admin/plugin/:
PluginRegistry, DetectorScheduler, JobDispatcher, SchemaCoordinator,
ConfigStore per DESIGN.md).

The reference uses a worker-initiated bidi gRPC stream
(pb/plugin.proto:12 PluginControlService.WorkerStream).  Over plain
HTTP the same conversation becomes: worker registers (WorkerHello with
capabilities + config-schema Descriptors), then long-polls
/worker/poll for admin->worker messages (RunDetectionRequest /
ExecuteJobRequest) and POSTs worker->admin messages (DetectionResult /
JobProgressUpdate / JobCompleted).

Round 5 (VERDICT r4 #7): with `data_dir` set the plane persists under
`<data_dir>/plugin/` — the reference's persistence layout — so jobs,
dedupe keys, decision traces, the worker registry and per-job-type
config SURVIVE an admin restart:
  plugin/jobs.jsonl    append-only job event log (folded at load,
                       compacted when it grows past 4x the live set)
  plugin/workers.json  registry snapshot (ids, capabilities, schemas)
  plugin/config.json   ConfigStore: schema-validated per-type values,
                       delivered to workers with each RunDetection
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field

from ..server.httpd import HttpServer, Request, http_json


def _trace_ctx() -> "tuple[str, str]":
    """(request id, trace parent) of the request minting a job, so
    the eventual worker execution joins the submitter's trace."""
    from .. import tracing
    from ..util.request_id import get_request_id
    return get_request_id(), tracing.traceparent_header()


@dataclass
class WorkerInfo:
    worker_id: str
    capabilities: list[dict] = field(default_factory=list)
    last_seen: float = 0.0
    inflight: int = 0
    max_concurrent: int = 1

    def can(self, job_type: str) -> bool:
        return any(c.get("jobType") == job_type
                   for c in self.capabilities)


@dataclass
class Job:
    job_id: str
    job_type: str
    params: dict
    dedupe_key: str
    status: str = "pending"   # pending -> assigned -> done/failed
    worker_id: str = ""
    progress: float = 0.0
    message: str = ""
    created: float = field(default_factory=time.time)
    updated: float = field(default_factory=time.time)
    # decision trace (admin/plugin DESIGN.md WorkflowMonitor): why the
    # job exists and every state transition, survives restart
    trace: list = field(default_factory=list)
    # distributed-tracing context of the request that minted the job
    # (tracing.py): delivered with executeJob so the worker's spans
    # land in the submitter's trace
    request_id: str = ""
    trace_parent: str = ""

    def add_trace(self, event: str) -> None:
        self.trace.append({"ts": round(time.time(), 3),
                           "event": event})

    def to_json(self) -> dict:
        return {"jobId": self.job_id, "jobType": self.job_type,
                "params": self.params, "dedupeKey": self.dedupe_key,
                "status": self.status, "workerId": self.worker_id,
                "progress": self.progress, "message": self.message,
                "created": self.created, "updated": self.updated,
                "trace": self.trace, "requestId": self.request_id,
                "traceParent": self.trace_parent}

    @classmethod
    def from_json(cls, d: dict) -> "Job":
        return cls(job_id=d["jobId"], job_type=d["jobType"],
                   params=d.get("params", {}),
                   dedupe_key=d.get("dedupeKey", ""),
                   status=d.get("status", "pending"),
                   worker_id=d.get("workerId", ""),
                   progress=d.get("progress", 0.0),
                   message=d.get("message", ""),
                   created=d.get("created", 0.0),
                   updated=d.get("updated", 0.0),
                   trace=d.get("trace", []),
                   request_id=d.get("requestId", ""),
                   trace_parent=d.get("traceParent", ""))


class AdminServer:
    """Maintenance plane controller."""

    def __init__(self, master: str, host: str = "127.0.0.1", port: int = 0,
                 detection_interval: float = 30.0,
                 data_dir: "str | None" = None):
        self.master = master
        self.detection_interval = detection_interval
        self.workers: dict[str, WorkerInfo] = {}
        self.jobs: dict[str, Job] = {}
        self._dedupe: dict[str, str] = {}  # dedupe_key -> job_id
        # jobType -> descriptor fields (SchemaCoordinator) and
        # jobType -> operator values (ConfigStore)
        self.schemas: dict[str, list] = {}
        self.config: dict[str, dict] = {}
        self.lock = threading.RLock()
        self._stop = threading.Event()
        self.data_dir = data_dir
        self._jobs_f = None
        self._job_records = 0
        if data_dir:
            import os
            self._plugin_dir = os.path.join(data_dir, "plugin")
            os.makedirs(self._plugin_dir, exist_ok=True)
            with self.lock:
                self._load_state()
        self.http = HttpServer(host, port)
        self.http.role = "admin"          # tracing server spans
        # browser-plane write protection: every mutating /ui/* POST
        # must present this per-process CSRF token (served embedded in
        # the GET forms) AND, when security.toml configures an admin
        # key, admin credentials — an unauthenticated cross-site form
        # post must not be able to submit maintenance jobs
        self._csrf = uuid.uuid4().hex
        r = self.http.route
        r("GET", "/maintenance/config", self._get_config)
        r("POST", "/maintenance/config", self._set_config)
        # status and poll chatter is quiet (HttpServer.route): a client
        # waiting on a job polls these many times a second, and the
        # ring must still hold the job's trace when it asks for it
        r("GET", "/maintenance/job", self._job_detail, quiet=True)
        r("POST", "/worker/register", self._register)     # WorkerHello
        r("POST", "/worker/poll", self._poll, quiet=True)  # admin->worker
        r("POST", "/worker/detection_result", self._detection_result)
        r("POST", "/worker/progress", self._progress,     # JobProgressUpdate
          quiet=True)
        r("POST", "/worker/complete", self._complete)     # JobCompleted
        r("GET", "/", self._ui)
        # multi-page admin UI (weed/admin/view/app/ pages)
        r("GET", "/ui/volumes", self._ui_volumes)
        r("GET", "/ui/ec", self._ui_ec)
        r("GET", "/ui/jobs", self._ui_jobs)
        r("GET", "/ui/config", self._ui_config)
        r("POST", "/ui/config", self._ui_config_submit)
        r("POST", "/ui/actions", self._ui_actions)
        r("GET", "/maintenance/queue", self._queue)
        r("POST", "/maintenance/trigger_detection", self._trigger)
        r("POST", "/maintenance/submit_job", self._submit_job)
        from ..server.debug import install_debug_routes
        install_debug_routes(self.http)  # incl. ingested job traces
        self._detect_thread: threading.Thread | None = None
        self._pending_detection: list[str] = []  # worker ids to ask

    # -- lifecycle --------------------------------------------------------

    def start(self):
        self.http.start()
        # the reference's worker transport is gRPC (plugin.proto
        # WorkerStream + worker.proto WorkerStream, both admin-hosted:
        # admin/dash/worker_grpc_server.go); serve both alongside the
        # HTTP long-poll plane
        self.grpc_server, self.grpc_port = None, 0
        try:
            from ..pb.plugin_service import start_admin_grpc
            self.grpc_server, self.grpc_port = start_admin_grpc(
                self, host=self.http.host)
        except ImportError:     # grpcio absent: HTTP-only mode
            pass
        except Exception as e:  # pragma: no cover — a real defect
            import sys
            print(f"admin {self.url}: gRPC plane failed to start: "
                  f"{e!r}", file=sys.stderr)
        self._detect_thread = threading.Thread(
            target=self._detection_loop, daemon=True)
        self._detect_thread.start()
        return self

    def stop(self):
        self._stop.set()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop(grace=0.5).wait()
            self.grpc_server = None
        self.http.stop()
        with self.lock:
            if self._jobs_f is not None:
                self._jobs_f.close()
                self._jobs_f = None

    # -- persistence (<dataDir>/plugin/, DESIGN.md layout) ---------------

    def _load_state(self) -> None:
        """Caller holds the lock (init-time recovery)."""
        import json
        import os
        jobs_path = os.path.join(self._plugin_dir, "jobs.jsonl")
        try:
            with open(jobs_path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        d = json.loads(line)
                    except ValueError:
                        break   # torn tail: later records rewritten
                    self.jobs[d["jobId"]] = Job.from_json(d)
                    self._job_records += 1
        except OSError:
            pass
        for job in self.jobs.values():
            # an admin crash mid-assignment loses the worker's report
            # channel state: requeue live assignments on recovery
            if job.status == "assigned":
                job.status = "pending"
                job.worker_id = ""
                job.add_trace("requeued: admin restart")
            self._dedupe[job.dedupe_key] = job.job_id
        try:
            with open(os.path.join(self._plugin_dir,
                                   "workers.json")) as f:
                for d in json.load(f):
                    self.workers[d["workerId"]] = WorkerInfo(
                        worker_id=d["workerId"],
                        capabilities=d.get("capabilities", []),
                        last_seen=0.0,
                        max_concurrent=d.get("maxConcurrent", 1))
                    for desc in d.get("descriptors", []):
                        if desc.get("jobType"):
                            self.schemas[desc["jobType"]] =                                 desc.get("fields", [])
        except (OSError, ValueError):
            pass
        try:
            with open(os.path.join(self._plugin_dir,
                                   "config.json")) as f:
                self.config = json.load(f)
        except (OSError, ValueError):
            pass
        if len(self.jobs):
            self._compact_jobs()

    def _persist_job(self, job: Job) -> None:
        """Append the job's current state (caller holds the lock)."""
        if not self.data_dir:
            return
        import json
        import os
        if self._jobs_f is None:
            self._jobs_f = open(
                os.path.join(self._plugin_dir, "jobs.jsonl"), "a")
        self._jobs_f.write(json.dumps(job.to_json()) + "\n")
        self._jobs_f.flush()
        self._job_records += 1
        if self._job_records > 4 * max(len(self.jobs), 64):
            self._compact_jobs()

    def _compact_jobs(self) -> None:
        """Caller holds the lock."""
        import json
        import os
        if not self.data_dir:
            return
        if self._jobs_f is not None:
            self._jobs_f.close()
        path = os.path.join(self._plugin_dir, "jobs.jsonl")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            for j in sorted(self.jobs.values(),
                            key=lambda j: j.created):
                f.write(json.dumps(j.to_json()) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._jobs_f = open(path, "a")
        self._job_records = len(self.jobs)

    def _persist_workers(self) -> None:
        """Caller holds the lock."""
        if not self.data_dir:
            return
        import json
        import os
        path = os.path.join(self._plugin_dir, "workers.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump([{
                "workerId": w.worker_id,
                "capabilities": w.capabilities,
                "maxConcurrent": w.max_concurrent,
                "descriptors": [
                    {"jobType": jt, "fields": fields}
                    for jt, fields in self.schemas.items()
                    if w.can(jt)],
            } for w in self.workers.values()], f)
        os.replace(tmp, path)

    def _persist_config(self) -> None:
        """Caller holds the lock."""
        if not self.data_dir:
            return
        import json
        import os
        path = os.path.join(self._plugin_dir, "config.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.config, f)
        os.replace(tmp, path)

    @property
    def url(self) -> str:
        return self.http.url

    # -- worker protocol handlers -----------------------------------------

    def _register(self, req: Request):
        b = req.json()
        wid = b.get("workerId") or uuid.uuid4().hex[:12]
        with self.lock:
            self.workers[wid] = WorkerInfo(
                worker_id=wid,
                capabilities=b.get("capabilities", []),
                # liveness ages on the monotonic clock (SWFS011): an
                # NTP step must not mass-reap or immortalize workers
                last_seen=time.monotonic(),
                max_concurrent=int(b.get("maxConcurrent", 1)))
            # SchemaCoordinator: Descriptors carry declarative config
            # forms (plugin.proto); the ConfigStore validates against
            # them and the UI renders them
            for desc in b.get("descriptors", []):
                if desc.get("jobType"):
                    self.schemas[desc["jobType"]] =                         desc.get("fields", [])
            self._persist_workers()
        return 200, {"workerId": wid}

    def _poll(self, req: Request):
        """Long-poll: return the next admin->worker message for this
        worker (detection request or job assignment)."""
        b = req.json()
        wid = b["workerId"]
        deadline = time.time() + float(b.get("waitSeconds", 10.0))
        while time.time() < deadline and not self._stop.is_set():
            with self.lock:
                w = self.workers.get(wid)
                if w is None:
                    return 404, {"error": "unregistered worker"}
                w.last_seen = time.monotonic()
                if wid in self._pending_detection:
                    self._pending_detection.remove(wid)
                    return 200, {"type": "runDetection",
                                 "config": dict(self.config)}
                job = self._next_job_for(w)
                if job is not None:
                    job.status = "assigned"
                    job.worker_id = wid
                    job.add_trace(f"assigned to {wid}")
                    self._persist_job(job)
                    w.inflight += 1
                    return 200, {"type": "executeJob",
                                 "jobId": job.job_id,
                                 "jobType": job.job_type,
                                 "params": job.params,
                                 "requestId": job.request_id,
                                 "traceParent": job.trace_parent}
            time.sleep(0.05)
        return 200, {"type": "none"}

    def _next_job_for(self, w: WorkerInfo) -> Job | None:
        if w.inflight >= w.max_concurrent:
            return None
        for job in sorted(self.jobs.values(), key=lambda j: j.created):
            if job.status == "pending" and w.can(job.job_type):
                return job
        return None

    def _detection_result(self, req: Request):
        """Worker Detect() proposals -> deduped job queue
        (DetectorScheduler + JobDispatcher)."""
        b = req.json()
        accepted = []
        with self.lock:
            for prop in b.get("proposals", []):
                key = prop.get("dedupeKey") or \
                    f"{prop['jobType']}:{prop['params'].get('volumeId')}"
                existing = self._dedupe.get(key)
                if existing and \
                        self.jobs[existing].status in ("pending",
                                                       "assigned"):
                    continue
                rid, tparent = _trace_ctx()
                job = Job(job_id=uuid.uuid4().hex[:12],
                          job_type=prop["jobType"],
                          params=prop["params"], dedupe_key=key,
                          request_id=rid, trace_parent=tparent)
                job.add_trace(
                    f"detected by {b.get('workerId', '?')}"
                    + (f": {prop['reason']}" if prop.get("reason")
                       else ""))
                self.jobs[job.job_id] = job
                self._dedupe[key] = job.job_id
                self._persist_job(job)
                accepted.append(job.job_id)
        return 200, {"accepted": accepted}

    def _ui(self, req: Request):
        """Status page (the minimal analog of the reference's admin web
        UI, weed/admin/view/ — live topology, workers, job queue)."""
        import html as _html
        try:
            from ..operation import master_json
            vl = master_json(self.master, "GET", "/vol/list")
            status = master_json(self.master, "GET", "/cluster/status")
        except OSError:
            vl, status = {}, {}
        rows = []
        for dc_name, dc in vl.get("dataCenters", {}).items():
            for rack_name, rack in dc.get("racks", {}).items():
                for node in rack.get("nodes", []):
                    rows.append(
                        f"<tr><td>{_html.escape(dc_name)}/"
                        f"{_html.escape(rack_name)}</td>"
                        f"<td>{_html.escape(node['url'])}</td>"
                        f"<td>{len(node.get('volumes', []))}/"
                        f"{node.get('maxVolumeCount', '?')}</td>"
                        f"<td>{len(node.get('ecShards', []))}</td>"
                        f"</tr>")
        with self.lock:
            workers = [
                f"<tr><td>{_html.escape(w.worker_id)}</td>"
                f"<td>{_html.escape(', '.join(sorted(str(c.get('jobType', '?')) for c in w.capabilities)))}</td>"
                f"<td>{w.inflight}/{w.max_concurrent}</td>"
                f"<td>{time.monotonic() - w.last_seen:.0f}s ago"
                f"</td></tr>"
                for w in self.workers.values()]
            jobs = [
                f"<tr><td><a href='/maintenance/job?id={j.job_id}'>"
                f"{j.job_id}</a></td>"
                f"<td>{_html.escape(j.job_type)}</td>"
                f"<td>{_html.escape(j.status)}</td>"
                f"<td>{j.progress:.0%}</td>"
                f"<td>{_html.escape(j.message or '')}</td>"
                f"<td>{_html.escape(j.trace[-1]['event'] if j.trace else '')}"
                f"</td></tr>"
                for j in sorted(self.jobs.values(),
                                key=lambda j: -j.created)[:50]]
            config_rows = [
                f"<tr><td>{_html.escape(jt)}</td>"
                f"<td>{_html.escape(', '.join(f['name'] for f in fields))}</td>"
                f"<td>{_html.escape(str(self.config.get(jt, {})))}"
                f"</td></tr>"
                for jt, fields in sorted(self.schemas.items())]
        inner = f"""<p>master: {_html.escape(self.master)} &middot;
leader: {_html.escape(str(status.get('leader', '?')))} &middot;
topology: {_html.escape(str(status.get('topologyId', '?')))}</p>
<h2>Data nodes</h2>
<table><tr><th>dc/rack</th><th>url</th><th>volumes</th>
<th>ec volumes</th></tr>{''.join(rows)}</table>
<h2>Workers</h2>
<table><tr><th>id</th><th>capabilities</th><th>inflight</th>
<th>seen</th></tr>{''.join(workers)}</table>
<h2>Job types (schemas + config)</h2>
<table><tr><th>type</th><th>schema fields</th><th>config</th></tr>
{''.join(config_rows)}</table>
<h2>Jobs (latest 50)</h2>
<table><tr><th>id</th><th>type</th><th>status</th><th>progress</th>
<th>message</th><th>last decision</th></tr>{''.join(jobs)}</table>"""
        return self._page("seaweedfs-tpu admin", inner)

    def _csrf_input(self) -> str:
        return (f"<input type='hidden' name='csrf' "
                f"value='{self._csrf}'>")

    def _ui_write_guard(self, req: Request,
                        form: dict) -> "tuple | None":
        """Gate for browser-driven writes (POST /ui/*): the
        security.toml admin key (when configured) and the GET-served
        CSRF token, both or 403.  Order matters — auth first, so an
        unauthenticated caller learns nothing about token validity."""
        from .. import security
        err = security.current().check_admin(
            req.query, req.headers, req.remote_ip)
        if err:
            return 403, {"error": f"admin credentials required: {err}"}
        if form.get("csrf") != self._csrf:
            return 403, {"error": "missing or stale CSRF token; "
                                  "reload the form page"}
        return None

    @staticmethod
    def _form(req: Request) -> dict:
        """Decode an HTML form body; keep_blank_values so a field
        cleared to empty REACHES validation instead of silently
        keeping the old value (shared by both UI POST handlers)."""
        import urllib.parse as _up
        return {k: v[0] for k, v in
                _up.parse_qs((req.body or b"").decode(),
                             keep_blank_values=True).items()}

    class _FormShim:
        """Request shim: hands a parsed HTML form to the JSON config
        handler so both entry points share one validation path."""

        def __init__(self, payload: dict):
            self._payload = payload
            self.query: dict = {}

        def json(self) -> dict:
            return self._payload

    # -- multi-page UI (weed/admin/view/app/: cluster_volumes.templ,
    # cluster_ec_volumes.templ, maintenance_queue.templ,
    # maintenance_config_schema.templ roles) ---------------------------

    _NAV = ("<p><a href='/'>dashboard</a> | "
            "<a href='/ui/volumes'>volumes</a> | "
            "<a href='/ui/ec'>ec</a> | "
            "<a href='/ui/jobs'>jobs</a> | "
            "<a href='/ui/config'>config</a></p>")

    def _page(self, title: str, inner: str):
        import html as _html
        body = f"""<!doctype html><html><head>
<title>{_html.escape(title)} - seaweedfs-tpu admin</title>
<style>body{{font-family:sans-serif;margin:2em}}
table{{border-collapse:collapse;margin:1em 0}}
td,th{{border:1px solid #ccc;padding:4px 10px;text-align:left}}
h2{{margin-top:1.5em}} .ok{{color:#2a2}} .bad{{color:#c22}}
input{{margin:2px}}</style></head><body>
<h1>{_html.escape(title)}</h1>{self._NAV}{inner}</body></html>"""
        return 200, (body.encode(), "text/html; charset=utf-8")

    def _topology(self) -> dict:
        try:
            from ..operation import master_json
            return master_json(self.master, "GET", "/vol/list")
        except OSError:
            return {}

    def _ui_volumes(self, req: Request):
        """Per-volume inventory across the topology
        (cluster_volumes.templ role)."""
        import html as _html
        from ..topology import iter_volume_list_volumes
        rows = []
        for node, v in sorted(
                iter_volume_list_volumes(self._topology()),
                key=lambda t: (t[1]["id"], t[0]["url"])):
            garbage = v.get("deletedByteCount", 0)
            size = max(v.get("size", 0), 1)
            flags = []
            if v.get("readOnly"):
                flags.append("readonly")
            if v.get("remoteTiered"):
                flags.append("remote")
            rows.append(
                f"<tr><td>{v['id']}</td>"
                f"<td>{_html.escape(v.get('collection') or '-')}</td>"
                f"<td>{_html.escape(node['url'])}</td>"
                f"<td>{v.get('size', 0):,}</td>"
                f"<td>{v.get('fileCount', 0)}</td>"
                f"<td>{garbage / size:.0%}</td>"
                f"<td>{_html.escape(','.join(flags) or '-')}</td>"
                f"</tr>")
        return self._page(
            "Volumes",
            "<table><tr><th>id</th><th>collection</th><th>node</th>"
            "<th>bytes</th><th>files</th><th>garbage</th>"
            f"<th>flags</th></tr>{''.join(rows)}</table>"
            f"<p>{len(rows)} volume replicas</p>")

    def _ui_ec(self, req: Request):
        """EC volumes and shard spread (cluster_ec_volumes.templ)."""
        import html as _html
        from ..topology import iter_volume_list_ec_shards
        by_vol: dict[int, list] = {}
        for node, e in iter_volume_list_ec_shards(self._topology()):
            bits = int(e.get("ecIndexBits", 0))
            sids = [i for i in range(32) if bits >> i & 1]
            by_vol.setdefault(e.get("volumeId", e.get("id")),
                              []).append((node["url"], sids))
        rows = []
        for vid, spread in sorted(by_vol.items()):
            total = sum(len(s) for _, s in spread)
            cells = "; ".join(
                f"{_html.escape(url)}: {','.join(map(str, s))}"
                for url, s in sorted(spread))
            cls = "ok" if total >= 14 else "bad"
            rows.append(f"<tr><td>{vid}</td>"
                        f"<td class='{cls}'>{total}</td>"
                        f"<td>{cells}</td></tr>")
        return self._page(
            "EC volumes",
            "<table><tr><th>volume</th><th>shards</th>"
            f"<th>placement</th></tr>{''.join(rows)}</table>"
            f"<p>{len(rows)} EC volumes</p>")

    def _ui_jobs(self, req: Request):
        """Full job history with status filter + decision traces
        (maintenance_queue.templ + persisted job history)."""
        import html as _html
        want = req.query.get("status", "")
        with self.lock:
            jobs = sorted(self.jobs.values(),
                          key=lambda j: -j.created)
        # counts from the SAME snapshot the table renders, so the
        # filter totals can never disagree with the rows
        counts: dict[str, int] = {}
        for j in jobs:
            counts[j.status] = counts.get(j.status, 0) + 1
        if want:
            jobs = [j for j in jobs if j.status == want]
        filters = " | ".join(
            f"<a href='/ui/jobs?status={s}'>{s} ({n})</a>"
            for s, n in sorted(counts.items()))
        rows = []
        for j in jobs[:200]:
            trace = "<br>".join(
                f"{_html.escape(t.get('event', ''))} "
                f"{_html.escape(str(t.get('detail', '')))}"
                for t in j.trace[-3:])
            rows.append(
                f"<tr><td><a href='/maintenance/job?id={j.job_id}'>"
                f"{j.job_id}</a></td>"
                f"<td>{_html.escape(j.job_type)}</td>"
                f"<td>{_html.escape(j.status)}</td>"
                f"<td>{j.progress:.0%}</td>"
                f"<td>{_html.escape(str(j.params)[:80])}</td>"
                f"<td>{trace}</td></tr>")
        with self.lock:
            types_ = sorted(self.schemas)
        submit_opts = "".join(f"<option>{_html.escape(t)}</option>"
                              for t in types_)
        actions = (
            "<h2>Actions</h2>"
            "<form method='post' action='/ui/actions' "
            "style='display:inline'>"
            "<input type='hidden' name='action' value='detect'>"
            f"{self._csrf_input()}"
            "<button>run detection now</button></form> "
            "<form method='post' action='/ui/actions' "
            "style='display:inline'>"
            "<input type='hidden' name='action' value='submit'>"
            f"{self._csrf_input()}"
            f"<select name='jobType'>{submit_opts}</select> "
            "params (JSON): <input name='params' value='{}' "
            "size='30'> <button>submit job</button></form>")
        return self._page(
            "Jobs",
            f"<p>filter: <a href='/ui/jobs'>all</a> | {filters}</p>"
            + actions +
            "<table><tr><th>id</th><th>type</th><th>status</th>"
            "<th>progress</th><th>params</th><th>decisions</th></tr>"
            f"{''.join(rows)}</table>")

    def _ui_actions(self, req: Request):
        """Browser-driven maintenance actions (the reference admin
        UI's POST handlers): run a detection round now, or submit a
        job by type — both share the JSON API handlers' logic."""
        import json as _json
        form = self._form(req)
        denied = self._ui_write_guard(req, form)
        if denied is not None:
            return denied
        if form.get("action") == "detect":
            self._trigger(self._FormShim({}))
            return 303, (b"", {"Location": "/ui/jobs",
                               "Content-Type": "text/plain"})
        if form.get("action") == "submit":
            try:
                params = _json.loads(form.get("params") or "{}")
            except ValueError as e:
                return self._page("Submit error",
                                  f"<p class='bad'>bad params JSON: "
                                  f"{e}</p>"
                                  "<p><a href='/ui/jobs'>back</a></p>")
            status, payload = self._submit_job(self._FormShim(
                {"jobType": form.get("jobType", ""),
                 "params": params}))
            if status != 200:
                import html as _html
                return self._page(
                    "Submit error",
                    f"<p class='bad'>"
                    f"{_html.escape(str(payload))}</p>"
                    "<p><a href='/ui/jobs'>back</a></p>")
            return 303, (b"", {"Location": "/ui/jobs",
                               "Content-Type": "text/plain"})
        return 400, {"error": "unknown action"}

    def _ui_config(self, req: Request):
        """Schema-driven config FORMS (admin/plugin/DESIGN.md
        SchemaCoordinator: worker Descriptors carry the field schema,
        the operator edits values, RunDetection delivers them)."""
        import html as _html
        with self.lock:
            schemas = {jt: list(fields)
                       for jt, fields in sorted(self.schemas.items())}
            values = {jt: dict(self.config.get(jt, {}))
                      for jt in schemas}
        forms = []
        for jt, fields in schemas.items():
            inputs = []
            for f in fields:
                name = f["name"]
                cur = values[jt].get(name, f.get("default", ""))
                ftype = f.get("type", "string")
                inputs.append(
                    f"<label>{_html.escape(name)} "
                    f"<small>({_html.escape(ftype)})</small> "
                    f"<input name='{_html.escape(name)}' "
                    f"value='{_html.escape(str(cur))}'></label><br>")
            forms.append(
                f"<h2>{_html.escape(jt)}</h2>"
                f"<form method='post' action='/ui/config'>"
                f"<input type='hidden' name='jobType' "
                f"value='{_html.escape(jt)}'>"
                f"{self._csrf_input()}"
                f"{''.join(inputs)}"
                f"<button>apply</button></form>")
        if not forms:
            forms = ["<p>no worker has registered a config schema "
                     "yet</p>"]
        return self._page("Config", "".join(forms))

    def _ui_config_submit(self, req: Request):
        """HTML-form arm of /maintenance/config POST: same schema
        validation, then redirect back to the form."""
        form = self._form(req)
        denied = self._ui_write_guard(req, form)
        if denied is not None:
            return denied
        form.pop("csrf", None)       # not a schema field
        jt = form.pop("jobType", "")
        status, payload = self._set_config(self._FormShim(
            {"jobType": jt, "values": form}))
        if status != 200:
            import html as _html
            return self._page(
                "Config error",
                f"<p class='bad'>{_html.escape(str(payload))}</p>"
                "<p><a href='/ui/config'>back</a></p>")
        return 303, (b"", {"Location": "/ui/config",
                           "Content-Type": "text/plain"})

    def _submit_job(self, req: Request):
        """Operator-submitted job (the analog of dispatching work from
        the admin UI / shell rather than detection) — e.g. a
        multi-volume batch EC job for the mesh-batched worker path."""
        b = req.json()
        job_type = b.get("jobType")
        if not job_type:
            return 400, {"error": "jobType required"}
        params = b.get("params", {})
        with self.lock:
            # a job nobody can run would sit pending forever and wedge
            # its dedupe key — refuse it at submit time
            if not any(w.can(job_type) for w in self.workers.values()):
                return 400, {"error": f"no registered worker has the "
                                      f"{job_type!r} capability"}
            key = b.get("dedupeKey") or uuid.uuid4().hex
            # a batch EC job claims every per-volume key too, so it can
            # never run concurrently with a detection-queued single-
            # volume job for one of its members (the loser's unwind
            # would delete the winner's mounted shards AFTER the
            # original volume is gone — permanent data loss)
            keys = [key]
            if job_type == "erasure_coding" and \
                    isinstance(params.get("volumeIds"), list):
                keys += [f"ec:{int(v)}" for v in params["volumeIds"]]
            for k in keys:
                existing = self._dedupe.get(k)
                if existing and self.jobs[existing].status in (
                        "pending", "assigned"):
                    return 409, {"error": f"conflicts with live job "
                                          f"{existing} ({k})",
                                 "jobId": existing, "deduped": True}
            rid, tparent = _trace_ctx()
            job = Job(job_id=uuid.uuid4().hex[:12], job_type=job_type,
                      params=params, dedupe_key=key,
                      request_id=rid, trace_parent=tparent)
            job.add_trace("submitted by operator")
            self.jobs[job.job_id] = job
            for k in keys:
                self._dedupe[k] = job.job_id
            self._persist_job(job)
        return 200, {"jobId": job.job_id}

    def _touch(self, worker_id: str) -> None:
        w = self.workers.get(worker_id)
        if w is not None:
            w.last_seen = time.monotonic()

    def _progress(self, req: Request):
        b = req.json()
        with self.lock:
            # progress is a liveness signal: a single-threaded worker
            # cannot poll mid-job, so the reaper must count this
            self._touch(b.get("workerId", ""))
            job = self.jobs.get(b["jobId"])
            if job is not None:
                job.progress = float(b.get("progress", 0.0))
                job.message = b.get("message", "")
                job.updated = time.time()
        return 200, {}

    def _complete(self, req: Request):
        b = req.json()
        # worker job spans ride the completion report (the worker has
        # no listener for trace.show to query); re-record them here so
        # this admin's /debug/traces serves the job's execution trace
        if b.get("spans"):
            from .. import tracing
            tracing.ingest(b["spans"])
        with self.lock:
            self._touch(b.get("workerId", ""))
            job = self.jobs.get(b["jobId"])
            if job is not None:
                reporter = b.get("workerId", "")
                if job.status != "assigned" or \
                        job.worker_id != reporter:
                    # only the current owner of a live assignment may
                    # complete it: finished jobs, stall-requeued jobs
                    # (status pending — inflight already returned by the
                    # reaper), and reassigned jobs all ignore the report
                    return 200, {"ignored": True}
                job.status = "done" if b.get("success") else "failed"
                job.message = b.get("message", "")
                job.progress = 1.0
                job.updated = time.time()
                job.add_trace(f"{job.status} by {reporter}: "
                              f"{job.message[:200]}")
                self._persist_job(job)
                w = self.workers.get(reporter)
                if w is not None:
                    w.inflight = max(0, w.inflight - 1)
        return 200, {}

    # -- ops API ----------------------------------------------------------

    _FIELD_TYPES = {"int": int, "float": float, "string": str,
                    "bool": bool}

    def _get_config(self, req: Request):
        """ConfigStore + SchemaCoordinator view: per-job-type schema
        (from worker Descriptors) with current values."""
        with self.lock:
            return 200, {"jobTypes": {
                jt: {"fields": fields,
                     "values": dict(self.config.get(jt, {}))}
                for jt, fields in sorted(self.schemas.items())}}

    def _set_config(self, req: Request):
        """Schema-validated config update ({jobType, values}); applied
        to workers with the next RunDetection, persisted across
        restarts."""
        b = req.json()
        jt = b.get("jobType", "")
        values = b.get("values", {})
        with self.lock:
            fields = self.schemas.get(jt)
            if fields is None:
                return 404, {"error": f"no schema for job type {jt!r} "
                                      f"(no worker registered it)"}
            by_name = {f["name"]: f for f in fields}
            cleaned = {}
            for name, val in values.items():
                f = by_name.get(name)
                if f is None:
                    return 400, {"error": f"unknown field {name!r} for "
                                          f"{jt} (schema: "
                                          f"{sorted(by_name)})"}
                want = self._FIELD_TYPES.get(f.get("type", "string"),
                                             str)
                try:
                    cleaned[name] = want(val) if want is not bool                         else (val if isinstance(val, bool)
                              else str(val).lower() in ("1", "true",
                                                        "yes"))
                except (TypeError, ValueError):
                    return 400, {"error":
                                 f"field {name!r} wants "
                                 f"{f.get('type')}, got {val!r}"}
            self.config.setdefault(jt, {}).update(cleaned)
            self._persist_config()
            return 200, {"jobType": jt,
                         "values": dict(self.config[jt])}

    def _job_detail(self, req: Request):
        """Full job record incl. the decision trace
        (DESIGN.md WorkflowMonitor surface)."""
        jid = req.query.get("id", "")
        with self.lock:
            job = self.jobs.get(jid)
            if job is None:
                return 404, {"error": f"no job {jid!r}"}
            return 200, job.to_json()

    def _queue(self, req: Request):
        with self.lock:
            return 200, {"jobs": [{
                "jobId": j.job_id, "jobType": j.job_type,
                "status": j.status, "progress": j.progress,
                "message": j.message, "params": j.params,
            } for j in sorted(self.jobs.values(),
                              key=lambda j: j.created)]}

    def _trigger(self, req: Request):
        with self.lock:
            self._pending_detection = [
                wid for wid, w in self.workers.items()
                if any(c.get("canDetect") for c in w.capabilities)]
            asked = list(self._pending_detection)
        return 200, {"asked": asked}

    # a worker silent for this long is presumed dead; its assigned jobs
    # requeue so the dedupe key stops blocking re-detection
    WORKER_DEAD_AFTER = 60.0
    # an assigned job with no progress for this long requeues even if
    # its worker still polls (covers a lost completion report)
    JOB_STALL_AFTER = 300.0

    def _detection_loop(self) -> None:
        tick = min(self.detection_interval, 5.0)
        next_detection = time.time() + self.detection_interval
        while not self._stop.wait(tick):
            self._reap_dead_workers()
            if time.time() >= next_detection:
                next_detection = time.time() + self.detection_interval
                with self.lock:
                    self._pending_detection = [
                        wid for wid, w in self.workers.items()
                        if any(c.get("canDetect")
                               for c in w.capabilities)]

    def _reap_dead_workers(self) -> None:
        now = time.time()        # job.updated is persisted wall time
        mono = time.monotonic()  # worker liveness is in-memory
        with self.lock:
            dead = {wid for wid, w in self.workers.items()
                    if w.inflight > 0 and
                    mono - w.last_seen > self.WORKER_DEAD_AFTER}
            for job in self.jobs.values():
                if job.status != "assigned":
                    continue
                # persisted wall timestamp survives an admin restart;
                # monotonic would not compare across processes
                stalled = (now - job.updated  # noqa: SWFS011
                           > self.JOB_STALL_AFTER)
                if job.worker_id in dead or stalled:
                    w = self.workers.get(job.worker_id)
                    if w is not None and job.worker_id not in dead:
                        w.inflight = max(0, w.inflight - 1)
                    job.status = "pending"
                    job.worker_id = ""
                    job.updated = now
                    job.message = "requeued: worker lost or stalled"
                    job.add_trace(job.message)
                    self._persist_job(job)
            for wid in dead:
                self.workers[wid].inflight = 0
