"""Plugin worker runtime (weed/plugin/worker/worker.go +
handler_registry.go): hosts JobHandlers, speaks the worker protocol
with the admin (register -> poll -> detect/execute -> report)."""

from __future__ import annotations

import threading
import traceback

from ..server.httpd import http_json


def must(r: dict, what: str) -> dict:
    """RPC error dicts abort the operation — shared by all handlers."""
    if isinstance(r, dict) and r.get("error"):
        raise RuntimeError(f"{what}: {r['error']}")
    return r


def _post_with_retry(url: str, payload: dict, attempts: int = 30) -> None:
    """Report-back POSTs must survive transient admin outages — a lost
    completion report would otherwise kill the worker loop thread.
    ~5 minutes of capped backoff; a still-lost report is backstopped by
    the admin's job-stall requeue (admin.py JOB_STALL_AFTER)."""
    import time
    for i in range(attempts):
        try:
            http_json("POST", url, payload)
            return
        except OSError:
            time.sleep(min(2.0 ** i, 10.0))


def apply_config_values(handler: "JobHandler", values: dict) -> None:
    """Admin ConfigStore values -> handler attributes: descriptor
    field names are camelCase on the wire (plugin.proto forms),
    handler attrs snake_case.  Unknown names are ignored (the admin
    already schema-validated).  Shared by the HTTP long-poll worker
    and the gRPC stream worker so the rule cannot drift."""
    for name, value in values.items():
        attr = PluginWorker._snake(name)
        if hasattr(handler, attr):
            setattr(handler, attr, value)


class JobHandler:
    """Contract mirrored from plugin/worker JobHandler
    (erasure_coding_handler.go:48 Capability, :61 Descriptor,
    :187 Detect, :445 Execute)."""

    job_type = "base"
    aliases: list[str] = []

    def capability(self) -> dict:
        return {"jobType": self.job_type, "canDetect": True,
                "canExecute": True, "weight": 50}

    def descriptor(self) -> dict:
        """Declarative config schema (plugin.proto descriptor forms)."""
        return {"jobType": self.job_type, "fields": []}

    def detect(self, worker: "PluginWorker") -> list[dict]:
        """Return job proposals: {jobType, params, dedupeKey}."""
        return []

    def execute(self, worker: "PluginWorker", job_id: str,
                params: dict) -> str:
        raise NotImplementedError


class PluginWorker:
    """A maintenance worker process (weed worker / tpu_ec sidecar)."""

    def __init__(self, admin: str, master: str, work_dir: str,
                 handlers: list[JobHandler],
                 max_concurrent: int = 1,
                 poll_wait: float = 5.0):
        self.admin = admin
        self.master = master
        self.work_dir = work_dir
        self.handlers = {h.job_type: h for h in handlers}
        for h in handlers:  # aliases resolve to the same handler
            for alias in h.aliases:
                self.handlers.setdefault(alias, h)
        self.max_concurrent = max_concurrent
        self.poll_wait = poll_wait
        self.worker_id = ""
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.executed: list[str] = []  # job ids, newest last

    # -- lifecycle --------------------------------------------------------

    def start(self):
        r = http_json("POST", f"{self.admin}/worker/register", {
            "capabilities": [h.capability() for h in
                             self.handlers.values()],
            "descriptors": [h.descriptor() for h in
                            self.handlers.values()],
            "maxConcurrent": self.max_concurrent,
        })
        self.worker_id = r["workerId"]
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- protocol loop ----------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                msg = http_json("POST", f"{self.admin}/worker/poll", {
                    "workerId": self.worker_id,
                    "waitSeconds": self.poll_wait,
                }, timeout=self.poll_wait + 10)
            except OSError:
                if self._stop.wait(1.0):
                    return
                continue
            if msg.get("error"):
                # e.g. the admin restarted and lost its registry —
                # re-register with backoff instead of hot-spinning
                if self._stop.wait(1.0):
                    return
                try:
                    r = http_json(
                        "POST", f"{self.admin}/worker/register", {
                            "workerId": self.worker_id,
                            "capabilities": [h.capability() for h in
                                             self.handlers.values()],
                            "maxConcurrent": self.max_concurrent})
                    self.worker_id = r.get("workerId", self.worker_id)
                except OSError:
                    pass
                continue
            mtype = msg.get("type")
            if mtype == "runDetection":
                self._apply_config(msg.get("config") or {})
                self._run_detection()
            elif mtype == "executeJob":
                self._execute(msg["jobId"], msg["jobType"],
                              msg.get("params", {}),
                              request_id=msg.get("requestId", ""),
                              trace_parent=msg.get("traceParent", ""))

    @staticmethod
    def _snake(name: str) -> str:
        return "".join("_" + c.lower() if c.isupper() else c
                       for c in name)

    def _apply_config(self, config: dict) -> None:
        for job_type, values in config.items():
            h = self.handlers.get(job_type)
            if h is not None:
                apply_config_values(h, values)

    def _run_detection(self) -> None:
        proposals = []
        for h in self.handlers.values():
            try:
                proposals.extend(h.detect(self))
            except Exception:  # noqa: BLE001 — detection must not kill loop
                traceback.print_exc()
        if proposals:
            _post_with_retry(f"{self.admin}/worker/detection_result",
                             {"workerId": self.worker_id,
                              "proposals": proposals})

    def _execute(self, job_id: str, job_type: str, params: dict,
                 request_id: str = "", trace_parent: str = "") -> None:
        # join the submitter's trace (tracing.py): the job rode the
        # admin queue, so context arrives in the message, not headers.
        # A detection-born job without context mints its own ids so
        # the execution is still traceable by `job-<id>`.  Context is
        # RESTORED afterwards — this loop thread lives on, and a
        # leaked rid would trace every later poll into this job.
        from .. import tracing
        from ..util.request_id import reset_request_id, set_request_id
        rid = request_id or f"job-{job_id}"
        token = set_request_id(rid)
        tracing.adopt_remote_parent(trace_parent, role="worker")
        h = self.handlers.get(job_type)
        try:
            # a job's spans: kept in the jobs' ring (tracing.py)
            with tracing.span(f"job:{job_type}", role="worker",
                              job=True) as sp:
                sp.set("jobId", job_id)
                try:
                    if h is None:
                        raise ValueError(
                            f"no handler for {job_type!r}")
                    message = h.execute(self, job_id, params)
                    success = True
                except Exception as e:  # noqa: BLE001 — report,
                    # don't die
                    traceback.print_exc()
                    message, success = f"{type(e).__name__}: {e}", \
                        False
                    sp.set_error(e)
            self.executed.append(job_id)
            _post_with_retry(f"{self.admin}/worker/complete", {
                "workerId": self.worker_id, "jobId": job_id,
                "success": success, "message": message,
                # the worker has no HTTP listener for trace.show to
                # query, so its spans ride the completion report and
                # the admin re-records them into ITS ring buffer
                "spans": tracing.spans_for(rid)})
        finally:
            reset_request_id(token)
            tracing.adopt_remote_parent("")

    def report_progress(self, job_id: str, progress: float,
                        message: str = "") -> None:
        try:
            http_json("POST", f"{self.admin}/worker/progress", {
                "workerId": self.worker_id, "jobId": job_id,
                "progress": progress, "message": message})
        except OSError:
            pass
