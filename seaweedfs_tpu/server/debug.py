"""Debug/profiling plane (the analog of util/grace/pprof.go:16
StartDebugServer — every reference role can expose a localhost pprof
endpoint).

Routes (admin-gated when the security plane is on, see
httpd.is_admin_path):

  GET /debug/stacks            — every thread's current stack
  GET /debug/vars              — gc / thread / rss counters (expvar)
  GET /debug/profile?seconds=N — statistical sampling profile:
      samples sys._current_frames at ~10ms for N seconds and returns
      collated (frames -> sample count), most-sampled first — the
      Python stand-in for a CPU pprof.
  GET /debug/traces?request_id=R — spans of one trace from this
      process's ring buffer (tracing.py); without request_id the
      most recent spans (?limit=N, default 200).  The shell's
      `trace.show` fans this endpoint out across the cluster and
      merges the results into one tree.
  GET/POST /debug/faults — the failpoint plane (faults.py): GET lists
      armed sites + trigger counts; POST arms ({"spec": "..."} or the
      explicit {"site","action",...} form) or clears ({"clear": true
      or "site"}).  The chaos suite's runtime lever on every role.
  GET /debug/health — this process's per-peer circuit-breaker map and
      retry budget (util/retry); `trace.show` appends it so a chaos
      run is debuggable from the shell.
  GET/POST /debug/pprof — the sampling wall-clock profiler
      (profiling.Sampler): POST {"action": "start", "hz": N} arms it,
      {"action": "stop"} disarms and returns the final snapshot,
      {"action": "reset"} clears the folded table; GET returns the
      snapshot (?top=N limits the folded table,
      ?format=collapsed returns flamegraph.pl input as text/plain).
      Off by default; SEAWEEDFS_TPU_PROFILE_HZ arms it at boot.  The
      shell's `cluster.profile` arms every node, waits, and merges
      the folded stacks into one cluster-wide flame view.
  GET/POST /debug/slow — the flight recorder's ring
      (profiling.FlightRecorder): complete records of the tail —
      requests slower than the self-tracked p95 threshold, errored,
      deadline-exceeded, or QoS/brownout-shed — each with its span
      tree, per-stage wall+cpu split, deadline verdict and flight
      notes.  POST {"clear": true} empties it.  `cluster.slow` fans
      this out and merges by trace id across roles.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import traceback
from collections import Counter

from .httpd import HttpServer, Request


def install_debug_routes(http: HttpServer) -> None:
    # reads of the debug plane are chatter (HttpServer.route quiet=):
    # a scrape or a trace.show must not write into the ring it reads
    for path, fn in (("/debug/stacks", _stacks), ("/debug/vars", _vars),
                     ("/debug/profile", _profile),
                     ("/debug/traces", _traces),
                     ("/debug/faults", _faults_get),
                     ("/debug/health", _health), ("/debug/qos", _qos_get),
                     ("/debug/pprof", _pprof_get),
                     ("/debug/slow", _slow_get),
                     ("/debug/attribution", _attr_get)):
        http.route("GET", path, fn, quiet=True)
    http.route("POST", "/debug/faults", _faults_post)
    http.route("POST", "/debug/qos", _qos_post)
    http.route("POST", "/debug/pprof", _pprof_post)
    http.route("POST", "/debug/slow", _slow_post)
    http.route("POST", "/debug/attribution", _attr_post)


def install_autopilot_routes(http: HttpServer, ap) -> None:
    """The SLO autopilot's runtime lever (autopilot.py, ISSUE 20),
    registered by the roles that run a loop (filer, volume).  GET is
    the controller's whole state — knobs with bounds and current
    values, plane-guard state, the bounded action log.  POST:
    {"enabled": bool} flips the loop; {"knob": name, "value": v}
    force-actuates ONE knob through the registry (still
    bounds-clamped — the lever is an operator override, not a bounds
    escape); {"tick": true} runs one synchronous control step (chaos
    tests pin the cadence with it)."""
    def _ap_get(req: Request):
        return 200, ap.snapshot()

    def _ap_post(req: Request):
        b = req.json()
        try:
            if "enabled" in b:
                ap.set_enabled(bool(b["enabled"]))
            if "knob" in b:
                name = str(b["knob"])
                if name not in ap.actuators:
                    return 400, {"error": f"unknown knob {name!r}"}
                ap.actuate(name, float(b["value"]),
                           "debug lever", force=True)
            if b.get("tick"):
                ap.tick()
        except (TypeError, ValueError, KeyError) as e:
            return 400, {"error": str(e)}
        return 200, ap.snapshot()

    http.route("GET", "/debug/autopilot", _ap_get, quiet=True)
    http.route("POST", "/debug/autopilot", _ap_post)
    from .. import profiling
    profiling.maybe_autostart()  # SEAWEEDFS_TPU_PROFILE_HZ boot arming
    profiling.maybe_start_sched_probe()  # gil_wait_ratio gauge


def _pprof_get(req: Request):
    from .. import profiling
    s = profiling.sampler()
    if req.query.get("format") == "collapsed":
        return 200, (s.collapsed().encode(), "text/plain")
    try:
        top = int(req.query.get("top", 0))
    except ValueError:
        top = 0
    return 200, s.snapshot(top=top)


def _pprof_post(req: Request):
    from .. import profiling
    s = profiling.sampler()
    b = req.json()
    action = str(b.get("action", ""))
    if action == "start":
        hz = b.get("hz")
        try:
            hz = float(hz) if hz is not None else None
        except (TypeError, ValueError):
            return 400, {"error": f"bad hz {b.get('hz')!r}"}
        started = s.start(hz)
        return 200, {"running": s.running, "hz": s.hz,
                     "started": started}
    if action == "stop":
        s.stop()
        return 200, s.snapshot()
    if action == "reset":
        s.reset()
        return 200, s.snapshot()
    return 400, {"error": "body needs action: start|stop|reset"}


def _slow_get(req: Request):
    """The flight recorder's ring (profiling.FlightRecorder): the
    captured slow/error/deadline/shed requests with their span trees,
    stage wall+cpu splits, deadline verdicts and flight notes.
    `weed shell cluster.slow` fans this endpoint out and merges
    records by trace id across roles."""
    from .. import profiling
    # drain the native-plane flight rings first (ISSUE 18): a scrape
    # must see plane requests that finished since the last drainer
    # tick, or cluster.slow races the tick
    profiling.run_scrape_hooks()
    return 200, profiling.flight_recorder().snapshot()


def _slow_post(req: Request):
    """{"clear": true} empties the ring and latency history (chaos
    runs reset between scenarios the way /debug/faults does)."""
    from .. import profiling
    if req.json().get("clear"):
        profiling.flight_recorder().reset()
        return 200, profiling.flight_recorder().snapshot()
    return 400, {"error": "body needs clear: true"}


def _attr_get(req: Request):
    from .. import profiling
    scope = profiling.attribution_disarmed()
    return 200, {"disarmed": scope is not None,
                 "scope": scope or "",
                 "drainEnabled": profiling.plane_drain_enabled()}


def _attr_post(req: Request):
    """{"disarmed": true|false, "scope": "all"|"plane"|"drain"} —
    runtime kill/restore switch for the cost-attribution plane in
    this process, no restart needed.  Scope "all" (default) disarms
    everything including the wall-stage decomposition; "plane"
    disarms only the ISSUE 15 additions (CPU clocks, flight
    recorder); "drain" disarms only the ISSUE 18 native-plane
    flight-record drain (records keep accumulating C-side and age
    off the ring).  Also the lever for a within-cluster overhead A/B:
    separate clusters cannot resolve a ~1% cost under arm-to-arm boot
    noise, alternating armed/disarmed traffic windows on ONE cluster
    can."""
    from .. import profiling
    b = req.json()
    if "disarmed" not in b:
        return 400, {"error": "body needs disarmed: true|false"}
    scope_in = str(b.get("scope", "all"))
    if scope_in == "drain":
        profiling.set_plane_drain_disarmed(bool(b["disarmed"]))
    else:
        profiling.set_attribution_disarmed(
            bool(b["disarmed"]), scope=scope_in)
    scope = profiling.attribution_disarmed()
    return 200, {"disarmed": scope is not None,
                 "scope": scope or "",
                 "drainEnabled": profiling.plane_drain_enabled()}


def _faults_get(req: Request):
    from .. import faults
    return 200, {"armed": faults.armed(),
                 "triggered": faults.triggered()}


def _faults_post(req: Request):
    from .. import faults
    b = req.json()
    clear = b.get("clear")
    if clear:
        faults.disarm(None if clear is True else str(clear))
        return 200, {"armed": faults.armed()}
    try:
        if "spec" in b:
            n = faults.arm_spec(str(b["spec"]))
        elif "site" in b:
            faults.arm(
                str(b["site"]), str(b.get("action", "error")),
                p=float(b.get("p", 1.0)),
                n=None if b.get("n") is None else int(b["n"]),
                ms=float(b.get("ms", 0.0)),
                seed=None if b.get("seed") is None else int(b["seed"]),
                match=str(b.get("match", "")))
            n = 1
        else:
            return 400, {"error": "body needs spec/site/clear"}
    except ValueError as e:
        return 400, {"error": str(e)}
    return 200, {"armedCount": n, "armed": faults.armed()}


def _qos_get(req: Request):
    from .. import qos
    snap = qos.controller().snapshot()
    snap["throttle"] = qos.throttle().snapshot()
    return 200, snap


def _qos_post(req: Request):
    """The QoS plane's runtime lever (qos.py), mirroring
    /debug/faults: set per-tenant limits ({"tenant": ..., "rps": ...,
    "burst": ..., "inflightMb": ...}; tenant "default"/"*" sets the
    default, {"remove": name} drops one), flip enforcement
    ({"enabled": bool}), retune the EC feedback throttle
    ({"sloP99Ms": ..., "paceMinMs"/"paceMaxMs"/"checkIntervalMs"}),
    or reset everything ({"clear": true}).  Responds with the same
    snapshot GET serves, so a lever call round-trips."""
    from .. import qos
    b = req.json()
    ctl = qos.controller()
    try:
        if b.get("clear"):
            qos.configure(None)
            # a pace forced via the paceMs big-red-button has no
            # watcher thread to decay it once the config is inert —
            # "reset everything" must include it
            qos.throttle().set_pace(0.0)
        if "enabled" in b:
            ctl.set_enabled(bool(b["enabled"]))
        if b.get("remove"):
            ctl.set_tenant(str(b["remove"]), None)
        if b.get("tenant"):
            ctl.set_tenant(str(b["tenant"]),
                           qos.TenantLimit.from_json(b))
        cfg = ctl.config()
        for key, attr in (("sloP99Ms", "slo_p99_ms"),
                          ("paceMinMs", "pace_min_ms"),
                          ("paceMaxMs", "pace_max_ms"),
                          ("checkIntervalMs", "check_interval_ms")):
            if key in b:
                setattr(cfg, attr, float(b[key]))
        if "sloP99Ms" in b:
            if cfg.slo_p99_ms <= 0:
                qos.throttle().set_pace(0.0)
            qos.throttle().maybe_start()
        if "paceMs" in b:               # direct pace override (tests /
            qos.throttle().set_pace(    # operator big-red-button)
                float(b["paceMs"]) / 1e3)
    except (TypeError, ValueError) as e:
        return 400, {"error": str(e)}
    return _qos_get(req)


def _health(req: Request):
    from ..util import retry
    return 200, {"peers": retry.health_snapshot(),
                 "retryBudgetRemaining": retry.budget_remaining()}


def _traces(req: Request):
    from .. import tracing
    rid = req.query.get("request_id", "")
    if rid:
        spans = tracing.spans_for(rid)
    else:
        spans = tracing.recent_spans(
            int(req.query.get("limit", 200)))
    return 200, {"requestId": rid, "spans": spans}


def _stacks(req: Request):
    out = []
    names = {t.ident: t.name for t in threading.enumerate()}
    for tid, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(tid, '?')} ({tid}) ---")
        out.extend(line.rstrip() for line in
                   traceback.format_stack(frame))
    return 200, ("\n".join(out).encode(), "text/plain")


def _vars(req: Request):
    rss_kb = 0
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    rss_kb = int(line.split()[1])
    except OSError:
        pass
    counts = gc.get_count()
    return 200, {
        "threads": threading.active_count(),
        "gcCounts": list(counts),
        "gcObjects": len(gc.get_objects()),
        "rssKb": rss_kb,
        "uptimeHint": time.process_time(),
    }


def _profile(req: Request):
    seconds = min(float(req.query.get("seconds", 2)), 30.0)
    interval = 0.01
    samples: Counter = Counter()
    deadline = time.monotonic() + seconds
    me = threading.get_ident()
    n = 0
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = []
            f = frame
            while f is not None and len(stack) < 24:
                stack.append(f"{f.f_code.co_filename.rsplit('/', 1)[-1]}"
                             f":{f.f_lineno}:{f.f_code.co_name}")
                f = f.f_back
            samples[";".join(reversed(stack))] += 1
        n += 1
        time.sleep(interval)
    lines = [f"samples: {n} over {seconds}s @ {interval * 1000:.0f}ms"]
    for stack, count in samples.most_common(50):
        lines.append(f"{count:6d}  {stack}")
    return 200, ("\n".join(lines).encode(), "text/plain")
