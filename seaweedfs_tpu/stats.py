"""Prometheus-style metrics (weed/stats/metrics.go — the reference
defines vectors per role and serves them on -metricsPort; ours is a
minimal in-process registry rendered in the Prometheus text format on
each server's /metrics endpoint), plus the push-gateway loop
(metrics.go:534 LoopPushingMetric)."""

from __future__ import annotations

import threading
import urllib.parse
from collections import defaultdict


# latency buckets in SECONDS, 5ms through 10s: loopback slice
# fetches sit in the low buckets, WAN shard pulls in the high ones —
# the EC rebuild observation range
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                   1.0, 2.5, 5.0, 10.0)

# group-commit observability (util/group_commit.py): batch sizes are
# small integers (mean batch = sum/count is the headline number), and
# barrier waits live in the 100us..100ms band between "rode a batch
# for free" and "waited out an fsync" — DEFAULT_BUCKETS can't resolve
# either
GROUP_COMMIT_BATCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
                              128.0)
GROUP_COMMIT_WAIT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                             0.005, 0.01, 0.025, 0.05, 0.1, 0.25)

# filer meta-plane sub-stages (filer/meta_plane.py): serialize and
# barrier live in the 50us..25ms band; the async apply's per-event
# share sits near the bottom of it.  Mean = sum/count is the number
# to read per sub-stage.
META_SUB_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
                    0.005, 0.01, 0.025, 0.05, 0.1, 0.25)


def escape_label_value(v) -> str:
    """Prometheus text-format label escaping (exposition format §text
    "label_value can be any sequence of UTF-8 characters, but the
    backslash, double-quote, and line-feed characters have to be
    escaped as \\\\, \\", and \\n"): an unescaped source url or error
    string must not tear the exposition line."""
    return str(v).replace("\\", r"\\").replace('"', r'\"') \
        .replace("\n", r"\n")


class Metrics:
    def __init__(self, namespace: str):
        self.namespace = namespace
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, tuple], float] = defaultdict(float)
        self._gauges: dict[tuple[str, tuple], float] = {}
        self._hists: dict[tuple[str, tuple], dict] = {}
        self._help: dict[str, str] = {}
        # shared observer memo for hot call sites whose OWNER object
        # is transient (per-request StageTracks, module functions):
        # caller-chosen hashable key -> observer closure.  Call sites
        # with a long-lived owner (HttpServer) keep their own dict.
        self.obs_memo: dict = {}

    def counter_add(self, name: str, value: float = 1.0,
                    help_text: str = "", **labels) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] += value
            if help_text:
                self._help.setdefault(name, help_text)

    def gauge_set(self, name: str, value: float, help_text: str = "",
                  **labels) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._gauges[key] = value
            if help_text:
                self._help.setdefault(name, help_text)

    def histogram_observe(self, name: str, value: float,
                          buckets: "tuple[float, ...]" = DEFAULT_BUCKETS,
                          help_text: str = "", **labels) -> None:
        """Prometheus histogram (metrics.go uses prometheus.Histogram
        for the same surfaces — request/operation latencies)."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {
                    "buckets": tuple(buckets),
                    "counts": [0] * (len(buckets) + 1),  # +Inf last
                    "sum": 0.0, "count": 0}
            for i, le in enumerate(h["buckets"]):
                if value <= le:
                    h["counts"][i] += 1
                    break
            else:
                h["counts"][-1] += 1
            h["sum"] += value
            h["count"] += 1
            if help_text:
                self._help.setdefault(name, help_text)

    def observer(self, name: str,
                 buckets: "tuple[float, ...]" = DEFAULT_BUCKETS,
                 help_text: str = "", **labels):
        """Pre-resolved histogram observe (ROADMAP 1d): the per-call
        overhead of `histogram_observe` — building
        `tuple(sorted(labels.items()))`, probing the registry dict,
        re-interning the help text — was bisected at ~10-15% of a
        saturated filer, paid again for every observation of a label
        set that never changes.  This resolves the (metric, labelset)
        cell ONCE and returns a closure over its mutable dict; the
        closure does only the bucket scan under the registry lock, and
        is freely shareable across threads.  Hot call sites cache one
        observer per label set (first observe) instead of calling
        histogram_observe per request."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {
                    "buckets": tuple(buckets),
                    "counts": [0] * (len(buckets) + 1),  # +Inf last
                    "sum": 0.0, "count": 0}
            if help_text:
                self._help.setdefault(name, help_text)
        lock = self._lock
        bkts = h["buckets"]
        counts = h["counts"]

        def observe(value: float) -> None:
            with lock:
                for i, le in enumerate(bkts):
                    if value <= le:
                        counts[i] += 1
                        break
                else:
                    counts[-1] += 1
                h["sum"] += value
                h["count"] += 1

        return observe

    def batch_observer(self, name: str,
                       buckets: "tuple[float, ...]" = DEFAULT_BUCKETS,
                       help_text: str = "", **labels):
        """Bulk sibling of `observer`: consumes a whole numpy array of
        values in one lock round, bucketing with np.searchsorted —
        the native-plane flight-record drain observes thousands of
        stage samples per tick, where even the pre-resolved
        per-value closure was a measurable share of one core."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {
                    "buckets": tuple(buckets),
                    "counts": [0] * (len(buckets) + 1),  # +Inf last
                    "sum": 0.0, "count": 0}
            if help_text:
                self._help.setdefault(name, help_text)
        lock = self._lock
        bkts = h["buckets"]
        counts = h["counts"]

        def observe_batch(values) -> None:
            n = len(values)
            if not n:
                return
            import numpy as np
            vals = np.asarray(values, dtype=np.float64)
            # side="left": first bucket with le >= value, matching
            # the scalar closure's `value <= le` scan
            idx = np.searchsorted(np.asarray(bkts), vals, side="left")
            per = np.bincount(idx, minlength=len(counts))
            total = float(vals.sum())
            with lock:
                for i, c in enumerate(per.tolist()):
                    if c:
                        counts[i] += c
                h["sum"] += total
                h["count"] += n

        return observe_batch

    def counter_value(self, name: str, **labels) -> "float | None":
        """Read one counter cell (exact label set), or None if that
        cell has never been incremented — the autopilot's sensors
        need the distinction: an absent counter is a sensor gap (hold
        the knob), a zero delta is evidence."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._counters.get(key)

    def counter_sum(self, name: str, **labels) -> float:
        """Sum a counter across every label set that carries at least
        the given labels (the programmatic twin of the shell's
        `_counter_sum` over rendered text)."""
        want = set(labels.items())
        with self._lock:
            return sum(v for (n, ls), v in self._counters.items()
                       if n == name and want.issubset(ls))

    def gauge_value(self, name: str, **labels) -> "float | None":
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._gauges.get(key)

    def histogram_merged(self, name: str) -> "dict | None":
        """Snapshot of histogram `name` merged across every label set
        (the QoS feedback throttle's foreground-latency source: it
        wants 'this role's request_seconds', not one method+code
        cell).  Returns {"buckets", "counts", "sum", "count"} or None
        when the histogram has never been observed."""
        merged: "dict | None" = None
        with self._lock:
            for (n, _labels), h in self._hists.items():
                if n != name:
                    continue
                if merged is None:
                    merged = {"buckets": h["buckets"],
                              "counts": list(h["counts"]),
                              "sum": h["sum"], "count": h["count"]}
                elif merged["buckets"] == h["buckets"]:
                    merged["counts"] = [
                        a + b for a, b in zip(merged["counts"],
                                              h["counts"])]
                    merged["sum"] += h["sum"]
                    merged["count"] += h["count"]
        return merged

    def render(self) -> str:
        """Prometheus text exposition format."""
        out = []
        with self._lock:
            seen_types: set[str] = set()
            for store, mtype in ((self._counters, "counter"),
                                 (self._gauges, "gauge")):
                for (name, labels), value in sorted(store.items()):
                    full = f"{self.namespace}_{name}"
                    if full not in seen_types:
                        if name in self._help:
                            out.append(f"# HELP {full} "
                                       f"{self._help[name]}")
                        out.append(f"# TYPE {full} {mtype}")
                        seen_types.add(full)
                    if labels:
                        lbl = ",".join(
                            f'{k}="{escape_label_value(v)}"'
                            for k, v in labels)
                        out.append(f"{full}{{{lbl}}} {value}")
                    else:
                        out.append(f"{full} {value}")
            for (name, labels), h in sorted(self._hists.items()):
                full = f"{self.namespace}_{name}"
                if full not in seen_types:
                    if name in self._help:
                        out.append(f"# HELP {full} {self._help[name]}")
                    out.append(f"# TYPE {full} histogram")
                    seen_types.add(full)
                base = [f'{k}="{escape_label_value(v)}"'
                        for k, v in labels]
                cum = 0
                for le, n in zip(h["buckets"], h["counts"]):
                    cum += n
                    lbl = ",".join(base + [f'le="{le}"'])
                    out.append(f"{full}_bucket{{{lbl}}} {cum}")
                lbl = ",".join(base + ['le="+Inf"'])
                out.append(f"{full}_bucket{{{lbl}}} {h['count']}")
                suffix = f"{{{','.join(base)}}}" if base else ""
                out.append(f"{full}_sum{suffix} {h['sum']}")
                out.append(f"{full}_count{suffix} {h['count']}")
        return "\n".join(out) + "\n"


# process-wide registry for cross-cutting planes that predate any one
# role's registry: unified retry/backoff (util/retry), the per-peer
# circuit breakers, failpoint triggers (faults.py), and EC degraded-
# read/failover counters.  Every role's /metrics appends its
# exposition (render_process) after the role registry's own — the
# namespaces differ, so the two blocks never collide.
PROCESS = Metrics("seaweedfs_tpu")


def _proc_tree_sample() -> "tuple[float, float, int] | None":
    """(cpu_seconds, rss_bytes, process_count) for this process's
    whole /proc subtree — pre-fork SO_REUSEPORT workers and native
    plane children included, transitively.  One /proc pass builds the
    ppid map; the walk is in-memory.  None where /proc is absent
    (non-Linux); self's cutime/cstime ride along so already-reaped
    children (a restarted native plane) stay accounted.

    Root selection: SEAWEEDFS_TPU_TREE_ROOT when set AND alive (the
    filer pre-fork parent exports its own pid before spawning
    SO_REUSEPORT siblings, so a scrape the kernel routed to any ONE
    worker still reports the whole fleet), else this process."""
    import os
    me = os.getpid()
    try:
        me = int(os.environ.get("SEAWEEDFS_TPU_TREE_ROOT", "") or me)
    except ValueError:
        pass
    try:
        clk = os.sysconf("SC_CLK_TCK")
        page = os.sysconf("SC_PAGE_SIZE")
        names = os.listdir("/proc")
    except (OSError, ValueError, AttributeError):
        return None
    info: "dict[int, tuple[int, float, float, float]]" = {}
    for d in names:
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat", "rb") as f:
                raw = f.read(4096)
            # fields after the ")" of comm (proc(5)): [1]=ppid,
            # [11]=utime, [12]=stime, [13]=cutime, [14]=cstime,
            # [21]=rss pages
            parts = raw.rsplit(b") ", 1)[1].split()
            info[int(d)] = (
                int(parts[1]),
                (int(parts[11]) + int(parts[12])) / clk,
                (int(parts[13]) + int(parts[14])) / clk,
                int(parts[21]) * page)
        except (OSError, IndexError, ValueError):
            continue
    if me not in info:
        # stale TREE_ROOT (pre-fork parent died): degrade to self
        me = os.getpid()
        if me not in info:
            return None
    kids: "dict[int, list[int]]" = {}
    for pid, (ppid, _c, _rc, _r) in info.items():
        kids.setdefault(ppid, []).append(pid)
    cpu = rss = 0.0
    count = 0
    stack, seen = [me], set()
    while stack:
        pid = stack.pop()
        if pid in seen or pid not in info:
            continue
        seen.add(pid)
        _ppid, own, reaped, mem = info[pid]
        cpu += own + reaped
        rss += mem
        count += 1
        stack.extend(kids.get(pid, ()))
    return cpu, rss, count


def render_process() -> str:
    # process CPU, refreshed per scrape — operator visibility
    # (cluster.top / any Prometheus scrape can divide its delta by
    # request-rate deltas per node).  os.times() covers every thread
    # and costs ~1us; the TREE gauges below close the gap this
    # per-process number used to leave open: a filer in -workers mode
    # answers each scrape from ONE random SO_REUSEPORT worker, and
    # the native write/read planes are separate child processes — the
    # /proc subtree walk charges all of them to the listener the
    # operator actually scraped.
    import os
    t = os.times()
    PROCESS.gauge_set(
        "process_cpu_seconds", t[0] + t[1],
        help_text="user+system CPU consumed by this process "
                  "(cumulative; exported as a gauge)")
    tree = _proc_tree_sample()
    if tree is not None:
        cpu, rss, count = tree
        PROCESS.gauge_set(
            "process_tree_cpu_seconds", round(cpu, 3),
            help_text="user+system CPU of this process's whole /proc "
                      "subtree (pre-fork workers + native plane "
                      "children; cumulative, refreshed per scrape)")
        PROCESS.gauge_set(
            "process_tree_rss_bytes", rss,
            help_text="resident set of this process's whole /proc "
                      "subtree (shared pages double-counted across "
                      "forked workers)")
        PROCESS.gauge_set(
            "process_tree_procs", float(count),
            help_text="processes in this node's /proc subtree")
    return PROCESS.render()


class MetricsPusher:
    """LoopPushingMetric (metrics.go:534): periodically PUT the
    rendered registry to a Prometheus pushgateway at
    /metrics/job/<job>/instance/<instance>.  Push failures are
    logged-and-retried, never fatal — metrics delivery must not take
    a data server down."""

    def __init__(self, metrics: "Metrics", job: str, instance: str,
                 gateway: str, interval: float = 15.0):
        from .server.httpd import http_bytes
        self._http = http_bytes
        self.metrics = metrics
        self.gateway = gateway
        self.interval = interval
        self.path = (f"/metrics/job/{urllib.parse.quote(job)}"
                     f"/instance/{urllib.parse.quote(instance)}")
        self._stop = threading.Event()
        self._thread: "threading.Thread | None" = None

    def push_once(self) -> bool:
        try:
            st, _, _ = self._http(
                "PUT", f"{self.gateway}{self.path}",
                self.metrics.render().encode(),
                {"Content-Type": "text/plain; version=0.0.4"})
            return st < 300
        except OSError:
            return False

    def start(self) -> "MetricsPusher":
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            self.push_once()
