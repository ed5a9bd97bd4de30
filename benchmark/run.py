"""One run of one cell: `python3 -m benchmark.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>`.

Brings the cluster up under a memory-backed data root, loads seeded
volumes, warms every program the window will use, measures for
`--seconds`, reads the worker child's ledgers (and, traced, its
profiler trace), tears everything down, checks what the timed path
left behind against the plain reference, and prints one JSON line
last.  This parent never imports jax.  What belongs to one
configuration, one traffic mix or one per-layer metric is a file found
by the name `BENCHMARK.json` gives it; nothing here names a cell.

`--rehearse` walks the same path at the sizes the configuration's
`rehearse` block states, with JAX held to the CPU: for the sandbox and
the tests.  It reports no device metric and its line says so.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import itertools
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

T_PROCESS_START = time.time()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import cluster as cl            # noqa: E402
from benchmark import load as ld               # noqa: E402
from benchmark import reference, trace_reduce  # noqa: E402
from benchmark.cluster import BenchFailure     # noqa: E402

TIME_LIMIT_S = 340            # the contract allows 360; a first run,
#                               which compiles, takes about 110 s here
JOB_TIMEOUT_S = 300.0


# -- BENCHMARK.json and the files it names ------------------------------------

def load_spec(root: str = REPO, held: bool = False) -> dict:
    """BENCHMARK.json; with `held`, the entries of
    benchmark/held_cells.json beside it: cells taken out until the
    program is mended, which `--held` and the tests still run."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    path = os.path.join(root, "benchmark", "held_cells.json")
    if held and os.path.exists(path):
        with open(path) as f:
            kept = json.load(f)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            names = {e["name"] for e in spec[key]}
            spec[key] = spec[key] + [e for e in kept.get(key, [])
                                     if e["name"] not in names]
    return spec


def cell_files(spec: dict, workload: str, root: str = REPO) -> dict:
    """The cell's entry, configuration and traffic mix, each from the
    file its name leads to."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        cfg = json.load(f)
    bench_dir = os.path.dirname(os.path.dirname(
        os.path.join(root, conf["file"])))
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"cell": cell, "cfg": cfg, "traffic": traffic,
            "bench_dir": bench_dir}


def metrics_of(spec: dict, kind: str, workload: str) -> "list[dict]":
    """The cell's metrics of one kind.  A metric with a `workloads` key
    belongs to the cells it lists; an end-to-end one without belongs to
    every cell; a per-layer one without belongs to every cell that
    reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def metric_reader(bench_dir: str, name: str):
    """`read(ctx)` of benchmark/metrics/<name>.py; a quantity split by
    the end-to-end metric it moves (`x.enc`, `x.rd`) may keep one
    reader, benchmark/metrics/x.py."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(bench_dir, "metrics",
                            name.rsplit(".", 1)[0] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- the window's rules, as plain functions ---------------------------------

def job_rate_GBps(jobs: "list[dict]") -> "float | None":
    """All `.dat` bytes of the jobs that finished well, over the time
    from the first submit to the last finish of any job: whole jobs,
    no rounding, no median of pieces."""
    if not jobs:
        return None
    span = max(j["finish"] for j in jobs) - min(j["submit"] for j in jobs)
    done = sum(j["bytes"] for j in jobs if j["ok"])
    return done / span / 1e9 if span > 0 and done > 0 else None


def run_chain(groups: "list[list[dict]]", seconds: float, t_open: float,
              submit, wait, now=time.time, ready=None, on_done=None,
              sleep=time.sleep, every: float = 0.02
              ) -> "tuple[list[dict], float]":
    """Jobs back to back, one job a group of volumes: the first at the
    window's opening, each next as its predecessor ends, none after
    `seconds` have passed; the one in flight then runs to its end.
    Where `ready(group)` is given, a group is submitted only once it
    says so; `on_done(group)` hears of every job that ends inside the
    window.  Returns the jobs and the seconds of the window left with
    no job: waits for a group not ready, and the rest of the window
    where the groups ran out."""
    end = t_open + seconds
    jobs, dry = [], 0.0
    for group in groups:
        t0 = t = now()
        while ready and t < end and not ready(group):
            sleep(every)
            t = now()
        if t >= end:
            return jobs, dry + max(0.0, end - t0)
        dry += t - t0
        job_id = submit(group)
        state = wait(job_id)
        jobs.append({"id": job_id, "vids": [v["vid"] for v in group],
                     "vols": group, "bytes": sum(v["bytes"] for v in group),
                     "submit": t, "finish": now(),
                     "ok": state["status"] == "done",
                     "message": state.get("message", "")})
        if on_done and jobs[-1]["finish"] < end:
            on_done(group)
    return jobs, dry + max(0.0, end - now())


def device_work(before: dict, after: dict) -> "int | None":
    """Values the worker child read back from the chip between two of
    its marks (`worker_proc.count_reads`): what the chip computed for
    the program.  None where nothing is counted (JAX on the CPU)."""
    a, b = before.get("reads"), after.get("reads")
    return None if a is None or b is None else b - a


def work_said(work: "int | None") -> str:
    return "not counted" if work is None else f"{work:+d}"


def check_device_work(what: str, work: "int | None", chip: bool) -> None:
    """Every cell's path runs on the chip: a set-up job or a window that
    did no work there fails the run at once, before it prints a line
    whose device numbers would read a path the cell does not have.
    Nothing is checked off the chip or where nothing is counted."""
    if chip and work is not None and work <= 0:
        raise BenchFailure(f"{what} did no work on the chip (values read "
                           f"back from it {work_said(work)}): this cell's "
                           "path does not run on the device")


def volumes_for(seconds: float, job_seconds: float, margin: float) -> int:
    """Volumes a window of `seconds` can start jobs on, when a job
    takes `margin` of what the set-up's own job took."""
    return max(1, math.ceil(seconds / (job_seconds * margin)))


# The share of the machine's memory that a run's data root may come to
# hold.  Every volume of a window rests in memory until the comparison
# after it, so a faster program asks for more of them; the machine has
# no more to give.  The machine is the least of `MemTotal`, a cgroup
# limit and what the chip tool holds its machine to from outside, which
# no file on it says: "ran out of memory. It met the machine's limit
# of 40 GiB" is the tool's own message where it ended a run on a host
# whose /proc/meminfo says 45.0 GiB (PERF.md 7, PR 32).  Two thirds of
# that is 28.63 GB; the third left is the roles', the chip's owner's
# (it pins some 4.7 GB while it initialises), the comparison's, and
# the tmpfs pages of the run before, which come back over seconds.
# One number for every cell: no flag, no variable.
MACHINE_LIMIT_BYTES = 40 * 2**30
MEMORY_SHARE = 2 / 3
SHORT_WINDOW_S = 20.0         # a window the budget closes under this is
#                               too short a measure: said loudly


def memory_budget(total: int) -> int:
    """Bytes a run's data root may come to hold on a machine whose
    memory (`MemTotal`, or a cgroup's limit where that is less) is
    `total`."""
    return int(min(total, MACHINE_LIMIT_BYTES) * MEMORY_SHARE)


def set_bytes(cfg: dict, vol_bytes: int, n: int, group: int = 1) -> int:
    """What `n` job volumes of `vol_bytes`, `group` of them a job, bring
    the data root to hold at the worst moment a window can reach: all
    but the last job's encoded and at rest as (k+r)/k of a volume of
    shards, the last job's in flight at the end of its distribute: for
    each its source, the worker's copy, the worker's shard files and
    the targets' (a job of several volumes pulls them all before it
    encodes and keeps them all until it ends)."""
    if n <= 0:
        return 0
    grow = (cfg["data_shards"] + cfg["parity_shards"]) / cfg["data_shards"]
    return math.ceil(vol_bytes * (grow * (n - group)
                                  + group * (2 + 2 * grow)))


def volumes_within(cfg: dict, vol_bytes: int, room: float,
                   group: int = 1) -> int:
    """`n_budget`: the most job volumes, in whole jobs of `group`,
    whose `set_bytes` fit `room`."""
    n = 0
    while set_bytes(cfg, vol_bytes, n + group, group) <= room:
        n += group
    return n


def burst_of(cfg: dict, traffic_jobs: dict) -> "int | None":
    """The stated number of jobs of a traffic file whose `jobs` name,
    under `count_from`, the configuration's key that holds it: that
    many back to back from the window's opening and no more, whatever a
    job takes.  None where the jobs fill the window."""
    key = traffic_jobs.get("count_from")
    if key is None:
        return None
    count = cfg.get(key)
    if not isinstance(count, int) or count < 1:
        raise BenchFailure(f"jobs.count_from {key!r}: the configuration "
                           f"has {count!r} there, not a count of jobs")
    return count


def group_of(cfg: dict, traffic_jobs: dict) -> int:
    """Volumes a job: the configuration's count under the key that a
    traffic file's `jobs` name as `group_from` (one `ec.encode` job of
    that many sealed volumes of one collection, the worker's batch
    path); 1 where none is named, one volume a job."""
    key = traffic_jobs.get("group_from")
    if key is None:
        return 1
    count = cfg.get(key)
    if not isinstance(count, int) or isinstance(count, bool) or count < 1:
        raise BenchFailure(f"jobs.group_from {key!r}: the configuration "
                           f"has {count!r} there, not a count of volumes")
    return count


def rotation_of(cfg: dict, traffic_jobs: dict) -> "int | None":
    """The count of volumes a repair window cycles through, for a
    traffic file whose `jobs` state the `kind` "rebuild" (the
    configuration's `lost_shards` taken from each before its repair);
    None for the default kind, "encode", where the configuration may
    state no shards lost."""
    kind = traffic_jobs.get("kind", "encode")
    lost = cfg.get("lost_shards")
    if kind == "encode":
        if lost is not None:
            raise BenchFailure(f"lost_shards {lost!r}: an encode cell's "
                               "volumes lose no shards")
        return None
    count = traffic_jobs.get("rotation")
    total = cfg["data_shards"] + cfg["parity_shards"]
    if kind != "rebuild":
        raise BenchFailure(f"jobs.kind {kind!r}: not encode or rebuild")
    if not isinstance(count, int) or isinstance(count, bool) or count < 1:
        raise BenchFailure(f"jobs.rotation {count!r}: not a count of "
                           "volumes")
    if not isinstance(lost, list) or not lost or \
            len(set(lost)) != len(lost) or \
            len(lost) > cfg["parity_shards"] or \
            not all(isinstance(s, int) and 0 <= s < total for s in lost):
        raise BenchFailure(f"lost_shards {lost!r}: not shard ids a "
                           f"{cfg['data_shards']}+{cfg['parity_shards']} "
                           "volume can be rebuilt without")
    if "group_from" in traffic_jobs or "count_from" in traffic_jobs:
        raise BenchFailure("a repair is one volume a job, back to back")
    return count


REBUILT = re.compile(r"rebuilt shards \[([\d, ]*)\]")


def rebuilt_ids(message: str) -> "list[int] | None":
    """The shard ids a repair job's result says it rebuilt."""
    got = REBUILT.search(message)
    return None if got is None else \
        [int(s) for s in got.group(1).split(",") if s.strip()]


def check_room(what: str, n: int, need: int, have: int) -> None:
    """Set-up ends here, with both numbers, rather than the machine
    running out of memory under a window."""
    if have < need:
        raise BenchFailure(
            f"{n} job volumes need {need} bytes ({need / 1e9:.2f} GB) of "
            f"{what} and {have} bytes ({have / 1e9:.2f} GB) are there")


def close_of(wanted: int, n_budget: int, started: int, dry: float,
             burst: bool = False) -> "tuple[str, float]":
    """("seconds" | "budget", `chain_dry_s`).  Seconds of the window
    left with no job are a fault when the run loaded what it asked for
    and still ran out.  They are the rule in two cases, each only when
    the chain started a job on every volume it was meant to have.  The
    memory budget cut the volumes: then the budget's last job closed
    the window, jobs back to back until then.  Or the traffic states a
    burst of `wanted` jobs: then the window stays open to its seconds,
    for what runs beside the jobs."""
    if dry > 0 and burst and started == wanted:
        return "seconds", 0.0
    if dry > 0 and wanted > n_budget and started == n_budget:
        return "budget", 0.0
    return "seconds", dry


PHASE_MARKS = (("pull", "marked readonly", "copied volume files"),
               ("encode", "copied volume files", "encoded "),
               ("distribute", "encoded ", "distributed shards"),
               ("finish", "distributed shards", "end"))
# a job of several volumes reports "pulled volume v (i/n)" once a
# volume, "batch-encoded n volumes" once, "distributed volume v (i/n)"
# once a volume: a phase runs to the last report of its kind
BATCH_MARKS = (("pull", "start", "pulled volume"),
               ("encode", "pulled volume", "batch-encoded"),
               ("distribute", "batch-encoded", "distributed volume"),
               ("finish", "distributed volume", "end"))


# a repair (`EcRebuildHandler`) reports "streaming rebuild on <server>"
# once the survivors are found, "rebuilt [ids]" once they are mounted,
# "rebalanced n shards" once the stripe is spread again
REPAIR_MARKS = (("locate", "start", "streaming rebuild on"),
                ("rebuild", "streaming rebuild on", "rebuilt"),
                ("balance", "rebuilt", "rebalanced"),
                ("finish", "rebalanced", "end"))


def job_phases(log: "list[list]") -> "dict[str, dict]":
    """{job id: {"start", "end", "phases": {name: (s, e)}}} from the
    worker child's log of progress reports."""
    by_job: "dict[str, list]" = {}
    for job_id, label, t in log:
        if job_id:
            by_job.setdefault(job_id, []).append((label, t))
    out = {}
    for job_id, marks in by_job.items():
        batch = any(lb.startswith(("pulled volume", "batch-encoded"))
                    for lb, _t in marks)
        repair = any(lb.startswith("streaming rebuild on")
                     for lb, _t in marks)

        def at(prefix):
            got = [t for lb, t in marks if lb.startswith(prefix)]
            return (got[-1] if batch else got[0]) if got else None
        phases = {}
        for name, a, b in (BATCH_MARKS if batch else REPAIR_MARKS if repair
                           else PHASE_MARKS):
            s, e = at(a), at(b)
            if s is not None and e is not None:
                phases[name] = (s, e)
        out[job_id] = {"start": at("start"), "end": at("end"),
                       "phases": phases}
    return out


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class Hooks:
    """Where the tests and the control break the timed path: each a
    callable (cluster, state) -> None.  `memory_total` stands in for
    the machine's `MemTotal`, so that a toy run can meet its budget;
    `root` for the checkout whose BENCHMARK.json and benchmark/ files
    name the cell, so that a test can bring entries of its own.
    `after_repair(cluster, vol)` runs on a repair window's helper
    thread as each repair of the window ends, before what it rebuilt is
    read back."""
    before_window: "object | None" = None
    before_verify: "object | None" = None
    after_repair: "object | None" = None
    memory_total: "int | None" = None
    root: "str | None" = None


def say(msg: str) -> None:
    print(msg, flush=True)


def verify_needles(cluster, vols: "list[dict]", per_volume: int,
                   seed: int) -> "tuple[int, int]":
    """(needles compared, needles wrong or unreadable): a sample drawn
    from the seed, the first and the last of each volume in it, read
    back through the served path."""
    from seaweedfs_tpu import operation
    picks = []
    for vol in vols:
        n = len(vol["order"])
        rng = np.random.default_rng([seed, 77, vol["index"]])
        idx = {0, n - 1} | set(int(i) for i in rng.choice(
            n, size=min(n, per_volume), replace=False))
        picks += [(vol["order"][i], vol["fids"][vol["order"][i]])
                  for i in sorted(idx)]

    def bad(item) -> int:
        fid, want = item
        try:
            return int(cl.digest(operation.read(cluster.master, fid))
                       != want)
        except Exception as e:  # noqa: BLE001 — unreadable is wrong
            sys.stderr.write(f"verify read {fid}: {e!r}\n")
            return 1
    with ThreadPoolExecutor(8) as pool:
        return len(picks), sum(pool.map(bad, picks))


def settled(what: str, count, timeout: float,
            every: float = 0.5) -> int:
    """`count()` once it reads 0, or what it still reads after
    `timeout` seconds.  The master hears of a mount or a delete by the
    servers' heartbeats, and a pulse taken before the change can land
    after the one that told of it: what the master says a second late
    is late, not wrong; only what it does not say after twenty pulses
    is a fault."""
    t0 = time.monotonic()
    got = count()
    while got and time.monotonic() - t0 < timeout:
        time.sleep(every)
        got = count()
    if time.monotonic() - t0 >= every:
        say(f"{what}: read {got} after {time.monotonic() - t0:.1f}s "
            "of waiting for the master")
    return got


class Rotation:
    """The volumes of a repair window and their losses: which have lost
    the configuration's `lost_shards`, and the losses after each
    repair, made on one thread of their own, off the chain's.  Before a
    repaired volume loses its shards again, that thread reads what the
    repair rebuilt back against the shards as the volume was encoded,
    kept aside under `keep_dir` before the first loss.  A volume is
    ready for its next repair once the master lists none of its lost
    shards, read again at each submit: a server's late heartbeat that
    listed them again would hold the chain, and count as its dry
    time."""

    def __init__(self, cluster, keep_dir: str, after_repair=None):
        self.cluster, self.keep_dir = cluster, keep_dir
        self.after_repair = after_repair
        self.sids = list(cluster.cfg["lost_shards"])
        self.lost: "set[int]" = set()
        self.lock = threading.Lock()
        self.waits: "list[float]" = []   # a job's end to its volume ready
        self.checked, self.bytes_wrong = 0, 0    # the window's repairs
        self.pool = ThreadPoolExecutor(1)
        self.pending: list = []

    def keep(self, vols: "list[dict]") -> None:
        """Each volume's shards `lost_shards`, as it was encoded, copied
        aside: what every repair of it has to rebuild."""
        os.makedirs(self.keep_dir, exist_ok=True)
        for vol in vols:
            found = self.cluster.shard_paths(vol)
            for sid in self.sids:
                shutil.copyfile(found[sid][0], self.kept(vol, sid))

    def kept(self, vol: dict, sid: int) -> str:
        return os.path.join(self.keep_dir, f"{vol['vid']}.{sid}")

    def rebuilt_wrong_bytes(self, vol: dict) -> int:
        """Bytes of the volume's `lost_shards`, wherever they now are,
        that differ from those kept aside (a shard missing: all of it)."""
        found = self.cluster.shard_paths(vol)
        return sum(reference.bytes_differing(self.kept(vol, sid), p)
                   for sid in self.sids
                   for p in found.get(sid) or [None])

    def broken(self, vol: dict) -> bool:
        with self.lock:
            return vol["vid"] in self.lost

    def ready(self, group: "list[dict]") -> bool:
        return self.broken(group[0]) and \
            not self.cluster.shards_listed(group[0], self.sids)

    def submit(self, group: "list[dict]") -> str:
        with self.lock:
            self.lost.discard(group[0]["vid"])
        return self.cluster.submit_rebuild(group[0])

    def lose(self, vols: "list[dict]") -> None:
        """Takes the lost shards from each volume, and returns once the
        master lists none of them."""
        for vol in vols:
            self.cluster.lose_shards(vol, self.sids)
        cl.wait_for(f"the master to list none of shards {self.sids} of "
                    f"volumes {[v['vid'] for v in vols]}", lambda: not any(
                        self.cluster.shards_listed(v, self.sids)
                        for v in vols), 60.0, every=0.02)
        with self.lock:
            self.lost.update(v["vid"] for v in vols)

    def lose_again(self, group: "list[dict]") -> None:
        """A window's repair ended: what it rebuilt read back, then the
        shards lost again."""
        vol, t0 = group[0], time.time()

        def again():
            if self.after_repair:
                self.after_repair(self.cluster, vol)
            wrong = self.rebuilt_wrong_bytes(vol)
            self.checked += 1
            self.bytes_wrong += wrong
            if wrong:
                sys.stderr.write(f"repair of volume {vol['vid']} ended at "
                                 f"{t0:.3f}: {wrong} rebuilt bytes wrong\n")
            self.lose([vol])
            self.waits.append(time.time() - t0)
        self.pending.append(self.pool.submit(again))

    def drain(self) -> "list[float]":
        """Waits for the losses in flight; the seconds each took."""
        self.pool.shutdown(wait=True)
        for f in self.pending:
            f.result()
        return self.waits


class Run:
    """One run's moving parts, so that each step below is short."""

    def __init__(self, args, hooks: Hooks):
        self.args, self.hooks = args, hooks
        self.spec = load_spec(hooks.root or REPO, held=args.held)
        files = cell_files(self.spec, args.workload, hooks.root or REPO)
        self.cell, self.bench_dir = files["cell"], files["bench_dir"]
        cfg, traffic = files["cfg"], files["traffic"]
        if args.rehearse:
            cfg = dict(cfg, **cfg.get("rehearse", {}))
            traffic = dict(traffic, **traffic.get("rehearse", {}))
        self.cfg, self.traffic = cfg, traffic
        self.reads, self.jobs_t = traffic.get("reads"), traffic.get("jobs")
        self.burst = burst_of(cfg, self.jobs_t) if self.jobs_t else None
        self.group = group_of(cfg, self.jobs_t) if self.jobs_t else 1
        self.rotation = rotation_of(cfg, self.jobs_t) if self.jobs_t \
            else None
        self.seconds = float(args.seconds)
        self.job_shape = (cfg["needles_per_volume"], cfg["needle_bytes"])
        # the program a job of several volumes compiles follows their
        # count, not their size (short volumes are zero-padded to the
        # step), so the set-up's group is of the configuration's toy
        # volumes and the memory budget is left to the window
        self.warm_shape = self.job_shape if self.group == 1 else (
            files["cfg"]["rehearse"]["needles_per_volume"],
            cfg["needle_bytes"])
        # the objects clients read may be other than what a sealed
        # volume for the maintenance job holds
        self.read_shapes = [(cfg["read_objects"] // cfg["read_volumes"],
                             cfg["read_object_bytes"])
                            ] * cfg["read_volumes"] if self.reads else []
        self.state: dict = {"seed": args.seed}

    def first_shapes(self) -> "list[tuple[int, int]]":
        """The read set, and one job's volumes whose set-up job warms
        every program the window's jobs will use."""
        return self.read_shapes + (
            [self.warm_shape] * self.group if self.jobs_t else [])

    # -- set-up -------------------------------------------------------------

    def bring_up(self, root: str) -> None:
        args, cfg = self.args, self.cfg
        self.root = root
        self.procs = cl.Procs(root, args.rehearse)
        self.cluster = cl.Cluster(self.procs, root, cfg)
        self.cluster.start()
        t0 = time.perf_counter()   # the worker child reaches the chip meanwhile
        first = self.cluster.load_volumes(
            args.seed, self.first_shapes(), group=self.group,
            grouped_from=len(self.read_shapes))
        say(f"loaded {[(len(v['order']), v['bytes']) for v in first]} "
            f"(needles, .dat bytes) in {time.perf_counter() - t0:.2f}s")
        ready = self.cluster.wait_worker()
        self.dev = ready["device"]
        say(f"worker child: {json.dumps(ready)}")
        say(f"machine: {json.dumps(cl.machine_now(ready.get('pid')))}")
        if not args.rehearse:
            if self.dev["platform"] != "tpu":
                raise BenchFailure(f"no chip: JAX runs on {self.dev}")
            if self.dev["count"] < self.cell["chips"]:
                raise BenchFailure(f"{self.dev['count']} chips, the cell "
                                   f"asks for {self.cell['chips']}")
            trace_reduce.peaks_for(self.dev["kind"])   # unknown kind: error
        self.read_vols = first[:len(self.read_shapes)]
        # every program the window will use is compiled or fetched
        # here: the read set's volumes one a job, then a job of the
        # window's shape; for repairs that volume then loses its shards
        # and is repaired once
        took = 0.0
        n_read = len(self.read_shapes)
        warm = first[n_read:]
        for vols in [[v] for v in first[:n_read]] + (
                [warm] if self.jobs_t else []):
            took = self.set_up_job(vols, self.cluster.submit_encode)
        if self.rotation:
            self.rot = Rotation(self.cluster, os.path.join(
                self.root, "lost_shards"), self.hooks.after_repair)
            self.rot.lose(warm)
            took = self.set_up_job(warm, self.rot.submit)
        self.job_vols, self.sizing = [], None
        if self.jobs_t:
            # a window's volume by the set-up's: the same needles, more
            vol_bytes = -(-first[-1]["bytes"] * self.job_shape[0]
                          // self.warm_shape[0])
            self.job_vols = self.load_job_volumes(took, vol_bytes,
                                                  len(first))
        if self.rotation:
            self.break_rotation()
        self.state.update(read_vols=self.read_vols, job_vols=self.job_vols)
        self.loaders = self.start_loaders() if self.reads else []

    def set_up_job(self, vols: "list[dict]", submit) -> float:
        """One job before the window; the seconds it took, compiling
        left out.  A job that did no work on the chip fails the run."""
        m0 = self.cluster.wire.ask("mark")
        t0 = time.perf_counter()
        j = self.cluster.wait_job(submit(vols), JOB_TIMEOUT_S)
        took = time.perf_counter() - t0
        m1 = self.cluster.wire.ask("mark")
        compiling = m1["compile"]["seconds"] - m0["compile"]["seconds"]
        work = device_work(m0, m1)
        on = ",".join(str(v["vid"]) for v in vols)
        say(f"set-up job on volume {on}: {j['status']} in {took:.2f}s "
            f"({compiling:.2f}s of it compiling): {j['message']}; values "
            f"read back from the chip {work_said(work)}")
        if j["status"] != "done":
            raise BenchFailure(f"set-up job failed: {j['message']}")
        check_device_work(f"the set-up's {j.get('jobType', 'job')} job on "
                          f"volume {on}", work, not self.args.rehearse)
        return took - compiling

    def break_rotation(self) -> None:
        """The rotation's volumes encoded, one a job, their lost shards
        kept aside, then each without them: the window opens once the
        master lists none."""
        t0 = time.perf_counter()
        for vol in self.job_vols:
            j = self.cluster.wait_job(self.cluster.submit_encode([vol]),
                                      JOB_TIMEOUT_S)
            if j["status"] != "done":
                raise BenchFailure(f"encoding volume {vol['vid']} for the "
                                   f"rotation failed: {j['message']}")
        self.rot.keep(self.job_vols)
        t1 = time.perf_counter()
        self.rot.lose(self.job_vols)
        say(f"rotation: {len(self.job_vols)} volumes encoded and shards "
            f"{self.rot.sids} kept aside in {t1 - t0:.2f}s, lost from each "
            f"and gone at the master {time.perf_counter() - t1:.2f}s later")

    def load_job_volumes(self, took: float, vol_bytes: int,
                         first_index: int) -> "list[dict]":
        """As many volumes as the window can start jobs on.  Where the
        jobs fill the window that is reckoned from the set-up's own job
        of that shape: none loaded idle, more of them when a later PR
        makes a job shorter, and never more than the memory budget
        holds: past that the window closes at the budget's last job
        (`close_of`).  A burst is its stated count, whatever a job
        takes, and one the budget cannot hold is not cut: set-up
        fails; so is a rotation of repairs."""
        root, g = self.root, self.group
        mem = cl.memory_now()
        total = self.hooks.memory_total or mem["total"]
        budget, resident = memory_budget(total), cl.tree_bytes(root)
        if self.burst:
            wanted = self.burst * g
            why = f"a burst of {self.burst}, the configuration's " \
                f"{self.jobs_t['count_from']}"
        elif self.rotation:
            wanted = self.rotation
            why = f"a rotation of {wanted} repairs, lost_shards " \
                f"{self.cfg['lost_shards']}"
        else:
            margin = self.jobs_t["job_seconds_margin"]
            wanted = volumes_for(self.seconds, took, margin) * g
            why = f"{took:.2f}s a job at margin {margin}"
        if g > 1:
            why += f", {g} volumes a job, the configuration's " \
                f"{self.jobs_t['group_from']}"
        n_budget = volumes_within(self.cfg, vol_bytes, budget - resident, g)
        n = min(wanted, n_budget)
        need = set_bytes(self.cfg, vol_bytes, max(n, g), g)
        free = cl.free_bytes(root)
        self.sizing = {"wanted": wanted, "n_budget": n_budget, "loaded": n,
                       "budget": budget, "total": total}
        say(f"window's volumes: wanted {wanted} ({self.seconds:.0f}s, {why}), "
            f"budget {n_budget} ({budget / 1e9:.2f} GB, "
            f"{MEMORY_SHARE:.3f} of the least of MemTotal "
            f"{total / 1e9:.2f} GB and the machine's limit "
            f"{MACHINE_LIMIT_BYTES / 1e9:.2f} GB; {resident / 1e9:.2f} GB "
            f"resident, {vol_bytes} bytes a volume), loading {n}: at most "
            f"{need / 1e9:.2f} GB more; MemAvailable "
            f"{mem['available'] / 1e9:.2f} GB, Shmem "
            f"{mem['shmem'] / 1e9:.2f} GB, data root free "
            f"{free / 1e9:.2f} GB")
        if (self.burst or self.rotation) and wanted > n_budget:
            what = "burst" if self.burst else "rotation"
            raise BenchFailure(
                f"the {what} is {wanted} job volumes ({why}) and the memory "
                f"budget holds {n_budget}: a {what} is not cut")
        check_room("the memory budget", max(n, g), resident + need, budget)
        check_room("available memory", n, need, mem["available"])
        check_room("the data root", n, need, free)
        t0 = time.perf_counter()
        vols = self.cluster.load_volumes(
            self.args.seed, [self.job_shape] * n, first_index=first_index,
            group=g)
        say(f"loaded {n} volumes for the window's jobs in "
            f"{time.perf_counter() - t0:.2f}s")
        return vols

    def start_loaders(self) -> list:
        reads, root = self.reads, self.root
        fids_path = os.path.join(root, "read_fids.json")
        with open(fids_path, "w") as f:
            json.dump([[fid, d] for v in self.read_vols
                       for fid, d in v["fids"].items()], f)
        loaders = []
        for p in range(reads["processes"]):
            out = os.path.join(root, f"load{p}.npz")
            proc = self.procs.spawn(f"load{p}", [
                "-m", "benchmark.load", "--master", self.cluster.master,
                "--fids", fids_path, "--threads",
                str(reads["threads_per_process"]), "--seed",
                str(self.args.seed), "--proc", str(p), "--timeout",
                str(reads["timeout_s"]), "--out", out], transient=True,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            loaders.append((proc, out))
        for proc, _ in loaders:
            if cl.read_line(proc, 120, "a load child") != b"ready":
                raise BenchFailure("a load child did not come up")
        return loaders

    # -- the window -----------------------------------------------------------

    def window(self) -> None:
        args, cluster, reads = self.args, self.cluster, self.reads
        if self.hooks.before_window:
            self.hooks.before_window(cluster, self.state)
        vreq0 = cluster.volume_counters() if reads else None
        mark0 = cluster.wire.ask(
            "trace_start", dir=os.path.join(self.root, "trace")) \
            if args.trace else cluster.wire.ask("mark")

        self.t_open = t_open = time.time() + (0.25 if reads else 0.0)
        self.t_stop = t_open + self.seconds
        self.setup_s = t_open - T_PROCESS_START
        for proc, _ in self.loaders:
            proc.stdin.write(f"{t_open!r} {self.t_stop!r}\n".encode())
            proc.stdin.flush()
        chain: dict = {"jobs": [], "dry": 0.0}

        def drive_chain():
            try:
                time.sleep(max(0.0, t_open - time.time()))
                vols, g = self.job_vols, self.group

                def wait(jid):
                    return cluster.wait_job(jid, JOB_TIMEOUT_S)
                if self.rotation:
                    chain["jobs"], chain["dry"] = run_chain(
                        itertools.cycle([[v] for v in vols]), self.seconds,
                        t_open, self.rot.submit, wait, ready=self.rot.ready,
                        on_done=self.rot.lose_again)
                else:
                    chain["jobs"], chain["dry"] = run_chain(
                        [vols[i:i + g] for i in range(0, len(vols), g)],
                        self.seconds, t_open, cluster.submit_encode, wait)
                if reads and time.time() < self.t_stop:
                    # the chain ended beside the readers: the servers'
                    # counters as they stand at its last finish
                    chain["vreq"] = cluster.volume_counters()
            except Exception as e:  # noqa: BLE001 — carried to the parent
                chain["error"] = e
        th = threading.Thread(target=drive_chain, daemon=True)
        watched: dict = {}
        unwatch = threading.Event()
        self.memory_seen: dict = {}
        watcher = threading.Thread(target=lambda: watched.update(
            alive=cluster.watch_alive(unwatch, memory=self.memory_seen)),
            daemon=True)
        if self.jobs_t:
            th.start()
            watcher.start()
        for proc, _ in self.loaders:
            line = cl.read_line(proc, self.seconds + 5 * reads["timeout_s"],
                                "a load child")
            if line != b"done":
                raise BenchFailure(f"a load child ended with {line!r}")
        if self.jobs_t:
            th.join(timeout=self.seconds + JOB_TIMEOUT_S)
            if th.is_alive() or "error" in chain:
                raise BenchFailure("the job chain did not end: "
                                   f"{chain.get('error', 'still running')}")
        self.jobs = jobs = chain["jobs"]
        sz = self.sizing or {"wanted": 0, "n_budget": 0}
        self.started = sum(len(j["vols"]) for j in jobs)     # volumes
        # a rotation never runs out: every second it waited is a fault
        self.closed_by, self.chain_dry_s = ("seconds", chain["dry"]) \
            if self.rotation else close_of(
                sz["wanted"], sz["n_budget"], self.started, chain["dry"],
                burst=bool(self.burst))
        self.relost = self.rot.drain() if self.rotation else []
        # closed by the budget, the window is the seconds the chain ran:
        # what is read beside the jobs is read over those
        self.t_end = max(j["finish"] for j in jobs) \
            if self.closed_by == "budget" else self.t_stop
        self.t_close = max([self.t_end if reads else time.time()]
                           + [j["finish"] for j in jobs])
        unwatch.set()
        if self.jobs_t:
            watcher.join(timeout=10)
        self.alive_seen = watched.get("alive", [])

        self.mark1 = mark1 = cluster.wire.ask("trace_stop", timeout=240) \
            if args.trace else cluster.wire.ask("mark")
        self.work = device_work(mark0, mark1)
        vreq1 = cluster.volume_counters() if reads else None
        self.report = report = cluster.wire.ask("report")
        self.check_one_owner()
        if self.closed_by == "budget" and "vreq" in chain:
            vreq1 = chain["vreq"]
        phases = job_phases(report["log"])
        for j in jobs:
            j.update(phases.get(j["id"], {"phases": {}}))
            j["spans"] = [s for s in report["spans"]
                          if j.get("start") and j.get("end") and
                          j["start"] <= s["start"] <= j["end"]]
        self.read_sum = None
        if reads:
            parts = []
            for _proc, out in self.loaders:
                with np.load(out) as z:
                    parts.append({k: z[k] for k in z.files})
            self.read_sum = ld.summarize(parts, t_open, self.t_end)

        def delta(key):
            return {k: mark1[key][k] - mark0[key][k] for k in mark1[key]
                    if isinstance(mark1[key][k], (int, float))}
        self.ctx = {
            "cfg": self.cfg, "traffic": self.traffic, "device": self.dev,
            "chips": self.cell["chips"],
            "window": {"open": t_open, "close": self.t_close,
                       "end": self.t_end},
            "jobs": jobs, "staging": delta("staging"),
            "compile": delta("compile"), "reads": self.read_sum,
            "volume_counters": None if vreq0 is None else
            {k: vreq1[k] - vreq0[k] for k in vreq1}, "trace": None}
        self.say_window()
        check_device_work("the window", self.work, not args.rehearse)
        if self.rotation:
            self.mend()

    def mend(self) -> None:
        """The volumes the window left without their lost shards,
        repaired after it, untimed: the cluster ends whole and every
        volume of the rotation is compared."""
        self.closing = []
        for vol in self.job_vols:
            if self.rot.broken(vol):
                j = self.cluster.wait_job(self.rot.submit([vol]),
                                          JOB_TIMEOUT_S)
                self.closing.append({"ok": j["status"] == "done",
                                     "message": j["message"]})
                say(f"closing repair of volume {vol['vid']}: "
                    f"{j['status']}: {j['message']}")

    def check_one_owner(self) -> None:
        libtpu = self.procs.pids_mapping("libtpu")
        jaxlib = self.procs.pids_mapping("jaxlib")
        say(f"libtpu mapped by {libtpu}; jaxlib mapped by {jaxlib}")
        if any(not k.startswith("worker:") for k in {**libtpu, **jaxlib}):
            raise BenchFailure("a process besides the worker child "
                               f"imported jax: {jaxlib}")

    def say_window(self) -> None:
        jobs, rs, m = self.jobs, self.read_sum, self.mark1
        if self.sizing:
            sz, ran = self.sizing, self.t_close - self.t_open
            say(f"window's volumes: wanted {sz['wanted']}, budget "
                f"{sz['n_budget']}, loaded {sz['loaded']}, started "
                f"{self.started}; closed by " + (
                    f"budget after {ran:.3f}s" if self.closed_by == "budget"
                    else f"seconds ({self.seconds:.0f}s)"
                    + (f", {self.chain_dry_s:.3f}s of them with no job: "
                       "A FAULT" if self.chain_dry_s else "")))
            if self.closed_by == "budget" and ran < SHORT_WINDOW_S:
                say(f"WARNING: THE MEMORY BUDGET CLOSED THE WINDOW AFTER "
                    f"{ran:.1f}s, UNDER {SHORT_WINDOW_S:.0f}s: THE CELL NEEDS "
                    "A WAY TO RECYCLE ITS VOLUMES OR A LARGER HOST")
            seen = self.memory_seen
            if seen:
                say(f"memory in the window ({seen['reads']} readings): "
                    f"least MemAvailable {seen['available'] / 1e9:.2f} GB, "
                    f"peak Shmem {seen['shmem'] / 1e9:.2f} GB; budget "
                    f"{sz['budget'] / 1e9:.2f} GB, MemTotal "
                    f"{sz['total'] / 1e9:.2f} GB")
            if self.rotation:
                times = [sum(j["vids"] == [v["vid"]] for j in jobs)
                         for v in self.job_vols]
                waits = sorted(self.relost) or [0.0]
                say(f"rotation: {len(jobs)} repairs over "
                    f"{len(self.job_vols)} volumes, each repaired {times} "
                    f"times; lost again after a repair in "
                    f"{waits[0]:.3f}-{waits[-1]:.3f}s, median "
                    f"{waits[len(waits) // 2]:.3f}s ({len(self.relost)} "
                    f"losses); {self.rot.checked} repairs read back before "
                    f"their loss, {self.rot.bytes_wrong} rebuilt bytes "
                    "wrong")
            if self.burst:
                last = max([j["finish"] for j in jobs] + [self.t_open])
                say(f"burst: {len(jobs)} of {self.burst} jobs started back "
                    f"to back from the opening, the last ended at "
                    f"+{last - self.t_open:.3f}s of the window's "
                    f"{self.seconds:.0f}s")
        say(f"window: {self.t_close - self.t_open:.3f}s, {len(jobs)} jobs "
            f"({sum(not j['ok'] for j in jobs)} failed)"
            + (f", {rs['requests']} requests ({rs['failed']} failed, "
               f"{rs['wrong']} wrong; {rs['requests_in_window']} of them "
               f"sent in the window's {self.t_end - self.t_open:.3f}s), "
               f"generator late mean "
               f"{rs['late_mean_ms']:.3f} ms max {rs['late_max_ms']:.3f} ms"
               if rs else ""))
        for j in jobs:
            say(f"  job {j['id']} vol {','.join(map(str, j['vids']))}: "
                f"{j['finish'] - j['submit']:.3f}s ok={j['ok']} " +
                " ".join(f"{n}={e - s:.3f}"
                         for n, (s, e) in j["phases"].items())
                + f": {j['message']}")
        # a server the master let go of is left out of a job's placement
        for t, alive in self.alive_seen:
            if len(alive) != self.cfg["volume_servers"] or \
                    t != self.alive_seen[0][0]:
                say(f"  at +{t - self.t_open:.1f}s the master held alive "
                    f"{len(alive)} of {self.cfg['volume_servers']} volume "
                    f"servers: {alive}")
        rss = cl.rss_bytes(self.cluster.ready.get("pid"))
        if rss:
            say(f"worker child resident after the window: "
                f"{rss / 1e9:.2f} GB")
        say(f"worker ledgers: staging {json.dumps(m['staging'])} "
            f"compile {json.dumps(m['compile'])} "
            f"peak_bytes {json.dumps(m['peak_bytes'])} values read back "
            f"from the chip {work_said(self.work)}")

    # -- after the window -----------------------------------------------------

    def read_trace(self, device: dict) -> "dict | None":
        """Device times into `device` and the context; the breakdown.
        On the chip a trace with no operation in the window fails the
        run."""
        ev, lo, hi = self.mark1["events"], self.t_open, self.t_close
        busy = trace_reduce.busy_by_device(ev, lo, hi)
        busy_s = trace_reduce.busy_seconds(busy)
        seen = f"{sum(len(v) for v in ev['devices'].values())} device " \
            f"events on {sorted(ev['devices'])}, {len(ev['sync'])} sync marks"
        say(f"trace: {self.mark1['trace_bytes']} bytes, {seen}")
        if self.args.rehearse:
            return None         # no device, no device time
        if not busy_s:
            # a traced line's device numbers need an operation in the
            # window: none is no line, never one without them
            raise BenchFailure(f"the trace holds no device operation in the "
                               f"window ({seen}): busy_s {busy_s}")
        self.ctx["trace"] = {"busy_s": busy_s, "busy": busy,
                             "window_s": hi - lo}
        device.update(busy_s=busy_s, window_s=hi - lo)
        spans = [(n, s, e) for j in self.jobs
                 for n, (s, e) in j["phases"].items()]
        return {"device_ops": trace_reduce.top_ops(ev, lo, hi),
                "idle_gaps": trace_reduce.gaps_by_phase(busy, spans, lo, hi)}

    def compare(self) -> "dict[str, list]":
        """{number: [value, limit]}: what the timed path left behind
        against the configuration's guarantees and the reference."""
        cluster, cfg, jobs = self.cluster, self.cfg, self.jobs
        self.state["jobs"] = jobs
        if self.hooks.before_verify:
            self.hooks.before_verify(cluster, self.state)
        t0 = time.perf_counter()
        # a repair window's volumes each once, the closing repairs done
        done = self.job_vols if self.rotation else \
            [v for j in jobs if j["ok"] for v in j["vols"]]
        compared = {}
        if self.jobs_t:
            k, total = cfg["data_shards"], \
                cfg["data_shards"] + cfg["parity_shards"]
            sets = []
            for vol in done:
                found = cluster.shard_paths(vol)
                if all(found.get(s) for s in range(total)):
                    sets.append([found[s][0] for s in range(total)])
            n_cmp, n_bad = verify_needles(
                cluster, done, self.traffic["verify"]["needles_per_volume"],
                self.args.seed)
            wait = 2.0 if self.args.rehearse else 20.0
            compared = {
                "jobs_failed": [sum(not j["ok"] for j in jobs), 0],
                # seconds of the window with no job because set-up had
                # loaded too few volumes: the cell was not what it says
                "chain_dry_s": [self.chain_dry_s, 0],
                "shard_placement_faults": [settled(
                    "shard_placement_faults", lambda: sum(
                        cluster.placement_faults(v) for v in done),
                    wait), 0],
                "sources_left": [settled("sources_left", lambda: sum(
                    cluster.source_left(v) for v in done), wait), 0],
                # a volume with a shard file missing cannot be compared
                "parity_mismatch_bytes": [
                    reference.parity_mismatch(sets, k)
                    if len(sets) == len(done) else -1, 0],
                "needles_wrong": [n_bad, 0]}
            if self.rotation:
                # a repair, of the window or closing it, that did not
                # rebuild just the shards lost: other ids, or none
                lost = sorted(cfg["lost_shards"])
                compared["rebuilt_wrong"] = [sum(
                    rebuilt_ids(j["message"]) != lost
                    for j in jobs + self.closing), 0]
                # what each repair rebuilt against the shards as encoded:
                # the window's read back before each loss, then the last
                # repair of every volume
                compared["rebuilt_bytes_wrong"] = [
                    self.rot.bytes_wrong + sum(
                        self.rot.rebuilt_wrong_bytes(v) for v in done), 0]
            say(f"compared {len(sets)} shard sets and {n_cmp} needles in "
                f"{time.perf_counter() - t0:.2f}s")
            for vid, seen in cluster.placement_seen.items():
                if seen["faults"]:
                    sys.stderr.write(f"placement of volume {vid}: "
                                     f"{json.dumps(seen)}\n")
        if self.read_sum:
            compared["bodies_wrong"] = [self.read_sum["wrong"], 0]
            compared["requests_failed"] = [self.read_sum["failed"], 0]
        return compared

    def result(self) -> dict:
        args, jobs, rs = self.args, self.jobs, self.read_sum
        dev = self.dev
        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"], "memory_peak_bytes":
                  max([0] + list(self.mark1["peak_bytes"].values()))}
        breakdown = self.read_trace(device) if args.trace else None
        compared = self.compare()
        correct = all(v == lim for v, lim in compared.values()) and \
            (len(jobs) > 0 or not self.jobs_t) and \
            (rs is None or rs["requests"] > 0)
        values = {"setup_s": self.setup_s}
        if self.jobs_t and self.jobs_t["role"] == "foreground":
            values["ec_GBps"] = job_rate_GBps(jobs)
        if rs:
            values.update({k: rs[k] for k in ("read_rps", "read_p99_ms")})
        metrics = {}
        for m in metrics_of(self.spec, "per_layer" if args.trace
                            else "end_to_end", args.workload):
            if args.trace and args.rehearse and \
                    m["source"] == "device_trace":
                continue        # no device, no device metric
            v = metric_reader(self.bench_dir, m["name"])(self.ctx) \
                if args.trace else values.get(m["name"])
            if v is not None:
                # a CPU number never stands under a device metric's name
                name = ("rehearsal." if args.rehearse else "") + m["name"]
                metrics[name] = {"value": v, "unit": m["unit"]}
        line = {"correct": bool(correct),
                "attempted": len(jobs) + (rs["requests"] if rs else 0),
                "failed": sum(not j["ok"] for j in jobs) + (
                    rs["failed"] + rs["wrong"] if rs else 0),
                "metrics": metrics, "device": device}
        if breakdown:
            line["breakdown"] = breakdown
        if args.rehearse:
            line["rehearsal"] = "JAX on the CPU at toy sizes: no number " \
                "here is a device metric"
        line["compared"] = {k: {"value": v, "limit": lim}
                            for k, (v, lim) in compared.items()}
        return line


def run_cell(args, hooks: "Hooks | None" = None) -> dict:
    """The result line of one run; raises where there is none."""
    held = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse and held and "tpu" not in held.split(","):
        raise BenchFailure(f"no chip: JAX_PLATFORMS={held} holds JAX off "
                           "the TPU (--rehearse walks the path on the CPU)")
    run = Run(args, hooks or Hooks())
    # a guess for choosing the root; what the window needs is reckoned
    # and checked once the set-up's job has been timed
    vol = run.job_shape[0] * run.job_shape[1]
    n = 0 if not run.jobs_t else run.rotation or min(
        volumes_for(run.seconds, 3.0, 1.0) * run.group, volumes_within(
            run.cfg, vol, memory_budget(
                run.hooks.memory_total or cl.memory_now()["total"]),
            run.group))
    parent, kind = cl.choose_data_root(
        2 * sum(count * size for count, size in run.first_shapes())
        + set_bytes(run.cfg, vol, n, run.group))
    root = cl.make_data_root(parent)
    say(f"data root: {root} on {kind}, "
        f"{cl.free_bytes(root) / 2**30:.1f} GiB free before")
    if kind not in cl.MEMORY_FS:
        say(f"WARNING: THE DATA ROOT IS ON {kind.upper()}, NOT MEMORY-"
            "BACKED: job and read times will measure this disk")
    try:
        run.bring_up(root)
        run.window()
        line = run.result()
        run.procs.log_tracebacks()
        return line
    except BaseException:
        if hasattr(run, "procs"):
            run.procs.stop_all()
            run.procs.log_tails()
        raise
    finally:
        if hasattr(run, "procs"):
            run.procs.stop_all()
        free_after = cl.free_bytes(parent)
        shutil.rmtree(root, ignore_errors=True)
        say(f"data root removed; {free_after / 2**30:.1f} GiB free after "
            "the run, before removal")


def main(argv=None, hooks: "Hooks | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on JAX-on-CPU; no device metric")
    ap.add_argument("--held", action="store_true",
                    help="look for the cell in benchmark/held_cells.json "
                    "too: cells taken out of BENCHMARK.json for now")
    args = ap.parse_args(argv)
    if importlib.util.find_spec("seaweedfs_tpu") is None:
        print("benchmark.run: the system under test (seaweedfs_tpu) is "
              "not in this directory", file=sys.stderr)
        return 2

    def out_of_time(*_):
        raise BenchFailure("the run's time limit was reached")
    in_main = threading.current_thread() is threading.main_thread()
    if in_main:
        signal.signal(signal.SIGALRM, out_of_time)
        signal.alarm(TIME_LIMIT_S)
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        line = run_cell(args, hooks)
    except BenchFailure as e:
        print(f"benchmark.run: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception:  # noqa: BLE001 — the outer edge: say it, exit non-zero
        traceback.print_exc()
        return 1
    finally:
        if in_main:
            signal.alarm(0)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
