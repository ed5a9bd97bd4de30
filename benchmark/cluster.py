"""The system under test as processes: master, volume servers, admin,
the worker child, all under one data root that the harness chooses and
removes.  `Procs` is a copy of `chip_smoke.py`'s class of that name.
Every wait has a timeout; every child is its own process group and is
killed by it.
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no line."""


# -- the data root --------------------------------------------------------

def fs_type(path: str) -> str:
    """Filesystem type of the mount that holds `path` (/proc/mounts)."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for ln in f:
                parts = ln.split()
                if len(parts) < 3:
                    continue
                mnt = parts[1].replace("\\040", " ")
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) >= len(best):
                    best, kind = mnt, parts[2]
    except OSError:
        pass
    return kind


def free_bytes(path: str) -> int:
    st = os.statvfs(path)
    return st.f_bavail * st.f_frsize


def tree_bytes(path: str) -> int:
    """Bytes the files under `path` hold, as their blocks count them."""
    return sum(os.lstat(os.path.join(d, name)).st_blocks * 512
               for d, _dirs, names in os.walk(path) for name in names)


MEMORY_FS = ("tmpfs", "ramfs")


def choose_data_root(need_bytes: int) -> "tuple[str, str]":
    """(parent directory, filesystem type): $TMPDIR where that is
    memory-backed with room, else /dev/shm where that is, else $TMPDIR
    whatever it is on (said loudly by the caller)."""
    tmp = tempfile.gettempdir()
    for cand in (tmp, "/dev/shm"):
        if os.path.isdir(cand) and os.access(cand, os.W_OK) and \
                fs_type(cand) in MEMORY_FS and \
                free_bytes(cand) >= need_bytes:
            return cand, fs_type(cand)
    return tmp, fs_type(tmp)


def memory_now() -> "dict[str, int]":
    """{"total", "available", "shmem", "mem_total"} in bytes, from
    /proc/meminfo; where a cgroup holds this process to less memory
    than the machine has ("mem_total"), "total" is that limit and
    "available" no more than what is left under it."""
    kb = {}
    with open("/proc/meminfo") as f:
        for ln in f:
            name, _, rest = ln.partition(":")
            if name in ("MemTotal", "MemAvailable", "Shmem"):
                kb[name] = int(rest.split()[0]) * 1024
    got = {"total": kb["MemTotal"], "available": kb["MemAvailable"],
           "shmem": kb["Shmem"], "mem_total": kb["MemTotal"]}
    for limit, used in (("memory.max", "memory.current"),
                        ("memory/memory.limit_in_bytes",
                         "memory/memory.usage_in_bytes")):
        try:
            with open("/sys/fs/cgroup/" + limit) as f:
                cap = int(f.read())        # "max" where there is none
            with open("/sys/fs/cgroup/" + used) as f:
                left = cap - int(f.read())
        except (OSError, ValueError):
            continue
        if cap < got["total"]:
            got.update(total=cap, available=min(got["available"], left))
    return got


def rss_bytes(pid: "int | None") -> "int | None":
    """Resident size of process `pid`, from /proc."""
    try:
        with open(f"/proc/{pid}/status") as f:
            return next(int(ln.split()[1]) * 1024 for ln in f
                        if ln.startswith("VmRSS:"))
    except (OSError, StopIteration):
        return None


def machine_now(pid: "int | None" = None) -> dict:
    """What the machine says of itself, for the run's record: its
    memory (`memory_now`), the CPUs this process may use, and the
    resident size of process `pid` (the chips' owner pins host memory
    while it initialises)."""
    return dict(memory_now(), cpus=len(os.sched_getaffinity(0)),
                rss=rss_bytes(pid))


def make_data_root(parent: str) -> str:
    """A directory of this run's own; the run removes it when it ends
    and looks at no other run's."""
    return tempfile.mkdtemp(prefix="swfs_bench_", dir=parent)


# -- processes ------------------------------------------------------------

class Procs:
    """Every process this run starts, so every one is stopped."""

    def __init__(self, work: str, rehearse: bool):
        self.work = work
        self.roles: "dict[str, subprocess.Popen]" = {}
        self.transient: "set[str]" = set()   # may end before the run does
        self.logs: "dict[str, str]" = {}
        # no role is told which platform to use: the one that owns the
        # chip finds it, the others never look.  BENCH_RUN is the
        # driver's own and reaches no child.
        env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
        self.child_env = dict(env, PYTHONPATH=REPO, PYTHONUNBUFFERED="1")
        self.role_env = {k: v for k, v in self.child_env.items()
                         if k != "JAX_PLATFORMS"}
        if rehearse:
            self.child_env = self.role_env = dict(
                self.child_env, JAX_PLATFORMS="cpu")

    def spawn(self, name: str, argv: "list[str]", env=None,
              transient: bool = False, **popen) -> subprocess.Popen:
        if transient:
            self.transient.add(name)
        self.logs[name] = os.path.join(self.work, f"{name}.log")
        with open(self.logs[name], "ab") as log:
            p = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO,
                env=env or self.role_env,
                stderr=log, start_new_session=True,
                **({"stdout": log} | popen))
        self.roles[name] = p
        return p

    def role(self, name: str, argv: "list[str]") -> None:
        self.spawn(name, ["-m", "seaweedfs_tpu", *argv])

    @staticmethod
    def _kill(p: subprocess.Popen) -> None:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGTERM)
                p.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    pass
                p.wait(timeout=10)
        else:
            try:                      # stragglers of a dead leader
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass

    def stop_all(self) -> None:
        worker = self.roles.get("worker")
        if worker is not None and worker.poll() is None and worker.stdin:
            # asked to leave, the chip's owner is gone in a second; a
            # SIGTERM makes libtpu write a stack trace for five
            try:
                worker.stdin.write(b'{"cmd": "exit"}\n')
                worker.stdin.flush()
                worker.wait(timeout=8)
            except (OSError, ValueError, subprocess.TimeoutExpired):
                pass
        for name in reversed(list(self.roles)):
            p = self.roles.pop(name)
            for pipe in (p.stdin, p.stdout):
                if pipe is not None:
                    try:
                        pipe.close()
                    except OSError:
                        pass
            self._kill(p)

    def check_alive(self) -> None:
        for name, p in self.roles.items():
            if p.poll() is not None and name not in self.transient:
                raise BenchFailure(f"role {name} exited with "
                                   f"{p.returncode}")

    def pids_mapping(self, needle: str) -> "dict[str, int]":
        """Roles (and their descendants) whose /proc/<pid>/maps names
        `needle` — who loaded libtpu, who imported jaxlib."""
        children: "dict[int, list[int]]" = {}
        for ent in os.listdir("/proc"):
            if ent.isdigit():
                try:
                    with open(f"/proc/{ent}/stat") as f:
                        ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                except (OSError, ValueError, IndexError):
                    continue
                children.setdefault(ppid, []).append(int(ent))
        hit = {}
        for name, p in self.roles.items():
            todo = [p.pid]
            while todo:
                pid = todo.pop()
                todo += children.get(pid, [])
                try:
                    with open(f"/proc/{pid}/maps") as f:
                        if needle in f.read():
                            hit[f"{name}:{pid}"] = pid
                except OSError:
                    pass
        return hit

    def log_tracebacks(self, out=sys.stderr, each: int = 3000) -> int:
        """Writes out what each role's log holds from its first
        traceback on; returns how many roles had one."""
        n = 0
        for name, path in self.logs.items():
            try:
                with open(path, "rb") as f:
                    text = f.read()
            except OSError:
                continue
            at = text.find(b"Traceback (most recent call last)")
            if at >= 0:
                n += 1
                out.write(f"--- {name}: traceback in its log ---\n"
                          + text[at:at + each].decode("utf-8", "replace")
                          + "\n")
        return n

    def log_tails(self, out=sys.stderr, each: int = 1500) -> None:
        for name, path in self.logs.items():
            try:
                with open(path, "rb") as f:
                    f.seek(max(0, os.path.getsize(path) - each))
                    tail = f.read()
            except OSError:
                continue
            out.write(f"--- {name} (tail) ---\n"
                      + tail.decode("utf-8", "replace") + "\n")


PORTS = range(20000, 32000)   # below what the kernel hands to outgoing
#                                 connections: a port got by binding to 0
#                                 can be some client's source port by the
#                                 time the role binds it
PORT_SLICE = 40
_next_port = [0]


def free_port() -> int:
    """A port no one listens on, from a slice of `PORTS` that is this
    process's own (by its pid), taken in turn: two harnesses at work at
    once (six test workers rehearse side by side) drew the same random
    port now and then, each found it free, and the second role to bind
    it died with "Address already in use" (seen in tier-1 runs)."""
    slices = len(PORTS) // PORT_SLICE
    base = PORTS.start + os.getpid() % slices * PORT_SLICE
    for _ in range(PORT_SLICE):
        port = base + _next_port[0] % PORT_SLICE
        _next_port[0] += 1
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise BenchFailure(f"no free port in {base}-{base + PORT_SLICE - 1}")


def wait_for(what: str, fn, timeout: float, every: float = 0.1):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            got = fn()
            if got:
                return got
        except (OSError, KeyError, ValueError, RuntimeError) as e:
            last = e
        time.sleep(every)
    raise BenchFailure(f"timed out after {timeout}s waiting for {what}"
                       + (f" (last error: {last})" if last else ""))


def port_open(port: int) -> bool:
    with socket.create_connection(("127.0.0.1", port), timeout=1.0):
        return True


def read_line(p: subprocess.Popen, timeout: float, what: str) -> bytes:
    """The next line a child writes on its stdout pipe, stripped; raises
    when none comes in `timeout` seconds or the child is gone."""
    import select
    deadline = time.monotonic() + timeout
    buf = b""
    fd = p.stdout.fileno()
    while not buf.endswith(b"\n"):
        left = deadline - time.monotonic()
        if left <= 0:
            raise BenchFailure(f"{what} said nothing in {timeout}s")
        ready, _, _ = select.select([fd], [], [], min(left, 1.0))
        if not ready:
            continue
        chunk = os.read(fd, 1 << 20)
        if not chunk:
            raise BenchFailure(f"{what} closed its pipe (exit {p.poll()})")
        buf += chunk
    return buf.splitlines()[-1].strip()


# -- the worker child's wire ------------------------------------------------

class WorkerWire:
    """One JSON line each way with benchmark/worker_proc.py."""

    def __init__(self, p: subprocess.Popen):
        self.p = p

    def recv(self, timeout: float) -> dict:
        return json.loads(read_line(self.p, timeout, "the worker child"))

    def ask(self, cmd: str, timeout: float = 60.0, **kw) -> dict:
        self.p.stdin.write((json.dumps(dict(kw, cmd=cmd)) + "\n").encode())
        self.p.stdin.flush()
        return self.recv(timeout)


# -- the cluster ----------------------------------------------------------

def digest(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def needle_bytes(seed: int, volume: int, i: int, size: int) -> bytes:
    """Needle i of seeded volume `volume`: the data every comparison
    goes back to."""
    import numpy as np
    return np.random.default_rng([seed, volume, i]).bytes(size)


CHUNK_NEEDLES = 8192   # one loader task uploads at most this many


def grow_volume(master: str, index: int) -> "tuple[int, str, str]":
    """(vid, collection, url) of a new volume in the collection that
    seeded volume `index` opens: its own, or its group's where it is
    that group's first."""
    from seaweedfs_tpu.server.httpd import http_json
    collection = f"bench{index}"
    vids = http_json("POST", f"{master}/vol/grow", {
        "collection": collection, "count": 1,
        "replication": "000"}).get("volumeIds") or []
    if len(vids) != 1:
        raise BenchFailure(f"grow in {collection!r} gave {vids}")
    url = wait_for(f"a location of volume {vids[0]}", lambda: http_json(
        "GET", f"{master}/dir/lookup?volumeId={vids[0]}"
    )["locations"][0]["url"], 30)
    return vids[0], collection, url


def load_chunk(task) -> "list[tuple[str, str]]":
    """Needles lo..hi of seeded volume `index`, uploaded under file ids
    made here from the seed (key i + 1, seeded cookie), so that the
    same seed gives the same objects under the same ids and no assign
    can send a needle elsewhere.  [(fid, digest), ...] in needle order."""
    import numpy as np
    url, vid, seed, index, n, size, lo, hi = task
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from seaweedfs_tpu import operation
    cookies = np.random.default_rng([seed, index]).integers(
        1, 2**32, size=n, dtype=np.uint64)

    def put(i: int) -> "tuple[str, str]":
        data = needle_bytes(seed, index, i, size)
        fid = f"{vid},{i + 1:x}{int(cookies[i]):08x}"
        operation.upload(url, fid, data)
        return fid, digest(data)
    with ThreadPoolExecutor(4) as pool:
        return list(pool.map(put, range(lo, hi)))


class Cluster:
    """master + N volume servers + admin + the worker child."""

    def __init__(self, procs: Procs, root: str, cfg: dict):
        self.procs = procs
        self.root = root
        self.cfg = cfg
        self.master = self.admin = ""
        self.vol_dirs: "list[str]" = []
        self.vol_urls: "list[str]" = []
        self.wire: "WorkerWire | None" = None
        self.ready: dict = {}
        self.placement_seen: "dict[int, dict]" = {}

    def start(self) -> None:
        from seaweedfs_tpu import native
        t0 = time.perf_counter()
        built = [bool(native.load()), bool(native.load_read_plane()),
                 bool(native.load_write_plane())]
        print(f"native build: {built} in "
              f"{time.perf_counter() - t0:.2f}s", flush=True)
        mport, aport = free_port(), free_port()
        self.master, self.admin = f"127.0.0.1:{mport}", f"127.0.0.1:{aport}"
        os.makedirs(os.path.join(self.root, "master"))
        self.procs.role("master", [
            "master", "-port", str(mport), "-mdir",
            os.path.join(self.root, "master"), "-volumeSizeLimitMB",
            str(self.cfg["volume_size_limit_mb"])])
        wait_for("master port", lambda: port_open(mport), 60)
        ports = []
        for i in range(self.cfg["volume_servers"]):
            d = os.path.join(self.root, f"vol{i}")
            os.makedirs(d)
            self.vol_dirs.append(d)
            ports.append(free_port())
            self.vol_urls.append(f"127.0.0.1:{ports[i]}")
            self.procs.role(f"volume{i}", [
                "volume", "-port", str(ports[i]), "-dir", d,
                "-mserver", self.master])
        self.procs.role("admin", ["admin", "-port", str(aport), "-master",
                                  self.master, "-detectionInterval",
                                  "3600"])
        for port in ports + [aport]:
            wait_for(f"port {port}", lambda p=port: port_open(p), 60)
        os.makedirs(os.path.join(self.root, "worker"))
        p = self.procs.spawn("worker", [
            "-m", "benchmark.worker_proc", "--admin", self.admin,
            "--master", self.master, "--dir",
            os.path.join(self.root, "worker"), "--backend",
            self.cfg["backend"]], env=self.procs.child_env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        self.wire = WorkerWire(p)
        from seaweedfs_tpu.server.httpd import http_json
        wait_for("the volume servers at the master", lambda: len(http_json(
            "GET", f"{self.master}/cluster/status")["dataNodes"])
            == self.cfg["volume_servers"], 60)

    def wait_worker(self, timeout: float = 300.0) -> dict:
        self.ready = self.wire.recv(timeout)
        if self.ready.get("event") != "ready":
            raise BenchFailure(f"worker child said {self.ready}")
        return self.ready

    # -- data ---------------------------------------------------------------

    def load_volumes(self, seed: int, shapes: "list[tuple[int, int]]",
                     first_index: int = 0, group: int = 1,
                     grouped_from: int = 0) -> "list[dict]":
        """One sealed volume's worth of seeded needles for each (count,
        bytes) of `shapes`, each volume in a collection of its own, or,
        from shape `grouped_from` on, each `group` of them in one (a
        job of several volumes states one collection):
        [{"vid", "collection", "index", "fids": {fid: digest}, "order":
        [fid of needle 0, 1, ...], "bytes": size of the .dat}].  A few
        processes upload at once (one interpreter makes and sends about
        350 MB/s, or 1,600 small needles a second), a volume of many
        needles in several chunks."""
        from concurrent.futures import ProcessPoolExecutor
        from multiprocessing import get_context
        if not shapes:
            return []
        servers = self.cfg["volume_servers"]
        vols, chunks = [], []
        for j, (n, size) in enumerate(shapes):
            index = first_index + j
            # the collection is named for the first volume of the group
            vid, collection, url = grow_volume(
                self.master, index - max(0, j - grouped_from) % group)
            vols.append({"vid": vid, "collection": collection,
                         "index": index, "fids": {}, "order": []})
            parts = min(servers, -(-n // CHUNK_NEEDLES))
            step = -(-n // parts)
            chunks.append([(j, (url, vid, seed, index, n, size, lo,
                                min(n, lo + step)))
                           for lo in range(0, n, step)])
        # chunk-major, so that the loaders work on different volumes
        # (and servers) at a time
        tasks = [c[i] for i in range(servers) for c in chunks
                 if i < len(c)]
        with ProcessPoolExecutor(min(len(tasks), servers),
                                 mp_context=get_context("spawn")) as pool:
            done = list(pool.map(load_chunk, [t for _j, t in tasks]))
        by_lo = sorted(zip(((j, t[6]) for j, t in tasks), done))
        for (j, _lo), pairs in by_lo:
            vols[j]["fids"].update(pairs)
            vols[j]["order"] += [f for f, _ in pairs]
        for vol in vols:
            vol["bytes"] = self.dat_bytes(vol)
        return vols

    def dat_bytes(self, vol: dict) -> int:
        """Size of the loaded volume's .dat where its server keeps it."""
        for d in self.vol_dirs:
            p = os.path.join(d, f"{vol['collection']}_{vol['vid']}.dat")
            if os.path.exists(p):
                return os.path.getsize(p)
        raise BenchFailure(f"no .dat of volume {vol['vid']} under "
                           f"{self.vol_dirs}")

    # -- jobs ---------------------------------------------------------------

    def submit_encode(self, vols: "list[dict]",
                      timeout: float = 180.0) -> str:
        """One `ec.encode` job of one volume (`volumeId`) or of several
        of one collection (`volumeIds`: the worker's batch path)."""
        from seaweedfs_tpu.server.httpd import http_json
        which = {"volumeId": vols[0]["vid"]} if len(vols) == 1 else \
            {"volumeIds": [v["vid"] for v in vols]}
        body = {"jobType": "erasure_coding", "params": dict(
            which, collection=vols[0]["collection"],
            dataShards=self.cfg["data_shards"],
            parityShards=self.cfg["parity_shards"])}
        return wait_for("the admin to take a job", lambda: http_json(
            "POST", f"{self.admin}/maintenance/submit_job", body
        ).get("jobId"), timeout, every=0.2)

    def job_state(self, job_id: str) -> dict:
        from seaweedfs_tpu.server.httpd import http_json
        return http_json("GET",
                         f"{self.admin}/maintenance/job?id={job_id}")

    def wait_job(self, job_id: str, timeout: float,
                 every: float = 0.02) -> dict:
        def finished():
            self.procs.check_alive()
            j = self.job_state(job_id)
            return j if j["status"] in ("done", "failed") else None
        return wait_for(f"job {job_id}", finished, timeout, every=every)

    # -- what a job left behind ---------------------------------------------

    def shard_paths(self, vol: dict) -> "dict[int, list[str]]":
        from seaweedfs_tpu.storage.erasure_coding.ec_context import to_ext
        total = self.cfg["data_shards"] + self.cfg["parity_shards"]
        found: "dict[int, list[str]]" = {}
        for d in self.vol_dirs:
            for sid in range(total):
                p = os.path.join(
                    d, f"{vol['collection']}_{vol['vid']}{to_ext(sid)}")
                if os.path.exists(p):
                    found.setdefault(sid, []).append(p)
        return found

    def placement_faults(self, vol: dict) -> int:
        """Shards missing, doubled or off the configuration's spread,
        as the master and the servers' disks have them.  What was seen
        is kept in `placement_seen`, for the run to say where it is not
        as configured."""
        from seaweedfs_tpu.server.httpd import http_json
        total = self.cfg["data_shards"] + self.cfg["parity_shards"]
        r = http_json("GET", f"{self.master}/dir/ec_lookup?volumeId="
                      f"{vol['vid']}")
        at_master: "dict[int, int]" = {}
        per_server = []
        for loc in r.get("shardIdLocations", []):
            per_server.append(len(loc["shardIds"]))
            for s in loc["shardIds"]:
                at_master[s] = at_master.get(s, 0) + 1
        faults = sum(1 for s in range(total) if at_master.get(s, 0) != 1)
        found = self.shard_paths(vol)
        faults += sum(1 for s in range(total) if len(found.get(s, [])) != 1)
        if sorted(per_server, reverse=True) != sorted(
                self.cfg["shard_spread"], reverse=True):
            faults += 1
        self.placement_seen[vol["vid"]] = {
            "faults": faults, "at_master": {
                loc["url"]: loc["shardIds"]
                for loc in r.get("shardIdLocations", [])},
            "on_disk": {s: [os.path.relpath(p, self.root) for p in ps]
                        for s, ps in sorted(found.items())}}
        return faults

    def watch_alive(self, stop, every: float = 1.0,
                    memory: "dict | None" = None) -> "list[tuple]":
        """Until `stop` is set, asks the master once a second which
        volume servers it holds alive, the question a job asks before
        it places its shards; keeps [(time, [urls])] at each change.  A
        server the master drops is one that a job would leave out.  In
        the same turn it reads the machine's memory into `memory`: the
        least "available" and the most "shmem" seen."""
        from seaweedfs_tpu.server.httpd import http_json
        seen: "list[tuple]" = []
        while not stop.wait(every):
            if memory is not None:
                now = memory_now()
                memory.update(
                    available=min(now["available"],
                                  memory.get("available", now["available"])),
                    shmem=max(now["shmem"], memory.get("shmem", 0)),
                    reads=memory.get("reads", 0) + 1)
            try:
                alive = sorted(http_json(
                    "GET", f"{self.master}/cluster/status",
                    timeout=5)["dataNodes"])
            except (OSError, KeyError, ValueError) as e:
                alive = [f"no answer: {e!r}"]
            if not seen or seen[-1][1] != alive:
                seen.append((time.time(), alive))
        return seen

    def source_left(self, vol: dict) -> int:
        """1 if the plain source volume still has a location."""
        from seaweedfs_tpu.server.httpd import http_json
        r = http_json("GET", f"{self.master}/vol/list")
        from seaweedfs_tpu.topology import iter_volume_list_volumes
        return int(any(v["id"] == vol["vid"]
                       for _n, v in iter_volume_list_volumes(r)))

    def volume_counters(self) -> "dict[str, float]":
        """Summed over the volume servers' /metrics: "req_s" and
        "req_n", the sum and count of the role's request_seconds
        histogram for needle GETs; "cache_hits" and "cache_misses" of
        the hot-needle cache."""
        from seaweedfs_tpu.server.httpd import http_bytes
        got = dict.fromkeys(("req_s", "req_n", "cache_hits",
                             "cache_misses"), 0.0)
        for url in self.vol_urls:
            _st, body, _ = http_bytes("GET", f"{url}/metrics")
            for ln in body.decode("utf-8", "replace").splitlines():
                if ln.startswith("#"):
                    continue
                name, _, val = ln.rpartition(" ")
                family = name.split("{")[0]
                if "request_seconds" in family and 'method="GET"' in name:
                    if family.endswith("_sum"):
                        got["req_s"] += float(val)
                    elif family.endswith("_count"):
                        got["req_n"] += float(val)
                elif 'cache="volume_needle"' in name:
                    if family.endswith("read_cache_hits_total"):
                        got["cache_hits"] += float(val)
                    elif family.endswith("read_cache_misses_total"):
                        got["cache_misses"] += float(val)
        return got
