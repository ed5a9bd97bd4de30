"""Chip smoke: the EC main path, end to end, on the accelerator.

    python chip_smoke.py [--seed N] [--workdir DIR]

Drives the store the way an operator does — `python -m seaweedfs_tpu
<role>` processes, HTTP, the admin's /maintenance/submit_job, a `weed
shell` one-shot — at the size an operator would call real: one full
volume at this repo's default -volumeSizeLimitMB 1024, RS(10,4), 1 MB
small-block rows (BASELINE.json config 1), checked byte for byte
against ops/rs_cpu.

  probe    one child: the device JAX reports, and what probe_backend()
           would choose between the host codec and the device
  phase 1  master + 3 volume servers + admin + ONE `worker -backend
           jax`; ~1 GiB of seeded 1 MiB blobs; an admin-submitted
           erasure_coding job; read-back, parity vs rs_cpu, loss of two
           data shards, degraded read-back, `ec.rebuild` via the shell
  phase 2  one child, cluster down: device rebuild of the same loss,
           RS(6,3) encode of a 256 MiB slice, the compiled Pallas
           kernel at the bench's 10 x 64 MiB shape (parity + a 2x10
           reconstruct matrix)
  phase 3  phase 2 again as a new process: zero backend compilations

The chip belongs to one process at a time, so this parent never
imports jax; each step that needs the chip is one child, and the next
starts only after the previous has exited.  Any failed check, a step
that did not run on a TPU, more than one libtpu-holding PID among the
roles, or no chip at all: non-zero exit and no result line.  The last
line of a passing run is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}.

--rehearse-cpu walks the same steps at a toy size on JAX-on-CPU
(Pallas interpreted) to debug the script where there is no chip; it
never prints the result line and always exits non-zero.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

try:   # nothing here imports jax: the parent must never hold the chip
    from seaweedfs_tpu.storage.erasure_coding.ec_context import to_ext
except ImportError:
    sys.exit("chip_smoke.py runs from a checkout of the repository "
             "(seaweedfs_tpu is not importable here)")

REPO = os.path.dirname(os.path.abspath(__file__))
COLLECTION = "chipsmoke"
BLOB = 1 << 20
LOST = (2, 7)                  # two data shards (BASELINE config 4)
TIME_LIMIT_S = 1150            # the contract allows 1200


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# -- sizes ------------------------------------------------------------------

def sizes(rehearse: bool) -> dict:
    if rehearse:
        return {"volume_limit_mb": 32, "blobs": 24, "slice_mb": 8,
                "pallas_shard_words": 2 * 8192}
    return {"volume_limit_mb": 1024, "blobs": 1016, "slice_mb": 256,
            "pallas_shard_words": (64 << 20) // 4}


def blob_bytes(seed: int, i: int) -> bytes:
    import numpy as np
    return np.random.default_rng([seed, i]).bytes(BLOB)


def digest(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def file_digest(path: str) -> str:
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb") as f:
        while chunk := f.read(8 << 20):
            h.update(chunk)
    return h.hexdigest()


def parity_matches(rs, data_paths, parity_paths, step: int = 4 << 20
                   ) -> bool:
    """parity files == rs.parity(data files), streamed in `step`-byte
    columns (rs is ops.rs_cpu.ReedSolomonCPU: the plain reference)."""
    import numpy as np
    size = os.path.getsize(data_paths[0])
    files = [open(p, "rb") for p in data_paths + parity_paths]
    try:
        if any(os.fstat(f.fileno()).st_size != size for f in files):
            return False
        for pos in range(0, size, step):
            n = min(step, size - pos)
            rows = [np.frombuffer(f.read(n), dtype=np.uint8)
                    for f in files]
            want = rs.parity(np.stack(rows[:len(data_paths)]))
            if not np.array_equal(want,
                                  np.stack(rows[len(data_paths):])):
                return False
        return True
    finally:
        for f in files:
            f.close()


# -- the device child (phases 2 and 3, and the probe) -----------------------

def child_probe() -> dict:
    from seaweedfs_tpu.storage.erasure_coding import ec_context
    t0 = time.perf_counter()
    dev = ec_context.own_device()
    init_s = time.perf_counter() - t0
    return {"device": dev, "init_seconds": round(init_s, 2),
            "probe": ec_context.probe_backend(),
            "compile_cache_dir": ec_context.compile_cache_dir()}


def child_device(args) -> dict:
    """Phase 2/3 body.  Owns the chip for its lifetime."""
    import numpy as np

    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext
    dev = ec_context.own_device()
    interpret = dev["platform"] == "cpu"   # the CPU rehearsal only
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256, rs_matrix, rs_pallas, staging
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomonCPU
    sz = sizes(args.rehearse_cpu)
    out: dict = {"device": dev, "checks": {}}
    checks = out["checks"]

    # (a) device rebuild of the two lost data shards: the 2x10
    # reconstruct shape through the staged pipeline
    with open(args.digests) as f:
        want = json.load(f)
    for sid in LOST:
        if os.path.exists(args.base + to_ext(sid)):
            os.remove(args.base + to_ext(sid))
    t0 = time.perf_counter()
    rebuilt = ec_encoder.rebuild_ec_files(
        args.base, ECContext(backend="jax"))
    out["rebuild_seconds"] = round(time.perf_counter() - t0, 2)
    checks["device_rebuild_shards"] = sorted(rebuilt) == list(LOST)
    checks["device_rebuild_bytes"] = all(
        file_digest(args.base + to_ext(sid)) == want[str(sid)]
        for sid in LOST)

    # RS(6,3) encode of a slice: the same kernel, another scheme
    # (BASELINE config 5)
    base63 = os.path.join(os.path.dirname(args.base), "rs63")
    n = sz["slice_mb"] << 20
    dat = np.frombuffer(np.random.default_rng(
        [args.seed, 63]).bytes(n), dtype=np.uint8)
    with open(base63 + ".dat", "wb") as f:
        f.write(dat.data)
    t0 = time.perf_counter()
    ec_encoder.write_ec_files(base63, ECContext(6, 3, backend="jax"))
    out["rs63_encode_seconds"] = round(time.perf_counter() - t0, 2)
    rows = -(-n // (6 * BLOB))
    striped = np.zeros(rows * 6 * BLOB, dtype=np.uint8)
    striped[:n] = dat
    striped = striped.reshape(rows, 6, BLOB)
    checks["rs63_data_shards"] = all(
        np.array_equal(
            np.fromfile(base63 + to_ext(i), dtype=np.uint8),
            striped[:, i, :].reshape(-1)) for i in range(6))
    checks["rs63_parity_vs_rs_cpu"] = parity_matches(
        ReedSolomonCPU(6, 3),
        [base63 + to_ext(i) for i in range(6)],
        [base63 + to_ext(i) for i in range(6, 9)])
    for i in range(9):
        os.remove(base63 + to_ext(i))
    os.remove(base63 + ".dat")
    del dat, striped

    out["staging"] = staging.snapshot()
    peaks = {f"{d.platform}:{d.id}":
             (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()}
    out["device_peak_bytes"] = peaks
    # every visible device held window data ("everything on device 0"
    # must fail); the CPU backend reports no memory stats
    checks["every_device_held_data"] = interpret or all(
        (p or 0) > 0 for p in peaks.values())

    # (b) the Pallas kernel, compiled, at the bench's own shape
    w = sz["pallas_shard_words"]
    host = np.random.default_rng([args.seed, 7]).integers(
        0, 2**32, size=(10, w), dtype=np.uint32)
    d32 = jax.device_put(host)
    host8 = host.view(np.uint8)
    par_mat = rs_matrix.parity_matrix(10, 4)
    rec_mat, _rows = rs_matrix.reconstruction_matrix(
        10, 4, [i not in LOST for i in range(14)], list(LOST))
    for name, mat in (("pallas_parity_4x10", par_mat),
                      ("pallas_reconstruct_2x10", rec_mat)):
        got = np.asarray(rs_pallas.gf_apply_matrix_pallas_words(
            jnp.asarray(rs_pallas.expand_tables(mat)), d32,
            interpret=interpret)).view(np.uint8)
        ok = True
        for pos in range(0, host8.shape[1], 8 << 20):
            sl = slice(pos, pos + (8 << 20))
            ok = ok and np.array_equal(
                got[:, sl], gf256.gf_apply_matrix(mat, host8[:, sl]))
        checks[name] = bool(ok)
    out["pallas_interpret"] = interpret
    out["compile"] = ec_context.compile_ledger()
    out["compile_cache_dir"] = ec_context.compile_cache_dir()
    return out


def child_main(args) -> int:
    body = child_probe if args.child == "probe" else \
        (lambda: child_device(args))
    print(json.dumps(body()), flush=True)
    return 0


# -- the parent: processes ---------------------------------------------------

class Procs:
    """Every process this script starts, so every one is stopped."""

    def __init__(self, work: str, rehearse: bool):
        self.work = work
        self.roles: "dict[str, subprocess.Popen]" = {}
        self.logs: "dict[str, str]" = {}
        # device children inherit this process's environment as is;
        # no ROLE is told which platform to use: the one that owns
        # the chip finds it, the others never look
        self.child_env = dict(os.environ, PYTHONPATH=REPO,
                              PYTHONUNBUFFERED="1")
        self.role_env = {k: v for k, v in self.child_env.items()
                         if k != "JAX_PLATFORMS"}
        if rehearse:
            self.child_env = self.role_env = dict(
                self.child_env, JAX_PLATFORMS="cpu")

    def spawn(self, name: str, argv: "list[str]") -> None:
        self.logs[name] = os.path.join(self.work, f"{name}.log")
        with open(self.logs[name], "ab") as log:
            self.roles[name] = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu", *argv],
                cwd=REPO, env=self.role_env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def run_child(self, kind: str, extra: "list[str]",
                  timeout: float) -> dict:
        """One device child, run to its end; returns its JSON line."""
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", kind,
             *extra], cwd=REPO, env=self.child_env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True)
        self.roles[f"child-{kind}"] = p
        try:
            stdout, stderr = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self._kill(p)
            raise SmokeFailure(f"{kind} child: no end after {timeout}s")
        finally:
            self.roles.pop(f"child-{kind}", None)
        lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
        if p.returncode != 0 or not lines:
            raise SmokeFailure(
                f"{kind} child exited {p.returncode}:\n"
                + stderr[-3000:])
        return json.loads(lines[-1])

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

    def stop_all(self) -> None:
        for name in reversed(list(self.roles)):
            self._kill(self.roles.pop(name))

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

    def dump_logs(self, dest: str) -> None:
        os.makedirs(dest, exist_ok=True)
        for name, path in self.logs.items():
            try:
                with open(path, "rb") as f:
                    f.seek(max(0, os.path.getsize(path) - 200_000))
                    tail = f.read()
            except OSError:
                continue
            with open(os.path.join(dest, f"{name}.log"), "wb") as f:
                f.write(tail)
            sys.stderr.write(f"--- {name} (tail) ---\n"
                             + tail[-1500:].decode("utf-8", "replace")
                             + "\n")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for(what: str, fn, timeout: float, every: float = 0.25):
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
    raise SmokeFailure(f"timed out after {timeout}s waiting for {what}"
                       + (f" (last error: {last})" if last else ""))


def port_open(port: int) -> bool:
    with socket.create_connection(("127.0.0.1", port), timeout=1.0):
        return True


def shard_files(vol_dirs: "list[str]", vid: int) -> "dict[int, list[str]]":
    found: "dict[int, list[str]]" = {}
    for d in vol_dirs:
        for sid in range(14):
            p = os.path.join(d, f"{COLLECTION}_{vid}{to_ext(sid)}")
            if os.path.exists(p):
                found.setdefault(sid, []).append(p)
    return found


# -- phase 1 ------------------------------------------------------------------

def phase1(procs: Procs, work: str, seed: int, sz: dict, report: dict
           ) -> "tuple[str, str]":
    """The store on the chip machine.  Returns (phase-2 shard base,
    digests file)."""
    from seaweedfs_tpu import native, operation
    from seaweedfs_tpu.ops.rs_cpu import ReedSolomonCPU
    from seaweedfs_tpu.server.httpd import http_json

    t0 = time.perf_counter()
    built = [bool(native.load()), bool(native.load_read_plane()),
             bool(native.load_write_plane())]
    report["native_build_seconds"] = round(time.perf_counter() - t0, 2)
    print(f"native build: {built} in "
          f"{report['native_build_seconds']}s", flush=True)

    mport, aport = free_port(), free_port()
    master, admin = f"127.0.0.1:{mport}", f"127.0.0.1:{aport}"
    os.makedirs(os.path.join(work, "master"))
    procs.spawn("master", ["master", "-port", str(mport), "-mdir",
                           os.path.join(work, "master"),
                           "-volumeSizeLimitMB",
                           str(sz["volume_limit_mb"])])
    wait_for("master port", lambda: port_open(mport), 120)
    vol_dirs, vports = [], []
    for i in range(3):
        d = os.path.join(work, f"vol{i}")
        os.makedirs(d)
        vol_dirs.append(d)
        vports.append(free_port())
        procs.spawn(f"volume{i}", ["volume", "-port", str(vports[i]),
                                   "-dir", d, "-mserver", master])
    procs.spawn("admin", ["admin", "-port", str(aport), "-master",
                          master, "-detectionInterval", "3600"])
    for port in vports + [aport]:
        wait_for(f"port {port}", lambda p=port: port_open(p), 120)
    # the ONE process that owns the chip
    procs.spawn("worker", ["worker", "-admin", admin, "-master", master,
                           "-dir", os.path.join(work, "worker"),
                           "-capabilities", "erasure_coding",
                           "-backend", "jax"])
    wait_for("3 volume servers at the master", lambda: len(http_json(
        "GET", f"{master}/cluster/status")["dataNodes"]) == 3, 60)
    vids = http_json("POST", f"{master}/vol/grow", {
        "collection": COLLECTION, "count": 1,
        "replication": "000"})["volumeIds"]
    check(len(vids) == 1, f"pre-grew one volume in {COLLECTION!r}: "
          f"{vids}")
    vid = vids[0]

    # load: seeded 1 MiB blobs; the plain reference is fid -> digest
    t0 = time.perf_counter()

    def put(i: int) -> "tuple[str, str]":
        data = blob_bytes(seed, i)
        return (operation.submit(master, data, collection=COLLECTION),
                digest(data))
    with ThreadPoolExecutor(8) as pool:
        reference = dict(pool.map(put, range(sz["blobs"])))
    report["bytes_loaded"] = len(reference) * BLOB
    report["load_seconds"] = round(time.perf_counter() - t0, 2)
    check(len(reference) == sz["blobs"] and
          {int(fid.split(",")[0]) for fid in reference} == {vid},
          f"loaded {len(reference)} x 1 MiB into volume {vid} in "
          f"{report['load_seconds']}s")

    def read_all(label: str) -> float:
        t = time.perf_counter()

        def get(item) -> bool:
            fid, want = item
            return digest(operation.read(master, fid)) == want
        with ThreadPoolExecutor(8) as pool:
            bad = [fid for (fid, _), ok in zip(
                reference.items(), pool.map(get, reference.items()))
                if not ok]
        dt = round(time.perf_counter() - t, 2)
        check(not bad, f"{label}: all {len(reference)} blobs "
              f"byte-identical in {dt}s" + (f" BAD {bad[:3]}" if bad
                                            else ""))
        return dt
    report["read_plain_seconds"] = read_all("read-back before encode")

    # the job: admin-submitted, executed by the worker on the chip
    t0 = time.perf_counter()
    job_id = wait_for("a registered erasure_coding worker", lambda: http_json(
        "POST", f"{admin}/maintenance/submit_job", {
            "jobType": "erasure_coding",
            "params": {"volumeId": vid, "collection": COLLECTION}}
    ).get("jobId"), 180, every=1.0)
    report["worker_ready_seconds"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()

    def finished():
        if procs.roles["worker"].poll() is not None:
            raise SmokeFailure("the worker exited mid-job")
        j = http_json("GET", f"{admin}/maintenance/job?id={job_id}")
        return j if j["status"] in ("done", "failed") else None
    job = wait_for("the erasure_coding job", finished, 600, every=0.5)
    report["job_seconds"] = round(time.perf_counter() - t0, 2)
    report["job_message"] = job["message"]
    print(f"job {job_id}: {job['status']} in {report['job_seconds']}s: "
          f"{job['message']}", flush=True)
    check(job["status"] == "done", "the erasure_coding job is done")
    events = [e["event"] for e in job["trace"]]
    check(not any("requeued" in e for e in events),
          f"no requeue during the cold run: {events}")
    spans = http_json("GET", f"{admin}/debug/traces?request_id="
                      f"{job['requestId'] or 'job-' + job_id}")["spans"]
    enc = [s for s in spans if s["name"] == "ec.encode"]
    check(len(enc) == 1 and "codec" in enc[0].get("attrs", {}),
          "the job's trace carries one ec.encode span with a device "
          "report")
    attrs = enc[0]["attrs"]
    report["worker"] = attrs
    report["encode_span_seconds"] = round(enc[0]["durationMs"] / 1e3, 2)
    codec = attrs["codec"]
    check(codec["backend"] == "jax" and
          codec["platform"] == report["device"]["platform"] and
          codec["kind"] == report["device"]["kind"],
          f"the worker encoded on {codec}")
    check(codec["platform"] in job["message"] and
          codec["kind"] in job["message"],
          "the completion message names platform and device_kind")
    peaks = attrs.get("devicePeakBytes", {})
    check(codec["platform"] == "cpu" or (
        len(peaks) == codec["count"] and
        all((p or 0) > 0 for p in peaks.values())),
        f"every visible device held window data: {peaks}")

    # EC read path, then the shard files on the servers' disks
    report["read_ec_seconds"] = read_all("read-back through the EC path")
    found = shard_files(vol_dirs, vid)
    check(sorted(found) == list(range(14)) and
          all(len(v) == 1 for v in found.values()),
          "14 shard files on the volume servers' disks, one each")
    paths = {sid: v[0] for sid, v in found.items()}
    t0 = time.perf_counter()
    check(parity_matches(ReedSolomonCPU(10, 4),
                         [paths[i] for i in range(10)],
                         [paths[i] for i in range(10, 14)]),
          ".ec10-.ec13 byte-identical to rs_cpu.ReedSolomonCPU(10,4)"
          ".parity over .ec00-.ec09")
    report["parity_check_seconds"] = round(time.perf_counter() - t0, 2)
    digests = {str(sid): file_digest(p) for sid, p in paths.items()}

    # phase 2's copy of the shard set (hard links: same bytes, and a
    # rebuild there writes new files, never these)
    p2 = os.path.join(work, "phase2")
    os.makedirs(p2)
    base2 = os.path.join(p2, f"{COLLECTION}_{vid}")
    for sid, p in paths.items():
        os.link(p, base2 + to_ext(sid))
    digests_path = os.path.join(p2, "digests.json")
    with open(digests_path, "w") as f:
        json.dump(digests, f)

    # lose two data shards on their holders (BASELINE config 4)
    for sid in LOST:
        port = vports[vol_dirs.index(os.path.dirname(paths[sid]))]
        r = http_json("POST", f"127.0.0.1:{port}/admin/ec/delete_shards",
                      {"volumeId": vid, "collection": COLLECTION,
                       "shardIds": [sid]})
        check("error" not in r and not os.path.exists(paths[sid]),
              f"deleted data shard {sid} on 127.0.0.1:{port}")
    report["read_degraded_seconds"] = read_all(
        "degraded read-back (2 data shards lost, from chip-made parity)")

    def master_sees_loss():
        r = http_json("GET", f"{master}/dir/ec_lookup?volumeId={vid}")
        have = {s for loc in r["shardIdLocations"]
                for s in loc["shardIds"]}
        return have == set(range(14)) - set(LOST)
    wait_for("the master to see 12 shards", master_sees_loss, 30)
    t0 = time.perf_counter()
    sh = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "shell", "-master",
         master, f"lock; ec.rebuild -volumeId={vid} "
         f"-collection={COLLECTION}; unlock"],
        cwd=REPO, env=procs.role_env, capture_output=True, text=True,
        timeout=600)
    report["shell_rebuild_seconds"] = round(time.perf_counter() - t0, 2)
    print("shell:", sh.stdout.strip().replace("\n", " | "), flush=True)
    check(sh.returncode == 0 and "rebuilt shards" in sh.stdout,
          "ec.rebuild through the shell" +
          ("" if sh.returncode == 0 else f": {sh.stderr[-500:]}"))
    after = shard_files(vol_dirs, vid)
    check(sorted(after) == list(range(14)) and all(
        file_digest(p) == digests[str(sid)]
        for sid in LOST for p in after[sid]),
        "rebuilt shard files digest-equal to the ones deleted")

    # who touched the chip: read every role's memory map while they
    # are all still up
    libtpu = procs.pids_mapping("libtpu")
    jaxlib = procs.pids_mapping("jaxlib")
    report["libtpu_pids"] = libtpu
    report["jaxlib_pids"] = jaxlib
    print(f"libtpu mapped by: {libtpu}; jaxlib mapped by: {jaxlib}",
          flush=True)
    worker_pid = procs.roles["worker"].pid
    check(set(jaxlib.values()) == {worker_pid},
          "the worker is the only role that imported jax")
    if report["device"]["platform"] == "tpu":
        check(list(libtpu.values()) == [worker_pid],
              "exactly one PID has libtpu mapped, and it is the worker")
    return base2, digests_path


# -- main -------------------------------------------------------------------

def run(args) -> int:
    held = os.environ.get("JAX_PLATFORMS", "")
    if held and "tpu" not in held.split(",") and not args.rehearse_cpu:
        print(f"chip_smoke.py: no chip: JAX_PLATFORMS={held} holds JAX "
              "off the TPU, and this check only counts on one "
              "(--rehearse-cpu walks it on the CPU)", file=sys.stderr)
        return 2

    def out_of_time(*_):
        raise SmokeFailure(f"time limit of {TIME_LIMIT_S}s reached")
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(TIME_LIMIT_S)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    sz = sizes(args.rehearse_cpu)
    work = tempfile.mkdtemp(prefix="chip_smoke_", dir=args.workdir)
    procs = Procs(work, args.rehearse_cpu)
    report: dict = {"seed": args.seed, "sizes": sz, "phase_seconds": {}}
    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    ok = False
    try:
        t0 = time.perf_counter()
        try:
            probe = procs.run_child("probe", [], 300)
        except SmokeFailure as e:
            raise SmokeFailure(f"no chip: JAX found no accelerator "
                               f"({e})") from None
        report.update(probe)
        report["phase_seconds"]["probe"] = round(
            time.perf_counter() - t0, 2)
        dev = probe["device"]
        print(f"device: platform={dev['platform']} "
              f"device_kind={dev['kind']} count={dev['count']} "
              f"(init {probe['init_seconds']}s)\n"
              f"probe_backend: {probe['probe']}", flush=True)
        if dev["platform"] != "tpu" and not args.rehearse_cpu:
            raise SmokeFailure(f"no chip: JAX runs on {dev}")

        t0 = time.perf_counter()
        print("phase 1: the store, one worker on the chip", flush=True)
        base2, digests_path = phase1(procs, work, args.seed, sz, report)
        procs.stop_all()   # cluster down, worker gone: the chip is free
        for i in range(3):
            shutil.rmtree(os.path.join(work, f"vol{i}"))
        report["phase_seconds"]["phase1"] = round(
            time.perf_counter() - t0, 2)

        extra = ["--base", base2, "--digests", digests_path,
                 "--seed", str(args.seed)] + \
            (["--rehearse-cpu"] if args.rehearse_cpu else [])
        for phase in ("phase2", "phase3"):
            t0 = time.perf_counter()
            print(f"{phase}: the device codec beyond encode, one child"
                  + (" (again, warm)" if phase == "phase3" else ""),
                  flush=True)
            rep = procs.run_child("device", extra, 600)
            report[phase] = rep
            report["phase_seconds"][phase] = round(
                time.perf_counter() - t0, 2)
            check(rep["device"] == dev, f"{phase} ran on {rep['device']}")
            for name, passed in rep["checks"].items():
                check(passed, f"{phase} {name}")
            check(args.rehearse_cpu or not rep["pallas_interpret"],
                  f"{phase} ran the compiled (non-interpret) Pallas "
                  "kernel")
            print(f"  {phase} compile ledger: {rep['compile']}",
                  flush=True)
        check(report["phase3"]["compile"]["compiled"] == 0,
              "the warm child compiled nothing (all "
              f"{report['phase3']['compile']['requests']} programs "
              "served from the persistent cache)")
        ok = True
    except Exception as e:  # noqa: BLE001 — the script's outer edge:
        # say what failed, keep the roles' logs, still clean up below
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        procs.dump_logs(os.path.join(out_dir, "chip_smoke_logs"))
    finally:
        signal.alarm(0)
        procs.stop_all()
        shutil.rmtree(work, ignore_errors=True)
        report["ok"] = ok
        if "device" in report:   # a run that found no chip has nothing
            try:                 # to say, and keeps the last report
                os.makedirs(out_dir, exist_ok=True)
                with open(os.path.join(
                        out_dir, "chip_smoke_report.json"), "w") as f:
                    json.dump(report, f, indent=1)
            except OSError:
                pass
    if not ok:
        return 1
    summarize(report)
    if args.rehearse_cpu:
        print("rehearsal on the CPU passed; this is not a chip run",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


def summarize(r: dict) -> None:
    """Everything by name: the first local-chip figures, as
    observations (none of this is a benchmark)."""
    w, p2, p3 = r["worker"], r["phase2"], r["phase3"]
    cold = w["compile"]["seconds"] + p2["compile"]["seconds"]
    line = {
        "platform": r["device"]["platform"],
        "device_kind": r["device"]["kind"],
        "device_count": r["device"]["count"],
        "libtpu_pids": r["libtpu_pids"],
        "bytes_loaded": r["bytes_loaded"],
        "phase_seconds": r["phase_seconds"],
        "job_seconds": r["job_seconds"],
        "encode_span_seconds": r["encode_span_seconds"],
        "cold_compile_seconds": round(cold, 2),
        "warm_compile_seconds": p3["compile"]["seconds"],
        "compiled_shapes": w["compile"]["compiled"]
        + p2["compile"]["compiled"],
        "compile_requests": w["compile"]["requests"]
        + p2["compile"]["requests"],
        "worker_staged_h2d_gbps": w["staging"]["h2d_gbps"],
        "worker_staged_d2h_gbps": w["staging"]["d2h_gbps"],
        "worker_overlap_fraction": w["staging"]["overlap_fraction"],
        "worker_windows": w["staging"]["windows"],
        "probe_cpu_engine": r["probe"]["cpu_engine"],
        "probe_cpu_gbps": r["probe"]["cpu_gbps"],
        "probe_h2d_gbps": r["probe"]["h2d_gbps"],
        "probe_choice": r["probe"]["choice"],
        "compile_cache_dir": r["compile_cache_dir"],
    }
    print("chip_smoke: " + json.dumps(line), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every blob and buffer")
    ap.add_argument("--workdir", default=None,
                    help="parent of the working directory (about 5 GiB "
                         "at peak, removed on exit; default: the "
                         "system temp dir)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size walk on JAX-on-CPU; never passes")
    ap.add_argument("--child", choices=["probe", "device"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--base", help=argparse.SUPPRESS)
    ap.add_argument("--digests", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
