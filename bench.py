"""North-star benchmark: RS(10,4) erasure-coding encode throughput per chip.

Measures the TPU GF(2^8) constant-matrix-apply kernel (the re-expression
of the reference's hot loop, weed/storage/erasure_coding/ec_encoder.go:265
enc.Encode via klauspost/reedsolomon SIMD) and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N}

Throughput accounting matches how `weed shell ec.encode` would be judged:
volume data bytes consumed per second (input bytes, not input+parity).
`vs_baseline` is the ratio to the reference CPU engine's typical RS(10,4)
single-core SIMD throughput (BASELINE.md records no published EC numbers;
klauspost/reedsolomon's own amd64 benchmarks put 10+4 encode at roughly
6 GB/s/core); the measured on-machine native C++ engine number is also
reported as `measured_native_cpu_gbps` so either denominator is available.

This file is an orchestrator that never imports jax in the parent
process: the chip belongs to one process, the child that measures
(``--measure tpu``, under a timeout).  There is no fallback — with no
TPU, or when a device phase throws, the JSON of the phases that did
finish is printed and the exit code is non-zero; a CPU figure is never
printed under the per-chip metric name.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

BASELINE_CPU_GBPS = 6.0

# Per-shard bytes per timed step. 64 MiB x 10 data shards = 640 MiB of
# volume data per step — large enough to hide dispatch overheads, small
# enough to triple-buffer in 16 GiB HBM.
SHARD_BYTES = 64 * 1024 * 1024
DATA_SHARDS = 10
PARITY_SHARDS = 4
CHAIN = 16  # kernel steps chained per timed launch (amortizes latency)
ITERS = 3

TPU_TIMEOUT_S = 720  # compile + e2e + tpu-forced e2e + rebuild cluster


def _best_of_gbps(parity_fn, shard_bytes=1024 * 1024, seed=1, iters=3):
    """Warmup + best-of-N wall-clock GB/s of a host parity(data) callable."""
    nd = np.random.default_rng(seed).integers(
        0, 256, size=(DATA_SHARDS, shard_bytes), dtype=np.uint8)
    parity_fn(nd[:, :1024])  # warmup
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        parity_fn(nd)
        best = min(best, time.perf_counter() - t0)
    return DATA_SHARDS * shard_bytes / best / 1e9


def _measure_native_cpu_gbps():
    """Measured on-machine CPU engine (our C++/AVX-512 klauspost analog)."""
    try:
        from seaweedfs_tpu.ops import rs_native
        if not rs_native.available():
            return None
        nat = rs_native.ReedSolomonNative(DATA_SHARDS, PARITY_SHARDS)
        return round(_best_of_gbps(nat.parity), 2)
    except Exception:
        return None


def _fsync_shards(base: str, ctx) -> None:
    """fsync shard outputs inside the timed window so e2e and the disk
    probe use the same durable-write accounting (otherwise e2e can
    "beat" the disk ceiling via page cache)."""
    for i in range(ctx.total):
        with open(base + ctx.to_ext(i), "rb+") as f:
            os.fsync(f.fileno())


def _disk_write_probe(tmp: str, blob: bytes, total_bytes: int,
                      nfiles: int = 14) -> float:
    """Disk write bandwidth in the ENCODE PIPELINE'S OWN pattern:
    round-robin appends across nfiles with an _OverlappedFlusher
    running (exactly as _generate_ec_files drives its outputs) and a
    final durable flush, over the SAME total volume as the shard
    output it bounds.  Round 4's probe used a serial write-then-fsync
    pass over fewer bytes and UNDERSTATED the fs — the pipeline then
    'beat' its own ceiling by 1.35x.  A ceiling you can exceed is not
    a ceiling; matching pattern + volume is what makes this one real."""
    from seaweedfs_tpu.storage.erasure_coding.ec_encoder import (
        _OverlappedFlusher)
    per_file = max(total_bytes // nfiles, 1 << 20)
    paths = [os.path.join(tmp, f"probe{i:02d}") for i in range(nfiles)]
    pfs = [open(p, "wb") for p in paths]
    flusher = _OverlappedFlusher(pfs)
    t0 = time.perf_counter()
    try:
        written = 0
        while written < per_file:
            n = min(4 << 20, per_file - written)
            for f in pfs:
                f.write(blob[:n])
            written += n
    finally:
        flusher.stop(final=True)
        for f in pfs:
            f.close()
    dt = time.perf_counter() - t0
    for p in paths:
        os.remove(p)
    return nfiles * per_file / dt / 1e9


def _disk_read_probe(paths: "list[str]") -> "tuple[float, bool]":
    """Read bandwidth over the given files, round-robin 4MB chunks
    (the rebuild/decode read pattern).  Tries to drop the page cache
    first; returns (gbps, cache_dropped) — when the drop fails the
    number is cache-optimistic and only useful as a non-binding
    ceiling term."""
    dropped = False
    try:
        with open("/proc/sys/vm/drop_caches", "w") as f:
            f.write("1\n")
        dropped = True
    except OSError:
        pass
    fhs = [open(p, "rb") for p in paths]
    total = 0
    t0 = time.perf_counter()
    alive = fhs[:]
    while alive:
        still = []
        for f in alive:
            b = f.read(4 << 20)
            if b:
                total += len(b)
                still.append(f)
        alive = still
    dt = time.perf_counter() - t0
    for f in fhs:
        f.close()
    return (total / dt / 1e9 if dt > 0 else 0.0), dropped


def _codec_reconstruct_rate(d: int, p: int, lost: "list[int]") -> float:
    """Volume-bytes/s of the codec op the rebuild pipeline ACTUALLY
    runs — a [len(lost), d] reconstruction-matrix apply over the
    survivor rows (ec_encoder._generate_missing_ec_files `compute`),
    NOT the generic full reconstruct (which regenerates every shard
    and would understate this ceiling term ~5x)."""
    from seaweedfs_tpu.ops import rs_matrix
    try:
        from seaweedfs_tpu.ops import rs_native
        eng = rs_native.ReedSolomonNative(d, p) \
            if rs_native.available() else None
    except Exception:
        eng = None
    if eng is None:
        from seaweedfs_tpu.ops import rs_cpu
        eng = rs_cpu.ReedSolomonCPU(d, p)
    present_mask = tuple(i not in lost for i in range(d + p))
    rec, _survivors = rs_matrix.cached_reconstruction_matrix(
        d, p, present_mask, tuple(lost))
    n = 4 << 20
    buf = np.random.default_rng(3).integers(
        0, 256, size=(d, n), dtype=np.uint8)
    eng.apply_matrix(rec, buf[:, :4096])  # warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        eng.apply_matrix(rec, buf)
        best = min(best, time.perf_counter() - t0)
    return d * n / best / 1e9


def _apply_ceiling(out: dict, key: str, measured: float,
                   ceilings: dict) -> None:
    """Record <key>_bound_by / _ceiling_gbps / _of_ceiling from the
    binding (minimum) resource.  The ceiling is a PREDICTION — every
    probe runs BEFORE the measurement it bounds — and is never raised
    to the observed number: a ceiling that chases the measurement is
    vacuous (VERDICT r5's "of_ceiling = 1.0").  of_ceiling > 1.0 is
    reported as-is with a note saying the probe under-measured the
    binding resource (disk probes race writeback state)."""
    ceilings = {k: v for k, v in ceilings.items() if v}
    if not ceilings or not measured:
        return
    bound_by = min(ceilings, key=ceilings.get)
    ceiling = ceilings[bound_by]
    if measured > ceiling:
        out[f"{key}_ceiling_note"] = (
            f"measured {round(measured, 3)} exceeds the predicted "
            f"ceiling {round(ceiling, 3)} — the pre-run probe "
            f"under-measured the binding resource")
    out[f"{key}_bound_by"] = bound_by
    out[f"{key}_ceiling_gbps"] = round(ceiling, 3)
    out[f"{key}_of_ceiling"] = round(measured / ceiling, 2)


def _calibrate_device(budget_s: float = 20.0) -> dict:
    """Small pre-run device probe, run FIRST: h2d bandwidth, per-chip
    GF kernel rate, device count.  Its numbers do two jobs no
    after-the-fact probe can: (1) the predicted roofline
    `min(h2d GB/s, kernel GB/s/chip x devices)` that of_ceiling is
    judged against — computed BEFORE the run so it can never be raised
    to the observed number, and (2) the scale factor that sizes every
    timed phase to fit the arm's budget (the BENCH_r05 lesson: a
    fixed-size TPU arm behind a slow host->device path ran out its
    whole timeout and yielded nothing)."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_matrix
    from seaweedfs_tpu.ops.rs_jax import gf_apply_matrix_words

    t_start = time.perf_counter()
    ndev = len(jax.devices())
    rng = np.random.default_rng(5)
    # h2d: grow 1MB -> 64MB, stopping as soon as one transfer costs
    # >= 1s or half the probe budget is gone — a slow link is
    # detected cheaply, a fast link gets a big-enough probe to trust
    size = 1 << 20
    h2d = 0.0
    while True:
        host = rng.integers(0, 2**32, size // 4, dtype=np.uint32)
        t0 = time.perf_counter()
        jax.device_put(host).block_until_ready()
        dt = max(time.perf_counter() - t0, 1e-9)
        h2d = host.nbytes / dt / 1e9
        if dt >= 1.0 or size >= (64 << 20) or \
                time.perf_counter() - t_start > budget_s / 2:
            break
        size *= 4
    # kernel rate on the default device at a modest batch
    kb = min(8 << 20, max(1 << 20, size))
    words = kb // 4
    mat = jnp.asarray(rs_matrix.parity_matrix(DATA_SHARDS,
                                              PARITY_SHARDS))
    d32 = jax.device_put(rng.integers(
        0, 2**32, size=(DATA_SHARDS, words), dtype=np.uint32))
    gf_apply_matrix_words(mat, d32).block_until_ready()  # compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        gf_apply_matrix_words(mat, d32).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    kernel = DATA_SHARDS * kb / best / 1e9
    return {
        "devices": ndev,
        "h2d_gbps": round(h2d, 3),
        "h2d_probe_bytes": size,
        "kernel_gbps_per_chip": round(kernel, 3),
        "predicted_roofline_gbps": round(min(h2d, kernel * ndev), 3),
        "probe_seconds": round(time.perf_counter() - t_start, 3),
    }


def _measure_e2e(on_tpu: bool, probe: "dict | None",
                 budget_s: float = float("inf"),
                 calib: "dict | None" = None):
    """End-to-end `ec.encode` + `ec.rebuild` + RS(6,3) `ec.decode`
    wall-clock through the staged disk<->codec pipelines
    (ec_encoder._staged_run), preserving the reference's 1GB/1MB row
    geometry (ec_encoder.go:280-319).  The codec backend is the
    feed-rate-probed default (ec_context.default_backend) — the engine
    a real `weed shell ec.encode` on this machine would run.
    Accounting is volume data bytes/s throughout (how `weed shell`
    would be judged); rebuild covers BASELINE config 4 (2 lost shards
    from survivors), decode covers config 5 (RS(6,3) shards -> .dat
    with a data shard missing).  Each config gets its own bound-by
    label + ceiling derived from pattern-matched disk probes and the
    codec's measured reconstruct rate.  Returns a dict."""
    import shutil
    import tempfile

    from seaweedfs_tpu.storage.erasure_coding import (ec_decoder,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    size = (1 << 30) if on_tpu else (128 << 20)
    if on_tpu and budget_s < float("inf"):
        # size the volume from the calibrated rate of the engine this
        # pipeline will ACTUALLY run, so ~6 timed/fsync passes over it
        # stay inside half the remaining budget (the pre-run scaling
        # the BENCH_r05 timeout demanded)
        rate = None
        if probe and probe.get("choice") == "jax" and calib:
            rate = calib.get("predicted_roofline_gbps")
        elif probe:
            rate = probe.get("cpu_gbps")
        if rate:
            per_pass = max(min(budget_s, 600.0) / 2 / 6, 5.0)
            size = int(min(size, rate * 1e9 * per_pass))
            # keep a whole number of 64MB write chunks (the .dat
            # writer below repeats a 64MB blob size//chunk times)
            size = max(128 << 20, (size >> 26) << 26)
    tmp = tempfile.mkdtemp(prefix="bench_ec_")
    out = {}
    try:
        base = os.path.join(tmp, "bench_vol")
        rng = np.random.default_rng(7)
        chunk = min(64 << 20, size)
        blob = rng.integers(0, 256, chunk, dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            for _ in range(size // chunk):
                f.write(blob)
            f.flush()
            os.fsync(f.fileno())  # drain: .dat writeback must not
            # steal disk bandwidth from the timed encode below

        disk_gbps = _disk_write_probe(tmp, blob, size * 14 // 10)
        out["disk_write_gbps"] = round(disk_gbps, 3)

        ctx = ECContext()  # feed-rate-probed backend
        out["e2e_backend"] = ctx.backend
        t0 = time.perf_counter()
        ec_encoder.write_ec_files(base, ctx)
        _fsync_shards(base, ctx)
        dt = time.perf_counter() - t0
        out["e2e_encode_gbps"] = round(size / dt / 1e9, 3)
        out["e2e_dat_bytes"] = size
        ceilings = {"shard-file disk writes (1.4x write amplification)":
                    disk_gbps / 1.4}
        if ctx.backend == "jax":
            if calib:
                ceilings["host->device staging (windowed)"] = \
                    calib.get("h2d_gbps")
                ceilings[f"GF kernel x {calib.get('devices')} "
                         f"devices"] = \
                    calib.get("kernel_gbps_per_chip", 0) * \
                    calib.get("devices", 1)
            elif probe:
                ceilings["host->device transfer"] = probe.get("h2d_gbps")
        elif probe:
            ceilings["GF codec engine"] = probe.get("cpu_gbps")
        _apply_ceiling(out, "e2e", out["e2e_encode_gbps"], ceilings)

        # read probe over the just-written shards (rebuild's input
        # pattern); cache-dropped when the platform allows
        read_gbps, dropped = _disk_read_probe(
            [base + ctx.to_ext(i) for i in range(ctx.total)])
        out["disk_read_gbps"] = round(read_gbps, 3)
        out["disk_read_cache_dropped"] = dropped

        # config 4: rebuild 2 lost shards (1 data + 1 parity) from the
        # 12 survivors, volume-bytes accounting.  Reads 12/10 of the
        # volume, reconstructs on the codec, writes 2/10.
        os.remove(base + ctx.to_ext(3))
        os.remove(base + ctx.to_ext(12))
        t0 = time.perf_counter()
        ec_encoder.rebuild_ec_files(base, ctx)
        _fsync_shards(base, ctx)
        dt = time.perf_counter() - t0
        out["rebuild_gbps"] = round(size / dt / 1e9, 3)
        out["rebuild_lost_shards"] = 2
        _apply_ceiling(out, "rebuild", out["rebuild_gbps"], {
            "survivor shard reads (1.2x)": read_gbps / 1.2,
            "rebuilt shard writes (0.2x)": disk_gbps / 0.2,
            "GF reconstruct": _codec_reconstruct_rate(10, 4, [3, 12]),
        })

        # config 5: RS(6,3) alternate scheme, then decode (shards ->
        # .dat) with a data shard missing — the degraded streaming
        # read path.  Timed section reads ~2.33x the volume (8
        # survivors then 6 data shards) and writes ~1.17x (rebuilt
        # shard + .dat).
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp, exist_ok=True)
        dsize = min(size, 256 << 20)
        with open(base + ".dat", "wb") as f:
            for _ in range(max(dsize // chunk, 1)):
                f.write(blob[:min(chunk, dsize)])
        dsize = os.path.getsize(base + ".dat")
        ctx63 = ECContext(6, 3, backend=ctx.backend)
        ec_encoder.write_ec_files(base, ctx63)
        os.remove(base + ".dat")
        os.remove(base + ctx63.to_ext(2))  # lose a data shard
        t0 = time.perf_counter()
        ec_encoder.rebuild_ec_files(base, ctx63)
        ec_decoder.write_dat_file(
            base, dsize, [base + ctx63.to_ext(i) for i in range(6)])
        with open(base + ".dat", "rb+") as f:
            os.fsync(f.fileno())
        dt = time.perf_counter() - t0
        out["rs63_decode_gbps"] = round(dsize / dt / 1e9, 3)
        _apply_ceiling(out, "rs63_decode", out["rs63_decode_gbps"], {
            "shard reads (2.33x)": read_gbps / 2.33,
            ".dat + rebuilt shard writes (1.17x)": disk_gbps / 1.17,
            "GF reconstruct (6,3)":
                _codec_reconstruct_rate(6, 3, [2]),
        })
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _proc_tree_cpu_s(pid: int) -> float:
    """user+system CPU seconds of `pid` plus its direct children
    (the filer's pre-fork workers), from /proc — per-role CPU
    attribution that survives multi-process roles, where sampling one
    random worker's /metrics would attribute a fraction to the
    whole."""
    clk = os.sysconf("SC_CLK_TCK")

    def one(statpath: str, want_ppid: "int | None" = None) -> float:
        try:
            with open(statpath, "rb") as f:
                parts = f.read().rsplit(b") ", 1)[1].split()
            if want_ppid is not None and int(parts[1]) != want_ppid:
                return 0.0
            return (int(parts[11]) + int(parts[12])) / clk
        except (OSError, IndexError, ValueError):
            return 0.0

    total = one(f"/proc/{pid}/stat")
    try:
        for d in os.listdir("/proc"):
            if d.isdigit() and int(d) != pid:
                total += one(f"/proc/{d}/stat", want_ppid=pid)
    except OSError:
        pass
    return total


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_port(port: int, timeout_s: float = 45.0) -> None:
    import socket
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=1.0):
                return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError(f"port {port} never opened")


_LEAN_WORKER = r"""
import http.client, json, os, sys, threading, time
cfg = json.load(sys.stdin)
filers, nthreads = cfg["filers"], cfg["threads"]
payload, seconds = cfg["payload"], cfg["seconds"]
start_at, wid0 = cfg["startAt"], cfg["wid0"]
plane_route = cfg.get("planeRoute", False)
blob = os.urandom(payload)
hdrs = {"Content-Type": "application/octet-stream"}
lat = [[] for _ in range(nthreads)]
errors = [0]
plane_acked = [0]
plane_fb = [0]

def plane_conn(target):
    # one /status probe per thread: the filer advertises its armed
    # native meta plane's port (0 / absent when disarmed).  Under
    # pre-fork workers each probe lands on a random sibling, which
    # conveniently spreads threads across the sibling planes.
    try:
        c = http.client.HTTPConnection(target, timeout=5)
        c.request("GET", "/status")
        r = c.getresponse()
        doc = json.loads(r.read())
        c.close()
        port = int(doc.get("metaPlanePort") or 0)
        if not port:
            return None
        host = target.rsplit(":", 1)[0]
        return [host + ":" + str(port),
                http.client.HTTPConnection(
                    host + ":" + str(port), timeout=30)]
    except (OSError, ValueError, http.client.HTTPException):
        return None

def writer(t):
    w = wid0 + t
    target = filers[w % len(filers)]
    conn = http.client.HTTPConnection(target, timeout=30)
    pc = plane_conn(target) if plane_route else None
    i = 0
    while time.time() < start_at:
        time.sleep(0.01)
    deadline = time.time() + seconds
    while time.time() < deadline:
        path = "/bench/w%d/%d" % (w, i)
        i += 1
        t0 = time.perf_counter()
        if pc is not None:
            # plane first; a 404 is the plane's documented "not
            # eligible / disarmed" answer -> replay on the Python
            # front within the same latency sample (the client-side
            # cost of a fallback is part of the honest number)
            try:
                pc[1].request("POST", path, blob, hdrs)
                r = pc[1].getresponse()
                r.read()
                if r.status == 201:
                    plane_acked[0] += 1
                    lat[t].append(time.perf_counter() - t0)
                    continue
                plane_fb[0] += 1
            except (OSError, http.client.HTTPException):
                plane_fb[0] += 1
                pc[1].close()
                try:
                    pc[1] = http.client.HTTPConnection(pc[0],
                                                       timeout=30)
                except OSError:
                    pc = None
        try:
            conn.request("POST", path, blob, hdrs)
            r = conn.getresponse()
            r.read()
            if r.status >= 300:
                errors[0] += 1
            else:
                lat[t].append(time.perf_counter() - t0)
        except (OSError, http.client.HTTPException):
            errors[0] += 1
            conn.close()
            conn = http.client.HTTPConnection(target, timeout=30)
    conn.close()

ts = [threading.Thread(target=writer, args=(t,)) for t in range(nthreads)]
[t.start() for t in ts]
[t.join() for t in ts]
json.dump({"lat": [x for per in lat for x in per],
           "errors": errors[0], "planeAcked": plane_acked[0],
           "planeFallbacks": plane_fb[0]}, sys.stdout)
"""


def _lean_load(filer_urls, writers, seconds, payload, tmp,
               threads_per_proc: int = 7,
               plane_route: bool = False) -> dict:
    """Drive the write load from MULTIPLE lean client processes (see
    the lean_client comment at the call site) and aggregate req/s and
    latency percentiles.  All workers synchronize on a shared start
    time so the measured window is common."""
    import subprocess
    import time as _time

    nprocs = max(1, (writers + threads_per_proc - 1) //
                 threads_per_proc)
    start_at = _time.time() + 2.0 + 0.3 * nprocs
    procs = []
    wid = 0
    for p in range(nprocs):
        n = min(threads_per_proc, writers - wid)
        if n <= 0:
            break
        cfg = {"filers": filer_urls, "threads": n, "payload": payload,
               "seconds": seconds, "startAt": start_at, "wid0": wid,
               "planeRoute": plane_route}
        wid += n
        sp = subprocess.Popen([sys.executable, "-c", _LEAN_WORKER],
                              stdin=subprocess.PIPE,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL)
        sp.stdin.write(json.dumps(cfg).encode())
        sp.stdin.close()
        procs.append(sp)
    lat: list = []
    errors = 0
    plane_acked = 0
    plane_fb = 0
    for sp in procs:
        out = sp.stdout.read()
        sp.wait(timeout=60)
        try:
            doc = json.loads(out)
        except ValueError:
            errors += 1
            continue
        lat.extend(doc["lat"])
        errors += doc["errors"]
        plane_acked += doc.get("planeAcked", 0)
        plane_fb += doc.get("planeFallbacks", 0)
    lat.sort()
    n = len(lat)
    return {
        **({"write_path_plane_acked": plane_acked,
            "write_path_plane_fallbacks": plane_fb}
           if plane_route else {}),
        "write_path_writers": wid,
        "write_path_client_procs": len(procs),
        "write_path_seconds": float(seconds),
        "write_path_requests": n,
        "write_path_errors": errors,
        "write_path_req_per_sec":
            round(n / seconds, 1) if seconds else 0,
        "write_path_p50_ms": round(lat[n // 2] * 1e3, 2) if n else 0,
        "write_path_p99_ms": round(
            lat[min(n - 1, int(n * 0.99))] * 1e3, 2) if n else 0,
    }


def _spawn_role(args, port, log_path, env_extra=None):
    """One real `python -m seaweedfs_tpu <role>` server process.
    JAX_PLATFORMS=cpu: repair nodes run the host codec (the probed
    default on any box where the chip is not the bottleneck) and must
    not grab the measurement TPU."""
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
               **(env_extra or {}))
    with open(log_path, "ab") as logf:  # child holds its own dup
        proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", *args],
            cwd=repo, env=env, stdout=logf, stderr=subprocess.STDOUT)
    try:
        _wait_port(port)
    except Exception:
        proc.kill()  # never leak a half-started role on boot failure
        proc.wait(timeout=10)
        raise
    return proc


def _measure_dist_rebuild(nodes: int = 3, blob_mb: int = 1,
                          n_blobs: int = 96) -> dict:
    """Distributed rebuild A/B over a loopback PROC-cluster (real
    master + volume server processes talking HTTP, so donors, the
    rebuilder, and its GF codec run on separate interpreters like a
    real deployment): the seed's copy-then-rebuild (serially pull
    every survivor whole onto one rebuilder via /admin/ec/copy, then
    rebuild from local files) vs the slice-pipelined streaming path
    (mode=stream: ranged /admin/ec/shard_read streams, one prefetching
    stream per survivor, straight into the GF pipeline).  Identical
    loss pattern both rounds; stream runs FIRST so the copy round
    cannot inherit staged survivor files.  Volume-bytes accounting
    (data_shards x shard_size), like every other number this bench
    emits."""
    import shutil
    import tempfile
    import time as _time

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.server.httpd import http_json
    from seaweedfs_tpu.shell import CommandEnv, run_command

    tmp = tempfile.mkdtemp(prefix="bench_rebuild_")
    procs = []
    try:
        mport = _free_port()
        mdir = os.path.join(tmp, "master-meta")
        os.makedirs(mdir)
        procs.append(_spawn_role(
            ["master", "-port", str(mport), "-mdir", mdir,
             "-volumeSizeLimitMB", "1024"], mport,
            os.path.join(tmp, "master.log")))
        master_url = f"127.0.0.1:{mport}"
        for i in range(nodes):
            d = os.path.join(tmp, f"v{i}")
            os.makedirs(d)
            vport = _free_port()
            procs.append(_spawn_role(
                ["volume", "-port", str(vport), "-dir", d,
                 "-mserver", master_url, "-max", "16"], vport,
                os.path.join(tmp, f"vol{i}.log")))
        deadline = _time.time() + 30
        while _time.time() < deadline:
            try:
                if len(http_json("GET",
                                 f"{master_url}/cluster/status"
                                 )["dataNodes"]) == nodes:
                    break
            except OSError:
                pass
            _time.sleep(0.1)
        rng = np.random.default_rng(23)
        blob = rng.integers(0, 256, blob_mb << 20,
                            dtype=np.uint8).tobytes()
        fids = [operation.submit(master_url, blob)
                for _ in range(n_blobs)]
        vid = int(fids[0].split(",")[0])
        env = CommandEnv(master_url)
        env.lock()
        run_command(env, f"ec.encode -volumeId={vid}")
        _time.sleep(0.5)

        from seaweedfs_tpu.topology import (fetch_ec_shard_locations,
                                            shard_ids_to_urls)

        def shard_map():
            return fetch_ec_shard_locations(master_url, vid)

        by_url = shard_map()
        rebuilder = max(by_url, key=lambda u: len(by_url[u]))
        info = http_json("GET",
                         f"{rebuilder}/admin/ec/info?volumeId={vid}")
        volume_bytes = info["dataShards"] * info["shardSize"]
        donors = [u for u in sorted(by_url) if u != rebuilder]
        victims = [(donors[0], by_url[donors[0]][0]),
                   (donors[-1], by_url[donors[-1]][-1])]
        for url, sid in victims:
            http_json("POST", f"{url}/admin/ec/delete_shards",
                      {"volumeId": vid, "shardIds": [sid]})
        _time.sleep(0.3)
        locs = shard_map()
        victim_sids = [sid for _u, sid in victims]
        out = {"dist_rebuild_nodes": nodes,
               "dist_rebuild_volume_bytes": volume_bytes,
               "dist_rebuild_lost_shards": len(victims)}
        # untimed warmup round first: the initial rebuild in the
        # rebuilder process pays one-off costs (native codec load, GF
        # tables, matrix cache) that must not be billed to either
        # mode.  Then MEDIAN of 4 interleaved rounds per mode: this
        # box's wall-clock jitters under its cpu-shares cap, and a
        # best-of would reward one mode's lucky tail instead of its
        # typical repair time.
        rounds: dict = {"stream": [], "copy": []}
        for mode in ("warmup", "stream", "copy", "stream", "copy",
                     "stream", "copy", "stream", "copy"):
            t0 = time.perf_counter()
            if mode == "copy":
                have = set(locs.get(rebuilder, []))
                sidecars_pending = True
                for url, sids in locs.items():
                    if url == rebuilder:
                        continue
                    need = [s for s in sids if s not in have]
                    if need:
                        r = http_json(
                            "POST", f"{rebuilder}/admin/ec/copy",
                            {"volumeId": vid, "collection": "",
                             "shardIds": need, "sourceDataNode": url,
                             "copyEcxFile": sidecars_pending,
                             "copyEcjFile": sidecars_pending,
                             "copyVifFile": sidecars_pending},
                            timeout=600.0)
                        if "error" in r:
                            raise RuntimeError(f"copy: {r['error']}")
                        sidecars_pending = False
                        have.update(need)
                r = http_json("POST", f"{rebuilder}/admin/ec/rebuild",
                              {"volumeId": vid, "mode": "local"},
                              timeout=600.0)
            else:
                # warmup is stream-shaped: it leaves no survivor files
                # behind on the rebuilder, so neither timed round
                # inherits state it should not have
                shard_locations = shard_ids_to_urls(locs)
                r = http_json("POST", f"{rebuilder}/admin/ec/rebuild",
                              {"volumeId": vid, "mode": "stream",
                               "shardLocations": shard_locations},
                              timeout=600.0)
            dt = time.perf_counter() - t0
            if "error" in r:
                raise RuntimeError(f"{mode} rebuild: {r['error']}")
            if sorted(r.get("rebuiltShardIds", [])) != \
                    sorted(victim_sids):
                raise RuntimeError(
                    f"{mode} rebuilt {r.get('rebuiltShardIds')}, "
                    f"wanted {victim_sids}")
            if mode != "warmup":
                rounds[mode].append(dt)
            if mode == "stream" and r.get("telemetry"):
                tele = r["telemetry"]
                out["dist_rebuild_slice_p95_ms"] = tele["sliceP95Ms"]
                out["dist_rebuild_bytes_fetched"] = \
                    tele["bytesFetchedTotal"]
            # reset: drop the rebuilt (unmounted) shard files — and,
            # after a copy round, the staged survivor copies — so every
            # round repairs the identical loss from the identical state
            cleanup = list(victim_sids)
            if mode == "copy":
                cleanup += [s for s in have
                            if s not in locs.get(rebuilder, [])]
            http_json("POST", f"{rebuilder}/admin/ec/delete_shards",
                      {"volumeId": vid, "shardIds": cleanup})
            # settle dirty pages (a copy round leaves ~0.7x the volume
            # in writeback) so one round's flush never bleeds into the
            # next round's timed window
            try:
                os.sync()
            except OSError:  # pragma: no cover
                pass
            _time.sleep(0.4)
        import statistics
        med = {m: statistics.median(ts) for m, ts in rounds.items()}
        out["dist_rebuild_pipelined_gbps"] = \
            round(volume_bytes / med["stream"] / 1e9, 3)
        out["dist_rebuild_copy_then_rebuild_gbps"] = \
            round(volume_bytes / med["copy"] / 1e9, 3)
        out["dist_rebuild_rounds_per_mode"] = len(rounds["stream"])
        out["dist_rebuild_speedup"] = round(
            med["copy"] / max(med["stream"], 1e-9), 2)
        return out
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _measure_dist_encode(nodes: int = 3, blob_mb: int = 1,
                         n_blobs: int = 96,
                         budget_s: "float | None" = None) -> dict:
    """Distributed encode A/B over a loopback PROC-cluster: the seed's
    encode-locally-then-balance (`ec.encode -mode=local`: all 14 shard
    files written on the source node, mounted, then balance-moved off
    it one at a time) vs scatter-encode (`-mode=scatter`: placement
    planned first, shard windows streamed off the GF pipeline straight
    to their destinations over concurrent chunked
    `/admin/ec/shard_write` streams — remote shards never touch the
    source disk and no balance round follows).  Equal durability is
    asserted every round (all 14 shards mounted at final destinations)
    and the first scatter round is byte-verified against a local seed
    encode of the same volume.  Rounds are interleaved, MEDIAN of 4
    per mode (same jitter rationale as dist_rebuild); between rounds
    `ec.decode` restores the normal volume so every round encodes the
    identical bytes.  Volume-bytes accounting (the .dat size) like
    every other number this bench emits."""
    import shutil
    import tempfile
    import time as _time

    from seaweedfs_tpu import operation
    from seaweedfs_tpu.server.httpd import http_bytes, http_json
    from seaweedfs_tpu.shell import CommandEnv, run_command
    from seaweedfs_tpu.storage.erasure_coding import ec_encoder
    from seaweedfs_tpu.storage.erasure_coding.ec_context import (
        ECContext, to_ext)

    tmp = tempfile.mkdtemp(prefix="bench_encode_")
    procs = []
    try:
        mport = _free_port()
        mdir = os.path.join(tmp, "master-meta")
        os.makedirs(mdir)
        procs.append(_spawn_role(
            ["master", "-port", str(mport), "-mdir", mdir,
             "-volumeSizeLimitMB", "1024"], mport,
            os.path.join(tmp, "master.log")))
        master_url = f"127.0.0.1:{mport}"
        for i in range(nodes):
            d = os.path.join(tmp, f"v{i}")
            os.makedirs(d)
            vport = _free_port()
            procs.append(_spawn_role(
                ["volume", "-port", str(vport), "-dir", d,
                 "-mserver", master_url, "-max", "16"], vport,
                os.path.join(tmp, f"vol{i}.log")))
        deadline = _time.time() + 30
        while _time.time() < deadline:
            try:
                if len(http_json("GET",
                                 f"{master_url}/cluster/status"
                                 )["dataNodes"]) == nodes:
                    break
            except OSError:
                pass
            _time.sleep(0.1)
        rng = np.random.default_rng(29)
        blob = rng.integers(0, 256, blob_mb << 20,
                            dtype=np.uint8).tobytes()
        fids = [operation.submit(master_url, blob)
                for _ in range(n_blobs)]
        vid = int(fids[0].split(",")[0])
        env = CommandEnv(master_url)
        env.lock()

        def pull(url, ext):
            status, body, _ = http_bytes(
                "GET", f"{url}/admin/volume_file?volumeId={vid}"
                f"&collection=&ext={ext}", timeout=120)
            if status != 200:
                raise RuntimeError(f"pull {ext} from {url}: {status}")
            return body

        def shard_map():
            r = http_json("GET",
                          f"{master_url}/dir/ec_lookup?volumeId={vid}")
            return {l["url"]: l["shardIds"]
                    for l in r.get("shardIdLocations", [])}

        # golden seed encode of the exact volume bytes, for the
        # byte-identity assertion on the first scatter round
        source = env.volume_locations(vid)[0]["url"]
        http_json("POST", f"{source}/admin/set_readonly",
                  {"volumeId": vid, "readOnly": True})
        gbase = os.path.join(tmp, f"golden_{vid}")
        for ext in (".dat", ".idx"):
            with open(gbase + ext, "wb") as f:
                f.write(pull(source, ext))
        http_json("POST", f"{source}/admin/set_readonly",
                  {"volumeId": vid, "readOnly": False})
        volume_bytes = os.path.getsize(gbase + ".dat")
        gctx = ECContext(backend="cpu")
        ec_encoder.write_sorted_file_from_idx(gbase)
        ec_encoder.write_ec_files(gbase, gctx)

        from seaweedfs_tpu.shell import commands as shell_commands

        def _seed_move_shard(env2, vid2, collection, sid, source,
                             dest) -> None:
            """The SEED's `_move_shard` verbatim (pre-relay,
            command_ec_common.go:336): the destination pulls the shard
            + sidecars WHOLE via `/admin/ec/copy` staging downloads,
            mounts, then the source drops its copy — the
            download-then-upload shape the scatter path removes."""
            http_json("POST", f"{dest}/admin/ec/copy", {
                "volumeId": vid2, "collection": collection,
                "shardIds": [sid], "sourceDataNode": source,
                "copyEcxFile": True, "copyEcjFile": True,
                "copyVifFile": True}, timeout=600.0)
            http_json("POST", f"{dest}/admin/ec/mount",
                      {"volumeId": vid2, "collection": collection,
                       "shardIds": [sid]})
            http_json("POST", f"{source}/admin/ec/delete_shards",
                      {"volumeId": vid2, "collection": collection,
                       "shardIds": [sid]})

        def encode_scatter() -> None:
            """One scatter round: the shipped `ec.encode -mode=scatter`
            shell flow end to end."""
            run_command(env, f"ec.encode -volumeId={vid} -mode=scatter")

        def encode_seed() -> None:
            """One SEED round: the shipped `-mode=local` flow
            (generate on the source, mount, the full balance pass)
            with the shell's shard move restored to the seed's
            whole-file `/admin/ec/copy` implementation — i.e. the
            exact encode+balance path the seed ran, reproduced the
            same way dist_rebuild reproduces its copy-then-rebuild
            baseline."""
            orig = shell_commands._move_shard
            shell_commands._move_shard = _seed_move_shard
            try:
                run_command(env,
                            f"ec.encode -volumeId={vid} -mode=local")
            finally:
                shell_commands._move_shard = orig

        out = {"dist_encode_nodes": nodes,
               "dist_encode_volume_bytes": volume_bytes}
        rounds: dict = {"scatter": [], "seed": []}
        arms = {"scatter": encode_scatter, "seed": encode_seed}
        verified = False
        # BOTH arms get an untimed warmup: each path pays one-off
        # per-server costs on first contact (imports, first
        # receive/copy on every destination) that belong to neither
        # timed round
        t_rounds0 = _time.monotonic()
        for mode in ("warmup-scatter", "warmup-seed",
                     "scatter", "seed", "scatter", "seed",
                     "scatter", "seed", "scatter", "seed"):
            if budget_s is not None and rounds["scatter"] and \
                    len(rounds["scatter"]) == len(rounds["seed"]):
                # the warmups + finished pairs ARE the calibration:
                # stop adding rounds once the next pair would not fit
                # the budget (median of fewer rounds over a dead arm)
                done = _time.monotonic() - t_rounds0
                per_pair = done / (1 + len(rounds["scatter"]))
                if done + per_pair > budget_s:
                    break
            warm = mode.startswith("warmup")
            m = mode.split("-")[-1] if warm else mode
            t0 = time.perf_counter()
            arms[m]()
            dt = time.perf_counter() - t0
            # equal durability: every round must end with all 14
            # shards mounted at their final destinations
            locs = shard_map()
            placed = sorted(s for sids in locs.values() for s in sids)
            if placed != list(range(14)):
                raise RuntimeError(
                    f"{mode}: only shards {placed} mounted")
            if not warm:
                rounds[m].append(dt)
            if m == "scatter" and not verified:
                for url, sids in locs.items():
                    for sid in sids:
                        with open(gbase + to_ext(sid), "rb") as gf:
                            if pull(url, to_ext(sid)) != gf.read():
                                raise RuntimeError(
                                    f"scatter shard {sid} differs "
                                    f"from seed encode")
                verified = True
                out["dist_encode_byte_identity"] = True
            # reset: decode back to a normal volume so the next round
            # encodes the identical bytes from a clean state
            run_command(env, f"ec.decode -volumeId={vid}")
            try:
                os.sync()
            except OSError:  # pragma: no cover
                pass
            _time.sleep(0.8)  # let v9fs writeback drain so one
            # round's dirty pages never bleed into the next's window
        import statistics
        med = {m: statistics.median(ts) for m, ts in rounds.items()}
        out["dist_encode_scatter_gbps"] = \
            round(volume_bytes / med["scatter"] / 1e9, 3)
        out["dist_encode_seed_balance_gbps"] = \
            round(volume_bytes / med["seed"] / 1e9, 3)
        out["dist_encode_rounds_per_mode"] = len(rounds["scatter"])
        out["dist_encode_speedup"] = round(
            med["seed"] / max(med["scatter"], 1e-9), 2)
        return out
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _measure_soak(duration_s: float = 20.0,
                  noisy_rps: float = 5.0) -> dict:
    """QoS-off vs QoS-on soak A/B (the ISSUE 6 acceptance scenario):
    a paced foreground tenant + an unbounded noisy tenant + looping
    EC encode/rebuild churn against an in-process cluster, one arm
    with the QoS plane inert (the interference baseline) and one with
    the noisy tenant token-bucketed and the EC feedback throttle
    armed.  Records p50/p99 + achieved rate per tenant per arm, so
    the QoS delta is a number, not a claim.  QoS-off runs FIRST: the
    off arm must not inherit a drained bucket or a residual pace."""
    import shutil
    import tempfile
    from pathlib import Path

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    from soak import EcChurn, SoakCluster, TenantTraffic, arm_qos

    from seaweedfs_tpu import qos

    def one_arm(with_qos: bool) -> dict:
        qos.reset()
        tmp = Path(tempfile.mkdtemp(prefix="bench_soak_"))
        sc = SoakCluster(tmp, volumes=3)
        try:
            vols = sc.prepare_ec_volumes(rounds=2)
            if with_qos:
                arm_qos(sc.filer_url,
                        {"tenant": "noisy", "rps": noisy_rps,
                         "burst": noisy_rps})
                arm_qos(sc.filer_url, {"sloP99Ms": 250.0,
                                       "paceMinMs": 25,
                                       "paceMaxMs": 1000})
            fg = TenantTraffic(sc.filer_url, "fg", payload=1500,
                               target_rps=12, seed=41).start()
            noisy = TenantTraffic(sc.filer_url, "noisy",
                                  payload=1500, target_rps=None,
                                  seed=42).start()
            churn = EcChurn(sc.master_url, vols, loop=True).start()
            time.sleep(duration_s)
            churn.stop()
            noisy.stop()
            fg.stop()
            # invariants hold in BOTH arms: identity is not something
            # QoS may trade away
            fg.verify_all()
            churn.verify_blobs()
            return {"fg": fg.stats.summary(),
                    "noisy": noisy.stats.summary(),
                    "ecRounds": churn.rounds_done,
                    "ecErrors": churn.errors[:3]}
        finally:
            sc.stop()
            qos.reset()
            shutil.rmtree(tmp, ignore_errors=True)

    off = one_arm(False)
    on = one_arm(True)
    return {
        "soak_seconds_per_arm": duration_s,
        "noisy_rps_limit": noisy_rps,
        "qos_off": off,
        "qos_on": on,
        "fg_p99_delta_ms": round(
            off["fg"]["p99Ms"] - on["fg"]["p99Ms"], 2),
        "noisy_ok_per_sec_off": off["noisy"]["okPerSec"],
        "noisy_ok_per_sec_on": on["noisy"]["okPerSec"],
    }


def _measure_slo_soak(duration_s: float = 30.0,
                      budget_s: float = 0.5) -> dict:
    """SLO-autopilot soak (ISSUE 20 acceptance): a diurnal load swing
    plus a slow-replica window against an in-process cluster, with a
    REAL autopilot (seaweedfs_tpu/autopilot.py) closing the loop over
    the hedge/brownout knobs while deadline-carrying reads measure
    the SLO.  Four phases — night (paced trickle), morning ramp
    (concurrent tight loops), a slow-replica window (one replica's
    Python read path wedged by an armed delay while the hedge plane
    absorbs it), evening (paced) — with a paced filer write tenant
    riding the whole run for byte-identity.  Acceptance is a VERDICT,
    not a number: p99 of every deadline read within the budget, blown
    + shed fractions bounded, zero corruption, and the controller's
    actions on the record."""
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    import chaos as _chaos
    from soak import SoakCluster, TenantTraffic, percentile

    from seaweedfs_tpu import faults, operation, qos, stats
    from seaweedfs_tpu.util import deadline, hedge

    qos.reset()
    hedge.reset()
    faults.reset()
    tmp = Path(tempfile.mkdtemp(prefix="bench_slo_"))
    sc = SoakCluster(tmp, volumes=3)
    # the controller under test is the filer's OWN loop (built by
    # FilerServer via autopilot.build_for_filer): hedge/brownout are
    # module-global in this in-process rig, so a second bench-side
    # controller would be exactly the dual-driver shape SWFS021
    # outlaws — observe the real one instead of competing with it
    ap = sc.filer.autopilot
    assert ap is not None and ap.enabled, \
        "slo_soak needs the filer autopilot armed " \
        "(SEAWEEDFS_TPU_AUTOPILOT)"
    # pin plane discovery to "no planes": the armed volume.read.serve
    # delay lives on the Python port, and the wedged-replica phase
    # must actually wedge the replica it targets
    with operation._uds_lock:
        for u in sc.cluster.all_urls:
            operation._uds_probe[u] = {}
    try:
        blobs = {}
        for _ in range(8):
            data = os.urandom(4096)
            fid = operation.submit(sc.master_url, data,
                                   replication="001")
            blobs[fid] = data
        for _ in range(4):          # warm the hedge tracker
            for f in blobs:
                assert operation.read(sc.master_url, f) == blobs[f]
        fid0 = next(iter(blobs))
        locs = operation.lookup(sc.master_url,
                                int(fid0.split(",")[0]))
        delayed = locs[0]["url"] if len(locs) >= 2 else None
        targets = [f for f in blobs if delayed and (
            lambda ls: len(ls) >= 2 and ls[0]["url"] == delayed)(
            operation.lookup(sc.master_url, int(f.split(",")[0])))]

        phases: "dict[str, dict]" = {}
        mismatches = 0

        def run_phase(name: str, seconds: float, threads: int,
                      pace_s: float, fids: "list[str]") -> None:
            nonlocal mismatches
            lat: "list[float]" = []
            blown = [0]
            lock = threading.Lock()
            stop_at = time.monotonic() + seconds

            def loop(seed: int) -> None:
                nonlocal mismatches
                i = seed
                while time.monotonic() < stop_at:
                    f = fids[i % len(fids)]
                    i += 1
                    t0 = time.monotonic()
                    try:
                        with deadline.scope(budget_s):
                            got = operation.read(sc.master_url, f)
                        if got != blobs[f]:
                            mismatches += 1
                        with lock:
                            lat.append(time.monotonic() - t0)
                    except deadline.DeadlineExceeded:
                        with lock:
                            blown[0] += 1
                    except (OSError, RuntimeError):
                        with lock:
                            blown[0] += 1
                    if pace_s:
                        time.sleep(pace_s)

            ts = [threading.Thread(target=loop, args=(k,),
                                   daemon=True)
                  for k in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=seconds + 30)
            phases[name] = {
                "reads": len(lat), "blown": blown[0],
                "p50_ms": round(percentile(lat, 0.50) * 1e3, 2),
                "p99_ms": round(percentile(lat, 0.99) * 1e3, 2),
            } if lat else {"reads": 0, "blown": blown[0]}

        shed0 = _chaos.metric_sum(
            stats.PROCESS.render(),
            "seaweedfs_tpu_qos_rejected_total", reason="brownout")
        writer = TenantTraffic(sc.filer_url, "slo", payload=2048,
                               target_rps=10, seed=91).start()
        u = duration_s / 6.0
        run_phase("night", u, threads=1, pace_s=0.05,
                  fids=list(blobs))
        run_phase("morning", 2 * u, threads=3, pace_s=0.0,
                  fids=list(blobs))
        if targets:
            _chaos.arm(delayed, "volume.read.serve=delay,ms=300,"
                                f"match={delayed}")
        run_phase("slow_replica", 2 * u, threads=2, pace_s=0.0,
                  fids=targets or list(blobs))
        faults.reset()
        run_phase("evening", u, threads=1, pace_s=0.05,
                  fids=list(blobs))
        writer.stop()
        writer.verify_all()

        all_lat_ms = [phases[p]["p99_ms"] for p in phases
                      if "p99_ms" in phases[p]]
        total_reads = sum(p["reads"] for p in phases.values())
        total_blown = sum(p["blown"] for p in phases.values())
        shed = _chaos.metric_sum(
            stats.PROCESS.render(),
            "seaweedfs_tpu_qos_rejected_total",
            reason="brownout") - shed0
        snap = ap.snapshot()
        blown_frac = total_blown / max(total_reads + total_blown, 1)
        shed_frac = shed / max(total_reads + total_blown, 1)
        slo_held = bool(
            all_lat_ms and
            max(all_lat_ms) <= budget_s * 1e3 and
            blown_frac <= 0.01 and shed_frac <= 0.05 and
            mismatches == 0 and not writer.stats.errors)
        return {
            "scenario": "slo_autopilot_soak",
            "budget_ms": budget_s * 1e3,
            "duration_s": duration_s,
            "phases": phases,
            "reads_total": total_reads,
            "blown_total": total_blown,
            "blown_frac": round(blown_frac, 5),
            "shed_total": shed,
            "shed_frac": round(shed_frac, 5),
            "mismatches": mismatches,
            "write_tenant": writer.stats.summary(),
            "autopilot": {
                "ticks": snap["ticks"],
                "knobs": {k: v["value"]
                          for k, v in snap["knobs"].items()},
                "actions": len(snap["actions"]),
                "last_actions": snap["actions"][-5:],
            },
            "slo_held": slo_held,
        }
    finally:
        with operation._uds_lock:
            for u in sc.cluster.all_urls:
                operation._uds_probe.pop(u, None)
        sc.stop()
        faults.reset()
        hedge.reset()
        qos.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def _measure_read_path(duration_s: float = 8.0, files: int = 48,
                       tenants: int = 3) -> dict:
    """Read-path cache tier A/B + degraded arm (ISSUE 11 acceptance).

    Zipfian multi-tenant READ load over one corpus through a fresh
    in-process SoakCluster per arm:

      cold      caches disabled (READ_CACHE_MB=0, FILER_META_CACHE=0)
                — the pre-PR serving path
      warm      caches on, corpus pre-warmed — zipfian steady state

    Headlines: warm cache-hit ratio (>= 0.8 acceptance), warm/cold
    throughput ratio (>= 2x acceptance), and a DEGRADED arm: an
    RS(4,2) volume with data shard 0 deleted, every read
    reconstructing through the GF kernel — byte identity asserted,
    decode p99 + promoted (second-pass, cache-hit) p99 recorded, and
    zero full rebuilds in the request path verified from /metrics."""
    import hashlib
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests"))
    import chaos as chaos_mod
    from soak import OpStats, SoakCluster, percentile

    from seaweedfs_tpu import operation, qos
    from seaweedfs_tpu import stats as swstats
    from seaweedfs_tpu.server.httpd import http_bytes, http_json

    rng = np.random.default_rng(11)
    sizes = [int(rng.integers(4 << 10, 160 << 10))
             for _ in range(files)]
    ranks = np.arange(1, files + 1, dtype=np.float64)
    weights = 1.0 / ranks ** 1.2          # zipf-ish popularity
    weights /= weights.sum()

    _KNOBS = ("SEAWEEDFS_TPU_READ_CACHE_MB",
              "SEAWEEDFS_TPU_FILER_META_CACHE")

    def _cache_counters() -> "tuple[float, float]":
        text = swstats.render_process()
        return (chaos_mod.metric_sum(
                    text, "seaweedfs_tpu_read_cache_hits_total"),
                chaos_mod.metric_sum(
                    text, "seaweedfs_tpu_read_cache_misses_total"))

    def one_arm(label: str, env: "dict[str, str]",
                warm: bool, attr_toggle_windows: int = 0) -> dict:
        saved = {k: os.environ.get(k)
                 for k in set(_KNOBS) | set(env)}
        for k in _KNOBS:
            os.environ.pop(k, None)
        os.environ.update(env)
        qos.reset()
        tmp = Path(tempfile.mkdtemp(prefix=f"bench_rp_{label}_"))
        sc = SoakCluster(tmp, volumes=3)
        try:
            corpus = []
            for i, size in enumerate(sizes):
                body = rng.integers(0, 256, size,
                                    dtype=np.uint8).tobytes()
                path = f"/rp/t{i % tenants}/f{i:03d}.bin"
                st, _, _ = http_bytes(
                    "POST", f"{sc.filer_url}{path}", body, timeout=60)
                assert st == 201, (path, st)
                corpus.append((path, hashlib.sha256(body).digest(),
                               size))
            if warm:
                for path, digest, _sz in corpus:
                    st, body, _ = http_bytes(
                        "GET", f"{sc.filer_url}{path}", timeout=60)
                    assert st == 200 and \
                        hashlib.sha256(body).digest() == digest
            h0, m0 = _cache_counters()
            # per-request cpu/wall from the front's request(_cpu)
            # histograms (ISSUE 15): delta over the traffic window
            from seaweedfs_tpu import profiling as _prof

            def _req_hists() -> "tuple[dict | None, dict | None]":
                try:
                    _st, body, _ = http_bytes(
                        "GET", f"{sc.filer_url}/metrics", timeout=10)
                except OSError:
                    return None, None
                parsed = _prof.parse_prom_text(
                    body.decode("utf-8", "replace"))
                return (_prof.prom_histogram(
                            parsed, "filer_request_seconds"),
                        _prof.prom_histogram(
                            parsed, "filer_request_cpu_seconds"))

            w0, c0 = _req_hists()
            per_tenant = [OpStats() for _ in range(tenants)]
            stop = threading.Event()

            def reader(t: int) -> None:
                r = np.random.default_rng(100 + t)
                st_t = per_tenant[t]
                hdrs = {"X-Tenant": f"tenant{t}"}
                while not stop.is_set():
                    i = int(r.choice(files, p=weights))
                    path, digest, _sz = corpus[i]
                    t0 = time.perf_counter()
                    try:
                        code, body, _ = http_bytes(
                            "GET", f"{sc.filer_url}{path}", None,
                            hdrs, timeout=30)
                    except OSError as e:
                        st_t.record_err(repr(e))
                        continue
                    dt = time.perf_counter() - t0
                    if code == 200 and \
                            hashlib.sha256(body).digest() == digest:
                        st_t.record_ok(dt)
                    else:
                        st_t.record_err(f"{path} -> {code}")

            threads = [threading.Thread(target=reader, args=(t,))
                       for t in range(tenants)]
            for th in threads:
                th.start()
            toggle_windows: "list[dict]" = []
            if attr_toggle_windows:
                # ISSUE 15 within-cluster A/B: alternate disarmed /
                # armed traffic windows on THIS warmed cluster (the
                # in-process rig toggles profiling directly — same
                # lever POST /debug/attribution pulls on a real
                # node); separate clusters cannot resolve a ~1% cost
                # under arm-to-arm boot noise
                from seaweedfs_tpu import profiling as _p
                win_s = max(1.5, duration_s / attr_toggle_windows)
                time.sleep(win_s / 2)        # settle, uncounted
                for w in range(attr_toggle_windows):
                    # scope=plane: only the ISSUE 15 additions (cpu
                    # clocks + recorder) toggle; the PR 7 wall-stage
                    # decomposition stays armed on BOTH sides — it
                    # predates the plane and every shipped number
                    # already paid for it
                    _p.set_attribution_disarmed(w % 2 == 0,
                                                scope="plane")
                    n0 = sum(len(s.lat_ok) for s in per_tenant)
                    time.sleep(win_s)
                    n1 = sum(len(s.lat_ok) for s in per_tenant)
                    toggle_windows.append(
                        {"disarmed": w % 2 == 0,
                         "okPerSec": round((n1 - n0) / win_s, 1)})
                _p.set_attribution_disarmed(False)
            else:
                time.sleep(duration_s)
            stop.set()
            for th in threads:
                th.join(timeout=30)
            h1, m1 = _cache_counters()
            hits, misses = h1 - h0, m1 - m0
            w1, c1 = _req_hists()
            lat = sorted(x for s in per_tenant for x in s.lat_ok)
            total_ok = len(lat)
            rec = {
                "okPerSec": round(total_ok / duration_s, 1),
                "p50Ms": round(percentile(lat, 0.5) * 1e3, 2),
                "p99Ms": round(percentile(lat, 0.99) * 1e3, 2),
                "errors": sum(len(s.errors) for s in per_tenant),
                "cacheHitRatio": round(hits / (hits + misses), 3)
                if hits + misses > 0 else 0.0,
                "perTenant": [s.summary() for s in per_tenant],
            }
            wd = _prof.histogram_delta(w1, w0)
            cd = _prof.histogram_delta(c1, c0)
            if wd and wd.get("count") and cd and cd.get("count"):
                cpu_ms = cd["sum"] / cd["count"] * 1e3
                wall_ms = wd["sum"] / wd["count"] * 1e3
                rec["cpuMsPerRequest"] = round(cpu_ms, 4)
                rec["waitMsPerRequest"] = round(
                    max(wall_ms - cpu_ms, 0.0), 4)
            if toggle_windows:
                on = [w["okPerSec"] for w in toggle_windows
                      if not w["disarmed"]]
                off = [w["okPerSec"] for w in toggle_windows
                       if w["disarmed"]]
                on_r = sum(on) / max(len(on), 1)
                off_r = sum(off) / max(len(off), 1)
                rec["attrToggle"] = {
                    "windows": toggle_windows,
                    "armedOkPerSec": round(on_r, 1),
                    "disarmedOkPerSec": round(off_r, 1),
                    "overheadFrac": round(
                        1.0 - on_r / max(off_r, 1e-9), 4),
                }
            return rec
        finally:
            sc.stop()
            qos.reset()
            shutil.rmtree(tmp, ignore_errors=True)
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def degraded_arm(seconds: float) -> dict:
        tmp = Path(tempfile.mkdtemp(prefix="bench_rp_degraded_"))
        c = chaos_mod.Cluster(tmp, volumes=3)
        try:
            from seaweedfs_tpu.shell import CommandEnv, run_command
            drng = np.random.default_rng(31)
            blobs: dict = {}
            for _ in range(12):
                data = drng.integers(
                    0, 256, int(drng.integers(8 << 10, 48 << 10)),
                    dtype=np.uint8).tobytes()
                blobs[operation.submit(c.master_url, data,
                                       collection="bench_rp")] = data
            vids = {int(f.split(",")[0]) for f in blobs}
            assert len(vids) == 1, vids
            vid = vids.pop()
            env2 = CommandEnv(c.master_url)
            run_command(env2, "lock")
            try:
                out = run_command(
                    env2, f"ec.encode -volumeId={vid} "
                          f"-collection=bench_rp "
                          f"-dataShards=4 -parityShards=2")
            finally:
                run_command(env2, "unlock")
            assert "error" not in out.lower(), out
            holder = next(u for u, sids in c.shard_map(vid).items()
                          if 0 in sids)
            r = http_json("POST",
                          f"{holder}/admin/ec/delete_shards",
                          {"volumeId": vid, "collection": "bench_rp",
                           "shardIds": [0]}, timeout=30)
            assert "error" not in r, r

            def rebuilds() -> float:
                return sum(chaos_mod.metric_sum(
                    chaos_mod.metrics_text(u),
                    "volume_server_ec_rebuilds_total")
                    for u in c.all_urls[1:])

            r0 = rebuilds()
            d0 = chaos_mod.metric_sum(
                swstats.render_process(),
                "seaweedfs_tpu_ec_degraded_reads_total")
            items = list(blobs.items())
            zw = 1.0 / np.arange(1, len(items) + 1) ** 1.2
            zw /= zw.sum()
            decode_lat: list = []
            rr = np.random.default_rng(32)
            deadline = time.monotonic() + seconds
            # first pass: every distinct needle decodes once, then the
            # zipfian tail keeps decoding whatever the LRU hasn't kept
            while time.monotonic() < deadline or not decode_lat:
                fid, payload = items[int(rr.choice(len(items), p=zw))]
                t0 = time.perf_counter()
                got = operation.read(c.master_url, fid)
                decode_lat.append(time.perf_counter() - t0)
                assert got == payload, f"degraded read {fid} corrupt"
            degraded_seen = chaos_mod.metric_sum(
                swstats.render_process(),
                "seaweedfs_tpu_ec_degraded_reads_total") - d0
            # second pass: the decoded needles were PROMOTED — the
            # hot tail now serves from memory
            warm_lat: list = []
            for fid, payload in items:
                t0 = time.perf_counter()
                assert operation.read(c.master_url, fid) == payload
                warm_lat.append(time.perf_counter() - t0)
            return {
                "reads": len(decode_lat),
                "degradedReads": degraded_seen,
                "byteIdentical": True,
                "decodeP50Ms": round(
                    percentile(decode_lat, 0.5) * 1e3, 2),
                "decodeP99Ms": round(
                    percentile(decode_lat, 0.99) * 1e3, 2),
                "promotedP99Ms": round(
                    percentile(warm_lat, 0.99) * 1e3, 2),
                "fullRebuildsInRequestPath": rebuilds() - r0,
            }
        finally:
            c.stop()
            shutil.rmtree(tmp, ignore_errors=True)

    cold = one_arm("cold", {"SEAWEEDFS_TPU_READ_CACHE_MB": "0",
                            "SEAWEEDFS_TPU_FILER_META_CACHE": "0"},
                   warm=False)
    warm = one_arm("warm", {}, warm=True)
    # ISSUE 15: the warm arm's attribution-off twin — same caches,
    # stage timers/flight recorder/scheduler probe disarmed — as the
    # cross-cluster context figure, plus the ACCEPTANCE figure from a
    # within-cluster A/B: one warmed cluster alternating disarmed /
    # armed traffic windows (separate clusters cannot resolve a ~1%
    # cost under arm-to-arm boot noise)
    warm_attr_off = one_arm("warm_attr_off",
                            dict(_ATTRIBUTION_OFF_ENV), warm=True)
    warm_toggle = one_arm("warm_toggle", {}, warm=True,
                          attr_toggle_windows=6)
    toggle = warm_toggle.get("attrToggle", {})
    # ISSUE 12: the warm arm re-run with the filer gateway on the
    # asyncio front — same caches, different concurrency substrate
    warm_async = one_arm(
        "warm_async", {"SEAWEEDFS_TPU_ASYNC_FRONT": "1"}, warm=True)
    degraded = degraded_arm(min(duration_s, 5.0))
    ratio = warm["okPerSec"] / max(cold["okPerSec"], 1e-9)
    return {
        "scenario": "read_path_cache_ab",
        "metric": "read_path_warm_over_cold_throughput",
        "value": round(ratio, 2),
        "unit": "x",
        "duration_s_per_arm": duration_s,
        "files": files,
        "tenants": tenants,
        "cold": cold,
        "warm": warm,
        "warm_attr_off": warm_attr_off,
        "attribution_overhead": {
            "cross_cluster_pair": {
                "on_ok_per_sec": warm["okPerSec"],
                "off_ok_per_sec": warm_attr_off["okPerSec"],
            },
            "toggle_windows": toggle.get("windows", []),
            "armed_ok_per_sec": toggle.get("armedOkPerSec", 0.0),
            "disarmed_ok_per_sec":
                toggle.get("disarmedOkPerSec", 0.0),
            "overhead_frac": toggle.get("overheadFrac", 0.0),
        },
        "accept_attribution_2pct":
            toggle.get("overheadFrac", 0.0) <= 0.02,
        "warm_async": warm_async,
        "asyncFrontSpeedup": round(
            warm_async["okPerSec"] / max(warm["okPerSec"], 1e-9), 2),
        "degraded": degraded,
        "warmCacheHitRatio": warm["cacheHitRatio"],
        "accept_hit_ratio_ge_0_8":
            warm["cacheHitRatio"] >= 0.8,
        "accept_warm_2x_cold": ratio >= 2.0,
        "accept_no_rebuild_in_request_path":
            degraded["fullRebuildsInRequestPath"] == 0,
    }


_LEAN_READER = r"""
import hashlib, http.client, json, os, sys, threading, time
cfg = json.load(sys.stdin)
filers, nthreads = cfg["filers"], cfg["threads"]
seconds, start_at = cfg["seconds"], cfg["startAt"]
rid0 = cfg["rid0"]
paths = cfg["paths"]
sha = cfg["sha"]
plane_route = cfg.get("planeRoute", False)
lat = [[] for _ in range(nthreads)]
errors = [0]
plane_acked = [0]
plane_fb = [0]
mismatches = [0]

def plane_conn(target):
    # one /status probe per thread: the filer advertises its armed
    # native READ plane's port (0 / absent when disarmed).  Under
    # pre-fork workers each probe lands on a random sibling, which
    # conveniently spreads threads across the sibling planes.
    try:
        c = http.client.HTTPConnection(target, timeout=5)
        c.request("GET", "/status")
        r = c.getresponse()
        doc = json.loads(r.read())
        c.close()
        port = int(doc.get("readPlanePort") or 0)
        if not port:
            return None
        host = target.rsplit(":", 1)[0]
        return [host + ":" + str(port),
                http.client.HTTPConnection(
                    host + ":" + str(port), timeout=30)]
    except (OSError, ValueError, http.client.HTTPException):
        return None

def check(path, body):
    if hashlib.sha256(body).hexdigest() != sha[path]:
        mismatches[0] += 1
        return False
    return True

def reader(t):
    rid = rid0 + t
    target = filers[rid % len(filers)]
    conn = http.client.HTTPConnection(target, timeout=30)
    pc = plane_conn(target) if plane_route else None
    i = rid * 7919          # decorrelate thread scan starts
    while time.time() < start_at:
        time.sleep(0.01)
    deadline = time.time() + seconds
    while time.time() < deadline:
        path = paths[i % len(paths)]
        i += 1
        t0 = time.perf_counter()
        if pc is not None:
            # plane first; a 404 is the plane's documented "not
            # eligible / not warm / disarmed" answer -> replay on the
            # Python front within the same latency sample (the
            # client-side cost of a fallback is part of the honest
            # number, and the replay is what re-warms the map)
            try:
                pc[1].request("GET", path)
                r = pc[1].getresponse()
                body = r.read()
                if r.status == 200:
                    plane_acked[0] += 1
                    check(path, body)
                    lat[t].append(time.perf_counter() - t0)
                    continue
                plane_fb[0] += 1
            except (OSError, http.client.HTTPException):
                plane_fb[0] += 1
                pc[1].close()
                try:
                    pc[1] = http.client.HTTPConnection(pc[0],
                                                       timeout=30)
                except OSError:
                    pc = None
        try:
            conn.request("GET", path)
            r = conn.getresponse()
            body = r.read()
            if r.status >= 300:
                errors[0] += 1
            else:
                check(path, body)
                lat[t].append(time.perf_counter() - t0)
        except (OSError, http.client.HTTPException):
            errors[0] += 1
            conn.close()
            conn = http.client.HTTPConnection(target, timeout=30)
    conn.close()

ts = [threading.Thread(target=reader, args=(t,)) for t in range(nthreads)]
[t.start() for t in ts]
[t.join() for t in ts]
json.dump({"lat": [x for per in lat for x in per],
           "errors": errors[0], "planeAcked": plane_acked[0],
           "planeFallbacks": plane_fb[0],
           "mismatches": mismatches[0]}, sys.stdout)
"""


def _lean_read_load(filer_urls, readers, seconds, paths, sha,
                    threads_per_proc: int = 7,
                    plane_route: bool = False) -> dict:
    """GET twin of _lean_load: multi-process lean readers over a fixed
    warm working set, every response sha256-verified against the
    seeded bytes (the byte-identity half of the plane acceptance)."""
    import subprocess
    import time as _time

    nprocs = max(1, (readers + threads_per_proc - 1) //
                 threads_per_proc)
    start_at = _time.time() + 2.0 + 0.3 * nprocs
    procs = []
    rid = 0
    for _p in range(nprocs):
        n = min(threads_per_proc, readers - rid)
        if n <= 0:
            break
        cfg = {"filers": filer_urls, "threads": n,
               "seconds": seconds, "startAt": start_at, "rid0": rid,
               "paths": paths, "sha": sha, "planeRoute": plane_route}
        rid += n
        sp = subprocess.Popen([sys.executable, "-c", _LEAN_READER],
                              stdin=subprocess.PIPE,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL)
        sp.stdin.write(json.dumps(cfg).encode())
        sp.stdin.close()
        procs.append(sp)
    lat: list = []
    errors = plane_acked = plane_fb = mismatches = 0
    for sp in procs:
        out = sp.stdout.read()
        sp.wait(timeout=60)
        try:
            doc = json.loads(out)
        except ValueError:
            errors += 1
            continue
        lat.extend(doc["lat"])
        errors += doc["errors"]
        plane_acked += doc.get("planeAcked", 0)
        plane_fb += doc.get("planeFallbacks", 0)
        mismatches += doc.get("mismatches", 0)
    lat.sort()
    n = len(lat)
    served = max(plane_acked + plane_fb, 1)
    return {
        "readers": rid,
        "client_procs": len(procs),
        "seconds": float(seconds),
        "requests": n,
        "errors": errors,
        "mismatches": mismatches,
        "req_per_sec": round(n / seconds, 1) if seconds else 0,
        "p50_ms": round(lat[n // 2] * 1e3, 2) if n else 0,
        "p99_ms": round(
            lat[min(n - 1, int(n * 0.99))] * 1e3, 2) if n else 0,
        **({"plane_acked": plane_acked,
            "plane_fallbacks": plane_fb,
            "plane_share": round(plane_acked / served, 4)}
           if plane_route else {}),
    }


def _measure_read_path_native(seconds: float = 8.0,
                              files: int = 48,
                              payload: int = 65536,
                              readers: int = 8) -> dict:
    """ISSUE 19 acceptance: the native read funnel (C++ filer read
    plane fused with the volume read plane over persistent plane
    sockets) vs the Python front, over a loopback proc-cluster.

    Arms (each its own cluster, per-arm plane stage split scraped from
    the filer's /metrics):
      py_w1    — threaded Python front, read plane disabled (the r10
                 879 req/s warm-read shape)
      async_w1 — the asyncio front on the same shape (the ISSUE 19
                 retire-or-fix decision arm; r10: 570 req/s at 3.6 ms
                 WAIT/req vs 0.07 ms CPU/req — pure loop<->pool GIL
                 convoy, nothing to fix inside the front)
      rp_w1    — plane-routed warm reads, one worker (the headline:
                 accept >= 1,600 req/s at plane share >= 0.9 with
                 zero sha mismatches)
      rp_w4    — same with 4 pre-fork workers, each with its own
                 plane (honest 1-core caveat: siblings thrash the
                 scheduler here; on a multi-core box this is the
                 scaling curve)
    Plus nm_keepalive: the ISSUE 17 nm_on write arm re-run on this
    build, where the meta plane's upload hop now rides the shared
    keep-alive upstream pool (plane_pool.h eager flush) — accept
    stageMsPerReq.upload < 1.5 ms vs the 1.91 ms r11 baseline."""
    import hashlib
    import shutil
    import tempfile
    import time as _time

    from seaweedfs_tpu import profiling
    from seaweedfs_tpu.server.httpd import http_bytes, http_json

    partial = _Partial()

    def one_arm(name: str, env: "dict | None", workers: int,
                plane_route: bool) -> dict:
        tmp = tempfile.mkdtemp(prefix=f"bench_rpn_{name}_")
        procs = []
        try:
            mport = _free_port()
            mdir = os.path.join(tmp, "master-meta")
            os.makedirs(mdir)
            procs.append(_spawn_role(
                ["master", "-port", str(mport), "-mdir", mdir,
                 "-volumeSizeLimitMB", "1024"], mport,
                os.path.join(tmp, "master.log"), env))
            master_url = f"127.0.0.1:{mport}"
            vdir = os.path.join(tmp, "v0")
            os.makedirs(vdir)
            vport = _free_port()
            procs.append(_spawn_role(
                ["volume", "-port", str(vport), "-dir", vdir,
                 "-mserver", master_url, "-max", "16"], vport,
                os.path.join(tmp, "vol0.log"), env))
            fport = _free_port()
            procs.append(_spawn_role(
                ["filer", "-port", str(fport), "-master", master_url,
                 "-store", os.path.join(tmp, "filer.db")], fport,
                os.path.join(tmp, "filer.log"), env))
            filer_url = f"127.0.0.1:{fport}"
            deadline = _time.time() + 30
            while _time.time() < deadline:
                try:
                    if len(http_json(
                            "GET", f"{master_url}/cluster/status",
                            timeout=5)["dataNodes"]) == 1:
                        break
                except OSError:
                    pass
                _time.sleep(0.1)

            # seed the warm working set; remember every sha for the
            # readers' byte-identity check
            rng = np.random.default_rng(11)
            paths, sha = [], {}
            for i in range(files):
                blob = rng.integers(0, 256, payload,
                                    dtype=np.uint8).tobytes()
                path = f"/bench/r{i}.bin"
                st, _, _ = http_bytes(
                    "PUT", f"{filer_url}{path}", blob,
                    {"Content-Type": "application/octet-stream"},
                    timeout=30)
                if st != 201:
                    raise RuntimeError(f"seed PUT {path}: {st}")
                paths.append(path)
                sha[path] = hashlib.sha256(blob).hexdigest()
            # warm: python-front reads fill the filer chunk cache;
            # with the plane armed they also fill its entry map and
            # (through the volume's UDS on_read hook) the volume
            # plane's needle index.  A couple of rounds so every
            # pre-fork sibling map warms too.
            for _r in range(2 if workers == 1 else 2 * workers):
                for path in paths:
                    http_bytes("GET", f"{filer_url}{path}",
                               timeout=30)
            rec = _lean_read_load([filer_url], readers, seconds,
                                  paths, sha,
                                  plane_route=plane_route)
            rec["workers"] = workers
            # plane telemetry: counters + per-stage split from the C
            # side's /metrics text (multi-scrape dedupe across the
            # SO_REUSEPORT siblings, keyed on each plane's own
            # request counter + stage sums)
            plane: dict = {"requests": 0.0, "fallbacks": 0.0,
                           "stale_misses": 0.0,
                           "upstream_errors": 0.0,
                           "parse_s": 0.0, "lookup_s": 0.0,
                           "fetch_s": 0.0, "send_s": 0.0,
                           "resp_count": 0.0, "resp_sum_s": 0.0}
            seen: set = set()
            for _ in range(max(8, 3 * workers)):
                try:
                    st, body, _ = http_bytes(
                        "GET", f"{filer_url}/metrics", timeout=5)
                except OSError:
                    continue
                if st >= 300:
                    continue
                parsed = profiling.parse_prom_text(
                    body.decode("utf-8", "replace"))

                def _one(nm: str) -> float:
                    return sum(v for _l, v in parsed.get(nm, []))
                reqs = _one("filer_read_plane_native_requests_total")
                h = profiling.prom_histogram(
                    parsed,
                    "filer_read_plane_native_response_seconds", {})
                key = (reqs, round(h["sum"], 9) if h else 0.0)
                if key in seen:
                    _time.sleep(0.05)
                    continue
                seen.add(key)
                plane["requests"] += reqs
                for k in ("fallbacks", "stale_misses",
                          "upstream_errors"):
                    plane[k] += _one(
                        f"filer_read_plane_native_{k}_total")
                for stage in ("parse", "lookup", "fetch", "send"):
                    plane[stage + "_s"] += sum(
                        v for l, v in parsed.get(
                            "filer_read_plane_native"
                            "_stage_seconds_total", [])
                        if l.get("stage") == stage)
                if h:
                    plane["resp_count"] += h["count"]
                    plane["resp_sum_s"] += h["sum"]
                _time.sleep(0.05)
            if plane["requests"]:
                reqs = plane["requests"]
                plane["workers_sampled"] = len(seen)
                plane["stageMsPerReq"] = {
                    s: round(plane[s + "_s"] / reqs * 1e3, 4)
                    for s in ("parse", "lookup", "fetch", "send")}
                plane["respMeanMs"] = round(
                    plane["resp_sum_s"] / plane["resp_count"] * 1e3,
                    3) if plane["resp_count"] else 0.0
                for k in ("parse_s", "lookup_s", "fetch_s",
                          "send_s", "resp_sum_s"):
                    plane[k] = round(plane[k], 4)
                rec["plane"] = plane
            partial.phase(name, **rec)
            return rec
        finally:
            for p in procs:
                p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
            shutil.rmtree(tmp, ignore_errors=True)

    py_env = dict(_NATIVE_ON_ENV,
                  SEAWEEDFS_TPU_FILER_READ_PLANE_NATIVE="0",
                  SEAWEEDFS_TPU_FILER_WORKERS="1")
    async_env = dict(py_env, SEAWEEDFS_TPU_ASYNC_FRONT="1")
    rp_env = dict(_NATIVE_ON_ENV,
                  SEAWEEDFS_TPU_FILER_READ_PLANE_NATIVE="1",
                  SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE="1",
                  SEAWEEDFS_TPU_FILER_WORKERS="1")
    rp_w4_env = dict(rp_env, SEAWEEDFS_TPU_FILER_WORKERS="4")
    arms = {
        "py_w1": one_arm("py_w1", py_env, 1, False),
        "async_w1": one_arm("async_w1", async_env, 1, False),
        "rp_w1": one_arm("rp_w1", rp_env, 1, True),
        "rp_w4": one_arm("rp_w4", rp_w4_env, 4, True),
    }
    # ISSUE 19's meta-plane half: nm_on re-run with the upload hop on
    # the shared keep-alive upstream pool (plane_pool.h): the r11
    # measurement put 1.91 of the 2.21 ms ack in `upload` and named
    # connection reuse as the remaining lever — this records the win.
    nm_env = dict(_NATIVE_ON_ENV,
                  SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE="1",
                  SEAWEEDFS_TPU_FILER_WORKERS="1")
    nm_arm = _measure_write_path(
        nodes=2, writers=24, seconds=seconds, env_extra=nm_env,
        filers=1, lean_client=True, plane_route=True)
    nm_stage = nm_arm.get("write_path_native_meta", {}).get(
        "stageMsPerReq", {})
    partial.phase("nm_keepalive",
                  req_per_sec=nm_arm.get("write_path_req_per_sec"),
                  stageMsPerReq=nm_stage)

    rp = arms["rp_w1"]
    py = arms["py_w1"]
    out = {
        "scenario": "read_path_native_funnel",
        "metric": "read_path_plane_warm_req_per_sec",
        "value": rp["req_per_sec"],
        "unit": "req/s",
        "duration_s_per_arm": seconds,
        "files": files,
        "payload_bytes": payload,
        "readers": readers,
        "arms": arms,
        "speedup_vs_python": round(
            rp["req_per_sec"] / max(py["req_per_sec"], 0.1), 2),
        "asyncFrontSpeedup": round(
            arms["async_w1"]["req_per_sec"] /
            max(py["req_per_sec"], 0.1), 2),
        "planeShare": rp.get("plane_share", 0.0),
        "fallbackShare": round(
            1.0 - rp.get("plane_share", 0.0), 4),
        "byteIdentical": sum(
            a["mismatches"] for a in arms.values()) == 0,
        "nm_keepalive": {
            "req_per_sec": nm_arm.get("write_path_req_per_sec", 0.0),
            "stageMsPerReq": nm_stage,
            "ackMeanMs": nm_arm.get(
                "write_path_native_meta", {}).get("ackMeanMs", 0.0),
            "uploadMsBaselineR11": 1.91,
            # hop decomposition: the volume plane's own recv->respond
            # window; `upload` minus this is loopback transit plus
            # two scheduler handoffs on this 1-core box
            "volumeInternalAckMs": nm_arm.get(
                "write_path_native", {}).get("volumeInternalAckMs"),
        },
        "accept_plane_1600": rp["req_per_sec"] >= 1600.0,
        "accept_plane_share_90": rp.get("plane_share", 0.0) >= 0.9,
        "accept_byte_identical": sum(
            a["mismatches"] for a in arms.values()) == 0,
        "accept_upload_keepalive_1_5ms":
            0.0 < nm_stage.get("upload", 99.0) < 1.5,
    }
    return out


def _stage_decomposition(parsed: dict, ns: str) -> "dict | None":
    """One role's write_stage_seconds decomposition from its parsed
    /metrics (profiling.py helpers): per-stage seconds/calls/mean plus
    `coverage` — the fraction of tracked per-request wall time the
    named stages account for.  Coverage is the acceptance number: a
    decomposition that explains < 80% of the wall is naming the wrong
    stages (arXiv:1709.05365's point about host-side overheads hiding
    between the instrumented calls)."""
    from seaweedfs_tpu import profiling
    name = f"{ns}_write_stage_seconds"
    stage_names = sorted({l.get("stage", "") for l, _v in
                          parsed.get(f"{name}_count", [])} - {""})
    if not stage_names:
        return None
    cpu_name = f"{ns}_write_stage_cpu_seconds"
    out: dict = {"stages": {}}
    total_sum = 0.0
    staged_sum = 0.0
    for stage in stage_names:
        h = profiling.prom_histogram(parsed, name, {"stage": stage})
        if not h or h["count"] <= 0:
            continue
        c = profiling.prom_histogram(parsed, cpu_name,
                                     {"stage": stage})
        cpu_mean_ms = round(c["sum"] / c["count"] * 1e3, 4) \
            if c and c["count"] else None
        if stage == "total":
            total_sum = h["sum"]
            out["requests"] = h["count"]
            out["meanTotalMs"] = round(h["sum"] / h["count"] * 1e3, 3)
            if cpu_mean_ms is not None:
                # the ISSUE 15 headline: per-request CPU from the
                # stage-cpu histograms; meanTotalMs minus this is the
                # request's GIL/lock/syscall wait
                out["cpuMsPerRequest"] = cpu_mean_ms
                out["waitMsPerRequest"] = round(
                    max(out["meanTotalMs"] - cpu_mean_ms, 0.0), 3)
            continue
        staged_sum += h["sum"]
        out["stages"][stage] = {
            "seconds": round(h["sum"], 4),
            "calls": h["count"],
            "meanMs": round(h["sum"] / h["count"] * 1e3, 3),
        }
        if cpu_mean_ms is not None:
            out["stages"][stage]["cpuMeanMs"] = cpu_mean_ms
    if total_sum > 0:
        out["totalSeconds"] = round(total_sum, 4)
        for stage, rec in out["stages"].items():
            rec["shareOfWall"] = round(rec["seconds"] / total_sum, 3)
        out["coverage"] = round(staged_sum / total_sum, 3)
    return out


def _measure_write_path(nodes: int = 2, writers: int = 4,
                        seconds: float = 10.0,
                        payload: int = 4096,
                        env_extra: "dict | None" = None,
                        filers: int = 1,
                        lean_client: bool = False,
                        attr_toggle_windows: int = 0,
                        plane_route: bool = False,
                        toggle_scope: str = "plane") -> dict:
    """ROADMAP item 1's tracker: concurrent small writes through the
    filer funnel of a loopback proc-cluster, reporting req/s and
    p50/p99 AND the per-stage decomposition from every role's
    write_stage_seconds histograms — so each bench round says not just
    how far from the reference's 15,708 req/s this build is, but WHERE
    the per-request wall went (filer: recv/assign/upload/meta; volume:
    recv/lock/index/append/flush).  `env_extra` parameterizes the
    cluster's write-path knobs (the group-commit on/off A/B arms).
    Emits its record incrementally (_Partial) so a timed-out run still
    yields the phases that finished."""
    import shutil
    import tempfile
    import threading
    import time as _time

    from seaweedfs_tpu import profiling
    from seaweedfs_tpu.server.httpd import http_bytes, http_json

    partial = _Partial()
    tmp = tempfile.mkdtemp(prefix="bench_write_path_")
    procs = []
    try:
        mport = _free_port()
        mdir = os.path.join(tmp, "master-meta")
        os.makedirs(mdir)
        procs.append(_spawn_role(
            ["master", "-port", str(mport), "-mdir", mdir,
             "-volumeSizeLimitMB", "1024"], mport,
            os.path.join(tmp, "master.log"), env_extra))
        master_url = f"127.0.0.1:{mport}"
        vports = []
        for i in range(nodes):
            d = os.path.join(tmp, f"v{i}")
            os.makedirs(d)
            vport = _free_port()
            vports.append(vport)
            procs.append(_spawn_role(
                ["volume", "-port", str(vport), "-dir", d,
                 "-mserver", master_url, "-max", "16"], vport,
                os.path.join(tmp, f"vol{i}.log"), env_extra))
        fports = []
        for i in range(filers):
            fport = _free_port()
            fports.append(fport)
            procs.append(_spawn_role(
                ["filer", "-port", str(fport), "-master", master_url,
                 "-store", os.path.join(tmp, f"filer{i}.db")], fport,
                os.path.join(tmp, f"filer{i}.log"), env_extra))
        filer_urls = [f"127.0.0.1:{p}" for p in fports]
        filer_url = filer_urls[0]
        deadline = _time.time() + 30
        while _time.time() < deadline:
            try:
                if len(http_json(
                        "GET", f"{master_url}/cluster/status",
                        timeout=5)["dataNodes"]) == nodes:
                    break
            except OSError:
                pass
            _time.sleep(0.1)
        partial.phase("cluster_up", nodes=nodes, filers=filers)

        # role process groups for /proc CPU attribution: procs[0] is
        # the master, then `nodes` volume servers, then the filers
        role_pids = {
            "volume": [p.pid for p in procs[1:1 + nodes]],
            "filer": [p.pid for p in procs[1 + nodes:]],
        }

        def _cpu_sample() -> dict:
            return {role: sum(_proc_tree_cpu_s(pid) for pid in pids)
                    for role, pids in role_pids.items()}

        def _native_sample() -> dict:
            out = {"requests": 0.0, "fallbacks": 0.0,
                   "ack_sum_s": 0.0, "ack_count": 0.0}
            for p in vports:
                try:
                    st, body, _ = http_bytes(
                        "GET", f"127.0.0.1:{p}/metrics", timeout=5)
                except OSError:
                    continue
                if st >= 300:
                    continue
                parsed = profiling.parse_prom_text(
                    body.decode("utf-8", "replace"))
                for key, name in (
                        ("requests",
                         "volume_server_write_plane_requests_total"),
                        ("fallbacks",
                         "volume_server_write_plane_fallbacks_total")):
                    out[key] += sum(v for _l, v in
                                    parsed.get(name, []))
                # the volume plane's own recv->respond window: the
                # upload hop's decomposition anchor (ISSUE 19) — the
                # filer-side `upload` stage minus this is transit +
                # scheduler handoff, the part no protocol lever cuts
                h = profiling.prom_histogram(
                    parsed, "volume_server_write_plane_ack_seconds",
                    {})
                if h:
                    out["ack_sum_s"] += h["sum"]
                    out["ack_count"] += h["count"]
            return out

        pre_cpu = _cpu_sample()
        pre_native = _native_sample()

        rng = np.random.default_rng(7)
        blob = rng.integers(0, 256, payload, dtype=np.uint8).tobytes()
        latencies: "list[list[float]]" = [[] for _ in range(writers)]
        errors = [0]
        stop = threading.Event()

        def writer(w: int) -> None:
            i = 0
            lat = latencies[w]
            target = filer_urls[w % len(filer_urls)]
            while not stop.is_set():
                t0 = _time.perf_counter()
                try:
                    st, _, _ = http_bytes(
                        "POST", f"{target}/bench/w{w}/{i}", blob,
                        {"Content-Type": "application/octet-stream"},
                        timeout=30)
                    if st >= 300:
                        errors[0] += 1
                    else:
                        lat.append(_time.perf_counter() - t0)
                except OSError:
                    errors[0] += 1
                i += 1

        if lean_client and attr_toggle_windows:
            # ISSUE 15 within-cluster attribution A/B: alternate
            # disarmed/armed traffic windows on THIS cluster via the
            # runtime POST /debug/attribution lever — separate
            # clusters cannot resolve a ~1% cost under ±5-20%
            # arm-to-arm boot noise.  `seconds` is PER WINDOW here.
            all_urls = [master_url] + \
                [f"127.0.0.1:{p}" for p in vports] + filer_urls

            def _set_disarmed(v: bool) -> None:
                for u in all_urls:
                    try:
                        # scope=plane toggles only the ISSUE 15
                        # additions (the PR 7 wall-stage tracks stay
                        # armed on both sides of the A/B);
                        # scope=drain toggles the ISSUE 18 native-
                        # plane record drain instead
                        http_json("POST", f"{u}/debug/attribution",
                                  {"disarmed": v,
                                   "scope": toggle_scope},
                                  timeout=5)
                    except OSError:
                        pass

            # ONE continuous lean load across every window — per-
            # window client respawns made window-to-window rates
            # ±12% noisy, far above the ~1% signal.  Windows are cut
            # server-side instead: the filer's own request_seconds
            # POST count sampled at each boundary.
            win_s = seconds
            settle = max(3.0, win_s / 2)
            total_s = settle + attr_toggle_windows * win_s + 1.0
            load_rec: dict = {}
            loader = threading.Thread(
                target=lambda: load_rec.update(
                    _lean_load(filer_urls, writers, total_s, payload,
                               tmp, plane_route=plane_route)))
            loader.start()

            def _post_count() -> float:
                try:
                    st, body, _ = http_bytes(
                        "GET", f"{filer_url}/metrics", timeout=5)
                except OSError:
                    return -1.0
                if st >= 300:
                    return -1.0
                parsed = profiling.parse_prom_text(
                    body.decode("utf-8", "replace"))
                if plane_route:
                    # plane-served requests never cross the Python
                    # front's request_seconds; count them off the
                    # plane's own stats counter instead
                    return sum(v for _l, v in parsed.get(
                        "filer_meta_plane_native_requests_total",
                        []))
                h = profiling.prom_histogram(
                    parsed, "filer_request_seconds",
                    {"method": "POST"})
                return float(h["count"]) if h else -1.0

            _time.sleep(settle)
            windows = []
            for w in range(attr_toggle_windows):
                _set_disarmed(w % 2 == 0)
                c0 = _post_count()
                t0 = _time.perf_counter()
                _time.sleep(win_s)
                c1 = _post_count()
                dt = _time.perf_counter() - t0
                if c0 >= 0 and c1 > c0 and dt > 0:
                    windows.append(
                        {"disarmed": w % 2 == 0,
                         "req_per_sec": round((c1 - c0) / dt, 1)})
            _set_disarmed(False)
            loader.join(timeout=total_s + 120)
            rec = load_rec
            # the first on/off pair is warmup — plane procs, page
            # cache and the allocator are still heating, and that
            # ramp lands entirely on whichever side runs first; the
            # aggregate skips it (the pair stays in "windows")
            agg = windows[2:] if len(windows) >= 6 else windows
            on = [x["req_per_sec"] for x in agg
                  if not x["disarmed"]]
            off = [x["req_per_sec"] for x in agg
                   if x["disarmed"]]
            on_r = sum(on) / max(len(on), 1)
            off_r = sum(off) / max(len(off), 1)
            # medians beside the means: this box's window-to-window
            # noise (scheduler, sibling procs) occasionally collapses
            # ONE window by 2x, which swamps a few-percent signal in
            # the mean — the median pair is the robust figure
            import statistics as _st
            on_m = _st.median(on) if on else 0.0
            off_m = _st.median(off) if off else 0.0
            rec["attr_toggle"] = {
                "windows": windows,
                "warmup_windows_excluded": len(windows) - len(agg),
                "armed_req_per_sec": round(on_r, 1),
                "disarmed_req_per_sec": round(off_r, 1),
                "overhead_frac": round(
                    1.0 - on_r / max(off_r, 1e-9), 4),
                "armed_req_per_sec_med": round(on_m, 1),
                "disarmed_req_per_sec_med": round(off_m, 1),
                "overhead_frac_med": round(
                    1.0 - on_m / max(off_m, 1e-9), 4),
            }
            rec["write_path_payload_bytes"] = payload
            partial.phase("traffic", **rec)
        elif lean_client:
            # multi-PROCESS load generator: one Python process
            # driving N writer threads is itself GIL-bound — at
            # cluster scale its delayed body sends and response reads
            # show up as server-side `recv` wall and cap the
            # measurement well under the cluster's capacity (the
            # reference's `weed benchmark` client is compiled Go and
            # has no such ceiling).  Each worker process runs a lean
            # persistent-connection loop over its slice of writers.
            rec = _lean_load(filer_urls, writers, seconds, payload,
                             tmp, plane_route=plane_route)
            rec["write_path_payload_bytes"] = payload
            partial.phase("traffic", **rec)
        else:
            threads = [threading.Thread(target=writer, args=(w,),
                                        daemon=True)
                       for w in range(writers)]
            t_start = _time.perf_counter()
            for t in threads:
                t.start()
            _time.sleep(seconds)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            wall = _time.perf_counter() - t_start

            lat = sorted(x for per in latencies for x in per)
            n = len(lat)
            rec = {
                "write_path_writers": writers,
                "write_path_payload_bytes": payload,
                "write_path_seconds": round(wall, 2),
                "write_path_requests": n,
                "write_path_errors": errors[0],
                "write_path_req_per_sec":
                    round(n / wall, 1) if wall else 0,
                "write_path_p50_ms": round(
                    lat[n // 2] * 1e3, 2) if n else 0,
                "write_path_p99_ms": round(
                    lat[min(n - 1, int(n * 0.99))] * 1e3, 2) if n else 0,
            }
            partial.phase("traffic", **rec)

        rec["write_path_filers"] = filers
        rec["write_path_volume_nodes"] = nodes
        # per-role Python CPU per request (the arXiv:1709.05365
        # host-overhead number): /proc process-tree CPU delta over
        # the traffic window divided by the CLIENT-acked request
        # count — robust across the filer's pre-fork workers, and the
        # denominator is the same for both roles (every bench write
        # is one filer request and one needle write).
        post_cpu = _cpu_sample()
        post_native = _native_sample()
        n_reqs = rec.get("write_path_requests", 0)
        cpu: dict = {}
        for role in role_pids:
            delta = post_cpu[role] - pre_cpu[role]
            cpu[role] = {
                "cpuSeconds": round(delta, 3),
                "requests": int(n_reqs),
                "cpuMsPerRequest": round(delta * 1e3 / n_reqs, 3)
                if n_reqs else 0.0,
            }
        rec["write_path_cpu"] = cpu
        rec["write_path_native"] = {
            "requests": post_native["requests"] -
            pre_native["requests"],
            "fallbacks": post_native["fallbacks"] -
            pre_native["fallbacks"],
        }
        d_ack = post_native["ack_count"] - pre_native["ack_count"]
        if d_ack > 0:
            rec["write_path_native"]["volumeInternalAckMs"] = round(
                (post_native["ack_sum_s"] -
                 pre_native["ack_sum_s"]) / d_ack * 1e3, 4)
        # per-round attribution: every role's stage decomposition
        decomp: dict = {}
        for url, ns, role in (
                [(u, "filer", f"filer{i}" if filers > 1 else "filer")
                 for i, u in enumerate(filer_urls)] +
                [(f"127.0.0.1:{p}", "volume_server", f"volume{i}")
                 for i, p in enumerate(vports)]):
            try:
                st, body, _ = http_bytes("GET", f"{url}/metrics",
                                         timeout=5)
            except OSError:
                continue
            if st >= 300:
                continue
            d = _stage_decomposition(
                profiling.parse_prom_text(
                    body.decode("utf-8", "replace")), ns)
            if d:
                decomp[role] = d
        rec["write_path_decomposition"] = decomp
        coverages = [d["coverage"] for d in decomp.values()
                     if "coverage" in d]
        rec["write_path_stage_coverage"] = round(
            min(coverages), 3) if coverages else 0.0

        # group-commit telemetry per site: mean batch (writers covered
        # per barrier) and barrier-wait p99 from the shared process
        # registry each node's /metrics appends
        gc: dict = {}
        for url in filer_urls + [f"127.0.0.1:{p}" for p in vports]:
            try:
                st, body, _ = http_bytes("GET", f"{url}/metrics",
                                         timeout=5)
            except OSError:
                continue
            if st >= 300:
                continue
            parsed = profiling.parse_prom_text(
                body.decode("utf-8", "replace"))
            sites = {l.get("site", "") for l, _v in parsed.get(
                "seaweedfs_tpu_group_commit_batch_size_count", [])}
            for site in sorted(sites):
                h = profiling.prom_histogram(
                    parsed, "seaweedfs_tpu_group_commit_batch_size",
                    {"site": site})
                w = profiling.prom_histogram(
                    parsed, "seaweedfs_tpu_group_commit_wait_seconds",
                    {"site": site})
                if not h or not h.get("count"):
                    continue
                cell = gc.setdefault(site, {
                    "flushes": 0.0, "committed": 0.0, "waitP99Ms": 0.0})
                cell["flushes"] += h["count"]
                cell["committed"] += h["sum"]
                cell["waitP99Ms"] = max(
                    cell["waitP99Ms"], round(
                        profiling.histogram_quantile(w, 0.99) * 1e3, 3))
        for cell in gc.values():
            cell["meanBatch"] = round(
                cell["committed"] / cell["flushes"], 2) \
                if cell["flushes"] else 0.0
        rec["write_path_group_commit"] = gc
        # meta-plane sub-stage split (ISSUE 13): serialize / barrier
        # per commit, apply per event (async) — aggregated across the
        # filer fleet from the shared process registry
        sub: dict = {}
        applied = 0.0
        for url in filer_urls:
            try:
                st, body, _ = http_bytes("GET", f"{url}/metrics",
                                         timeout=5)
            except OSError:
                continue
            if st >= 300:
                continue
            parsed = profiling.parse_prom_text(
                body.decode("utf-8", "replace"))
            for l, v in parsed.get(
                    "seaweedfs_tpu_meta_plane_applied_total", []):
                applied += v
            stages = {l.get("stage", "") for l, _v in parsed.get(
                "seaweedfs_tpu_filer_meta_sub_seconds_count", [])}
            for stage in sorted(stages - {""}):
                h = profiling.prom_histogram(
                    parsed, "seaweedfs_tpu_filer_meta_sub_seconds",
                    {"stage": stage})
                if not h or not h.get("count"):
                    continue
                cell = sub.setdefault(stage,
                                      {"seconds": 0.0, "calls": 0})
                cell["seconds"] += h["sum"]
                cell["calls"] += h["count"]
        for cell in sub.values():
            cell["meanMs"] = round(
                cell["seconds"] / cell["calls"] * 1e3, 4) \
                if cell["calls"] else 0.0
            cell["seconds"] = round(cell["seconds"], 4)
        if sub:
            rec["write_path_meta_sub"] = sub
        if applied:
            rec["write_path_meta_plane_applied"] = int(applied)
        # the filer `meta` stage mean: THE ISSUE 13 acceptance number
        # (<= 4 ms on the single-filer meta-plane arm).  In -workers
        # mode each /metrics scrape lands on ONE random SO_REUSEPORT
        # worker (per-process registries), so sample several times,
        # dedupe identical worker snapshots by (count, sum), and
        # request-weight the distinct samples — a single scrape could
        # land on the busiest (applier) worker and read 2x high.
        import http.client as _hc
        samples: dict = {}
        for url in filer_urls:
            for _ in range(8):
                try:
                    # a FRESH connection per scrape: the pooled client
                    # keeps one socket alive, which pins every scrape
                    # to the same SO_REUSEPORT worker
                    conn = _hc.HTTPConnection(url, timeout=5)
                    conn.request("GET", "/metrics")
                    resp = conn.getresponse()
                    st, body = resp.status, resp.read()
                    conn.close()
                except OSError:
                    continue
                if st >= 300:
                    continue
                parsed = profiling.parse_prom_text(
                    body.decode("utf-8", "replace"))
                h = profiling.prom_histogram(
                    parsed, "filer_write_stage_seconds",
                    {"stage": "meta"})
                if h and h.get("count"):
                    samples[(url, h["count"], round(h["sum"], 6))] = \
                        (h["sum"], h["count"])
                _time.sleep(0.05)
        tot_s = sum(s for s, _c in samples.values())
        tot_c = sum(c for _s, c in samples.values())
        rec["write_path_filer_meta_ms"] = round(
            tot_s / tot_c * 1e3, 3) if tot_c else 0.0
        rec["write_path_filer_meta_workers_sampled"] = len(samples)
        # native meta-plane telemetry (ISSUE 17): the C++ plane's
        # requests never cross the Python stage histograms, so its
        # per-stage split (parse / upstream upload / WAL append) and
        # ack-latency histogram come from the plane's own counters on
        # /metrics.  Same multi-scrape + dedupe dance as the meta-ms
        # block: each worker process runs its OWN plane instance.
        nm: dict = {"requests": 0.0, "fallbacks": 0.0,
                    "fid_misses": 0.0, "wal_errors": 0.0,
                    "upstream_errors": 0.0, "wal_batches": 0.0,
                    "wal_lines": 0.0, "parse_s": 0.0,
                    "upload_s": 0.0, "wal_s": 0.0,
                    "ack_count": 0.0, "ack_sum_s": 0.0}
        nm_seen: set = set()
        try:
            _nw = int((env_extra or {}).get(
                "SEAWEEDFS_TPU_FILER_WORKERS", "1") or 1)
        except ValueError:
            _nw = 1
        for url in filer_urls:
            for _ in range(max(8, 3 * _nw)):
                try:
                    conn = _hc.HTTPConnection(url, timeout=5)
                    conn.request("GET", "/metrics")
                    resp = conn.getresponse()
                    st, body = resp.status, resp.read()
                    conn.close()
                except OSError:
                    continue
                if st >= 300:
                    continue
                parsed = profiling.parse_prom_text(
                    body.decode("utf-8", "replace"))

                def _one(name: str) -> float:
                    return sum(v for _l, v in parsed.get(name, []))
                reqs = _one("filer_meta_plane_native_requests_total")
                h = profiling.prom_histogram(
                    parsed, "filer_meta_plane_native_ack_seconds", {})
                key = (url, reqs,
                       round(h["sum"], 9) if h else 0.0)
                if key in nm_seen:
                    _time.sleep(0.05)
                    continue
                nm_seen.add(key)
                nm["requests"] += reqs
                for k, name in (
                        ("fallbacks", "fallbacks_total"),
                        ("fid_misses", "fid_misses_total"),
                        ("wal_errors", "wal_errors_total"),
                        ("upstream_errors", "upstream_errors_total"),
                        ("wal_batches", "wal_batches_total"),
                        ("wal_lines", "wal_lines_total")):
                    nm[k] += _one(
                        "filer_meta_plane_native_" + name)
                for stage in ("parse", "upload", "wal"):
                    nm[stage + "_s"] += sum(
                        v for l, v in parsed.get(
                            "filer_meta_plane_native"
                            "_stage_seconds_total", [])
                        if l.get("stage") == stage)
                if h:
                    nm["ack_count"] += h["count"]
                    nm["ack_sum_s"] += h["sum"]
                _time.sleep(0.05)
        if nm["requests"]:
            reqs = nm["requests"]
            nm["workers_sampled"] = len(nm_seen)
            nm["stageMsPerReq"] = {
                "parse": round(nm["parse_s"] / reqs * 1e3, 4),
                "upload": round(nm["upload_s"] / reqs * 1e3, 4),
                "wal": round(nm["wal_s"] / reqs * 1e3, 4),
            }
            nm["ackMeanMs"] = round(
                nm["ack_sum_s"] / nm["ack_count"] * 1e3, 3) \
                if nm["ack_count"] else 0.0
            nm["meanBatch"] = round(
                nm["wal_lines"] / nm["wal_batches"], 2) \
                if nm["wal_batches"] else 0.0
            for k in ("parse_s", "upload_s", "wal_s", "ack_sum_s"):
                nm[k] = round(nm[k], 4)
            rec["write_path_native_meta"] = nm
        # flight-deck per-stage tails (ISSUE 18): p99/p999 from the
        # drained PlaneRec stage histograms, aggregated across every
        # node that runs a plane (meta on the filer, write/read on
        # the volumes).  A /debug/slow touch per node first: the
        # scrape hook forces drain_now, so the tail includes records
        # still sitting in the C-side ring.
        fd: dict = {}
        fd_tot = {"records": 0.0, "dropped": 0.0}
        for url in filer_urls + [f"127.0.0.1:{p}" for p in vports]:
            try:
                http_bytes("GET", f"{url}/debug/slow", timeout=5)
                st, body, _ = http_bytes("GET", f"{url}/metrics",
                                         timeout=5)
            except OSError:
                continue
            if st >= 300:
                continue
            parsed = profiling.parse_prom_text(
                body.decode("utf-8", "replace"))
            fd_tot["records"] += sum(v for _l, v in parsed.get(
                "seaweedfs_tpu_plane_records_total", []))
            fd_tot["dropped"] += sum(v for _l, v in parsed.get(
                "seaweedfs_tpu_plane_ring_dropped_total", []))
            pairs = {(l.get("plane", ""), l.get("stage", ""))
                     for l, _v in parsed.get(
                         "seaweedfs_tpu_plane_stage_seconds_count",
                         [])}
            for plane, stage in sorted(pairs):
                h = profiling.prom_histogram(
                    parsed, "seaweedfs_tpu_plane_stage_seconds",
                    {"plane": plane, "stage": stage})
                if not h or not h.get("count"):
                    continue
                cell = fd.get((plane, stage))
                if cell is None:
                    fd[(plane, stage)] = h
                else:
                    cell["sum"] += h["sum"]
                    cell["count"] += h["count"]
                    cell["counts"] = [
                        a + b for a, b in zip(cell["counts"],
                                              h["counts"])]
        if fd:
            rec["write_path_plane_stages"] = {
                f"{plane}.{stage}": {
                    "count": int(h["count"]),
                    "meanMs": round(h["sum"] / h["count"] * 1e3, 4),
                    "p99Ms": round(profiling.histogram_quantile(
                        h, 0.99) * 1e3, 3),
                    "p999Ms": round(profiling.histogram_quantile(
                        h, 0.999) * 1e3, 3),
                } for (plane, stage), h in sorted(fd.items())}
        if fd_tot["records"]:
            rec["write_path_plane_records"] = {
                "drained": int(fd_tot["records"]),
                "ringDropped": int(fd_tot["dropped"]),
            }
        partial.phase("decomposition",
                      coverage=rec["write_path_stage_coverage"])
        return rec
    finally:
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except OSError:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


# the r5 write path, reproduced as the A arm: no group-commit layer
# (per-write flush/commit barriers), the sqlite rollback journal's
# full-sync commits, and per-write master assigns — exactly the write
# path VERDICT r5 measured at ~250-290 req/s on this box
_WRITE_PATH_OFF_ENV = {"SEAWEEDFS_TPU_GROUP_COMMIT": "0",
                       "SEAWEEDFS_TPU_SQLITE_SYNC": "full",
                       "SEAWEEDFS_TPU_ASSIGN_BATCH": "1"}


def _measure_write_path_ab(seconds: float = 10.0,
                           writers: int = 16) -> dict:
    """Group-commit on/off A/B over the same proc-cluster scenario
    (tracked per round like dist_rebuild): the `off` arm reproduces
    the r5 write path (per-write barriers, full-sync sqlite commits,
    per-write assigns), the `on` arm is this build's default.  Both
    throughput arms run the same concurrency, where the r5 path's
    serialized barriers flatline and the group-commit path scales.  A
    concurrency=1 pair rides along to prove the zero-wait passthrough:
    group commit must not tax the single-writer p50 (acceptance:
    within 10%)."""
    arms = {}
    for name, env, nw, dur, nf, nn, lean in (
            ("off", _WRITE_PATH_OFF_ENV, writers, seconds, 1, 2, False),
            ("on", None, writers, seconds, 1, 2, False),
            ("c1_off", _WRITE_PATH_OFF_ENV, 1, max(4.0, seconds / 2),
             1, 2, False),
            ("c1_on", None, 1, max(4.0, seconds / 2), 1, 2, False),
            # production shape: N gateway processes over one cluster.
            # A single pure-Python filer process is GIL-bound at
            # ~330 req/s no matter how cheap the barriers get; the
            # cluster's aggregate write capacity is what the 50x gap
            # is measured against, so the scaled arms fan the same
            # load across 7 filers + 7 volume servers via the
            # multi-process lean client (both arms get the identical
            # topology — the A/B stays group commit).
            ("scaled_off", _WRITE_PATH_OFF_ENV, 56, seconds, 7, 7,
             True),
            ("scaled_on", None, 56, seconds, 7, 7, True)):
        arms[name] = _measure_write_path(
            nodes=nn, writers=nw, seconds=dur, env_extra=env,
            filers=nf, lean_client=lean)
    out = {
        "scenario": "write_path_group_commit_ab",
        "arms": arms,
        "speedup": round(
            arms["on"]["write_path_req_per_sec"] /
            max(arms["off"]["write_path_req_per_sec"], 0.1), 2),
        "scaled_speedup": round(
            arms["scaled_on"]["write_path_req_per_sec"] /
            max(arms["scaled_off"]["write_path_req_per_sec"], 0.1), 2),
        "scaled_req_per_sec":
            arms["scaled_on"]["write_path_req_per_sec"],
        "c1_p50_ratio": round(
            arms["c1_on"]["write_path_p50_ms"] /
            max(arms["c1_off"]["write_path_p50_ms"], 0.001), 3),
    }
    return out


# ISSUE 12's A arm: this build with the native funnel switched OFF —
# pure-Python volume write path + threaded filer front, i.e. exactly
# the PR 8 (r06) write path the 421/1978 req/s numbers measured
_NATIVE_OFF_ENV = {"SEAWEEDFS_TPU_WRITE_PLANE": "0",
                   "SEAWEEDFS_TPU_ASYNC_FRONT": "0",
                   "SEAWEEDFS_TPU_FILER_WORKERS": "1"}

# ISSUE 15's attribution-off twin: the whole cost-attribution plane
# disarmed — no stage wall/cpu sampling, no flight-recorder arming or
# capture, no scheduler probe.  Overlaid on an armed arm's env to
# measure what always-on attribution actually costs.
_ATTRIBUTION_OFF_ENV = {"SEAWEEDFS_TPU_STAGE_TIMERS": "0",
                        "SEAWEEDFS_TPU_FLIGHT_RECORDER": "0",
                        "SEAWEEDFS_TPU_SCHED_PROBE": "0",
                        "SEAWEEDFS_TPU_CPU_SAMPLE": "0"}
# B arm: C++ needle-write plane on (default); the filer front stays
# threaded here — under write saturation the asyncio loop thread
# competes for the GIL it shares with the handlers (the async arm is
# recorded separately, and read_path's warm_async arm is its home
# turf: thousands of mostly-idle connections)
_NATIVE_ON_ENV = {"SEAWEEDFS_TPU_WRITE_PLANE": "1",
                  "SEAWEEDFS_TPU_ASYNC_FRONT": "0"}


def _measure_write_path_native_ab(seconds: float = 10.0,
                                  writers: int = 16) -> dict:
    """Native-funnel on/off A/B (ISSUE 12 acceptance): same proc
    cluster shape, the off arm reproducing the PR 8 write path
    (GIL-bound ~420 req/s single-filer), the on arm routing plain
    chunk uploads through the C++ write plane with the filer on the
    asyncio front.  Single-filer and production-shape (7 filers x 7
    volume servers, multi-process lean load) pairs, plus per-role
    Python-CPU-per-request before/after — the decomposition that must
    show the host-side per-request cost cut in half."""
    # the on arm's single-filer shape also turns on the filer's
    # pre-fork workers (4 processes, one port, one store; since ISSUE
    # 13 the meta cache STAYS on in worker mode because the meta
    # plane's log follower is the coherence channel): SO_REUSEPORT
    # spreads connections and the GIL stops being ONE ceiling —
    # recorded in the arm as write_path_filer_workers.
    # native_on_async is the same shape through the asyncio front
    # (its cost under write saturation, recorded honestly beside the
    # threaded number).
    #
    # ISSUE 13 grows the meta-plane on/off arms: `meta_*` pairs A/B
    # the metalog-as-WAL commit (async store checkpointing) against
    # the synchronous sqlite commit, at one worker (the meta-stage
    # latency acceptance: <= 4 ms mean) and at w4 (the worker-scaling
    # acceptance: >= 2.5x one worker — previously sibling coherence
    # storms tripled CPU/request).  native_on doubles as meta_on_w4:
    # the plane is this build's default.
    on_env = dict(_NATIVE_ON_ENV, SEAWEEDFS_TPU_FILER_WORKERS="4")
    on_async_env = dict(on_env, SEAWEEDFS_TPU_ASYNC_FRONT="1")
    # ISSUE 15: native_on's attribution-off twin — stage wall+cpu
    # timers, flight recorder and scheduler probe all disarmed; the
    # rate delta vs native_on IS the armed attribution plane's cost
    # (acceptance: <= 2%)
    attr_off_env = dict(on_env, **_ATTRIBUTION_OFF_ENV)
    meta_off_env = dict(_NATIVE_ON_ENV,
                        SEAWEEDFS_TPU_FILER_META_PLANE="0")
    meta_on_env = dict(_NATIVE_ON_ENV,
                       SEAWEEDFS_TPU_FILER_META_PLANE="1")
    meta_off_w4_env = dict(meta_off_env,
                           SEAWEEDFS_TPU_FILER_WORKERS="4")
    # ISSUE 17 native-meta arms: the same single-filer shape with the
    # lean client routing eligible PUTs straight at the C++ meta
    # plane's port (planeRoute — /status discovery, 404 => replay on
    # the Python front).  nm_on is the headline arm against BENCH_r10
    # native_on (1,607 req/s on this box; acceptance >= 2,400): ONE
    # filer process whose single epoll plane owns the hot path — on
    # this 1-core box extra siblings only thrash the scheduler, which
    # the w4/w8/w16 pre-fork arms record rather than hide (on a
    # multi-core box the same arms become the scaling curve).
    nm_env = dict(_NATIVE_ON_ENV,
                  SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE="1",
                  SEAWEEDFS_TPU_FILER_WORKERS="1")
    nm_w4_env = dict(nm_env, SEAWEEDFS_TPU_FILER_WORKERS="4")
    nm_w8_env = dict(nm_env, SEAWEEDFS_TPU_FILER_WORKERS="8")
    nm_w16_env = dict(nm_env, SEAWEEDFS_TPU_FILER_WORKERS="16")
    arms = {}
    for name, env, nw, nf, nn, lean, plane in (
            ("native_off", _NATIVE_OFF_ENV, 24, 1, 2, True, False),
            ("meta_off", meta_off_env, 24, 1, 2, True, False),
            ("meta_on", meta_on_env, 24, 1, 2, True, False),
            ("meta_off_w4", meta_off_w4_env, 24, 1, 2, True, False),
            ("native_on", on_env, 24, 1, 2, True, False),
            ("native_on_attr_off", attr_off_env, 24, 1, 2, True,
             False),
            ("native_on_async", on_async_env, 24, 1, 2, True, False),
            ("nm_on", nm_env, 24, 1, 2, True, True),
            ("nm_on_w4", nm_w4_env, 24, 1, 2, True, True),
            ("nm_on_w8", nm_w8_env, 24, 1, 2, True, True),
            ("nm_on_w16", nm_w16_env, 24, 1, 2, True, True),
            ("scaled_native_off", _NATIVE_OFF_ENV, 56, 7, 7, True,
             False),
            ("scaled_native_on", _NATIVE_ON_ENV, 56, 7, 7, True,
             False),
            ("scaled_nm_on", dict(
                _NATIVE_ON_ENV,
                SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE="1"),
             56, 7, 7, True, True)):
        arms[name] = _measure_write_path(
            nodes=nn, writers=nw, seconds=seconds, env_extra=env,
            filers=nf, lean_client=lean, plane_route=plane)
        arms[name]["write_path_filer_workers"] = int(
            (env or {}).get("SEAWEEDFS_TPU_FILER_WORKERS", "1"))

    def _cpu_ms(arm: dict, role: str) -> float:
        return arm.get("write_path_cpu", {}).get(role, {}).get(
            "cpuMsPerRequest", 0.0)

    out = {
        "scenario": "write_path_native_funnel_ab",
        "arms": arms,
        "speedup": round(
            arms["native_on"]["write_path_req_per_sec"] /
            max(arms["native_off"]["write_path_req_per_sec"], 0.1), 2),
        "scaled_speedup": round(
            arms["scaled_native_on"]["write_path_req_per_sec"] /
            max(arms["scaled_native_off"]["write_path_req_per_sec"],
                0.1), 2),
        "scaled_req_per_sec":
            arms["scaled_native_on"]["write_path_req_per_sec"],
        "nativeAckedOn":
            arms["native_on"]["write_path_native"]["requests"],
        "cpuMsPerRequest": {
            "volume_off": _cpu_ms(arms["native_off"], "volume"),
            "volume_on": _cpu_ms(arms["native_on"], "volume"),
            "filer_off": _cpu_ms(arms["native_off"], "filer"),
            "filer_on": _cpu_ms(arms["native_on"], "filer"),
        },
        "pythonCpuMsPerRequest": {
            "off": round(_cpu_ms(arms["native_off"], "volume") +
                         _cpu_ms(arms["native_off"], "filer"), 3),
            "on": round(_cpu_ms(arms["native_on"], "volume") +
                        _cpu_ms(arms["native_on"], "filer"), 3),
        },
    }
    v_off = out["cpuMsPerRequest"]["volume_off"]
    v_on = out["cpuMsPerRequest"]["volume_on"]
    f_off = out["cpuMsPerRequest"]["filer_off"]
    f_on = out["cpuMsPerRequest"]["filer_on"]
    out["cpu_cut"] = {
        "volume": round(1.0 - v_on / v_off, 3) if v_off else 0.0,
        "filer": round(1.0 - f_on / f_off, 3) if f_off else 0.0,
    }
    out["accept_native_2x"] = out["speedup"] >= 2.0
    out["accept_cpu_halved"] = out["cpu_cut"]["volume"] >= 0.5 or \
        out["cpu_cut"]["filer"] >= 0.5
    # -- ISSUE 15 cost attribution ------------------------------------
    # per-role cpu/wait per request from the stage-cpu histograms
    # (the /proc tree number above includes idle-thread bookkeeping;
    # this one is the per-REQUEST thread-time bill)
    stage_cpu: dict = {}
    for role, d in arms["native_on"].get(
            "write_path_decomposition", {}).items():
        if "cpuMsPerRequest" in d:
            stage_cpu[role] = {
                "cpuMsPerRequest": d["cpuMsPerRequest"],
                "waitMsPerRequest": d.get("waitMsPerRequest", 0.0),
                "meanTotalMs": d.get("meanTotalMs", 0.0),
            }
    out["stage_cpu_ms_per_req"] = stage_cpu
    # attribution-armed overhead (<= 2% acceptance).  The cross-
    # cluster twin pair above is recorded as context, but separate
    # clusters cannot resolve a ~1% signal under this box's ±5-20%
    # arm-to-arm boot noise — the acceptance figure comes from ONE
    # cluster alternating disarmed/armed traffic windows via the
    # runtime POST /debug/attribution lever.  Single-worker filer:
    # the lever is per-process and SO_REUSEPORT siblings cannot be
    # addressed individually; the per-request cost is per-process
    # regardless.
    toggle_arm = _measure_write_path(
        nodes=2, writers=24, seconds=max(4.0, seconds * 0.5),
        env_extra=_NATIVE_ON_ENV, filers=1, lean_client=True,
        attr_toggle_windows=10)
    tg = toggle_arm.get("attr_toggle", {})
    out["attribution_overhead"] = {
        "cross_cluster_pair": {
            "on_req_per_sec":
                arms["native_on"]["write_path_req_per_sec"],
            "off_req_per_sec":
                arms["native_on_attr_off"]["write_path_req_per_sec"],
        },
        "toggle_windows": tg.get("windows", []),
        "armed_req_per_sec": tg.get("armed_req_per_sec", 0.0),
        "disarmed_req_per_sec": tg.get("disarmed_req_per_sec", 0.0),
        "overhead_frac": tg.get("overhead_frac", 1.0),
    }
    out["accept_attribution_2pct"] = \
        out["attribution_overhead"]["overhead_frac"] <= 0.02
    # -- ISSUE 18 flight-deck drain overhead --------------------------
    # the same within-cluster alternating-window lever, scope="drain":
    # plane-routed traffic on the nm_on shape with the record drainer
    # armed vs disarmed (the C++ side rings records either way, so
    # the A/B isolates the Python drain + fan-out cost; lean clients
    # send no rid, so the armed windows exercise the common span-free
    # path).  Acceptance: <= 2%.  The arm also carries the per-stage
    # p99/p999 flight-deck tails scraped at teardown.
    drain_arm = _measure_write_path(
        nodes=2, writers=24, seconds=max(4.0, seconds * 0.5),
        env_extra=nm_env, filers=1, lean_client=True,
        attr_toggle_windows=10, plane_route=True,
        toggle_scope="drain")
    dg = drain_arm.get("attr_toggle", {})
    out["drain_overhead"] = {
        "toggle_windows": dg.get("windows", []),
        "drain_on_req_per_sec": dg.get("armed_req_per_sec", 0.0),
        "drain_off_req_per_sec": dg.get("disarmed_req_per_sec", 0.0),
        "overhead_frac": dg.get("overhead_frac", 1.0),
        "overhead_frac_med": dg.get("overhead_frac_med", 1.0),
        "plane_stage_tails_ms": drain_arm.get(
            "write_path_plane_stages", {}),
        "plane_records": drain_arm.get(
            "write_path_plane_records", {}),
    }
    # acceptance on the median-of-windows figure: a single collapsed
    # window (2x dips happen on this box) shifts the mean by more
    # than the whole 2% budget, so the mean can't resolve the signal
    out["accept_drain_2pct"] = \
        out["drain_overhead"]["overhead_frac_med"] <= 0.02
    # -- ISSUE 13 meta-plane acceptance ------------------------------
    out["meta_plane"] = {
        "speedup_w1": round(
            arms["meta_on"]["write_path_req_per_sec"] /
            max(arms["meta_off"]["write_path_req_per_sec"], 0.1), 2),
        "w4_over_w1": round(
            arms["native_on"]["write_path_req_per_sec"] /
            max(arms["meta_on"]["write_path_req_per_sec"], 0.1), 2),
        "w4_over_w4_off": round(
            arms["native_on"]["write_path_req_per_sec"] /
            max(arms["meta_off_w4"]["write_path_req_per_sec"], 0.1),
            2),
        "metaMs": {
            "off": arms["meta_off"].get("write_path_filer_meta_ms",
                                        0.0),
            "on": arms["meta_on"].get("write_path_filer_meta_ms",
                                      0.0),
        },
        "metaSub_on": arms["meta_on"].get("write_path_meta_sub", {}),
    }
    out["accept_meta_4ms"] = 0 < out["meta_plane"]["metaMs"]["on"] \
        <= 4.0
    out["accept_w4_scaling_2_5x"] = \
        out["meta_plane"]["w4_over_w1"] >= 2.5
    # -- ISSUE 17 native meta plane ----------------------------------
    nm_arm = arms["nm_on"]
    nm_reqs = max(nm_arm.get("write_path_requests", 0), 1)
    out["native_meta"] = {
        "req_per_sec": {
            "w1": nm_arm["write_path_req_per_sec"],
            "w4": arms["nm_on_w4"]["write_path_req_per_sec"],
            "w8": arms["nm_on_w8"]["write_path_req_per_sec"],
            "w16": arms["nm_on_w16"]["write_path_req_per_sec"],
            "scaled": arms["scaled_nm_on"]["write_path_req_per_sec"],
        },
        "speedup_vs_native_on": round(
            nm_arm["write_path_req_per_sec"] /
            max(arms["native_on"]["write_path_req_per_sec"], 0.1), 2),
        "planeAcked": nm_arm.get("write_path_plane_acked", 0),
        "planeShare": round(
            nm_arm.get("write_path_plane_acked", 0) / nm_reqs, 4),
        "stageMsPerReq": nm_arm.get(
            "write_path_native_meta", {}).get("stageMsPerReq", {}),
        "ackMeanMs": nm_arm.get(
            "write_path_native_meta", {}).get("ackMeanMs", 0.0),
        "meanWalBatch": nm_arm.get(
            "write_path_native_meta", {}).get("meanBatch", 0.0),
    }
    out["accept_native_meta_1_5x"] = \
        out["native_meta"]["speedup_vs_native_on"] >= 1.5
    out["accept_native_meta_2400"] = \
        nm_arm["write_path_req_per_sec"] >= 2400.0
    return out


def _measure_e2e_tpu_forced(size: int = 128 << 20):
    """The staged encode pipeline with the JAX/TPU backend FORCED
    (VERDICT r4 #3: the headline kernel number is device-side; the
    probed default pipeline may run the native engine, so the TPU e2e
    must be published too, not inferred).  The staging triple-buffers
    disk reads against device dispatch, so H2D is pipelined rather
    than serialized."""
    import shutil
    import tempfile

    from seaweedfs_tpu.storage.erasure_coding import ec_encoder
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    tmp = tempfile.mkdtemp(prefix="bench_ec_tpu_")
    try:
        base = os.path.join(tmp, "vol")
        rng = np.random.default_rng(11)
        blob = rng.integers(0, 256, min(64 << 20, size),
                            dtype=np.uint8).tobytes()
        with open(base + ".dat", "wb") as f:
            for _ in range(max(size // len(blob), 1)):
                f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        # account the bytes actually on disk: a requested size that is
        # not a blob multiple writes fewer — reporting size/dt would
        # overstate the headline number
        size = os.path.getsize(base + ".dat")
        ctx = ECContext(backend="jax")
        ec_encoder.write_ec_files(base, ctx)  # warm compile cache
        for i in range(ctx.total):
            os.remove(base + ctx.to_ext(i))
        t0 = time.perf_counter()
        ec_encoder.write_ec_files(base, ctx)
        _fsync_shards(base, ctx)
        dt = time.perf_counter() - t0
        return {"e2e_encode_gbps_tpu": round(size / dt / 1e9, 3),
                "e2e_tpu_dat_bytes": size}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _emit(gbps, backend, shard_bytes, e2e=None, h2d=None,
          probe=None):
    """e2e is the dict from _measure_e2e; probe is the feed-rate probe
    record (ec_context.probe_backend) whose `choice` is the engine the
    e2e pipeline ACTUALLY RAN — the ceilings below are derived from the
    chosen engine's own feed rate, so the e2e_bound_by label can never
    contradict the recorded e2e."""
    native_cpu = _measure_native_cpu_gbps()
    rec = {
        "metric": "ec_encode_rs10+4_GBps_per_chip",
        "value": round(gbps, 2),
        "unit": "GB/s",
        "vs_baseline": round(gbps / BASELINE_CPU_GBPS, 2),
        "backend": backend,
        "shard_bytes": shard_bytes,
        "baseline_cpu_gbps": BASELINE_CPU_GBPS,
        "measured_native_cpu_gbps": native_cpu,
    }
    if h2d is not None:
        rec["h2d_gbps"] = h2d
    if probe is not None:
        rec["backend_probe"] = {k: probe.get(k) for k in
                                ("cpu_engine", "cpu_gbps", "h2d_gbps",
                                 "choice")}
    if e2e is not None:
        # per-config ceilings + bound-by labels computed inside
        # _measure_e2e from pattern-matched probes
        rec.update(e2e)
    print(json.dumps(rec))


def measure() -> None:
    """Child-process mode (`--measure tpu`): this process owns the
    chip; run the device measurement and print the JSON.  Every phase
    boundary flushes an incremental record (_Partial) so a timeout
    mid-pipeline still leaves the finished phases on disk, and every
    sized phase is scaled from the pre-run calibration probe + the
    remaining BENCH_BUDGET_S so the arm FINISHES inside its timeout
    instead of dying mid-pipeline (BENCH_r05's TPU arm).  No TPU is a
    failure, and so is a phase that throws: the JSON of the finished
    phases is still printed, the exit code is non-zero."""
    partial = _Partial()
    from seaweedfs_tpu.storage.erasure_coding import ec_context
    dev = ec_context.own_device()  # raises when JAX found no chip
    if dev["platform"] != "tpu":
        sys.exit(f"bench: needs a TPU, JAX runs on {dev['platform']}")
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import rs_matrix
    from seaweedfs_tpu.ops import rs_pallas

    try:
        budget_s = float(os.environ.get("BENCH_BUDGET_S", "0") or 0)
    except ValueError:
        budget_s = 0.0
    t_begin = time.monotonic()
    failed: "list[str]" = []

    def remaining() -> float:
        if budget_s <= 0:
            return float("inf")
        return budget_s - (time.monotonic() - t_begin)

    def phase_failed(name: str, exc: Exception) -> None:
        print(f"bench: {name} failed: {exc!r}", file=sys.stderr)
        failed.append(name)

    backend = dev["platform"]
    shard_bytes = SHARD_BYTES
    chain = CHAIN

    # calibration FIRST: tiny h2d + kernel probe whose rates (a) size
    # every phase below to fit the budget and (b) fix the predicted
    # roofline of_ceiling is judged against
    try:
        calib = _calibrate_device()
    except Exception as exc:
        phase_failed("calibrate", exc)
        calib = None
    partial.phase("calibrate", **(calib or {}))

    if calib:
        # size the chained-kernel microbench: ITERS timed launches of
        # `chain` kernel steps plus the one-time h2d of the batch must
        # fit its slice of the budget even at the calibrated rates
        cap = min(90.0, max(20.0, remaining() * 0.15))

        def est(sb: int, ch: int) -> float:
            kern = (ITERS + 1) * ch * DATA_SHARDS * sb / \
                max(calib["kernel_gbps_per_chip"], 1e-3) / 1e9
            h2d_cost = 2 * DATA_SHARDS * sb / \
                max(calib["h2d_gbps"], 1e-3) / 1e9
            return kern + h2d_cost

        while shard_bytes > (4 << 20) and est(shard_bytes, chain) > cap:
            shard_bytes //= 2
        while chain > 4 and est(shard_bytes, chain) > cap:
            chain //= 2

    words = shard_bytes // 4
    rng = np.random.default_rng(0)
    data32 = rng.integers(0, 2**32, size=(DATA_SHARDS, words),
                          dtype=np.uint32)
    mat = rs_matrix.parity_matrix(DATA_SHARDS, PARITY_SHARDS)
    tables = jnp.asarray(rs_pallas.expand_tables(mat))
    d0 = jax.device_put(jnp.asarray(data32))

    # Chain CHAIN dependent kernel steps inside one jit and fetch a
    # scalar checksum: chaining amortizes dispatch out of the per-step
    # time, and the fetch is the fence.
    chain_steps = chain

    @jax.jit
    def chain_fn(tables, d):
        def body(_, d):
            out = rs_pallas.gf_apply_matrix_pallas_words(
                tables, d, interpret=False)
            return d.at[:PARITY_SHARDS].set(d[:PARITY_SHARDS] ^ out)
        d = jax.lax.fori_loop(0, chain_steps, body, d)
        return jnp.sum(d[0, :: max(words // 1024, 1)], dtype=jnp.uint32)

    gbps = 0.0
    try:
        int(chain_fn(tables, d0))  # warmup / compile
        best_dt = float("inf")
        for _ in range(ITERS):
            t0 = time.perf_counter()
            int(chain_fn(tables, d0))
            best_dt = min(best_dt,
                          (time.perf_counter() - t0) / chain_steps)
        gbps = (DATA_SHARDS * shard_bytes) / best_dt / 1e9
    except Exception as exc:
        phase_failed("kernel", exc)
    partial.phase("kernel", gbps=round(gbps, 2), backend=backend)

    # H2D bandwidth (the device feed ceiling of the e2e pipeline)
    h2d = None
    try:
        host = np.ascontiguousarray(data32)
        jax.device_put(host[:, :1024]).block_until_ready()  # warmup
        best = float("inf")
        for _ in range(ITERS):
            t0 = time.perf_counter()
            jax.device_put(host).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        h2d = round(DATA_SHARDS * shard_bytes / best / 1e9, 2)
    except Exception as exc:
        phase_failed("h2d", exc)
    partial.phase("h2d", h2d_gbps=h2d)

    # Feed-rate probe: the engine the e2e pipeline will actually run
    try:
        probe = ec_context.probe_backend()
    except Exception as exc:
        phase_failed("probe", exc)
        probe = None
    partial.phase("probe", choice=(probe or {}).get("choice"))

    try:
        e2e = _measure_e2e(True, probe, budget_s=remaining(),
                           calib=calib)
    except Exception as exc:
        phase_failed("e2e", exc)
        e2e = None
    partial.phase("e2e", gbps=(e2e or {}).get("e2e_encode_gbps"))
    if remaining() < 280:
        # out of budget for a proc-cluster A/B: say so in the trail
        # instead of dying mid-cluster (a timed-out arm must still
        # yield a diagnosable record)
        partial.phase("dist_rebuild",
                      skipped=f"budget: {int(remaining())}s left")
    else:
        try:
            # loopback-cluster rebuild A/B: copy-then-rebuild vs the
            # slice-pipelined streaming repair path
            e2e = dict(e2e or {}, **_measure_dist_rebuild())
        except Exception as exc:
            phase_failed("dist_rebuild", exc)
        partial.phase("dist_rebuild",
                      speedup=(e2e or {}).get("dist_rebuild_speedup"))
    if remaining() < 200:
        partial.phase("dist_encode",
                      skipped=f"budget: {int(remaining())}s left")
    else:
        try:
            # loopback-cluster encode A/B: encode-locally-then-balance
            # vs scatter-encode streaming shards to their placements
            e2e = dict(e2e or {}, **_measure_dist_encode(
                budget_s=remaining() - 90))
        except Exception as exc:
            phase_failed("dist_encode", exc)
        partial.phase("dist_encode",
                      speedup=(e2e or {}).get("dist_encode_speedup"))
    # VERDICT r4 #3: publish the TPU-backed e2e number (the probed
    # pipeline may choose the native engine; the device path must be a
    # measured quantity, not an inference from the kernel
    # microbenchmark).  Sized from the calibration: the windowed
    # staging pipeline's predicted rate is the roofline
    # min(h2d, kernel x devices).
    try:
        from seaweedfs_tpu.ops import staging
        tpu_size = 128 << 20
        roof = None
        if calib:
            roof = calib["predicted_roofline_gbps"]
            # warm + timed encode both pass over the volume; size
            # for ~2 passes at HALF the roofline (overlap may be
            # imperfect), floor 32MB, cap 1GB
            span = max(20.0, min(remaining() * 0.4, 120.0))
            tpu_size = int(max(32 << 20, min(
                1 << 30, roof * 0.5 * 1e9 * span / 2)))
            if tpu_size > (64 << 20):
                # whole 64MB blob repetitions (the .dat writer's
                # unit) so requested == written
                tpu_size = (tpu_size >> 26) << 26
        staging.reset_aggregate()
        tpu_e2e = _measure_e2e_tpu_forced(size=tpu_size)
        snap = staging.snapshot()
        tpu_e2e["tpu_h2d_windows"] = snap["windows"]
        tpu_e2e["tpu_h2d_overlap_fraction"] = \
            snap["overlap_fraction"]
        tpu_e2e["tpu_staged_h2d_gbps"] = snap["h2d_gbps"]
        tpu_e2e["tpu_staged_d2h_gbps"] = snap["d2h_gbps"]
        if calib:
            _apply_ceiling(
                tpu_e2e, "e2e_tpu",
                tpu_e2e.get("e2e_encode_gbps_tpu", 0.0),
                {"host->device staging (windowed)":
                 calib["h2d_gbps"],
                 f"GF kernel x {calib['devices']} devices":
                 calib["kernel_gbps_per_chip"] *
                 calib["devices"]})
        e2e = dict(e2e or {}, **tpu_e2e)
    except Exception as exc:
        phase_failed("tpu_forced_e2e", exc)
    partial.phase(
        "tpu_forced_e2e",
        gbps=(e2e or {}).get("e2e_encode_gbps_tpu"),
        overlap=(e2e or {}).get("tpu_h2d_overlap_fraction"))
    if calib is not None:
        e2e = dict(e2e or {}, device_calibration=calib)
    e2e = dict(e2e or {}, device=dev)
    if failed:
        e2e["failed_phases"] = failed
    _emit(gbps, backend, shard_bytes, e2e=e2e, h2d=h2d, probe=probe)
    if failed:
        sys.exit(1)


class _Partial:
    """Incremental bench record (the BENCH_r05 lesson: the TPU arm
    timed out and yielded NOTHING).  Each completed phase is flushed
    atomically to $BENCH_PARTIAL_PATH as it lands, with per-phase
    elapsed seconds — so when an arm is killed at its timeout, the
    parent salvages a diagnosable record saying which phase finished,
    how long each took, and which one it died in, instead of an empty
    hand.  No env var set (direct scenario runs) -> in-memory only."""

    def __init__(self):
        self.path = os.environ.get("BENCH_PARTIAL_PATH", "")
        self._t0 = time.monotonic()
        self._last = self._t0
        self.doc: dict = {"partial": True, "phases": {},
                          "phaseSeconds": {}}

    def phase(self, name: str, **data) -> None:
        now = time.monotonic()
        self.doc["phases"][name] = {
            k: v for k, v in data.items() if v is not None}
        self.doc["phaseSeconds"][name] = round(now - self._last, 3)
        self.doc["elapsedSeconds"] = round(now - self._t0, 3)
        self.doc["lastPhase"] = name
        self._last = now
        if not self.path:
            return
        try:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.doc, f)
            os.replace(tmp, self.path)
        except OSError:
            pass  # partial records must never fail the measurement


def _run_child(platform: str, timeout_s: int):
    """Run `bench.py --measure <platform>`; returns (json_line,
    partial, rc) — json_line is None on failure/timeout, partial is
    whatever phase record the child managed to flush before dying (or
    None), rc the child's exit code (None when it was killed)."""
    import tempfile
    env = dict(os.environ)
    partial_path = os.path.join(
        tempfile.gettempdir(),
        f"bench_partial_{platform}_{os.getpid()}.json")
    env["BENCH_PARTIAL_PATH"] = partial_path
    # the child self-schedules its phases against this (calibration
    # probe first, then every sized phase scaled to what's left)
    env["BENCH_BUDGET_S"] = str(max(60, timeout_s - 30))

    def read_partial():
        try:
            with open(partial_path) as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else None
        except (OSError, ValueError):
            return None
    # start_new_session + killpg: a hung TPU-runtime grandchild inheriting
    # the capture pipes would otherwise keep communicate() blocked after
    # the direct child is killed — the exact parent hang this guards.
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--measure", platform],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            proc.kill()
        try:
            proc.communicate(timeout=10)
        except Exception:
            pass
        print(f"bench: --measure {platform} timed out after {timeout_s}s",
              file=sys.stderr)
        partial = read_partial()
        if partial is not None:
            partial["timeoutS"] = timeout_s
            partial["platform"] = platform
        _rm_quiet(partial_path)
        return None, partial, None
    partial = read_partial()
    _rm_quiet(partial_path)
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                json.loads(line)
                return line, None, proc.returncode
            except ValueError:
                continue
    print(f"bench: --measure {platform} rc={proc.returncode}, no JSON; "
          f"stderr tail: {stderr[-2000:]}", file=sys.stderr)
    if partial is not None:
        partial["rc"] = proc.returncode
        partial["platform"] = platform
    return None, partial, proc.returncode


def _rm_quiet(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


def main() -> None:
    """Run the device arm in a child; exit non-zero unless it ran to
    the end on a TPU with every phase intact.  What did finish is
    printed either way."""
    line, partial, rc = _run_child("tpu", TPU_TIMEOUT_S)
    if line is not None:
        print(line)
    elif partial is not None:
        print(json.dumps(dict(
            partial, metric="ec_encode_rs10+4_GBps_per_chip")))
    if line is None or rc != 0:
        sys.exit(rc or 1)


if __name__ == "__main__":
    if sys.argv[1:] == ["--measure", "tpu"]:
        measure()
    elif len(sys.argv) >= 2 and sys.argv[1] == "dist_encode":
        # standalone scatter-vs-seed encode A/B (the acceptance
        # scenario): one JSON line, no accelerator needed.  Optional
        # arg = round budget in seconds (warmup pair calibrates the
        # per-round cost; rounds stop when the next pair won't fit).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        bud = float(sys.argv[2]) if len(sys.argv) > 2 else None
        print(json.dumps(_measure_dist_encode(budget_s=bud)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "dist_rebuild":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        print(json.dumps(_measure_dist_rebuild()))
    elif len(sys.argv) >= 2 and sys.argv[1] == "write_path":
        # write-path throughput + per-stage latency decomposition
        # (ROADMAP item 1's tracker): group-commit on/off A/B plus a
        # concurrency=1 pair, one JSON line attributing the
        # per-request wall across recv/assign/upload/meta (filer) and
        # recv/lock/index/append/flush (volume), with per-site mean
        # batch size + barrier-wait p99.  `write_path_single` runs
        # just the default-config arm (the old behavior).
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 10.0
        print(json.dumps(_measure_write_path_ab(seconds=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "write_path_native":
        # native-funnel on/off A/B (ISSUE 12): C++ write plane +
        # asyncio filer front vs the PR 8 pure-Python path, single
        # filer and 7x7, with per-role Python-CPU-per-request
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 10.0
        print(json.dumps(_measure_write_path_native_ab(seconds=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "read_path_native":
        # native read funnel (ISSUE 19): C++ filer read plane fused
        # with the volume read plane over persistent plane sockets,
        # vs the threaded and asyncio Python fronts, plus the nm_on
        # write arm re-run with the keep-alive upload hop
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 8.0
        print(json.dumps(_measure_read_path_native(seconds=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "drain_ab":
        # flight-deck drain A/B alone (ISSUE 18): plane-routed load,
        # drain armed vs disarmed via the runtime scope="drain"
        # lever, plus per-stage p99/p999 tails — the quick probe for
        # the <= 2% acceptance without the full 14-arm native run
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 5.0
        nm_env = dict(_NATIVE_ON_ENV,
                      SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE="1",
                      SEAWEEDFS_TPU_FILER_WORKERS="1")
        arm = _measure_write_path(
            nodes=2, writers=24, seconds=dur, env_extra=nm_env,
            filers=1, lean_client=True, attr_toggle_windows=10,
            plane_route=True, toggle_scope="drain")
        dg = arm.get("attr_toggle", {})
        print(json.dumps({
            "scenario": "plane_record_drain_ab",
            "toggle_windows": dg.get("windows", []),
            "drain_on_req_per_sec": dg.get("armed_req_per_sec", 0.0),
            "drain_off_req_per_sec": dg.get(
                "disarmed_req_per_sec", 0.0),
            "overhead_frac": dg.get("overhead_frac", 1.0),
            "overhead_frac_med": dg.get("overhead_frac_med", 1.0),
            "accept_drain_2pct": dg.get("overhead_frac_med", 1.0)
            <= 0.02,
            "plane_stage_tails_ms": arm.get(
                "write_path_plane_stages", {}),
            "plane_records": arm.get("write_path_plane_records", {}),
            "req_per_sec": arm.get("write_path_req_per_sec", 0.0),
            "plane_acked": arm.get("write_path_plane_acked", 0),
        }))
    elif len(sys.argv) >= 2 and sys.argv[1] == "write_path_single":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 10.0
        print(json.dumps(_measure_write_path(seconds=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "read_path":
        # zipfian multi-tenant read-path cache A/B + degraded arm
        # (ISSUE 11): warm hit ratio, warm/cold throughput ratio, and
        # degraded-read p99 with byte identity, one JSON line
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 8.0
        print(json.dumps(_measure_read_path(duration_s=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "soak":
        # sustained-load QoS A/B (ISSUE 6): per-tenant p50/p99 with
        # and without the QoS plane, one JSON line
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 20.0
        print(json.dumps(_measure_soak(duration_s=dur)))
    elif len(sys.argv) >= 2 and sys.argv[1] == "slo_soak":
        # SLO-autopilot soak (ISSUE 20): diurnal swing + slow-replica
        # window with the autopilot closing the loop; acceptance is
        # the slo_held verdict (p99 within budget, shed bounded)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        dur = float(sys.argv[2]) if len(sys.argv) > 2 else 30.0
        print(json.dumps(_measure_slo_soak(duration_s=dur)))
    else:
        main()
