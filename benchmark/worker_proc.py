"""The `tpu_ec` worker as one child of the harness: the only process of
a run that maps libtpu.

It does what `python -m seaweedfs_tpu worker -capabilities
erasure_coding -backend jax` does (`ec_context.own_device()`, then a
`PluginWorker` hosting `EcEncodeHandler(backend=...)` and
`EcRebuildHandler()`), and besides answers the harness over its stdin
and stdout, one JSON object a line each way: ledgers and the count of
values read back from the chip at a mark, the profiler started and
stopped around the window, the trace reduced to lists, and the time of
every progress report of every job.  The program's own prints go to
stderr (the role's log).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import threading
import time


def count_reads():
    """A reader of the count of values the process has read back from
    its JAX arrays, each `np.asarray` of one: whatever a chip computes
    for the program comes back to the host this way, and a path that
    computed nothing there reads nothing back.  The runtime keeps no
    count that moves on every launch (its `num_allocs` counts only what
    its buffer cache cannot serve: PERF.md 3), nor does the program:
    its staging ledger misses the batch path, its compile ledger a
    program already compiled.  On the CPU an array is read through the
    buffer protocol and passes this by, so there the count is not
    taken."""
    from jax._src.array import ArrayImpl
    value = ArrayImpl.__dict__["_value"].fget
    lock, n = threading.Lock(), [0]

    def counted(self):
        with lock:
            n[0] += 1
        return value(self)
    ArrayImpl._value = property(counted)
    return lambda: n[0]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--admin", required=True)
    ap.add_argument("--master", required=True)
    ap.add_argument("--dir", required=True)
    ap.add_argument("--backend", required=True)
    args = ap.parse_args()

    wire = os.fdopen(os.dup(1), "w")      # the harness's end
    os.dup2(2, 1)                         # stray prints join the log
    sys.stdout = sys.stderr

    def say(obj: dict) -> None:
        wire.write(json.dumps(obj) + "\n")
        wire.flush()

    from seaweedfs_tpu import tracing
    from seaweedfs_tpu.ops import staging
    from seaweedfs_tpu.plugin.handlers import (EcEncodeHandler,
                                               EcRebuildHandler)
    from seaweedfs_tpu.plugin.worker import PluginWorker
    from seaweedfs_tpu.storage.erasure_coding import ec_context

    from benchmark import trace_reduce

    t0 = time.perf_counter()
    dev = ec_context.own_device()
    init_s = time.perf_counter() - t0
    import jax

    log: "list[list]" = []           # [job id, label, host seconds]
    lock = threading.Lock()

    def note(job_id: str, label: str) -> None:
        now = time.time_ns()
        with lock:
            log.append([job_id, label, now / 1e9])
        # a zero-length host event that carries the host's clock into
        # the trace (trace_reduce.SYNC)
        with jax.profiler.TraceAnnotation(f"{trace_reduce.SYNC}{now}"):
            pass

    class Worker(PluginWorker):
        def _execute(self, job_id, job_type, params, **kw):
            note(job_id, "start")
            try:
                super()._execute(job_id, job_type, params, **kw)
            finally:
                note(job_id, "end")

        def report_progress(self, job_id, progress, message=""):
            note(job_id, message or f"{progress}")
            super().report_progress(job_id, progress, message)

    def peaks() -> dict:
        return {f"{d.platform}:{d.id}":
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices()}

    reads = count_reads() if dev["platform"] != "cpu" else None

    def mark() -> dict:
        return {"t": time.time(), "staging": staging.snapshot(),
                "compile": ec_context.compile_ledger(),
                "peak_bytes": peaks(),
                "reads": None if reads is None else reads()}

    worker = Worker(args.admin, args.master, args.dir,
                    [EcEncodeHandler(backend=args.backend),
                     EcRebuildHandler()])
    worker.start()
    say({"event": "ready", "device": dev, "init_s": init_s,
         "probe": ec_context.probe_backend() if dev["platform"] != "cpu"
         else None,
         "devices": [str(d) for d in jax.local_devices()],
         "compile_cache_dir": ec_context.compile_cache_dir(),
         "worker_id": worker.worker_id, "pid": os.getpid()})

    trace_dir = None
    for line in sys.stdin:
        try:
            req = json.loads(line)
        except ValueError:
            continue
        cmd = req.get("cmd")
        if cmd == "mark":
            say(mark())
        elif cmd == "trace_start":
            trace_dir = req["dir"]
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1    # TraceAnnotations only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            note("", "trace_start")
            say(mark())
        elif cmd == "trace_stop":
            note("", "trace_stop")
            out = mark()
            jax.profiler.stop_trace()
            found = sorted(glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
            out["events"] = trace_reduce.device_events(found[-1]) \
                if found else {"devices": {}, "sync": []}
            out["trace_bytes"] = os.path.getsize(found[-1]) if found else 0
            say(out)
        elif cmd == "report":
            with lock:
                events = list(log)
            spans = [{"name": s["name"], "start": s["start"],
                      "durationMs": s["durationMs"],
                      "busySeconds": (s.get("attrs") or {}).get(
                          "busySeconds")}
                     for s in tracing.recent_spans(100000)
                     if s["name"] in ("ec.encode", "encode.read",
                                      "encode.codec", "encode.write")]
            say(dict(mark(), log=events, spans=spans))
        elif cmd == "exit":
            break
    worker.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
