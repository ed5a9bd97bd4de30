"""The read load: closed-loop clients in a process of their own.

A copy of the read loop of `seaweedfs_tpu/benchmark.py` (this repo's
port of `weed benchmark`: each client reads a random id of those
written, then the next), with what a benchmark needs added: a seeded
choice, a fixed window given by the parent, every body compared with
its seeded digest, and the time a client spent between a response and
its next send.  Run as `python -m benchmark.load`; the parent starts a
few of these so that the clients share no interpreter lock with the
harness or the worker.

Wire: prints "ready" once its connections are warm, then reads one line
`<start_unix> <stop_unix>` and runs from start to stop; requests sent
before stop are waited for.  Results go to --out as an .npz.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import threading
import time

import numpy as np

OK, WRONG, FAILED = 0, 1, 2


def percentile(sorted_vals, p: float) -> float:
    """The value below which p percent of samples lie (nearest rank
    above): all requests, failures included at what they cost."""
    if len(sorted_vals) == 0:
        raise ValueError("no samples")
    return float(sorted_vals[min(len(sorted_vals) - 1,
                                 int(p / 100.0 * len(sorted_vals)))])


def summarize(parts: "list[dict]", start: float, stop: float) -> dict:
    """The window's numbers from the children's records.  Latency is
    over every request sent in the window; a failed or wrong one
    counts at the larger of what it took and the request timeout, so
    it misses any limit.  The rate is correct responses completed
    inside the window over the window.  Where the window closes before
    the clients stop (`stop` earlier than the stop they were given),
    rate, latency and lateness leave out what was sent after it;
    "requests", "wrong" and "failed" count every request made."""
    sent = np.concatenate([p["sent"] for p in parts])
    lat = np.concatenate([p["latency"] for p in parts])
    status = np.concatenate([p["status"] for p in parts])
    late = np.concatenate([p["late"] for p in parts])
    timeout = float(parts[0]["timeout"])
    inside = sent <= stop
    cost = np.where(status == OK, lat, np.maximum(lat, timeout))
    ordered = np.sort(cost[inside])
    done_in = inside & (status == OK) & (sent + lat <= stop)
    late = late[inside]
    return {"requests": int(len(sent)),
            "requests_in_window": int(inside.sum()),
            "wrong": int((status == WRONG).sum()),
            "failed": int((status == FAILED).sum()),
            "completed_in_window": int(done_in.sum()),
            "read_rps": float(done_in.sum() / (stop - start)),
            "read_p50_ms": percentile(ordered, 50) * 1e3,
            "read_p99_ms": percentile(ordered, 99) * 1e3,
            "late_mean_ms": float(late.mean() * 1e3) if len(late) else 0.0,
            "late_max_ms": float(late.max() * 1e3) if len(late) else 0.0}


def client(master: str, fids, want, seed_key, start: float, stop: float,
           timeout: float, out: list) -> None:
    from seaweedfs_tpu import operation
    rng = np.random.default_rng(seed_key)
    rows = []
    while time.time() < start:
        time.sleep(min(0.005, max(0.0, start - time.time())))
    last_done = time.time()
    while True:
        i = int(rng.integers(len(fids)))
        t0 = time.time()
        if t0 >= stop:
            break
        late = t0 - last_done
        try:
            body = operation.read(master, fids[i])
            t1 = time.time()
            good = hashlib.blake2b(
                body, digest_size=16).hexdigest() == want[i]
            rows.append((t0, t1 - t0, OK if good else WRONG, late))
        except Exception as e:  # noqa: BLE001 — a client's outer edge:
            # any failure of the request is a failed request
            t1 = time.time()
            rows.append((t0, t1 - t0, FAILED, late))
            sys.stderr.write(f"read {fids[i]} failed: {e!r}\n")
        last_done = t1
    out.append(rows)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--master", required=True)
    ap.add_argument("--fids", required=True,
                    help="JSON file: [[fid, digest], ...]")
    ap.add_argument("--threads", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--proc", type=int, required=True)
    ap.add_argument("--timeout", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.fids) as f:
        pairs = json.load(f)
    fids = [p[0] for p in pairs]
    want = [p[1] for p in pairs]
    from seaweedfs_tpu import operation
    operation.read(args.master, fids[0])       # lookup and pool warm
    print("ready", flush=True)
    start, stop = (float(x) for x in sys.stdin.readline().split())
    out: list = []
    threads = [threading.Thread(
        target=client, args=(args.master, fids, want,
                             [args.seed, args.proc, t], start, stop,
                             args.timeout, out), daemon=True)
        for t in range(args.threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=stop - time.time() + 4 * args.timeout)
    hung = sum(t.is_alive() for t in threads)
    rows = [r for part in out for r in part]
    # a client that never came back: one request that never answered
    rows += [(stop, 4 * args.timeout, FAILED, 0.0)] * hung
    a = np.array(rows, dtype=np.float64).reshape(-1, 4)
    np.savez(args.out, sent=a[:, 0], latency=a[:, 1],
             status=a[:, 2].astype(np.int64), late=a[:, 3],
             timeout=args.timeout)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
