"""Host->device staging for the encode path: a launch is one window,
put on the device as the caller filled it.

    reader thread   fills a recycled [K, B] host buffer
    compute stage   device_put, fence, kernel dispatch   (WindowedLaunch())
    writer thread   writes the K data rows, then fetches  (.materialize())
                    the parity and writes that

* The unit is a WINDOW: a [K, W] batch of packed uint32 words (4 GF
  bytes per word, see ops.rs_jax), ``WINDOW_BYTES`` of them where the
  EC file pipeline made it (ECContext.rows_per_launch / batch_size
  reckon a work item from K and that size, and nowhere else is a batch
  sized).  A batch of any other size is put whole just the same.
* The thread that makes the launch puts the buffer, fences ONLY ITSELF
  (an honest h2d wall that stalls no fetch) and dispatches the kernel;
  the thread that asks for the result fetches it.  No thread is made
  here.  A launch that is never fetched (a pipeline unwinding on an
  error) is a device array that becomes garbage.
* Windows in flight are bounded by the CALLER's recycled buffers
  (ec_encoder._staged_run sizes its pool from ``INFLIGHT``).  A buffer
  is reused only after its window's OUTPUT is on the host — the
  aliasing-safe recycle point on backends where ``device_put`` may
  alias host memory (CPU).
* With more than one visible device, a window whose words divide the
  device count is placed with ``NamedSharding(Mesh(jax.devices(),
  ("batch",)), PartitionSpec(None, "batch"))`` — the packed-words
  batch axis is split across the mesh and the jitted kernel runs SPMD
  with no collectives (the apply is columnwise).  Any other window
  (one device, the odd tail of a rebuild) is placed plain on the
  default device.

Telemetry: per-launch ``device_note``/``kernel_note`` (profiling.py)
plus an overlap fraction per RUN (the launches of one encode or
rebuild; a launch made alone is its own run) — 0 when the h2d and the
fetch plane ran serially, 1 when the wall from the first put to the
last fetch equals the slower plane alone — surfaced as the
``device_h2d_overlap_fraction`` gauge (cluster.top) and a process-wide
aggregate snapshot() the benchmark takes deltas of.  Per launch, two
trace spans under the span that was current when the launch was made
(tracing.py, one batch when the launch is fetched): ``stage.h2d`` (put
+ fence) and ``stage.d2h`` (the fetch).  The ledger counts payload
beside sent bytes; how long either side of the hand-off waited for the
other is in the pipeline's own spans (``encode.read`` / ``.codec`` /
``.write``: busySeconds against duration).
"""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

WINDOW_BYTES = 32 << 20     # staged bytes of one window, and so of one
#                             work item of a device encode
INFLIGHT = 2                # staged windows between reader and writer


@functools.lru_cache(maxsize=None)
def encode_shardings() -> "tuple[object | None, object | None, int]":
    """(batch_sharding, replicated_sharding, n_devices) for mesh
    placement of [K, W] windows, or (None, None, 1) with one visible
    device.  batch_sharding splits axis 1 (the packed-words batch
    axis) across every device; replicated_sharding is for the small
    constant matrix.  Cached: the device set never changes in-process.
    """
    import jax
    devs = jax.devices()
    if len(devs) == 1:
        return None, None, 1
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(devs), ("batch",))
    return (NamedSharding(mesh, PartitionSpec(None, "batch")),
            NamedSharding(mesh, PartitionSpec()), len(devs))


# -- per-process staging accounting ---------------------------------------

class StagingStats:
    """One launch's staging ledger (a launch = one parity_lazy /
    apply_matrix_lazy batch), or one Run's for the overlap.  Three keys
    have no mechanism left and read 0.0; the benchmark's readers index
    them, so they stay:
    pack_seconds — nothing is copied between the caller's buffer and a put;
    slot_wait_seconds — no semaphore: the caller's buffers bound the windows;
    ready_wait_seconds — a result is fetched by the thread that wants it."""

    __slots__ = ("windows", "direct_windows", "h2d_bytes",
                 "h2d_seconds", "d2h_bytes",
                 "d2h_seconds", "start", "end", "overlap_fraction",
                 "overlap_numer", "overlap_denom", "payload_bytes",
                 "pack_seconds", "slot_wait_seconds",
                 "ready_wait_seconds")

    def __init__(self):
        self.windows = 0
        self.direct_windows = 0    # put as the caller filled them: all
        self.h2d_bytes = 0         # what was sent, padding included
        self.payload_bytes = 0     # the part of it that was asked for
        self.h2d_seconds = 0.0     # put + fence
        self.pack_seconds = 0.0
        self.slot_wait_seconds = 0.0
        self.ready_wait_seconds = 0.0
        self.d2h_bytes = 0
        self.d2h_seconds = 0.0
        self.start = 0.0
        self.end = 0.0
        self.overlap_fraction = 0.0
        self.overlap_numer = 0.0
        self.overlap_denom = 0.0

    def finish(self) -> None:
        """Compute the overlap fraction: 0 = the h2d plane and the
        consume plane (kernel remainder + d2h fetch — the only fence
        async backends offer is the host-side fetch) ran strictly
        serially (wall == sum of both), 1 = fully overlapped (wall ==
        the slower plane alone).  numer/denom are kept so the process
        aggregate can weight runs without re-deriving the math."""
        wall = self.end - self.start
        busy = self.h2d_seconds + self.d2h_seconds
        headroom = busy - max(self.h2d_seconds, self.d2h_seconds)
        if headroom > 1e-9:
            self.overlap_numer = max(0.0, min(busy - wall, headroom))
            self.overlap_denom = headroom
            self.overlap_fraction = self.overlap_numer / headroom
        else:
            self.overlap_numer = self.overlap_denom = 0.0
            self.overlap_fraction = 0.0


_agg_lock = threading.Lock()
_agg = {"launches": 0, "windows": 0, "direct_windows": 0,
        "h2d_bytes": 0,
        "h2d_seconds": 0.0, "d2h_bytes": 0, "d2h_seconds": 0.0,
        "overlap_numer": 0.0, "overlap_denom": 0.0,
        "payload_bytes": 0, "pack_seconds": 0.0,
        "slot_wait_seconds": 0.0, "ready_wait_seconds": 0.0}


def reset_aggregate() -> None:
    with _agg_lock:
        for k in _agg:
            _agg[k] = 0 if isinstance(_agg[k], int) else 0.0


def _note_launch(s: StagingStats) -> None:
    """Fold one consumed launch into the process aggregate; its share
    of the overlap comes with its run (_close_run)."""
    with _agg_lock:
        _agg["launches"] += 1
        for key in _agg:
            if key not in ("launches", "overlap_numer", "overlap_denom"):
                _agg[key] += getattr(s, key)


def _close_run(s: StagingStats, op: str) -> None:
    """finish() a run's ledger (one definition of the overlap), show
    it on the gauge and weigh it into the process aggregate."""
    from .. import profiling
    s.finish()
    profiling.overlap_note(s.overlap_fraction, s.windows, op=op)
    with _agg_lock:
        _agg["overlap_numer"] += s.overlap_numer
        _agg["overlap_denom"] += s.overlap_denom


class Run:
    """The launches of one encode or rebuild, for the overlap: a
    launch overlaps nothing INSIDE itself, launch k+1's put overlaps
    launch k's fetch.  The pipeline that makes the launches hands its
    Run to every *_lazy call and closes it; the overlap is the run's
    wall, first put to last fetch, against its summed h2d and d2h
    seconds.  Launches join as their one consumer
    drains them; `inflight` is how many the pipeline may have staged
    between its reader and its writer."""

    def __init__(self, op: str = "encode"):
        self.op = op
        self.inflight = INFLIGHT
        self.stats = StagingStats()

    def add(self, s: StagingStats) -> None:
        r = self.stats
        r.start = min(r.start, s.start) if r.windows else s.start
        r.end = max(r.end, s.end)
        r.windows += s.windows
        r.h2d_seconds += s.h2d_seconds
        r.d2h_seconds += s.d2h_seconds

    def close(self) -> None:
        if self.stats.windows:
            _close_run(self.stats, self.op)


def snapshot() -> dict:
    """Process-wide aggregate across every staged launch since the
    last reset_aggregate() — what the benchmark takes deltas of and an
    `ec.encode` span carries (windows staged, achieved staged-h2d GB/s,
    overlap fraction weighted over the runs)."""
    with _agg_lock:
        a = dict(_agg)
    a["h2d_gbps"] = round(
        a["h2d_bytes"] / a["h2d_seconds"] / 1e9, 3) \
        if a["h2d_seconds"] > 0 else 0.0
    a["d2h_gbps"] = round(
        a["d2h_bytes"] / a["d2h_seconds"] / 1e9, 3) \
        if a["d2h_seconds"] > 0 else 0.0
    a["overlap_fraction"] = round(
        a["overlap_numer"] / a["overlap_denom"], 3) \
        if a["overlap_denom"] > 0 else 0.0
    return a


# -- the launch ------------------------------------------------------------

class WindowedLaunch:
    """One kernel launch over a [K, W] packed batch, put as it stands.

    Making the launch puts ``flat32`` on the device (sharded where
    encode_shardings() has a mesh that W divides), fences the put and
    dispatches ``kernel(mat_dev, window_dev) -> out32`` — all on the
    calling thread.  ``materialize()`` fetches the [rows, nbytes] uint8
    result on the thread that asks for it, so the fetch of launch k
    overlaps the put of k+1 when a pipeline makes launches ahead.

    Aliasing contract (same as rs_jax.*_lazy): the caller may recycle
    ``flat32`` only after materialize() returns — the kernel has
    consumed the input by the time its output is on the host.
    """

    def __init__(self, mat, flat32: np.ndarray, kernel, out_rows: int,
                 nbytes: int, op: str = "encode",
                 payload_bytes: "int | None" = None,
                 run: "Run | None" = None):
        import jax

        from .. import profiling, tracing
        batch_sh, repl_sh, ndev = encode_shardings()
        if ndev > 1 and flat32.shape[1] % ndev == 0:
            # the constant matrix must be REPLICATED across the mesh:
            # a single-device-committed mat + a mesh-sharded window
            # would be "incompatible devices" to jit
            mat = jax.device_put(np.asarray(mat), repl_sh)
        else:
            batch_sh = None
        self._rows = out_rows
        self._nbytes = nbytes
        self._op = op  # telemetry label: "encode" vs "rebuild"
        self._run = run  # whose overlap this launch counts in
        # the launch's spans hang under the span current NOW: the
        # fetch may be asked for on another thread
        self._trace_ctx = tracing.current_ids()
        s = self.stats = StagingStats()
        self._h2d_wall = time.time()
        s.start = time.perf_counter()
        dev = jax.device_put(flat32, batch_sh)
        dev.block_until_ready()
        s.h2d_seconds = time.perf_counter() - s.start
        s.windows = s.direct_windows = 1
        s.h2d_bytes = flat32.nbytes
        # payload: what the caller says of the batch is volume bytes
        # (the encoder sends a short tail in the full window's shape
        # and knows how much of it it read); without that, the batch
        # less its word padding
        s.payload_bytes = flat32.shape[0] * nbytes \
            if payload_bytes is None else payload_bytes
        profiling.device_note("h2d", flat32.nbytes, s.h2d_seconds)
        self._dispatched = time.perf_counter()
        self._out = kernel(mat, dev)

    def materialize(self) -> np.ndarray:
        """Fetch the [rows, nbytes] uint8 result (the backend's only
        fence: waits out any kernel remainder + the d2h transfer),
        note the ledger, join the run and emit the launch's spans."""
        from .. import profiling
        if self._out is None:
            raise RuntimeError("WindowedLaunch consumed twice")
        s = self.stats
        wall0 = time.time()
        t0 = time.perf_counter()
        host = np.asarray(self._out)
        s.end = time.perf_counter()
        self._out = None
        s.d2h_bytes = host.nbytes
        s.d2h_seconds = s.end - t0
        profiling.device_note("d2h", host.nbytes, s.d2h_seconds)
        profiling.kernel_note("gf_apply_matrix", s.end - self._dispatched)
        _note_launch(s)
        if self._run is not None:
            self._run.add(s)
        else:               # made alone: a run of its own
            _close_run(s, self._op)
        ctx = self._trace_ctx
        if ctx is not None:     # somebody is tracing this launch
            # one batch, under the caller's span; a span's start is
            # the wall clock read at the event, so the spans lie on
            # the clock a device trace is tied to
            from .. import tracing
            base = {"trace_id": ctx[0], "parent": ctx[1], "role": ctx[2]}
            tracing.emit_span_batch([
                dict(base, name="stage.h2d", start=self._h2d_wall,
                     duration=s.h2d_seconds,
                     attrs={"bytes": s.h2d_bytes, "packSeconds": 0.0}),
                dict(base, name="stage.d2h", start=wall0,
                     duration=s.d2h_seconds,
                     attrs={"bytes": s.d2h_bytes})])
        return host.view(np.uint8).reshape(self._rows, -1)[:, :self._nbytes]
