"""Windowed host->device staging for the encode path (ROADMAP item 2:
the end-to-end multi-chip TPU encode).

A one-shot ``device_put`` of a whole volume *serializes* the h2d plane
against the kernel: nothing computes while bytes move, nothing moves
while the kernel runs.  Here three planes run concurrently:

    host window N+1 --device_put--> device        (staging thread)
    device window N --kernel------> parity        (async dispatch)
    device window N-1 --fetch-----> sinks         (consumer thread)

* The unit is a WINDOW of ~``h2d window MB`` staged bytes of a [K, W]
  batch of packed uint32 words (4 GF bytes per word, see ops.rs_jax).
  GF constant-matrix apply is byte-column-independent, so window
  boundaries never change an output byte.
* A batch that is one whole C-contiguous window is put ON THE DEVICE AS
  IT STANDS (a direct window): the EC file pipeline reads each work
  item straight into such a buffer (ec_encoder._encode_work_items), so
  an encode copies nothing on the host between the read and the put.
  A wider batch (a direct caller, the mesh path's wide batches) is cut
  into column windows, each packed (``np.copyto``) into a reused pool
  buffer first.  What the stager sees decides, nothing else.
* The staging thread issues ``jax.device_put``, fences ONLY ITSELF (an
  honest per-window h2d wall without stalling dispatch or fetch) and
  dispatches the kernel for that window.
* Windows in flight: inside a cut launch a semaphore bounds them;
  across direct launches the CALLER's recycled buffers do
  (ec_encoder._staged_run sizes its pool from ``inflight_depth()``).
  Either buffer is reused only after its window's OUTPUT is on the
  host — the aliasing-safe recycle point on backends where
  ``device_put`` may alias host memory (CPU).
* With more than one visible device the window is placed with
  ``NamedSharding(Mesh(jax.devices(), ("batch",)),
  PartitionSpec(None, "batch"))`` — the packed-words batch axis is
  split across the mesh and the jitted kernel runs SPMD with no
  collectives (the apply is columnwise).  A single-device box (or
  ``SEAWEEDFS_TPU_ENCODE_MESH=0``) falls back to plain placement.

Knobs:
  SEAWEEDFS_TPU_H2D_WINDOW_MB   staged bytes per window, and so per
                                work item of a device encode (default
                                32; 0 disables staging -> legacy
                                one-shot device_put)
  SEAWEEDFS_TPU_H2D_INFLIGHT    staged windows in flight (default 2)
  SEAWEEDFS_TPU_ENCODE_MESH     1/0 force mesh sharding on/off
                                (default: on when >1 device)

Telemetry: per-window ``device_note``/``kernel_note`` (profiling.py)
plus an overlap fraction per RUN (the launches of one encode or
rebuild; a launch made alone is its own run) — 0 when the h2d and the
fetch plane ran serially, 1 when the wall from the first put to the
last fetch equals the slower plane alone — surfaced as the
``device_h2d_overlap_fraction`` gauge (cluster.top) and a process-wide
aggregate snapshot() the benchmark takes deltas of.  Per window, two
trace spans under the span that was current when the launch began
(tracing.py, one batch when the launch ends): ``stage.h2d`` (pack +
put + fence) and ``stage.d2h`` (the fetch).  The ledger splits the h2d
seconds into the host pack (microseconds for a direct window) and the
rest, counts direct beside all windows and payload beside padded
bytes, and says which side of the hand-off waited: the stager on a
slot (the consumer is slower) or the consumer on a ready window (the
stager is slower).
"""

from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

DEFAULT_WINDOW_MB = 32.0
DEFAULT_INFLIGHT = 2


def window_bytes() -> int:
    """Staged bytes per window; 0 disables windowing entirely."""
    raw = os.environ.get("SEAWEEDFS_TPU_H2D_WINDOW_MB", "")
    try:
        mb = float(raw) if raw else DEFAULT_WINDOW_MB
    except ValueError:
        mb = DEFAULT_WINDOW_MB
    return max(0, int(mb * (1 << 20)))


def inflight_depth() -> int:
    try:
        d = int(os.environ.get("SEAWEEDFS_TPU_H2D_INFLIGHT",
                               str(DEFAULT_INFLIGHT)))
    except ValueError:
        d = DEFAULT_INFLIGHT
    return max(1, d)


def mesh_enabled() -> bool:
    return os.environ.get("SEAWEEDFS_TPU_ENCODE_MESH", "") != "0"


_shardings_lock = threading.Lock()
_shardings_cache: "dict[tuple, tuple]" = {}


def encode_shardings() -> "tuple[object | None, object | None, int]":
    """(batch_sharding, replicated_sharding, n_devices) for mesh
    placement of [K, W] windows, or (None, None, 1) on the
    single-device fallback (``len(jax.devices()) == 1`` or the mesh
    knob off).  batch_sharding splits axis 1 (the packed-words batch
    axis) across every device; replicated_sharding is for the small
    constant matrix.  Cached: the device set never changes in-process.
    """
    import jax
    devs = jax.devices()
    key = (len(devs), mesh_enabled())
    if len(devs) == 1 or not mesh_enabled():
        return None, None, 1
    with _shardings_lock:
        hit = _shardings_cache.get(key)
        if hit is not None:
            return hit
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.asarray(devs), ("batch",))
        out = (NamedSharding(mesh, PartitionSpec(None, "batch")),
               NamedSharding(mesh, PartitionSpec()), len(devs))
        _shardings_cache[key] = out
        return out


def plan_windows(k: int, w: int, ndev: int
                 ) -> "list[tuple[int, int, int]]":
    """Column-window schedule over a [k, w] packed-words batch:
    [(w0, real_words, padded_words)] tiling [0, w) in order.  Window
    width targets ``window_bytes()`` total staged bytes; padded_words
    rounds the (possibly short tail) window up to a multiple of ndev
    so the batch axis always divides the mesh."""
    wb = window_bytes()
    if wb <= 0 or w == 0:
        return []
    win = max(1, wb // (4 * max(k, 1)))
    win = -(-win // ndev) * ndev
    out = []
    pos = 0
    while pos < w:
        n = min(win, w - pos)
        out.append((pos, n, -(-n // ndev) * ndev))
        pos += n
    return out


# -- reused host staging buffers ------------------------------------------

_pool_lock = threading.Lock()
_buf_pool: "list[np.ndarray]" = []
_POOL_CAP_BUFS = 8
_POOL_CAP_BYTES = 256 << 20


def _take_buf(shape: "tuple[int, int]") -> np.ndarray:
    with _pool_lock:
        for i, b in enumerate(_buf_pool):
            if b.shape == shape:
                return _buf_pool.pop(i)
    return np.empty(shape, dtype=np.uint32)


def _give_buf(buf: np.ndarray) -> None:
    """Return a staging buffer to the pool, bounded GLOBALLY (count
    and bytes) with FIFO eviction — tail-window shapes vary per
    volume, so a per-shape cap alone would grow RSS without bound in
    a long-lived EC worker.  Recently returned buffers are the likely
    active shape; the oldest entries are the stale shapes to drop."""
    with _pool_lock:
        _buf_pool.append(buf)
        total = sum(b.nbytes for b in _buf_pool)
        while _buf_pool and (len(_buf_pool) > _POOL_CAP_BUFS or
                             total > _POOL_CAP_BYTES):
            total -= _buf_pool.pop(0).nbytes


# -- per-process staging accounting ---------------------------------------

class StagingStats:
    """One launch's staging ledger (a launch = one parity_lazy /
    apply_matrix_lazy batch), or one Run's for the overlap."""

    __slots__ = ("windows", "direct_windows", "h2d_bytes",
                 "h2d_seconds", "d2h_bytes",
                 "d2h_seconds", "start", "end", "overlap_fraction",
                 "overlap_numer", "overlap_denom", "payload_bytes",
                 "pack_seconds", "slot_wait_seconds",
                 "ready_wait_seconds")

    def __init__(self):
        self.windows = 0
        self.direct_windows = 0    # put as the caller filled them
        self.h2d_bytes = 0         # what was sent, padding included
        self.payload_bytes = 0     # the part of it that was asked for
        self.h2d_seconds = 0.0     # pack + put + fence
        self.pack_seconds = 0.0    # of it, getting a buffer to put:
        # the np.copyto of a packed window, a glance for a direct one
        self.slot_wait_seconds = 0.0    # stager blocked on a slot
        self.ready_wait_seconds = 0.0   # consumer blocked on a window
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
    launch of one window overlaps nothing INSIDE itself, launch k+1's
    put overlaps launch k's fetch.  The pipeline that makes the
    launches hands its Run to every *_lazy call and closes it; the
    overlap is the run's wall, first put to last fetch, against its
    summed h2d and d2h seconds.  Launches join as their one consumer
    drains them; `inflight` is how many the pipeline may have staged
    between its reader and its writer."""

    def __init__(self, op: str = "encode"):
        self.op = op
        self.inflight = inflight_depth()
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
    last reset_aggregate() — what the bench records next to the e2e
    number (windows staged and how many of them direct, achieved
    staged-h2d GB/s, overlap fraction weighted over the runs)."""
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


# -- the windowed launch ---------------------------------------------------

class _StagingError(Exception):
    """Internal: the launch was aborted before all windows staged."""


class _Stager:
    """The staging thread's whole world: plan, input batch, queues,
    stats.  Deliberately a SEPARATE object from the consumer-facing
    WindowedLaunch so the running thread holds no reference to the
    handle — a handle dropped unconsumed (pipeline unwind) becomes
    garbage, its weakref.finalize fires, and the parked thread exits
    on its next 0.2s tick instead of leaking forever (a thread whose
    target is a bound method of the handle would pin the handle alive
    and the finalizer/__del__ could never run)."""

    def __init__(self, mat, flat32: np.ndarray, kernel, sharding):
        self.mat = mat
        self.flat = flat32
        self.kernel = kernel
        self.sharding = sharding
        self.slots = threading.Semaphore(inflight_depth())
        self.ready: "queue.Queue" = queue.Queue()
        self.stop = threading.Event()
        self.errors: "list[BaseException]" = []
        self.stats = StagingStats()
        self.stats.start = time.perf_counter()
        # [(wall start, seconds, bytes, pack seconds)] per window, for
        # the launch's stage.h2d spans
        self.h2d_windows: "list[tuple]" = []

    def run(self, plan) -> None:
        import jax

        from .. import profiling
        k = self.flat.shape[0]
        try:
            for (w0, n, npad) in plan:
                t_wait = time.perf_counter()
                while not self.slots.acquire(timeout=0.2):
                    if self.stop.is_set():
                        raise _StagingError()
                self.stats.slot_wait_seconds += \
                    time.perf_counter() - t_wait
                wall0 = time.time()
                t0 = time.perf_counter()
                # a batch that is one whole window goes as it stands;
                # the caller keeps it until the launch is consumed
                direct = n == npad == self.flat.shape[1] and \
                    self.flat.flags.c_contiguous
                if direct:
                    buf = self.flat
                else:
                    buf = _take_buf((k, npad))
                    np.copyto(buf[:, :n], self.flat[:, w0:w0 + n])
                t_pack = time.perf_counter() - t0
                # pad columns (mesh divisibility) are left dirty on
                # purpose: the GF apply is column-independent and the
                # consumer slices them off, so stale pool bytes can
                # never reach an output byte.
                dev = jax.device_put(buf, self.sharding) \
                    if self.sharding is not None else \
                    jax.device_put(buf)
                dev.block_until_ready()
                dt = time.perf_counter() - t0
                self.stats.windows += 1
                self.stats.direct_windows += direct
                self.stats.h2d_bytes += buf.nbytes
                self.stats.h2d_seconds += dt
                self.stats.pack_seconds += t_pack
                self.h2d_windows.append((wall0, dt, buf.nbytes, t_pack))
                profiling.device_note("h2d", buf.nbytes, dt)
                t_dispatch = time.perf_counter()
                out = self.kernel(self.mat, dev)
                self.ready.put((w0, n, out, None if direct else buf,
                                t_dispatch))
        except _StagingError:
            pass
        except BaseException as e:  # noqa: BLE001 — re-raised by the
            self.errors.append(e)   # consumer
        finally:
            self.ready.put(None)


class WindowedLaunch:
    """One staged kernel launch over a [K, W] packed batch: one
    direct window, or the batch cut into double-buffered windows.

    ``kernel(mat_dev, window_dev) -> out32`` is dispatched per window
    by the staging thread as soon as that window's transfer fences, so
    dispatch is never gated on the consumer.  ``windows()`` yields
    ``(byte0, uint8[rows, real_bytes])`` in order; the fetch of window
    k overlaps the staging of k+1 and k+2 (depth permitting).

    Aliasing contract (same as rs_jax.*_lazy): the caller may recycle
    ``flat32`` only after the final window is consumed — windows() /
    materialize() returning implies every host->device copy is done.
    """

    def __init__(self, mat, flat32: np.ndarray, kernel, out_rows: int,
                 nbytes: int, op: str = "encode",
                 payload_bytes: "int | None" = None,
                 run: "Run | None" = None):
        import weakref

        from .. import tracing
        batch_sh, repl_sh, ndev = encode_shardings()
        k, w = flat32.shape
        self._rows = out_rows
        self._nbytes = nbytes
        self._op = op  # telemetry label: "encode" vs "rebuild"
        self._run = run  # whose overlap this launch counts in
        self._consumed = False
        # the launch's spans hang under the span current NOW, on the
        # caller's thread: the stager and the consumer run elsewhere
        self._trace_ctx = tracing.current_ids()
        if repl_sh is not None:
            # the constant matrix must be REPLICATED across the mesh:
            # a single-device-committed mat + a mesh-sharded window
            # would be "incompatible devices" to jit
            import jax
            mat = jax.device_put(np.asarray(mat), repl_sh)
        self._s = _Stager(mat, flat32, kernel, batch_sh)
        # payload: what the caller says of the batch is volume bytes
        # (the encoder sends a short tail in the full window's shape
        # and knows how much of it it read); without that, the batch
        # less its word and mesh padding
        self._s.stats.payload_bytes = k * nbytes \
            if payload_bytes is None else payload_bytes
        # dropped-handle backstop: stop the stager when the handle is
        # collected (the thread itself only references the _Stager)
        weakref.finalize(self, self._s.stop.set)
        self._t = threading.Thread(target=self._s.run,
                                   args=(plan_windows(k, w, ndev),),
                                   daemon=True, name="h2d-stager")
        self._t.start()

    @property
    def stats(self) -> StagingStats:
        return self._s.stats

    def windows(self):
        """Yield (byte0, uint8[rows, real_bytes]) in launch order.
        Always drains fully (a partial drain would recycle staging
        buffers the stager still reads); raises the stager's error
        after the drain if it died."""
        from .. import profiling
        if self._consumed:
            raise RuntimeError("WindowedLaunch consumed twice")
        self._consumed = True
        s = self._s
        d2h_windows = []
        try:
            while True:
                t_wait = time.perf_counter()
                item = s.ready.get()
                s.stats.ready_wait_seconds += \
                    time.perf_counter() - t_wait
                if item is None:
                    break
                w0, n, out, buf, t_dispatch = item
                wall0 = time.time()
                t0 = time.perf_counter()
                host = np.asarray(out)  # the backend's only fence:
                # waits out any kernel remainder + the d2h transfer
                dt = time.perf_counter() - t0
                if buf is not None:     # a pool buffer, not the caller's
                    _give_buf(buf)
                s.slots.release()
                s.stats.d2h_bytes += host.nbytes
                s.stats.d2h_seconds += dt
                d2h_windows.append((wall0, dt, host.nbytes))
                profiling.device_note("d2h", host.nbytes, dt)
                profiling.kernel_note("gf_apply_matrix",
                                      t0 + dt - t_dispatch)
                byte0 = 4 * w0
                real = min(self._nbytes - byte0, 4 * n)
                yield byte0, host.view(np.uint8).reshape(
                    self._rows, -1)[:, :real]
            if s.errors:
                raise s.errors[0]
            s.stats.end = time.perf_counter()
            _note_launch(s.stats)
            if self._run is not None:
                self._run.add(s.stats)
            else:               # made alone: a run of its own
                _close_run(s.stats, self._op)
        finally:
            s.stop.set()
            self._emit_spans(d2h_windows)

    def _emit_spans(self, d2h_windows: list) -> None:
        """One batch for the whole launch, under the caller's span:
        a window's start is the wall clock read at the event, so the
        spans lie on the clock a device trace is tied to."""
        from .. import tracing
        ctx = self._trace_ctx
        if ctx is None:
            return      # nobody is tracing this launch
        base = {"trace_id": ctx[0], "parent": ctx[1], "role": ctx[2]}
        tracing.emit_span_batch(
            [dict(base, name="stage.h2d", start=t, duration=dt,
                  attrs={"bytes": nb, "packSeconds": round(pack, 6)})
             for t, dt, nb, pack in self._s.h2d_windows] +
            [dict(base, name="stage.d2h", start=t, duration=dt,
                  attrs={"bytes": nb})
             for t, dt, nb in d2h_windows])

    def materialize(self) -> np.ndarray:
        """Drain every window into one [rows, nbytes] uint8 array."""
        out = np.empty((self._rows, self._nbytes), dtype=np.uint8)
        for byte0, chunk in self.windows():
            out[:, byte0:byte0 + chunk.shape[1]] = chunk
        return out

    def abort(self) -> None:
        """Stop the stager promptly (error unwind path); the parked
        thread exits on its next timeout tick."""
        self._s.stop.set()
