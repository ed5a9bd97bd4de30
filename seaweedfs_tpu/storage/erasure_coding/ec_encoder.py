"""EC encode/rebuild file pipeline
(weed/storage/erasure_coding/ec_encoder.go).

`.dat` -> `.ec00..ecNN`: the volume stream is striped into rows of
data_shards blocks (1GB rows first, then 1MB rows for the tail, zero-
padded past EOF), parity blocks are computed per row, and each block is
appended to its shard file.  The file geometry is identical to the
reference for ANY batch size that divides the block size — the Go path
encodes in 256KB batches (ec_encoder.go:61); the device path's batch is
one staging window (ops.staging, 32MB staged: 3 rows of RS(10,4)),
read straight into the buffer that is put on the device;
outputs are byte-identical.

Rebuild regenerates missing shards from >= data_shards survivors in
1MB steps (ec_encoder.go:323 rebuildEcFiles).
"""

from __future__ import annotations

import os

import numpy as np

from .. import idx as idxmod
from .. import types
from ..volume_info import (EcShardConfig, VolumeInfo,
                           maybe_load_volume_info, save_volume_info)
from .ec_context import (ECContext, LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE,
                         to_ext)  # noqa: F401  (re-exported)


# --- .ecx generation ----------------------------------------------------

def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx"
                               ) -> None:
    """Generate the sorted needle index (ec_encoder.go:31
    WriteSortedFileFromIdx): replay .idx with memdb semantics — a delete
    REMOVES the key entirely (readNeedleMap ec_encoder.go:387-393 routes
    tombstones through MemDb.Delete), so pre-encode deletes never appear
    in .ecx — then write live entries ascending by key."""
    with open(base_file_name + ".idx", "rb") as f:
        live = idxmod.live_entries(f.read())
    entries = sorted(live.items())
    with open(base_file_name + ext, "wb") as out:
        if entries:
            keys = [k for k, _ in entries]
            offs = [o for _, (o, _) in entries]
            sizes = [s for _, (_, s) in entries]
            out.write(idxmod.pack_index(keys, offs, sizes))


# --- encode -------------------------------------------------------------

def write_ec_files(base_file_name: str, ctx: ECContext | None = None,
                   progress=None) -> None:
    """ec_encoder.go:61 WriteEcFiles / :67 WriteEcFilesWithContext.
    `progress(done_bytes, total_bytes)` is called from the write stage
    as volume bytes land in the shard files."""
    ctx = ctx or ECContext()
    _generate_ec_files(base_file_name, ctx, progress=progress)


def write_parity_files(base_file_name: str, ctx: ECContext,
                       progress=None) -> list:
    """The encode of a caller that keeps the `.dat` until the shards
    are sent (the worker's single-volume job): the parity shards go to
    their `.ecNN` files as in write_ec_files, and no data shard is
    written at all.  Returns one shard_sink.DatShardView a data shard,
    by shard id: where in the `.dat` that shard's bytes already lie."""
    from .shard_sink import DatShardView, LocalShardSink
    dat_path = base_file_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    d = ctx.data_shards
    views = [DatShardView(dat_path, dat_size, d, i, LARGE_BLOCK_SIZE,
                          SMALL_BLOCK_SIZE) for i in range(d)]
    parity = [LocalShardSink(base_file_name + ctx.to_ext(i))
              for i in range(d, ctx.total)]
    _generate_ec_files(base_file_name, ctx, sinks=views + parity,
                       progress=progress)
    for sink in parity:
        sink.commit()
    return views


def _encode_work_items(dat_size: int, ctx: ECContext
                       ) -> "list[tuple[int, int, int, int, int]]":
    """The exact batch schedule of ec_encoder.go:280 encodeDatFile
    (1GB rows, then 1MB rows for the tail) as a flat work list of
    (row_start, block_size, batch_offset, batch_bytes, real_rows):

    - large rows (1GB blocks) are chunked WITHIN a block: one item per
      (row, batch-offset), real_rows == 1, and the reader gathers the
      d strided block slices at batch_offset;
    - small rows (1MB blocks) are AGGREGATED: one item covers
      real_rows consecutive rows read contiguously and stacked on the
      batch axis.  batch_bytes is the same for every item, the tail's
      too (ctx.rows_per_launch rows: for a device codec what one
      staging window holds, so every volume of a scheme runs one
      compiled shape); a tail of fewer rows leaves its last columns
      dirty and the writer emits only real_rows * block_size bytes
      per shard.

    Either way the shard files are byte-identical to the reference:
    shard i's file is the in-order concatenation of row blocks i, and
    both chunking-within-a-block and stacking-whole-rows preserve that
    order."""
    work = []
    large_row = LARGE_BLOCK_SIZE * ctx.data_shards
    small_row = SMALL_BLOCK_SIZE * ctx.data_shards
    remaining = dat_size
    processed = 0
    while remaining >= large_row:
        batch = ctx.batch_size(LARGE_BLOCK_SIZE)
        for b0 in range(0, LARGE_BLOCK_SIZE, batch):
            work.append((processed, LARGE_BLOCK_SIZE, b0, batch, 1))
        remaining -= large_row
        processed += large_row
    rows_left = (remaining + small_row - 1) // small_row
    r_full = ctx.rows_per_launch(SMALL_BLOCK_SIZE)
    while rows_left > 0:
        g = min(r_full, rows_left)
        work.append((processed, SMALL_BLOCK_SIZE, 0,
                     r_full * SMALL_BLOCK_SIZE, g))
        rows_left -= g
        processed += g * small_row
    return work


class _Stopped(Exception):
    """Internal: a pipeline stage was asked to abort."""


class _StageTimer:
    """Wraps one pipeline-stage callback to measure its true window:
    wall-clock start of the first call, end of the last call, and
    cumulative busy seconds.  The three stage windows OVERLAP by
    design (the triple-buffered pipeline) — emitted as sibling trace
    spans they show exactly that overlap (tracing.py), which is the
    stage-level timing arXiv:1908.01527 says repair tuning needs."""

    def __init__(self, fn):
        import time as _time
        self._fn = fn
        self._clock = _time.perf_counter
        self._wall = _time.time
        self.start_wall = 0.0
        self.first = 0.0
        self.last = 0.0
        self.busy = 0.0
        self.calls = 0

    def __call__(self, *args):
        t0 = self._clock()
        if not self.calls:
            self.first = t0
            self.start_wall = self._wall()
        try:
            return self._fn(*args)
        finally:
            t1 = self._clock()
            self.busy += t1 - t0
            self.last = t1
            self.calls += 1

    def emit(self, name: str, trace_ctx, **attrs) -> None:
        """Record the stage window as a trace span parented to the
        span active when the rebuild started (`trace_ctx` from
        tracing.current_ids() — stages ran on other threads, so the
        contextvar cannot be relied on here).  A device codec stage's
        busySeconds are its puts (ops.staging); the kernel runs behind
        them and is fetched in the write stage."""
        if not self.calls:
            return
        from ... import tracing
        attrs.update(calls=self.calls, busySeconds=round(self.busy, 6))
        tracing.emit_span(
            name, self.start_wall, self.last - self.first,
            role=trace_ctx[2] if trace_ctx else "",
            parent=trace_ctx[1] if trace_ctx else "",
            trace_id=trace_ctx[0] if trace_ctx else "",
            attrs=attrs)


class _OverlappedFlusher:
    """Background thread that round-robins flush+fdatasync over the
    output files while the pipeline runs, so disk/network flush
    overlaps reads+compute instead of serializing after them.  Without
    it the whole 1.4x shard output sits in page cache until a final
    fsync — measured as 50% of e2e encode wall-clock on a 1GB volume
    (and sync_file_range is a silent no-op on network filesystems like
    the v9fs this was measured on, so a real fdatasync from a side
    thread is the only portable overlap).  Flush errors are latched and
    re-raised by stop(): a failing disk must fail the encode, not be
    swallowed by the helper thread."""

    def __init__(self, files, interval: float = 0.05):
        import threading
        self._files = list(files)
        self._interval = interval
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        import os as _os
        while not self._stop.wait(self._interval):
            for f in self._files:
                if self._stop.is_set():
                    return
                try:
                    f.flush()
                    _os.fdatasync(f.fileno())
                except ValueError:  # closed under us at teardown
                    return
                except OSError as e:
                    self._error = e
                    return

    def stop(self, final: bool = True):
        """Join the flusher; when `final`, leave every file durably
        flushed and raise the first flush error, if any.  With
        final=False (pipeline already failing) latched errors are
        dropped so this never masks the caller's original exception."""
        import os as _os
        self._stop.set()
        self._t.join()
        if not final:
            return
        if self._error is not None:
            raise self._error
        for f in self._files:
            f.flush()
            _os.fdatasync(f.fileno())


def _staged_run(work, read_item, compute, write_item, run=None) -> None:
    """Triple-buffered staging pipeline (SURVEY §7 "hard parts" #2),
    shared by encode and rebuild: a reader thread stages disk batches
    into host buffers, the calling thread runs the GF kernel (device
    round-trip on the TPU backend), and a writer thread appends to the
    shard files — so disk reads, the codec, and disk writes overlap
    instead of serializing.

    read_item(item, buf) -> payload: fill (or replace) the recycled
    buffer; the payload's FIRST element must be the buffer to recycle.
    compute(payload) -> result: a host array, or a device launch
    (ops.staging: put and dispatched here, not waited for).
    write_item(payload, result) -> None: append to the output files;
    it takes the result through _on_host, so D2H of launch k overlaps
    H2D+kernel of k+1, and the buffer is recycled only after it
    returns — the aliasing contract of *_lazy: the kernel has consumed
    the buffer once its output is on the host.

    Host memory is bounded by a pool of recycled buffers: one being
    read, one being written and between them 1 in a host codec, or in
    a device codec the staged windows in flight of `run` (_device_run:
    a device batch is one window, so this pool is what bounds them;
    the run is closed here), so peak RSS stays a few batches
    instead of growing with queue depth.  A shared stop event unblocks
    every stage on any error or interrupt: a parked producer can never
    deadlock the join, and a writer failure (ENOSPC) aborts the read +
    compute stages promptly rather than after the whole volume.
    Output append order is preserved because every stage is FIFO."""
    import queue
    import threading

    q_read: "queue.Queue" = queue.Queue()
    q_write: "queue.Queue" = queue.Queue()
    pool: "queue.Queue" = queue.Queue()
    for _ in range((run.inflight if run else 1) + 2):
        pool.put(None)  # lazy-allocated buffer slots
    stop = threading.Event()
    errors: list[BaseException] = []

    def _blocking(q_op, *args):
        """put/get that stays interruptible by the stop event; returns
        the result or raises _Stopped."""
        while True:
            try:
                return q_op(*args, timeout=0.2)
            except (queue.Full, queue.Empty):
                if stop.is_set():
                    raise _Stopped() from None

    def reader():
        try:
            for item in work:
                buf = _blocking(pool.get)
                _blocking(q_read.put, read_item(item, buf))
        except _Stopped:
            pass
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
            stop.set()
        finally:
            q_read.put(None)

    def writer():
        try:
            while True:
                item = _blocking(q_write.get)
                if item is None:
                    return
                write_item(*item)
                pool.put(item[0][0])  # recycle the slot for the reader
        except _Stopped:
            pass
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            stop.set()  # abort reader+compute promptly (don't encode
            # the rest of a 30GB volume just to report ENOSPC)

    rt = threading.Thread(target=reader, daemon=True)
    wt = threading.Thread(target=writer, daemon=True)
    rt.start()
    wt.start()
    try:
        while not stop.is_set():
            payload = q_read.get()
            if payload is None:
                break
            q_write.put((payload, compute(payload)))
    except BaseException as e:  # noqa: BLE001 — incl. KeyboardInterrupt
        errors.insert(0, e)
    finally:
        stop.set()  # unblocks any parked stage (timeouted puts/gets)
        q_write.put(None)
        rt.join()
        wt.join()
        if run is not None:
            run.close()
    if errors:
        raise errors[0]


def _on_host(result) -> np.ndarray:
    """A codec stage's result as a host array: a device launch is
    fetched here, on the caller's thread; a host codec's is there."""
    return result.materialize() if hasattr(result, "materialize") \
        else result


def _device_run(lazy, op: str):
    """The staging.Run that a device codec's launches of one pipeline
    count their overlap in, and that says how many of them may be in
    flight; None for a host codec."""
    if lazy is None:
        return None
    from ...ops import staging
    return staging.Run(op)


def _generate_ec_files(base_file_name: str, ctx: ECContext,
                       sinks: "list | None" = None,
                       stats=None, progress=None) -> None:
    """Staged encode: .dat batches -> GF parity -> d+p shard streams.

    `sinks` (shard_sink.ShardSink, one per shard id) parameterizes the
    write stage: None keeps the seed semantics (LocalShardSink per
    `.ecNN` file on this node), the scatter path passes RemoteShardSink
    streams to each shard's placement target, write_parity_files a
    DatShardView for each data shard, which is handed no rows: the
    shard is read out of the `.dat` itself.  Ownership transfers
    either way: on success every sink is finish()ed (delivery
    verified), on failure every sink is abort()ed (staged bytes
    discarded — a failed encode leaves no partial shard for discovery
    to mistake for a real one).  COMMIT remains the caller's step:
    sidecars must land on the destinations before shards become
    visible."""
    from .shard_sink import LocalShardSink, ScatterStats
    dat_path = base_file_name + ".dat"
    dat_size = os.path.getsize(dat_path)
    codec = ctx.create_codec()
    d = ctx.data_shards
    work = _encode_work_items(dat_size, ctx)
    own_sinks = sinks is None
    if sinks is None:
        sinks = [LocalShardSink(base_file_name + ctx.to_ext(i))
                 for i in range(ctx.total)]
    if stats is None:
        stats = ScatterStats()
    for s in sinks:
        if hasattr(s, "set_stats"):
            s.set_stats(stats)
    dat = open(dat_path, "rb")

    def read_item(item, buf):
        row_start, block_size, b0, batch, real_rows = item
        if buf is None or buf.shape != (d, batch):
            buf = np.empty((d, batch), dtype=np.uint8)
        # NO full-buffer memset (the same lesson the rebuild reader
        # learned): only short/EOF read TAILS are zeroed — that is the
        # reference's zero-padding (ec_encoder.go:258-262) and the
        # only region whose stale recycled-buffer bytes could reach
        # the output.  Rows padded past real_rows (device-shape
        # padding) are left dirty on purpose: the GF apply is
        # byte-column-independent and the writer truncates at `real`,
        # so their content can never affect an emitted byte.
        from_dat = 0    # volume bytes in this batch, no fill, no pad
        if batch <= block_size:
            # chunk WITHIN one (large) row: gather the d strided
            # block slices at batch offset b0
            for i in range(d):
                dat.seek(row_start + i * block_size + b0)
                got = dat.readinto(memoryview(buf[i])[:batch])
                from_dat += got
                if got < batch:
                    buf[i, got:] = 0
        else:
            # real_rows stacked small rows: one strictly sequential
            # pass over the contiguous region
            dat.seek(row_start)
            for r in range(real_rows):
                base = r * block_size
                for i in range(d):
                    got = dat.readinto(
                        memoryview(buf[i])[base:base + block_size])
                    from_dat += got
                    if got < block_size:
                        buf[i, base + got:base + block_size] = 0
        real = min(batch, real_rows * block_size)
        return (buf, real, from_dat)

    lazy = getattr(codec, "parity_lazy", None)
    run = _device_run(lazy, "encode")

    def compute(payload):
        buf, _real, from_dat = payload
        if lazy is not None:
            # put and dispatched as the reader filled it, not waited
            # for: the writer fetches
            return lazy(buf, payload_bytes=from_dat, run=run)
        return np.ascontiguousarray(np.asarray(codec.parity(buf)))

    written = 0  # volume bytes whose d+p shard slices reached the sinks

    def note(done):
        if progress is not None:
            progress(min(done, dat_size), dat_size)

    # a data shard that is read out of the .dat itself (DatShardView)
    # is handed no rows
    row_sinks = [(i, s) for i, s in enumerate(sinks[:d]) if s.stores_rows]

    def write_item(payload, parity):
        nonlocal written
        buf, real, _from_dat = payload
        for i, sink in row_sinks:
            sink.write(buf[i, :real].data)
        # the data rows first: a device launch's fetch runs under them
        parity = _on_host(parity)
        for j in range(ctx.total - d):
            sinks[d + j].write(parity[j, :real].data)
        written += d * real
        # per work item: a launch behind a cold compile is the longest
        # silence a job has, and the admin presumes a silent worker dead
        note(written)

    # stage spans (tracing.py): capture the caller's span context NOW
    # — the reader/writer stages run on pipeline threads where the
    # contextvar does not follow.  encode.read / encode.codec /
    # encode.write windows OVERLAP by design (the triple buffer);
    # per-destination encode.scatter.<sid> spans come from the remote
    # sinks' send threads.
    from ... import tracing
    trace_ctx = tracing.current_ids()
    read_item = _StageTimer(read_item)
    compute = _StageTimer(compute)
    write_item = _StageTimer(write_item)

    flusher = _OverlappedFlusher(
        [s.file for s in sinks if hasattr(s, "file")])
    ok = False
    try:
        _staged_run(work, read_item, compute, write_item, run)
        for s in sinks:
            s.end_stream()   # all tail chunks + receiver responses
        for s in sinks:      # drain concurrently, then verify each
            s.finish()
        ok = True
    finally:
        dat.close()
        try:
            flusher.stop(final=ok)
        except Exception:
            ok = False
            raise
        finally:
            if not ok:
                for s in sinks:
                    try:
                        s.abort()
                    except OSError:
                        pass
            elif own_sinks:
                # seed semantics: local files land in place now; the
                # scatter caller commits AFTER pushing sidecars
                for s in sinks:
                    s.commit()
            by_dest = stats.snapshot()[0]
            read_item.emit("encode.read", trace_ctx,
                           datBytes=dat_size, windows=len(work))
            compute.emit("encode.codec", trace_ctx, dataShards=d,
                         parityShards=ctx.total - d, backend=ctx.backend)
            write_item.emit("encode.write", trace_ctx,
                            bytesByDest=by_dest, aborted=not ok)


# --- rebuild ------------------------------------------------------------

def scheme_from_vif(base_file_name: str) -> ECContext | None:
    """Recover the EC scheme persisted to .vif
    (server/volume_grpc_erasure_coding.go:132); None when absent or
    recorded without a scheme.  The single recovery point for every
    consumer (rebuild, decode-to-volume, shell)."""
    vi = maybe_load_volume_info(base_file_name + ".vif")
    if vi is not None and vi.ec_shard_config is not None and \
            vi.ec_shard_config.data_shards:
        return ECContext(vi.ec_shard_config.data_shards,
                         vi.ec_shard_config.parity_shards)
    return None


def rebuild_ec_files(base_file_name: str, ctx: ECContext | None = None,
                     additional_dirs: list[str] | None = None
                     ) -> list[int]:
    """ec_encoder.go:74 RebuildEcFiles: recover the scheme from .vif,
    then regenerate missing shard files from survivors.  Returns the
    generated shard ids."""
    if ctx is None:
        ctx = scheme_from_vif(base_file_name) or ECContext()
    return _generate_missing_ec_files(
        base_file_name, ctx, additional_dirs or [])


def _find_shard_file(base_file_name: str, ext: str,
                     additional_dirs: list[str]) -> str | None:
    """ec_encoder.go:131 findShardFile: primary path, then extra dirs."""
    primary = base_file_name + ext
    if os.path.exists(primary):
        return primary
    base = os.path.basename(base_file_name)
    for d in additional_dirs:
        cand = os.path.join(d, base + ext)
        if os.path.exists(cand):
            return cand
    return None


def discover_shard_files(base_file_name: str, ctx: ECContext,
                         additional_dirs: list[str]
                         ) -> "tuple[dict[int, str], list[int]]":
    """(present shard paths by id, locally-missing shard ids) — the
    discovery half of the two-pass rebuild (ec_encoder.go:146), shared
    with the streaming server handler which fills the gaps with remote
    sources instead of erroring."""
    present_paths: dict[int, str] = {}
    missing: list[int] = []
    for sid in range(ctx.total):
        p = _find_shard_file(base_file_name, ctx.to_ext(sid),
                             additional_dirs)
        if p is not None:
            present_paths[sid] = p
        else:
            missing.append(sid)
    return present_paths, missing


def _generate_missing_ec_files(base_file_name: str, ctx: ECContext,
                               additional_dirs: list[str]) -> list[int]:
    """Two-pass discover-then-create (ec_encoder.go:146), local files
    only — every survivor must already be on this node's disks."""
    present_paths, missing = discover_shard_files(
        base_file_name, ctx, additional_dirs)
    if len(present_paths) < ctx.data_shards:
        raise ValueError(
            f"not enough shards to rebuild {base_file_name}: found "
            f"{len(present_paths)}, need {ctx.data_shards}, "
            f"missing {missing}")
    if not missing:
        return []
    from .shard_source import LocalShardSource
    sources = {sid: LocalShardSource(p)
               for sid, p in present_paths.items()}
    return rebuild_from_sources(base_file_name, ctx, sources, missing)


def rebuild_from_sources(base_file_name: str, ctx: ECContext,
                         sources: dict, missing: list[int],
                         stats=None, slice_bytes: int | None = None,
                         shard_size: int | None = None) -> list[int]:
    """Regenerate `missing` shard files from survivor `sources`
    ({shard_id: ShardSource}) through the staged pipeline: a
    MultiSourceFetcher streams slice windows (one concurrent ranged
    stream per prefetching source), the GF kernel applies the
    reconstruction matrix, and the writer appends to the new shard
    files — fetch, codec, and writes overlap end to end.  Slice
    boundaries never change output bytes (the GF apply is
    byte-independent), so this is byte-identical to the local
    collect-then-rebuild path for any window size.  Closes every
    source."""
    from ...ops import rs_matrix
    from .shard_source import MultiSourceFetcher
    outputs: dict = {}
    fetcher = None
    try:
        if len(sources) < ctx.data_shards:
            raise ValueError(
                f"not enough shards to rebuild {base_file_name}: "
                f"found {len(sources)}, need {ctx.data_shards}, "
                f"missing {missing}")
        codec = ctx.create_codec()
        # One matrix maps the first data_shards survivors directly
        # onto ALL missing rows (data and parity targets alike), so
        # each step is a single [len(missing), d] x [d, batch] apply
        # over only the bytes that are actually regenerated — no
        # full-array copies.
        present_mask = tuple(sid in sources
                             for sid in range(ctx.total))
        rec_matrix, survivor_rows = \
            rs_matrix.cached_reconstruction_matrix(
                ctx.data_shards, ctx.parity_shards, present_mask,
                tuple(missing))
        used = {sid: sources[sid] for sid in survivor_rows}
        for sid in sources:
            if sid not in used:  # survivors beyond the first d: unused
                sources[sid].close()
        if shard_size is None:
            # every shard file is the same length by construction, so
            # a caller holding ANY shard passes the size and spares
            # one metadata round-trip per remote source (they were
            # serial and measurably front-loaded the repair)
            shard_size = max(src.size() for src in used.values())
        for sid in missing:
            outputs[sid] = open(base_file_name + ctx.to_ext(sid), "wb")
        if slice_bytes:
            # `slice_bytes` caps the window; small shards get windows
            # cut to ~1/8 of the shard (floor 1MB, or the explicit cap
            # when smaller) so the per-source prefetch pipelines
            # actually overlap fetch with compute instead of
            # degenerating to one or two giant slices
            step = max(min(slice_bytes, -(-shard_size // 8)),
                       min(slice_bytes, 1 << 20))
        else:
            step = ctx.batch_size(LARGE_BLOCK_SIZE)
        work = [(pos, min(step, shard_size - pos))
                for pos in range(0, shard_size, step)]
        d = ctx.data_shards
        fetcher = MultiSourceFetcher(used, work, stats=stats)
    except BaseException:
        # setup failed before the pipeline owned these resources: a
        # retrying caller (worker cron) must not leak one fd set per
        # attempt, nor leave empty target files for discovery to
        # mistake for survivors
        if fetcher is not None:
            fetcher.close()
        else:
            for src in sources.values():
                src.close()
        for sid, f in outputs.items():
            f.close()
            try:
                os.remove(base_file_name + ctx.to_ext(sid))
            except OSError:
                pass
        raise

    def read_item(item, buf):
        pos, n = item
        if buf is None or buf.shape != (d, n):
            buf = np.empty((d, n), dtype=np.uint8)
        # every source fills its staging row in place (local files
        # readinto it directly; remote windows are copied once out of
        # a recycled receive buffer).  Only the short tail of a row is
        # zeroed (EOF zero-padding, ec_encoder.go:258-262) — a
        # full-buffer memset per window was measurably the pipeline's
        # single largest memory cost.
        filled = fetcher.get(
            item, rows={sid: memoryview(buf[row])
                        for row, sid in enumerate(survivor_rows)})
        for row, sid in enumerate(survivor_rows):
            got = filled[sid]
            if got < n:
                buf[row, got:] = 0
        return (buf, n)

    lazy = getattr(codec, "apply_matrix_lazy", None)
    run = _device_run(lazy, "rebuild")

    def compute(payload):
        buf, _n = payload
        if lazy is not None:
            return lazy(rec_matrix, buf, run=run)
        return np.ascontiguousarray(
            np.asarray(codec.apply_matrix(rec_matrix, buf)))

    def write_item(payload, rec):
        _buf, n = payload
        rec = _on_host(rec)
        for row, sid in enumerate(missing):
            outputs[sid].write(rec[row, :n].data)

    # stage spans (tracing.py): capture the caller's span context NOW
    # — the reader/writer stages run on pipeline threads where the
    # contextvar does not follow
    from ... import tracing
    trace_ctx = tracing.current_ids()
    read_item = _StageTimer(read_item)
    compute = _StageTimer(compute)
    write_item = _StageTimer(write_item)

    flusher = _OverlappedFlusher(outputs.values())
    ok = False
    try:
        _staged_run(work, read_item, compute, write_item, run)
        ok = True
    finally:
        try:
            flusher.stop(final=ok)
        finally:
            fetcher.close()  # joins prefetch threads, closes sources
            for sid, f in outputs.items():
                f.close()
                if not ok:
                    # a truncated .ecNN left behind would be counted
                    # as a SURVIVOR by the next rebuild's discovery —
                    # failed repairs must leave no partial targets
                    try:
                        os.remove(base_file_name + ctx.to_ext(sid))
                    except OSError:
                        pass
            by_source = stats.snapshot()[0] if stats is not None \
                else {}
            read_item.emit("rebuild.fetch", trace_ctx,
                           bytesBySource=by_source,
                           windows=len(work), sliceBytes=step)
            compute.emit("rebuild.codec", trace_ctx,
                         missingShards=list(missing),
                         dataShards=ctx.data_shards)
            write_item.emit("rebuild.write", trace_ctx,
                            bytesWritten=len(missing) * shard_size,
                            aborted=not ok)
    return missing


def save_ec_volume_info(base_file_name: str, ctx: ECContext,
                        dat_file_size: int, version: int) -> None:
    """Persist the EC scheme to .vif so rebuild/decode can recover it
    (server/volume_grpc_erasure_coding.go:132)."""
    vi = maybe_load_volume_info(base_file_name + ".vif") or VolumeInfo()
    vi.version = version
    vi.dat_file_size = dat_file_size
    vi.ec_shard_config = EcShardConfig(ctx.data_shards, ctx.parity_shards)
    save_volume_info(base_file_name + ".vif", vi)
