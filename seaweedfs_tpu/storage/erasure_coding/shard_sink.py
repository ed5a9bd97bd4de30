"""Pluggable encode outputs: where freshly-encoded shard slices go.

The scatter-encode path ("the I/O funnel, not the codec, bounds online
erasure coding" — arXiv:1709.05365; the mirror image of PR 2's repair
pipelining, arXiv:1908.01527) replaces encode-locally-then-balance —
write all d+p shard files on the source node's disks, then have
`ec.balance` re-read and re-write most of them a second time to move
them off — with a slice pipeline OUT of the GF kernel: each shard's
output windows stream through a ShardSink (a local file when the shard
is placed on this node, ONE long chunked `/admin/ec/shard_write` HTTP
stream when it is placed remotely), one concurrent send thread per
remote destination with a bounded in-flight queue and recycled
buffers.  Shards destined elsewhere never touch the source disk, so
the source's 1.4x shard write amplification collapses to the sidecar
files only (~0.07x) and aggregate write bandwidth becomes the SUM of
the destinations' disks.

Commit protocol (the no-partial-stripe invariant): the receiver
streams each shard into a `.scatter.<uploadId>` temp file with an
incremental CRC32 and registers it UNMOUNTED; only an explicit
`shard_write_commit` carrying the sender's own running CRC renames it
to its final `.ecNN` name (and optionally mounts it).  Any failure —
sender, receiver, or wire — leaves nothing but an unregistered temp
file, which the receiver removes; a stripe is only ever visible whole.

Memory stays bounded by sinks x (inflight + 1) x window bytes: the
defaults (16MB windows, 2 in flight) keep a 14-shard scatter under
~0.7GB of staged slices.
"""

from __future__ import annotations

import os
import queue
import threading
import time
import uuid
import zlib


def scatter_window_bytes() -> int:
    """Send window per destination stream.  The GF apply is
    byte-independent so the window never changes output bytes; bigger
    windows amortize chunk framing, smaller ones bound staging RAM.
    SEAWEEDFS_TPU_EC_SCATTER_WINDOW_MB overrides."""
    try:
        mb = int(os.environ.get("SEAWEEDFS_TPU_EC_SCATTER_WINDOW_MB",
                                "16"))
    except ValueError:
        mb = 16
    return max(1, min(mb, 1024)) << 20


def scatter_inflight_depth() -> int:
    """Windows queued ahead per destination stream (>= 2 so the send of
    window k overlaps the codec on k+1 even when one destination
    hiccups).  SEAWEEDFS_TPU_EC_SCATTER_INFLIGHT overrides."""
    try:
        d = int(os.environ.get("SEAWEEDFS_TPU_EC_SCATTER_INFLIGHT", "2"))
    except ValueError:
        d = 2
    return max(1, d)


class ScatterStats:
    """Per-encode telemetry accumulator: bytes pushed per destination,
    window send latencies, local bytes.  Thread-safe (send threads
    record concurrently); summarized once at the end."""

    def __init__(self):
        self._lock = threading.Lock()
        self.bytes_by_dest: dict[str, int] = {}
        self.local_bytes = 0
        self.latencies: list[float] = []
        self.windows = 0

    def record(self, dest: str, nbytes: int, seconds: float) -> None:
        with self._lock:
            self.bytes_by_dest[dest] = \
                self.bytes_by_dest.get(dest, 0) + nbytes
            self.latencies.append(seconds)
            self.windows += 1

    def record_local(self, nbytes: int) -> None:
        with self._lock:
            self.local_bytes += nbytes

    def snapshot(self) -> "tuple[dict[str, int], list[float], int]":
        with self._lock:
            return (dict(self.bytes_by_dest), list(self.latencies),
                    self.local_bytes)

    @staticmethod
    def _pct(sorted_vals: "list[float]", q: float) -> float:
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
        return sorted_vals[i]

    def summary(self, volume_bytes: int, wall_seconds: float) -> dict:
        """JSON-able summary; `volume_bytes` is the .dat size (how
        `weed shell` encode throughput is judged everywhere else)."""
        with self._lock:
            lats = sorted(self.latencies)
            by_dest = dict(self.bytes_by_dest)
            local = self.local_bytes
        total = sum(by_dest.values())
        wall = max(wall_seconds, 1e-9)
        return {
            "bytesScatteredByDest": by_dest,
            "bytesScatteredTotal": total,
            "localWriteBytes": local,
            "windows": self.windows,
            "windowP50Ms": round(self._pct(lats, 0.50) * 1e3, 3),
            "windowP95Ms": round(self._pct(lats, 0.95) * 1e3, 3),
            "wallSeconds": round(wall, 3),
            "scatterGbps": round(total / wall / 1e9, 6),
            "volumeGbps": round(volume_bytes / wall / 1e9, 6),
        }


class ShardSink:
    """One shard's ordered byte stream to wherever placement put it.

    Lifecycle: write(window)* -> finish() -> commit(); abort() on any
    failure; close() is idempotent and aborts anything unfinished, so
    `with` / close-in-finally is always safe (SWFS008)."""

    label = "?"
    # False for a sink whose bytes are already where it will be read
    # from (DatShardView): the encode then hands it no rows
    stores_rows = True

    def write(self, data) -> None:
        """Append one window (bytes/memoryview).  The buffer may be
        recycled by the caller as soon as write() returns."""
        raise NotImplementedError

    def end_stream(self) -> None:
        """Signal that no more windows are coming, WITHOUT waiting for
        delivery — call this on every sink first, then finish() each:
        all the tail chunks and receiver responses then overlap instead
        of serializing one stream-drain per sink."""

    def finish(self) -> None:
        """End the stream and verify delivery (remote: join the send
        thread, check the receiver's byte count + CRC against the
        sender's running CRC)."""

    def commit(self, mount: bool = False) -> None:
        """Make the shard visible at its final name (remote: the
        receiver's atomic rename, optionally mount-on-commit)."""

    def abort(self) -> None:
        """Tear the stream down and discard anything staged."""

    def close(self) -> None:
        pass

    def __enter__(self) -> "ShardSink":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class LocalShardSink(ShardSink):
    """A shard file on this node's disks — the seed's only output.
    `temp=True` (the scatter path) stages to a `.scatter.<id>` sibling
    and renames on commit, matching the remote sink's
    nothing-visible-until-commit contract; `temp=False` keeps the
    seed's write-in-place semantics byte-for-byte."""

    label = "local"

    def __init__(self, path: str, temp: bool = False,
                 stats: "ScatterStats | None" = None):
        self.path = path
        self._final = path
        if temp:
            self.path = f"{path}.scatter.{uuid.uuid4().hex}"
        self._stats = stats
        self.file = open(self.path, "wb")
        self.bytes = 0
        self._committed = False
        self._closed = False

    def write(self, data) -> None:
        self.file.write(data)
        n = len(data)
        self.bytes += n
        if self._stats is not None:
            self._stats.record_local(n)

    def finish(self) -> None:
        # flush only: durability comes from the encode pipeline's
        # _OverlappedFlusher, which covers every local sink's file and
        # fdatasyncs on its final stop — a second sync here would
        # serialize 14 fsyncs after the pipeline already overlapped them
        self.file.flush()

    def commit(self, mount: bool = False) -> None:
        self.file.close()
        self._closed = True
        if self.path != self._final:
            os.replace(self.path, self._final)
        self._committed = True

    def abort(self) -> None:
        if not self._closed:
            self.file.close()
            self._closed = True
        if not self._committed:
            try:
                os.remove(self.path)
            except OSError:
                pass

    def close(self) -> None:
        if self._closed:
            return
        if self._committed:
            self.file.close()
            self._closed = True
        else:
            self.abort()


class DatShardView(ShardSink):
    """A data shard that is never written: block `shard_id` of every
    row of the `.dat` the encode reads, plus zeros past its end
    (ec_locate.data_shard_ranges), for a caller that keeps the `.dat`
    beside the parity files until the shards have been sent.  As a sink
    it takes no rows (`stores_rows`), so the encode's writer leaves a
    window's data rows where the reader put them, and it has no `file`
    for the flusher; as a source it is `dat_path`, `pieces` and `size`,
    which `httpd.http_upload` sends as ranges of the one file."""

    label = "dat"
    stores_rows = False

    def __init__(self, dat_path: str, dat_size: int, data_shards: int,
                 shard_id: int, large_block_size: int,
                 small_block_size: int):
        from .ec_locate import data_shard_ranges
        self.dat_path = dat_path
        self.shard_id = shard_id
        self.pieces = data_shard_ranges(
            large_block_size, small_block_size, dat_size, data_shards,
            shard_id)
        self.size = sum(got + zeros for _, got, zeros in self.pieces)

    def write(self, data) -> None:
        pass


class _SinkAborted(Exception):
    """The sink was aborted while a stage was parked on its queue."""


class RemoteShardSink(ShardSink):
    """One shard streamed to its placement target as a single long
    chunked `POST /admin/ec/shard_write` — a dedicated send thread per
    destination pulls windows off a bounded queue (backpressure: the
    pipeline's writer stage blocks when a destination falls more than
    `depth` windows behind) with recycled send buffers, so the hot
    loop allocates nothing after warm-up.  The sender keeps a running
    CRC32; finish() verifies the receiver saw the same byte count and
    CRC, commit() performs the receiver-side atomic rename (+ mount)."""

    def __init__(self, url: str, vid: int, sid: int,
                 collection: str = "", headers=None,
                 timeout: float = 600.0, depth: int | None = None,
                 window_bytes: int | None = None):
        self.url = url
        self.vid = vid
        self.sid = sid
        self.collection = collection
        self.label = url
        self.upload_id = uuid.uuid4().hex
        self._headers = headers or (lambda: {})
        self._timeout = timeout
        self._window = window_bytes or scatter_window_bytes()
        depth = depth or scatter_inflight_depth()
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._pool: "queue.Queue" = queue.Queue()
        for _ in range(depth + 1):
            self._pool.put(None)  # lazy-allocated slots
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._response: dict | None = None
        self._cur: "bytearray | None" = None  # coalescing buffer
        self._fill = 0
        self.bytes = 0
        self.crc = 0
        self._committed = False
        self._finished = False
        self._truncated = False  # armed truncate fault fired mid-send
        self._stats: "ScatterStats | None" = None
        # span context of the caller (the scatter handler): the send
        # thread emits one per-destination stream span, and the
        # contextvar does not follow threading.Thread (tracing.py)
        from ... import tracing
        self._trace_ctx = tracing.current_ids()
        self._t = threading.Thread(target=self._send_loop, daemon=True)
        self._t.start()

    def set_stats(self, stats: "ScatterStats | None") -> None:
        self._stats = stats

    # -- producer side (pipeline writer stage) -------------------------

    def _take_slot(self):
        while True:
            try:
                b = self._pool.get(timeout=0.2)
                return b
            except queue.Empty:
                if self._stop.is_set() or self._error is not None:
                    raise self._error or _SinkAborted() from None

    def _put(self, item) -> None:
        while True:
            try:
                self._q.put(item, timeout=0.2)
                return
            except queue.Full:
                if self._stop.is_set() or self._error is not None:
                    raise self._error or _SinkAborted() from None

    def write(self, data) -> None:
        """COALESCES small writes up to the send window: the encode
        pipeline produces one block-sized slice per work item (1MB on
        the CPU backend), and enqueueing each separately costs a
        queue hop + chunk frame + socket wakeup per MB — batching to
        the window (16MB default) amortizes all three."""
        mv = memoryview(data)
        off = 0
        while off < len(mv):
            if self._error is not None:
                raise self._error
            if self._cur is None:
                b = self._take_slot()
                if b is None or len(b) != self._window:
                    b = bytearray(self._window)
                self._cur = b
                self._fill = 0
            take = min(len(mv) - off, self._window - self._fill)
            piece = mv[off:off + take]
            self._cur[self._fill:self._fill + take] = piece
            self.crc = zlib.crc32(piece, self.crc)
            self.bytes += take
            self._fill += take
            off += take
            if self._fill == self._window:
                self._put((self._cur, self._fill))
                self._cur = None

    def _flush_partial(self) -> None:
        if self._cur is not None and self._fill:
            self._put((self._cur, self._fill))
            self._cur = None
            self._fill = 0

    # -- send thread ----------------------------------------------------

    def _chunks(self):
        """Generator the chunked-POST body pulls from: windows off the
        queue until the None sentinel.  Wire time per window (the gap
        between yields, minus queue wait) is recorded so a slow codec
        never shows up as a slow destination."""
        from ... import faults
        while True:
            try:
                item = self._q.get(timeout=0.2)
            except queue.Empty:
                if self._stop.is_set():
                    raise _SinkAborted() from None
                continue
            if item is None:
                return
            buf, n = item
            # background-priority pacing (qos.py): while foreground
            # request_seconds p99 violates the SLO, each window waits
            # the throttle's pace before touching the wire — the
            # bounded queue backpressures the codec stage behind it
            from ... import qos
            qos.ec_pace("encode")
            directive = faults.fire("ec.encode.window", key=self.url)
            if directive == "truncate":
                # stop mid-shard with CLEAN chunked framing: the
                # receiver banks a short stream, and the commit
                # handshake's byte-count/CRC verify MUST refuse it.
                # _truncated lets the send loop turn the premature end
                # into a dest-attributed error once the response is in
                self._truncated = True
                self._pool.put(buf)
                return
            if directive == "drop":
                # FaultInjected (not plain OSError) so
                # http_stream_request skips its receiver-verdict probe
                # — with both ends alive that probe would block on a
                # receiver still waiting for chunks — and tears the
                # connection down instead
                self._pool.put(buf)
                raise faults.FaultInjected(
                    f"shard_write {self.vid}.{self.sid} -> "
                    f"{self.url}: fault-injected drop")
            t0 = time.perf_counter()
            yield memoryview(buf)[:n]
            if self._stats is not None:
                self._stats.record(self.url, n,
                                   time.perf_counter() - t0)
            self._pool.put(buf)

    def _send_loop(self) -> None:
        from ... import tracing
        from ...server.httpd import http_stream_request
        from ...util.request_id import HEADER as _RID_HEADER
        span_start = time.time()
        t0 = time.perf_counter()
        failed = False
        try:
            headers = dict(self._headers())
            ctx = self._trace_ctx
            if ctx:
                # this thread bypasses the pooled-client funnel, so
                # forward the id/trace headers ourselves — the
                # receiver's shard_write server span must hang under
                # the encode trace, not mint a fresh one
                headers.setdefault(_RID_HEADER, ctx[0])
                headers.setdefault(tracing.HEADER,
                                   f"{ctx[0]}-{ctx[1]}")
            status, body = http_stream_request(
                "POST",
                f"{self.url}/admin/ec/shard_write?volumeId={self.vid}"
                f"&shardId={self.sid}&collection={self.collection}"
                f"&uploadId={self.upload_id}",
                self._chunks(), headers=headers,
                timeout=self._timeout)
            import json
            try:
                self._response = json.loads(body or b"{}")
            except ValueError:
                self._response = {"error": body[:200].decode(
                    errors="replace")}
            if status != 200 or "error" in self._response:
                raise OSError(
                    f"shard_write {self.vid}.{self.sid} -> {self.url}: "
                    f"HTTP {status} {self._response.get('error', '')}")
            if self._truncated:
                # the armed truncation ended the stream early with
                # clean framing; the receiver banked a short upload —
                # surface it as this DESTINATION's failure so the
                # caller aborts (and can re-plan around the dest)
                # instead of discovering the mismatch only at finish()
                raise OSError(
                    f"shard_write {self.vid}.{self.sid} -> {self.url}: "
                    f"stream truncated at "
                    f"{self._response.get('bytes')} bytes")
        except _SinkAborted:
            pass
        except BaseException as e:  # noqa: BLE001 — re-raised by the
            # producer (write/finish); the send thread must never die
            # silently mid-encode
            failed = True
            self._error = e
        finally:
            # unblock a producer parked on a full queue/empty pool
            self._stop.set()
            self._pool.put(None)
            ctx = self._trace_ctx
            tracing.emit_span(
                f"encode.scatter.{self.sid}", span_start,
                time.perf_counter() - t0,
                role=ctx[2] if ctx else "",
                parent=ctx[1] if ctx else "",
                trace_id=ctx[0] if ctx else "",
                attrs={"shard": self.sid, "dest": self.url,
                       "bytes": self.bytes},
                error=failed)

    # -- completion ------------------------------------------------------

    def end_stream(self) -> None:
        if not self._finished:
            self._flush_partial()
            self._put(None)
            self._finished = True

    def finish(self) -> None:
        self.end_stream()
        self._t.join(timeout=self._timeout)
        if self._t.is_alive():
            self._stop.set()
            raise OSError(
                f"shard_write {self.vid}.{self.sid} -> {self.url}: "
                f"send thread stuck past {self._timeout}s")
        if self._error is not None:
            raise self._error
        r = self._response or {}
        if int(r.get("bytes", -1)) != self.bytes or \
                int(r.get("crc32", -1)) != self.crc:
            raise OSError(
                f"shard_write {self.vid}.{self.sid} -> {self.url}: "
                f"receiver saw {r.get('bytes')} bytes crc "
                f"{r.get('crc32')}, sent {self.bytes} crc {self.crc}")

    def mark_committed(self) -> None:
        """The owner committed this shard out-of-band (the scatter
        handler's batched one-round-trip-per-destination
        `shard_write_commit`, the only commit path remote shards have)
        — close() must no longer abort it."""
        self._committed = True

    def abort(self) -> None:
        self._stop.set()
        # drain the queue so a parked producer can't deadlock, then
        # join the (now aborting) send thread
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._pool.put(None)
        self._t.join(timeout=5)
        from ...server.httpd import http_json
        try:
            http_json("POST",
                      f"{self.url}/admin/ec/shard_write_abort",
                      {"volumeId": self.vid,
                       "collection": self.collection,
                       "shardId": self.sid,
                       "uploadId": self.upload_id},
                      timeout=10, headers=self._headers())
        except OSError:
            pass  # receiver also reaps stale temps on its own

    def close(self) -> None:
        if not self._committed:
            self.abort()
