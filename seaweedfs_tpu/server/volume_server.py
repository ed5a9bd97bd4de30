"""Volume server: public HTTP data path + admin endpoints + heartbeat.

Data path mirrors the reference's public API exactly
(server/volume_server_handlers_write.go:19 PostHandler,
volume_server_handlers_read.go:138 GetOrHeadHandler):
GET/POST/DELETE on /<vid>,<fid>.

Admin gRPC surface (pb/volume_server.proto) is mirrored as JSON/HTTP
(see server/__init__.py): each handler cites its RPC.  The EC generate
handler preserves the reference's race invariant — the .ecx is written
BEFORE the shard files (volume_grpc_erasure_coding.go:89-98).
"""

from __future__ import annotations

import os
import queue
import re
import threading
import time
import urllib.parse

from ..util import wlog
from .. import security, tracing
from ..storage import types
from ..storage.erasure_coding import ECContext
from ..storage.erasure_coding import ec_decoder, ec_encoder
from ..storage.erasure_coding.ec_context import to_ext
from ..storage.needle import Needle
from ..stats import PROCESS
from ..storage.store import Store
from .httpd import CopyHalves, FileSlice, HttpServer, Request, \
    copy_to_file, http_bytes, http_download, http_json, is_admin_path

# shared request-field validator (also used by the master's assign
# front door) lives in security.py
_check_path_fields = security.check_path_fields

# byte offset of the payload inside a needle record (header + DataSize
# field) — the read plane's registration math (read_plane.py), reused
# when native WRITE-plane appends warm the read plane
_WP_DATA_OFFSET = types.NEEDLE_HEADER_SIZE + 4


def _count_heartbeat_error(e: BaseException) -> str:
    """One more heartbeat that raised or did not reach the master,
    under the exception's type; returns that label."""
    kind = type(e).__name__
    PROCESS.counter_add(
        "volume_heartbeat_errors_total", 1.0,
        help_text="heartbeats that raised or did not reach the master",
        error=kind)
    return kind


class VolumeServer:
    def __init__(self, directories: list[str], master: str,
                 host: str = "127.0.0.1", port: int = 0,
                 public_url: str = "", pulse_seconds: float = 1.0,
                 data_center: str = "", rack: str = "",
                 max_volume_count: int = 8,
                 security_config: "security.SecurityConfig | None" = None,
                 fsync: bool = False):
        self.master = master
        self._security_override = security_config
        self.pulse_seconds = pulse_seconds
        self.data_center = data_center
        self.rack = rack
        self.http = HttpServer(host, port)
        self.store = Store(directories, ip=host, port=self.http.port,
                           public_url=public_url or self.http.url,
                           fsync=fsync)
        for loc in self.store.locations:
            loc.max_volume_count = max_volume_count
        # job=True: what a maintenance job asks of this role (mark
        # readonly, the pull, the pushes, mount, the source's delete),
        # whose spans client reads must not evict (HttpServer.route)
        r = self.http.route
        r("GET", "/status", self._status)
        # volume admin <- volume_server.proto AllocateVolume etc.
        r("POST", "/admin/allocate_volume", self._allocate_volume)
        r("POST", "/admin/delete_volume", self._delete_volume, job=True)
        r("POST", "/admin/mount_volume", self._mount_volume)
        r("POST", "/admin/unmount_volume", self._unmount_volume)
        r("POST", "/admin/set_readonly", self._set_readonly, job=True)
        r("POST", "/admin/configure_volume", self._configure_volume)
        r("POST", "/admin/vacuum", self._vacuum)
        r("GET", "/admin/volume_file", self._read_volume_file, job=True)
        r("POST", "/admin/receive_file", self._receive_file, job=True)
        # EC admin <- volume_server.proto:89-108
        r("POST", "/admin/ec/generate", self._ec_generate)
        r("POST", "/admin/ec/shard_write", self._ec_shard_write, job=True)
        r("POST", "/admin/ec/shard_write_commit",
          self._ec_shard_write_commit)
        r("POST", "/admin/ec/shard_write_abort",
          self._ec_shard_write_abort)
        r("POST", "/admin/ec/mount", self._ec_mount, job=True)
        r("POST", "/admin/ec/unmount", self._ec_unmount, job=True)
        r("POST", "/admin/ec/copy", self._ec_copy)
        r("POST", "/admin/ec/delete_shards", self._ec_delete_shards,
          job=True)
        r("POST", "/admin/ec/rebuild", self._ec_rebuild)
        r("POST", "/admin/ec/to_volume", self._ec_to_volume)
        r("GET", "/admin/ec/shard_read", self._ec_shard_read)
        r("GET", "/admin/ec/info", self._ec_info)
        r("POST", "/admin/query", self._query)
        r("POST", "/admin/tier_move", self._tier_move)
        r("POST", "/admin/tier_fetch", self._tier_fetch)
        r("GET", "/admin/volume_index", self._volume_index)
        r("POST", "/admin/delete_needle", self._admin_delete_needle)
        r("GET", "/admin/needle_raw", self._needle_raw)
        r("POST", "/admin/write_needle_raw", self._write_needle_raw)
        r("POST", "/admin/scrub", self._scrub)
        r("POST", "/admin/volume/merge", self._merge_volume)
        r("POST", "/admin/leave", self._leave)
        r("POST", "/admin/vacuum_toggle", self._vacuum_toggle)
        r("POST", "/admin/ec/scrub", self._ec_scrub)
        r("GET", "/metrics", self._metrics, quiet=True)
        from .debug import install_debug_routes
        install_debug_routes(self.http)  # util/grace/pprof.go analog
        self.http.fallback = self._data_path
        self.http.guard = self._guard
        self._hb_stop = threading.Event()
        self._hb_thread: threading.Thread | None = None
        self._topology_id = ""
        self._last_hb_error: str | None = None
        # staged scatter-encode shard uploads awaiting commit:
        # uploadId -> {path, crc, bytes, vid, collection, stamp}
        self._pending_shard_writes: dict[str, dict] = {}
        self._pending_lock = threading.Lock()
        from .store_ec import EcReader
        self.ec_reader = EcReader(
            master, self.http.url,
            security_headers=lambda: self.security.admin_headers())
        # hot-needle cache (util/chunk_cache promoted server-side, the
        # reference's chunk_cache role at the volume tier): repeated
        # reads of a hot needle skip the index lookup + .dat read (and
        # for EC volumes the whole interval/degraded resolution).  Keys
        # carry a per-volume generation so compact-swap / merge /
        # unmount invalidate wholesale without enumerating needles;
        # write/delete invalidate their needle's group point-wise.
        from ..util.chunk_cache import (TieredChunkCache, read_cache_mb,
                                        read_cache_disk)
        mb = read_cache_mb(64)
        disk_dir, disk_mb = read_cache_disk()
        self.needle_cache = TieredChunkCache(
            mem_limit=mb << 20,
            disk_dir=(os.path.join(disk_dir, f"vol{self.http.port}")
                      if disk_dir else None),
            disk_limit=disk_mb << 20,
            name="volume_needle") if mb > 0 else None
        self._nc_gen: dict[int, int] = {}
        self._nc_gen_lock = threading.Lock()
        # fill/invalidate race guard: a GET that read the store BEFORE
        # a write landed must not cache its (now stale) needle AFTER
        # the write's invalidation ran — fills carry the epoch they
        # began at and land only if no invalidation intervened (the
        # same rule the filer metadata cache enforces)
        self._nc_epoch = 0
        from ..stats import Metrics
        self.metrics = Metrics("volume_server")
        self.http.role = "volume"        # tracing + request_seconds
        self.http.metrics = self.metrics
        # QoS plane (qos.py): tenant admission scoped to the admin /
        # maintenance plane (foreground needle traffic is internal and
        # protected by the EC feedback throttle, not tenant buckets);
        # this role's request_seconds is the throttle's primary
        # foreground signal — EC jobs hammer exactly these servers
        from .. import qos
        qos.install(self.http, "volume", path_prefix="/admin/")
        qos.throttle().add_metrics(f"volume:{self.http.port}",
                                   self.metrics)
        qos.throttle().maybe_start()
        # SLO autopilot (autopilot.py, ISSUE 20): this role's loop
        # owns the hot-needle cache size
        from .. import autopilot as _autopilot
        from .debug import install_autopilot_routes
        self.autopilot = _autopilot.build_for_volume(self)
        install_autopilot_routes(self.http, self.autopilot)
        self.autopilot.start()

    # -- lifecycle --------------------------------------------------------

    def start(self):
        # sweep staged scatter-upload temps orphaned by a crash: the
        # in-memory pending registry died with the old process, so
        # nothing else will ever reclaim these multi-MB files (the
        # lazy reaper only sees uploads registered in THIS process)
        for loc in self.store.locations:
            try:
                names = os.listdir(loc.directory)
            except OSError:
                continue
            for name in names:
                if ".scatter." in name:
                    try:
                        os.remove(os.path.join(loc.directory, name))
                    except OSError:
                        pass
        self.http.start()
        # UDS zero-copy read plane (RDMA sidecar analog,
        # seaweedfs-rdma-sidecar/rdma-engine/src/ipc.rs): same-host
        # readers fetch raw needle records via sendfile(2); path
        # advertised in /status (udsPath)
        self.uds_server = None
        if not self.security.volume_read_key:
            # the UDS plane carries no JWT; with read signing
            # configured it would be an auth bypass for any local
            # process, so it only exists on unauthenticated-read
            # deployments
            try:
                from .uds_reader import UdsNeedleServer
                sock = os.path.join(
                    self.store.locations[0].directory, "uds.sock")
                self.uds_server = UdsNeedleServer(
                    self.store, sock,
                    on_read=self._rp_warm_key).start()
            except OSError:  # pragma: no cover — no AF_UNIX
                self.uds_server = None
        # native TCP read plane (the C++ second implementation of the
        # needle-read surface — seaweed-volume/ Rust server +
        # rdma-sidecar role, native/read_plane.cc): plain needles are
        # served by an epoll+sendfile loop; port advertised in /status
        # (readPlanePort).  Same auth rule as the UDS plane.
        self.read_plane = None
        self._rp_volumes: set[int] = set()
        self._rp_lock = threading.Lock()
        self._rp_gen: dict[int, int] = {}
        self._rp_seen: dict[int, set] = {}
        self._rp_queue = None
        if not self.security.volume_read_key:
            try:
                from .read_plane import ReadPlane
                self.read_plane = ReadPlane(self.http.host)
            except (RuntimeError, OSError):
                self.read_plane = None
        if self.read_plane is not None:
            # write-path registrations drain through a worker so the
            # needle ack never waits on plane bookkeeping (the plane
            # is a read cache: until the entry lands, reads fall back
            # to this port and warm it lazily).  Bounded; overflow
            # drops the registration, lazy warm recovers it.
            self._rp_queue = queue.Queue(maxsize=4096)
            threading.Thread(target=self._rp_worker,
                             daemon=True).start()
            # flight-deck drainer (ISSUE 18): plane-served reads train
            # the hedge read_tracker + feed the flight recorder
            self.read_plane.start_record_drain()
        # native TCP WRITE plane (native/write_plane.cc — the C++
        # sibling of the read plane on the needle-write hot path):
        # plain anonymous uploads are recv'd, serialized, appended and
        # acked by an epoll loop; everything else 404s and the client
        # falls back to this port.  Same auth rule as the read plane
        # (the plane carries no JWT), kill switch
        # SEAWEEDFS_TPU_WRITE_PLANE=0.
        self.write_plane = None
        if not self.security.volume_write_key and \
                os.environ.get("SEAWEEDFS_TPU_WRITE_PLANE", "1") \
                not in ("0", "false"):
            try:
                from .write_plane import WritePlane
                self.write_plane = WritePlane(
                    self.http.host, on_tick=self._wp_tick,
                    on_epoch=self._wp_epoch)
            except (RuntimeError, OSError):
                self.write_plane = None   # pure-Python fallback
        if self.write_plane is not None:
            # eager attach: a volume the plane doesn't own answers
            # every native write with a 404 + client fallback, so
            # eligible volumes are handed over up front (and re-synced
            # at every lifecycle transition below)
            for loc in self.store.locations:
                for vid in list(loc.volumes):
                    self._wp_sync_volume(vid)
            self.write_plane.start_record_drain()
        # gRPC wire plane (volume_server.proto subset) — optional;
        # JSON-HTTP stays the always-on surface
        try:
            from ..pb.volume_service import start_volume_grpc
            self.grpc_server, self.grpc_port = start_volume_grpc(
                self, self.http.host)
        except ImportError:  # grpcio absent: HTTP-only mode
            self.grpc_server, self.grpc_port = None, 0
        except Exception as e:  # pragma: no cover — a real defect
            self.grpc_server, self.grpc_port = None, 0
            wlog.error(f"volume server {self.url}: gRPC plane failed to "
                  f"start: {e!r}")
        self._heartbeat_once()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True)
        self._hb_thread.start()
        return self

    def _rp_worker(self) -> None:
        while True:
            item = self._rp_queue.get()
            if item is None:
                return
            try:
                vid, n = item
                if isinstance(n, int):
                    # key-only warm (UDS on_read hook): the serve path
                    # only touched the needle map, so re-read the
                    # record here — off the hot path, once per needle
                    # (the _rp_seen gate below makes repeats free)
                    if n in self._rp_seen.get(vid, ()):
                        continue
                    n = self.store.read_needle(vid, n)
                self._rp_register(vid, n, lazy=True)
            except Exception:  # noqa: SWFS004 — read-plane cache
                pass           # upkeep must never kill the worker

    def _rp_warm_key(self, vid: int, key: int) -> None:
        """UDS post-serve hook: lazily mirror a needle the zero-copy
        path just served into the native read plane.  Without this,
        needles only ever read over UDS never reach the plane and the
        filer's native read funnel 404s on them forever."""
        q = getattr(self, "_rp_queue", None)
        if q is None or key in self._rp_seen.get(vid, ()):
            return
        try:
            q.put_nowait((vid, key))
        except queue.Full:
            pass           # drop: the next UDS read retries

    def _rp_enqueue(self, vid: int, needle) -> None:
        """Async write-path registration (see start()); no-op without
        the plane (getattr: a request can land between http.start()
        and the plane's init)."""
        q = getattr(self, "_rp_queue", None)
        if q is None:
            return
        try:
            q.put_nowait((vid, needle))
        except queue.Full:
            pass           # drop: lazy warm re-registers on first read

    def _rp_register(self, vid: int, needle,
                     lazy: bool = False) -> None:
        """Mirror a plain needle into the native read plane (write
        path + lazy on-read warm); no-ops without the plane.

        Epoch-checked against _rp_drop_volume: the needle offset is
        read AFTER snapshotting the volume's drop generation and the
        plane entry lands only if no drop intervened — otherwise a
        lazy warm racing a vacuum could re-bind pre-compaction offsets
        against the post-compaction .dat (silent wrong bytes)."""
        rp = self.read_plane
        if rp is None:
            return
        if lazy and needle.id in self._rp_seen.get(vid, ()):
            return      # already warm: skip the flush + native call
        v = self.store.find_volume(vid)
        if v is None or getattr(v, "version", 2) < 2:
            return      # v1 records lack the DataSize field the
            # plane's offset math assumes
        with self._rp_lock:
            gen = self._rp_gen.get(vid, 0)
        got = v.nm.get(needle.id)
        if got is None:
            return
        if lazy:
            # the plane reads its own fd: buffered appends must reach
            # the OS file before the entry is servable.  The write
            # path skips this — write_needle's group-commit barrier
            # already flushed the record before acking, so another
            # flush here would only re-serialize writers on the
            # volume lock.
            v.flush()
        with self._rp_lock:
            if self._rp_gen.get(vid, 0) != gen:
                return  # dropped (vacuum/delete) after our offset read
            if vid not in self._rp_volumes:
                try:
                    if not rp.add_volume(vid, v.file_name(".dat")):
                        return
                except OSError:
                    return
                self._rp_volumes.add(vid)
            rp.register_needle(vid, got[0], needle)
            self._rp_seen.setdefault(vid, set()).add(needle.id)

    # -- native write plane glue (server/write_plane.py) ------------------

    def _wp_sync_volume(self, vid: int) -> None:
        """(Re-)offer a volume to the native write plane after a
        lifecycle transition; attach failures fall back lazily — the
        Python port owns the writes and nothing breaks (the read
        plane's registration-failure contract)."""
        wp = getattr(self, "write_plane", None)
        if wp is None:
            return
        v = self.store.find_volume(vid)
        if v is None:
            return
        try:
            v.attach_native(wp)   # False for ineligible shapes
        except (OSError, RuntimeError, ValueError) as e:
            wlog.warning(f"write plane attach vid={vid} failed "
                         f"(python path serves it): {e!r}")

    def _wp_tick(self) -> None:
        """Pump-thread tick: drain every attached volume's completed
        native appends into its needle map / .idx checkpoint, and
        mirror them into the read plane (epoch-checked like
        _rp_register — a vacuum racing the drain drops the warm, lazy
        re-registration recovers it)."""
        rp = self.read_plane
        for loc in self.store.locations:
            for v in list(loc.volumes.values()):
                vid = v.id
                with self._rp_lock:
                    gen = self._rp_gen.get(vid, 0)
                entries = v.drain_native()
                if not entries or rp is None:
                    continue
                data_off_base = _WP_DATA_OFFSET
                with self._rp_lock:
                    if self._rp_gen.get(vid, 0) != gen:
                        continue   # dropped mid-drain: offsets stale
                    if vid not in self._rp_volumes:
                        try:
                            if not rp.add_volume(
                                    vid, v.file_name(".dat")):
                                continue
                        except OSError:
                            continue
                        self._rp_volumes.add(vid)
                    seen = self._rp_seen.setdefault(vid, set())
                    for e in entries:
                        rp.register_raw(
                            vid, e.key, e.cookie,
                            e.offset + data_off_base, e.data_len)
                        seen.add(e.key)

    def _wp_epoch(self, vid: int, epoch: int) -> None:
        """fsync-tier handshake: parked native acks wait on the
        volume's CommitBarrier — one barrier (one os.fsync) covers
        the whole epoch window, group commit across the C++
        boundary."""
        v = self.store.find_volume(vid)
        if v is not None:
            v._barrier.commit()

    def _rp_drop_volume(self, vid: int) -> None:
        """Forget a volume in the read plane (vacuum swapped the .dat,
        or the volume is gone); live needles lazily re-register.  The
        hot-needle cache drops the volume too — every caller of this
        is a point where the .dat is swapped, merged, or unmounted."""
        self._nc_drop_volume(vid)
        if self.read_plane is not None:
            with self._rp_lock:
                self._rp_gen[vid] = self._rp_gen.get(vid, 0) + 1
                self.read_plane.remove_volume(vid)
                self._rp_volumes.discard(vid)
                self._rp_seen.pop(vid, None)

    # -- hot-needle cache (util/chunk_cache server tier) ------------------

    def _nc_key(self, vid: int, key: int, cookie: int) -> str:
        with self._nc_gen_lock:
            gen = self._nc_gen.get(vid, 0)
        return f"{vid}.g{gen}.{key:x}.{cookie:08x}"

    def _nc_get(self, fid: types.FileId) -> "tuple[str, bytes] | None":
        """Cached (mime, data) for a needle, or None.  The cookie is
        part of the key: a wrong-cookie request misses and takes the
        store path, which raises the CookieMismatch the cache must not
        paper over."""
        if self.needle_cache is None:
            return None
        blob = self.needle_cache.get(
            self._nc_key(fid.volume_id, fid.key, fid.cookie))
        if blob is None:
            return None
        mlen = int.from_bytes(blob[:2], "big")
        return blob[2:2 + mlen].decode(), blob[2 + mlen:]

    def _nc_put(self, fid: types.FileId, n,
                token: "int | None" = None) -> None:
        """Promote a freshly read needle.  TTL'd needles stay out (the
        cache has no expiry clock of its own), as do bodies over the
        memory tier's bound (MemChunkCache skips them anyway).
        `token` is the epoch the fill's store read began at — a fill
        racing an invalidation is discarded, never resurrected."""
        if self.needle_cache is None or n.has_ttl():
            return
        if token is not None and token != self._nc_epoch:
            return
        mime = n.mime.decode() if n.mime else "application/octet-stream"
        blob = len(mime.encode()).to_bytes(2, "big") + \
            mime.encode() + bytes(n.data)
        key = self._nc_key(fid.volume_id, fid.key, fid.cookie)
        self.needle_cache.set(key, blob,
                              group=f"{fid.volume_id}.{fid.key:x}")
        # the pre-set epoch check alone is not atomic with set(): an
        # invalidation completing in between would wipe the group
        # BEFORE our key joined it, resurrecting the stale needle.
        # Re-verify after the insert and undo our own fill — one of
        # the two (group wipe or this delete) always removes it.
        if token is not None and token != self._nc_epoch:
            self.needle_cache.delete(key)

    def _nc_invalidate_needle(self, vid: int, key: int) -> None:
        """Point invalidation for one needle (every cookie spelling:
        the group is keyed without the cookie, so an admin delete that
        carries none still clears it)."""
        if self.needle_cache is not None:
            with self._nc_gen_lock:
                self._nc_epoch += 1
            self.needle_cache.invalidate_group(f"{vid}.{key:x}")

    def _nc_drop_volume(self, vid: int) -> None:
        """Wholesale invalidation by generation bump: old keys become
        unreachable and age out of the LRU (compact-swap, merge,
        unmount, delete, ec_to_volume, received .dat)."""
        if self.needle_cache is not None:
            with self._nc_gen_lock:
                self._nc_epoch += 1
                self._nc_gen[vid] = self._nc_gen.get(vid, 0) + 1

    def stop(self):
        self._hb_stop.set()
        from .. import qos
        if getattr(self, "autopilot", None) is not None:
            self.autopilot.stop()
        qos.throttle().remove_source(f"volume:{self.http.port}")
        if getattr(self, "_rp_queue", None) is not None:
            try:
                self._rp_queue.put_nowait(None)   # end the worker
            except queue.Full:
                pass           # daemon worker dies with the process
        if getattr(self, "read_plane", None) is not None:
            self.read_plane.stop()
        if getattr(self, "uds_server", None) is not None:
            self.uds_server.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop(grace=0.5)
        self.http.stop()
        self.ec_reader.close()
        # store.close() detaches every volume from the write plane
        # (drain + .idx checkpoint), so the plane must outlive it
        self.store.close()
        if getattr(self, "write_plane", None) is not None:
            self.write_plane.stop()

    @property
    def url(self) -> str:
        return self.http.url

    # -- auth (security/guard.go Guard + jwt.go) --------------------------

    @property
    def security(self) -> "security.SecurityConfig":
        # late-bound so security.configure() after construction applies
        return self._security_override or security.current()

    def _guard(self, req: Request):
        """Admin-plane gate (guard.go WhiteList+Jwt: every admin RPC is
        credential-gated in the reference)."""
        if is_admin_path(req.path):
            err = self.security.check_admin(req.query, req.headers,
                                            req.remote_ip)
            if err:
                return 401, {"error": err}
        return None

    # -- heartbeat (volume_grpc_client_to_master.go:51) -------------------

    def _heartbeat_once(self) -> None:
        hb = self.store.collect_heartbeat()
        if self.data_center:
            hb["dataCenter"] = self.data_center
        if self.rack:
            hb["rack"] = self.rack
        try:
            from .. import faults
            # armed `master.heartbeat` faults: delay stalls this pulse
            # (the chaos suite's slow-heartbeat scenario), error skips
            # it entirely — both retried next pulse like a real stall
            faults.fire("master.heartbeat", key=self.url)
            from ..operation import master_json
            # master_json re-dials the raft leader on "not leader"
            # replies (volume_grpc_client_to_master.go:109
            # doHeartbeatWithRetry re-dials on leader change)
            r = master_json(self.master, "POST", "/heartbeat", hb,
                            timeout=5,
                            headers=self.security.admin_headers())
        except OSError as e:
            # no leader reachable, or none that answered in time: the
            # next pulse tries again, and the miss is on the record
            # (three in a row and the master holds this server dead)
            _count_heartbeat_error(e)
            return
        err = r.get("error")
        if err:
            # a rejected heartbeat (bad admin key, whitelist miss) means
            # this server is invisible to the master — say so, once per
            # distinct error, instead of looping silently unregistered
            if err != self._last_hb_error:
                self._last_hb_error = err
                wlog.warning(f"volume server {self.url}: heartbeat rejected "
                      f"by master: {err}")
            return
        self._last_hb_error = None
        tid = r.get("topologyId", "")
        if tid and tid != self._topology_id:
            # new leadership epoch: this heartbeat already re-registered
            # the full volume/shard state (heartbeats are always full);
            # remember the id so a changed epoch is observable
            self._topology_id = tid

    def _heartbeat_loop(self) -> None:
        """One beat a pulse for as long as the server lives.  A beat
        that raises is counted (`volume_heartbeat_errors_total{error}`),
        said once per kind, and followed by the next: a thread that
        ended here would leave the server dead at the master for good,
        and every job after would place its shards without it."""
        said: set[str] = set()
        while not self._hb_stop.wait(self.pulse_seconds):
            t0 = time.perf_counter()
            try:
                self._heartbeat_once()
            except Exception as e:  # noqa: BLE001 — the loop's outer
                # edge: whatever a beat raises, the next one is due
                kind = _count_heartbeat_error(e)
                if kind not in said:
                    said.add(kind)
                    wlog.error(f"volume server {self.url}: heartbeat "
                               f"raised {kind}: {e}; beating on")
            PROCESS.histogram_observe(
                "volume_heartbeat_seconds", time.perf_counter() - t0,
                help_text="one heartbeat: collecting the tables and "
                          "the master's answer")

    # -- public data path -------------------------------------------------

    def _data_path(self, req: Request):
        fid_str = req.path.lstrip("/")
        try:
            fid = types.parse_file_id(fid_str)
        except ValueError:
            return 404, {"error": f"bad file id {fid_str!r}"}
        self.metrics.counter_add(
            "request_total", 1.0,
            help_text="data-path requests", method=req.method)
        # per-fid JWT gate (volume_server_handlers_write.go
        # maybeCheckJwtAuthorization): writes/deletes need a token signed
        # with the write key, reads with the read key — when configured
        sec = self.security
        key = sec.volume_read_key if req.method in ("GET", "HEAD") \
            else sec.volume_write_key
        err = sec.check_fid_jwt(key, req.query, req.headers, str(fid))
        if err:
            return 401, {"error": err}
        if req.method in ("GET", "HEAD"):
            return self._get_needle(fid, req.headers.get("Range", ""),
                                    req.query, req=req)
        if req.method in ("POST", "PUT"):
            # body deliberately untouched here: the first read happens
            # inside _put_needle's "recv" stage so the decomposition
            # sees the true socket-drain cost
            return self._put_needle(fid, req)
        if req.method == "DELETE":
            return self._delete_needle(fid, req)
        return 405, {"error": "method not allowed"}

    def _metrics(self, req: Request):
        """Prometheus text endpoint (stats/metrics.go:49-662 analog)."""
        hb = self.store.collect_heartbeat()
        self.metrics.gauge_set("volumes", len(hb["volumes"]),
                               help_text="mounted volumes")
        self.metrics.gauge_set("ec_volumes", len(hb["ecShards"]))
        self.metrics.gauge_set(
            "max_volume_count", hb["maxVolumeCount"])
        from ..stats import render_process
        return 200, ((self.metrics.render() +
                      self._plane_metrics_text() +
                      render_process()).encode(),
                     "text/plain; version=0.0.4")

    def _plane_metrics_text(self) -> str:
        """Native-plane counters rendered straight from the C++
        atomics (the plane has no Python on its hot path, so the
        registry hears about it only at scrape time): write-plane
        requests/fallbacks + native-ack latency histogram, and the
        read plane's served counter beside its Python-port fallback
        sibling (counted in _get_needle)."""
        out = []
        rp = getattr(self, "read_plane", None)
        if rp is not None:
            out.append(
                "# HELP volume_server_read_plane_requests_total "
                "needle reads served by the native read plane\n"
                "# TYPE volume_server_read_plane_requests_total "
                "counter\n"
                f"volume_server_read_plane_requests_total "
                f"{rp.served()}\n")
        wp = getattr(self, "write_plane", None)
        if wp is None:
            return "".join(out)
        out.append(
            "# HELP volume_server_write_plane_requests_total needle "
            "writes acked by the native write plane\n"
            "# TYPE volume_server_write_plane_requests_total counter\n"
            f"volume_server_write_plane_requests_total "
            f"{wp.requests()}\n"
            "# HELP volume_server_write_plane_fallbacks_total native "
            "writes answered 404 (python port owns them)\n"
            "# TYPE volume_server_write_plane_fallbacks_total "
            "counter\n"
            f"volume_server_write_plane_fallbacks_total "
            f"{wp.fallbacks()}\n")
        from .write_plane import ACK_BUCKETS_S
        buckets, count, total_s = wp.ack_histogram()
        out.append("# HELP volume_server_write_plane_ack_seconds "
                   "native write-plane ack latency\n"
                   "# TYPE volume_server_write_plane_ack_seconds "
                   "histogram\n")
        for le, cum in zip(ACK_BUCKETS_S, buckets):
            out.append(f"volume_server_write_plane_ack_seconds_bucket"
                       f'{{le="{le}"}} {cum}\n')
        out.append(f"volume_server_write_plane_ack_seconds_bucket"
                   f'{{le="+Inf"}} {count}\n'
                   f"volume_server_write_plane_ack_seconds_sum "
                   f"{total_s}\n"
                   f"volume_server_write_plane_ack_seconds_count "
                   f"{count}\n")
        return "".join(out)

    def _get_needle(self, fid: types.FileId, rng: str = "",
                    query: "dict | None" = None, req=None):
        # armed `volume.read.serve` faults (delay: one slow replica;
        # error: one dead replica) fire before the cache OR the store
        # answers — the chaos lever behind the hedged-read scenarios;
        # keyed by this server's url so `match` can wedge exactly one
        # replica of a volume
        from .. import faults
        faults.fire("volume.read.serve", key=f"{self.http.url}/{fid}")
        cached = self._nc_get(fid)
        if cached is not None:
            mime, data = cached
        else:
            token = self._nc_epoch    # BEFORE the store read
            try:
                n = self.store.read_needle(
                    fid.volume_id, fid.key, cookie=fid.cookie,
                    ec_reader=self.ec_reader,
                    traced=req is not None and
                    bool(req.headers.get(tracing.HEADER)))
            except KeyError:
                return 404, {"error": "not found"}
            except ValueError as e:
                return 404, {"error": str(e)}
            if self.read_plane is not None:
                # symmetry with write_plane_fallbacks_total: a read
                # served here while the native plane is up is a
                # fallback (unwarmed, non-plain, or a client that
                # never tried the plane)
                self.metrics.counter_add(
                    "read_plane_fallbacks_total", 1.0,
                    help_text="python-port data reads while the "
                              "native read plane is active")
            self._rp_register(fid.volume_id, n, lazy=True)  # plane warm
            if not getattr(n, "was_degraded", False) or \
                    os.environ.get("SEAWEEDFS_TPU_DEGRADED_PROMOTE",
                                   "1") not in ("0", "false"):
                # degraded decodes are promoted by default (the
                # zipfian payoff: first read pays the d-way fan-out,
                # the rest hit memory) — the knob opts a cluster out
                # when decode results must never occupy cache
                self._nc_put(fid, n, token=token)
            mime = n.mime.decode() if n.mime \
                else "application/octet-stream"
            data = n.data
        if query and ("width" in query or "height" in query):
            # resize-on-read (volume_server_handlers_read.go:353 ->
            # images/resizing.go)
            from .. import images
            try:
                w = int(query.get("width", 0))
                h = int(query.get("height", 0))
            except ValueError:
                w = h = 0
            data = images.resized(data, mime, w, h,
                                  query.get("mode", ""))
        # response-side QoS byte metering (qos.charge_response): a
        # cache-hit stampede spends the tenant's in-flight-bytes
        # budget exactly like a store-read would — the hot cache must
        # not be a QoS bypass
        def _serve(status: int, body: bytes, headers: dict):
            if req is not None:
                from .. import qos
                release, deny = qos.charge_response(req, len(body),
                                                    "volume")
                if deny is not None:
                    return deny
                if release is not None:
                    headers = {**headers,
                               "Content-Length": str(len(body))}
                    return status, (qos.MeteredBody(body, release),
                                    headers)
            return status, (body, headers)

        # ranged needle reads keep the filer's chunk-view reads from
        # overfetching whole chunks (volume_server_handlers_read.go
        # serves Range on the data path)
        if rng.startswith("bytes="):
            try:
                lo, _, hi = rng[6:].partition("-")
                total = len(data)
                if lo:
                    start = int(lo)
                    stop = int(hi) + 1 if hi else total
                else:
                    start = total - min(int(hi), total)
                    stop = total
                part = data[start:stop]
                return _serve(206, part, {
                    "Content-Type": mime,
                    "Content-Range":
                        f"bytes {start}-{start + len(part) - 1}"
                        f"/{total}"})
            except ValueError:
                pass
        return _serve(200, data, {"Content-Type": mime})

    def _put_needle(self, fid: types.FileId, req: Request):
        # armed `volume.write.serve` faults (delay: one wedged
        # replica; error: one dead replica) fire before the write
        # track opens — the WRITE-side sibling of volume.read.serve,
        # the chaos lever behind the deadline/flight-recorder
        # scenarios; keyed by this server's url so `match` can wedge
        # exactly one replica of a volume
        from .. import faults
        faults.fire("volume.write.serve", key=f"{self.http.url}/{fid}")
        # write-path latency decomposition (profiling.py): the track
        # covers this handler; recv/index/append/flush/replicate stage
        # cells land in write_stage_seconds{stage} plus sibling trace
        # spans, so `trace.show` can say WHERE a slow write spent its
        # time (the 50x ROADMAP gap is unlocatable without this,
        # arXiv:1709.05365 §5)
        from .. import profiling
        with profiling.track("write", role="volume",
                             metrics=self.metrics):
            return self._put_needle_tracked(fid, req)

    def _put_needle_tracked(self, fid: types.FileId, req: Request):
        from .. import profiling
        with profiling.stage("recv"):
            body = req.body
        self.metrics.counter_add("received_bytes", len(body))
        with profiling.stage("prep"):
            # needle construction is real per-request work (CRC over
            # the body, header encode) — uninstrumented it hides as
            # unattributed wall in the decomposition
            n = Needle(cookie=fid.cookie, id=fid.key, data=body)
            name = req.query.get("name", "")
            if name:
                n.set_name(name.encode())
            mime = req.headers.get("Content-Type", "")
            if mime and mime not in ("application/octet-stream",
                                     "multipart/form-data"):
                n.set_mime(mime.encode())
            ts = req.query.get("ts")
            ts_val = int(ts) if ts else int(time.time())
            n.set_last_modified(ts_val)
        try:
            size, unchanged = self.store.write_needle(fid.volume_id, n)
        except KeyError:
            return 404, {"error": f"volume {fid.volume_id} not found"}
        except PermissionError as e:
            return 409, {"error": str(e)}
        self._nc_invalidate_needle(fid.volume_id, fid.key)
        with profiling.stage("register"):
            self._rp_enqueue(fid.volume_id, n)
        # synchronous replication fan-out
        # (topology/store_replicate.go:27 ReplicatedWrite); forward the
        # original Content-Type and stamp ts so every replica writes a
        # byte-identical needle record (store_replicate.go ReplicatedWrite
        # forwards the request as-is)
        if req.query.get("type") != "replicate":
            # always set Content-Type: with a body and no header urllib
            # injects x-www-form-urlencoded, which the replica would store
            # as the needle mime (octet-stream is in the excluded list)
            with profiling.stage("replicate"):
                err = self._replicate(
                    fid, req, "POST", body,
                    extra_query={"ts": str(ts_val)},
                    headers={"Content-Type":
                             mime or "application/octet-stream"})
            if err:
                # the flight record of a failed write names the
                # replication fan-out, not just "500"
                profiling.flight_note("replicate", {"error": str(err)})
                return 500, {"error": f"replication: {err}"}
        return 201, {"name": name, "size": size, "eTag": n.etag(),
                     "unchanged": unchanged}

    def _delete_needle(self, fid: types.FileId, req: Request):
        if self.read_plane is not None:
            self.read_plane.delete_needle(fid.volume_id, fid.key)
        try:
            freed = self.store.delete_needle(
                fid.volume_id, Needle(cookie=fid.cookie, id=fid.key))
        except KeyError:
            freed = None
        # AFTER the store mutation (like _put_needle): invalidating
        # first would let a concurrent GET re-cache the pre-delete
        # needle with no later invalidation ever coming
        self._nc_invalidate_needle(fid.volume_id, fid.key)
        # deletes fan out like writes (store_replicate.go:142
        # ReplicatedDelete; EC: store_ec_delete.go:38) — a delete lost on
        # one holder would leave the object readable there via the read
        # path's location fallback.  Fan out even when the local copy is
        # already gone, and accept a sibling's 404, so concurrent/retried
        # deletes stay idempotent.
        if req.query.get("type") != "replicate":
            if self.store.find_ec_volume(fid.volume_id) is not None:
                err = self._ec_delete_fan_out(fid)
            else:
                err = self._replicate(fid, req, "DELETE", None,
                                      ok_statuses=(404,))
            if err:
                return 500, {"error": f"replication: {err}"}
        if freed is None:
            return 404, {"error": "not found"}
        return 202, {"size": freed}

    def _ec_delete_fan_out(self, fid: types.FileId) -> str | None:
        """Tombstone the needle in every other shard holder's .ecx/.ecj
        (store_ec_delete.go:38 doDeleteNeedleFromAtLeastOneRemoteEcShards;
        each holder keeps a full index copy)."""
        from ..operation import master_json
        try:
            r = master_json(
                self.master, "GET",
                f"/dir/ec_lookup?volumeId={fid.volume_id}",
                timeout=5)
        except OSError as e:
            return str(e)
        if "error" in r:
            # master doesn't know the shard set (restart, re-registration
            # in flight) — failing loudly beats a silent lost delete
            return f"ec_lookup: {r['error']}"
        headers = self.security.write_headers(str(fid))
        for loc in {l["url"] for l in r.get("shardIdLocations", [])}:
            if loc in (self.url, self.store.public_url):
                continue
            status, data, _ = http_bytes(
                "DELETE", f"{loc}/{fid}?type=replicate", headers=headers,
                                  timeout=60)
            if status >= 300 and status != 404:
                return f"{loc} -> {status}: {data[:200]!r}"
        return None

    def _replicate(self, fid: types.FileId, req: Request, method: str,
                   body: bytes | None,
                   extra_query: dict[str, str] | None = None,
                   headers: dict[str, str] | None = None,
                   ok_statuses: tuple[int, ...] = ()) -> str | None:
        """Fan out to sibling replicas, excluding self
        (store_replicate.go:192 DistributedOperation)."""
        v = self.store.find_volume(fid.volume_id)
        if v is None or not v.super_block.replica_placement.byte():
            return None
        from ..operation import master_json
        try:
            locs = master_json(
                self.master, "GET",
                f"/dir/lookup?volumeId={fid.volume_id}",
                timeout=5).get("locations", [])
        except OSError as e:
            return str(e)
        query = {k: v for k, v in req.query.items()
                 if k not in ("type", "jwt")}
        query.update(extra_query or {})
        qs = urllib.parse.urlencode(query)
        # re-sign for the replicas: the reference forwards the request's
        # jwt (store_replicate.go); holding the key, signing fresh avoids
        # forwarding expired tokens on slow fan-outs
        auth = self.security.write_headers(str(fid))
        if auth:
            headers = {**(headers or {}), **auth}
        for loc in locs:
            if loc["url"] in (self.url, self.store.public_url):
                continue
            status, data, _ = http_bytes(
                method,
                f"{loc['url']}/{fid}?type=replicate" +
                (f"&{qs}" if qs else ""),
                body, headers=headers, timeout=60)
            if status >= 300 and status not in ok_statuses:
                return f"{loc['url']} -> {status}: {data[:200]!r}"
        return None

    # -- status -----------------------------------------------------------

    def _status(self, req: Request):
        uds = getattr(self, "uds_server", None)
        rp = getattr(self, "read_plane", None)
        wp = getattr(self, "write_plane", None)
        return 200, {"version": "seaweedfs-tpu/0.1",
                     "udsPath": uds.sock_path if uds else "",
                     "readPlanePort": rp.port if rp else 0,
                     "writePlanePort": wp.port if wp else 0,
                     **self.store.collect_heartbeat()}

    # -- volume admin -----------------------------------------------------

    def _allocate_volume(self, req: Request):
        """volume_server.proto AllocateVolume."""
        b = req.json()
        collection = b.get("collection", "")
        _check_path_fields(collection)  # lands in the .dat/.idx path
        self.store.add_volume(
            int(b["volumeId"]), collection,
            b.get("replication", ""), b.get("ttl", ""))
        self._wp_sync_volume(int(b["volumeId"]))
        self._heartbeat_once()  # instant topology notify
        return 200, {}

    def _delete_volume(self, req: Request):
        vid = int(req.json()["volumeId"])
        self._rp_drop_volume(vid)
        self.store.delete_volume(vid)
        self._heartbeat_once()
        return 200, {}

    def _mount_volume(self, req: Request):
        b = req.json()
        collection = b.get("collection", "")
        _check_path_fields(collection)
        self.store.mount_volume(int(b["volumeId"]), collection)
        self._wp_sync_volume(int(b["volumeId"]))
        return 200, {}

    def _unmount_volume(self, req: Request):
        vid = int(req.json()["volumeId"])
        self._rp_drop_volume(vid)
        self.store.unmount_volume(vid)
        return 200, {}

    def _set_readonly(self, req: Request):
        b = req.json()
        vid = int(b["volumeId"])
        self.store.set_volume_read_only(vid, bool(b.get("readOnly", True)))
        v = self.store.find_volume(vid)
        if v is not None and v.read_only:
            v.sync()  # commit buffered .dat/.idx before anyone copies them
        elif v is not None:
            self._wp_sync_volume(vid)   # un-freeze: plane-eligible again
        # instant topology notify (same rule as mount/unmount): until
        # the master sees the flag it keeps ASSIGNING this volume, and
        # every write raced into the readonly window costs the client
        # a 409 + fresh-assign retry — with a pulse-length window that
        # outlasts the retry budget under an ec.encode burst
        self._heartbeat_once()
        return 200, {}

    def _configure_volume(self, req: Request):
        """volume_server.proto VolumeConfigure: rewrite the replica
        placement byte in the superblock + cached info."""
        b = req.json()
        vid = int(b["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        try:
            v.configure_replication(str(b.get("replication", "000")))
        except ValueError as e:
            return 400, {"error": str(e)}
        self._heartbeat_once()
        return 200, {"replication": str(
            v.super_block.replica_placement)}

    def _leave(self, req: Request):
        """volume.server.leave (command_volume_server_leave.go
        VolumeServerLeave): stop heartbeating so the master forgets
        this node after its pulse timeout; volumes stay served until
        the process exits (the operator evacuates first)."""
        self._hb_stop.set()
        return 200, {"left": True}

    def _vacuum_toggle(self, req: Request):
        """volume.vacuum.enable/disable (command_volume_vacuum_*.go
        DisableVacuum/EnableVacuum): a maintenance gate the vacuum
        handler honors."""
        self._vacuum_disabled = not bool(req.json().get("enabled",
                                                        True))
        return 200, {"vacuumEnabled": not self._vacuum_disabled}

    def _vacuum(self, req: Request):
        """volume_server.proto VacuumVolume{Check,Compact,Commit}."""
        if getattr(self, "_vacuum_disabled", False):
            return 409, {"error": "vacuum disabled on this server "
                                  "(volume.vacuum.enable to resume)"}
        vid = int(req.json()["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": "volume not found"}
        garbage = v.garbage_level()
        # compaction rewrites the .dat (offsets move): drop the read
        # plane's index FIRST so no stale (offset,len) can be served
        # against the swapped file; survivors lazily re-register.
        # (Volume.compact detaches the write plane itself — the .idx
        # snapshot must be complete — so re-offer it after the swap.)
        self._rp_drop_volume(vid)
        v.vacuum()
        self._wp_sync_volume(vid)
        return 200, {"garbageRatio": garbage}

    def _merge_volume(self, req: Request):
        """volume.merge server side (shell/command_volume_merge.go):
        pull peer replicas' .dat files and rewrite the local copy as
        the AppendAtNs-ordered union (Volume.merge_from).  The volume
        must already be readonly — the shell marks every replica
        before calling."""
        b = req.json()
        vid = int(b["volumeId"])
        peers = b.get("peers", [])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": "volume not found"}
        if not v.read_only:
            return 409, {"error": f"volume {vid} must be readonly "
                                  "before merging"}
        self._rp_drop_volume(vid)   # offsets move under the merge
        import tempfile
        tmp_paths = []
        try:
            for peer in peers:
                fd, tmp = tempfile.mkstemp(
                    suffix=".dat", dir=os.path.dirname(
                        v.file_name(".dat")))
                os.close(fd)
                # track BEFORE the pull: a failed download must not
                # leak a .dat-sized temp file past the finally
                tmp_paths.append(tmp)
                status, _hdrs = http_download(
                    f"{peer}/admin/volume_file?volumeId={vid}"
                    f"&collection={v.collection}&ext=.dat", tmp,
                    headers=self.security.admin_headers(), timeout=600)
                if status != 200:
                    return 500, {"error":
                                 f"pull .dat from {peer}: {status}"}
            merged = v.merge_from(tmp_paths)
        except (OSError, ValueError, PermissionError) as e:
            return 500, {"error": f"merge: {e}"}
        finally:
            for tmp in tmp_paths:
                try:
                    os.remove(tmp)
                except FileNotFoundError:
                    pass
        self._heartbeat_once()
        return 200, {"mergedNeedles": merged,
                     "datBytes": v.dat_size()}

    def _query(self, req: Request):
        """volume_server.proto:132 Query (server/volume_grpc_query.go):
        evaluate a SQL-subset SELECT over one stored needle's JSON/CSV
        content, returning matched rows — the compute-pushdown shape
        (filtering happens where the bytes live)."""
        from ..query import QueryError, run_query
        b = req.json()
        vid = int(b["volumeId"])
        key = int(b["key"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        try:
            n = v.read_needle(key)
        except KeyError as e:
            return 404, {"error": str(e)}
        try:
            rows = run_query(b["expression"], n.data,
                             b.get("inputFormat", "json"),
                             bool(b.get("csvHeader", True)))
        except QueryError as e:
            return 400, {"error": str(e)}
        return 200, {"rows": rows, "count": len(rows)}

    def _tier_move(self, req: Request):
        """volume_server.proto VolumeTierMoveDatToRemote
        (storage/volume_tier.go + s3_backend): upload the `.dat` to an
        S3-compatible backend, record it in .vif, drop the local copy,
        and reopen the volume in remote-read mode."""
        from ..storage.backend import configure_s3_backend, get_backend
        b = req.json()
        vid = int(b["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        if v.is_remote:
            return 200, {"alreadyRemote": True}
        backend_id = b.get("backendId", "default")
        if b.get("endpoint"):
            storage = configure_s3_backend(
                backend_id, b["endpoint"], b.get("bucket", "tier"),
                b.get("accessKey", ""), b.get("secretKey", ""))
        else:
            try:
                storage = get_backend(backend_id)
            except KeyError as e:
                return 400, {"error": str(e)}
        collection = v.collection
        # freeze + flush so the uploaded object is the complete volume;
        # heartbeat IMMEDIATELY so the master drops this volume from
        # its writable list — when the tier target is this very
        # cluster (the reference's own test trick), the upload's chunk
        # assigns must not route back into the frozen volume
        was_read_only = v.read_only
        self.store.set_volume_read_only(vid, True)
        v.sync()
        self._heartbeat_once()
        # per-replica object key: each replica tiers its OWN copy
        # (replicas can diverge; sharing one key would let the last
        # upload overwrite the object other replicas' .vif describe)
        replica_tag = f"{self.http.port}"
        key = (f"{collection}_" if collection else "") + \
            f"{vid}.{replica_tag}.dat"
        dat_path = v.file_name(".dat")
        try:
            storage.ensure_bucket()
            size = storage.upload(dat_path, key)
        except Exception as e:  # noqa: BLE001 — roll back the freeze
            if not was_read_only:
                self.store.set_volume_read_only(vid, False)
                self._heartbeat_once()
            return 500, {"error": f"tier upload failed: {e}"}
        v.volume_info.files = [{
            "backendType": "s3", "backendId": backend_id, "key": key,
            "fileSize": size, "extension": ".dat"}]
        v.volume_info.read_only = True
        v.save_volume_info()
        # swap to remote mode: close, drop the local .dat, remount —
        # Volume.__init__ sees the .vif files entry and opens the
        # backend-backed reader
        self.store.unmount_volume(vid)
        os.remove(dat_path)
        self.store.mount_volume(vid, collection)
        self._heartbeat_once()
        return 200, {"key": key, "fileSize": size,
                     "backendId": backend_id}

    def _tier_fetch(self, req: Request):
        """The inverse: download the remote `.dat` back to local disk
        (volume.tier.download / VolumeTierMoveDatFromRemote)."""
        from ..storage.backend import get_backend
        b = req.json()
        vid = int(b["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        if not v.is_remote:
            return 200, {"alreadyLocal": True}
        remote = v.volume_info.files[0]
        storage = get_backend(remote.get("backendId", "default"))
        collection = v.collection
        dat_path = v.file_name(".dat")
        size = storage.download(remote["key"], dat_path)
        v.volume_info.files = []
        # the volume is local and writable again; a stale readOnly in
        # the .vif would make a Go reader treat it as frozen forever
        v.volume_info.read_only = False
        v.save_volume_info()
        self.store.unmount_volume(vid)
        self.store.mount_volume(vid, collection)
        self._wp_sync_volume(vid)   # local + writable again
        if bool(b.get("deleteRemote", True)):
            storage.delete(remote["key"])
        self._heartbeat_once()
        # report which backend held the copy: volume.tier.compact
        # re-uploads to the SAME backend, and the binding in
        # volume_info.files was just cleared above
        return 200, {"fileSize": size,
                     "backendId": remote.get("backendId", "default")}

    def _volume_index(self, req: Request):
        """Live needle inventory of one volume: [key, size] pairs after
        replaying .idx delete semantics.  The repair plane
        (volume.check.disk / volume.fsck, shell/command_volume_fsck.go
        + command_volume_check_disk.go) diffs these across replicas or
        against filer references."""
        from ..storage import idx as idxmod
        vid = int(req.query["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        v.sync()
        with open(v.file_name(".idx"), "rb") as f:
            live = idxmod.live_entries(f.read())  # noqa: SWFS013 — admin repair inventory: live_entries needs the whole .idx (16B/needle), no byte response to stream
        return 200, {"volumeId": vid,
                     "entries": sorted((k, s)
                                       for k, (_o, s) in live.items())}

    def _admin_delete_needle(self, req: Request):
        """Tombstone one needle by key (no cookie: admin plane) — the
        purge arm of volume.fsck (-reallyDeleteFromVolume)."""
        b = req.json()
        vid = int(b["volumeId"])
        key = int(b["key"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        if self.read_plane is not None:
            self.read_plane.delete_needle(vid, key)
        try:
            n = v.read_needle(key)
        except KeyError:
            self._nc_invalidate_needle(vid, key)
            return 200, {"freed": 0}
        try:
            freed = v.delete_needle(n)
        except PermissionError as e:
            return 409, {"error": str(e)}
        # after the mutation, same ordering rule as _delete_needle
        self._nc_invalidate_needle(vid, key)
        return 200, {"freed": freed}

    def _needle_raw(self, req: Request):
        """Serve one needle's full on-disk record (header..padding) —
        the replica-repair copy unit (the reference syncs raw needles
        between replicas in command_volume_check_disk.go)."""
        vid = int(req.query["volumeId"])
        key = int(req.query["key"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        try:
            n = v.read_needle(key)
        except KeyError as e:
            return 404, {"error": str(e)}
        return 200, (n.to_bytes(v.version),
                     {"Content-Type": "application/octet-stream",
                      "X-Needle-Version": str(v.version)})

    def _write_needle_raw(self, req: Request):
        """Append a raw needle record pulled from a healthy replica
        (the receiving side of replica repair)."""
        vid = int(req.query["volumeId"])
        version = int(req.query.get("version", types.CURRENT_VERSION))
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        import struct
        if len(req.body) < 16:
            return 400, {"error": "needle record shorter than header"}
        try:
            n = Needle.parse_header(req.body[:16])
            n.parse_body(req.body[16:], version)
        except (ValueError, struct.error) as e:
            # struct.error: truncated body/CRC tail is not a ValueError
            return 400, {"error": f"bad needle record: {e}"}
        size, _ = self.store.write_needle(vid, n)
        self._nc_invalidate_needle(vid, n.id)
        self._rp_register(vid, n)
        return 200, {"size": size}

    def _read_volume_file(self, req: Request):
        """volume_server.proto:69 CopyFile equivalent: stream a byte
        range of a volume/EC file (.dat/.idx/.ecx/.ecj/.vif/.ecNN)."""
        vid = int(req.query["volumeId"])
        ext = req.query["ext"]
        collection = req.query.get("collection", "")
        try:
            _check_path_fields(collection, ext)
        except ValueError as e:
            return 400, {"error": str(e)}
        offset = int(req.query.get("offset", 0))
        size = int(req.query.get("size", -1))
        if ext in (".dat", ".idx"):
            v = self.store.find_volume(vid)
            if v is not None:
                v.sync()  # serve committed bytes, not a buffered tail
        path = self._file_path(vid, collection, ext)
        if path is None:
            return 404, {"error": f"no {ext} file for volume {vid}"}
        # stream, never buffer: a 30GB .dat pull must not hold the file
        # in RAM (the reference streams CopyFile in chunks,
        # volume_server.proto:69)
        total = os.path.getsize(path)
        n = max(total - offset, 0) if size < 0 else \
            max(min(size, total - offset), 0)
        f = open(path, "rb")
        f.seek(offset)
        return 200, (FileSlice(f, n), {"Content-Length": str(n)})

    def _receive_file(self, req: Request):
        """volume_server.proto ReceiveFile: accept a shard/index file
        pushed by a worker (erasure_coding/shard_distribution.go:101
        DistributeEcShards target side)."""
        vid = int(req.query["volumeId"])
        collection = req.query.get("collection", "")
        ext = req.query["ext"]
        try:
            _check_path_fields(collection, ext)
        except ValueError as e:
            return 400, {"error": str(e)}
        base = self._base_path(vid, collection)
        if ext in (".dat", ".idx"):
            # a pushed data/index file replaces volume content under
            # any cached needles — and under the write plane's owned
            # tail, which must be given back first
            v = self.store.find_volume(vid)
            if v is not None:
                v.detach_native()
            self._nc_drop_volume(vid)
        # temp + rename, like the gRPC ReceiveFile twin: a push that
        # dies mid-stream (or whose relay SOURCE dies — http_relay
        # starts this upload before the download completes) must never
        # leave a truncated file at the final path for _base_path to
        # later resolve
        import uuid as _uuid
        from .. import faults

        def chunks():
            for chunk in req.stream_body():
                if faults.fire("volume.receive_file.recv",
                               key=f"{vid}{ext}") is not None:
                    raise IOError(
                        f"receive_file {vid}{ext}: fault-injected "
                        f"mid-stream failure")
                yield chunk

        tmp = f"{base}{ext}.recv.{_uuid.uuid4().hex}"
        halves = CopyHalves()
        try:
            with open(tmp, "wb") as f:
                n = copy_to_file(chunks(), f, halves)
            os.replace(tmp, base + ext)
        finally:
            try:
                os.remove(tmp)
            except OSError:
                pass
        # under this request's server span: recv against the write
        tracing.emit_span_batch(halves.spans("receive"))
        return 200, {"bytes": n}

    def _file_path(self, vid: int, collection: str, ext: str
                   ) -> str | None:
        _check_path_fields(collection, ext)
        name = (f"{collection}_" if collection else "") + f"{vid}{ext}"
        for loc in self.store.locations:
            p = os.path.join(loc.directory, name)
            if os.path.exists(p):
                return p
        return None

    def _base_path(self, vid: int, collection: str) -> str:
        """Base file path for volume vid on the disk holding it (or the
        first location for new files)."""
        _check_path_fields(collection)
        for ext in (".dat", ".ecx", ".ec00"):
            p = self._file_path(vid, collection, ext)
            if p is not None:
                return p[: -len(ext)]
        name = (f"{collection}_" if collection else "") + str(vid)
        return os.path.join(self.store.locations[0].directory, name)

    # -- EC admin (volume_grpc_erasure_coding.go) -------------------------

    def _ec_generate(self, req: Request):
        """:43 VolumeEcShardsGenerate.  Invariant: write .ecx BEFORE the
        shard files and snapshot datSize first (race rationale :89-98),
        then persist the scheme to .vif (:132).

        With a `placement` map ({shard_id: url}) in the body this
        becomes SCATTER-encode: shard slices stream straight off the GF
        pipeline to their placement targets (one chunked
        `/admin/ec/shard_write` stream per remote shard), sidecars are
        pushed, and every shard is committed + mounted at its final
        destination — remote shards never touch this node's disks and
        the later `ec.balance` re-copy round disappears entirely."""
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        ctx = ECContext(
            int(b.get("dataShards") or 10),
            int(b.get("parityShards") or 4),
            collection, vid)
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        if collection != v.collection:
            # a mismatched collection would generate shards the mount
            # step (addressing <collection>_<vid>) can never find
            return 409, {"error": f"collection mismatch: volume {vid} "
                                  f"is {v.collection!r}, "
                                  f"not {collection!r}"}
        if not v.read_only:
            return 409, {"error": "volume must be readonly before encode"}
        v.sync()
        base = v.file_name("")
        dat_size = v.dat_size()
        placement = b.get("placement")
        if placement is not None:
            return self._ec_scatter_generate(
                v, ctx, collection, base, dat_size, placement,
                replan=int(b.get("replan", 0)))
        ec_encoder.write_sorted_file_from_idx(base)      # .ecx first!
        ec_encoder.write_ec_files(base, ctx)
        ec_encoder.save_ec_volume_info(base, ctx, dat_size, v.version)
        return 200, {"shardIds": list(range(ctx.total))}

    def _ec_scatter_generate(self, v, ctx: ECContext, collection: str,
                             base: str, dat_size: int,
                             placement: dict, replan: int = 0):
        """Placement-first streaming encode (the scatter tentpole).
        Order is the no-partial-stripe invariant: (1) pipeline every
        shard's windows to its sink and VERIFY delivery (crc + byte
        count, still uncommitted temps), (2) push sidecars
        (.ecx/.vif[/.ecj]) to every remote destination, (3) commit each
        shard — the receiver's atomic rename — with mount-on-commit,
        (4) mount local shards.  A failure anywhere unwinds: uncommitted
        temps are aborted, committed/mounted shards are deleted via
        delete_shards, and the caller (shell/worker) restores the
        volume to read-write.  Nothing is ever mounted from a partial
        stripe."""
        import time as _time
        from concurrent.futures import ThreadPoolExecutor

        from ..storage.erasure_coding.shard_sink import (
            LocalShardSink, RemoteShardSink, ScatterStats)
        dests: dict[int, str] = {}
        for sid_s, url in (placement or {}).items():
            dests[int(sid_s)] = url
        if sorted(dests) != list(range(ctx.total)):
            return 400, {"error": f"placement must cover shards "
                                  f"0..{ctx.total - 1}, got "
                                  f"{sorted(dests)}"}
        self_urls = {self.http.url, self.store.public_url}
        stats = ScatterStats()
        if replan:
            # the shell re-planned a failed stripe around dead/tripped
            # destinations and is retrying on this source: make the
            # re-plan COUNT so chaos runs can assert it happened
            self.metrics.counter_add(
                "ec_scatter_replans_total", float(replan),
                help_text="scatter encodes re-planned around failed "
                          "destinations")
        # destinations observed failing this run, for the shell's
        # re-planner ({failedDests: [...]} rides the error body)
        failed_dests: set = set()
        failed_lock = threading.Lock()

        def _note_failed(url: str) -> None:
            with failed_lock:
                failed_dests.add(url)
        t_start = _time.perf_counter()
        # snapshot any pre-existing .vif: for a TIERED volume it is the
        # ONLY reference to the remote .dat, and the unwind must
        # restore it verbatim, never delete it
        vif_before: "bytes | None" = None
        try:
            with open(base + ".vif", "rb") as vf:
                vif_before = vf.read()  # noqa: SWFS013 — .vif sidecar, format-bounded to a few hundred bytes
        except OSError:
            pass
        ec_encoder.write_sorted_file_from_idx(base)      # .ecx first!
        sinks: list = []
        local_sids: list[int] = []
        try:
            for sid in range(ctx.total):
                if dests[sid] in self_urls:
                    local_sids.append(sid)
                    sinks.append(LocalShardSink(
                        base + ctx.to_ext(sid), temp=True,
                        stats=stats))
                else:
                    sinks.append(RemoteShardSink(
                        dests[sid], v.id, sid, collection=collection,
                        headers=self.security.admin_headers))
            # (1) stream the volume through the GF pipeline; on return
            # every sink is finished (delivery verified) or aborted
            ec_encoder._generate_ec_files(base, ctx, sinks=sinks,
                                          stats=stats)
            t_encoded = _time.perf_counter()
            ec_encoder.save_ec_volume_info(base, ctx, dat_size,
                                           v.version)
            # (2) sidecars to every remote destination BEFORE any
            # commit: mount needs .ecx, and a destination must never
            # hold a visible shard it cannot serve.  One thread per
            # destination — the files are small, the round-trips are
            # what would serialize.
            remote_dests = sorted({u for s, u in dests.items()
                                   if s not in local_sids})
            sidecars: list[tuple[str, bytes]] = []
            for ext in (".ecx", ".vif", ".ecj"):
                if os.path.exists(base + ext):  # .ecj: post-encode
                    with open(base + ext, "rb") as sf:
                        sidecars.append((ext, sf.read()))  # noqa: SWFS013 — encode-plane sidecars (.ecx/.vif/.ecj) pushed whole by protocol, bounded by needle count

            def push_sidecars(url: str) -> None:
                try:
                    for ext, payload in sidecars:
                        st, body, _ = http_bytes(
                            "POST",
                            f"{url}/admin/receive_file?volumeId={v.id}"
                            f"&collection={collection}&ext={ext}",
                            payload,
                            headers=self.security.admin_headers(), timeout=60)
                        if st != 200:
                            raise OSError(f"push {ext} to {url}: {st} "
                                          f"{body[:200]!r}")
                except OSError:
                    _note_failed(url)
                    raise
            with ThreadPoolExecutor(
                    max_workers=max(1, len(remote_dests))) as spool:
                list(spool.map(push_sidecars, remote_dests))
            t_sidecars = _time.perf_counter()
            # (3) + (4) commit-and-mount: ONE batched round trip per
            # destination (every shard verified before any rename on
            # the receiving side, one mount rescan + one heartbeat per
            # dest instead of 14 of each), destinations in parallel
            by_dest_sids: dict[str, list[int]] = {}
            for sid in range(ctx.total):
                if sid not in local_sids:
                    by_dest_sids.setdefault(dests[sid], []).append(sid)

            def commit_dest(item):
                url, sids = item
                try:
                    r = http_json(
                        "POST", f"{url}/admin/ec/shard_write_commit",
                        {"volumeId": v.id, "collection": collection,
                         "mount": True,
                         "commits": [{"uploadId": sinks[sid].upload_id,
                                      "shardId": sid,
                                      "crc32": sinks[sid].crc,
                                      "bytes": sinks[sid].bytes}
                                     for sid in sids]},
                        headers=self.security.admin_headers(), timeout=30)
                    if "error" in r:
                        raise OSError(
                            f"commit {sids} on {url}: {r['error']}")
                except OSError:
                    _note_failed(url)
                    raise
                for sid in sids:
                    sinks[sid].mark_committed()
            with ThreadPoolExecutor(
                    max_workers=max(1, len(by_dest_sids))) as pool:
                list(pool.map(commit_dest, by_dest_sids.items()))
            for sid in local_sids:
                sinks[sid].commit()
            if local_sids:
                self.store.mount_ec_shards(v.id, collection,
                                           local_sids)
            else:
                # no shard stays here: drop the staging .ecx so the
                # source is not left resolving a stale EC base for
                # this vid forever (delete_volume only cleans .vif;
                # the destinations own their own sidecar copies)
                try:
                    os.remove(base + ".ecx")
                except OSError:
                    pass
            self._heartbeat_once()
            t_mounted = _time.perf_counter()
        except Exception as e:  # noqa: BLE001 — unwind, then report
            for sink in sinks:
                url = getattr(sink, "url", "")
                if url and (getattr(sink, "_error", None) is not None
                            or url in str(e)):
                    # the sink's send thread failed, or the raised
                    # error names this destination (finish()'s
                    # byte/CRC mismatch carries the dest url)
                    _note_failed(url)
                try:
                    sink.close()  # aborts anything uncommitted
                except OSError:
                    pass
            self._ec_scatter_unwind(v.id, collection, ctx, dests,
                                    base, vif_before)
            # failedDests lets the caller re-plan the stripe around
            # the dead destinations instead of failing the job
            return 500, {"error": f"scatter encode: {e}",
                         "failedDests": sorted(failed_dests)}
        wall = _time.perf_counter() - t_start
        tele = stats.summary(dat_size, wall)
        tele["mode"] = "scatter"
        tele["encodeSeconds"] = round(t_encoded - t_start, 3)
        tele["sidecarSeconds"] = round(t_sidecars - t_encoded, 3)
        tele["commitSeconds"] = round(t_mounted - t_sidecars, 3)
        self._record_scatter_metrics(stats, tele)
        return 200, {"shardIds": list(range(ctx.total)),
                     "placement": {str(s): u for s, u in dests.items()},
                     "localShardIds": local_sids,
                     "telemetry": tele}

    def _ec_scatter_unwind(self, vid: int, collection: str,
                           ctx: ECContext, dests: "dict[int, str]",
                           base: str,
                           vif_before: "bytes | None") -> None:
        """Failure unwind for a scatter encode: tear down anything a
        destination may already hold (committed shards, pushed
        sidecars) plus this node's local artifacts, so the still-live
        volume is the only copy the master serves.  delete_shards is
        idempotent and cleans sidecars when the last shard goes.  The
        .vif is RESTORED to its pre-encode bytes, never just deleted —
        for a tiered volume it is the only pointer to the remote
        .dat."""
        for url in sorted(set(dests.values())):
            try:
                http_json("POST", f"{url}/admin/ec/delete_shards",
                          {"volumeId": vid, "collection": collection,
                           "shardIds": list(range(ctx.total))},
                          headers=self.security.admin_headers(), timeout=30)
            except OSError:
                pass
        try:
            os.remove(base + ".ecx")  # staging index of the aborted run
        except OSError:
            pass
        try:
            if vif_before is not None:
                with open(base + ".vif", "wb") as vf:
                    vf.write(vif_before)
            else:
                os.remove(base + ".vif")
        except OSError:
            pass

    def _record_scatter_metrics(self, stats, tele: dict) -> None:
        """stats.py + telemetry.py emission for one scatter encode:
        the write-amplification claim must be OBSERVABLE in /metrics
        (bytes scattered per destination vs bytes written locally),
        not just inferred from the bench."""
        by_dest, latencies, local_bytes = stats.snapshot()
        for dest, nbytes in by_dest.items():
            self.metrics.counter_add(
                "ec_encode_bytes_scattered_total", float(nbytes),
                help_text="shard bytes streamed to placement targets "
                          "during scatter-encode",
                dest=dest)
        self.metrics.counter_add(
            "ec_encode_local_write_bytes_total", float(local_bytes),
            help_text="shard bytes written to this node's own disks "
                      "during scatter-encode")
        for seconds in latencies:
            self.metrics.histogram_observe(
                "ec_encode_push_slice_seconds", seconds,
                help_text="per-window destination push latency")
        self.metrics.counter_add("ec_scatter_encodes_total", 1.0,
                                 help_text="scatter encodes run")
        self.metrics.gauge_set(
            "ec_encode_volume_gbps", tele["volumeGbps"],
            help_text="volume-bytes/s of the last scatter encode")
        from .. import telemetry as _telemetry
        _telemetry.note_ec_scatter_encode(tele["bytesScatteredTotal"])

    # -- scatter shard_write receivers (the ReceiveFile twin for the
    # streaming encode path: temp + crc while streaming, atomic rename
    # only at explicit commit) ------------------------------------------

    def _ec_shard_write(self, req: Request):
        """Stream one shard's bytes (chunked) into a `.scatter.<id>`
        temp file with an incremental CRC32.  The shard stays invisible
        (unmounted, temp-named) until `shard_write_commit`; a stream
        that dies mid-body leaves nothing registered and the temp is
        removed."""
        import zlib
        vid = int(req.query["volumeId"])
        sid = int(req.query["shardId"])
        collection = req.query.get("collection", "")
        upload_id = req.query.get("uploadId", "")
        try:
            _check_path_fields(collection)
        except ValueError as e:
            return 400, {"error": str(e)}
        if not upload_id.isalnum():
            return 400, {"error": "bad uploadId"}
        self._reap_stale_shard_writes()
        base = self._base_path(vid, collection)
        tmp = f"{base}{to_ext(sid)}.scatter.{upload_id}"
        crc = 0
        halves = CopyHalves()
        ok = False
        try:
            # page-cache writes, like every other ReceiveFile surface
            # (receive_file, ec/copy): the scatter shard's durability
            # contract matches the seed balance-move it replaces —
            # integrity is the CRC + commit handshake, not fsync
            from .. import faults

            def chunks():
                for chunk in req.stream_body():
                    directive = faults.fire("volume.shard_write.recv",
                                            key=f"{vid}.{sid}")
                    if directive is not None:
                        # truncate/drop on the RECEIVER both mean the
                        # stream dies here: the temp is removed, the
                        # upload never registers, the sender errors
                        raise IOError(
                            f"shard_write {vid}.{sid}: fault-injected "
                            f"{directive} mid-stream")
                    yield chunk

            def running_crc(chunk):
                nonlocal crc
                crc = zlib.crc32(chunk, crc)

            with open(tmp, "wb") as f:
                n = copy_to_file(chunks(), f, halves, each=running_crc)
            ok = True
        finally:
            if not ok:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        tracing.emit_span_batch(halves.spans("receive"))
        with self._pending_lock:
            self._pending_shard_writes[upload_id] = {
                "path": tmp, "crc": crc, "bytes": n, "vid": vid,
                "sid": sid, "collection": collection,
                "stamp": time.monotonic()}
        return 200, {"bytes": n, "crc32": crc}

    def _reap_stale_shard_writes(self, max_age: float = 3600.0) -> None:
        """Drop staged uploads whose sender died without an abort —
        their temps must not accumulate forever."""
        now = time.monotonic()
        with self._pending_lock:
            stale = [k for k, rec in self._pending_shard_writes.items()
                     if now - rec["stamp"] > max_age]
            recs = [self._pending_shard_writes.pop(k) for k in stale]
        for rec in recs:
            try:
                os.remove(rec["path"])
            except OSError:
                pass

    def _ec_shard_write_commit(self, req: Request):
        """Verify the sender's CRC + byte count against what was
        streamed, then atomically rename the temp(s) to their final
        `.ecNN` names; `mount: true` mounts in the same step (the
        scatter source commits only after the whole stripe delivered +
        sidecars landed, so mount-on-commit can never mount a partial
        stripe).  Accepts a single upload ({uploadId, shardId, crc32,
        bytes}) or a batch (`commits: [...]`) — the scatter source
        commits all of one destination's shards in ONE round trip, all
        verified BEFORE any rename, with one mount + one heartbeat."""
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        commits = b.get("commits")
        if commits is None:
            commits = [{"uploadId": b.get("uploadId", ""),
                        "shardId": b.get("shardId", -1),
                        "crc32": b.get("crc32", -1),
                        "bytes": b.get("bytes", -1)}]
        recs: list[tuple[dict, dict]] = []
        with self._pending_lock:
            for c in commits:
                rec = self._pending_shard_writes.pop(
                    str(c.get("uploadId", "")), None)
                if rec is not None:
                    recs.append((c, rec))
        def _discard():
            for _c, rec in recs:
                try:
                    os.remove(rec["path"])
                except OSError:
                    pass
        if len(recs) != len(commits):
            _discard()
            return 404, {"error": f"unknown staged upload in "
                                  f"{[c.get('uploadId') for c in commits]}"}
        for c, rec in recs:
            sid = int(c["shardId"])
            if int(c.get("bytes", -1)) != rec["bytes"] or \
                    int(c.get("crc32", -1)) != rec["crc"] or \
                    vid != rec["vid"] or sid != rec["sid"] or \
                    collection != rec["collection"]:
                _discard()
                return 409, {"error":
                             f"shard {vid}.{sid} upload mismatch: "
                             f"staged {rec['bytes']}B crc "
                             f"{rec['crc']}, caller says "
                             f"{c.get('bytes')}B crc {c.get('crc32')}"}
        base = self._base_path(vid, collection)
        sids = []
        for c, rec in recs:
            sid = int(c["shardId"])
            os.replace(rec["path"], base + to_ext(sid))
            sids.append(sid)
        if b.get("mount"):
            self.store.mount_ec_shards(vid, collection, sids)
            self._heartbeat_once()
        return 200, {"shardIds": sids,
                     "bytes": sum(rec["bytes"] for _c, rec in recs)}

    def _ec_shard_write_abort(self, req: Request):
        b = req.json()
        upload_id = str(b.get("uploadId", ""))
        with self._pending_lock:
            rec = self._pending_shard_writes.pop(upload_id, None)
        if rec is not None:
            try:
                os.remove(rec["path"])
            except OSError:
                pass
        return 200, {}

    def _ec_mount(self, req: Request):
        """:443 VolumeEcShardsMount."""
        b = req.json()
        collection = b.get("collection", "")
        _check_path_fields(collection)
        ev = self.store.mount_ec_shards(
            int(b["volumeId"]), collection,
            [int(s) for s in b.get("shardIds", [])])
        self._heartbeat_once()
        return 200, {"shardIds": ev.shard_ids}

    def _ec_unmount(self, req: Request):
        """:464 VolumeEcShardsUnmount — honors shardIds so a balance
        move unmounts only the migrated shards.  Absent shardIds key =
        full unmount (HTTP-internal convention); present-but-empty =
        no-op (reference wire semantics)."""
        b = req.json()
        self.store.unmount_ec_shards(
            int(b["volumeId"]),
            [int(s) for s in b["shardIds"]]
            if "shardIds" in b else None)
        self._heartbeat_once()
        return 200, {}

    def _ec_copy(self, req: Request):
        """:228 VolumeEcShardsCopy: pull shard/index files from the
        source server's CopyFile endpoint."""
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        source = b["sourceDataNode"]
        base = self._base_path(vid, collection)
        exts = [to_ext(int(s)) for s in b.get("shardIds", [])]
        if exts:
            # streaming rebuild must keep this at zero for survivors;
            # balance moves are the legitimate remaining traffic
            self.metrics.counter_add(
                "ec_shard_whole_file_copies", float(len(exts)),
                help_text="whole shard files pulled via /admin/ec/copy")
        if b.get("copyEcxFile", False):
            exts.append(".ecx")
        if b.get("copyEcjFile", False) :
            exts.append(".ecj")
        if b.get("copyVifFile", False):
            exts.append(".vif")
        for ext in exts:
            status, _hdrs = http_download(
                f"{source}/admin/volume_file?volumeId={vid}"
                f"&collection={collection}&ext={ext}", base + ext,
                headers=self.security.admin_headers(), timeout=600)
            if status != 200:
                if ext == ".ecj":  # journal may legitimately not exist
                    continue
                return 500, {"error":
                             f"copy {ext} from {source}: {status}"}
        return 200, {}

    def _ec_delete_shards(self, req: Request):
        """:327 VolumeEcShardsDelete: remove local shard files; clean up
        index files when no shards remain."""
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        base = self._base_path(vid, collection)
        for s in b.get("shardIds", []):
            try:
                os.remove(base + to_ext(int(s)))
            except FileNotFoundError:
                pass
        vid_has_shards = any(
            os.path.exists(base + to_ext(s)) for s in range(32))
        if not vid_has_shards:
            for ext in (".ecx", ".ecj", ".vif"):
                try:
                    os.remove(base + ext)
                except FileNotFoundError:
                    pass
            self.store.unmount_ec_shards(vid)
        else:
            # refresh the mounted shard set
            self.store.mount_ec_shards(vid, collection, [])
        self._heartbeat_once()
        return 200, {}

    def _ec_rebuild(self, req: Request):
        """:149 VolumeEcShardsRebuild — streaming by default: survivors
        this node lacks are read in slice windows straight off their
        host servers' `/admin/ec/shard_read` (one concurrent prefetching
        stream per source) and fed through the staged GF pipeline, so
        repair never stages whole survivor files on this node's disks
        (arXiv:1908.01527 repair pipelining).  `mode: "local"` keeps the
        seed semantics (every survivor must already be local).  Remote
        survivor locations come from the request's `shardLocations`
        ({shard_id: [urls]}) or, absent that, a master ec_lookup —
        missing both, the handler degrades to the local behavior."""
        t_start = time.perf_counter()
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        base = self._base_path(vid, collection)
        extra_dirs = [loc.directory for loc in self.store.locations]
        if b.get("mode", "stream") == "local":
            generated = ec_encoder.rebuild_ec_files(
                base, additional_dirs=extra_dirs)
            return 200, {"rebuiltShardIds": generated, "mode": "local"}
        from ..storage.erasure_coding.shard_source import (
            LocalShardSource, RebuildStats, RemoteShardSource,
            rebuild_slice_bytes)
        ctx = ec_encoder.scheme_from_vif(base) or ECContext(
            int(b.get("dataShards") or 10),
            int(b.get("parityShards") or 4))
        # file discovery is the correctness anchor: survivors staged
        # by a prior VolumeEcShardsCopy exist on disk UNMOUNTED, and
        # the legacy gRPC copy-then-rebuild flow depends on seeing
        # them.  The mounted-shard registry only contributes the shard
        # size (sparing per-remote-source metadata round-trips).
        present_paths, local_missing = \
            ec_encoder.discover_shard_files(base, ctx, extra_dirs)
        ev = self.store.find_ec_volume(vid)
        size_hint = None
        if ev is not None:
            with ev.lock:
                if ev.shards:
                    size_hint = max(s.size for s in ev.shards.values())
        remote: dict[int, list[str]] = {}
        raw_locs = b.get("shardLocations")
        if raw_locs is None:
            raw_locs = self._master_shard_locations(vid)
        self_urls = {self.http.url, self.store.public_url}
        for sid_s, urls in (raw_locs or {}).items():
            sid = int(sid_s)
            urls = [u for u in urls if u not in self_urls]
            if sid not in present_paths and urls:
                remote[sid] = urls
        targets = [sid for sid in local_missing if sid not in remote]
        if not targets:
            return 200, {"rebuiltShardIds": [], "mode": "stream"}
        sources: dict[int, object] = {
            sid: LocalShardSource(p) for sid, p in present_paths.items()}
        # any d survivors reconstruct (every d x d generator submatrix
        # is invertible), so prefer the free ones: all local shards
        # first, then only (d - local) remote rows, round-robined
        # across donor nodes so no single peer's disk serializes the
        # fetch streams
        want_remote = max(ctx.data_shards - len(present_paths), 0)
        by_donor: dict[str, list[int]] = {}
        for sid in sorted(remote):
            by_donor.setdefault(remote[sid][0], []).append(sid)
        chosen: list[int] = []
        tiers = list(by_donor.values())
        i = 0
        while len(chosen) < want_remote and any(tiers):
            if tiers[i % len(tiers)]:
                chosen.append(tiers[i % len(tiers)].pop(0))
            i += 1
        for sid in chosen:
            sources[sid] = RemoteShardSource(
                remote[sid], vid, sid,
                headers=self.security.admin_headers)
        stats = RebuildStats()
        t0 = time.perf_counter()
        try:
            if size_hint is None and present_paths:
                size_hint = max(os.path.getsize(p)
                                for p in present_paths.values())
            generated = ec_encoder.rebuild_from_sources(
                base, ctx, sources, targets, stats=stats,
                slice_bytes=rebuild_slice_bytes() if chosen else None,
                shard_size=size_hint)
        except ValueError as e:
            return 500, {"error": str(e)}
        wall = time.perf_counter() - t0
        shard_size = os.path.getsize(base + ctx.to_ext(targets[0]))
        tele = stats.summary(ctx.data_shards * shard_size, wall)
        tele["mode"] = "stream"
        tele["rebuiltBytes"] = len(generated) * shard_size
        tele["setupSeconds"] = round(t0 - t_start, 3)
        self._record_rebuild_metrics(stats, tele)
        return 200, {"rebuiltShardIds": generated, "mode": "stream",
                     "telemetry": tele}

    def _master_shard_locations(self, vid: int) -> "dict[str, list[str]]":
        """Survivor locations for a rebuild that arrived without a
        `shardLocations` payload (e.g. over the gRPC bridge, whose proto
        has no such field): ask the master.  Unreachable master degrades
        to local-only rebuild semantics rather than failing repair."""
        from ..topology import fetch_ec_shard_locations, \
            shard_ids_to_urls
        try:
            return shard_ids_to_urls(
                fetch_ec_shard_locations(self.master, vid))
        except OSError:
            return {}

    def _record_rebuild_metrics(self, stats, tele: dict) -> None:
        """stats.py + telemetry.py emission for one streaming rebuild:
        bytes per source, slice latency histogram, effective GB/s."""
        by_source, latencies = stats.snapshot()
        for label, nbytes in by_source.items():
            self.metrics.counter_add(
                "ec_rebuild_bytes_fetched_total", float(nbytes),
                help_text="survivor bytes streamed into EC rebuild",
                source=label)
        for seconds in latencies:
            self.metrics.histogram_observe(
                "ec_rebuild_slice_seconds", seconds,
                help_text="per-slice survivor fetch latency")
        self.metrics.counter_add("ec_rebuilds_total", 1.0,
                                 help_text="streaming EC rebuilds run")
        self.metrics.gauge_set(
            "ec_rebuild_volume_gbps", tele["volumeGbps"],
            help_text="volume-bytes/s of the last streaming rebuild")
        from .. import telemetry as _telemetry
        _telemetry.note_ec_rebuild(tele["bytesFetchedTotal"])

    def _ec_to_volume(self, req: Request):
        """:586 VolumeEcShardsToVolume (decode EC -> normal volume)."""
        b = req.json()
        vid = int(b["volumeId"])
        collection = b.get("collection", "")
        base = self._base_path(vid, collection)
        if not ec_decoder.has_live_needles(base):
            return 400, {"error": f"volume {vid} has no live entries"}
        dat_size = ec_decoder.find_dat_file_size(base, base)
        # decode with the scheme the volume was encoded with
        scheme = ec_encoder.scheme_from_vif(base)
        n_data = scheme.data_shards if scheme else 10
        shard_files = [base + to_ext(i) for i in range(n_data)]
        ec_decoder.write_dat_file(base, dat_size, shard_files)
        ec_decoder.write_idx_file_from_ec_index(base)
        self.store.unmount_ec_shards(vid)
        self.store.mount_volume(vid, collection)
        self._wp_sync_volume(vid)
        self._heartbeat_once()
        return 200, {}

    def _ec_shard_read(self, req: Request):
        """:101 VolumeEcShardRead: raw range read of one local shard.

        Served from a PRIVATE fd over the shard file: shard files are
        immutable post-encode, so ranged reads need no shared-handle
        seek lock — concurrent rebuild slice streams off this node no
        longer serialize on ev.lock — and the FileSlice response rides
        the dispatcher's sendfile(2) zero-copy path instead of staging
        the slice through Python bytes."""
        vid = int(req.query["volumeId"])
        shard_id = int(req.query["shardId"])
        offset = int(req.query.get("offset", 0))
        size = int(req.query.get("size", 0))
        ev = self.store.find_ec_volume(vid)
        if ev is None or shard_id not in ev.shards:
            return 404, {"error": f"shard {vid}.{shard_id} not found"}
        shard = ev.shards[shard_id]
        n = max(0, min(size, shard.size - offset))
        from .. import faults
        directive = faults.fire("volume.shard_read.serve",
                                key=f"{vid}.{shard_id}")
        f = open(shard.path, "rb")
        f.seek(offset)
        if directive in ("truncate", "drop"):
            # a donor dying mid-serve: PROMISE n bytes, deliver fewer
            # (half, or none for drop), and sever the connection so
            # the reader sees EOF short of the Content-Length — the
            # exact signature RemoteShardSource's failover treats as a
            # dead donor, never as a short shard to zero-pad
            served = n // 2 if directive == "truncate" else 0
            req._handler.close_connection = True
            return 200, (FileSlice(f, served),
                         {"Content-Length": str(n)})
        return 200, (FileSlice(f, n), {"Content-Length": str(n)})

    def _scrub(self, req: Request):
        """server/volume_grpc_scrub.go ScrubVolume."""
        vid = int(req.json()["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return 404, {"error": f"volume {vid} not found"}
        count, errors = v.scrub()
        return 200, {"checked": count, "errors": errors}

    def _ec_scrub(self, req: Request):
        """server/volume_grpc_scrub.go ScrubEcVolume; modes index/local
        (shell/command_ec_scrub.go:52)."""
        b = req.json()
        vid = int(b["volumeId"])
        mode = b.get("mode", "local")
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return 404, {"error": f"ec volume {vid} not mounted"}
        if mode == "index":
            count, errors = ev.scrub_index()
            return 200, {"checked": count, "errors": errors,
                         "brokenShards": []}
        count, broken, errors = ev.scrub_local()
        return 200, {"checked": count, "errors": errors,
                     "brokenShards": broken}

    def _ec_info(self, req: Request):
        """:688 VolumeEcShardsInfo."""
        vid = int(req.query["volumeId"])
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            return 404, {"error": f"ec volume {vid} not mounted"}
        return 200, {
            "volumeId": vid,
            "collection": ev.collection,
            "shardIds": ev.shard_ids,
            "shardSize": ev.shard_size(),
            "dataShards": ev.ctx.data_shards,
            "parityShards": ev.ctx.parity_shards,
        }
