"""Filer HTTP server (weed/server/filer_server.go + handlers).

Public API mirrors the reference's filer HTTP surface:
  POST/PUT /path/to/file     upload (auto-chunked)
  GET      /path/to/file     ranged read
  GET      /path/to/dir/     JSON listing (?limit=&lastFileName=&prefix=)
  DELETE   /path             (?recursive=true for directories)
  HEAD     /path             existence/size probe
plus JSON-over-HTTP mirrors of key filer.proto RPCs:
  GET  /__meta__/lookup?path=         <- filer.proto LookupDirectoryEntry
  POST /__meta__/rename               <- filer.proto AtomicRenameEntry
  GET  /__meta__/events?sinceNs=      <- SubscribeMetadata (poll form)
"""

from __future__ import annotations

from ..filer import Entry, Filer
from ..filer.filer_store import SqliteStore
from .httpd import HttpServer, Request, parse_range


def cluster_statistics(master: str, collection: str = "") -> dict:
    """Aggregate used/total/file counts from the master topology —
    the filer Statistics feed (filer.proto Statistics) shared by the
    HTTP route, the gRPC servicer, and the mount's quota poll.
    Raises OSError when the master is unreachable."""
    from .httpd import http_json
    vl = http_json("GET", f"{master}/dir/status", timeout=30)
    cs = http_json("GET", f"{master}/cluster/status", timeout=30)
    used = files = max_count = 0
    for dc in vl.get("dataCenters", {}).values():
        for rack in dc.get("racks", {}).values():
            for node in rack.get("nodes", []):
                max_count += node.get("maxVolumeCount", 0)
                for v in node.get("volumes", []):
                    if collection and \
                            v.get("collection") != collection:
                        continue
                    used += v.get("size", 0)
                    files += v.get("fileCount", 0)
    total = cs.get("volumeSizeLimit", 0) * max(max_count, 1)
    return {"totalSize": total, "usedSize": used,
            "fileCount": files}


class FilerServer:
    def __init__(self, master: str, host: str = "127.0.0.1",
                 port: int = 0, store_path: str = ":memory:",
                 collection: str = "", replication: str = "",
                 meta_log_dir: str | None = None,
                 store_type: str = "sqlite",
                 notification: str = "",
                 lock_peers: "list[str] | None" = None,
                 reuse_port: bool = False):
        self._notification_spec = notification
        self._notifier = None
        self._lock_peers = lock_peers or []
        # bind the listener FIRST: the default metalog dir below needs
        # the RESOLVED port so two co-located filers derive distinct
        # dirs (binding also fails fast on a taken port, before any
        # store file is touched).  reuse_port: the pre-fork worker
        # mode — N filer processes share this listener, one sqlite
        # WAL store, and one metalog dir (exactly the supported
        # two-filers-one-store topology, multiplied)
        self.http = HttpServer(host, port, reuse_port=reuse_port)
        try:
            if meta_log_dir is None and store_path != ":memory:" and \
                    store_type in ("sqlite", "lsm"):
                # persist the metadata log beside the store by default —
                # subscribers must survive a filer restart
                # (filer_notify_append.go).  Only for LOCAL-path stores:
                # a redis/elastic store_path is a network ADDRESS, and
                # "host:port.metalog" would litter the working directory
                meta_log_dir = store_path + ".metalog"
            elif meta_log_dir is None and store_type in ("redis",
                                                         "elastic"):
                # per-address uniqueness (two filers on different redis
                # servers must not interleave one log) is NOT enough: two
                # CO-LOCATED filers sharing one redis/ES server would
                # still derive the same dir and interleave their
                # monotonic stamp clocks — so the dir carries this
                # filer's port too.  Path-safe chars only.  Port-0
                # (ephemeral, test) filers get a fresh dir per boot; a
                # production filer pins its port, so its log survives
                # restart like the sqlite/lsm case.
                safe = store_path.replace(":", "_").replace("/", "_")
                meta_log_dir = (f"filer-{store_type}-{safe}"
                                f"-p{self.http.port}.metalog")
            if store_type == "lsm":
                if store_path == ":memory:":
                    raise ValueError(
                        "the lsm store needs a directory path, not "
                        ":memory: (use -storeType sqlite for in-memory)")
                from ..filer.lsm_store import LsmStore
                store = LsmStore(store_path)
            elif store_type == "sqlite":
                store = SqliteStore(store_path)
            elif store_type == "redis":
                # store_path = host:port of a RESP server
                # (filer/redis_store.py; reference weed/filer/redis2)
                from ..filer.redis_store import RedisFilerStore, RespClient
                r_host, _, r_port = store_path.rpartition(":")
                if not r_host or not r_port.isdigit():
                    raise ValueError(
                        "-storeType redis needs -store host:port of a "
                        "RESP server")
                store = RedisFilerStore(RespClient(r_host, int(r_port)))
            elif store_type == "elastic":
                # store_path = host:port of an ES-wire server
                # (filer/elastic_store.py; reference weed/filer/elastic)
                from ..filer.elastic_store import (ElasticClient,
                                                   ElasticFilerStore)
                store = ElasticFilerStore(ElasticClient(store_path))
            else:
                raise ValueError(f"unknown filer store type "
                                 f"{store_type!r} "
                                 f"(sqlite|lsm|redis|elastic)")
            # the metadata cache's cross-filer coherence rides shared
            # metalog watermark files: sqlite/lsm siblings share the
            # store-derived dir by construction, redis/elastic
            # siblings deliberately keep distinct dirs (PR 6) — so the
            # cache defaults OFF for them (env =force overrides)
            import os as _os

            from ..filer.meta_plane import meta_plane_enabled
            from ..util.chunk_cache import read_cache_disk
            coherent = store_type not in ("redis", "elastic") or \
                _os.environ.get("SEAWEEDFS_TPU_FILER_META_CACHE") == \
                "force"
            # will the meta plane run for this store shape?  (Filer
            # makes the final call; this mirrors its gate so the
            # worker-mode cache decision below can see it.)
            plane_on = store_type in ("sqlite", "lsm") and \
                store_path != ":memory:" and \
                meta_plane_enabled() is not False
            if reuse_port and not plane_on and _os.environ.get(
                    "SEAWEEDFS_TPU_FILER_META_CACHE") != "force":
                # pre-fork worker mode WITHOUT the meta plane: N
                # co-located siblings over one store advance the
                # shared durable-ts watermark at the combined commit
                # rate, so a fill's expected servable lifetime is one
                # sibling commit window (~ms) — the cache degenerates
                # into pure invalidation bookkeeping (measured: 8.3 ->
                # 3.4 ms filer CPU/request at 4 workers under write
                # load).  With the plane ON the cache stays: sibling
                # commits arrive as per-path invalidations through
                # the plane's log follower, so fills survive (ISSUE
                # 13's worker-scalable coherence).
                coherent = False
            cache_dir, _ = read_cache_disk()
            self.filer = Filer(master, store,
                               collection=collection,
                               replication=replication,
                               meta_log_dir=meta_log_dir,
                               meta_cache=coherent,
                               chunk_cache_dir=(
                                   _os.path.join(
                                       cache_dir,
                                       f"filer{self.http.port}")
                                   if cache_dir else None))
        except BaseException:
            # the listener above is already bound; a store-setup
            # failure must not leak a socket that accepts (and
            # then hangs) connections with no server behind it
            self.http.abort()
            raise
        # native META plane (native/meta_plane.cc — the filer-side
        # sibling of the volume write plane): plain single-chunk PUTs
        # into provably-fresh directories are parsed, uploaded to the
        # volume write plane, WAL-appended and acked by a C++ epoll
        # loop; everything else 404s and the client falls back to this
        # port.  Kill switch SEAWEEDFS_TPU_FILER_META_PLANE_NATIVE=0;
        # requires the Python meta plane (the WAL protocol owner).
        self.native_meta = None
        if self.filer.meta_plane is not None:
            from .meta_plane_native import (NativeMetaPlane,
                                            native_meta_plane_enabled)
            if native_meta_plane_enabled() is not False:
                try:
                    mp_host = self.http.host if all(
                        c in "0123456789." for c in self.http.host) \
                        else "127.0.0.1"
                    self.native_meta = NativeMetaPlane(
                        self.filer.meta_log.dir, master, host=mp_host,
                        collection=collection,
                        replication=replication)
                except (RuntimeError, OSError):
                    self.native_meta = None  # pure-Python fallback
        # native READ plane (native/filer_read_plane.cc — the read
        # sibling): eligible warm GETs are parsed, looked up against a
        # C-side entry map, fetched from the volume read plane over
        # the shared persistent plane-socket pool and answered by a
        # C++ epoll loop; everything else 404s and the client falls
        # back to this port.  Kill switch
        # SEAWEEDFS_TPU_FILER_READ_PLANE_NATIVE=0.  Requires an event
        # channel covering every writer that can mutate the namespace:
        # this process's own listener always, plus the meta plane's
        # follower tap when pre-fork siblings share the store — so in
        # worker mode without the meta plane the read plane stays off
        # (a sibling's overwrite would never invalidate our map).
        self.native_read = None
        if self.filer.meta_plane is not None or not reuse_port:
            from .filer_read_plane_native import (
                NativeReadPlane, native_read_plane_enabled)
            if native_read_plane_enabled() is not False:
                try:
                    rp_host = self.http.host if all(
                        c in "0123456789." for c in self.http.host) \
                        else "127.0.0.1"
                    self.native_read = NativeReadPlane(master,
                                                       host=rp_host)
                except (RuntimeError, OSError):
                    self.native_read = None  # pure-Python fallback
        # directory/entry truth flows in from both sides: this
        # process's own Python-path mutations (listener) and every
        # sibling writer's WAL lines (the meta plane's follower tap,
        # fanned out when both native planes are up)
        taps = []
        if self.native_meta is not None:
            self.filer.subscribe(self.native_meta.on_event)
            taps.append(self.native_meta.on_follower_events)
        if self.native_read is not None:
            self.filer.subscribe(self.native_read.on_event)
            taps.append(self.native_read.on_follower_events)
        if taps and self.filer.meta_plane is not None:
            if len(taps) == 1:
                self.filer.meta_plane.sink = taps[0]
            else:
                def _fan_sink(evs, _taps=tuple(taps)):
                    evs = list(evs)  # both taps see the full batch
                    for t in _taps:
                        t(evs)
                self.filer.meta_plane.sink = _fan_sink
        if self.native_meta is not None:
            self.native_meta.arm(True)
            # flight-deck drainer (ISSUE 18): pull the plane's
            # per-request records into traces / FlightRecorder /
            # stage histograms on a tick + at /debug/slow scrape
            self.native_meta.start_record_drain()
        if self.native_read is not None:
            self.native_read.arm(True)
            self.native_read.start_record_drain()
        self.http.route("GET", "/status", self._status)
        self.http.route("POST", "/debug/meta_plane",
                        self._debug_meta_plane)
        self.http.route("POST", "/debug/read_plane",
                        self._debug_read_plane)
        self.http.route("GET", "/__meta__/lookup", self._meta_lookup)
        self.http.route("POST", "/__meta__/rename", self._meta_rename)
        self.http.route("POST", "/__meta__/set_attrs",
                        self._meta_set_attrs)
        self.http.route("POST", "/__meta__/create",
                        self._meta_create)
        self.http.route("POST", "/__meta__/put_entry",
                        self._meta_put_entry)
        self.http.route("POST", "/__meta__/patch_extended",
                        self._meta_patch_extended)
        self.http.route("GET", "/__meta__/events", self._meta_events)
        self.http.route("GET", "/__meta__/statistics",
                        self._meta_statistics)
        # distributed lock manager (weed/cluster/lock_manager) — the
        # filer hosts the lock ring, as in the reference.  Ring
        # membership comes from -lockPeers (every filer of a deployment
        # configured with the same list); each key hashes to exactly
        # one member, so clients dialing DIFFERENT filers still agree
        # on the lock host via movedTo redirects.  Without peers the
        # ring is this filer alone — correct for single-filer clusters,
        # and multi-filer deployments that skip -lockPeers get per-
        # filer (not cluster-wide) locks.
        from ..cluster import LockManager
        from ..cluster.lock_manager import normalize_address
        # ring identity is the NORMALIZED address (ADVICE r4): if the
        # operator's -lockPeers spelling differs from our advertised
        # url (localhost vs 127.0.0.1), exact-string membership would
        # make the owning filer redirect its own keys forever.
        # HttpServer.url is unbracketed host:port; bracket a v6 host
        # first or an address like ::1:8888 parses ambiguously
        self._ring_self = normalize_address(
            f"[{self.http.host}]:{self.http.port}"
            if ":" in self.http.host else self.http.url)
        self.lock_manager = LockManager(self._ring_self)
        if self._lock_peers:
            members = {normalize_address(p) for p in self._lock_peers}
            if self._ring_self not in members:
                # fail HARD (review r5): silently adding ourselves
                # would run a ring whose member list diverges from the
                # peers' (they don't list us under this spelling) —
                # two filers could then both compute target == self
                # for one key and grant the same cluster lock twice.
                # A diverged ring is worse than not starting.
                raise ValueError(
                    f"filer {self.http.url} (normalized "
                    f"{self._ring_self}) is not in -lockPeers "
                    f"{sorted(members)}; every filer must appear in "
                    f"the shared peer list under a spelling that "
                    f"normalizes to its advertised address")
            self.lock_manager.members = sorted(members)
        self.http.route("POST", "/admin/locks/acquire",
                        self._lock_acquire)
        self.http.route("POST", "/admin/locks/release",
                        self._lock_release)
        self.http.route("GET", "/admin/locks/list", self._lock_list)
        # metrics registry + /metrics endpoint (stats/metrics.go
        # FilerGather): the filer serves the same Prometheus text
        # plane as master/volume/s3, fed request_seconds by the httpd
        # middleware plus filer-specific gauges below
        from ..stats import Metrics
        self.metrics = Metrics("filer")
        self.http.route("GET", "/metrics", self._metrics, quiet=True)
        self.http.role = "filer"
        self.http.metrics = self.metrics
        from .debug import install_debug_routes
        install_debug_routes(self.http)  # util/grace/pprof.go analog
        self.http.guard = self._guard
        # pre-parsed prefix routes (httpd.route_prefix): the TUS and
        # interval-chunk planes resolve from the compiled table
        # instead of per-request startswith chains in the fallback
        for m in ("OPTIONS", "POST", "HEAD", "PATCH", "DELETE", "GET",
                  "PUT"):
            self.http.route_prefix(m, "/__tus__/", self._tus_route)
        self.http.route_prefix("POST", "/__chunk__/",
                               self._chunk_route)
        self.http.fallback = self._dispatch
        # QoS plane (qos.py): per-tenant admission at the filer edge
        # (tenant = auth principal / X-Tenant / anonymous), and this
        # filer's request_seconds feeds the background EC throttle
        from .. import qos
        qos.install(self.http, "filer")
        qos.throttle().add_metrics(f"filer:{self.http.port}",
                                   self.metrics)
        qos.throttle().maybe_start()
        # SLO autopilot (autopilot.py, ISSUE 20): closes the loop
        # over hedge/brownout/cache knobs and supervises both native
        # planes; the tick thread only spins when the env kill switch
        # allows (the registry still serves /debug/autopilot when
        # held, so the lever can re-enable without a restart)
        from .. import autopilot as _autopilot
        from .debug import install_autopilot_routes
        self.autopilot = _autopilot.build_for_filer(self)
        install_autopilot_routes(self.http, self.autopilot)
        self.autopilot.start()

    def _guard(self, req: Request):
        """Admin-plane gate (guard.go): the filer's /debug plane must
        honor the same admin JWT as every other role."""
        from .. import security
        from .httpd import is_admin_path
        if is_admin_path(req.path):
            err = security.current().check_admin(
                req.query, req.headers, req.remote_ip)
            if err:
                return 401, {"error": err}
        return None

    # -- distributed locks (distributed_lock_manager.go) ---------------

    def _lock_acquire(self, req: Request):
        b = req.json()
        key = str(b.get("key", ""))
        if not key:
            return 400, {"error": "missing lock key"}
        target = self.lock_manager.target_server(key)
        if target and target != self._ring_self:
            return 200, {"movedTo": target}
        r = self.lock_manager.acquire(
            key, str(b.get("owner", "")),
            float(b.get("ttlSec", 10.0)),
            str(b.get("renewToken", "")))
        if isinstance(r, str):
            return 423, {"error": "locked", "owner": r}
        token, expires_at = r
        return 200, {"renewToken": token, "expiresAt": expires_at}

    def _lock_release(self, req: Request):
        b = req.json()
        key = str(b.get("key", ""))
        target = self.lock_manager.target_server(key)
        if target and target != self._ring_self:
            return 200, {"movedTo": target}
        ok = self.lock_manager.release(key,
                                       str(b.get("renewToken", "")))
        if not ok:
            return 409, {"error": "token mismatch"}
        return 200, {}

    def _lock_list(self, req: Request):
        return 200, {"locks": self.lock_manager.all_locks()}

    def _metrics(self, req: Request):
        """Prometheus text endpoint (stats/metrics.go FilerGather
        analog): request_seconds arrives via the httpd middleware;
        namespace-shape gauges are refreshed per scrape."""
        self.metrics.gauge_set(
            "meta_log_last_ts_ns", float(self.filer.meta_log.last_ts()),
            help_text="timestamp of the newest metadata log event")
        self.metrics.gauge_set(
            "locks_held", float(len(self.lock_manager.all_locks())),
            help_text="distributed locks currently held here")
        if self.filer.meta_plane is not None:
            mp = self.filer.meta_plane.snapshot()
            self.metrics.gauge_set(
                "meta_plane_overlay_entries", float(mp["overlay"]),
                help_text="WAL-acked entries awaiting the async store "
                          "checkpoint (the overlay index)")
            self.metrics.gauge_set(
                "meta_plane_applier", float(bool(mp["holder"])),
                help_text="1 when this process holds the designated-"
                          "applier lock for the shared metalog")
            self.metrics.gauge_set(
                "meta_plane_checkpoint_ts_ns",
                float(mp["checkpointTsNs"]),
                help_text="newest event stamp the store checkpoint "
                          "covers")
        from ..stats import render_process
        return 200, ((self.metrics.render() +
                      self._native_meta_metrics_text() +
                      self._native_read_metrics_text() +
                      render_process()).encode(),
                     "text/plain; version=0.0.4")

    def _native_meta_metrics_text(self) -> str:
        """Native meta-plane counters rendered straight from the C++
        atomics at scrape time (the plane has no Python on its hot
        path): requests/fallbacks/fid pool, the ack latency histogram,
        and the per-stage wall split (parse / upload / wal) that keeps
        cluster.slow able to attribute a tail request that crossed the
        native plane."""
        nm = self.native_meta
        if nm is None:
            return ""
        st = nm.stats()
        out = []
        for key, help_text in (
                ("requests", "filer writes acked by the native meta "
                             "plane"),
                ("fallbacks", "native meta-plane requests answered "
                              "404 (python filer owns them)"),
                ("fid_misses", "native requests that fell back on an "
                               "empty pre-assigned fid pool"),
                ("wal_errors", "group-commit batches that failed the "
                               "WAL append (every member fell back)"),
                ("upstream_errors", "chunk uploads the volume write "
                                    "plane refused or dropped"),
                ("wal_batches", "group-commit barrier batches landed"),
                ("wal_lines", "WAL lines landed by the native plane")):
            name = f"filer_meta_plane_native_{key}_total"
            out.append(f"# HELP {name} {help_text}\n"
                       f"# TYPE {name} counter\n"
                       f"{name} {st[key]}\n")
        out.append("# HELP filer_meta_plane_native_stage_seconds_total"
                   " cumulative native-plane wall per stage\n"
                   "# TYPE filer_meta_plane_native_stage_seconds_total"
                   " counter\n")
        for stage in ("parse", "upload", "wal"):
            out.append(f"filer_meta_plane_native_stage_seconds_total"
                       f'{{stage="{stage}"}} '
                       f"{st[stage + '_ns'] / 1e9}\n")
        out.append("# HELP filer_meta_plane_native_fid_level "
                   "pre-assigned fids ready in the native pool\n"
                   "# TYPE filer_meta_plane_native_fid_level gauge\n"
                   f"filer_meta_plane_native_fid_level "
                   f"{max(nm.fid_level(), 0)}\n")
        from .meta_plane_native import ACK_BUCKETS_S
        buckets, count, total_s = nm.ack_histogram()
        out.append("# HELP filer_meta_plane_native_ack_seconds "
                   "native meta-plane ack latency\n"
                   "# TYPE filer_meta_plane_native_ack_seconds "
                   "histogram\n")
        for le, cum in zip(ACK_BUCKETS_S, buckets):
            out.append(f"filer_meta_plane_native_ack_seconds_bucket"
                       f'{{le="{le}"}} {cum}\n')
        out.append(f"filer_meta_plane_native_ack_seconds_bucket"
                   f'{{le="+Inf"}} {count}\n'
                   f"filer_meta_plane_native_ack_seconds_sum "
                   f"{total_s}\n"
                   f"filer_meta_plane_native_ack_seconds_count "
                   f"{count}\n")
        return "".join(out)

    def _native_read_metrics_text(self) -> str:
        """Native read-plane counters rendered straight from the C++
        atomics at scrape time: requests/fallbacks/stale/upstream, the
        response latency histogram, the entry-map gauge, and the
        per-stage wall split (parse / lookup / fetch / send) that
        keeps cluster.slow able to attribute a tail read that crossed
        the native plane."""
        nr = self.native_read
        if nr is None:
            return ""
        st = nr.stats()
        out = []
        for key, help_text in (
                ("requests", "filer reads served by the native read "
                             "plane"),
                ("fallbacks", "native read-plane requests answered "
                              "404 (python filer owns them)"),
                ("stale_misses", "native fetches the volume plane "
                                 "404'd (registration invalidated)"),
                ("upstream_errors", "chunk fetches the volume read "
                                    "plane refused or dropped")):
            name = f"filer_read_plane_native_{key}_total"
            out.append(f"# HELP {name} {help_text}\n"
                       f"# TYPE {name} counter\n"
                       f"{name} {st[key]}\n")
        out.append("# HELP filer_read_plane_native_stage_seconds_total"
                   " cumulative native-plane wall per stage\n"
                   "# TYPE filer_read_plane_native_stage_seconds_total"
                   " counter\n")
        for stage in ("parse", "lookup", "fetch", "send"):
            out.append(f"filer_read_plane_native_stage_seconds_total"
                       f'{{stage="{stage}"}} '
                       f"{st[stage + '_ns'] / 1e9}\n")
        out.append("# HELP filer_read_plane_native_entries "
                   "paths registered in the C-side entry map\n"
                   "# TYPE filer_read_plane_native_entries gauge\n"
                   f"filer_read_plane_native_entries {nr.entries()}\n")
        from .filer_read_plane_native import RESPONSE_BUCKETS_S
        buckets, count, total_s = nr.response_histogram()
        out.append("# HELP filer_read_plane_native_response_seconds "
                   "native read-plane response latency\n"
                   "# TYPE filer_read_plane_native_response_seconds "
                   "histogram\n")
        for le, cum in zip(RESPONSE_BUCKETS_S, buckets):
            out.append(
                f"filer_read_plane_native_response_seconds_bucket"
                f'{{le="{le}"}} {cum}\n')
        out.append(f"filer_read_plane_native_response_seconds_bucket"
                   f'{{le="+Inf"}} {count}\n'
                   f"filer_read_plane_native_response_seconds_sum "
                   f"{total_s}\n"
                   f"filer_read_plane_native_response_seconds_count "
                   f"{count}\n")
        return "".join(out)

    def _status(self, req: Request):
        """Plane discovery (the volume server's /status precedent):
        lean clients probe this once per process and pin their hot
        PUTs/GETs to the native plane ports."""
        nm = self.native_meta
        nr = self.native_read
        return 200, {"version": "seaweedfs-tpu/0.1",
                     "role": "filer",
                     "metaPlanePort":
                         nm.port if nm is not None and nm.armed else 0,
                     "readPlanePort":
                         nr.port if nr is not None and nr.armed else 0}

    def _debug_meta_plane(self, req: Request):
        """The PR 11 native_on/native_off lever, filer edition:
        POST /debug/meta_plane {"native": "on"|"off"} arms/disarms the
        native meta plane without tearing down its listener (clients
        keep their sockets; every request 404s to Python while off)."""
        nm = self.native_meta
        if nm is None:
            return 404, {"error": "native meta plane not running"}
        b = req.json() if req.body else {}
        want = str(b.get("native", "")).lower()
        if want in ("on", "1", "true"):
            nm.arm(True)
        elif want in ("off", "0", "false"):
            nm.arm(False)
        if "uploadDelayMs" in b:
            # ISSUE 18 failpoint: stall the native volume-upload hop
            # so a plane-served write lands in cluster.slow on demand
            try:
                nm.set_upload_delay_ms(int(b.get("uploadDelayMs")
                                           or 0))
            except (TypeError, ValueError):
                pass
        return 200, {"armed": nm.armed, "port": nm.port,
                     "fidLevel": max(nm.fid_level(), 0),
                     "recordsDropped": nm.records_dropped(),
                     **nm.stats()}

    def _debug_read_plane(self, req: Request):
        """The arm/disarm lever, read edition: POST /debug/read_plane
        {"native": "on"|"off"} arms/disarms the native read plane
        without tearing down its listener (clients keep their sockets;
        every request 404s to Python while off)."""
        nr = self.native_read
        if nr is None:
            return 404, {"error": "native read plane not running"}
        b = req.json() if req.body else {}
        want = str(b.get("native", "")).lower()
        if want in ("on", "1", "true"):
            nr.arm(True)
        elif want in ("off", "0", "false"):
            nr.arm(False)
        if "fetchDelayMs" in b:
            # chaos failpoint: stall the native volume-fetch hop so a
            # SIGKILL lands mid-flight / a plane-served read lands in
            # cluster.slow on demand
            try:
                nr.set_fetch_delay_ms(int(b.get("fetchDelayMs") or 0))
            except (TypeError, ValueError):
                pass
        return 200, {"armed": nr.armed, "port": nr.port,
                     "entries": nr.entries(),
                     "recordsDropped": nr.records_dropped(),
                     **nr.stats()}

    def start(self):
        self.http.start()
        # gRPC plane (filer.proto SeaweedFiler): entries CRUD, atomic
        # rename, streaming list, SubscribeMetadata fed by the meta
        # log, KV, distributed locks — the reference's most-trafficked
        # proto (filer.proto:13-87)
        try:
            from ..pb.filer_service import start_filer_grpc
            self.grpc_server, self.grpc_port = start_filer_grpc(
                self, host=self.http.host)
        except ImportError:     # grpcio absent: HTTP-only mode
            self.grpc_server, self.grpc_port = None, 0
        # follow stream: push-fed vid map + instant leader tracking
        # (the reference filer keeps KeepConnected open for the same
        # reason, masterclient.go:471)
        from .. import operation
        operation.enable_follow(self.filer.master)
        if self._notification_spec:
            # metadata notification fan-out (weed/notification):
            # every namespace mutation is published to the configured
            # sink with at-least-once delivery
            from .. import notification
            state = None
            if self.filer.meta_log.dir:
                import os
                state = os.path.join(self.filer.meta_log.dir,
                                     "notify.offset")
            self._notifier = notification.NotificationTailer(
                self.filer.meta_log,
                notification.from_spec(self._notification_spec),
                state_path=state).start()
        return self

    def stop(self):
        from .. import operation, qos
        if getattr(self, "autopilot", None) is not None:
            self.autopilot.stop()
        qos.throttle().remove_source(f"filer:{self.http.port}")
        operation.disable_follow(self.filer.master)
        if self._notifier is not None:
            self._notifier.stop()
        if getattr(self, "grpc_server", None) is not None:
            self.grpc_server.stop(grace=0.5)
        if getattr(self, "native_meta", None) is not None:
            # before the Python listener: once the native port stops
            # acking, clients retry here and must still find a server
            self.native_meta.stop()
        if getattr(self, "native_read", None) is not None:
            self.native_read.stop()
        self.http.stop()
        # meta plane first (final async apply), then store + metalog
        self.filer.close()

    @property
    def url(self) -> str:
        return self.http.url

    # -- dispatch ---------------------------------------------------------

    def _tus_route(self, req: Request):
        """Compiled-prefix entry for the TUS plane (see route_prefix
        registration): unquote once, delegate."""
        import urllib.parse
        return self._tus(req, urllib.parse.unquote(req.path))

    def _chunk_route(self, req: Request):
        import urllib.parse
        return self._chunk_write(
            req, urllib.parse.unquote(req.path)[len("/__chunk__"):])

    def _dispatch(self, req: Request):
        import urllib.parse
        # the wire path is percent-encoded (every client quotes);
        # storing it un-decoded would persist names like "a%21" for
        # "a!" — visible in listings and to in-process consumers.
        # (The /__tus__/ and /__chunk__/ planes normally resolve from
        # the compiled prefix table before this fallback runs; the
        # checks below keep percent-encoded spellings routing the way
        # they always did.)
        path = urllib.parse.unquote(req.path)
        if path.startswith("/__tus__/"):
            return self._tus(req, path)
        if path.startswith("/__chunk__/"):
            return self._chunk_write(req, path[len("/__chunk__"):])
        if req.method in ("POST", "PUT"):
            return self._put(req, path)
        if req.method in ("GET", "HEAD"):
            return self._get(req, path)
        if req.method == "DELETE":
            return self._delete(req, path)
        return 405, {"error": "method not allowed"}

    def _chunk_write(self, req: Request, path: str):
        """Interval chunk write (mount dirty-page flush target):
        POST /__chunk__/<path>?offset=N[&truncateTo=M] with raw bytes
        — appends overlapping chunks / clips length without rewriting
        the whole file (filer.proto UpdateEntry + AssignVolume)."""
        if req.method != "POST":
            return 405, {"error": "POST only"}
        offset = int(req.query.get("offset", 0))
        trunc = req.query.get("truncateTo")
        trunc = int(trunc) if trunc is not None else None
        try:
            if req.body or trunc is None:
                entry = self.filer.append_chunks(path, offset, req.body,
                                                 truncate_to=trunc)
            else:
                entry = self.filer.truncate_file(path, trunc)
        except IsADirectoryError:
            return 409, {"error": "is a directory"}
        except FileNotFoundError:
            return 404, {"error": "not found"}
        return 200, {"name": entry.name, "size": entry.total_size()}

    def _put(self, req: Request, path: str):
        if path.endswith("/"):
            # mkdir (filer_server_handlers_write.go mkdir on trailing /)
            e = Entry(path.rstrip("/") or "/", is_directory=True)
            self.filer.create_entry(e)
            return 201, {"name": e.name}
        mime = req.headers.get("Content-Type", "")
        if mime == "application/x-www-form-urlencoded":
            mime = ""
        from .. import faults, profiling
        # armed `filer.entry.put` faults fail the write BEFORE any
        # chunk is assigned — the caller's retry policy (not a
        # half-written entry) owns recovery
        faults.fire("filer.entry.put", key=path)
        # filer-funnel decomposition: assign/upload stages recorded by
        # operation.py (on the limiter pool threads, via use_track),
        # the metadata commit by filer.write_file — together they say
        # whether a slow filer write sat in master assigns, volume
        # round-trips, or the store
        with profiling.track("write", role="filer",
                             metrics=self.metrics):
            with profiling.stage("recv"):
                body = req.body
            entry = self.filer.write_file(path, body, mime=mime)
        return 201, {"name": entry.name, "size": entry.total_size()}

    def _get(self, req: Request, path: str):
        if path.endswith("/") or path == "":
            return self._list(req, path or "/")
        # read-plane fill fence (SWFS020 guard shape): capture the
        # plane's generation token BEFORE the store lookup, so the
        # warm fill below loses to any invalidation that raced it
        nr = self.native_read
        token = nr.begin_fill() if nr is not None else 0
        entry = self.filer.find_entry(path, count_negative=True)
        if entry is None:
            return 404, {"error": f"{path} not found"}
        if entry.is_directory:
            return self._list(req, path)
        if not entry.chunks and entry.extended.get("remote"):
            return self._get_remote(req, path, entry)
        rng = req.headers.get("Range", "")
        file_size = entry.total_size()
        parsed = parse_range(rng, file_size)
        if parsed == "unsatisfiable":
            return 416, (b"", {"Content-Range": f"bytes */{file_size}"})
        if parsed is None:
            rng = ""  # absent/malformed: full body (RFC 9110)
            offset, size = 0, file_size
        else:
            # parse_range already clamps size within [1, total-offset]
            offset, size = parsed
        mime = entry.attributes.mime or "application/octet-stream"
        # response-side QoS byte metering (qos.charge_response): held
        # for the whole response write, so a stampede of concurrent
        # big reads — hot-cache hits included — is bounded by the
        # tenant's in-flight-bytes budget like uploads are
        from .. import qos
        release, deny = qos.charge_response(req, size, "filer")
        if deny is not None:
            return deny
        # stream, never buffer: views fetch lazily as the response
        # drains (through the hot chunk cache), so a multi-GB GET
        # holds one chunk in memory, not the file
        body = self.filer.open_read_stream(entry, offset, size,
                                           on_close=release)
        if nr is not None and not rng:
            # warm fill: the NEXT read of this path can be served
            # natively (fenced by the pre-lookup token above)
            nr.warm_fill(path, entry, token)
        headers = {"Content-Type": mime,
                   "Content-Length": str(size)}
        if rng:
            headers["Content-Range"] = \
                f"bytes {offset}-{offset + size - 1}/{file_size}"
            return 206, (body, headers)
        return 200, (body, headers)

    def _get_remote(self, req: Request, path: str, entry):
        """Read-through for uncached remote-mounted entries
        (filer_remote_read: fetch from the foreign store on demand;
        remote.cache materializes local chunks so this path stops
        being hit)."""
        import json as _json
        from ..remote import RemoteError, remote_for_path
        try:
            located = remote_for_path(self.url, path)
            if located is None:
                return 404, {"error": f"{path}: remote mount gone"}
            client, key = located
            marker = _json.loads(entry.extended["remote"])
            total = int(marker.get("size", 0))
            parsed = parse_range(req.headers.get("Range", ""), total)
            if parsed == "unsatisfiable":
                return 416, (b"", {"Content-Range": f"bytes */{total}"})
            if parsed is not None:
                offset, size = parsed
                data = client.read(key, offset, size)
                end = offset + len(data) - 1
                return 206, (data, {
                    "Content-Type": "application/octet-stream",
                    "Content-Range": f"bytes {offset}-{end}/{total}"})
            return 200, (client.read(key),
                         "application/octet-stream")
        except FileNotFoundError:
            return 404, {"error": f"{path}: gone on remote"}
        except (RemoteError, OSError, ValueError) as e:
            return 502, {"error": f"remote read {path}: {e}"}

    def _list(self, req: Request, path: str):
        limit = int(req.query.get("limit", 1000))
        last = req.query.get("lastFileName", "")
        prefix = req.query.get("prefix", "")
        entries = self.filer.list_directory(
            path.rstrip("/") or "/", start_file=last, limit=limit,
            prefix=prefix)
        return 200, {
            "path": path,
            "entries": [e.to_json() for e in entries],
            "lastFileName": entries[-1].name if entries else "",
            "shouldDisplayLoadMore": len(entries) >= limit,
        }

    def _delete(self, req: Request, path: str):
        recursive = req.query.get("recursive", "") == "true"
        # ignoreChunks: remove metadata only (filer.proto
        # DeleteEntryRequest.is_delete_data=false) — multipart
        # completion strips its scratch dir while the final entry now
        # references the parts' chunks
        keep_chunks = req.query.get("ignoreChunks", "") == "true"
        try:
            self.filer.delete_entry(path.rstrip("/") or "/",
                                    recursive=recursive,
                                    delete_chunks=not keep_chunks)
        except IsADirectoryError as e:
            return 409, {"error": str(e)}
        return 204, b""

    # -- TUS resumable uploads (filer_server_tus_handlers.go) -------------

    TUS_VERSION = "1.0.0"
    _TUS_DIR = "/.tus"

    def _tus(self, req: Request, path: str):
        """tus.io core protocol: creation (POST), offset probe (HEAD),
        append (PATCH), abort (DELETE).  Upload parts are staged as
        filer files under /.tus/<id>/ — resumable across filer
        restarts — and the completed upload materializes by STITCHING
        the parts' chunk lists (no data copy, the multipart-complete
        trick)."""
        tus_headers = {"Tus-Resumable": self.TUS_VERSION,
                       "Tus-Version": self.TUS_VERSION,
                       "Tus-Extension": "creation,termination"}
        if req.method == "OPTIONS":
            return 204, (b"", tus_headers)
        if req.method == "POST":
            try:
                length = int(req.headers.get("Upload-Length", -1))
            except ValueError:
                length = -1
            target = req.query.get("path", "")
            if length < 0 or not target:
                return 400, {"error": "Upload-Length header and "
                                      "?path= are required"}
            import uuid as _uuid
            uid = _uuid.uuid4().hex
            marker = Entry(f"{self._TUS_DIR}/{uid}",
                           is_directory=True)
            marker.extended["tusTarget"] = target
            marker.extended["tusLength"] = str(length)
            self.filer.create_entry(marker)
            h = dict(tus_headers)
            h["Location"] = f"/__tus__/{uid}"
            return 201, (b"", h)

        uid = path[len("/__tus__/"):].strip("/")
        if not uid or "/" in uid:
            # an empty id would resolve to the /.tus staging ROOT —
            # DELETE would then wipe every in-flight upload
            return 404, {"error": "unknown upload"}
        updir = f"{self._TUS_DIR}/{uid}"
        marker = self.filer.find_entry(updir)
        if marker is None or not marker.extended.get("tusTarget"):
            return 404, {"error": "unknown upload"}
        length = int(marker.extended.get("tusLength", 0))
        parts = sorted(
            (e for e in self.filer.list_directory(updir, limit=100000)
             if e.name.endswith(".part")),
            key=lambda e: int(e.name.split(".")[0]))
        offset = sum(e.total_size() for e in parts)

        if req.method == "HEAD":
            h = dict(tus_headers)
            h.update({"Upload-Offset": str(offset),
                      "Upload-Length": str(length),
                      "Cache-Control": "no-store"})
            return 200, (b"", h)
        if req.method == "DELETE":
            self.filer.delete_entry(updir, recursive=True)
            return 204, (b"", tus_headers)
        if req.method == "PATCH":
            try:
                claimed = int(req.headers.get("Upload-Offset", -1))
            except ValueError:
                claimed = -1
            if claimed != offset:
                # 409: the client's view of the offset is stale
                h = dict(tus_headers)
                h["Upload-Offset"] = str(offset)
                return 409, (b"", h)
            data = req.body
            if offset + len(data) > length:
                return 413, {"error": "upload exceeds Upload-Length"}
            self.filer.write_file(f"{updir}/{offset:020d}.part", data)
            offset += len(data)
            if offset == length:
                # materialize: stitch part chunk lists, zero data copy
                target = marker.extended["tusTarget"]
                chunks = []
                base = 0
                parts = sorted(
                    (e for e in self.filer.list_directory(
                        updir, limit=100000)
                     if e.name.endswith(".part")),
                    key=lambda e: int(e.name.split(".")[0]))
                for p in parts:
                    for c in p.chunks:
                        chunks.append(type(c)(
                            c.file_id, base + c.offset, c.size,
                            c.e_tag, c.mtime_ns))
                    base += p.total_size()
                old = self.filer.find_entry(target)
                final = Entry(target, chunks=chunks)
                self.filer.create_entry(final)
                if old is not None and not old.is_directory:
                    # reclaim the replaced file's chunks, matching
                    # write_file's overwrite semantics — create_entry
                    # alone would orphan them on the volume servers
                    self.filer._delete_chunks(old)
                self.filer.delete_entry(updir, recursive=True,
                                        delete_chunks=False)
            h = dict(tus_headers)
            h["Upload-Offset"] = str(offset)
            return 204, (b"", h)
        return 405, {"error": f"method {req.method} not allowed"}

    # -- meta RPC mirrors -------------------------------------------------

    def _meta_lookup(self, req: Request):
        entry = self.filer.find_entry(req.query["path"])
        if entry is None:
            return 404, {"error": "not found"}
        return 200, entry.to_json()

    def _meta_rename(self, req: Request):
        b = req.json()
        try:
            self.filer.rename(b["oldPath"], b["newPath"])
        except FileNotFoundError as e:
            return 404, {"error": str(e)}
        return 200, {}

    def _meta_set_attrs(self, req: Request):
        """Attribute-only update (filer.proto UpdateEntry with unchanged
        chunks) — filer.sync uses this to propagate mode/uid/gid/mtime
        that the content PUT cannot carry."""
        b = req.json()
        entry = self.filer.find_entry(b["path"])
        if entry is None:
            return 404, {"error": "not found"}
        from ..filer.entry import Attributes
        entry.attributes = Attributes.from_json(b.get("attributes", {}))
        self.filer.create_entry(entry, create_parents=False)
        return 200, {}

    def _meta_create(self, req: Request):
        """Create/replace a chunkless entry with extended metadata —
        the remote-mount pointer entries (filer_pb.RemoteEntry shape)
        and remote.uncache both need an entry with metadata but no
        content."""
        from ..filer.entry import Entry
        b = req.json()
        entry = Entry(b["path"],
                      is_directory=bool(b.get("isDirectory")))
        entry.extended = dict(b.get("extended", {}))
        old_entry = self.filer.find_entry(b["path"])
        self.filer.create_entry(entry)
        if old_entry is not None and old_entry.chunks:
            # replacing a file with a chunkless entry (uncache /
            # remote-pointer refresh) must reclaim the old content —
            # write_file does the same for content overwrites
            self.filer._delete_chunks(old_entry)
        return 200, {}

    def _meta_put_entry(self, req: Request):
        """Full-entry create/replace (filer.proto CreateEntry):
        attributes, extended metadata AND chunk list — what remote
        gateways (weed s3 -filer) need to write entries they
        assembled themselves (multipart completion, delete markers,
        config mutations)."""
        from ..filer.entry import Entry
        self.filer.create_entry(Entry.from_json(req.json()))
        return 200, {}

    def _meta_patch_extended(self, req: Request):
        """Merge extended keys into an entry, keeping chunks/attrs."""
        b = req.json()
        entry = self.filer.find_entry(b["path"])
        if entry is None:
            return 404, {"error": "not found"}
        entry.extended.update(b.get("extended", {}))
        self.filer.create_entry(entry, create_parents=False)
        return 200, {}

    def _meta_statistics(self, req: Request):
        """Cluster usage aggregated from the master topology
        (filer.proto Statistics; also the mount's quota feed —
        weedfs_quota.go polls the same numbers)."""
        try:
            return 200, cluster_statistics(
                self.filer.master, req.query.get("collection", ""))
        except OSError as e:
            return 503, {"error": str(e)}

    def _meta_events(self, req: Request):
        since = int(req.query.get("sinceNs", 0))
        limit = int(req.query.get("limit", 0))
        return 200, {"events": self.filer.events_since(since, limit)}
