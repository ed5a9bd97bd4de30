"""The `tpu_ec` worker handler — the north-star TPU entry point.

Mirrors the reference's canonical JobHandler
(plugin/worker/erasure_coding_handler.go: Capability :48, Descriptor
:61, Detect :187, Execute :445 delegating to
worker/tasks/erasure_coding/ec_task.go:59):

    markVolumeReadonly        (:261)
    copyVolumeFilesToWorker   (:300)  <- bulk .dat/.idx pull
    generateEcShardsLocally   (:426)  <- THE TPU HOT PATH: the worker
                                         owns the accelerator
                                         (ec_context.own_device); the
                                         result names where it ran
    distributeEcShards        (:532)  -> ReceiveFile pushes to targets
    mountEcShards             (shard_distribution.go:209)
    deleteOriginalVolume      (:547)
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ... import tracing
from ...operation import master_json
from ...server.httpd import (CopyHalves, http_download, http_json,
                             http_upload)
from ...storage.erasure_coding import ECContext
from ...storage.erasure_coding import ec_context, ec_decoder, ec_encoder
from ...storage.erasure_coding.ec_context import to_ext
from ...storage.erasure_coding.shard_sink import DatShardView
from ...topology import iter_volume_list_volumes
from ..worker import JobHandler


from ..worker import must as _must

# how many of the master's pulses a job gives a server the master has
# just let go of (a beat late, the master's machine stalled): at its
# start, before it takes the servers named as the spread it promises,
# and at distribute, before it fails rather than place narrower.  The
# master lets go after three; the fourth is for the beat that follows.
PLACEMENT_WAIT_PULSES = 4


def _cluster_status(worker) -> dict:
    return master_json(worker.master, "GET", "/cluster/status", timeout=30)


def _await_cluster(worker, settled) -> "tuple[dict, float, bool]":
    """(the master's /cluster/status, seconds spent asking again,
    whether `settled(status)` held at last): asks every quarter pulse
    until it holds, for PLACEMENT_WAIT_PULSES at most."""
    t0 = time.monotonic()
    waited = 0.0
    while True:
        status = _cluster_status(worker)
        pulse = float(status["pulseSeconds"])
        ok = settled(status)
        if ok or waited >= PLACEMENT_WAIT_PULSES * pulse:
            return status, waited, ok
        time.sleep(pulse / 4)
        waited = time.monotonic() - t0


def _servers_at_start(worker) -> "tuple[list[str], float]":
    """(the servers a job starts under, seconds it waited for them):
    those the master holds alive, once it has let go of none within
    the last PLACEMENT_WAIT_PULSES.  One look in such a second would
    take a cluster of three for one of two and promise 7/7; a server
    gone for longer is no part of the cluster."""
    def settled(status: dict) -> bool:
        wait = PLACEMENT_WAIT_PULSES * float(status["pulseSeconds"])
        return all(gone >= wait
                   for gone in status["silentDataNodes"].values())
    status, waited, _ = _await_cluster(worker, settled)
    return status["dataNodes"], waited


class EcEncodeHandler(JobHandler):
    job_type = "erasure_coding"
    aliases = ["ec", "erasure-coding"]

    def __init__(self, fullness_ratio: float = 0.9,
                 collection_filter: str | None = None,
                 data_shards: int = 10, parity_shards: int = 4,
                 backend: str | None = None,
                 encode_mode: str = "worker"):
        self.fullness_ratio = fullness_ratio
        self.collection_filter = collection_filter
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.backend = backend  # None -> ec_context.default_backend()
        # "worker": pull the volume here, encode on this worker's
        # accelerator, distribute (the TPU hot path).  "scatter": drive
        # the SOURCE server's scatter-encode — placement-first, shard
        # windows streamed straight to their destinations; the worker
        # only orchestrates (no volume bytes cross the plugin boundary)
        self.encode_mode = encode_mode

    def capability(self) -> dict:
        # weight 80 per erasure_coding_handler.go:48
        return {"jobType": self.job_type, "canDetect": True,
                "canExecute": True, "weight": 80}

    def descriptor(self) -> dict:
        """Declarative admin/worker config forms (handler :61)."""
        return {"jobType": self.job_type, "fields": [
            {"name": "fullnessRatio", "type": "float",
             "default": self.fullness_ratio,
             "help": "encode volumes fuller than this fraction"},
            {"name": "collectionFilter", "type": "string",
             "default": self.collection_filter or "",
             "help": "only encode volumes of this collection"},
            {"name": "dataShards", "type": "int",
             "default": self.data_shards},
            {"name": "parityShards", "type": "int",
             "default": self.parity_shards},
            {"name": "encodeMode", "type": "string",
             "default": self.encode_mode,
             "help": "worker (pull+encode here) or scatter "
                     "(source streams shards to placement targets)"},
        ]}

    # -- Detect (:187) ------------------------------------------------

    def detect(self, worker) -> list[dict]:
        vl = master_json(worker.master, "GET", "/vol/list", timeout=30)
        size_limit = self._volume_size_limit(worker)
        proposals = []
        seen = set()
        for _node, v in iter_volume_list_volumes(vl):
            vid = v["id"]
            if vid in seen:
                continue
            seen.add(vid)
            if self.collection_filter not in (None, "") and \
                    v.get("collection", "") != self.collection_filter:
                continue
            if v.get("size", 0) < self.fullness_ratio * size_limit:
                continue
            proposals.append({
                "jobType": self.job_type,
                "dedupeKey": f"ec:{vid}",
                "params": {
                    "volumeId": vid,
                    "collection": v.get("collection", ""),
                    "dataShards": self.data_shards,
                    "parityShards": self.parity_shards,
                },
            })
        return proposals

    def _volume_size_limit(self, worker) -> int:
        r = master_json(worker.master, "GET", "/cluster/status", timeout=30)
        return int(r.get("volumeSizeLimit", 1 << 30))

    # -- Execute (ec_task.go:59) ---------------------------------------

    def _make_ctx(self, params: dict, collection: str,
                  vid: int) -> ECContext:
        ctx_kw = {}
        if self.backend:
            ctx_kw["backend"] = self.backend
        return ECContext(
            int(params.get("dataShards", self.data_shards)),
            int(params.get("parityShards", self.parity_shards)),
            collection, vid, **ctx_kw)

    def _lookup_urls(self, worker, vid: int) -> list[str]:
        locations = master_json(worker.master, "GET",
                                f"/dir/lookup?volumeId={vid}"
                                , timeout=30).get("locations", [])
        if not locations:
            raise RuntimeError(f"volume {vid} has no locations")
        return [l["url"] for l in locations]

    # Each step below is one span under the worker's `job:<type>` span
    # (tracing.py), named the same in the single, batch and rebuild
    # handlers: ec.mark_readonly, ec.pull, ec.sort_index, ec.encode,
    # ec.distribute (ec.push per file, ec.mount per target),
    # ec.delete_source.  The bulk paths forward the trace parent
    # (httpd._trace_headers), so the volume servers' own spans of a
    # pull or a push hang under these.

    def _mark_readonly(self, urls: list[str], vid: int) -> None:
        # (:261)
        with tracing.span("ec.mark_readonly", role="worker"):
            for url in urls:
                _must(http_json("POST", f"{url}/admin/set_readonly",
                                {"volumeId": vid, "readOnly": True},
                                timeout=30),
                      f"set readonly on {url}")

    def _pull_volume(self, worker, vid: int, collection: str,
                     source: str, base: str) -> None:
        """Copy .dat/.idx to the worker (:300) — the bulk pull the
        plugin boundary is designed to carry.  Streamed to disk in
        chunks (http_download): a 30GB volume must never be buffered in
        worker RAM (the reference streams CopyFile the same way,
        ec_task.go:300 / volume_server.proto:69)."""
        os.makedirs(worker.work_dir, exist_ok=True)
        with tracing.span("ec.pull", role="worker") as sp:
            sp.set("source", source)
            pulled = 0
            # one pair of stage spans for both files: the socket's half
            # of the copy against the tmpfs write's
            halves = CopyHalves()
            for ext in (".dat", ".idx"):
                status, _hdrs = http_download(
                    f"{source}/admin/volume_file?volumeId={vid}"
                    f"&collection={collection}&ext={ext}", base + ext,
                    timeout=600, halves=halves)
                if status != 200:
                    raise RuntimeError(
                        f"copy {ext} from {source}: {status}")
                pulled += os.path.getsize(base + ext)
            sp.set("bytes", pulled)
            tracing.emit_span_batch(halves.spans("pull"))

    def _unwind_volumes(self, worker, collection: str, ctx: ECContext,
                        vol_urls: "dict[int, list[str]]") -> None:
        """Failure unwind, in order: (1) tear down any
        distributed/mounted shards so the master never serves stale EC
        state alongside the still-live volume, then (2) restore
        writability so the volume is not stranded readonly."""
        try:
            targets = master_json(worker.master, "GET",
                                  "/cluster/status", timeout=30)["dataNodes"]
        except (OSError, KeyError):
            targets = []
        for vid, urls in vol_urls.items():
            for target in targets:
                try:
                    http_json("POST",
                              f"{target}/admin/ec/delete_shards",
                              {"volumeId": vid,
                               "collection": collection,
                               "shardIds": list(range(ctx.total))}, timeout=30)
                except OSError:
                    pass
            for url in urls:
                try:
                    http_json("POST", f"{url}/admin/set_readonly",
                              {"volumeId": vid, "readOnly": False}, timeout=30)
                except OSError:
                    pass

    @staticmethod
    def _cleanup_local(base: str, ctx: ECContext) -> None:
        for ext in [".dat", ".idx", ".ecx", ".ecj", ".vif"] + \
                [to_ext(i) for i in range(ctx.total)]:
            try:
                os.remove(base + ext)
            except FileNotFoundError:
                pass

    def _delete_originals(self, urls: list[str], vid: int) -> None:
        # (:547) — only after every shard is safely mounted
        with tracing.span("ec.delete_source", role="worker"):
            for url in urls:
                _must(http_json("POST", f"{url}/admin/delete_volume",
                                {"volumeId": vid}, timeout=30),
                      f"delete original on {url}")

    def execute(self, worker, job_id: str, params: dict) -> str:
        if params.get("encodeMode", self.encode_mode) == "scatter":
            if "volumeIds" in params:
                # scatter has no mesh-batch form (each volume streams
                # from its own source); run the volumes sequentially
                # rather than silently falling back to the
                # pull-everything worker path
                out = []
                for v in dict.fromkeys(int(x)
                                       for x in params["volumeIds"]):
                    p = dict(params, volumeId=v)
                    p.pop("volumeIds", None)
                    out.append(self.execute_scatter(worker, job_id, p))
                return "\n".join(out)
            return self.execute_scatter(worker, job_id, params)
        if "volumeIds" in params:
            return self.execute_batch(worker, job_id, params)
        vid = int(params["volumeId"])
        collection = params.get("collection", "")
        ctx = self._make_ctx(params, collection, vid)
        urls = self._lookup_urls(worker, vid)
        base = os.path.join(worker.work_dir, f"{vid}")
        # the pull-then-push path moves volume bytes THROUGH this
        # worker, which serves no foreground traffic of its own — so
        # the feedback throttle watches the source/dest volume
        # servers' /metrics for the job's duration (qos.py; a no-op
        # unless an SLO is configured)
        from ... import qos
        try:
            with qos.remote_slo_watch(urls):
                placement = self._encode_and_distribute(
                    worker, job_id, vid, collection, ctx, urls,
                    urls[0], base)
        except Exception:
            self._unwind_volumes(worker, collection, ctx, {vid: urls})
            raise
        finally:
            self._cleanup_local(base, ctx)
        self._delete_originals(urls, vid)
        return (f"volume {vid}: {ctx} shards encoded on worker "
                f"({_ran_on(ctx)}) and distributed to "
                f"{sum(1 for s in placement.values() if s)} servers")

    def execute_scatter(self, worker, job_id: str,
                        params: dict) -> str:
        """Admin-driven scatter-encode OFF the shell path: the worker
        plans placement and drives the source server's streaming
        scatter generate (`/admin/ec/generate` + placement) — volume
        bytes flow source -> destinations directly, never through this
        worker.  Runs under the cluster admin lease (the shell's lock)
        so placement cannot interleave with an operator's balance."""
        from ...shell.commands import _do_ec_encode
        from .balance import _LockedShellRun
        vid = int(params["volumeId"])
        collection = params.get("collection", "")
        worker.report_progress(job_id, 0.1,
                               f"scatter-encoding volume {vid}")
        opts = {"collection": collection}
        if "dataShards" in params:
            opts["dataShards"] = params["dataShards"]
        if "parityShards" in params:
            opts["parityShards"] = params["parityShards"]
        with _LockedShellRun(worker.master) as env:
            msg = _do_ec_encode(
                env, vid,
                int(params.get("dataShards", self.data_shards)),
                int(params.get("parityShards", self.parity_shards)),
                opts, mode="scatter")
        worker.report_progress(job_id, 0.9, "scattered and mounted")
        return msg

    def _encode_and_distribute(self, worker, job_id: str, vid: int,
                               collection: str, ctx: ECContext,
                               urls: list[str], source: str,
                               base: str) -> dict:
        self._mark_readonly(urls, vid)
        # the spread the job starts under is the one it places on
        started = _servers_at_start(worker)
        worker.report_progress(job_id, 0.1, "marked readonly")
        self._pull_volume(worker, vid, collection, source, base)
        worker.report_progress(job_id, 0.3, "copied volume files")

        # 3. encode locally (:426) — on the device this worker owns
        dat_size = os.path.getsize(base + ".dat")
        version = _read_dat_version(base)
        _sort_index(base)
        with tracing.span("ec.encode", role="worker") as sp:
            # the .dat stays here until the job's end, so a data shard
            # is not written a second time: it is sent as ranges of it
            views = ec_encoder.write_parity_files(
                base, ctx, progress=_encode_progress(
                    functools.partial(worker.report_progress, job_id)))
            # the result names where it ran, so /maintenance/job and
            # the job's trace can tell a TPU encode from anything else
            for key, val in _device_report(ctx).items():
                sp.set(key, val)
        ec_encoder.save_ec_volume_info(base, ctx, dat_size, version)
        worker.report_progress(
            job_id, 0.6, f"encoded {ctx.total} shards ({_ran_on(ctx)})")

        # consistency check (:638 verifyDatIdxConsistency analog):
        # decode geometry must reproduce the source size
        if ec_decoder.find_dat_file_size(base, base, version) > dat_size:
            raise RuntimeError("ecx entries exceed dat size")

        # 4+5. distribute + mount
        where = _pushed_files(base, ctx)
        where.update((to_ext(v.shard_id), v) for v in views)
        placement = self._distribute_and_mount(worker, vid, collection,
                                               ctx, where, started)
        worker.report_progress(job_id, 0.8, "distributed shards")
        return placement

    @staticmethod
    def _placement_targets(worker, started: list[str]
                           ) -> "tuple[list[str], float]":
        """(the servers to place on, seconds waited for them): those
        the job started under, in the master's order, once the master
        names every one of them alive.  A server it does not name is
        waited for PLACEMENT_WAIT_PULSES of the master's pulses; then
        the job fails, and its caller unwinds, rather than spread the
        shards over fewer servers than the volume's placement was
        promised."""
        if not started:
            raise RuntimeError("no alive volume servers")
        status, waited, whole = _await_cluster(
            worker, lambda st: set(started) <= set(st["dataNodes"]))
        targets = [t for t in status["dataNodes"] if t in started]
        if not whole:
            gone = sorted(set(started) - set(targets))
            raise RuntimeError(
                f"the master names {len(targets)} of the {len(started)} "
                f"servers the job started under ({', '.join(gone)} "
                f"unheard for {waited:.1f}s, {PLACEMENT_WAIT_PULSES} "
                f"pulses of {status['pulseSeconds']:g}s): not placing "
                "on fewer")
        return targets, waited

    def _distribute_and_mount(self, worker, vid: int, collection: str,
                              ctx: ECContext, where: dict,
                              started: "tuple[list[str], float]") -> dict:
        """Round-robin shard spread over the servers the job started
        under (:532), pushed to all of them at once, + mount
        (shard_distribution.go:209): nothing is mounted unless every
        file reached every target.  `where` says for each ext pushed
        where its bytes are: a file's path, or a data shard's
        DatShardView of the `.dat` (_pushed_files)."""
        with tracing.span("ec.distribute", role="worker") as sp:
            servers, waited_at_start = started
            sp.set("serversAtStart", len(servers))
            targets, waited = self._placement_targets(worker, servers)
            if waited_at_start + waited > 0:
                sp.set("waitSeconds", round(waited_at_start + waited, 3))
            placement: dict[str, list[int]] = {t: [] for t in targets}
            for sid in range(ctx.total):
                placement[targets[sid % len(targets)]].append(sid)
            holders = {t: sids for t, sids in placement.items() if sids}
            pushed, push_seconds = _push_to_each(holders, vid, collection,
                                                 where)
            sp.set("servers", len(holders))
            sp.set("bytes", pushed)
            sp.set("bytesFromDat", sum(
                src.size for src in where.values()
                if isinstance(src, DatShardView)))
            # Σ seconds of the ec.push spans over pushSeconds is how
            # many streams really ran at once
            sp.set("streams", len(holders))
            sp.set("pushSeconds", round(push_seconds, 6))
            for target, sids in holders.items():
                _mount_shards(target, vid, collection, sids)
        return placement

    # -- batch execute: N volumes in one job ---------------------------
    # (BASELINE config 3; VERDICT r2 Next #9) — each volume through the
    # single job's staged pipeline; on a worker with several devices
    # every window is split over all of them
    # (ops/staging.encode_shardings).  The volumes go through pull,
    # encode and distribute as three stages, in volume order: volume
    # i+1 is pulled and volume i-1 distributed while volume i encodes

    def execute_batch(self, worker, job_id: str, params: dict) -> str:
        from ...ops import staging

        # dedupe while preserving order: a repeated id would pull and
        # encode the same volume twice into one set of shard files
        vids = list(dict.fromkeys(int(v) for v in params["volumeIds"]))
        collection = params.get("collection", "")
        ctx = self._make_ctx(params, collection, 0)
        os.makedirs(worker.work_dir, exist_ok=True)
        vol_urls: dict[int, list[str]] = {}
        bases = {vid: os.path.join(worker.work_dir, f"{vid}")
                 for vid in vids}
        n = len(vids)
        # per-volume progress throughout: a 64-volume batch takes
        # long enough that a silent job would trip the admin's
        # stall reaper and double-execute
        report = _forward_progress(worker, job_id)
        # the servers the job starts under, asked once its first volume
        # is readonly; each volume's data-shard views once encoded
        started: "list[tuple[list[str], float]]" = []
        views: "dict[int, list[DatShardView]]" = {}

        def pull(i: int, vid: int) -> None:
            vol_urls[vid] = self._lookup_urls(worker, vid)
            self._mark_readonly(vol_urls[vid], vid)
            if not started:
                started.append(_servers_at_start(worker))
            self._pull_volume(worker, vid, collection, vol_urls[vid][0],
                              bases[vid])
            _sort_index(bases[vid])
            report(0.05 + 0.25 * (i + 1) / n,
                   f"pulled volume {vid} ({i + 1}/{n})")

        def encode(i: int, vid: int) -> None:
            base = bases[vid]
            views[vid] = ec_encoder.write_parity_files(
                base, ctx, progress=_encode_progress(
                    report, 0.3 + 0.3 * i / n, 0.3 / n))
            # the .vif and the .ecx are pushed with the shards
            dat_size = os.path.getsize(base + ".dat")
            version = _read_dat_version(base)
            ec_encoder.save_ec_volume_info(base, ctx, dat_size, version)
            if ec_decoder.find_dat_file_size(base, base,
                                             version) > dat_size:
                raise RuntimeError(
                    f"volume {vid}: ecx entries exceed dat size")

        def distribute(i: int, vid: int) -> None:
            # the .dat stays here until the job's end: a data shard
            # is sent as ranges of it, as in a single job
            where = _pushed_files(bases[vid], ctx)
            where.update((to_ext(v.shard_id), v) for v in views[vid])
            self._distribute_and_mount(worker, vid, collection, ctx,
                                       where, started[0])
            report(0.6 + 0.3 * (i + 1) / n,
                   f"distributed volume {vid} ({i + 1}/{n})")

        # each stage's calls, busy seconds and window, a span under the
        # job's: their busy seconds over the job's are the stages at once
        trace_ctx = tracing.current_ids()
        stages = {name: ec_encoder._StageTimer(step) for name, step in
                  (("batch.pull", pull), ("batch.encode", encode),
                   ("batch.distribute", distribute))}
        try:
            with _VolumePipeline(vids, stages["batch.pull"],
                                 stages["batch.distribute"]) as pipe:
                pulled = iter(pipe)
                first = next(pulled, None)
                if first is not None:
                    # one span for the job's encodes, from its first
                    # volume's to its last's: job_encode_s.batch is a
                    # job's, and `windows` the launches of its volumes
                    with tracing.span("ec.encode", role="worker") as sp:
                        sp.set("volumes", n)
                        launches = staging.snapshot()["launches"]
                        for i, vid in itertools.chain([first], pulled):
                            stages["batch.encode"](i, vid)
                            pipe.encoded(vid)
                        sp.set("windows",
                               staging.snapshot()["launches"] - launches)
                        sp.set("windowDevices", _window_devices(
                            [bases[v] for v in views], ctx))
                if len(views) == n:
                    report(0.6,
                           f"batch-encoded {n} volumes ({_ran_on(ctx)})")
        except Exception:
            self._unwind_volumes(worker, collection, ctx, vol_urls)
            raise
        finally:
            for name, stage in stages.items():
                stage.emit(name, trace_ctx)
            for base in bases.values():
                self._cleanup_local(base, ctx)
        for vid in vids:
            self._delete_originals(vol_urls[vid], vid)
        return (f"batch of {n} volumes {ctx} encoded over the "
                f"mesh ({_ran_on(ctx)}) and distributed")


class _VolumePipeline:
    """The two stages round a batch job's encode.  `pull(i, vid)` runs
    ahead through the volumes in order on one helper thread;
    iterating gives the caller, on its own thread, each (i, vid) as its
    pull ends; `distribute(i, vid)` follows on a second helper thread,
    each volume as the caller hands it on (`encoded`), in the order
    handed.  Each helper runs in its own copy of the caller's context,
    so that its spans hang under the caller's span, as the pushers'
    do (_push_to_each).  A stage that fails stops the other two before
    their next volume; leaving the `with` waits for both helpers and
    raises the first failure, unless the caller's own is on its way."""

    def __init__(self, vids: "list[int]", pull, distribute):
        self._vids = vids
        self._stop = threading.Event()
        self._errors: "list[BaseException]" = []
        self._pulled: "queue.SimpleQueue" = queue.SimpleQueue()
        self._encoded: "queue.SimpleQueue" = queue.SimpleQueue()
        self._pool = ThreadPoolExecutor(2, thread_name_prefix="ec-batch")
        for step, take, give in (
                (pull, vids.__getitem__, self._pulled.put),
                (distribute, lambda _i: self._encoded.get(),
                 lambda _vid: None)):
            self._pool.submit(contextvars.copy_context().run,
                              self._stage, step, take, give)

    def _stage(self, step, take, give) -> None:
        try:
            for i in range(len(self._vids)):
                vid = take(i)
                if vid is None or self._stop.is_set():
                    return
                step(i, vid)
                give(vid)
        except BaseException as e:  # noqa: BLE001 — raised by __exit__
            self._errors.append(e)
            self._stop.set()
        finally:
            give(None)

    def __iter__(self):
        for i in range(len(self._vids)):
            vid = self._pulled.get()
            if vid is None or self._stop.is_set():
                return
            yield i, vid

    def encoded(self, vid: int) -> None:
        self._encoded.put(vid)

    def __enter__(self) -> "_VolumePipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self._stop.set()
        self._encoded.put(None)
        self._pool.shutdown(wait=True)
        if exc is None and self._errors:
            raise self._errors[0]


class EcRebuildHandler(JobHandler):
    """Repair-plane twin of the encode handler: detect EC volumes with
    missing shards, trigger a slice-pipelined rebuild on the node
    holding the most survivors (command_ec_rebuild.go Detect/Execute
    shape).  The worker never stages shard bytes itself — the rebuilder
    streams survivors off its peers via ranged `/admin/ec/shard_read`
    (no whole-shard `/admin/ec/copy` round), so the accelerator node's
    ingest link is not the repair bottleneck."""

    job_type = "ec_rebuild"
    aliases = ["rebuild"]

    def capability(self) -> dict:
        # repair outranks balance (30) but defers to encode (80)
        return {"jobType": self.job_type, "canDetect": True,
                "canExecute": True, "weight": 70}

    def descriptor(self) -> dict:
        return {"jobType": self.job_type, "fields": []}

    def _shard_locations(self, worker, vid: int) -> "dict[str, list[int]]":
        from ...topology import fetch_ec_shard_locations
        return fetch_ec_shard_locations(worker.master, vid)

    def detect(self, worker) -> list[dict]:
        from ...storage.erasure_coding.ec_context import (
            TOTAL_SHARDS_COUNT)
        from ...topology import iter_volume_list_ec_shards
        vl = master_json(worker.master, "GET", "/vol/list", timeout=30)
        per_vid: dict[int, set] = {}
        holders: dict[int, str] = {}
        for node, e in iter_volume_list_ec_shards(vl):
            sids = per_vid.setdefault(e["volumeId"], set())
            bits = int(e.get("shardBits", e.get("ecIndexBits", 0)))
            sids.update(i for i in range(32) if bits >> i & 1)
            holders.setdefault(e["volumeId"], node["url"])
        proposals = []
        for vid, present in sorted(per_vid.items()):
            if present == set(range(TOTAL_SHARDS_COUNT)):
                # a full default-scheme stripe needs no per-volume
                # probes: the healthy steady state must cost zero
                # extra round-trips per detect cycle
                continue
            # a gap OR a non-default scheme: one info probe decides
            r = http_json(
                "GET", f"{holders[vid]}/admin/ec/info?volumeId={vid}",
                    timeout=30)
            if "error" in r:
                continue
            total = r["dataShards"] + r["parityShards"]
            missing = [s for s in range(total) if s not in present]
            if missing and len(present) >= r["dataShards"]:
                proposals.append({
                    "jobType": self.job_type,
                    "dedupeKey": f"ec_rebuild:{vid}",
                    "params": {"volumeId": vid,
                               "collection": r.get("collection", ""),
                               "missingShardIds": missing},
                })
        return proposals

    def execute(self, worker, job_id: str, params: dict) -> str:
        vid = int(params["volumeId"])
        collection = params.get("collection", "")
        locs = self._shard_locations(worker, vid)
        if not locs:
            raise RuntimeError(f"ec volume {vid} has no shards")
        # the authoritative scheme from a shard holder: a rebuilder
        # whose .vif predates the destroy()-keeps-.vif fix must not
        # fall back to a default 10+4 for a custom-scheme volume
        info = None
        for url in locs:
            r = http_json("GET", f"{url}/admin/ec/info?volumeId={vid}",
                    timeout=30)
            if "error" not in r:
                info = r
                break
        if info is None:
            raise RuntimeError(f"ec volume {vid}: no reachable shards")
        collection = collection or info.get("collection", "")
        from ...topology import shard_ids_to_urls
        rebuilder = max(locs, key=lambda u: len(locs[u]))
        shard_locations = shard_ids_to_urls(locs)
        worker.report_progress(job_id, 0.1,
                               f"streaming rebuild on {rebuilder}")
        r = _must(http_json(
            "POST", f"{rebuilder}/admin/ec/rebuild",
            {"volumeId": vid, "collection": collection,
             "mode": "stream", "shardLocations": shard_locations,
             "dataShards": info["dataShards"],
             "parityShards": info["parityShards"]},
            timeout=600.0), f"rebuild on {rebuilder}")
        rebuilt = r.get("rebuiltShardIds", [])
        if rebuilt:
            _mount_shards(rebuilder, vid, collection, rebuilt)
        worker.report_progress(job_id, 0.7, f"rebuilt {rebuilt}")
        # re-spread like the shell flow: leaving every rebuilt shard
        # on the max-survivor node would silently break the stripe's
        # anti-correlation (one node failure must not cost >1 shard).
        # Under the cluster admin lease (.balance convention): an
        # unlocked balance interleaving with an operator's locked one
        # could dedupe/delete the same transient shard copy twice.
        from ...shell.commands import _balance_ec_volume
        from .balance import _LockedShellRun
        with _LockedShellRun(worker.master) as env:
            moved = _balance_ec_volume(
                env, vid, collection,
                info["dataShards"] + info["parityShards"])
        worker.report_progress(job_id, 0.9,
                               f"rebalanced {moved} shards")
        tele = r.get("telemetry") or {}
        return (f"volume {vid}: rebuilt shards {rebuilt} on "
                f"{rebuilder}, rebalanced {moved} (streamed "
                f"{tele.get('bytesFetchedTotal', 0) >> 20}MB @ "
                f"{tele.get('volumeGbps', 0)} GB/s volume-rate)")


def _ran_on(ctx: ECContext) -> str:
    """"jax on tpu TPU v5 lite x1" / "native on host" — the platform
    and device_kind a job message must name."""
    w = ec_context.where(ctx.backend)
    if "kind" not in w:
        return f"{w['backend']} on {w['platform']}"
    return (f"{w['backend']} on {w['platform']} {w['kind']} "
            f"x{w['count']}")


def _device_report(ctx: ECContext) -> dict:
    """Span attributes of one encode: where the codec ran and, on the
    device path, this process's staging and compile ledgers and each
    device's peak memory (a mesh that left a chip empty shows 0)."""
    out = {"codec": ec_context.where(ctx.backend)}
    if ctx.backend == "jax":
        from ... import profiling
        from ...ops import staging
        out["staging"] = staging.snapshot()
        out["compile"] = ec_context.compile_ledger()
        out["devicePeakBytes"] = {
            label: ms.get("peak_bytes_in_use", 0) for label, ms in
            profiling.sample_device_memory().items()}
    return out


def _encode_progress(report, lo: float = 0.3, width: float = 0.3,
                     every: float = 2.0):
    """write_parity_files progress -> `report(progress, message)` from
    `lo` to `lo + width` of the job, at most one report per `every`
    seconds.  The worker cannot poll while it executes, so these
    reports are its liveness across device init, cold compiles and the
    encode itself (admin WORKER_DEAD_AFTER)."""
    last = 0.0

    def note(done: int, total: int) -> None:
        nonlocal last
        now = time.monotonic()
        if now - last >= every or done >= total:
            last = now
            report(lo + width * done / max(total, 1),
                   f"encoding {done >> 20}/{total >> 20} MiB")
    return note


def _forward_progress(worker, job_id: str):
    """`report(progress, message)` for a job whose stages report from
    several threads: each report carries the highest progress reported
    so far and is sent under a lock, so that two cannot pass each
    other on the way and the admin never sees the job's progress
    fall."""
    lock = threading.Lock()
    high = 0.0

    def report(progress: float, message: str) -> None:
        nonlocal high
        with lock:
            high = max(high, progress)
            worker.report_progress(job_id, high, message)
    return report


def _window_devices(bases: "list[str]", ctx: ECContext) -> int:
    """How many devices every window of these volumes' encode is split
    over: all of staging.encode_shardings()' where each window's packed
    words divide them (as staging.WindowedLaunch puts it), else 1; 0
    for a host codec, which puts none."""
    if ctx.backend != "jax":
        return 0
    from ...ops import staging
    ndev = staging.encode_shardings()[2]
    words = {-(-batch // 4) for base in bases
             for _row, _block, _off, batch, _rows in
             ec_encoder._encode_work_items(
                 os.path.getsize(base + ".dat"), ctx)}
    return ndev if all(w % ndev == 0 for w in words) else 1


def _read_dat_version(base: str) -> int:
    from ...storage.super_block import SuperBlock
    with open(base + ".dat", "rb") as f:
        return SuperBlock.parse(f.read(8), require_extra=False).version


def _sort_index(base: str) -> None:
    with tracing.span("ec.sort_index", role="worker"):
        ec_encoder.write_sorted_file_from_idx(base)


def _mount_shards(target: str, vid: int, collection: str,
                  sids: "list[int]") -> None:
    with tracing.span("ec.mount", role="worker") as sp:
        sp.set("target", target)
        _must(http_json("POST", f"{target}/admin/ec/mount",
                        {"volumeId": vid, "collection": collection,
                         "shardIds": sids}, timeout=30),
              f"mount shards on {target}")


def _pushed_files(base: str, ctx: ECContext) -> "dict[str, str]":
    """{ext: path} of what a volume's targets are sent, every shard a
    file of the work dir; a job whose encode wrote no data shards puts
    their views in the paths' place."""
    return {ext: base + ext for ext in
            [to_ext(sid) for sid in range(ctx.total)] + [".ecx", ".vif"]}


def _push_file(target: str, vid: int, collection: str, ext: str,
               source: "str | DatShardView") -> int:
    """Streamed push (http_upload) of a file of the work dir, or of a
    data shard that is a view of the `.dat` there: from disk to the
    socket by sendfile, or under TLS through one 1 MiB buffer
    (shard_distribution.go:101 target side).  Returns the bytes sent.
    The `ec.push` span carries them, where they came from (`source`
    "file" or "dat", and `ranges`, how many of it were sent), which of
    the two ways they went (`via`, as http_upload reports it) and this
    thread's CPU for the push: against the span's wall and the
    receiver's own `POST /admin/receive_file` span beneath it, that
    says whether the sender, the receiver or neither was busy."""
    with tracing.span("ec.push", role="worker") as sp:
        if isinstance(source, DatShardView):
            path, pieces, size = source.dat_path, source.pieces, source.size
        else:
            path, pieces, size = source, None, os.path.getsize(source)
        sp.set("target", target)
        sp.set("ext", ext)
        sp.set("bytes", size)
        sp.set("source", "file" if pieces is None else "dat")
        sp.set("ranges", 1 if pieces is None else len(pieces))
        cpu0 = time.thread_time()
        try:
            sent = http_upload(
                "POST", f"{target}/admin/receive_file?volumeId={vid}"
                f"&collection={collection}&ext={ext}", path, timeout=600,
                pieces=pieces)
            sp.set("via", sent.via)
            status, body, _ = sent
        finally:
            sp.set("cpuSeconds", round(time.thread_time() - cpu0, 6))
        if status != 200:
            raise RuntimeError(f"push {ext} to {target}: {status} "
                               f"{body[:200]!r}")
    return size


def _push_to_each(holders: "dict[str, list[int]]", vid: int,
                  collection: str, where: dict) -> "tuple[int, float]":
    """(bytes pushed, seconds from the first push's start to the last
    one's end): one pusher thread a target, each sending its own
    target's files one at a time (its shards ascending, then .ecx,
    .vif; each from `where[ext]`), so that a receiver never sees two
    pushes of one job at once while the receivers, a process each, all
    work.  A push that
    fails stops the others before their next file; once all have
    ended the first failure in target order is raised."""
    failed = threading.Event()

    def push(target: str, sids: "list[int]") -> int:
        sent = 0
        for ext in [to_ext(sid) for sid in sids] + [".ecx", ".vif"]:
            if failed.is_set():
                break
            try:
                sent += _push_file(target, vid, collection, ext,
                                   where[ext])
            except BaseException:
                failed.set()
                raise
        return sent

    t0 = time.monotonic()
    with ThreadPoolExecutor(len(holders),
                            thread_name_prefix="ec-push") as pool:
        # the current span, the request id and an armed deadline are
        # contextvars and do not follow a thread: each pusher runs in
        # its own copy of this context (a Context cannot be entered
        # twice at once), so ec.push hangs under ec.distribute and the
        # receiver under the push
        pushers = [pool.submit(contextvars.copy_context().run, push,
                               target, sids)
                   for target, sids in holders.items()]
    seconds = time.monotonic() - t0
    return sum(p.result() for p in pushers), seconds
