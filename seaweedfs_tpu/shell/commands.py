"""Shell commands (weed/shell/command_*.go).

Implemented commands (north-star set, SURVEY §3.3):
  volume.list, volume.vacuum, volume.delete, volume.mount, volume.unmount
  ec.encode, ec.decode, ec.rebuild, ec.balance
  lock, unlock, cluster.check

Commands run against a CommandEnv holding the master address and the
cluster admin lock token (shell/command_lock_unlock.go semantics:
mutating commands require the lock).
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor

from ..operation import master_json
from ..server.httpd import http_bytes, http_json
from ..storage.erasure_coding.ec_context import to_ext

COMMANDS: dict[str, "callable"] = {}


def command(name):
    def reg(fn):
        COMMANDS[name] = fn
        fn.command_name = name
        return fn
    return reg


class CommandEnv:
    def __init__(self, master: str, filer: str = ""):
        self.master = master
        self.filer = filer  # host:port for the fs.* family
        self.admin_token: int | None = None

    def require_filer(self) -> str:
        if not self.filer:
            raise RuntimeError(
                "no filer configured; start the shell with -filer or "
                "run `fs.configure -filer=host:port`")
        return self.filer

    # -- admin lock (command_lock_unlock.go) ------------------------------

    def lock(self) -> None:
        r = master_json(self.master, "POST", "/cluster/lease_admin_token",
                      {"previousToken": self.admin_token or 0,
                       "lockName": "admin"}, timeout=30)
        if "token" not in r:
            raise RuntimeError(f"cannot acquire cluster lock: {r}")
        self.admin_token = r["token"]

    def unlock(self) -> None:
        master_json(self.master, "POST", "/cluster/release_admin_token",
                  {"previousToken": self.admin_token or 0}, timeout=30)
        self.admin_token = None

    def confirm_is_locked(self) -> None:
        """command_ec_encode.go:104 confirmIsLocked equivalent."""
        if self.admin_token is None:
            raise RuntimeError(
                "lock is lost, or it is not locked; run `lock` first")

    def volume_list(self) -> dict:
        return master_json(self.master, "GET", "/vol/list", timeout=30)

    def volume_locations(self, vid: int) -> list[dict]:
        r = master_json(self.master, "GET", f"/dir/lookup?volumeId={vid}",
                timeout=30)
        return r.get("locations", [])


# --- basic commands ------------------------------------------------------

@command("lock")
def cmd_lock(env: CommandEnv, args: list[str]) -> str:
    env.lock()
    return "locked"


@command("unlock")
def cmd_unlock(env: CommandEnv, args: list[str]) -> str:
    env.unlock()
    return "unlocked"


@command("volume.list")
def cmd_volume_list(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_list.go."""
    return json.dumps(env.volume_list(), indent=2)


@command("cluster.check")
def cmd_cluster_check(env: CommandEnv, args: list[str]) -> str:
    r = master_json(env.master, "GET", "/cluster/status", timeout=30)
    return json.dumps(r, indent=2)


@command("volume.vacuum")
def cmd_volume_vacuum(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_vacuum.go: compact all (or one) volume."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    target_vid = int(opts["volumeId"]) if "volumeId" in opts else None
    done = []
    for vid, urls in _volumes_by_id(env).items():
        if target_vid is not None and vid != target_vid:
            continue
        for url in urls:
            http_json("POST", f"{url}/admin/vacuum", {"volumeId": vid},
                timeout=30)
        done.append(vid)
    return f"vacuumed volumes: {sorted(done)}"


# --- EC commands (the north-star pipeline, command_ec_encode.go:86) ------

@command("ec.encode")
def cmd_ec_encode(env: CommandEnv, args: list[str]) -> str:
    """shell/command_ec_encode.go:86 Do, placement-first.

    Default `-mode=scatter`: plan every shard's destination up front
    (the same rack-spread + placement-score rules ec.balance enforces),
    then have the source server stream each shard's GF-pipeline windows
    DIRECTLY to its destination over one long chunked
    `/admin/ec/shard_write` stream — shards bound elsewhere never touch
    the source's disks and no balance re-copy round follows (the 1.4x
    source write amplification collapses to the sidecars, ~0.07x).
    `-mode=local` keeps the seed shape — generate all shards on the
    source, mount, then balance-move them off — the A/B baseline
    (SEAWEEDFS_TPU_EC_ENCODE_MODE overrides the default)."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    import os as _os
    mode = opts.get("mode", _os.environ.get(
        "SEAWEEDFS_TPU_EC_ENCODE_MODE", "scatter"))
    if mode not in ("scatter", "local"):
        return f"unknown -mode={mode}; use scatter or local"
    data_shards = int(opts.get("dataShards", 10))
    parity_shards = int(opts.get("parityShards", 4))
    vids = _select_volumes(env, opts)
    if not vids:
        return "no volumes qualify for ec encoding"
    out = []
    for vid in vids:
        out.append(_do_ec_encode(env, vid, data_shards, parity_shards,
                                 opts, mode))
    return "\n".join(out)


def _do_ec_encode(env: CommandEnv, vid: int, data_shards: int,
                  parity_shards: int, opts: dict,
                  mode: str = "scatter") -> str:
    # pre-collect locations before mutating (race fix,
    # command_ec_encode.go:160-166)
    locations = env.volume_locations(vid)
    if not locations:
        raise RuntimeError(f"volume {vid} has no locations")
    collection = opts.get("collection", "")
    if collection == "ALL":
        # "ALL" is a volume-SELECTION sentinel (the empty collection),
        # never a real collection name — passing it through would make
        # generate/mount address nonexistent "ALL_<vid>" files
        collection = ""
    total = data_shards + parity_shards
    source = locations[0]["url"]
    # 1. mark all replicas readonly (:250) — and UNWIND on any later
    # failure: a failed generate/mount must not strand the volume
    # readonly forever (it is still the only copy of the data)
    marked: list[str] = []
    try:
        for loc in locations:
            _must(http_json("POST",
                            f"{loc['url']}/admin/set_readonly",
                            {"volumeId": vid, "readOnly": True}, timeout=30),
                  f"set readonly on {loc['url']}")
            marked.append(loc["url"])
        if mode == "scatter":
            # 2s. placement FIRST (the scores/rack rules balance would
            # apply after the fact), then one scatter generate: the
            # source streams every shard to its final destination and
            # mounts it there — no local mount, no balance round.
            # Failure handling: a generate that dies on specific
            # destinations reports them (failedDests) and the stripe
            # is RE-PLANNED around them — up to twice — before giving
            # up; a re-plan with no remaining candidates falls back to
            # `-mode=local` (encode still completes, balance spreads
            # later) instead of failing the job.  The planner also
            # skips peers whose circuit breaker is open.
            exclude: set = set()
            replans = 0
            while True:
                try:
                    placement = _plan_ec_placement(env, vid, total,
                                                   exclude=exclude)
                except RuntimeError:
                    if not exclude:
                        raise  # nothing excluded: a real planning error
                    # nowhere left to scatter after exclusions: local
                    # mode still completes the encode on the source
                    return _do_ec_encode(env, vid, data_shards,
                                         parity_shards, opts, "local")
                r = http_json("POST", f"{source}/admin/ec/generate", {
                    "volumeId": vid, "collection": collection,
                    "dataShards": data_shards,
                    "parityShards": parity_shards,
                    "replan": replans,
                    "placement": {str(s): u
                                  for s, u in placement.items()}},
                    timeout=600.0)
                if "error" not in r:
                    break
                failed = [d for d in r.get("failedDests", [])
                          if d != source]
                if replans >= 2 or not failed:
                    _must(r, f"scatter generate on {source}")
                dropped = set(failed) - exclude
                if not dropped:
                    _must(r, f"scatter generate on {source}")
                exclude |= dropped
                replans += 1
            moved = 0
        else:
            # 2. generate EC shards on the first replica (:359)
            _must(http_json("POST", f"{source}/admin/ec/generate", {
                "volumeId": vid, "collection": collection,
                "dataShards": data_shards,
                "parityShards": parity_shards}, timeout=600.0),
                f"generate on {source}")
            # 3. mount all shards on source (:314) — a silent mount
            # failure here would let step 5 delete the originals with
            # the EC copy unregistered (data loss)
            _must(http_json("POST", f"{source}/admin/ec/mount", {
                "volumeId": vid, "collection": collection,
                "shardIds": list(range(total))}, timeout=30),
                f"mount ec shards on {source}")
            # 4. spread shards across servers (EcBalance, :199)
            moved = _balance_ec_volume(env, vid, collection, total)
            r = {}
    except BaseException:
        # restore read-write on every replica we froze, then surface
        # the ORIGINAL error (scatter/generate handlers already tore
        # down their own partial state)
        for url in marked:
            try:
                http_json("POST", f"{url}/admin/set_readonly",
                          {"volumeId": vid, "readOnly": False}, timeout=30)
            except OSError:
                pass
        raise
    # 5. delete original volume replicas (:329) — only now, with every
    # shard mounted at its destination
    for loc in locations:
        http_json("POST", f"{loc['url']}/admin/delete_volume",
                  {"volumeId": vid}, timeout=30)
    if mode == "scatter":
        tele = r.get("telemetry") or {}
        dests = len(set((r.get("placement") or {}).values())) or 1
        msg = (f"volume {vid}: scatter-encoded {total} shards from "
               f"{source} to {dests} destinations, deleted originals")
        if tele:
            msg += (f" [{tele['bytesScatteredTotal'] >> 20}MB "
                    f"scattered, {tele['localWriteBytes'] >> 20}MB "
                    f"local, {tele['volumeGbps']} GB/s volume-rate, "
                    f"window p95 {tele['windowP95Ms']}ms]")
        return msg
    return (f"volume {vid}: encoded {total} shards on {source}, "
            f"moved {moved} shards, deleted originals")


def _plan_ec_placement(env: CommandEnv, vid: int, total: int,
                       exclude: "frozenset | set" = frozenset()
                       ) -> "dict[int, str]":
    """Placement-first shard->server plan, applying the same rules
    `_balance_ec_volume` would enforce AFTER the fact: spread across
    racks toward ceil(total/racks) per rack, even out per-server
    counts within a rack, and break ties by placement score
    (diskDistributionScore role — anti-correlation with this volume's
    shards weighs heaviest).  Computing this BEFORE encode is what
    lets scatter stream every shard to its final home in one hop.

    Robustness: nodes in `exclude` (destinations a previous attempt
    watched fail) and nodes whose circuit breaker is OPEN in this
    process's health map (util/retry) are never chosen — a tripped
    destination is planned around, not rediscovered the hard way
    mid-stripe."""
    from ..util import retry as _retry
    nodes = _all_node_urls(env)
    nodes = [n for n in nodes
             if n not in exclude and _retry.peer_available(n)]
    if not nodes:
        raise RuntimeError("no alive volume servers to place shards")
    vl = env.volume_list()   # one topology fetch for both helpers
    rack_of = _rack_of_nodes(env, vl)
    score = _ec_placement_scores(env, vid, vl)
    racks = sorted({rack_of.get(n, "?") for n in nodes})
    per_rack_cap = max(1, -(-total // len(racks)))  # ceil
    rack_load: dict[str, int] = {r: 0 for r in racks}
    node_load: dict[str, int] = {n: 0 for n in nodes}
    placement: dict[int, str] = {}
    for sid in range(total):
        open_racks = [r for r in racks if rack_load[r] < per_rack_cap]
        if not open_racks:
            open_racks = racks  # more shards than rack capacity: wrap
        rack = min(open_racks, key=lambda r: rack_load[r])
        members = [n for n in nodes if rack_of.get(n, "?") == rack]
        dst = min(members, key=lambda n: (node_load[n],
                                          score.get(n, 0)))
        placement[sid] = dst
        rack_load[rack] += 1
        node_load[dst] += 1
    return placement


def _rack_of_nodes(env: CommandEnv, vl: "dict | None" = None
                   ) -> dict[str, str]:
    """url -> "dc/rack" from the topology tree."""
    vl = vl if vl is not None else env.volume_list()
    out: dict[str, str] = {}
    for dc_name, dc in vl.get("dataCenters", {}).items():
        for rack_name, rack in dc.get("racks", {}).items():
            for node in rack.get("nodes", []):
                out[node["url"]] = f"{dc_name}/{rack_name}"
    return out


def _ec_placement_scores(env: CommandEnv, vid: int,
                         vl: "dict | None" = None) -> dict[str, int]:
    """Per-node placement score, LOWER is better
    (command_ec_common.go:1380 diskDistributionScore + :1441 pick):
    shards of THIS volume weigh 100 (anti-correlation — losing one
    node must not take multiple shards of a stripe), total EC shards
    weigh 10 (overall spread), free volume slots subtract (headroom
    attracts placements)."""
    from ..topology import iter_volume_list_ec_shards
    vl = vl if vl is not None else env.volume_list()
    scores: dict[str, int] = {}
    headroom: dict[str, int] = {}
    for dc in vl.get("dataCenters", {}).values():
        for rack in dc.get("racks", {}).values():
            for node in rack.get("nodes", []):
                headroom[node["url"]] = \
                    int(node.get("maxVolumeCount", 8)) - \
                    len(node.get("volumes", []))
                scores[node["url"]] = 0
    for node, e in iter_volume_list_ec_shards(vl):
        cnt = bin(int(e.get("ecIndexBits", 0))).count("1")
        url = node["url"]
        scores[url] = scores.get(url, 0) + cnt * 10
        if e.get("volumeId", e.get("id")) == vid:
            scores[url] += cnt * 100
    return {u: s - headroom.get(u, 0) for u, s in scores.items()}


def _balance_ec_volume(env: CommandEnv, vid: int, collection: str,
                       total: int) -> int:
    """The balance algorithm of command_ec_common.go:59-124:
    (1) dedupe shard copies, (2) spread shards across racks toward
    total/numRacks per rack, (3) even out per-server counts within each
    rack.  Destination picks among equally-loaded candidates break
    ties by placement score (diskDistributionScore role)."""
    shard_locs = _ec_shard_locations(env, vid)
    nodes = _all_node_urls(env)
    if not nodes:
        return 0
    rack_of = _rack_of_nodes(env)
    score = _ec_placement_scores(env, vid)
    moved = 0

    # (1) dedupe: keep first copy of each shard
    owner: dict[int, str] = {}
    for url, sids in sorted(shard_locs.items()):
        for sid in sids:
            if sid in owner:
                _delete_shards(url, vid, collection, [sid])
                moved += 1
            else:
                owner[sid] = url

    def load_by_url() -> dict[str, list[int]]:
        load = {n: [] for n in nodes}
        for sid, url in owner.items():
            load.setdefault(url, []).append(sid)
        return load

    def move(sid: int, src: str, dst: str):
        nonlocal moved
        _move_shard(env, vid, collection, sid, src, dst)
        owner[sid] = dst
        moved += 1

    # (2) across racks: doBalanceEcShardsAcrossRacks.  Only racks with
    # an alive member can receive (shards may sit on dead nodes whose
    # rack has no live servers).
    racks = sorted({rack_of.get(n, "?") for n in nodes})
    avg_per_rack = max(1, -(-total // max(len(racks), 1)))  # ceil
    def rack_load() -> dict[str, list[int]]:
        rl: dict[str, list[int]] = {r: [] for r in racks}
        for sid, url in owner.items():
            rl.setdefault(rack_of.get(url, "?"), []).append(sid)
        return rl
    rl = rack_load()
    for rack in sorted(rl, key=lambda r: -len(rl[r])):
        while len(rl[rack]) > avg_per_rack:
            receivable = [r for r in rl if r != rack and
                          any(rack_of.get(n, "?") == r for n in nodes)]
            if not receivable:
                break
            dest_rack = min(receivable, key=lambda r: len(rl[r]))
            if len(rl[dest_rack]) + 1 > avg_per_rack:
                break
            load = load_by_url()
            dest_candidates = [n for n in nodes
                               if rack_of.get(n, "?") == dest_rack]
            dst = min(dest_candidates,
                      key=lambda n: (len(load[n]),
                                     score.get(n, 0)))
            sid = rl[rack][-1]
            move(sid, owner[sid], dst)
            rl = rack_load()

    # (3) within racks: doBalanceEcShardsWithinOneRack
    for rack in racks:
        members = [n for n in nodes if rack_of.get(n, "?") == rack]
        if not members:
            continue
        load = load_by_url()
        rack_shards = [sid for sid, url in owner.items()
                       if url in members]
        avg = max(1, -(-len(rack_shards) // len(members)))
        for donor in sorted(members, key=lambda n: -len(load[n])):
            while len(load[donor]) > avg:
                recv = min(members,
                           key=lambda n: (len(load[n]),
                                          score.get(n, 0)))
                if recv == donor or len(load[recv]) + 1 > avg:
                    break
                sid = load[donor][-1]
                move(sid, donor, recv)
                load[donor].remove(sid)
                load[recv].append(sid)
    return moved


def _move_shard(env: CommandEnv, vid: int, collection: str, sid: int,
                source: str, dest: str) -> None:
    """command_ec_common.go:336 oneServerCopyAndMountEcShardsFromSource:
    copy (+ecx/ecj/vif), mount on dest, delete+unmount on source.

    The copy legs are pipelined through `httpd.http_relay` (the shape
    PR 2 gave `_copy_volume_files`): each file streams chunk-by-chunk
    from source to dest with the push starting at the first downloaded
    chunk, instead of the dest's download-then-upload
    `/admin/ec/copy` staging pass.  The shard file and `.ecx` are
    required; `.ecj`/`.vif` ride along when present (the journal
    legitimately may not exist)."""
    from ..server.httpd import http_relay
    for ext in (to_ext(sid), ".ecx", ".ecj", ".vif"):
        src_status, dst_status, body = http_relay(
            f"{source}/admin/volume_file?volumeId={vid}"
            f"&collection={collection}&ext={ext}",
            "POST", f"{dest}/admin/receive_file?volumeId={vid}"
            f"&collection={collection}&ext={ext}", timeout=600)
        if src_status != 200:
            if ext in (".ecj", ".vif"):
                continue
            raise RuntimeError(
                f"move shard {vid}.{sid}: pull {ext} from {source}: "
                f"{src_status}")
        if dst_status != 200:
            raise RuntimeError(
                f"move shard {vid}.{sid}: push {ext} to {dest}: "
                f"{dst_status} {body[:200]!r}")
    _must(http_json("POST", f"{dest}/admin/ec/mount",
                    {"volumeId": vid, "collection": collection,
                     "shardIds": [sid]}, timeout=30),
          f"mount shard {vid}.{sid} on {dest}")
    _delete_shards(source, vid, collection, [sid])


def _delete_shards(url: str, vid: int, collection: str,
                   sids: list[int]) -> None:
    """The server refreshes its mounted shard set + heartbeat itself."""
    http_json("POST", f"{url}/admin/ec/delete_shards",
              {"volumeId": vid, "collection": collection,
               "shardIds": sids}, timeout=30)


@command("ec.decode")
def cmd_ec_decode(env: CommandEnv, args: list[str]) -> str:
    """shell/command_ec_decode.go:64: collect all shards onto one server,
    decode back to a normal volume, drop shards elsewhere."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    vid = int(opts["volumeId"])
    collection = opts.get("collection", "")
    shard_locs = _ec_shard_locations(env, vid)
    if not shard_locs:
        return f"volume {vid} has no ec shards"
    # choose the server with the most shards as decode target
    target = max(shard_locs, key=lambda u: len(shard_locs[u]))
    have = set(shard_locs[target])
    for url, sids in shard_locs.items():
        if url == target:
            continue
        need = [s for s in sids if s not in have]
        if need:
            http_json("POST", f"{target}/admin/ec/copy", {
                "volumeId": vid, "collection": collection,
                "shardIds": need, "sourceDataNode": url,
                "copyEcxFile": False, "copyEcjFile": True,
                "copyVifFile": False}, timeout=30)
            have.update(need)
    r = http_json("POST", f"{target}/admin/ec/to_volume",
                  {"volumeId": vid, "collection": collection},
                  timeout=600.0)
    if "error" in r:
        raise RuntimeError(f"decode: {r['error']}")
    # remove shards from all other servers — AND the decode target's
    # own shard files: stale `.ecNN` files left on its disks would be
    # re-registered by the next encode's mount scan (duplicate shard
    # locations) and mistaken for survivors by rebuild discovery
    for url, sids in shard_locs.items():
        if url != target:
            _delete_shards(url, vid, collection, sids)
    _delete_shards(target, vid, collection, sorted(have))
    return f"volume {vid}: decoded to normal volume on {target}"


@command("ec.rebuild")
def cmd_ec_rebuild(env: CommandEnv, args: list[str]) -> str:
    """shell/command_ec_rebuild.go:83: for each ec volume missing
    shards, rebuild on the node holding the most survivors, re-spread.

    Default `-mode=stream`: the rebuilder streams remote survivors in
    slice windows straight into the GF pipeline (no whole-shard
    pre-copies).  `-mode=copy` keeps the legacy collect-then-rebuild
    (every remote survivor pulled in full via /admin/ec/copy first) —
    the A/B baseline."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    import os as _os
    mode = opts.get("mode", _os.environ.get(
        "SEAWEEDFS_TPU_EC_REBUILD_MODE", "stream"))
    if mode not in ("stream", "copy"):
        return f"unknown -mode={mode}; use stream or copy"
    vids = ([int(opts["volumeId"])] if "volumeId" in opts
            else list(_ec_volumes(env)))
    out = []
    for vid in vids:
        out.append(_rebuild_one(env, vid, opts.get("collection", ""),
                                mode))
    return "\n".join(out) if out else "no ec volumes"


def _rebuild_one(env: CommandEnv, vid: int, collection: str,
                 mode: str = "stream") -> str:
    shard_locs = _ec_shard_locations(env, vid)
    present = sorted({s for sids in shard_locs.values() for s in sids})
    info = None
    for url in shard_locs:
        r = http_json("GET", f"{url}/admin/ec/info?volumeId={vid}", timeout=30)
        if "error" not in r:
            info = r
            break
    if info is None:
        return f"volume {vid}: no reachable shards"
    total = info["dataShards"] + info["parityShards"]
    missing = [s for s in range(total) if s not in present]
    if not missing:
        return f"volume {vid}: all {total} shards present"
    # rebuilder = node with most shards (fewest bytes left to ingest)
    rebuilder = max(shard_locs, key=lambda u: len(shard_locs[u]))
    if mode == "copy":
        # legacy collect-then-rebuild: pull survivors the rebuilder
        # lacks, in full, one source at a time.  Sidecars
        # (.ecx/.ecj/.vif) ride along ONCE with the first shard copy —
        # they are identical on every source, so re-pulling them per
        # source was pure waste.
        have = set(shard_locs[rebuilder])
        sidecars_pending = True
        for url, sids in shard_locs.items():
            if url == rebuilder:
                continue
            need = [s for s in sids if s not in have]
            if need:
                http_json("POST", f"{rebuilder}/admin/ec/copy", {
                    "volumeId": vid, "collection": collection,
                    "shardIds": need, "sourceDataNode": url,
                    "copyEcxFile": sidecars_pending,
                    "copyEcjFile": sidecars_pending,
                    "copyVifFile": sidecars_pending}, timeout=30)
                sidecars_pending = False
                have.update(need)
        r = http_json("POST", f"{rebuilder}/admin/ec/rebuild",
                      {"volumeId": vid, "collection": collection,
                       "mode": "local"}, timeout=30)
    else:
        # streaming: hand the rebuilder every survivor's locations and
        # let it range-read slices off its peers — zero /admin/ec/copy
        # traffic, no survivor files staged on the rebuilder's disks
        from ..topology import shard_ids_to_urls
        shard_locations = shard_ids_to_urls(shard_locs)
        r = http_json("POST", f"{rebuilder}/admin/ec/rebuild",
                      {"volumeId": vid, "collection": collection,
                       "mode": "stream",
                       "shardLocations": shard_locations,
                       "dataShards": info["dataShards"],
                       "parityShards": info["parityShards"]},
                      timeout=600.0)
    if "error" in r:
        raise RuntimeError(f"rebuild: {r['error']}")
    http_json("POST", f"{rebuilder}/admin/ec/mount",
              {"volumeId": vid, "collection": collection,
               "shardIds": r["rebuiltShardIds"]}, timeout=30)
    moved = _balance_ec_volume(env, vid, collection, total)
    msg = (f"volume {vid}: rebuilt shards {r['rebuiltShardIds']} on "
           f"{rebuilder}, rebalanced {moved}")
    tele = r.get("telemetry")
    if tele:
        msg += (f" [streamed {tele['bytesFetchedTotal'] >> 20}MB "
                f"from {len(tele['bytesFetchedBySource'])} sources, "
                f"{tele['volumeGbps']} GB/s volume-rate, "
                f"slice p95 {tele['sliceP95Ms']}ms]")
    return msg


@command("ec.balance")
def cmd_ec_balance(env: CommandEnv, args: list[str]) -> str:
    """shell/command_ec_balance.go."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    collection = opts.get("collection", "")
    out = []
    for vid in _ec_volumes(env):
        info = None
        for url in _ec_shard_locations(env, vid):
            r = http_json("GET", f"{url}/admin/ec/info?volumeId={vid}",
                    timeout=30)
            if "error" not in r:
                info = r
                break
        total = (info["dataShards"] + info["parityShards"]) if info else 14
        moved = _balance_ec_volume(env, vid, collection, total)
        out.append(f"volume {vid}: moved {moved} shards")
    return "\n".join(out) if out else "no ec volumes"


def _copy_volume_files(env: CommandEnv, vid: int, collection: str,
                       src: str, dst: str) -> None:
    """Pull .dat/.idx/.vif from src and push to dst (the CopyFile /
    ReceiveFile pattern, volume_server.proto:69-101).  The two legs are
    pipelined through http_relay — the push to dst starts at the first
    downloaded chunk instead of after a full stage-to-temp-file pass —
    while RAM stays bounded by one 4MB chunk, so the shell never
    buffers a 30GB .dat any more than the worker may."""
    from ..server.httpd import http_relay
    for ext in (".dat", ".idx", ".vif"):
        src_status, dst_status, body = http_relay(
            f"{src}/admin/volume_file?volumeId={vid}"
            f"&collection={collection}&ext={ext}",
            "POST", f"{dst}/admin/receive_file?volumeId={vid}"
            f"&collection={collection}&ext={ext}", timeout=600)
        if src_status != 200:
            if ext == ".vif":
                continue
            raise RuntimeError(f"copy {ext} from {src}: {src_status}")
        if dst_status != 200:
            raise RuntimeError(
                f"push {ext} to {dst}: {dst_status} {body[:200]!r}")


def _move_volume(env: CommandEnv, vid: int, collection: str,
                 src: str, dst: str, delete_source: bool = True) -> None:
    """shell/command_volume_move.go pipeline: freeze, copy, mount,
    delete source."""
    _must(http_json("POST", f"{src}/admin/set_readonly",
                    {"volumeId": vid, "readOnly": True}, timeout=30),
          f"set readonly on {src}")
    _copy_volume_files(env, vid, collection, src, dst)
    _must(http_json("POST", f"{dst}/admin/mount_volume",
                    {"volumeId": vid, "collection": collection}, timeout=30),
          f"mount on {dst}")
    if delete_source:
        _must(http_json("POST", f"{src}/admin/delete_volume",
                        {"volumeId": vid}, timeout=30),
              f"delete source on {src}")
    else:
        _must(http_json("POST", f"{src}/admin/set_readonly",
                        {"volumeId": vid, "readOnly": False}, timeout=30),
              f"clear readonly on {src}")


@command("volume.balance")
def cmd_volume_balance(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_balance.go: even out volume counts across
    servers by moving volumes from the fullest to the emptiest."""
    env.confirm_is_locked()
    from ..topology import iter_volume_list_volumes
    vl = env.volume_list()
    per_node: dict[str, list[dict]] = {}
    for node, v in iter_volume_list_volumes(vl):
        per_node.setdefault(node["url"], []).append(v)
    for url in _all_node_urls(env):
        per_node.setdefault(url, [])
    if not per_node:
        return "no volume servers"
    total = sum(len(v) for v in per_node.values())
    avg = max(1, -(-total // len(per_node)))
    moved = 0
    for donor in sorted(per_node, key=lambda u: -len(per_node[u])):
        while len(per_node[donor]) > avg:
            recv = min(per_node, key=lambda u: len(per_node[u]))
            if recv == donor or len(per_node[recv]) + 1 > avg:
                break
            donor_vids = {v["id"] for v in per_node[donor]}
            recv_vids = {v["id"] for v in per_node[recv]}
            movable = [v for v in per_node[donor]
                       if v["id"] not in recv_vids]
            if not movable:
                break
            v = movable[-1]
            _move_volume(env, v["id"], v.get("collection", ""),
                         donor, recv)
            per_node[donor].remove(v)
            per_node[recv].append(v)
            moved += 1
    return f"moved {moved} volumes"


@command("volume.fix.replication")
def cmd_volume_fix_replication(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_fix_replication.go: re-create missing
    replicas for under-replicated volumes."""
    env.confirm_is_locked()
    from ..storage.replica_placement import ReplicaPlacement
    from ..topology import iter_volume_list_volumes
    vl = env.volume_list()
    locations: dict[int, list[str]] = {}
    meta: dict[int, dict] = {}
    for node, v in iter_volume_list_volumes(vl):
        locations.setdefault(v["id"], []).append(node["url"])
        meta[v["id"]] = v
    nodes = _all_node_urls(env)
    fixed = []
    for vid, locs in sorted(locations.items()):
        v = meta[vid]
        want = ReplicaPlacement.from_byte(
            v.get("replicaPlacement", 0)).copy_count()
        missing = want - len(locs)
        if missing <= 0:
            continue
        candidates = [n for n in nodes if n not in locs]
        for dst in candidates[:missing]:
            _copy_volume_files(env, vid, v.get("collection", ""),
                               locs[0], dst)
            _must(http_json("POST", f"{dst}/admin/mount_volume",
                            {"volumeId": vid,
                             "collection": v.get("collection", "")},
                      timeout=30),
                  f"mount on {dst}")
            fixed.append(f"{vid}->{dst}")
    return f"fixed replicas: {fixed}" if fixed else \
        "all volumes sufficiently replicated"


@command("ec.scrub")
def cmd_ec_scrub(env: CommandEnv, args: list[str]) -> str:
    """shell/command_ec_scrub.go:31 — modes index/local (:52)."""
    opts = _parse_flags(args)
    mode = opts.get("mode", "local")
    out = []
    for vid in _ec_volumes(env):
        for url in _ec_shard_locations(env, vid):
            r = http_json("POST", f"{url}/admin/ec/scrub",
                          {"volumeId": vid, "mode": mode}, timeout=30)
            if r.get("error"):
                out.append(f"volume {vid} @ {url}: ERROR {r['error']}")
            else:
                status = "ok" if not r["errors"] else \
                    f"{len(r['errors'])} errors, broken shards " \
                    f"{r['brokenShards']}"
                out.append(f"volume {vid} @ {url}: checked "
                           f"{r['checked']} entries, {status}")
    return "\n".join(out) if out else "no ec volumes"


# --- distributed tracing (tracing.py; the operator's flame view) ---------

def _cluster_debug_nodes(env: CommandEnv) -> list[str]:
    """Every node that may hold spans of a trace: master(s), every
    volume server, and the filer when the shell knows one."""
    r = master_json(env.master, "GET", "/cluster/status", timeout=30)
    nodes = [env.master]
    nodes += [p for p in r.get("peers", []) if p not in nodes]
    nodes += r.get("dataNodes", [])
    if env.filer and env.filer not in nodes:
        nodes.append(env.filer)
    return nodes


def collect_trace(env: CommandEnv, request_id: str,
                  extra_nodes: "list[str] | None" = None
                  ) -> "list[dict]":
    """Fan /debug/traces?request_id= out to every cluster node and
    merge the spans (deduped by span id; an unreachable node
    contributes nothing rather than failing the whole view).

    Runs under a FRESH request id: a shell context still carrying the
    queried id would otherwise trace its own topology/debug calls
    into the very trace it is rendering."""
    from ..util.request_id import (new_request_id, reset_request_id,
                                   set_request_id)
    token = set_request_id(new_request_id())
    try:
        nodes = _cluster_debug_nodes(env)
    finally:
        reset_request_id(token)
    for n in extra_nodes or []:
        if n not in nodes:
            nodes.append(n)

    def fetch(url: str) -> list:
        try:
            r = http_json(
                "GET", f"{url}/debug/traces?request_id={request_id}",
                timeout=10)
        except OSError:
            return []
        spans = r.get("spans", []) if isinstance(r, dict) else []
        for s in spans:
            s["node"] = url
        return spans

    merged: dict[str, dict] = {}
    with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
        for spans in ex.map(fetch, nodes):
            for s in spans:
                merged.setdefault(s["spanId"], s)
    return sorted(merged.values(), key=lambda s: s["start"])


def render_trace(spans: "list[dict]") -> str:
    """Time-aligned tree: children indent under their parent, each
    line shows offset from the trace's first span, duration, role@node
    and attrs — one request id becomes a cross-node flame view."""
    if not spans:
        return "no spans found (buffer rolled over, or wrong id?)"
    t0 = min(s["start"] for s in spans)
    by_parent: dict[str, list] = {}
    ids = {s["spanId"] for s in spans}
    for s in spans:
        parent = s.get("parentId") or ""
        if parent not in ids:
            parent = ""          # orphan (parent not collected): root
        by_parent.setdefault(parent, []).append(s)
    lines = [f"trace {spans[0]['traceId']}: {len(spans)} span(s), "
             f"{len({s.get('role') or '?' for s in spans})} role(s)"]

    def walk(parent: str, depth: int) -> None:
        for s in sorted(by_parent.get(parent, []),
                        key=lambda x: x["start"]):
            off = (s["start"] - t0) * 1e3
            attrs = s.get("attrs") or {}
            extra = " ".join(f"{k}={v}" for k, v in attrs.items())
            mark = " ERROR" if s.get("error") else ""
            lines.append(
                f"{'  ' * depth}+{off:8.1f}ms {s['name']}  "
                f"[{s.get('role') or '?'}@{s.get('node', '?')}] "
                f"{s['durationMs']}ms{mark}"
                + (f"  {extra}" if extra else ""))
            walk(s["spanId"], depth + 1)

    walk("", 0)
    return "\n".join(lines)


def collect_peer_health(env: CommandEnv,
                        extra_nodes: "list[str] | None" = None
                        ) -> "list[str]":
    """Every node's /debug/health (util/retry breaker map + budget),
    rendered one line per non-closed peer — the view that makes a
    chaos run debuggable from the shell: which node has stopped
    talking to which peer, and why."""
    try:
        nodes = _cluster_debug_nodes(env)
    except OSError:
        nodes = [env.master]
    for n in extra_nodes or []:
        if n not in nodes:
            nodes.append(n)

    def fetch(url: str):
        # best-effort probe: keep the budget per node tight — this
        # runs mid-incident, when a wedged node would otherwise stall
        # the whole shell command for its full timeout x retries
        try:
            r = http_json("GET", f"{url}/debug/health", timeout=3)
        except OSError:
            return url, None
        return url, r if isinstance(r, dict) else None

    lines: list[str] = []
    with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
        for url, r in ex.map(fetch, nodes):
            if not r:
                continue
            for peer, h in (r.get("peers") or {}).items():
                if h.get("state") == "closed" and not h.get("trips"):
                    continue
                lines.append(
                    f"  {url}: peer {peer} {h.get('state')} "
                    f"(consecutive failures "
                    f"{h.get('consecutiveFailures', 0)}, trips "
                    f"{h.get('trips', 0)})"
                    + (f" last: {h['lastError']}"
                       if h.get("lastError") else ""))
    return lines


@command("trace.show")
def cmd_trace_show(env: CommandEnv, args: list[str]) -> str:
    """Assemble one request's spans from every cluster node's
    /debug/traces ring buffer and render the time-aligned tree —
    turns a request id from a log line into a cross-node flame view
    (tracing.py; the operator entry point of the tracing plane).
    `-nodes=host:port[,...]` queries extra debug planes the topology
    doesn't know — e.g. the admin server, which holds ingested worker
    job spans.  When the trace shows failure activity (retry.* or
    error spans) — or always with `-health` — a "peer health" section
    is appended from every node's /debug/health, so retry stalls in
    the tree line up with the breaker that caused them; a clean trace
    skips that second cluster-wide fan-out (mid-incident, wedged
    nodes make every extra probe a stall)."""
    rids = [a for a in args if not a.startswith("-")]
    opts = _parse_flags(args)
    extra = [n.strip() for n in opts.get("nodes", "").split(",")
             if n.strip()]
    if not rids:
        return "usage: trace.show <request_id> [-nodes=host:port,...]" \
               " [-health]"
    traces = [collect_trace(env, rid, extra_nodes=extra)
              for rid in rids]
    out = [render_trace(spans) for spans in traces]
    want_health = "health" in opts or any(
        str(s.get("name", "")).startswith("retry.") or s.get("error")
        for spans in traces for s in spans)
    if want_health:
        health = collect_peer_health(env, extra_nodes=extra)
        if health:
            out.append("peer health (non-closed breakers):")
            out.extend(health)
        else:
            out.append("peer health: all breakers closed")
    return "\n".join(out)


@command("qos.status")
def cmd_qos_status(env: CommandEnv, args: list[str]) -> str:
    """Cluster-wide QoS view (qos.py): every node's /debug/qos —
    admission config, per-tenant in-flight bytes, and the EC feedback
    throttle's pace/p99.  `-nodes=host:port,...` adds listeners the
    topology doesn't know (e.g. a standalone S3 gateway)."""
    opts = _parse_flags(args)
    try:
        nodes = _cluster_debug_nodes(env)
    except OSError:
        nodes = [env.master]
    for n in (opts.get("nodes", "") or "").split(","):
        n = n.strip()
        if n and n not in nodes:
            nodes.append(n)
    out = []
    for url in nodes:
        try:
            r = http_json("GET", f"{url}/debug/qos", timeout=3)
        except OSError:
            out.append(f"{url}: unreachable")
            continue
        if not isinstance(r, dict) or "config" not in r:
            out.append(f"{url}: {r.get('error', 'no qos plane')}"
                       if isinstance(r, dict) else f"{url}: ?")
            continue
        cfg = r["config"]
        th = r.get("throttle", {})
        tenants = cfg.get("tenants", {})
        out.append(
            f"{url}: enabled={cfg.get('enabled')} "
            f"tenants={len(tenants)} "
            f"slo_p99={cfg.get('sloP99Ms', 0):.0f}ms "
            f"pace={th.get('paceMs', 0):.0f}ms "
            f"p99={th.get('lastP99Ms', 0):.1f}ms")
        for t, lim in sorted(tenants.items()):
            inflight = r.get("inflightBytes", {}).get(t, 0)
            out.append(f"  {t}: rps={lim.get('rps')} "
                       f"burst={lim.get('burst')} "
                       f"inflight_mb={lim.get('inflightMb')} "
                       f"(in flight now: {inflight}B)")
    return "\n".join(out)


@command("qos.set")
def cmd_qos_set(env: CommandEnv, args: list[str]) -> str:
    """Push one tenant's limits (or the default, tenant `*`) to every
    node's runtime QoS lever: `qos.set -tenant=AK -rps=10 [-burst=20]
    [-inflightMb=8]` — or `-sloP99Ms=200` to retune the EC throttle,
    `-clear` to reset the whole plane."""
    opts = _parse_flags(args)
    body: dict = {}
    if "clear" in opts:
        body["clear"] = True
    if "tenant" in opts:
        body["tenant"] = opts["tenant"]
        for k in ("rps", "burst", "inflightMb"):
            if k in opts:
                body[k] = float(opts[k])
    if "sloP99Ms" in opts:
        body["sloP99Ms"] = float(opts["sloP99Ms"])
    if not body:
        return ("usage: qos.set -tenant=<access-key|*> -rps=N "
                "[-burst=N] [-inflightMb=N] | -sloP99Ms=N | -clear")
    try:
        nodes = _cluster_debug_nodes(env)
    except OSError:
        nodes = [env.master]
    ok, failed = 0, []
    for url in nodes:
        try:
            r = http_json("POST", f"{url}/debug/qos", body, timeout=5)
            if isinstance(r, dict) and "error" in r:
                failed.append(f"{url}: {r['error']}")
            else:
                ok += 1
        except OSError as e:
            failed.append(f"{url}: {e}")
    out = [f"qos updated on {ok}/{len(nodes)} nodes"]
    out.extend(failed)
    return "\n".join(out)


_ROLE_NAMESPACES = ("master", "volume_server", "filer", "s3")


def _top_nodes(env: CommandEnv, opts: dict) -> "list[str]":
    """Fan-out target list: the topology's debug planes plus any
    `-nodes=` extras (a standalone S3 gateway, the admin server)."""
    try:
        nodes = _cluster_debug_nodes(env)
    except OSError:
        nodes = [env.master]
    for n in (opts.get("nodes", "") or "").split(","):
        n = n.strip()
        if n and n not in nodes:
            nodes.append(n)
    return nodes


def _fetch_metrics(url: str) -> "dict[str, list] | None":
    """One node's /metrics, parsed (profiling.parse_prom_text);
    None when unreachable."""
    from .. import profiling
    try:
        st, body, _ = http_bytes("GET", f"{url}/metrics", timeout=3)
    except OSError:
        return None
    if st >= 300:
        return None
    return profiling.parse_prom_text(body.decode("utf-8", "replace"))


def _node_role(metrics: "dict[str, list]") -> str:
    """Which role registry this listener renders (each role's Metrics
    namespace prefixes its request_seconds histogram)."""
    for ns in _ROLE_NAMESPACES:
        if f"{ns}_request_seconds_count" in metrics:
            return ns
    return "?"


def _gauge(metrics: "dict[str, list]", name: str,
           match: "dict | None" = None) -> "float | None":
    match = match or {}
    for labels, value in metrics.get(name, []):
        if all(labels.get(k) == v for k, v in match.items()):
            return value
    return None


def _counter_sum(metrics: "dict[str, list]", name: str,
                 match: "dict | None" = None) -> float:
    match = match or {}
    return sum(v for l, v in metrics.get(name, [])
               if all(l.get(k) == mv for k, mv in match.items()))


def _read_cache_report(before: "dict[str, list]",
                       after: "dict[str, list]") -> str:
    """Per-cache hot-read-cache view over the sampling window: hit
    ratio + bytes served from cache (util/chunk_cache meters on the
    shared registry).  Empty when no instrumented cache was touched."""
    caches = {l.get("cache", "") for name in
              ("seaweedfs_tpu_read_cache_hits_total",
               "seaweedfs_tpu_read_cache_misses_total")
              for l, _v in after.get(name, [])}
    parts = []
    for c in sorted(caches):
        hits = _counter_sum(
            after, "seaweedfs_tpu_read_cache_hits_total",
            {"cache": c}) - _counter_sum(
            before, "seaweedfs_tpu_read_cache_hits_total",
            {"cache": c})
        misses = _counter_sum(
            after, "seaweedfs_tpu_read_cache_misses_total",
            {"cache": c}) - _counter_sum(
            before, "seaweedfs_tpu_read_cache_misses_total",
            {"cache": c})
        if hits + misses <= 0:
            continue
        served = _counter_sum(
            after, "seaweedfs_tpu_read_cache_bytes_served_total",
            {"cache": c}) - _counter_sum(
            before, "seaweedfs_tpu_read_cache_bytes_served_total",
            {"cache": c})
        parts.append(f"{c} {hits / (hits + misses) * 100:.0f}% "
                     f"({served / (1 << 20):.1f}MB served)")
    if not parts:
        return ""
    return "read-cache: " + "  ".join(parts)


def _stage_report(before: "dict[str, list]", after: "dict[str, list]",
                  ns: str) -> str:
    """Per-stage share of write-path wall time over the sampling
    window, from the write_stage_seconds decomposition (profiling.py),
    with each stage's cpu/wall mean beside it (write_stage_cpu_seconds
    — ISSUE 15): `upload 45% cpu=0.12/1.30ms` reads "45% of write
    wall, of which each call burned 0.12ms CPU out of 1.30ms wall —
    the other 1.18ms was GIL/lock/IO wait".  Empty string when no
    write landed in the window."""
    from .. import profiling
    name = f"{ns}_write_stage_seconds"
    cpu_name = f"{ns}_write_stage_cpu_seconds"
    stages: dict[str, tuple] = {}
    total = 0.0
    seen = {l.get("stage", "") for l, _v in
            after.get(f"{name}_count", [])}
    for stage in sorted(seen):
        h = profiling.histogram_delta(
            profiling.prom_histogram(after, name, {"stage": stage}),
            profiling.prom_histogram(before, name, {"stage": stage}))
        if not h or h["count"] <= 0:
            continue
        c = profiling.histogram_delta(
            profiling.prom_histogram(after, cpu_name,
                                     {"stage": stage}),
            profiling.prom_histogram(before, cpu_name,
                                     {"stage": stage}))
        cpu_mean = (c["sum"] / c["count"]) if c and c["count"] else None
        if stage == "total":
            total = h["sum"]
        else:
            stages[stage] = (h["sum"], h["sum"] / h["count"], cpu_mean)
    if not stages or total <= 0:
        return ""
    parts = []
    for s, (secs, wall_mean, cpu_mean) in sorted(
            stages.items(), key=lambda kv: -kv[1][0]):
        p = f"{s} {secs / total * 100.0:.0f}%"
        if cpu_mean is not None:
            p += (f" cpu={cpu_mean * 1e3:.2f}/"
                  f"{wall_mean * 1e3:.2f}ms")
        parts.append(p)
    return "write stages: " + " ".join(parts)


def _cpu_report(before: "dict[str, list]", after: "dict[str, list]",
                ns: str, req: "dict | None", window: float) -> str:
    """The node's cost-attribution line (ISSUE 15): mean CPU vs wall
    per request from request_cpu_seconds/request_seconds, the
    scheduler-probe gil_wait_ratio, and the /proc process-TREE CPU
    burn + RSS (pre-fork workers and native plane children included).
    Empty when the window saw no requests and no tree gauges."""
    from .. import profiling
    parts = []
    c = profiling.histogram_delta(
        profiling.prom_histogram(after, f"{ns}_request_cpu_seconds"),
        profiling.prom_histogram(before, f"{ns}_request_cpu_seconds"))
    if c and c["count"] > 0 and req and req["count"] > 0:
        cpu_ms = c["sum"] / c["count"] * 1e3
        wall_ms = req["sum"] / req["count"] * 1e3
        if wall_ms > 0:
            parts.append(
                f"{cpu_ms:.2f}ms cpu of {wall_ms:.2f}ms wall/req "
                f"(wait {max(1.0 - cpu_ms / wall_ms, 0.0) * 100:.0f}%)")
    gil = _gauge(after, "seaweedfs_tpu_gil_wait_ratio")
    if gil is not None:
        parts.append(f"gil-wait={gil * 100:.0f}%")
    tree_a = _gauge(after, "seaweedfs_tpu_process_tree_cpu_seconds")
    tree_b = _gauge(before, "seaweedfs_tpu_process_tree_cpu_seconds")
    if tree_a is not None and tree_b is not None and window > 0:
        burn = max(tree_a - tree_b, 0.0) / window
        procs = _gauge(after, "seaweedfs_tpu_process_tree_procs") or 1
        rss = _gauge(after, "seaweedfs_tpu_process_tree_rss_bytes") \
            or 0.0
        parts.append(f"tree={burn:.2f} cores/{procs:.0f} procs "
                     f"rss={rss / (1 << 20):.0f}MB")
    if not parts:
        return ""
    return "cpu: " + "  ".join(parts)


def _group_commit_report(before: "dict[str, list]",
                         after: "dict[str, list]") -> str:
    """Per-site group-commit view over the sampling window: mean
    batch (writers covered per shared durability barrier) and
    barrier-wait p99, from the util/group_commit metrics on the
    shared process registry.  Empty when no barrier fired."""
    from .. import profiling
    batch = "seaweedfs_tpu_group_commit_batch_size"
    wait = "seaweedfs_tpu_group_commit_wait_seconds"
    sites = {l.get("site", "") for l, _v in
             after.get(f"{batch}_count", [])}
    parts = []
    for site in sorted(sites):
        h = profiling.histogram_delta(
            profiling.prom_histogram(after, batch, {"site": site}),
            profiling.prom_histogram(before, batch, {"site": site}))
        if not h or h["count"] <= 0:
            continue
        w = profiling.histogram_delta(
            profiling.prom_histogram(after, wait, {"site": site}),
            profiling.prom_histogram(before, wait, {"site": site}))
        p99 = profiling.histogram_quantile(w, 0.99) if w else 0.0
        parts.append(f"{site} batch={h['sum'] / h['count']:.1f} "
                     f"wait-p99={p99 * 1e3:.2f}ms")
    if not parts:
        return ""
    return "group-commit: " + "  ".join(parts)


def _native_plane_report(before: "dict[str, list]",
                         after: "dict[str, list]") -> str:
    """Native read/write/meta plane view over the sampling window:
    acks and fallbacks per plane plus the native ack-latency p99 (C++
    atomics rendered by the volume server's and filer's /metrics).
    Empty when the node runs no native plane."""
    from .. import profiling
    parts = []
    wname = "volume_server_write_plane_ack_seconds"
    wr = _counter_sum(
        after, "volume_server_write_plane_requests_total") - \
        _counter_sum(before, "volume_server_write_plane_requests_total")
    wf = _counter_sum(
        after, "volume_server_write_plane_fallbacks_total") - \
        _counter_sum(before,
                     "volume_server_write_plane_fallbacks_total")
    if f"{wname}_count" in after:
        h = profiling.histogram_delta(
            profiling.prom_histogram(after, wname),
            profiling.prom_histogram(before, wname))
        p99 = profiling.histogram_quantile(h, 0.99) \
            if h and h.get("count") else 0.0
        parts.append(f"write {wr:.0f} acked/{wf:.0f} fallback"
                     f" ack-p99={p99 * 1e3:.2f}ms")
    rr = _counter_sum(
        after, "volume_server_read_plane_requests_total") - \
        _counter_sum(before,
                     "volume_server_read_plane_requests_total")
    rf = _counter_sum(
        after, "volume_server_read_plane_fallbacks_total") - \
        _counter_sum(before,
                     "volume_server_read_plane_fallbacks_total")
    if "volume_server_read_plane_requests_total" in after:
        parts.append(f"read {rr:.0f} served/{rf:.0f} fallback")
    # the filer's native READ plane (ISSUE 19): warm GETs served with
    # zero Python, coherence misses surfaced beside the fallbacks
    fr = _counter_sum(
        after, "filer_read_plane_native_requests_total") - \
        _counter_sum(before, "filer_read_plane_native_requests_total")
    ff = _counter_sum(
        after, "filer_read_plane_native_fallbacks_total") - \
        _counter_sum(before,
                     "filer_read_plane_native_fallbacks_total")
    if "filer_read_plane_native_requests_total" in after:
        fstale = _counter_sum(
            after, "filer_read_plane_native_stale_misses_total") - \
            _counter_sum(before,
                         "filer_read_plane_native_stale_misses_total")
        seg = f"filer-read {fr:.0f} served/{ff:.0f} fallback"
        if fstale > 0:
            seg += f" stale={fstale:.0f}"
        parts.append(seg)
    # the filer's native META plane (ISSUE 17): creates acked with
    # zero Python, plus its ack-latency p99 and mean WAL batch
    mname = "filer_meta_plane_native_ack_seconds"
    mr = _counter_sum(
        after, "filer_meta_plane_native_requests_total") - \
        _counter_sum(before, "filer_meta_plane_native_requests_total")
    mf = _counter_sum(
        after, "filer_meta_plane_native_fallbacks_total") - \
        _counter_sum(before,
                     "filer_meta_plane_native_fallbacks_total")
    if f"{mname}_count" in after:
        h = profiling.histogram_delta(
            profiling.prom_histogram(after, mname),
            profiling.prom_histogram(before, mname))
        p99 = profiling.histogram_quantile(h, 0.99) \
            if h and h.get("count") else 0.0
        batches = _counter_sum(
            after, "filer_meta_plane_native_wal_batches_total") - \
            _counter_sum(before,
                         "filer_meta_plane_native_wal_batches_total")
        lines = _counter_sum(
            after, "filer_meta_plane_native_wal_lines_total") - \
            _counter_sum(before,
                         "filer_meta_plane_native_wal_lines_total")
        seg = (f"meta {mr:.0f} acked/{mf:.0f} fallback"
               f" ack-p99={p99 * 1e3:.2f}ms")
        if batches > 0:
            seg += f" wal-batch={lines / batches:.1f}"
        parts.append(seg)
    # per-stage tails from the drained flight records (ISSUE 18): the
    # plane_stage_seconds family is fed by the Python drainer, so each
    # plane's stage decomposition shows up windowed, like every other
    # cluster.top figure
    sname = "seaweedfs_tpu_plane_stage_seconds"
    planes = sorted({l.get("plane", "") for l, _v in
                     after.get(f"{sname}_count", []) if l.get("plane")})
    from ..server.filer_read_plane_native import (
        RECORD_STAGES as _FILER_READ_STAGES)
    from ..server.meta_plane_native import (
        RECORD_STAGES as _META_STAGES)
    from ..server.read_plane import RECORD_STAGES as _READ_STAGES
    from ..server.write_plane import RECORD_STAGES as _WRITE_STAGES
    stage_order = {"meta": _META_STAGES, "write": _WRITE_STAGES,
                   "read": _READ_STAGES,
                   "filer_read": _FILER_READ_STAGES}
    for plane in planes:
        segs = []
        for stg in stage_order.get(plane, ()):
            h = profiling.histogram_delta(
                profiling.prom_histogram(
                    after, sname, {"plane": plane, "stage": stg}),
                profiling.prom_histogram(
                    before, sname, {"plane": plane, "stage": stg}))
            if h and h.get("count"):
                p99 = profiling.histogram_quantile(h, 0.99)
                segs.append(f"{stg}-p99={p99 * 1e3:.2f}ms")
        dropped = _counter_sum(
            after, "seaweedfs_tpu_plane_ring_dropped_total",
            {"plane": plane}) - _counter_sum(
            before, "seaweedfs_tpu_plane_ring_dropped_total",
            {"plane": plane})
        if dropped > 0:
            segs.append(f"ring-dropped={dropped:.0f}")
        if segs:
            parts.append(f"{plane}-stages " + " ".join(segs))
    if not parts:
        return ""
    return "native-planes: " + "  ".join(parts)


def _autopilot_report(before: "dict[str, list]",
                      after: "dict[str, list]") -> str:
    """SLO-autopilot view (autopilot.py, ISSUE 20): loop state, the
    knobs it currently holds, and any actuation that landed in the
    sampling window with its direction.  Empty for a role that runs
    no loop; "off" is explicit — an operator must be able to see a
    killed controller at a glance."""
    enabled = _gauge(after, "seaweedfs_tpu_autopilot_enabled")
    if enabled is None:
        return ""
    knobs = " ".join(
        f"{l.get('knob', '?')}={v:.4g}"
        for l, v in sorted(after.get(
            "seaweedfs_tpu_autopilot_knob", []),
            key=lambda kv: kv[0].get("knob", "")))
    line = "autopilot: " + ("on" if enabled else "off")
    if knobs:
        line += "  " + knobs
    moved = []
    for l, v in after.get("seaweedfs_tpu_autopilot_actions_total",
                          []):
        d = v - _counter_sum(
            before, "seaweedfs_tpu_autopilot_actions_total",
            {"knob": l.get("knob", ""),
             "direction": l.get("direction", "")})
        if d > 0:
            arrow = {"up": "^", "down": "v"}.get(
                l.get("direction", ""), l.get("direction", ""))
            moved.append(f"{l.get('knob', '?')}{arrow}x{d:.0f}")
    if moved:
        line += "  moved: " + " ".join(sorted(moved))
    return line


def _deadline_report(before: "dict[str, list]",
                     after: "dict[str, list]") -> str:
    """Deadline-plane view over the sampling window: budgets refused
    (per fail-fast site) and hedged replica reads issued/won
    (util/deadline + util/hedge meter on the shared registry).  Empty
    when the window saw neither — the common healthy state."""
    exceeded = _counter_sum(
        after, "seaweedfs_tpu_deadline_exceeded_total") - \
        _counter_sum(before, "seaweedfs_tpu_deadline_exceeded_total")
    issued = _counter_sum(
        after, "seaweedfs_tpu_hedges_issued_total") - \
        _counter_sum(before, "seaweedfs_tpu_hedges_issued_total")
    won = _counter_sum(
        after, "seaweedfs_tpu_hedges_won_total") - \
        _counter_sum(before, "seaweedfs_tpu_hedges_won_total")
    parts = []
    if exceeded > 0:
        sites = {l.get("site", "") for l, _v in after.get(
            "seaweedfs_tpu_deadline_exceeded_total", [])}
        worst = []
        for s in sorted(sites):
            d = _counter_sum(
                after, "seaweedfs_tpu_deadline_exceeded_total",
                {"site": s}) - _counter_sum(
                before, "seaweedfs_tpu_deadline_exceeded_total",
                {"site": s})
            if d > 0:
                worst.append((d, s))
        worst.sort(reverse=True)
        top = " ".join(f"{s}={d:.0f}" for d, s in worst[:3])
        parts.append(f"exceeded={exceeded:.0f} ({top})")
    if issued > 0:
        parts.append(f"hedges={issued:.0f} issued/{won:.0f} won")
    if not parts:
        return ""
    return "deadline: " + "  ".join(parts)


@command("cluster.top")
def cmd_cluster_top(env: CommandEnv, args: list[str]) -> str:
    """Live one-screen cluster view: every node's /metrics sampled
    twice `-interval=N` seconds apart (default 2), the delta rendered
    as per-role req/s, windowed p99, in-flight requests, pooled-client
    connection reuse, breaker/QoS state, device telemetry where the
    node has touched a TPU, the write-path stage decomposition and
    group-commit batching (mean batch size, barrier-wait p99) when
    writes landed in the window, and the top profiler stacks on any
    node whose sampler is armed.  The operator's answer to "what is
    this cluster doing RIGHT NOW"."""
    from .. import profiling
    opts = _parse_flags(args)
    try:
        window = max(0.2, float(opts.get("interval", 2.0)))
    except ValueError:
        return "bad -interval"
    nodes = _top_nodes(env, opts)

    with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
        before = dict(zip(nodes, ex.map(_fetch_metrics, nodes)))
        time.sleep(window)
        after = dict(zip(nodes, ex.map(_fetch_metrics, nodes)))

    out = [f"cluster.top — {len(nodes)} nodes, "
           f"{window:.1f}s window"]
    for url in nodes:
        b, a = before.get(url), after.get(url)
        if a is None:
            out.append(f"{url}: unreachable")
            continue
        if b is None:
            # no baseline sample: rendering cumulative-since-boot
            # counters as this window's delta would show a day-old
            # node at absurd req/s
            out.append(f"{url}: no baseline sample this window")
            continue
        try:
            out.extend(_render_node_top(url, b, a, window))
        except Exception as e:  # noqa: BLE001 — one node's partial or
            # malformed mid-interval scrape must cost that node a
            # note, never the whole cluster view
            out.append(f"{url}: render failed: {e}")
    return "\n".join(out)


def _render_node_top(url: str, b: "dict[str, list]",
                     a: "dict[str, list]",
                     window: float) -> "list[str]":
    """One node's cluster.top block, split out so the caller can
    contain a render failure (a node restarting mid-interval hands
    back truncated metrics; a role skew hands back unexpected label
    shapes) to that node's line."""
    from .. import profiling
    out: list[str] = []
    ns = _node_role(a)
    req = profiling.histogram_delta(
        profiling.prom_histogram(a, f"{ns}_request_seconds"),
        profiling.prom_histogram(b, f"{ns}_request_seconds"))
    rate = (req["count"] / window) if req else 0.0
    p99 = profiling.histogram_quantile(req, 0.99) if req else 0.0
    inflight = _gauge(a, f"{ns}_requests_in_flight") or 0
    line = (f"{url} [{ns}] {rate:7.1f} req/s  "
            f"p99={p99 * 1e3:7.1f}ms  in-flight={inflight:.0f}")
    reused = _counter_sum(
        a, "seaweedfs_tpu_pool_connections_reused_total")
    opened = _counter_sum(
        a, "seaweedfs_tpu_pool_connections_opened_total")
    if reused + opened > 0:
        line += (f"  pool-reuse={reused / (reused + opened) * 100:.0f}%"
                 f" ({opened:.0f} dials)")
    open_breakers = sum(
        1 for _l, v in a.get("seaweedfs_tpu_peer_breaker_state", [])
        if v != 0)
    if open_breakers:
        line += f"  breakers:{open_breakers} non-closed"
    pace = _gauge(a, "seaweedfs_tpu_qos_ec_pace_ms")
    if pace:
        line += f"  ec-pace={pace:.0f}ms"
    rejected = _counter_sum(a, "seaweedfs_tpu_qos_rejected_total") \
        - _counter_sum(b, "seaweedfs_tpu_qos_rejected_total")
    if rejected > 0:
        line += f"  qos-rejected={rejected:.0f}"
    # a volume server whose heartbeats raise or miss the master is one
    # the master is about to let go of: the count since it started
    hb_errors = _counter_sum(
        a, "seaweedfs_tpu_volume_heartbeat_errors_total")
    if hb_errors > 0:
        line += f"  heartbeat-errors={hb_errors:.0f}"
    out.append(line)
    kern = _gauge(a, "seaweedfs_tpu_device_kernel_last_ms",
                  {"kernel": "gf_apply_matrix"})
    if kern is not None:
        h2d = _gauge(a, "seaweedfs_tpu_device_h2d_gbps") or 0.0
        d2h = _gauge(a, "seaweedfs_tpu_device_d2h_gbps") or 0.0
        line = (f"  device: kernel={kern:.2f}ms "
                f"h2d={h2d:.2f}GB/s d2h={d2h:.2f}GB/s")
        # windowed staging figures (ops.staging): window count
        # since the previous sample + how overlapped the last
        # launch's h2d/d2h planes actually ran
        ov = _gauge(a, "seaweedfs_tpu_device_h2d_overlap_fraction",
                    {"op": "encode"})
        if ov is None:  # rebuild-only workload stages too
            ov = _gauge(a,
                        "seaweedfs_tpu_device_h2d_overlap_fraction",
                        {"op": "rebuild"})
        wins = _counter_sum(
            a, "seaweedfs_tpu_device_staged_windows_total") - \
            _counter_sum(
                b, "seaweedfs_tpu_device_staged_windows_total")
        if ov is not None:
            line += f"  overlap={ov * 100:.0f}%"
        if wins > 0:
            line += f"  windows={wins:.0f}"
        out.append(line)
    cpu = _cpu_report(b, a, ns, req, window)
    if cpu:
        out.append("  " + cpu)
    cache_line = _read_cache_report(b, a)
    degraded = _counter_sum(
        a, "seaweedfs_tpu_ec_degraded_reads_total") - \
        _counter_sum(b, "seaweedfs_tpu_ec_degraded_reads_total")
    if degraded > 0:
        cache_line += ("  " if cache_line else "") + \
            f"degraded-reads={degraded:.0f}"
    if cache_line:
        out.append("  " + cache_line)
    stages = _stage_report(b, a, ns)
    if stages:
        out.append("  " + stages)
    planes = _native_plane_report(b, a)
    if planes:
        out.append("  " + planes)
    gc = _group_commit_report(b, a)
    if gc:
        out.append("  " + gc)
    dl = _deadline_report(b, a)
    if dl:
        out.append("  " + dl)
    ap = _autopilot_report(b, a)
    if ap:
        out.append("  " + ap)
    try:
        prof = http_json("GET", f"{url}/debug/pprof?top=3",
                         timeout=3)
    except OSError:
        prof = None
    if isinstance(prof, dict) and prof.get("stacks"):
        total = max(1, prof["stacks"])
        for stack, n in sorted(prof.get("folded", {}).items(),
                               key=lambda kv: -kv[1]):
            leaf = stack.rsplit(";", 2)[-2:]
            out.append(f"  prof {n / total * 100:4.1f}% "
                       f"{';'.join(leaf)}")
    return out


def _render_slow_hop(url: str, rec: dict) -> "list[str]":
    """One flight record as an indented hop block: the wall/cpu/wait
    split, the deadline budget+verdict, the stage decomposition
    (wall/cpu per stage) and the hedge/QoS/plane flight notes."""
    wall = rec.get("wallMs", 0.0)
    cpu = rec.get("cpuMs")     # absent = request didn't draw the
    # CPU-attribution sample (SEAWEEDFS_TPU_CPU_SAMPLE): wall only,
    # never a fake 0ms cpu
    head = (f"  {rec.get('role', '?')}@{url}: "
            f"{rec.get('method', '?')} {rec.get('path', '?')} "
            f"status={rec.get('status', 0)}")
    if wall > 0 and cpu is not None:
        wait = rec.get("waitMs", max(wall - cpu, 0.0))
        line = (f"{head} {wall:.1f}ms wall / {cpu:.2f}ms cpu "
                f"(wait {wait / wall * 100:.0f}%)")
    elif wall > 0:
        line = f"{head} {wall:.1f}ms wall (cpu unsampled)"
    else:
        line = head
    dl = rec.get("deadline")
    if dl:
        line += (f"  deadline={dl.get('budgetMs', 0)}ms"
                 f"->{dl.get('remainingMs', 0)}ms left")
    if rec.get("verdict") not in (None, "slow"):
        line += f"  verdict={rec['verdict']}"
    out = [line]
    stages = (rec.get("stages") or {}).get("stages") or {}
    if stages:
        with_cpu = any("cpuMs" in d for d in stages.values())
        parts = [(f"{s} {d.get('wallMs', 0):.1f}/"
                  f"{d.get('cpuMs', 0):.2f}ms" if "cpuMs" in d else
                  f"{s} {d.get('wallMs', 0):.1f}ms")
                 for s, d in sorted(stages.items(),
                                    key=lambda kv:
                                    -kv[1].get("wallMs", 0))]
        out.append(("    stages (wall/cpu): " if with_cpu else
                    "    stages (wall): ") + " ".join(parts))
    notes = dict(rec.get("notes") or {})
    notes.update((rec.get("stages") or {}).get("notes") or {})
    if notes:
        out.append("    notes: " + " ".join(
            f"{k}={json.dumps(v, separators=(',', ':'))}"
            if isinstance(v, (dict, list)) else f"{k}={v}"
            for k, v in sorted(notes.items())))
    return out


@command("cluster.slow")
def cmd_cluster_slow(env: CommandEnv, args: list[str]) -> str:
    """The cluster's tail, after the fact: every node's flight
    recorder ring (/debug/slow, profiling.FlightRecorder) fanned out,
    merged by trace id, and rendered as the top-N slowest END-TO-END
    requests — one block per request with each hop's wall/cpu/wait
    split, stage decomposition, deadline budget+verdict and
    hedge/QoS/native-plane notes, then the merged cross-role span
    tree, time-aligned like trace.show.  `-top=N` blocks (default 5),
    `-verdict=slow|error|deadline|shed` filters on any hop's verdict,
    `-nodes=host:port,...` adds listeners the topology doesn't know,
    `-clear` empties every ring instead (chaos runs reset between
    scenarios).  A node whose scrape fails mid-fan-out is noted and
    skipped — mid-incident is exactly when one wedged node must not
    take the whole view down."""
    opts = _parse_flags(args)
    try:
        top = max(1, int(opts.get("top", 5)))
    except ValueError:
        return "bad -top"
    want = opts.get("verdict", "")
    nodes = _top_nodes(env, opts)

    if "clear" in opts:
        def clear(url: str) -> "tuple[str, bool]":
            try:
                r = http_json("POST", f"{url}/debug/slow",
                              {"clear": True}, timeout=5)
                return url, isinstance(r, dict) and "error" not in r
            except OSError:
                return url, False
        with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
            results = dict(ex.map(clear, nodes))
        ok = sum(1 for v in results.values() if v)
        out = [f"cluster.slow — cleared {ok}/{len(nodes)} rings"]
        out.extend(f"  {u}: unreachable" for u, v in results.items()
                   if not v)
        return "\n".join(out)

    def fetch(url: str) -> "tuple[str, dict | None]":
        try:
            r = http_json("GET", f"{url}/debug/slow", timeout=5)
        except OSError:
            return url, None
        return url, r if isinstance(r, dict) and "records" in r \
            else None

    with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
        snaps = dict(ex.map(fetch, nodes))

    # merge by trace id: the same end-to-end request appears in each
    # hop's ring under one id; records with no id stand alone
    groups: "dict[str, list[tuple[str, dict]]]" = {}
    captured = 0
    skipped: list[str] = []
    loose = 0
    seen_recs: "set[str]" = set()
    for url in nodes:
        snap = snaps.get(url)
        if snap is None:
            skipped.append(f"  {url}: scrape failed, skipped")
            continue
        for rec in snap.get("records", []):
            if not isinstance(rec, dict):
                continue
            # one recorder answering under two addresses (a node
            # listed both by the topology and -nodes=, or an
            # in-process multi-role rig sharing one ring) must not
            # double every hop of every request it captured
            fp = json.dumps(rec, sort_keys=True,
                            separators=(",", ":"))
            if fp in seen_recs:
                continue
            seen_recs.add(fp)
            captured += 1
            tid = rec.get("traceId") or ""
            if not tid:
                loose += 1
                tid = f"(no-trace-{loose})"
            groups.setdefault(tid, []).append((url, rec))
    if want:
        groups = {tid: hops for tid, hops in groups.items()
                  if any(r.get("verdict") == want for _u, r in hops)}

    # end-to-end wall = the slowest hop's wall (the edge's record
    # covers its downstream hops); rank the groups by it
    def group_wall(hops: "list[tuple[str, dict]]") -> float:
        return max(r.get("wallMs", 0.0) for _u, r in hops)

    ranked = sorted(groups.items(), key=lambda kv: -group_wall(kv[1]))
    out = [f"cluster.slow — {captured} records on "
           f"{sum(1 for u in nodes if snaps.get(u) is not None)}"
           f"/{len(nodes)} nodes, "
           f"{len(groups)} distinct requests"
           + (f" (verdict={want})" if want else "")
           + f", top {min(top, len(ranked))}"]
    out.extend(skipped)
    for tid, hops in ranked[:top]:
        # a hop with a terminal verdict names the incident better
        # than "slow"; surface the worst one in the header
        verdicts = {r.get("verdict", "slow") for _u, r in hops}
        headline = next((v for v in ("deadline", "error", "shed")
                         if v in verdicts), "slow")
        out.append(f"{group_wall(hops):9.1f}ms  trace={tid}  "
                   f"verdict={headline}  {len(hops)} hop(s)")
        spans: "dict[str, dict]" = {}
        for url, rec in sorted(hops,
                               key=lambda ur: -ur[1].get("wallMs", 0)):
            try:
                out.extend(_render_slow_hop(url, rec))
            except Exception as e:  # noqa: BLE001 — one malformed
                # record must not hide the rest of the request
                out.append(f"  {url}: record render failed: {e}")
            for s in rec.get("spans") or []:
                if isinstance(s, dict) and s.get("spanId"):
                    s.setdefault("node", url)
                    spans.setdefault(s["spanId"], s)
        if spans:
            tree = render_trace(
                sorted(spans.values(), key=lambda s: s["start"]))
            out.extend("  " + t for t in tree.splitlines())
    if len(out) == 1 + len(skipped):
        out.append("  (no records — rings empty or filtered out)")
    return "\n".join(out)


@command("cluster.profile")
def cmd_cluster_profile(env: CommandEnv, args: list[str]) -> str:
    """Arm the sampling profiler on every node, wait
    `-duration=N` seconds (default 10), disarm, and merge the folded
    stacks into one cluster-wide flame view (`-hz=N` sampling rate,
    `-top=N` lines shown, `-out=FILE` writes the full merged
    collapsed-stack file for flamegraph.pl).  A node whose sampler
    was already armed keeps its window but is still collected and
    disarmed — two operators profiling at once merge, not clobber."""
    from .. import profiling
    opts = _parse_flags(args)
    try:
        duration = max(0.2, float(opts.get("duration", 10.0)))
        hz = float(opts.get("hz", 100.0))
        top = int(opts.get("top", 25))
    except ValueError:
        return "bad -duration/-hz/-top"
    nodes = _top_nodes(env, opts)

    def arm(url: str) -> "tuple[str, bool]":
        try:
            r = http_json("POST", f"{url}/debug/pprof",
                          {"action": "start", "hz": hz}, timeout=5)
            return url, isinstance(r, dict) and "error" not in r
        except OSError:
            return url, False

    def disarm(url: str) -> "tuple[str, dict | None]":
        try:
            r = http_json("POST", f"{url}/debug/pprof",
                          {"action": "stop"}, timeout=10)
            return url, r if isinstance(r, dict) else None
        except OSError:
            return url, None

    with ThreadPoolExecutor(max_workers=min(8, len(nodes))) as ex:
        armed = dict(ex.map(arm, nodes))
        time.sleep(duration)
        snaps = dict(ex.map(disarm, nodes))

    tables, per_node = [], []
    for url in nodes:
        snap = snaps.get(url)
        if snap is None:
            per_node.append(f"  {url}: unreachable"
                            if not armed.get(url) else
                            f"  {url}: armed but no snapshot")
            continue
        tables.append(snap.get("folded") or {})
        per_node.append(
            f"  {url}: {snap.get('samples', 0)} passes, "
            f"{snap.get('stacks', 0)} stacks, "
            f"overhead={snap.get('overhead', 0.0) * 100:.2f}%")
    merged = profiling.merge_folded(tables)
    total = sum(merged.values()) or 1
    out = [f"cluster.profile — {duration:.1f}s @ {hz:.0f}Hz, "
           f"{len(tables)}/{len(nodes)} nodes, "
           f"{len(merged)} distinct stacks"]
    out.extend(per_node)
    if "out" in opts:
        with open(opts["out"], "w", encoding="utf-8") as f:
            for stack, n in sorted(merged.items(),
                                   key=lambda kv: -kv[1]):
                f.write(f"{stack} {n}\n")
        out.append(f"full collapsed-stack file: {opts['out']} "
                   f"(flamegraph.pl input)")
    for stack, n in sorted(merged.items(),
                           key=lambda kv: -kv[1])[:top]:
        frames = stack.split(";")
        tail = ";".join(frames[-3:]) if len(frames) > 3 else stack
        out.append(f"{n:6d} {n / total * 100:4.1f}%  {tail}")
    return "\n".join(out)


@command("volume.scrub")
def cmd_volume_scrub(env: CommandEnv, args: list[str]) -> str:
    """CRC-verify every needle of every (or one) volume
    (volume.fsck-style integrity pass)."""
    opts = _parse_flags(args)
    target = int(opts["volumeId"]) if "volumeId" in opts else None
    out = []
    for vid, urls in sorted(_volumes_by_id(env).items()):
        if target is not None and vid != target:
            continue
        for url in urls:
            r = http_json("POST", f"{url}/admin/scrub",
                          {"volumeId": vid}, timeout=30)
            if r.get("error"):
                out.append(f"volume {vid} @ {url}: ERROR {r['error']}")
            else:
                status = "ok" if not r["errors"] else r["errors"][:3]
                out.append(f"volume {vid} @ {url}: checked "
                           f"{r['checked']}, {status}")
    return "\n".join(out) if out else "no volumes"


# --- helpers -------------------------------------------------------------

def _must(r: dict, what: str) -> dict:
    if isinstance(r, dict) and r.get("error"):
        raise RuntimeError(f"{what}: {r['error']}")
    return r


def _parse_flags(args: list[str]) -> dict:
    """-volumeId=3 -collection=x style flags."""
    out = {}
    for a in args:
        if a.startswith("-") and "=" in a:
            k, v = a[1:].split("=", 1)
            out[k] = v
        elif a.startswith("-"):
            out[a[1:]] = "true"
    return out


def _volumes_by_id(env: CommandEnv) -> dict[int, list[str]]:
    from ..topology import iter_volume_list_volumes
    out: dict[int, list[str]] = {}
    for node, v in iter_volume_list_volumes(env.volume_list()):
        out.setdefault(v["id"], []).append(node["url"])
    return out


def _ec_volumes(env: CommandEnv) -> dict[int, None]:
    from ..topology import iter_volume_list_ec_shards
    out: dict[int, None] = {}
    for _node, e in iter_volume_list_ec_shards(env.volume_list()):
        out[e["volumeId"]] = None
    return out


def _ec_shard_locations(env: CommandEnv, vid: int) -> dict[str, list[int]]:
    from ..topology import fetch_ec_shard_locations
    return fetch_ec_shard_locations(env.master, vid)


def _all_node_urls(env: CommandEnv) -> list[str]:
    r = master_json(env.master, "GET", "/cluster/status", timeout=30)
    return r.get("dataNodes", [])


def _select_volumes(env: CommandEnv, opts: dict) -> list[int]:
    """command_ec_encode.go:375 collectVolumeIdsForEcEncode (simplified:
    explicit -volumeId, or all volumes of -collection)."""
    if "volumeId" in opts:
        return [int(opts["volumeId"])]
    collection = opts.get("collection")
    if collection is None:
        return []
    from ..topology import iter_volume_list_volumes
    vids = []
    for _node, v in iter_volume_list_volumes(env.volume_list()):
        if v.get("collection", "") == (
                "" if collection == "ALL" else collection):
            vids.append(v["id"])
    return sorted(set(vids))


def run_command(env: CommandEnv, line: str) -> str:
    parts = line.split()
    if not parts:
        return ""
    name, args = parts[0], parts[1:]
    fn = COMMANDS.get(name)
    if fn is None:
        raise ValueError(
            f"unknown command {name!r}; known: {sorted(COMMANDS)}")
    # shell ingress of the deadline plane (util/deadline): with
    # SEAWEEDFS_TPU_DEADLINE_DEFAULT_MS configured every command runs
    # under a budget that its outbound hops forward and derive their
    # timeouts from — a wedged peer fails an operator's command fast
    # instead of parking the shell.  Unconfigured: nothing is bound.
    from ..util import deadline as _dl
    budget = _dl.default_budget()
    if budget > 0:
        with _dl.scope(budget):
            return fn(env, args)
    return fn(env, args)


def _volume_meta(env: CommandEnv, vid: int) -> "dict | None":
    """Collection etc. from the master volume list (the lookup
    endpoint returns urls only)."""
    from ..topology import iter_volume_list_volumes
    for _node, v in iter_volume_list_volumes(env.volume_list()):
        if v["id"] == vid:
            return v
    return None


@command("volume.copy")
def cmd_volume_copy(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_copy.go: replicate one volume to a target
    server — freeze-copy-mount via the shared _move_volume pipeline
    (unfenced copies of live volumes tear .dat/.idx)."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    vid = int(opts["volumeId"])
    dst = opts["target"]
    locs = env.volume_locations(vid)
    if not locs:
        return f"volume {vid} not found"
    src = opts.get("source", locs[0]["url"])
    meta = _volume_meta(env, vid) or {}
    if any(loc["url"] == dst for loc in locs):
        return f"volume {vid} already on {dst}"
    _move_volume(env, vid, meta.get("collection", ""), src, dst,
                 delete_source=False)
    return f"copied volume {vid}: {src} -> {dst}"


@command("volume.move")
def cmd_volume_move(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_move.go: freeze, copy to target, mount,
    delete at the source (the shared _move_volume pipeline — data is
    readable at every step)."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    vid = int(opts["volumeId"])
    src = opts["source"]
    dst = opts["target"]
    if src == dst:
        return "source and target are the same server"
    locs = env.volume_locations(vid)
    if not any(loc["url"] == src for loc in locs):
        return f"volume {vid} is not on {src}"
    meta = _volume_meta(env, vid) or {}
    collection = meta.get("collection", "")
    if any(loc["url"] == dst for loc in locs):
        # target already holds a replica: deleting src would still
        # need its copy verified — just drop the source replica
        _must(http_json("POST", f"{src}/admin/delete_volume",
                        {"volumeId": vid,
                         "collection": collection}, timeout=30),
              f"delete on {src}")
    else:
        _move_volume(env, vid, collection, src, dst,
                     delete_source=True)
    return f"moved volume {vid}: {src} -> {dst}"


@command("volume.grow")
def cmd_volume_grow(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_grow.go / master VolumeGrow."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    r = master_json(env.master, "POST", "/vol/grow", {
        "collection": opts.get("collection", ""),
        "replication": opts.get("replication", ""),
        "count": int(opts.get("count", 1))}, timeout=30)
    if "volumeIds" not in r:
        return f"grow failed: {r}"
    return f"grew volumes: {r['volumeIds']}"


@command("collection.list")
def cmd_collection_list(env: CommandEnv, args: list[str]) -> str:
    """shell/command_collection_list.go: collections + volume counts
    from the master's volume list."""
    from ..topology import iter_volume_list_volumes
    vols: dict[str, set] = {}
    for _node, v in iter_volume_list_volumes(env.volume_list()):
        # count DISTINCT volumes, not replica pairs
        vols.setdefault(v.get("collection", ""), set()).add(v["id"])
    return "\n".join(
        f"{name or '(default)'}: {len(ids)} volumes"
        for name, ids in sorted(vols.items())) or "no volumes"


@command("collection.delete")
def cmd_collection_delete(env: CommandEnv, args: list[str]) -> str:
    """shell/command_collection_delete.go: delete every volume of a
    collection on every server (requires the lock + an explicit
    -force)."""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    name = opts.get("collection", "")
    if not name:
        return "need -collection=<name>"
    if "force" not in opts:
        return ("this deletes EVERY volume of the collection; "
                "re-run with -force")
    from ..topology import iter_volume_list_volumes
    deleted = []
    vl = env.volume_list()
    for node, v in list(iter_volume_list_volumes(vl)):
        if v.get("collection", "") != name:
            continue
        _must(http_json("POST", f"{node['url']}/admin/delete_volume",
                        {"volumeId": v["id"],
                         "collection": name}, timeout=30),
              f"delete {v['id']} on {node['url']}")
        deleted.append(v["id"])
    # EC volumes of the collection too (the Go analog deletes both)
    ec_deleted = []
    for dc in vl.get("dataCenters", {}).values():
        for rack in dc.get("racks", {}).values():
            for node in rack.get("nodes", []):
                for e in node.get("ecShards", []):
                    if e.get("collection", "") != name:
                        continue
                    shard_ids = [i for i in range(32)
                                 if e.get("shardBits", 0) >> i & 1]
                    _must(http_json(
                        "POST",
                        f"{node['url']}/admin/ec/delete_shards",
                        {"volumeId": e["volumeId"],
                         "collection": name,
                         "shardIds": shard_ids}, timeout=30),
                        f"delete ec {e['volumeId']} on "
                        f"{node['url']}")
                    ec_deleted.append(e["volumeId"])
    out = f"deleted collection {name!r}: volumes {sorted(set(deleted))}"
    if ec_deleted:
        out += f", ec volumes {sorted(set(ec_deleted))}"
    return out


@command("volume.merge")
def cmd_volume_merge(env: CommandEnv, args: list[str]) -> str:
    """shell/command_volume_merge.go (-volumeId=N): merge DIVERGED
    replicas in append-timestamp order into one copy, then replace
    every replica with it.

    1) mark all replicas readonly (remembering prior state)
    2) merge on the first replica, pulling peers' .dat files
       (AppendAtNs-ordered union, newest write/tombstone wins)
    3) re-copy the merged volume over the other replicas
    4) restore writable state"""
    env.confirm_is_locked()
    opts = _parse_flags(args)
    if "volumeId" not in opts:
        return "usage: volume.merge -volumeId=N"
    vid = int(opts["volumeId"])
    urls = _volumes_by_id(env).get(vid)
    if not urls:
        raise RuntimeError(f"volume {vid} not found")
    meta = _volume_meta(env, vid) or {}
    collection = meta.get("collection", "")
    was_writable = not meta.get("readOnly", False)
    primary, others = urls[0], urls[1:]
    for url in urls:
        _must(http_json("POST", f"{url}/admin/set_readonly",
                        {"volumeId": vid, "readOnly": True}, timeout=30),
              f"set readonly on {url}")
    try:
        r = _must(http_json(
            "POST", f"{primary}/admin/volume/merge",
            {"volumeId": vid, "collection": collection,
             "peers": others}, timeout=30), f"merge on {primary}")
        # replace the other replicas with the merged copy
        for url in others:
            _must(http_json("POST", f"{url}/admin/delete_volume",
                            {"volumeId": vid}, timeout=30),
                  f"drop stale replica on {url}")
            _copy_volume_files(env, vid, collection, primary, url)
            _must(http_json("POST", f"{url}/admin/mount_volume",
                            {"volumeId": vid,
                             "collection": collection}, timeout=30),
                  f"mount merged on {url}")
            _must(http_json("POST", f"{url}/admin/set_readonly",
                            {"volumeId": vid, "readOnly": True}, timeout=30),
                  f"re-freeze merged on {url}")
    finally:
        if was_writable:
            for url in urls:
                try:
                    http_json("POST", f"{url}/admin/set_readonly",
                              {"volumeId": vid, "readOnly": False}, timeout=30)
                except OSError:
                    pass
    return (f"volume {vid}: merged {len(urls)} replicas "
            f"({r['mergedNeedles']} live needles, "
            f"{r['datBytes']} bytes) on {primary}; "
            f"replaced {len(others)} peer copies")
