"""Topology tree + volume layouts + EC shard registry.

Mirrors the behavior of weed/topology/topology.go (Topology,
:322 PickForWrite), volume_layout.go (writable lists per
(collection, replication, ttl)), data_center.go/rack.go/data_node.go
(the tree), and topology_ec.go:124 RegisterEcShards / :153
LookupEcShards.  The Go pointer-tree with per-node locks collapses to
plain dataclasses under one topology lock (single master process).
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field

from .. import stats
from ..storage.replica_placement import ReplicaPlacement
from ..util import wlog


@dataclass
class VolumeInfo:
    id: int
    collection: str = ""
    size: int = 0
    file_count: int = 0
    delete_count: int = 0
    deleted_byte_count: int = 0
    read_only: bool = False
    replica_placement: int = 0
    ttl: int = 0
    version: int = 3


@dataclass
class EcShardInfo:
    volume_id: int
    collection: str = ""
    shard_bits: int = 0  # bitmask of shard ids present on the node
    data_shards: int = 10
    parity_shards: int = 4

    @property
    def shard_ids(self) -> list[int]:
        return [s for s in range(32) if self.shard_bits & (1 << s)]


@dataclass
class DataNodeInfo:
    """One volume server (weed/topology/data_node.go)."""

    url: str                  # ip:port — the node's identity
    public_url: str = ""
    data_center: str = "DefaultDataCenter"
    rack: str = "DefaultRack"
    max_volume_count: int = 8
    volumes: dict[int, VolumeInfo] = field(default_factory=dict)
    ec_shards: dict[int, EcShardInfo] = field(default_factory=dict)
    last_seen: float = 0.0
    # what the last look at this node's liveness found; a change is
    # counted (Topology._note_liveness)
    held_alive: bool = True

    @property
    def volume_count(self) -> int:
        return len(self.volumes)

    @property
    def free_space(self) -> int:
        return self.max_volume_count - len(self.volumes)


class Topology:
    """weed/topology/topology.go:76."""

    def __init__(self, volume_size_limit: int = 8 * 1024 * 1024 * 1024,
                 pulse_seconds: float = 5.0):
        self.lock = threading.RLock()
        self.nodes: dict[str, DataNodeInfo] = {}
        self.volume_size_limit = volume_size_limit
        self.pulse_seconds = pulse_seconds
        self._max_volume_id = 0
        import itertools
        self._pick_rr = itertools.count()
        # when the master's own clock last ticked (MasterServer ticks a
        # few times a pulse); None where nobody ticks, and liveness is
        # then by the heartbeats' ages alone
        self._ticked: float | None = None

    # -- heartbeat registration (topology.go RegisterVolumeLayout etc) ----

    def register_heartbeat(self, hb: dict) -> None:
        url = f"{hb['ip']}:{hb['port']}"
        with self.lock:
            node = self.nodes.get(url)
            if node is None:
                node = DataNodeInfo(url=url)
                self.nodes[url] = node
            else:
                # a node that fell silent and is back: both halves on
                # the record, whether or not anyone asked meanwhile
                self._note_liveness(
                    node, node.last_seen >= self._liveness_deadline())
                self._note_liveness(node, True)
            node.public_url = hb.get("publicUrl", url)
            node.data_center = hb.get("dataCenter") or node.data_center
            node.rack = hb.get("rack") or node.rack
            node.max_volume_count = hb.get("maxVolumeCount",
                                           node.max_volume_count)
            node.last_seen = time.monotonic()
            node.volumes = {
                v["id"]: VolumeInfo(
                    id=v["id"], collection=v.get("collection", ""),
                    size=v.get("size", 0),
                    file_count=v.get("fileCount", 0),
                    delete_count=v.get("deleteCount", 0),
                    deleted_byte_count=v.get("deletedByteCount", 0),
                    read_only=v.get("readOnly", False),
                    replica_placement=v.get("replicaPlacement", 0),
                    ttl=v.get("ttl", 0), version=v.get("version", 3))
                for v in hb.get("volumes", [])}
            node.ec_shards = {
                e["id"]: EcShardInfo(
                    volume_id=e["id"], collection=e.get("collection", ""),
                    shard_bits=e.get("ecIndexBits", 0),
                    data_shards=e.get("dataShards", 10),
                    parity_shards=e.get("parityShards", 4))
                for e in hb.get("ecShards", [])}
            for vid in node.volumes:
                self._max_volume_id = max(self._max_volume_id, vid)
            for vid in node.ec_shards:
                self._max_volume_id = max(self._max_volume_id, vid)

    def tick(self) -> None:
        """The master's own clock, a few times a pulse."""
        with self.lock:
            self._forgive_own_stall()
            self._ticked = time.monotonic()

    def _forgive_own_stall(self) -> None:
        """A master that did not run for a pulse or more (its process
        or its whole machine stood still: 4.5-9 s when a TPU's owner
        initialises beside it, PERF.md 7) heard no heartbeat meanwhile,
        and the first look after it would find every server three
        pulses unheard.  The time it did not listen is not held against
        them: three pulses are three pulses of listening.  Called before
        liveness is judged."""
        with self.lock:     # re-entrant: the callers hold it already
            if self._ticked is None:
                return
            now = time.monotonic()
            gap = now - self._ticked
            if gap <= self.pulse_seconds:
                return
            self._ticked = now
            for n in self.nodes.values():
                if n.last_seen > 0:
                    n.last_seen = min(now, n.last_seen + gap)
        stats.PROCESS.counter_add(
            "master_own_stall_seconds_total", gap,
            help_text="seconds the master itself did not run (a pulse "
                      "or more at a time), not held against the "
                      "volume servers' heartbeats")
        wlog.warning("the master did not run for %.1fs: not held against "
                     "%d volume servers", gap, len(self.nodes),
                     component="topology")

    def _liveness_deadline(self) -> float:
        # heartbeat ages on the monotonic clock (SWFS011): an NTP step
        # backwards would otherwise declare the whole fleet dead, and
        # a step forward would immortalize nodes that stopped pulsing
        self._forgive_own_stall()
        return time.monotonic() - 3 * self.pulse_seconds

    def _note_liveness(self, node: DataNodeInfo, alive: bool) -> None:
        """Counts a node's passing from alive to dead or back
        (`master_node_transitions_total{to}`); called under `lock`."""
        if alive != node.held_alive:
            node.held_alive = alive
            wlog.warning(
                "volume server %s %s: last heard %.1fs ago (%.0fs pulse)",
                node.url, "is back" if alive else "held dead",
                time.monotonic() - node.last_seen, self.pulse_seconds,
                component="topology")
            stats.PROCESS.counter_add(
                "master_node_transitions_total", 1.0,
                help_text="volume servers the master let go of (three "
                          "pulses unheard) or took back",
                to="alive" if alive else "dead")

    def alive_nodes(self) -> list[DataNodeInfo]:
        with self.lock:
            deadline = self._liveness_deadline()
            alive = []
            for n in self.nodes.values():
                self._note_liveness(n, n.last_seen >= deadline)
                if n.held_alive:
                    alive.append(n)
            return alive

    def silent_nodes(self) -> dict[str, float]:
        """{url: seconds since the master let go of it} for every
        server it has heard and no longer holds alive, the ones marked
        dead on sight apart.  Whoever promises a placement (an EC job)
        reads it to tell a cluster of two from a cluster of three a
        beat of which is late."""
        with self.lock:
            deadline = self._liveness_deadline()
            return {n.url: deadline - n.last_seen
                    for n in self.nodes.values()
                    if 0 < n.last_seen < deadline}

    def mark_dead(self, url: str) -> None:
        """Immediately expire a node observed unreachable (the analog of
        topology_event_handling.go UnRegisterDataNode on a broken
        heartbeat stream) — don't wait out the missed-pulse deadline."""
        with self.lock:
            n = self.nodes.get(url)
            if n is not None:
                n.last_seen = 0.0

    # -- volume id assignment ---------------------------------------------

    def next_volume_id(self) -> int:
        with self.lock:
            self._max_volume_id += 1
            return self._max_volume_id

    # -- lookups (master_grpc_server_volume.go LookupVolume,
    #    topology_ec.go:153 LookupEcShards) -------------------------------

    def lookup(self, vid: int, collection: str | None = None) -> list[dict]:
        """All locations serving volume vid (normal or EC)."""
        out = []
        with self.lock:
            for node in self.nodes.values():
                v = node.volumes.get(vid)
                if v is not None and \
                        (collection is None or v.collection == collection):
                    out.append({"url": node.url,
                                "publicUrl": node.public_url})
            if not out:
                for node in self.nodes.values():
                    e = node.ec_shards.get(vid)
                    if e is not None:
                        out.append({"url": node.url,
                                    "publicUrl": node.public_url,
                                    "shardBits": e.shard_bits})
        return out

    def lookup_ec_shards(self, vid: int) -> dict[str, list[int]]:
        """url -> shard ids (topology_ec.go:153)."""
        out: dict[str, list[int]] = {}
        with self.lock:
            for node in self.nodes.values():
                e = node.ec_shards.get(vid)
                if e is not None:
                    out[node.url] = e.shard_ids
        return out

    # -- write placement (topology.go:322 PickForWrite +
    #    volume_layout.go writable selection) ----------------------------

    def writable_volumes(self, collection: str = "", replication: str = "",
                         ttl_u32: int = 0) -> list[tuple[int, list[DataNodeInfo]]]:
        """(vid, nodes) groups satisfying (collection, rp, ttl), not
        read-only and under the size limit, with a full replica set."""
        rp = ReplicaPlacement.from_string(replication or "000")
        want_copies = rp.copy_count()
        by_vid: dict[int, list[DataNodeInfo]] = {}
        with self.lock:
            deadline = self._liveness_deadline()
            for node in self.nodes.values():
                if node.last_seen < deadline:
                    # a disconnected node's volumes leave the writable
                    # set (volume_layout.go SetVolumeUnavailable)
                    continue
                for vid, v in node.volumes.items():
                    if v.collection != collection:
                        continue
                    if replication and v.replica_placement != rp.byte():
                        continue
                    if v.ttl != ttl_u32:
                        continue
                    if v.read_only or v.size >= self.volume_size_limit:
                        continue
                    by_vid.setdefault(vid, []).append(node)
        return [(vid, nodes) for vid, nodes in by_vid.items()
                if len(nodes) >= want_copies]

    def pick_for_write(self, collection: str = "", replication: str = "",
                       ttl_u32: int = 0) -> tuple[int, list[DataNodeInfo]]:
        candidates = self.writable_volumes(collection, replication, ttl_u32)
        if not candidates:
            raise LookupError("no writable volumes")
        # round-robin, not random.choice: with clients batching fids
        # (assign?count=N windows) each assign pins a volume for many
        # writes, and random selection leaves streaks where several
        # gateways hammer one volume while its siblings idle — strict
        # rotation keeps the per-volume write load even
        candidates.sort(key=lambda c: c[0])
        return candidates[next(self._pick_rr) % len(candidates)]

    # -- growth (volume_growth.go) ----------------------------------------

    def plan_growth(self, replication: str = "",
                    exclude: set[str] | None = None
                    ) -> list[DataNodeInfo]:
        """Pick target nodes for a new volume's replica set honoring the
        xyz placement (volume_growth.go findEmptySlotsForOneVolume,
        simplified: grouped by DC then rack with free-slot weighting).
        `exclude` drops nodes that just refused an allocation."""
        rp = ReplicaPlacement.from_string(replication or "000")
        alive = [n for n in self.alive_nodes()
                 if n.free_space > 0 and n.url not in (exclude or ())]
        if not alive:
            raise LookupError("no free volume slots in cluster")
        main = max(alive, key=lambda n: (n.free_space, random.random()))
        picked = [main]

        def pick(pool, count, err):
            chosen = []
            pool = [n for n in pool if n not in picked and n.free_space > 0]
            if len(pool) < count:
                raise LookupError(err)
            pool.sort(key=lambda n: (-n.free_space, random.random()))
            chosen.extend(pool[:count])
            return chosen

        picked += pick([n for n in alive
                        if n.data_center == main.data_center
                        and n.rack == main.rack],
                       rp.same_rack_count,
                       "not enough same-rack nodes")
        picked += pick([n for n in alive
                        if n.data_center == main.data_center
                        and n.rack != main.rack],
                       rp.diff_rack_count,
                       "not enough cross-rack nodes")
        picked += pick([n for n in alive
                        if n.data_center != main.data_center],
                       rp.diff_data_center_count,
                       "not enough cross-DC nodes")
        return picked

    # -- full cluster snapshot (master_grpc_server_volume.go VolumeList) --

    def to_volume_list(self) -> dict:
        with self.lock:
            dcs: dict[str, dict] = {}
            for node in self.nodes.values():
                dc = dcs.setdefault(node.data_center, {"racks": {}})
                rack = dc["racks"].setdefault(node.rack, {"nodes": []})
                rack["nodes"].append({
                    "url": node.url,
                    "publicUrl": node.public_url,
                    "maxVolumeCount": node.max_volume_count,
                    # camelCase field names: same wire contract as the
                    # heartbeat messages (VolumeInformationMessage)
                    "volumes": [{
                        "id": v.id,
                        "collection": v.collection,
                        "size": v.size,
                        "fileCount": v.file_count,
                        "deleteCount": v.delete_count,
                        "deletedByteCount": v.deleted_byte_count,
                        "readOnly": v.read_only,
                        "replicaPlacement": v.replica_placement,
                        "ttl": v.ttl,
                        "version": v.version,
                    } for v in node.volumes.values()],
                    "ecShards": [{
                        "volumeId": e.volume_id,
                        "collection": e.collection,
                        "shardBits": e.shard_bits,
                        "dataShards": e.data_shards,
                        "parityShards": e.parity_shards,
                    } for e in node.ec_shards.values()],
                })
            return {"maxVolumeId": self._max_volume_id,
                    "dataCenters": dcs}
