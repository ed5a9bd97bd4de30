"""Store: the per-volume-server storage manager
(weed/storage/store.go, disk_location.go).

Owns one or more disk locations (one per -dir), loads/creates volumes
and mounted EC shards, routes needle reads/writes by volume id, and
assembles the heartbeat snapshot the master consumes.
"""

from __future__ import annotations

import glob
import os
import re
import threading

from . import types
from .erasure_coding import ECContext, EcVolume
from .erasure_coding.ec_context import to_ext
from .needle import Needle
from .replica_placement import ReplicaPlacement
from .ttl import EMPTY_TTL, read_ttl
from .volume import Volume

_VOL_RE = re.compile(r"^(?:(?P<col>.+)_)?(?P<vid>\d+)\.dat$")
_VIF_RE = re.compile(r"^(?:(?P<col>.+)_)?(?P<vid>\d+)\.vif$")
_EC_RE = re.compile(r"^(?:(?P<col>.+)_)?(?P<vid>\d+)\.ec00$")


def _vif_is_remote(vif_path: str) -> bool:
    """True when the .vif records a remote-tiered .dat
    (storage/volume_tier.go: files[] carries the backend copy)."""
    from .volume_info import maybe_load_volume_info
    try:
        vi = maybe_load_volume_info(vif_path)
    except ValueError:
        return False
    return bool(vi and vi.files)


# process-wide mmap read cap in MB (backend/memory_map role, the
# volume server's -memoryMapMaxSizeMb flag); 0 disables.  Set by the
# CLI before Store construction.
MMAP_READ_MB = 0


class DiskLocation:
    """One storage directory (weed/storage/disk_location.go)."""

    def __init__(self, directory: str, max_volume_count: int = 8,
                 index_directory: str | None = None,
                 fsync: bool = False):
        self.directory = os.path.abspath(directory)
        self.index_directory = index_directory or self.directory
        self.max_volume_count = max_volume_count
        self.fsync = fsync
        self.volumes: dict[int, Volume] = {}
        self.ec_volumes: dict[int, EcVolume] = {}
        os.makedirs(self.directory, exist_ok=True)

    def load_existing(self) -> None:
        for path in glob.glob(os.path.join(self.directory, "*.dat")):
            m = _VOL_RE.match(os.path.basename(path))
            if not m:
                continue
            vid = int(m.group("vid"))
            self.volumes[vid] = Volume(
                self.directory, vid, collection=m.group("col") or "",
                mmap_read_mb=MMAP_READ_MB, fsync=self.fsync)
        # tiered volumes have no local .dat; their .vif names the
        # remote copy (volume_tier.go)
        for path in glob.glob(os.path.join(self.directory, "*.vif")):
            m = _VIF_RE.match(os.path.basename(path))
            if not m:
                continue
            vid = int(m.group("vid"))
            if vid in self.volumes or not _vif_is_remote(path):
                continue
            try:
                self.volumes[vid] = Volume(
                    self.directory, vid,
                    collection=m.group("col") or "")   # remote: no mmap
            except KeyError as e:
                # backend not configured on this server: the tiered
                # volume is unavailable, but one bad .vif must not
                # abort startup and take every healthy volume with it
                import sys
                print(f"volume {vid}: cannot open tiered volume: {e} "
                      f"(start with -tierBackend)", file=sys.stderr)
        for path in glob.glob(os.path.join(self.directory, "*.ec00")):
            m = _EC_RE.match(os.path.basename(path))
            if not m:
                continue
            vid = int(m.group("vid"))
            self.ec_volumes[vid] = EcVolume(
                self.directory, vid, collection=m.group("col") or "")


class Store:
    """storage/store.go:88 NewStore."""

    def __init__(self, directories: list[str], ip: str = "localhost",
                 port: int = 0, public_url: str = "",
                 fsync: bool = False):
        self.ip = ip
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        # -fsync: every volume's group-commit barrier also fsyncs (the
        # power-loss durability tier, one fsync per commit window)
        self.fsync = fsync
        self.locations = [DiskLocation(d, fsync=fsync)
                          for d in directories]
        self.lock = threading.RLock()
        # guards the locations' tables alone, for as long as an entry
        # takes to put in, take out or copy: `lock` is held through a
        # mount's or a delete's file I/O, and a heartbeat must wait on
        # neither (nor they on its dat_size() calls)
        self._tables = threading.Lock()
        for loc in self.locations:
            loc.load_existing()

    # -- volume lookup ----------------------------------------------------

    def find_volume(self, vid: int) -> Volume | None:
        for loc in self.locations:
            v = loc.volumes.get(vid)
            if v is not None:
                return v
        return None

    def find_ec_volume(self, vid: int) -> EcVolume | None:
        for loc in self.locations:
            ev = loc.ec_volumes.get(vid)
            if ev is not None:
                return ev
        return None

    def _location_for_new_volume(self) -> DiskLocation:
        best, slack = None, -1
        for loc in self.locations:
            s = loc.max_volume_count - len(loc.volumes)
            if s > slack:
                best, slack = loc, s
        if best is None:
            raise RuntimeError("no disk locations")
        return best

    # -- volume admin -----------------------------------------------------

    def add_volume(self, vid: int, collection: str = "",
                   replication: str = "", ttl: str = "") -> Volume:
        with self.lock:
            if self.find_volume(vid) is not None:
                raise ValueError(f"volume {vid} already exists")
            loc = self._location_for_new_volume()
            v = Volume(
                loc.directory, vid, collection=collection,
                replica_placement=ReplicaPlacement.from_string(replication),
                ttl=read_ttl(ttl) if ttl else EMPTY_TTL,
                mmap_read_mb=MMAP_READ_MB, fsync=loc.fsync)
            with self._tables:
                loc.volumes[vid] = v
            return v

    def delete_volume(self, vid: int) -> None:
        with self.lock:
            for loc in self.locations:
                with self._tables:
                    v = loc.volumes.pop(vid, None)
                if v is not None:
                    v.destroy()
                    return
            raise KeyError(f"volume {vid} not found")

    def unmount_volume(self, vid: int) -> None:
        with self.lock:
            for loc in self.locations:
                with self._tables:
                    v = loc.volumes.pop(vid, None)
                if v is not None:
                    v.close()
                    return
            raise KeyError(f"volume {vid} not found")

    def mount_volume(self, vid: int, collection: str = "") -> Volume:
        with self.lock:
            for loc in self.locations:
                name = (f"{collection}_" if collection else "") + \
                    f"{vid}"
                base = os.path.join(loc.directory, name)
                # a tiered volume has no local .dat — its .vif names
                # the remote copy (storage/volume_tier.go)
                if os.path.exists(base + ".dat") or \
                        _vif_is_remote(base + ".vif"):
                    v = Volume(loc.directory, vid,
                               collection=collection,
                               mmap_read_mb=MMAP_READ_MB,
                               fsync=loc.fsync)
                    with self._tables:
                        loc.volumes[vid] = v
                    return v
            raise KeyError(f"volume {vid} files not found")

    def set_volume_read_only(self, vid: int, read_only: bool) -> None:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        if read_only:
            # freeze means freeze: the native write plane must stop
            # acking appends the Python side would now refuse (the
            # volume server re-attaches on un-freeze via its
            # eligibility sync)
            v.detach_native()
        v.read_only = read_only

    # -- needle IO (store.go:580/:604) ------------------------------------

    def write_needle(self, vid: int, n: Needle,
                     check_cookie: bool = True) -> tuple[int, bool]:
        v = self.find_volume(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        _, size, unchanged = v.write_needle(n, check_cookie=check_cookie)
        return size, unchanged

    def read_needle(self, vid: int, needle_id: int,
                    cookie: int | None = None, ec_reader=None,
                    traced: bool = False) -> Needle:
        """store.go:604 ReadVolumeNeedle.  For EC volumes, `ec_reader`
        (server/store_ec.EcReader) enables scatter/degraded resolution;
        without it only locally-complete needles are readable;
        `traced` asks it for a span of each interval."""
        v = self.find_volume(vid)
        if v is not None:
            return v.read_needle(needle_id, cookie=cookie)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            if ec_reader is not None:
                return ec_reader.read_needle(ev, needle_id, cookie=cookie,
                                             traced=traced)
            return ev.read_needle_local(needle_id, cookie=cookie)
        raise KeyError(f"volume {vid} not found")

    def delete_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is not None:
            return v.delete_needle(n)
        ev = self.find_ec_volume(vid)
        if ev is not None:
            ev.delete_needle(n.id)
            return 0
        raise KeyError(f"volume {vid} not found")

    # -- EC shard admin (store_ec.go) -------------------------------------

    def mount_ec_shards(self, vid: int, collection: str,
                        shard_ids: list[int]) -> EcVolume:
        """Open an EcVolume over locally-present shard files
        (store_ec.go MountEcShards equivalent)."""
        with self.lock:
            ev = self.find_ec_volume(vid)
            if ev is not None:
                ev.close()
            for loc in self.locations:
                base = os.path.join(
                    loc.directory,
                    (f"{collection}_" if collection else "") + str(vid))
                if any(os.path.exists(base + to_ext(s))
                       for s in (shard_ids or range(32))):
                    ev = EcVolume(loc.directory, vid, collection=collection)
                    with self._tables:
                        loc.ec_volumes[vid] = ev
                    return ev
            raise KeyError(f"no local shards for volume {vid}")

    def unmount_ec_shards(self, vid: int,
                          shard_ids: "list[int] | None" = None) -> None:
        """Unmount EC shards of `vid`.  shard_ids=None unmounts the
        whole EC volume (internal full-unmount callers); an EMPTY list
        is a no-op, matching the reference servicer which only loops
        over req.ShardIds (volume_grpc_erasure_coding.go:463-481) — a
        reference-compatible tool sending no ids must not take every
        shard offline.  A non-empty subset closes only those shards:
        a balance unmounting one migrated shard must not take the
        node's other shards of that volume offline."""
        if shard_ids is not None and not shard_ids:
            return
        with self.lock:
            for loc in self.locations:
                ev = loc.ec_volumes.get(vid)
                if ev is None:
                    continue
                if shard_ids is not None:
                    for sid in shard_ids:
                        shard = ev.shards.pop(int(sid), None)
                        if shard is not None:
                            shard.close()
                if shard_ids is None or not ev.shards:
                    with self._tables:
                        del loc.ec_volumes[vid]
                    ev.close()
                return

    # -- heartbeat (store.go:371 CollectHeartbeat) ------------------------

    def collect_heartbeat(self) -> dict:
        """What this server holds, for the master.  The tables are
        copied under `_tables` and read from the copies, so a volume
        may be mounted or deleted while this runs; one that goes away
        between the copy and its reading is left out, as it would be a
        pulse later."""
        volumes = []
        ec_shards = []
        max_volume_count = 0
        max_file_key = 0
        with self._tables:
            tables = [(loc.max_volume_count, list(loc.volumes.items()),
                       list(loc.ec_volumes.items()))
                      for loc in self.locations]
        for max_count, vols, ec_vols in tables:
            max_volume_count += max_count
            for vid, v in vols:
                try:
                    size = v.dat_size()
                except (ValueError, OSError):
                    continue    # closed under us: deleted or unmounted
                max_file_key = max(max_file_key, v.max_file_key())
                volumes.append({
                    "id": vid,
                    "collection": v.collection,
                    "size": size,
                    "fileCount": v.file_count(),
                    "deleteCount": v.deleted_count(),
                    "deletedByteCount": v.deleted_bytes(),
                    "readOnly": v.read_only,
                    "replicaPlacement":
                        v.super_block.replica_placement.byte(),
                    "ttl": v.super_block.ttl.to_u32(),
                    "version": v.version,
                    # master.proto VolumeInformationMessage
                    # remote_storage_name (field 21) role: lets
                    # volume.tier.compact select tiered volumes
                    "remoteTiered": v.is_remote,
                })
            for vid, ev in ec_vols:
                ec_shards.append({
                    "id": vid,
                    "collection": ev.collection,
                    "ecIndexBits": sum(1 << s for s in ev.shard_ids),
                    "dataShards": ev.ctx.data_shards,
                    "parityShards": ev.ctx.parity_shards,
                })
        return {
            "ip": self.ip,
            "port": self.port,
            "publicUrl": self.public_url,
            "maxVolumeCount": max_volume_count,
            # sequencer fencing input (master.proto Heartbeat
            # max_file_key field 5): a new leader floors its file-id
            # sequence above every key any volume server has stored
            "maxFileKey": max_file_key,
            "volumes": volumes,
            "ecShards": ec_shards,
        }

    def close(self) -> None:
        for loc in self.locations:
            for v in loc.volumes.values():
                v.close()
            for ev in loc.ec_volumes.values():
                ev.close()
