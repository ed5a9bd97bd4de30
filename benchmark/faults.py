"""Ways to break what the timed path produces, for the control and the
tests: each must make `correct` come out false.  A fault is a `Hooks`
whose callables run inside `benchmark.run.run_cell`."""

from __future__ import annotations

import os

from benchmark.run import Hooks


def _flip(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0x5A]))


def flip_parity_byte() -> Hooks:
    """One byte of one parity shard of the window's first job (of its
    last volume, where it has several), altered where the job left it
    (an answer altered where it is produced)."""
    def before_verify(cluster, state):
        vol = next(j["vols"][-1] for j in state["jobs"] if j["ok"])
        paths = cluster.shard_paths(vol)
        last = max(paths)
        _flip(paths[last][0], os.path.getsize(paths[last][0]) // 2)
    return Hooks(before_verify=before_verify)


def lose_shard() -> Hooks:
    """One shard of the window's last job unmounted and deleted: the
    configuration's placement guarantee broken."""
    def before_verify(cluster, state):
        from seaweedfs_tpu.server.httpd import http_json
        vol = [j["vols"][-1] for j in state["jobs"] if j["ok"]][-1]
        paths = cluster.shard_paths(vol)
        holder = cluster.vol_urls[cluster.vol_dirs.index(
            os.path.dirname(paths[0][0]))]
        http_json("POST", f"{holder}/admin/ec/delete_shards",
                  {"volumeId": vol["vid"], "collection": vol["collection"],
                   "shardIds": [0]})
    return Hooks(before_verify=before_verify)


def alter_read_set() -> Hooks:
    """A byte flipped in every data shard of the read set before the
    window opens, at 63 places each (some hundreds of the read set's
    objects): bodies served from there are no longer the seeded
    objects."""
    def before_window(cluster, state):
        k = cluster.cfg["data_shards"]
        for vol in state["read_vols"]:
            paths = cluster.shard_paths(vol)
            for sid in range(k):
                size = os.path.getsize(paths[sid][0])
                for part in range(1, 64):
                    _flip(paths[sid][0], size * part // 64)
    return Hooks(before_window=before_window)


def starve_chain(keep: int = 1) -> Hooks:
    """All but `keep` of the window's volumes taken away before it
    opens: the background (or the foreground) the cell states is not
    there for the rest of the window.  (With one kept, the window has
    to outlast a job; the tests, whose jobs a loaded sandbox stretches,
    keep none.)"""
    def before_window(_cluster, state):
        del state["job_vols"][keep:]
    return Hooks(before_window=before_window)


FAULTS = {"flip_parity_byte": flip_parity_byte, "lose_shard": lose_shard,
          "alter_read_set": alter_read_set, "starve_chain": starve_chain}
