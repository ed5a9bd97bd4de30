"""The shard push goes to its targets at once (ISSUE 32): one stream a
target that holds shards, each target's files one at a time in their
order, nothing mounted unless every file reached every target, and a
push that fails stops the other streams before their next file."""

import re
import threading
import time

import pytest
from test_live_cluster import fill_volume, run_job, settle, spread

from seaweedfs_tpu import faults, operation
from seaweedfs_tpu.plugin import AdminServer, PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler
from seaweedfs_tpu.plugin.handlers import erasure_coding as ec_handler
from seaweedfs_tpu.server.httpd import http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.erasure_coding.ec_context import to_ext


def start_cluster(tmp, n_servers: int):
    master = MasterServer(volume_size_limit_mb=1).start()
    servers = []
    for i in range(n_servers):
        d = tmp / f"vol{i}"
        d.mkdir()
        servers.append(VolumeServer([str(d)], master.url,
                                    pulse_seconds=0.3).start())
    admin = AdminServer(master.url, detection_interval=3600).start()
    worker = PluginWorker(
        admin.url, master.url, str(tmp / "worker"),
        handlers=[EcEncodeHandler(fullness_ratio=0.5)],
        poll_wait=0.5).start()
    time.sleep(0.6)

    def stop():
        worker.stop()
        admin.stop()
        for vs in servers:
            vs.stop()
        master.stop()
    return (master, servers, admin), stop


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    roles, stop = start_cluster(tmp_path_factory.mktemp("streams3"), 3)
    yield roles
    stop()


@pytest.fixture(scope="module")
def one_server(tmp_path_factory):
    roles, stop = start_cluster(tmp_path_factory.mktemp("streams1"), 1)
    yield roles
    stop()


def spans_of(admin, detail) -> "dict[str, list[dict]]":
    """The job's spans by name, each list in order of start (every
    role of these clusters records into this process's ring)."""
    got = http_json("GET", f"{admin.url}/debug/traces?request_id="
                    f"{detail['requestId']}")["spans"]
    by_name: "dict[str, list[dict]]" = {}
    for s in sorted(got, key=lambda s: s["start"]):
        by_name.setdefault(s["name"], []).append(s)
    return by_name


def end_of(span: dict) -> float:
    return span["start"] + span["durationMs"] / 1e3


@pytest.fixture(scope="module")
def plain_job(cluster):
    """(targets in the master's order, the spans by name) of one job
    on the cluster of three."""
    master, _servers, admin = cluster
    vid, _ = fill_volume(master, "plain")
    targets = http_json("GET", f"{master.url}/cluster/status")["dataNodes"]
    detail = run_job(admin, vid, "plain")
    assert detail["status"] == "done", detail
    return targets, spans_of(admin, detail)


def test_the_three_pushers_are_inside_push_file_at_once(
        cluster, monkeypatch):
    """A barrier of three in front of each target's first file: only
    three concurrent callers pass it."""
    master, _servers, admin = cluster
    vid, _ = fill_volume(master, "atonce")
    barrier = threading.Barrier(3)
    lock = threading.Lock()
    met, threads = set(), set()
    real = ec_handler._push_file

    def push_file(target, *args):
        with lock:
            first = target not in met
            met.add(target)
            threads.add(threading.get_ident())
        if first:
            barrier.wait(timeout=10)
        return real(target, *args)

    monkeypatch.setattr(ec_handler, "_push_file", push_file)
    detail = run_job(admin, vid, "atonce")
    assert detail["status"] == "done", detail
    assert len(met) == len(threads) == 3 and not barrier.broken
    assert "distributed to 3 servers" in detail["message"]
    assert settle(master, vid, [5, 5, 4]) == [5, 5, 4]


def test_a_targets_files_arrive_one_at_a_time_in_their_order(plain_job):
    targets, spans = plain_job
    assert len(spans["ec.push"]) == 14 + 2 * 3
    for i, target in enumerate(targets):
        mine = [s for s in spans["ec.push"]
                if s["attrs"]["target"] == target]
        assert [s["attrs"]["ext"] for s in mine] == \
            [to_ext(sid) for sid in range(i, 14, 3)] + [".ecx", ".vif"]
        for a, b in zip(mine, mine[1:]):
            assert end_of(a) <= b["start"] + 1e-3, (target, a, b)
    # nothing is mounted before the last file of the last target is in
    last_push = max(end_of(s) for s in spans["ec.push"])
    assert [s["attrs"]["target"] for s in spans["ec.mount"]] == targets
    assert all(s["start"] >= last_push - 1e-3 for s in spans["ec.mount"])


def test_distribute_says_its_streams_and_their_seconds(plain_job):
    _targets, spans = plain_job
    (dist,) = spans["ec.distribute"]
    assert dist["attrs"]["streams"] == dist["attrs"]["servers"] == 3
    assert 0 < dist["attrs"]["pushSeconds"] <= dist["durationMs"] / 1e3
    # from the first push's start to the last one's end
    pushes = spans["ec.push"]
    assert dist["attrs"]["pushSeconds"] >= \
        max(end_of(s) for s in pushes) - pushes[0]["start"] - 1e-3
    assert all(s["parentId"] == dist["spanId"]
               for s in pushes + spans["ec.mount"])


def test_a_failed_push_stops_the_others_and_nothing_is_mounted(
        cluster, monkeypatch):
    """The first stream's first chunk fails at its receiver; the other
    two are held until then, send the file they had in hand and, having
    seen the failure, no other."""
    master, servers, admin = cluster
    vid, blobs = fill_volume(master, "failed")
    lock = threading.Lock()
    raised = threading.Event()
    calls: "list[tuple[str, str]]" = []
    real = ec_handler._push_file

    def push_file(target, vid_, collection, ext, path):
        with lock:
            first = not calls
            calls.append((target, ext))
        if not first:
            assert raised.wait(timeout=10)
            time.sleep(0.3)      # the failing stream has set the flag
            return real(target, vid_, collection, ext, path)
        try:
            return real(target, vid_, collection, ext, path)
        except RuntimeError:
            raised.set()
            raise

    monkeypatch.setattr(ec_handler, "_push_file", push_file)
    faults.arm("volume.receive_file.recv", "error", n=1)
    try:
        detail = run_job(admin, vid, "failed")
    finally:
        faults.reset()
    assert detail["status"] == "failed", detail
    # the failure names its ext and its target
    (target, ext) = calls[0]
    assert re.fullmatch(r"\.ec0[012]", ext)
    assert f"push {ext} to {target}: 500" in detail["message"]
    # one file a stream: the one that failed, the two in hand
    assert len(calls) == 3 and len({t for t, _ in calls}) == 3
    spans = spans_of(admin, detail)
    assert len(spans["ec.push"]) == 3 and "ec.mount" not in spans
    (dist,) = spans["ec.distribute"]
    assert dist.get("error") and "servers" not in dist["attrs"]
    # nothing was placed, and the volume is as it was: there, writable
    assert spread(master, vid) == []
    for vs in servers:
        for loc in vs.store.locations:
            assert vid not in loc.ec_volumes
    holders = [vs for vs in servers if vs.store.find_volume(vid)]
    assert len(holders) == 1
    assert holders[0].store.find_volume(vid).read_only is False
    fid, data = next(iter(blobs.items()))
    assert operation.read(master.url, fid) == data


def test_a_one_server_cluster_pushes_one_stream_and_places_as_before(
        one_server):
    master, (vs,), admin = one_server
    vid, _ = fill_volume(master, "alone")
    detail = run_job(admin, vid, "alone")
    assert detail["status"] == "done", detail
    assert "distributed to 1 servers" in detail["message"]
    spans = spans_of(admin, detail)
    (dist,) = spans["ec.distribute"]
    assert dist["attrs"]["streams"] == dist["attrs"]["servers"] == \
        dist["attrs"]["serversAtStart"] == 1
    assert [(s["attrs"]["target"], s["attrs"]["ext"])
            for s in spans["ec.push"]] == \
        [(vs.url, to_ext(sid)) for sid in range(14)] + \
        [(vs.url, ".ecx"), (vs.url, ".vif")]
    assert len(spans["ec.mount"]) == 1
    assert settle(master, vid, [14]) == [14]
