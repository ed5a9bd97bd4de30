"""What encoding on a cluster that goes on serving forces (ISSUE 27): a
heartbeat that survives volumes coming and going, and its loop an
exception; a job that places its shards on the servers it started under
or not at all; the EC read path counted by where an interval came from;
the master's count of servers let go of and taken back."""

import collections
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu import operation, stats, tracing
from seaweedfs_tpu.plugin import AdminServer, PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler
from seaweedfs_tpu.plugin.handlers import erasure_coding as ec_handler
from seaweedfs_tpu.server.httpd import http_bytes, http_json
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.topology.topology import Topology


def counter(name: str, **labels) -> float:
    return stats.PROCESS.counter_value(name, **labels) or 0.0


# -- the heartbeat ------------------------------------------------------------

def _churn_volumes(store, stop):
    vid = 100
    while not stop.is_set():
        vid += 1
        store.add_volume(vid, collection="x")
        time.sleep(0.001)
        store.delete_volume(vid)


def _churn_unmounts(store, stop):
    vid = 100
    while not stop.is_set():
        vid += 1
        store.add_volume(vid, collection="x")
        store.unmount_volume(vid)
        store.mount_volume(vid, collection="x")
        store.delete_volume(vid)


def _churn_ec_mounts(store, stop):
    directory = store.locations[0].directory
    vid = 200
    while not stop.is_set():
        vid = 200 + (vid - 199) % 40
        for sid in (1, 2):
            with open(f"{directory}/e_{vid}.ec{sid:02d}", "wb") as f:
                f.write(b"\0" * 64)
        store.mount_ec_shards(vid, "e", [1, 2])
        time.sleep(0.001)
        store.unmount_ec_shards(vid, [1])
        store.unmount_ec_shards(vid)


@pytest.mark.parametrize("churn", [_churn_volumes, _churn_unmounts,
                                   _churn_ec_mounts])
def test_a_heartbeat_is_collected_while_the_tables_change(tmp_path, churn):
    """`collect_heartbeat()` reads copies of the tables: nothing a
    delete, a mount or an unmount does under it makes it raise, and
    the volumes that stay are in every beat."""
    store = Store([str(tmp_path)], ip="127.0.0.1", port=1)
    for loc in store.locations:
        loc.max_volume_count = 1000
    for vid in range(1, 4):
        store.add_volume(vid, collection=f"c{vid}")
    errors, beats, stop = collections.Counter(), [0], threading.Event()

    def beat():
        while not stop.is_set():
            try:
                hb = store.collect_heartbeat()
                assert {1, 2, 3} <= {v["id"] for v in hb["volumes"]}
                beats[0] += 1
            except Exception as e:  # noqa: BLE001 — counted, then shown
                errors[f"{type(e).__name__}: {e}"] += 1
    threads = [threading.Thread(target=beat),
               threading.Thread(target=churn, args=(store, stop))]
    for t in threads:
        t.start()
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join()
    store.close()
    assert beats[0] > 100
    assert not errors, dict(errors)


def test_a_heartbeat_does_not_wait_for_a_mount(tmp_path):
    """`Store.lock` is held through a mount's or a delete's file I/O;
    the heartbeat takes only the tables' own lock."""
    store = Store([str(tmp_path)], ip="127.0.0.1", port=1)
    store.add_volume(1)
    done = []
    with store.lock:                 # a mount in the middle of its I/O
        t = threading.Thread(
            target=lambda: done.append(store.collect_heartbeat()))
        t.start()
        t.join(timeout=5)
        assert done and [v["id"] for v in done[0]["volumes"]] == [1]
    store.close()


def test_the_heartbeat_loop_outlives_a_beat_that_raises(tmp_path):
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:1", pulse_seconds=0.02)
    calls = []

    def once():
        calls.append(time.monotonic())
        if len(calls) == 1:
            raise RuntimeError("dictionary changed size during iteration")
    vs._heartbeat_once = once
    before = counter("volume_heartbeat_errors_total", error="RuntimeError")
    timed = (stats.PROCESS.histogram_merged("volume_heartbeat_seconds")
             or {"count": 0})["count"]
    t = threading.Thread(target=vs._heartbeat_loop)
    t.start()
    deadline = time.monotonic() + 10
    while len(calls) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    vs._hb_stop.set()
    t.join(timeout=5)
    vs.store.close()
    assert len(calls) >= 3 and not t.is_alive()
    assert counter("volume_heartbeat_errors_total",
                   error="RuntimeError") == before + 1
    assert stats.PROCESS.histogram_merged(
        "volume_heartbeat_seconds")["count"] >= timed + 3


def test_a_beat_that_does_not_reach_the_master_is_counted(tmp_path):
    vs = VolumeServer([str(tmp_path)], "127.0.0.1:1", pulse_seconds=5)
    before = stats.PROCESS.counter_sum("volume_heartbeat_errors_total")
    vs._heartbeat_once()             # nobody listens on port 1
    vs.store.close()
    assert stats.PROCESS.counter_sum(
        "volume_heartbeat_errors_total") == before + 1


# -- the master's count of servers let go of and taken back --------------------

def test_the_master_counts_a_server_going_and_coming_back():
    topo = Topology(pulse_seconds=0.05)
    hb = {"ip": "127.0.0.1", "port": 7, "volumes": [], "ecShards": []}
    dead0 = counter("master_node_transitions_total", to="dead")
    alive0 = counter("master_node_transitions_total", to="alive")
    topo.register_heartbeat(hb)
    assert [n.url for n in topo.alive_nodes()] == ["127.0.0.1:7"]
    assert counter("master_node_transitions_total", to="alive") == alive0
    time.sleep(0.2)                  # four pulses unheard
    assert topo.alive_nodes() == [] and topo.alive_nodes() == []
    assert counter("master_node_transitions_total", to="dead") == dead0 + 1
    topo.register_heartbeat(hb)
    assert len(topo.alive_nodes()) == 1
    assert counter("master_node_transitions_total", to="alive") == \
        alive0 + 1
    # gone and back between two looks: both halves are on the record
    time.sleep(0.2)
    topo.register_heartbeat(hb)
    assert counter("master_node_transitions_total", to="dead") == dead0 + 2
    assert counter("master_node_transitions_total", to="alive") == \
        alive0 + 2


# -- a cluster of three, an admin and a worker ---------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("live")
    master = MasterServer(volume_size_limit_mb=1).start()
    servers = []
    for i in range(3):
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
    yield master, servers, admin
    worker.stop()
    admin.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def fill_volume(master, collection: str) -> "tuple[int, dict]":
    rng = np.random.default_rng(27)
    blobs = {}
    for _ in range(12):
        data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
        blobs[operation.submit(master.url, data,
                               collection=collection)] = data
    vids = {int(fid.split(",")[0]) for fid in blobs}
    assert len(vids) == 1, vids
    return vids.pop(), blobs


def run_job(admin, vid: int, collection: str) -> dict:
    job_id = http_json("POST", f"{admin.url}/maintenance/submit_job", {
        "jobType": "erasure_coding",
        "params": {"volumeId": vid, "collection": collection}})["jobId"]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        detail = http_json("GET",
                           f"{admin.url}/maintenance/job?id={job_id}")
        if detail["status"] in ("done", "failed"):
            return detail
        time.sleep(0.05)
    raise AssertionError(f"job {job_id} did not end: {detail}")


def spread(master, vid: int) -> "list[int]":
    r = http_json("GET", f"{master.url}/dir/ec_lookup?volumeId={vid}")
    return sorted((len(loc["shardIds"])
                   for loc in r.get("shardIdLocations", [])), reverse=True)


def settle(master, vid: int, want: "list[int]") -> "list[int]":
    deadline = time.monotonic() + 10
    while spread(master, vid) != want and time.monotonic() < deadline:
        time.sleep(0.1)
    return spread(master, vid)


class TwoOfThree:
    """`/cluster/status` as a job sees it when the master lets go of
    one server under it: whole at the job's first look, then two of
    three, until `back_after` seconds have passed since the first
    narrow answer.  It states a pulse of its own, which is what the
    job counts its wait in."""

    def __init__(self, real, back_after: "float | None", pulse: float):
        self.real, self.back_after, self.pulse = real, back_after, pulse
        self.asked, self.narrow_since = 0, None

    def __call__(self, worker):
        status = dict(self.real(worker), pulseSeconds=self.pulse)
        self.asked += 1
        if self.asked == 1:
            return status
        now = time.monotonic()
        self.narrow_since = self.narrow_since or now
        if self.back_after is None or \
                now - self.narrow_since < self.back_after:
            gone = status["dataNodes"].pop()
            status["silentDataNodes"] = {gone: now - self.narrow_since}
        return status


def distribute_spans(admin, detail) -> "list[dict]":
    got = http_json("GET", f"{admin.url}/debug/traces?request_id="
                    f"{detail['requestId']}")
    return [s for s in got["spans"] if s["name"] == "ec.distribute"]


def test_a_job_whose_third_server_comes_back_in_time_places_5_5_4(
        cluster, monkeypatch):
    master, _servers, admin = cluster
    vid, _ = fill_volume(master, "back")
    status = TwoOfThree(ec_handler._cluster_status, back_after=0.6,
                        pulse=2.0)             # it may wait 8 s
    monkeypatch.setattr(ec_handler, "_cluster_status", status)
    detail = run_job(admin, vid, "back")
    assert detail["status"] == "done", detail
    assert "distributed to 3 servers" in detail["message"]
    assert settle(master, vid, [5, 5, 4]) == [5, 5, 4]
    (sp,) = distribute_spans(admin, detail)
    assert sp["attrs"]["serversAtStart"] == sp["attrs"]["servers"] == 3
    assert 0.5 <= sp["attrs"]["waitSeconds"] < 8.0


def test_a_job_whose_third_server_stays_away_fails_and_unwinds(
        cluster, monkeypatch):
    master, servers, admin = cluster
    vid, blobs = fill_volume(master, "away")
    status = TwoOfThree(ec_handler._cluster_status, back_after=None,
                        pulse=0.2)             # it waits 0.8 s
    monkeypatch.setattr(ec_handler, "_cluster_status", status)
    t0 = time.monotonic()
    detail = run_job(admin, vid, "away")
    assert detail["status"] == "failed", detail
    assert "the master names 2 of the 3 servers the job started under" \
        in detail["message"] and "not placing on fewer" in detail["message"]
    assert "4 pulses of 0.2s" in detail["message"]
    assert status.asked >= 4          # it asked again, for a while
    assert time.monotonic() - status.narrow_since >= 0.8
    assert status.narrow_since >= t0
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
    spans = distribute_spans(admin, detail)
    assert spans, detail
    for sp in spans:                  # (a retry would fail alike)
        assert sp["attrs"]["serversAtStart"] == 3 and sp.get("error")
        assert "servers" not in sp["attrs"]


def test_a_job_on_a_whole_cluster_says_so_and_waits_for_nobody(cluster):
    master, _servers, admin = cluster
    vid, _ = fill_volume(master, "whole")
    detail = run_job(admin, vid, "whole")
    assert detail["status"] == "done", detail
    assert "distributed to 3 servers" in detail["message"]
    (sp,) = distribute_spans(admin, detail)
    assert sp["attrs"]["serversAtStart"] == sp["attrs"]["servers"] == 3
    assert "waitSeconds" not in sp["attrs"]


# -- the same against a master's own word, at another pulse --------------------

PULSE = 0.5


@pytest.fixture(scope="module")
def half_second_cluster(tmp_path_factory):
    """A master whose pulse is half a second (it lets go after 1.5 s;
    a job gives a server 2 s), three servers beating at it."""
    tmp = tmp_path_factory.mktemp("pulse")
    master = MasterServer(volume_size_limit_mb=1,
                          pulse_seconds=PULSE).start()
    servers = []
    for i in range(3):
        d = tmp / f"vol{i}"
        d.mkdir()
        servers.append(VolumeServer([str(d)], master.url,
                                    pulse_seconds=PULSE / 2).start())
    admin = AdminServer(master.url, detection_interval=3600).start()
    worker = PluginWorker(
        admin.url, master.url, str(tmp / "worker"),
        handlers=[EcEncodeHandler(fullness_ratio=0.5)],
        poll_wait=0.2).start()
    time.sleep(0.4)
    yield master, servers, admin
    worker.stop()
    admin.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def status_of(master) -> dict:
    return http_json("GET", f"{master.url}/cluster/status")


def fall_silent(master, vs, past: float = 0.0) -> None:
    """`vs` stops beating; returns once the master has let go of it
    for `past` seconds or more."""
    vs._heartbeat_once = lambda: None        # shadows the method
    deadline = time.monotonic() + 20
    while status_of(master)["silentDataNodes"].get(vs.url, -1) < past:
        assert time.monotonic() < deadline, status_of(master)
        time.sleep(0.05)


def beat_again(vs) -> None:
    del vs._heartbeat_once
    vs._heartbeat_once()


def test_a_job_that_starts_in_a_late_beats_second_still_promises_three(
        half_second_cluster, monkeypatch):
    """The master names two of three when the job takes its first look
    and says it let go of the third a moment ago: the job asks again,
    for up to four of the master's pulses, and starts under three."""
    master, servers, admin = half_second_cluster
    assert status_of(master)["pulseSeconds"] == PULSE
    vid, _ = fill_volume(master, "late")
    late = next(vs for vs in servers if not vs.store.find_volume(vid))
    real, looks = ec_handler._cluster_status, []

    def look(worker):
        status = real(worker)
        looks.append(len(status["dataNodes"]))
        if len(looks) == 3:
            beat_again(late)
        return status

    fall_silent(master, late)
    assert len(status_of(master)["dataNodes"]) == 2
    monkeypatch.setattr(ec_handler, "_cluster_status", look)
    detail = run_job(admin, vid, "late")
    assert detail["status"] == "done", detail
    assert looks[:4] == [2, 2, 2, 3], looks
    assert "distributed to 3 servers" in detail["message"]
    assert settle(master, vid, [5, 5, 4]) == [5, 5, 4]
    (sp,) = distribute_spans(admin, detail)
    assert sp["attrs"]["serversAtStart"] == sp["attrs"]["servers"] == 3
    assert 0 < sp["attrs"]["waitSeconds"] < 4 * PULSE


def test_a_server_long_gone_is_not_waited_for(half_second_cluster):
    """Let go of more than four pulses ago, a server is no part of the
    cluster a job starts under: two servers, named as two, at once."""
    master, servers, admin = half_second_cluster
    vid, _ = fill_volume(master, "gone")
    gone = next(vs for vs in servers if not vs.store.find_volume(vid))
    fall_silent(master, gone, past=4 * PULSE)
    try:
        detail = run_job(admin, vid, "gone")
    finally:
        beat_again(gone)
    assert detail["status"] == "done", detail
    assert "distributed to 2 servers" in detail["message"]
    assert settle(master, vid, [7, 7]) == [7, 7]
    (sp,) = distribute_spans(admin, detail)
    assert sp["attrs"]["serversAtStart"] == sp["attrs"]["servers"] == 2
    assert "waitSeconds" not in sp["attrs"]


# -- the EC read path, counted -------------------------------------------------

@pytest.fixture(scope="module")
def encoded(cluster):
    """(vid, collection, blobs, holder of shard 0, another server): a
    volume under 1 MB, so every needle's interval lies in shard 0."""
    master, servers, admin = cluster
    vid, blobs = fill_volume(master, "reads")
    assert run_job(admin, vid, "reads")["status"] == "done"
    assert settle(master, vid, [5, 5, 4]) == [5, 5, 4]
    holder = next(vs for vs in servers
                  if 0 in vs.store.find_ec_volume(vid).shards)
    other = next(vs for vs in servers if vs is not holder)
    return vid, "reads", blobs, holder, other


def intervals() -> "dict[str, float]":
    return {s: counter("ec_read_intervals_total", source=s)
            for s in ("local", "remote", "reconstructed")}


def moved(before: dict) -> dict:
    return {s: v - before[s] for s, v in intervals().items()
            if v != before[s]}


def test_an_interval_is_counted_by_where_it_came_from(encoded):
    vid, collection, blobs, holder, other = encoded
    fids = iter(blobs)               # a fid once: the hot cache is above
    remote_timed = (stats.PROCESS.histogram_merged(
        "ec_remote_read_seconds") or {"count": 0})["count"]

    def get(vs, fid, **headers):
        status, body, _ = http_bytes("GET", f"{vs.url}/{fid}",
                                     headers=headers)
        assert status == 200 and body == blobs[fid]

    before = intervals()
    get(holder, next(fids))
    assert moved(before) == {"local": 1}
    before = intervals()
    get(other, next(fids))
    assert moved(before) == {"remote": 1}
    assert stats.PROCESS.histogram_merged(
        "ec_remote_read_seconds")["count"] == remote_timed + 1

    # a request somebody will read the trace of leaves a span of each
    # interval under the role's server span; any other leaves none
    tracing.reset_buffer()
    get(other, next(fids))
    assert not [s for s in tracing.recent_spans(1000)
                if s["name"] == "ec.read_interval"]
    get(other, next(fids), **{tracing.HEADER: "feedface-abc123"})
    spans = tracing.recent_spans(1000)
    (iv,) = [s for s in spans if s["name"] == "ec.read_interval"]
    assert iv["attrs"]["source"] == "remote" and iv["attrs"]["shard"] == 0
    assert iv["attrs"]["bytes"] > 50_000 and iv["role"] == "volume"
    # (the server span closes after the response is on the wire)
    deadline = time.monotonic() + 5
    while not (server := [s for s in tracing.recent_spans(1000)
                          if s["spanId"] == iv["parentId"]]) and \
            time.monotonic() < deadline:
        time.sleep(0.01)
    assert server[0]["name"].startswith("GET /") and \
        server[0]["parentId"] == "abc123"

    # shard 0 gone from its only holder: the degraded arm, counted too
    r = http_json("POST", f"{holder.url}/admin/ec/delete_shards",
                  {"volumeId": vid, "collection": collection,
                   "shardIds": [0]}, timeout=30)
    assert "error" not in r, r
    before = intervals()
    get(holder, next(fids))
    assert moved(before) == {"reconstructed": 1}


def test_cluster_top_names_heartbeat_errors_only_where_there_are_some():
    from seaweedfs_tpu.shell.commands import _render_node_top
    quiet = {"volume_server_requests_in_flight": [({}, 0.0)]}
    loud = dict(quiet, seaweedfs_tpu_volume_heartbeat_errors_total=[
        ({"error": "RuntimeError"}, 2.0), ({"error": "TimeoutError"}, 1.0)])
    assert "heartbeat-errors" not in _render_node_top(
        "127.0.0.1:1", quiet, quiet, 1.0)[0]
    assert "heartbeat-errors=3" in _render_node_top(
        "127.0.0.1:1", quiet, loud, 1.0)[0]


def test_a_master_that_stood_still_does_not_let_go_of_its_servers():
    """Three pulses unheard are three pulses of listening: the time
    the master itself did not run (its clock did not tick) is not held
    against the servers; a server that stays silent after it is let go
    of three pulses later; without a clock thread nothing is forgiven."""
    hb = {"ip": "127.0.0.1", "port": 9, "volumes": [], "ecShards": []}
    dead0 = counter("master_node_transitions_total", to="dead")
    stalled0 = counter("master_own_stall_seconds_total")
    topo = Topology(pulse_seconds=0.05)
    topo.register_heartbeat(hb)
    topo.tick()
    time.sleep(0.3)                  # the whole machine stood still
    assert [n.url for n in topo.alive_nodes()] == ["127.0.0.1:9"]
    assert counter("master_node_transitions_total", to="dead") == dead0
    assert counter("master_own_stall_seconds_total") >= stalled0 + 0.3
    for _ in range(8):               # the master runs, the server is silent
        time.sleep(0.025)
        topo.tick()
    assert topo.alive_nodes() == []
    assert counter("master_node_transitions_total", to="dead") == dead0 + 1
    quiet = Topology(pulse_seconds=0.05)      # nobody ticks this one
    quiet.register_heartbeat(hb)
    time.sleep(0.3)
    assert quiet.alive_nodes() == []


def test_a_server_silent_since_before_a_stall_gets_three_pulses_in_all():
    """Forgiving the master's stall gives a server that fell silent
    before it no pulse more: once the master has listened three pulses
    in all without hearing it, before and after, it is let go of."""
    pulse, tick = 0.4, 0.02
    hb = {"ip": "127.0.0.1", "port": 11, "volumes": [], "ecShards": []}
    stalled0 = counter("master_own_stall_seconds_total")
    topo = Topology(pulse_seconds=pulse)
    topo.register_heartbeat(hb)      # the last that is heard of it
    heard = time.monotonic()
    topo.tick()
    while time.monotonic() - heard < 2 * pulse:    # two pulses listening
        time.sleep(tick)
        topo.tick()
    time.sleep(3 * pulse)            # the machine stands still
    assert len(topo.alive_nodes()) == 1
    while topo.alive_nodes():        # listening again
        assert time.monotonic() - heard < 20 * pulse
        time.sleep(tick)
        topo.tick()
    silent = time.monotonic() - heard
    forgiven = counter("master_own_stall_seconds_total") - stalled0
    assert forgiven >= 3 * pulse
    # let go of within three pulses of listening (and a tick or two)
    assert 3 * pulse <= silent - forgiven < 3 * pulse + 0.2

