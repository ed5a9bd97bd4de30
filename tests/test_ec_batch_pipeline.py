"""A batch job's volumes go through pull, encode and distribute as a
pipeline (EcEncodeHandler.execute_batch): volume i+1 is pulled and
volume i-1 distributed while volume i encodes, every stage's spans in
the job's trace, the volumes distributed in their order, and a stage
that fails stops the others and unwinds every volume.  Counts and
order only; a speed is the chip's to say."""

import contextvars
import random
import re
import sys
import threading
import time

import numpy as np

from test_ec_dat_view_push import (cluster,  # noqa: F401
                                   on_the_targets, spans_of,
                                   submit_and_wait)
from test_live_cluster import settle

from benchmark import run
from seaweedfs_tpu import operation, tracing
from seaweedfs_tpu.plugin import PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler
from seaweedfs_tpu.plugin.handlers import erasure_coding as ec_handler
from seaweedfs_tpu.server.httpd import http_json

PULL_SLEEP = 0.5


def fill_volumes(master, collection: str, seed: int
                 ) -> "tuple[list[int], dict]":
    """Three or more volumes of `collection`, and the blobs in them."""
    http_json("POST", f"{master.url}/vol/grow",
              {"count": 3, "replication": "000", "collection": collection})
    rng = np.random.default_rng(seed)
    blobs = {}
    for _ in range(36):
        data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
        blobs[operation.submit(master.url, data,
                               collection=collection)] = data
    vids = sorted({int(fid.split(",")[0]) for fid in blobs})
    assert len(vids) >= 3, vids
    time.sleep(0.5)                  # the master hears of their sizes
    return vids, blobs


def _vid_of(url: str) -> int:
    return int(re.search(r"volumeId=(\d+)", url).group(1))


def test_a_batch_jobs_stages_overlap_in_volume_order(cluster, monkeypatch):
    """With every pull slowed, the first volume is distributed while a
    later one is still being pulled; each stage's spans hang under the
    job's; the volumes are distributed in their order; the three stage
    spans count a call a volume; the progress the admin is sent never
    falls."""
    master, servers, admin, _tmp = cluster
    vids, blobs = fill_volumes(master, "pipe", 41)
    n = len(vids)
    pull_span, dist_span = {}, {}
    download, push_to_each = ec_handler.http_download, \
        ec_handler._push_to_each

    def slow_download(url, path, **kw):
        # inside the ec.pull span: the span's id names the volume
        pull_span[tracing.current_ids()[1]] = _vid_of(url)
        if "ext=.dat" in url:
            time.sleep(PULL_SLEEP)
        return download(url, path, **kw)

    def pushes(holders, vid, collection, where):
        dist_span[tracing.current_ids()[1]] = vid
        return push_to_each(holders, vid, collection, where)

    reports = []
    report = PluginWorker.report_progress

    def report_progress(self, job_id, progress, message=""):
        reports.append((job_id, progress, message))
        return report(self, job_id, progress, message)

    monkeypatch.setattr(ec_handler, "http_download", slow_download)
    monkeypatch.setattr(ec_handler, "_push_to_each", pushes)
    monkeypatch.setattr(PluginWorker, "report_progress", report_progress)
    detail = submit_and_wait(admin, {"volumeIds": vids,
                                     "collection": "pipe"})
    assert detail["status"] == "done", detail
    for fid, data in blobs.items():
        assert operation.read(master.url, fid) == data

    spans = spans_of(admin, detail)
    (job,) = spans["job:erasure_coding"]
    pulls, (encode,), dists = (spans["ec.pull"], spans["ec.encode"],
                               spans["ec.distribute"])
    assert len(pulls) == len(dists) == n
    for s in pulls + [encode] + dists:
        assert s["parentId"] == job["spanId"], s["name"]
        assert s["traceId"] == job["traceId"]
    assert encode["attrs"]["volumes"] == n

    def end(s):
        return s["start"] + s["durationMs"] / 1e3
    pull_of = {pull_span[s["spanId"]]: s for s in pulls}
    dist_of = {dist_span[s["spanId"]]: s for s in dists}
    assert sorted(pull_of) == sorted(dist_of) == vids
    # the first volume is out while the last is still coming in
    assert dist_of[vids[0]]["start"] < end(pull_of[vids[-1]])
    assert end(dist_of[vids[0]]) > pull_of[vids[1]]["start"]
    # one pull at a time, and the volumes distributed in their order
    assert [pull_span[s["spanId"]] for s in pulls] == vids
    assert [dist_span[s["spanId"]] for s in dists] == vids
    for a, b in zip(pulls, pulls[1:]):
        assert end(a) <= b["start"] + 1e-3
    for a, b in zip(dists, dists[1:]):
        assert end(a) <= b["start"] + 1e-3
    # the encode's span runs from the first volume's pulled to the
    # last one's encoded
    assert end(pull_of[vids[0]]) <= encode["start"] + 1e-3
    assert end(pull_of[vids[-1]]) <= end(encode) + 1e-3

    for name in ("batch.pull", "batch.encode", "batch.distribute"):
        (stage,) = spans[name]
        assert stage["parentId"] == job["spanId"], name
        assert stage["attrs"]["calls"] == n, name
        assert 0 < stage["attrs"]["busySeconds"] <= \
            stage["durationMs"] / 1e3 + 1e-3, name
    assert spans["batch.pull"][0]["attrs"]["busySeconds"] >= \
        n * PULL_SLEEP

    mine = [(p, m) for j, p, m in reports if j == detail["jobId"]]
    fractions = [p for p, _m in mine]
    assert fractions == sorted(fractions), mine
    labels = [m for _p, m in mine]
    assert [m for m in labels if m.startswith("pulled volume")] == [
        f"pulled volume {v} ({i + 1}/{n})" for i, v in enumerate(vids)]
    assert [m for m in labels if m.startswith("distributed volume")] == [
        f"distributed volume {v} ({i + 1}/{n})" for i, v in enumerate(vids)]
    (encoded,) = [i for i, m in enumerate(labels)
                  if m.startswith("batch-encoded")]
    assert labels[encoded].startswith(f"batch-encoded {n} volumes")
    assert labels.index(f"pulled volume {vids[-1]} ({n}/{n})") < \
        encoded < labels.index(f"distributed volume {vids[-1]} ({n}/{n})")


def test_a_pull_that_fails_mid_pipeline_unwinds_every_volume(
        cluster, monkeypatch):
    """The third volume's pull raises once the first volume is mounted:
    the job fails, no volume keeps an EC shard, every volume is
    writable again and no original is deleted."""
    master, servers, admin, _tmp = cluster
    vids, blobs = fill_volumes(master, "pipefail", 43)
    mounted = threading.Event()
    download, distribute = ec_handler.http_download, \
        EcEncodeHandler._distribute_and_mount

    def failing_download(url, path, **kw):
        if _vid_of(url) == vids[2]:
            assert mounted.wait(timeout=60)
            raise OSError("the source went away")
        return download(url, path, **kw)

    def distribute_and_mount(self, worker, vid, *args):
        placement = distribute(self, worker, vid, *args)
        if vid == vids[0]:
            mounted.set()
        return placement

    monkeypatch.setattr(ec_handler, "http_download", failing_download)
    monkeypatch.setattr(EcEncodeHandler, "_distribute_and_mount",
                        distribute_and_mount)
    detail = submit_and_wait(admin, {"volumeIds": vids,
                                     "collection": "pipefail"})
    assert detail["status"] == "failed", detail
    assert "the source went away" in detail["message"]
    assert mounted.is_set()

    spans = spans_of(admin, detail)
    # the first volume got as far as its mounts; the stages stopped at
    # their next volume after the third one's pull failed
    assert len(spans["ec.distribute"]) in (1, 2)
    assert len(spans["ec.pull"]) == 3
    assert spans["ec.pull"][2].get("error")
    assert spans["batch.pull"][0]["attrs"]["calls"] == 3
    for vid in vids:
        assert settle(master, vid, []) == [], vid
        for vs in servers:
            for loc in vs.store.locations:
                assert vid not in loc.ec_volumes, vid
        assert not on_the_targets(servers, vid, "pipefail", ".ec[0-9][0-9]")
        holders = [vs for vs in servers if vs.store.find_volume(vid)]
        assert len(holders) == 1, vid
        assert holders[0].store.find_volume(vid).read_only is False, vid
        assert on_the_targets(servers, vid, "pipefail", ".dat"), vid
    for fid, data in blobs.items():
        assert operation.read(master.url, fid) == data


def test_the_harness_reads_a_pipelined_jobs_phases():
    """A pipelined job's marks interleave (a volume distributed before
    the last is pulled); its phases still run to the last report of
    their kind and partition the job from start to end."""
    log = [["", "trace_start", 9.0], ["a", "start", 10.0],
           ["a", "pulled volume 11 (1/4)", 10.9],
           ["a", "encoding 0/1016 MiB", 10.95],
           ["a", "pulled volume 12 (2/4)", 11.8],
           ["a", "distributed volume 11 (1/4)", 12.1],
           ["a", "pulled volume 13 (3/4)", 12.7],
           ["a", "distributed volume 12 (2/4)", 13.0],
           ["a", "pulled volume 14 (4/4)", 13.6],
           ["a", "distributed volume 13 (3/4)", 13.9],
           ["a", "batch-encoded 4 volumes (jax on tpu TPU v5 lite x4)",
            14.2],
           ["a", "distributed volume 14 (4/4)", 14.9], ["a", "end", 15.0]]
    job = run.job_phases(log)["a"]
    assert job["start"] == 10.0 and job["end"] == 15.0
    assert job["phases"] == {"pull": (10.0, 13.6),
                             "encode": (13.6, 14.2),
                             "distribute": (14.2, 14.9),
                             "finish": (14.9, 15.0)}
    order = [job["phases"][p] for p in ("pull", "encode", "distribute",
                                        "finish")]
    assert order[0][0] == job["start"] and order[-1][1] == job["end"]
    for (_s, e), (s, _e) in zip(order, order[1:]):
        assert e == s


# -- the pipeline and the progress, alone ----------------------------------

def test_the_pipeline_hands_each_volume_on_in_order():
    """Pulls run ahead on one helper thread, distributes follow on a
    second, each in volume order, and both in a copy of the caller's
    context."""
    here = contextvars.ContextVar("here", default=None)
    here.set("the job's")
    rng = random.Random(7)
    vids = list(range(20, 40))
    seen = {"pull": [], "distribute": []}

    def step(name):
        def run_step(i, vid):
            time.sleep(rng.random() / 500)
            seen[name].append((i, vid, here.get(),
                               threading.current_thread().name))
        return run_step

    encoded = []
    with ec_handler._VolumePipeline(vids, step("pull"),
                                    step("distribute")) as pipe:
        for i, vid in pipe:
            encoded.append((i, vid))
            pipe.encoded(vid)
    assert encoded == list(enumerate(vids))
    for name in ("pull", "distribute"):
        assert [(i, v) for i, v, _h, _t in seen[name]] == encoded, name
        assert {h for _i, _v, h, _t in seen[name]} == {"the job's"}
        (thread,) = {t for _i, _v, _h, t in seen[name]}
        assert thread != threading.current_thread().name
    assert seen["pull"][0][3] != seen["distribute"][0][3]


def test_a_failed_stage_stops_the_others_at_their_next_volume():
    """The second volume's distribute raises: the pull in hand ends and
    no other starts, the caller's loop ends, and leaving the pipeline
    raises the distribute's error."""
    vids = list(range(10))
    failed = threading.Event()
    pulled, distributed = [], []

    def pull(i, vid):
        if i >= 3:
            assert failed.wait(timeout=30)
        pulled.append(vid)

    def distribute(i, vid):
        if i == 1:
            failed.set()
            raise RuntimeError("the second push failed")
        distributed.append(vid)

    encoded = []
    try:
        with ec_handler._VolumePipeline(vids, pull, distribute) as pipe:
            for _i, vid in pipe:
                encoded.append(vid)
                pipe.encoded(vid)
    except RuntimeError as e:
        assert str(e) == "the second push failed"
    else:
        raise AssertionError("the pipeline did not raise")
    assert pulled == [0, 1, 2, 3]
    assert distributed == [0]
    assert encoded[:2] == [0, 1] and len(encoded) <= 4


def test_an_encode_that_fails_stops_both_helpers():
    """The third volume's encode raises: no volume after it is
    distributed, and the pull stops at its next volume."""
    vids = list(range(6))
    pulled, distributed = [], []
    try:
        with ec_handler._VolumePipeline(
                vids, lambda i, v: pulled.append(v),
                lambda i, v: distributed.append(v)) as pipe:
            for i, vid in pipe:
                if i == 2:
                    raise ValueError("the encode failed")
                pipe.encoded(vid)
    except ValueError:
        pass
    else:
        raise AssertionError("the encode's error was lost")
    # the two handed on go out unless the failure stops them first
    assert distributed in ([], [0], [0, 1])
    assert pulled[:3] == [0, 1, 2]


def test_progress_from_many_threads_never_falls():
    """Sixteen threads report random fractions with the interpreter
    switching threads as often as it can: what the admin is sent never
    falls, and ends at the highest fraction reported."""
    class Worker:
        def __init__(self):
            self.sent = []

        def report_progress(self, job_id, progress, message=""):
            self.sent.append(progress)

    worker = Worker()
    report = ec_handler._forward_progress(worker, "j")
    highest = []

    def reporter(seed):
        rng = random.Random(seed)
        fractions = [rng.random() for _ in range(300)]
        highest.append(max(fractions))
        for f in fractions:
            report(f, "")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reporter, args=(s,))
                   for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert len(worker.sent) == 16 * 300
    assert worker.sent == sorted(worker.sent)
    assert worker.sent[-1] == max(highest)
