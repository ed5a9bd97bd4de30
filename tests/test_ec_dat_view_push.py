"""The data shards are never written on the worker (ISSUE 35): a data
shard is a view of the `.dat` the job pulled (block i of every row,
zeros past the end), the encode's writer keeps the parity alone, and
the push sends the view as ranges of the `.dat`.  What every target
receives is byte for byte the shard file `write_ec_files` writes.
Counts and bytes only; a speed is the chip's to say."""

import glob
import hashlib
import os
import shutil
import time

import numpy as np
import pytest

from test_http_upload import MIB, PLANES, receiver  # noqa: F401 — a fixture
from test_live_cluster import fill_volume, settle

from seaweedfs_tpu import operation
from seaweedfs_tpu import security as sec_mod
from seaweedfs_tpu.plugin import AdminServer, PluginWorker
from seaweedfs_tpu.plugin.handlers import EcEncodeHandler
from seaweedfs_tpu.plugin.handlers import erasure_coding as ec_handler
from seaweedfs_tpu.server.httpd import http_json, http_upload
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage.erasure_coding import (ECContext, ec_context,
                                                  ec_decoder, ec_encoder)
from seaweedfs_tpu.storage.erasure_coding.ec_locate import data_shard_ranges
from seaweedfs_tpu.storage.erasure_coding.shard_sink import DatShardView

LARGE, SMALL = 4096, 1024
SCHEMES = [(10, 4), (6, 3)]


# -- (a) the geometry --------------------------------------------------------

@pytest.fixture
def toy_blocks(monkeypatch):
    for mod in (ec_encoder, ec_context):
        monkeypatch.setattr(mod, "LARGE_BLOCK_SIZE", LARGE)
        monkeypatch.setattr(mod, "SMALL_BLOCK_SIZE", SMALL)


def dat_sizes(d: int) -> "dict[str, int]":
    return {
        "whole_rows": 3 * d * SMALL,
        "ends_mid_block": 2 * d * SMALL + 2 * SMALL + 317,
        "whole_blocks_past_the_end": d * SMALL + SMALL + 1,
        "a_large_row_first": d * LARGE + d * SMALL + 3 * SMALL + 5,
        "two_large_rows_and_no_tail": 2 * d * LARGE,
        "one_byte": 1,
    }


def assemble(dat: bytes, pieces) -> bytes:
    return b"".join(dat[off:off + got] + bytes(zeros)
                    for off, got, zeros in pieces)


@pytest.mark.parametrize("shape", sorted(dat_sizes(1)))
@pytest.mark.parametrize("d,p", SCHEMES)
def test_a_views_pieces_are_the_shard_file_the_encode_writes(
        tmp_path, toy_blocks, d, p, shape):
    size = dat_sizes(d)[shape]
    dat = np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    base = str(tmp_path / "v")
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    ctx = ECContext(d, p, backend="cpu")
    ec_encoder.write_ec_files(base, ctx)
    parity_size = os.path.getsize(base + ctx.to_ext(d))
    for sid in range(d):
        pieces = data_shard_ranges(LARGE, SMALL, size, d, sid)
        with open(base + ctx.to_ext(sid), "rb") as f:
            want = f.read()
        assert assemble(dat, pieces) == want, sid
        # what a piece takes from the .dat lies inside it; none is empty
        assert all(got + zeros > 0 and (not got or off + got <= size)
                   for off, got, zeros in pieces)
        view = DatShardView(base + ".dat", size, d, sid, LARGE, SMALL)
        assert view.pieces == pieces
        assert view.size == len(want) == parity_size


def test_an_empty_dat_has_no_pieces():
    assert data_shard_ranges(LARGE, SMALL, 0, 10, 3) == []


@pytest.mark.parametrize("d,p", SCHEMES)
def test_the_encode_with_views_writes_the_parity_alone(
        tmp_path, toy_blocks, d, p):
    """`write_parity_files` leaves the parity files `write_ec_files`
    writes and no data shard file; with no `sinks` the encode writes
    all its files as before."""
    size = dat_sizes(d)["a_large_row_first"]
    dat = np.random.default_rng(d).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    ctx = ECContext(d, p, backend="cpu")
    for name in ("views", "files"):
        os.mkdir(tmp_path / name)
        with open(tmp_path / name / "v.dat", "wb") as f:
            f.write(dat)
    seen = []
    views = ec_encoder.write_parity_files(
        str(tmp_path / "views" / "v"), ctx,
        progress=lambda done, total: seen.append((done, total)))
    ec_encoder.write_ec_files(str(tmp_path / "files" / "v"), ctx)
    assert sorted(os.listdir(tmp_path / "views")) == \
        ["v.dat"] + [f"v.ec{sid:02d}" for sid in range(d, d + p)]
    assert sorted(os.listdir(tmp_path / "files")) == \
        ["v.dat"] + [f"v.ec{sid:02d}" for sid in range(d + p)]
    for sid in range(d, d + p):
        with open(tmp_path / "views" / f"v.ec{sid:02d}", "rb") as a, \
                open(tmp_path / "files" / f"v.ec{sid:02d}", "rb") as b:
            assert a.read() == b.read(), sid
    assert [v.shard_id for v in views] == list(range(d))
    for v in views:
        with open(tmp_path / "files" / f"v.ec{v.shard_id:02d}", "rb") as f:
            assert assemble(dat, v.pieces) == f.read()
    # the progress note still counts volume bytes, up to the whole
    assert seen and seen[-1] == (size, size)
    assert [done for done, _ in seen] == sorted(done for done, _ in seen)


# -- (b) the body ------------------------------------------------------------

def _dat(tmp_path, size: int) -> "tuple[str, bytes]":
    body = np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    path = tmp_path / "pulled.dat"
    path.write_bytes(body)
    return str(path), body


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
def test_pieces_arrive_as_one_body_of_their_summed_length(
        receiver, tmp_path):
    """Ranges out of order in the file, a range longer than the TLS
    path's buffer, zero fill after a range, on its own and longer than
    a block: the receiver sees one body, `Content-Length` its length."""
    http, seen = receiver
    path, dat = _dat(tmp_path, 5 * MIB + 123)
    pieces = [(3 * MIB, 2 * MIB + 123, 0), (0, 7, 11), (MIB, MIB + 1, 0),
              (5 * MIB + 123, 0, MIB + 5), (17, 100, 1)]
    want = assemble(dat, pieces)
    res = http_upload("POST", f"{http.url}/admin/take", path,
                      pieces=pieces)
    status, body, _headers = res
    assert status == 200 and b'"bytes"' in body
    assert res.via == ("blocks" if sec_mod.current().tls else "sendfile")
    assert seen["headers"]["Content-Length"] == str(len(want))
    assert seen["bytes"] == len(want)
    assert seen["sha256"] == hashlib.sha256(want).hexdigest()


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
def test_a_shards_view_arrives_as_the_shard_file(receiver, tmp_path):
    http, seen = receiver
    d, size = 10, 3 * 10 * 64 * 1024 + 70_000
    path, dat = _dat(tmp_path, size)
    view = DatShardView(path, size, d, 1, 1 << 20, 64 * 1024)
    status, _, _ = http_upload("POST", f"{http.url}/admin/take", path,
                               pieces=view.pieces)
    assert status == 200
    assert seen["bytes"] == view.size == 4 * 64 * 1024
    assert seen["sha256"] == hashlib.sha256(
        assemble(dat, view.pieces)).hexdigest()


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
def test_a_dat_cut_short_under_the_sender_fails_the_upload(
        receiver, tmp_path):
    """The pieces were laid out for a `.dat` of 4 MiB and the file is
    3 MiB + 5 when it is sent: the body ends inside a piece and the
    push raises, whatever follows the piece."""
    http, seen = receiver
    path, _ = _dat(tmp_path, 4 * MIB)
    pieces = [(0, MIB, 0), (2 * MIB, 2 * MIB, 9)]
    os.truncate(path, 3 * MIB + 5)
    with pytest.raises(OSError, match="ended at 2097157 of 3145737"):
        http_upload("POST", f"{http.url}/admin/take", path, timeout=5,
                    pieces=pieces)
    assert "sha256" not in seen


@pytest.mark.parametrize("receiver", PLANES, indirect=True)
def test_a_receiver_that_refuses_a_view_mid_body_is_heard(
        receiver, tmp_path):
    http, seen = receiver
    size = 48 * MIB
    path = str(tmp_path / "sparse.dat")
    with open(path, "wb") as f:
        f.truncate(size)
    pieces = data_shard_ranges(1 << 30, MIB, size, 1, 0)
    assert len(pieces) == 48
    got, body, _headers = http_upload(
        "POST", f"{http.url}/admin/reject?status=507&after={2 * MIB}",
        path, timeout=20, pieces=pieces)
    assert got == 507 and b"no room" in body
    assert 2 * MIB <= seen["bytes"] < size


# -- (c), (d) a job on a cluster of three ------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("datview")
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
    yield master, servers, admin, tmp
    worker.stop()
    admin.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def submit_and_wait(admin, params: dict) -> dict:
    job_id = http_json("POST", f"{admin.url}/maintenance/submit_job", {
        "jobType": "erasure_coding", "params": params})["jobId"]
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        detail = http_json("GET",
                           f"{admin.url}/maintenance/job?id={job_id}")
        if detail["status"] in ("done", "failed"):
            return detail
        time.sleep(0.05)
    raise AssertionError(f"job {job_id} did not end: {detail}")


def spans_of(admin, detail) -> "dict[str, list[dict]]":
    got = http_json("GET", f"{admin.url}/debug/traces?request_id="
                    f"{detail['requestId']}")["spans"]
    by_name: "dict[str, list[dict]]" = {}
    for s in sorted(got, key=lambda s: s["start"]):
        by_name.setdefault(s["name"], []).append(s)
    return by_name


def keep_the_source(tmp, servers, vid: int, collection: str) -> str:
    """A copy of the volume's `.dat` as its server holds it before the
    job, and the base name of the copy."""
    (src,) = [p for vs in servers for loc in vs.store.locations
              for p in glob.glob(os.path.join(
                  loc.directory, f"{collection}_{vid}.dat"))]
    os.makedirs(tmp / "expected", exist_ok=True)
    base = str(tmp / "expected" / f"{collection}_{vid}")
    shutil.copy(src, base + ".dat")
    return base


def on_the_targets(servers, vid: int, collection: str, ext: str) -> list:
    return [p for vs in servers for loc in vs.store.locations
            for p in glob.glob(os.path.join(
                loc.directory, f"{collection}_{vid}{ext}"))]


@pytest.fixture
def watched(cluster, monkeypatch):
    """What the worker's work dir held at each push and at the job's
    clean-up, and how often the consistency check ran."""
    _master, _servers, _admin, tmp = cluster
    seen = {"held": set(), "checks": []}
    push, clean = ec_handler._push_file, EcEncodeHandler._cleanup_local
    check = ec_decoder.find_dat_file_size

    def look():
        seen["held"].update(os.listdir(tmp / "worker"))

    def push_file(*args):
        look()
        return push(*args)

    def cleanup_local(base, ctx):
        look()
        return clean(base, ctx)

    def find_dat_file_size(*args):
        seen["checks"].append(args)
        return check(*args)

    monkeypatch.setattr(ec_handler, "_push_file", push_file)
    monkeypatch.setattr(EcEncodeHandler, "_cleanup_local",
                        staticmethod(cleanup_local))
    monkeypatch.setattr(ec_decoder, "find_dat_file_size",
                        find_dat_file_size)
    return seen


@pytest.mark.parametrize("d,p", SCHEMES)
def test_a_job_pushes_its_data_shards_out_of_the_dat(cluster, watched,
                                                     d, p):
    master, servers, admin, tmp = cluster
    collection = f"view{d}"
    vid, blobs = fill_volume(master, collection)
    base = keep_the_source(tmp, servers, vid, collection)
    detail = submit_and_wait(admin, {
        "volumeId": vid, "collection": collection,
        "dataShards": d, "parityShards": p})
    assert detail["status"] == "done", detail
    total = d + p
    want = [total // 3 + (i < total % 3) for i in range(3)]
    assert settle(master, vid, want) == want

    # every shard on every target is what write_ec_files writes
    ctx = ECContext(d, p)
    ec_encoder.write_ec_files(base, ctx)
    for sid in range(total):
        (there,) = on_the_targets(servers, vid, collection, ctx.to_ext(sid))
        with open(there, "rb") as got, \
                open(base + ctx.to_ext(sid), "rb") as expected:
            assert got.read() == expected.read(), sid
    for fid, data in blobs.items():
        assert operation.read(master.url, fid) == data

    # the work dir held the .dat and the parity, never a data shard
    names = {n.split(".", 1)[1] for n in watched["held"]
             if n.startswith(f"{vid}.")}
    assert {"dat", "idx", "ecx", "vif"} <= names
    assert {f"ec{sid:02d}" for sid in range(d, total)} <= names
    assert not {f"ec{sid:02d}" for sid in range(d)} & names
    assert os.listdir(tmp / "worker") == []
    # the worker's own check ran, on the version the .dat states
    assert len(watched["checks"]) == 1
    assert watched["checks"][0][2] == 3

    spans = spans_of(admin, detail)
    pushes = spans["ec.push"]
    assert len(pushes) == total + 2 * 3
    by_source = {"dat": [], "file": []}
    for s in pushes:
        by_source[s["attrs"]["source"]].append(s["attrs"]["ext"])
    assert sorted(by_source["dat"]) == [ctx.to_ext(i) for i in range(d)]
    assert sorted(by_source["file"]) == sorted(
        [ctx.to_ext(i) for i in range(d, total)] + [".ecx", ".vif"] * 3)
    shard_size = os.path.getsize(base + ctx.to_ext(0))
    for s in pushes:
        if s["attrs"]["source"] == "dat":
            assert s["attrs"]["bytes"] == shard_size
            assert s["attrs"]["ranges"] == len(data_shard_ranges(
                1 << 30, 1 << 20, os.path.getsize(base + ".dat"), d, 0))
        else:
            assert s["attrs"]["ranges"] == 1
        assert s["attrs"]["via"] == "sendfile"
    (dist,) = spans["ec.distribute"]
    assert dist["attrs"]["bytesFromDat"] == d * shard_size
    assert dist["attrs"]["bytes"] == sum(s["attrs"]["bytes"]
                                         for s in pushes)


def run_a_batch_job(cluster, watched, collection: str) -> None:
    """A job of two or more volumes: each volume's data shards go out
    of its `.dat` as in a single job, its parity as files, and the
    job's one `ec.encode` span says how many windows its volumes'
    encodes put on the device and over how many devices each."""
    master, servers, admin, tmp = cluster
    http_json("POST", f"{master.url}/vol/grow",
              {"count": 2, "replication": "000", "collection": collection})
    rng = np.random.default_rng(35)
    blobs = {}
    for _ in range(24):
        data = rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes()
        blobs[operation.submit(master.url, data,
                               collection=collection)] = data
    vids = sorted({int(fid.split(",")[0]) for fid in blobs})
    assert len(vids) >= 2, vids
    time.sleep(0.5)                  # the master hears of their sizes
    bases = {vid: keep_the_source(tmp, servers, vid, collection)
             for vid in vids}
    detail = submit_and_wait(admin, {"volumeIds": vids,
                                     "collection": collection})
    assert detail["status"] == "done", detail
    ctx = ECContext()
    d = ctx.data_shards
    for vid in vids:
        assert settle(master, vid, [5, 5, 4]) == [5, 5, 4]
        ec_encoder.write_ec_files(bases[vid], ctx)
        for sid in range(ctx.total):
            (there,) = on_the_targets(servers, vid, collection,
                                      ctx.to_ext(sid))
            with open(there, "rb") as got, \
                    open(bases[vid] + ctx.to_ext(sid), "rb") as expected:
                assert got.read() == expected.read(), (vid, sid)
    for fid, data in blobs.items():
        assert operation.read(master.url, fid) == data

    spans = spans_of(admin, detail)
    pushes = spans["ec.push"]
    assert len(pushes) == len(vids) * (14 + 2 * 3)
    for s in pushes:
        ext = s["attrs"]["ext"]
        data_shard = ext in [ctx.to_ext(i) for i in range(d)]
        assert s["attrs"]["source"] == ("dat" if data_shard else "file")
    dists = spans["ec.distribute"]
    assert len(dists) == len(vids)
    for vid, dist in zip(vids, dists):
        shard_size = os.path.getsize(bases[vid] + ctx.to_ext(0))
        assert dist["attrs"]["bytesFromDat"] == d * shard_size, vid
    # its work dir held the parity as files, never a data shard; its
    # check read the version off the .dat
    for vid in vids:
        assert {f"{vid}.ec{sid:02d}" for sid in range(d, 14)} <= \
            watched["held"]
        assert not {f"{vid}.ec{sid:02d}" for sid in range(d)} & \
            watched["held"]
    assert [len(args) for args in watched["checks"]] == [3] * len(vids)

    (encode,) = spans["ec.encode"]
    assert encode["attrs"]["volumes"] == len(vids)
    reads = spans["encode.read"]
    assert len(reads) == len(vids)
    if ctx.backend == "jax":
        # every volume's windows, each a put and a fetch, and each put
        # split over the 8 virtual devices
        windows = sum(s["attrs"]["windows"] for s in reads)
        assert encode["attrs"]["windows"] == windows > 0
        assert len(spans["stage.h2d"]) == len(spans["stage.d2h"]) == windows
        assert encode["attrs"]["windowDevices"] == 8
    else:
        # a host codec makes no launch
        assert encode["attrs"]["windows"] == 0
        assert encode["attrs"]["windowDevices"] == 0
        assert "stage.h2d" not in spans


def test_a_batch_job_still_pushes_files(cluster, watched):
    """On the worker's host codec."""
    run_a_batch_job(cluster, watched, "batch")


def test_a_batch_job_on_the_device_codec(cluster, watched, monkeypatch):
    """On the device codec: JAX on eight virtual CPU devices, a window
    of one 1 MiB row."""
    from seaweedfs_tpu.ops import staging
    monkeypatch.setenv("SEAWEEDFS_TPU_EC_BACKEND", "jax")
    monkeypatch.setattr(staging, "WINDOW_BYTES", 10 << 20)
    assert ECContext().backend == "jax"
    run_a_batch_job(cluster, watched, "batchjax")
