"""One timed copy loop (httpd.copy_to_file) under the three socket-to-file
copies: the worker's pull (http_download), the volume role's
`receive_file` and its `ec/shard_write`.  Each half, socket and file, is
timed apart; the bytes, the temp files and the fault hooks are what
they were."""

import io
import json
import math
import os
import time
import zlib

import pytest

from seaweedfs_tpu import faults, tracing
from seaweedfs_tpu.server.httpd import (CopyHalves, HttpServer,
                                        copy_to_file, http_download,
                                        http_stream_request, http_upload)
from seaweedfs_tpu.server.master_server import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util import retry
from seaweedfs_tpu.util.request_id import reset_request_id, set_request_id

BLOB = 300_000
CHUNK = 65_536


@pytest.fixture(autouse=True)
def _isolate():
    faults.reset()
    retry.reset()
    yield
    faults.reset()
    retry.reset()


@pytest.fixture
def giver(tmp_path):
    http = HttpServer("127.0.0.1", 0)
    blob = os.urandom(BLOB)
    (tmp_path / "served.bin").write_bytes(blob)

    def give(req):
        f = open(tmp_path / "served.bin", "rb")
        return 200, (f, {"Content-Length": str(len(blob))})

    http.route("GET", "/give", give)
    http.start()
    yield http, blob
    http.stop()


@pytest.fixture
def volume(tmp_path):
    master = MasterServer(volume_size_limit_mb=64).start()
    d = tmp_path / "vol"
    d.mkdir()
    vs = VolumeServer([str(d)], master.url, pulse_seconds=0.3).start()
    yield vs, str(d)
    vs.stop()
    master.stop()


def test_the_halves_add_up_to_no_more_than_the_loop():
    chunks = [b"x" * 1000, b"y" * 10, b"z" * 4096]
    seen = []
    f = io.BytesIO()
    halves = CopyHalves()
    t0 = time.time()
    assert copy_to_file(iter(chunks), f, halves, each=seen.append) \
        == 5106
    assert f.getvalue() == b"".join(chunks) and seen == chunks
    assert halves.socket[0] == halves.file[0] == len(chunks)
    assert halves.socket[3] == halves.file[3] == 5106
    assert halves.socket[1] + halves.file[1] <= halves.wall
    assert t0 <= halves.start <= time.time()
    names = [s["name"] for s in halves.spans("pull")]
    assert names == ["pull.socket", "pull.file"]


def test_a_download_counts_its_chunks_and_its_bytes(giver, tmp_path):
    http, blob = giver
    halves = CopyHalves()
    dest = tmp_path / "got.bin"
    st, _ = http_download(f"{http.url}/give", str(dest),
                          chunk_size=CHUNK, halves=halves)
    assert st == 200 and dest.read_bytes() == blob
    sock, fil = halves.socket, halves.file
    assert sock[0] == fil[0] == math.ceil(BLOB / CHUNK)
    assert sock[3] == fil[3] == os.path.getsize(dest)
    assert 0 < sock[1] + fil[1] <= halves.wall
    assert sock[2] >= 0 and fil[2] >= 0
    # a second copy into the same halves adds to them: a pull's .dat
    # and .idx are one pair of spans
    start = halves.start
    st, _ = http_download(f"{http.url}/give", str(tmp_path / "again"),
                          chunk_size=CHUNK, halves=halves)
    assert st == 200 and halves.start == start
    assert halves.socket[3] == halves.file[3] == 2 * BLOB
    assert halves.socket[0] == 2 * math.ceil(BLOB / CHUNK)


def test_a_download_without_halves_leaves_no_span(giver, tmp_path):
    http, _blob = giver
    tracing.reset_buffer()
    token = set_request_id("no-halves")
    try:
        st, _ = http_download(f"{http.url}/give", str(tmp_path / "g"))
    finally:
        reset_request_id(token)
    assert st == 200
    time.sleep(0.05)
    assert not [s for s in tracing.spans_for("no-halves")
                if s["name"].split(".")[-1] in ("socket", "file")]


def test_the_download_fault_still_fires_and_leaves_no_file(giver,
                                                           tmp_path):
    http, _blob = giver
    faults.arm("httpd.download.chunk", "truncate", n=1)
    dest = tmp_path / "got.bin"
    with pytest.raises(IOError, match="fault-injected"):
        http_download(f"{http.url}/give", str(dest), chunk_size=CHUNK)
    assert faults.triggered() == {"httpd.download.chunk": 1}
    assert not [p for p in os.listdir(tmp_path) if p.startswith("got")]


def test_a_receive_times_its_halves_under_its_server_span(volume,
                                                          tmp_path):
    vs, d = volume
    src = tmp_path / "shard.bin"
    src.write_bytes(os.urandom(BLOB))
    tracing.reset_buffer()
    token = set_request_id("recv-1")
    try:
        with tracing.span("ec.push", role="worker") as push:
            res = http_upload("POST", f"{vs.url}/admin/receive_file?"
                              "volumeId=901&collection=&ext=.ec00",
                              str(src))
    finally:
        reset_request_id(token)
    assert res[0] == 200
    assert open(os.path.join(d, "901.ec00"), "rb").read() == \
        src.read_bytes()
    deadline = time.monotonic() + 2
    while time.monotonic() < deadline:
        spans = tracing.spans_for("recv-1")
        server = [s for s in spans
                  if s["name"] == "POST /admin/receive_file"]
        if server:
            break
        time.sleep(0.01)
    (server,) = server
    assert server["parentId"] == push.span_id
    halves = {s["name"]: s for s in spans
              if s["parentId"] == server["spanId"]}
    assert sorted(halves) == ["receive.file", "receive.socket"]
    for s in halves.values():
        assert s["attrs"]["bytes"] == BLOB == server["attrs"]["bytes"]
        assert s["attrs"]["calls"] >= 1 and s["role"] == "volume"


@pytest.mark.parametrize("site", ["volume.receive_file.recv",
                                  "volume.shard_write.recv"])
def test_a_receivers_fault_still_fires_and_leaves_no_file(volume, site,
                                                          tmp_path):
    vs, d = volume
    faults.arm(site, "error", n=1)
    if site == "volume.receive_file.recv":
        src = tmp_path / "shard.bin"
        src.write_bytes(os.urandom(BLOB))
        status = http_upload("POST", f"{vs.url}/admin/receive_file?"
                             "volumeId=902&collection=&ext=.ec01",
                             str(src))[0]
    else:
        try:
            status, _ = http_stream_request(
                "POST", f"{vs.url}/admin/ec/shard_write?volumeId=902"
                "&shardId=1&collection=&uploadId=abc123",
                iter([b"s" * CHUNK] * 4), timeout=30)
        except OSError:
            status = 0
    assert status != 200
    assert faults.triggered() == {site: 1}
    deadline = time.monotonic() + 5
    while True:
        left = [p for p in os.listdir(d) if p.startswith("902")]
        if not left or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert left == []


def test_a_shard_write_keeps_its_crc_and_times_its_halves(volume):
    vs, _d = volume
    body = [os.urandom(CHUNK) for _ in range(4)]
    tracing.reset_buffer()
    token = set_request_id("sw-1")
    try:
        status, resp = http_stream_request(
            "POST", f"{vs.url}/admin/ec/shard_write?volumeId=903"
            "&shardId=2&collection=&uploadId=def456", iter(body),
            timeout=30)
    finally:
        reset_request_id(token)
    assert status == 200
    got = json.loads(resp)
    assert got == {"bytes": 4 * CHUNK, "crc32": zlib.crc32(b"".join(body))}
    deadline = time.monotonic() + 2
    while True:
        spans = tracing.spans_for("sw-1")
        server = [s for s in spans
                  if s["name"] == "POST /admin/ec/shard_write"]
        if server or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    (server,) = server
    halves = [s for s in spans if s["parentId"] == server["spanId"]]
    assert sorted(s["name"] for s in halves) == ["receive.file",
                                                 "receive.socket"]
    assert all(s["attrs"]["bytes"] == 4 * CHUNK for s in halves)
