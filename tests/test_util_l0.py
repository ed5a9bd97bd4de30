"""Round-5 L0 foundation utils: leveled logging (glog analog),
request-id propagation, slab buffer pool, skiplist, bounded executor,
mmap volume reads, env/TOML config layer (reference: weed/glog,
weed/util/request_id, util/mem/slot_pool.go, util/skiplist,
util/limited_executor.go, storage/backend/memory_map,
util/config.go + command/scaffold TOMLs)."""

import argparse
import logging
import os
import time

import pytest

from seaweedfs_tpu.util import config as wconfig
from seaweedfs_tpu.util import mem, wlog
from seaweedfs_tpu.util.limiter import BoundedExecutor, bounded_parallel
from seaweedfs_tpu.util.request_id import (ensure_request_id,
                                           get_request_id,
                                           reset_request_id,
                                           set_request_id)
from seaweedfs_tpu.util.skiplist import SkipList


# -- wlog ------------------------------------------------------------------


@pytest.fixture()
def log_capture():
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(self.format(record))
    h = Capture()
    h.setFormatter(wlog._Formatter())
    logging.getLogger("weed").addHandler(h)
    yield records
    logging.getLogger("weed").removeHandler(h)


def test_wlog_severities_and_format(log_capture):
    wlog.info("hello %s", "world", component="test")
    wlog.warning("watch out")
    wlog.error("broke")
    assert any(l.startswith("I") and "hello world" in l and
               "test]" in l for l in log_capture)
    assert any(l.startswith("W") for l in log_capture)
    assert any(l.startswith("E") for l in log_capture)


def test_wlog_v_gating(log_capture):
    old = wlog.get_verbosity()
    try:
        wlog.set_verbosity(1)
        wlog.v(2, "too detailed")
        wlog.v(1, "just right")
        if wlog.V(2):
            wlog.info("also too detailed")
        wlog.V(1).info("gate object form")
        assert not any("too detailed" in l for l in log_capture)
        assert any("just right" in l for l in log_capture)
        assert any("gate object form" in l for l in log_capture)
    finally:
        wlog.set_verbosity(old)


def test_wlog_carries_request_id(log_capture):
    tok = set_request_id("riddle42")
    try:
        wlog.info("traced line")
    finally:
        from seaweedfs_tpu.util.request_id import reset_request_id
        reset_request_id(tok)
    assert any("traced line" in l and "rid=riddle42" in l
               for l in log_capture)


def test_wlog_file_rotation(tmp_path):
    path = str(tmp_path / "weed.log")
    wlog.set_output(path, max_bytes=400, backups=2)
    try:
        for i in range(40):
            wlog.info("filler line %d xxxxxxxxxxxxxxxxxxxx", i)
        assert os.path.exists(path)
        assert os.path.exists(path + ".1"), "rotation never happened"
        assert os.path.getsize(path) <= 500
    finally:
        wlog._logger.removeHandler(wlog._file_handler)
        wlog._file_handler.close()


# -- request id ------------------------------------------------------------


def test_request_id_adopt_and_mint():
    # the id lives in this thread's context: given back at the end, or
    # every later test of this process would run under it (a job's
    # spans are told apart by request id, tests/test_ec_push_streams.py)
    before = set_request_id(get_request_id())
    try:
        rid = ensure_request_id("abc123")
        assert rid == "abc123" and get_request_id() == "abc123"
        rid2 = ensure_request_id(None)
        assert rid2 and rid2 != "abc123"
    finally:
        reset_request_id(before)


def test_request_id_propagates_through_cluster(tmp_path):
    """Gateway-in: the id rides X-Request-ID through filer -> volume
    and is echoed on every response (util/request_id middleware +
    outbound-forwarding shape)."""
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.httpd import http_bytes
    from seaweedfs_tpu.server.master_server import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer().start()
    vs = VolumeServer([str(tmp_path / "v0")], master.url,
                      pulse_seconds=0.3).start()
    time.sleep(0.4)
    filer = FilerServer(master.url).start()
    try:
        st, _, h = http_bytes(
            "POST", f"{filer.http.url}/rid/f.txt", b"trace me",
            {"X-Request-ID": "fixed-rid-1"})
        assert st < 300
        assert h.get("X-Request-ID") == "fixed-rid-1"
        # absent id: server mints one and echoes it
        st, _, h = http_bytes("GET", f"{filer.http.url}/rid/f.txt")
        assert h.get("X-Request-ID")
    finally:
        filer.stop()
        vs.stop()
        master.stop()


# -- mem slab pool ---------------------------------------------------------


def test_mem_pool_reuse_and_sizing():
    a = mem.allocate(1500)
    assert len(a) == 1500
    mem.free(a)
    b = mem.allocate(2000)          # same 2KB slab
    assert len(b) == 2000
    assert mem.stats()["reuses"] >= 1
    mem.free(b)
    # tiny and huge fall through / are dropped, never crash
    t = mem.allocate(10)
    mem.free(t)
    assert isinstance(mem.allocate(1), bytearray)


# -- skiplist --------------------------------------------------------------


def test_skiplist_ordered_ops():
    sl = SkipList()
    import random
    keys = [f"k{i:04d}" for i in range(200)]
    shuffled = keys[:]
    random.Random(7).shuffle(shuffled)
    for k in shuffled:
        sl.insert(k, k.upper())
    assert len(sl) == 200
    assert list(sl.keys()) == keys          # in-order despite inserts
    assert sl.get("k0100") == "K0100"
    assert sl.get("missing", "dflt") == "dflt"
    assert "k0042" in sl
    # range scan [start, end)
    window = list(sl.items("k0010", "k0013"))
    assert [k for k, _ in window] == ["k0010", "k0011", "k0012"]
    # overwrite keeps one entry
    sl.insert("k0100", "NEW")
    assert sl.get("k0100") == "NEW" and len(sl) == 200
    # delete
    assert sl.delete("k0100") and not sl.delete("k0100")
    assert sl.get("k0100") is None and len(sl) == 199
    assert sl.first()[0] == "k0000"


def test_skiplist_heights_deterministic_across_processes():
    """ISSUE 13 satellite (advisor round-5 leftover): the documented
    deterministic-tree property was FALSE across processes — heights
    came from the salted builtin hash() for str keys.  Now they come
    from crc32, so a child interpreter with a different PYTHONHASHSEED
    must derive identical towers."""
    import json
    import os
    import subprocess
    import sys

    keys = [f"/bench/w{i}/f{i:04d}" for i in range(64)] + ["", "a",
                                                           "über"]
    ours = [SkipList._height_for(k) for k in keys]
    assert all(1 <= h <= 16 for h in ours)
    assert len(set(ours)) > 1, "degenerate towers: no mixing at all"
    prog = (
        "import json,sys\n"
        "from seaweedfs_tpu.util.skiplist import SkipList\n"
        "keys=json.loads(sys.argv[1])\n"
        "print(json.dumps([SkipList._height_for(k) for k in keys]))\n")
    out = subprocess.run(
        [sys.executable, "-c", prog, json.dumps(keys)],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, PYTHONHASHSEED="12345",
                 JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.dirname(os.path.dirname(
                     os.path.abspath(__file__)))))
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout) == ours, \
        "tower heights diverged across interpreters — hash salt leak"
    # bytes keys ride the same unsalted digest; non-str/bytes may
    # still use hash() (ints are unsalted by design)
    assert SkipList._height_for(b"abc") == \
        SkipList._height_for(b"abc")


# -- bounded executor ------------------------------------------------------


def test_bounded_executor_backpressure():
    import threading
    peak = [0]
    active = [0]
    lock = threading.Lock()

    def work(_):
        with lock:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        time.sleep(0.02)
        with lock:
            active[0] -= 1
        return _

    ex = BoundedExecutor(limit=3)
    futs = [ex.submit(work, i) for i in range(12)]
    assert [f.result() for f in futs] == list(range(12))
    ex.shutdown()
    assert peak[0] <= 3, f"bound violated: {peak[0]}"
    # order-preserving map form; first failure re-raised
    assert bounded_parallel(lambda x: x * 2, range(5), limit=2) == \
        [0, 2, 4, 6, 8]
    with pytest.raises(ZeroDivisionError):
        bounded_parallel(lambda x: 1 // x, [1, 0, 2], limit=2)


# -- mmap volume reads -----------------------------------------------------


def test_volume_mmap_read_path(tmp_path):
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    v = Volume(str(tmp_path), 7, mmap_read_mb=64)
    payloads = {}
    for i in range(1, 20):
        n = Needle(cookie=0x1234, id=i, data=f"blob{i}".encode() * 9)
        v.write_needle(n)
        payloads[i] = n.data
    for i, want in payloads.items():
        assert v.read_needle(i, 0x1234).data == want
    assert v._mm is not None, "mmap path never engaged"
    # growth past the map remaps transparently
    n = Needle(cookie=0x1234, id=99, data=b"appended-after-map" * 20)
    v.write_needle(n)
    assert v.read_needle(99, 0x1234).data == n.data
    # vacuum swaps the .dat: the map must follow the new inode
    v.delete_needle(Needle(cookie=0x1234, id=1))
    v.vacuum()
    with pytest.raises(KeyError):
        v.read_needle(1, 0x1234)
    assert v.read_needle(5, 0x1234).data == payloads[5]
    v.close()
    # disabled by default: no map without the flag
    v2 = Volume(str(tmp_path), 8)
    v2.write_needle(Needle(cookie=1, id=1, data=b"x"))
    v2.read_needle(1, 1)
    assert v2._mm is None
    v2.close()


# -- config layer ----------------------------------------------------------


def test_env_defaults_override_parser():
    p = argparse.ArgumentParser()
    sub = p.add_subparsers(dest="cmd")
    m = sub.add_parser("master")
    m.add_argument("-port", type=int, default=9333)
    m.add_argument("-defaultReplication", default="000")
    m.add_argument("-telemetry", action="store_true")
    env = {"WEED_MASTER_PORT": "19444",
           "WEED_MASTER_DEFAULTREPLICATION": "001",
           "WEED_MASTER_TELEMETRY": "true"}
    applied = wconfig.apply_env_defaults(sub.choices, environ=env)
    assert len(applied) == 3
    args = p.parse_args(["master"])
    assert args.port == 19444
    assert args.defaultReplication == "001"
    assert args.telemetry is True
    # explicit flags still beat the env
    args = p.parse_args(["master", "-port", "1"])
    assert args.port == 1


def test_filer_toml_store_selection(tmp_path):
    toml = tmp_path / "filer.toml"
    toml.write_text('[leveldb2]\nenabled = true\n'
                    'dir = "./meta-ldb"\n\n'
                    '[sqlite]\nenabled = false\n')
    assert wconfig.filer_store_from_toml(str(toml)) == \
        ("lsm", "./meta-ldb")
    toml.write_text('[redis2]\nenabled = true\n'
                    'address = "10.0.0.5:6379"\n')
    assert wconfig.filer_store_from_toml(str(toml)) == \
        ("redis", "10.0.0.5:6379")
    toml.write_text('[sqlite]\nenabled = false\n')
    assert wconfig.filer_store_from_toml(str(toml)) is None


def test_notification_and_replication_toml(tmp_path):
    n = tmp_path / "notification.toml"
    n.write_text('[notification.webhook]\nenabled = true\n'
                 'url = "http://hook:9000/ev"\n')
    assert wconfig.notification_from_toml(str(n)) == \
        "webhook:http://hook:9000/ev"
    n.write_text('[notification.kafka]\nenabled = true\n'
                 'hosts = ["k1:9092"]\ntopic = "meta"\n')
    assert wconfig.notification_from_toml(str(n)) == \
        "kafka:k1:9092/meta"
    r = tmp_path / "replication.toml"
    r.write_text('[sink.s3]\nenabled = true\n'
                 'bucket = "backup"\nendpoint = "s3:8333"\n')
    kind, cfg = wconfig.replication_sink_from_toml(str(r))
    assert kind == "s3" and cfg["bucket"] == "backup"


def test_volume_mmap_survives_compaction_with_diff_replay(tmp_path):
    """Review r5: _makeup_diff's reads may recreate a map of the OLD
    .dat mid-commit; a map surviving the rename would serve
    old-layout bytes at new-layout offsets.  Also covers the remap
    threshold: small fresh tails are handle-served with the map
    intact."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume
    v = Volume(str(tmp_path), 9, mmap_read_mb=64)
    data = {}
    for i in range(1, 12):
        n = Needle(cookie=7, id=i, data=f"pay{i}".encode() * 30)
        v.write_needle(n)
        data[i] = n.data
    assert v.read_needle(3, 7).data == data[3]   # map engaged
    assert v._mm is not None
    v.delete_needle(Needle(cookie=7, id=2))
    data.pop(2)
    v.compact()
    # a write AFTER the snapshot: replayed by makeupDiff in commit
    late = Needle(cookie=7, id=50, data=b"late-diff-write" * 10)
    v.write_needle(late)
    data[50] = late.data
    # force the map to be live right before commit (worst case)
    v.read_needle(5, 7)
    v.commit_compact()
    for i, want in data.items():
        got = v.read_needle(i, 7).data
        assert got == want, f"needle {i} corrupted after compaction"
    # small append after commit: served correctly without remap churn
    n = Needle(cookie=7, id=60, data=b"tail")
    v.write_needle(n)
    mm_before = v._mm
    assert v.read_needle(60, 7).data == b"tail"
    assert v._mm is mm_before, "small tail read must not remap"
    v.close()
