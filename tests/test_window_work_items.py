"""The device encode's work item is one staging window (ISSUE 28): the
buffer the reader fills is the buffer that is put on the device.  Shard
files stay byte-identical to the CPU twin for any row count, the
staging ledger counts every window direct, one compiled shape serves
volumes of any size, a batch of any other size is put whole (ISSUE 29:
one way to the chip, no thread a launch, three env names that are no
longer read), and the overlap is reckoned over the launches of one
encode."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_cpu, staging
from seaweedfs_tpu.ops.rs_jax import (ReedSolomonJax,
                                      gf_apply_matrix_words, pack_words)
from seaweedfs_tpu.storage.erasure_coding import ec_context, ec_encoder
from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

BLOCK = 4096            # a "1MB" small row block, shrunk
LARGE = 8 * BLOCK       # a "1GB" large row block, shrunk
WINDOW = 32 * BLOCK     # 3 rows of RS(10,4), 5 of RS(6,3)
SCHEMES = {"rs10_4": (10, 4, 3), "rs6_3": (6, 3, 5)}
MESH = staging.encode_shardings     # the conftest's 8 devices


@pytest.fixture
def toy(monkeypatch):
    """Toy geometry, and one device to be seen (the mesh is the
    conftest's, and test_an_encode_puts_every_window_direct[1]'s)."""
    monkeypatch.setattr(staging, "WINDOW_BYTES", WINDOW)
    monkeypatch.setattr(staging, "encode_shardings",
                        lambda: (None, None, 1))
    monkeypatch.setattr(ec_encoder, "SMALL_BLOCK_SIZE", BLOCK)
    staging.reset_aggregate()
    return monkeypatch


def _write_dat(path, size: int, seed: int) -> bytes:
    blob = np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    with open(path, "wb") as f:
        f.write(blob)
    return blob


def _twin_shards(blob: bytes, k: int, r: int,
                 large: int) -> "list[bytes]":
    """Shard files by ops/rs_cpu straight from the geometry (large rows
    while a whole one is left, then small rows zero-filled past EOF),
    nothing of the file pipeline used."""
    blocks = []                             # [k, block] per row
    pos = 0
    while len(blob) - pos >= large * k:
        blocks.append(np.frombuffer(
            blob, np.uint8, large * k, pos).reshape(k, large))
        pos += large * k
    tail = blob[pos:]
    tail += bytes(-len(tail) % (BLOCK * k))
    blocks += list(np.frombuffer(tail, np.uint8).reshape(-1, k, BLOCK))
    data = np.concatenate(blocks, axis=1)
    parity = np.asarray(rs_cpu.ReedSolomonCPU(k, r).parity(data))
    return [row.tobytes() for row in data] + \
        [row.tobytes() for row in parity]


def _assert_identical(base, blob: bytes, k: int, r: int,
                      large: int = ec_context.LARGE_BLOCK_SIZE) -> None:
    for i, want in enumerate(_twin_shards(blob, k, r, large)):
        with open(f"{base}.ec{i:02d}", "rb") as f:
            assert f.read() == want, f"shard {i}"


# -- (a) byte-identical for any row count, both schemes, both paths ---------

@pytest.mark.parametrize("rows", ["one", "window-1", "window",
                                  "window+1", "2window+1"])
@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_shards_match_the_cpu_twin_on_both_sides_of_a_window(
        toy, tmp_path, scheme, rows):
    k, r, per = SCHEMES[scheme]
    ctx = ECContext(k, r, backend="jax")
    assert ctx.rows_per_launch(BLOCK) == per
    n = {"one": 1, "window-1": per - 1, "window": per,
         "window+1": per + 1, "2window+1": 2 * per + 1}[rows]
    size = (n - 1) * k * BLOCK + 5_001          # a short last row
    blob = _write_dat(tmp_path / "v.dat", size, seed=n)
    ec_encoder._generate_ec_files(str(tmp_path / "v"), ctx)
    _assert_identical(tmp_path / "v", blob, k, r)
    snap = staging.snapshot()
    assert snap["launches"] == -(-n // per)
    assert snap["direct_windows"] == snap["windows"] == snap["launches"]
    assert snap["payload_bytes"] == size
    assert snap["h2d_bytes"] == snap["launches"] * per * k * BLOCK


@pytest.mark.parametrize("scheme", sorted(SCHEMES))
def test_shards_match_the_cpu_twin_on_the_large_row_path(
        toy, tmp_path, scheme):
    """A whole large row is chunked WITHIN its blocks: the largest
    chunk that divides the block and fits the window, each chunk one
    direct window; the small rows after it as above."""
    toy.setattr(ec_encoder, "LARGE_BLOCK_SIZE", LARGE)
    k, r, per = SCHEMES[scheme]
    ctx = ECContext(k, r, backend="jax")
    chunk = ctx.batch_size(LARGE)
    assert LARGE % chunk == 0 and k * chunk <= WINDOW
    assert k * 2 * chunk > WINDOW or chunk == LARGE
    size = LARGE * k + (per + 1) * k * BLOCK + 777
    blob = _write_dat(tmp_path / "v.dat", size, seed=k)
    ec_encoder._generate_ec_files(str(tmp_path / "v"), ctx)
    _assert_identical(tmp_path / "v", blob, k, r, LARGE)
    snap = staging.snapshot()
    assert snap["launches"] == LARGE // chunk + 2
    assert snap["direct_windows"] == snap["windows"] == snap["launches"]


# -- (b) what the ledger says of an encode ----------------------------------

@pytest.mark.parametrize("mesh", ["0", "1"])
def test_an_encode_puts_every_window_direct(toy, tmp_path, mesh):
    """No pack: every window is the reader's buffer, the pack clock
    has nothing to read, and the padding is less than one window's
    rows a volume.  On the conftest's 8-device mesh too: a window's
    words divide the mesh."""
    if mesh == "1":
        toy.setattr(staging, "encode_shardings", MESH)
        assert MESH()[2] == 8
    k, r, per = SCHEMES["rs10_4"]
    n = 7                                       # 3 + 3 + 1
    _write_dat(tmp_path / "v.dat", n * k * BLOCK, seed=2)
    ec_encoder.write_ec_files(str(tmp_path / "v"),
                              ECContext(k, r, backend="jax"))
    snap = staging.snapshot()
    assert snap["windows"] == snap["direct_windows"] == 3
    assert snap["pack_seconds"] == 0 < snap["h2d_seconds"]
    assert snap["payload_bytes"] / snap["h2d_bytes"] >= \
        1 - (per - 1) / n


# -- (c) one compiled shape, whatever the volume's size ---------------------

_COMPILES = """
import json, sys
import numpy as np
from seaweedfs_tpu.ops import staging
from seaweedfs_tpu.storage.erasure_coding import ec_context, ec_encoder
ec_context.own_device()
ec_encoder.SMALL_BLOCK_SIZE = 4096
staging.WINDOW_BYTES = 32 * 4096
ctx = ec_context.ECContext(backend="jax")
seen = []
for i, rows in enumerate((4, 7, 11)):
    base = sys.argv[1] + "/v%d" % i
    with open(base + ".dat", "wb") as f:
        f.write(np.random.default_rng(i).integers(
            0, 256, (rows - 1) * 10 * 4096 + 999 * (i + 1),
            dtype=np.uint8).tobytes())
    ec_encoder.write_ec_files(base, ctx)
    seen.append(ec_context.compile_ledger()["requests"])
print(json.dumps(seen))
"""


def test_volumes_of_three_sizes_compile_once(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-c", _COMPILES, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    first, second, third = json.loads(out.stdout.strip().splitlines()[-1])
    assert first >= 1               # the window's shape, compiled once
    assert first == second == third


# -- (d) a batch of any other size is put whole ------------------------------

def test_a_wide_batch_is_still_cut_packed_and_correct(toy):
    """Since ISSUE 29 neither cut nor packed: three windows' worth
    goes in one put, as parity() has always sent it."""
    data = np.random.default_rng(5).integers(
        0, 256, size=(10, 3 * 3 * BLOCK + 10), dtype=np.uint8)
    pend = ReedSolomonJax(10, 4).parity_lazy(data)
    np.testing.assert_array_equal(
        pend.materialize(), rs_cpu.ReedSolomonCPU(10, 4).parity(data))
    snap = staging.snapshot()
    assert snap["launches"] == snap["windows"] == 1
    assert snap["direct_windows"] == 1 and snap["pack_seconds"] == 0
    assert snap["h2d_bytes"] == 10 * (3 * 3 * BLOCK + 12)
    # and a strided view, not a buffer of its own, is put whole too
    staging.reset_aggregate()
    view = pack_words(data)[:, :3 * BLOCK // 4]
    assert not view.flags.c_contiguous
    rs = ReedSolomonJax(10, 4)
    got = staging.WindowedLaunch(rs._parity_rows, view,
                                 gf_apply_matrix_words, 4,
                                 3 * BLOCK).materialize()
    np.testing.assert_array_equal(
        got, rs_cpu.ReedSolomonCPU(10, 4).parity(data[:, :3 * BLOCK]))
    snap = staging.snapshot()
    assert snap["windows"] == 1 and snap["pack_seconds"] == 0


# -- (d') three names nobody reads, and no thread a launch ------------------

def _encode_of(tmp_path, name: str, rows: int, **kw) -> dict:
    """Encode a volume of `rows` toy rows of RS(10,4); the shard files'
    bytes by extension and the ledger of it."""
    staging.reset_aggregate()
    _write_dat(tmp_path / f"{name}.dat", rows * 10 * BLOCK - 99, seed=9)
    ec_encoder._generate_ec_files(str(tmp_path / name),
                                  ECContext(backend="jax"), **kw)
    shards = {i: (tmp_path / f"{name}.ec{i:02d}").read_bytes()
              for i in range(14)}
    counts = {k: v for k, v in staging.snapshot().items()
              if isinstance(v, int)}
    return {"shards": shards, "counts": counts}


@pytest.mark.parametrize("name", ["SEAWEEDFS_TPU_H2D_WINDOW_MB",
                                  "SEAWEEDFS_TPU_H2D_INFLIGHT",
                                  "SEAWEEDFS_TPU_ENCODE_MESH"])
def test_the_three_old_knobs_are_no_longer_read(toy, tmp_path, name):
    toy.delenv(name, raising=False)
    want = _encode_of(tmp_path, "unset", 7)
    assert want["counts"]["launches"] == want["counts"]["windows"] == 3
    for value in ("0", "junk"):
        toy.setenv(name, value)
        assert _encode_of(tmp_path, f"set-{value}", 7) == want
        assert staging.Run().inflight == 2


class _Full(OSError):
    pass


@pytest.mark.parametrize("writer", ["writes", "raises on the third"])
def test_an_encode_leaves_no_thread_behind(toy, tmp_path, writer):
    """Ten work items, none with a thread of its own: as many threads
    live after the encode as before it, and after one whose writer
    gave way under launches already made (those are device arrays
    nobody fetches; the writer's error is the one that rises)."""
    seen = []

    def progress(_done, _total):        # on the writer's thread
        seen.append(threading.active_count())
        if writer != "writes" and len(seen) == 3:
            raise _Full("no space left on the toy device")
    before = threading.active_count()
    if writer == "writes":
        got = _encode_of(tmp_path, "v", 30, progress=progress)
        assert got["counts"]["launches"] == 10 == len(seen)
        # reader, writer, the sinks' flusher: the pipeline's own three
        assert max(seen) <= before + 3
    else:
        with pytest.raises(_Full):
            _encode_of(tmp_path, "v", 30, progress=progress)
        assert len(seen) == 3
        assert not [f for f in os.listdir(tmp_path) if ".ec" in f]
    assert threading.active_count() == before


def test_a_traced_rehearsal_reads_every_staging_metric_as_a_number(
        capfd, monkeypatch):
    """Seven readers under benchmark/metrics/ index the ledger's keys
    and are not this repo's to edit with the program: on a traced
    rehearsal of the encode cell each still prints a number, and the
    three whose mechanism went print 0."""
    from benchmark import run
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = run.main(["--workload", "ec10_4_vol1g.encode", "--seed",
                     "2147900003", "--seconds", "2", "--trace", "1",
                     "--rehearse"])
    out = capfd.readouterr().out
    assert code == 0, out[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    m = {n[len("rehearsal."):]: v["value"]
         for n, v in line["metrics"].items()}
    for name in ("staged_h2d_GBps", "staging_overlap_fraction",
                 "staging_launch_ratio", "staging_pad_share",
                 "staging_pack_share", "staging_slot_wait_s",
                 "staging_ready_wait_s"):
        assert isinstance(m[name], (int, float)), name
    assert m["staging_pack_share"] == m["staging_slot_wait_s"] == \
        m["staging_ready_wait_s"] == 0
    assert m["staged_h2d_GBps"] > 0 and m["staging_launch_ratio"] >= 1
    assert 0 < m["staging_pad_share"] < 1
    assert m["job_encode_s"] > 0 and m["enc_write_busy_s"] > 0


# -- (e) the overlap of one encode ------------------------------------------

class _SlowFetch:
    """A kernel output whose fetch takes its time."""

    def __init__(self, out, seconds):
        self._out, self._seconds = out, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._seconds)
        return np.asarray(self._out)


def _run_of(launches: int, serial: bool, monkeypatch) -> dict:
    """One Run of `launches` windows whose put and fetch take 30 ms
    each; serial: each is consumed before the next is made.  Ahead,
    the fetches run on a thread of their own, as the encoder's writer
    does, while this one makes the launches."""
    import jax
    put = jax.device_put

    def slow_put(x, *a, **kw):
        time.sleep(0.03)
        return put(x, *a, **kw)
    monkeypatch.setattr(jax, "device_put", slow_put)

    def kernel(mat, window):
        return _SlowFetch(gf_apply_matrix_words(mat, window), 0.03)
    rs = ReedSolomonJax(10, 4)
    bufs = [pack_words(np.random.default_rng(i).integers(
        0, 256, size=(10, 3 * BLOCK), dtype=np.uint8))
        for i in range(launches)]
    staging.reset_aggregate()
    run = staging.Run("encode")

    def make(buf):
        return staging.WindowedLaunch(rs._parity_rows, buf, kernel, 4,
                                      3 * BLOCK, run=run)
    if serial:
        for buf in bufs:
            make(buf).materialize()
    else:
        import queue
        made: "queue.Queue" = queue.Queue()
        fetcher = threading.Thread(target=lambda: [
            launch.materialize() for launch in iter(made.get, None)])
        fetcher.start()
        for buf in bufs:
            made.put(make(buf))
        made.put(None)
        fetcher.join(timeout=30)
        assert not fetcher.is_alive()
    assert staging.snapshot()["overlap_denom"] == 0     # not yet closed
    run.close()
    return staging.snapshot()


def test_the_overlap_is_reckoned_over_the_launches_of_one_encode(toy):
    ahead = _run_of(4, serial=False, monkeypatch=toy)
    assert ahead["direct_windows"] == ahead["windows"] == 4
    assert ahead["overlap_denom"] > 0.05
    assert ahead["overlap_fraction"] > 0.3
    serial = _run_of(4, serial=True, monkeypatch=toy)
    assert serial["overlap_denom"] > 0.05       # there was room for it
    assert serial["overlap_numer"] == 0 == serial["overlap_fraction"]
