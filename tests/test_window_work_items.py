"""The device encode's work item is one staging window (ISSUE 28): the
buffer the reader fills is the buffer that is put on the device.  Shard
files stay byte-identical to the CPU twin for any row count, the
staging ledger counts every window direct, one compiled shape serves
volumes of any size, a batch wider than a window is still cut and
packed, and the overlap is reckoned over the launches of one encode."""

import json
import os
import subprocess
import sys
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
WINDOW_MB = "0.125"     # 32 blocks: 3 rows of RS(10,4), 5 of RS(6,3)
SCHEMES = {"rs10_4": (10, 4, 3), "rs6_3": (6, 3, 5)}


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", WINDOW_MB)
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_INFLIGHT", "2")
    monkeypatch.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "0")
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
    assert LARGE % chunk == 0 and k * chunk <= staging.window_bytes()
    assert k * 2 * chunk > staging.window_bytes() or chunk == LARGE
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
    reads the glance it took to see that, and the padding is less than
    one window's rows a volume.  On the conftest's 8-device mesh too: a
    window's words divide the mesh."""
    toy.setenv("SEAWEEDFS_TPU_ENCODE_MESH", mesh)
    k, r, per = SCHEMES["rs10_4"]
    n = 7                                       # 3 + 3 + 1
    _write_dat(tmp_path / "v.dat", n * k * BLOCK, seed=2)
    ec_encoder.write_ec_files(str(tmp_path / "v"),
                              ECContext(k, r, backend="jax"))
    snap = staging.snapshot()
    assert snap["windows"] == snap["direct_windows"] == 3
    assert 0 < snap["pack_seconds"] < 1e-3 * snap["windows"]
    assert snap["pack_seconds"] < snap["h2d_seconds"]
    assert snap["payload_bytes"] / snap["h2d_bytes"] >= \
        1 - (per - 1) / n


# -- (c) one compiled shape, whatever the volume's size ---------------------

_COMPILES = """
import json, sys
import numpy as np
from seaweedfs_tpu.storage.erasure_coding import ec_context, ec_encoder
ec_context.own_device()
ec_encoder.SMALL_BLOCK_SIZE = 4096
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
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               SEAWEEDFS_TPU_H2D_WINDOW_MB=WINDOW_MB,
               SEAWEEDFS_TPU_ENCODE_MESH="0")
    out = subprocess.run(
        [sys.executable, "-c", _COMPILES, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    first, second, third = json.loads(out.stdout.strip().splitlines()[-1])
    assert first >= 1               # the window's shape, compiled once
    assert first == second == third


# -- (d) a batch wider than a window is still cut and packed ----------------

def test_a_wide_batch_is_still_cut_packed_and_correct(toy):
    data = np.random.default_rng(5).integers(
        0, 256, size=(10, 3 * 3 * BLOCK + 10), dtype=np.uint8)
    pend = ReedSolomonJax(10, 4).parity_lazy(data)
    np.testing.assert_array_equal(
        pend.materialize(), rs_cpu.ReedSolomonCPU(10, 4).parity(data))
    snap = staging.snapshot()
    assert snap["launches"] == 1 and snap["windows"] >= 3
    assert snap["direct_windows"] == 0
    assert snap["pack_seconds"] > 0
    # and one window's worth that is a strided view, not a buffer of
    # its own, is packed too: the stager puts only what stands whole
    staging.reset_aggregate()
    view = pack_words(data)[:, :3 * BLOCK // 4]
    rs = ReedSolomonJax(10, 4)
    got = staging.WindowedLaunch(rs._parity_rows, view,
                                 gf_apply_matrix_words, 4,
                                 3 * BLOCK).materialize()
    np.testing.assert_array_equal(
        got, rs_cpu.ReedSolomonCPU(10, 4).parity(data[:, :3 * BLOCK]))
    assert staging.snapshot()["direct_windows"] == 0


# -- (e) the overlap of one encode ------------------------------------------

class _SlowFetch:
    """A kernel output whose fetch takes its time."""

    def __init__(self, out, seconds):
        self._out, self._seconds = out, seconds

    def __array__(self, dtype=None, copy=None):
        time.sleep(self._seconds)
        return np.asarray(self._out)


def _run_of(launches: int, serial: bool, monkeypatch) -> dict:
    """One Run of `launches` direct windows whose put and fetch take
    30 ms each; serial: each is consumed before the next is made."""
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
        made = [make(buf) for buf in bufs]  # the puts go ahead at once
        for launch in made:
            launch.materialize()
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
