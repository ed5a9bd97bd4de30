"""EC file-pipeline tests: encode/decode/rebuild round-trips and golden
runs against the reference's checked-in volume fixture (the analog of
storage/erasure_coding/ec_roundtrip_test.go + ec_test.go, SURVEY §4.1).

Block sizes are scaled down (large=4KB, small=1KB) the same way the
reference's own unit tests do (ec_test.go uses small buffers) — the
geometry math is size-parameterized.  Golden tests run the REAL block
sizes over the reference's 2.5MB fixture volume.
"""

import os
import shutil

import numpy as np
import pytest

from seaweedfs_tpu.storage import idx as idxmod
from seaweedfs_tpu.storage import types
from seaweedfs_tpu.storage.erasure_coding import (
    ECContext, EcVolume, ec_encoder, ec_decoder, locate_data)
from seaweedfs_tpu.storage.erasure_coding.ec_encoder import (
    rebuild_ec_files, save_ec_volume_info, write_ec_files,
    write_sorted_file_from_idx)
from seaweedfs_tpu.storage.erasure_coding.ec_decoder import (
    find_dat_file_size, has_live_needles, write_dat_file,
    write_idx_file_from_ec_index)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume

REF_EC = "/root/reference/weed/storage/erasure_coding"
needs_ref = pytest.mark.skipif(
    not os.path.exists(f"{REF_EC}/1.dat"),
    reason="reference fixtures not mounted")


def small_ctx(**kw):
    return ECContext(**kw)


@pytest.fixture
def patched_blocks(monkeypatch):
    """Scale block geometry down so tests cover multi-row layouts fast."""
    from seaweedfs_tpu.storage import erasure_coding as ec
    for mod in (ec.ec_encoder, ec.ec_decoder, ec.ec_volume):
        monkeypatch.setattr(mod, "LARGE_BLOCK_SIZE", 4096)
        monkeypatch.setattr(mod, "SMALL_BLOCK_SIZE", 1024)
    return 4096, 1024


def _make_volume(tmp_path, vid=5, n_files=40, seed=0):
    v = Volume(str(tmp_path), vid)
    rng = np.random.default_rng(seed)
    for i in range(n_files):
        size = int(rng.integers(10, 3000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=i + 1, id=i + 1, data=data))
    v.close()
    return str(tmp_path / f"{vid}")


def test_locate_data_basic():
    # 2 large rows + small rows tail, d=10
    large, small, d = 1 << 30, 1 << 20, 10
    shard_size = 2 * large + 3 * small
    ivs = locate_data(large, small, shard_size, 0, 100, d)
    assert len(ivs) == 1 and ivs[0].is_large_block
    sid, off = ivs[0].to_shard_id_and_offset(large, small, d)
    assert (sid, off) == (0, 0)
    # crosses a large-block boundary
    ivs = locate_data(large, small, shard_size, large - 10, 20, d)
    assert [iv.size for iv in ivs] == [10, 10]
    assert ivs[0].block_index == 0 and ivs[1].block_index == 1
    # into the small-block area
    off0 = 20 * large  # past all large rows
    ivs = locate_data(large, small, shard_size, off0 + 1500, 100, d)
    assert not ivs[0].is_large_block


def test_encode_decode_roundtrip(tmp_path, patched_blocks):
    base = _make_volume(tmp_path, vid=5)
    ctx = ECContext(backend="cpu")
    write_sorted_file_from_idx(base)
    write_ec_files(base, ctx)
    orig = open(base + ".dat", "rb").read()
    version = ec_decoder.read_ec_volume_version(base)
    save_ec_volume_info(base, ctx, len(orig), version)
    # all 14 shard files exist with equal sizes
    sizes = {os.path.getsize(base + ctx.to_ext(i)) for i in range(ctx.total)}
    assert len(sizes) == 1
    # decode back into .dat, byte-compare
    dec_base = str(tmp_path / "decoded")
    write_dat_file(dec_base, len(orig),
                   [base + ctx.to_ext(i) for i in range(10)])
    assert open(dec_base + ".dat", "rb").read() == orig


def test_rebuild_missing_shards(tmp_path, patched_blocks):
    base = _make_volume(tmp_path, vid=6)
    ctx = ECContext(backend="cpu")
    write_ec_files(base, ctx)
    golden = {i: open(base + ctx.to_ext(i), "rb").read()
              for i in range(ctx.total)}
    save_ec_volume_info(base, ctx, os.path.getsize(base + ".dat"), 3)
    # destroy two data shards and one parity shard => still rebuildable
    for sid in (0, 7, 12):
        os.remove(base + ctx.to_ext(sid))
    generated = rebuild_ec_files(base)
    assert generated == [0, 7, 12]
    for sid in (0, 7, 12):
        assert open(base + ctx.to_ext(sid), "rb").read() == golden[sid]
    # too few shards -> error
    for sid in range(5):
        os.remove(base + ctx.to_ext(sid))
    os.remove(base + ctx.to_ext(13))
    with pytest.raises(ValueError, match="not enough shards"):
        rebuild_ec_files(base)


def test_ecx_idx_roundtrip_with_deletes(tmp_path, patched_blocks):
    base = _make_volume(tmp_path, vid=7, n_files=20)
    ctx = ECContext(backend="cpu")
    write_sorted_file_from_idx(base)
    write_ec_files(base, ctx)
    save_ec_volume_info(base, ctx, os.path.getsize(base + ".dat"), 3)
    ev = EcVolume(str(tmp_path), 7)
    assert ev.shard_ids == list(range(14))
    # ecx binary search finds every live needle
    for key in (1, 10, 20):
        off, size = ev.search_sorted_index(key)
        assert types.size_is_valid(size)
    # delete via tombstone + journal
    ev.delete_needle(10)
    _, size = ev.search_sorted_index(10)
    assert size == types.TOMBSTONE_FILE_SIZE
    assert list(ec_decoder.iterate_ecj_file(base)) == [10]
    assert has_live_needles(base)
    # .ecx + .ecj -> .idx : tombstone appended
    os.remove(base + ".idx")
    write_idx_file_from_ec_index(base)
    entries = list(idxmod.walk_index(open(base + ".idx", "rb").read()))
    assert entries[-1][0] == 10
    assert entries[-1][2] == types.TOMBSTONE_FILE_SIZE
    ev.close()


def test_ec_volume_read_needles(tmp_path, patched_blocks):
    base = _make_volume(tmp_path, vid=8, n_files=30, seed=3)
    v = Volume(str(tmp_path), 8)
    originals = {i: v.read_needle(i).data for i in range(1, 31)}
    v.close()
    ctx = ECContext(backend="cpu")
    write_sorted_file_from_idx(base)
    write_ec_files(base, ctx)
    save_ec_volume_info(base, ctx, os.path.getsize(base + ".dat"), 3)
    ev = EcVolume(str(tmp_path), 8)
    for i, want in originals.items():
        got = ev.read_needle_local(i)
        assert got.data == want, f"needle {i}"
    ev.close()


def test_find_dat_file_size(tmp_path, patched_blocks):
    base = _make_volume(tmp_path, vid=9, n_files=10)
    ctx = ECContext(backend="cpu")
    write_sorted_file_from_idx(base)
    write_ec_files(base, ctx)
    assert find_dat_file_size(base, base) == os.path.getsize(base + ".dat")


# --- golden runs over the reference fixture (real 1GB/1MB geometry) -----

@needs_ref
def test_golden_encode_reference_volume(tmp_path):
    """Encode the reference's real 2.5MB volume with REAL block sizes:
    3 small rows; verify shard sizes, decode-back byte-identity, and
    needle readability through the EC read path."""
    base = str(tmp_path / "1")
    shutil.copy(f"{REF_EC}/1.dat", base + ".dat")
    shutil.copy(f"{REF_EC}/1.idx", base + ".idx")
    ctx = ECContext(backend="cpu")
    write_sorted_file_from_idx(base)
    write_ec_files(base, ctx)
    dat_size = os.path.getsize(base + ".dat")
    save_ec_volume_info(base, ctx, dat_size,
                        ec_decoder.read_ec_volume_version(base))
    shard_size = os.path.getsize(base + ".ec00")
    import math
    want = math.ceil(dat_size / (10 * 1024 * 1024)) * 1024 * 1024
    assert shard_size == want, (shard_size, want)
    # decode back
    dec = str(tmp_path / "dec")
    write_dat_file(dec, dat_size, [base + ctx.to_ext(i) for i in range(10)])
    assert open(dec + ".dat", "rb").read() == \
        open(base + ".dat", "rb").read()
    # rebuild 2 lost data shards + read needles through EC path
    golden5 = open(base + ".ec05", "rb").read()
    os.remove(base + ".ec05")
    os.remove(base + ".ec11")
    assert rebuild_ec_files(base) == [5, 11]
    assert open(base + ".ec05", "rb").read() == golden5
    ev = EcVolume(str(tmp_path), 1)
    live = [(k, s) for k, _, s in ev.walk_index()
            if types.size_is_valid(s)]
    assert live
    n = ev.read_needle_local(live[0][0])
    assert len(n.data) > 0
    ev.close()


@needs_ref
def test_golden_jax_backend_matches_cpu(tmp_path):
    """TPU-kernel backend produces byte-identical shards to the CPU twin
    on the reference fixture (cross-implementation parity, SURVEY §4.3)."""
    for backend in ("cpu", "jax"):
        d = tmp_path / backend
        d.mkdir()
        base = str(d / "1")
        shutil.copy(f"{REF_EC}/1.dat", base + ".dat")
        write_ec_files(base, ECContext(backend=backend))
    for i in range(14):
        a = open(tmp_path / "cpu" / f"1.ec{i:02d}", "rb").read()
        b = open(tmp_path / "jax" / f"1.ec{i:02d}", "rb").read()
        assert a == b, f"shard {i} differs between cpu and jax backends"


def _merge_intervals(ivs):
    out = []
    for start, length in sorted(ivs):
        if out and out[-1][0] + out[-1][1] == start:
            out[-1][1] += length
        else:
            out.append([start, length])
    return [(s, n) for s, n in out]


@pytest.mark.parametrize("backend", ["cpu", "jax"])
def test_encode_work_items_tile_exactly(backend):
    """Property test: for arbitrary dat_size the work schedule tiles
    the volume exactly — every shard's strided blocks covered once
    with no gap and no overlap, and the writer emits exactly the ceil
    geometry (n_large*1GB + ceil(tail/row)*1MB per shard).  Fuzzed
    over sizes straddling the 1GB-row and 1MB-row boundaries; pure
    index arithmetic, no bytes are allocated.  The device codec's
    items are one staging window each: every batch of small rows has
    the one shape, the tail's too, and a chunk of a large row divides
    the block and fits the window."""
    from seaweedfs_tpu.ops import staging
    from seaweedfs_tpu.storage.erasure_coding.ec_context import (
        LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE)
    from seaweedfs_tpu.storage.erasure_coding.ec_encoder import (
        _encode_work_items)
    ctx = ECContext(backend=backend)
    d = ctx.data_shards
    large_row = LARGE_BLOCK_SIZE * d
    small_row = SMALL_BLOCK_SIZE * d
    rng = np.random.default_rng(17)
    sizes = {1, 2, 1023, SMALL_BLOCK_SIZE, SMALL_BLOCK_SIZE + 1,
             small_row - 1, small_row, small_row + 1,
             37 * small_row + 12345,
             large_row - 1, large_row, large_row + 1,
             large_row + small_row - 1, large_row + small_row,
             2 * large_row + 3 * small_row + 777}
    sizes.update(int(rng.integers(1, 3 * large_row)) for _ in range(20))
    for dat_size in sorted(sizes):
        work = _encode_work_items(dat_size, ctx)
        n_large = dat_size // large_row
        tail = dat_size - n_large * large_row
        n_small = -(-tail // small_row)
        # expected coverage of shard 0 and shard d-1 (strided blocks)
        for shard in (0, d - 1):
            expect = [(r * large_row + shard * LARGE_BLOCK_SIZE,
                       LARGE_BLOCK_SIZE) for r in range(n_large)]
            expect += [(n_large * large_row + k * small_row +
                        shard * SMALL_BLOCK_SIZE, SMALL_BLOCK_SIZE)
                       for k in range(n_small)]
            got = []
            for row_start, block, b0, batch, real_rows in work:
                assert batch > 0 and real_rows >= 1
                if batch <= block:  # chunk WITHIN one row (the reader
                    # gathers the d strided slices at b0; a lone small
                    # row with batch == block takes this branch too)
                    assert real_rows == 1
                    assert b0 + batch <= block
                    if block == SMALL_BLOCK_SIZE:
                        assert b0 == 0 and batch == block
                    else:
                        assert block == LARGE_BLOCK_SIZE
                    got.append((row_start + shard * block + b0, batch))
                else:               # aggregated small rows
                    assert block == SMALL_BLOCK_SIZE and b0 == 0
                    assert batch % block == 0  # whole padded rows
                    assert real_rows * block <= batch
                    assert batch == ctx.rows_per_launch(block) * block
                    got += [(row_start + r * small_row + shard * block,
                             block) for r in range(real_rows)]
                if backend == "jax":    # one window, never wider
                    assert d * batch <= staging.WINDOW_BYTES
                    assert block % batch == 0 or batch % block == 0
            assert _merge_intervals(got) == _merge_intervals(expect), \
                f"dat_size={dat_size} shard={shard}"
        # writer geometry: per-shard output bytes == ceil geometry
        written = sum(min(batch, real_rows * block)
                      for _rs, block, _b0, batch, real_rows in work)
        assert written == n_large * LARGE_BLOCK_SIZE + \
            n_small * SMALL_BLOCK_SIZE, f"dat_size={dat_size}"


def test_encode_pipeline_compute_error_no_deadlock(tmp_path, monkeypatch):
    """A compute-stage failure must propagate promptly — not deadlock
    the reader parked on a full staging queue (review regression)."""
    import threading

    import numpy as np

    from seaweedfs_tpu.storage.erasure_coding import ec_encoder
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    base = str(tmp_path / "boom")
    # 4 small rows -> 4 work items, so the 2nd parity call exists
    data = np.random.default_rng(3).integers(
        0, 256, 32 * 1024 * 1024, dtype=np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(data.tobytes())

    class BoomCodec:
        calls = 0

        def parity(self, buf):
            BoomCodec.calls += 1
            if BoomCodec.calls >= 2:
                raise RuntimeError("device exploded")
            return np.zeros((4, buf.shape[1]), dtype=np.uint8)

    ctx = ECContext(backend="cpu")
    monkeypatch.setattr(ECContext, "create_codec",
                        lambda self: BoomCodec())

    result: list = []

    def run():
        try:
            ec_encoder.write_ec_files(base, ctx)
            result.append(None)
        except RuntimeError as e:
            result.append(e)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=20)
    assert not t.is_alive(), "encode pipeline deadlocked on compute error"
    assert result and isinstance(result[0], RuntimeError)


def test_row_aggregated_encode_byte_identical(tmp_path, patched_blocks,
                                              monkeypatch):
    """Stacking many small-block rows into one codec launch
    (ECContext.rows_per_launch > 1, the round-3 dispatch-amortization
    fix) must produce byte-identical shard files to encoding one row
    per launch — the shard-file layout is the in-order concatenation of
    row blocks either way.  Covers: a large row, a run of aggregated
    small rows, a tail group of fewer rows than a launch holds, and
    zero-padding past EOF inside the final row."""
    d_agg = tmp_path / "agg"
    d_one = tmp_path / "one"
    d_agg.mkdir()
    d_one.mkdir()
    base_agg = _make_volume(d_agg, n_files=60, seed=9)
    base_one = str(d_one / "5")
    shutil.copy(base_agg + ".dat", base_one + ".dat")

    ctx = ECContext(backend="cpu")
    assert ctx.rows_per_launch(1024) > 1  # aggregation engages
    write_ec_files(base_agg, ctx)

    monkeypatch.setattr(ECContext, "rows_per_launch",
                        lambda self, block_size: 1)
    write_ec_files(base_one, ECContext(backend="cpu"))

    for i in range(14):
        a = open(base_agg + f".ec{i:02d}", "rb").read()
        b = open(base_one + f".ec{i:02d}", "rb").read()
        assert a == b, f"shard {i} differs: aggregated vs one-row"
