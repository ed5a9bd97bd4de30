"""Staged h2d + mesh-sharded encode: byte-identity and plumbing.

Tier-1 on the conftest's 8 virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8): a lazy launch —
placed across the mesh where its words divide the device count, plain
where they do not — must be byte-identical to the one-call `parity()`
path and to the CPU twin, whatever the batch's size."""

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ops import rs_cpu, rs_matrix, staging
from seaweedfs_tpu.ops.rs_jax import ReedSolomonJax

D, P = 10, 4


@pytest.fixture
def knobs(monkeypatch):
    """A tiny window (so a toy volume is many work items) on the
    8-device conftest mesh."""
    monkeypatch.setattr(staging, "WINDOW_BYTES", 2048)
    staging.reset_aggregate()
    return monkeypatch


@pytest.fixture
def placed(monkeypatch):
    """The shardings every device_put of a [D, W] batch was given."""
    seen = []
    put = jax.device_put

    def spy(x, device=None, **kw):
        if getattr(x, "ndim", 0) == 2 and x.shape[0] == D:
            seen.append(device)
        return put(x, device, **kw)
    monkeypatch.setattr(jax, "device_put", spy)
    return seen


def _data(nbytes: int, rows: int = D, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, size=(rows, nbytes), dtype=np.uint8)


# -- unit: the mesh's shardings --------------------------------------------

def test_mesh_shardings_on_conftest_mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 devices"
    batch_sh, repl_sh, ndev = staging.encode_shardings()
    assert ndev == 8 and batch_sh is not None
    spec = batch_sh.spec
    assert tuple(spec) == (None, "batch")
    assert tuple(repl_sh.spec) == ()


# -- byte-identity: windowed / mesh vs single-shot / CPU twin -------------

def test_windowed_matches_single_shot_and_cpu(knobs):
    """Uneven everything: payload not a multiple of 4 (pack padding),
    a batch many times the window, put whole: the lazy launch against
    the one-call parity() and the CPU twin."""
    nbytes = 40_003
    data = _data(nbytes, seed=1)
    want = rs_cpu.ReedSolomonCPU(D, P).parity(data)
    codec = ReedSolomonJax(D, P)
    pend = codec.parity_lazy(data)
    got = pend.materialize()
    assert got.shape == (P, nbytes) and got.dtype == np.uint8
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(codec.parity(data)), want)


def test_mesh_sharded_matches_single_device(knobs, placed):
    """The same batch placed across the 8-device mesh and, with one
    device to be seen, plain: the same bytes."""
    data = _data(4 * 1000, seed=2)
    codec = ReedSolomonJax(D, P)
    mesh_out = codec.parity_lazy(data).materialize()
    knobs.setattr(staging, "encode_shardings", lambda: (None, None, 1))
    single_out = codec.parity_lazy(data).materialize()
    assert placed[0] is not None and placed[1] is None
    np.testing.assert_array_equal(mesh_out, single_out)
    np.testing.assert_array_equal(
        mesh_out, rs_cpu.ReedSolomonCPU(D, P).parity(data))


@pytest.mark.parametrize("words", [1024, 1001])
def test_a_window_is_placed_by_whether_its_words_divide_the_mesh(
        placed, words):
    """ISSUE 29: 1024 words divide the 8 devices and are split across
    them; 1001 do not and go plain to the default device, unpadded."""
    staging.reset_aggregate()
    data = _data(4 * words, seed=words)
    got = ReedSolomonJax(D, P).parity_lazy(data).materialize()
    np.testing.assert_array_equal(
        got, rs_cpu.ReedSolomonCPU(D, P).parity(data))
    if words % 8:
        assert placed == [None]
    else:
        assert placed == [staging.encode_shardings()[0]]
    assert staging.snapshot()["h2d_bytes"] == D * 4 * words


def test_windows_stream_in_order_with_stats(knobs):
    """One launch, its ledger: one window, put as it stood, every byte
    of it sent and every parity byte fetched, fetched once."""
    nbytes = 16_000
    data = _data(nbytes, seed=3)
    pend = ReedSolomonJax(D, P).parity_lazy(data)
    s = pend.stats
    assert s.windows == s.direct_windows == 1
    assert s.h2d_bytes == s.payload_bytes == D * nbytes
    assert s.h2d_seconds > 0 and s.d2h_bytes == 0   # not yet fetched
    assert staging.snapshot()["launches"] == 0      # nor counted
    np.testing.assert_array_equal(
        pend.materialize(), rs_cpu.ReedSolomonCPU(D, P).parity(data))
    assert s.d2h_bytes == P * nbytes and s.d2h_seconds > 0
    assert s.start < s.end and 0.0 <= s.overlap_fraction <= 1.0
    assert s.pack_seconds == s.slot_wait_seconds == \
        s.ready_wait_seconds == 0.0
    assert staging.snapshot()["launches"] == 1
    with pytest.raises(RuntimeError):
        pend.materialize()  # single-consumer contract


def test_apply_matrix_lazy_windowed_rebuild_path(knobs):
    """The rebuild pipeline's generic apply takes the same staged
    path: reconstruction-matrix apply equals the CPU twin's."""
    nbytes = 12_289  # odd tail: word padding, 3073 words placed plain
    cpu = rs_cpu.ReedSolomonCPU(D, P)
    data = _data(nbytes, seed=4)
    full = np.asarray(cpu.encode(np.concatenate(
        [data, np.zeros((P, nbytes), np.uint8)], axis=0)))
    lost = [2, 11]
    present = [i not in lost for i in range(D + P)]
    coeffs, rows = rs_matrix.reconstruction_matrix(D, P, present, lost)
    codec = ReedSolomonJax(D, P)
    pend = codec.apply_matrix_lazy(coeffs, full[list(rows)])
    np.testing.assert_array_equal(pend.materialize(), full[lost])


def test_aggregate_snapshot(knobs):
    staging.reset_aggregate()
    codec = ReedSolomonJax(D, P)
    codec.parity_lazy(_data(8_192, seed=5)).materialize()
    codec.parity_lazy(_data(8_192, seed=6)).materialize()
    snap = staging.snapshot()
    assert snap["launches"] == snap["windows"] == 2
    assert snap["h2d_bytes"] == 2 * D * 8_192
    assert snap["h2d_gbps"] > 0
    assert 0.0 <= snap["overlap_fraction"] <= 1.0


# -- file pipeline: _generate_ec_files through the staged path ------------

def test_generate_ec_files_windowed_byte_identical(knobs, tmp_path,
                                                   monkeypatch):
    """Full encode pipeline (reader -> staged codec -> sinks) vs the
    CPU reference files, with a ragged tail volume."""
    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    # shrink geometry: 4KB "small rows"; the knobs' 2KB window holds
    # no whole row, so a work item is one row, wider than the window's
    # size: put whole all the same
    monkeypatch.setattr(ec_encoder, "SMALL_BLOCK_SIZE", 4096)
    monkeypatch.setattr(ec_context, "SMALL_BLOCK_SIZE", 4096)
    staging.reset_aggregate()

    blob = np.random.default_rng(7).integers(
        0, 256, 200_001, dtype=np.uint8).tobytes()
    for kind in ("j", "c"):
        with open(tmp_path / f"{kind}.dat", "wb") as f:
            f.write(blob)
    ec_encoder.write_ec_files(str(tmp_path / "j"),
                              ECContext(backend="jax"))
    ec_encoder.write_ec_files(str(tmp_path / "c"),
                              ECContext(backend="cpu"))
    for i in range(D + P):
        a = (tmp_path / f"j.ec{i:02d}").read_bytes()
        b = (tmp_path / f"c.ec{i:02d}").read_bytes()
        assert a == b, f"shard {i} differs under windowed staging"
    snap = staging.snapshot()
    assert snap["launches"] == 5            # 200,001 B: 5 rows of 40KB
    assert snap["windows"] == snap["direct_windows"] == 5
    assert snap["h2d_bytes"] == 5 * D * 4096


def test_generate_ec_files_one_shot_fallback(tmp_path, monkeypatch):
    """A window that holds the whole toy volume (the default 32 MiB
    against 4KB rows): the pipeline's one work item is one launch."""
    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    monkeypatch.setattr(ec_encoder, "SMALL_BLOCK_SIZE", 4096)
    monkeypatch.setattr(ec_context, "SMALL_BLOCK_SIZE", 4096)
    staging.reset_aggregate()
    blob = np.random.default_rng(8).integers(
        0, 256, 60_000, dtype=np.uint8).tobytes()
    for kind in ("j", "c"):
        with open(tmp_path / f"{kind}.dat", "wb") as f:
            f.write(blob)
    ec_encoder.write_ec_files(str(tmp_path / "j"),
                              ECContext(backend="jax"))
    ec_encoder.write_ec_files(str(tmp_path / "c"),
                              ECContext(backend="cpu"))
    for i in range(D + P):
        assert (tmp_path / f"j.ec{i:02d}").read_bytes() == \
            (tmp_path / f"c.ec{i:02d}").read_bytes(), f"shard {i}"
    snap = staging.snapshot()
    assert snap["launches"] == snap["windows"] == 1
    assert snap["payload_bytes"] == 60_000
