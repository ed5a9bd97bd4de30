"""Windowed double-buffered h2d staging + mesh-sharded encode:
byte-identity and plumbing (ROADMAP item 2 tentpole).

Tier-1 on the conftest's 8 virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8): the mesh-sharded
and windowed paths must be byte-identical to the single-device,
single-shot `device_put` path — and to the CPU twin — for every window
geometry, including uneven tails and batch axes that don't divide the
device count."""

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ops import rs_cpu, rs_matrix, staging
from seaweedfs_tpu.ops.rs_jax import ReedSolomonJax

D, P = 10, 4


@pytest.fixture
def knobs(monkeypatch):
    """Baseline knob state: tiny windows (so even small test arrays
    span many), mesh ON (the 8-device conftest mesh), depth 2."""
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0.002")
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_INFLIGHT", "2")
    monkeypatch.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "1")
    return monkeypatch


def _data(nbytes: int, rows: int = D, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, size=(rows, nbytes), dtype=np.uint8)


# -- unit: window planner + knobs -----------------------------------------

def test_knob_parsing(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0.5")
    assert staging.window_bytes() == 512 * 1024
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0")
    assert staging.window_bytes() == 0
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "junk")
    assert staging.window_bytes() == \
        int(staging.DEFAULT_WINDOW_MB * (1 << 20))
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_INFLIGHT", "0")
    assert staging.inflight_depth() == 1  # floor: one slot
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_INFLIGHT", "3")
    assert staging.inflight_depth() == 3
    monkeypatch.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "0")
    assert not staging.mesh_enabled()
    assert staging.encode_shardings() == (None, None, 1)
    monkeypatch.delenv("SEAWEEDFS_TPU_ENCODE_MESH")
    assert staging.mesh_enabled()


def test_plan_windows_tiles_exactly(monkeypatch):
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0.001")
    for w, ndev in ((1, 8), (7, 8), (1000, 8), (1024, 8), (333, 3),
                    (26, 1)):
        plan = staging.plan_windows(D, w, ndev)
        pos = 0
        for (w0, n, npad) in plan:
            assert w0 == pos and n >= 1
            assert npad % ndev == 0 and npad >= n
            pos += n
        assert pos == w, (w, ndev)
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0")
    assert staging.plan_windows(D, 1024, 8) == []  # disabled


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
    word count spanning many windows with a short tail."""
    nbytes = 40_003
    data = _data(nbytes, seed=1)
    want = rs_cpu.ReedSolomonCPU(D, P).parity(data)
    codec = ReedSolomonJax(D, P)
    pend = codec.parity_lazy(data)
    assert hasattr(pend, "windows")  # the staged handle
    got = pend.materialize()
    np.testing.assert_array_equal(got, want)
    # single-shot reference: windowing disabled, mesh off
    knobs.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", "0")
    knobs.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "0")
    one_shot = codec.parity_lazy(data)
    assert not hasattr(one_shot, "windows")
    np.testing.assert_array_equal(one_shot.materialize(), want)


def test_mesh_sharded_matches_single_device(knobs):
    """Batch axis NOT divisible by the 8-device mesh (1001 words),
    exercising the pad-then-slice path."""
    nbytes = 4 * 1001
    data = _data(nbytes, seed=2)
    codec = ReedSolomonJax(D, P)
    mesh_out = codec.parity_lazy(data).materialize()
    knobs.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "0")
    single_out = codec.parity_lazy(data).materialize()
    np.testing.assert_array_equal(mesh_out, single_out)
    np.testing.assert_array_equal(
        mesh_out, rs_cpu.ReedSolomonCPU(D, P).parity(data))


def test_windows_stream_in_order_with_stats(knobs):
    nbytes = 16_000
    data = _data(nbytes, seed=3)
    codec = ReedSolomonJax(D, P)
    pend = codec.parity_lazy(data)
    got = np.empty((P, nbytes), dtype=np.uint8)
    covered = 0
    n_windows = 0
    for byte0, chunk in pend.windows():
        assert byte0 == covered  # strict launch order
        got[:, byte0:byte0 + chunk.shape[1]] = chunk
        covered += chunk.shape[1]
        n_windows += 1
    assert covered == nbytes and n_windows > 1
    np.testing.assert_array_equal(
        got, rs_cpu.ReedSolomonCPU(D, P).parity(data))
    s = pend.stats
    assert s.windows == n_windows
    assert 0.0 <= s.overlap_fraction <= 1.0
    assert s.h2d_bytes > 0 and s.d2h_bytes > 0
    with pytest.raises(RuntimeError):
        list(pend.windows())  # single-consumer contract


def test_apply_matrix_lazy_windowed_rebuild_path(knobs):
    """The rebuild pipeline's generic apply takes the same staged
    path: reconstruction-matrix apply, windowed + mesh-sharded, equals
    the CPU twin's."""
    nbytes = 12_289  # odd tail
    cpu = rs_cpu.ReedSolomonCPU(D, P)
    data = _data(nbytes, seed=4)
    full = np.asarray(cpu.encode(np.concatenate(
        [data, np.zeros((P, nbytes), np.uint8)], axis=0)))
    lost = [2, 11]
    present = [i not in lost for i in range(D + P)]
    coeffs, rows = rs_matrix.reconstruction_matrix(D, P, present, lost)
    codec = ReedSolomonJax(D, P)
    pend = codec.apply_matrix_lazy(coeffs, full[list(rows)])
    assert hasattr(pend, "windows")
    np.testing.assert_array_equal(pend.materialize(), full[lost])


def test_aggregate_snapshot(knobs):
    staging.reset_aggregate()
    codec = ReedSolomonJax(D, P)
    codec.parity_lazy(_data(8_192, seed=5)).materialize()
    codec.parity_lazy(_data(8_192, seed=6)).materialize()
    snap = staging.snapshot()
    assert snap["launches"] == 2 and snap["windows"] >= 4
    assert snap["h2d_gbps"] > 0
    assert 0.0 <= snap["overlap_fraction"] <= 1.0


# -- file pipeline: _generate_ec_files through the staged path ------------

def test_generate_ec_files_windowed_byte_identical(knobs, tmp_path,
                                                   monkeypatch):
    """Full encode pipeline (reader -> windowed staged codec -> sink
    drain pushing parity windows as they land) vs the CPU reference
    files, with a ragged tail volume."""
    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    # shrink geometry: 4KB "small rows"; the knobs' 2KB window holds
    # no whole row, so a work item is one row, wider than a window:
    # every launch is cut and packed
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
    assert snap["windows"] > 5 * 10 and snap["direct_windows"] == 0


@pytest.mark.parametrize("window_mb", ["0", "64"])
def test_generate_ec_files_one_shot_fallback(tmp_path, monkeypatch,
                                             window_mb):
    """Review regression: with staging switched off ("0") the codec
    hands the pipeline the LEGACY _PendingParity handle — the
    accepts_lazy writer must materialize it itself instead of
    subscripting the handle (TypeError at the parity write).  With a
    window that holds the whole toy volume ("64") the pipeline's one
    work item is one window, staged as it stands."""
    from seaweedfs_tpu.storage.erasure_coding import (ec_context,
                                                      ec_encoder)
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    monkeypatch.setenv("SEAWEEDFS_TPU_ENCODE_MESH", "0")
    monkeypatch.setenv("SEAWEEDFS_TPU_H2D_WINDOW_MB", window_mb)
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
    assert snap["windows"] == snap["direct_windows"] == \
        (1 if window_mb == "64" else 0)


# -- bench: predictive roofline stays honest ------------------------------

def test_bench_ceiling_never_raised_to_observed():
    import bench
    out = {}
    bench._apply_ceiling(out, "k", 5.0, {"a": 2.0, "b": 3.0})
    assert out["k_bound_by"] == "a"
    assert out["k_ceiling_gbps"] == 2.0  # NOT raised to 5.0
    assert out["k_of_ceiling"] == 2.5    # >1.0 reported honestly
    assert "exceeds the predicted ceiling" in out["k_ceiling_note"]
    out = {}
    bench._apply_ceiling(out, "k", 1.5, {"a": 2.0})
    assert out["k_of_ceiling"] == 0.75 and "k_ceiling_note" not in out
