"""Sharded EC over a virtual 8-device mesh: bit-identity vs the CPU twin.

Mirrors the reference's cross-implementation parity testing pattern
(test/volume_server/rust/rust_volume_test.go — same assertions against a
second implementation) with the distributed TPU path as the second
implementation.
"""

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ops import rs_cpu, rs_matrix
from seaweedfs_tpu.ops.rs_jax import pack_words, unpack_words
from seaweedfs_tpu.parallel import ec_sharded, make_mesh


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force 8 virtual devices"
    return make_mesh()


def test_mesh_shape(mesh):
    assert mesh.shape == {"stripe": 2, "shard": 4}


def test_encode_sharded_matches_cpu(mesh):
    rng = np.random.default_rng(0)
    d, p, nbytes = 10, 4, 4096 * 8
    data = rng.integers(0, 256, size=(d, nbytes), dtype=np.uint8)
    cpu = rs_cpu.ReedSolomonCPU(d, p)
    want = cpu.parity(data)
    mat = rs_matrix.parity_matrix(d, p)
    got32 = ec_sharded.encode_sharded(mesh, mat, pack_words(data))
    got = unpack_words(np.asarray(got32), nbytes)
    np.testing.assert_array_equal(got, want)


def test_reconstruct_sharded_matches_cpu(mesh):
    rng = np.random.default_rng(1)
    d, p, nbytes = 10, 4, 4096 * 8
    data = rng.integers(0, 256, size=(d, nbytes), dtype=np.uint8)
    cpu = rs_cpu.ReedSolomonCPU(d, p)
    full = cpu.encode(np.concatenate(
        [data, np.zeros((p, nbytes), np.uint8)], axis=0))
    lost = [1, 12]
    present = [i not in lost for i in range(d + p)]
    coeffs, rows = rs_matrix.reconstruction_matrix(d, p, present, lost)
    survivors32 = pack_words(full[rows])
    coeffs_p, survivors32_p = ec_sharded.pad_survivors(
        coeffs, survivors32, mesh.shape["shard"])
    got32 = ec_sharded.reconstruct_sharded(mesh, coeffs_p, survivors32_p)
    got = unpack_words(np.asarray(got32), nbytes)
    np.testing.assert_array_equal(got, full[lost])


@pytest.mark.parametrize("lost", [(0, 11), (3, 7), (10, 13), (0, 1)])
def test_distributed_ec_step(mesh, lost):
    rng = np.random.default_rng(2)
    d, nbytes = 10, 1024 * 8
    data = rng.integers(0, 256, size=(d, nbytes), dtype=np.uint8)
    par, rec, err = ec_sharded.distributed_ec_step(
        mesh, pack_words(data), data_shards=d, parity_shards=4, lost=lost)
    assert err == 0
    cpu = rs_cpu.ReedSolomonCPU(d, 4)
    np.testing.assert_array_equal(
        unpack_words(par, nbytes), cpu.parity(data))


def test_rs63_scheme(mesh):
    """RS(6,3) alternate scheme (BASELINE.json config 5)."""
    rng = np.random.default_rng(3)
    d, p, nbytes = 6, 3, 2048 * 8
    data = rng.integers(0, 256, size=(d, nbytes), dtype=np.uint8)
    cpu = rs_cpu.ReedSolomonCPU(d, p)
    want = cpu.parity(data)
    # p=3 not divisible by the shard axis (4): pad parity rows with a zero
    # coefficient row, drop it after.
    mat = np.pad(rs_matrix.parity_matrix(d, p), ((0, 1), (0, 0)))
    got32 = ec_sharded.encode_sharded(mesh, mat, pack_words(data))
    got = unpack_words(np.asarray(got32), nbytes)[:p]
    np.testing.assert_array_equal(got, want)


def test_encode_volume_batch(mesh):
    """BASELINE config 3: batch of volumes across the mesh."""
    rng = np.random.default_rng(4)
    v, d, p, nbytes = 4, 10, 4, 1024 * 4
    batch = rng.integers(0, 256, size=(v, d, nbytes), dtype=np.uint8)
    cpu = rs_cpu.ReedSolomonCPU(d, p)
    mat = rs_matrix.parity_matrix(d, p)
    batch32 = np.stack([pack_words(b) for b in batch])
    got = np.asarray(ec_sharded.encode_volume_batch(mesh, mat, batch32))
    for i in range(v):
        np.testing.assert_array_equal(
            unpack_words(got[i], nbytes), cpu.parity(batch[i]),
            err_msg=f"volume {i}")


def test_named_sharding_staged_encode_matches_shard_map(mesh,
                                                        monkeypatch):
    """The 1D Mesh(jax.devices(), ("batch",)) +
    NamedSharding(P(None, "batch")) staging path (ops.staging, what
    parity_lazy ships) against the 2D shard_map path and the CPU twin
    — the same cross-implementation identity this module has always
    asserted, with the NamedSharding idiom as the third
    implementation (ROADMAP D13: two mesh placements)."""
    from seaweedfs_tpu.ops import staging
    from seaweedfs_tpu.ops.rs_jax import ReedSolomonJax

    asked = []
    shardings = staging.encode_shardings

    def seen():
        asked.append(shardings())
        return asked[-1]
    monkeypatch.setattr(staging, "encode_shardings", seen)
    rng = np.random.default_rng(5)
    d, p, nbytes = 10, 4, 4096 * 8
    data = rng.integers(0, 256, size=(d, nbytes), dtype=np.uint8)
    want = rs_cpu.ReedSolomonCPU(d, p).parity(data)
    staged = ReedSolomonJax(d, p).parity_lazy(data)
    # the staged mesh path ran: 8 devices, and 8192 words divide them
    assert [a[2] for a in asked] == [8] and asked[0][0] is not None
    np.testing.assert_array_equal(staged.materialize(), want)
    mat = rs_matrix.parity_matrix(d, p)
    got32 = ec_sharded.encode_sharded(mesh, mat, pack_words(data))
    np.testing.assert_array_equal(
        unpack_words(np.asarray(got32), nbytes), want)


def test_encode_volume_files_batch_byte_identical(mesh, tmp_path,
                                                  monkeypatch):
    """The multi-volume FILE batch path (parallel/ec_batch.py — what
    the tpu_ec worker's execute_batch runs) produces shard files
    byte-identical to per-volume write_ec_files, across volumes of
    DIFFERENT sizes (per-volume tails, zero-volume mesh padding)."""
    from seaweedfs_tpu.parallel import ec_batch
    from seaweedfs_tpu.storage.erasure_coding import ec_encoder
    from seaweedfs_tpu.storage.erasure_coding.ec_context import ECContext

    # shrink geometry so several rows/steps exercise the batching
    monkeypatch.setattr(ec_batch, "SMALL_BLOCK_SIZE", 1024)
    monkeypatch.setattr(ec_batch, "TPU_BATCH_SIZE", 4096)
    monkeypatch.setattr(ec_encoder, "SMALL_BLOCK_SIZE", 1024)

    rng = np.random.default_rng(11)
    sizes = [50_000, 31_000, 12_345]  # 5/4/2 rows: ragged tails
    bases_batch, bases_ref = [], []
    for i, size in enumerate(sizes):
        blob = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        for kind, acc in (("b", bases_batch), ("r", bases_ref)):
            base = str(tmp_path / f"{kind}{i}")
            with open(base + ".dat", "wb") as f:
                f.write(blob)
            acc.append(base)

    ctx = ECContext(backend="cpu")
    ec_batch.encode_volume_files_batch(bases_batch, ctx, mesh)
    for base in bases_ref:
        ec_encoder.write_ec_files(base, ctx)

    for bb, br in zip(bases_batch, bases_ref):
        for i in range(14):
            a = open(bb + f".ec{i:02d}", "rb").read()
            b = open(br + f".ec{i:02d}", "rb").read()
            assert a == b, f"{bb} shard {i} differs from per-volume"
