"""The numpy reference agrees with the program's ops/rs_cpu and with
known RS(10,4) and RS(6,3) generator rows, and sees a flipped byte."""

import numpy as np
import pytest

from benchmark import reference
from seaweedfs_tpu.ops import rs_matrix
from seaweedfs_tpu.ops.rs_cpu import ReedSolomonCPU

# klauspost/reedsolomon-compatible parity rows (Vandermonde made
# systematic over GF(2^8), polynomial 0x11D)
KNOWN = {
    (10, 4): [[129, 150, 175, 184, 210, 196, 254, 232, 3, 2],
              [150, 129, 184, 175, 196, 210, 232, 254, 2, 3],
              [191, 214, 98, 10, 6, 111, 223, 183, 5, 4],
              [214, 191, 10, 98, 111, 6, 183, 223, 4, 5]],
    (6, 3): [[7, 6, 5, 4, 3, 2], [6, 7, 4, 5, 2, 3],
             [160, 223, 223, 183, 254, 232]],
}


@pytest.mark.parametrize("k,r", [(10, 4), (6, 3), (4, 2), (12, 4)])
def test_matrix_agrees_with_the_program(k, r):
    assert np.array_equal(reference.parity_matrix(k, r),
                          rs_matrix.parity_matrix(k, r))


@pytest.mark.parametrize("k,r", sorted(KNOWN))
def test_known_generator_rows(k, r):
    assert reference.parity_matrix(k, r).tolist() == KNOWN[(k, r)]


def test_field_tables():
    assert reference.MUL[2, 128] == 29          # x * x^7 = 0x11D - 0x100
    assert all(reference.MUL[a, reference.gf_inv(a)] == 1
               for a in range(1, 256))
    assert reference.gf_exp(0, 0) == 1 and reference.gf_exp(0, 3) == 0


@pytest.mark.parametrize("k,r", [(10, 4), (6, 3)])
def test_parity_agrees_with_rs_cpu(k, r):
    data = np.random.default_rng(k).integers(
        0, 256, size=(k, 65536 + 13), dtype=np.uint8)
    assert np.array_equal(
        reference.parity(reference.parity_matrix(k, r), data),
        ReedSolomonCPU(k, r).parity(data))


@pytest.mark.parametrize("k,r", [(10, 4), (6, 3)])
def test_parity_mismatch_counts_altered_bytes(tmp_path, k, r):
    n = reference.BLOCK + 4096       # more than one block, a short tail
    data = np.random.default_rng(r).integers(0, 256, size=(k, n),
                                             dtype=np.uint8)
    shards = np.concatenate([data, ReedSolomonCPU(k, r).parity(data)])
    paths = []
    for i, row in enumerate(shards):
        paths.append(str(tmp_path / f"v.ec{i:02d}"))
        row.tofile(paths[-1])
    assert reference.parity_mismatch([paths], k, workers=2) == 0
    with open(paths[-1], "r+b") as f:       # one parity byte altered
        f.seek(n - 7)
        f.write(bytes([shards[-1, n - 7] ^ 1]))
    assert reference.parity_mismatch([paths], k, workers=2) == 1
    with open(paths[0], "r+b") as f:        # one data byte: r parities
        f.seek(5)
        f.write(bytes([shards[0, 5] ^ 0x80]))
    assert reference.parity_mismatch([paths], k, workers=2) == 1 + r
    with open(paths[1], "ab") as f:         # a shard of another length
        f.write(b"x")
    assert reference.parity_mismatch([paths], k, workers=2) >= r
