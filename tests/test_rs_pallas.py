"""Pallas kernel bit-identity vs the numpy twin.  Tier-1 runs on the CPU,
so every call asks for interpret mode explicitly; the compiled kernel is
checked on the chip by chip_smoke.py."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_matrix
from seaweedfs_tpu.ops.rs_pallas import (TILE_WORDS, expand_tables,
                                         gf_apply_matrix_pallas)


@pytest.mark.parametrize("d,p", [(10, 4), (6, 3)])
def test_pallas_parity_bit_identical(d, p):
    rng = np.random.default_rng(d * 10 + p)
    mat = rs_matrix.parity_matrix(d, p)
    data = rng.integers(0, 256, size=(d, TILE_WORDS * 4), dtype=np.uint8)
    got = np.asarray(gf_apply_matrix_pallas(mat, data, interpret=True))
    want = gf256.gf_apply_matrix(mat, data)
    assert np.array_equal(got, want)


def test_pallas_unaligned_length_padding():
    rng = np.random.default_rng(3)
    mat = rs_matrix.parity_matrix(4, 2)
    data = rng.integers(0, 256, size=(4, 12345), dtype=np.uint8)
    got = np.asarray(gf_apply_matrix_pallas(mat, data, interpret=True))
    want = gf256.gf_apply_matrix(mat, data)
    assert got.shape == (2, 12345)
    assert np.array_equal(got, want)


def test_pallas_decode_matrix_apply():
    # arbitrary (non-parity) matrices must work through the same kernel
    rng = np.random.default_rng(4)
    mat = rng.integers(0, 256, size=(3, 5)).astype(np.uint8)
    data = rng.integers(0, 256, size=(5, 4096), dtype=np.uint8)
    got = np.asarray(gf_apply_matrix_pallas(mat, data, interpret=True))
    want = gf256.gf_apply_matrix(mat, data)
    assert np.array_equal(got, want)


def test_expand_tables_shape():
    mat = rs_matrix.parity_matrix(10, 4)
    t = expand_tables(mat)
    assert t.shape == (4 * 10 * 8,)
    assert t.dtype == np.uint32
