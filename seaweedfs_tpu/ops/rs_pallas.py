"""Pallas TPU kernel for the GF(2^8) constant-matrix apply (EC hot loop).

Same math as `rs_jax._packed_xor_network` (packed uint32 bit-select XOR
network) but with explicit VMEM tiling so the whole accumulation chain
stays on-chip: one HBM read of the data tile, one HBM write of the output
tile, all 8*K*R select/mul/XOR terms fused in VMEM.  This is the TPU
equivalent of the reference's SIMD assembly in klauspost/reedsolomon
(invoked at weed/storage/erasure_coding/ec_encoder.go:265).

The coding matrix rides in SMEM as scalars, so ONE compiled kernel serves
every coding/decoding matrix of the same [R, K] shape — encode, decode,
and every rebuild loss-pattern reuse the same binary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gf256

# Words (uint32) per grid step along the stream axis. 8192 words = 32KiB
# per shard row per tile; with RS(10,4) that is ~448KiB of VMEM live per
# step — small enough to double-buffer comfortably in 16MiB VMEM.
TILE_WORDS = 8192


def _rs_kernel(tab_ref, data_ref, out_ref, *, r: int, k: int):
    """data_ref: [K, S, 128] uint32 tile; out_ref: [R, S, 128] uint32;
    tab_ref: [R*K*8] uint32 in SMEM."""
    lane_mask = jnp.uint32(0x01010101)
    accs = [jnp.zeros(data_ref.shape[1:], dtype=jnp.uint32)
            for _ in range(r)]
    for ki in range(k):
        d = data_ref[ki]
        for b in range(8):
            mask = (d >> jnp.uint32(b)) & lane_mask
            for ri in range(r):
                c = tab_ref[(ri * k + ki) * 8 + b]
                accs[ri] = accs[ri] ^ (mask * c)
    for ri in range(r):
        out_ref[ri] = accs[ri]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf_apply_matrix_pallas_words(tables_flat: jax.Array, data32: jax.Array,
                                 interpret: bool = False) -> jax.Array:
    """tables_flat [R*K*8] uint32 (from `expand_tables`); data32 [K, W]
    uint32 with W % TILE_WORDS == 0.  Returns [R, W] uint32."""
    k, w = data32.shape
    r = tables_flat.shape[0] // (k * 8)
    assert w % TILE_WORDS == 0
    lanes = 128
    s = TILE_WORDS // lanes
    grid = (w // TILE_WORDS,)
    d3 = data32.reshape(k, w // lanes, lanes)
    kernel = functools.partial(_rs_kernel, r=r, k=k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((k, s, lanes), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, s, lanes), lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, w // lanes, lanes), jnp.uint32),
        interpret=interpret,
    )(tables_flat, d3)
    return out.reshape(r, w)


def expand_tables(mat: np.ndarray) -> np.ndarray:
    """[R, K] uint8 coding matrix -> flat [R*K*8] uint32 bit tables."""
    return gf256.MUL_BY_POW2[np.asarray(mat, dtype=np.uint8)].astype(
        np.uint32).reshape(-1)


def gf_apply_matrix_pallas(mat, data, interpret: bool = False
                           ) -> jax.Array:
    """Byte-in/byte-out wrapper over the Pallas kernel (for tests and
    small inputs; bulk callers use gf_apply_matrix_pallas_words with
    host-packed uint32 buffers).  `interpret` is the caller's explicit
    choice — CPU tests pass True; the wrapper never picks it, so a
    missing chip fails instead of silently interpreting.

    mat: [R, K] uint8; data: [K, B] uint8 numpy -> [R, B] uint8."""
    from . import rs_jax

    mat = np.asarray(mat, dtype=np.uint8)
    data = np.asarray(data, dtype=np.uint8)
    r, k = mat.shape
    if data.shape[0] != k:
        raise ValueError(f"matrix k={k} vs data rows {data.shape[0]}")
    batch_shape = data.shape[1:]
    flat = data.reshape(k, -1)
    b = flat.shape[1]
    data32 = rs_jax.pack_words(flat, multiple=TILE_WORDS * 4)
    out32 = gf_apply_matrix_pallas_words(
        jnp.asarray(expand_tables(mat)), jnp.asarray(data32),
        interpret=interpret)
    out = rs_jax.unpack_words(np.asarray(out32), b)
    return jnp.asarray(out).reshape((r,) + batch_shape)
