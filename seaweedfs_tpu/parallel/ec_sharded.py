"""Sharded erasure coding over a device mesh (shard_map + ICI collectives).

The reference distributes EC work across *machines*: shards live on
different volume servers (weed/storage/erasure_coding/
shard_distribution.go:101) and degraded reads fan out parallel reads of
surviving shards, XOR-combining reconstructed data on the caller
(weed/storage/store_ec.go:366-443).  On a TPU slice those fan-outs become
XLA collectives over ICI:

  * encode  — stripe columns are data-parallel ("stripe" axis), parity
    rows are tensor-parallel ("shard" axis).  No collective needed: GF
    parity is columnwise-independent, so each device writes its slice of
    its parity rows.
  * reconstruct — survivor shard rows live distributed over the "shard"
    axis (the natural storage layout: one shard per device/server).  Each
    device computes its partial XOR-sum of coefficient×shard terms and a
    ring XOR-reduce (`ppermute`, the storage analog of ring attention)
    combines them — bit-exact, since XOR is associative/commutative.

All bulk data rides as packed uint32 words ([K, W] — 4 GF bytes per word,
see ops.rs_jax) so no uint8 relayout happens on device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..ops import rs_matrix
from ..ops.rs_jax import _packed_xor_network, expand_tables_u32
from .mesh import SHARD_AXIS, STRIPE_AXIS


def _ring_xor(x: jax.Array, axis_name: str) -> jax.Array:
    """XOR all-reduce over `axis_name` via a ring of ppermutes.

    s-1 hops, each overlapping neighbor transfers on ICI; bit-exact in any
    order because XOR is associative and commutative.
    """
    s = jax.lax.axis_size(axis_name)
    if s == 1:
        return x
    perm = [(j, (j + 1) % s) for j in range(s)]
    acc = x
    t = x
    for _ in range(s - 1):
        t = jax.lax.ppermute(t, axis_name, perm)
        acc = acc ^ t
    return acc


def _apply_tables_local(mat_local: jax.Array, data32: jax.Array) -> jax.Array:
    """[r_local, K] uint8 × [K, W_local] uint32 -> [r_local, W_local]."""
    return _packed_xor_network(expand_tables_u32(mat_local), data32)


@functools.lru_cache(maxsize=32)
def _encode_shard_map(mesh):
    """Per-mesh encode shard_map (traceable, un-jitted): parity rows
    tensor-parallel over "shard", columns data-parallel over "stripe"."""
    return shard_map(
        _apply_tables_local, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(None, STRIPE_AXIS)),
        out_specs=P(SHARD_AXIS, STRIPE_AXIS))


@functools.lru_cache(maxsize=32)
def _reconstruct_shard_map(mesh):
    """Per-mesh distributed-reconstruction shard_map (ring XOR-reduce)."""
    return shard_map(
        _reconstruct_local, mesh=mesh,
        in_specs=(P(None, SHARD_AXIS), P(SHARD_AXIS, STRIPE_AXIS)),
        # the ring XOR leaves every shard-axis device with the full sum;
        # replication can't be statically inferred through ppermute
        out_specs=P(None, STRIPE_AXIS), check_vma=False)


@functools.lru_cache(maxsize=32)
def _encode_fn(mesh):
    """Jitted per-mesh encode; cached so repeated calls don't retrace."""
    return jax.jit(_encode_shard_map(mesh))


@functools.lru_cache(maxsize=32)
def _reconstruct_fn(mesh):
    """Jitted per-mesh reconstruction; cached to avoid retraces."""
    return jax.jit(_reconstruct_shard_map(mesh))


def encode_sharded(mesh, mat, data32):
    """Distributed parity computation.

    mat: [R, K] uint8 parity rows (R divisible by the "shard" axis size).
    data32: [K, W] uint32 packed data shards (W divisible by the "stripe"
    axis size × 1 word).  Returns [R, W] uint32 parity, sharded
    P("shard", "stripe").
    """
    return _encode_fn(mesh)(mat, data32)


def _reconstruct_local(coeffs_local: jax.Array, survivors_local: jax.Array
                       ) -> jax.Array:
    """coeffs_local [T, k_local] uint8, survivors_local [k_local, W_local]
    uint32 -> full [T, W_local] after ring XOR-reduce over the shard axis."""
    partial = _apply_tables_local(coeffs_local, survivors_local)
    return _ring_xor(partial, SHARD_AXIS)


def reconstruct_sharded(mesh, coeffs, survivors32):
    """Distributed reconstruction: survivors live sharded over the "shard"
    axis (one group of shard rows per device — the storage layout), output
    target rows are produced on every shard-axis device via ring XOR.

    coeffs: [T, K] uint8 reconstruction matrix (targets × survivors);
    K must be divisible by the shard axis size (pad with zero-coefficient
    columns + zero rows if not — XOR identity makes padding free).
    survivors32: [K, W] uint32.  Returns [T, W] uint32.
    """
    return _reconstruct_fn(mesh)(coeffs, survivors32)


def _apply_tables_batch_local(mat_local: jax.Array, batch32: jax.Array
                              ) -> jax.Array:
    """[r_local, K] × [V_local, K, W] -> [V_local, r_local, W]."""
    return jax.vmap(lambda d: _apply_tables_local(mat_local, d))(batch32)


@functools.lru_cache(maxsize=32)
def _encode_batch_fn(mesh):
    return jax.jit(shard_map(
        _apply_tables_batch_local, mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), P(STRIPE_AXIS, None, None)),
        out_specs=P(STRIPE_AXIS, SHARD_AXIS, None)))


def encode_volume_batch(mesh, mat, batch32):
    """Batch-of-volumes encode (BASELINE.json config 3: 64 volumes
    across the slice): volumes ride the data-parallel "stripe" axis,
    parity rows the tensor-parallel "shard" axis.

    mat: [R, K] uint8; batch32: [V, K, W] uint32 with V divisible by
    the stripe axis.  Returns [V, R, W] uint32.
    """
    return _encode_batch_fn(mesh)(mat, batch32)


def pad_survivors(coeffs: np.ndarray, survivors32: np.ndarray, multiple: int):
    """Pad the survivor dimension up to `multiple` with zero rows/columns
    (zero GF coefficients contribute nothing to the XOR sum)."""
    t, k = coeffs.shape
    pad = (-k) % multiple
    if pad == 0:
        return coeffs, survivors32
    coeffs = np.pad(coeffs, ((0, 0), (0, pad)))
    survivors32 = np.pad(survivors32, ((0, pad), (0, 0)))
    return coeffs, survivors32


@functools.partial(jax.jit, static_argnames=("mesh", "survivor_rows",
                                             "pad_rows"))
def _ec_step(mesh, data32, parity_mat, recon_coeffs,
             survivor_rows: tuple, pad_rows: int):
    """One full distributed EC pipeline step (see distributed_ec_step)."""
    par = _encode_shard_map(mesh)(parity_mat, data32)
    all_shards = jnp.concatenate([data32, par], axis=0)
    survivors = all_shards[jnp.asarray(survivor_rows)]
    if pad_rows:
        survivors = jnp.concatenate(
            [survivors,
             jnp.zeros((pad_rows, survivors.shape[1]), survivors.dtype)],
            axis=0)
    rec = _reconstruct_shard_map(mesh)(recon_coeffs, survivors)
    return par, rec


def distributed_ec_step(mesh, data32: np.ndarray, data_shards: int = 10,
                        parity_shards: int = 4, lost=(0, 11)):
    """The framework's "training step": encode a striped volume batch over
    the mesh, lose shards, reconstruct them distributed, and return
    (parity, reconstructed, max_abs_error).

    Exercises the real production shardings end-to-end: data-parallel
    stripes, tensor-parallel shard rows, and the ring-XOR collective.
    """
    total = data_shards + parity_shards
    shard_ax = mesh.shape[SHARD_AXIS]
    k, w = data32.shape
    assert k == data_shards
    parity_mat = rs_matrix.parity_matrix(data_shards, parity_shards)
    present = [i not in lost for i in range(total)]
    coeffs, rows = rs_matrix.reconstruction_matrix(
        data_shards, parity_shards, present, list(lost))
    pad = (-len(rows)) % shard_ax
    coeffs, _ = pad_survivors(
        coeffs, np.zeros((len(rows), 0), np.uint32), shard_ax)
    par, rec = _ec_step(
        mesh, jnp.asarray(data32), jnp.asarray(parity_mat),
        jnp.asarray(coeffs), survivor_rows=tuple(rows), pad_rows=pad)
    # check reconstruction against ground truth
    full = np.concatenate([np.asarray(data32), np.asarray(par)], axis=0)
    err = int(np.max(np.abs(
        full[list(lost)].astype(np.int64) -
        np.asarray(rec).astype(np.int64))))
    return np.asarray(par), np.asarray(rec), err
