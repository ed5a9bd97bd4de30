"""JAX/TPU Reed-Solomon kernels: GF(2^8) constant-matrix apply as an
XOR network over bit-planes.

This is the TPU-native re-expression of the reference's hot loop
(weed/storage/erasure_coding/ec_encoder.go:265 enc.Encode,
:360 enc.Reconstruct, weed/storage/store_ec.go:435 ReconstructData —
klauspost/reedsolomon SIMD on CPU).

Math: GF(2^8) multiplication by a constant c is GF(2)-linear over the
bits of the input byte:  c*x = XOR_b [bit_b(x) ? c*(2^b) : 0].
So a parity row  out[r] = XOR_k mat[r,k] * data[k]  becomes a fused
select/XOR network with 8*K terms per output row — pure integer VPU work,
bit-exact on every backend (CPU tests == TPU production), and entirely
fusible by XLA into a single HBM-bandwidth-bound elementwise kernel.
No bf16/MXU is used for the GF math itself: exactness is mandatory
(bit-identical shards vs the CPU reference path).

All public entry points accept/return uint8 arrays; the constant matrix is
a *traced* argument so one compiled kernel serves every (d, p) scheme and
every reconstruction pattern of the same shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256, rs_matrix, staging


def _expand_tables(mat: jax.Array) -> jax.Array:
    """[R, K] constant matrix -> [R, K, 8] per-bit multiply tables.

    MUL_BY_POW2 ([256, 8] uint8: c * 2^b in GF(2^8)) is embedded as a
    trace-time constant rather than a module-level device array: a
    module-level device_put would initialize the default JAX backend
    — and so take the chip — at IMPORT time, before the importing
    process has said whether it owns the device
    (ec_context.own_device)."""
    return jnp.asarray(gf256.MUL_BY_POW2)[mat]


def expand_tables_u32(mat: jax.Array) -> jax.Array:
    """[R, K] constant matrix -> [R, K, 8] uint32 per-bit multiply tables
    (the form `_packed_xor_network` consumes); shared by every caller so
    the table layout has a single definition."""
    return _expand_tables(mat).astype(jnp.uint32)


def _packed_xor_network(tables: jax.Array, data32: jax.Array) -> jax.Array:
    """Packed-word GF constant-matrix apply.

    tables: [R, K, 8] uint32 per-bit multiply constants (< 256)
    data32: [K, W] uint32 — 4 data bytes per word
    returns [R, W] uint32.

    Per word: mask = (d >> b) & 0x01010101 isolates bit b of each of the 4
    bytes in place; mask * c multiplies each byte by the constant without
    cross-byte carries (products are < 256).  4x fewer VPU lane-ops than a
    per-byte formulation.  Byte order inside the word cancels out between
    pack and unpack, so results are platform-independent.
    """
    r, k = tables.shape[0], tables.shape[1]
    lane_mask = jnp.uint32(0x01010101)
    accs = [jnp.zeros_like(data32[0]) for _ in range(r)]
    for ki in range(k):
        d = data32[ki]
        for b in range(8):
            mask = (d >> jnp.uint32(b)) & lane_mask
            for ri in range(r):
                accs[ri] = accs[ri] ^ (mask * tables[ri, ki, b])
    return jnp.stack(accs)


@jax.jit
def gf_apply_matrix_words(mat: jax.Array, data32: jax.Array) -> jax.Array:
    """Fast path: mat [R, K] uint8 (traced), data32 [K, W] uint32 (4 GF
    bytes per word) -> [R, W] uint32.

    This is the production entry point for bulk encode/rebuild: callers
    keep shard buffers as uint32 words (a free numpy `.view` on the host)
    so no uint8 relayout ever happens on device.  Eager uint8 reshapes of
    multi-GB arrays were observed to pad 12.8x on TPU (layout {0,1}
    T(8,128)(4,1)) and OOM — words in, words out avoids the entire issue.
    """
    tables = expand_tables_u32(mat)
    return _packed_xor_network(tables, data32)


def pack_words(data: np.ndarray, multiple: int = 4) -> np.ndarray:
    """Host-side [K, B] uint8 -> [K, ceil(B/4)] uint32 (pads B up to
    `multiple` bytes; multiple must itself be a multiple of 4)."""
    assert multiple % 4 == 0
    data = np.ascontiguousarray(data)
    k, b = data.shape
    pad = (-b) % multiple
    if pad:
        data = np.pad(data, ((0, 0), (0, pad)))
    return data.view(np.uint32)


def unpack_words(data32: np.ndarray, b: int) -> np.ndarray:
    """Host-side [R, W] uint32 -> [R, b] uint8."""
    return np.ascontiguousarray(data32).view(np.uint8)[:, :b]


def gf_apply_matrix(mat, data) -> jax.Array:
    """out[r] = XOR_k mat[r,k] * data[k] over GF(2^8).

    mat: [R, K] uint8 (traced; any coding/decoding matrix)
    data: [K, B] uint8 (B is padded to a word multiple internally)
    returns [R, B]: numpy uint8 for numpy input (host word-packing fast
    path, no device relayout or re-upload), device uint8 otherwise.

    Convenience byte-in/byte-out wrapper; for multi-GB streams prefer
    gf_apply_matrix_words with host-packed uint32 buffers.
    """
    mat = jnp.asarray(mat, dtype=jnp.uint8)
    k = data.shape[0]
    batch_shape = data.shape[1:]
    if isinstance(data, np.ndarray):
        flat = pack_words(data.reshape(k, -1).astype(np.uint8, copy=False))
        b = int(np.prod(batch_shape))
        out32 = gf_apply_matrix_words(mat, jnp.asarray(flat))
        out = unpack_words(np.asarray(out32), b)
        return out.reshape((mat.shape[0],) + batch_shape)
    data = jnp.asarray(data, dtype=jnp.uint8)
    flat = data.reshape(k, -1)
    b = flat.shape[1]
    pad = (-b) % 4
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    flat32 = jax.lax.bitcast_convert_type(
        flat.reshape(k, (b + pad) // 4, 4), jnp.uint32)
    out32 = gf_apply_matrix_words(mat, flat32)
    out = jax.lax.bitcast_convert_type(out32, jnp.uint8).reshape(
        mat.shape[0], -1)
    if pad:
        out = out[:, :b]
    return out.reshape((mat.shape[0],) + batch_shape)


def _launch_lazy(mat, data: np.ndarray, op: str, payload_bytes, run):
    """Dispatch mat x data without waiting (ops.staging): the packed
    batch is put on the device whole, whatever its size."""
    return staging.WindowedLaunch(
        mat, pack_words(data), gf_apply_matrix_words, len(mat),
        data.shape[1], op=op, payload_bytes=payload_bytes, run=run)


class ReedSolomonJax:
    """TPU encoder/decoder for RS(data, parity), API-compatible with the
    CPU twin (`rs_cpu.ReedSolomonCPU`)."""

    def __init__(self, data_shards: int, parity_shards: int):
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix = rs_matrix.build_matrix(data_shards, self.total_shards)
        self._parity_rows = jnp.asarray(self.matrix[data_shards:])

    def _check(self, arr, rows: int):
        """Validate without converting: numpy stays numpy so the host
        word-packing fast path in gf_apply_matrix is taken (device-side
        eager uint8 relayout of huge arrays pads 12.8x and OOMs)."""
        if not hasattr(arr, "dtype"):
            arr = np.asarray(arr, dtype=np.uint8)
        if arr.dtype != np.uint8:
            raise TypeError(f"shards must be uint8, got {arr.dtype}")
        if arr.ndim != 2 or arr.shape[0] != rows:
            raise ValueError(
                f"expected [{rows}, B] shard array, got {arr.shape}")
        return arr

    # -- encode ------------------------------------------------------------

    def parity(self, data) -> jax.Array:
        """data: [data_shards, B] uint8 -> parity [parity_shards, B]."""
        data = self._check(data, self.data_shards)
        return gf_apply_matrix(self._parity_rows, data)

    def parity_lazy(self, data,
                    payload_bytes: "int | None" = None,
                    run=None) -> "staging.WindowedLaunch":
        """Dispatch the parity launch WITHOUT waiting for the result.

        Returns a handle whose .materialize() blocks on the device and
        yields the [parity_shards, B] uint8 numpy array.  This lets a
        pipeline overlap the D2H fetch of launch k with the H2D+kernel
        of launch k+1 (the encode staging pipeline materializes in its
        writer thread while the compute stage puts ahead).

        Aliasing contract: `data` may be a recycled buffer, but only
        AFTER materialize() returns — on backends where device_put
        aliases host memory (CPU), the kernel has consumed the input by
        the time the output is fetchable.

        The batch is put on the device whole, as parity() has always
        done: how big a device work item is, is the caller's to decide
        (the EC file pipeline hands over one staging window,
        ECContext.rows_per_launch).

        `payload_bytes`: how many of `data`'s bytes the caller counts
        as its own (the encoder sends a short tail in the full
        window's shape); the staging ledger keeps them beside what it
        sent.  `run`: the staging.Run of the encode this launch is
        part of, for the overlap.
        """
        data = self._check(data, self.data_shards)
        return _launch_lazy(self._parity_rows, data, "encode",
                            payload_bytes, run)

    def apply_matrix(self, mat, data) -> np.ndarray:
        """out[r] = XOR_k mat[r,k] * data[k] — public generic apply
        (numpy in, numpy out via the host word-packing fast path)."""
        return gf_apply_matrix(jnp.asarray(mat, dtype=jnp.uint8), data)

    def apply_matrix_lazy(self, mat, data, run=None
                          ) -> "staging.WindowedLaunch":
        """Async generic apply: dispatch without waiting (same contract
        as parity_lazy, the batch put whole) so a staged pipeline can
        overlap D2H of launch k with H2D+kernel of k+1."""
        return _launch_lazy(np.asarray(mat, dtype=np.uint8), data,
                            "rebuild", None, run)

    def encode(self, shards) -> jax.Array:
        """shards: [total, B] with data rows filled; returns full array with
        parity rows computed."""
        shards = self._check(shards, self.total_shards)
        par = gf_apply_matrix(self._parity_rows, shards[: self.data_shards])
        return jnp.concatenate([shards[: self.data_shards], par], axis=0)

    def verify(self, shards) -> bool:
        shards = self._check(shards, self.total_shards)
        par = gf_apply_matrix(self._parity_rows, shards[: self.data_shards])
        return bool(jnp.array_equal(par, shards[self.data_shards:]))

    # -- reconstruct -------------------------------------------------------

    def reconstruct_onto(self, survivors, survivor_indices, present,
                         targets) -> jax.Array:
        """Compute shard rows `targets` from surviving shards.

        survivors: [data_shards, B] uint8 shard rows, in the order named by
        survivor_indices.  survivor_indices must be the first `data_shards`
        present shard ids in ascending index order (the order the decode
        matrix is built for); anything else raises rather than silently
        producing corrupt output.
        present: total-length bool mask. targets: list of shard ids to
        produce (data and/or parity).
        """
        m, rows = rs_matrix.cached_reconstruction_matrix(
            self.data_shards, self.parity_shards,
            tuple(bool(x) for x in present), tuple(int(t) for t in targets))
        if tuple(int(i) for i in survivor_indices) != rows:
            raise ValueError(
                f"survivors must be shards {list(rows)} in that order, "
                f"got {list(survivor_indices)}")
        survivors = self._check(survivors, self.data_shards)
        return gf_apply_matrix(jnp.asarray(m), survivors)

    def reconstruct(self, shards, present, data_only: bool = False
                    ) -> np.ndarray:
        """Fill missing rows of `shards` (host array in, host array out);
        mirrors rs_cpu.ReedSolomonCPU.reconstruct."""
        shards = np.asarray(shards, dtype=np.uint8)
        present = [bool(x) for x in present]
        if shards.shape[0] != self.total_shards or \
                len(present) != self.total_shards:
            raise ValueError("bad shard array / presence mask")
        survivor_rows = [i for i in range(self.total_shards) if present[i]]
        if len(survivor_rows) < self.data_shards:
            raise ValueError("too few shards present to reconstruct")
        survivor_rows = survivor_rows[: self.data_shards]
        targets = [i for i in range(self.total_shards) if not present[i]]
        if data_only:
            targets = [i for i in targets if i < self.data_shards]
        if not targets:
            return shards.copy()
        rec = self.reconstruct_onto(
            shards[survivor_rows], survivor_rows, present, targets)
        out = shards.copy()
        out[targets] = np.asarray(rec)
        return out
