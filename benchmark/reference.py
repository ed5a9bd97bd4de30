"""The plain reference: Reed-Solomon over GF(2^8) in numpy.

A copy of the arithmetic of `seaweedfs_tpu/ops/gf256.py` and
`ops/rs_matrix.py` (polynomial 0x11D, Vandermonde matrix made
systematic, as klauspost/reedsolomon builds it), kept here so that no
later change to the program can move the yardstick.  It imports nothing
of the program and reads only what the timed jobs left on the servers'
disks: the shard files.  `parity_mismatch` recomputes every parity byte
from the data shards in blocks over a process pool.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import numpy as np

POLY = 29            # low bits of 0x11D = x^8 + x^4 + x^3 + x^2 + 1
BLOCK = 4 << 20      # bytes of every shard compared by one pool task


def _tables() -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
    log = np.zeros(256, dtype=np.int32)
    exp = np.zeros(510, dtype=np.uint8)
    b = 1
    for i in range(255):
        log[b] = i
        exp[i] = exp[i + 255] = b
        b <<= 1
        if b >= 256:
            b = (b - 256) ^ POLY
    mul = exp[log[:, None] + log[None, :]]
    mul[0, :] = 0
    mul[:, 0] = 0
    return log, exp, mul.astype(np.uint8)


LOG, EXP, MUL = _tables()


def gf_exp(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[int(LOG[a]) * n % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    return int(EXP[(255 - int(LOG[a])) % 255])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[1]):
        out ^= MUL[a[:, i][:, None], b[i][None, :]]
    return out


def gf_invert(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    work = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for r in range(n):
        if work[r, r] == 0:
            for below in range(r + 1, n):
                if work[below, r] != 0:
                    work[[r, below]] = work[[below, r]]
                    break
        if work[r, r] == 0:
            raise ValueError("singular matrix")
        if work[r, r] != 1:
            work[r] = MUL[gf_inv(int(work[r, r]))][work[r]]
        for other in range(n):
            if other != r and work[other, r] != 0:
                work[other] ^= MUL[int(work[other, r])][work[r]]
    return work[:, n:].copy()


@functools.lru_cache(maxsize=None)
def _parity_matrix_bytes(k: int, r: int) -> bytes:
    v = np.array([[gf_exp(i, j) for j in range(k)]
                  for i in range(k + r)], dtype=np.uint8)
    return gf_matmul(v, gf_invert(v[:k]))[k:].tobytes()


def parity_matrix(k: int, r: int) -> np.ndarray:
    """[r, k] generator rows: V = vandermonde(k + r, k) with
    V[i][j] = i**j, G = V @ inv(V[:k]); rows k.. of G."""
    return np.frombuffer(_parity_matrix_bytes(k, r),
                         dtype=np.uint8).reshape(r, k).copy()


def parity(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[j] = XOR_i mat[j, i] * data[i] over [k, B] uint8 rows."""
    out = np.zeros((mat.shape[0], data.shape[1]), dtype=np.uint8)
    for i in range(mat.shape[1]):
        for j in range(mat.shape[0]):
            out[j] ^= MUL[mat[j, i]][data[i]]
    return out


def _block_mismatch(task) -> int:
    paths, k, pos, n = task
    rows = []
    for p in paths:
        with open(p, "rb") as f:
            f.seek(pos)
            rows.append(np.frombuffer(f.read(n), dtype=np.uint8))
    if any(len(r) != n for r in rows):
        return n * (len(paths) - k)     # a short shard: all of it wrong
    want = parity(parity_matrix(k, len(paths) - k), np.stack(rows[:k]))
    return int(np.count_nonzero(want != np.stack(rows[k:])))


def parity_mismatch(shard_sets: "list[list[str]]", k: int,
                    workers: int = 8) -> int:
    """Parity bytes that differ from the reference's, summed over the
    shard sets (each the k + r shard paths of one volume, in shard
    order).  Shards of unequal length count wholly as wrong."""
    tasks, bad = [], 0
    for paths in shard_sets:
        sizes = {os.path.getsize(p) for p in paths}
        size = min(sizes)
        if len(sizes) != 1:
            bad += (max(sizes) - size) * (len(paths) - k)
        tasks += [(paths, k, pos, min(BLOCK, size - pos))
                  for pos in range(0, size, BLOCK)]
    if not tasks:
        return bad
    with ProcessPoolExecutor(min(workers, len(tasks)),
                             mp_context=get_context("spawn")) as pool:
        return bad + sum(pool.map(_block_mismatch, tasks, chunksize=4))
