"""EC -> normal volume decode (weed/storage/erasure_coding/ec_decoder.go).

`.ec00..09` -> `.dat` by interleaved block copy (large rows then small
rows); `.ecx` + `.ecj` -> `.idx`; dat size inferred from the max .ecx
entry when no .vif records it.
"""

from __future__ import annotations

import os

from .. import idx as idxmod
from .. import types
from ..needle import get_actual_size
from ..super_block import SUPER_BLOCK_SIZE, SuperBlock
from .ec_context import LARGE_BLOCK_SIZE, SMALL_BLOCK_SIZE

_COPY_CHUNK = 8 * 1024 * 1024


def iterate_ecx_file(index_base_file_name: str):
    """Yield (key, stored_offset, size) from .ecx (ec_decoder.go:113)."""
    with open(index_base_file_name + ".ecx", "rb") as f:
        yield from idxmod.walk_index(f.read())


def iterate_ecj_file(index_base_file_name: str):
    """Yield deleted needle ids from .ecj (ec_decoder.go:143)."""
    path = index_base_file_name + ".ecj"
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        while True:
            b = f.read(types.NEEDLE_ID_SIZE)
            if len(b) != types.NEEDLE_ID_SIZE:
                return
            yield int.from_bytes(b, "big")


def has_live_needles(index_base_file_name: str) -> bool:
    """ec_decoder.go:23 HasLiveNeedles (no-op guard for ec.decode)."""
    for _, _, size in iterate_ecx_file(index_base_file_name):
        if not types.size_is_deleted(size):
            return True
    return False


def write_idx_file_from_ec_index(base_file_name: str) -> None:
    """.ecx + .ecj -> .idx (ec_decoder.go:35): copy .ecx then append a
    tombstone entry per journaled delete."""
    with open(base_file_name + ".idx", "wb") as out:
        with open(base_file_name + ".ecx", "rb") as ecx:
            while True:
                chunk = ecx.read(_COPY_CHUNK)
                if not chunk:
                    break
                out.write(chunk)
        for key in iterate_ecj_file(base_file_name):
            out.write(idxmod.entry_bytes(key, 0,
                                         types.TOMBSTONE_FILE_SIZE))


def read_ec_volume_version(base_file_name: str) -> int:
    """Superblock lives at the start of .ec00 (ec_decoder.go:94)."""
    with open(base_file_name + ".ec00", "rb") as f:
        return SuperBlock.read_from(f).version


def find_dat_file_size(data_base_file_name: str,
                       index_base_file_name: str,
                       version: "int | None" = None) -> int:
    """Max (offset + record size) over live .ecx entries
    (ec_decoder.go:65); at least the superblock size.  `version` is
    the volume's, for a caller that holds it and no `.ec00` (the worker
    reads it from the `.dat`'s superblock: the same bytes)."""
    if version is None:
        version = read_ec_volume_version(data_base_file_name)
    dat_size = SUPER_BLOCK_SIZE
    for _, stored_off, size in iterate_ecx_file(index_base_file_name):
        if types.size_is_deleted(size):
            continue
        stop = types.to_actual_offset(stored_off) + \
            get_actual_size(size, version)
        dat_size = max(dat_size, stop)
    return dat_size


def write_dat_file(base_file_name: str, dat_file_size: int,
                   shard_file_names: list[str]) -> None:
    """ec_decoder.go:176 WriteDatFile: interleave data shard blocks back
    into the contiguous volume stream.  The row geometry follows the
    number of data shards actually passed (callers pass exactly the
    data shards, default 10; RS(6,3) volumes pass 6), so alternate
    schemes decode with the same stripe layout they were encoded
    with."""
    inputs = [open(p, "rb") for p in shard_file_names]
    n_data = len(inputs)
    try:
        with open(base_file_name + ".dat", "wb") as dat:
            remaining = dat_file_size
            while remaining >= n_data * LARGE_BLOCK_SIZE:
                for f in inputs:
                    _copy_n(f, dat, LARGE_BLOCK_SIZE)
                    remaining -= LARGE_BLOCK_SIZE
            while remaining > 0:
                for f in inputs:
                    to_read = min(remaining, SMALL_BLOCK_SIZE)
                    if to_read <= 0:
                        break
                    _copy_n(f, dat, to_read)
                    remaining -= to_read
    finally:
        for f in inputs:
            f.close()


def _copy_n(src, dst, n: int) -> None:
    left = n
    while left > 0:
        chunk = src.read(min(_COPY_CHUNK, left))
        if not chunk:
            raise IOError(f"short read copying {n} bytes from shard")
        dst.write(chunk)
        left -= len(chunk)
