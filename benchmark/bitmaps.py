"""Packed-bit helpers and the fragment writer (numpy only).

A shard is 2**20 columns; a row of a shard is ``uint32[32768]`` with
column ``c`` at bit ``c % 32`` of word ``c // 32``.  Fragments are
written in the pilosa roaring file format (bitmap containers only), the
on-disk format the server opens — copied from ``chip_smoke.py`` /
``pilosa_tpu/store/roaring.py`` ``serialize_dense`` so that the data
loader, like the oracle, is the benchmark's own.
"""

from __future__ import annotations

import struct

import numpy as np

SHARD_WIDTH = 1 << 20
WORDS = SHARD_WIDTH // 32

_MAGIC, _VERSION, _TYPE_BITMAP = 12348, 0, 2
_CONTAINER_WORDS = 65536 // 32


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """bool[..., 2^20] -> uint32[..., W]."""
    return np.packbits(bits, axis=-1, bitorder="little").view("<u4")


def unpack_bits(words: np.ndarray) -> np.ndarray:
    """uint32[W] -> bool[2^20]."""
    return np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                         bitorder="little").astype(bool)


def one_hot_rows(categories: np.ndarray, n_rows: int) -> np.ndarray:
    """uint8[2^20] of row ids -> uint32[n_rows, W]: row r has the
    columns whose category is r (a mutex-style field)."""
    out = np.empty((n_rows, WORDS), np.uint32)
    for r in range(n_rows):  # a broadcast compare is 100x slower
        out[r] = pack_bits(categories == r)
    return out


def bsi_depth(max_value: int) -> int:
    return max(1, int(max_value).bit_length())


def bsi_rows(values: np.ndarray, depth: int) -> np.ndarray:
    """A non-negative int field with a value on every column, base 0:
    row 0 exists, row 1 sign (never set), row 2+b bit b."""
    rows = np.zeros((2 + depth, WORDS), np.uint32)
    rows[0] = 0xFFFFFFFF
    for b in range(depth):
        rows[2 + b] = pack_bits(((values >> b) & 1).astype(bool))
    return rows


def serialize_dense(words: np.ndarray) -> bytes:
    """``uint32[R, W]`` -> one fragment file: every non-empty 65536-bit
    block as a BITMAP container keyed ``row * 16 + block``."""
    words = np.ascontiguousarray(words, dtype=np.uint32)
    r, w = words.shape
    per_row = w // _CONTAINER_WORDS
    conts = words.reshape(r * per_row, _CONTAINER_WORDS)
    cards = np.bitwise_count(conts).sum(axis=1, dtype=np.int64)
    keys = np.arange(r * per_row, dtype=np.uint64)
    nz = cards > 0
    conts, cards, keys = conts[nz], cards[nz], keys[nz]
    n = len(keys)
    meta = np.zeros(n, dtype=[("k", "<u8"), ("t", "<u2"), ("c", "<u2")])
    meta["k"], meta["t"], meta["c"] = keys, _TYPE_BITMAP, cards - 1
    data_start = 8 + 12 * n + 4 * n
    offsets = (data_start + 8192 * np.arange(n, dtype=np.int64)) \
        .astype("<u4")
    return (struct.pack("<HHI", _MAGIC, _VERSION, n) + meta.tobytes()
            + offsets.tobytes() + conts.astype("<u4").tobytes())
