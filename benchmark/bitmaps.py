"""Packed-bit helpers and the fragment writer (numpy only).

A shard is 2**20 columns; a row of a shard is ``uint32[32768]`` with
column ``c`` at bit ``c % 32`` of word ``c // 32``.  Fragments are
written in the pilosa roaring file format, the on-disk format the
server opens, as a roaring writer leaves them (``serialize_rows``: an
array container for a thin block, a bitmap container otherwise; written
from the format), so that the data loader, like the oracle, is the
benchmark's own.
"""

from __future__ import annotations

import struct

import numpy as np

SHARD_WIDTH = 1 << 20
WORDS = SHARD_WIDTH // 32

_MAGIC, _VERSION, _TYPE_ARRAY, _TYPE_BITMAP = 12348, 0, 1, 2
_CONTAINER_WORDS = 65536 // 32
_ARRAY_MAX = 4096       # bits: above it a block is a bitmap container


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


def _set_bits(words: np.ndarray) -> np.ndarray:
    """Packed words -> the positions of their set bits, ascending,
    through the bytes that hold one: thin data costs little."""
    octets = np.ascontiguousarray(words).reshape(-1).view(np.uint8)
    at = np.flatnonzero(octets != 0)
    bit = np.flatnonzero(np.unpackbits(octets[at], bitorder="little")
                         .view(bool))
    return at[bit >> 3] << 3 | bit & 7


def serialize_rows(words: np.ndarray) -> bytes:
    """``uint32[R, W]`` -> one fragment file as a roaring writer leaves
    it: every non-empty 65536-bit block keyed ``row * 16 + block``, as
    an ARRAY container (its bits' low 16, sorted ``uint16``) where it
    holds at most 4,096 bits and as a BITMAP container otherwise."""
    words = np.ascontiguousarray(words, dtype="<u4")
    r, w = words.shape
    conts = words.reshape(r * w // _CONTAINER_WORDS, _CONTAINER_WORDS)
    cards = np.bitwise_count(conts).sum(axis=1, dtype=np.int64)
    keys = np.flatnonzero(cards)
    cards = cards[keys]
    n = len(keys)
    sparse = cards <= _ARRAY_MAX
    meta = np.zeros(n, dtype=[("k", "<u8"), ("t", "<u2"), ("c", "<u2")])
    meta["k"], meta["c"] = keys, cards - 1
    meta["t"] = np.where(sparse, _TYPE_ARRAY, _TYPE_BITMAP)
    # the data in uint16s, container after container: an array
    # container is one for each of its bits, a bitmap container 4,096
    sizes = np.where(sparse, cards, 4096)
    in_array = np.repeat(sparse, sizes)
    data = np.empty(len(in_array), "<u2")
    data[~in_array] = conts[keys[~sparse]].view("<u2").reshape(-1)
    # ascending over the thin blocks in key order: block by block, sorted
    data[in_array] = _set_bits(conts[keys[sparse]]) & 0xFFFF
    offsets = 8 + 12 * n + 4 * n + 2 * (np.cumsum(sizes) - sizes)
    return (struct.pack("<HHI", _MAGIC, _VERSION, n) + meta.tobytes()
            + offsets.astype("<u4").tobytes() + data.tobytes())
