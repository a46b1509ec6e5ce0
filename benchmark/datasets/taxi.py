"""``taxi``: one ride per column.  Every categorical field holds
exactly one row per column, drawn from the seed with the fixed shares
the configuration states (``shares`` per field, quantised to 1/65536 by
a lookup table — one uint16 draw and one gather per field); the int
field has a value on every column, drawn with the same device from
``int_shares``-style buckets (uniform inside a bucket)."""

from __future__ import annotations

import numpy as np

from benchmark.bitmaps import SHARD_WIDTH, one_hot_rows

_LUT_SIZE = 1 << 16


def _lut(shares: list) -> np.ndarray:
    """shares (any positive weights) -> uint8[65536] of category ids,
    each category with its share of the table, at least one slot."""
    w = np.asarray(shares, dtype=np.float64)
    edges = np.round(np.cumsum(w / w.sum()) * _LUT_SIZE).astype(np.int64)
    edges = np.maximum(edges, np.arange(1, len(w) + 1))
    edges[-1] = _LUT_SIZE
    lut = np.zeros(_LUT_SIZE, np.uint8)
    lo = 0
    for cat, hi in enumerate(edges):
        lut[lo:hi] = cat
        lo = hi
    return lut


def generate(dataset: dict, seed: int, shard: int) -> dict:
    rng = np.random.default_rng([seed, shard])
    sets, ints = {}, {}
    for field, spec in dataset["set_fields"].items():
        draw = rng.integers(0, _LUT_SIZE, size=SHARD_WIDTH, dtype=np.uint16)
        sets[field] = one_hot_rows(_lut(spec["shares"])[draw],
                                   len(spec["shares"]))
    for field, spec in dataset["int_fields"].items():
        # bucket b covers [edges[b], edges[b+1]); uniform inside
        draw = rng.integers(0, _LUT_SIZE, size=SHARD_WIDTH, dtype=np.uint16)
        bucket = _lut(spec["shares"])[draw]
        edges = np.asarray(spec["edges"], dtype=np.int64)
        lo, hi = edges[:-1][bucket], edges[1:][bucket]
        inside = rng.integers(0, 1 << 16, size=SHARD_WIDTH, dtype=np.uint16)
        ints[field] = (lo + (inside.astype(np.int64) * (hi - lo) >> 16)) \
            .astype(np.int32)
    return {"sets": sets, "ints": ints}
