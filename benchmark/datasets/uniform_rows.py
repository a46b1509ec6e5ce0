"""``uniform_rows``: pi bench ``import`` — uniform random bits over
max-row-id x max-column-id.  One set field, each bit set with
probability 1/4 (two random words ANDed), exactly ``chip_smoke.py``'s
``gen_shard`` for its field ``f``."""

from __future__ import annotations

import numpy as np

from benchmark.bitmaps import WORDS


def generate(dataset: dict, seed: int, shard: int) -> dict:
    """One shard, a pure function of (seed, shard):
    ``{"sets": {field: uint32[rows, W]}, "ints": {}}``."""
    rng = np.random.default_rng([seed, shard])
    n_rows = dataset["rows"]
    f = rng.integers(0, 1 << 32, size=(n_rows, WORDS), dtype=np.uint32)
    f &= rng.integers(0, 1 << 32, size=(n_rows, WORDS), dtype=np.uint32)
    return {"sets": {dataset["field"]: f}, "ints": {}}
