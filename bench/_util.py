"""Shared benchmark helpers.  Each config script prints ONE JSON line
(same shape as the top-level bench.py) plus stderr diagnostics."""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(metric: str, value: float, unit: str, vs_baseline: float) -> None:
    print(json.dumps({"metric": metric, "value": round(value, 3),
                      "unit": unit, "vs_baseline": round(vs_baseline, 3)}))


def time_p50(fn, iters: int, warmup: int = 2) -> float:
    """Median seconds per call, READING the result every iteration.

    Read-inclusive timing is mandatory: dispatch is asynchronous, so
    a timing that ends before the host read measures the enqueue, not
    the work."""
    import jax

    def run():
        return jax.tree.map(np.asarray, fn())

    for _ in range(warmup):
        run()
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def time_wall(fn, iters: int) -> float:
    """Plain wall-clock seconds per call (host-side work included)."""
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def random_shard_rows(rng, n_shards: int, n_rows: int,
                      density: float = 0.25) -> np.ndarray:
    """uint32[n_shards, n_rows, 32768] random plane at given density."""
    words = rng.integers(0, 1 << 32, size=(n_shards, n_rows, 32768),
                         dtype=np.uint32)
    if density <= 0.25:
        words &= rng.integers(0, 1 << 32, size=words.shape, dtype=np.uint32)
    return words


def cpu_popcount(words: np.ndarray) -> int:
    if hasattr(np, "bitwise_count"):
        return int(np.bitwise_count(words).sum(dtype=np.int64))
    return int(np.unpackbits(words.reshape(-1).view(np.uint8))
               .sum(dtype=np.int64))
