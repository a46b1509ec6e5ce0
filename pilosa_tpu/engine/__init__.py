"""L0 device engine: packed-word bitmap kernels on JAX/XLA.

This package replaces the reference's roaring container hot path
(``roaring/roaring.go`` — array/bitmap/run containers with pairwise
specialized AND/OR/XOR/ANDNOT kernels and ``math/bits.OnesCount64``
popcounts; SURVEY.md §3.1) with dense packed ``uint32`` planes in HBM and
fused XLA bitwise + ``lax.population_count`` kernels.  Roaring remains the
host/disk format (:mod:`pilosa_tpu.store.codec`); the device side is dense:
XLA wants static shapes, and bitwise+popcount over dense words at HBM
bandwidth beats container branching on a vector machine.

This module is deliberately jax-free (host layout constants and numpy
helpers only) so that ``import pilosa_tpu`` has no side effects; the
compute modules (:mod:`.kernels`, :mod:`.bsi`, :mod:`.sparse`) configure
JAX on *their* import via :mod:`._jaxcfg` — the compile-cache location,
and NOT ``jax_enable_x64``: device accumulations stay int32 (exact per
shard) and cross-shard totals finish on the host in int64.
"""

from pilosa_tpu.engine.words import (
    SHARD_WIDTH,
    WORD_BITS,
    WORDS_PER_SHARD,
    pack_columns,
    unpack_columns,
)

__all__ = [
    "SHARD_WIDTH",
    "WORD_BITS",
    "WORDS_PER_SHARD",
    "pack_columns",
    "unpack_columns",
]
