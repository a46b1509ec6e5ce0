"""Core bitmap kernels: XLA bitwise + popcount over packed uint32 words.

These replace the reference's pairwise container kernels — nine
container-type-pair specializations per op like ``intersectArrayBitmap`` /
``unionBitmapBitmap`` / ``intersectionCountArrayRun`` in
``roaring/roaring.go`` (SURVEY.md §3.1) — with single dense ops that XLA
fuses end-to-end (e.g. ``Intersect + Count`` compiles to one
and+popcount+reduce pass at HBM bandwidth).

All kernels are shape-polymorphic over leading batch axes: a "bitmap" is
``uint32[..., W]`` where the trailing axis is packed words.  The executor
batches ``[n_shards, W]`` (one row across resident shards) or
``[n_shards, n_rows, W]`` (a whole field plane) and the same kernels apply.

Counts are ``int32`` on device — always exact per (shard, row) since a
shard is 2^20 columns — and finished in int64 on the host where
cluster-wide totals could overflow (:func:`shard_totals`).  TPUs have no
native int64; keeping the device path int32 avoids ~1000x emulation
overhead on the popcount matrix (see ``engine._jaxcfg``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu.engine import _jaxcfg  # noqa: F401  (device int32 policy)

# ---------------------------------------------------------------------------
# Boolean algebra (reference: roaring.Bitmap Intersect/Union/Difference/Xor)
# ---------------------------------------------------------------------------


def intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_and(a, b)


def union(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_or(a, b)


def difference(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


def xor(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.bitwise_xor(a, b)


def complement(a: jax.Array, exists: jax.Array) -> jax.Array:
    """``Not(a)`` against an existence bitmap (reference: ``Not`` via the
    ``_exists`` field ANDNOT, ``executor.go#executeNot``; SURVEY.md §3.2)."""
    return jnp.bitwise_and(exists, jnp.bitwise_not(a))


# ---------------------------------------------------------------------------
# Popcount / Count (reference: Bitmap.Count, IntersectionCount)
# ---------------------------------------------------------------------------


def popcount(words: jax.Array) -> jax.Array:
    return lax.population_count(words)


# words per inner reduce tile of the popcount chain (r17 roofline
# chase).  A flat jnp.sum over a 32K-word trailing axis emits one long
# serial int32 accumulation chain per (shard, row); splitting the axis
# into COUNT_TILE-word tiles reduced innermost-first gives the
# vectorizer W/COUNT_TILE independent partial sums to interleave
# (measured per kind, before and after, in r17).  Exact
# at any tiling: every partial sum of per-word popcounts (<=32 each)
# stays far under int32.
COUNT_TILE = 512


def count(words: jax.Array) -> jax.Array:
    """Total set bits over the trailing word axis -> int32[...] (exact:
    one shard's 2^20 bits << 2^31)."""
    w = words.shape[-1]
    if w >= 2 * COUNT_TILE and w % COUNT_TILE == 0:
        tiles = words.reshape(words.shape[:-1] + (w // COUNT_TILE,
                                                  COUNT_TILE))
        inner = jnp.sum(popcount(tiles), axis=-1, dtype=jnp.int32)
        return jnp.sum(inner, axis=-1, dtype=jnp.int32)
    return jnp.sum(popcount(words), axis=-1, dtype=jnp.int32)


def intersection_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """Fused and+popcount+sum (reference: ``Bitmap.IntersectionCount`` — the
    no-materialize fast path used by ``Count(Intersect(..))``)."""
    return count(jnp.bitwise_and(a, b))


def union_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return count(jnp.bitwise_or(a, b))


def difference_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return count(jnp.bitwise_and(a, jnp.bitwise_not(b)))


def xor_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return count(jnp.bitwise_xor(a, b))


def any_bit(words: jax.Array) -> jax.Array:
    """True if any bit set over trailing axis (reference: ``Bitmap.Any``)."""
    return jnp.any(words != 0, axis=-1)


# ---------------------------------------------------------------------------
# Plane-level kernels: one field's rows as uint32[..., n_rows, W]
# ---------------------------------------------------------------------------


def row_counts(plane: jax.Array, filter_words: jax.Array | None = None) -> jax.Array:
    """Per-row popcounts, optionally intersected with a filter bitmap.

    This is the brute-force TPU replacement for the reference's per-fragment
    rank/LRU TopN cache (``cache.go#RankCache``, ``fragment.top``; SURVEY.md
    §3.2/§4.3): recount every row at HBM bandwidth instead of maintaining a
    cache + two-phase threshold protocol.

    plane: uint32[..., R, W]; filter: uint32[..., W] -> int32[..., R].
    """
    if filter_words is not None:
        plane = jnp.bitwise_and(plane, filter_words[..., None, :])
    return count(plane)


def pair_counts(a_plane: jax.Array, b_plane: jax.Array,
                filter_words: jax.Array | None = None) -> jax.Array:
    """Intersection counts of every row of one plane with every row of
    another, summed over shards: ``out[c, r] = Σ_s Σ_w popcount(filter[s, w]
    & a[s, c, w] & b[s, r, w])``.

    a_plane: uint32[S, Ra, W]; b_plane: uint32[S, Rb, W]; filter:
    uint32[S, W] -> int32[Ra, Rb] (exact while S <= SAFE_SHARD_SUM).

    The GroupBy kernel (reference: ``executor.go#executeGroupByShard``
    intersects per combination): both planes are read where they lie,
    through broadcasts only, so XLA fuses AND + popcount + reduce into
    one pass with no intermediate — a ``uint32[S, Ra, Rb, W]`` is never
    written.  The reduce is flat on purpose: :func:`count`'s
    ``COUNT_TILE`` reshape re-tiles a whole plane on the TPU when the
    row axis is not the reduced one.
    """
    words = jnp.bitwise_and(a_plane[:, :, None, :], b_plane[:, None, :, :])
    if filter_words is not None:
        words = jnp.bitwise_and(words, filter_words[:, None, None, :])
    return jnp.sum(popcount(words), axis=(0, 3), dtype=jnp.int32)


def shard_pair_counts(bitmaps, rows: jax.Array) -> jax.Array:
    """Per-shard intersection counts of each of K bitmaps with every row
    of a plane: ``out[k, r, s] = Σ_w popcount(bitmaps[k][s, w] & rows[r,
    s, w])``.

    bitmaps: K x uint32[S, W]; rows: uint32[R, S, W] (a plane as its
    rows, ``bsi.sum_pair_counts``) -> int32[K, R, S].

    One reduction per bitmap over the same rows: XLA fuses the K
    siblings into one pass that reads ``rows`` once.  The bitmaps stay
    K operands on purpose: stacked into one ``[K, S, W]`` array they
    are a copy that the v5e compiler re-lays out at some K (two items
    over 318 shards ran 8.6 ms stacked, 1.5 ms as siblings; PR 38).
    """
    return jnp.stack([jnp.sum(popcount(b[None] & rows), axis=-1,
                              dtype=jnp.int32) for b in bitmaps])


def selected_row_counts(plane: jax.Array, row_idx: jax.Array,
                        sorted_idx: bool = False) -> jax.Array:
    """Popcounts of N SELECTED rows in one pass over only their memory.

    plane: uint32[..., R, W]; row_idx: int32[N] -> int32[..., N].

    The multi-query fused popcount (ROADMAP item 5): where
    :func:`row_counts` scans every row of the plane to answer any
    subset, this gathers exactly the requested rows — one memory pass
    over ``N/R`` of the plane, N accumulators — so a batch of Counts
    touching a small fraction of a wide plane stops paying the whole
    plane's bandwidth.  ``row_idx`` is a traced operand: one compiled
    program serves any row selection of the same width.  Duplicate
    indices are fine (each answers independently); indices must be in
    range (callers resolve through the plane's slot map first).

    ``sorted_idx`` is a STATIC promise (part of the compiled program)
    that the traced indices arrive in non-decreasing order, letting
    the gather walk the row axis in ascending memory stride instead of
    request order (r17 roofline chase — the batcher sorts its slot
    unions before dispatch).  A program compiled with the promise must
    never be fed unsorted indices.
    """
    sel = jnp.take(plane, row_idx, axis=-2,
                   indices_are_sorted=sorted_idx)
    return count(sel)


# ---------------------------------------------------------------------------
# Whole-tree boolean programs (compound PQL compilation, ROADMAP item 3)
# ---------------------------------------------------------------------------
#
# A compound boolean query (``Count(Intersect(Row, Union(Row, Row),
# Not(Row)))``) evaluates here as ONE kernel: the operand bitmaps are a
# stacked ``uint32[G, ..., W]`` array (rows gathered from a resident
# plane plus any extra bitmaps), and each query's call tree is a small
# POSTFIX program folded word-wise over them.  The fold splits the
# program into a STATIC skeleton (the opcode sequence — part of the
# compiled-program cache key, like every other fused family's shape)
# and TRACED push arguments (which operand each push reads), so any
# tree of the same skeleton — any row ids, any predicate values —
# reuses one executable, and XLA fuses the whole fold into a single
# bitwise+popcount pass with no interpreter machinery at run time.
# ``Not`` needs no opcode: the planner lowers it to ``ANDNOT(exists,
# x)`` with the existence row pushed as an operand.

TREE_NOP = 0     # padding: no-op (pow2 program-length buckets)
TREE_PUSH = 1    # push rows[arg] (gathered plane rows)
TREE_PUSHX = 2   # push extras[arg] (exists / other-field / predicate)
TREE_ZERO = 3    # push an all-zero bitmap (empty Union, absent rows)
TREE_AND = 4
TREE_OR = 5
TREE_ANDNOT = 6  # a & ~b (Difference; Not via ANDNOT(exists, x))
TREE_XOR = 7
TREE_SHIFT = 8   # unary: shift top of stack by STATIC arg n columns
TREE_LIMIT = 9   # unary: keep bits ranked [off, off+lim); STATIC args

# STATIC ops carry their argument IN the skeleton (a ``(op, arg)``
# entry instead of a bare opcode): shift distances and limit bounds
# are compile-time structure, exactly like the fused "shift" node's
# ``n`` — the LRU program cache bounds the key space they open.
TREE_STATIC_OPS = (TREE_SHIFT, TREE_LIMIT)

# postfix evaluation of a depth-d call tree needs ~d+1 live values;
# the planner rejects (falls back past) this bound so a hostile tree
# cannot explode the fused expression
TREE_STACK_DEPTH = 8

_TREE_BIN = {TREE_AND: intersect, TREE_OR: union,
             TREE_ANDNOT: difference, TREE_XOR: xor}


def tree_fold(rows, skeleton: tuple, row_args: jax.Array,
              extras: jax.Array | None = None,
              extra_args: jax.Array | None = None,
              zero: jax.Array | None = None) -> jax.Array:
    """Fold ONE postfix boolean program over operand bitmaps.

    ``rows``: uint32[G, ..., W] gathered plane rows, OR a callable
    ``rows(arg) -> uint32[..., W]`` that materializes one row per
    push (the solo path passes a direct plane indexer so XLA fuses
    each row read straight into the bitwise chain — no intermediate
    gathered array); ``extras``: uint32[E, ..., W] extra bitmaps or
    None; ``skeleton``: the STATIC opcode tuple (``TREE_*`` values;
    binary ops pop two, push one); ``row_args``/``extra_args``:
    traced int32 operand indices consumed in order by the
    ``TREE_PUSH``/``TREE_PUSHX`` ops; ``zero``: the empty-bitmap
    template for ``TREE_ZERO`` (defaults to ``zeros_like(rows[0])``
    when ``rows`` is an array).  Returns the final uint32[..., W]
    bitmap.  The skeleton is trace-time structure — the emitted XLA
    is a plain fused bitwise expression chain; only the operand
    CHOICE is a runtime gather, so any tree of the same skeleton
    (any row ids, any predicate values) reuses one compiled
    program."""
    fetch = rows if callable(rows) else (lambda a: rows[a])
    stack: list = []
    ri = xi = 0
    for entry in skeleton:
        op, sarg = entry if isinstance(entry, tuple) else (entry, None)
        if op == TREE_PUSH:
            stack.append(fetch(row_args[ri]))
            ri += 1
        elif op == TREE_PUSHX:
            stack.append(extras[extra_args[xi]])
            xi += 1
        elif op == TREE_ZERO:
            stack.append(jnp.zeros_like(rows[0]) if zero is None
                         else zero)
        elif op == TREE_NOP:
            continue
        elif op == TREE_SHIFT:
            stack.append(shift(stack.pop(), sarg))
        elif op == TREE_LIMIT:
            stack.append(rank_limit(stack.pop(), sarg[0], sarg[1]))
        else:
            b = stack.pop()
            a = stack.pop()
            stack.append(_TREE_BIN[op](a, b))
    return stack[-1]


def top_n(counts: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """(values, row_ids) of the n largest counts (reference: two-phase
    ``executeTopN`` merge, SURVEY.md §4.3 — exact by construction here).

    counts: int32[R] (already reduced across shards) -> (int32[k], int32[k])
    with ``k = min(n, R)`` — an oversized ``n`` returns every row, matching
    the reference's TopN semantics.  Rows with zero count may appear;
    callers filter them.
    """
    vals, idx = lax.top_k(counts, min(n, counts.shape[-1]))
    return vals, idx


def union_rows(plane: jax.Array, row_mask: jax.Array) -> jax.Array:
    """OR together the rows of ``plane`` selected by boolean ``row_mask``.

    Used for time-quantum range unions (reference: ``viewsByTimeRange`` then
    row union; SURVEY.md §3.1) and ``Rows``-driven unions.
    plane: uint32[..., R, W], row_mask: bool[R] -> uint32[..., W].
    """
    masked = jnp.where(row_mask[..., :, None], plane, jnp.uint32(0))
    return jax.lax.reduce(
        masked,
        jnp.uint32(0),
        lambda x, y: jnp.bitwise_or(x, y),
        dimensions=(masked.ndim - 2,),
    )


def column_bits(plane: jax.Array, word_idx: jax.Array,
                bit_idx: jax.Array) -> jax.Array:
    """Membership of k columns in every row: one gather per column word.

    plane: uint32[S, R, W]; word_idx int32[k] (word of each column
    within its shard), bit_idx uint32[k] -> uint32[S, R, k] 0/1.  The
    device half of ``Extract`` (reference: v2 ``executeExtract``) — k
    column probes against all rows in ONE program instead of a host
    walk per (column, row).
    """
    g = plane[:, :, word_idx]
    return (g >> bit_idx[None, None, :]) & jnp.uint32(1)


def column_bits_grouped(plane: jax.Array, word_idx: jax.Array,
                        bit_idx: jax.Array) -> jax.Array:
    """Per-shard column probes: word_idx int32[S, k] / bit_idx
    uint32[S, k] select DIFFERENT columns in each shard ->
    uint32[S, R, k].  One program (and one host read) covers an entire
    Extract regardless of how many shards the selected columns span —
    the per-shard :func:`column_bits` dispatch loop costs one read per
    shard, ruinous on transports with a per-read floor (BASELINE.md)."""
    g = jnp.take_along_axis(plane, word_idx[:, None, :], axis=2)
    return (g >> bit_idx[:, None, :]) & jnp.uint32(1)


def shift(words: jax.Array, n: int = 1) -> jax.Array:
    """Shift every bit's column position up by ``n`` within its shard
    (reference: v2 ``Shift(row, n)`` — bits crossing the shard boundary
    drop, matching upstream's per-fragment shift).

    words: uint32[..., W]; bit order is LSB-first within a word, so a
    +1 column shift is a logical LEFT shift with carry between words.
    """
    if n < 0:
        raise ValueError("shift n must be non-negative")
    word_n, bit_n = divmod(n, 32)
    w = words.shape[-1]
    if word_n:
        # move whole words towards higher indices, zero-fill the bottom
        pad = jnp.zeros(words.shape[:-1] + (word_n,), dtype=words.dtype)
        words = jnp.concatenate([pad, words[..., :w - word_n]], axis=-1)
    if bit_n:
        carry_in = jnp.concatenate(
            [jnp.zeros(words.shape[:-1] + (1,), dtype=words.dtype),
             words[..., :-1]], axis=-1) >> (32 - bit_n)
        words = (words << bit_n) | carry_in
    return words


def rank_limit(words: jax.Array, offset: int, limit: int) -> jax.Array:
    """Keep only the bits whose global rank falls in ``[offset,
    offset + limit)`` — the device form of ``Limit(x, limit, offset)``.

    ``words``: uint32[S, W] in GLOBAL column order (shard axis in the
    serving shard order, words ascending, bits LSB-first within each
    word — the same order the host ``_limit_bitmap`` oracle walks);
    ``offset``/``limit`` are STATIC (``limit < 0`` = unbounded).  Rank
    arithmetic is int32 — safe while the shard axis stays under the
    executor's ``_REDUCE_SHARD_MAX`` (2^31 bits total), the same bound
    every fused count family already lives by."""
    shape = words.shape
    flat = words.reshape(-1)                       # [N] shard-major
    pw = popcount(flat).astype(jnp.int32)          # per-word set bits
    start = jnp.cumsum(pw) - pw                    # exclusive prefix
    lanes = jnp.arange(32, dtype=jnp.uint32)
    bits = ((flat[:, None] >> lanes[None, :])
            & jnp.uint32(1)).astype(jnp.int32)     # [N, 32]
    within = jnp.cumsum(bits, axis=1) - bits       # exclusive, per word
    rank = start[:, None] + within
    keep = (bits != 0) & (rank >= offset)
    if limit >= 0:
        keep = keep & (rank < offset + limit)
    packed = jnp.sum(jnp.where(keep, jnp.uint32(1) << lanes[None, :],
                               jnp.uint32(0)), axis=1, dtype=jnp.uint32)
    return packed.reshape(shape)


# ---------------------------------------------------------------------------
# Mutation kernels (device-side scatter of bit updates)
# ---------------------------------------------------------------------------
#
# Device analogue of ``fragment.setBit``/``clearBit`` bulk application
# (SURVEY.md §4.5).  The host op-log remains the durability truth; these
# kernels refresh a resident plane in place without a full rebuild.  To keep
# the scatter well-defined under XLA (duplicate scatter indices have
# unspecified combine order), the *host* first reduces raw bit positions to
# unique ``(word_idx, word_mask)`` pairs (``coalesce_updates``); the device
# then applies one gather + bitwise op + scatter with unique indices.
# Padding entries use ``word_idx >= n_words`` (out-of-bounds high; JAX wraps
# negative indices, so -1 is NOT a safe sentinel) and are dropped.


def apply_word_or(words: jax.Array, word_idx: jax.Array, word_mask: jax.Array) -> jax.Array:
    """words[idx] |= mask over trailing word axis; idx unique, >=W = pad."""
    words = jnp.asarray(words)
    gathered = words.at[..., word_idx].get(mode="fill", fill_value=0)
    return words.at[..., word_idx].set(
        jnp.bitwise_or(gathered, word_mask), mode="drop"
    )


def apply_word_andnot(words: jax.Array, word_idx: jax.Array, word_mask: jax.Array) -> jax.Array:
    """words[idx] &= ~mask over trailing word axis; idx unique, >=W = pad."""
    words = jnp.asarray(words)
    gathered = words.at[..., word_idx].get(mode="fill", fill_value=0)
    return words.at[..., word_idx].set(
        jnp.bitwise_and(gathered, jnp.bitwise_not(word_mask)), mode="drop"
    )


# ---------------------------------------------------------------------------
# Host-finished reductions (int64 exactness beyond int32 device range)
# ---------------------------------------------------------------------------

# Summing int32 per-shard counts over more shards than this could
# overflow int32 (2047 full shards of 2^20 bits ~ 2^31); beyond it the
# reduction chunks on device and finishes in int64 on host.
SAFE_SHARD_SUM = 2047


def shard_totals(counts: jax.Array) -> np.ndarray:
    """Reduce int32 per-shard counts over axis 0 exactly -> np.int64[...].

    Device-sums chunks that cannot overflow; the (tiny) chunk totals are
    finished in int64 on the host.  This is the cross-shard merge for
    Count/TopN/Rows at any scale without device int64 emulation.
    """
    s = counts.shape[0]
    if s <= SAFE_SHARD_SUM:
        return np.asarray(jnp.sum(counts, axis=0, dtype=jnp.int32)
                          ).astype(np.int64)
    parts = [np.asarray(jnp.sum(counts[i:i + SAFE_SHARD_SUM], axis=0,
                                dtype=jnp.int32))
             for i in range(0, s, SAFE_SHARD_SUM)]
    return np.stack(parts).astype(np.int64).sum(axis=0)
