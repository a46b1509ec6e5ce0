"""Bit-sliced integer (BSI) kernels: Range / Sum / Min / Max over bit planes.

Reference: ``field.go#bsiGroup`` + ``fragment.go`` range decomposition
(``fragment.rangeOp``, ``fragment.sum``; SURVEY.md §3.1, §4.4).  The
reference stores an int field as one roaring row per bit position plus an
existence ("not null") row and a sign row, and answers ``Range``/``Sum``
with boolean algebra over those rows.  We keep exactly that encoding — it
is already the right layout for a vector machine — as a dense plane:

    plane: uint32[..., depth + 2, W]
      plane[..., EXISTS_ROW, :]   not-null bitmap
      plane[..., SIGN_ROW, :]     sign bitmap (1 = negative)
      plane[..., OFFSET_ROW+b, :] bit b of |value - base|

Invariant (maintained by the store): a column never has SIGN set with zero
magnitude — there is no negative zero.

Predicates arrive as *traced* scalars/bit-masks so one compiled kernel
serves every predicate value (no recompile per query): ``pred_masks`` is
``uint32[depth]`` with lane-broadcast 0x00000000/0xFFFFFFFF per bit of
``|p|``, built by :func:`predicate_masks`.

All kernels accept arbitrary leading batch axes (the executor batches
``[n_shards, depth+2, W]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.engine import _jaxcfg  # noqa: F401  (device int32 policy)
from pilosa_tpu.engine import kernels

EXISTS_ROW = 0
SIGN_ROW = 1
OFFSET_ROW = 2



def depth_of(plane: jax.Array) -> int:
    return plane.shape[-2] - OFFSET_ROW


def predicate_masks(magnitude: int, depth: int) -> np.ndarray:
    """Lane-broadcast per-bit masks of ``|p|`` for :func:`unsigned_cmp`.

    Raises if ``|p|`` does not fit in ``depth`` bits — silently truncating
    would invert comparison results.  Callers (the executor) must saturate
    out-of-depth predicates first: a bound beyond the representable range
    has a trivial answer (everything / nothing) that needs no kernel.
    """
    if magnitude < 0:
        raise ValueError("magnitude must be non-negative")
    if depth < 64 and magnitude >= (1 << depth):
        raise ValueError(f"predicate magnitude {magnitude} exceeds bit depth {depth}")
    bits = [(magnitude >> b) & 1 for b in range(depth)]
    return np.array([0xFFFFFFFF if b else 0 for b in bits], dtype=np.uint32)


def unsigned_cmp(
    mag: jax.Array, pred_masks: jax.Array, universe: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Columns' magnitude vs predicate magnitude: (lt, eq, gt) bitmaps.

    MSB->LSB digital comparison, the same strictly-greater accumulator
    pattern as the reference's ``fragment.rangeOp`` walk (SURVEY.md §4.4),
    vectorized over 2**20 columns at once.

    mag: uint32[..., depth, W]; pred_masks: uint32[depth] (see
    :func:`predicate_masks`); universe: uint32[..., W] — the columns under
    consideration (typically the exists row).
    """
    depth = mag.shape[-2]
    eq = universe
    lt = jnp.zeros_like(universe)
    gt = jnp.zeros_like(universe)
    for b in reversed(range(depth)):
        bitplane = mag[..., b, :]
        pmask = pred_masks[b]
        lt = jnp.bitwise_or(lt, eq & ~bitplane & pmask)
        gt = jnp.bitwise_or(gt, eq & bitplane & ~pmask)
        eq = eq & ~(bitplane ^ pmask)
    return lt, eq, gt


def range_cmp(
    plane: jax.Array,
    pred_masks: jax.Array,
    pred_negative: jax.Array,
    filter_words: jax.Array | None = None,
) -> dict[str, jax.Array]:
    """All six signed comparisons of stored values vs predicate ``p``.

    Returns bitmaps {"lt","le","gt","ge","eq","ne"}; the executor picks one
    (or combines two for between).  ``pred_negative`` is a traced bool
    scalar (sign of p); ``pred_masks`` encodes ``|p|``.
    """
    exists = plane[..., EXISTS_ROW, :]
    if filter_words is not None:
        exists = exists & filter_words
    sign = plane[..., SIGN_ROW, :] & exists
    pos = exists & ~sign
    mag = plane[..., OFFSET_ROW:, :]

    m_lt, m_eq, m_gt = unsigned_cmp(mag, pred_masks, exists)

    # p >= 0: v < p  <=>  v negative, or v >= 0 with |v| < |p|
    lt_nonneg = sign | (pos & m_lt)
    # p < 0:  v < p  <=>  v negative with |v| > |p|
    lt_neg = sign & m_gt
    # p >= 0: v > p  <=>  v >= 0 with |v| > |p|
    gt_nonneg = pos & m_gt
    # p < 0:  v > p  <=>  v >= 0, or v negative with |v| < |p|
    gt_neg = pos | (sign & m_lt)
    eq_signed = jnp.where(pred_negative, sign & m_eq, pos & m_eq)

    lt = jnp.where(pred_negative, lt_neg, lt_nonneg)
    gt = jnp.where(pred_negative, gt_neg, gt_nonneg)
    return {
        "lt": lt,
        "le": lt | eq_signed,
        "gt": gt,
        "ge": gt | eq_signed,
        "eq": eq_signed,
        "ne": exists & ~eq_signed,
    }


def not_null(plane: jax.Array, filter_words: jax.Array | None = None) -> jax.Array:
    exists = plane[..., EXISTS_ROW, :]
    if filter_words is not None:
        exists = exists & filter_words
    return exists


def bit_counts(
    plane: jax.Array, filter_words: jax.Array | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-bit positive/negative popcounts + non-null count, all int32.

    Reference: ``fragment.sum`` decomposition (SURVEY.md §4.4) — per bit
    b, ``popcount(filter & bitrow_b)`` split by sign.  The device stays
    in int32 (each count <= 2^20 per shard); :func:`combine_sum` does the
    ``<< b`` weighting exactly on the host.

    Returns (pos[..., depth], neg[..., depth], count[...]), jit-safe.
    """
    exists = not_null(plane, filter_words)
    sign = plane[..., SIGN_ROW, :] & exists
    pos = exists & ~sign
    mag = plane[..., OFFSET_ROW:, :]
    pos_c = kernels.count(mag & pos[..., None, :])
    neg_c = kernels.count(mag & sign[..., None, :])
    return pos_c, neg_c, kernels.count(exists)


def sum_pair_counts(plane: jax.Array, filters) -> jax.Array:
    """:func:`bit_counts` of K items over ONE plane, the plane read once:
    int32[K, S, 2*depth+1], per item and shard ``pos[depth]``,
    ``neg[depth]`` and the non-null count — the row layout
    :func:`decode_sum_packed` reads.

    plane: uint32[S, depth+2, W]; filters: K x (uint32[S, W] | None),
    None = the exists row alone.

    A pair matrix of the K items' column masks (``F & E``, K x [S, W]
    written once) against every row of the plane
    (``kernels.shard_pair_counts``): ``pos[b] = |F & E & M_b| -
    |F & E & S & M_b|``, ``neg[b] = |F & E & S & M_b|``.  The plane is
    taken as its rows ``[R, S, W]``: the TPU lays a ``[S, R, W]`` plane
    out row by row, so the transpose is free, where a slice of the
    magnitude rows or a ``[S, R, W]`` reduce is a copy of the plane.
    The sign side (K more masks, ``F & E & S``, in the same pass) runs
    only when the sign row has a bit: a ``lax.cond``, exact, and a plane
    of non-negative offsets pays half the popcounts (PR 38's
    microbenchmark, ten items over 318 shards: 3.7 ms unsigned, 8.4 ms
    signed, against 9.3–9.4 ms for ten ``bit_counts`` in turn).
    """
    rows = jnp.transpose(plane, (1, 0, 2))
    exists = rows[EXISTS_ROW]
    masks = [exists if f is None else f & exists for f in filters]

    def unsigned(masks, rows):
        c = kernels.shard_pair_counts(masks, rows)
        return c, jnp.zeros_like(c)

    def signed(masks, rows):
        sign = rows[SIGN_ROW]
        c = kernels.shard_pair_counts(masks + [m & sign for m in masks],
                                      rows)
        return c[:len(masks)], c[len(masks):]

    has_neg = jnp.any(rows[SIGN_ROW] != 0)
    c, n = jax.lax.cond(has_neg, signed, unsigned, masks, rows)
    neg = n[:, OFFSET_ROW:]                                  # [K, d, S]
    pos = c[:, OFFSET_ROW:] - neg
    return jnp.concatenate([jnp.transpose(pos, (0, 2, 1)),
                            jnp.transpose(neg, (0, 2, 1)),
                            c[:, EXISTS_ROW, :, None]], axis=-1)


def sum_pair_matrix(plane: jax.Array, a: jax.Array | None, b: jax.Array,
                    prefix: jax.Array | None = None):
    """:func:`sum_pair_counts` of the pair masks ``a_i & b_j & prefix``
    of two planes, every pair at once and summed over shards:
    ``(pos int32[n, m, depth], neg int32[n, m, depth], cnt int32[n, m])``.

    plane: uint32[S, depth+2, W]; a: uint32[S, n, W] or None (one
    all-ones row); b: uint32[S, m, W]; prefix: uint32[S, W] or None.

    The aggregate GroupBy's pair matrix, one ``kernels.pair_counts`` of
    the two planes a step of a sequential map over the BSI plane's rows,
    the step's row folded in where ``pair_counts`` takes its filter: the
    planes are read where they lie and no mask is written.  (One fused
    reduce over all rows at once writes the ``[n, m, S, W]`` masks out on
    the TPU: 6.3 GB at 7 x 40 rows over 172 shards, by the compiler's
    own memory analysis.)  The sign side runs only when the sign row
    has a bit.
    """
    exists = plane[:, EXISTS_ROW]
    if prefix is not None:
        exists = exists & prefix
    ae = exists[:, None, :] if a is None else a & exists[:, None, :]
    depth = plane.shape[1] - OFFSET_ROW

    def over(rows, x):
        def one(r):
            row = jax.lax.dynamic_index_in_dim(plane, r, axis=1,
                                               keepdims=False)
            return kernels.pair_counts(x, b, row)
        return jax.lax.map(one, jnp.asarray(rows, jnp.int32))

    c = over([EXISTS_ROW] + list(range(OFFSET_ROW, OFFSET_ROW + depth)), ae)
    neg = jax.lax.cond(
        jnp.any(plane[:, SIGN_ROW] != 0),
        lambda: over(range(OFFSET_ROW, OFFSET_ROW + depth),
                     ae & plane[:, SIGN_ROW][:, None, :]),
        lambda: jnp.zeros((depth,) + c.shape[1:], jnp.int32))
    pos = c[1:] - neg
    return (jnp.transpose(pos, (1, 2, 0)), jnp.transpose(neg, (1, 2, 0)),
            c[0])


def code_masks(slots: np.ndarray, depth: int) -> np.ndarray:
    """uint32[N, depth]: :func:`predicate_masks` of N non-negative
    values at once (the operand of :func:`equal_rows`)."""
    bits = (np.asarray(slots, np.int64)[:, None] >> np.arange(depth)) & 1
    return np.where(bits == 1, 0xFFFFFFFF, 0).astype(np.uint32)


def equal_rows(plane: jax.Array, masks: jax.Array) -> jax.Array:
    """The columns whose stored value equals each of N non-negative
    values: uint32[S, N, W] — :func:`unsigned_cmp`'s ``eq`` for N
    predicates in one pass over the plane (``masks``: uint32[N, depth]
    from :func:`code_masks`).  Over a coded field's plane
    (``exec.planes`` ``CodeSet``) these are the field's rows."""
    rows = jnp.transpose(plane, (1, 0, 2))
    eq = (rows[EXISTS_ROW] & ~rows[SIGN_ROW])[None]
    for b in range(rows.shape[0] - OFFSET_ROW):
        eq = eq & ~(rows[OFFSET_ROW + b][None] ^ masks[:, b, None, None])
    return jnp.transpose(eq, (1, 0, 2))


def _value_masks(bits: jax.Array) -> jax.Array:
    """uint32[2^k, ...]: for every k-bit value v, the columns whose k
    bit rows (``bits``: uint32[k, ...]) spell v."""
    k = bits.shape[0]
    values = np.arange(1 << k)
    acc = jnp.full((1 << k,) + bits.shape[1:], 0xFFFFFFFF, jnp.uint32)
    for i in range(k):
        on = jnp.asarray((values >> i & 1).astype(bool)).reshape(
            (-1,) + (1,) * (bits.ndim - 1))
        acc = acc & jnp.where(on, bits[i][None], ~bits[i][None])
    return acc


# shards a step of the histogram's shard map holds masks for at once
HISTOGRAM_SHARDS = 8


def value_histogram(plane: jax.Array,
                    filter_words: jax.Array | None = None) -> jax.Array:
    """int32[2^depth]: the columns under ``filter_words`` that hold each
    non-negative stored value, summed over shards.

    The value's bits split into a high and a low half; the columns of
    every high value and of every low value are masks, and the
    histogram is their pair matrix — ``2^hi + 2^lo`` masks a shard in
    place of ``2^depth`` equalities.  The masks exist for
    ``HISTOGRAM_SHARDS`` shards at a time (a sequential map over blocks
    of the shard axis, the last block shifted back to end at the last
    shard, its shards counted already masked out), so the temporaries
    stay a few tens of MB at any shard count.  Rows are taken as
    ``[R, S, W]`` (see :func:`sum_pair_counts`): a block is a slice of
    every row, never a copy of the plane.

    Under a filter the high half is descended first: one pass finds the
    n high values the filter's columns hold (an OR over the high masks,
    no popcount), and a ``lax.switch`` on the power of two K >= n pairs
    only those K highs' masks with the low ones — a high no column
    reaches has a zero row, so the histogram is the same, for
    ``K x 2^lo`` pair popcounts a word in place of ``2^depth``.  One
    program for every filter: the bucket is chosen on the device.
    Without a filter nearly every high is reached, so the pairs are
    formed at once (:func:`histogram_pairs` counts the pairs a call
    formed)."""
    rows = jnp.transpose(plane, (1, 0, 2))
    n_shards = rows.shape[1]
    block = min(HISTOGRAM_SHARDS, n_shards)
    depth = rows.shape[0] - OFFSET_ROW
    lo = depth // 2
    hi = depth - lo

    def over_blocks(step):
        """``step(bits, e)`` of every block stacked: the block's value
        bit rows and its columns that count (present, non-negative,
        under the filter, not counted by an earlier block)."""
        def one(i):
            start = jnp.minimum(i * block, n_shards - block)
            part = jax.lax.dynamic_slice_in_dim(rows, start, block, axis=1)
            fresh = start + jnp.arange(block) >= i * block
            e = jnp.where(fresh[:, None],
                          part[EXISTS_ROW] & ~part[SIGN_ROW], jnp.uint32(0))
            if filter_words is not None:
                e = e & jax.lax.dynamic_slice_in_dim(filter_words, start,
                                                     block, axis=0)
            return step(part[OFFSET_ROW:], e)
        return jax.lax.map(one, jnp.arange(-(-n_shards // block)))

    def pairs(high_of):
        """int32[K, 2^lo]: ``high_of(high bits, e)``'s K masks paired
        with every low value's, summed over shards."""
        def step(bits, e):
            high = high_of(bits[lo:], e)
            low = _value_masks(bits[:lo])
            return jnp.sum(kernels.popcount(high[:, None] & low[None]),
                           axis=(2, 3), dtype=jnp.int32)
        return jnp.sum(over_blocks(step), axis=0, dtype=jnp.int32)

    def every_high():
        return pairs(lambda bits, e: _value_masks(bits) & e[None])

    if filter_words is None:
        return every_high().reshape(-1)

    reached = jnp.any(over_blocks(
        lambda bits, e: jnp.any((_value_masks(bits[lo:]) & e[None]) != 0,
                                axis=(1, 2))), axis=0)
    n = jnp.sum(reached, dtype=jnp.int32)

    def some_highs(k):
        def branch():
            (high_ids,) = jnp.nonzero(reached, size=k, fill_value=0)
            live = jnp.arange(k) < n
            # per slot and high bit: all ones where the bit is clear,
            # so ``row ^ flip`` is the columns that agree with the bit
            flip = jnp.where((high_ids[:, None] >> jnp.arange(hi)) & 1 == 1,
                             jnp.uint32(0), jnp.uint32(0xFFFFFFFF))

            def high_of(bits, e):
                acc = jnp.where(live[:, None, None], e[None], jnp.uint32(0))
                for b in range(hi):
                    acc = acc & (bits[b][None] ^ flip[:, b, None, None])
                return acc
            # a slot past n counts 0 at high 0: the add leaves it so
            return jnp.zeros((1 << hi, 1 << lo), jnp.int32).at[
                high_ids].add(pairs(high_of))
        return branch

    branches = ([lambda: jnp.zeros((1 << hi, 1 << lo), jnp.int32)]
                + [some_highs(1 << j) for j in range(hi)] + [every_high])
    # 0 for no high, else 1 + log2 of the power of two K >= n (the bit
    # length of n - 1, plus one)
    pick = jnp.where(n == 0, 0, 33 - jax.lax.clz(jnp.maximum(n - 1, 0)))
    return jax.lax.switch(pick, branches).reshape(-1)


def histogram_pairs(hist: np.ndarray, filtered: bool) -> int:
    """The pair popcounts a word that :func:`value_histogram` formed for
    the histogram ``hist`` it returned: ``2^depth`` unfiltered, else
    ``K x 2^lo`` for the power of two K >= the high values that hold a
    count (0 for none) — the bucket the device chose."""
    depth = int(hist.size).bit_length() - 1
    if not filtered:
        return 1 << depth
    lo = depth // 2
    n = int(np.count_nonzero(hist.reshape(-1, 1 << lo).any(axis=1)))
    return 0 if n == 0 else (1 << (n - 1).bit_length()) << lo


def combine_sum(pos_c, neg_c, cnt) -> tuple[int, int]:
    """Host combine of :func:`bit_counts` outputs over ALL leading axes:
    exact python-int (sum_of_offsets, count)."""
    pos_c = np.asarray(pos_c, dtype=np.int64)
    neg_c = np.asarray(neg_c, dtype=np.int64)
    depth = pos_c.shape[-1]
    flat_p = pos_c.reshape(-1, depth).sum(axis=0)
    flat_n = neg_c.reshape(-1, depth).sum(axis=0)
    total = sum((int(flat_p[b]) - int(flat_n[b])) << b
                for b in range(depth))
    return total, int(np.asarray(cnt, dtype=np.int64).sum())


def sum_count(
    plane: jax.Array, filter_words: jax.Array | None = None
) -> tuple[int, int]:
    """(sum of offsets, count of non-null) over all batch elements —
    device bit counts + exact host combine.  NOT jit-safe (host
    finishing); inside compiled programs use :func:`bit_counts`."""
    return combine_sum(*bit_counts(plane, filter_words))


def _mag_max(cand: jax.Array, mag: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Largest magnitude among candidate columns: (bits bool[..., depth],
    final candidate bitmap).  Data-dependent bit descent done branch-free
    with ``where`` on per-batch "any" scalars (jit/TPU friendly); the
    value is reconstructed exactly on host from the bit flags (int64-free
    device path)."""
    depth = mag.shape[-2]
    bits = []
    for b in reversed(range(depth)):
        hit = cand & mag[..., b, :]
        has = kernels.any_bit(hit)
        cand = jnp.where(has[..., None], hit, cand)
        bits.append(has)
    return jnp.stack(bits[::-1], axis=-1), cand


def _mag_min(cand: jax.Array, mag: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Smallest magnitude among candidate columns (bit flags, see
    :func:`_mag_max`)."""
    depth = mag.shape[-2]
    bits = []
    for b in reversed(range(depth)):
        zero_side = cand & ~mag[..., b, :]
        has_zero = kernels.any_bit(zero_side)
        cand = jnp.where(has_zero[..., None], zero_side, cand)
        bits.append(~has_zero)
    # columns that survived only because no zero-side existed at some bit
    # all share the same magnitude, so the flags are exact
    return jnp.stack(bits[::-1], axis=-1), cand


def min_max_bits(
    plane: jax.Array, filter_words: jax.Array | None = None
) -> dict[str, jax.Array]:
    """Per-batch min/max as device-side bit flags + counts (jit-safe,
    int64-free).  Host reconstruction in :func:`combine_min_max`.

    Reference: ``fragment.min``/``fragment.max`` bit descent (SURVEY.md
    §3.1)."""
    exists = not_null(plane, filter_words)
    sign = plane[..., SIGN_ROW, :] & exists
    pos = exists & ~sign
    mag = plane[..., OFFSET_ROW:, :]

    has_neg = kernels.any_bit(sign)
    has_pos = kernels.any_bit(pos)

    # min: most-negative (largest |.| among negatives) else smallest positive
    neg_bits, neg_cand = _mag_max(sign, mag)
    posmin_bits, posmin_cand = _mag_min(pos, mag)
    min_bits = jnp.where(has_neg[..., None], neg_bits, posmin_bits)
    min_cand = jnp.where(has_neg[..., None], neg_cand, posmin_cand)
    min_cnt = jnp.where(has_neg | has_pos, kernels.count(min_cand), 0)

    # max: largest positive else least-negative (smallest |.| among negatives)
    posmax_bits, posmax_cand = _mag_max(pos, mag)
    negmin_bits, negmin_cand = _mag_min(sign, mag)
    max_bits = jnp.where(has_pos[..., None], posmax_bits, negmin_bits)
    max_cand = jnp.where(has_pos[..., None], posmax_cand, negmin_cand)
    max_cnt = jnp.where(has_neg | has_pos, kernels.count(max_cand), 0)

    return {"min_bits": min_bits, "min_neg": has_neg, "min_cnt": min_cnt,
            "max_bits": max_bits, "max_neg": has_neg & ~has_pos,
            "max_cnt": max_cnt}


def combine_min_max(out: dict) -> list[tuple[int, int, int, int]]:
    """Host reconstruction of :func:`min_max_bits` per batch element:
    [(min_value, min_count, max_value, max_count), ...] exact python
    ints (offsets relative to base; counts 0 = no non-null columns)."""
    min_bits = np.asarray(out["min_bits"]).reshape(-1,
                                                   out["min_bits"].shape[-1])
    max_bits = np.asarray(out["max_bits"]).reshape(-1,
                                                   out["max_bits"].shape[-1])
    min_neg = np.asarray(out["min_neg"]).reshape(-1)
    max_neg = np.asarray(out["max_neg"]).reshape(-1)
    min_cnt = np.asarray(out["min_cnt"]).reshape(-1)
    max_cnt = np.asarray(out["max_cnt"]).reshape(-1)

    def val(bits) -> int:
        return sum(1 << b for b, hit in enumerate(bits) if hit)

    res = []
    for i in range(len(min_neg)):
        mn = -val(min_bits[i]) if min_neg[i] else val(min_bits[i])
        mx = -val(max_bits[i]) if max_neg[i] else val(max_bits[i])
        res.append((mn, int(min_cnt[i]), mx, int(max_cnt[i])))
    return res


# Shards per distinct_presence scan step: bounds the program's scratch
# (per-column decoded values are 4 B/col — an UNBLOCKED expansion of a
# 1B-col field materialized ~4 GB values + ~9 GB masks/indices and
# OOM'd a 16 GB chip; found in r5).  32 shards ≈ 0.5 GB
# peak per step.
DISTINCT_BLOCK = 32

# Value-space cutover: at depth <= this, presence is computed per VALUE
# on packed words (bit-plane XNOR-AND algebra — no per-column decode,
# no scatter; work ∝ 2^depth × plane, 14 s → sub-second at depth 7 /
# 1B cols).  Deeper fields keep the column-scatter scan (work ∝ cols).
DISTINCT_VALUE_DEPTH = 10
_DISTINCT_VALUE_BLOCK = 8  # values per scan step (scratch ∝ block×plane)


def distinct_presence(
    plane: jax.Array, filter_words: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Presence bitmaps over the value space: which offsets occur among
    the (filtered) columns — the device core of ``Distinct`` (v2 PQL).

    Scans shard blocks (``DISTINCT_BLOCK`` per step): each step expands
    its block's magnitudes from the bit planes and scatters into the
    carried boolean presence arrays of size ``2^depth`` (positive and
    negative offsets separately), so scratch stays per-block no matter
    the field size.  Requires ``depth <= 24`` (a 16M-entry presence
    array); the executor enforces the cap.

    plane: uint32[S, depth+2, W] -> (pos bool[2^depth], neg bool[2^depth]).
    """
    depth = depth_of(plane)
    if depth <= DISTINCT_VALUE_DEPTH:
        return _distinct_by_value(plane, filter_words)
    size = 1 << depth
    s, rows, w = plane.shape
    block = min(DISTINCT_BLOCK, s)
    pad = (-s) % block
    if pad:
        # zero shards: exists=0 -> every column maps to the dropped
        # sentinel, so padding never adds presence
        plane = jnp.concatenate(
            [plane, jnp.zeros((pad, rows, w), plane.dtype)])
        if filter_words is not None:
            filter_words = jnp.concatenate(
                [filter_words, jnp.zeros((pad, w), filter_words.dtype)])
    n_blocks = plane.shape[0] // block
    plane_blocks = plane.reshape(n_blocks, block, rows, w)
    fw_blocks = (jnp.zeros((n_blocks, 0), plane.dtype)
                 if filter_words is None
                 else filter_words.reshape(n_blocks, block, w))

    def expand(words: jax.Array) -> jax.Array:
        # uint32[..., W] -> uint32[..., W*32] (column-major LSB-first)
        bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
        return bits.reshape(*words.shape[:-1], -1)

    def step(carry, inputs):
        pos, neg = carry
        pl, fw = inputs
        exists = not_null(pl, fw if fw.size else None)
        sign = pl[..., SIGN_ROW, :] & exists
        mag = pl[..., OFFSET_ROW:, :]
        values = jnp.zeros((block, w * 32), dtype=jnp.uint32)
        for b in range(depth):
            values = values | (expand(mag[..., b, :]) << b)
        exists_b = expand(exists).astype(bool)
        sign_b = expand(sign).astype(bool)
        # out-of-range sentinel drops non-participating columns
        pos_idx = jnp.where(exists_b & ~sign_b, values, size)
        neg_idx = jnp.where(exists_b & sign_b, values, size)
        pos = pos.at[pos_idx.reshape(-1)].set(True, mode="drop")
        neg = neg.at[neg_idx.reshape(-1)].set(True, mode="drop")
        return (pos, neg), None

    init = (jnp.zeros(size, bool), jnp.zeros(size, bool))
    (pos, neg), _ = jax.lax.scan(step, init, (plane_blocks, fw_blocks))
    return pos, neg


def _distinct_by_value(plane: jax.Array,
                       filter_words: jax.Array | None):
    """Small-value-space Distinct: for each magnitude ``v`` the match
    words are ``AND_b (bit_b(v) ? mag_b : ~mag_b) & exists`` — packed
    32-cols-per-word algebra, scanned ``_DISTINCT_VALUE_BLOCK`` values
    per step.  presence[v] = any match word nonzero, split by sign."""
    depth = depth_of(plane)
    size = 1 << depth
    exists = not_null(plane, filter_words)
    sign = plane[..., SIGN_ROW, :] & exists
    mag = plane[..., OFFSET_ROW:, :]
    vb = min(_DISTINCT_VALUE_BLOCK, size)
    vals = jnp.arange(size, dtype=jnp.uint32).reshape(-1, vb)

    def step(_, block_vals):
        m = jnp.broadcast_to(exists, (vb,) + exists.shape)
        for b in range(depth):
            pb = mag[..., b, :]
            bit = ((block_vals >> b) & 1).astype(bool)
            m = m & jnp.where(bit[:, None, None], pb, ~pb)
        pos = jnp.any((m & ~sign).astype(bool), axis=(1, 2))
        neg = jnp.any((m & sign).astype(bool), axis=(1, 2))
        return None, (pos, neg)

    _, (pos, neg) = jax.lax.scan(step, None, vals)
    return pos.reshape(-1), neg.reshape(-1)


def min_max(
    plane: jax.Array, filter_words: jax.Array | None = None
) -> list[tuple[int, int, int, int]]:
    """Per-batch (min_offset, min_count, max_offset, max_count) — device
    bit descent + exact host reconstruction.  NOT jit-safe; inside
    compiled programs use :func:`min_max_bits`."""
    return combine_min_max(min_max_bits(plane, filter_words))


def decode_sum_packed(row: np.ndarray) -> tuple[int, int]:
    """Host decode of one ``fused.run_sum_plane_batch`` row
    (int32[n_shards, 2*depth+1]) -> exact (sum of offsets, count)."""
    depth = (row.shape[-1] - 1) // 2
    return combine_sum(row[:, :depth], row[:, depth:2 * depth], row[:, -1])


def decode_minmax_packed(row: np.ndarray):
    """Host decode of one ``fused.run_minmax_plane_batch`` row
    (int32[n_shards (+ overlay columns), 2*depth+4]) -> per-entry
    (min, min_cnt, max, max_cnt) tuples (zero-count entries are
    dropped by the caller's combine)."""
    depth = (row.shape[-1] - 4) // 2
    return combine_min_max({
        "min_bits": row[:, :depth],
        "max_bits": row[:, depth:2 * depth],
        "min_neg": row[:, 2 * depth].astype(bool),
        "min_cnt": row[:, 2 * depth + 1],
        "max_neg": row[:, 2 * depth + 2].astype(bool),
        "max_cnt": row[:, 2 * depth + 3]})


# ---------------------------------------------------------------------------
# Percentile: the whole binary search on device, one dispatch
# ---------------------------------------------------------------------------


def _count_le_device(plane: jax.Array, filter_words, v: jax.Array,
                     depth: int) -> jax.Array:
    """count of columns with stored offset <= signed ``v`` — traced-value
    variant of the executor's compare path (one :func:`range_cmp` with
    masks derived from the traced scalar instead of host-built)."""
    neg = v < 0
    mag_v = jnp.abs(v).astype(jnp.uint32)
    bits = (mag_v >> jnp.arange(depth, dtype=jnp.uint32)) & jnp.uint32(1)
    masks = jnp.where(bits > 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    le = range_cmp(plane, masks, neg, filter_words)["le"]
    # int32-exact: total bits <= n_shards * 2^20 < 2^31 for <= 2047 shards
    return jnp.sum(kernels.popcount(le), dtype=jnp.int32)


def percentile_total(plane: jax.Array,
                     filter_words: jax.Array | None) -> jax.Array:
    """Non-null (filtered) column count, int32 — the rank universe for
    :func:`percentile_search`.  The host computes the exact integer
    target rank from this (device float32 would misround products past
    2^24; int64 is emulated on TPU)."""
    return jnp.sum(kernels.popcount(not_null(plane, filter_words)),
                   dtype=jnp.int32)


def percentile_search(plane: jax.Array, filter_words: jax.Array | None,
                      target: jax.Array):
    """[offset, count_at_offset] stacked int32: the smallest stored
    offset whose ``count_le`` reaches ``target`` — the whole binary
    search as ONE program via ``lax.while_loop`` over compare+popcount
    steps (the reference's ``executeSumCountShard``-style per-step
    dispatch pays a device round trip per bit of depth; SURVEY.md §4.4).

    ``target`` is a traced int32 rank >= 1 (exact, host-computed).

    Iteration is bounded STATICALLY by the bit depth (r20): the
    search interval is ``2^(depth+1) - 1`` wide and halves per step,
    so ``depth + 1`` steps always converge — a ``fori_loop`` with
    that trip count replaces the data-dependent ``while_loop``, which
    XLA must lower as a device-side dynamic loop with a convergence
    check per step (the fori form's trip count is auditable and it
    unrolls/pipelines freely).  Converged steps are no-ops (``lo >=
    hi`` keeps both bounds via the ``where``).  Microbench (CPU, 4
    shards × depth 16, warm programs): host-driven bisection pays 17
    device dispatches/call at 8.0 ms; this one cached program answers
    in 3.7 ms — 2.2x, and the device->host read count falls from 18
    to 2."""
    depth = depth_of(plane)
    bound = (1 << depth) - 1

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) >> 1  # arithmetic shift: floor for negatives
        le = _count_le_device(plane, filter_words, mid, depth)
        done = lo >= hi
        new_lo = jnp.where(done | (le >= target), lo, mid + 1)
        new_hi = jnp.where(done, hi, jnp.where(le >= target, mid, hi))
        return new_lo, new_hi

    lo, _ = jax.lax.fori_loop(
        0, depth + 1, body, (jnp.int32(-bound), jnp.int32(bound)))
    at = _count_le_device(plane, filter_words, lo, depth)
    below = jnp.where(
        lo > -bound,
        _count_le_device(plane, filter_words, lo - 1, depth), 0)
    return jnp.stack([lo, at - below])
