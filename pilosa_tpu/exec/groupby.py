"""Vectorized GroupBy: the whole combination tree in one device program.

Reference: ``executor.go#executeGroupByShard`` walks the cross-product of
``Rows()`` selections recursively, intersecting per combination.  Host
recursion costs one dispatch (plus a device->host read) per prefix
combination; this module instead runs ONE compiled program per block of
combinations — O(1) dispatch + O(1) reads for the entire GroupBy, any
number of levels.

Counts come from PAIRS OF ROWS, not copies of them: the innermost
``Rows()`` level and the prefix level above it are two vectorised axes
of one ``kernels.pair_counts`` matrix over the two resident planes, and
a combination selects from that small int32 matrix, never from a plane.
Only the levels ABOVE those two loop (a sequential ``lax.map`` on the
device, one pair matrix a step under the step's prefix rows).

``aggregate=Sum(field=f)`` rides the same pairs: a step's combinations
(the two innermost levels' pairs under the step's prefix rows) are the
masks of one ``bsi.sum_pair_matrix`` against the BSI plane's rows, so a
step reads the plane once for all of them.  Min/Max keep a
per-combination body mapped over every prefix level (min/max bit
descent).  Sums and bit counts reduce over shards on device in int32;
the host finishes the ``<< b`` weighting in exact int64
(``bsi.combine_sum`` policy).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.engine import bsi as bsik
from pilosa_tpu.engine import kernels

# Device shard-axis sums are int32: per-shard per-bit counts are <= 2^20,
# so totals stay exact for up to 2047 shards (kernels.SAFE_SHARD_SUM) —
# far beyond a 1B-column index (954 shards).  The executor asserts this.
MAX_SHARDS = kernels.SAFE_SHARD_SUM

# Min/Max device path reconstructs |value| as int32 from bit flags:
# depths beyond 30 bits would overflow the signed reconstruction.
MINMAX_MAX_DEPTH = 30


def block_form(prefix_planes, agg) -> str:
    """How one block's counts are computed: ``"pair"`` (a pair-count
    matrix over the two innermost levels, and for a Sum the pair
    matrix of their masks against the BSI plane) or ``"mapped"`` (one
    body per combination: Min/Max, and a bare or Count GroupBy of a
    single ``Rows()``)."""
    if agg == "sum" or (prefix_planes and agg is None):
        return "pair"
    return "mapped"


def _prefix_words(planes, ix, filter_words):
    """``filter_words`` ANDed with row slot ``ix[l]`` of every plane
    (uint32[S, W]; None when there is neither)."""
    prefix = filter_words
    for lvl, plane in enumerate(planes):
        row = plane[:, ix[lvl], :]
        prefix = row if prefix is None else jnp.bitwise_and(prefix, row)
    return prefix


def _pair_block(prefix_planes, combo_idx, last_plane, filter_words,
                agg_plane=None, agg_delta=None):
    """Outputs of one pair-form block: ``counts`` int32[Bo * n, n_last]
    and, with ``agg_plane`` (a Sum), ``pos`` / ``neg``
    int32[Bo * n, n_last, depth] and ``cnt`` int32[Bo * n, n_last].

    combo_idx: int32[Bo, n, L-1] — Bo combinations of the OUTER prefix
    levels, each with the n selected rows of the innermost prefix level
    (slots 0..n-1 of its plane: ``PlaneCache.rows_plane`` puts the
    selected rows first, the pow2 pad behind them stays out of the scan);
    int32[1, 0] for a single ``Rows()`` (a Sum only: its one step pairs
    the filter with every row).

    ``agg_delta``: the BSI plane's write overlay (``groupby_out``); its
    touched word columns leave the base pass and are counted from the
    merged mini plane, as single-word shards of the same pair matrix.
    """
    if prefix_planes:
        *outer, inner = prefix_planes
        n = combo_idx.shape[1]
        inner = inner[:, :n, :]
    else:
        outer, inner = [], None
    excl = mini = None
    if agg_delta is not None:
        from pilosa_tpu.ingest.delta import (bsi_excl_filter,
                                             bsi_mini_plane)
        cs, cw, cv, cm = agg_delta
        excl = bsi_excl_filter(agg_plane, cs, cw, None)   # [S, W]
        mini = bsi_mini_plane(agg_plane, cs, cw, cv, cm)  # [K, R, 1]
        s = agg_plane.shape[0]
        valid = jnp.where(cs < s, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        cs_c = jnp.clip(cs, 0, s - 1)

    def step(ix):
        prefix = _prefix_words(outer, ix, filter_words)
        if inner is None:
            counts = jnp.sum(kernels.row_counts(last_plane, prefix),
                             axis=0, dtype=jnp.int32)[None]
        else:
            counts = kernels.pair_counts(inner, last_plane, prefix)
        out = {"counts": counts}
        if agg_plane is None:
            return out
        base = prefix if excl is None else (
            excl if prefix is None else prefix & excl)
        pos, neg, cnt = bsik.sum_pair_matrix(agg_plane, inner, last_plane,
                                             base)
        if mini is not None:
            # the touched columns as K single-word shards: the
            # combination's words gathered there, the pads masked out
            m_pre = valid if prefix is None else valid & prefix[cs_c, cw]
            mp, mn, mc = bsik.sum_pair_matrix(
                mini, None if inner is None else inner[cs_c, :, cw][..., None],
                last_plane[cs_c, :, cw][..., None], m_pre[:, None])
            pos, neg, cnt = pos + mp, neg + mn, cnt + mc
        out.update(pos=pos, neg=neg, cnt=cnt)
        return out

    if outer:
        # a plain (sequential) map: one step is a whole pair matrix, and
        # its prefix rows are dynamic slices fused into it.  A vmapped
        # step turns the row read into a gather that the TPU expands to
        # a loop copying every row out before anything is counted.
        mats = jax.lax.map(step, combo_idx[:, 0, :-1])
    else:
        mats = jax.tree.map(lambda x: x[None], step(None))
    if inner is None:
        return jax.tree.map(lambda x: x[0], mats)
    sel = combo_idx[:, :, -1]
    return {k: jnp.take_along_axis(
                v, sel.reshape(sel.shape + (1,) * (v.ndim - 2)), axis=1)
            .reshape((-1,) + v.shape[2:])
            for k, v in mats.items()}


def groupby_out(prefix_planes, combo_idx, last_plane, filter_words,
                agg_plane, agg, agg_delta=None):
    """All GroupBy combination counts (+ optional aggregate) in one program.

    prefix_planes: tuple of uint32[S, n_l, W], one per non-innermost
        ``Rows()`` level (possibly empty).  ``combo_idx`` holds one row
        slot per level per combination: int32[Bo, n, L-1] in the pair
        form (:func:`block_form`; see :func:`_pair_block`),
        int32[C, L-1] in the mapped form (Min/Max, and a single bare
        ``Rows()``).
    last_plane: uint32[S, n_last, W] — innermost level, vectorized.
    filter_words: uint32[S, W] | None.
    agg_plane: BSI uint32[S, D+2, W] | None; agg: None | "sum" | "minmax".
    agg_delta (r20): the agg plane's pending write overlay as
        ``(col_shard, col_word, col_vals, col_mask)`` — aggregates
        answer base⊕delta with the same split the flat families use
        (touched word columns excluded from the base pass, answered
        by a merged mini plane), so GroupBy stays fold-free under
        sustained BSI ingest.

    Returns per-combination stacked outputs: counts int32[C, n_last]
    (C = Bo * n in the pair form) and aggregate arrays (see body).
    """
    if block_form(prefix_planes, agg) == "pair":
        return _pair_block(prefix_planes, combo_idx, last_plane,
                           filter_words,
                           agg_plane if agg == "sum" else None, agg_delta)
    mini = excl = None
    if agg_delta is not None:
        from pilosa_tpu.ingest.delta import (bsi_excl_filter,
                                             bsi_mini_plane)
        cs, cw, cv, cm = agg_delta
        excl = bsi_excl_filter(agg_plane, cs, cw, None)   # [S, W]
        mini = bsi_mini_plane(agg_plane, cs, cw, cv, cm)  # [K, R, 1]
        s = agg_plane.shape[0]
        cs_ok = cs < s
        cs_c = jnp.clip(cs, 0, s - 1)

    def body(ix):
        prefix = _prefix_words(prefix_planes, ix, filter_words)
        counts = jnp.sum(kernels.row_counts(last_plane, prefix), axis=0,
                         dtype=jnp.int32)
        out = {"counts": counts}
        if agg is None:
            return out
        words = (last_plane if prefix is None
                 else jnp.bitwise_and(last_plane, prefix[:, None, :]))
        aplane = agg_plane[:, None]  # (S, 1, D+2, W) broadcast over rows
        if mini is not None:
            # mini side first: the combination's filter words GATHERED
            # at the touched columns (from the PRE-exclusion words —
            # the exclusion below zeroes exactly these), zero on pad
            # lanes; base side: touched word columns masked out
            wmini = jnp.where(cs_ok[:, None],
                              words[cs_c, :, cw], 0)     # [K, n_last]
            words = jnp.bitwise_and(words, excl[:, None, :])
            mini_b = mini[:, None]       # [K, 1, R, 1] over n_last
            wmini_b = wmini[..., None]   # [K, n_last, 1]
        # minmax: signed int32 offsets, sentinel-reduced over shards
        mm = bsik.min_max_bits(aplane, words)
        if mini is not None:
            # touched columns append as pseudo-shard entries (the
            # per-key shapes match: [S, n_last, ...] ⧺ [K, n_last,
            # ...]); the sentinel reduce over axis 0 below then
            # combines base and mini exactly
            mmm = bsik.min_max_bits(mini_b, wmini_b)
            mm = {k: jnp.concatenate([mm[k], mmm[k]], axis=0)
                  for k in mm}
        depth = mm["min_bits"].shape[-1]
        weights = (jnp.int32(1) << jnp.arange(depth, dtype=jnp.int32))

        def signed(bits, neg):
            v = jnp.sum(bits.astype(jnp.int32) * weights, axis=-1)
            return jnp.where(neg, -v, v)

        big = jnp.int32(2**31 - 1)
        mn = jnp.where(mm["min_cnt"] > 0,
                       signed(mm["min_bits"], mm["min_neg"]), big)
        mx = jnp.where(mm["max_cnt"] > 0,
                       signed(mm["max_bits"], mm["max_neg"]), -big)
        gmn, gmx = jnp.min(mn, axis=0), jnp.max(mx, axis=0)
        out["min"] = gmn
        out["min_cnt"] = jnp.sum(
            jnp.where(mn == gmn[None], mm["min_cnt"], 0), axis=0,
            dtype=jnp.int32)
        out["max"] = gmx
        out["max_cnt"] = jnp.sum(
            jnp.where(mx == gmx[None], mm["max_cnt"], 0), axis=0,
            dtype=jnp.int32)
        return out

    if not prefix_planes:
        return jax.tree.map(lambda x: x[None],
                            body(jnp.zeros((0,), jnp.int32)))
    # Min/Max only: batch_size vmaps combos in chunks of 32, which
    # amortizes the per-iteration overhead of a serial map (~1.7 ms a
    # combination on a v5e — 4.3 s for a 50x50 prefix grid) while
    # bounding the fused intermediate.  Its price is the vmapped row
    # read above: a gather that copies each chunk's prefix rows out.
    return jax.lax.map(body, combo_idx, batch_size=32)


_groupby_program = partial(jax.jit, static_argnames=("agg",))(groupby_out)


@jax.jit
def level_counts(plane, filter_words):
    """int32[R]: each row's columns under the filter, summed over
    shards (a level's reach; exact while S <= MAX_SHARDS)."""
    return jnp.sum(kernels.row_counts(plane, filter_words), axis=0,
                   dtype=jnp.int32)


def run_block(planes, combo_idx, last_plane, filter_words, agg_plane, agg,
              delta=None):
    """One block as its own program (no batcher, and the batcher's
    fallback); ``delta`` is the agg plane's ``BsiOverlay`` or None."""
    at = ((delta.col_shard, delta.col_word, delta.col_vals,
           delta.col_mask) if delta is not None else None)
    return _groupby_program(planes, combo_idx, last_plane, filter_words,
                            agg_plane, agg, agg_delta=at)


def block_part_names(agg: str | None) -> tuple[str, ...]:
    """The canonical part order of one flattened GroupBy block (the
    ``fused.run_groupby_batch`` layout)."""
    if agg == "sum":
        return ("counts", "pos", "neg", "cnt")
    if agg == "minmax":
        return ("counts", "min", "min_cnt", "max", "max_cnt")
    return ("counts",)


def block_shapes(n_combos: int, n_last: int, depth: int,
                 agg: str | None) -> dict[str, tuple]:
    """Per-part shapes of one block's outputs (leading dim C = the
    padded combination count; prefix-less GroupBys run C = 1)."""
    c = n_combos
    shapes = {"counts": (c, n_last)}
    if agg == "sum":
        shapes.update(pos=(c, n_last, depth), neg=(c, n_last, depth),
                      cnt=(c, n_last))
    elif agg == "minmax":
        shapes.update({"min": (c, n_last), "min_cnt": (c, n_last),
                       "max": (c, n_last), "max_cnt": (c, n_last)})
    return shapes


def unflatten_block(flat: np.ndarray, n_combos: int, n_last: int,
                    depth: int, agg: str | None) -> dict[str, np.ndarray]:
    """Invert ``fused.run_groupby_batch``'s flatten: one packed int32
    read back into the per-part arrays ``iter_blocks`` consumers
    slice."""
    shapes = block_shapes(n_combos, n_last, depth, agg)
    out = {}
    off = 0
    for name in block_part_names(agg):
        shape = shapes[name]
        size = int(np.prod(shape, dtype=np.int64))
        out[name] = flat[off:off + size].reshape(shape)
        off += size
    return out


def combo_grid(levels: list[np.ndarray]) -> np.ndarray:
    """Cartesian product of per-level arrays in lexicographic order,
    [C, L] in the input dtype (row-slot int32 or row-id uint64 — row
    ids are uint64 like the storage layer's; fragment._check_rows caps
    them at 2^40)."""
    if not levels:
        return np.zeros((1, 0), np.int32)
    grids = np.meshgrid(*levels, indexing="ij")
    return np.stack([g.reshape(-1) for g in grids], axis=-1)


# Per-dispatch device-output budget: bounds the combination block so a
# huge tree (e.g. 256^3 combos with a Sum aggregate) streams in fixed-
# size pieces instead of materializing int32[C, n_last, depth] at once.
BLOCK_OUT_BYTES = 64 << 20
# Smaller blocks when a limit= may stop the stream after the first few
# groups — trades a couple of extra dispatches for early exit.
LIMIT_BLOCK = 1024


def iter_blocks(specs, filter_words, agg_plane, agg_kind,
                limited: bool = False, run=run_block, agg_delta=None):
    """Execute the program over lexicographic combination blocks.

    specs: list of (field, rows np.ndarray, PlaneSet); the last spec is
    the vectorized innermost level.  Yields (combo_rows uint64[B, L-1],
    outputs dict of np arrays) in combination order; callers stop
    consuming once a ``limit=`` is satisfied.  Blocks are padded to one
    static shape (single compile), the pad tail is sliced off here.  A
    pair-form block (:func:`block_form`) holds whole runs of the
    innermost prefix level — its combinations go out shaped
    int32[Bo, n, L-1], so the level's row count is a shape the program
    sees — and a block boundary falls between outer combinations.

    ``run`` (r20): an alternative block dispatcher with
    :func:`run_block`'s signature returning a dict of HOST arrays —
    the executor routes blocks through the batcher's collection
    window here, so a GroupBy block shares its dispatch window and
    packed readback with concurrent Counts/aggregates instead of
    interleaving solo device round trips.
    """
    *prefix_specs, (last_f, last_rows, last_ps) = specs
    slot_levels = [np.array([ps.slot_of[int(r)] for r in rows], np.int32)
                   for _, rows, ps in prefix_specs]
    row_levels = [np.asarray(rows, np.uint64) for _, rows, _ in prefix_specs]
    combo_slots = combo_grid(slot_levels).astype(np.int32)
    combo_rows = combo_grid(row_levels)
    n_combos = combo_slots.shape[0]

    n_last = last_ps.plane.shape[1]
    per_combo = n_last * 4
    if agg_kind == "sum":
        depth = agg_plane.plane.shape[1] - 2
        per_combo += n_last * (2 * depth + 1) * 4
    elif agg_kind == "minmax":
        per_combo += n_last * 16
    planes = tuple(ps.plane for _, _, ps in prefix_specs)
    # a block is a whole number of units: one combination, or in the
    # pair form one run of the innermost prefix level
    runs = block_form(planes, agg_kind) == "pair" and bool(prefix_specs)
    unit = len(slot_levels[-1]) if runs else 1
    block = max(unit, min(n_combos, BLOCK_OUT_BYTES // per_combo,
                          *([LIMIT_BLOCK] if limited else []))
                // unit * unit)

    aplane = agg_plane.plane if agg_plane is not None else None
    for start in range(0, n_combos, block):
        sl = combo_slots[start:start + block]
        n = sl.shape[0]
        if n < block:  # pad to the compiled shape; tail dropped below
            sl = np.concatenate(
                [sl, np.tile(sl[-unit:], ((block - n) // unit, 1))])
        if runs:
            sl = sl.reshape(block // unit, unit, -1)
        # the combo block stays a HOST array here: the batcher route
        # hashes it for dedupe (a device array would force a blocking
        # D2H read per block just to digest bytes that originated
        # host-side), and jit converts it on dispatch either way
        out = run(planes, sl, last_ps.plane,
                  filter_words, aplane, agg_kind, agg_delta)
        yield (combo_rows[start:start + n],
               {k: np.asarray(v)[:n] for k, v in out.items()})
