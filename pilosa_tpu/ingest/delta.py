"""Device-side delta planes: bounded write overlays merged at dispatch.

The device half of SURVEY.md §4.5 ingest (host delta queues → device
scatter), rebuilt so the QUERY path never rewrites the base plane:

- the **mirror** (:class:`DeltaMirror`) is the host-side truth of the
  overlay: an insertion-ordered ``(flat_row, word) → current word
  value`` map, absorbed from fragment mutation journals
  (``Fragment.changed_cells_since``) when a resident plane's
  generations fall behind.  A cell's value is the word's CURRENT
  contents, so sets AND clears are both "overwrite this word" — no
  separate set/clear masks, no ordering hazard.

- the **overlay** (:class:`DeltaOverlay`) is the mirror's device form:
  three pow2-padded arrays (flat row index, word index, value) the
  merge kernels consume.  Padding uses an out-of-range row index so
  scatter-adds drop pad lanes and gathers mask them.

- the **merge kernels** (:func:`adjusted_row_counts`,
  :func:`adjusted_selected_counts`) answer base⊕delta in one program:
  scan the UNCHANGED base plane exactly as the clean path does, gather
  the overlay's base words, and adjust each touched row's count by
  ``popcount(new) − popcount(old)``.  The base plane is read-only —
  no donation, no 4 GB re-scatter — so the marginal cost per query is
  one small gather + scatter-add over the overlay, and concurrent
  readers share the same immutable arrays.

Capacity is bounded (``PlaneCache.delta_cells``); past the compaction
threshold a background compactor folds the overlay into the base plane
via the existing ``dynamic_update_slice``/scatter machinery and swaps
the cache entry's generation atomically (exec/planes.py owns that).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclass
class DeltaOverlay:
    """Device form of one plane's pending write cells.

    ``rows`` are FLAT row indices (``shard_axis * R_pad + row_slot``)
    into ``plane.reshape(S * R_pad, W)``; pad lanes carry
    ``rows == S * R_pad`` (out of range → dropped/masked by the merge
    kernels).  ``vals`` are the cells' current word values — base⊕delta
    is "replace these words"."""

    rows: jax.Array   # int32[C_pad]
    words: jax.Array  # int32[C_pad]
    vals: jax.Array   # uint32[C_pad]
    n: int            # live cells (<= C_pad)
    bits: int         # set bits carried by live cells (gauge fodder)

    @property
    def nbytes(self) -> int:
        return int(self.rows.size) * 12


@dataclass
class BsiOverlay:
    """Device form of a BIT-SLICED plane's pending write cells,
    grouped by touched word-COLUMN (r20 BSI ingest).

    A BSI write changes several rows of ONE word column at once (the
    exists row, maybe the sign row, and the changed magnitude bits of
    the same 32-column word), and the aggregate kernels
    (``bit_counts``/``min_max_bits``/``range_cmp``) read whole columns
    — so the overlay's unit is the (shard, word) column, not the
    single cell.  The delta-aware aggregates split base⊕delta into

      base side   the untouched columns: the clean kernel over the
                  immutable base plane with touched word columns
                  masked OUT of the filter (:func:`bsi_excl_filter`);
      mini side   the touched columns as a tiny standalone plane
                  ``uint32[K, rows, 1]`` holding the MERGED words
                  (:func:`bsi_mini_plane`), run through the SAME
                  kernel with a per-column filter word.

    Exact by construction: every column is counted on exactly one
    side.  Pad lanes carry ``col_shard == n_shards`` (dropped by the
    exclusion scatter) and an all-zero mini filter (no contribution).
    """

    col_shard: jax.Array  # int32[K_pad] (pad lanes = n_shards)
    col_word: jax.Array   # int32[K_pad]
    col_vals: jax.Array   # uint32[K_pad, rows] new word values
    col_mask: jax.Array   # uint32[K_pad, rows] 0xFFFFFFFF = row touched
    n: int                # live touched columns (<= K_pad)
    bits: int             # set bits carried by live cell values

    @property
    def nbytes(self) -> int:
        # per lane: vals + mask words (col_vals.size covers both at
        # 4 B each) plus the shard + word indices (4 B each)
        return int(self.col_vals.size) * 8 + int(self.col_shard.size) * 8


class DeltaMirror:
    """Host mirror of one resident plane's overlay cells.

    Mutated only under the owning ``PlaneCache``'s lock; the built
    :class:`DeltaOverlay` is immutable, so serving threads read a
    fully-formed object or none.  ``cap`` bounds cells — absorb refuses
    past it and the caller compacts/rebuilds instead.

    Backing store is three parallel numpy arrays plus a cell→slot
    index: absorbing a batch costs one append/overwrite per BATCH cell
    (not a rebuild of the whole mirror), and :meth:`build_overlay` is
    a vectorized pad-copy — the work done under the cache lock scales
    with the write batch, not the overlay's fill."""

    _GROW = 1024
    # build_bsi_overlay's minimum pow2 column bucket (see there)
    BSI_COL_PAD_MIN = 64

    def __init__(self, cap: int):
        self.cap = int(cap)
        self._index: dict[tuple[int, int], int] = {}
        size = min(self._GROW, max(1, self.cap))
        self._rows = np.empty(size, np.int64)
        self._words = np.empty(size, np.int64)
        self._vals = np.empty(size, np.uint32)
        self.bits = 0  # sum of bit_count over live cell values

    def __len__(self) -> int:
        return len(self._index)

    def would_fit(self, new_cells) -> bool:
        """Whether absorbing ``new_cells`` keeps the mirror at/under
        cap (overwrites of existing cells don't grow it)."""
        grow = sum(1 for k in new_cells if k not in self._index)
        return len(self._index) + grow <= self.cap

    def absorb(self, new_cells: dict[tuple[int, int], int]) -> None:
        """Overwrite-merge journal cells (values are current word
        truth, so later absorbs supersede earlier ones per word)."""
        for key, val in new_cells.items():
            slot = self._index.get(key)
            if slot is None:
                slot = len(self._index)
                if slot >= len(self._rows):
                    grow = min(max(len(self._rows) * 2, self._GROW),
                               max(self.cap, slot + 1))
                    for name in ("_rows", "_words", "_vals"):
                        arr = getattr(self, name)
                        new = np.empty(grow, arr.dtype)
                        new[:len(arr)] = arr
                        setattr(self, name, new)
                self._index[key] = slot
                self._rows[slot], self._words[slot] = key
            else:
                self.bits -= int(self._vals[slot]).bit_count()
            self._vals[slot] = val
            self.bits += val.bit_count()

    def snapshot(self) -> dict:
        """{(flat_row, word): value} copy (the fold path's input)."""
        n = len(self._index)
        return dict(zip(zip(self._rows[:n].tolist(),
                            self._words[:n].tolist()),
                        self._vals[:n].tolist()))

    def build_overlay(self, place, flat_total: int) -> DeltaOverlay:
        """Materialize the device overlay (pow2-padded; pad rows =
        ``flat_total`` → masked/dropped by the kernels).  ``place`` is
        the device placement callable."""
        n = len(self._index)
        c_pad = _pow2(max(1, n))
        rows = np.full(c_pad, flat_total, np.int32)
        words = np.zeros(c_pad, np.int32)
        vals = np.zeros(c_pad, np.uint32)
        rows[:n] = self._rows[:n]
        words[:n] = self._words[:n]
        vals[:n] = self._vals[:n]
        return DeltaOverlay(place(rows), place(words), place(vals),
                            n=n, bits=self.bits)

    def build_bsi_overlay(self, place, n_rows: int,
                          n_shards: int) -> BsiOverlay:
        """Materialize the BSI (word-column-grouped) device overlay:
        live cells regroup by (shard, word) so each touched column
        carries its new row words + touched-row mask in one lane.
        ``n_rows`` is the plane's row count (depth + 2); pad columns
        carry ``col_shard == n_shards`` (dropped/masked).

        Vectorized: this runs under the cache lock on every absorb
        (once per write gap a read observes), so the work must stay
        one ``np.unique`` + two fancy scatters — a python loop over
        the mirror measured O(cells) per READ under sustained ingest
        and collapsed a mixed read/write phase (r20)."""
        n = len(self._index)
        flat = self._rows[:n]
        word = self._words[:n]
        # one sortable key per (shard, word) column; words are < 2^32
        key = (flat // n_rows).astype(np.int64) * (1 << 32) + word
        uniq, inv = np.unique(key, return_inverse=True)
        k = len(uniq)
        # floor the pow2 column bucket: every bucket size is a fresh
        # XLA compile of each delta-aware aggregate family, so the
        # low rungs of the ladder (1, 2, 4, ... columns) are pure
        # compile churn during ingest warm-up — pad lanes are masked,
        # so a 64-column floor costs only trivial device scratch
        k_pad = _pow2(max(self.BSI_COL_PAD_MIN, k))
        col_shard = np.full(k_pad, n_shards, np.int32)
        col_word = np.zeros(k_pad, np.int32)
        col_vals = np.zeros((k_pad, n_rows), np.uint32)
        col_mask = np.zeros((k_pad, n_rows), np.uint32)
        col_shard[:k] = (uniq >> 32).astype(np.int32)
        col_word[:k] = (uniq & 0xFFFFFFFF).astype(np.int32)
        rows_in_col = (flat % n_rows).astype(np.int64)
        col_vals[inv, rows_in_col] = self._vals[:n]
        col_mask[inv, rows_in_col] = 0xFFFFFFFF
        return BsiOverlay(place(col_shard), place(col_word),
                          place(col_vals), place(col_mask),
                          n=k, bits=self.bits)


# ---------------------------------------------------------------------------
# Merge kernels (pure jnp; jitted through FusedCache — one program per
# (plane shape, overlay bucket[, filter]) like every other fused family)
# ---------------------------------------------------------------------------


def _cell_diffs(plane: jax.Array, d_rows: jax.Array, d_words: jax.Array,
                d_vals: jax.Array, filter_words: jax.Array | None):
    """Per-cell popcount deltas vs the base plane: int32[C_pad] (pad
    lanes 0) plus each cell's plane row slot (pad lanes out of range)."""
    s, r, w = plane.shape
    total = s * r
    flat = plane.reshape(total, w)
    rc = jnp.clip(d_rows, 0, total - 1)
    base = flat[rc, d_words]
    val = d_vals
    if filter_words is not None:
        fflat = filter_words.reshape(s * w)
        f = fflat[jnp.clip((rc // r) * w + d_words, 0, s * w - 1)]
        base = jnp.bitwise_and(base, f)
        val = jnp.bitwise_and(val, f)
    diff = (jax.lax.population_count(val).astype(jnp.int32)
            - jax.lax.population_count(base).astype(jnp.int32))
    valid = d_rows < total
    diff = jnp.where(valid, diff, 0)
    slot = jnp.where(valid, rc % r, r)  # pad → R (dropped)
    return diff, slot


def adjusted_row_counts(plane: jax.Array, d_rows: jax.Array,
                        d_words: jax.Array, d_vals: jax.Array,
                        filter_words: jax.Array | None = None,
                        reduce_shards: bool = True) -> jax.Array:
    """Whole-plane per-row popcounts of base⊕delta.

    plane uint32[S, R, W]; overlay arrays int32/uint32[C_pad] →
    int32[R] (``reduce_shards``) or int32[S, R].  The base scan is
    byte-identical to the clean ``row_counts`` path; delta cells only
    adjust the touched (shard, row) entries, so N concurrent queries
    over the same (plane, overlay) pair still dedupe to one scan.
    base⊕delta stays ONE program: the adjustment traces into the same
    jit as the scan."""
    from pilosa_tpu.engine import kernels
    s, r, _ = plane.shape
    counts = kernels.row_counts(plane, filter_words)  # int32[S, R]
    diff, _slot = _cell_diffs(plane, d_rows, d_words, d_vals,
                              filter_words)
    flat = counts.reshape(s * r)
    flat = flat.at[jnp.where(d_rows < s * r, d_rows, s * r)].add(
        diff, mode="drop")
    counts = flat.reshape(s, r)
    if reduce_shards:
        return jnp.sum(counts, axis=0, dtype=jnp.int32)
    return counts


def overlay_gathered_rows(sel: jax.Array, row_idx: jax.Array,
                          d_rows: jax.Array, d_words: jax.Array,
                          d_vals: jax.Array, r_pad: int) -> jax.Array:
    """Apply the overlay's word overwrites to a row GATHER: ``sel``
    uint32[S, G, W] is ``jnp.take(plane, row_idx, axis=-2)``, and each
    overlay cell whose (shard, row slot) lands in the gathered set
    overwrites its word with the cell's current value — the base⊕delta
    form the whole-tree kernels consume (the tree folds over gathered
    WORDS, so counts-only adjustment doesn't apply; the words
    themselves must be fresh).  ``row_idx`` lanes past the live width
    may repeat slot 0 (pow2 padding); a cell matches its FIRST lane
    only, and programs never address pad lanes, so stale pad words are
    unobservable.  Pad cells (``d_rows >= S * r_pad``) drop."""
    s, g, _ = sel.shape
    total = s * r_pad
    valid = d_rows < total
    cell_s = jnp.where(valid, d_rows // r_pad, s)  # pad → out of range
    cell_slot = d_rows % r_pad
    match = (cell_slot[:, None] == row_idx[None, :]) & valid[:, None]
    lane = jnp.where(jnp.any(match, axis=1),
                     jnp.argmax(match, axis=1), g)  # no lane → drop
    return sel.at[cell_s, lane, d_words].set(d_vals, mode="drop")


def overlay_row(val: jax.Array, slot, d_rows: jax.Array,
                d_words: jax.Array, d_vals: jax.Array,
                r_pad: int) -> jax.Array:
    """Apply the overlay's word overwrites to ONE plane row: ``val``
    uint32[S, W] is ``plane[:, slot, :]`` (``slot`` traced); every
    overlay cell whose row slot matches overwrites its word.  The
    per-push form of :func:`overlay_gathered_rows` — the solo tree
    program reads rows straight off the plane, so the merge happens
    row-wise inside the same fused chain."""
    s = val.shape[0]
    total = s * r_pad
    match = (d_rows % r_pad == slot) & (d_rows < total)
    cell_s = jnp.where(match, d_rows // r_pad, s)  # non-match → drop
    return val.at[cell_s, d_words].set(d_vals, mode="drop")


def adjusted_selected_counts(plane: jax.Array, row_idx: jax.Array,
                             d_rows: jax.Array, d_words: jax.Array,
                             d_vals: jax.Array,
                             sorted_idx: bool = False) -> jax.Array:
    """Selected-row popcounts of base⊕delta, shard axis reduced on
    device: int32[N] for ``row_idx`` int32[N] (plane row slots, the
    multi-query fused gather).  Each overlay cell contributes its diff
    to EVERY matching output lane (duplicate slots answer
    independently, like the clean gather).  ``sorted_idx``: the static
    ascending-stride gather promise (see
    ``kernels.selected_row_counts``)."""
    from pilosa_tpu.engine import kernels
    sel = jnp.sum(
        kernels.selected_row_counts(plane, row_idx,
                                    sorted_idx=sorted_idx),
        axis=-2, dtype=jnp.int32)                        # int32[N]
    diff, slot = _cell_diffs(plane, d_rows, d_words, d_vals, None)
    match = slot[:, None] == row_idx[None, :]            # [C_pad, N]
    add = jnp.sum(jnp.where(match, diff[:, None], 0), axis=0,
                  dtype=jnp.int32)
    return sel + add


# ---------------------------------------------------------------------------
# BSI split kernels (r20): base-with-exclusion ⊕ merged mini plane.
# Pure jnp — jitted through FusedCache's run_*_plane_batch family; the
# overlay arrays are traced operands, so one program serves any
# overlay of the same pow2 column bucket.
# ---------------------------------------------------------------------------


def bsi_sides(plane: jax.Array, filter_words, overlay):
    """The base⊕delta split as ``[(plane, filter), ...]`` sides for
    EAGER consumers (the batcher's per-item fallbacks): the clean
    plane alone when there is no overlay, else the base with touched
    columns excluded plus the merged mini plane.  Fused programs use
    ``FusedCache._bsi_split`` (same math, traced operands)."""
    if overlay is None:
        return [(plane, filter_words)]
    return [
        (plane, bsi_excl_filter(plane, overlay.col_shard,
                                overlay.col_word, filter_words)),
        (bsi_mini_plane(plane, overlay.col_shard, overlay.col_word,
                        overlay.col_vals, overlay.col_mask),
         bsi_mini_filter(plane, overlay.col_shard, overlay.col_word,
                         filter_words))]


def bsi_excl_filter(plane: jax.Array, col_shard: jax.Array,
                    col_word: jax.Array,
                    filter_words: jax.Array | None) -> jax.Array:
    """The base side's filter: the caller's ``filter_words`` (all-ones
    when absent) with every overlay-touched word column zeroed — those
    32-column words are answered by the mini plane instead.  Pad lanes
    (``col_shard == S``) drop."""
    s, _, w = plane.shape
    base = (jnp.full((s, w), 0xFFFFFFFF, jnp.uint32)
            if filter_words is None else filter_words)
    return base.at[col_shard, col_word].set(0, mode="drop")


def bsi_mini_plane(plane: jax.Array, col_shard: jax.Array,
                   col_word: jax.Array, col_vals: jax.Array,
                   col_mask: jax.Array) -> jax.Array:
    """The mini side: each touched word column as one single-word
    shard of a tiny standalone BSI plane ``uint32[K, rows, 1]`` —
    overlay words where touched, base words elsewhere.  Pad lanes
    gather shard 0 garbage; the mini FILTER zeroes them."""
    s = plane.shape[0]
    cs = jnp.clip(col_shard, 0, s - 1)
    # row by row: the TPU lays a plane out as its rows, and one gather
    # over the [S, R, W] view makes the compiler copy the whole plane
    base_cols = jnp.stack([plane[:, r, :][cs, col_word]
                           for r in range(plane.shape[1])], axis=1)
    merged = jnp.where(col_mask.astype(bool), col_vals, base_cols)
    return merged[..., None]                      # [K, rows, 1]


def bsi_mini_filter(plane: jax.Array, col_shard: jax.Array,
                    col_word: jax.Array,
                    filter_words: jax.Array | None) -> jax.Array:
    """The mini side's per-column filter word ``uint32[K, 1]``: the
    caller's filter at each touched column (all-ones when absent),
    zero on pad lanes so they contribute nothing anywhere."""
    s = plane.shape[0]
    valid = (col_shard < s).astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF)
    if filter_words is not None:
        cs = jnp.clip(col_shard, 0, s - 1)
        valid = valid & filter_words[cs, col_word]
    return valid[:, None]                         # [K, 1]
