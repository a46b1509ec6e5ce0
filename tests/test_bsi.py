"""BSI kernel tests against a numpy oracle.

Mirrors the reference's BSI range/sum edge-case tests (sign, base,
boundaries; ``fragment_test.go``, SURVEY.md §5)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from pilosa_tpu.engine import bsi, kernels, words

W = 64
NBITS = W * 32
DEPTH = 12
LO, HI = -(1 << (DEPTH - 1)), (1 << (DEPTH - 1)) - 1


def encode(cols, vals, base=0):
    return words.bsi_encode(np.array(cols, np.uint64), np.array(vals, np.int64),
                            base, DEPTH, W)


def to_set(ws):
    return set(words.unpack_columns(np.asarray(ws)).tolist())


values_strategy = st.lists(
    st.tuples(
        st.integers(min_value=0, max_value=NBITS - 1),
        st.integers(min_value=LO, max_value=HI),
    ),
    max_size=100,
    unique_by=lambda t: t[0],
)


@settings(max_examples=20, deadline=None)
@given(pairs=values_strategy, pred=st.integers(min_value=LO, max_value=HI))
def test_range_cmp(pairs, pred):
    cols = [c for c, _ in pairs]
    vals = [v for _, v in pairs]
    plane = encode(cols, vals)
    masks = jnp.asarray(bsi.predicate_masks(abs(pred), DEPTH))
    out = bsi.range_cmp(plane, masks, jnp.asarray(pred < 0))
    d = dict(zip(cols, vals))
    oracles = {
        "lt": {c for c, v in d.items() if v < pred},
        "le": {c for c, v in d.items() if v <= pred},
        "gt": {c for c, v in d.items() if v > pred},
        "ge": {c for c, v in d.items() if v >= pred},
        "eq": {c for c, v in d.items() if v == pred},
        "ne": {c for c, v in d.items() if v != pred},
    }
    for op, expect in oracles.items():
        assert to_set(out[op]) == expect, op


@settings(max_examples=20, deadline=None)
@given(pairs=values_strategy)
def test_sum_count_min_max(pairs):
    cols = [c for c, _ in pairs]
    vals = [v for _, v in pairs]
    plane = encode(cols, vals)
    total, cnt = bsi.sum_count(plane)
    assert cnt == len(cols)
    assert total == sum(vals)

    ((mn, mn_c, mx, mx_c),) = bsi.min_max(plane)
    if cols:
        assert mn == min(vals)
        assert mn_c == vals.count(min(vals))
        assert mx == max(vals)
        assert mx_c == vals.count(max(vals))
    else:
        assert mn_c == 0 and mx_c == 0


def test_base_offset_encoding():
    # base shifts stored offsets; kernels work in offset space
    cols, vals = [1, 2, 3], [100, 150, 90]
    base = 100
    plane = words.bsi_encode(np.array(cols, np.uint64), np.array(vals, np.int64),
                             base, DEPTH, W)
    total, cnt = bsi.sum_count(plane)
    assert total + base * cnt == sum(vals)
    masks = jnp.asarray(bsi.predicate_masks(abs(120 - base), DEPTH))
    out = bsi.range_cmp(plane, masks, jnp.asarray(120 - base < 0))
    assert to_set(out["lt"]) == {1, 3}  # values < 120


def test_filtered_sum_and_range():
    cols, vals = [0, 1, 2, 3], [5, -7, 9, 11]
    plane = encode(cols, vals)
    filt = words.pack_columns(np.array([0, 1], np.uint64), W)
    total, cnt = bsi.sum_count(plane, jnp.asarray(filt))
    assert (total, cnt) == (-2, 2)
    ((mn, mn_c, mx, mx_c),) = bsi.min_max(plane, jnp.asarray(filt))
    assert (mn, mn_c, mx, mx_c) == (-7, 1, 5, 1)


def test_batched_shard_axis(rng):
    # [n_shards, depth+2, W] batching
    p0 = encode([1, 2], [3, -4])
    p1 = encode([5], [7])
    planes = jnp.stack([jnp.asarray(p0), jnp.asarray(p1)])
    # sum_count combines over ALL leading axes (the executor's use);
    # per-shard splits come from bit_counts
    total, cnt = bsi.sum_count(planes)
    assert (total, cnt) == (6, 3)
    pos, neg, c = bsi.bit_counts(planes)
    assert np.asarray(c).tolist() == [2, 1]
    per_shard = bsi.min_max(planes)
    assert [t[0] for t in per_shard] == [-4, 7]   # per-shard min
    assert [t[2] for t in per_shard] == [3, 7]    # per-shard max


@pytest.mark.parametrize("signed", [True, False], ids=["sign_set", "sign_empty"])
@pytest.mark.parametrize("items", [
    (None,), (0,), (0, None), (None, 0, 1, 2, 0), tuple(range(10))],
    ids=["k1_unfiltered", "k1", "k2", "k5_dup", "k10"])
def test_sum_pair_counts_equals_bit_counts_item_by_item(signed, items):
    """The one-read K-item form against the per-item kernel on random
    bits (every row, the exists and sign rows too): the sign test takes
    the signed pass when the sign row has a bit, the unsigned one when
    it is empty — both exact."""
    rng = np.random.default_rng(len(items) * 2 + signed)
    s, rows = 3, DEPTH + 2
    plane = rng.integers(0, 2**32, (s, rows, W), dtype=np.uint32)
    if not signed:
        plane[:, bsi.SIGN_ROW] = 0
    filters = rng.integers(0, 2**32, (10, s, W), dtype=np.uint32)
    fs = [None if i is None else jnp.asarray(filters[i]) for i in items]
    out = np.asarray(jax.jit(bsi.sum_pair_counts)(jnp.asarray(plane), fs))
    assert out.shape == (len(items), s, 2 * DEPTH + 1)
    for k, f in enumerate(fs):
        pos, neg, cnt = bsi.bit_counts(jnp.asarray(plane), f)
        want = np.concatenate([pos, neg, np.asarray(cnt)[:, None]], axis=-1)
        np.testing.assert_array_equal(out[k], want)
    assert (out[..., DEPTH:2 * DEPTH] != 0).any() == signed


HISTOGRAM_CASES = {
    # id: (depth, shards, the high values the filter keeps; None = no
    # filter, "all" = half the columns at random, "dropped" = only the
    # columns the histogram leaves out: negative or absent)
    "unfiltered": (10, 3, None),
    "empty_filter": (10, 3, ()),
    "one_high": (10, 3, (7,)),
    "two_far_highs": (10, 3, (1, 30)),
    "three_highs_k4": (10, 3, (0, 2, 5)),
    "every_high": (10, 3, "all"),
    "odd_depth": (7, 3, (2, 9)),
    "shifted_last_block": (8, 11, (3,)),
    "shifted_last_block_unfiltered": (8, 11, None),
    "negative_and_absent_only": (10, 3, "dropped"),
}


@pytest.mark.parametrize("depth,n_shards,highs", HISTOGRAM_CASES.values(),
                         ids=HISTOGRAM_CASES.keys())
def test_value_histogram_equals_numpy_under_the_filter(depth, n_shards,
                                                        highs):
    """The histogram of the non-negative stored values under a filter
    against numpy's, whichever bucket of high values the filter picks;
    the pairs it formed are the bucket's (``K x 2^lo``, ``2^depth``
    without a filter)."""
    rng = np.random.default_rng(depth * 100 + n_shards)
    lo = depth // 2
    vals = rng.integers(-(1 << depth) + 1, 1 << depth, (n_shards, NBITS))
    present = rng.random((n_shards, NBITS)) < 0.9
    plane = np.stack([
        words.bsi_encode(np.flatnonzero(present[s]).astype(np.uint64),
                         vals[s][present[s]], 0, depth, W)
        for s in range(n_shards)])
    counted = present & (vals >= 0)
    if highs is None:
        keep = np.ones_like(present)
    elif highs == "all":
        keep = rng.random(present.shape) < 0.5
    elif highs == "dropped":
        keep = ~counted
    else:
        keep = np.isin(np.abs(vals) >> lo, highs)
    filt = np.stack([words.pack_columns(np.flatnonzero(k).astype(np.uint64),
                                        W) for k in keep])
    got = np.asarray(jax.jit(bsi.value_histogram)(
        jnp.asarray(plane), None if highs is None else jnp.asarray(filt)))
    want = np.bincount(vals[counted & keep], minlength=1 << depth)
    np.testing.assert_array_equal(got, want)
    n = len(np.unique(vals[counted & keep] >> lo))
    if highs not in (None, "all", "dropped"):
        assert n == len(highs)
    pairs = (1 << depth if highs is None else
             0 if n == 0 else (1 << (n - 1).bit_length()) << lo)
    assert bsi.histogram_pairs(got, highs is not None) == pairs
