"""L0 kernel tests against a numpy oracle.

Mirrors the reference's exhaustive roaring kernel tests
(``roaring/roaring_test.go``; SURVEY.md §5): every boolean op and count
checked against an independent set-based oracle, plus hypothesis
property tests over random bit patterns.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from pilosa_tpu.engine import kernels, words

W = 64  # small word count for tests; kernels are trailing-axis polymorphic
NBITS = W * 32


def mk(positions):
    return words.pack_columns(np.array(positions, dtype=np.uint64), W)


def oracle_count(ws):
    return words.popcount_words(ws)


positions_strategy = st.lists(
    st.integers(min_value=0, max_value=NBITS - 1), max_size=200, unique=True
)


def test_pack_unpack_roundtrip(rng):
    cols = np.sort(rng.choice(NBITS, size=500, replace=False)).astype(np.uint64)
    ws = words.pack_columns(cols, W)
    assert np.array_equal(words.unpack_columns(ws), cols)
    assert words.popcount_words(ws) == 500


def test_pack_out_of_range():
    with pytest.raises(ValueError):
        words.pack_columns(np.array([NBITS], dtype=np.uint64), W)


@settings(max_examples=25, deadline=None)
@given(a=positions_strategy, b=positions_strategy)
def test_boolean_algebra_matches_set_oracle(a, b):
    sa, sb = set(a), set(b)
    wa, wb = mk(a), mk(b)
    cases = {
        kernels.intersect: sa & sb,
        kernels.union: sa | sb,
        kernels.difference: sa - sb,
        kernels.xor: sa ^ sb,
    }
    for fn, expect in cases.items():
        got = set(words.unpack_columns(np.asarray(fn(wa, wb))).tolist())
        assert got == expect, fn.__name__


@settings(max_examples=25, deadline=None)
@given(a=positions_strategy, b=positions_strategy)
def test_counts_match(a, b):
    sa, sb = set(a), set(b)
    wa, wb = mk(a), mk(b)
    assert int(kernels.count(wa)) == len(sa)
    assert int(kernels.intersection_count(wa, wb)) == len(sa & sb)
    assert int(kernels.union_count(wa, wb)) == len(sa | sb)
    assert int(kernels.difference_count(wa, wb)) == len(sa - sb)
    assert int(kernels.xor_count(wa, wb)) == len(sa ^ sb)


def test_complement_against_existence():
    exists = mk(range(100))
    a = mk([5, 10, 99])
    got = set(words.unpack_columns(np.asarray(kernels.complement(a, exists))).tolist())
    assert got == set(range(100)) - {5, 10, 99}


def _np_popcount(ws):
    return np.bitwise_count(ws).astype(np.int64)


def _fill(rng, shape, fill):
    if fill == "random":
        return rng.integers(0, 2**32, size=shape, dtype=np.uint32)
    return np.full(shape, 0xFFFFFFFF if fill == "ones" else 0, np.uint32)


T = kernels.COUNT_TILE


# kernels must be polymorphic over leading axes ([n_shards, W]) and
# exact on both sides of count()'s tiling rule: widths under two
# tiles, exact multiples of the tile (the tiled reduce), and widths
# the tile does not divide (the flat reduce) — the shapes the Pallas
# kernels were swept over, now against numpy alone
@pytest.mark.parametrize("shape,fill", [
    ((4, W), "random"),              # the original [n_shards, W] case
    ((1, 64), "random"),
    ((3, 200), "random"),
    ((5, 1300), "random"),           # > 2 tiles, not divisible: flat
    ((2, 2 * T), "random"),          # exactly two tiles: tiled
    ((2, 2 * T - 1), "random"),      # one word short: flat
    ((2, 4096), "random"),
    ((11, 4 * T + 4101), "random"),  # ragged word blocks, odd shards
    ((4, 130048), "random"),         # 254 tiles
    ((3, 5, 2 * T + 96), "random"),  # a plane, width not a tile multiple
    ((2, 96), "ones"),
    ((2, 4 * T), "ones"),            # the largest partial sums there are
    ((3, 160), "empty"),
])
def test_batched_axes(rng, shape, fill):
    planes = _fill(rng, shape, fill)
    counts = np.asarray(kernels.count(planes))
    assert counts.shape == shape[:-1] and counts.dtype == np.int32
    assert np.array_equal(counts, _np_popcount(planes).sum(-1))


@pytest.mark.parametrize("s,r,w,filt", [
    (3, 10, 2048, "random"),         # rows not a multiple of 8
    (3, 10, 2048, None),
    (2, 8, 2 * T + 96, "random"),    # width the tile does not divide
    (11, 130, 2 * T + 96, "random"),  # every axis ragged at once
    (11, 130, 2 * T + 96, None),
    (1, 1, 1, "random"),
    (3, 19, 299, "random"),          # nothing a power of two
    (2, 5, 96, "empty"),             # an empty filter counts nothing
    (2, 5, 96, "ones"),              # a full one changes nothing
    (2, 8, 4 * T, "ones"),
])
def test_row_counts_shapes(rng, s, r, w, filt):
    plane = _fill(rng, (s, r, w), "random")
    fw = None if filt is None else _fill(rng, (s, w), filt)
    got = np.asarray(kernels.row_counts(plane, fw))
    masked = plane if fw is None else plane & fw[:, None, :]
    assert got.shape == (s, r) and got.dtype == np.int32
    assert np.array_equal(got, _np_popcount(masked).sum(-1))


@pytest.mark.parametrize("s,ra,rb,w,filt,pad", [
    (1, 4, 4, 96, None, 0),          # one shard
    (1, 4, 4, 96, "random", 0),
    (3, 10, 8, 2048, None, 0),       # several shards, Ra != Rb
    (3, 10, 8, 2048, "random", 0),
    (3, 16, 8, 2048, "random", 6),   # a pow2 plane's zero pad slots
    (2, 8, 32, 2 * T + 96, None, 3),
    (5, 1, 7, 299, "random", 0),     # a single prefix row
    (5, 7, 1, 299, None, 0),
    (2, 5, 3, 96, "empty", 0),       # an empty filter counts nothing
    (2, 5, 3, 4 * T, "ones", 0),     # the largest partial sums there are
])
def test_pair_counts_matches_numpy(rng, s, ra, rb, w, filt, pad):
    a = _fill(rng, (s, ra, w), "random")
    a[:, ra - pad:, :] = 0
    b = _fill(rng, (s, rb, w), "random")
    fw = None if filt is None else _fill(rng, (s, w), filt)
    got = np.asarray(kernels.pair_counts(a, b, fw))
    words_ = a[:, :, None, :] & b[:, None, :, :]
    if fw is not None:
        words_ = words_ & fw[:, None, None, :]
    assert got.shape == (ra, rb) and got.dtype == np.int32
    assert np.array_equal(got, _np_popcount(words_).sum(axis=(0, 3)))
    assert not got[ra - pad:].any()


def _plane_reads(lowered_text, plane_shape):
    """The indexed reads of a lowered program whose operand is a whole
    plane: ``stablehlo.gather`` / ``dynamic_slice`` lines naming the
    plane's tensor type among their operand types."""
    plane_type = "tensor<" + "x".join(map(str, plane_shape)) + "xui32>"
    return [line.strip() for line in lowered_text.splitlines()
            if ("stablehlo.gather" in line
                or "stablehlo.dynamic_slice" in line)
            and plane_type in line.split("->")[0]]


@pytest.mark.parametrize("filt", [False, True])
def test_a_two_level_groupby_gathers_nothing_from_a_plane(rng, filt):
    """The guard a CPU run can give against the TPU's gather-by-``while``
    coming back: with one prefix level no row of either plane is read
    by index — the one indexed read is the take on the int32 matrix."""
    import jax
    from pilosa_tpu.exec import groupby as gb
    s, n, w = 3, 10, 2048
    prefix = _fill(rng, (s, 16, w), "random")
    prefix[:, n:, :] = 0
    last = _fill(rng, (s, 8, w), "random")
    fw = _fill(rng, (s, w), "random") if filt else None
    ci = np.arange(n, dtype=np.int32).reshape(1, n, 1)
    program = jax.jit(gb.groupby_out, static_argnames=("agg",))
    text = program.lower((prefix,), ci, last, fw, None, None).as_text()
    assert _plane_reads(text, prefix.shape) == []
    assert _plane_reads(text, last.shape) == []
    assert "stablehlo.while" not in text
    gathers = [ln for ln in text.splitlines() if "stablehlo.gather" in ln]
    assert len(gathers) == 1 and "xi32>" in gathers[0].split("->")[0]
    masked = prefix[:, :n, None, :] & last[:, None, :, :]
    if filt:
        masked = masked & fw[:, None, None, :]
    got = np.asarray(program((prefix,), ci, last, fw, None, None)["counts"])
    assert np.array_equal(got, _np_popcount(masked).sum(axis=(0, 3)))


def test_row_counts_and_topn(rng):
    n_rows = 16
    plane = rng.integers(0, 2**32, size=(n_rows, W), dtype=np.uint32)
    filt = rng.integers(0, 2**32, size=(W,), dtype=np.uint32)
    counts = np.asarray(kernels.row_counts(plane, filt))
    expect = np.array([oracle_count(plane[r] & filt) for r in range(n_rows)])
    assert np.array_equal(counts, expect)

    vals, ids = kernels.top_n(kernels.row_counts(plane, None), 5)
    vals, ids = np.asarray(vals), np.asarray(ids)
    order = np.argsort(-np.array([oracle_count(plane[r]) for r in range(n_rows)]),
                       kind="stable")
    assert np.array_equal(np.sort(vals)[::-1], vals)  # descending
    assert set(vals.tolist()) == set(
        np.array([oracle_count(plane[r]) for r in range(n_rows)])[order[:5]].tolist()
    )


def test_union_rows(rng):
    plane = rng.integers(0, 2**32, size=(8, W), dtype=np.uint32)
    mask = np.array([1, 0, 1, 0, 0, 1, 0, 0], dtype=bool)
    got = np.asarray(kernels.union_rows(plane, mask))
    expect = plane[0] | plane[2] | plane[5]
    assert np.array_equal(got, expect)
    # empty mask -> zeros
    got0 = np.asarray(kernels.union_rows(plane, np.zeros(8, bool)))
    assert not got0.any()


def test_apply_word_updates(rng):
    base = rng.integers(0, 2**32, size=(W,), dtype=np.uint32)
    positions = rng.choice(NBITS, size=300, replace=False)
    idx, mask = words.coalesce_updates(positions)
    got = np.asarray(kernels.apply_word_or(base, idx, mask))
    expect_set = set(words.unpack_columns(base).tolist()) | set(positions.tolist())
    assert set(words.unpack_columns(got).tolist()) == expect_set

    got2 = np.asarray(kernels.apply_word_andnot(got, idx, mask))
    assert set(words.unpack_columns(got2).tolist()) == expect_set - set(positions.tolist())


def test_apply_word_updates_padding():
    base = np.zeros(W, dtype=np.uint32)
    idx = np.array([W, 3], dtype=np.int64)  # W = out-of-bounds pad sentinel
    mask = np.array([0xFFFFFFFF, 0b101], dtype=np.uint32)
    got = np.asarray(kernels.apply_word_or(base, idx, mask))
    assert got[3] == 0b101 and got.sum() == 0b101  # pad entry dropped
