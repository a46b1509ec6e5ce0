"""Pallas kernel tests: interpreter mode on CPU against numpy oracles
and the XLA kernels (same-answer guarantees for the hot-loop variants),
plus a chip-less TPU lowering of every kernel at serving shapes — the
interpreter accepts block shapes Mosaic rejects, so parity alone never
showed whether a kernel could run on the device it was written for."""

import numpy as np
import pytest

from pilosa_tpu.engine import kernels, pallas_kernels
from pilosa_tpu.engine.words import pack_columns

W = 2048  # smaller word count keeps interpreter-mode tests fast


@pytest.fixture
def planes(rng):
    s, r = 3, 10
    plane = rng.integers(0, 1 << 32, size=(s, r, W), dtype=np.uint32)
    filt = rng.integers(0, 1 << 32, size=(s, W), dtype=np.uint32)
    return plane, filt


class TestSwarPopcount:
    def test_matches_numpy(self, rng):
        import jax.numpy as jnp
        x = rng.integers(0, 1 << 32, size=(64,), dtype=np.uint32)
        got = np.asarray(pallas_kernels._popcount_u32(jnp.asarray(x)))
        expect = np.bitwise_count(x).astype(np.int32)
        np.testing.assert_array_equal(got, expect)

    def test_edges(self):
        import jax.numpy as jnp
        x = jnp.asarray(np.array([0, 1, 0xFFFFFFFF, 0x80000000], np.uint32))
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels._popcount_u32(x)), [0, 1, 32, 1])


class TestRowCounts:
    def test_matches_xla_kernel(self, planes):
        plane, filt = planes
        got = np.asarray(pallas_kernels.row_counts(plane, filt,
                                                   interpret=True))
        expect = np.asarray(kernels.row_counts(plane, filt))
        np.testing.assert_array_equal(got, expect)

    def test_no_filter_and_row_padding(self, planes):
        plane, _ = planes  # r=10 with row_block=8 -> pad to 16
        got = np.asarray(pallas_kernels.row_counts(plane, interpret=True))
        expect = np.asarray(kernels.row_counts(plane))
        assert got.shape == expect.shape
        np.testing.assert_array_equal(got, expect)

    def test_wide_plane_non_divisible_width(self, rng):
        # w > _WB forces the word-block grid; a non-multiple width
        # exercises the word-axis padding fix (pre-fix: BlockSpec over
        # a ragged word axis returned wrong counts for the tail block)
        w = pallas_kernels._WB + 96
        plane = rng.integers(0, 1 << 32, size=(2, 8, w), dtype=np.uint32)
        filt = rng.integers(0, 1 << 32, size=(2, w), dtype=np.uint32)
        got = np.asarray(pallas_kernels.row_counts(plane, filt,
                                                   interpret=True))
        np.testing.assert_array_equal(
            got, np.asarray(kernels.row_counts(plane, filt)))


def _np_popcount(words):
    return np.bitwise_count(words).astype(np.int64)


class TestCount:
    """Whole-plane count chain: pallas_kernels.count vs kernels.count
    and the numpy popcount oracle."""

    @pytest.mark.parametrize("shape", [(1, 64), (3, 200), (5, 1300),
                                       (2, 4096), (4, 130048)])
    def test_parity_sweep(self, rng, shape):
        words = rng.integers(0, 1 << 32, size=shape, dtype=np.uint32)
        got = np.asarray(pallas_kernels.count(words, interpret=True))
        np.testing.assert_array_equal(got, np.asarray(kernels.count(words)))
        np.testing.assert_array_equal(
            got.astype(np.int64), _np_popcount(words).sum(-1))

    def test_all_ones_and_empty(self):
        ones = np.full((2, 96), 0xFFFFFFFF, np.uint32)
        got = np.asarray(pallas_kernels.count(ones, interpret=True))
        np.testing.assert_array_equal(got, np.full(2, 96 * 32, np.int32))
        zero = np.zeros((3, 160), np.uint32)
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels.count(zero, interpret=True)),
            np.zeros(3, np.int32))


class TestRandomizedParity:
    """Randomized sweep across awkward (non-pow2, non-block-aligned)
    shapes — every pallas kernel vs its XLA oracle on the same draw."""

    def test_sweep(self, rng):
        for _ in range(6):
            s = int(rng.integers(1, 4))
            r = int(rng.integers(1, 20))
            w = int(rng.integers(1, 300))
            plane = rng.integers(0, 1 << 32, size=(s, r, w),
                                 dtype=np.uint32)
            filt = rng.integers(0, 1 << 32, size=(s, w), dtype=np.uint32)
            np.testing.assert_array_equal(
                np.asarray(pallas_kernels.row_counts(plane, filt,
                                                     interpret=True)),
                np.asarray(kernels.row_counts(plane, filt)))
            np.testing.assert_array_equal(
                np.asarray(pallas_kernels.count(filt, interpret=True)),
                np.asarray(kernels.count(filt)))

    def test_empty_filter(self, rng):
        plane = rng.integers(0, 1 << 32, size=(2, 5, 96), dtype=np.uint32)
        filt = np.zeros((2, 96), np.uint32)
        got = np.asarray(pallas_kernels.row_counts(plane, filt,
                                                   interpret=True))
        np.testing.assert_array_equal(got, np.zeros((2, 5), np.int32))


class TestEdgeBlocks:
    """No operand is padded: shard/row edge blocks read past the array
    into outputs that are dropped, and the word-axis edge block is
    masked in the kernel.  Shapes chosen so every axis has a ragged
    last block at once."""

    def test_every_axis_ragged(self, rng):
        s, r, w = 11, 130, pallas_kernels._WB * 2 + 96
        plane = rng.integers(0, 1 << 32, size=(s, r, w), dtype=np.uint32)
        filt = rng.integers(0, 1 << 32, size=(s, w), dtype=np.uint32)
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels.row_counts(plane, interpret=True)),
            _np_popcount(plane).sum(-1))
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels.row_counts(plane, filt,
                                                 interpret=True)),
            _np_popcount(plane & filt[:, None, :]).sum(-1))

    def test_count_ragged_word_blocks(self, rng):
        words = rng.integers(0, 1 << 32,
                             size=(11, pallas_kernels._CWB + 4101),
                             dtype=np.uint32)
        np.testing.assert_array_equal(
            np.asarray(pallas_kernels.count(words, interpret=True)),
            _np_popcount(words).sum(-1))


class TestLowersForTpu:
    """Every kernel in ``engine/pallas_kernels.py`` must get through
    the Pallas→Mosaic lowering for the TPU platform — no chip and no
    libtpu needed.  This is where an illegal block shape (a size-1
    block in the second-minor position, a last dimension that is
    neither 128-divisible nor the whole axis) is rejected; interpret
    mode never checks it.  Shapes: the 1B-column serving plane
    ``[954, 32, 32768]``, and one with R > 128 and W % 1024 != 0 so
    the row grid and the masked word tail lower too."""

    SHAPES = [(954, 32, 32768), (20, 130, 32768 + 96)]

    @staticmethod
    def _lower(fn, *avals):
        import jax
        return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))

    def test_every_public_kernel_is_covered(self):
        # the kernels are the module's public jitted callables
        public = {n for n, f in vars(pallas_kernels).items()
                  if not n.startswith("_") and hasattr(f, "lower")}
        assert public == {"row_counts", "count"}, \
            f"new kernel(s) {public} need a TPU lowering case below"

    @pytest.mark.parametrize("shape", SHAPES)
    def test_row_counts(self, shape):
        import jax
        import jax.numpy as jnp
        s, r, w = shape
        plane = jax.ShapeDtypeStruct((s, r, w), jnp.uint32)
        filt = jax.ShapeDtypeStruct((s, w), jnp.uint32)
        assert "tpu_custom_call" in self._lower(
            pallas_kernels.row_counts, plane).as_text()
        assert "tpu_custom_call" in self._lower(
            pallas_kernels.row_counts, plane, filt).as_text()

    @pytest.mark.parametrize("shape", SHAPES)
    def test_count(self, shape):
        import jax
        import jax.numpy as jnp
        s, _, w = shape
        words = jax.ShapeDtypeStruct((s, w), jnp.uint32)
        assert "tpu_custom_call" in self._lower(
            pallas_kernels.count, words).as_text()
