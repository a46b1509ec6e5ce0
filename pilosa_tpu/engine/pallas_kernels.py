"""Pallas/Mosaic TPU kernels for the popcount hot loop.

The XLA-fused kernels in :mod:`pilosa_tpu.engine.kernels` are the
default compute path; these Pallas variants give explicit control of
the HBM→VMEM streaming and accumulation for the two whole-plane scans
(reference hot loop: the popcount matrix behind TopN,
``fragment.top``; SURVEY.md §4.2–4.3):

- :func:`row_counts`: ``uint32[S, R, W] (× filter) → int32[S, R]``
  (the TopN matrix), gridded over shards × row blocks × word blocks so
  each step streams a ≤4 MB tile through VMEM;
- :func:`count`: ``uint32[S, W] → int32[S]`` (the whole-bitmap count
  chain), word-blocked so a wide scan accumulates through VMEM-sized
  tiles like :func:`kernels.count`'s tiled reduce.

These are the ``kernel_tier="pallas"`` serving tier: ``exec/fused.py``
routes the count-batch and rowcounts families here when the knob is
on, keeping the XLA kernels as the correctness oracle.  Delta-overlay
adjustment (base⊕delta) stays one program: the fused layer composes
these base scans with the overlay scatter inside a single jit.  The
selected-row gather stays on XLA: a one-row block in the second-minor
(sublane) position is not a legal Mosaic block, and widening it to the
8-row tile reads 8× the bytes.

No operand is padded or copied: the shard and row grids are
``pl.cdiv`` grids whose edge block reads past the array (unspecified
data) into output rows/columns that are themselves past the array and
therefore dropped on write-back — every (shard, row) count depends on
its own words only.  The word axis is a reduction, so ITS edge block
is masked in the kernel (only when ``W`` is not a block multiple; the
production shard width, 32768 words, always is).

Popcount uses the SWAR bit-twiddling reduction (shift/mask adds) —
portable across Mosaic versions regardless of ``population_count``
support.  Tests run the same kernels in interpreter mode on CPU
against the numpy oracle and lower them for TPU without a chip; on TPU
they compile to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SB = 8      # shard block (Mosaic sublane granule)
_RB = 128    # row block (int32 lane granule)
_WB = 1024   # word block: 8 x 128 x 1024 x 4B = 4MB tile through VMEM
_CWB = 128 * 1024  # count word block: 8 x 128K x 4B = 4MB tile


def _popcount_u32(x: jax.Array) -> jax.Array:
    """SWAR popcount per uint32 lane -> int32.  Masks are weak python
    ints (pallas kernels must not close over concrete arrays)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    # sum the 4 bytes via shifts (byte values <= 8, no overflow)
    x = x + (x >> 8)
    x = x + (x >> 16)
    return (x & 0x3F).astype(jnp.int32)


def _mask_word_tail(words: jax.Array, k, wb: int, w: int) -> jax.Array:
    """Zero the lanes of word block ``k`` that lie past the array's
    ``w`` words (an edge block reads unspecified data there)."""
    col = k * wb + jax.lax.broadcasted_iota(jnp.int32, words.shape,
                                            words.ndim - 1)
    return jnp.where(col < w, words, 0)


def _accumulate(out_ref, counts, k) -> None:
    """The output tile persists across the innermost (word) grid axis:
    the first word block stores, the rest add."""
    @pl.when(k == 0)
    def _init():
        out_ref[...] = counts

    @pl.when(k != 0)
    def _acc():
        out_ref[...] += counts


def _row_counts_kernel(*refs, w: int, wb: int):
    plane_ref, out_ref = refs[0], refs[-1]
    k = pl.program_id(2)
    words = plane_ref[...]                       # (SB, rb, wb)
    if len(refs) == 3:
        # filter (SB, wb) broadcasts over the row axis in VMEM
        words = words & refs[1][...][:, None, :]
    if w % wb:
        words = _mask_word_tail(words, k, wb, w)
    _accumulate(out_ref, jnp.sum(_popcount_u32(words), axis=-1), k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_counts(plane: jax.Array, filter_words: jax.Array | None = None,
               interpret: bool = False) -> jax.Array:
    """Per-row popcounts (the TopN matrix): uint32[S, R, W] -> int32[S, R].

    Grid (shard blocks, row blocks, word blocks): each step streams an
    8-shard x <=128-row x 1K-word tile (4MB) through VMEM; the output
    tile is indexed (i, j) only, so it persists across the innermost
    word-block axis and accumulates partial counts.
    """
    s, r, w = plane.shape
    # blocks: the whole axis when it fits one tile, else the tile —
    # NEVER the whole of a wide word axis (an 8 x 128 x w tile blows
    # the VMEM budget at real plane widths)
    rb, wb = min(r, _RB), min(w, _WB)
    operands = [plane]
    in_specs = [pl.BlockSpec((_SB, rb, wb), lambda i, j, k: (i, j, k))]
    if filter_words is not None:
        operands.append(filter_words)
        in_specs.append(pl.BlockSpec((_SB, wb), lambda i, j, k: (i, k)))
    return pl.pallas_call(
        functools.partial(_row_counts_kernel, w=w, wb=wb),
        grid=(pl.cdiv(s, _SB), pl.cdiv(r, rb), pl.cdiv(w, wb)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_SB, rb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((s, r), jnp.int32),
        interpret=interpret,
    )(*operands)


def _count_kernel(w_ref, out_ref, *, w: int, wb: int):
    k = pl.program_id(1)
    words = w_ref[...]                           # (SB, wb)
    if w % wb:
        words = _mask_word_tail(words, k, wb, w)
    _accumulate(out_ref,
                jnp.sum(_popcount_u32(words), axis=-1, keepdims=True), k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def count(words: jax.Array, interpret: bool = False) -> jax.Array:
    """Whole-bitmap count chain: uint32[S, W] -> int32[S].

    The Pallas face of :func:`kernels.count`'s tiled reduce — grid
    (shard blocks, word blocks), the output tile indexed by shard
    block only so it persists across the word axis and accumulates
    partial popcounts (each step streams a <=4MB tile through VMEM).
    """
    s, w = words.shape
    wb = min(w, _CWB)
    out = pl.pallas_call(
        functools.partial(_count_kernel, w=w, wb=wb),
        grid=(pl.cdiv(s, _SB), pl.cdiv(w, wb)),
        in_specs=[pl.BlockSpec((_SB, wb), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((_SB, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.int32),
        interpret=interpret,
    )(words)
    return out[:, 0]
