"""Sparse (container-blocked) device layout for high-row-cardinality fields.

SURVEY.md §8 "dense blowup": a field with millions of distinct sparse
rows cannot live as a dense plane (5M rows × 128KB/shard ≈ 640GB), and
round 1's fallback re-streamed row blocks through the device on every
query.  This module keeps such fields DEVICE-RESIDENT in a form whose
memory scales with SET BITS, not rows × shard width:

    word_idx int32[N_pad]    flat index of each bit's word in the
                             flattened (n_shards · W) filter
    mask    uint32[N_pad]    the bit's lane mask (0 for padding)
    row_ptr int32[R_pad + 1] CSR row boundaries into the bit arrays
                             (bits sorted by row; pad rows repeat N)

8 bytes per set bit + 4 per row — a 100M-bit 5M-row field is ~820MB
instead of 640GB dense.  ``TopN(filter)`` is one compiled program:
gather the filter word per bit, AND the mask, then a SEGMENTED SUM via
cumsum + boundary gathers — deliberately NOT ``segment_sum``: XLA
lowers that to scatter-add, which serializes on TPU (measured 16×
slower than the cumsum form on a v5e for 32M bits / 8M rows).  The
filter bitmap is the only per-query device input; the CSR arrays stay
in HBM until the field mutates (the dense planes' generation protocol).

Unfiltered TopN never touches the device at all: row cardinalities come
from host fragment metadata (:mod:`pilosa_tpu.exec.planes`).
"""

from __future__ import annotations

import functools as _functools
from functools import partial

import jax
import jax.numpy as jnp

from pilosa_tpu.engine import _jaxcfg  # noqa: F401  (device int32 policy)


def _counts(filter_words: jax.Array, word_idx: jax.Array,
            mask: jax.Array, row_ptr: jax.Array) -> jax.Array:
    """int32[R_pad] per-row |row ∧ filter| — gather + cumsum + boundary
    difference.  Padding bits carry mask 0 (contribute nothing); padding
    rows have ptr[i] == ptr[i+1] (count 0)."""
    flat = filter_words.reshape(-1)
    hits = (jnp.bitwise_and(flat[word_idx], mask) != 0).astype(jnp.int32)
    cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(hits, dtype=jnp.int32)])
    return cum[row_ptr[1:]] - cum[row_ptr[:-1]]


@partial(jax.jit, static_argnames=("k",))
def topn_sparse(filter_words: jax.Array, word_idx: jax.Array,
                mask: jax.Array, row_ptr: jax.Array, k: int):
    """(values int32[k], slots int32[k]) of |row ∧ filter| ranked desc."""
    return jax.lax.top_k(_counts(filter_words, word_idx, mask, row_ptr), k)


@jax.jit
def sparse_row_counts(filter_words: jax.Array, word_idx: jax.Array,
                      mask: jax.Array, row_ptr: jax.Array) -> jax.Array:
    """Full per-row count vector — for callers that need every row
    (tanimoto thresholding, ids= restriction, cluster partials)."""
    return _counts(filter_words, word_idx, mask, row_ptr)


# ---------------------------------------------------------------------------
# mesh-sharded form: shard-local CSR blocks + psum over ICI
# ---------------------------------------------------------------------------
#
# Under a device mesh the filter plane is sharded over its shard axis;
# a global-index gather would force XLA to all-gather the filter to
# every chip.  Instead the CSR arrays are built PER DEVICE (word
# indices local to the device's filter block, see
# ``planes.PlaneCache._build_sparse``): each chip gathers only from its
# resident filter words, computes partial per-row counts over its own
# bits, and one ``psum`` over ICI produces exact global counts — which
# also divides the measured ~50M gathers/s single-chip floor
# (BASELINE.md r2) by the device count.


def _partial_counts(axis: str):
    def block(fw, wi, mask, rp):
        # block shapes: fw (S/D, W), wi/mask (1, Nd), rp (1, R_pad+1)
        local = _counts(fw, wi[0], mask[0], rp[0])
        return jax.lax.psum(local, axis)
    return block


@_functools.lru_cache(maxsize=64)
def _mesh_program(mesh, axis: str, k: int | None):
    """jitted (filter, word_idx, mask, row_ptr) -> counts | top_k.
    Cached per (mesh, axis, k): shard_map re-wrapping per call would
    retrace every query."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sm = shard_map(
        _partial_counts(axis), mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=P())
    if k is None:
        return jax.jit(sm)
    # top_k runs on the replicated (tiny) count vector post-collective
    return jax.jit(lambda fw, wi, m, rp: jax.lax.top_k(
        sm(fw, wi, m, rp), k))


def topn_sparse_meshed(mesh, axis: str, filter_words: jax.Array,
                       word_idx: jax.Array, mask: jax.Array,
                       row_ptr: jax.Array, k: int):
    """(values int32[k], slots int32[k]) over device-blocked CSR arrays
    (word_idx/mask int32|uint32[D, Nd_pad], row_ptr int32[D, R_pad+1],
    axis 0 sharded over ``mesh``)."""
    return _mesh_program(mesh, axis, int(k))(filter_words, word_idx,
                                             mask, row_ptr)


def sparse_row_counts_meshed(mesh, axis: str, filter_words: jax.Array,
                             word_idx: jax.Array, mask: jax.Array,
                             row_ptr: jax.Array) -> jax.Array:
    """Exact global int32[R_pad] counts from per-device partials."""
    return _mesh_program(mesh, axis, None)(filter_words, word_idx,
                                           mask, row_ptr)
