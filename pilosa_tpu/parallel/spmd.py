"""Compiled SPMD query programs over the shard mesh.

One jitted function per query shape (SURVEY.md §8): inputs are plane
arrays whose leading axis is sharded over the mesh
(:class:`~pilosa_tpu.parallel.mesh.MeshPlacement`); cross-shard
reductions inside ``jit`` compile to XLA all-reduces over ICI.  The
``shard_map`` variants make the collective explicit (``psum`` over the
shard axis) — the compiled-in replacement for the reference's
coordinator-side HTTP merge (``executor.go#mapReduce`` reducers,
SURVEY.md §3.6).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pilosa_tpu.engine import bsi as bsik
from pilosa_tpu.engine import kernels


# -- implicit-collective programs (inputs carry NamedSharding) --------------


@jax.jit
def intersect_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """Count(Intersect(Row, Row)) over all shards: int64 scalar."""
    return jnp.sum(kernels.intersection_count(a, b))


@jax.jit
def union_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.sum(kernels.union_count(a, b))


@partial(jax.jit, static_argnames=("n",))
def topn(plane: jax.Array, filter_words: jax.Array | None, n: int):
    """TopN over a [n_shards, R, W] plane: (counts[n], slots[n])."""
    counts = kernels.row_counts(plane, filter_words)
    return kernels.top_n(jnp.sum(counts, axis=0), n)


@jax.jit
def bsi_bit_counts(plane: jax.Array, filter_words: jax.Array | None):
    """Per-shard per-bit BSI counts over a [n_shards, depth+2, W] plane;
    finish with ``engine.bsi.combine_sum`` on host."""
    return bsik.bit_counts(plane, filter_words)


# -- explicit shard_map programs (collectives spelled out) -------------------


def make_intersect_count_psum(mesh: Mesh, axis: str = "shard"):
    """Explicit SPMD Count(Intersect): each chip reduces its resident
    shard block, then one ``psum`` over ICI."""

    def per_chip(a, b):
        return jax.lax.psum(jnp.sum(kernels.intersection_count(a, b)),
                            axis_name=axis)

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=P()))


def make_topn_psum(mesh: Mesh, n: int, axis: str = "shard"):
    """Explicit SPMD TopN: per-chip row popcounts, psum of the count
    matrix over ICI, replicated top_k."""

    def per_chip(plane, filter_words):
        counts = jnp.sum(kernels.row_counts(plane, filter_words), axis=0)
        counts = jax.lax.psum(counts, axis_name=axis)
        return kernels.top_n(counts, n)

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=(P(), P())))


def make_bsi_sum_psum(mesh: Mesh, axis: str = "shard"):
    """Cluster-wide per-bit count matrices via ICI psum (int32 — exact
    for <2047 full shards per bit); host combine_sum finishes."""

    def per_chip(plane, filter_words):
        pos, neg, cnt = bsik.bit_counts(plane, filter_words)
        return (jax.lax.psum(jnp.sum(pos, axis=0, dtype=jnp.int32),
                             axis_name=axis),
                jax.lax.psum(jnp.sum(neg, axis=0, dtype=jnp.int32),
                             axis_name=axis),
                jax.lax.psum(jnp.sum(cnt, dtype=jnp.int32),
                             axis_name=axis))

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=(P(), P(), P())))


def make_intersect_count_psum2d(mesh: Mesh, shard_axis: str = "shard",
                                words_axis: str = "words"):
    """Explicit 2D-SPMD Count(Intersect) over a (shard × words) mesh:
    each chip holds a block of shards × a slice of each row's words;
    partial popcounts psum over BOTH axes (SURVEY.md §6 long-context
    analogue — the word axis is the 'sequence' being split)."""

    def per_chip(a, b):
        partial = jnp.sum(kernels.intersection_count(a, b))
        return jax.lax.psum(partial, axis_name=(shard_axis, words_axis))

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(shard_axis, words_axis), P(shard_axis, words_axis)),
        out_specs=P()))


def make_topn_psum2d(mesh: Mesh, n: int, shard_axis: str = "shard",
                     words_axis: str = "words"):
    """2D TopN: per-chip partial row counts, psum over shards + word
    slices, replicated top_k."""

    def per_chip(plane, filter_words):
        counts = jnp.sum(kernels.row_counts(plane, filter_words), axis=0)
        counts = jax.lax.psum(counts, axis_name=(shard_axis, words_axis))
        return kernels.top_n(counts, n)

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(shard_axis, None, words_axis),
                  P(shard_axis, words_axis)),
        out_specs=(P(), P())))


def make_ingest_step(mesh: Mesh, axis: str = "shard"):
    """Sharded device-side mutation: apply coalesced (word_idx, mask)
    updates to each chip's resident rows (SURVEY.md §4.5 device half).
    Updates are per-shard: uint idx/mask arrays with leading shard axis."""

    def per_chip(words, word_idx, word_mask):
        # one scatter per resident shard (indices differ per shard)
        return jax.vmap(kernels.apply_word_or)(words, word_idx, word_mask)

    return jax.jit(shard_map(
        per_chip, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None)))
