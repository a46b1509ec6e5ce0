"""Whole-query fusion: one compiled XLA program per call-tree shape.

SURVEY.md §8: "One compiled function per (call-shape, row-bucket)".
Eager per-op dispatch costs one device round trip per AST node; here the
bitmap-call tree is planned into (structure key, leaf arrays), the
structure is compiled once into a single jitted program (bitwise tree +
optional popcount-reduce fused end-to-end by XLA), and subsequent
queries with the same shape — any row IDs, any predicate values — reuse
it with zero retracing.

Predicate values enter as *traced* leaves (lane-broadcast masks and a
sign scalar, see ``engine.bsi.predicate_masks``), so ``amount > 5`` and
``amount > 99`` hit the same executable.
"""

from __future__ import annotations

import threading as _threading
import time as _time

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.engine import bsi as bsik
from pilosa_tpu.engine import kernels
from pilosa_tpu.obs import metrics as _metrics

# node encodings (hashable nested tuples):
#   ("leaf", i)                      leaf i is uint32[..., W] words
#   ("zeros",)                       all-empty bitmap
#   ("or-leaves", (i, j, ...))       union of row leaves (time ranges)
#   ("and"|"or"|"andnot"|"xor", (child, child, ...))   fold left
#   ("not", child, i_exists)
#   ("shift", child, n)
#   ("bsi", i_plane, i_masks, i_neg, op_key)
#   ("bsi-between", i_plane, i_lo_masks, i_lo_neg, lo_op,
#                   i_hi_masks, i_hi_neg, hi_op)


class Unfusable(Exception):
    """Raised by planners for shapes the fused path doesn't cover."""


def sharding_key(arr) -> object:
    """Hashable sharding identity for program keys (mesh serving).

    A jitted program specializes on its operands' shardings — GSPMD
    compiles the cross-shard reductions (``sum`` over the shard axis,
    shard-axis-sum-then-``top_k``) into ICI collectives — so the same
    shape under two placements is two programs.  Keys carry this
    alongside shape; single-device arrays map to None so the pre-mesh
    key space is unchanged."""
    sh = getattr(arr, "sharding", None)
    if sh is None:
        return None
    try:
        if len(sh.device_set) <= 1:
            return None
        mesh = getattr(sh, "mesh", None)
        spec = getattr(sh, "spec", None)
        if mesh is not None:
            return (tuple(mesh.shape.items()), str(spec))
        return str(sh)
    except Exception:  # noqa: BLE001 — identity, best effort
        return str(sh)


#: One launch at a time for collective-bearing (meshed) programs,
#: process-wide.  Multi-program collectives only compose when every
#: device sees the programs in the SAME order, so launches must not
#: interleave across threads; on the host-platform CPU backend the
#: hazard is harder still — two in-flight 8-device programs split the
#: per-device execution threads between two AllReduce rendezvous and
#: deadlock outright (each waits forever for participants the other
#: run is holding) — so there the launch also drains before the lock
#: is released.  Module-level: a process may hold several meshed
#: executors over the same devices.
_MESH_LAUNCH_LOCK = _threading.Lock()


def mesh_serialized(fn, stats=None):
    """Wrap a meshed jitted program so launches serialize (and, on the
    CPU backend, complete) under ``_MESH_LAUNCH_LOCK``.  Applied at
    cache-insert time by ``FusedCache`` instances serving a placement,
    so every fused family — including the readback pack — flows
    through the one choke point.  ``stats`` counts every launch
    (``mesh_launches_total``) and observes the time from the call to
    the lock's acquisition (``mesh_launch_wait_seconds``, /status
    ``mesh.launchWait``); while a capture is open the wait is also
    ``pilosa.mesh.launch_wait`` in the profiler's trace."""
    from pilosa_tpu.obs import NopStats
    stats = stats or NopStats()
    drain = jax.default_backend() == "cpu"

    def call(*args, **kw):
        t0 = _time.perf_counter()
        with _metrics.span("mesh.launch_wait"):
            _MESH_LAUNCH_LOCK.acquire()
        try:
            stats.observe("mesh_launch_wait_seconds",
                          _time.perf_counter() - t0)
            stats.count("mesh_launches_total", 1)
            out = fn(*args, **kw)
            if drain:
                jax.block_until_ready(out)
            return out
        finally:
            _MESH_LAUNCH_LOCK.release()

    return call


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= max(n, 1).  Batch widths pad to pow2
    buckets (repeating element 0) so the compiled-program set stays
    bounded per shape — without it every distinct batch size compiles
    a fresh program and the compiles land on serving latency
    (measured: a recompile storm collapsed 32 concurrent HTTP clients
    to ~23 qps)."""
    bucket = 1
    while bucket < n:
        bucket *= 2
    return bucket


def dedupe_filters(filters) -> tuple[list, list[int]]:
    """The distinct filters of K same-plane aggregate items (by array
    identity; None = unfiltered, put first so the program's static
    shape does not depend on the order of the calls) and each item's
    slot among them: identical items share one scan."""
    uniq: dict[int, object] = {}
    for f in filters:
        uniq.setdefault(0 if f is None else id(f), f)
    order = sorted(uniq, key=lambda k: k != 0)  # stable: first seen
    slot = {k: i for i, k in enumerate(order)}
    return ([uniq[k] for k in order],
            [slot[0 if f is None else id(f)] for f in filters])


def _after(plane, row):
    """Inside the K-item Min/Max program: make the next item's bit
    descent over ``plane`` wait for the previous item's ``row``.  Left
    free, XLA fuses the K items side by side and keeps every item's
    candidate masks in HBM at once (840 MB for ten items over 318
    shards, by the v5e compiler's memory analysis) beside planes that
    fill the chip; in turn, the temporaries are one item's.  (The Sum
    program needs none: ``bsi.sum_pair_counts`` reads the plane once
    for all K items.)"""
    return jax.lax.optimization_barrier((plane, row))


def _build(node, leaves):
    kind = node[0]
    if kind == "leaf":
        return leaves[node[1]]
    if kind == "zeros":
        return jnp.zeros_like(leaves[0])
    if kind == "or-leaves":
        acc = leaves[node[1][0]]
        for i in node[1][1:]:
            acc = jnp.bitwise_or(acc, leaves[i])
        return acc
    if kind in ("and", "or", "andnot", "xor"):
        op = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
              "xor": jnp.bitwise_xor,
              "andnot": lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
              }[kind]
        acc = _build(node[1][0], leaves)
        for child in node[1][1:]:
            acc = op(acc, _build(child, leaves))
        return acc
    if kind == "not":
        return kernels.complement(_build(node[1], leaves), leaves[node[2]])
    if kind == "shift":
        return kernels.shift(_build(node[1], leaves), node[2])
    if kind == "bsi":
        _, i_plane, i_masks, i_neg, op_key = node
        cmp = bsik.range_cmp(leaves[i_plane], leaves[i_masks],
                             leaves[i_neg])
        return cmp[op_key]
    if kind == "bsi-between":
        (_, i_plane, i_lo, i_lo_neg, lo_op, i_hi, i_hi_neg, hi_op) = node
        lo = bsik.range_cmp(leaves[i_plane], leaves[i_lo],
                            leaves[i_lo_neg])[lo_op]
        hi = bsik.range_cmp(leaves[i_plane], leaves[i_hi],
                            leaves[i_hi_neg])[hi_op]
        return jnp.bitwise_and(lo, hi)
    raise AssertionError(f"bad node {node!r}")


def shift_leaves(node, offset: int):
    """Re-index a plan tree's leaf references by ``offset`` — used to
    concatenate several plans' leaf lists into one batched program."""
    kind = node[0]
    if kind == "leaf":
        return ("leaf", node[1] + offset)
    if kind == "zeros":
        return node
    if kind == "or-leaves":
        return ("or-leaves", tuple(i + offset for i in node[1]))
    if kind in ("and", "or", "andnot", "xor"):
        return (kind, tuple(shift_leaves(c, offset) for c in node[1]))
    if kind == "not":
        return ("not", shift_leaves(node[1], offset), node[2] + offset)
    if kind == "shift":
        return ("shift", shift_leaves(node[1], offset), node[2])
    if kind == "bsi":
        return ("bsi", node[1] + offset, node[2] + offset,
                node[3] + offset, node[4])
    if kind == "bsi-between":
        return ("bsi-between", node[1] + offset, node[2] + offset,
                node[3] + offset, node[4], node[5] + offset,
                node[6] + offset, node[7])
    raise AssertionError(f"bad node {node!r}")


class PingPong:
    """Retired-output pool for donated dispatch chains (r17).

    The chain families (selected counts, rowcounts batches, the
    window's readback pack) pass a RETIRED output buffer back as a
    donated scratch argument, so consecutive dispatches reuse its
    device memory instead of allocating a fresh output per window.
    Depth 2 per (shape, dtype) — ping-pong — so window N can dispatch
    against one buffer while window N-1's readback still owns the
    other; a buffer is only retired AFTER its host read completed
    (every consumer copies out), so donating it can never clobber
    bytes a reader still wants.

    ``scratch`` POPS (the same buffer must never reach two concurrent
    dispatches); returns None when no retired buffer of that shape
    exists — callers then run the un-donated program variant.  The
    pool is bounded (``MAX_SHAPES`` shapes LRU) so churning window
    shapes cannot pin arbitrary device memory."""

    MAX_SHAPES = 8
    DEPTH = 2

    def __init__(self):
        import threading
        from collections import OrderedDict
        self._pools: "OrderedDict[tuple, list]" = OrderedDict()
        self._lock = threading.Lock()

    def scratch(self, shape: tuple, dtype) -> "jax.Array | None":
        key = (tuple(shape), str(dtype))
        with self._lock:
            pool = self._pools.get(key)
            if pool:
                self._pools.move_to_end(key)
                return pool.pop()
        return None

    def retire(self, arr) -> None:
        """Hand a read-back output's device buffer to the pool.  The
        caller must not touch ``arr`` again — a later dispatch may
        donate (invalidate) it."""
        if arr is None:
            return
        key = (tuple(arr.shape), str(arr.dtype))
        with self._lock:
            pool = self._pools.setdefault(key, [])
            self._pools.move_to_end(key)
            if len(pool) < self.DEPTH:
                pool.append(arr)
            while len(self._pools) > self.MAX_SHAPES:
                self._pools.popitem(last=False)


def _pad_skeleton(prog: tuple) -> tuple:
    """A postfix program's STATIC opcode skeleton, NOP-padded to the
    pow2 length bucket — the one bucketing rule every tree entry
    (solo, window item) keys on, so the paths cannot drift apart.
    STATIC ops (Shift/Limit, r23) keep their argument in the skeleton
    as an ``(op, arg)`` entry: the argument is compiled structure
    (like the fused "shift" node's ``n``), so it must live in the
    program key, not the traced operands."""
    p_pad = pow2_bucket(max(1, len(prog)))
    return (tuple((op, arg) if op in kernels.TREE_STATIC_OPS else op
                  for op, arg in prog)
            + (kernels.TREE_NOP,) * (p_pad - len(prog)))


def _pad_extras(extras: tuple) -> tuple:
    """Extra-operand tuple padded to its pow2 bucket by repeating
    element 0 (pad lanes are never addressed by programs)."""
    if not extras:
        return ()
    e_pad = pow2_bucket(len(extras))
    return tuple(extras) + (extras[0],) * (e_pad - len(extras))


class FusedCache:
    """structure key -> jitted program, LRU-bounded: structure keys can
    embed user-controlled constants (e.g. Shift n), so the program set
    must not grow without bound.  One instance per executor.

    Concurrency (r6): the hot path is LOCK-FREE — a plain-dict lookup
    plus a recency-stamp write, both GIL-atomic — because the previous
    single lock was taken on every cached-program hit by every serving
    thread (32 streams × several programs per request).  Compilation
    serializes PER KEY (two threads racing the same new shape compile
    it once; different shapes compile concurrently); the global lock
    guards only insertion and eviction."""

    MAX_PROGRAMS = 256

    def __init__(self, stats=None, mesh_guard: bool = False,
                 ledger=None, flight=None):
        import threading
        from pilosa_tpu.exec._lru import Stamps
        from pilosa_tpu.obs import NULL_FLIGHT, NULL_LEDGER, NopStats
        # mesh_guard (r21): this cache compiles collective-bearing
        # programs (its executor serves a placement), so every program
        # is wrapped in ``mesh_serialized`` at insert time — launches
        # stay cross-device-ordered and the CPU backend's rendezvous
        # deadlock (see _MESH_LAUNCH_LOCK) cannot form.
        self._mesh_guard = mesh_guard
        self._programs: dict = {}     # key -> jitted fn (GIL-atomic reads)
        self._idx_cache: dict = {}    # padded slot tuple -> device int32
        self._stamps = Stamps()       # approx-LRU recency (lock-free touch)
        self._lock = threading.Lock()       # insert / evict only
        self._compiling: dict = {}          # key -> per-key compile lock
        self._threading = threading
        # program-set telemetry (r14): built/evicted counters plus the
        # fused_program_count scrape-time gauge make a recompile storm
        # (the class that once collapsed 32 clients to ~23 qps, see
        # pow2_bucket) visible on /metrics instead of only as latency
        self._stats = stats or NopStats()
        # K-item Sum launches and the items that shared each one's read
        # (run_agg_plane_batch), registered at 0 so the series print
        # before the first one
        self._stats.count("sum_plane_launches_total", 0)
        self._stats.count("sum_plane_items_total", 0)
        # compile observability (r19): per-family compile seconds with
        # first-compile trace exemplars land in the cost ledger, and
        # every compile is a flight-recorder event — a recompile storm
        # shows up on the incident timeline with the shapes that
        # caused it, not just as a climbing built counter
        self._ledger = ledger or NULL_LEDGER
        self.flight = flight or NULL_FLIGHT

    @property
    def program_count(self) -> int:
        return len(self._programs)

    def _get_fast(self, key):
        fn = self._programs.get(key)
        if fn is not None:
            self._stamps.touch(key)
        return fn

    def _insert(self, key, fn) -> None:
        evicted = 0
        with self._lock:
            self._programs[key] = fn
            self._stamps.insert(key)
            if len(self._programs) > self.MAX_PROGRAMS:
                excess = len(self._programs) - self.MAX_PROGRAMS
                stamps = self._stamps.snapshot()
                for k, _ in sorted(stamps, key=lambda kv: kv[1])[:excess]:
                    if k == key:
                        continue
                    if self._programs.pop(k, None) is not None:
                        evicted += 1
                    self._stamps.pop(k)
                    self._compiling.pop(k, None)
            self._stamps.cleanup(self._programs)
        self._stats.count("fused_programs_built_total", 1)
        if evicted:
            self._stats.count("fused_programs_evicted_total", evicted)

    @staticmethod
    def _family(key) -> str:
        """The program key's fused-family tag for compile attribution:
        the head tuple's leading string (``"selcounts"``,
        ``"tree-item"``, a plan node kind, ...) or the trailing want /
        batch tag — every form is a BOUNDED vocabulary, so the
        ``fused_compile_seconds{family}`` series set stays small."""
        try:
            head = key[0]
            if isinstance(head, tuple) and head \
                    and isinstance(head[0], str):
                return head[0]
            tail = key[-1]
            if isinstance(tail, str):
                return tail
        except (IndexError, TypeError):
            pass
        return "fused"

    def _timed_first_call(self, key, fn):
        """jax.jit is LAZY — tracing + XLA compilation happen on the
        program's FIRST invocation, not at jit() time — so compile
        seconds are measured by wrapping exactly that call.  After the
        first call the raw fn replaces the wrapper in the program dict
        (GIL-atomic), so the steady-state hit path pays nothing."""
        family = self._family(key)
        once = []

        def first(*args, **kw):
            t0 = _time.perf_counter()
            with _metrics.span("compile", family=family):
                out = fn(*args, **kw)
            if not once:
                once.append(True)
                dt = _time.perf_counter() - t0
                if self._programs.get(key) is first:
                    self._programs[key] = fn  # un-wrap: off hot path
                self._ledger.note_compile(family, dt, first=True)
                self.flight.record("compile", family, "", dt)
            return out

        return first

    def _cached(self, key, build, donate: tuple = ()):
        fn = self._get_fast(key)
        if fn is not None:
            return fn
        # per-structure-key compile lock: setdefault is atomic, so two
        # racers share one lock and the loser reuses the winner's program
        lock = self._compiling.setdefault(key, self._threading.Lock())
        with lock:
            fn = self._programs.get(key)
            if fn is None:
                # ``donate``: argument positions donated to the
                # program (the r17 ping-pong scratch slots) — XLA
                # aliases the output onto the donated buffer, so a
                # chained dispatch writes into the retired output of
                # two windows ago instead of allocating.  Donation is
                # part of the program, hence part of the key.
                fn = jax.jit(build(), donate_argnums=donate)
                if self._mesh_guard:
                    fn = mesh_serialized(fn, self._stats)
                fn = self._timed_first_call(key, fn)
                self._insert(key, fn)
        return fn

    def run(self, node, leaves, want: str):
        """Execute a planned tree: ``want`` is "words" (bitmap) or
        "count" (fused popcount-reduce scalar)."""
        if want == "words" and node[0] == "leaf":
            # a bare leaf IS its words: an identity program would
            # launch, and copy [S, W] words, to hand back the same
            # bits — and a filter that is the resident row itself is
            # one object however many calls name it, so same-plane
            # items under it dedupe by identity (arrays are immutable
            # and no program donates a filter)
            return leaves[node[1]]
        key = (node, sharding_key(leaves[0]) if leaves else None, want)

        def build():
            if want == "count":
                # per-shard int32 counts; the caller finishes the tiny
                # cross-shard sum in int64 on host (engine int32 policy)
                def program(*ls):
                    return kernels.count(_build(node, ls))
            else:
                def program(*ls):
                    return _build(node, ls)
            return program

        return self._cached(key, build)(*leaves)

    def run_count_batch(self, nodes: tuple, leaves, scratch=None):
        """K Count trees in ONE program: returns int32[K, n_shards] —
        one dispatch and one host read amortize fixed per-read costs
        across every Count in the request.  ``scratch`` (r17): a
        retired int32[K, n_shards] output to donate for the
        chained-dispatch form."""
        n_leaves = len(leaves)
        out_shape = (len(nodes), leaves[0].shape[0])
        donate_ok = (scratch is not None
                     and tuple(scratch.shape) == out_shape)

        def build():
            def program(*ls):
                return jnp.stack([kernels.count(_build(n, ls))
                                  for n in nodes])
            return program
        key = ((nodes, donate_ok, sharding_key(leaves[0])),
               "count-batch")
        if donate_ok:
            return self._cached(key, build,
                                donate=(n_leaves,))(*leaves, scratch)
        return self._cached(key, build)(*leaves)

    def run_rowcounts_batch(self, flags: tuple, leaves, scratch=None):
        """K whole-plane row-count items (same plane shape) in ONE
        program: per item, ``row_counts`` over the plane (AND a filter
        bitmap when flagged) reduced over the shard axis in int32 —
        exact while n_shards·2^20 < 2^31; callers gate on that.
        ``flags[k]`` = item k has a filter leaf; leaves alternate
        plane[, filter] per item.  Returns int32[K, R_pad]: one stacked
        array = one read for the whole coalescing window (the dense
        TopN / same-field count-batch serving spine).  ``scratch``
        (r17): a retired int32[K, R_pad] output to donate for the
        chained-dispatch form."""
        n_leaves = len(leaves)
        out_shape = (len(flags), leaves[0].shape[-2])
        donate_ok = (scratch is not None
                     and tuple(scratch.shape) == out_shape)

        def build():
            def program(*ls):
                rows = []
                i = 0
                for has_filter in flags:
                    plane = ls[i]
                    flt = ls[i + 1] if has_filter else None
                    i += 2 if has_filter else 1
                    rows.append(jnp.sum(kernels.row_counts(plane, flt),
                                        axis=0, dtype=jnp.int32))
                return jnp.stack(rows)
            return program
        # (donate flag inside the key, tag kept LAST — callers
        # introspect the program set by trailing tag)
        key = (flags, leaves[0].shape, sharding_key(leaves[0]),
               donate_ok, "rowcounts-batch")
        if donate_ok:
            return self._cached(key, build,
                                donate=(n_leaves,))(*leaves, scratch)
        return self._cached(key, build)(*leaves)

    # bounded device-resident slot-index cache (r17 solo fast lane):
    # a repeating solo query shape re-dispatches the same slot tuple
    # every request — keep its padded int32 operand resident so a
    # chained dispatch never re-uploads (re-lays-out) the indices
    _IDX_CACHE_MAX = 256

    def _slot_idx(self, padded: tuple) -> jax.Array:
        idx = self._idx_cache.get(padded)
        if idx is None:
            idx = jnp.asarray(padded, dtype=jnp.int32)
            with self._lock:
                self._idx_cache[padded] = idx
                while len(self._idx_cache) > self._IDX_CACHE_MAX:
                    self._idx_cache.pop(next(iter(self._idx_cache)))
        return idx

    def run_selected_counts(self, plane, slots, delta=None,
                            scratch=None,
                            sorted_idx: bool = False) -> jax.Array:
        """N selected-row Counts over one resident plane in ONE
        program: gather the requested rows, popcount, reduce the shard
        axis on device -> int32[N] (callers gate on the int32-exact
        shard bound, like :meth:`run_rowcounts_batch`).  ``slots`` are
        plane row indices (already slot-resolved); the width pads to a
        pow2 bucket by repeating slot 0 so the program set stays
        bounded per (plane shape, width bucket) — the slot VALUES are
        a traced int32 operand, so any row selection of the same width
        bucket reuses one executable.  Returns the device array
        un-read: the batcher packs it into the window's single
        readback.

        ``delta`` (an ``ingest.delta.DeltaOverlay``) merges the
        plane's pending write cells at dispatch time (base⊕delta):
        the overlay arrays are traced operands, so one program serves
        any overlay of the same pow2 cell bucket.

        ``scratch`` (r17): a retired int32[bucket] output buffer to
        donate — the chained-dispatch form (see :class:`PingPong`).
        ``sorted_idx`` statically promises ascending slot order
        (ascending-stride gather); the batcher's slot unions and the
        solo fast lane sort before calling."""
        bucket = pow2_bucket(len(slots))
        # pad with the LAST slot, not slot 0: keeps the padded tuple
        # non-decreasing when the live slots are sorted
        padded = tuple(slots) + (slots[-1],) * (bucket - len(slots))
        idx = self._slot_idx(padded)
        donate_ok = (scratch is not None
                     and tuple(scratch.shape) == (bucket,))
        if delta is not None:
            key = self._selcounts_delta_key(
                plane.shape, sharding_key(plane), bucket,
                delta.rows.shape[0], sorted_idx, donate_ok)
            build = self._selcounts_delta_build(sorted_idx)
            args = (plane, idx, delta.rows, delta.words, delta.vals)
            if donate_ok:
                return self._cached(key, build,
                                    donate=(5,))(*args, scratch)
            return self._cached(key, build)(*args)
        key = self._selcounts_key(plane.shape, sharding_key(plane),
                                  bucket, sorted_idx, donate_ok)
        build = self._selcounts_build(sorted_idx)
        if donate_ok:
            return self._cached(key, build,
                                donate=(2,))(plane, idx, scratch)
        return self._cached(key, build)(plane, idx)

    # selcounts key/build helpers: SHARED between the serving path and
    # the warm-up ladder (warm_delta_ladder), so a warmed program IS
    # the serving program — the two can never drift apart on key shape

    def _selcounts_key(self, shape, shard, bucket, sorted_idx,
                       donate_ok):
        return (("selcounts", shape, shard, bucket, sorted_idx,
                 donate_ok), "count")

    def _selcounts_build(self, sorted_idx: bool):
        def build():
            def program(p, ix, *sc):
                return jnp.sum(
                    kernels.selected_row_counts(p, ix,
                                                sorted_idx=sorted_idx),
                    axis=0, dtype=jnp.int32)
            return program
        return build

    def _selcounts_delta_key(self, shape, shard, bucket, dbucket,
                             sorted_idx, donate_ok):
        return (("selcounts-delta", shape, shard, bucket, dbucket,
                 sorted_idx, donate_ok), "count")

    def _selcounts_delta_build(self, sorted_idx: bool):
        from pilosa_tpu.ingest.delta import adjusted_selected_counts

        def build():
            def program(p, ix, dr, dw, dv, *sc):
                return adjusted_selected_counts(
                    p, ix, dr, dw, dv, sorted_idx=sorted_idx)
            return program
        return build

    def run_rowcounts_delta(self, plane, delta, filter_words=None,
                            reduce: bool = True) -> jax.Array:
        """Whole-plane per-row counts of base⊕delta in ONE program:
        the clean ``row_counts`` scan of the immutable base plus a
        gather + scatter-add adjustment over the overlay cells —
        int32[R_pad] (``reduce``, callers gate on the int32-exact
        shard bound) or int32[S, R_pad].  Overlay arrays are traced
        operands; the program set is bounded per (plane shape, overlay
        bucket, filtered, reduce)."""
        has_filter = filter_words is not None
        key = self._rowcounts_delta_key(
            plane.shape, sharding_key(plane), delta.rows.shape[0],
            has_filter, reduce)
        build = self._rowcounts_delta_build(has_filter, reduce)
        args = (plane, delta.rows, delta.words, delta.vals)
        if has_filter:
            args += (filter_words,)
        return self._cached(key, build)(*args)

    def _rowcounts_delta_key(self, shape, shard, dbucket, has_filter,
                             reduce):
        return (("rowcounts-delta", shape, shard, dbucket, has_filter,
                 reduce), "count")

    def _rowcounts_delta_build(self, has_filter: bool, reduce: bool):
        from pilosa_tpu.ingest.delta import adjusted_row_counts

        def build():
            if has_filter:
                def program(p, dr, dw, dv, fw):
                    return adjusted_row_counts(p, dr, dw, dv, fw,
                                               reduce_shards=reduce)
            else:
                def program(p, dr, dw, dv):
                    return adjusted_row_counts(p, dr, dw, dv, None,
                                               reduce_shards=reduce)
            return program
        return build

    # -- compile-ladder warm-up (r24) -------------------------------------

    #: slot width bucket the warmer pre-compiles for the selected-count
    #: delta family: bucket 1 is the post-ingest first-serve shape (a
    #: solo Count(Row) through the fast lane or a width-1 window)
    WARM_SLOT_BUCKET = 1

    def _warm_insert(self, key, build, avatars: tuple,
                     donate: tuple = ()):
        """AOT-compile ONE program from shape avatars and insert it
        pre-warmed: ``jit().lower().compile()`` runs tracing + XLA
        compilation HERE (off the serving path) instead of lazily on
        first call, and the Compiled object lands directly in the
        program dict (lower/compile does not populate jit's dispatch
        cache).  Returns compile seconds, or None when the key was
        already cached."""
        if self._get_fast(key) is not None:
            return None
        lock = self._compiling.setdefault(key, self._threading.Lock())
        with lock:
            if key in self._programs:
                return None
            t0 = _time.perf_counter()
            fn = jax.jit(build(), donate_argnums=donate)
            fn = fn.lower(*avatars).compile()
            dt = _time.perf_counter() - t0
            if self._mesh_guard:
                fn = mesh_serialized(fn, self._stats)
            self._insert(key, fn)
        return dt

    def _warm_jobs(self, shape: tuple, overlay_bucket: int) -> list:
        """The delta-aware program ladder rungs for one resident plane
        shape × one pow2 overlay bucket: the serving forms a first
        post-ingest query hits (whole-plane rowcounts-delta with and
        without a filter; width-1 selected-counts-delta, donated and
        not).  Keys/builds come from the SAME helpers the serving path
        uses."""
        sds = jax.ShapeDtypeStruct
        s, _r, w = shape
        shard = None  # the warmer only runs un-placed (single-device)
        plane_av = sds(tuple(shape), jnp.uint32)
        flt_av = sds((s, w), jnp.uint32)
        dr = sds((overlay_bucket,), jnp.int32)
        dw = sds((overlay_bucket,), jnp.int32)
        dv = sds((overlay_bucket,), jnp.uint32)
        jobs = []
        for has_filter in (False, True):
            jobs.append((
                self._rowcounts_delta_key(tuple(shape), shard,
                                          overlay_bucket, has_filter,
                                          True),
                self._rowcounts_delta_build(has_filter, True),
                (plane_av, dr, dw, dv) + ((flt_av,) if has_filter
                                          else ()),
                ()))
        b = self.WARM_SLOT_BUCKET
        ix_av, scr_av = sds((b,), jnp.int32), sds((b,), jnp.int32)
        for donate_ok in (False, True):
            jobs.append((
                self._selcounts_delta_key(tuple(shape), shard, b,
                                          overlay_bucket, True,
                                          donate_ok),
                self._selcounts_delta_build(True),
                (plane_av, ix_av, dr, dw, dv) + ((scr_av,)
                                                 if donate_ok else ()),
                (5,) if donate_ok else ()))
        return jobs

    def warm_delta_ladder(self, shape: tuple,
                          overlay_bucket: int) -> tuple[int, float]:
        """Pre-compile the delta-aware serving programs for one plane
        shape × pow2 overlay bucket (r24 compile-ladder warm-up) —
        returns (programs compiled, compile seconds).  A rung that
        fails to compile here is left to the serving path's own lazy
        compile; the other rungs still warm."""
        n, secs = 0, 0.0
        for key, build, avatars, donate in self._warm_jobs(
                shape, overlay_bucket):
            try:
                dt = self._warm_insert(key, build, avatars, donate)
            except Exception:  # noqa: BLE001 — lowering/compile
                continue
            if dt is not None:
                n += 1
                secs += dt
        return n, secs

    def _tree_cached(self, key, build):
        """``_cached`` + tree-family build telemetry: a climbing
        ``tree_programs_built_total`` under a REPEATING mix means the
        skeleton/bucket keying is not containing the program set (the
        recompile-storm class, r16 runbook)."""
        built = []

        def counting_build():
            built.append(True)
            return build()

        fn = self._cached(key, counting_build)
        if built:
            self._stats.count("tree_programs_built_total", 1)
        return fn

    def _tree_gather(self, plane, slots: tuple, delta) -> jax.Array:
        """The window's ONE memory pass over the plane: gather the
        union of requested row slots (traced int32, pow2-width
        bucket) and overlay pending delta cells (base⊕delta) →
        uint32[G_pad, S, W].  Every item program in the window reads
        from this shared array instead of touching the plane again."""
        g = len(slots)
        g_pad = pow2_bucket(max(1, g))
        padded = (tuple(slots) or (0,)) + \
            ((slots[0] if slots else 0),) * (g_pad - max(1, g))
        has_delta = delta is not None
        key = (("tree-gather", plane.shape, sharding_key(plane), g_pad,
                delta.rows.shape[0] if has_delta else None), "words")

        def build():
            def program(p, ix, *dl):
                sel = jnp.take(p, ix, axis=-2)       # [S, G_pad, W]
                if has_delta:
                    from pilosa_tpu.ingest.delta import \
                        overlay_gathered_rows
                    sel = overlay_gathered_rows(sel, ix, *dl,
                                                p.shape[-2])
                return jnp.moveaxis(sel, -2, 0)      # [G_pad, S, W]
            return program

        args = (plane, self._slot_idx(tuple(padded)))
        if has_delta:
            args += (delta.rows, delta.words, delta.vals)
        return self._tree_cached(key, build)(*args)

    def _tree_item(self, rows, ex_stack, prog: tuple, want: str):
        """One tree's postfix program against the window's gathered
        rows: the cache key is the item's opcode SKELETON (NOP-padded
        to a pow2 length bucket) — per-QUERY-shape, never
        per-window-combination — while the push args (which gathered
        row / which extra each push reads) stay traced, so any tree
        of the same skeleton reuses one compiled program.  ``want``
        "count" → int32[1] total (shard axis reduced on device);
        "words" → uint32[S, W]."""
        skeleton = _pad_skeleton(prog)
        row_args = [arg for op, arg in prog
                    if op == kernels.TREE_PUSH]
        ex_args = [arg for op, arg in prog
                   if op == kernels.TREE_PUSHX]
        has_ex = ex_stack is not None
        key = (("tree-item", rows.shape, sharding_key(rows),
                ex_stack.shape if has_ex else None, skeleton), want)

        def build():
            def program(r, ra, xa, *ex):
                words = kernels.tree_fold(
                    r, skeleton, ra, ex[0] if has_ex else None, xa)
                if want == "words":
                    return words
                return jnp.sum(kernels.count(words),
                               dtype=jnp.int32)[None]
            return program

        args = (rows,
                self._slot_idx(tuple(row_args) or (0,)),
                self._slot_idx(tuple(ex_args) or (0,)))
        if has_ex:
            args += (ex_stack,)
        return self._tree_cached(key, build)(*args)

    def _tree_solo(self, plane, slots: tuple, prog: tuple,
                   extras: tuple, delta, want: str):
        """A SINGLE tree in one end-to-end program: each push reads
        its row STRAIGHT off the plane (a traced dynamic index XLA
        fuses into the bitwise chain — no intermediate gathered
        array), the delta overlay merges row-wise in the same chain,
        and counts popcount-reduce before leaving the device.  The
        solo serving path pays one round trip and one pass over
        exactly the rows the tree touches.  Push args carry SLOT
        values directly; the cache key is the skeleton + pow2 arg
        buckets, so any same-shape tree reuses the program."""
        extras = _pad_extras(extras)
        skeleton = _pad_skeleton(prog)
        # push args carry the slot VALUES (traced); the slots tuple's
        # role here is only dedup bookkeeping for the batcher union
        row_args = [slots[arg] for op, arg in prog
                    if op == kernels.TREE_PUSH]
        ex_args = [arg for op, arg in prog if op == kernels.TREE_PUSHX]
        has_delta = delta is not None
        key = (("tree-solo", plane.shape, sharding_key(plane),
                len(extras), skeleton,
                delta.rows.shape[0] if has_delta else None), want)

        def build():
            def program(p, ra, xa, *rest):
                if has_delta:
                    dr, dw, dv = rest[:3]
                    ex_arrays = rest[3:]
                else:
                    ex_arrays = rest
                r_pad = p.shape[-2]

                def row(slot):
                    val = jax.lax.dynamic_index_in_dim(
                        p, jnp.clip(slot, 0, r_pad - 1), p.ndim - 2,
                        keepdims=False)              # [S, W]
                    if has_delta:
                        from pilosa_tpu.ingest.delta import overlay_row
                        val = overlay_row(val, slot, dr, dw, dv, r_pad)
                    return val

                ex = jnp.stack(ex_arrays) if ex_arrays else None
                zero = jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                 jnp.uint32)
                words = kernels.tree_fold(row, skeleton, ra, ex, xa,
                                          zero=zero)
                if want == "words":
                    return words
                return jnp.sum(kernels.count(words),
                               dtype=jnp.int32)[None]
            return program

        # push/extra args ride the device-resident idx cache: a
        # repeating solo tree shape re-binds ZERO operands per dispatch
        # (the pre-bound chain the r17 fast lane rides)
        args = (plane,
                self._slot_idx(tuple(row_args) or (0,)),
                self._slot_idx(tuple(ex_args) or (0,)))
        if has_delta:
            args += (delta.rows, delta.words, delta.vals)
        args += tuple(extras)
        return self._tree_cached(key, build)(*args)

    def _tree_program(self, plane, slots: tuple, progs: tuple,
                      extras: tuple, delta, want: str):
        """Shared assembly for the whole-tree entries (r16 tentpole).
        A single tree (the solo path, and every "words" call) fuses
        end-to-end into one program.  A multi-item window splits into
        ONE gather pass over the plane (slot union, pow2-width
        bucket, delta overlay merged in-program) plus one cached
        program per item SKELETON reading the gathered rows, with the
        item outputs packed into one device array so the window still
        costs a single readback.  Splitting gather from items keeps
        the compiled-program key space per-query-shape: a window key
        spanning every member's structure would compile one program
        per item COMBINATION, which collapsed a 4-way diverse mix to
        ~18 qps on CPU (measured) — the recompile-storm class.

        ``progs``' PUSH args address the ``slots`` union and PUSHX
        args the ``extras`` tuple (see ``exec.tree.assemble_items``)."""
        if len(progs) == 1:
            return self._tree_solo(plane, slots, progs[0], extras,
                                   delta, want)
        rows = self._tree_gather(plane, slots, delta)
        padded = _pad_extras(extras)
        ex_stack = jnp.stack(padded) if padded else None
        outs = tuple(self._tree_item(rows, ex_stack, prog, want)
                     for prog in progs)
        return self.run_readback_pack(outs)

    def run_tree_counts(self, plane, slots: tuple, progs: tuple,
                        extras: tuple = (), delta=None) -> jax.Array:
        """K compound-tree Counts over ONE resident plane in ONE fused
        XLA program: gather the union of requested row slots, overlay
        pending delta cells (base⊕delta — fused trees stay
        rebuild-free under sustained ingest), stack the extra operands
        (exists row, other-field rows, BSI predicate bitmaps) and fold
        each item's postfix program over the words.  Returns the
        device int32[K] totals un-read: the batcher packs them into
        the window's single readback."""
        return self._tree_program(plane, slots, progs, extras, delta,
                                  "count")

    def run_tree_words(self, plane, slots: tuple, prog: tuple,
                       extras: tuple = (), delta=None) -> jax.Array:
        """One compound tree's final BITMAP (uint32[S, W]) in one
        program — the ``want="words"`` form for bitmap-valued compound
        calls (Row trees, Store/filter sources)."""
        return self._tree_program(plane, slots, (prog,), extras, delta,
                                  "words")

    def run_time_range(self, plane, start: int, length: int,
                       delta=None) -> jax.Array:
        """One time field's ``[t0, t1)`` bitmap off its bucketed time
        plane (``pilosa_tpu.timeviews``) in ONE program: gather the
        CONTIGUOUS slot run ``[start, start + length)`` (clip-padded
        to the pow2 length bucket — dead lanes clip to the last slot
        and are masked AFTER the delta overlay, so the overlay's
        first-lane matching always lands on a live lane), overlay
        pending (row, bucket) delta cells, and OR-reduce the bucket
        lanes.  Returns uint32[S, W]; the program key is the plane
        shape + pow2 length bucket (start/length stay traced), so any
        range of the same padded width reuses one executable."""
        l_pad = pow2_bucket(max(1, length))
        has_delta = delta is not None
        key = (("trange", plane.shape, sharding_key(plane), l_pad,
                delta.rows.shape[0] if has_delta else None), "words")

        def build():
            def program(p, st, n, *dl):
                r_pad = p.shape[-2]
                lane = jnp.arange(l_pad, dtype=jnp.int32)
                idx = jnp.clip(st[0] + lane, 0, r_pad - 1)
                sel = jnp.take(p, idx, axis=-2)      # [S, L_pad, W]
                if has_delta:
                    from pilosa_tpu.ingest.delta import \
                        overlay_gathered_rows
                    sel = overlay_gathered_rows(sel, idx, *dl, r_pad)
                sel = jnp.where((lane < n[0])[None, :, None], sel,
                                jnp.uint32(0))
                return jax.lax.reduce(
                    sel, jnp.uint32(0),
                    lambda x, y: jnp.bitwise_or(x, y),
                    dimensions=(sel.ndim - 2,))
            return program

        args = (plane, self._slot_idx((int(start),)),
                self._slot_idx((int(length),)))
        if has_delta:
            args += (delta.rows, delta.words, delta.vals)
        return self._cached(key, build)(*args)

    def run_readback_pack(self, arrays: tuple,
                          scratch=None) -> jax.Array:
        """Concatenate the flattened int32 outputs of a collection
        window's programs into ONE device array — the whole window
        then costs a single device->host read instead of one per
        kind/shape group (each read has a fixed cost, so the read
        count sets the serving floor).
        ``scratch`` (r17): a retired packed output of the same total
        size to donate — consecutive windows of the same shape mix
        ping-pong through two standing packed buffers instead of
        allocating one per window."""
        shapes = tuple(a.shape for a in arrays)
        total = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
        donate_ok = (scratch is not None
                     and tuple(scratch.shape) == (total,))

        def build():
            def program(*xs):
                return jnp.concatenate(
                    [x.reshape(-1) for x in xs[:len(shapes)]])
            return program
        key = (shapes, sharding_key(arrays[0]), donate_ok,
               "readback-pack")
        if donate_ok:
            return self._cached(key, build,
                                donate=(len(arrays),))(*arrays, scratch)
        return self._cached(key, build)(*arrays)

    def run_percentile(self, plane, filter_words, nth: float):
        """Percentile in two bounded programs (cached/evicted like every
        other fused program): total count, then the on-device rank
        binary search with an exact host-computed integer target (f64
        host ceil; device f32 would misround past 2^24).  Returns
        ((offset, count) array | None, total)."""
        import math

        has_filter = filter_words is not None
        args = (plane,) + ((filter_words,) if has_filter else ())

        def total_build():
            def program(*ls):
                return bsik.percentile_total(
                    ls[0], ls[1] if has_filter else None)
            return program

        def search_build():
            def program(*ls):
                return bsik.percentile_search(
                    ls[0], ls[1] if has_filter else None, ls[-1])
            return program

        key_t = (("pct-total", plane.shape, sharding_key(plane),
                  has_filter), "pct")
        total = int(self._cached(key_t, total_build)(*args))
        if total == 0:
            return None, 0
        target = min(total, max(1, math.ceil(nth / 100.0 * total)))
        key_s = (("pct-search", plane.shape, sharding_key(plane),
                  has_filter), "pct")
        out = self._cached(key_s, search_build)(*args, jnp.int32(target))
        return out, total

    # ------------------------------------------------- BSI plane batches
    #
    # r20 (the PQL-surface work): the per-PLANE aggregate families.
    # These take ONE resident plane plus the items' filter leaves, so
    # concurrent aggregates over the same plane co-batch into one
    # program that references the plane once, and a pending BSI write
    # overlay (``ingest.delta.BsiOverlay``) merges in-program: the base
    # side scans the untouched columns (touched word columns masked out
    # of the filter), the mini side runs the SAME kernel over the merged
    # touched columns as a tiny standalone plane — base⊕delta exact with
    # zero plane rewrites.

    def run_agg_plane_batch(self, kind: str, plane, filters,
                            delta=None, bucket: bool = False):
        """K ``"sum"`` | ``"minmax"`` items over ONE resident plane,
        each given by its filter (None = unfiltered), as one program:
        :func:`dedupe_filters` folds identical items into one scan.
        A single request's group runs at its own width (K is fixed by
        the request's text, so the program is the request's shape);
        ``bucket`` pads to a pow2 width by repeating item 0 — the
        collection window's rule, whose width is whatever arrived
        together.  Returns ``(device out, each item's row in it, the
        row decoder)``."""
        uniq, assign = dedupe_filters(filters)
        if kind == "sum":
            # one launch, and the distinct items that share its one
            # read of the plane (pads excluded)
            self._stats.count("sum_plane_launches_total", 1)
            self._stats.count("sum_plane_items_total", len(uniq))
        if bucket:
            uniq += [uniq[0]] * (pow2_bucket(len(uniq)) - len(uniq))
        flags = tuple(f is not None for f in uniq)
        leaves = tuple(f for f in uniq if f is not None)
        if kind == "sum":
            return (self.run_sum_plane_batch(plane, flags, leaves,
                                             delta=delta),
                    assign, bsik.decode_sum_packed)
        return (self.run_minmax_plane_batch(plane, flags, leaves,
                                            delta=delta),
                assign, bsik.decode_minmax_packed)

    @staticmethod
    def _bsi_split(plane, flt, delta_ops):
        """(base filter, mini plane, mini filter) for one item: clean
        pass-through when the plane has no overlay."""
        from pilosa_tpu.ingest.delta import (bsi_excl_filter,
                                             bsi_mini_filter,
                                             bsi_mini_plane)
        if delta_ops is None:
            return flt, None, None
        cs, cw, cv, cm = delta_ops
        return (bsi_excl_filter(plane, cs, cw, flt),
                bsi_mini_plane(plane, cs, cw, cv, cm),
                bsi_mini_filter(plane, cs, cw, flt))

    def _delta_args(self, delta):
        if delta is None:
            return None, ()
        return (delta.col_shard.shape[0],
                (delta.col_shard, delta.col_word, delta.col_vals,
                 delta.col_mask))

    def run_sum_plane_batch(self, plane, flags: tuple, filters: tuple,
                            delta=None):
        """K BSI Sum items over ONE resident plane in one program that
        reads the plane once (``bsi.sum_pair_counts``) —
        int32[K, n_shards, 2*depth+1], decoded by
        ``bsi.decode_sum_packed``.  ``flags[k]`` = item k has a filter;
        ``filters`` holds the flagged items' uint32[S, W] bitmaps in
        order.  With ``delta`` (a ``BsiOverlay``) the base side is the
        same pair form over the K exclusion filters, and each item's
        mini-side counts (a tiny plane, item by item) fold into shard
        0's row (Sum is linear over columns), so the output shape and
        decode stay identical."""
        from pilosa_tpu.ingest.delta import (bsi_excl_filter,
                                             bsi_mini_filter,
                                             bsi_mini_plane)
        n_filters = len(filters)
        bucket, delta_ops = self._delta_args(delta)
        key = (("sum-plane", plane.shape, sharding_key(plane), flags,
                bucket), "agg")

        def build():
            def program(p, *rest):
                it = iter(rest[:n_filters])
                items = [next(it) if has else None for has in flags]
                if not rest[n_filters:]:
                    return bsik.sum_pair_counts(p, items)
                cs, cw, cv, cm = rest[n_filters:]
                out = bsik.sum_pair_counts(
                    p, [bsi_excl_filter(p, cs, cw, f) for f in items])
                mini = bsi_mini_plane(p, cs, cw, cv, cm)
                adj = []
                for f in items:
                    mp, mn, mc = bsik.bit_counts(
                        mini, bsi_mini_filter(p, cs, cw, f))
                    adj.append(jnp.concatenate(
                        [jnp.sum(mp, axis=0, dtype=jnp.int32),
                         jnp.sum(mn, axis=0, dtype=jnp.int32),
                         jnp.sum(mc, dtype=jnp.int32)[None]]))
                return out.at[:, 0].add(jnp.stack(adj))
            return program
        return self._cached(key, build)(plane, *filters, *delta_ops)

    def run_minmax_plane_batch(self, plane, flags: tuple,
                               filters: tuple, delta=None):
        """K BSI Min/Max items over ONE resident plane — int32
        [K, n_shards (+ overlay columns), 2*depth+4], decoded by
        ``bsi.decode_minmax_packed`` (the host combine reduces over
        the whole leading axis and drops zero-count entries, so the
        mini side's touched columns just append as extra pseudo-shard
        rows)."""
        n_filters = len(filters)
        bucket, delta_ops = self._delta_args(delta)
        key = (("minmax-plane", plane.shape, sharding_key(plane),
                flags, bucket), "agg")

        def build():
            def pack(mm):
                return jnp.concatenate(
                    [mm["min_bits"].astype(jnp.int32),
                     mm["max_bits"].astype(jnp.int32),
                     mm["min_neg"].astype(jnp.int32)[..., None],
                     mm["min_cnt"][..., None],
                     mm["max_neg"].astype(jnp.int32)[..., None],
                     mm["max_cnt"][..., None]], axis=-1)

            def program(p, *rest):
                filts = rest[:n_filters]
                dops = rest[n_filters:] or None
                rows = []
                fi = 0
                for has_filter in flags:
                    if rows:
                        p, rows[-1] = _after(p, rows[-1])
                    flt = filts[fi] if has_filter else None
                    fi += 1 if has_filter else 0
                    excl, mini, mflt = self._bsi_split(p, flt, dops)
                    row = pack(bsik.min_max_bits(p, excl))
                    if mini is not None:
                        # mini plane [K, R, 1] → per-column tuples
                        # [K, 2d+4] appended as pseudo-shard rows;
                        # pad columns carry cnt 0 (mini filter zero)
                        # and drop in the host combine
                        mrow = pack(bsik.min_max_bits(mini, mflt))
                        row = jnp.concatenate([row, mrow], axis=0)
                    rows.append(row)
                return jnp.stack(rows)
            return program
        return self._cached(key, build)(plane, *filters, *delta_ops) \
            if delta_ops else self._cached(key, build)(plane, *filters)

    def run_range_batch(self, plane, specs: tuple, operands: tuple,
                        delta=None):
        """K BSI Range-counts over ONE resident plane in one program —
        int32[K] totals (shard axis reduced on device; callers gate on
        the int32-exact shard bound).  ``specs[k]`` is the item's
        STATIC shape ``(op_keys tuple of 1–2, has_filter)``; the
        predicate masks/signs and filter bitmaps are traced operands
        in ``operands`` (flattened per item: masks, neg per op, then
        the filter when flagged) — any predicate VALUE of the same
        comparison shape reuses one executable.  A two-op item ANDs
        its comparisons (between).  Delta-aware like the other
        plane-batch families."""
        bucket, delta_ops = self._delta_args(delta)
        n_ops = len(operands)
        key = (("range-plane", plane.shape, sharding_key(plane),
                specs, bucket), "count")

        def build():
            def program(p, *rest):
                ops = rest[:n_ops]
                dops = rest[n_ops:] or None
                totals = []
                i = 0
                for op_keys, has_filter in specs:
                    preds = []
                    for okey in op_keys:
                        preds.append((ops[i], ops[i + 1], okey))
                        i += 2
                    flt = ops[i] if has_filter else None
                    i += 1 if has_filter else 0
                    excl, mini, mflt = self._bsi_split(p, flt, dops)

                    def side(pl, fw):
                        words = None
                        for masks, neg, okey in preds:
                            cmp = bsik.range_cmp(pl, masks, neg,
                                                 fw)[okey]
                            words = cmp if words is None \
                                else jnp.bitwise_and(words, cmp)
                        return jnp.sum(kernels.count(words),
                                       dtype=jnp.int32)

                    total = side(p, excl)
                    if mini is not None:
                        total = total + side(mini, mflt)
                    totals.append(total)
                return jnp.stack(totals)
            return program
        return self._cached(key, build)(plane, *operands, *delta_ops) \
            if delta_ops else self._cached(key, build)(plane, *operands)

    def run_groupby_batch(self, planes: tuple, combo_idx, last_plane,
                          filter_words, agg_plane, agg: str | None,
                          delta=None):
        """One GroupBy combination block as a batcher-windowable
        program: the whole ``exec.groupby`` body with its output dict
        FLATTENED into one int32 array, so a GroupBy block joins the
        collection window's packed readback alongside counts and BSI
        aggregates instead of dispatching solo.  ``delta`` (the agg
        plane's ``BsiOverlay``) keeps aggregate GroupBys fold-free
        under sustained BSI ingest.  Unflatten with
        ``exec.groupby.unflatten_block``."""
        from pilosa_tpu.exec import groupby as gb
        has_filter = filter_words is not None
        has_agg = agg_plane is not None
        bucket, delta_ops = self._delta_args(
            delta if has_agg else None)
        key = (("groupby", tuple(p.shape for p in planes),
                sharding_key(last_plane),
                combo_idx.shape, last_plane.shape, has_filter,
                agg_plane.shape if has_agg else None, agg, bucket),
               "agg")

        def build():
            def program(*ls):
                n = len(planes)
                pl = ls[:n]
                ci, lp = ls[n], ls[n + 1]
                j = n + 2
                fw = ls[j] if has_filter else None
                j += 1 if has_filter else 0
                ap = ls[j] if has_agg else None
                j += 1 if has_agg else 0
                ad = ls[j:] or None
                out = gb.groupby_out(pl, ci, lp, fw, ap, agg,
                                     agg_delta=ad)
                return jnp.concatenate(
                    [out[name].astype(jnp.int32).reshape(-1)
                     for name in gb.block_part_names(agg)])
            return program
        args = planes + (combo_idx, last_plane)
        if has_filter:
            args += (filter_words,)
        if has_agg:
            args += (agg_plane,)
        args += delta_ops
        return self._cached(key, build)(*args)
