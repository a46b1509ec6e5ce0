"""Device-resident plane cache: host fragments → packed uint32 arrays in HBM.

The device is a cache over host truth (SURVEY.md §8): a (field, view) is
materialized as ``uint32[n_shards, R_pad, W]`` (set fields) or
``uint32[n_shards, depth+2, W]`` (BSI), placed via an optional
``jax.sharding.Sharding`` so the shard axis lands across the mesh — the
TPU analogue of the reference's shard→node placement
(``cluster.go#shardNodes``).

Invalidation: entries remember the source fragments' generation counters
and rebuild when any changed (fragment mutations bump them).  Row-count
padding to the next power of two bounds XLA recompiles (one compile per
row bucket, SURVEY.md §8 "static shapes vs dynamic row sets").

Eviction: byte-budgeted LRU — the working-set management half of the
"host→HBM streaming" hard part; fields that exceed the budget are
rebuilt per query rather than cached.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
import numpy as np

from pilosa_tpu.engine import bsi as bsik
from pilosa_tpu.engine.bsi import EXISTS_ROW, OFFSET_ROW
from pilosa_tpu.engine.words import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.obs import metrics as _metrics
from pilosa_tpu.store.field import TYPE_MUTEX, TYPE_SET, Field
from pilosa_tpu.store.view import VIEW_STANDARD

PAD_SHARD = -1  # shard-list padding entry (meshed execution): all-zero words

DEFAULT_BUDGET = 4 << 30

# A single-valued set field of more than this many rows keeps its
# standard view as a bit-sliced code (the row's slot in binary plus an
# existence row, :class:`CodeSet`) in place of one dense row per row id.
# The widest set field of the deployments measured before the code
# existed has 64 rows (taxi-full-mesh4's dist_miles and
# duration_minutes), so none of their planes changes layout.
CODED_ROWS_OVER = 64


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@jax.jit
def _scatter_cells(plane, cell_rows, cell_words, cell_vals, reset_rows,
                   reset_vals):
    flat = plane.reshape(-1, plane.shape[-1])
    flat = flat.at[reset_rows].set(reset_vals, mode="drop")
    flat = flat.at[cell_rows, cell_words].set(cell_vals, mode="drop")
    return flat.reshape(plane.shape)


def _apply_plane_cells(plane, cell_rows, cell_words, cell_vals,
                       reset_rows, reset_vals):
    """Scatter changed cells / whole rows into a resident device plane.
    Index arrays pow2-pad with out-of-range values (``mode="drop"``) so
    the compiled program set stays bounded per plane shape."""
    total = plane.shape[0] * plane.shape[1]
    w = plane.shape[-1]
    n1, n2 = _pow2(len(cell_rows)), _pow2(len(reset_rows))
    cr = np.full(n1, total, np.int32)
    cw = np.zeros(n1, np.int32)
    cv = np.zeros(n1, np.uint32)
    cr[:len(cell_rows)] = cell_rows
    cw[:len(cell_words)] = cell_words
    cv[:len(cell_vals)] = cell_vals
    rr = np.full(n2, total, np.int32)
    rv = np.zeros((n2, w), np.uint32)
    rr[:len(reset_rows)] = reset_rows
    rv[:len(reset_vals)] = reset_vals
    return _scatter_cells(plane, cr, cw, cv, rr, rv)


def merge_row_cards(frags) -> tuple[np.ndarray, np.ndarray]:
    """Merge per-fragment (row_ids, cardinalities) across shards:
    (uint64[R] sorted ids, int64[R] summed cards).  Shared by the sparse
    build and the executor's unfiltered-TopN host path."""
    id_parts, card_parts = [], []
    for frag in frags:
        ids, cards = frag.row_cardinalities()
        if len(ids):
            id_parts.append(ids)
            card_parts.append(cards)
    if not id_parts:
        return np.empty(0, np.uint64), np.empty(0, np.int64)
    all_ids = np.unique(np.concatenate(id_parts))
    totals = np.zeros(len(all_ids), np.int64)
    for ids, cards in zip(id_parts, card_parts):
        totals[np.searchsorted(all_ids, ids)] += cards
    return all_ids, totals


@dataclass
class PlaneSet:
    """One materialized (field, view): device plane + row-slot mapping.

    ``delta`` (r15 ingest): a bounded device-side write overlay
    (:class:`pilosa_tpu.ingest.delta.DeltaOverlay`) carrying cells
    written since the base plane was built.  ``plane`` itself is the
    IMMUTABLE base; delta-aware kernels answer base⊕delta at dispatch
    time, and consumers that need a clean plane go through
    :meth:`PlaneCache.field_plane`, which folds first."""

    plane: jax.Array          # uint32[n_shards, R_pad, W]
    shards: tuple[int, ...]   # axis-0 ids, PAD_SHARD entries are zeros
    row_ids: np.ndarray       # uint64[R] real rows (slots beyond are pad)
    slot_of: dict[int, int]
    delta: object | None = None  # ingest.delta.DeltaOverlay when dirty

    @property
    def n_rows(self) -> int:
        return len(self.row_ids)

    def slots_for(self, row_ids) -> list:
        """Plane row slots for ``row_ids`` (None per absent row — a
        row with no set bit anywhere has no slot and callers lower it
        as an all-zero operand).  Resolution happens fresh per query:
        a row that gains its first bit after the plane was built
        reaches the plane through the normal staleness machinery
        (delta absorb / rebuild) before this map is consulted."""
        return [self.slot_of.get(int(r)) if r is not None else None
                for r in row_ids]


@dataclass
class CodeSet:
    """A coded field's standard view over ``shards``: BSI's plane shape
    ``uint32[n_shards, depth+2, W]`` (existence row, a sign row that
    stays empty, ``depth`` bit rows) holding each column's row slot —
    the row's position in ``row_ids``.  Valid while no column lies in
    two rows; the build checks that (unless the field is ``mutex``)
    and refuses the code otherwise."""

    plane: jax.Array
    shards: tuple[int, ...]
    row_ids: np.ndarray       # uint64[R] sorted; slot = position
    slot_of: dict[int, int]

    @property
    def n_rows(self) -> int:
        return len(self.row_ids)

    @property
    def depth(self) -> int:
        return self.plane.shape[-2] - OFFSET_ROW


def _in_two_rows(cols: np.ndarray) -> bool:
    """Whether a column of one fragment's set positions lies in two
    rows (the check a non-mutex field's code build makes)."""
    return bool(np.bincount(cols, minlength=SHARD_WIDTH).max() > 1)


_equal_rows = jax.jit(bsik.equal_rows)
_value_histogram = jax.jit(bsik.value_histogram)


@jax.jit
def _take_rows(plane, slots):
    # a row at a time: a gather over the [S, R, W] view makes the TPU
    # compiler copy the plane out first (542 MB for 5 of 32 rows over
    # 172 shards, by its memory analysis; 0.1 MB this way)
    rows = jax.lax.map(lambda s: jax.lax.dynamic_index_in_dim(
        plane, s, axis=1, keepdims=False), slots)
    return jax.numpy.transpose(rows, (1, 0, 2))


@dataclass
class SparseSet:
    """Container-blocked sparse residency (``engine.sparse``): one
    (field, view) as CSR bit arrays — memory scales with set bits, not
    rows × shard width (SURVEY.md §8 "dense blowup").

    Two layouts: unmeshed (``mesh is None``) arrays are flat
    (``word_idx int32[N_pad]`` of global flat filter indices); meshed
    arrays are DEVICE-BLOCKED (``int32[D, Nd_pad]`` with word indices
    local to each device's filter block, axis 0 sharded over the mesh)
    so each chip gathers only from its resident filter words and counts
    merge with one ``psum`` (``engine.sparse`` mesh form)."""

    word_idx: jax.Array       # int32[N_pad] | int32[D, Nd_pad]
    mask: jax.Array           # uint32 same shape (0 = padding)
    row_ptr: jax.Array        # int32[R_pad+1] | int32[D, R_pad+1]
    row_ids: np.ndarray       # uint64[R] sorted global rows
    row_cards: np.ndarray     # int64[R] full per-row cardinalities
    shards: tuple[int, ...]
    nbytes: int
    n_rows_pad: int           # pow2 row bucket (static program shape)
    mesh: object = None       # jax.sharding.Mesh when device-blocked
    axis: str | None = None   # mesh axis name

    @property
    def n_rows(self) -> int:
        return len(self.row_ids)


class PlaneCache:
    def __init__(self, place=None, budget_bytes: int = DEFAULT_BUDGET,
                 placement=None, stats=None, sidecars: bool = True,
                 delta_cells: int = 65536,
                 delta_compact_fraction: float = 0.5,
                 governor=None, flight=None):
        """``place(np_array) -> jax.Array`` controls device placement /
        mesh sharding; default is plain ``jax.device_put``.
        ``placement`` (the MeshPlacement the executor runs under, if
        any) additionally drives the sparse build's device blocking.
        ``stats`` (an obs registry) receives the plane-build metrics;
        ``sidecars`` toggles the warm dense-plane cache (``<fragment>
        .dense`` images written on cold builds, loaded at near
        raw-copy speed after a restart).

        ``delta_cells`` (r15 ingest) bounds the per-plane device delta
        overlay: writes to a resident whole-view plane absorb into a
        (cell → word value) overlay the query kernels merge at
        dispatch time instead of rebuilding or re-scattering the base;
        0 disables (pre-r15 incremental-scatter behavior).
        ``delta_compact_fraction``: overlay fill ratio past which the
        background compactor folds the overlay into the base plane and
        swaps generations atomically.

        ``governor`` (r17 tenancy): an optional
        :class:`pilosa_tpu.tenancy.ResidencyGovernor` — when present,
        serving hits feed its telemetry and every eviction pass orders
        by its keep-score (recent hits × bytes × rebuild cost) before
        the LRU stamp; without it (or before any telemetry) ordering
        is the stamped LRU exactly."""
        from pilosa_tpu.exec._lru import Stamps
        from pilosa_tpu.obs import NULL_FLIGHT, NopStats
        self.place = place or (placement.place if placement is not None
                               else jax.device_put)
        self.placement = placement
        self.budget = budget_bytes
        self._stats = stats or NopStats()
        self.sidecars = sidecars
        self.governor = governor
        # flight recorder (r19): evictions land on the incident
        # timeline with their reason — "why did that plane vanish at
        # 03:14" is answerable from the dump
        self.flight = flight or NULL_FLIGHT
        # compile-ladder warmer (r24): set by the executor after
        # construction; _insert_entry notes standard-plane residency
        self.warmer = None
        # bound once: the ledger's plane-attribution stamp runs on the
        # lock-free serving fast path
        from pilosa_tpu.obs.ledger import set_plane_context
        self._set_plane_ctx = set_plane_context
        # eviction accounting (r17 tenancy): every entry drop through
        # _evict_entry tallies here and on plane_evictions_total{reason}
        self.evictions = 0
        self._evictions_by_reason: dict[str, int] = {}
        # plane-build accounting (also on /status via stats()):
        # warm = fragment expansions served from a dense sidecar
        self.warm_hits = 0
        self.warm_misses = 0
        self.builds = 0
        self.build_failures = 0
        self.build_seconds_total = 0.0
        self.build_bytes_total = 0
        # serving-path residency accounting (r14 device telemetry):
        # hit = a query answered from a resident entry (fast-path,
        # locked revalidation, or in-place incremental refresh), miss
        # = a full build or a streamed answer while a background build
        # runs.  Plain int increments — racing serving threads may
        # lose the odd count, which a RATIO gauge never notices; a
        # lock here would sit on the lock-free fast path.
        self.hits = 0
        self.misses = 0
        self._failed_logged: set = set()
        # plain dict (NOT OrderedDict): the serving hot path revalidates
        # entries lock-free (GIL-atomic dict reads + a recency-stamp
        # write), so the one cache RLock stops serializing every
        # concurrent plane fetch; recency for eviction lives in _stamps
        # (shared race-handling with FusedCache — exec/_lru.py)
        self._entries: dict[tuple, tuple[tuple, object, int]] = {}
        self._stamps = Stamps()
        # a view's live row set over a shard tuple, memoised against
        # the view's fragment generations (:meth:`live_rows`): key
        # (field path, view, shards) -> (gens, read-only sorted rows).
        # Counted like hits / misses: plain increments, no lock.
        self._row_sets: dict[tuple, tuple[tuple, np.ndarray]] = {}
        self._row_set_bytes = 0
        self.row_set_hits = 0
        self.row_set_misses = 0
        # coded fields (:meth:`code_plane`): key -> the generations at
        # which the field was found to be held dense, and the code
        # rebuilds that found a once-coded field multi-valued
        self._code_dense: dict[tuple, tuple] = {}
        self.coded_fallbacks = 0
        self._stats.count("plane_coded_fallbacks_total", 0)
        # pair popcounts a word that coded fields' histograms formed
        # (:meth:`code_counts`)
        self._stats.count("code_histogram_pairs_total", 0)
        self._zeros: dict[int, jax.Array] = {}
        self._bytes = 0
        self._lock = threading.RLock()
        self.incremental_applied = 0  # delta-scatter refreshes (stats)
        # device delta overlays (r15 ingest): host mirrors of each
        # resident plane's pending write cells, keyed like _entries;
        # the stored tuple is (base plane array, DeltaMirror) — a
        # rebuilt base invalidates its mirror by identity.  Meshed
        # placements participate too (r21): the overlay's flat-index
        # math is LOGICAL-array math, so with the overlay arrays
        # replicated across the mesh (``MeshPlacement.replicate``)
        # base⊕delta stays one GSPMD program over the sharded base —
        # sustained ingest keeps the zero-rebuild guarantee on 1 chip
        # or 8.
        self.delta_cells = int(delta_cells)
        self.delta_compact_fraction = float(delta_compact_fraction)
        self._delta_mirrors: dict[tuple, tuple] = {}
        self._compacting: dict[tuple, threading.Thread] = {}
        self.delta_absorbs = 0
        self.delta_compactions = 0
        self.last_compaction_seconds = 0.0
        # keys leased to in-flight queries, per serving thread: eviction
        # must skip these — the query's frames hold live device refs, so
        # evicting frees no HBM and only forces a rebuild on next use
        # (the r4 OOM-retry thrash class)
        self._leases: dict[int, set] = {}
        # serve-while-build (r5): big dense planes build on background
        # threads in chunked, donated device updates; queries stream
        # until the flip.  key -> Thread (single-flight per key)
        self._building: dict[tuple, threading.Thread] = {}

    # -- in-flight leases ----------------------------------------------------

    def begin_query(self) -> None:
        """Open a lease set for this thread; every plane `_get` hands
        out until `end_query` stays pinned against eviction."""
        with self._lock:
            self._leases[threading.get_ident()] = set()

    def end_query(self) -> None:
        with self._lock:
            self._leases.pop(threading.get_ident(), None)

    def _pinned(self) -> set:
        # caller holds self._lock
        if not self._leases:
            return set()
        return set().union(*self._leases.values())

    def _eviction_order(self, pinned: set, keys=None) -> list:
        """Unpinned cache keys in EXPLICIT eviction order (evict the
        head first).  Primary key: the governor's keep-score ascending
        (cheap-to-rebuild, cold, small entries go first); tie-break —
        and the whole order when no governor is attached or an entry
        has no telemetry yet — is the recency stamp, i.e. the original
        approximate LRU.  Caller holds ``self._lock``."""
        g = self.governor
        ks = [k for k in (self._entries if keys is None else keys)
              if k not in pinned and k in self._entries]
        if g is None:
            return sorted(ks, key=lambda k: self._stamps.get(k))
        return sorted(ks, key=lambda k: (g.keep_score(
            k, self._entries[k][2]), self._stamps.get(k)))

    def _evict_entry(self, key, reason: str) -> int:
        """Drop one entry (caller holds ``self._lock`` and has checked
        pins); returns the bytes freed.  The single exit point every
        eviction path shares, so ``plane_evictions_total{reason}`` and
        the governor's recency reset can't be missed by a new path."""
        _, _, nbytes = self._entries.pop(key)
        self._stamps.pop(key)
        self._delta_mirrors.pop(key, None)
        self._bytes -= nbytes
        self.evictions += 1
        self._evictions_by_reason[reason] = \
            self._evictions_by_reason.get(reason, 0) + 1
        self._stats.count("plane_evictions_total", 1, reason=reason)
        self.flight.record("evict", f"{key[1]}/{key[2]}", reason,
                           float(nbytes))
        if self.governor is not None:
            self.governor.note_evict(key)
        return nbytes

    def evict_unpinned(self, target_bytes: int | None = None,
                       reason: str = "oom") -> int:
        """Free entries NOT leased by an in-flight query — the memory
        that eviction can actually reclaim — in explicit eviction
        order, stopping once ``target_bytes`` are freed (None = free
        everything unpinned, the OOM-recovery contract).  OOM recovery
        uses this instead of `invalidate`: dropping leased entries
        under concurrent load evicts planes whose HBM cannot be freed
        and makes every other in-flight query rebuild from scratch.
        Returns the bytes freed."""
        with self._lock:
            self._row_sets.clear()
            self._row_set_bytes = 0
            pinned = self._pinned()
            freed = 0
            for key in self._eviction_order(pinned):
                if target_bytes is not None and freed >= target_bytes:
                    break
                freed += self._evict_entry(key, reason)
            return freed

    def evict_tenant(self, index: str, need_bytes: int,
                     reason: str = "quota") -> int:
        """Free up to ``need_bytes`` of ONE tenant's unpinned entries
        in eviction order — the page-in admission path makes room
        within a tenant's own byte quota without touching neighbors'
        residency.  Returns the bytes freed."""
        with self._lock:
            pinned = self._pinned()
            keys = [k for k in self._entries if k[1] == index]
            freed = 0
            for key in self._eviction_order(pinned, keys):
                if freed >= need_bytes:
                    break
                freed += self._evict_entry(key, reason)
            return freed

    def tenant_bytes(self, index: str) -> int:
        """Resident cache bytes attributed to one tenant (all key
        kinds carry the index at position 1)."""
        with self._lock:
            return sum(v[2] for k, v in self._entries.items()
                       if k[1] == index)

    # -- public -------------------------------------------------------------

    def field_plane(self, index: str, field: Field, view_name: str,
                    shards: tuple[int, ...]) -> PlaneSet:
        """Whole-view plane (TopN / Rows / GroupBy path)."""
        key = ("plane", index, field.name, view_name, shards)
        build = (self._build_plane_chunked if self.placement is not None
                 else self._build_plane)
        return self._get(key, field, view_name, shards, build)

    def bsi_plane(self, index: str, field: Field,
                  shards: tuple[int, ...]) -> PlaneSet:
        """BSI bit-plane: rows are the fixed exists/sign/bit layout.
        Always CLEAN (a pending overlay folds first) — consumers that
        can answer base⊕delta go through :meth:`bsi_plane_delta`."""
        view_name = field.bsi_view_name
        key = ("bsi", index, field.name, view_name, shards,
               field.options.bit_depth)
        return self._get(key, field, view_name, shards, self._build_bsi)

    def bsi_plane_delta(self, index: str, field: Field,
                        shards: tuple[int, ...]) -> PlaneSet:
        """BSI bit-plane for the delta-aware aggregate consumers
        (r20): a STALE resident plane absorbs its write gap into a
        bounded device overlay (``ingest.delta.BsiOverlay``) and the
        returned PlaneSet carries it as ``.delta`` — Sum/Min/Max/
        Range-count kernels answer base⊕delta at dispatch, so
        sustained ingest on an int field stops forcing folds or
        rebuilds on the aggregate path."""
        view_name = field.bsi_view_name
        key = ("bsi", index, field.name, view_name, shards,
               field.options.bit_depth)
        # lock-free fast path: fresh entry serves as-is, overlay and
        # all (the aggregate kernels merge it in-program)
        hit = self._entries.get(key)
        if hit is not None and hit[0] == self._gens_fast(field, view_name,
                                                         shards):
            self._touch(key)
            self._lease_fast(key)
            self.hits += 1
            return hit[1]
        if hit is not None and self.delta_cells > 0:
            gens = self._gens(field, view_name, shards)
            with self._lock:
                cur = self._entries.get(key)
                if cur is not None and cur[0] == gens:
                    self._touch(key)
                    self._lease(key)
                    self.hits += 1
                    return cur[1]
            if cur is not None:
                ps = self._delta_update(key, field, view_name, shards,
                                        cur)
                if ps is not None:
                    with self._lock:
                        self._lease(key)
                    self.hits += 1
                    return ps
        return self._get(key, field, view_name, shards, self._build_bsi)

    # Planes at or under this build inline (the latency of spawning a
    # builder + answering via the streaming path isn't worth it); above
    # it, field_plane_nowait hands the build to a background thread.
    SYNC_BUILD_MAX = 256 << 20

    # Bytes per background-build transfer chunk: bounds host staging
    # memory (2× with the r10 double buffer) AND splits one multi-GB
    # device_put into restartable pieces.
    BUILD_CHUNK_BYTES = 256 << 20

    def field_plane_nowait(self, index: str, field: Field, view_name: str,
                           shards: tuple[int, ...]) -> PlaneSet | None:
        """Resident whole-view plane if fresh, else None — with the
        build running on a background thread (single-flight per key).
        Callers answer through their streaming/per-row fallback until
        the flip; restart-to-first-answer stops costing the full plane
        residency wait (VERDICT r4 weak #6: ~4.4 min at 1B cols).
        Upstream serves straight from mmap with no warm-up
        (``fragment.Open``, SURVEY §4.1) — availability first."""
        key = ("plane", index, field.name, view_name, shards)
        # lock-free fast path (mirrors _get): fresh resident plane
        hit = self._entries.get(key)
        if hit is not None and hit[0] == self._gens_fast(field, view_name,
                                                         shards):
            self._touch(key)
            self._lease_fast(key)
            self.hits += 1
            return hit[1]
        gens = self._gens(field, view_name, shards)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0] == gens:
                self._touch(key)
                self._lease(key)
                self.hits += 1
                return hit[1]
            if key in self._building:
                self.misses += 1
                return None
        if hit is not None:
            # a STALE resident plane absorbs its write gap into the
            # device delta overlay (base⊕delta answered at dispatch,
            # zero base rewrites) or folds — never spawn a full
            # GB-scale rebuild (and degrade to streaming) for a few
            # written cells
            ps = self._delta_update(key, field, view_name, shards, hit)
            if ps is not None:
                with self._lock:
                    self._lease(key)
                self.hits += 1
                return ps
        est = self.plane_bytes(field, view_name, shards)
        if est > self.budget:
            # the entry-resident fast checks upstream skip the budget
            # walk, so growth past budget is caught here: never spawn
            # a build the cache would refuse to keep
            self.misses += 1
            return None
        if est <= self.SYNC_BUILD_MAX or self.placement is not None:
            # small plane, or meshed placement: inline, on the
            # requesting thread — a meshed build is the chunked
            # pipeline too (field_plane), but the streaming fallback a
            # background build would answer through is not sharded
            return self.field_plane(index, field, view_name, shards)
        self.misses += 1
        with self._lock:
            if key in self._building:
                return None
            t = threading.Thread(
                target=self._background_build,
                args=(key, field, view_name, shards, gens),
                name="plane-build", daemon=True)
            self._building[key] = t
        t.start()
        return None

    def time_plane_nowait(self, index: str, field: Field,
                          shards: tuple[int, ...]):
        """One time field's bucketed device plane
        (:class:`pilosa_tpu.timeviews.TimePlaneSet`) if resident and
        serving-fresh, else None after kicking a build — the r23 time
        family's residency entry point, mirroring
        :meth:`field_plane_nowait`'s lock discipline.

        Validity is the SUFFIX-TAGGED per-bucket-view generation
        tuple (``timeviews.time_gens``): a bumped fragment absorbs
        into the (row, bucket)-keyed delta overlay (zero rebuilds
        under sustained event ingest); a new bucket, new row, or
        whole-row clear rebuilds.  Callers fall back to the
        op-at-a-time ``_time_row_span`` oracle on None."""
        from pilosa_tpu import timeviews
        key = ("tplane", index, field.name, shards)
        # lock-free fast path: fresh entry serves as-is, overlay and
        # all (run_time_range merges it in-program)
        hit = self._entries.get(key)
        if hit is not None and hit[0] == timeviews.time_gens(
                field, shards, fast=True):
            self._touch(key)
            self._lease_fast(key)
            self.hits += 1
            return hit[1]
        gens = timeviews.time_gens(field, shards)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0] == gens:
                self._touch(key)
                self._lease(key)
                self.hits += 1
                return hit[1]
        if hit is not None:
            tps = self._time_absorb(key, field, shards, hit)
            if tps is not None:
                with self._lock:
                    self._lease(key)
                self.hits += 1
                return tps
        plan = timeviews.plan_time_plane(field, shards)
        self.misses += 1
        if plan is None:
            return None  # no time views yet: nothing to serve from
        nbytes = plan[-1]
        if nbytes > self.budget:
            return None  # caller stays on the oracle path
        import time as _time
        t0 = _time.perf_counter()
        with _metrics.span("plane_build", field=field.name):
            tps = timeviews.build_time_plane(field, shards, self.place,
                                             plan=plan)
        dt = _time.perf_counter() - t0
        self.builds += 1
        self.build_seconds_total += dt
        self.build_bytes_total += nbytes
        self._stats.observe("plane_build_seconds", dt)
        self._stats.count("plane_build_bytes_total", nbytes)
        self._stats.gauge("time_view_buckets", float(len(plan[0])))
        self._insert_entry(key, gens, tps, nbytes, lease=True)
        return tps

    def _time_absorb(self, key, field: Field, shards: tuple[int, ...],
                     hit, attempts: int = 3):
        """Absorb the write gap of a stale "tplane" entry into its
        bounded device overlay (cells keyed by flat (row, bucket)
        slot) and advance the suffix-tagged generations — the step
        that keeps sustained time-bucketed ingest ZERO-rebuild.  None
        = can't absorb (disabled, new bucket/row, whole-row clear,
        overlay full, journal gap): the caller rebuilds — time planes
        have no fold path (the bucketed row axis doesn't match any
        single view's scatter), and rebuilds are sized by the live
        (row × bucket) set, not the field's full history.  Losing the
        entry-swap race to a concurrent reader's absorb retries
        against the new entry (up to ``attempts``) rather than
        degrading to a rebuild — under write+read concurrency that
        race is routine, a rebuild is not."""
        if self.delta_cells <= 0:
            return None
        from pilosa_tpu.ingest.delta import DeltaMirror
        from pilosa_tpu.timeviews import TimePlaneSet
        while True:
            old_gens, tps, nbytes = hit
            got = self._time_collect_changes(field, shards, hit,
                                             self.delta_cells)
            if got is None:
                return None
            cells, actual = got
            with self._lock:
                cur = self._entries.get(key)
                if cur is None or cur[1] is not tps:
                    if cur is not None and cur[0] == actual:
                        return cur[1]  # raced absorb, serving-fresh
                    if cur is None or attempts <= 0:
                        return None
                    attempts -= 1
                    hit = cur  # re-collect against the new entry
                    continue
                if actual == tuple(old_gens):
                    return tps  # no real gap (benign generation race)
                mir = self._delta_mirrors.get(key)
                if mir is None or mir[0] is not tps.plane:
                    mir = (tps.plane, DeltaMirror(self.delta_cells))
                    self._delta_mirrors[key] = mir
                mirror = mir[1]
                if not mirror.would_fit(cells):
                    return None  # overlay full: rebuild supersedes it
                mirror.absorb(cells)
                overlay = mirror.build_overlay(
                    self._overlay_put(),
                    tps.plane.shape[0] * tps.plane.shape[1])
                new_tps = TimePlaneSet(tps.plane, tps.shards,
                                       tps.row_ids, tps.slot_of,
                                       tps.buckets, tps.bucket_starts,
                                       tps.unit, delta=overlay)
                self._entries[key] = (actual, new_tps, nbytes)
                self._stamps.insert(key)
            self.delta_absorbs += 1
            return new_tps

    def _time_collect_changes(self, field: Field,
                              shards: tuple[int, ...], hit, cap: int):
        """Gather a "tplane" entry's write gap across its bucket
        views as overwrite cells ``({(flat_row, word): value},
        covered-through suffix-tagged gens)``; None = rebuild (bucket
        directory changed, new fragment/row, whole-row clear, over
        cap)."""
        from pilosa_tpu import timeviews
        from pilosa_tpu.store.view import VIEW_STANDARD
        old_gens, tps, _nbytes = hit
        if tuple(timeviews.bucket_suffixes(field)) != tps.buckets \
                or tuple(s for s, _ in old_gens) != tps.buckets:
            return None  # bucket appeared/vanished: geometry changed
        rb_pad = tps.plane.shape[1]
        nb = tps.n_buckets
        cells: dict = {}
        actual = []
        for bi, (suf, gens) in enumerate(old_gens):
            view = field.views.get(VIEW_STANDARD + "_" + suf)
            if view is None or len(gens) != len(shards):
                return None
            new_gens = list(gens)
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    if gens[si] != -1:
                        return None  # fragment vanished: rebuild
                    continue
                with frag.lock:
                    if gens[si] == -1:
                        return None  # new fragment: row set unknown
                    if frag.generation == gens[si]:
                        continue
                    changed = frag.changed_cells_since(gens[si])
                    if changed is None:
                        return None
                    for r, words in changed.items():
                        slot = tps.slot_of.get(int(r))
                        if slot is None:
                            return None  # new row: shape changed
                        if words is None:
                            return None  # whole-row clear: rebuild
                        flat = si * rb_pad + slot * nb + bi
                        row_words = np.asarray(
                            frag.row(int(r)).words(), np.uint32)
                        w_arr = np.fromiter(words, np.int64,
                                            len(words))
                        for w, v in zip(w_arr.tolist(),
                                        row_words[w_arr].tolist()):
                            cells[(flat, int(w))] = int(v)
                        if len(cells) > cap:
                            return None
                    new_gens[si] = frag.generation
            actual.append((suf, tuple(new_gens)))
        return cells, tuple(actual)

    def time_plane_status(self) -> list[dict]:
        """Resident "tplane" entries for the /status timeViews block:
        one row per (index, field) with bucket/row/byte geometry and
        overlay state — the operator's view of which time fields are
        serving at device speed."""
        out = []
        with self._lock:
            entries = list(self._entries.items())
        for key, (gens, tps, nbytes) in entries:
            if key[0] != "tplane":
                continue
            out.append({
                "index": key[1],
                "field": key[2],
                "shards": len(key[3]),
                "buckets": int(tps.n_buckets),
                "unit": tps.unit,
                "rows": int(len(tps.row_ids)),
                "bytes": int(nbytes),
                "delta": tps.delta is not None,
            })
        return out

    def wait_builds(self, timeout: float = 300.0) -> None:
        """Join in-flight background builds (OOM recovery's exclusive
        stage must not race GBs of invisible build residency)."""
        import time as _time
        end = _time.monotonic() + timeout
        while _time.monotonic() < end:
            with self._lock:
                t = next(iter(self._building.values()), None)
            if t is None:
                return
            t.join(max(0.1, end - _time.monotonic()))

    def _background_build(self, key, field: Field, view_name: str,
                          shards: tuple[int, ...], gens) -> None:
        try:
            ps = self._build_plane_chunked(field, view_name, shards)
            # publish BEFORE clearing _building (in the finally): a
            # wait_builds() caller must never observe "no builds" while
            # the plane is still about to be inserted — OOM recovery
            # invalidates right after that wait.
            # gens from BEFORE assembly: a mid-build write makes the
            # entry stale and the next query refreshes incrementally.
            self._insert_entry(key, gens, ps, ps.plane.size * 4)
        except Exception:  # noqa: BLE001 — build failure ≠ serving failure
            # queries keep streaming and the next request retries, but
            # a wedged build must be observable: count every failure
            # and log the traceback once per key (not once per retry)
            with self._lock:
                self.build_failures += 1
                first_for_key = key not in self._failed_logged
                if first_for_key:
                    if len(self._failed_logged) > 64:
                        self._failed_logged.clear()
                    self._failed_logged.add(key)
            self._stats.count("plane_build_failures_total", 1)
            if first_for_key:
                import logging
                logging.getLogger("pilosa_tpu.exec").exception(
                    "background plane build failed for %s "
                    "(queries keep streaming; next request retries)", key)
        finally:
            with self._lock:
                self._building.pop(key, None)

    # Builder threads for parallel fragment expansion: each expansion
    # is one native rc_expand_rows_into call that releases the GIL, so
    # the roaring→dense decode of a whole chunk runs at N-core speed
    # instead of one fragment at a time (BENCH_r05: 364 s of host-side
    # expansion in front of a 2.9 s raw copy).
    BUILD_WORKERS = 8

    def _build_plane_chunked(self, field: Field, view_name: str,
                             shards: tuple[int, ...]) -> PlaneSet:
        """Assemble a dense plane on device as a PIPELINE (r10):
        fragments of a chunk expand concurrently on a thread pool
        (bulk ``Fragment.expand_rows_into`` — native decode straight
        into the staging slab, dense sidecars served at memcpy speed),
        and chunks double-buffer so chunk N's host expansion overlaps
        chunk N−1's ``device_put`` + donated ``dynamic_update_slice``.
        Device memory stays 1× the plane (+1 chunk) and no single
        transfer exceeds BUILD_CHUNK_BYTES.

        Chunk axis: whole shards when a shard's slab fits a chunk (the
        common many-shards case — lets each fragment expand ONCE and
        write/read its dense sidecar), else row blocks across all
        shards (few huge shards)."""
        import time as _time
        t0 = _time.perf_counter()
        row_ids = self._union_row_ids(field, view_name, shards)
        r_pad = _pow2(max(1, len(row_ids)))
        slot_of = {int(r): i for i, r in enumerate(row_ids)}
        slab = r_pad * WORDS_PER_SHARD * 4
        with _metrics.span("plane_build", field=field.name):
            if slab <= self.BUILD_CHUNK_BYTES:
                ps = self._build_shard_chunks(field, view_name, shards,
                                              row_ids, r_pad, slot_of)
            else:
                ps = self._build_row_chunks(field, view_name, shards,
                                            row_ids, r_pad, slot_of)
        dt = _time.perf_counter() - t0
        nbytes = ps.plane.size * 4
        with self._lock:  # concurrent background builds both tally
            self.builds += 1
            self.build_seconds_total += dt
            self.build_bytes_total += nbytes
        self._stats.observe("plane_build_seconds", dt)
        self._stats.count("plane_build_bytes_total", nbytes)
        return ps

    def _expand_tasks(self, pool, tasks, tally: bool = True) -> None:
        """Run fragment-expansion closures on the builder pool and
        tally sidecar warm/cold accounting (one count per FRAGMENT —
        callers whose chunks revisit fragments pass tally=False);
        re-raises the first failure (a build must never silently ship
        a half-expanded chunk)."""
        from concurrent.futures import wait
        futs = [pool.submit(t) for t in tasks]
        wait(futs)
        hits = misses = 0
        for f in futs:
            mode = f.result()
            if not tally:
                continue
            if mode == "warm":
                hits += 1
            elif self.sidecars:  # a miss only exists with the cache on
                misses += 1
        if hits or misses:
            # counters shared with concurrent builds + stats() readers
            with self._lock:
                self.warm_hits += hits
                self.warm_misses += misses
            if hits:
                self._stats.count("plane_cache_warm_hits_total", hits)
            if misses:
                self._stats.count("plane_cache_warm_misses_total", misses)

    def _plane_accumulator(self, shape: tuple, axis: int):
        """-> (all-zero device plane of ``shape``, donated
        ``update(full, chunk, start)`` that writes ``chunk`` at offset
        ``start`` along ``axis``).  Under a placement the plane is
        born sharded and every device writes its own part of the
        chunk into its own part of the plane (``shard_map``: offsets
        are local, no collective, no gather)."""
        import jax.numpy as jnp

        def put(full, chunk, start):
            at = [0] * len(shape)
            at[axis] = start
            return jax.lax.dynamic_update_slice(full, chunk, at)

        p = self.placement
        if p is None:
            full = jnp.zeros(shape, dtype=jnp.uint32)
        else:
            from jax import shard_map
            from jax.sharding import PartitionSpec
            sharding = p.sharding(len(shape))
            full = jnp.zeros(shape, dtype=jnp.uint32, device=sharding)
            put = shard_map(put, mesh=p.mesh,
                            in_specs=(sharding.spec, sharding.spec,
                                      PartitionSpec()),
                            out_specs=sharding.spec)
        return full, jax.jit(put, donate_argnums=(0,))

    def _build_shard_chunks(self, field: Field, view_name: str,
                            shards: tuple[int, ...], row_ids: np.ndarray,
                            r_pad: int, slot_of: dict) -> PlaneSet:
        """Shard-major pipeline: each chunk is a group of whole shards,
        so every fragment expands exactly once (all rows, one native
        call) and its dense sidecar is written/read in one piece.

        Under a placement the shard axis is cut into ``n`` equal runs,
        one per device along it, and a chunk holds the same ``glen``
        local shards of every run: one sharded ``device_put`` feeds
        all the devices at once and each writes its block into its own
        part of the plane (``shard_map``: no collective, no gather), so
        the host never holds more than two chunks of a plane of any
        size."""
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial

        slab = r_pad * WORDS_PER_SHARD * 4
        p = self.placement
        n = p.n_devices if p is not None else 1
        if len(shards) % n:
            raise ValueError(
                f"plane build: {len(shards)} shards do not divide over "
                f"a {n}-device shard axis (executor pads via placement)")
        local = len(shards) // n   # shards of one device's run
        spc = max(1, min(local, self.BUILD_CHUNK_BYTES // (slab * n)))
        full, update = self._plane_accumulator(
            (len(shards), r_pad, WORDS_PER_SHARD), axis=0)

        view = field.view(view_name)
        slots = np.arange(len(row_ids), dtype=np.uint64)
        # sidecar disk writes overlap the build on one writer thread
        # (bounded queue: a slow disk backpressures the expansion pool
        # instead of buffering unbounded blob bytes).  Safe deferred:
        # each item is immutable bytes stamped under the fragment lock.
        import queue as _queue
        from pilosa_tpu.store.fragment import Fragment
        wq: _queue.Queue | None = None
        wt = None
        submit = None
        if self.sidecars:
            wq = _queue.Queue(maxsize=8)

            def submit(path, hdr, blob):  # noqa: E306 — writer feed
                wq.put((path, hdr, blob))

            def _writer():
                while True:
                    item = wq.get()
                    if item is None:
                        return
                    Fragment.write_sidecar_file(*item)

            wt = threading.Thread(target=_writer, name="plane-sidecar",
                                  daemon=True)
            wt.start()
        # double buffers keyed (parity, group length): the tail group
        # may be narrower — its own buffer, its own compiled shape
        bufs: dict[tuple, np.ndarray] = {}
        inflight: dict[int, object] = {}
        try:
            with ThreadPoolExecutor(max_workers=self.BUILD_WORKERS) as pool:
                for gi, s0 in enumerate(range(0, local, spc)):
                    glen = min(spc, local - s0)
                    par = gi % 2
                    buf = bufs.get((par, glen))
                    if buf is None:
                        buf = bufs[(par, glen)] = np.zeros(
                            (n * glen, r_pad, WORDS_PER_SHARD), np.uint32)
                    else:
                        # reusing a staging buffer: its previous H2D
                        # copy must have completed — the placed chunk
                        # that consumed it being ready guarantees that
                        if inflight.get(par) is not None:
                            inflight[par].block_until_ready()
                        buf[:] = 0
                    tasks = []
                    if view is not None and len(row_ids):
                        for bi in range(n * glen):
                            run, li = divmod(bi, glen)
                            s = shards[run * local + s0 + li]
                            if s == PAD_SHARD:
                                continue
                            frag = view.fragment(s)
                            if frag is None:
                                continue
                            tasks.append(partial(
                                frag.expand_rows_into, row_ids, buf[bi],
                                slots, sidecar=self.sidecars,
                                sidecar_submit=submit))
                    self._expand_tasks(pool, tasks)
                    placed = self.place(buf)
                    full = update(full, placed, np.int32(s0))
                    # track the NON-donated placed chunk: it being
                    # ready proves the H2D copy out of buf completed
                    # (full itself is donated into the next update and
                    # can't be polled)
                    inflight[par] = placed
        finally:
            if wq is not None:
                wq.put(None)
                wt.join()
        full.block_until_ready()
        return PlaneSet(full, shards, row_ids, slot_of)

    def _build_row_chunks(self, field: Field, view_name: str,
                          shards: tuple[int, ...], row_ids: np.ndarray,
                          r_pad: int, slot_of: dict) -> PlaneSet:
        """Row-block pipeline for planes whose per-shard slab exceeds
        BUILD_CHUNK_BYTES: chunks span all shards × a row block (the
        pre-r10 tiling, now with parallel expansion + overlapped H2D).
        Sidecars are OFF here: a row block never covers a fragment's
        full row set (so images could never be written), and warm
        reads would re-open + re-crc the entire multi-hundred-MB image
        once per chunk — O(chunks × image bytes) of redundant work."""
        from concurrent.futures import ThreadPoolExecutor
        from functools import partial

        block = max(1, self.BUILD_CHUNK_BYTES
                    // (len(shards) * WORDS_PER_SHARD * 4))
        # pow2 ≤ r_pad so chunks tile evenly — dynamic_update_slice
        # CLAMPS an out-of-bounds start, which would misplace the tail
        block = min(r_pad, 1 << max(0, block.bit_length() - 1))
        full, update = self._plane_accumulator(
            (len(shards), r_pad, WORDS_PER_SHARD), axis=1)

        view = field.view(view_name)
        bufs: list = [None, None]
        inflight: list = [None, None]
        with ThreadPoolExecutor(max_workers=self.BUILD_WORKERS) as pool:
            for ci, start in enumerate(range(0, r_pad, block)):
                chunk_rows = row_ids[start:start + block]
                if not len(chunk_rows):
                    break  # the pow2 tail is already zeros
                par = ci % 2
                buf = bufs[par]
                if buf is None:
                    buf = bufs[par] = np.zeros(
                        (len(shards), block, WORDS_PER_SHARD), np.uint32)
                else:
                    if inflight[par] is not None:
                        inflight[par].block_until_ready()
                    buf[:] = 0
                slots = np.arange(len(chunk_rows), dtype=np.uint64)
                tasks = []
                if view is not None:
                    for si, s in enumerate(shards):
                        if s == PAD_SHARD:
                            continue
                        frag = view.fragment(s)
                        if frag is None:
                            continue
                        tasks.append(partial(
                            frag.expand_rows_into, chunk_rows, buf[si],
                            slots))
                self._expand_tasks(pool, tasks, tally=False)
                placed = self.place(buf)
                full = update(full, placed, np.int32(start))
                inflight[par] = placed  # non-donated: pollable copy fence
        full.block_until_ready()
        return PlaneSet(full, shards, row_ids, slot_of)

    def has_plane(self, index: str, field: Field, view_name: str,
                  shards: tuple[int, ...]) -> bool:
        """Whether a whole-view plane entry can serve: fresh
        (generations match) or — with delta overlays on — stale but
        absorbable (the nowait fetch folds the write gap into the
        overlay without a rebuild).  Callers skip their admission/
        budget walks on True; growth past the budget is re-checked by
        ``field_plane_nowait`` before any rebuild spawns."""
        key = ("plane", index, field.name, view_name, shards)
        hit = self._entries.get(key)  # GIL-atomic; no lock needed
        if hit is None:
            return False
        if hit[0] == self._gens_fast(field, view_name, shards):
            return True
        return self.delta_cells > 0

    def has_entry(self, index: str, field: Field, view_name: str,
                  shards: tuple[int, ...]) -> bool:
        """A whole-view plane entry exists (fresh, delta-dirty, or
        stale).  The TopN admission path uses this to keep the
        per-request ``plane_bytes`` fragment walk off the hot path
        under sustained writes."""
        return ("plane", index, field.name, view_name,
                shards) in self._entries

    def has_code(self, index: str, field: Field,
                 shards: tuple[int, ...]) -> bool:
        """The field's standard view holds a code entry (fresh or
        stale; :meth:`code_plane`): a hint that its rows are derived
        from the code and no dense plane of the field is worth
        building, never a promise that the next fetch still codes."""
        return ("code", index, field.name, VIEW_STANDARD,
                shards) in self._entries

    def has_rows(self, index: str, field: Field, view_name: str,
                 row_ids, shards: tuple[int, ...]) -> bool:
        """Every one of ``row_ids`` holds a single-row entry
        (:meth:`row_words`), fresh or stale: a hint for admission
        decisions (the words are on the device already), never a
        promise that the next fetch will not rebuild."""
        return all(("row", index, field.name, view_name, r, shards)
                   in self._entries for r in row_ids)

    def rows_plane(self, index: str, field: Field, view_name: str,
                   row_ids: np.ndarray,
                   shards: tuple[int, ...]) -> PlaneSet:
        """Plane over EXACTLY the requested rows (GroupBy/UnionRows:
        memory bounded by the selection, not the field's cardinality)."""
        row_ids = np.asarray(row_ids, dtype=np.uint64)
        key = ("rows", index, field.name, view_name,
               tuple(int(r) for r in row_ids), shards)
        return self._get(key, field, view_name, shards,
                         lambda f, v, s: self._build_rows(f, v, s, row_ids))

    def _build_rows(self, field: Field, view_name: str,
                    shards: tuple[int, ...],
                    row_ids: np.ndarray) -> PlaneSet:
        r_pad = _pow2(max(1, len(row_ids)))
        host = np.zeros((len(shards), r_pad, WORDS_PER_SHARD),
                        dtype=np.uint32)
        slot_of = {int(r): i for i, r in enumerate(row_ids)}
        view = field.view(view_name)
        if view is not None:
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    continue
                frag.plane_rows(list(slot_of.keys()), host[si],
                                slots=list(slot_of.values()))
        return PlaneSet(self.place(host), shards, row_ids, slot_of)

    def _sparse_mesh(self):
        """(D, mesh, axis) when the sparse build should device-block:
        a 1-D shard mesh with >1 device (2-D word-split meshes keep the
        flat layout replicated — sparse CP-splitting is not built)."""
        p = self.placement
        if (p is not None and getattr(p, "words_size", 1) == 1
                and getattr(p, "n_devices", 1) > 1
                and getattr(p, "mesh", None) is not None):
            return p.n_devices, p.mesh, p.axis
        return None

    def sparse_bytes(self, field: Field, view_name: str,
                     shards: tuple[int, ...]) -> int:
        """Sparse-residency footprint with the SAME padding the build
        applies — the budget gate must never admit a set the cache then
        refuses (which would silently re-build per query).  Meshed:
        every device block pads to the LARGEST device's pow2 bucket, so
        the estimate groups per-shard cardinalities by device."""
        view = field.view(view_name)
        mesh_info = self._sparse_mesh()
        d = mesh_info[0] if mesh_info else 1
        per_dev = np.zeros(d, np.int64)
        total_rows = 0
        if view is not None:
            spd = max(1, len(shards) // d)
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is not None:
                    per_dev[min(si // spd, d - 1)] += frag.cardinality()
                    total_rows += len(frag.row_cardinalities()[0])
        r_term = (_pow2(max(1, total_rows)) + 1) * 4 * d
        return d * _pow2(max(1, int(per_dev.max()))) * 8 + r_term

    def sparse_plane(self, index: str, field: Field, view_name: str,
                     shards: tuple[int, ...]) -> SparseSet:
        """Device-resident sparse triplets for a high-row-cardinality
        view (cached/invalidation like dense planes)."""
        key = ("sparse", index, field.name, view_name, shards)
        return self._get(key, field, view_name, shards, self._build_sparse)

    def _build_sparse(self, field: Field, view_name: str,
                      shards: tuple[int, ...]) -> SparseSet:
        from pilosa_tpu.engine.words import SHARD_WIDTH
        view = field.view(view_name)
        mesh_info = self._sparse_mesh()
        d = mesh_info[0] if mesh_info else 1
        if len(shards) % d:
            raise AssertionError(
                f"sparse build: {len(shards)} shards not padded to the "
                f"{d}-device mesh (executor pads via placement)")
        spd = len(shards) // d
        per_shard = []  # (si, positions)
        frags = []
        if view is not None:
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    continue
                frags.append(frag)
                per_shard.append((si, frag.positions()))
        all_ids, row_cards = merge_row_cards(frags)
        r_pad = _pow2(max(1, len(all_ids)))

        # per-device bit lists; unmeshed is the d == 1 special case.
        # word indices are LOCAL to the device's filter block (si % spd)
        # so each chip's gather never leaves its resident words.
        wi_parts: list[list] = [[] for _ in range(d)]
        mask_parts: list[list] = [[] for _ in range(d)]
        slot_parts: list[list] = [[] for _ in range(d)]
        for si, pos in per_shard:
            if not len(pos):
                continue
            dev = si // spd
            rows = pos // np.uint64(SHARD_WIDTH)
            cols = (pos % np.uint64(SHARD_WIDTH)).astype(np.int64)
            wi_parts[dev].append(((si % spd) * WORDS_PER_SHARD
                                  + (cols >> 5)).astype(np.int32))
            mask_parts[dev].append(
                (np.uint32(1) << (cols & 31).astype(np.uint32)))
            slot_parts[dev].append(
                np.searchsorted(all_ids, rows).astype(np.int32))

        def assemble(parts_w, parts_m, parts_s):
            if parts_w:
                wi = np.concatenate(parts_w)
                mk = np.concatenate(parts_m)
                sl = np.concatenate(parts_s)
                order = np.argsort(sl, kind="stable")  # CSR row order
                return wi[order], mk[order], sl[order]
            return (np.empty(0, np.int32), np.empty(0, np.uint32),
                    np.empty(0, np.int32))

        blocks = [assemble(wi_parts[i], mask_parts[i], slot_parts[i])
                  for i in range(d)]
        n_pad = _pow2(max(1, max(len(b[0]) for b in blocks)))
        wi_out = np.zeros((d, n_pad), np.int32)
        mk_out = np.zeros((d, n_pad), np.uint32)  # mask 0 = padding
        rp_out = np.empty((d, r_pad + 1), np.int32)
        for i, (wi, mk, sl) in enumerate(blocks):
            wi_out[i, :len(wi)] = wi
            mk_out[i, :len(mk)] = mk
            # CSR boundaries; pad rows collapse to empty segments at N
            rp_out[i] = np.searchsorted(
                sl, np.arange(r_pad + 1, dtype=np.int64))
        nbytes = d * n_pad * 8 + d * (r_pad + 1) * 4
        if mesh_info:
            _, mesh, axis = mesh_info
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = NamedSharding(mesh, P(axis, None))
            return SparseSet(
                word_idx=jax.device_put(wi_out, sh),
                mask=jax.device_put(mk_out, sh),
                row_ptr=jax.device_put(rp_out, sh), row_ids=all_ids,
                row_cards=row_cards, shards=shards, nbytes=nbytes,
                n_rows_pad=r_pad, mesh=mesh, axis=axis)
        return SparseSet(
            word_idx=self.place(wi_out[0]), mask=self.place(mk_out[0]),
            row_ptr=self.place(rp_out[0]), row_ids=all_ids,
            row_cards=row_cards, shards=shards, nbytes=nbytes,
            n_rows_pad=r_pad)

    def generations(self, field: Field, view_name: str,
                    shards: tuple[int, ...]) -> tuple:
        """One lock-free sweep of a view's fragment generations — what
        a caller that fetches several rows of one view per request
        reads ONCE and hands to :meth:`row_words` /
        :meth:`plane_bytes` (``gens=``), instead of each of them
        sweeping the shard axis again."""
        return self._gens_fast(field, view_name, shards)

    def row_words(self, index: str, field: Field, view_name: str,
                  row_id: int, shards: tuple[int, ...],
                  gens: tuple | None = None) -> jax.Array:
        """One row across shards: uint32[n_shards, W] (Row-call fast path —
        avoids materializing the whole plane for wide fields).  With
        ``gens`` (this request's own :meth:`generations` sweep) a
        fresh resident row answers without a second sweep."""
        key = ("row", index, field.name, view_name, row_id, shards)
        ps = self._get(key, field, view_name, shards,
                       lambda f, v, s: self._build_row(f, v, s, row_id,
                                                       index),
                       gens=gens)
        return ps.plane

    # -- coded fields ----------------------------------------------------------

    def code_plane(self, index: str, field: Field,
                   shards: tuple[int, ...]) -> CodeSet | None:
        """The field's standard view as a bit-sliced code when it is
        held so — a set or mutex field of more than
        ``CODED_ROWS_OVER`` rows in which no column lies in two rows —
        else None (the field is dense).  Validated against the view's
        generations like every entry: a write rebuilds the code, and a
        rebuild that finds a column in two rows holds the field dense
        again (``coded_fallbacks``).  Both answers are memoised, so a
        fresh one costs one generation sweep."""
        if field.options.type not in (TYPE_SET, TYPE_MUTEX):
            return None
        key = ("code", index, field.name, VIEW_STANDARD, shards)
        gens = self._gens_fast(field, VIEW_STANDARD, shards)
        hit = self._entries.get(key)
        if hit is not None and hit[0] == gens:
            self._touch(key)
            self._lease_fast(key)
            self.hits += 1
            return hit[1]
        if self._code_dense.get(key) == gens:
            return None
        # the row set the caller's Rows / Row just had from the memo is
        # read without counting a second consult of it
        memo = self._row_sets.get((field.path, VIEW_STANDARD, shards))
        rows = (memo[1] if memo is not None and memo[0] == gens
                else self.live_rows(field, VIEW_STANDARD, shards, gens))
        code = None
        if len(rows) > CODED_ROWS_OVER:
            self.misses += 1
            code = self._build_code(field, shards, rows)
        if code is None:
            with self._lock:
                self._code_dense[key] = gens
                stale = self._entries.pop(key, None)
                if stale is not None:
                    self._stamps.pop(key)
                    self._bytes -= stale[2]
                    if len(rows) > CODED_ROWS_OVER:
                        # the rebuild met a column in two rows
                        self.coded_fallbacks += 1
                        self._stats.count("plane_coded_fallbacks_total", 1)
            return None
        self._insert_entry(key, gens, code, code.plane.size * 4, lease=True)
        return code

    def _build_code(self, field: Field, shards: tuple[int, ...],
                    row_ids: np.ndarray) -> CodeSet | None:
        """Each column's row slot as bit rows, one fragment at a time
        from its set positions (no dense expansion); None where a
        column of a non-mutex field lies in two rows."""
        from concurrent.futures import ThreadPoolExecutor
        import time as _time
        t0 = _time.perf_counter()
        # one value past the last slot stays free: the code of a row
        # with no bit anywhere (``code_rows``)
        depth = max(1, len(row_ids).bit_length())
        host = np.zeros((len(shards), depth + OFFSET_ROW, WORDS_PER_SHARD),
                        np.uint32)
        view = field.view(VIEW_STANDARD)
        check = field.options.type != TYPE_MUTEX
        # rows 0..R-1 are their own slots
        slots_are_ids = int(row_ids[-1]) == len(row_ids) - 1
        weights = np.arange(depth, dtype=np.int64)

        def one(si: int) -> bool:
            frag = view.fragment(shards[si]) \
                if view is not None and shards[si] != PAD_SHARD else None
            if frag is None:
                return True
            pos = frag.positions()
            if not len(pos):
                return True
            cols = (pos % np.uint64(SHARD_WIDTH)).astype(np.int64)
            if check and _in_two_rows(cols):
                return False
            rows = pos // np.uint64(SHARD_WIDTH)
            slot = (rows.astype(np.int64) if slots_are_ids
                    else np.searchsorted(row_ids, rows).astype(np.int64))
            bits = np.zeros((depth + 1, SHARD_WIDTH), bool)
            bits[0, cols] = True
            bits[1:, cols] = (slot[None] >> weights[:, None]) & 1
            packed = np.packbits(bits, axis=1, bitorder="little") \
                .view(np.uint32)
            host[si, EXISTS_ROW] = packed[0]
            host[si, OFFSET_ROW:] = packed[1:]
            return True

        with _metrics.span("planes.code_rows", field=field.name):
            with ThreadPoolExecutor(self.BUILD_WORKERS) as pool:
                ok = all(pool.map(one, range(len(shards))))
            if not ok:
                return None
            code = CodeSet(self.place(host), shards, row_ids,
                           {int(r): i for i, r in enumerate(row_ids)})
        dt = _time.perf_counter() - t0
        with self._lock:
            self.builds += 1
            self.build_seconds_total += dt
            self.build_bytes_total += host.nbytes
        self._stats.observe("plane_build_seconds", dt)
        self._stats.count("plane_build_bytes_total", host.nbytes)
        return code

    def code_rows(self, code: CodeSet, row_ids) -> PlaneSet:
        """Rows of a coded field as a dense plane over exactly
        ``row_ids`` (``uint32[n_shards, len(row_ids), W]``, derived on
        the device; not cached — the caller bounds its size).  A row
        with no slot (no bit anywhere) is all zeros."""
        row_ids = np.asarray(row_ids, np.uint64)
        # the slot no column holds: one past the last row
        slots = np.array([code.slot_of.get(int(r), code.n_rows)
                          for r in row_ids], np.int64)
        with _metrics.span("planes.code_rows"):
            plane = _equal_rows(code.plane,
                                bsik.code_masks(slots, code.depth))
        return PlaneSet(plane, code.shards, row_ids,
                        {int(r): i for i, r in enumerate(row_ids)})

    def code_counts(self, code: CodeSet,
                    filter_words: jax.Array | None) -> np.ndarray:
        """int64[R]: the columns under ``filter_words`` (all, without
        one) that each row of a coded field holds — one pass over its
        code rows (``bsi.value_histogram``)."""
        hist = np.asarray(_value_histogram(code.plane, filter_words))
        self._stats.count("code_histogram_pairs_total", bsik.histogram_pairs(
            hist, filter_words is not None))
        return hist[:code.n_rows].astype(np.int64)

    @staticmethod
    def take_rows(ps: PlaneSet, row_ids) -> PlaneSet:
        """A dense plane over ``row_ids`` of ``ps``'s rows, copied out
        on the device (not cached)."""
        row_ids = np.asarray(row_ids, np.uint64)
        slots = np.array([ps.slot_of[int(r)] for r in row_ids], np.int32)
        return PlaneSet(_take_rows(ps.plane, slots), ps.shards, row_ids,
                        {int(r): i for i, r in enumerate(row_ids)})

    def plane_bytes(self, field: Field, view_name: str,
                    shards: tuple[int, ...],
                    gens: tuple | None = None) -> int:
        """Estimated dense-plane footprint (for budget decisions): the
        view's live rows padded as the build pads them.  The row count
        comes from :meth:`live_rows`' memo — the estimate runs on
        every query of the field that finds no resident plane, and
        walking the fragments for it measured ~7 s a query for a
        5M-row sparse field at 954 shards.  ``gens`` is the caller's
        own sweep of the view (:meth:`generations`)."""
        n_rows = len(self.live_rows(field, view_name, shards, gens))
        return len(shards) * _pow2(max(1, n_rows)) * WORDS_PER_SHARD * 4

    # the row-set memo's bounds: entries, and bytes of row ids (a
    # 5M-row field's set is 40 MB); the newest entry always stays
    ROW_SET_MAX = 256
    ROW_SET_MAX_BYTES = 512 << 20

    def live_rows(self, field: Field, view_name: str,
                  shards: tuple[int, ...],
                  gens: tuple | None = None) -> np.ndarray:
        """Sorted uint64 ids of the rows with at least one bit in the
        view over ``shards`` (``PAD_SHARD`` skipped), memoised against
        the view's fragment generations: a hit costs one generation
        sweep — none given the caller's own (``gens``, taken before
        this call) — where a miss walks every fragment.

        Exact because a fragment's live row set moves only with its
        generation: every mutation bumps it, and a snapshot row that
        expands lazily has at least one bit (its containers' headers
        say so), so the expansion changes no live row.  The array is
        shared and read-only: filter it, never write into it."""
        if gens is None:
            gens = self._gens_fast(field, view_name, shards)
        key = (field.path, view_name, shards)
        hit = self._row_sets.get(key)  # GIL-atomic; no lock needed
        if hit is not None and hit[0] == gens:
            self.row_set_hits += 1
            return hit[1]
        self.row_set_misses += 1
        rows = self._union_row_ids(field, view_name, shards)
        rows.flags.writeable = False
        with self._lock:
            old = self._row_sets.pop(key, None)
            if old is not None:
                self._row_set_bytes -= old[1].nbytes
            self._row_sets[key] = (gens, rows)
            self._row_set_bytes += rows.nbytes
            while len(self._row_sets) > 1 and (
                    len(self._row_sets) > self.ROW_SET_MAX
                    or self._row_set_bytes > self.ROW_SET_MAX_BYTES):
                _, dropped = self._row_sets.pop(next(iter(self._row_sets)))
                self._row_set_bytes -= dropped.nbytes
        return rows

    @staticmethod
    def _union_row_ids(field: Field, view_name: str,
                       shards: tuple[int, ...]) -> np.ndarray:
        """Sorted distinct row ids across shards, vectorized (one
        np.unique over concatenated per-fragment arrays instead of a
        Python set union + sort)."""
        view = field.view(view_name)
        parts = []
        if view is not None:
            for s in shards:
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is not None:
                    parts.append(frag.row_ids_array())
        if not parts:
            return np.empty(0, np.uint64)
        return np.unique(np.concatenate(parts))

    def iter_row_blocks(self, field: Field, view_name: str,
                        shards: tuple[int, ...], block_rows: int):
        """Stream a view's rows through the device in fixed-size blocks:
        yields (row_ids[block], device uint32[n_shards, block, W]).

        The working-set half of SURVEY.md §8's "dense blowup" hard part:
        fields whose full plane exceeds the HBM budget never materialize
        it — each block reuses one compiled shape.  The final block is
        zero-padded (padded rows yield zero counts; callers slice)."""
        view = field.view(view_name)
        row_ids = self._union_row_ids(field, view_name, shards)
        for start in range(0, len(row_ids), block_rows):
            chunk = row_ids[start:start + block_rows]
            host = np.zeros((len(shards), block_rows, WORDS_PER_SHARD),
                            dtype=np.uint32)
            slot_of = {int(r): i for i, r in enumerate(chunk)}
            if view is not None:
                for si, s in enumerate(shards):
                    if s == PAD_SHARD:
                        continue
                    frag = view.fragment(s)
                    if frag is None:
                        continue
                    frag.plane_rows(list(slot_of.keys()), host[si],
                                    slots=list(slot_of.values()))
            yield chunk, self.place(host)

    def zeros(self, n_shards: int) -> jax.Array:
        """Cached all-zero bitmap uint32[n_shards, W] (empty Row / empty
        Union results) — built and transferred once per shard count, not
        per query."""
        key = n_shards
        with self._lock:
            hit = self._zeros.get(key)
        if hit is not None:
            return hit
        placed = self.place(np.zeros((n_shards, WORDS_PER_SHARD),
                                     dtype=np.uint32))
        with self._lock:
            self._zeros[key] = placed
        return placed

    def stats(self) -> dict:
        """Occupancy snapshot for /status and /metrics (one lock; the
        only supported external view of the cache's internals)."""
        with self._lock:
            hits, misses = self.hits, self.misses
            coded = [e for k, e in self._entries.items() if k[0] == "code"]
            return {"bytes": self._bytes, "budgetBytes": self.budget,
                    "entries": len(self._entries),
                    "pinnedEntries": len(self._pinned()),
                    # HBM residency (r14): open lease sets = in-flight
                    # queries holding device refs eviction must skip;
                    # hitRatio = fraction of plane requests answered
                    # from a resident entry (vs built or streamed)
                    "leases": len(self._leases),
                    "hits": hits, "misses": misses,
                    "hitRatio": (round(hits / (hits + misses), 4)
                                 if hits + misses else 0.0),
                    "incrementalRefreshes": self.incremental_applied,
                    # live row sets answered from the memo vs walked
                    # from the fragments (:meth:`live_rows`)
                    "rowSetHits": self.row_set_hits,
                    "rowSetMisses": self.row_set_misses,
                    # fields held as bit-sliced codes (code_plane),
                    # their resident bytes, and the rebuilds that
                    # found a coded field multi-valued
                    "codedFields": len(coded),
                    "codedBytes": sum(e[2] for e in coded),
                    "codedFallbacks": self.coded_fallbacks,
                    # r17 tenancy: explicit-order eviction accounting
                    # (budget pass, OOM recovery, quota make-room,
                    # stale page drops)
                    "evictions": self.evictions,
                    "evictionsByReason": dict(
                        self._evictions_by_reason),
                    # plane-build pipeline (r10): cold-build volume and
                    # the dense-sidecar warm cache's hit ratio
                    "builds": self.builds,
                    "buildSeconds": round(self.build_seconds_total, 3),
                    "buildBytes": self.build_bytes_total,
                    "buildFailures": self.build_failures,
                    "warmHits": self.warm_hits,
                    "warmMisses": self.warm_misses,
                    # meshed (ISSUE 16): builds land sharded across a
                    # placement (the inline meshed build path)
                    "meshed": self.placement is not None,
                    # r15 ingest: device delta overlays (writes served
                    # as base⊕delta without rebuild stalls)
                    "delta": self.delta_stats()}

    def invalidate(self, index: str | None = None) -> None:
        with self._lock:
            # memoised row sets drop wholesale either way: their
            # generation guard can false-match after an index is
            # deleted and recreated at the same path (generations
            # restart at 0), and recomputing them is one walk
            self._row_sets.clear()
            self._row_set_bytes = 0
            self._code_dense.clear()
            if index is None:
                self._entries.clear()
                self._stamps.clear()
                self._delta_mirrors.clear()
                self._bytes = 0
                return
            for key in [k for k in self._entries if k[1] == index]:
                _, _, nbytes = self._entries.pop(key)
                self._stamps.pop(key)
                self._delta_mirrors.pop(key, None)
                self._bytes -= nbytes

    # -- internal -----------------------------------------------------------

    def _gens(self, field: Field, view_name: str,
              shards: tuple[int, ...]) -> tuple:
        view = field.view(view_name)
        if view is None:
            return ()
        # PAD_SHARD (-1) is never a fragment key, so it maps to -1
        # like any absent shard
        return view.generations(shards)

    def _gens_fast(self, field: Field, view_name: str,
                   shards: tuple[int, ...]) -> tuple:
        """Lock-free generation read for the revalidation fast path:
        skips the field lock (``views`` dict read is GIL-atomic) AND
        the view lock (:meth:`View.generations_fast`) — the two
        per-query lock round trips the r6 concurrency work removed."""
        view = field.views.get(view_name)
        if view is None:
            return ()
        return view.generations_fast(shards)

    def _touch(self, key) -> None:
        # lock-free recency (the eviction tie-break) + governor value
        # telemetry (plain dict increment — a lost count under racing
        # threads never matters to a relative ordering)
        self._stamps.touch(key)
        if self.governor is not None:
            self.governor.note_hit(key)

    def _lease(self, key) -> None:
        # caller holds self._lock
        lease = self._leases.get(threading.get_ident())
        if lease is not None:
            lease.add(key)

    def _lease_fast(self, key) -> None:
        """Lock-free lease: replace this thread's lease set wholesale
        (existing-key dict write — atomic, no resize).  ``_pinned``
        snapshots the values under the cache lock and unions fully-
        formed set objects, so it sees either the old or the new set,
        never a torn one."""
        tid = threading.get_ident()
        lease = self._leases.get(tid)
        if lease is not None and key not in lease:
            self._leases[tid] = lease | {key}

    def _get(self, key, field: Field, view_name: str,
             shards: tuple[int, ...], build,
             gens: tuple | None = None) -> PlaneSet:
        # cost-ledger plane attribution (r19): stamp the serving
        # thread with the plane this query is about to scan — one
        # thread-local write, nothing else on the fast path
        self._set_plane_ctx(f"{key[1]}/{key[2]}")
        # lock-free fast path: the common serving case is a fresh
        # resident plane — one dict read + one generation compare,
        # no cache lock, no view lock.  Delta-dirty entries never
        # return here: every _get caller needs a CLEAN plane (the
        # delta-aware consumers go through field_plane_nowait), so a
        # pending overlay folds first.  ``gens``: the caller's own
        # sweep of the view for this request (:meth:`generations`),
        # compared in place of another one.
        hit = self._entries.get(key)
        if hit is not None and hit[0] == (
                gens if gens is not None
                else self._gens_fast(field, view_name, shards)) \
                and getattr(hit[1], "delta", None) is None:
            self._touch(key)
            self._lease_fast(key)
            self.hits += 1
            return hit[1]
        gens = self._gens(field, view_name, shards)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None and hit[0] == gens \
                    and getattr(hit[1], "delta", None) is None:
                self._touch(key)
                self._lease(key)
                self.hits += 1
                return hit[1]
        if hit is not None and key[0] == "plane":
            # fold overlay + journal gap into the base in one scatter
            ps = self._fold(key, field, view_name, shards, hit)
            if ps is not None:
                with self._lock:
                    self._lease(key)
                self.hits += 1
                return ps
        elif hit is not None and key[0] in ("bsi", "rows", "row"):
            ps = None
            if getattr(hit[1], "delta", None) is None:
                ps = self._incremental(key, field, view_name, shards, hit)
            if ps is None and key[0] == "bsi":
                # a pending BSI overlay (r20) or a gap past the
                # incremental cap: fold overlay + journal gap into the
                # base in one scatter (bounded by delta_cells +
                # MAX_INCR_CELLS) — never silently drop overlay cells
                # by scattering around them, never rebuild for a
                # coverable gap
                ps = self._fold(key, field, view_name, shards, hit)
            if ps is not None:
                with self._lock:
                    self._lease(key)
                self.hits += 1
                return ps
        self.misses += 1
        ps = build(field, view_name, shards)
        nbytes = getattr(ps, "nbytes", None)
        if nbytes is None:
            nbytes = ps.plane.size * 4
        self._insert_entry(key, gens, ps, nbytes, lease=True)
        return ps

    def _insert_entry(self, key, gens, ps, nbytes: int,
                      lease: bool = False) -> None:
        """Cache a built plane and run the pinned-aware LRU eviction
        pass (shared by the query-path build and background builds —
        both must trim to budget or the cache sits over it until the
        next miss)."""
        with self._lock:
            if nbytes > self.budget:
                return
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
            # a rebuilt base supersedes any pending overlay: the
            # fresh build re-read every fragment, so the mirror's
            # cells are already IN the new plane
            self._delta_mirrors.pop(key, None)
            self._entries[key] = (gens, ps, nbytes)
            self._stamps.insert(key)
            self._bytes += nbytes
            if lease:
                self._lease(key)
            # budget eviction skips leased entries: their device refs
            # are alive in query frames, so popping them frees no
            # HBM and forces the other query to rebuild mid-flight.
            # (_pinned() unions every lease set — only pay for it
            # when an eviction pass actually runs).  Order is the
            # explicit _eviction_order: governor keep-score when one
            # is attached, recency stamp otherwise.
            if self._bytes > self.budget and len(self._entries) > 1:
                pinned = self._pinned()
                for k in self._eviction_order(pinned):
                    if (self._bytes <= self.budget
                            or len(self._entries) <= 1):
                        break
                    if k == key:
                        continue
                    self._evict_entry(k, "budget")
            self._stamps.cleanup(self._entries)
        # compile-ladder warm-up (r24): a standard plane just became
        # resident — hand its shape to the background warmer so the
        # delta-aware program ladder compiles off the serving path
        # (outside the lock: note_resident is cheap but never worth
        # holding the cache lock for)
        if self.warmer is not None and key[0] == "plane":
            try:
                self.warmer.note_resident(tuple(ps.plane.shape))
            except Exception:  # noqa: BLE001 — warming is best-effort
                pass

    # Incremental cap: beyond this many changed (row, word) cells a
    # full rebuild is cheaper than the scatter
    MAX_INCR_CELLS = 4096

    def _incremental(self, key, field: Field, view_name: str,
                     shards: tuple[int, ...], hit):
        """Refresh a cached device plane IN PLACE from fragments'
        mutation journals instead of rebuilding + re-uploading — the
        device half of SURVEY.md §4.5 ingest (host delta queues →
        device scatter).  Returns the refreshed PlaneSet, or None when
        the journal can't cover the gap (fall back to rebuild)."""
        old_gens, ps, nbytes = hit
        kind = key[0]
        view = field.view(view_name)
        if view is None or len(old_gens) != len(shards):
            # no view yet, or the entry was cached before the view
            # existed (_gens returns () then): rebuild
            return None
        if kind == "row":
            the_row = key[4]
        r_pad = 1 if kind == "row" else ps.plane.shape[1]
        cell_rows, cell_words, cell_vals = [], [], []
        reset_rows, reset_vals = [], []
        actual = list(old_gens)
        for si, s in enumerate(shards):
            if s == PAD_SHARD:
                continue
            frag = view.fragment(s)
            if frag is None:
                if old_gens[si] != -1:
                    return None  # fragment vanished: rebuild
                continue
            with frag.lock:
                if old_gens[si] == -1:
                    return None  # new fragment: row set unknown
                if frag.generation == old_gens[si]:
                    continue
                cells = frag.changed_cells_since(old_gens[si])
                if cells is None:
                    return None
                for r, words in cells.items():
                    # running cap: don't assemble millions of cells
                    # only to discard them
                    if (len(cell_rows) + 64 * len(reset_rows)
                            > self.MAX_INCR_CELLS):
                        return None
                    if kind == "plane":
                        slot = ps.slot_of.get(int(r))
                        if slot is None:
                            return None  # new row: shape/row set changed
                    elif kind == "bsi":
                        if r >= r_pad:
                            return None  # bit depth grew
                        slot = int(r)
                    elif kind == "rows":
                        slot = ps.slot_of.get(int(r))
                        if slot is None:
                            continue  # outside the selection
                    else:  # "row"
                        if int(r) != the_row:
                            continue
                        slot = 0
                        words = None  # refresh the whole single row
                    flat = si * r_pad + slot
                    row_words = frag.row(int(r)).words()
                    if words is None:
                        reset_rows.append(flat)
                        reset_vals.append(np.array(row_words, np.uint32))
                    else:
                        w_arr = np.fromiter(words, np.int64, len(words))
                        cell_rows.extend([flat] * len(w_arr))
                        cell_words.extend(int(w) for w in w_arr)
                        cell_vals.extend(
                            np.asarray(row_words)[w_arr].tolist())
                actual[si] = frag.generation
        n_cells = len(cell_rows) + 64 * len(reset_rows)
        if n_cells > self.MAX_INCR_CELLS:
            return None
        new_plane = _apply_plane_cells(
            ps.plane if kind != "row" else ps.plane[:, None, :],
            np.asarray(cell_rows, np.int32), np.asarray(cell_words, np.int32),
            np.asarray(cell_vals, np.uint32),
            np.asarray(reset_rows, np.int32),
            (np.stack(reset_vals) if reset_vals
             else np.zeros((0, ps.plane.shape[-1]), np.uint32)))
        if kind == "row":
            new_plane = new_plane[:, 0, :]
        new_plane = self._repin(new_plane, ps.plane)
        new_ps = PlaneSet(new_plane, ps.shards, ps.row_ids, ps.slot_of)
        with self._lock:
            cur = self._entries.get(key)
            if cur is not None and cur[1] is ps:  # not replaced meanwhile
                self._entries[key] = (tuple(actual), new_ps, nbytes)
                self._stamps.insert(key)
        self.incremental_applied += 1
        return new_ps

    # -- delta overlays (r15 ingest) ----------------------------------------

    def _collect_changes(self, field: Field, view_name: str,
                         shards: tuple[int, ...], hit, cap: int):
        """Gather the write gap between a "plane" entry's covered
        generations and fragment truth as overwrite cells:
        ``({(flat_row, word): current word value}, [(flat_row,
        full-row words)] resets, covered-through gens)``, or None when
        the journal can't cover it (gap, new rows, over cap) — the
        caller compacts or rebuilds."""
        old_gens, ps, _nbytes = hit
        view = field.view(view_name)
        if view is None or len(old_gens) != len(shards):
            return None
        r_pad = ps.plane.shape[1]
        cells: dict = {}
        resets: list = []
        actual = list(old_gens)
        for si, s in enumerate(shards):
            if s == PAD_SHARD:
                continue
            frag = view.fragment(s)
            if frag is None:
                if old_gens[si] != -1:
                    return None  # fragment vanished: rebuild
                continue
            with frag.lock:
                if old_gens[si] == -1:
                    return None  # new fragment: row set unknown
                if frag.generation == old_gens[si]:
                    continue
                changed = frag.changed_cells_since(old_gens[si])
                if changed is None:
                    return None
                for r, words in changed.items():
                    slot = ps.slot_of.get(int(r))
                    if slot is None:
                        return None  # new row: shape/row set changed
                    flat = si * r_pad + slot
                    row_words = np.asarray(frag.row(int(r)).words(),
                                           np.uint32)
                    if words is None:
                        resets.append((flat, row_words))
                    else:
                        w_arr = np.fromiter(words, np.int64, len(words))
                        for w, v in zip(w_arr.tolist(),
                                        row_words[w_arr].tolist()):
                            cells[(flat, int(w))] = int(v)
                    if len(cells) + 64 * len(resets) > cap:
                        return None
                actual[si] = frag.generation
        return cells, resets, tuple(actual)

    def _delta_update(self, key, field: Field, view_name: str,
                      shards: tuple[int, ...], hit):
        """Bring a stale "plane" entry back to serving truth without a
        rebuild: absorb the gap into the device overlay (base stays
        immutable; queries answer base⊕delta), or fold overlay+gap
        into the base when the overlay can't take it.  None = rebuild
        (journal gap / new rows)."""
        ps = self._delta_absorb(key, field, view_name, shards, hit)
        if ps is not None:
            return ps
        hit = self._entries.get(key)
        if hit is None:
            return None
        return self._fold(key, field, view_name, shards, hit)

    def _overlay_put(self):
        """Placement for overlay device arrays: replicated across the
        mesh when one exists, plain ``device_put`` otherwise."""
        p = self.placement
        if p is not None and hasattr(p, "replicate"):
            return p.replicate
        return jax.device_put

    def _repin(self, arr, like):
        """Keep a refreshed plane on its predecessor's sharding: the
        scatter's output layout is GSPMD's choice, and fused program
        keys carry sharding identity (``exec.fused.sharding_key``) —
        a drifted layout would recompile every family for the plane."""
        if self.placement is None:
            return arr
        try:
            if arr.sharding == like.sharding:
                return arr
            return jax.device_put(arr, like.sharding)
        except Exception:  # noqa: BLE001 — best-effort pinning
            return arr

    def _delta_absorb(self, key, field: Field, view_name: str,
                      shards: tuple[int, ...], hit):
        """Absorb journal cells into the plane's bounded device
        overlay and advance the entry's covered generations — the
        serving-path write step: no base-plane rewrite, no
        generation-stale window.  None = can't absorb (disabled,
        whole-row ops, overlay full, journal gap)."""
        if self.delta_cells <= 0:
            return None
        old_gens, ps, nbytes = hit
        got = self._collect_changes(field, view_name, shards, hit,
                                    self.delta_cells)
        if got is None:
            return None
        cells, resets, actual = got
        if resets:
            return None  # whole-row replacements fold instead
        from pilosa_tpu.ingest.delta import DeltaMirror
        with self._lock:
            cur = self._entries.get(key)
            if cur is None or cur[1] is not ps:
                # raced another absorb/fold/rebuild: report the
                # current entry if it is already serving-fresh
                if cur is not None and cur[0] == actual:
                    return cur[1]
                return None
            if actual == tuple(old_gens):
                return ps  # no real gap (benign generation race)
            mir = self._delta_mirrors.get(key)
            if mir is None or mir[0] is not ps.plane:
                mir = (ps.plane, DeltaMirror(self.delta_cells))
                self._delta_mirrors[key] = mir
            mirror = mir[1]
            if not mirror.would_fit(cells):
                return None  # overlay full: fold/compact
            mirror.absorb(cells)
            # overlay arrays are tiny — under a mesh they replicate
            # (one copy per chip) so the merge with the shard-sharded
            # base compiles without a host round trip
            put = self._overlay_put()
            if key[0] == "bsi":
                # bit-sliced planes overlay by touched word COLUMN
                # (the aggregate kernels read whole columns) — see
                # ingest.delta.BsiOverlay
                overlay = mirror.build_bsi_overlay(
                    put, ps.plane.shape[1],
                    ps.plane.shape[0])
            else:
                overlay = mirror.build_overlay(
                    put,
                    ps.plane.shape[0] * ps.plane.shape[1])
            new_ps = PlaneSet(ps.plane, ps.shards, ps.row_ids,
                              ps.slot_of, delta=overlay)
            self._entries[key] = (actual, new_ps, nbytes)
            self._stamps.insert(key)
            fill = len(mirror) / max(1, self.delta_cells)
        self.delta_absorbs += 1
        if fill >= self.delta_compact_fraction:
            self._compact_async(key, field, view_name, shards)
        return new_ps

    # raced sentinel: a concurrent absorb/fold replaced the entry
    # mid-fold — retry against the new entry (NOT a rebuild signal)
    _RACED = object()

    def _fold(self, key, field: Field, view_name: str,
              shards: tuple[int, ...], hit):
        """Fold the entry's overlay plus any remaining journal gap
        into the base plane in ONE scatter (the existing
        ``dynamic_update_slice``/scatter machinery) and atomically
        swap the entry to a clean PlaneSet at the new generations —
        the compaction step.  Retries when a concurrent absorb swaps
        the entry mid-fold (under sustained writes the race is the
        common case, and giving up would force a spurious rebuild; on
        a starved CPU the swaps come slower than the retries, so the
        bound is sized for an oversubscribed box, not the happy path).
        None = the gap genuinely isn't coverable (rebuild)."""
        for _ in range(8):
            out = self._fold_once(key, field, view_name, shards, hit)
            if out is not self._RACED:
                return out
            hit = self._entries.get(key)
            if hit is None:
                return None
        return None

    def _fold_once(self, key, field: Field, view_name: str,
                   shards: tuple[int, ...], hit):
        import time as _time
        old_gens, ps, nbytes = hit
        got = self._collect_changes(field, view_name, shards, hit,
                                    self.delta_cells
                                    + self.MAX_INCR_CELLS)
        if got is None:
            return None
        cells, resets, actual = got
        t0 = _time.perf_counter()
        with self._lock:
            cur = self._entries.get(key)
            if cur is None or cur[1] is not ps:
                if cur is not None and cur[0] == actual \
                        and getattr(cur[1], "delta", None) is None:
                    return cur[1]
                return self._RACED if cur is not None else None
            mir = self._delta_mirrors.get(key)
            mirror_cells = (mir[1].snapshot()
                            if mir is not None and mir[0] is ps.plane
                            else {})
        if ps.delta is not None and not mirror_cells:
            # overlay without its mirror (dropped out from under us —
            # e.g. an invalidate raced): the cells can't be recovered
            # host-side, so rebuild rather than silently lose them
            return None
        if not cells and not resets and not mirror_cells:
            if actual == tuple(old_gens):
                return ps
            # generations advanced with empty journal coverage — swap
            # the covered gens forward without touching the plane
            new_ps = ps
        else:
            reset_rows = [fr for fr, _ in resets]
            reset_set = set(reset_rows)
            merged = {k: v for k, v in mirror_cells.items()
                      if k[0] not in reset_set}
            merged.update(cells)  # journal truth supersedes the mirror
            new_plane = _apply_plane_cells(
                ps.plane,
                np.fromiter((k[0] for k in merged), np.int64,
                            len(merged)).astype(np.int32),
                np.fromiter((k[1] for k in merged), np.int64,
                            len(merged)).astype(np.int32),
                np.fromiter(merged.values(), np.uint32, len(merged)),
                np.asarray(reset_rows, np.int32),
                (np.stack([rv for _, rv in resets]) if resets
                 else np.zeros((0, ps.plane.shape[-1]), np.uint32)))
            new_plane = self._repin(new_plane, ps.plane)
            new_ps = PlaneSet(new_plane, ps.shards, ps.row_ids,
                              ps.slot_of)
        with self._lock:
            cur = self._entries.get(key)
            if cur is None or cur[1] is not ps:
                return self._RACED if cur is not None else None
            self._entries[key] = (actual, new_ps, nbytes)
            self._stamps.insert(key)
            self._delta_mirrors.pop(key, None)
        self.incremental_applied += 1
        if mirror_cells or ps.delta is not None:
            self.delta_compactions += 1
            self.last_compaction_seconds = _time.perf_counter() - t0
            self._stats.count("delta_compactions_total", 1)
        return new_ps

    def _compact_async(self, key, field: Field, view_name: str,
                       shards: tuple[int, ...]) -> None:
        """Kick the background compactor for one plane (single-flight
        per key): folds the overlay into the base off the serving path
        and swaps generations atomically."""
        with self._lock:
            if key in self._compacting:
                return
            t = threading.Thread(
                target=self._compact_run,
                args=(key, field, view_name, shards),
                name="delta-compact", daemon=True)
            self._compacting[key] = t
        t.start()

    def _compact_run(self, key, field: Field, view_name: str,
                     shards: tuple[int, ...]) -> None:
        try:
            hit = self._entries.get(key)
            if hit is not None:
                self._fold(key, field, view_name, shards, hit)
        except Exception:  # noqa: BLE001 — compaction ≠ serving
            import logging
            logging.getLogger("pilosa_tpu.exec").exception(
                "delta compaction failed for %s (queries keep "
                "answering base⊕delta; next trigger retries)", key)
        finally:
            with self._lock:
                self._compacting.pop(key, None)

    def delta_stats(self) -> dict:
        """The /status ``ingest`` block's overlay half."""
        with self._lock:
            cells = sum(len(m) for _, m in self._delta_mirrors.values())
            bits = sum(m.bits for _, m in self._delta_mirrors.values())
            pending = len(self._compacting)
        cap = max(1, self.delta_cells)
        return {"deltaCells": cells, "deltaCap": self.delta_cells,
                "deltaOverlayBits": bits,
                "deltaFillRatio": (round(cells / cap, 4)
                                   if self.delta_cells else 0.0),
                "absorbs": self.delta_absorbs,
                "compactions": self.delta_compactions,
                "pendingCompactions": pending,
                "lastCompactionSeconds": round(
                    self.last_compaction_seconds, 6)}

    def _build_plane(self, field: Field, view_name: str,
                     shards: tuple[int, ...]) -> PlaneSet:
        """Monolithic single-transfer build — the pure-Python
        ``plane_rows`` path, kept untouched as the ORACLE the pipelined
        chunked build is tested bit-exact against."""
        import time as _time
        t0 = _time.perf_counter()
        view = field.view(view_name)
        row_ids = self._union_row_ids(field, view_name, shards)
        r_pad = _pow2(max(1, len(row_ids)))
        host = np.zeros((len(shards), r_pad, WORDS_PER_SHARD), dtype=np.uint32)
        slot_of = {int(r): i for i, r in enumerate(row_ids)}
        if view is not None:
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    continue
                rows_here = frag.row_ids()
                frag.plane_rows(rows_here, host[si],
                                slots=[slot_of[r] for r in rows_here])
        ps = PlaneSet(self.place(host), shards, row_ids, slot_of)
        dt = _time.perf_counter() - t0
        with self._lock:
            self.builds += 1
            self.build_seconds_total += dt
            self.build_bytes_total += host.nbytes
        self._stats.observe("plane_build_seconds", dt)
        self._stats.count("plane_build_bytes_total", host.nbytes)
        return ps

    def mesh_stats(self) -> dict | None:
        """/status ``mesh`` block (ISSUE 16): device count, shard
        axis, per-device resident plane bytes, padded-shard count —
        None when serving single-device.  Also refreshes the
        ``plane_shard_bytes{device}`` gauges so /metrics shows the
        HBM spread across the mesh."""
        p = self.placement
        if p is None:
            return None
        with self._lock:
            entries = [e[1] for e in self._entries.values()]
        per_dev: dict[str, int] = {}
        padded = 0
        seen = set()
        for ps in entries:
            for s in getattr(ps, "shards", ()):
                if s == PAD_SHARD:
                    padded += 1
            plane = getattr(ps, "plane", None)
            if plane is None or id(plane) in seen:
                continue
            seen.add(id(plane))
            try:
                for sh in plane.addressable_shards:
                    d = str(sh.device)
                    per_dev[d] = per_dev.get(d, 0) + int(sh.data.nbytes)
            except Exception:  # noqa: BLE001 — telemetry best effort
                continue
        for d, b in per_dev.items():
            self._stats.gauge("plane_shard_bytes", b, device=d)
        n_dev = int(getattr(p, "n_devices", 1)
                    * getattr(p, "words_size", 1))
        axis = getattr(p, "axis", None) or getattr(p, "shard_axis",
                                                   "shard")
        return {"devices": n_dev, "axis": axis,
                "perDeviceBytes": per_dev,
                # scalars beside the dict: the fullest and the emptiest
                # chip, for readers that cannot index by device name
                "maxDeviceBytes": max(per_dev.values(), default=0),
                "minDeviceBytes": min(per_dev.values(), default=0),
                "paddedShards": padded}

    def _build_bsi(self, field: Field, view_name: str,
                   shards: tuple[int, ...]) -> PlaneSet:
        depth = field.options.bit_depth
        n_rows = OFFSET_ROW + depth
        host = np.zeros((len(shards), n_rows, WORDS_PER_SHARD), dtype=np.uint32)
        view = field.view(view_name)
        if view is not None:
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is None:
                    continue
                rows_here = [r for r in frag.row_ids() if r < n_rows]
                frag.plane_rows(rows_here, host[si], slots=rows_here)
        row_ids = np.arange(n_rows, dtype=np.uint64)
        return PlaneSet(self.place(host), shards, row_ids,
                        {i: i for i in range(n_rows)})

    def _build_row(self, field: Field, view_name: str,
                   shards: tuple[int, ...], row_id: int,
                   index: str | None = None) -> PlaneSet:
        code = (self.code_plane(index, field, shards)
                if index is not None and view_name == VIEW_STANDARD
                else None)
        if code is not None:
            ps = self.code_rows(code, [row_id])
            return PlaneSet(ps.plane[:, 0, :], shards,
                            np.array([row_id], np.uint64), {row_id: 0})
        host = np.zeros((len(shards), WORDS_PER_SHARD), dtype=np.uint32)
        view = field.view(view_name)
        if view is not None:
            for si, s in enumerate(shards):
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is not None:
                    # plane_rows: snapshot rows come straight off the
                    # blob (bitmap containers memcpy) — no RowBits
                    frag.plane_rows([row_id], host[si:si + 1], slots=[0])
        return PlaneSet(self.place(host), shards,
                        np.array([row_id], np.uint64), {row_id: 0})
