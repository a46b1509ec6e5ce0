"""Fragment: one (field, view, shard) storage unit.

Reference: ``fragment.go`` (SURVEY.md §3.1) — bits of all rows of one view
of one shard in a single roaring bitmap keyed by
``rowID * ShardWidth + column``, persisted as an mmap'd snapshot plus an
op-log, compacted when ``opN > MaxOpN``.

This rebuild keeps the same on-disk contract (roaring snapshot file +
CRC-framed op-log, same position encoding) but host memory is per-row
:class:`~pilosa_tpu.store.row.RowBits` (sparse/dense auto-converting) —
the natural shape for assembling dense device planes.  The reference's
per-fragment TopN rank/LRU cache (``cache.go``) is intentionally absent:
on TPU, TopN recounts every row at HBM bandwidth (``engine.kernels.row_counts``),
so there is no cache to maintain or invalidate.

Concurrency: one RLock per fragment (reference: per-fragment
``sync.RWMutex``); mutators and plane assembly take it.
"""

from __future__ import annotations

import os
import struct
import threading
import zlib

import numpy as np

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.store import health as _storage_health
from pilosa_tpu.store import roaring
from pilosa_tpu.store.oplog import (OP_CLEAR_BITS, OP_CLEAR_ROW, OP_SET_BITS,
                                    OP_SET_ROW, OpLog)
from pilosa_tpu.store.row import RowBits

# Reference default: compact the op-log into a snapshot after ~2000 ops.
MAX_OP_N = 2000

# Rows per anti-entropy checksum block (reference: HashBlockSize = 100).
HASH_BLOCK_SIZE = 100

_SW = np.uint64(SHARD_WIDTH)


def no_index() -> None:
    """``shards_changed`` of a fragment, view or field that no index
    holds (unit tests build them bare)."""


class Fragment:
    """Bits of one (field, view, shard)."""

    def __init__(self, path: str, shard: int, *, max_op_n: int = MAX_OP_N,
                 fsync: bool = False, snapshot_submit=None, health=None,
                 shards_changed=None):
        self.path = path                      # snapshot file
        self.shard = shard
        self.max_op_n = max_op_n
        # disk-health governor + quarantine registry (r19), threaded
        # down from the holder like snapshot_submit; None for bare
        # fragments (unit tests) — every check is guarded
        self._health = health
        # when set, op-log compaction is handed to a background queue
        # (reference: the fragment snapshot queue in holder.go) instead
        # of running inline on the write path
        self._snapshot_submit = snapshot_submit
        # called (no arguments) whenever ``present`` flips either way:
        # the index's shard-set epoch bump, threaded down the same way
        self._shards_changed = shards_changed or no_index
        # does this fragment hold any row?  KEPT, not computed: every
        # method that can empty or fill the three row tiers ends in
        # _sync_presence() under the fragment lock, so lock-free
        # readers (the index's shard walk, backup inventory) never see
        # the transient emptiness inside a flush or a compaction
        self.present = False
        self.rows: dict[int, RowBits] = {}    # materialized/overlay rows
        self.op_n = 0
        self.generation = 0                   # bumped per mutation; device
                                              # plane caches key on this
        self.lock = threading.RLock()
        self._oplog = OpLog(path + ".oplog", fsync=fsync)
        self._open = False
        # lazy snapshot (mmap FromBuffer path, SURVEY.md §3.1 syswrap):
        # rows still in _snap_pending live only in the mapped file;
        # _ensure_row materializes them into self.rows on first touch
        self._snap_mm = None
        self._snap_dir: roaring.Directory | None = None
        self._snap_pending: set[int] = set()
        # the framed snapshot's declared crc32 (None = legacy unframed
        # file): re-checked when the mmap demotes to a heap copy
        self._snap_crc: int | None = None
        # recent-mutation journal for incremental device-plane updates
        # (exec.planes): (generation_after, {row: word_idx set | None}),
        # None = whole row changed.  Bounded; a gap means "rebuild".
        from collections import deque
        self._recent: deque = deque(maxlen=self.RECENT_MAX)
        # LSM-style pending tier (r5; reference: the amortization
        # ``fragment.bulkImport`` gets from one bulk union, SURVEY.md
        # §4.5): OP_SET_BITS batches append their genuinely-new
        # positions to one sorted array instead of paying a
        # sorted-union per (row, fragment) micro-chunk — the cost that
        # bounded spread ingest at ~0.17M bits/s (BASELINE.md r4).
        # ``_probe_cache`` is the merged tier's sorted positions for
        # O(log n) exact-changed probes; invariant: pending non-empty
        # ⇒ probe cache valid.  The op-log write still precedes all of
        # this, so crash replay re-derives pending — durability
        # semantics unchanged.
        self._pend_pos: np.ndarray = np.empty(0, np.uint64)
        self._probe_cache: np.ndarray | None = None

    # journal bounds: entries beyond RECENT_MAX or ops touching more
    # cells than RECENT_CELL_CAP evict history (planes falls back to a
    # compaction/rebuild).  The cell cap covers import-batch-sized ops
    # (r15 delta planes absorb bulk writes into device overlays —
    # positions-form entries alias the batch's already-allocated array,
    # so the cap bounds only the dict-form classic path's word lists)
    RECENT_MAX = 128
    RECENT_CELL_CAP = 65536

    # pending tier: flush to per-row RowBits at this many buffered bits
    # (bounds pending memory at 8 B/bit and keeps the per-batch sorted
    # insert cheap); probe caches beyond this bit count are not built
    # (8 B/bit of extra host memory — huge fragments keep the classic
    # per-row path)
    PEND_FLUSH_N = 65536
    PROBE_CACHE_MAX_BITS = 8 << 20

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "Fragment":
        with self.lock:
            if self._open:
                return self
            if os.path.exists(self.path) and os.path.getsize(self.path) > 0:
                try:
                    self._open_snapshot()
                except Exception as e:  # noqa: BLE001 — a corrupt
                    # snapshot must quarantine the FRAGMENT, never
                    # fail the whole holder open (the node still
                    # serves every healthy fragment; this one reads
                    # from replicas until repaired)
                    self._mark_corrupt("snapshot", f"open failed: {e}")
            for op, aux, positions in self._oplog.replay():
                self._apply(op, aux, positions)
                self.op_n += 1
            self._open = True
            self._sync_presence()
        return self

    # r19 snapshot frame: versioned header + crc32 of the roaring blob
    # (the end-to-end checksum the `.dense` sidecar already had).
    # Legacy unframed snapshots (raw roaring, first two bytes ==
    # roaring.MAGIC) still load — they just carry no checksum.
    SNAP_MAGIC = b"PSF1"
    SNAP_VERSION = 1
    _SNAP_HDR = struct.Struct("<4sHHQI")  # magic, ver, rsvd, len, crc

    def _open_snapshot(self) -> None:
        """mmap the snapshot and parse only its container directory —
        zero-copy cold start (the reference's ``roaring.FromBuffer`` over
        ``syswrap.Mmap``): no bit is expanded until a row is touched.
        Map count is bounded by ``syswrap.GLOBAL`` (LRU demotion to a
        heap copy — the reference's mmap→heap fallback).  Framed (r19)
        snapshots verify their crc BEFORE any bit can be served —
        corruption that would still parse (a flipped container key
        silently misroutes bits) quarantines instead."""
        import mmap as _mmaplib

        from pilosa_tpu.store import syswrap
        with open(self.path, "rb") as f:
            head = f.read(self._SNAP_HDR.size)
            if head[:4] == self.SNAP_MAGIC:
                if len(head) < self._SNAP_HDR.size:
                    self._mark_corrupt("snapshot",
                                       "truncated frame header")
                    return
                _m, ver, _r, blen, crc = self._SNAP_HDR.unpack(head)
                if ver != self.SNAP_VERSION:
                    self._mark_corrupt(
                        "snapshot", f"unknown frame version {ver}")
                    return
                mm = _mmaplib.mmap(f.fileno(), 0,
                                   access=_mmaplib.ACCESS_READ)
                blob = memoryview(mm)[self._SNAP_HDR.size:]
                # integrity before use; zlib releases the GIL so
                # concurrent fragment opens overlap the passes
                bad = len(blob) != blen or zlib.crc32(blob) != crc
                if bad:
                    del blob
                    try:
                        mm.close()
                    except BufferError:
                        pass
                    self._mark_corrupt(
                        "snapshot",
                        "frame length/crc mismatch (disk corruption)")
                    return
                self._snap_mm = mm
                self._snap_crc = crc
                self._snap_dir = roaring.Directory(blob)
                self._snap_pending = set(
                    int(r) for r in self._snap_dir.row_ids())
                syswrap.GLOBAL.register(self)
                return
            if len(head) >= 2 and struct.unpack("<H", head[:2])[0] == \
                    roaring.MAGIC:
                # legacy unframed (pre-r19) snapshot: no checksum
                mm = _mmaplib.mmap(f.fileno(), 0,
                                   access=_mmaplib.ACCESS_READ)
                self._snap_mm = mm
                self._snap_crc = None
                self._snap_dir = roaring.Directory(memoryview(mm))
                self._snap_pending = set(
                    int(r) for r in self._snap_dir.row_ids())
                syswrap.GLOBAL.register(self)
                return
            # non-pilosa (e.g. standard32) snapshot: legacy eager load
            f.seek(0)
            self._load_positions(roaring.deserialize(f.read()))

    def poison_snapshot(self) -> None:
        """Scrub-detected snapshot corruption on a LIVE fragment: drop
        the in-memory mapping so lazily-pending rows can no longer
        expand from the corrupt blob (reads then serve the overlay
        rows only — loud and quarantined, never silently wrong; the
        generation bump invalidates device planes built over the bad
        bytes).  The registry entry is the caller's job."""
        with self.lock:
            self._drop_snapshot()
            self._snap_crc = None
            self.generation += 1
            self._recent.clear()
            self._recent.append((self.generation, None))
            self._sync_presence()

    def _mark_corrupt(self, kind: str, detail: str) -> None:
        """Quarantine this fragment after an end-to-end checksum (or
        parse) failure: drop the snapshot refs and serve EMPTY locally
        — in cluster mode reads route to a replica and the scrubber's
        repair pulls a fresh copy; single-node, a loud quarantined
        empty beats silently-wrong bits.  The generation moves with
        the rows it drops, as in :meth:`poison_snapshot`: caches keyed
        on it (device planes, live row sets) must not serve them."""
        self._drop_snapshot()
        self._snap_crc = None
        self.generation += 1
        self._recent.clear()
        self._recent.append((self.generation, None))
        self._sync_presence()
        h = self._health
        if h is not None:
            h.quarantine(self.path, kind, detail)
        else:
            import logging
            logging.getLogger("pilosa_tpu.store").error(
                "fragment snapshot corrupt (%s) at %s: %s",
                kind, self.path, detail)

    def _demote_map(self) -> bool:
        """Swap the mmap'd snapshot for a heap copy (syswrap LRU
        eviction); returns False when the timed lock acquire fails so
        the pool can keep tracking this fragment (on contention the cap
        stays soft rather than deadlocking against a concurrent
        opener)."""
        if not self.lock.acquire(timeout=1.0):
            return False
        try:
            if self._snap_mm is None or self._snap_dir is None:
                return True  # nothing to demote — already heap/absent
            heap = bytes(self._snap_dir.buf)
            if self._snap_crc is not None \
                    and zlib.crc32(heap) != self._snap_crc:
                # the mapped bytes changed under us (disk/page-cache
                # corruption): the heap copy is poisoned — quarantine
                # at the demotion re-parse instead of serving it
                self._mark_corrupt(
                    "snapshot", "crc mismatch at mmap demotion")
                return True
            self._snap_dir = roaring.Directory(memoryview(heap))
            self._snap_mm = None  # closed when the last view dies
            return True
        finally:
            self.lock.release()

    def _drop_snapshot(self) -> None:
        from pilosa_tpu.store import syswrap
        syswrap.GLOBAL.release(self)
        self._snap_dir = None
        self._snap_pending = set()
        if self._snap_mm is not None:
            try:
                self._snap_mm.close()
            except BufferError:
                pass  # in-flight views; refcounting closes it later
            self._snap_mm = None

    def _ensure_row(self, row_id: int) -> None:
        """Materialize one snapshot-resident row into the overlay."""
        if row_id in self._snap_pending:
            self.rows[row_id] = RowBits.from_columns(
                self._snap_dir.expand_row(row_id))
            self._snap_pending.discard(row_id)

    def _materialize_all(self) -> None:
        for r in sorted(self._snap_pending):
            self._ensure_row(r)

    # -- pending tier -------------------------------------------------------

    def _flush_pending(self) -> None:
        """Merge the pending tier into per-row RowBits: ONE presorted
        union per touched row per flush, however many batches
        accumulated.  Callers hold the lock."""
        if not len(self._pend_pos):
            return
        pend = self._pend_pos
        self._pend_pos = np.empty(0, np.uint64)
        self._probe_cache = None
        for r, chunk in _split_by_row(pend, presorted=True):
            self._ensure_row(r)
            row = self.rows.get(r)
            if row is None:
                row = self.rows[r] = RowBits()
            row.add(chunk, presorted=True)

    def _pend_add(self, positions: np.ndarray) -> np.ndarray | None:
        """Append the genuinely-new subset of sorted-unique
        ``positions`` to the pending tier; returns that subset (exact
        changed count = its length), or None when the tier can't serve
        this fragment (probe cache would exceed its bit cap — caller
        falls back to the classic per-row path)."""
        if self._probe_cache is None:
            # pending is empty whenever the cache is absent, so
            # positions() here is merged-tier truth
            if self.cardinality() > self.PROBE_CACHE_MAX_BITS:
                return None
            self._probe_cache = self.positions()
        cache = self._probe_cache
        if len(cache):
            i = np.searchsorted(cache, positions)
            ic = np.minimum(i, len(cache) - 1)
            new = positions[~((i < len(cache)) & (cache[ic] == positions))]
        else:
            new = positions
        pend = self._pend_pos
        if len(pend) and len(new):
            j = np.searchsorted(pend, new)
            jc = np.minimum(j, len(pend) - 1)
            new = new[~((j < len(pend)) & (pend[jc] == new))]
        if len(new):
            self._pend_pos = np.insert(pend, np.searchsorted(pend, new),
                                       new)
            if len(self._pend_pos) >= self.PEND_FLUSH_N:
                self._flush_pending()
        return new

    def close(self) -> None:
        with self.lock:
            if self.op_n > 0:
                self.snapshot()
            self._drop_snapshot()
            self._oplog.close()
            self._open = False
            self._sync_presence()

    # -- reads --------------------------------------------------------------

    def _touch_map(self) -> None:
        if self._snap_mm is not None:
            from pilosa_tpu.store import syswrap
            syswrap.GLOBAL.touch(self)

    def row(self, row_id: int) -> RowBits:
        with self.lock:
            self._touch_map()
            self._flush_pending()
            self._ensure_row(row_id)
            return self.rows.get(row_id) or RowBits()

    def row_ids(self) -> list[int]:
        with self.lock:
            live = {r for r, b in self.rows.items() if b.any()}
            if len(self._pend_pos):
                live |= set((self._pend_pos // _SW).tolist())
            return sorted(live | self._snap_pending)

    def row_ids_array(self) -> np.ndarray:
        """Live row ids as an UNSORTED uint64 array, duplicates
        possible across tiers (callers np.unique) — the vectorized
        form for cross-shard unions (a 5M-row field's per-query
        set-union/sort through ``row_ids`` measured ~7 s across 954
        shards)."""
        with self.lock:
            live = [r for r, b in self.rows.items() if b.any()]
            pend = (_dedup_sorted(self._pend_pos // _SW)
                    if len(self._pend_pos) else ())
            n = len(live) + len(self._snap_pending) + len(pend)
            out = np.empty(n, np.uint64)
            out[:len(live)] = live
            out[len(live):len(live) + len(self._snap_pending)] = \
                list(self._snap_pending)
            out[len(live) + len(self._snap_pending):] = pend
            return out

    def _sync_presence(self) -> None:
        """Bring ``present`` in line with the row tiers — overlay rows,
        rows still resident in the mmap'd snapshot, pending-tier bits;
        nothing is expanded (``rows`` alone misses lazily-opened
        snapshot fragments — a cold-reopened multi-shard index would
        report no shards and queries would silently cover only
        shard 0) — and tell the index when it flipped.  O(1); callers
        hold the lock and call it BEFORE the write is acknowledged, so
        the next ``Index.available_shards()`` walks again."""
        now = (bool(self.rows) or bool(self._snap_pending)
               or len(self._pend_pos) > 0)
        if now != self.present:
            self.present = now
            self._shards_changed()

    def max_row_id(self) -> int:
        ids = self.row_ids()
        return ids[-1] if ids else 0

    def cardinality(self) -> int:
        with self.lock:
            cached = getattr(self, "_card_cache", None)
            if cached is not None and cached[0] == self.generation:
                return cached[1]
            # vectorized via row_cardinalities: a sparse snapshot can
            # hold millions of pending rows
            _, cards = self.row_cardinalities()
            total = int(cards.sum())
            self._card_cache = (self.generation, total)
            return total

    def positions(self) -> np.ndarray:
        """All set bits as sorted uint64 ``row*ShardWidth + col``.

        Snapshot-resident rows decode straight from the blob (native
        codec when built) WITHOUT materializing host ``RowBits`` — the
        bulk path for snapshot compaction and the sparse device build."""
        with self.lock:
            self._touch_map()
            parts = []
            if self._snap_pending:
                snap = roaring.deserialize(self._snap_dir.buf)
                if len(self._snap_pending) != len(
                        self._snap_dir.row_ids()):
                    # some snapshot rows were materialized (overlay wins)
                    pend = np.fromiter(self._snap_pending, np.uint64,
                                       len(self._snap_pending))
                    keep = np.isin(snap // _SW, pend)
                    snap = snap[keep]
                parts.append(snap)
            parts += [
                np.uint64(r) * _SW + b.columns().astype(np.uint64)
                for r, b in sorted(self.rows.items())
                if b.any()
            ]
            if len(self._pend_pos):
                # disjoint from both other tiers by construction
                parts.append(self._pend_pos)
        if not parts:
            return np.empty(0, dtype=np.uint64)
        if len(parts) == 1:
            return parts[0]
        return np.sort(np.concatenate(parts))

    def row_cardinalities(self) -> tuple[np.ndarray, np.ndarray]:
        """(row_ids uint64[R] sorted, cards int64[R]) without expanding
        any bits: directory sums for snapshot-resident rows, RowBits
        cardinality for overlay rows."""
        with self.lock:
            ids, cards = [], []
            if self._snap_pending and self._snap_dir is not None:
                uniq, ucards = self._snap_dir.row_cards()
                if len(self._snap_pending) != len(uniq):
                    pend = np.fromiter(self._snap_pending, np.uint64,
                                       len(self._snap_pending))
                    keep = np.isin(uniq, pend)
                    uniq, ucards = uniq[keep], ucards[keep]
                ids.append(uniq)
                cards.append(ucards)
            live = [(r, b.cardinality) for r, b in self.rows.items()
                    if b.any()]
            if live:
                live.sort()
                ids.append(np.array([r for r, _ in live], np.uint64))
                cards.append(np.array([c for _, c in live], np.int64))
            if len(self._pend_pos):
                # pending rows may ALSO exist in the overlay/snapshot —
                # sum-merge below folds the duplicates
                pr = self._pend_pos // _SW
                uniq = _dedup_sorted(pr)
                bounds = np.searchsorted(pr, uniq)
                ids.append(uniq)
                cards.append(np.diff(np.append(bounds, len(pr)))
                             .astype(np.int64))
        if not ids:
            return np.empty(0, np.uint64), np.empty(0, np.int64)
        if len(ids) == 1:
            return ids[0], cards[0]
        all_ids = np.concatenate(ids)
        all_cards = np.concatenate(cards)
        uniq = np.unique(all_ids)
        if len(uniq) == len(all_ids):
            order = np.argsort(all_ids, kind="stable")
            return all_ids[order], all_cards[order]
        sums = np.zeros(len(uniq), np.int64)
        np.add.at(sums, np.searchsorted(uniq, all_ids), all_cards)
        return uniq, sums

    def plane_rows(self, row_ids, out: np.ndarray, slots=None) -> None:
        """Fill ``out[slots[i]] = words of row_ids[i]`` (uint32[.., W]).

        The plane-assembly fast path: rows still resident in the mmap'd
        snapshot expand straight from the blob — via the C++
        ``rc_expand_plane`` when built (one pass over the file's
        containers for any number of rows), else per-row — without ever
        materializing host ``RowBits``.  Overlay rows copy their packed
        words.  Rows absent everywhere leave ``out`` untouched (callers
        pass zeroed slabs)."""
        from pilosa_tpu.store import native
        if slots is None:
            slots = range(len(row_ids))
        with self.lock:
            self._touch_map()
            self._flush_pending()
            pend, pend_slots = [], []
            for r, s in zip(row_ids, slots):
                r = int(r)
                if r in self._snap_pending:
                    pend.append(r)
                    pend_slots.append(s)
                else:
                    b = self.rows.get(r)
                    if b is not None and b.any():
                        out[s] = b.words()
            if not pend:
                return
            if native.available() and len(pend) >= 8:
                order = np.argsort(pend)
                pend_sorted = np.array(pend, np.uint64)[order]
                tmp = np.zeros((len(pend), out.shape[-1]), np.uint32)
                native.expand_plane(self._snap_dir.buf, SHARD_WIDTH,
                                    pend_sorted, tmp)
                out[np.array(pend_slots)[order]] = tmp
            else:
                # few rows: per-row directory slices (bitmap containers
                # memcpy from the blob) — no RowBits materialization,
                # and unlike the native one-pass expand it never walks
                # containers of rows that weren't asked for
                for r, s in zip(pend, pend_slots):
                    self._snap_dir.row_words(r, out[s])

    # -- bulk expansion + dense sidecar (r10 plane pipeline) ----------------

    # <snapshot>.dense sidecar: header + a serialize_dense roaring
    # image of the fragment's full dense rows.  The header stamps the
    # on-disk state the image captured plus a crc32 of the image; any
    # write grows the op-log and any compaction replaces the snapshot,
    # so a stamp mismatch is the (restart-stable) invalidation, and
    # the crc catches byte corruption that would otherwise still parse
    # (a flipped container key silently misroutes bits).
    DENSE_MAGIC = b"PDN1"
    DENSE_VERSION = 1
    _DENSE_HDR = struct.Struct("<4sHHQQQQI")

    @property
    def dense_path(self) -> str:
        return self.path + ".dense"

    def _dense_stamp(self) -> tuple[int, int, int]:
        """Restart-stable identity of this fragment's on-disk state:
        (snapshot size, snapshot mtime_ns, op-log size).  The op-log is
        flushed per append, so the size moves with every mutation."""
        try:
            st = os.stat(self.path)
            snap = (st.st_size, st.st_mtime_ns)
        except OSError as e:
            # an ABSENT snapshot (ENOENT) is the deliberate fallback —
            # the fragment has never compacted, stamp (0, 0).  Any
            # other errno is a disk fault: log once + feed the
            # governor, then keep the conservative fallback (a zero
            # stamp can only make the next build go cold, never wrong)
            _storage_health.note_os_error("fragment.stamp", self.path,
                                          e, health=self._health)
            snap = (0, 0)
        return (snap[0], snap[1], self._oplog.size())

    def expand_rows_into(self, row_ids, out: np.ndarray, slots=None, *,
                         sidecar: bool = False,
                         sidecar_submit=None) -> str:
        """Bulk-direct :meth:`plane_rows`: OR ``row_ids[i]``'s packed
        words into ``out[slots[i]]`` (caller passes zeroed slabs),
        writing straight into the destination via the native codec —
        no tmp slab + reorder copy, and the C call releases the GIL so
        builder threads genuinely overlap.  ``plane_rows`` remains the
        pure-Python fallback and oracle.

        With ``sidecar=True``: a fresh ``<path>.dense`` image
        short-cuts the whole expansion (all-bitmap containers — the
        word-aligned memcpy fast path), and a cold expansion covering
        the fragment's full row set writes one for the next restart.
        ``sidecar_submit`` (a ``(path, header, blob)`` callable) defers
        the disk write off the expansion critical path — safe because
        content and stamp are captured together under the fragment
        lock; a mutation racing the deferred write only stale-stamps
        the file, which the next reader rejects.
        Returns ``"warm"`` or ``"cold"`` for cache accounting."""
        row_ids = np.asarray(row_ids, dtype=np.uint64)
        if slots is None:
            slots = np.arange(len(row_ids), dtype=np.uint64)
        else:
            slots = np.asarray(slots, dtype=np.uint64)
        if len(row_ids) > 1 and not (row_ids[1:] >= row_ids[:-1]).all():
            # the native lookup binary-searches row_ids: unsorted input
            # would silently MISS rows, not error
            order = np.argsort(row_ids, kind="stable")
            row_ids, slots = row_ids[order], slots[order]
        with self.lock:
            self._touch_map()
            if sidecar and self._expand_sidecar(row_ids, slots, out):
                return "warm"
            self._flush_pending()
            pend, pend_slots = [], []
            for r, s in zip(row_ids, slots):
                r = int(r)
                if r in self._snap_pending:
                    pend.append(r)
                    pend_slots.append(int(s))
                else:
                    b = self.rows.get(r)
                    if b is not None and b.any():
                        out[int(s)] |= b.words()
            if pend:
                from pilosa_tpu.store import native
                if native.available():
                    order = np.argsort(pend)
                    native.expand_rows_into(
                        self._snap_dir.buf, SHARD_WIDTH,
                        np.array(pend, np.uint64)[order],
                        np.array(pend_slots, np.uint64)[order], out)
                else:
                    for r, s in zip(pend, pend_slots):
                        self._snap_dir.row_words(r, out[s])
            if sidecar:
                self._write_sidecar(row_ids, slots, out, sidecar_submit)
            return "cold"

    def _expand_sidecar(self, row_ids: np.ndarray, slots: np.ndarray,
                        out: np.ndarray) -> bool:
        """OR a valid sidecar image into ``out``; False when absent,
        stale (stamp mismatch) or corrupt (caller cold-builds and
        rewrites).  Caller holds the fragment lock."""
        import mmap as _mmaplib
        try:
            with open(self.dense_path, "rb") as f:
                hdr = f.read(self._DENSE_HDR.size)
                if len(hdr) != self._DENSE_HDR.size:
                    return False
                magic, ver, _, s0, s1, s2, blen, crc = \
                    self._DENSE_HDR.unpack(hdr)
                if (magic != self.DENSE_MAGIC or ver != self.DENSE_VERSION
                        or (s0, s1, s2) != self._dense_stamp()):
                    return False
                if os.fstat(f.fileno()).st_size \
                        != self._DENSE_HDR.size + blen:
                    return False
                mm = _mmaplib.mmap(f.fileno(), 0,
                                   access=_mmaplib.ACCESS_READ)
        except (OSError, ValueError):
            return False
        try:
            blob = memoryview(mm)[self._DENSE_HDR.size:]
            # integrity before use: corruption inside the image can
            # still PARSE (silently wrong bits).  zlib releases the
            # GIL, so the pass overlaps across builder threads.
            if zlib.crc32(blob) != crc:
                return False
            from pilosa_tpu.store import native
            if native.available():
                native.expand_rows_into(blob, SHARD_WIDTH, row_ids,
                                        slots, out)
            else:
                d = roaring.Directory(blob)
                for r, s in zip(row_ids, slots):
                    d.row_words(int(r), out[int(s)])
                del d
            return True
        except ValueError:
            return False  # corrupt image: cold build overwrites it
        finally:
            del blob
            try:
                mm.close()
            except BufferError:  # a stray view: freed on GC instead
                pass

    def _write_sidecar(self, row_ids: np.ndarray, slots: np.ndarray,
                       out: np.ndarray, submit=None) -> None:
        """Persist the just-expanded dense image (best-effort: sidecar
        failure must never fail a plane build).  Only written when the
        expansion covered the fragment's FULL row set — a partial image
        would serve missing rows as absent on the next warm load."""
        live = np.asarray(self.row_ids(), np.uint64)
        if not len(live) or not np.isin(live, row_ids).all():
            return
        stamp = self._dense_stamp()
        try:
            img = out[slots.astype(np.intp)]
            blob = roaring.serialize_dense(img, row_ids)
        except ValueError:
            return  # image exceeds the format limit: stay cold
        hdr = self._DENSE_HDR.pack(
            self.DENSE_MAGIC, self.DENSE_VERSION, 0, *stamp,
            len(blob), zlib.crc32(blob))
        if submit is not None:
            submit(self.dense_path, hdr, blob)
        else:
            self.write_sidecar_file(self.dense_path, hdr, blob,
                                    health=self._health)

    @staticmethod
    def write_sidecar_file(path: str, hdr: bytes, blob: bytes,
                           health=None) -> None:
        """Atomic best-effort sidecar write (also the deferred-writer
        entry point — the blob is immutable bytes, so writing after
        the build moved on is safe).  Failure is DELIBERATELY
        swallowed (a sidecar is a cache; a plane build must never fail
        on it) but no longer silently: log once + feed the disk-health
        governor — an ENOSPC here is the same full disk the oplog seam
        would hit next."""
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(hdr)
                f.write(blob)
            os.replace(tmp, path)
        except OSError as e:
            _storage_health.note_os_error("sidecar.write", path, e,
                                          health=health)
            try:
                os.unlink(tmp)
            except OSError:
                pass  # tmp may never have been created (ENOENT)

    # Cap on the generation-cached inverted index (sparse bits copied
    # into one flat array): 64M bits = 256MB.  Beyond it a second flat
    # copy of a huge field is not held.
    COLINDEX_MAX_BITS = 64 << 20

    # Building the colindex materializes every row as a host RowBits —
    # fine for 100k rows, pathological for a 5M-row lazy snapshot (GBs
    # of per-object overhead for 20M actual bits).  Row-counts beyond
    # this cap skip the cache regardless of bit count.
    COLINDEX_MAX_ROWS = 100_000

    # With the colindex unavailable, fragments with at most this many
    # rows answer by per-row O(1) word probes; beyond it, one
    # vectorized positions() scan of the blob (O(bits) numpy, zero
    # materialization).  Regime crossover measured on this host
    # (round 3): 64 dense rows × 15M bits — probes 132 ms vs scan
    # 984 ms (7×); 500k sparse rows × 2M bits — scan 213 ms vs
    # probe-loop ≈4.5 s extrapolated (20×, and the scan materializes
    # zero host rows).
    COLINDEX_CONTAINS_MAX_ROWS = 4096

    def rows_containing(self, col: int) -> np.ndarray:
        """Sorted row IDs whose bit ``col`` is set — the ``Rows(column=)``
        membership check (reference: per-row ``row.Includes`` walk in
        ``executor.go#executeRowsShard``).

        One decision, three regimes, chosen from directory metadata
        BEFORE any row materializes (unified in round 3 — the old
        over-cap path materialized every row first):

        1. bits ≤ COLINDEX_MAX_BITS: generation-cached flat (col, row)
           index, vectorized scan per query (the common case);
        2. few rows of many bits: per-row O(1) word probes;
        3. many rows of many bits: one vectorized blob positions()
           scan, no host row objects."""
        with self.lock:
            ids, cards = self.row_cardinalities()
            if (int(cards.sum()) <= self.COLINDEX_MAX_BITS
                    and len(ids) <= self.COLINDEX_MAX_ROWS):
                sp_cols, sp_rows, dense = self._colindex()
                hits = sp_rows[sp_cols == np.uint32(col)]
                w, bit = col >> 5, np.uint32(1 << (col & 31))
                dense_hits = [r for r, words in dense if words[w] & bit]
                out = np.concatenate(
                    [hits, np.array(dense_hits, np.uint64)]) \
                    if dense_hits else hits
                out.sort()
                return out.astype(np.uint64)
            if len(ids) <= self.COLINDEX_CONTAINS_MAX_ROWS:
                return np.array(
                    [int(r) for r in ids if self.row(int(r)).contains(col)],
                    dtype=np.uint64)
            pos = self.positions()  # blob-composed, no materialize
            rows = pos[pos % _SW == np.uint64(col)] // _SW
            rows.sort()
            return rows.astype(np.uint64)

    def _colindex(self):
        """(sparse_cols, sparse_rows, dense_list) cached per generation.
        Only called with total bits pre-checked under the cap."""
        cached = getattr(self, "_colindex_cache", None)
        if cached is not None and cached[0] == self.generation:
            return cached[1]
        self._flush_pending()
        self._materialize_all()
        sp_parts, sp_ids, dense = [], [], []
        for r, b in self.rows.items():
            if not b.any():
                continue
            if b.is_dense:
                dense.append((r, b.words()))
                continue
            sp_parts.append(b.columns())
            sp_ids.append(r)
        if sp_parts:
            sp_cols = np.concatenate(sp_parts)
            sp_rows = np.repeat(
                np.array(sp_ids, np.uint64),
                np.array([len(p) for p in sp_parts]))
        else:
            sp_cols = np.empty(0, np.uint32)
            sp_rows = np.empty(0, np.uint64)
        idx = (sp_cols, sp_rows, dense)
        self._colindex_cache = (self.generation, idx)
        return idx

    # -- mutation -----------------------------------------------------------

    def _write_gate(self) -> None:
        """Refuse mutations BEFORE any in-memory change when the
        storage layer is sick (node read-only on disk-full, or this
        fragment quarantined) — a refusal can never half-apply.  The
        healthy path costs one attribute load and a falsy branch
        (``StorageHealth.gate_active``)."""
        h = self._health
        if h is not None and h.gate_active:
            h.check_write(self.path)

    def set_bit(self, row_id: int, col: int) -> bool:
        return self.set_bits(np.array([row_id], np.uint64),
                             np.array([col], np.uint64)) > 0

    def clear_bit(self, row_id: int, col: int) -> bool:
        return self.clear_bits(np.array([row_id], np.uint64),
                               np.array([col], np.uint64)) > 0

    def set_bits(self, row_ids: np.ndarray, cols: np.ndarray,
                 sync_batch=None) -> int:
        """Bulk set; returns number of newly-set bits (reference:
        ``fragment.bulkImport``, SURVEY.md §4.5).  ``sync_batch`` (an
        :class:`~pilosa_tpu.store.oplog.SyncBatch`) defers the op-log
        fsync to the import batch boundary — one fsync per batch per
        touched fragment, not one per record."""
        self._write_gate()
        positions = (np.asarray(row_ids, np.uint64) * _SW
                     + np.asarray(cols, np.uint64))
        with self.lock:
            changed = self._apply(OP_SET_BITS, 0, positions)
            if changed:
                self._log(OP_SET_BITS, 0, positions,
                          sync_batch=sync_batch)
            return changed

    def clear_bits(self, row_ids: np.ndarray, cols: np.ndarray,
                   sync_batch=None) -> int:
        self._write_gate()
        positions = (np.asarray(row_ids, np.uint64) * _SW
                     + np.asarray(cols, np.uint64))
        with self.lock:
            changed = self._apply(OP_CLEAR_BITS, 0, positions)
            if changed:
                self._log(OP_CLEAR_BITS, 0, positions,
                          sync_batch=sync_batch)
            return changed

    def set_bits_grouped(self, groups: list[tuple[int, np.ndarray]]) -> int:
        """Bulk set with pre-grouped (row_id, cols) — skips the global
        position sort/segmentation when the caller already has per-row
        columns (BSI imports build exactly this shape)."""
        return self._apply_grouped(groups, clear=False)

    def clear_bits_grouped(self, groups: list[tuple[int, np.ndarray]]) -> int:
        return self._apply_grouped(groups, clear=True)

    def _apply_grouped(self, groups, clear: bool) -> int:
        self._write_gate()
        op = OP_CLEAR_BITS if clear else OP_SET_BITS
        with self.lock:
            self._probe_cache = None  # mutates merged truth directly
            self._flush_pending()
            changed = 0
            parts = []
            delta: dict = {}
            for row_id, cols in groups:
                cols = np.asarray(cols, dtype=np.uint32)
                if len(cols) == 0:
                    continue
                self._ensure_row(int(row_id))  # lazy snapshot rows
                if clear:
                    row = self.rows.get(int(row_id))
                    if row is not None:
                        changed += row.remove(cols)
                        if not row.any():
                            del self.rows[int(row_id)]
                else:
                    row = self.rows.get(int(row_id))
                    if row is None:
                        row = self.rows[int(row_id)] = RowBits()
                    changed += row.add(cols)
                words = np.unique(cols >> np.uint32(5))
                prev = delta.get(int(row_id))
                delta[int(row_id)] = (words if prev is None
                                      else np.union1d(prev, words))
                parts.append(np.uint64(row_id) * _SW + cols.astype(np.uint64))
            if changed:
                self.generation += 1
                self._note_delta(delta)
                self._sync_presence()
                self._log(op, 0, np.concatenate(parts))
            return changed

    def clear_row(self, row_id: int) -> int:
        """Clear every bit of a row (reference: ``fragment.clearRow``)."""
        self._write_gate()
        with self.lock:
            changed = self._apply(OP_CLEAR_ROW, row_id, None)
            if changed:
                self._log(OP_CLEAR_ROW, row_id, None)
            return changed

    def set_row(self, row_id: int, cols: np.ndarray) -> bool:
        """Replace a row's bits wholesale (reference: ``Store()`` /
        ``fragment.setRow``).  Logged as ONE op-log record carrying the
        row's complete new contents, so a crash mid-call can never replay
        a cleared row without its replacement bits."""
        self._write_gate()
        with self.lock:
            self._flush_pending()     # equality check needs merged truth
            self._ensure_row(row_id)  # no-op check needs snapshot truth
            before = self.rows.get(row_id)
            new = RowBits.from_columns(cols)
            before_cols = before.columns() if before is not None else np.empty(0, np.uint32)
            if np.array_equal(before_cols, new.columns()):
                return False
            positions = np.uint64(row_id) * _SW + new.columns().astype(np.uint64)
            self._apply(OP_SET_ROW, row_id, positions)
            self._log(OP_SET_ROW, row_id, positions)
            return True

    def import_roaring(self, blob: bytes, clear: bool = False,
                       sync_batch=None) -> int:
        """Union (or clear) an already-roaring-encoded bit set — the bulk
        loader fast path (reference: ``API.ImportRoaring``, SURVEY.md §4.5)."""
        self._write_gate()
        positions = roaring.deserialize(blob)
        op = OP_CLEAR_BITS if clear else OP_SET_BITS
        with self.lock:
            changed = self._apply(op, 0, positions)
            if changed:
                self._log(op, 0, positions, sync_batch=sync_batch)
            return changed

    # -- durability ---------------------------------------------------------

    def snapshot(self) -> None:
        """Rewrite the snapshot file and truncate the op-log (reference:
        ``fragment.snapshot``).  Atomic via temp+rename.  Afterwards the
        fragment re-opens the NEW file as its lazy backing and drops the
        overlay — compaction is also the host-memory release point
        (positions() composes from the old blob + overlay without
        materializing, so rows must not be left half-resident)."""
        h = self._health
        if (h is not None and not getattr(self, "_rebuilding", False)
                and h.is_quarantined(self.path)):
            # compacting a QUARANTINED fragment would overwrite the
            # corrupt-but-detectable file with a validly-framed
            # snapshot of whatever partial state memory holds —
            # masking the corruption forever (the registry is
            # in-memory; a restart would open 'healthy').  Keep the
            # evidence; replica repair owns the way out.
            import logging
            logging.getLogger("pilosa_tpu.store").warning(
                "refusing to compact quarantined fragment %s "
                "(would mask corruption as valid data)", self.path)
            return
        from pilosa_tpu.store import syswrap
        with self.lock:
            pre_stamp = self._dense_stamp()  # state the sidecar may match
            # merge the pending tier into rows FIRST: a failed file
            # write below (disk full) must leave merged in-memory
            # truth intact, not drop the pending bits with the blob
            self._flush_pending()
            blob = roaring.serialize(self.positions())
            tmp = self.path + ".tmp"
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            # r19 frame: versioned header + crc32 of the blob, written
            # through the sys.write/sys.fsync failpoints so chaos
            # schedules cover snapshots exactly like op-logs
            hdr = self._SNAP_HDR.pack(self.SNAP_MAGIC, self.SNAP_VERSION,
                                      0, len(blob), zlib.crc32(blob))
            try:
                with open(tmp, "wb") as f:
                    syswrap.checked_write(f, hdr)
                    syswrap.checked_write(f, blob)
                    f.flush()
                    syswrap.checked_fsync(f)
                os.replace(tmp, self.path)
            except OSError as e:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                h = self._health
                if h is not None \
                        and not isinstance(
                            e, _storage_health.StorageFaultError):
                    raise h.write_failed(self.path, e,
                                         site="fragment.snapshot") from e
                raise
            self._drop_snapshot()
            self.rows = {}
            try:
                if os.path.getsize(self.path) > 0:
                    self._open_snapshot()
            except Exception:
                # mmap/fd failure must not leave the fragment EMPTY in
                # memory (a later compaction would persist that empty
                # state over the good file): fall back to eager load
                # from the blob just written
                self._load_positions(roaring.deserialize(blob))
            self._sync_presence()
            self._oplog.truncate()
            self.op_n = 0
            # compaction preserves CONTENT, so a sidecar that matched
            # the pre-compaction state stays byte-valid: re-stamp it
            # against the new snapshot+empty-oplog identity instead of
            # discarding it (a clean shutdown compacts every dirty
            # fragment — deleting here would strand every restart cold)
            self._restamp_sidecar(pre_stamp)

    def _restamp_sidecar(self, pre_stamp: tuple[int, int, int]) -> None:
        """After compaction: carry a still-valid sidecar forward to the
        new on-disk identity, drop a stale one.  Caller holds the lock.
        A crash mid-rewrite only tears the header — the stamp then
        mismatches and the next build goes cold (never wrong)."""
        hdr_s = self._DENSE_HDR
        try:
            with open(self.dense_path, "r+b") as f:
                hdr = f.read(hdr_s.size)
                valid = False
                if len(hdr) == hdr_s.size:
                    magic, ver, _, s0, s1, s2, blen, crc = \
                        hdr_s.unpack(hdr)
                    valid = (magic == self.DENSE_MAGIC
                             and ver == self.DENSE_VERSION
                             and (s0, s1, s2) == pre_stamp)
                if valid:
                    f.seek(0)
                    f.write(hdr_s.pack(magic, ver, 0,
                                       *self._dense_stamp(), blen, crc))
                    return
        except OSError as e:
            # ENOENT (no sidecar) is the deliberate no-op; any other
            # errno (unreadable, disk fault) logs once + feeds the
            # governor — the stale-stamp fallback stays safe either
            # way (the next build just goes cold)
            _storage_health.note_os_error("sidecar.restamp",
                                          self.dense_path, e,
                                          health=self._health)
            return
        try:
            os.unlink(self.dense_path)
        except OSError as e:
            _storage_health.note_os_error("sidecar.unlink",
                                          self.dense_path, e,
                                          health=self._health)

    # -- anti-entropy -------------------------------------------------------

    def blocks(self) -> dict[int, int]:
        """Per-block checksums: block = ``row_id // HASH_BLOCK_SIZE``;
        checksum = crc32 over the block's sorted positions (reference:
        ``fragment.Blocks``, SURVEY.md §4.6).

        Generation-cached: decoding every position of a dense fragment
        is ~0.9 s on the r5 host (a no-op AAE round at
        954 fragments cost 14 minutes, recomputed on BOTH ends).  An
        unchanged fragment answers from the cache, so steady-state
        sweeps only pay for fragments that actually mutated."""
        with self.lock:
            cached = getattr(self, "_blocks_cache", None)
            if cached is not None and cached[0] == self.generation:
                return cached[1]
            gen = self.generation
            # one vectorized pass over positions() (snapshot rows decode
            # from the blob — no RowBits materialization, so AAE stays
            # cheap on multi-million-row sparse fragments)
            pos = self.positions()
        out: dict[int, int] = {}
        if len(pos):
            blocks = (pos // _SW
                      // np.uint64(HASH_BLOCK_SIZE)).astype(np.int64)
            uniq, starts = np.unique(blocks, return_index=True)
            bounds = np.append(starts, len(pos))
            data = pos.astype("<u8")
            for i, blk in enumerate(uniq):
                out[int(blk)] = zlib.crc32(
                    data[bounds[i]:bounds[i + 1]].tobytes())
        with self.lock:
            if self.generation == gen:
                self._blocks_cache = (gen, out)
        return out

    def block_positions(self, block: int) -> np.ndarray:
        """All positions of one checksum block (for AAE data exchange)."""
        lo = np.uint64(block * HASH_BLOCK_SIZE) * _SW
        hi = np.uint64((block + 1) * HASH_BLOCK_SIZE) * _SW
        with self.lock:
            pos = self.positions()
        return pos[(pos >= lo) & (pos < hi)]

    def merge_positions(self, positions: np.ndarray) -> int:
        """Union positions in (AAE repair receive path)."""
        self._write_gate()
        with self.lock:
            changed = self._apply(OP_SET_BITS, 0, positions)
            if changed:
                self._log(OP_SET_BITS, 0, positions)
            return changed

    # -- internal -----------------------------------------------------------

    def _note_delta(self, rows_words: dict) -> None:
        """Journal one mutation's touched cells for incremental device
        updates: {row: unique word idxs | None = whole row}."""
        cells = sum(64 if v is None else len(v)
                    for v in rows_words.values())
        if cells > self.RECENT_CELL_CAP:
            self._recent.clear()
            self._recent.append((self.generation, None))  # gap marker
        else:
            self._recent.append((self.generation, rows_words))

    def _note_delta_positions(self, positions: np.ndarray) -> None:
        """Positions-form journal entry (pending-tier writes): the
        {row: words} dict is derived lazily in changed_cells_since —
        per-row dict assembly at write time cost more than the whole
        pending append."""
        if len(positions) > self.RECENT_CELL_CAP:
            self._recent.clear()
            self._recent.append((self.generation, None))
        else:
            self._recent.append((self.generation, ("pos", positions)))

    def changed_cells_since(self, gen: int):
        """Merged {row: word idx set | None} covering generations
        (gen, current], or None if the journal has gaps (caller must
        rebuild).  ``{}`` when nothing changed."""
        with self.lock:
            if gen == self.generation:
                return {}
            if gen > self.generation:
                # cached gens AHEAD of this fragment: it was replaced
                # (e.g. field dropped+recreated) — force a rebuild
                return None
            entries = [(g, rw) for g, rw in self._recent if g > gen]
            if [g for g, _ in entries] != list(range(gen + 1,
                                                     self.generation + 1)):
                return None
            merged: dict = {}
            for _, rw in entries:
                if rw is None:
                    return None  # oversized op: rebuild
                if isinstance(rw, tuple):  # ("pos", positions) form
                    arr = rw[1]
                    rws = (arr // _SW).tolist()
                    wds = ((arr % _SW) >> np.uint64(5)).tolist()
                    for r, w in zip(rws, wds):
                        if merged.get(r, 0) is None:
                            continue
                        merged.setdefault(r, set()).add(int(w))
                    continue
                for r, words in rw.items():
                    if words is None or merged.get(r, 0) is None:
                        merged[r] = None
                    else:
                        merged.setdefault(r, set()).update(
                            int(w) for w in words)
            return merged

    def _apply(self, op: int, aux: int, positions: np.ndarray | None) -> int:
        """Apply an op to memory; returns bits changed.  Shared by the
        mutation API and op-log replay."""
        changed = 0
        delta: dict = {}
        if op == OP_SET_BITS and positions is not None \
                and len(positions) < self.PEND_FLUSH_N:
            # pending-tier fast path: probe + append, no per-row
            # unions.  Batches at/over the flush size skip it — they
            # are already amortized, and staging them through the
            # pending tier costs an extra probe+insert pass (measured
            # 2× on ImportRoaring blobs)
            if not len(positions):
                return 0
            self._check_rows(positions)
            positions = np.unique(np.asarray(positions, np.uint64))
            new = self._pend_add(positions)
            if new is not None:
                if len(new):
                    self.generation += 1
                    self._note_delta_positions(new)
                    self._sync_presence()
                return len(new)
            # probe cache over cap: classic per-row path below
        # every classic path below mutates merged truth: a probe cache
        # built earlier is stale the moment rows change — even when
        # pending is empty and the flush below is a no-op (a stale
        # cache would silently drop re-sets of cleared bits)
        self._probe_cache = None
        if len(self._pend_pos):
            # row-level ops, clears, and big batches need merged
            # per-row truth
            self._flush_pending()
        if op == OP_CLEAR_ROW:
            if aux in self._snap_pending:
                # whole row drops: count from the directory, never expand
                changed = self._snap_dir.row_cardinality(aux)
                self._snap_pending.discard(aux)
            row = self.rows.get(aux)
            if row is not None and row.any():
                changed += row.cardinality
            self.rows.pop(aux, None)
            delta[aux] = None
        elif op == OP_SET_ROW:
            if aux in self._snap_pending:
                changed += self._snap_dir.row_cardinality(aux)
                self._snap_pending.discard(aux)
            old = self.rows.pop(aux, None)
            if old is not None and old.any():
                changed += old.cardinality
            delta[aux] = None
            if positions is not None and len(positions):
                self._check_rows(positions)
                for r, chunk in _split_by_row(positions):
                    self._snap_pending.discard(r)
                    row = self.rows[r] = RowBits()
                    changed += row.add(chunk)
                    delta[r] = None
        elif op in (OP_SET_BITS, OP_CLEAR_BITS):
            assert positions is not None
            self._check_rows(positions)
            # ONE global sort+dedup; per-row chunks are then sorted-
            # unique, so row.add/remove skip their per-chunk np.unique
            # (a 100k-pair import touching every shard makes ~30 tiny
            # per-row calls per fragment — per-call work dominates)
            positions = np.unique(np.asarray(positions, np.uint64))
            for r, chunk in _split_by_row(positions, presorted=True):
                self._ensure_row(r)
                if op == OP_SET_BITS:
                    row = self.rows.get(r)
                    if row is None:
                        row = self.rows[r] = RowBits()
                    changed += row.add(chunk, presorted=True)
                else:
                    row = self.rows.get(r)
                    if row is not None:
                        changed += row.remove(chunk, presorted=True)
                        if not row.any():
                            del self.rows[r]
                # dedup without a re-sort (chunk is sorted): delta
                # cells count against RECENT_CELL_CAP, and one entry
                # per POSITION would inflate a dense-clustered batch
                # ~32x, tripping the journal-gap full-rebuild path
                delta[r] = _dedup_sorted(chunk >> np.uint32(5))
        else:
            raise ValueError(f"fragment: unknown op {op}")
        if changed:
            self.generation += 1
            self._note_delta(delta)
        self._sync_presence()
        return changed

    def _check_rows(self, positions: np.ndarray) -> None:
        if len(positions) and int(positions.max() // _SW) >= (1 << 40):
            raise ValueError("row id out of range (>= 2^40)")

    def _log(self, op: int, aux: int, positions: np.ndarray | None,
             sync_batch=None) -> None:
        try:
            self._oplog.append(op, aux, positions, sync_batch=sync_batch)
        except OSError as e:
            # disk-fault governor seam (r19): classify by errno —
            # ENOSPC flips the node read-only (this op is NOT acked;
            # memory ran ahead of disk, which the at-least-once
            # contract absorbs exactly like a torn write), repeated
            # EIO quarantines just this fragment
            h = self._health
            if h is not None \
                    and not isinstance(e, _storage_health.StorageFaultError):
                raise h.write_failed(self._oplog.path, e,
                                     site="oplog.append") from e
            raise
        h = self._health
        if h is not None:
            h.note_write_success(self.path)
        self.op_n += 1
        if self.op_n > self.max_op_n:
            if self._snapshot_submit is not None:
                self._snapshot_submit(self)  # background compaction
            else:
                self.snapshot()

    def rebuild_from_positions(self, positions: np.ndarray) -> None:
        """Replace this fragment's ENTIRE state with ``positions`` —
        the quarantine-repair receive path (r19): the local copy is
        untrustworthy (corrupt snapshot/op-log), so a healthy replica's
        full position set becomes the new truth.  Discards the old
        snapshot, op-log and overlay wholesale, loads the new bits,
        and compacts them into a fresh framed snapshot (verified by
        the caller before un-quarantine).  Deliberately bypasses the
        write gate — this IS the path out of quarantine."""
        with self.lock:
            self._drop_snapshot()
            self.rows = {}
            self._pend_pos = np.empty(0, np.uint64)
            self._probe_cache = None
            self._oplog.truncate()
            self.op_n = 0
            self._load_positions(positions)
            self.generation += 1
            self._sync_presence()
            # device-plane journals cannot describe a wholesale
            # replacement: force the rebuild path
            self._recent.clear()
            self._recent.append((self.generation, None))
            try:
                os.unlink(self.dense_path)  # sidecar captured old bytes
            except OSError:
                pass
            # the one compaction allowed while still quarantined:
            # this snapshot IS the replacement of the corrupt bytes
            self._rebuilding = True
            try:
                self.snapshot()
            finally:
                self._rebuilding = False

    def maybe_snapshot(self) -> None:
        """Background-queue entry point: compact only if still OVER the
        threshold — a dedup race can enqueue a fragment twice, and the
        duplicate must not re-serialize a huge fragment for one op."""
        with self.lock:
            if self._open and self.op_n > self.max_op_n:
                self.snapshot()

    def _load_positions(self, positions: np.ndarray) -> None:
        for r, cols in _split_by_row(positions):
            self.rows[r] = RowBits.from_columns(cols)


def _dedup_sorted(a: np.ndarray) -> np.ndarray:
    """Unique values of an already-sorted array, no re-sort."""
    if len(a) < 2:
        return a
    return a[np.concatenate(([True], a[1:] != a[:-1]))]


def _split_by_row(positions: np.ndarray,
                  presorted: bool = False) -> list[tuple[int, np.ndarray]]:
    """Split positions (any order, duplicates OK) into per-row column
    chunks: [(row_id, uint32 cols), ...].  The single place that owns the
    position→(row, col) segmentation invariant."""
    positions = np.asarray(positions, dtype=np.uint64)
    if len(positions) == 0:
        return []
    if not presorted:
        positions = np.sort(positions)
    row_ids = positions // _SW
    cols = (positions % _SW).astype(np.uint32)
    uniq, starts = np.unique(row_ids, return_index=True)
    bounds = np.append(starts, len(positions))
    return [(int(uniq[i]), cols[bounds[i]:bounds[i + 1]])
            for i in range(len(uniq))]
