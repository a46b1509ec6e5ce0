"""Field: a typed attribute group within an index.

Reference: ``field.go`` (SURVEY.md §3.1) — field types ``set``, ``int``
(BSI), ``time``, ``mutex``, ``bool`` plus v2's ``decimal`` and
``timestamp``; options (cache type/size kept for API parity, keys, time
quantum, min/max); the ``bsiGroup`` bit-sliced encoding with an exists
row, a sign row, and one row per magnitude bit of ``value - base``.

BSI row layout matches :mod:`pilosa_tpu.engine.bsi` exactly (EXISTS=0,
SIGN=1, OFFSET=2) — the device kernels consume fragment planes without
re-indexing.  ``bit_depth`` grows dynamically as larger values arrive
(reference: ``bsiGroup.bitDepth`` growth) and is persisted in the field
meta.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import asdict, dataclass, field as dc_field
from datetime import datetime, timezone

import numpy as np

from pilosa_tpu.engine.bsi import EXISTS_ROW, OFFSET_ROW, SIGN_ROW
from pilosa_tpu.store import timeq
from pilosa_tpu.store.fragment import no_index
from pilosa_tpu.store.view import VIEW_BSI_PREFIX, VIEW_STANDARD, View

TYPE_SET = "set"
TYPE_INT = "int"
TYPE_TIME = "time"
TYPE_MUTEX = "mutex"
TYPE_BOOL = "bool"
TYPE_DECIMAL = "decimal"
TYPE_TIMESTAMP = "timestamp"

BSI_TYPES = (TYPE_INT, TYPE_DECIMAL, TYPE_TIMESTAMP)

_UNIX_EPOCH = datetime(1970, 1, 1, tzinfo=timezone.utc)
_TS_UNITS = {"s": 1, "ms": 10**3, "us": 10**6, "ns": 10**9}


@dataclass
class FieldOptions:
    """Reference: ``field.go#FieldOptions`` / ``fieldOptions``."""

    type: str = TYPE_SET
    keys: bool = False
    cache_type: str = "ranked"   # ranked | lru | none (API parity; the TPU
    cache_size: int = 50000      # TopN path recounts, caches are not used)
    time_quantum: str = ""
    min: int | None = None
    max: int | None = None
    base: int = 0
    bit_depth: int = 0
    scale: int = 0               # decimal: value stored as int(v * 10^scale)
    epoch: str = ""              # timestamp: ISO epoch, default Unix
    time_unit: str = "s"         # timestamp: s | ms | us | ns
    created_at: float = 0.0      # wall time of creation (cluster schema
                                 # tombstones compare against this)

    def __post_init__(self):
        if self.type not in (TYPE_SET, TYPE_INT, TYPE_TIME, TYPE_MUTEX,
                             TYPE_BOOL, TYPE_DECIMAL, TYPE_TIMESTAMP):
            raise ValueError(f"invalid field type {self.type!r}")
        if self.type == TYPE_TIME and self.time_quantum:
            self.time_quantum = timeq.validate_quantum(self.time_quantum)
        if self.type == TYPE_TIMESTAMP and self.time_unit not in _TS_UNITS:
            raise ValueError(f"invalid timestamp unit {self.time_unit!r}")
        if self.type in BSI_TYPES and self.min is not None and self.max is not None:
            if self.min > self.max:
                raise ValueError("field min > max")
            # base minimizes stored magnitudes (reference: v2 base offset)
            if self.base == 0:
                if self.min > 0:
                    self.base = self.min
                elif self.max < 0:
                    self.base = self.max
            if self.bit_depth == 0:
                span = max(abs(self.min - self.base), abs(self.max - self.base))
                self.bit_depth = max(1, int(span).bit_length())
        if self.type in BSI_TYPES and self.bit_depth == 0:
            self.bit_depth = 1


class Field:
    def __init__(self, path: str, index_name: str, name: str,
                 options: FieldOptions | None = None, *, fsync: bool = False,
                 snapshot_submit=None, health=None, shards_changed=None):
        self.path = path
        self.index_name = index_name
        self.name = name
        self.options = options or FieldOptions()
        self.fsync = fsync
        self.snapshot_submit = snapshot_submit
        self.health = health
        # the index's shard-set epoch bump, handed on to every view
        self.shards_changed = shards_changed or no_index
        self.views: dict[str, View] = {}
        self._row_attrs = None
        self._lock = threading.RLock()

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "Field":
        meta = os.path.join(self.path, ".meta")
        if os.path.exists(meta):
            with open(meta) as f:
                self.options = FieldOptions(**json.load(f))
        views_dir = os.path.join(self.path, "views")
        if os.path.isdir(views_dir):
            for name in os.listdir(views_dir):
                v = View(os.path.join(views_dir, name), name,
                         fsync=self.fsync,
                         snapshot_submit=self.snapshot_submit,
                         health=self.health,
                         shards_changed=self.shards_changed)
                self.views[name] = v.open()
        return self

    def save_meta(self) -> None:
        os.makedirs(self.path, exist_ok=True)
        tmp = os.path.join(self.path, ".meta.tmp")
        with open(tmp, "w") as f:
            json.dump(asdict(self.options), f)
        os.replace(tmp, os.path.join(self.path, ".meta"))

    def close(self) -> None:
        for v in self.views.values():
            v.close()
        if self._row_attrs is not None:
            self._row_attrs.close()
            self._row_attrs = None

    @property
    def row_attrs(self):
        """Row attribute store (reference: field-level AttrStore,
        ``field.go``), created on first use."""
        with self._lock:
            if self._row_attrs is None:
                from pilosa_tpu.store.attrs import AttrStore
                self._row_attrs = AttrStore(
                    os.path.join(self.path, "_attrs.db"))
            return self._row_attrs

    @property
    def has_row_attrs(self) -> bool:
        """Whether an attr store EXISTS, without creating one — pure
        read paths (Row results attaching attrs) must not write a
        sqlite file to a possibly read-only data dir."""
        with self._lock:
            if self._row_attrs is not None:
                return True
        return os.path.exists(os.path.join(self.path, "_attrs.db"))

    # -- views --------------------------------------------------------------

    def view(self, name: str, create: bool = False) -> View | None:
        with self._lock:
            v = self.views.get(name)
            if v is None and create:
                v = View(os.path.join(self.path, "views", name), name,
                         fsync=self.fsync,
                         snapshot_submit=self.snapshot_submit,
                         health=self.health,
                         shards_changed=self.shards_changed).open()
                self.views[name] = v
                # AFTER the insert (time-quantum views land here too):
                # the view's own bump in open() came before any walk
                # could reach it
                self.shards_changed()
            return v

    @property
    def bsi_view_name(self) -> str:
        return VIEW_BSI_PREFIX + self.name

    def standard_view(self, create: bool = False) -> View | None:
        return self.view(VIEW_STANDARD, create)

    def bsi_view(self, create: bool = False) -> View | None:
        return self.view(self.bsi_view_name, create)

    def available_shards(self) -> list[int]:
        shards: set[int] = set()
        with self._lock:
            for v in self.views.values():
                shards.update(v.available_shards())
        return sorted(shards)

    def max_row_id(self) -> int:
        v = self.standard_view()
        return v.max_row_id() if v else 0

    # -- bit writes (set / time / mutex / bool) -----------------------------

    def set_bit(self, row_id: int, col: int, timestamp: datetime | None = None) -> bool:
        return self.import_bits(np.array([row_id], np.uint64),
                                np.array([col], np.uint64),
                                [timestamp] if timestamp else None) > 0

    def clear_bit(self, row_id: int, col: int) -> bool:
        if self.options.type in BSI_TYPES:
            raise ValueError(f"field {self.name}: Clear on BSI field")
        from pilosa_tpu.engine.words import SHARD_WIDTH
        shard, off = col // SHARD_WIDTH, col % SHARD_WIDTH
        changed = 0
        with self._lock:
            for v in self.views.values():
                frag = v.fragment(shard)
                if frag is not None:
                    changed += frag.clear_bits(np.array([row_id], np.uint64),
                                               np.array([off], np.uint64))
        return changed > 0

    def import_bits(self, row_ids: np.ndarray, cols: np.ndarray,
                    timestamps: list[datetime | None] | None = None,
                    sync_batch=None) -> int:
        """Bulk (row, col[, ts]) writes routed to standard + time views
        (reference: ``field.Import`` → view fan-out, SURVEY.md §4.5).
        ``sync_batch`` (an :class:`~pilosa_tpu.store.oplog.SyncBatch`)
        coalesces durable op-log fsyncs to one per touched fragment at
        the batch boundary (the caller flushes)."""
        from pilosa_tpu.engine.words import SHARD_WIDTH
        opts = self.options
        if opts.type in BSI_TYPES:
            raise ValueError(f"field {self.name}: bit import on BSI field")
        row_ids = np.asarray(row_ids, np.uint64)
        cols = np.asarray(cols, np.uint64)
        if len(row_ids) != len(cols):
            raise ValueError(
                f"import_bits: {len(row_ids)} rows vs {len(cols)} columns")
        if opts.type == TYPE_BOOL and len(row_ids) and int(row_ids.max()) > 1:
            raise ValueError("bool field rows must be 0 or 1")
        shards = cols // np.uint64(SHARD_WIDTH)
        offs = cols % np.uint64(SHARD_WIDTH)
        # one sort + boundary slices, not a boolean mask per shard (an
        # O(batch × n_shards) rescan that dominated the 954-shard
        # spread — BASELINE.md r4 ingest profile)
        order = np.argsort(shards, kind="stable")
        shards_s, rows_s, offs_s = shards[order], row_ids[order], offs[order]
        uniq = np.unique(shards_s)
        bounds = np.searchsorted(shards_s, uniq)
        bounds = np.append(bounds, len(shards_s))
        changed = 0
        for i, shard in enumerate(uniq):
            lo, hi = bounds[i], bounds[i + 1]
            r, c = rows_s[lo:hi], offs_s[lo:hi]
            if opts.type in (TYPE_MUTEX, TYPE_BOOL):
                changed += self._set_mutex(int(shard), r, c)
            else:
                frag = self.standard_view(create=True).fragment(int(shard), create=True)
                changed += frag.set_bits(r, c, sync_batch=sync_batch)
            if opts.type == TYPE_TIME and timestamps is not None and opts.time_quantum:
                idx = order[lo:hi]
                for j, (rr, cc) in enumerate(zip(r, c)):
                    ts = timestamps[idx[j]] if idx[j] < len(timestamps) else None
                    if ts is None:
                        continue
                    for vname in timeq.views_by_time(VIEW_STANDARD, ts, opts.time_quantum):
                        tf = self.view(vname, create=True).fragment(int(shard), create=True)
                        tf.set_bits(np.array([rr], np.uint64),
                                    np.array([cc], np.uint64),
                                    sync_batch=sync_batch)
        return changed

    def clear_import(self, row_ids: np.ndarray, cols: np.ndarray,
                     sync_batch=None) -> int:
        """Bulk clear of (row, col) pairs — the ``clear=true`` half of
        the import endpoint, batched per fragment (one op-log record +
        one deferred fsync per touched fragment instead of a
        ``clear_bit`` round trip per pair).  Clears apply to EVERY view
        (a time-view copy left set would resurface in range queries),
        like :meth:`clear_bit`."""
        from pilosa_tpu.engine.words import SHARD_WIDTH
        if self.options.type in BSI_TYPES:
            raise ValueError(f"field {self.name}: bit clear on BSI field")
        row_ids = np.asarray(row_ids, np.uint64)
        cols = np.asarray(cols, np.uint64)
        if len(row_ids) != len(cols):
            raise ValueError(
                f"clear_import: {len(row_ids)} rows vs {len(cols)} columns")
        shards = cols // np.uint64(SHARD_WIDTH)
        offs = cols % np.uint64(SHARD_WIDTH)
        order = np.argsort(shards, kind="stable")
        shards_s, rows_s, offs_s = shards[order], row_ids[order], offs[order]
        uniq = np.unique(shards_s)
        bounds = np.append(np.searchsorted(shards_s, uniq), len(shards_s))
        changed = 0
        with self._lock:
            views = list(self.views.values())
        for i, shard in enumerate(uniq):
            lo, hi = bounds[i], bounds[i + 1]
            for v in views:
                frag = v.fragment(int(shard))
                if frag is not None:
                    changed_v = frag.clear_bits(rows_s[lo:hi],
                                                offs_s[lo:hi],
                                                sync_batch=sync_batch)
                    if v.name == VIEW_STANDARD:
                        changed += changed_v
        return changed

    def _set_mutex(self, shard: int, row_ids: np.ndarray, cols: np.ndarray) -> int:
        """Mutex semantics: setting (row, col) clears every other row of
        col (reference: mutex enforcement in ``fragment.setMutex``).
        Vectorized: one clear per existing row, one set per target row."""
        frag = self.standard_view(create=True).fragment(shard, create=True)
        # last write per column wins within the batch
        _, last_idx = np.unique(cols[::-1], return_index=True)
        keep = len(cols) - 1 - last_idx
        row_ids, cols = row_ids[keep].astype(np.uint64), cols[keep].astype(np.uint32)
        changed = 0
        for existing in frag.row_ids():
            # clear batch columns set in `existing` unless being set there
            to_clear = cols[np.isin(cols, frag.row(existing).columns())
                            & (row_ids != existing)]
            if len(to_clear):
                changed += frag.clear_bits(
                    np.full(len(to_clear), existing, np.uint64), to_clear)
        changed += frag.set_bits(row_ids, cols)
        return changed

    # -- BSI value writes ---------------------------------------------------

    def to_stored(self, value) -> int:
        """API value -> stored integer (decimal scaling / timestamp epoch)."""
        opts = self.options
        if opts.type == TYPE_DECIMAL:
            return int(round(float(value) * 10**opts.scale))
        if opts.type == TYPE_TIMESTAMP:
            if isinstance(value, str):
                value = timeq.parse_pql_time(value).replace(tzinfo=timezone.utc)
            if isinstance(value, datetime):
                epoch = (datetime.fromisoformat(opts.epoch)
                         if opts.epoch else _UNIX_EPOCH)
                if value.tzinfo is None:
                    value = value.replace(tzinfo=timezone.utc)
                return int((value - epoch).total_seconds() * _TS_UNITS[opts.time_unit])
            return int(value)
        return int(value)

    def _to_stored_batch(self, values) -> np.ndarray:
        """Vectorized :meth:`to_stored` for bulk imports (a python-level
        per-value loop dominates ingest otherwise)."""
        opts = self.options
        if opts.type == TYPE_INT:
            return np.asarray(values, dtype=np.int64)
        if opts.type == TYPE_DECIMAL and not any(
                isinstance(v, str) for v in values[:1]):
            return np.round(np.asarray(values, dtype=np.float64)
                            * 10**opts.scale).astype(np.int64)
        return np.array([self.to_stored(v) for v in values], dtype=np.int64)

    def from_stored(self, stored: int):
        opts = self.options
        if opts.type == TYPE_DECIMAL:
            return stored / 10**opts.scale
        return stored

    def set_value(self, col: int, value) -> bool:
        return self.import_values(np.array([col], np.uint64), [value]) > 0

    def import_values(self, cols: np.ndarray, values) -> int:
        """Bulk BSI writes: per bit-plane set/clear so overwrites need no
        read-back (reference: ``field.importValue`` → ``fragment.importValue``)."""
        opts = self.options
        if opts.type not in BSI_TYPES:
            raise ValueError(f"field {self.name}: value import on non-BSI field")
        from pilosa_tpu.engine.words import SHARD_WIDTH
        cols = np.asarray(cols, np.uint64)
        stored = self._to_stored_batch(values)
        if opts.min is not None and (stored < self.to_stored(opts.min)).any():
            raise ValueError(f"value below field min {opts.min}")
        if opts.max is not None and (stored > self.to_stored(opts.max)).any():
            raise ValueError(f"value above field max {opts.max}")
        offs = stored - np.int64(opts.base)
        mag = np.abs(offs).astype(np.uint64)
        need = (max(1, int(mag.max()).bit_length()) if len(mag) else 1)
        if need > opts.bit_depth:
            opts.bit_depth = need
            self.save_meta()
        depth = opts.bit_depth

        shards = cols // np.uint64(SHARD_WIDTH)
        col_offs = cols % np.uint64(SHARD_WIDTH)
        changed = 0
        for shard in np.unique(shards):
            m = shards == shard
            c, o, g = col_offs[m], offs[m], mag[m]
            frag = self.bsi_view(create=True).fragment(int(shard), create=True)
            # last write per column wins within the batch
            _, last = np.unique(c[::-1], return_index=True)
            keep = len(c) - 1 - last
            c, o, g = c[keep], o[keep], g[keep]
            # pre-grouped per-plane batches: ONE set op + ONE clear op
            # per shard (2 op-log records instead of 2*depth+3) with no
            # global position re-sort — the bulk-ingest hot path
            neg = o < 0
            set_groups = [(EXISTS_ROW, c), (SIGN_ROW, c[neg])]
            clr_groups = [(SIGN_ROW, c[~neg])]
            for b in range(depth):
                hit = (g >> np.uint64(b)) & np.uint64(1) != 0
                set_groups.append((OFFSET_ROW + b, c[hit]))
                clr_groups.append((OFFSET_ROW + b, c[~hit]))
            changed += frag.set_bits_grouped(set_groups)
            changed += frag.clear_bits_grouped(clr_groups)
        return changed

    def value(self, col: int) -> tuple[int, bool]:
        """Read one column's BSI value: (value, exists)."""
        from pilosa_tpu.engine.words import SHARD_WIDTH
        opts = self.options
        v = self.bsi_view()
        if v is None:
            return 0, False
        frag = v.fragment(col // SHARD_WIDTH)
        if frag is None:
            return 0, False
        off = col % SHARD_WIDTH
        if not frag.row(EXISTS_ROW).contains(off):
            return 0, False
        mag = 0
        for b in range(opts.bit_depth):
            if frag.row(OFFSET_ROW + b).contains(off):
                mag |= 1 << b
        if frag.row(SIGN_ROW).contains(off):
            mag = -mag
        return self.from_stored(mag + opts.base), True

    def clear_value(self, col: int) -> bool:
        """Remove a column's BSI value entirely."""
        from pilosa_tpu.engine.words import SHARD_WIDTH
        v = self.bsi_view()
        if v is None:
            return False
        frag = v.fragment(col // SHARD_WIDTH)
        if frag is None:
            return False
        off = col % SHARD_WIDTH
        rows = np.arange(OFFSET_ROW + self.options.bit_depth, dtype=np.uint64)
        return frag.clear_bits(rows, np.full(len(rows), off, np.uint64)) > 0
