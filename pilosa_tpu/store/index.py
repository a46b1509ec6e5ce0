"""Index: a collection of fields over one column space.

Reference: ``index.go`` (SURVEY.md §3.1) — per-index options ``keys`` and
``trackExistence``; when existence is tracked, an internal ``_exists``
field (one row, row 0) records which columns exist, enabling ``Not`` and
``All`` (``executor.go#executeNot``).

The shard set — which shards hold any row of any field — is KEPT, not
walked per query: ``available_shards`` returns one immutable sorted
tuple until the shard-set epoch moves.  Whatever can change the answer
bumps the epoch AFTER the change is visible to a walk and BEFORE the
write that caused it is acknowledged: a fragment whose ``present``
flips either way (``Fragment._sync_presence``), a fragment entering or
leaving a view (``View.fragment(create=True)``, ``View.open``,
``View.remove_fragment``), a view entering a field
(``Field.view(create=True)``), ``create_field`` / ``delete_field``,
``open``.  A new mutation path follows the same rule or the served
reads go stale.
"""

from __future__ import annotations

import json
import os
import threading
from datetime import datetime

import numpy as np

from pilosa_tpu.store.field import Field, FieldOptions

EXISTENCE_FIELD = "_exists"


class Index:
    def __init__(self, path: str, name: str, *, keys: bool = False,
                 track_existence: bool = True, fsync: bool = False,
                 created_at: float = 0.0, snapshot_submit=None,
                 health=None):
        self.path = path
        self.name = name
        self.keys = keys
        self.track_existence = track_existence
        self.created_at = created_at
        self.fsync = fsync
        self.snapshot_submit = snapshot_submit
        self.health = health
        self.fields: dict[str, Field] = {}
        self._column_attrs = None
        self._lock = threading.RLock()
        # the kept shard set: (epoch it was walked at, sorted tuple).
        # The epoch lock is a leaf — bumps arrive holding fragment,
        # view or field locks and take nothing else under it
        self._shard_epoch = 0
        self._shard_epoch_lock = threading.Lock()
        self._shard_set: tuple[int, tuple[int, ...]] = (-1, ())

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "Index":
        meta = os.path.join(self.path, ".meta")
        if os.path.exists(meta):
            with open(meta) as f:
                opts = json.load(f)
            self.keys = opts.get("keys", False)
            self.track_existence = opts.get("track_existence", True)
            self.created_at = opts.get("created_at", 0.0)
        for entry in sorted(os.listdir(self.path)) if os.path.isdir(self.path) else []:
            fpath = os.path.join(self.path, entry)
            if os.path.isdir(fpath) and not entry.startswith("."):
                self.fields[entry] = Field(
                    fpath, self.name, entry, fsync=self.fsync,
                    snapshot_submit=self.snapshot_submit,
                    health=self.health,
                    shards_changed=self.shards_changed).open()
        if self.track_existence and EXISTENCE_FIELD not in self.fields:
            self._create_existence()
        self.shards_changed()
        return self

    def save_meta(self) -> None:
        os.makedirs(self.path, exist_ok=True)
        tmp = os.path.join(self.path, ".meta.tmp")
        with open(tmp, "w") as f:
            json.dump({"keys": self.keys,
                       "track_existence": self.track_existence,
                       "created_at": self.created_at}, f)
        os.replace(tmp, os.path.join(self.path, ".meta"))

    def close(self) -> None:
        for f in self.fields.values():
            f.close()
        if self._column_attrs is not None:
            self._column_attrs.close()
            self._column_attrs = None

    # -- fields -------------------------------------------------------------

    def create_field(self, name: str, options: FieldOptions | None = None) -> Field:
        import time
        with self._lock:
            if name in self.fields:
                raise ValueError(f"field {name!r} already exists")
            options = options or FieldOptions()
            if not options.created_at:
                options.created_at = time.time()
            f = Field(os.path.join(self.path, name), self.name, name,
                      options, fsync=self.fsync,
                      snapshot_submit=self.snapshot_submit,
                      health=self.health,
                      shards_changed=self.shards_changed)
            os.makedirs(f.path, exist_ok=True)
            f.save_meta()
            self.fields[name] = f
            self.shards_changed()
            return f

    def ensure_field(self, name: str, options: FieldOptions | None = None) -> Field:
        with self._lock:
            return self.fields.get(name) or self.create_field(name, options)

    def field(self, name: str) -> Field | None:
        return self.fields.get(name)

    def delete_field(self, name: str) -> None:
        import shutil
        with self._lock:
            f = self.fields.pop(name, None)
            if f is None:
                raise KeyError(name)
            self.shards_changed()
            f.close()
            shutil.rmtree(f.path, ignore_errors=True)

    def _create_existence(self) -> Field:
        return self.create_field(EXISTENCE_FIELD, FieldOptions(type="set"))

    @property
    def existence_field(self) -> Field | None:
        return self.fields.get(EXISTENCE_FIELD)

    @property
    def column_attrs(self):
        """Column attribute store (reference: index-level AttrStore,
        ``index.go``/``attrstore.go``), created on first use."""
        with self._lock:
            if self._column_attrs is None:
                from pilosa_tpu.store.attrs import AttrStore
                self._column_attrs = AttrStore(
                    os.path.join(self.path, "_attrs.db"))
            return self._column_attrs

    # -- column tracking ----------------------------------------------------

    def note_columns(self, cols: np.ndarray) -> None:
        """Record columns in the existence field (row 0) — called by every
        write path when ``trackExistence`` (reference: ``index.go``)."""
        ef = self.existence_field
        if ef is not None and len(cols):
            ef.import_bits(np.zeros(len(cols), np.uint64),
                           np.asarray(cols, np.uint64))

    def shards_changed(self) -> None:
        """Bump the shard-set epoch: the next ``available_shards``
        walks again.  Handed down to every field, view and fragment
        at construction, like ``snapshot_submit`` and ``health``."""
        with self._shard_epoch_lock:
            self._shard_epoch += 1

    def available_shards(self) -> tuple[int, ...]:
        """Shards in which any field holds a row, sorted — the SAME
        tuple object until the epoch moves, so the executor's contexts,
        plan entries and plane-cache keys share it."""
        epoch, kept = self._shard_set
        if epoch == self._shard_epoch:
            return kept
        # the epoch is read BEFORE the walk: a bump that lands while
        # the walk runs leaves what is stored stale-marked, never
        # fresh-marked
        epoch = self._shard_epoch
        shards = self.walk_shards()
        if shards == kept:
            shards = kept  # most bumps change nothing: keep the object
        if self.health is not None:
            self.health.count("shard_set_rebuilds_total")
        self._shard_set = (epoch, shards)
        return shards

    def walk_shards(self) -> tuple[int, ...]:
        """The shard set asked of every fragment of every view of
        every field: the one slow path, taken once per epoch."""
        shards: set[int] = set()
        for f in list(self.fields.values()):
            shards.update(f.available_shards())
        return tuple(sorted(shards))

    # -- write facade (used by API/executor) --------------------------------

    def set_bit(self, field: str, row_id: int, col: int,
                timestamp: datetime | None = None) -> bool:
        f = self.fields.get(field)
        if f is None:
            raise KeyError(f"field {field!r} not found")
        changed = f.set_bit(row_id, col, timestamp)
        self.note_columns(np.array([col], np.uint64))
        return changed

    def set_value(self, field: str, col: int, value) -> bool:
        f = self.fields.get(field)
        if f is None:
            raise KeyError(f"field {field!r} not found")
        changed = f.set_value(col, value)
        self.note_columns(np.array([col], np.uint64))
        return changed
