"""View: groups the fragments of one flavor of one field.

Reference: ``view.go`` (SURVEY.md §3.1) — a field has a ``standard`` view
plus time-quantum views (``standard_2017``, …); an int (BSI) field keeps
its bit-planes in a ``bsi_<field>`` view.  Fragments are created on
demand per shard.
"""

from __future__ import annotations

import os
import threading

from pilosa_tpu.store.fragment import Fragment, no_index

VIEW_STANDARD = "standard"
VIEW_BSI_PREFIX = "bsi_"


class View:
    def __init__(self, path: str, name: str, *, fsync: bool = False,
                 snapshot_submit=None, health=None, shards_changed=None):
        self.path = path  # <field>/views/<name>
        self.name = name
        self.fsync = fsync
        self.snapshot_submit = snapshot_submit
        self.health = health  # disk-health governor (r19), holder's
        # the index's shard-set epoch bump: a fragment calls it when
        # its presence flips, the view after a fragment enters or
        # leaves ``fragments``
        self.shards_changed = shards_changed or no_index
        self.fragments: dict[int, Fragment] = {}
        self._lock = threading.RLock()

    def open(self) -> "View":
        frag_dir = os.path.join(self.path, "fragments")
        if os.path.isdir(frag_dir):
            # a fragment exists if EITHER its snapshot or its op-log does
            # (a crash before the first snapshot leaves only the op-log —
            # it must still be discovered or replay never runs)
            shards: set[int] = set()
            for entry in os.listdir(frag_dir):
                if entry.isdigit():
                    shards.add(int(entry))
                elif entry.endswith(".oplog") and entry[:-6].isdigit():
                    shards.add(int(entry[:-6]))
            for shard in shards:
                frag = Fragment(os.path.join(frag_dir, str(shard)), shard,
                                fsync=self.fsync,
                                snapshot_submit=self.snapshot_submit,
                                health=self.health,
                                shards_changed=self.shards_changed)
                self.fragments[shard] = frag.open()
        self.shards_changed()
        return self

    def fragment(self, shard: int, create: bool = False) -> Fragment | None:
        with self._lock:
            frag = self.fragments.get(shard)
            if frag is None and create:
                path = os.path.join(self.path, "fragments", str(shard))
                os.makedirs(os.path.dirname(path), exist_ok=True)
                frag = Fragment(path, shard, fsync=self.fsync,
                                snapshot_submit=self.snapshot_submit,
                                health=self.health,
                                shards_changed=self.shards_changed).open()
                self.fragments[shard] = frag
                # AFTER the insert: open() may have replayed bits in
                # (and bumped) while no walk could see the fragment yet
                self.shards_changed()
            return frag

    def remove_fragment(self, shard: int) -> Fragment | None:
        """Take a fragment out of the view (the caller closes it and
        unlinks its files).  The one way out of ``fragments``: a bare
        ``fragments.pop`` would leave the index's kept shard set
        counting the shard."""
        with self._lock:
            frag = self.fragments.pop(shard, None)
            if frag is not None:
                self.shards_changed()
            return frag

    def available_shards(self) -> list[int]:
        with self._lock:
            return sorted(s for s, f in self.fragments.items() if f.present)

    def generations(self, shards) -> tuple:
        """Fragment generation per shard (-1 = absent), ONE lock
        acquisition for the whole list — the device plane cache
        revalidates on every query, so per-shard ``fragment()`` calls
        (954 lock round trips on a 1B-column index) are serving-path
        poison."""
        with self._lock:
            frags = self.fragments
            return tuple(
                frags[s].generation if s in frags else -1 for s in shards)

    def generations_fast(self, shards) -> tuple:
        """Lock-free :meth:`generations`: dict lookups and int reads
        are GIL-atomic, and the view lock never serialized against
        fragment mutations anyway (those bump ``Fragment.generation``
        under the FRAGMENT lock) — so the freshness semantics are
        identical while the serving hot path stops taking the view
        lock per plane revalidation.  A torn read across a concurrent
        fragment creation only yields a conservative mismatch (the
        caller rebuilds), never a stale hit."""
        frags = self.fragments
        out = []
        for s in shards:
            # .get, not membership+subscript: a fragment popped between
            # the two (empty-orphan deletion) must read as absent, not
            # raise on the serving hot path
            f = frags.get(s)
            out.append(f.generation if f is not None else -1)
        return tuple(out)

    def max_row_id(self) -> int:
        with self._lock:
            return max((f.max_row_id() for f in self.fragments.values()),
                       default=0)

    def close(self) -> None:
        with self._lock:
            for frag in self.fragments.values():
                frag.close()
