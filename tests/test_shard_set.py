"""The index keeps its shard set (PR 29): ``Index.available_shards``
returns one kept tuple until the shard-set epoch moves, and whatever
can change the answer bumps the epoch before the write that caused it
is acknowledged.  The kept set must equal the walk over every fragment
after ANY mutation (a property test over random mutation sequences),
a query must count a bit the moment its ``Set`` into a never-seen shard
returns (plan-cached and parsed paths, with and without a placement's
padding), a concurrent reader must never see a set older than a
completed write, and ``shard_set_rebuilds_total`` must say how often
the walk ran."""

import os
import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.planes import PAD_SHARD
from pilosa_tpu.obs import Stats
from pilosa_tpu.parallel import MeshPlacement
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.store import FieldOptions, Holder

SW = SHARD_WIDTH


def reference_walk(index) -> tuple:
    """The shard set from the fragments' row tiers themselves, each
    read under its fragment's lock — independent of ``Fragment.present``
    and of everything the index keeps."""
    out = set()
    for f in list(index.fields.values()):
        for v in list(f.views.values()):
            for shard, frag in list(v.fragments.items()):
                with frag.lock:
                    if (frag.rows or frag._snap_pending
                            or len(frag._pend_pos)):
                        out.add(shard)
    return tuple(sorted(out))


def check(index) -> tuple:
    want = reference_walk(index)
    got = index.available_shards()
    assert got == want
    assert index.walk_shards() == want
    # nothing was written in between: the kept object, not a new walk
    assert index.available_shards() is got
    return got


def _counter(stats, name) -> float:
    return sum(stats.snapshot()["counters"].get(name, {}).values())


# -- (a) the kept set equals the walk after any mutation ---------------------


N_SHARDS = 9
STEPS = 140


class _Driver:
    """One random mutation per ``step``; few rows and few columns per
    shard, so clears empty whole fragments again and again."""

    def __init__(self, path, rng, track_existence):
        self.path, self.rng = path, rng
        self.holder = Holder(path).open()
        idx = self.holder.create_index("i", track_existence=track_existence)
        idx.create_field("f")
        idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
        idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        self.n_extra = 0
        self._fresh_executor()

    def _fresh_executor(self):
        self.ex = Executor(self.holder)
        self.idx = self.holder.index("i")

    def _col(self) -> int:
        return (int(self.rng.integers(N_SHARDS)) * SW
                + int(self.rng.integers(3)))

    def _set_fields(self):
        return [n for n, f in self.idx.fields.items()
                if f.options.type == "set" and not n.startswith("_")]

    def _pql(self, q):
        return self.ex.execute("i", q)

    # every op returns a word for the failure message

    def op_set(self):
        f = self.rng.choice(self._set_fields())
        self._pql(f"Set({self._col()}, {f}={int(self.rng.integers(3))})")
        return f"set {f}"

    def op_clear(self):
        f = self.rng.choice(self._set_fields())
        self._pql(f"Clear({self._col()}, {f}={int(self.rng.integers(3))})")
        return f"clear {f}"

    def op_clearrow(self):
        f = self.rng.choice(self._set_fields())
        self._pql(f"ClearRow({f}={int(self.rng.integers(3))})")
        return f"clearrow {f}"

    def op_int(self):
        self._pql(f"Set({self._col()}, v={int(self.rng.integers(1000))})")
        return "int set"

    def op_int_clear(self):
        self._pql(f"Clear({self._col()}, v=0)")
        return "int clear"

    def op_time(self):
        day = int(self.rng.integers(1, 28))
        self._pql(f"Set({self._col()}, t=1, 2017-0{1 + day % 9}-"
                  f"{day:02d}T00:00)")
        return "time set"

    def op_import(self):
        n = int(self.rng.integers(1, 6))
        cols = np.array([self._col() for _ in range(n)], np.uint64)
        rows = self.rng.integers(0, 3, size=n).astype(np.uint64)
        f = self.idx.field(str(self.rng.choice(self._set_fields())))
        if self.rng.integers(2):
            f.import_bits(rows, cols)
            return "import_bits"
        f.clear_import(rows, cols)
        return "clear_import"

    def op_create_field(self):
        self.n_extra += 1
        self.idx.create_field(f"x{self.n_extra}")
        return "create_field"

    def op_delete_field(self):
        extra = [n for n in self._set_fields() if n.startswith("x")]
        if not extra:
            return self.op_create_field()
        self.idx.delete_field(str(self.rng.choice(extra)))
        return "delete_field"

    def _some_fragment(self):
        frags = [(v, s, fr) for f in self.idx.fields.values()
                 for v in f.views.values()
                 for s, fr in v.fragments.items()]
        if not frags:
            return None
        return frags[int(self.rng.integers(len(frags)))]

    def op_remove_fragment(self):
        hit = self._some_fragment()
        if hit is None:
            return self.op_set()
        view, shard, frag = hit
        with view._lock:
            assert view.remove_fragment(shard) is frag
            frag.close()
            for suffix in ("", ".oplog"):
                try:
                    os.remove(frag.path + suffix)
                except OSError:
                    pass
        return "remove_fragment"

    def op_snapshot(self):
        hit = self._some_fragment()
        if hit is None:
            return self.op_set()
        hit[2].snapshot()
        return "snapshot"

    def op_reopen(self):
        self.holder.close()
        self.holder = Holder(self.path).open()
        self._fresh_executor()
        return "reopen"

    OPS = (["op_set"] * 6 + ["op_clear"] * 5 + ["op_clearrow"] * 3
           + ["op_int"] * 2 + ["op_int_clear"] * 2 + ["op_time"] * 2
           + ["op_import"] * 4 + ["op_create_field", "op_delete_field",
                                  "op_remove_fragment", "op_remove_fragment",
                                  "op_snapshot", "op_reopen"])

    def step(self) -> str:
        return getattr(self, str(self.rng.choice(self.OPS)))()


@pytest.mark.parametrize("track_existence", [True, False],
                         ids=["exists", "no-exists"])
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_kept_set_equals_the_walk_after_every_mutation(
        tmp_path, seed, track_existence):
    d = _Driver(str(tmp_path), np.random.default_rng(seed), track_existence)
    try:
        seen_sizes = set()
        check(d.idx)
        for n in range(STEPS):
            what = d.step()
            try:
                seen_sizes.add(len(check(d.idx)))
            except AssertionError as e:
                raise AssertionError(f"step {n} ({what}): {e}") from e
        # the sequence really moved the set, both ways
        assert len(seen_sizes) > 2
    finally:
        d.holder.close()


def test_presence_flag_has_no_transient_inside_a_flush(tmp_path):
    """``Fragment.present`` is kept, not computed: the moment inside a
    pending-tier flush or a compaction in which every tier is empty is
    never visible to a lock-free reader, so a walk that races one
    cannot keep a set that misses the shard."""
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i", track_existence=False)
        f = idx.create_field("f")
        f.import_bits(np.array([1], np.uint64), np.array([5], np.uint64))
        frag = f.standard_view().fragment(0)
        assert frag.present and len(frag._pend_pos) == 1
        seen = []
        real = frag._ensure_row

        def watching(row_id):          # runs mid-flush, pending emptied
            seen.append((frag.present, bool(frag.rows),
                         len(frag._pend_pos)))
            return real(row_id)

        frag._ensure_row = watching
        frag.clear_bit(1, 6)           # a classic-path op: flushes first
        assert seen and seen[0] == (True, False, 0)
        assert idx.available_shards() == (0,)
    finally:
        holder.close()


# -- (b) read-your-writes through Executor.execute ---------------------------


def _placement(where):
    return MeshPlacement(jax.devices()[:4]) if where == "mesh4" else None


@pytest.mark.parametrize("path", ["planned", "parsed"])
@pytest.mark.parametrize("where", ["single", "mesh4"])
def test_count_sees_a_set_into_a_never_seen_shard(tmp_path, where, path):
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        idx.create_field("f")
        stats = Stats()
        ex = Executor(holder, placement=_placement(where), stats=stats)
        pql = "Count(Row(f=1))"
        query = pql if path == "planned" else parse(pql)

        def count():
            return ex.execute("i", query)[0]

        assert count() == 0                      # an empty index serves
        for c in (3, SW + 3, 2 * SW + 3):
            ex.execute("i", f"Set({c}, f=1)")
        n = 3
        assert [count(), count(), count()] == [n, n, n]
        hits = _counter(stats, "plan_cache_hits")
        for shard in (7, 9, 4, 20, 3):
            assert shard not in idx.available_shards()
            ex.execute("i", f"Set({shard * SW + 11}, f=1)")
            n += 1
            assert count() == n, f"shard {shard} not counted"
            served = ex._shards_for(idx, None, None)
            assert shard in served
            # one object per epoch, padded once: not per call
            assert ex._shards_for(idx, None, None) is served
            if where == "mesh4":
                assert len(served) % 4 == 0
                assert served[:len(idx.available_shards())] \
                    == idx.available_shards()
                assert set(served[len(idx.available_shards()):]) \
                    <= {PAD_SHARD}
            else:
                assert served is idx.available_shards()
            assert count() == n
        if path == "planned":
            assert _counter(stats, "plan_cache_hits") > hits
        # and the other way: the last bit of a shard cleared, then a
        # whole row — exactly as the walk reflects it
        ex.execute("i", f"Clear({20 * SW + 11}, f=1)")
        assert count() == n - 1
        assert idx.available_shards() == reference_walk(idx)
        ex.execute("i", "ClearRow(f=1)")
        assert count() == 0
        assert idx.available_shards() == reference_walk(idx)
        # explicit shards bypass the kept set as they bypassed the walk
        ex.execute("i", f"Set({5 * SW}, f=1) Set({6 * SW}, f=1)")
        assert ex.execute("i", query, shards=[5]) == [1]
        assert ex.execute(
            "i", "Options(Count(Row(f=1)), shards=[6, 8])") == [1]
        assert count() == 2
    finally:
        holder.close()


# -- (c) a reader never sees a set older than a completed write --------------


@pytest.mark.parametrize("through", ["index", "executor"])
def test_reader_never_sees_a_set_older_than_a_completed_write(
        tmp_path, through):
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        f = idx.create_field("f")
        ex = Executor(holder)
        n_shards = 200 if through == "index" else 6
        acked = [0]          # shards 1..acked[0] are acknowledged
        failures = []
        done = threading.Event()

        def writer():
            try:
                for s in range(1, n_shards + 1):
                    if through == "index":
                        f.set_bit(1, s * SW + 1)
                    else:
                        ex.execute("i", f"Set({s * SW + 1}, f=1)")
                    acked[0] = s
            except Exception as e:  # noqa: BLE001
                failures.append(repr(e))
            finally:
                done.set()

        def reader():
            try:
                while True:
                    last = done.is_set()
                    a = acked[0]             # BEFORE the read begins
                    if through == "index":
                        got = idx.available_shards()
                        missing = set(range(1, a + 1)) - set(got)
                        if missing:
                            failures.append(
                                f"acked {a}, missing {sorted(missing)}")
                            return
                    else:
                        c = ex.execute("i", "Count(Row(f=1))")[0]
                        if c < a:
                            failures.append(f"acked {a}, counted {c}")
                            return
                    if last:
                        return
            except Exception as e:  # noqa: BLE001
                failures.append(repr(e))

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert failures == []
        assert idx.available_shards() == tuple(range(1, n_shards + 1))
        assert idx.available_shards() == reference_walk(idx)
    finally:
        holder.close()


def test_a_write_that_lands_during_the_walk_leaves_it_stale_marked(tmp_path):
    """The epoch is read BEFORE the walk: a shard that appears while
    the walk runs (after its fragment was passed) is missing from what
    that walk stores, so the stored set must not pass for fresh."""
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        f = idx.create_field("f")
        f.set_bit(1, 1)
        real = idx.walk_shards

        def walk_then_write():
            out = real()
            f.set_bit(1, 3 * SW + 1)   # acknowledged before the store
            return out

        idx.walk_shards = walk_then_write
        assert idx.available_shards() == (0,)      # the racing walk's own
        idx.walk_shards = real
        assert idx.available_shards() == (0, 3)    # the next read's
    finally:
        holder.close()


# -- (d) the counter says how often the walk ran -----------------------------


@pytest.mark.parametrize("path", ["planned", "parsed"])
def test_rebuild_counter_moves_once_per_new_shard(tmp_path, path):
    holder = Holder(str(tmp_path))
    stats = Stats()
    # the store counts through the registry the server wires
    holder.storage_health.configure(stats=stats)
    holder.open()
    try:
        idx = holder.create_index("i")
        idx.create_field("f")
        ex = Executor(holder, stats=stats)
        pql = "Count(Row(f=1)) Count(Row(f=2))"
        query = pql if path == "planned" else parse(pql)
        for c in range(40):
            ex.execute("i", f"Set({(c % 4) * SW + c}, f={c % 3})")
        want = ex.execute("i", query)
        before = _counter(stats, "shard_set_rebuilds_total")
        assert before >= 1
        for _ in range(100):
            assert ex.execute("i", query) == want
        assert _counter(stats, "shard_set_rebuilds_total") == before
        # a write into a shard that is there changes no presence
        ex.execute("i", f"Set({2 * SW + 77}, f=1)")
        assert ex.execute("i", query) == [want[0] + 1, want[1]]
        assert _counter(stats, "shard_set_rebuilds_total") == before
        # a new shard: one walk, by the next read, and no more after it
        ex.execute("i", f"Set({11 * SW + 1}, f=2)")
        for _ in range(20):
            assert ex.execute("i", query) == [want[0] + 1, want[1] + 1]
        assert _counter(stats, "shard_set_rebuilds_total") == before + 1
        # the other callers of the shard set share the kept tuple
        assert idx.available_shards() is idx.available_shards()
        assert _counter(stats, "shard_set_rebuilds_total") == before + 1
    finally:
        holder.close()
