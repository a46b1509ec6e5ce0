"""A view's live row set is memoised against its fragment generations
(``PlaneCache.live_rows``): ``Rows``, ``UnionRows`` and ``GroupBy``
answer from the memo while the data stands still, and every write,
new shard, snapshot, reopen and quarantine is seen on the next
request.  Each answer is held to a host model of the data and to an
un-memoised walk of the fragments."""

import itertools
import json
import random
import sys
import threading
import urllib.request
from datetime import datetime, timedelta

import numpy as np
import pytest

from pilosa_tpu.api import API, Server
from pilosa_tpu.engine.words import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs.metrics import Stats
from pilosa_tpu.store import FieldOptions, Holder
from pilosa_tpu.store.fragment import Fragment

N_SHARDS = 3
T0 = datetime(2021, 3, 1)


class World:
    """One index over three shards: set fields ``f`` and ``g``, a keyed
    field ``k`` and a day-quantum time field ``t``, with the bits each
    holds kept beside it on the host (``data[field][row]``)."""

    def __init__(self, path: str):
        self.path = path
        self.data = {"f": {}, "g": {}}
        self.events = []  # (row, col, day) of field t
        self.open()
        idx = self.holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        idx.create_field("k", FieldOptions(keys=True))
        idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        rng = np.random.default_rng(40)
        for name, n_rows in (("f", 5), ("g", 3)):
            for row in range(1, n_rows + 1):
                cols = rng.choice(N_SHARDS * SHARD_WIDTH, 12, replace=False)
                self.set_bits(name, [row] * len(cols), cols.tolist())
        for c, key in enumerate(["apple", "apricot", "banana", "cherry"]):
            self.q(f'Set({c * 7}, k="{key}")')
        for row, col, day in [(1, 3, 0), (2, SHARD_WIDTH + 5, 2),
                              (3, 2 * SHARD_WIDTH + 9, 4), (4, 11, 6)]:
            self.set_time(row, col, day)

    def open(self):
        self.holder = Holder(self.path).open()
        self.ex = Executor(self.holder, stats=Stats())
        self.api = API(self.holder, self.ex)

    def reopen(self):
        self.holder.close()
        self.open()

    def q(self, pql):
        return self.ex.execute("i", pql)

    # -- writes, applied to the index and to the host model -------------

    def set_bits(self, field, rows, cols):
        self.api.import_bits("i", field, row_ids=rows, col_ids=cols)
        for r, c in zip(rows, cols):
            self.data[field].setdefault(int(r), set()).add(int(c))

    def clear_bit(self, field, row, col):
        self.q(f"Clear({col}, {field}={row})")
        self.data[field][row].discard(col)
        if not self.data[field][row]:
            del self.data[field][row]

    def clear_row(self, field, row):
        self.q(f"ClearRow({field}={row})")
        self.data[field].pop(row, None)

    def set_time(self, row, col, day):
        stamp = (T0 + timedelta(days=day)).strftime("%Y-%m-%dT%H:%M")
        self.q(f"Set({col}, t={row}, {stamp})")
        self.events.append((row, col, day))

    # -- what the answers must be ---------------------------------------

    def rows_of(self, field):
        return sorted(self.data[field])

    def walk(self, field, view="standard"):
        """The live rows by a walk of every fragment, no memo."""
        v = self.holder.index("i").field(field).views.get(view)
        out = set()
        for frag in (list(v.fragments.values()) if v is not None else []):
            out.update(frag.row_ids())
        return sorted(out)

    def groups(self, fields):
        out = []
        levels = [self.rows_of(f) for f in fields]
        for combo in itertools.product(*levels):
            cols = set.intersection(*(self.data[f][r]
                                      for f, r in zip(fields, combo)))
            if cols:
                out.append((list(combo), len(cols)))
        return out

    def check(self):
        """Every form that reads the row sets, against the model."""
        for field in ("f", "g"):
            assert self.walk(field) == self.rows_of(field)
            assert rows(self, f"Rows({field})") == self.rows_of(field)
            union = set().union(*self.data[field].values())
            assert self.q(f"Count(UnionRows(Rows({field})))") == [len(union)]
        assert groups(self, "GroupBy(Rows(f), Rows(g))") == \
            self.groups("fg")


def rows(world, pql):
    (r,) = world.q(pql)
    return [int(x) for x in r.rows]


def groups(world, pql):
    (g,) = world.q(pql)
    return [([fr.row_id for fr in gc.group], gc.count) for gc in g.groups]


def counts(world):
    st = world.ex.planes.stats()
    return st["rowSetHits"], st["rowSetMisses"]


@pytest.fixture
def world(tmp_path):
    w = World(str(tmp_path))
    yield w
    w.holder.close()


@pytest.fixture
def walks(monkeypatch):
    """Every call of ``Fragment.row_ids`` / ``row_ids_array`` from here
    on, by name."""
    seen = []
    for name in ("row_ids", "row_ids_array"):
        orig = getattr(Fragment, name)

        def counted(self, _orig=orig, _name=name):
            seen.append(_name)
            return _orig(self)
        monkeypatch.setattr(Fragment, name, counted)
    return seen


def _set(w):
    w.set_bits("f", [7], [SHARD_WIDTH + 77])


def _clear(w):
    for col in sorted(w.data["f"][2]):  # the whole of row 2, bit by bit
        w.clear_bit("f", 2, col)


def _clear_row(w):
    w.clear_row("f", 3)


def _import_bits(w):
    w.set_bits("g", [9, 9, 1], [4, 2 * SHARD_WIDTH + 4, 5])


def _new_shard(w):
    w.set_bits("f", [11], [N_SHARDS * SHARD_WIDTH + 1])


def _snapshot(w):
    # rows move to the lazily expanded tier; no generation moves
    for field in ("f", "g"):
        for frag in w.holder.index("i").field(field).views[
                "standard"].fragments.values():
            frag.snapshot()


def _reopen(w):
    w.reopen()


MUTATIONS = {"set": _set, "clear": _clear, "clear_row": _clear_row,
             "import_bits": _import_bits, "new_shard": _new_shard,
             "snapshot": _snapshot, "reopen": _reopen}


@pytest.mark.parametrize("mutation", list(MUTATIONS))
def test_memoised_answers_equal_a_fragment_walk(world, mutation):
    world.check()
    world.check()  # the second time from the memo
    MUTATIONS[mutation](world)
    world.check()
    world.check()


def test_a_lazily_opened_fragment_answers_before_and_after_it_expands(
        world, walks):
    _snapshot(world)
    world.reopen()
    world.check()  # lazily opened: every row still in the mapped file
    for row in world.rows_of("f"):  # expand every row of f
        assert world.q(f"Count(Row(f={row}))") == [len(world.data["f"][row])]
    del walks[:]
    hits, misses = counts(world)
    world.check()
    # expansion moved no generation and no live row: all from the memo
    assert "row_ids_array" not in walks
    assert counts(world)[1] == misses and counts(world)[0] > hits
    _set(world)
    world.check()


def test_a_quarantined_snapshot_drops_its_rows_on_the_next_request(world):
    _snapshot(world)
    world.reopen()
    world.check()
    frag = world.holder.index("i").field("f").views["standard"].fragments[1]
    gen = frag.generation
    with frag.lock:
        frag._mark_corrupt("snapshot", "test: crc mismatch at demotion")
    assert frag.generation > gen
    # the fragment now serves empty: shard 1's bits of f are gone
    kept = {r: {c for c in cols if c // SHARD_WIDTH != 1}
            for r, cols in world.data["f"].items()}
    world.data["f"] = {r: cols for r, cols in kept.items() if cols}
    assert rows(world, "Rows(f)") == world.walk("f") == world.rows_of("f")
    assert groups(world, "GroupBy(Rows(f), Rows(g))") == world.groups("fg")


def test_a_hit_walks_no_fragment_and_a_write_misses_once(world, walks):
    world.check()
    del walks[:]
    hits, misses = counts(world)
    assert rows(world, "Rows(f)") == world.rows_of("f")
    assert groups(world, "GroupBy(Rows(f), Rows(g))") == world.groups("fg")
    assert walks == []
    assert counts(world) == (hits + 3, misses)

    _set(world)
    del walks[:]
    hits, misses = counts(world)
    assert rows(world, "Rows(f)") == world.rows_of("f")
    assert counts(world) == (hits, misses + 1)
    n_frags = len(world.holder.index("i").field("f").views[
        "standard"].fragments)
    assert walks == ["row_ids_array"] * n_frags
    del walks[:]
    # f was walked again; g never moved
    assert groups(world, "GroupBy(Rows(f), Rows(g))") == world.groups("fg")
    assert walks == []
    assert counts(world) == (hits + 2, misses + 1)


def test_status_and_metrics_report_the_memo(tmp_path):
    holder = Holder(str(tmp_path)).open()
    api = API(holder, Executor(holder))
    srv = Server(api, host="127.0.0.1", port=0, stats=Stats())
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{srv.address[1]}"

    def status():
        pc = json.loads(urllib.request.urlopen(url + "/status").read())[
            "planeCache"]
        return pc["rowSetHits"], pc["rowSetMisses"]

    try:
        api.create_index("i")
        api.create_field("i", "f")
        api.create_field("i", "g")
        api.query("i", f"Set(1, f=2) Set({SHARD_WIDTH + 3}, g=5)")
        assert status() == (0, 0)
        api.query("i", "GroupBy(Rows(f), Rows(g))")
        assert status() == (0, 2)
        api.query("i", "GroupBy(Rows(f), Rows(g))")
        api.query("i", "Rows(g)")
        assert status() == (3, 2)
        api.query("i", "Set(2, g=6)")
        api.query("i", "GroupBy(Rows(f), Rows(g))")
        assert status() == (4, 3)
        text = urllib.request.urlopen(url + "/metrics").read().decode()
        assert "plane_cache_row_set_hits 4" in text
        assert "plane_cache_row_set_misses 3" in text
    finally:
        srv.close()
        holder.close()


def test_time_range_covers_read_each_view_from_its_own_memo(world):
    def want(start, end):
        return sorted({r for r, _, d in world.events if start <= d < end})

    def stamp(day):
        return (T0 + timedelta(days=day)).strftime("%Y-%m-%dT%H:%M")

    def got(start, end):
        return rows(world, f"Rows(t, from={stamp(start)}, to={stamp(end)})")

    ranges = [(0, 3), (2, 7), (0, 30), (5, 6)]
    for _ in range(2):
        assert [got(*r) for r in ranges] == [want(*r) for r in ranges]
    world.set_time(9, 2 * SHARD_WIDTH + 1, 2)   # inside (0, 3) and (2, 7)
    world.set_time(8, 4, 20)                    # inside (0, 30) only
    assert [got(*r) for r in ranges] == [want(*r) for r in ranges]
    assert rows(world, "Rows(t)") == sorted({r for r, _, _ in world.events})


def test_column_like_previous_and_limit_still_hold(world):
    def check():
        f = world.data["f"]
        col = sorted(f[1])[0]
        assert rows(world, f"Rows(f, column={col})") == \
            sorted(r for r, cols in f.items() if col in cols)
        assert rows(world, "Rows(f, previous=2)") == \
            [r for r in world.rows_of("f") if r > 2]
        assert rows(world, "Rows(f, limit=2)") == world.rows_of("f")[:2]
        assert rows(world, "Rows(f, previous=1, limit=2)") == \
            [r for r in world.rows_of("f") if r > 1][:2]
        (r,) = world.q('Rows(k, like="ap%")')
        assert sorted(r.keys) == sorted(
            k for k in keys if k.startswith("ap"))
        got = groups(world, "GroupBy(Rows(f, limit=2), Rows(g, previous=1))")
        assert got == [(c, n) for c, n in world.groups("fg")
                       if c[0] in world.rows_of("f")[:2] and c[1] > 1]

    keys = ["apple", "apricot", "banana", "cherry"]
    check()
    check()
    world.set_bits("f", [1, 0], [SHARD_WIDTH + 2, 6])  # a row below the rest
    world.q('Set(50, k="apex")')
    keys.append("apex")
    check()
    # the filters copy or slice the memo's array, never write into it
    ex = world.ex
    field = world.holder.index("i").field("f")
    live = ex.planes.live_rows(field, "standard",
                               tuple(world.holder.index("i")
                                     .available_shards()))
    assert not live.flags.writeable
    assert list(live) == world.rows_of("f")


def test_plane_bytes_takes_its_row_count_from_the_memo(world, walks):
    idx = world.holder.index("i")
    field, shards = idx.field("f"), tuple(idx.available_shards())
    planes = world.ex.planes
    want = len(shards) * 8 * WORDS_PER_SHARD * 4  # five rows pad to eight
    assert planes.plane_bytes(field, "standard", shards) == want
    del walks[:]
    assert planes.plane_bytes(field, "standard", shards) == want
    assert walks == []
    world.set_bits("f", [6, 7, 8, 9], [1, 2, 3, 4])  # nine rows pad to 16
    assert planes.plane_bytes(field, "standard", shards) == want * 2


def test_oom_recovery_releases_the_memo(world, walks):
    world.check()
    planes = world.ex.planes
    assert planes.stats()["rowSetHits"] > 0 and planes._row_sets
    planes.evict_unpinned()
    assert planes._row_sets == {} and planes._row_set_bytes == 0
    del walks[:]
    hits, misses = counts(world)
    assert rows(world, "Rows(f)") == world.rows_of("f")  # walked anew
    assert counts(world) == (hits, misses + 1) and walks
    world.check()


def test_reads_beside_a_writer_end_equal_to_the_final_state(world):
    """Writes only add here, so every answer read while they land lies
    between the state before and the state after."""
    first = set(world.rows_of("f"))
    rng = random.Random(40)
    plan = [(rng.randrange(1, 12),
             rng.randrange(N_SHARDS) * SHARD_WIDTH + rng.randrange(500))
            for _ in range(60)]
    seen, errors = [], []
    done = threading.Event()

    def reader():
        try:
            while not done.is_set():
                seen.append(set(rows(world, "Rows(f)")))
                groups(world, "GroupBy(Rows(f), Rows(g))")
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        for row, col in plan:
            world.set_bits("f", [row], [col])
    finally:
        done.set()
        for t in readers:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in readers)
    assert not errors
    final = set(world.rows_of("f"))
    assert seen and all(first <= s <= final for s in seen)
    world.check()
