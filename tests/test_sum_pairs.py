"""K-item Sums through ``Executor.execute`` against a set-based host
reference: one request's same-field Sums are one program that reads the
BSI plane once (``bsi.sum_pair_counts``) — a signed and an unsigned int
field (both branches of the kernel's sign test), K = 1, 2 and 10 with a
duplicate filter and an unfiltered item, the collection window's pow2
padding, a plane carrying a ``BsiOverlay`` after writes, a four-device
mesh, and the two counters of the launch."""

import threading

import jax
import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.fused import FusedCache
from pilosa_tpu.obs import Stats
from pilosa_tpu.parallel import MeshPlacement
from pilosa_tpu.store import FieldOptions, Holder

N_SHARDS = 3
N_ROWS = 10
INT_FIELDS = {"signed": (-500, 500), "unsigned": (0, 999)}


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Three shards; a set field ``f`` of ten rows over one pool of
    columns, and the int fields ``signed`` (negative values present)
    and ``unsigned`` (min 0: an empty sign row) with a value on most
    pool columns."""
    holder = Holder(str(tmp_path_factory.mktemp("sums"))).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    for name, (lo, hi) in INT_FIELDS.items():
        idx.create_field(name, FieldOptions(type="int", min=lo, max=hi))
    api = API(holder, Executor(holder, count_batch_window=0))
    rng = np.random.default_rng(38)
    pool = rng.choice(N_SHARDS * SHARD_WIDTH, 500, replace=False)
    data = {"f": {}}
    for row in range(1, N_ROWS + 1):
        cols = pool[rng.random(pool.size) < 0.3]
        data["f"][row] = set(cols.tolist())
        api.import_bits("i", "f", row_ids=[row] * len(cols),
                        col_ids=cols.tolist())
    for name, (lo, hi) in INT_FIELDS.items():
        cols = pool[rng.random(pool.size) < 0.8]
        vals = rng.integers(lo, hi + 1, cols.size)
        data[name] = dict(zip(cols.tolist(), vals.tolist()))
        api.import_values("i", name, col_ids=cols.tolist(),
                          values=vals.tolist())
    assert min(data["signed"].values()) < 0
    executors = {}

    def executor(mode: str):
        if mode not in executors:
            kw = {"off": {"count_batch_window": 0},
                  "mesh": {"placement": MeshPlacement(jax.devices()[:4])},
                  }.get(mode, {"count_batch_window": "adaptive"})
            executors[mode] = Executor(holder, stats=Stats(), **kw)
        return executors[mode]

    yield executor, data
    holder.close()


def reference(data, field, row=None):
    """(sum, count) of ``field`` over the columns of ``f`` row ``row``
    (every column with a value when None)."""
    vals = data[field]
    cols = vals.keys() if row is None else data["f"][row] & vals.keys()
    return sum(vals[c] for c in cols), len(cols)


# each item: a row of ``f`` as its filter, or None for no filter
CASES = {
    "k1_unfiltered": [None],
    "k1": [3],
    "k2_unfiltered": [2, None],
    "k10": list(range(1, 11)),
    "k10_dup_unfiltered": [1, 2, 3, 4, 2, None, 5, 6, 7, 1],
}


def _pql(field, rows):
    return "".join(f"Sum(field={field})" if r is None
                   else f"Sum(Row(f={r}), field={field})" for r in rows)


def _got(ex, field, rows):
    return [(v.value, v.count) for v in ex.execute("i", _pql(field, rows))]


def _want(data, field, rows):
    return [reference(data, field, r) for r in rows]


def _counters(ex) -> tuple:
    c = ex.stats.snapshot()["counters"]
    return (sum(c["sum_plane_launches_total"].values()),
            sum(c["sum_plane_items_total"].values()))


@pytest.mark.parametrize("mode", ["off", "lane"])
@pytest.mark.parametrize("field", list(INT_FIELDS))
@pytest.mark.parametrize("case", list(CASES))
def test_sums_equal_the_host_reference(world, case, field, mode):
    executor, data = world
    rows = CASES[case]
    assert _got(executor(mode), field, rows) == _want(data, field, rows)


@pytest.mark.parametrize("field", list(INT_FIELDS))
@pytest.mark.parametrize("case", ["k1", "k10_dup_unfiltered"])
def test_sums_on_four_devices_equal_the_host_reference(world, case, field):
    """Three shards padded to four over a four-device mesh."""
    executor, data = world
    ex = executor("mesh")
    assert ex.mesh_status()["devices"] == 4
    rows = CASES[case]
    assert _got(ex, field, rows) == _want(data, field, rows)


@pytest.mark.parametrize("field", list(INT_FIELDS))
def test_two_requests_in_one_window_pad_to_a_power_of_two(
        world, monkeypatch, field):
    """The lane is held, so two concurrent requests' Sums meet in one
    collection window: five distinct items over one plane launch once,
    padded to eight, and each request reads its own rows."""
    from tests.test_executor import _hold_fast_lane
    executor, data = world
    ex = executor("window")
    widths = []
    orig = FusedCache.run_sum_plane_batch

    def spy(self, plane, flags, filters, delta=None):
        widths.append(len(flags))
        return orig(self, plane, flags, filters, delta=delta)
    monkeypatch.setattr(FusedCache, "run_sum_plane_batch", spy)
    reqs = [[1, 2, None], [3, 4, 1]]
    got = [None, None]
    gate = threading.Barrier(2)

    def send(i):
        gate.wait(30)
        got[i] = _got(ex, field, reqs[i])
    before = _counters(ex)
    let_go = _hold_fast_lane(ex.batcher)
    try:
        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        let_go()
    assert got == [_want(data, field, r) for r in reqs]
    assert all(w & (w - 1) == 0 for w in widths), widths
    launches, items = (a - b for a, b in zip(_counters(ex), before))
    assert launches == len(widths)
    # the distinct items of each launch, pads excluded: five when both
    # requests shared the window, else three a launch
    assert items == (5 if launches == 1 else 3 * launches)


@pytest.mark.parametrize("field", list(INT_FIELDS))
def test_sums_under_a_bsi_overlay_equal_the_host_reference(
        tmp_path, monkeypatch, field):
    """Writes after the plane is resident ride its ``BsiOverlay``: the
    base side is the pair form over the exclusion filters, the touched
    columns the mini side — exact before any compaction."""
    lo, hi = INT_FIELDS[field]
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field(field, FieldOptions(type="int", min=lo, max=hi))
        ex = Executor(holder, count_batch_window=0, max_concurrent=0,
                      stats=Stats())
        rng = np.random.default_rng(7)
        cols = rng.choice(2 * SHARD_WIDTH, 200, replace=False)
        vals = rng.integers(lo, hi + 1, cols.size)
        data = {field: dict(zip(cols.tolist(), vals.tolist())),
                "f": {1: set(cols[::2].tolist()),
                      2: set(cols[::3].tolist())}}
        for row, rcols in data["f"].items():
            idx.field("f").import_bits(
                np.full(len(rcols), row, np.uint64),
                np.array(sorted(rcols), np.uint64))
        idx.field(field).import_values(cols.astype(np.uint64),
                                       vals.tolist())
        idx.note_columns(cols.astype(np.uint64))
        rows = [1, None, 2]
        assert _got(ex, field, rows) == _want(data, field, rows)
        deltas = []
        orig = FusedCache.run_sum_plane_batch

        def spy(self, plane, flags, filters, delta=None):
            deltas.append(delta)
            return orig(self, plane, flags, filters, delta=delta)
        monkeypatch.setattr(FusedCache, "run_sum_plane_batch", spy)
        for step in range(4):
            # new values on old columns (some in rows 1 / 2) and new
            # columns, the sign flipping where the field is signed
            picks = rng.choice(cols, 6, replace=False).tolist()
            picks += rng.integers(0, 2 * SHARD_WIDTH, 2).tolist()
            pql = ""
            for c in picks:
                v = int(rng.integers(lo, hi + 1))
                data[field][int(c)] = v
                pql += f"Set({int(c)}, {field}={v})"
            ex.execute("i", pql)
            assert _got(ex, field, rows) == _want(data, field, rows), step
        assert any(d is not None for d in deltas)
        st = ex.planes.delta_stats()
        assert st["absorbs"] > 0 and st["compactions"] == 0
    finally:
        holder.close()


@pytest.mark.parametrize("case,launches,items", [
    ("k1", 1, 1), ("k2_unfiltered", 1, 2), ("k10", 1, 10),
    ("k10_dup_unfiltered", 1, 8)])
def test_the_counters_count_one_launch_and_its_distinct_items(
        world, case, launches, items):
    executor, _ = world
    ex = executor("lane")
    before = _counters(ex)
    _got(ex, "unsigned", CASES[case])
    after = _counters(ex)
    assert (after[0] - before[0], after[1] - before[1]) == (launches, items)


def test_the_counters_print_before_the_first_launch(tmp_path):
    holder = Holder(str(tmp_path)).open()
    try:
        ex = Executor(holder, stats=Stats())
        assert _counters(ex) == (0, 0)
    finally:
        holder.close()
