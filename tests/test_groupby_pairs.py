"""GroupBy through ``Executor.execute`` against a set-based host
reference: two and three ``Rows`` levels (the pair form of
``exec.groupby``, one pair matrix or one per outer combination), every
argument the call takes, aggregates (a Sum in the pair form, Min/Max
in the mapped form), combination
blocks small enough that a boundary falls inside a level, a four-device
mesh (whose padded shard tuple keys the live-row memo), and the counter
that says which form a block took."""

import itertools

import jax
import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import groupby as gb
from pilosa_tpu.exec.planes import PAD_SHARD
from pilosa_tpu.obs import Stats
from pilosa_tpu.parallel import MeshPlacement
from pilosa_tpu.store import FieldOptions, Holder
from pilosa_tpu.store.fragment import Fragment

ROWS = {"f": 5, "g": 3, "h": 4}   # f pads to 8 slots, g to 4
N_SHARDS = 3


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Three shards; set fields ``f`` / ``g`` / ``h`` over one pool of
    columns (a column may sit in several rows of a field), and an int
    field ``amount`` with a value on every pool column."""
    holder = Holder(str(tmp_path_factory.mktemp("pairs"))).open()
    idx = holder.create_index("i")
    for name in ROWS:
        idx.create_field(name)
    idx.create_field("amount", FieldOptions(type="int", min=-500, max=500))
    api = API(holder, Executor(holder, count_batch_window=0))
    rng = np.random.default_rng(35)
    pool = rng.choice(N_SHARDS * SHARD_WIDTH, 400, replace=False)
    data = {}
    for name, n_rows in ROWS.items():
        data[name] = {}
        for row in range(1, n_rows + 1):
            cols = pool[rng.random(pool.size) < 0.4]
            data[name][row] = set(cols.tolist())
            api.import_bits("i", name, row_ids=[row] * len(cols),
                            col_ids=cols.tolist())
    vals = rng.integers(-500, 500, pool.size)
    data["amount"] = dict(zip(pool.tolist(), vals.tolist()))
    api.import_values("i", "amount", col_ids=pool.tolist(),
                      values=vals.tolist())
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


def reference(data, fields, filter_cols=None, agg=None, having=None,
              previous=None, limit=None):
    """Every combination in lexicographic row order, one set
    intersection each: ``(rows, count, agg)`` of the groups that
    survive ``having`` (a predicate on count and agg), ``previous`` and
    ``limit``, in that order."""
    out = []
    levels = [sorted(data[f]) for f in fields]
    for combo in itertools.product(*levels):
        cols = set.intersection(*(data[f][r] for f, r in zip(fields, combo)))
        if filter_cols is not None:
            cols &= filter_cols
        if not cols:
            continue
        vals = [data["amount"][c] for c in cols]
        value = {None: None, "Sum": sum(vals), "Min": min(vals),
                 "Max": max(vals)}[agg]
        if having is not None and not having(len(cols), value):
            continue
        if previous is not None and combo <= tuple(previous):
            continue
        out.append((list(combo), len(cols), value))
    return out if limit is None else out[:limit]


# fields, PQL arguments after the Rows calls, the reference's arguments
CASES = {
    "two": ("fg", "", {}),
    "three": ("fgh", "", {}),
    "two_swapped": ("gf", "", {}),           # the wider level innermost
    "two_filter": ("fg", "filter=Row(h=2)", {"filter": ("h", 2)}),
    "three_filter": ("fgh", "filter=Row(f=1)", {"filter": ("f", 1)}),
    "two_limit": ("fg", "limit=4", {"limit": 4}),
    "three_limit": ("fgh", "limit=7", {"limit": 7}),
    "two_previous": ("fg", "previous=[2, 1]", {"previous": [2, 1]}),
    "three_previous_limit": ("fgh", "previous=[2, 3, 1], limit=9",
                             {"previous": [2, 3, 1], "limit": 9}),
    "two_having": ("fg", "having=Condition(count > 65)",
                   {"having": lambda n, v: n > 65}),
    "three_having_limit": ("fgh", "having=Condition(count >= 28), limit=5",
                           {"having": lambda n, v: n >= 28, "limit": 5}),
    "two_sum": ("fg", "aggregate=Sum(field=amount)", {"agg": "Sum"}),
    "three_sum_filter": ("fgh",
                         "filter=Row(f=1), aggregate=Sum(field=amount)",
                         {"filter": ("f", 1), "agg": "Sum"}),
    "two_sum_having": ("fg", "aggregate=Sum(field=amount), "
                             "having=Condition(sum > 100)",
                       {"agg": "Sum", "having": lambda n, v: v > 100}),
    "two_min": ("fg", "aggregate=Min(field=amount)", {"agg": "Min"}),
    "three_min_previous": ("fgh", "aggregate=Min(field=amount), "
                                  "previous=[3, 1, 4]",
                           {"agg": "Min", "previous": [3, 1, 4]}),
    "two_max": ("fg", "aggregate=Max(field=amount)", {"agg": "Max"}),
    "three_max_limit": ("fgh", "aggregate=Max(field=amount), limit=11",
                        {"agg": "Max", "limit": 11}),
}


def _pql(fields, args):
    parts = [f"Rows({f})" for f in fields] + ([args] if args else [])
    return "GroupBy(" + ", ".join(parts) + ")"


def _want(data, fields, ref_args):
    ref_args = dict(ref_args)
    flt = ref_args.pop("filter", None)
    return reference(data, fields,
                     filter_cols=data[flt[0]][flt[1]] if flt else None,
                     **ref_args)


def _got(ex, pql):
    (g,) = ex.execute("i", pql)
    return [([fr.row_id for fr in gc.group], gc.count, gc.agg)
            for gc in g.groups]


def _blocks(ex) -> dict:
    return {dict(k)["form"]: v for k, v in ex.stats.snapshot()[
        "counters"]["groupby_blocks_total"].items()}


# 112 B: seven combinations of a bare GroupBy whose last plane has four
# slots — two whole runs of ``g`` a block, so ``f`` splits; under a
# Min/Max one combination a block, so every level splits
@pytest.mark.parametrize("mode,block_bytes", [
    ("off", None), ("lane", None), ("lane", 112)],
    ids=["off", "lane", "lane_small_blocks"])
@pytest.mark.parametrize("case", list(CASES))
def test_groupby_equals_the_host_reference(world, monkeypatch, case, mode,
                                           block_bytes):
    executor, data = world
    fields, args, ref_args = CASES[case]
    if block_bytes is not None:
        monkeypatch.setattr(gb, "BLOCK_OUT_BYTES", block_bytes)
    want = _want(data, fields, ref_args)
    assert len(want) > 1, "the case must leave something to compare"
    assert _got(executor(mode), _pql(fields, args)) == want


@pytest.mark.parametrize("case", ["two", "three_filter", "two_sum"])
def test_groupby_in_the_window_equals_the_host_reference(world, case):
    """The batcher's windowed route (``_dispatch_groupby``: the lane is
    held by another thread) unflattens a pair-form block as the lane
    does."""
    from tests.test_executor import _hold_fast_lane
    executor, data = world
    ex = executor("window")
    fields, args, ref_args = CASES[case]
    let_go = _hold_fast_lane(ex.batcher)
    try:
        got = _got(ex, _pql(fields, args))
    finally:
        let_go()
    assert got == _want(data, fields, ref_args)


@pytest.mark.parametrize("case", ["two", "three", "two_filter",
                                  "three_sum_filter"])
def test_groupby_on_four_devices_equals_the_host_reference(world, case):
    """Three shards padded to four over a four-device mesh: the pair
    matrix's shard sum ends in the cross-device reduce."""
    executor, data = world
    ex = executor("mesh")
    assert ex.mesh_status()["devices"] == 4
    fields, args, ref_args = CASES[case]
    assert _got(ex, _pql(fields, args)) == _want(data, fields, ref_args)


@pytest.mark.parametrize("case", ["two", "three", "two_filter"])
def test_groupby_on_four_devices_reads_its_row_sets_from_the_memo(
        world, monkeypatch, case):
    """The padded shard tuple (three shards and a pad) keys the live-row
    memo: once warm, a GroupBy walks no fragment for its row sets, takes
    one memo hit per ``Rows`` level and answers as before."""
    executor, data = world
    ex = executor("mesh")
    fields, args, ref_args = CASES[case]
    want = _want(data, fields, ref_args)
    assert _got(ex, _pql(fields, args)) == want
    walked = []
    for name in ("row_ids", "row_ids_array"):
        orig = getattr(Fragment, name)
        monkeypatch.setattr(Fragment, name,
                            lambda self, _o=orig: walked.append(1) or _o(self))
    before = ex.planes.stats()
    assert _got(ex, _pql(fields, args)) == want
    after = ex.planes.stats()
    assert walked == []
    assert after["rowSetHits"] - before["rowSetHits"] == len(fields)
    assert after["rowSetMisses"] == before["rowSetMisses"]
    padded = [k[2] for k in ex.planes._row_sets if k[1] == "standard"]
    assert padded and all(s[-1] == PAD_SHARD and len(s) == 4
                          for s in padded)


@pytest.mark.parametrize("case,block_bytes,pair,mapped", [
    ("two", None, 1, 0),
    ("two_filter", 112, 1, 0),        # one prefix level never splits
    ("three", None, 1, 0),
    ("three", 112, 3, 0),             # five runs of g, two a block
    ("three_limit", 112, 1, 0),       # the limit is met in the first
    ("two_sum", None, 1, 0),
    ("two_sum", 112, 1, 0),           # a Sum's block is whole runs too
    ("two_min", 112, 0, 5),
])
def test_groupby_blocks_total_says_which_form_ran(world, monkeypatch, case,
                                                  block_bytes, pair, mapped):
    executor, _ = world
    ex = executor("lane")
    fields, args, _ = CASES[case]
    if block_bytes is not None:
        monkeypatch.setattr(gb, "BLOCK_OUT_BYTES", block_bytes)
    before = _blocks(ex)
    _got(ex, _pql(fields, args))
    after = _blocks(ex)
    assert after["pair"] - before["pair"] == pair
    assert after["mapped"] - before["mapped"] == mapped


def test_a_single_rows_groupby_is_one_mapped_block(world):
    executor, data = world
    ex = executor("lane")
    before = _blocks(ex)
    assert _got(ex, "GroupBy(Rows(f))") == reference(data, "f")
    after = _blocks(ex)
    assert (after["pair"] - before["pair"],
            after["mapped"] - before["mapped"]) == (0, 1)
