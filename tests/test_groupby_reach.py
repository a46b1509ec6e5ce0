"""GroupBy levels cut to the rows the filter reaches, and the aggregate
Sum from the pair matrix (``Executor._execute_groupby``,
``exec/groupby.py``): against a numpy walk over the columns, for one to
three levels, dense and coded levels, ``limit=`` / ``previous=``, filters
that reach nothing, signed and unsigned sums across block boundaries,
and the counters that say how many combinations were dispatched."""

import itertools

import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import groupby as gb
from pilosa_tpu.obs import Stats
from pilosa_tpu.store import FieldOptions, Holder

N_SHARDS = 3
ROWS = {"a": 6, "b": 90, "c": 4}     # b is coded: 90 rows, one a column


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Every column of a pool in exactly one row of ``a``, ``b`` and
    ``c``; ``w`` a filter field whose row 1 reaches some rows of each
    level and whose row 2 reaches none; ``signed`` (values of both
    signs) and ``unsigned`` int fields."""
    holder = Holder(str(tmp_path_factory.mktemp("reach"))).open()
    idx = holder.create_index("i")
    for name in (*ROWS, "w"):
        idx.create_field(name)
    idx.create_field("signed", FieldOptions(type="int", min=-3000, max=3000))
    idx.create_field("unsigned", FieldOptions(type="int", min=0, max=70000))
    rng = np.random.default_rng(44)
    cols = np.sort(rng.choice(N_SHARDS * SHARD_WIDTH, 5000, replace=False))
    data = {"cols": cols}
    api = API(holder, Executor(holder, count_batch_window=0))
    for name, n in ROWS.items():
        data[name] = rng.integers(0, n, cols.size)
        api.import_bits("i", name, row_ids=data[name].tolist(),
                        col_ids=cols.tolist())
    # row 1 of w: the columns of a in {1, 4} and of b under 30
    w1 = np.isin(data["a"], (1, 4)) & (data["b"] < 30)
    api.import_bits("i", "w", row_ids=[1] * int(w1.sum()),
                    col_ids=cols[w1].tolist())
    api.import_bits("i", "w", row_ids=[2], col_ids=[N_SHARDS * SHARD_WIDTH
                                                    - 1])
    data["w1"] = w1
    data["signed"] = rng.integers(-3000, 3000, cols.size)
    data["unsigned"] = rng.integers(0, 70000, cols.size)
    for f in ("signed", "unsigned"):
        api.import_values("i", f, col_ids=cols.tolist(),
                          values=data[f].tolist())
    yield holder, data
    holder.close()


def walk(data, fields, flt=None, agg=None, previous=None, limit=None,
         rows=ROWS):
    """Every combination in row order, from the columns themselves."""
    keep = np.ones(data["cols"].size, bool)
    if flt == 1:
        keep = data["w1"]
    elif flt == 2:
        keep = np.zeros_like(keep)
    out = []
    levels = [range(rows[f]) for f in fields]
    for combo in itertools.product(*levels):
        m = keep.copy()
        for f, r in zip(fields, combo):
            m &= data[f] == r
        if not m.any():
            continue
        if previous is not None and combo <= tuple(previous):
            continue
        out.append((list(combo), int(m.sum()),
                    int(data[agg][m].sum()) if agg else None))
    return out if limit is None else out[:limit]


def _pql(fields, flt=None, agg=None, previous=None, limit=None):
    parts = [f"Rows({f})" for f in fields]
    if flt is not None:
        parts.append(f"filter=Row(w={flt})")
    if agg:
        parts.append(f"aggregate=Sum(field={agg})")
    if previous is not None:
        parts.append(f"previous={list(previous)}")
    if limit is not None:
        parts.append(f"limit={limit}")
    return "GroupBy(" + ", ".join(parts) + ")"


def _got(ex, pql):
    (g,) = ex.execute("i", pql)
    return [([fr.row_id for fr in gc.group], gc.count, gc.agg)
            for gc in g.groups]


def _counter(ex, name):
    return sum(ex.stats.snapshot()["counters"].get(name, {}).values())


CASES = [
    ("a", {}), ("b", {"flt": 1}), ("ab", {"flt": 1}), ("ba", {"flt": 1}),
    ("abc", {"flt": 1}), ("bca", {"flt": 1}), ("abc", {}),
    ("ab", {"flt": 1, "limit": 7}), ("cba", {"flt": 1, "limit": 11}),
    ("abc", {"flt": 1, "previous": (1, 12, 2)}),
    ("ba", {"flt": 1, "previous": (20, 1), "limit": 5}),
    ("ab", {"flt": 2}), ("abc", {"flt": 2, "agg": "signed"}),
    ("b", {"flt": 1, "agg": "signed"}), ("a", {"agg": "unsigned"}),
    ("ab", {"flt": 1, "agg": "signed"}), ("ba", {"agg": "unsigned"}),
    ("abc", {"flt": 1, "agg": "unsigned"}),
    ("cab", {"flt": 1, "agg": "signed", "limit": 9}),
]


@pytest.mark.parametrize("mode", ["lane", "off"])
@pytest.mark.parametrize("fields,args", CASES,
                         ids=[f"{f}-{'-'.join(f'{k}{v}' for k, v in a.items())}"
                              for f, a in CASES])
def test_a_pruned_groupby_equals_the_walk(world, mode, fields, args):
    holder, data = world
    ex = Executor(holder, stats=Stats(),
                  count_batch_window=0 if mode == "off" else "adaptive")
    assert _got(ex, _pql(fields, **args)) == walk(data, fields, **args)


@pytest.mark.parametrize("block_bytes,coded_bytes", [
    (1, None), (64, None), (None, 1), (1, 1)],
    ids=["one_run_a_block", "small_blocks", "one_coded_row_a_block",
         "both"])
@pytest.mark.parametrize("fields,args", [
    ("ab", {"flt": 1, "agg": "signed"}), ("bac", {"agg": "unsigned"}),
    ("ab", {"agg": "signed", "limit": 13}),
    ("ba", {"flt": 1, "previous": (3, 2)}),
])
def test_the_blocks_of_a_groupby_add_up_to_the_walk(
        world, monkeypatch, block_bytes, coded_bytes, fields, args):
    """A block size forced down to one unit (one combination, or one run
    of the innermost prefix level), and a coded level derived one row at
    a time: the groups come out whole and in order."""
    holder, data = world
    if block_bytes is not None:
        monkeypatch.setattr(gb, "BLOCK_OUT_BYTES", block_bytes)
    if coded_bytes is not None:
        monkeypatch.setattr(Executor, "GROUPBY_CODED_BYTES", coded_bytes)
    ex = Executor(holder, stats=Stats())
    assert _got(ex, _pql(fields, **args)) == walk(data, fields, **args)


@pytest.mark.parametrize("fields,flt", [("ab", 1), ("abc", 1), ("ba", 1),
                                        ("abc", None), ("ab", 2)])
def test_the_combinations_counted_are_those_left_after_the_cut(
        world, fields, flt):
    holder, data = world
    ex = Executor(holder, stats=Stats())
    keep = data["w1"] if flt == 1 else np.ones(data["cols"].size, bool)
    if flt == 2:
        keep = np.zeros_like(keep)
    reached = [np.unique(data[f][keep]) for f in fields]
    live = [np.unique(data[f]) for f in fields]
    before = (_counter(ex, "groupby_combinations_total"),
              _counter(ex, "groupby_rows_pruned_total"))
    _got(ex, _pql(fields, flt))
    after = (_counter(ex, "groupby_combinations_total"),
             _counter(ex, "groupby_rows_pruned_total"))
    if flt == 2:
        # the first level reaches nothing: nothing is dispatched
        assert after[0] - before[0] == 0
        assert after[1] - before[1] == len(live[0])
        return
    assert after[0] - before[0] == int(np.prod([len(r) for r in reached]))
    assert after[1] - before[1] == sum(len(v) - len(r)
                                       for v, r in zip(live, reached))
    if flt == 1:
        assert after[1] > before[1]


def test_a_sum_groupby_runs_in_the_pair_form(world):
    holder, data = world
    ex = Executor(holder, stats=Stats())
    _got(ex, _pql("ab", 1, "signed"))
    _got(ex, _pql("a", None, "unsigned"))
    forms = {dict(k)["form"]: v for k, v in ex.stats.snapshot()[
        "counters"]["groupby_blocks_total"].items()}
    assert forms["mapped"] == 0 and forms["pair"] >= 2


def test_a_sum_over_a_live_write_overlay_equals_the_walk(tmp_path):
    """Values written after the BSI plane went resident are absorbed
    into its overlay: the pair-form Sum counts the touched words from
    the merged mini plane, for one level and for three."""
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    for name in ("a", "b", "c"):
        idx.create_field(name)
    idx.create_field("v", FieldOptions(type="int", min=-5000, max=5000))
    rng = np.random.default_rng(45)
    cols = np.sort(rng.choice(2 * SHARD_WIDTH, 3000, replace=False))
    levels = {"a": rng.integers(0, 4, cols.size),
              "b": rng.integers(0, 70, cols.size),
              "c": rng.integers(0, 3, cols.size)}
    ex = Executor(holder, stats=Stats())
    api = API(holder, ex)
    for name, rows in levels.items():
        api.import_bits("i", name, row_ids=rows.tolist(),
                        col_ids=cols.tolist())
    vals = rng.integers(-5000, 5000, cols.size)
    api.import_values("i", "v", col_ids=cols.tolist(), values=vals.tolist())
    data = dict(levels, cols=cols, w1=np.ones(cols.size, bool), signed=vals)

    def check():
        for fields in ("b", "abc", "ca"):
            want = walk(data, fields, agg="signed",
                        rows={"a": 4, "b": 70, "c": 3})
            assert _got(ex, _pql(fields, agg="v")) == want, fields

    try:
        check()
        absorbs = ex.planes.delta_absorbs
        for _ in range(3):
            at = rng.choice(cols.size, 5, replace=False)
            vals[at] = rng.integers(-5000, 5000, at.size)
            api.import_values("i", "v", col_ids=cols[at].tolist(),
                              values=vals[at].tolist())
            check()
        assert ex.planes.delta_absorbs > absorbs
    finally:
        holder.close()
