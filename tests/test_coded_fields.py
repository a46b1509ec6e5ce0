"""Coded fields (``exec/planes.py`` ``PlaneCache.code_plane``): a set
field of more than ``CODED_ROWS_OVER`` rows in which no column lies in
two rows keeps its standard view as a bit-sliced code.  Which fields are
coded, that every read over one answers as the dense layout and a numpy
walk over the bits do, and that a write which puts a column in a second
row holds the field dense again."""

import json
import os

import numpy as np
import pytest

from pilosa_tpu.api import API
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import planes as planes_mod
from pilosa_tpu.obs import Stats
from pilosa_tpu.store import FieldOptions, Holder

N_SHARDS = 3
CITY_ROWS = 150          # coded: over 64, one row a column
NATION_ROWS = 15         # dense
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Three shards of ``city`` (150 rows, coded), ``nation`` (city //
    10, dense), ``tag`` (a column may hold two of its 80 rows: dense),
    ``kind`` (a mutex field of 70 rows) and the int field ``amount``."""
    holder = Holder(str(tmp_path_factory.mktemp("coded"))).open()
    idx = holder.create_index("i")
    for name in ("city", "nation", "tag"):
        idx.create_field(name)
    idx.create_field("kind", FieldOptions(type="mutex"))
    idx.create_field("amount", FieldOptions(type="int", min=-900, max=900))
    rng = np.random.default_rng(43)
    cols = np.sort(rng.choice(N_SHARDS * SHARD_WIDTH, 6000, replace=False))
    city = rng.integers(0, CITY_ROWS, cols.size)
    tag = rng.integers(0, 80, cols.size)
    kind = rng.integers(0, 70, cols.size)
    vals = rng.integers(-900, 900, cols.size)
    api = API(holder, Executor(holder, count_batch_window=0))
    api.import_bits("i", "city", row_ids=city.tolist(), col_ids=cols.tolist())
    api.import_bits("i", "nation", row_ids=(city // 10).tolist(),
                    col_ids=cols.tolist())
    api.import_bits("i", "tag", row_ids=tag.tolist(), col_ids=cols.tolist())
    api.import_bits("i", "tag", row_ids=((tag[:50] + 1) % 80).tolist(),
                    col_ids=cols[:50].tolist())
    api.import_bits("i", "kind", row_ids=kind.tolist(), col_ids=cols.tolist())
    api.import_values("i", "amount", col_ids=cols.tolist(),
                      values=vals.tolist())
    data = {"cols": cols, "city": city, "nation": city // 10, "kind": kind,
            "amount": vals}
    yield holder, data
    holder.close()


def _executor(holder, dense=False, monkeypatch=None):
    ex = Executor(holder, stats=Stats(), count_batch_window="adaptive")
    if dense:
        # the same reads with the coded layout out of reach
        monkeypatch.setattr(planes_mod, "CODED_ROWS_OVER", 1 << 30)
    return ex


def _status(ex):
    return ex.planes.stats()


def test_a_wide_single_valued_field_is_coded_and_the_others_dense(world):
    holder, _ = world
    ex = _executor(holder)
    shards = tuple(range(N_SHARDS))
    idx = holder.index("i")
    assert ex.planes.code_plane("i", idx.field("city"), shards) is not None
    assert ex.planes.code_plane("i", idx.field("nation"), shards) is None
    assert ex.planes.code_plane("i", idx.field("tag"), shards) is None
    code = ex.planes.code_plane("i", idx.field("city"), shards)
    # the slot in binary (150 rows: 8 bits) beside existence and sign
    assert code.plane.shape == (N_SHARDS, 10, 32768)
    st = _status(ex)
    assert st["codedFields"] == 1
    assert st["codedBytes"] == N_SHARDS * 10 * 32768 * 4
    assert st["codedFallbacks"] == 0


def test_a_mutex_field_is_coded_without_the_data_check(world, monkeypatch):
    holder, _ = world
    checked = []
    orig = planes_mod._in_two_rows
    monkeypatch.setattr(planes_mod, "_in_two_rows",
                        lambda cols: checked.append(1) or orig(cols))
    ex = _executor(holder)
    shards = tuple(range(N_SHARDS))
    assert ex.planes.code_plane("i", holder.index("i").field("kind"),
                                shards) is not None
    assert checked == []
    assert ex.planes.code_plane("i", holder.index("i").field("city"),
                                shards) is not None
    assert len(checked) == N_SHARDS


@pytest.mark.parametrize("config", ["pibench1b", "taxi333m",
                                    "taxi-full-mesh4"])
def test_no_field_of_an_entered_configuration_is_coded(config):
    """The widest set field of the three has 64 rows: their planes keep
    the layout they had."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as fh:
        ds = json.load(fh)["dataset"]
    rows = ({f: len(s["shares"]) for f, s in ds["set_fields"].items()}
            if "set_fields" in ds else {ds["field"]: ds["rows"]})
    assert max(rows.values()) <= planes_mod.CODED_ROWS_OVER
    assert max(rows.values()) == (64 if config == "taxi-full-mesh4" else
                                  32)


def _walk(data, pql_kind, arg=None):
    """The numpy walk over the columns."""
    city, nation = data["city"], data["nation"]
    if pql_kind == "count_row":
        return int((city == arg).sum())
    if pql_kind == "count_and":
        return int(((city == arg[0]) & (nation == arg[1])).sum())
    if pql_kind == "count_union":
        return int(np.isin(city, arg).sum())
    raise ValueError(pql_kind)


COUNTS = [
    ("Count(Row(city=17))", "count_row", 17),
    ("Count(Row(city=149))", "count_row", 149),
    ("Count(Intersect(Row(city=23), Row(nation=2)))", "count_and", (23, 2)),
    ("Count(Intersect(Row(city=23), Row(nation=3)))", "count_and", (23, 3)),
    ("Count(Union(Row(city=3), Row(city=77), Row(city=120)))",
     "count_union", [3, 77, 120]),
]


@pytest.mark.parametrize("pql,kind,arg", COUNTS,
                         ids=[c[0][:40] for c in COUNTS])
def test_counts_over_a_coded_field_equal_the_walk_and_the_dense_path(
        world, monkeypatch, pql, kind, arg):
    holder, data = world
    coded = _executor(holder)
    (got,) = coded.execute("i", pql)
    assert got == _walk(data, kind, arg)
    assert _status(coded)["codedFields"] == 1
    (dense,) = _executor(holder, True, monkeypatch).execute("i", pql)
    assert dense == got


def test_a_row_of_a_coded_field_is_its_columns(world):
    holder, data = world
    ex = _executor(holder)
    (row,) = ex.execute("i", "Row(city=42)")
    want = data["cols"][data["city"] == 42]
    assert list(row.columns) == want.tolist()
    # derived from the code on the device and kept as a row entry
    assert any(k[0] == "row" and k[2] == "city" and k[4] == 42
               for k in ex.planes._entries)


@pytest.mark.parametrize("pql,nation,n", [
    ("TopN(city, n=5)", None, 5), ("TopN(city, Row(nation=4), n=3)", 4, 3),
    ("TopN(city, Row(nation=14))", 14, None)])
def test_topn_over_a_coded_field_equals_the_walk(world, monkeypatch, pql,
                                                  nation, n):
    holder, data = world
    city = data["city"]
    keep = (np.ones(city.size, bool) if nation is None
            else data["nation"] == nation)
    counts = np.bincount(city[keep], minlength=CITY_ROWS)
    order = sorted((r for r in range(CITY_ROWS) if counts[r]),
                   key=lambda r: (-counts[r], r))[:n]
    ex = _executor(holder)
    (got,) = ex.execute("i", pql)
    pairs = [(p.id, p.count) for p in got.pairs]
    assert pairs == [(r, int(counts[r])) for r in order]
    assert not any(k[0] == "plane" and k[2] == "city"
                   for k in ex.planes._entries)
    (dense,) = _executor(holder, True, monkeypatch).execute("i", pql)
    assert [(p.id, p.count) for p in dense.pairs] == pairs


def test_rows_of_a_coded_field_are_its_live_rows(world):
    holder, data = world
    (got,) = _executor(holder).execute("i", "Rows(city, previous=140)")
    assert list(got.rows) == sorted(set(data["city"][data["city"] > 140]))


@pytest.mark.parametrize("pql,fields,flt", [
    ("GroupBy(Rows(nation), Rows(city))", ("nation", "city"), None),
    ("GroupBy(Rows(city), Rows(nation), filter=Row(nation=7))",
     ("city", "nation"), 7),
    ("GroupBy(Rows(city), aggregate=Sum(field=amount))", ("city",), None),
    ("GroupBy(Rows(nation), Rows(city), filter=Row(nation=11), "
     "aggregate=Sum(field=amount))", ("nation", "city"), 11),
])
def test_groupby_over_a_coded_field_equals_the_walk_and_the_dense_path(
        world, monkeypatch, pql, fields, flt):
    holder, data = world
    keep = np.ones(data["city"].size, bool) if flt is None \
        else data["nation"] == flt
    groups = {}
    for i in np.flatnonzero(keep):
        key = tuple(int(data[f][i]) for f in fields)
        n, s = groups.get(key, (0, 0))
        groups[key] = (n + 1, s + int(data["amount"][i]))
    agg = "aggregate" in pql
    want = [(list(k), n, s if agg else None)
            for k, (n, s) in sorted(groups.items())]

    def got(ex):
        (g,) = ex.execute("i", pql)
        return [([fr.row_id for fr in gc.group], gc.count, gc.agg)
                for gc in g.groups]

    assert got(_executor(holder)) == want
    assert got(_executor(holder, True, monkeypatch)) == want


@pytest.mark.parametrize("pql,pairs", [
    # city = nation x 10 + d, 8 code bits: high = city >> 4
    ("GroupBy(Rows(city), Rows(nation), filter=Row(nation=7))", 1 * 16),
    ("GroupBy(Rows(city), filter=Row(nation=4))", 2 * 16),
    ("GroupBy(Rows(city), filter=Union(Row(nation=0), Row(nation=14)))",
     4 * 16),
    ("TopN(city, n=5)", 256),
    ("GroupBy(Rows(city))", 0),
], ids=["one_high", "two_highs", "three_highs_k4", "unfiltered", "no_reach"])
def test_code_histogram_pairs_count_the_bucket_a_call_formed(world, pql,
                                                             pairs):
    """``code_histogram_pairs_total`` grows by ``K x 2^lo`` for a
    filtered histogram (K the power of two at or above the high values
    the filter reaches) and by ``2^depth`` for an unfiltered one; a
    GroupBy with no filter cuts no level and forms none."""
    holder, _ = world
    ex = _executor(holder)

    def total():
        return sum(ex.stats.snapshot()["counters"][
            "code_histogram_pairs_total"].values())

    before = total()
    ex.execute("i", pql)
    assert total() - before == pairs


def test_a_write_that_puts_a_column_in_two_rows_holds_the_field_dense(
        tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder, stats=Stats())
    api = API(holder, ex)
    cols = np.arange(0, 2 * SHARD_WIDTH, 997)
    rows = cols % 100
    api.import_bits("i", "f", row_ids=rows.tolist(), col_ids=cols.tolist())
    assert ex.execute("i", "Count(Row(f=5))") == [int((rows == 5).sum())]
    assert ex.planes.stats()["codedFields"] == 1
    # column 0 (row 0) gains row 5: no longer one row a column
    api.query("i", "Set(0, f=5)")
    assert ex.execute("i", "Count(Row(f=5))") == [int((rows == 5).sum()) + 1]
    assert ex.execute("i", "Count(Row(f=0))") == [int((rows == 0).sum())]
    st = ex.planes.stats()
    assert (st["codedFields"], st["codedFallbacks"]) == (0, 1)
    fallbacks = ex.stats.snapshot()["counters"]["plane_coded_fallbacks_total"]
    assert sum(fallbacks.values()) == 1
    (g,) = ex.execute("i", "GroupBy(Rows(f), limit=1)")
    assert [(gc.group[0].row_id, gc.count) for gc in g.groups] == [
        (0, int((rows == 0).sum()))]
    holder.close()


def test_a_coded_field_over_a_four_device_mesh_answers_as_the_walk(world):
    """Three shards padded to four over a four-device mesh: the code
    plane is sharded on its shard axis like every plane."""
    import jax
    from pilosa_tpu.parallel import MeshPlacement
    holder, data = world
    ex = Executor(holder, stats=Stats(),
                  placement=MeshPlacement(jax.devices()[:4]))
    city, nation, amount = data["city"], data["nation"], data["amount"]
    assert ex.execute("i", "Count(Row(city=31))") == [int((city == 31).sum())]
    (top,) = ex.execute("i", "TopN(city, Row(nation=6), n=2)")
    counts = np.bincount(city[nation == 6], minlength=CITY_ROWS)
    assert [p.count for p in top.pairs] == sorted(counts, reverse=True)[:2]
    (g,) = ex.execute("i", "GroupBy(Rows(city), filter=Row(nation=9), "
                           "aggregate=Sum(field=amount))")
    want = [([c], int((city == c).sum()), int(amount[city == c].sum()))
            for c in range(90, 100)]
    assert [([fr.row_id for fr in gc.group], gc.count, gc.agg)
            for gc in g.groups] == want
    assert ex.planes.stats()["codedFields"] == 1


def test_a_row_with_no_bit_is_empty_when_the_rows_fill_the_code(tmp_path):
    """128 rows: the slots fill seven bits, so the code takes an eighth
    and the value past the last slot, which a row with no bit reads,
    is held by no column."""
    holder = Holder(str(tmp_path)).open()
    holder.create_index("i").create_field("f")
    ex = Executor(holder, stats=Stats())
    api = API(holder, ex)
    cols = np.arange(0, SHARD_WIDTH, 211)
    api.import_bits("i", "f", row_ids=(cols % 128).tolist(),
                    col_ids=cols.tolist())
    assert ex.execute("i", "Count(Row(f=0))") == [int((cols % 128 == 0).sum())]
    assert ex.planes.stats()["codedFields"] == 1
    code = ex.planes.code_plane("i", holder.index("i").field("f"), (0,))
    assert code.depth == 8
    assert ex.execute("i", "Count(Row(f=500))") == [0]
    assert ex.execute("i", "Count(Union(Row(f=500), Row(f=3)))") == [
        int((cols % 128 == 3).sum())]
    holder.close()
