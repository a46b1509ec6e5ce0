"""The Star Schema Benchmark's configuration, generator and mix
(``ssb-sf30``, ``benchmark/datasets/ssb.py``, ``traffic/ssb_c1.json``):
the dimension hierarchies hold, the oracle's two GroupBy paths agree on
an SSB shard, the 13 queries render to PQL the program parses back,
both read controls come out as not correct, and the analytic byte count
is what it says.  (The whole cell rehearses through
``test_cell_rehearses_to_a_correct_line``.)"""

import json
import os

import numpy as np
import pytest

from benchmark import controls, load, loader, manifest, queries, traffic
from benchmark import roofline_analytic
from benchmark.bitmaps import SHARD_WIDTH, WORDS, unpack_bits
from benchmark.datasets import ssb
from pilosa_tpu import pql
from tests.benchmark.test_benchmark_analytic import _call_of

CELL = "ssb-sf30.ssb_c1"
SEED = 4_300_000_011


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def shards(cell):
    return [ssb.generate(cell["config"]["dataset"], SEED, s) for s in (0, 1)]


def _codes(rows: np.ndarray) -> np.ndarray:
    """uint32[R, W] of a field that holds one row a column -> its row
    of every column (the test's own walk over unpacked bits)."""
    codes = np.full(SHARD_WIDTH, -1, np.int64)
    for r, row in enumerate(rows):
        cols = np.flatnonzero(unpack_bits(row))
        assert (codes[cols] == -1).all(), f"a column in row {r} and another"
        codes[cols] = r
    assert (codes >= 0).all(), "a column in no row"
    return codes


@pytest.mark.parametrize("child,parent,of", [
    ("c_city", "c_nation", lambda x: x // 10),
    ("s_city", "s_nation", lambda x: x // 10),
    ("c_nation", "c_region", lambda x: np.asarray(ssb.NATION_REGION)[x]),
    ("s_nation", "s_region", lambda x: np.asarray(ssb.NATION_REGION)[x]),
    ("p_brand1", "p_category", lambda x: x // 40),
    ("p_category", "p_mfgr", lambda x: x // 5),
    ("d_yearmonthnum", "d_year", lambda x: x // 12),
])
def test_every_column_lies_in_one_row_of_each_level_of_a_hierarchy(
        shards, child, parent, of):
    for data in shards:
        assert (of(_codes(data["sets"][child]))
                == _codes(data["sets"][parent])).all()


def test_a_part_gives_every_lineorder_of_it_one_brand_and_its_prices(cell):
    dataset = cell["config"]["dataset"]
    n = dataset["tables"]
    tables = ssb._tables(SEED, n["customers"], n["suppliers"], n["parts"])
    assert tables["retailprice"].max() <= 209_900
    data = ssb.generate(dataset, SEED, 0)
    ints = data["ints"]
    assert (ints["lo_revenue"] <= dataset["int_fields"]["lo_revenue"]["max"]
            ).all()
    for f, spec in dataset["int_fields"].items():
        assert 0 <= ints[f].min() and ints[f].max() <= spec["max"], f
    # the stored product is the product of what it multiplies
    price = ints["lo_supplycost"].astype(np.int64)
    assert (ints["xd"] % np.maximum(ints["lo_discount"], 1) == 0).all()
    assert (price <= 6 * 209_900 // 10).all()


def test_the_generator_is_a_pure_function_of_seed_and_shard(cell):
    dataset = cell["config"]["dataset"]
    a, b = (ssb.generate(dataset, SEED, 1) for _ in range(2))
    c = ssb.generate(dataset, SEED + 1, 1)
    assert all((a["sets"][f] == b["sets"][f]).all() for f in a["sets"])
    assert not (a["sets"]["p_brand1"] == c["sets"]["p_brand1"]).all()
    assert all(rows.shape == (len(dataset["set_fields"][f]["shares"]), WORDS)
               for f, rows in a["sets"].items())


def _narrow(data: dict, words: int) -> dict:
    """The first ``32 * words`` columns of a shard: small enough for the
    oracle to AND every combination of three levels."""
    return {"sets": {f: np.ascontiguousarray(r[:, :words])
                     for f, r in data["sets"].items()},
            "ints": {f: v[:32 * words] for f, v in data["ints"].items()}}


def _templates(cell):
    return {t["name"]: t for t in cell["traffic"]["templates"]}


@pytest.mark.parametrize("name", ["q2_1", "q3_1", "q4_1", "q4_2"])
def test_the_oracles_two_groupby_paths_agree_on_an_ssb_shard(
        cell, shards, name):
    data = _narrow(shards[1], 256)
    for c in _templates(cell)[name]["calls"]:
        by_codes = queries.groupby_by_codes(c, data)
        assert by_codes is not None
        assert (by_codes[0].sum() > 0), "the filter must reach something"
        assert (queries.groupby_by_planes(c, data) == by_codes).all()


def test_the_thirteen_queries_render_to_pql_the_program_parses_back(cell):
    mix = cell["traffic"]
    # Q3.2 first: the template the warm-up waits on
    assert [t["name"] for t in mix["templates"]] == [
        "q3_2", "q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1",
        "q3_3", "q3_4", "q4_1", "q4_2", "q4_3"]
    assert mix["pool"] % 13 == 0
    assert not any("params" in t or "foreach" in t for t in mix["templates"])
    for t in mix["templates"]:
        text = queries.render(t["calls"])
        assert [_call_of(c) for c in pql.parse(text).calls] == t["calls"]
    # Q4.x: the two sums profit is the client's difference of
    assert [len(t["calls"]) for t in mix["templates"]] == [1] * 10 + [2] * 3


@pytest.fixture(scope="module")
def oracle(cell):
    pool = traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(cell["config"]), SEED)
    calls, index = pool.distinct_calls()
    totals = None
    for s in range(2):
        data = cell["generate"](cell["config"]["dataset"], SEED, s)
        totals = queries.combine(
            totals, [queries.partial(c, data) for c in calls])
    expected = [[queries.finish(calls[i], totals[i]) for i in ids]
                for ids in index]
    return pool, calls, index, totals, expected


@pytest.mark.parametrize("control", sorted(controls.READ))
def test_both_read_controls_come_out_as_not_correct(cell, oracle, control):
    pool, calls, index, totals, expected = oracle
    sound = [[(int(rid), 0.0, 0.01, 200,
               json.dumps({"results": expected[rid]}).encode())
              for rid in pool.client_order(0)[:130]]]
    assert load.judge(sound, expected)["wrong"] == 0
    broken = controls.READ[control](sound, cell, pool, calls, index, totals,
                                   SEED, 2)
    verdict = load.judge(broken, expected)
    assert verdict["attempted"] == 130
    # most answers are off even at two shards, not a stray one
    assert verdict["wrong"] >= 100, verdict["first_wrong"]


def test_the_analytic_byte_count_is_pinned_by_hand(cell):
    dataset = cell["config"]["dataset"]
    t = _templates(cell)
    row = 172 * SHARD_WIDTH // 8
    # Q1.1: one d_year row; lo_discount 4 bits + 2, lo_quantity 6 + 2,
    # xd 27 + 2
    assert roofline_analytic.required_bytes(
        t["q1_1"]["calls"], dataset, 172) == (1 + 6 + 8 + 29) * row
    # Q3.2: c_city and s_city a level each, 250 rows: code width 8 + 1;
    # d_year a level, 7 rows, and six filter rows: code width 3 + 1;
    # c_nation and s_nation one row each; lo_revenue 24 bits + 2
    assert roofline_analytic.required_bytes(
        t["q3_2"]["calls"], dataset, 172) == (9 + 9 + 4 + 1 + 1 + 26) * row
    with pytest.raises(ValueError):
        roofline_analytic.required_rows([{"call": "Set"}], dataset)


def test_the_config_states_the_cut_and_its_fields_as_the_mix_names_them(cell):
    config = cell["config"]
    with open(os.path.join(os.path.dirname(manifest.HERE),
                           "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == config["name"])
    assert entry["source"] == config["source"]
    assert len(config["source"]) <= 200
    assert config["columns"] == config["shards"] * SHARD_WIDTH
    rows = loader.dataset_field_rows(config)
    assert {f: rows[f] for f in ("c_city", "s_city", "p_brand1",
                                 "d_yearmonthnum", "d_weeknuminyear")} == {
        "c_city": 250, "s_city": 250, "p_brand1": 1000,
        "d_yearmonthnum": 80, "d_weeknuminyear": 53}
    named = set()
    for t in cell["traffic"]["templates"]:
        for c in t["calls"]:
            named.update(c.get("fields", []))
            named.update(json.dumps(c.get("filter", {})).split('"'))
    assert set(rows) <= named
