"""Analytic PQL in the request model and roaring array containers in
the loader: every new form renders to PQL that the program's parser
reads back as the same call; the oracle's two GroupBy paths agree; a
fragment file reads back bit for bit through the program's reader,
holds the containers the format gives its blocks, byte for byte as the
program's own writers leave them, and is served with the oracle's
answers.  Answers and counts only."""

import contextlib
import glob
import io
import json
import os

import numpy as np
import pytest

from benchmark import bitmaps, loader, manifest, queries, roofline, traffic
from benchmark import run as bench_run
from pilosa_tpu import pql
from pilosa_tpu.store import roaring

SEED = 3_400_000_007
AMOUNT = "total_amount_dollars"


# -- every new form, rendered and parsed back ---------------------------------------

def _bitmap_of(call):
    """A parsed bitmap call back in the request model's shape."""
    if call.name != "Row":
        return {"op": call.name, "args": [_bitmap_of(c)
                                          for c in call.children]}
    ((field, value),) = call.args.items()
    if not isinstance(value, pql.Condition):
        return {"row": [field, value]}
    if value.op == "<=><=":
        return {"cond": [field, "between", *value.value]}
    return {"cond": [field, value.op, value.value]}


def _call_of(call):
    """A parsed read call back in the request model's shape."""
    if call.name == "Count":
        return {"call": "Count", "of": _bitmap_of(call.children[0])}
    if call.name == "Sum":
        out = {"call": "Sum", "field": call.args["field"]}
        if call.children:
            out["filter"] = _bitmap_of(call.children[0])
        return out
    assert call.name == "GroupBy"
    assert all(c.name == "Rows" for c in call.children)
    out = {"call": "GroupBy",
           "fields": [c.args["_field"] for c in call.children]}
    if "filter" in call.args:
        out["filter"] = _bitmap_of(call.args["filter"])
    if "aggregate" in call.args:
        agg = call.args["aggregate"]
        assert agg.name == "Sum" and not agg.children
        out["aggregate"] = {"sum": agg.args["field"]}
    return out


IN_CAB = {"row": ["cab_type", 1]}
BETWEEN = {"cond": [AMOUNT, "between", 10, 30]}
FORMS = [
    ({"call": "Count", "of": {"cond": [AMOUNT, op, 50]}},
     f"Count(Row({AMOUNT} {op} 50))")
    for op in ("<", "<=", ">", ">=", "==", "!=")
] + [
    ({"call": "Count", "of": BETWEEN},
     f"Count(Row(10 <= {AMOUNT} <= 30))"),
    ({"call": "Sum", "field": AMOUNT,
      "filter": {"op": "Intersect", "args": [IN_CAB, BETWEEN]}},
     f"Sum(Intersect(Row(cab_type=1), Row(10 <= {AMOUNT} <= 30)), "
     f"field={AMOUNT})"),
    ({"call": "GroupBy", "fields": ["passenger_count"],
      "aggregate": {"sum": AMOUNT}},
     f"GroupBy(Rows(passenger_count), aggregate=Sum(field={AMOUNT}))"),
    ({"call": "GroupBy", "fields": ["passenger_count", "pickup_year"],
      "filter": IN_CAB},
     "GroupBy(Rows(passenger_count), Rows(pickup_year), "
     "filter=Row(cab_type=1))"),
    ({"call": "GroupBy", "fields": ["passenger_count", "pickup_year"],
      "filter": {"op": "Union", "args": [IN_CAB, BETWEEN]},
      "aggregate": {"sum": AMOUNT}},
     "GroupBy(Rows(passenger_count), Rows(pickup_year), "
     f"filter=Union(Row(cab_type=1), Row(10 <= {AMOUNT} <= 30)), "
     f"aggregate=Sum(field={AMOUNT}))"),
]


@pytest.mark.parametrize("call,text", FORMS, ids=[t[:48] for _, t in FORMS])
def test_a_new_form_renders_to_pql_that_the_program_parses_back(call, text):
    assert queries.render_call(call) == text
    (parsed,) = pql.parse(text).calls
    assert _call_of(parsed) == call


@pytest.mark.parametrize("cond", [[AMOUNT, "=", 5], [AMOUNT, "<", 5, 6],
                                  [AMOUNT, "between", 5]])
def test_a_condition_the_model_does_not_know_is_refused(cond):
    with pytest.raises(ValueError, match="unknown condition"):
        queries.render_bitmap({"cond": cond})


def test_the_probes_templates_are_the_five_the_issue_names():
    cell = manifest.cell("taxi333m.analytic_c1")
    mix = cell["traffic"]
    assert (cell["workload"]["config"], cell["workload"]["chips"]) \
        == ("taxi333m", 1)
    assert (mix["loop"], mix["clients"], mix["pool"]) == ("closed", 1, 1000)
    pool = traffic.Pool(mix, loader.dataset_field_rows(cell["config"]), SEED)
    cover = [pool.requests[r]["pql"] for r in pool.cover]
    assert cover[0] == (f"GroupBy(Rows(passenger_count), "
                        f"aggregate=Sum(field={AMOUNT}))")
    assert f"Count(Row({AMOUNT} > 50))" in cover
    assert (f"Sum(Intersect(Row(pickup_year=0), Row(10 <= {AMOUNT} <= 30)), "
            f"field={AMOUNT})") in cover
    assert (f"Count(Intersect(Row(cab_type=2), Row({AMOUNT} < 10)))"
            in cover)
    assert (f"GroupBy(Rows(passenger_count), Rows(pickup_year), "
            f"filter=Row(cab_type=1), aggregate=Sum(field={AMOUNT}))"
            in cover)
    assert len(mix["templates"]) == 5
    assert {r["template"] for r in pool.requests} \
        == {t["name"] for t in mix["templates"]}
    # an input of the tests: no entry, and no file under benchmark/
    bench = manifest.benchmark_json()
    assert "taxi333m.analytic_c1" not in [w["name"]
                                          for w in bench["workloads"]]
    assert "analytic_c1" not in [w["traffic"] for w in bench["workloads"]]
    assert not glob.glob(os.path.join(manifest.HERE, "*", "*analytic_c1*"))


# -- the oracle's two GroupBy paths ---------------------------------------------------

@pytest.fixture(scope="module")
def taxi_shard():
    cell = manifest.cell("taxi333m.analytic_c1")
    return cell["generate"](cell["config"]["dataset"], SEED, 0)


GROUPBYS = [
    {"fields": ["passenger_count"], "aggregate": {"sum": AMOUNT}},
    {"fields": ["passenger_count", "pickup_year"], "filter": IN_CAB},
    {"fields": ["passenger_count", "pickup_year"], "filter": IN_CAB,
     "aggregate": {"sum": AMOUNT}},
    {"fields": ["cab_type", "pickup_year", "passenger_count"],
     "filter": {"op": "Difference", "args": [{"cond": [AMOUNT, ">=", 25]},
                                             {"row": ["dist_miles", 1]}]},
     "aggregate": {"sum": AMOUNT}},
    {"fields": ["pickup_year", "cab_type"]},
]


@pytest.mark.parametrize("call", GROUPBYS,
                         ids=lambda c: queries.render_call(
                             dict(c, call="GroupBy"))[8:60])
def test_the_bincount_path_equals_the_general_path(call, taxi_shard):
    call = dict(call, call="GroupBy")
    by_codes = queries.groupby_by_codes(call, taxi_shard)
    by_planes = queries.groupby_by_planes(call, taxi_shard)
    assert by_codes.dtype == by_planes.dtype == np.int64
    assert by_codes.shape == by_planes.shape
    assert (by_codes == by_planes).all()
    assert by_codes.sum() > 0
    shape = tuple({"cab_type": 3, "passenger_count": 10, "pickup_year": 8}[f]
                  for f in call["fields"])
    assert by_codes.shape == ((2,) if "aggregate" in call else ()) + shape
    # ``partial`` takes either, and a plain GroupBy the general one
    assert (queries.partial(call, taxi_shard) == by_planes).all()


def test_both_paths_agree_on_a_narrow_shard_that_a_write_made():
    cell = manifest.cell("taxi333m.ingest_c1")
    write = [t for t in cell["traffic"]["templates"] if "write" in t][0]
    calls = [c for k in range(9) for c in traffic.write_calls(
        write["write"], cell["config"]["dataset"], SEED, k, 5 << 20)]
    tail = queries.written(calls, loader.dataset_field_rows(cell["config"]),
                           [AMOUNT])
    assert tail["ints"][AMOUNT].size == 144       # of 192 padded columns
    for call in GROUPBYS:
        call = dict(call, call="GroupBy")
        by_codes = queries.groupby_by_codes(call, tail)
        assert (by_codes == queries.groupby_by_planes(call, tail)).all()
        counts = by_codes[0] if "aggregate" in call else by_codes
        assert 0 < counts.sum() <= 144
    # a padding column holds no value and meets no condition, not even !=
    for op, v in (("!=", 10 ** 6), (">=", 0), ("<", 10 ** 6)):
        count = {"call": "Count", "of": {"cond": [AMOUNT, op, v]}}
        assert queries.partial(count, tail) == 144


def test_a_field_with_a_column_in_two_rows_keeps_the_general_path():
    cell = manifest.cell("pibench1b.point_c1")
    data = cell["generate"](cell["config"]["dataset"], SEED, 0)
    data["ints"] = {"v": (np.arange(bitmaps.SHARD_WIDTH) % 1000)
                    .astype(np.int32)}
    keep = {"op": "Intersect", "args": [{"row": ["f", 0]}, {"row": ["f", 1]},
                                        {"cond": ["v", "<", 100]}]}
    call = {"call": "GroupBy", "fields": ["f"], "filter": keep,
            "aggregate": {"sum": "v"}}
    assert queries.groupby_by_codes(call, data) is None
    got = queries.partial(call, data)
    kept = bitmaps.unpack_bits(
        queries.eval_bitmap(keep, data).view(np.uint32))
    rows = [bitmaps.unpack_bits(r) & kept for r in data["sets"]["f"][:3]]
    assert got[0, :3].tolist() == [int(r.sum()) for r in rows]
    assert got[1, :3].tolist() == [int(data["ints"]["v"][r].sum())
                                   for r in rows]
    assert got[0, 0] == got[0, 1] == kept.sum() > got[0, 2] > 0


# -- array containers -------------------------------------------------------------------

def _block(n_bits, rng):
    bits = np.zeros(65536, bool)
    bits[rng.choice(65536, n_bits, replace=False)] = True
    return bits


def _containers(blob):
    """[(key, type, cardinality, offset)] of a fragment file."""
    n = int(np.frombuffer(blob[4:8], "<u4")[0])
    meta = np.frombuffer(blob[8:8 + 12 * n], "<u8,<u2,<u2")
    offsets = np.frombuffer(blob[8 + 12 * n:8 + 16 * n], "<u4")
    return [(int(k), int(t), int(c) + 1, int(o))
            for (k, t, c), o in zip(meta.tolist(), offsets)]


@pytest.mark.parametrize("n_bits,kind", [(0, None), (1, 1), (4096, 1),
                                         (4097, 2)])
def test_a_block_reads_back_and_takes_the_container_the_format_gives_it(
        n_bits, kind):
    rng = np.random.default_rng(n_bits)
    rows = np.zeros((3, bitmaps.SHARD_WIDTH), bool)
    rows[1, 5 * 65536:6 * 65536] = _block(n_bits, rng)    # the block asked for
    rows[2, :65536] = _block(70, rng)                     # and others around
    rows[2, 65536:2 * 65536] = _block(40000, rng)
    rows[2, 15 * 65536:] = _block(4096, rng)
    words = bitmaps.pack_bits(rows)
    blob = bitmaps.serialize_rows(words)
    want = np.flatnonzero(rows.reshape(-1)).astype(np.uint64)
    assert np.array_equal(roaring.deserialize(blob), want)
    assert np.array_equal(roaring._deserialize_pilosa(memoryview(blob)), want)
    # no block here is runny: the program's minimal writer agrees
    assert roaring.serialize(want) == blob
    found = {k: (t, c) for k, t, c, _ in _containers(blob)}
    assert found.get(16 + 5) == (None if kind is None else (kind, n_bits))
    assert found[32] == (1, 70) and found[33] == (2, 40000)
    assert found[47] == (1, 4096)
    # the data lies back to back in key order: 2 bytes a bit or 8 KiB
    end = 8 + 16 * len(found)
    for _, t, c, off in _containers(blob):
        assert off == end
        end += 2 * c if t == 1 else 8192
    assert end == len(blob)


def test_a_fragment_with_nothing_in_it_is_a_header():
    empty = bitmaps.serialize_rows(np.zeros((2, bitmaps.WORDS), np.uint32))
    assert empty == np.array([12348, 0, 0, 0], "<u2").tobytes()
    assert len(roaring.deserialize(empty)) == 0


# -- the loader ---------------------------------------------------------------------------

def _fragments(config, data_dir):
    for field, view in loader.views(config):
        d = loader.fragment_dir(data_dir, config["index"], field, view)
        for shard in sorted(os.listdir(d), key=int):
            with open(os.path.join(d, shard), "rb") as fh:
                yield field, int(shard), fh.read()


# bytes of two shards at seed 1, and (array, bitmap) containers in them
WRITTEN = {"pibench1b": (8667680, 0, 1056),
           "taxi333m": (8794522, 1280, 800),
           "taxi-full-mesh4": (24608900, 6255, 1184)}


@pytest.mark.parametrize("name", sorted(WRITTEN))
def test_the_loaders_files_hold_the_datasets_bits_as_a_roaring_writer_would(
        name, tmp_path):
    config = manifest._load("configs", name, "test")
    _, written = loader.load(config, str(tmp_path), 1, 2, [], workers=2)
    gen = manifest.datasets.generator(config["dataset"]["kind"])
    shards = [gen(config["dataset"], 1, s) for s in range(2)]
    int_fields = config["dataset"].get("int_fields", {})
    arrays = bitmaps_ = 0
    for field, shard, blob in _fragments(config, str(tmp_path)):
        if field == "_exists":
            words = np.full((1, bitmaps.WORDS), 0xFFFFFFFF, np.uint32)
        elif field in int_fields:
            words = bitmaps.bsi_rows(
                shards[shard]["ints"][field],
                bitmaps.bsi_depth(int_fields[field]["max"]))
        else:
            words = shards[shard]["sets"][field]
        want = np.flatnonzero(np.unpackbits(
            words.view(np.uint8), bitorder="little")).astype(np.uint64)
        assert np.array_equal(roaring.deserialize(blob), want), field
        for _, kind, bits, _ in _containers(blob):
            assert kind == (1 if bits <= 4096 else 2)
            arrays, bitmaps_ = arrays + (kind == 1), bitmaps_ + (kind == 2)
        if name == "pibench1b":
            # every block a quarter full: the program's bulk writer of
            # bitmap containers leaves the same file
            assert blob == roaring.serialize_dense(words)
    # the thin rows of dist_miles, passenger_count, the days and minutes
    assert (written, arrays, bitmaps_) == WRITTEN[name]
    assert written == sum(len(b) for _, _, b in _fragments(config,
                                                           str(tmp_path)))


def test_the_probe_rehearses_to_a_correct_line_from_array_containers(
        tmp_path, monkeypatch):
    """A whole rehearsal of the probe at two shards of ``taxi333m``,
    whose thin blocks are array containers (above): the oracle does not
    know how the files are written, so ``correct`` says that the program
    answers from them what the bits say."""
    for key in list(os.environ):
        if key.startswith(("XLA_", "TPU_", "LIBTPU")):
            monkeypatch.delenv(key)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "3")
    monkeypatch.setattr(bench_run, "WARMUP_TIMEOUT_S", 120.0)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", "taxi333m.analytic_c1", "--seed",
                             str(SEED), "--seconds", "2", "--trace", "0",
                             "--rehearse", "--shards", "2"])
    assert rc == 0, err.getvalue()[-3000:]
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 5
    # all in bitmap containers two such shards were 17,072,736 bytes
    assert line["samples"]["bytes_written"] < 0.8 * 17072736


def test_a_range_row_has_no_byte_count():
    calls = [{"call": "Count", "of": {"op": "Intersect", "args": [
        IN_CAB, {"cond": [AMOUNT, "<", 10]}]}}]
    with pytest.raises(ValueError, match="no byte count"):
        roofline.required_row_bytes(calls, 318)
