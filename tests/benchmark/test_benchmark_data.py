"""Datasets, the request model, the oracle and the traffic generator,
at two shards: every template's oracle answer equals a brute force over
unpacked bits; the seed decides the request sequence."""

import json
import operator
import os

import numpy as np
import pytest

from benchmark import bitmaps, load, loader, manifest, queries, traffic

CELLS = ("pibench1b.point_c1", "pibench1b.intersect_c32", "taxi333m.dash_c1",
         "pibench1b.trees_c32", "taxi333m.dash_c8", "taxi333m.analytic_c1")
N_SHARDS = 2
SEED = 2_400_000_011          # over 2**31, as the driver's are
CMP = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
       ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def _cell_templates():
    for name in CELLS:
        for t in manifest.cell(name)["traffic"]["templates"]:
            yield pytest.param(name, t["name"], id=f"{name}:{t['name']}")


_SHARDS: dict = {}


def _shards(cell):
    """Both shards of a config, generated once, with every set field
    and int field unpacked to per-column arrays for the brute force."""
    key = cell["workload"]["config"]
    if key not in _SHARDS:
        out = []
        for s in range(N_SHARDS):
            data = cell["generate"](cell["config"]["dataset"], SEED, s)
            bits = {f: np.stack([bitmaps.unpack_bits(r) for r in rows])
                    for f, rows in data["sets"].items()}
            out.append((data, bits))
        _SHARDS[key] = out
    return _SHARDS[key]


def _brute_bitmap(b, data, bits):
    """bool[2^20], column by column, from the unpacked rows and the
    int field's values."""
    if "row" in b:
        field, row = b["row"]
        return bits[field][row]
    if "cond" in b:
        field, op, *v = b["cond"]
        vals = data["ints"][field]
        if op == "between":
            return np.array([v[0] <= x <= v[1] for x in vals.tolist()])
        return CMP[op](vals, v[0])
    args = [_brute_bitmap(a, data, bits) for a in b["args"]]
    op = b["op"]
    if op == "Not":
        return np.logical_not(args[0])
    out = args[0]
    for a in args[1:]:
        out = {"Intersect": np.logical_and(out, a),
               "Union": np.logical_or(out, a),
               "Difference": np.logical_and(out, np.logical_not(a)),
               "Xor": np.logical_xor(out, a)}[op]
    return out


def _brute(call, shards):
    """The server's JSON for one call, by counting columns."""
    kind = call["call"]
    if kind == "Count":
        return sum(int(_brute_bitmap(call["of"], data, bits).sum())
                   for data, bits in shards)
    if kind == "TopN":
        n_rows = shards[0][1][call["field"]].shape[0]
        counts = [0] * n_rows
        for data, bits in shards:
            keep = _brute_bitmap(call["filter"], data, bits) \
                if call.get("filter") else True
            for r in range(n_rows):
                counts[r] += int((bits[call["field"]][r] & keep).sum())
        order = sorted(range(n_rows), key=lambda r: (-counts[r], r))
        order = order[:call["n"]] if call.get("n") else order
        return [{"id": r, "count": counts[r]} for r in order if counts[r]]
    if kind == "Sum":
        total = count = 0
        for data, bits in shards:
            keep = _brute_bitmap(call["filter"], data, bits)
            total += int(data["ints"][call["field"]][keep].sum(dtype=np.int64))
            count += int(keep.sum())
        return {"value": total, "count": count}
    if kind == "GroupBy":
        fields, out = call["fields"], []
        for rows in np.ndindex(*(shards[0][1][f].shape[0] for f in fields)):
            count = total = 0
            for data, bits in shards:
                keep = _brute_bitmap(call["filter"], data, bits) \
                    if call.get("filter") else True
                for f, r in zip(fields, rows):
                    keep = keep & bits[f][r]
                count += int(keep.sum())
                if call.get("aggregate"):
                    vals = data["ints"][call["aggregate"]["sum"]]
                    total += sum(vals[keep].tolist())
            if count:
                out.append({"group": [{"field": f, "rowID": r}
                                      for f, r in zip(fields, rows)],
                            "count": count})
                if call.get("aggregate"):
                    out[-1]["agg"] = total
        return out
    raise AssertionError(kind)


@pytest.mark.parametrize("cell_name,template", _cell_templates())
def test_oracle_equals_brute_force_over_unpacked_bits(cell_name, template):
    cell = manifest.cell(cell_name)
    t = next(t for t in cell["traffic"]["templates"] if t["name"] == template)
    rows = loader.dataset_field_rows(cell["config"])
    calls = traffic.instantiate(t, rows, np.random.default_rng(7))
    shards = _shards(cell)
    for call in calls[:4]:      # q2 repeats one shape for every row
        total = sum(queries.partial(call, data) for data, _ in shards)
        assert queries.finish(call, total) == _brute(call, shards)


@pytest.mark.parametrize("cell_name", ["pibench1b.point_c1", "taxi333m.dash_c8"])
def test_fields_hold_what_the_config_states(cell_name):
    cell = manifest.cell(cell_name)
    data, bits = _shards(cell)[0]
    ds = cell["config"]["dataset"]
    if ds["kind"] == "uniform_rows":
        f = bits[ds["field"]]
        assert f.shape == (ds["rows"], bitmaps.SHARD_WIDTH)
        assert abs(f.mean() - ds["density"]) < 0.002
    else:
        for field, spec in ds["set_fields"].items():
            # exactly one row per column (mutex-style), at the shares
            assert (bits[field].sum(axis=0) == 1).all()
            share = np.asarray(spec["shares"]) / sum(spec["shares"])
            got = bits[field].mean(axis=1)
            assert np.abs(got - share).max() < 0.003
        for field, spec in ds["int_fields"].items():
            v = data["ints"][field]
            assert v.min() >= 0 and v.max() <= spec["max"]


def test_fragment_round_trip_and_bsi_rows():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 1 << 32, size=(3, bitmaps.WORDS), dtype=np.uint32)
    rows[1] = 0                      # an empty row writes no container
    blob = bitmaps.serialize_rows(rows)   # half full: bitmap containers
    magic, version, n = np.frombuffer(blob[:8], "<u2,<u2,<u4")[0]
    assert (magic, version, n) == (12348, 0, 32)
    keys = np.frombuffer(blob[8:8 + 12 * n], "<u8,<u2,<u2")["f0"]
    assert keys.tolist() == list(range(16)) + list(range(32, 48))
    assert blob[-8192:] == rows[2, -2048:].tobytes()
    vals = rng.integers(0, 1000, size=bitmaps.SHARD_WIDTH).astype(np.int32)
    bsi = bitmaps.bsi_rows(vals, bitmaps.bsi_depth(999))
    assert bsi.shape[0] == 12 and (bsi[0] == 0xFFFFFFFF).all()
    back = sum(bitmaps.unpack_bits(bsi[2 + b]).astype(np.int64) << b
               for b in range(10))
    assert (back == vals).all() and not bsi[1].any()


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_seed_decides_the_request_sequence(cell_name):
    cell = manifest.cell(cell_name)
    rows = loader.dataset_field_rows(cell["config"])

    def sequence(seed):
        pool = traffic.Pool(cell["traffic"], rows, seed)
        return [pool.requests[r]["pql"] for r in pool.client_order(0)[:200]]
    assert sequence(SEED) == sequence(SEED)
    assert sequence(SEED) != sequence(SEED + 1)
    pool = traffic.Pool(cell["traffic"], rows, SEED)
    assert not np.array_equal(pool.client_order(0), pool.client_order(1))
    # every seed sends the same shares of every template
    per = len(pool.entries) // len(cell["traffic"]["templates"])
    counts = {}
    for rid in pool.entries:
        name = pool.requests[rid]["template"]
        counts[name] = counts.get(name, 0) + 1
    assert all(abs(c - per) <= 1 for c in counts.values()), counts
    # and every round of a client's walk holds each template once, so a
    # window of any length sends the same work whatever the seed
    names = sorted(t["name"] for t in cell["traffic"]["templates"])
    walk = pool.client_order(0)
    assert len(walk) == len(pool.entries) // len(names) * len(names)
    for lo in range(0, len(walk), len(names)):
        assert sorted(pool.requests[r]["template"]
                      for r in walk[lo:lo + len(names)]) == names
    assert sorted(walk.tolist()) == sorted(pool.entries[:len(walk)])


def test_the_trees_pool_holds_4096_entries_over_exactly_the_8_skeletons():
    cell = manifest.cell("pibench1b.trees_c32")
    pool = traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(cell["config"]), SEED)
    assert len(pool.entries) == 4096
    skeletons = {}
    for rid in pool.entries:
        r = pool.requests[rid]
        shape = "".join(ch for ch in r["pql"] if not ch.isdigit())
        skeletons.setdefault(r["template"], set()).add(shape)
    assert len(skeletons) == 8
    assert all(len(shapes) == 1 for shapes in skeletons.values())
    assert len({next(iter(s)) for s in skeletons.values()}) == 8
    for t in cell["traffic"]["templates"]:
        leaves = json.dumps(t["calls"]).count('"row"')
        assert 2 <= leaves <= 6


def _bound(template_node, request_node, out):
    """Collect {parameter: value} by walking a template's calls beside
    one of its instances."""
    if isinstance(template_node, dict):
        for k in template_node:
            _bound(template_node[k], request_node[k], out)
    elif isinstance(template_node, list):
        for t, r in zip(template_node, request_node):
            _bound(t, r, out)
    elif isinstance(template_node, str) and template_node.startswith("$"):
        out.setdefault(template_node[1:], set()).add(request_node)


@pytest.mark.parametrize("cell_name", CELLS)
def test_the_cover_names_every_row_in_every_position(cell_name):
    cell = manifest.cell(cell_name)
    rows = loader.dataset_field_rows(cell["config"])
    pool = traffic.Pool(cell["traffic"], rows, SEED)
    other = traffic.Pool(cell["traffic"], rows, SEED + 5)
    # the cover is the same for every seed
    assert [pool.requests[r]["pql"] for r in pool.cover] == \
        [other.requests[r]["pql"] for r in other.cover]
    for t in cell["traffic"]["templates"]:
        seen: dict = {}
        for rid in pool.cover:
            if pool.requests[rid]["template"] == t["name"]:
                got, n = pool.requests[rid]["calls"], len(t["calls"])
                for lo in range(0, len(got), n):   # one chunk per foreach
                    _bound(t["calls"], got[lo:lo + n], seen)
        for name, spec in t.get("params", {}).items():
            assert seen[name] == set(range(rows[spec["field"]]))
        for name, spec in t.get("foreach", {}).items():
            assert seen[name] == set(range(rows[spec["field"]]))


def test_a_corrupted_expected_body_makes_the_run_incorrect():
    expected = [[41], [[{"id": 0, "count": 3}]]]
    good = [[(0, 0.0, 0.001, 200, b'{"results": [41]}'),
             (1, 0.001, 0.002, 200,
              b'{"results": [[{"id": 0, "count": 3}]]}')]]
    verdict = load.judge(good, expected)
    assert (verdict["attempted"], verdict["wrong"], verdict["failed"]) == \
        (2, 0, 0)
    assert load.judge(good, [[42], expected[1]])["wrong"] == 1
    shed = [[(0, 0.0, 0.001, 503, b"busy")]]
    assert load.judge(shed, expected)["failed"] == 1
    torn = [[(0, 0.0, 0.001, 200, b'{"results": [4')]]
    assert load.judge(torn, expected)["wrong"] == 1


def test_loader_writes_every_view_and_sums_partials(tmp_path):
    cell = manifest.cell("taxi333m.dash_c8")
    config = cell["config"]
    calls = [{"call": "Count", "of": {"row": ["cab_type", 0]}},
             {"call": "TopN", "field": "pickup_year"}]
    totals, written = loader.load(config, str(tmp_path), SEED, N_SHARDS,
                                  calls, workers=2)
    shards = _shards(cell)
    assert int(totals[0]) == _brute(calls[0], shards)
    assert queries.finish(calls[1], totals[1]) == _brute(calls[1], shards)
    on_disk = 0
    for field, view in loader.views(config):
        d = loader.fragment_dir(str(tmp_path), config["index"], field, view)
        assert sorted(os.listdir(d)) == ["0", "1"]
        on_disk += sum(os.path.getsize(os.path.join(d, f))
                       for f in os.listdir(d))
    assert on_disk == written
