"""Writes in the harness: the two write calls, the write template of a
traffic mix, the oracle with a state, the judge's replay, the second
boot, the three write controls and a write lost underneath a whole
rehearsal; and, for every traffic file the benchmark had before it
learned writes, the same pool and the same walks as before (digests
computed on the parent, f4fa80d).  Answers and counts only."""

import contextlib
import hashlib
import http.client
import io
import json
import operator
import os
import re

import numpy as np
import pytest

from benchmark import (bitmaps, controls, load, loader, manifest, queries,
                       traffic)
from benchmark import run as bench_run
from benchmark import server as bench_server

CELL = "taxi333m.ingest_c1"
SEED, N_SHARDS = 3_300_000_019, 2
FIRST_COLUMN = N_SHARDS * bitmaps.SHARD_WIDTH
NEW_METRICS = ("client.write_ms", "client.read_after_write_ms",
               "ingest.absorbs_per_write", "ingest.compactions_in_window",
               "planes.builds_in_window",
               "index.shard_set_rebuilds_in_window")


# -- the request model ---------------------------------------------------------

@pytest.mark.parametrize("call,pql", [
    ({"call": "Set", "field": "cab_type", "column": 333447168, "row": 2},
     "Set(333447168, cab_type=2)"),
    ({"call": "SetValue", "field": "total_amount_dollars",
      "column": 333447169, "value": 0},
     "Set(333447169, total_amount_dollars=0)"),
])
def test_a_write_call_renders_to_pql(call, pql):
    assert queries.render_call(call) == pql
    assert queries.render([call, call]) == pql + pql


@pytest.mark.parametrize("calls,message", [
    ([{"call": "Set", "field": "a", "column": 7, "row": 1}] * 2,
     "written twice"),
    ([{"call": "Set", "field": "a", "column": 7, "row": 1}],
     "lacks a value"),
    ([{"call": "SetValue", "field": "v", "column": 7, "value": 1},
      {"call": "Count", "of": {"row": ["a", 0]}, "column": 7}],
     "not a write call"),
])
def test_what_does_not_add_to_every_read_is_refused(calls, message):
    with pytest.raises(ValueError, match=message):
        queries.written(calls, {"a": 3}, ["v"])


# -- the write template ----------------------------------------------------------

@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


@pytest.fixture(scope="module")
def pool(cell):
    return traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(cell["config"]), SEED)


def _write_calls(cell, pool, k, seed=SEED):
    return traffic.write_calls(pool.write, cell["config"]["dataset"], seed, k,
                               FIRST_COLUMN)


def test_the_cell_is_the_one_the_issue_names(cell, pool):
    mix = cell["traffic"]
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == "taxi333m"
    assert (mix["loop"], mix["clients"], mix["connection"], mix["pool"],
            mix["trace_seconds"]) == ("closed", 1, "keepalive", 1000, 3)
    dash = manifest.cell("taxi333m.dash_c1")["traffic"]["templates"]
    reads = [t for t in mix["templates"] if "write" not in t]
    assert [{k: v for k, v in t.items() if k != "weight"} for t in reads] \
        == dash
    assert all(t["weight"] == 4 for t in reads)
    assert pool.cycle_len == 21 and pool.write["rides"] == 16
    # new columns start where taxi333m's loaded shards end
    assert cell["config"]["shards"] * bitmaps.SHARD_WIDTH == 333_447_168 \
        == cell["config"]["columns"]


def test_a_write_request_is_16_new_rides_of_five_calls_each(cell, pool):
    calls = _write_calls(cell, pool, 0)
    assert len(calls) == 80
    assert [c["call"] for c in calls[:5]] == ["Set"] * 4 + ["SetValue"]
    assert [c["field"] for c in calls[:5]] == [
        "cab_type", "passenger_count", "pickup_year", "dist_miles",
        "total_amount_dollars"]
    columns = [c["column"] for c in calls]
    assert columns == [FIRST_COLUMN + i // 5 for i in range(80)]
    # 16 ops on each of five fragments of one shard
    by_field = {}
    for c in calls:
        by_field.setdefault(c["field"], set()).add(
            c["column"] // bitmaps.SHARD_WIDTH)
    assert by_field == {f: {N_SHARDS} for f in by_field} and len(by_field) == 5
    ds = cell["config"]["dataset"]
    for c in calls:
        if c["call"] == "Set":
            assert 0 <= c["row"] < len(ds["set_fields"][c["field"]]["shares"])
        else:
            assert 0 <= c["value"] <= ds["int_fields"][c["field"]]["max"]


def test_the_kth_write_is_drawn_from_seed_and_k_and_writes_nothing_twice(
        cell, pool):
    seen = set()
    for k in range(40):
        calls = _write_calls(cell, pool, k)
        assert calls == _write_calls(cell, pool, k)
        cols = {c["column"] for c in calls}
        assert min(cols) == FIRST_COLUMN + 16 * k and len(cols) == 16
        assert not cols & seen
        seen |= cols
    assert _write_calls(cell, pool, 3) != _write_calls(cell, pool, 3, SEED + 1)
    drawn = [c["row"] for k in range(40) for c in _write_calls(cell, pool, k)
             if c["field"] == "passenger_count"]
    assert max(set(drawn), key=drawn.count) == 1    # 1 passenger about 70 %


def test_every_round_of_the_walk_holds_20_reads_and_one_write(pool):
    walk = pool.client_order(0)
    assert len(walk) == 21 * (1000 // 21)
    names = [t["template"] for t in pool.requests]
    for r in range(len(walk) // 21):
        one = walk[21 * r:21 * (r + 1)]
        assert (one == traffic.WRITE).sum() == 1
        sent = [names[rid] for rid in one if rid != traffic.WRITE]
        assert sorted(set(sent)) == sorted(set(names)) and len(sent) == 20
        assert all(sent.count(n) == 4 for n in set(sent))
    assert all("Set(" not in r["pql"] for r in pool.requests)
    assert traffic.WRITE not in pool.cover


@pytest.mark.parametrize("change,message", [
    (lambda mix: mix.update(clients=2), "one client"),
    (lambda mix: mix["templates"][4]["calls"][0].update(
        of={"op": "Not", "args": [{"row": ["cab_type", 0]}]}), "Not beside"),
    (lambda mix: mix["templates"].append(dict(mix["templates"][-1],
                                              name="again")), "one write"),
])
def test_a_write_mix_the_judge_cannot_judge_is_refused(cell, change, message):
    mix = json.loads(json.dumps(cell["traffic"]))
    change(mix)
    with pytest.raises(traffic.MixError, match=message):
        traffic.Pool(mix, loader.dataset_field_rows(cell["config"]), SEED)


# -- the tail against a from-scratch count ------------------------------------------

@pytest.fixture(scope="module")
def base(cell, pool):
    """Per distinct call: the totals over the two loaded shards."""
    calls, index = pool.distinct_calls()
    totals = None
    for s in range(N_SHARDS):
        data = cell["generate"](cell["config"]["dataset"], SEED, s)
        totals = queries.combine(
            totals, [queries.partial(c, data) for c in calls])
    return calls, index, totals


def _state(cell, pool, base):
    writes, write_calls, oracle = bench_run.write_state(
        pool, cell["config"], SEED, N_SHARDS, *base)
    return writes, write_calls, oracle


def _rides(cell, pool, ks, lose=()):
    """Ride by ride, as dicts: the from-scratch view of what was
    written (``lose``: (k, ride) pairs left out)."""
    out = []
    for k in ks:
        calls = _write_calls(cell, pool, k)
        for j in range(16):
            if (k, j) in lose:
                continue
            out.append({c["field"]: c.get("row", c.get("value"))
                        for c in calls[5 * j:5 * j + 5]})
    return out


CMP = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
       ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def _holds(b, ride):
    if "row" in b:
        return ride[b["row"][0]] == b["row"][1]
    if "cond" in b:
        field, op, *v = b["cond"]
        if op == "between":
            return v[0] <= ride[field] <= v[1]
        return CMP[op](ride[field], v[0])
    args = [_holds(a, ride) for a in b["args"]]
    return {"Intersect": all(args), "Union": any(args),
            "Difference": args[0] and not any(args[1:]),
            "Xor": sum(args) % 2 == 1}[b["op"]]


def _from_scratch(call, total, rides, field_rows):
    """The finished answer from the base total and the rides counted
    one by one, with no packed bits anywhere."""
    kind = call["call"]
    if kind == "Count":
        return int(total) + sum(_holds(call["of"], r) for r in rides)
    keep = [r for r in rides
            if not call.get("filter") or _holds(call["filter"], r)]
    if kind == "TopN":
        counts = [int(x) for x in total]
        for r in keep:
            counts[r[call["field"]]] += 1
        order = sorted(range(len(counts)), key=lambda i: (-counts[i], i))
        order = order[:call["n"]] if call.get("n") else order
        return [{"id": i, "count": counts[i]} for i in order if counts[i]]
    if kind == "Sum":
        return {"value": int(total[0]) + sum(r[call["field"]] for r in keep),
                "count": int(total[1]) + len(keep)}
    if kind == "GroupBy":
        groups = np.array(total)
        for r in keep:
            group = tuple(r[f] for f in call["fields"])
            if call.get("aggregate"):
                groups[(0,) + group] += 1
                groups[(1,) + group] += r[call["aggregate"]["sum"]]
            else:
                groups[group] += 1
        return queries.finish(call, groups)
    raise AssertionError(kind)


EXTRA_CALLS = [
    {"call": "Sum", "field": "total_amount_dollars"},
    {"call": "Count", "of": {"op": "Union", "args": [
        {"row": ["cab_type", 1]}, {"row": ["pickup_year", 7]}]}},
    {"call": "Count", "of": {"op": "Difference", "args": [
        {"row": ["passenger_count", 1]}, {"row": ["cab_type", 0]}]}},
    {"call": "Count", "of": {"op": "Xor", "args": [
        {"row": ["dist_miles", 1]}, {"row": ["cab_type", 0]}]}},
    {"call": "GroupBy", "fields": ["cab_type", "pickup_year", "dist_miles"]},
    {"call": "Count", "of": {"cond": ["total_amount_dollars", ">", 12]}},
    {"call": "Count", "of": {"op": "Difference", "args": [
        {"cond": ["total_amount_dollars", "!=", 7]},
        {"cond": ["total_amount_dollars", "between", 10, 30]}]}},
    {"call": "Sum", "field": "total_amount_dollars", "filter": {
        "op": "Intersect", "args": [
            {"row": ["cab_type", 0]},
            {"cond": ["total_amount_dollars", "<=", 20]}]}},
    {"call": "GroupBy", "fields": ["passenger_count"],
     "aggregate": {"sum": "total_amount_dollars"}},
    {"call": "GroupBy", "fields": ["passenger_count", "pickup_year"],
     "filter": {"op": "Union", "args": [
         {"row": ["cab_type", 1]},
         {"cond": ["total_amount_dollars", ">=", 15]}]},
     "aggregate": {"sum": "total_amount_dollars"}},
    {"call": "GroupBy", "fields": ["cab_type", "pickup_year"],
     "filter": {"cond": ["total_amount_dollars", "<", 15]}},
]


def _template_names():
    mix = manifest.cell(CELL)["traffic"]
    return [t["name"] for t in mix["templates"] if "write" not in t]


@pytest.mark.parametrize("template", _template_names())
def test_base_plus_the_tails_partial_equals_a_from_scratch_count(
        cell, pool, base, template):
    calls, index, totals = base
    _, _, oracle = _state(cell, pool, base)
    for k in range(23):          # 368 rides: more than five 64-bit words
        oracle.absorb(k)
    rides = _rides(cell, pool, range(23))
    field_rows = loader.dataset_field_rows(cell["config"])
    rids = [i for i, r in enumerate(pool.requests)
            if r["template"] == template]
    assert rids
    for rid in rids:
        want = [_from_scratch(calls[i], totals[i], rides, field_rows)
                for i in index[rid]]
        assert oracle[rid] == want
        # and the writes moved the answer: the base alone is wrong now
    stale = [[queries.finish(calls[i], totals[i]) for i in index[rid]]
             for rid in rids]
    assert stale != [oracle[rid] for rid in rids]


@pytest.mark.parametrize("call", EXTRA_CALLS,
                         ids=lambda c: queries.render_call(c)[:40])
def test_every_other_call_kind_adds_up_the_same_way(cell, pool, call):
    field_rows = loader.dataset_field_rows(cell["config"])
    data = cell["generate"](cell["config"]["dataset"], SEED, 0)
    total = queries.partial(call, data)
    ks = range(5)
    tail = queries.written(
        [c for k in ks for c in _write_calls(cell, pool, k)], field_rows,
        pool.write["int_fields"])
    got = queries.finish(call, total + queries.partial(call, tail))
    assert got == _from_scratch(call, total, _rides(cell, pool, ks),
                                field_rows)
    assert got != queries.finish(call, total)       # the rides moved it


# -- the judge's replay ------------------------------------------------------------

def _sound_records(pool, oracle, write_calls, n=300, status=200):
    """What a sound program answers along the client's walk: the state
    advances at every write (``oracle`` is left at the end)."""
    acks = json.dumps({"results": [True] * write_calls}).encode()
    rec, k = [], len(oracle.writes)
    for i, rid in enumerate(pool.client_order(0)[:n]):
        rid = int(rid)
        if rid == traffic.WRITE:
            oracle.absorb(k)
            rec.append((-1 - k, i * 0.01, i * 0.01 + 0.005, status, acks))
            k += 1
        else:
            rec.append((rid, i * 0.01, i * 0.01 + 0.005, 200,
                        json.dumps({"results": oracle[rid]}).encode()))
    return [rec]


class _Fixed(list):
    """Today's ``expected`` of a read-only cell, deaf to writes."""

    def absorb(self, k):
        pass


def test_the_judge_replays_the_record_and_ends_in_the_final_state(
        cell, pool, base):
    _, write_calls, oracle = _state(cell, pool, base)
    start = oracle.copy()
    records = _sound_records(pool, oracle, write_calls)
    verdict = load.judge(records, start, write_calls)
    assert verdict["attempted"] == 300 and verdict["wrong"] == 0
    assert verdict["failed"] == 0 and verdict["acks_wrong"] == 0
    assert verdict["acked"] == 300 // 21 + (traffic.WRITE in
                                            pool.client_order(0)[294:300])
    assert start.writes == oracle.writes == list(range(verdict["acked"]))
    assert all(start[r] == oracle[r] for r in range(len(pool.requests)))
    # a judge that kept the loaded totals would fail most of these reads
    calls, index, totals = base
    fixed = _Fixed([queries.finish(calls[i], totals[i]) for i in ids]
                   for ids in index)
    assert load.judge(records, fixed, write_calls)["wrong"] > 100


def test_the_first_read_after_a_write_must_already_see_it(cell, pool, base):
    _, write_calls, oracle = _state(cell, pool, base)
    start = oracle.copy()
    (rec,) = _sound_records(pool, oracle, write_calls)
    at = next(i for i, r in enumerate(rec) if r[0] < 0)
    q1 = next(i for i, r in enumerate(pool.requests)
              if r["template"] == "q1_topn_cab_type")
    # the read right after the first write answers as before it
    rec[at + 1] = (q1, rec[at + 1][1], rec[at + 1][2], 200,
                   json.dumps({"results": start[q1]}).encode())
    verdict = load.judge([rec], start.copy(), write_calls)
    assert verdict["wrong"] == 1 and verdict["ok"][at + 1] is False
    assert verdict["ok"].count(False) == 1


@pytest.mark.parametrize("status,body,acked,acks_wrong,failed", [
    (200, json.dumps({"results": [True] * 79 + [False]}), 0, 1, 0),
    (200, json.dumps({"results": [True] * 79}), 0, 1, 0),
    (200, "not json", 0, 1, 0),
    (500, json.dumps({"error": "boom"}), 0, 0, 1),
    (0, "ConnectionResetError", 0, 0, 1),
])
def test_a_write_not_wholly_acknowledged_is_never_assumed_present_or_absent(
        cell, pool, base, status, body, acked, acks_wrong, failed):
    _, write_calls, oracle = _state(cell, pool, base)
    rec = [(-1, 0.0, 0.01, status, body.encode())]
    verdict = load.judge([rec], oracle, write_calls)
    assert (verdict["acked"], verdict["acks_wrong"], verdict["failed"]) \
        == (acked, acks_wrong, failed)
    assert oracle.writes == [] and verdict["ok"] == [False]


def test_the_window_counts_the_writes_and_the_reads_that_follow_them():
    recs = [[(3, 0.00, 0.01, 200, b""), (-1, 0.01, 0.05, 200, b""),
             (4, 0.05, 0.07, 200, b""), (5, 0.07, 0.08, 200, b""),
             (-2, 0.08, 0.14, 200, b"")]]
    stats = load.window_stats(recs, [True] * 5, 0.0, 1.0)
    assert stats["writes"] == 2
    assert stats["write_ms"] == pytest.approx(50.0)
    assert stats["read_after_write_ms"] == pytest.approx(20.0)
    none = load.window_stats([recs[0][:1]], [True], 0.0, 1.0)
    assert none["writes"] == 0 and none["write_ms"] is None
    assert none["read_after_write_ms"] is None


# -- the controls -------------------------------------------------------------------

@pytest.mark.parametrize("control", sorted(controls.WRITE))
def test_the_write_control_comes_out_as_not_correct(cell, pool, base, control):
    _, write_calls, oracle = _state(cell, pool, base)
    start = oracle.copy()
    sound = _sound_records(pool, oracle, write_calls)
    assert load.judge(sound, start.copy(), write_calls)["wrong"] == 0
    broken, final = controls.WRITE[control](sound, start.copy(), write_calls)
    truth = start.copy()
    verdict = load.judge(broken, truth, write_calls)
    assert verdict["acked"] == len(oracle.writes) and not verdict["failed"]
    after_restart_wrong = sum(final[r] != truth[r]
                              for r in range(len(pool.requests)))
    if control == "lost_after_restart":
        assert verdict["wrong"] == 0        # exact all through the window
        assert after_restart_wrong >= 3     # q1, q2 and q3 at the least
    elif control == "stale_read":
        assert 1 <= verdict["wrong"] <= verdict["acked"]
        assert after_restart_wrong == 0
    else:
        assert verdict["wrong"] >= 50       # one ride, missing ever after
        assert after_restart_wrong >= 1
    assert set(controls.ALL) == set(controls.READ) | set(controls.WRITE)


# -- a whole rehearsal, and one with a write lost underneath ------------------------

WARMUP_ROUNDS = 3      # of the walk, in a rehearsal (64 on the chip)


def _rehearse(monkeypatch, tmp, *argv):
    for key in list(os.environ):
        if key.startswith(("XLA_", "TPU_", "LIBTPU")):
            monkeypatch.delenv(key)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "cache"))
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "3")
    monkeypatch.setattr(bench_run, "WARMUP_TIMEOUT_S", 120.0)
    monkeypatch.setattr(bench_run, "WRITE_WARMUP_ROUNDS", WARMUP_ROUNDS)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                             "--seconds", "2", "--rehearse", "--shards", "2",
                             "--out", str(tmp / "out"), *argv])
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("writes")
    with pytest.MonkeyPatch.context() as mp:
        line, err = _rehearse(mp, tmp, "--trace", "1")
    with open(tmp / "out" / "record.json") as fh:
        record = json.load(fh)
    with open(tmp / "out" / "requests.json") as fh:
        record["requests"] = json.load(fh)  # [client, id, send s, ms]
    return line, err, record, sorted(os.listdir(tmp / "out"))


def test_the_cell_rehearses_to_a_correct_line_over_a_restart(rehearsal):
    line, err, record, files = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    assert compared["acked_writes"]["value"] >= 1
    assert compared["write_acks_wrong"] == {"value": 0, "limit": 0}
    assert compared["after_restart_compared"]["value"] >= 100
    assert compared["after_restart_wrong"] == {"value": 0, "limit": 0}
    assert compared["after_restart_failed"] == {"value": 0, "limit": 0}
    assert compared["wrong_answers"] == {"value": 0, "limit": 0}
    # the serving path's facts are read from both children
    facts = [k for k in compared if k.startswith("after_restart_")
             and k[len("after_restart_"):] in compared]
    assert len(facts) == 11
    assert all(c["value"] <= c["limit"] for c in compared.values()
               if "limit" in c)
    assert err.strip().splitlines()[-1] == "correct: True"
    assert {"server.log", "restart.log", "memory.json",
            "restart_memory.json"} <= set(files)


def test_the_line_says_what_the_restart_and_the_warm_up_did(rehearsal):
    line, _, record, _ = rehearsal
    samples = line["samples"]
    assert samples["writes"] == line["compared"]["acked_writes"]["value"]
    assert samples["write_ms"] > 0 and samples["read_after_write_ms"] > 0
    # the set-up wrote once before any plane was built and once in every
    # warm-up round, so the window's first write is request 1 + 3
    sent = [rid for _, rid, _, _ in record["requests"] if rid < 0]
    assert sent[0] == -1 - (1 + WARMUP_ROUNDS)
    assert 0 < samples["restart_to_serving_s"] \
        < samples["restart_to_read_back_s"]
    assert record["run"]["restart_to_read_back_s"] \
        == samples["restart_to_read_back_s"]
    assert record["warmup"]["rounds"] >= WARMUP_ROUNDS + 3


@pytest.mark.parametrize("name", NEW_METRICS + ("fused.compiles_in_window",))
def test_the_cells_per_layer_metric_prints_in_a_traced_rehearsal(
        name, rehearsal):
    line = rehearsal[0]
    decl = manifest.metric(name)
    assert line["metrics"][name]["unit"] == decl["unit"]
    value = line["metrics"][name]["value"]
    if name.startswith("client.") or name == "ingest.absorbs_per_write":
        assert value > 0
    else:
        assert value >= 0
    if name in NEW_METRICS:
        assert decl["workloads"] == [CELL]


def test_a_cell_reports_the_metric_files_that_name_it_entry_or_not():
    bench = manifest.benchmark_json()
    names = [m["name"] for m in manifest.metrics_of(CELL, bench)[1]]
    assert set(NEW_METRICS) | {"fused.compiles_in_window"} <= set(names)
    assert len(names) == len(set(names))
    assert "mesh.launch_wait_ms" not in names       # another cell's
    # a cell that is an entry reports its entries and nothing else
    _, per_layer = manifest.metrics_of("taxi333m.dash_c1", bench)
    assert all(m in bench["per_layer"] for m in per_layer)
    assert not set(NEW_METRICS) & {m["name"] for m in per_layer}


class _LosingConnection(http.client.HTTPConnection):
    """The third write request reaches the server without its last
    ride, and is acknowledged whole all the same: a write lost where
    it is stored."""
    writes = 0

    def request(self, method, url, body=None, **kw):
        self.faked = None
        if body and body.startswith(b"Set("):
            type(self).writes += 1
            if type(self).writes == 3:
                calls = re.findall(rb"Set\([^)]*\)", body)
                self.faked = len(calls)
                body = b"".join(calls[:-5])
        return super().request(method, url, body=body, **kw)

    def getresponse(self):
        resp = super().getresponse()
        if self.faked:
            body = json.dumps({"results": [True] * self.faked}).encode()
            resp.read()
            resp.read = lambda *a: body
        return resp


def test_a_write_lost_underneath_makes_a_whole_run_incorrect(
        tmp_path, monkeypatch):
    monkeypatch.setattr(
        bench_server.Server, "connect",
        lambda self, timeout=300.0: _LosingConnection(
            "127.0.0.1", self.port, timeout=timeout))
    line, err = _rehearse(monkeypatch, tmp_path, "--trace", "0")
    assert _LosingConnection.writes >= 3
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["write_acks_wrong"]["value"] == 0   # it was acknowledged
    lost = compared["wrong_answers"]["value"] \
        + compared["warmup_wrong_answers"]["value"]
    assert lost >= 1 and compared["after_restart_wrong"]["value"] >= 1
    assert err.strip().splitlines()[-1] == "correct: False"
    # the end-to-end metrics of the cell print as every cell's do
    assert set(line["metrics"]) == {"requests_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}


@pytest.mark.parametrize("control,number", [
    ("lost_write", "wrong_answers"), ("stale_read", "wrong_answers"),
    ("lost_after_restart", "after_restart_wrong")])
def test_a_rehearsal_under_a_write_control_reads_false(
        control, number, tmp_path, monkeypatch):
    # a longer window where one stale read in a few has to show: a
    # 3-way Count that no new ride matches reads the same before and after
    line, err = _rehearse(monkeypatch, tmp_path, "--trace", "0",
                          "--control", control, "--seconds", "5")
    assert line["correct"] is False and line["control"] == control
    compared = line["compared"]
    assert compared[number]["value"] >= 1
    assert compared["acked_writes"]["value"] >= 1
    assert compared["write_acks_wrong"]["value"] == 0
    if control == "lost_after_restart":
        assert compared["wrong_answers"]["value"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"


def test_a_write_control_is_refused_on_a_mix_that_does_not_write(capsys):
    rc = bench_run.main(["--workload", "taxi333m.dash_c1", "--seed", "1",
                         "--seconds", "1", "--rehearse", "--shards", "2",
                         "--control", "lost_write"])
    assert rc == 1
    assert "is for a mix that writes" in capsys.readouterr().err


def test_every_limit_of_the_serving_path_is_zero_and_no_config_can_raise_it():
    status = {"deviceHealth": {"stateCode": 0, "faultsTotal": 0,
                               "watchdogTrips": 0, "quarantinedWindows": 0},
              "tenancy": {"pageIns": 3},
              "storage": {"planeBuild": {"buildFailures": 0}},
              "admission": {"shedTotal": 0}, "planeCache": {"evictions": 2}}
    facts = bench_server.health_facts(status, {})
    assert facts["page_ins"] == (3, 0) and facts["plane_evictions"] == (2, 0)
    assert len(facts) == 11 and {lim for _, lim in facts.values()} == {0}
    for name in ("pibench1b", "taxi333m", "taxi-full-mesh4"):
        g = manifest._load("configs", name, "test")["guarantees"]
        assert "limits" not in g
        # the sentence says which cell shows it, and that it is no entry
        assert CELL in g["durability"] and "no entry" in g["durability"]


# -- what the harness sent before, it sends now -------------------------------------

PARENT_DIGESTS = {
    "intersect_c32:1":
        "13e9a8d6b3a71734b6f491308f3cad67c7442bf00107f18ac1b1e3beefaf09c8",
    "intersect_c32:2":
        "52c52970d01f83d3bb63649ed78abb73b392574ddf9fd3c8133bb578a313559b",
    "point_c1:1":
        "3f261a24db1b11afb4b0e954a661a2711b1f6445614b0f49c2fcffd9098b7fae",
    "point_c1:2":
        "90eb00e38ed4f17f188d0da52fde644ec5988966f2a662c859152dbb33c9f1f2",
    "trees_c32:1":
        "525752e0839d4422461171f4436cd7f6997a34eb522f4f7020f4cc145c6fff83",
    "trees_c32:2":
        "fb4481958db7115493445289be4b84c6ddfac37a9e6347aef799c9f9e2c990b7",
    "dashfull_c1:1":
        "b18e103308444f5f56ba4c39155674c54980212fc12a96799ecd310181b2cf2b",
    "dashfull_c1:2":
        "ae4d5f4e614c715fad78eb455098af2ec1c7f25ebe25832e2e2a31e3a463f9ca",
    "dash_c1:1":
        "74703c070000aa075ec262d2c101462370ea8be356ead5621e2881fdb67ddbd8",
    "dash_c1:2":
        "b40bc29480cf276d20fe800462bc73a67edc6c1dfcb54ff2a86a4630e3f9a2ff",
    "dash_c8:1":
        "3840aabf22208983be41b7bfd52edd284ce6ecef1f828a4171524134edb75d1d",
    "dash_c8:2":
        "223c7aab776a2304cc95fd30131fdab34a1971cce70622f0d52a267ae2c750c5",
}
CELL_OF = {"intersect_c32": "pibench1b.intersect_c32",
           "point_c1": "pibench1b.point_c1",
           "trees_c32": "pibench1b.trees_c32",
           "dashfull_c1": "taxi-full-mesh4.dashfull_c1",
           "dash_c1": "taxi333m.dash_c1", "dash_c8": "taxi333m.dash_c8"}


def _sent(pool, clients):
    """Every body of the pool, the pool's entries and cover, and every
    client's walk, hashed (the caller may add to it)."""
    h = hashlib.sha256()
    for r in pool.requests:
        h.update(r["pql"].encode())
        h.update(b"\n")
    h.update(json.dumps(pool.entries).encode())
    h.update(json.dumps(pool.cover).encode())
    for c in range(clients):
        h.update(pool.client_order(c).astype("<i8").tobytes())
    return h


# the write mix, computed on afa3bcb before the request model learned
# the analytic calls: the read-only digest's parts and, after them, the
# first 64 write bodies (columns from taxi333m's 318 shards on)
PARENT_INGEST_DIGESTS = {
    1: "5ba2c5f6bf4cecf99e906a2ce807a1f2ce8405bc59811174203ef694d6ffe4a6",
    2: "92f0ed66b4fa8589809c71b1b8633c14f060ec0cbbf269245fbfee2b42ac9af3",
}


@pytest.mark.parametrize("seed", sorted(PARENT_INGEST_DIGESTS))
def test_the_write_mix_sends_what_the_parent_sent(seed, cell):
    mix, config = cell["traffic"], cell["config"]
    pool = traffic.Pool(mix, loader.dataset_field_rows(config), seed)
    h = _sent(pool, 1)
    first = config["shards"] * bitmaps.SHARD_WIDTH
    for k in range(64):
        h.update(queries.render(traffic.write_calls(
            pool.write, config["dataset"], seed, k, first)).encode())
        h.update(b"\n")
    assert h.hexdigest() == PARENT_INGEST_DIGESTS[seed]


@pytest.mark.parametrize("key", sorted(PARENT_DIGESTS))
def test_a_read_only_mix_sends_what_the_parent_sent(key):
    """Every body of the pool, the pool's entries and cover, and every
    client's walk, hashed as ``f4fa80d`` gave them."""
    mix_name, seed = key.split(":")
    cell = manifest.cell(CELL_OF[mix_name])
    mix = cell["traffic"]
    pool = traffic.Pool(mix, loader.dataset_field_rows(cell["config"]),
                        int(seed))
    assert _sent(pool, int(mix["clients"])).hexdigest() == PARENT_DIGESTS[key]
    assert pool.write is None
