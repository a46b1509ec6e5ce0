"""``executor.plan_row_serves_per_request``: the share of a window's
requests that a cached plan answered by its per-row form
(``plan_cache_row_serves_total`` in ``/metrics``, PR 27) — the metric
file evaluates through ``benchmark/readers.py`` against a synthetic
``/metrics`` delta, a program without the counter (the parent) leaves
the metric out instead of failing, and a rehearsal of a cell the
counter was made for plans no request twice.  Counts only: nothing
here is a speed."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402

NAME = "executor.plan_row_serves_per_request"
TWIN = "executor.plan_fallthroughs_per_request"   # the file it copies
COUNTER = "plan_cache_row_serves_total"
# two rows of 32 a request; a CPU rehearsal cannot trace point_c1, whose
# roofline metric needs the chip's peaks
CELL = "pibench1b.intersect_c32"


def _ctx(prom_before: dict, prom_after: dict, requests) -> dict:
    return {"status_before": {}, "status_after": {},
            "prom_before": prom_before, "prom_after": prom_after,
            "client": {"requests": requests}, "run": {}, "trace": None,
            "device_kind": "cpu"}


def test_the_file_declares_a_counter_of_the_executor_that_moves_p50():
    decl = manifest.metric(NAME)
    twin = manifest.metric(TWIN)
    assert decl["name"] == NAME
    assert decl["better"] == "higher" and twin["better"] == "lower"
    for key in ("unit", "source", "layer", "moves"):
        assert decl[key] == twin[key]
    assert decl["source"] == "program_counter"
    assert decl["reader"] == {"div": [{"prom_delta": COUNTER},
                                      {"client": "requests"}]}
    e2e = {m["name"] for m in manifest.benchmark_json()["end_to_end"]}
    assert decl["moves"] in e2e


@pytest.mark.parametrize("before,after,requests,want", [
    ({COUNTER: 2048.0}, {COUNTER: 10078.0}, 8030, 1.0),   # every request
    ({COUNTER: 0.0}, {COUNTER: 0.0}, 1900, 0.0),     # a resident plane
    ({}, {COUNTER: 40.0}, 160, 0.25),    # series first printed in-window
    ({COUNTER: 5.0}, {COUNTER: 5.0}, 0, None),       # no request: no share
])
def test_the_reader_divides_the_counters_delta_by_the_requests(
        before, after, requests, want):
    got = readers.evaluate(manifest.metric(NAME)["reader"],
                           _ctx(before, after, requests))
    assert got == want


def test_a_program_without_the_counter_leaves_the_metric_out():
    """The parent's ``/metrics`` has ``plan_cache_fallthrough_total``
    and no row-serve series: the reader yields None (the harness then
    leaves the metric out of the line) and does not raise."""
    parent = {"plan_cache_fallthrough_total": 8030.0,
              "plan_cache_hits": 9054.0}
    reader = manifest.metric(NAME)["reader"]
    assert readers.evaluate(reader, _ctx(parent, parent, 8030)) is None
    # the twin still reads on the same output
    assert readers.evaluate(manifest.metric(TWIN)["reader"],
                            _ctx({}, parent, 8030)) == 1.0


def test_where_benchmark_json_lists_the_metric_it_is_the_files_entry():
    """The entry is this PR's to append and a later one's to list:
    wherever ``BENCHMARK.json`` names the metric, it names it as the
    file declares it, after every metric PR 26 left there."""
    per_layer = manifest.benchmark_json()["per_layer"]
    entries = [m for m in per_layer if m["name"] == NAME]
    assert len(entries) <= 1
    for entry in entries:
        decl = manifest.metric(NAME)
        assert {k: v for k, v in decl.items() if k != "reader"} == entry
        names = [m["name"] for m in per_layer]
        assert names.index(NAME) > names.index(TWIN)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the concurrent cell at two shards: the
    field keeps its 32 rows, so a two-row request is a tiny slice of
    it here as at 954 shards."""
    tmp = tmp_path_factory.mktemp("row_serves")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jaxcache"),
               TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2700000011", "--seconds", "2",
         "--trace", "1", "--rehearse", "--shards", "2",
         "--out", str(tmp / "out")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(tmp / "out" / "record.json") as fh:
        record = json.load(fh)
    return line, record


def test_no_request_of_the_cell_is_planned_twice(rehearsal):
    """The cell's requests touch two of 32 rows: every one is a
    plan-cache hit that its per-row form answers, none falls through,
    and ``plan_cache`` and ``plan`` are entered once a request."""
    line, record = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][TWIN]["value"] == 0.0
    assert line["samples"]["compiles_in_window"] == 0
    stages = {k.split("=", 1)[1]: v for k, v in
              record["query_stages"].items()}
    assert stages["plan_cache"]["count"] == stages["plan"]["count"]
    assert "parse" not in stages or \
        stages["parse"]["count"] < stages["plan"]["count"] / 10
    if NAME in line["metrics"]:     # once BENCHMARK.json lists it
        assert line["metrics"][NAME] == {"value": 1.0, "unit": "count"}
