"""The per-layer metrics that read the stage clock
(``query_stage_seconds`` as ``/status`` ``queryStages``) and the
plan-cache fall-through counter: each metric file evaluates against a
rehearsal run's own ``/status`` and ``/metrics``, and ``BENCHMARK.json``
and the files agree.  Answers and counts only: a CPU's milliseconds are
not speeds."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402

STAGE_METRICS = ["api.http_ms", "api.outside_stages_ms", "executor.plan_ms",
                 "executor.plan_fallthroughs_per_request",
                 "executor.read_ms", "fused.dispatch_ms", "batcher.queue_ms"]
STAGES = ["http_in", "admit", "plan_cache", "parse", "plan", "queue",
          "dispatch", "read", "deliver", "assemble", "encode", "http_out"]
CELL = "pibench1b.intersect_c32"  # 32 clients: the window path, queue


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the concurrent cell at two shards: its
    result line and its record."""
    tmp = tmp_path_factory.mktemp("stage_metrics")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jaxcache"),
               TF_CPP_MIN_LOG_LEVEL="3")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2600000011", "--seconds", "2",
         "--trace", "1", "--rehearse", "--shards", "2",
         "--out", str(tmp / "out")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(tmp / "out" / "record.json") as fh:
        record = json.load(fh)
    return line, record


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_stage_metric_prints_in_a_rehearsal_of_its_cell(name, rehearsal):
    line, _ = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    decl = manifest.metric(name)
    got = line["metrics"][name]
    assert got["unit"] == decl["unit"]
    if name == "executor.plan_fallthroughs_per_request":
        assert 0.0 <= got["value"] <= 1.0    # at most one attempt a request
    elif name == "api.outside_stages_ms":
        # the client's mean latency less every stage: what no span
        # covers (accept queue, thread hand-off, the client's own side)
        assert math.isfinite(got["value"])
    else:
        assert got["value"] > 0.0


def test_every_stage_the_window_path_enters_is_in_status(rehearsal):
    _, record = rehearsal
    seen = {k.split("=", 1)[1] for k in record["query_stages"]}
    assert {"http_in", "admit", "plan", "queue", "dispatch", "read",
            "deliver", "assemble", "encode", "http_out"} <= seen
    assert seen <= set(STAGES)


def _metric_files():
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(REPO, "benchmark", "metrics")) if f.endswith(".json"))


@pytest.mark.parametrize("name", _metric_files())
def test_metric_file_and_benchmark_json_agree(name):
    """The harness takes a cell's per-layer list from the metric files
    (``manifest.metrics_of``) and the driver takes it from
    ``BENCHMARK.json``: in every cell that is an entry, a file reports
    exactly where an entry of its name does, wherever that entry stands
    in the list, and the two are equal."""
    bench = manifest.benchmark_json()
    decl = {k: v for k, v in manifest.metric(name).items() if k != "reader"}
    assert decl["name"] == name
    for cell in (w["name"] for w in bench["workloads"]):
        def here(m):
            return "workloads" not in m or cell in m["workloads"]
        entries = [m for m in bench["per_layer"]
                   if m["name"] == name and here(m)]
        assert entries == ([decl] if here(decl) else []), cell
        assert (decl in manifest.metrics_of(cell, bench)[1]) == here(decl)


def test_every_per_layer_entry_has_its_file():
    bench = manifest.benchmark_json()
    assert {m["name"] for m in bench["per_layer"]} <= set(_metric_files())
    assert "api.parse_ms" not in _metric_files()


def _leaves(expr):
    if isinstance(expr, dict):
        for kind, arg in expr.items():
            if kind == "status_delta":
                yield expr
            elif isinstance(arg, list):
                for a in arg:
                    yield from _leaves(a)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_a_stage_no_request_entered_counts_as_zero_not_as_absent(name):
    """Every ``status_delta`` leaf of a sum carries ``"default": 0``:
    against a /status that lacks the stages the sums read 0, and the
    counter's metric, whose series the program lacks, is left out."""
    reader = manifest.metric(name)["reader"]
    leaves = list(_leaves(reader))
    assert all(leaf.get("default") == 0 for leaf in leaves)
    assert all(leaf["status_delta"][0] == "queryStages" and
               leaf["status_delta"][1].split("=", 1)[1] in STAGES
               for leaf in leaves)
    empty = {"status_before": {"queryStages": {}},
             "status_after": {"queryStages": {}},
             "prom_before": {}, "prom_after": {},
             "client": {"requests": 100, "latency_mean_ms": 4.0},
             "run": {}, "trace": None, "device_kind": "cpu"}
    value = readers.evaluate(reader, empty)
    if name == "api.outside_stages_ms":
        assert value == 4.0
    elif leaves:
        assert value == 0.0
    else:
        assert value is None


def test_outside_stages_subtracts_every_stage_per_request():
    reader = manifest.metric("api.outside_stages_ms")["reader"]
    read = {leaf["status_delta"][1].split("=", 1)[1]
            for leaf in _leaves(reader)}
    assert read == set(STAGES)
    after = {f"stage={s}": {"count": 50, "sum": 0.001 * (i + 1)}
             for i, s in enumerate(STAGES)}
    ctx = {"status_before": {"queryStages": {}},
           "status_after": {"queryStages": after},
           "prom_before": {}, "prom_after": {},
           "client": {"requests": 50, "latency_mean_ms": 2.0},
           "run": {}, "trace": None, "device_kind": "cpu"}
    total_ms = 1e3 * sum(0.001 * (i + 1) for i in range(len(STAGES))) / 50
    assert readers.evaluate(reader, ctx) == pytest.approx(2.0 - total_ms)
