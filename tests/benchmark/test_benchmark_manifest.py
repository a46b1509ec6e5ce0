"""BENCHMARK.json and every file it names: loads, names and units use
only the allowed characters, and everything is found by name."""

import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import manifest  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _files(kind):
    return sorted(glob.glob(os.path.join(BENCH, kind, "*.json")))


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


# and the probes, which the tests hold as their inputs (conftest.py)
@pytest.mark.parametrize("path", [
    p for kind in ("configs", "traffic", "workloads", "metrics")
    for p in _files(kind)] + sorted(glob.glob(os.path.join(
        os.path.dirname(__file__), "probes", "*", "*.json"))),
    ids=lambda p: os.path.relpath(p, BENCH))
def test_file_loads_and_is_named_for_what_it_holds(path):
    with open(path) as fh:
        obj = json.load(fh)
    stem = os.path.basename(path)[:-len(".json")]
    assert obj["name"] == stem
    assert NAME.match(stem)
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", os.path.relpath(path, REPO))


@pytest.mark.parametrize("entry", _bench()["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert LINE.match(entry["source"]) and LINE.match(entry["why"])
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    with open(os.path.join(REPO, entry["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["source"] == entry["source"]
    assert len(entry["reduced"]) <= 16
    assert all(NAME.match(k) and k in cfg for k in entry["reduced"])
    # every cut is written into the file, the guarantees are stated
    assert [r.split(":")[0] for r in cfg["reduced"]] == entry["reduced"]
    assert {"answers", "serving_path", "durability"} <= set(cfg["guarantees"])
    assert cfg["assumed"]


@pytest.mark.parametrize("entry", _bench()["workloads"],
                         ids=lambda e: e["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4)
    assert LINE.match(entry["why"])
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key])
    cell = manifest.cell(entry["name"])
    assert cell["workload"] == entry
    assert entry["config"] in {c["name"] for c in _bench()["configs"]}
    assert callable(cell["generate"])


@pytest.mark.parametrize("entry", _bench()["end_to_end"],
                         ids=lambda e: e["name"])
def test_end_to_end_entry(entry):
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in ("host_clock", "device_trace")
    assert 0.01 <= entry["bound"] <= 0.25


@pytest.mark.parametrize("entry", _bench()["per_layer"],
                         ids=lambda e: e["name"])
def test_per_layer_entry_matches_its_file_and_moves_a_reported_metric(entry):
    bench = _bench()
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES and LINE.match(entry["layer"])
    declared = manifest.metric(entry["name"])
    reader = declared.pop("reader")
    assert declared == entry and isinstance(reader, dict)
    # `moves` names an end-to-end metric that every cell reporting this
    # metric reports
    moved = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    cells = entry.get("workloads", [w["name"] for w in bench["workloads"]])
    for cell in cells:
        e2e, per_layer = manifest.metrics_of(cell, bench)
        assert moved in e2e and entry in per_layer


def test_manifest_top_level():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check of 24 cells fits the driver's limit
    n = 24
    assert ((2 + 14 * n) * (bench["run_seconds"] + 60) + n * 180 + 1200
            <= 43200)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


@pytest.mark.parametrize("kind,field", [("configs", "config"),
                                        ("traffic", "traffic")])
def test_a_workload_that_names_a_missing_file_is_an_error_that_names_it(
        kind, field, tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "HERE", str(tmp_path))
    for d in ("workloads", "configs", "traffic", "datasets"):
        (tmp_path / d).mkdir()
    wl = {"name": "x.y", "config": "x", "traffic": "y", "chips": 1, "why": "."}
    (tmp_path / "workloads" / "x.y.json").write_text(json.dumps(wl))
    present = {"configs": ("x", {"dataset": {"kind": "nowhere"}}),
               "traffic": ("y", {})}
    for k, (name, body) in present.items():
        if k != kind:
            (tmp_path / k / f"{name}.json").write_text(json.dumps(body))
    with pytest.raises(manifest.ManifestError,
                       match=f"benchmark/{kind}/{wl[field]}.json"):
        manifest.cell("x.y")


def test_a_config_that_names_a_missing_dataset_is_an_error_that_names_it(
        tmp_path, monkeypatch):
    monkeypatch.setattr(manifest, "HERE", str(tmp_path))
    for d in ("workloads", "configs", "traffic", "datasets"):
        (tmp_path / d).mkdir()
    wl = {"name": "x.y", "config": "x", "traffic": "y", "chips": 1, "why": "."}
    (tmp_path / "workloads" / "x.y.json").write_text(json.dumps(wl))
    (tmp_path / "configs" / "x.json").write_text(
        json.dumps({"dataset": {"kind": "nowhere"}}))
    (tmp_path / "traffic" / "y.json").write_text("{}")
    with pytest.raises(manifest.ManifestError,
                       match="benchmark/datasets/nowhere.py"):
        manifest.cell("x.y")


def test_an_unknown_workload_is_an_error_that_names_it():
    with pytest.raises(manifest.ManifestError, match="no.such"):
        manifest.cell("no.such")
