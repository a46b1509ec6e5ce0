"""``BENCHMARK.json`` and the files it names.  Everything that belongs
to one configuration, traffic mix, cell or per-layer metric is a file
of its own, found by name."""

from __future__ import annotations

import json
import os

from benchmark import datasets

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(ValueError):
    pass


def _load(kind: str, name: str, asked_by: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise ManifestError(f"{asked_by} names the missing file "
                            f"benchmark/{kind}/{name}.json")
    with open(path) as fh:
        return json.load(fh)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell(name: str) -> dict:
    """-> {"workload", "config", "traffic", "generate"} of one cell."""
    wl = _load("workloads", name, f"--workload {name}")
    config = _load("configs", wl["config"], f"workload {name}")
    mix = _load("traffic", wl["traffic"], f"workload {name}")
    kind = config["dataset"]["kind"]
    if not os.path.isfile(os.path.join(HERE, "datasets", f"{kind}.py")):
        raise ManifestError(f"config {wl['config']} names the missing "
                            f"dataset benchmark/datasets/{kind}.py")
    return {"workload": wl, "config": config, "traffic": mix,
            "generate": datasets.generator(kind)}


def metric(name: str) -> dict:
    return _load("metrics", name, "BENCHMARK.json")


def metrics_of(cell_name: str, bench: dict) -> tuple:
    """(end-to-end entries, per-layer entries) that this cell reports.
    The per-layer list is the metric files' own: every file under
    ``metrics/`` that names the cell in its ``workloads``, or has none,
    as the entry it is or will be (the file less its ``reader``).  For a
    cell that is an entry of ``BENCHMARK.json`` these are its
    ``per_layer`` entries and no others: the tests hold the two
    together."""
    def here(m):
        return "workloads" not in m or cell_name in m["workloads"]
    per_layer = [{k: v for k, v in metric(f[:-len(".json")]).items()
                  if k != "reader"}
                 for f in sorted(os.listdir(os.path.join(HERE, "metrics")))
                 if f.endswith(".json")]
    return ([m for m in bench["end_to_end"] if here(m)],
            [m for m in per_layer if here(m)])
