"""Every template of ``dashfull_c1`` over the ``taxi-full-mesh4``
schema through ``Executor.execute`` under a four-device CPU mesh at six
shards (padded to eight): the meshed answers equal the single-device
executor's and the benchmark's numpy oracle, with the planes built
sharded and resident.  Answers only: nothing here
is a speed."""

import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import loader, manifest, queries, traffic  # noqa: E402
from pilosa_tpu.exec import Executor, result_to_json  # noqa: E402
from pilosa_tpu.obs import Stats  # noqa: E402
from pilosa_tpu.parallel import MeshPlacement  # noqa: E402
from pilosa_tpu.store import FieldOptions, Holder  # noqa: E402

CELL = "taxi-full-mesh4.dashfull_c1"
N_SHARDS, SEED = 6, 2_800_000_021
TEMPLATES = [t["name"] for t in manifest.cell(CELL)["traffic"]["templates"]]


def _requests(cell):
    """Per template: one drawn request and the first two of its cover
    (each a list of calls)."""
    rows = loader.dataset_field_rows(cell["config"])
    rng = np.random.default_rng(SEED)
    out = {}
    for t in cell["traffic"]["templates"]:
        reqs = [traffic.instantiate(t, rows, rng)]
        reqs += [traffic.instantiate(t, rows, None, step)
                 for step in (0, 5)]
        out[t["name"]] = reqs
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The cell's own schema and data at six shards, written as the
    harness writes them (the program's store makes the schema, the
    benchmark's loader the fragments and the oracle's totals)."""
    cell = manifest.cell(CELL)
    config = cell["config"]
    data_dir = str(tmp_path_factory.mktemp("taxi_mesh"))
    h = Holder(data_dir).open()
    idx = h.create_index(config["index"])
    for f in config["dataset"]["set_fields"]:
        idx.create_field(f)
    for f, spec in config["dataset"]["int_fields"].items():
        idx.create_field(f, FieldOptions(type="int", min=0,
                                         max=spec["max"]))
    h.close()
    requests = _requests(cell)
    calls, where = [], {}
    for reqs in requests.values():
        for req in reqs:
            for c in req:
                where.setdefault(queries.render_call(c), len(calls))
                if where[queries.render_call(c)] == len(calls):
                    calls.append(c)
    for f, view in loader.views(config):
        os.makedirs(loader.fragment_dir(data_dir, config["index"], f, view),
                    exist_ok=True)
    totals, _ = loader._load_chunk((config, data_dir, SEED,
                                    list(range(N_SHARDS)), calls))
    expected = {key: queries.finish(calls[i], totals[i])
                for key, i in where.items()}
    h = Holder(data_dir).open()
    placement = MeshPlacement(jax.devices()[:4])
    stats = Stats()
    meshed = Executor(h, placement=placement, stats=stats,
                      plane_budget=4 << 30)
    plain = Executor(h, plane_budget=4 << 30)
    yield config, requests, expected, meshed, plain, stats
    h.close()


def _answers(ex, index, request):
    results = ex.execute(index, queries.render(request))
    return json.loads(json.dumps([result_to_json(r) for r in results]))


@pytest.mark.parametrize("template", TEMPLATES)
def test_template_under_the_mesh_equals_one_device_and_numpy(
        served, template):
    config, requests, expected, meshed, plain, _ = served
    for request in requests[template]:
        want = [expected[queries.render_call(c)] for c in request]
        got = _answers(meshed, config["index"], request)
        assert got == want, queries.render(request)[:200]
        assert _answers(plain, config["index"], request) == want
        # and again from what is resident now
        assert _answers(meshed, config["index"], request) == want


def test_the_planes_are_resident_sharded_and_every_launch_counted(
        served):
    config, requests, _, meshed, _, stats = served
    for reqs in requests.values():
        _answers(meshed, config["index"], reqs[0])
    block = meshed.mesh_status()
    assert block["devices"] == 4 and block["paddedShards"] > 0
    per = block["perDeviceBytes"]
    # eight padded shards over four chips: the same bytes on each
    assert block["maxDeviceBytes"] == block["minDeviceBytes"] > 0
    assert set(per.values()) == {block["maxDeviceBytes"]}
    # the whole-field plane of every field a TopN or GroupBy reads is
    # there at its padded row count (pickup_mday and pickup_month are
    # only ever named row by row, and live as per-row entries)
    rows = loader.dataset_field_rows(config)
    whole = ("cab_type", "passenger_count", "pickup_year", "dist_miles",
             "pickup_time", "duration_minutes")
    padded = sum(1 << max(0, (rows[f] - 1).bit_length()) for f in whole)
    assert padded == 4 + 16 + 8 + 64 + 64 + 64
    assert sum(per.values()) >= padded * 8 * 32768 * 4
    pc = meshed.planes.stats()
    assert pc["evictions"] == 0 and pc["buildFailures"] == 0
    launches = sum(stats.snapshot()["counters"]["mesh_launches_total"]
                   .values())
    assert block["launchWait"]["count"] == launches > 0
