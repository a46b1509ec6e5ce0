"""``taxi-full-mesh4.dashfull_c1`` (PR 28), on the CPU at two shards:
the configuration is the docs' nine fields at their row counts and the
five ``dash_c1`` templates unchanged; the oracle answers the four new
templates as a brute force over unpacked bits does; both controls come
out as not correct; a rehearsal under four virtual devices serves
through the mesh (``device.count`` 4, ``/status`` ``mesh``) to a
correct line; and the three metric files of the mesh read a live
meshed server's ``/status`` and ``/metrics``.  Answers and counts
only: a CPU's milliseconds are not speeds."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import (bitmaps, controls, load, loader,  # noqa: E402
                       manifest, queries, readers, traffic)
from benchmark import run as bench_run  # noqa: E402
from benchmark.server import Server  # noqa: E402

CELL = "taxi-full-mesh4.dashfull_c1"
PARENT_CELL = "taxi333m.dash_c1"
SEED, N_SHARDS = 2_800_000_031, 2
NEW_FIELDS = {"pickup_month": 12, "pickup_mday": 31, "pickup_time": 48,
              "duration_minutes": 64}
NEW_TEMPLATES = ("topn_time_in_month", "topn_duration_in_time",
                 "count_month_mday_cab", "sum_in_month")
MESH_METRICS = ("mesh.launch_wait_ms", "mesh.launches_per_request",
                "planes.build_gb_per_s")


def _child_env(tmp, devices=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "jaxcache"),
               TF_CPP_MIN_LOG_LEVEL="3")
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return env


# -- the configuration and the traffic are the source's ---------------------

def test_the_configuration_is_the_docs_whole_deployment():
    cell = manifest.cell(CELL)
    config, parent = cell["config"], manifest.cell(PARENT_CELL)["config"]
    assert config["shards"] == 1049
    assert config["columns"] == 1049 * bitmaps.SHARD_WIDTH == 1_099_956_224
    rows = loader.dataset_field_rows(config)
    assert rows == dict({"cab_type": 3, "passenger_count": 10,
                         "pickup_year": 8, "dist_miles": 64}, **NEW_FIELDS)
    assert sum(rows.values()) == 240
    sets, old = config["dataset"]["set_fields"], \
        parent["dataset"]["set_fields"]
    for f in ("cab_type", "passenger_count", "pickup_year"):
        assert sets[f] == old[f]
    # the same geometric head, carried on to 64 rows
    assert sets["dist_miles"]["shares"][:31] == old["dist_miles"]["shares"][:31]
    assert config["dataset"]["int_fields"] == parent["dataset"]["int_fields"]
    assert all(s > 0 for spec in sets.values() for s in spec["shares"])
    assert config["guarantees"] == parent["guarantees"]
    # the budget is the mesh's total: 48 GiB over four chips
    assert config["server_env"] == {
        "PILOSA_PLANE_BUDGET_BYTES": str(48 << 30),
        "PILOSA_PLANE_SIDECARS": "false"}
    assert "total" in config["server_env_why"]["PILOSA_PLANE_BUDGET_BYTES"]
    assert cell["workload"]["chips"] == 4
    assert [r.split(":")[0] for r in config["reduced"]] == ["dataset"]
    assert len(config["source"]) <= 200


def test_the_five_dash_templates_are_carried_over_unchanged():
    new = manifest.cell(CELL)["traffic"]
    old = manifest.cell(PARENT_CELL)["traffic"]
    assert new["templates"][:5] == old["templates"]
    assert tuple(t["name"] for t in new["templates"][5:]) == NEW_TEMPLATES
    assert all("weight" not in t for t in new["templates"])   # equal shares
    for key in ("loop", "clients", "connection", "pool", "trace_seconds"):
        assert new[key] == old[key]
    # every one of the nine fields is read in every round
    text = json.dumps(new["templates"])
    fields = list(loader.dataset_field_rows(manifest.cell(CELL)["config"]))
    assert all(f'"{f}"' in text for f in fields + ["total_amount_dollars"])


def test_the_new_cell_is_the_benchmarks_one_four_chip_cell():
    bench = manifest.benchmark_json()
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == manifest.cell(CELL)["workload"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == [CELL]
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "taxi-full-mesh4"]
    assert config["source"] == manifest.cell(CELL)["config"]["source"]
    assert config["reduced"] == ["dataset"]


# -- (ii) the oracle against brute force -------------------------------------

@pytest.fixture(scope="module")
def shards():
    cell = manifest.cell(CELL)
    out = []
    for s in range(N_SHARDS):
        data = cell["generate"](cell["config"]["dataset"], SEED, s)
        bits = {f: np.stack([bitmaps.unpack_bits(r) for r in rows])
                for f, rows in data["sets"].items()}
        out.append((data, bits))
    return cell, out


def _brute_bitmap(b, bits):
    if "row" in b:
        return bits[b["row"][0]][b["row"][1]]
    assert b["op"] == "Intersect"
    out = _brute_bitmap(b["args"][0], bits)
    for a in b["args"][1:]:
        out = np.logical_and(out, _brute_bitmap(a, bits))
    return out


def _brute(call, shards):
    kind = call["call"]
    if kind == "Count":
        return sum(int(_brute_bitmap(call["of"], bits).sum())
                   for _, bits in shards)
    if kind == "TopN":
        plane = [bits[call["field"]] for _, bits in shards]
        keep = [_brute_bitmap(call["filter"], bits) for _, bits in shards]
        counts = [sum(int((p[r] & k).sum()) for p, k in zip(plane, keep))
                  for r in range(plane[0].shape[0])]
        order = sorted(range(len(counts)), key=lambda r: (-counts[r], r))
        return [{"id": r, "count": counts[r]}
                for r in order[:call["n"]] if counts[r]]
    assert kind == "Sum"
    total = count = 0
    for data, bits in shards:
        keep = _brute_bitmap(call["filter"], bits)
        total += int(data["ints"][call["field"]][keep].sum(dtype=np.int64))
        count += int(keep.sum())
    return {"value": total, "count": count}


@pytest.mark.parametrize("template", NEW_TEMPLATES)
def test_oracle_equals_brute_force_on_the_new_templates(shards, template):
    cell, data = shards
    t = next(t for t in cell["traffic"]["templates"] if t["name"] == template)
    rows = loader.dataset_field_rows(cell["config"])
    requests = [traffic.instantiate(t, rows, np.random.default_rng(7)),
                traffic.instantiate(t, rows, None, 11)]
    for calls in requests:
        for call in calls:
            total = sum(queries.partial(call, d) for d, _ in data)
            assert queries.finish(call, total) == _brute(call, data)


@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_the_new_fields_hold_what_the_config_states(shards, field):
    cell, data = shards
    spec = cell["config"]["dataset"]["set_fields"][field]
    bits = data[0][1][field]
    assert bits.shape == (NEW_FIELDS[field], bitmaps.SHARD_WIDTH)
    # exactly one row per column (mutex-style), at the shares stated
    assert (bits.sum(axis=0) == 1).all()
    share = np.asarray(spec["shares"]) / sum(spec["shares"])
    assert np.abs(bits.mean(axis=1) - share).max() < 0.003
    assert (bits.sum(axis=1) > 0).all()      # no row of the field is empty


def test_the_curves_have_the_shapes_the_config_names():
    sets = manifest.cell(CELL)["config"]["dataset"]["set_fields"]
    t = sets["pickup_time"]["shares"]      # half-hours from 00:00
    assert max(t[4:10]) < min(t[14:44])    # 02:00-05:00 is the trough
    assert max(range(48), key=t.__getitem__) in range(36, 40)   # 18-20 h
    assert t[16] > t[12] and t[16] > t[20]                      # 08:00
    d = sets["duration_minutes"]["shares"]
    assert 8 <= max(range(63), key=d.__getitem__) <= 12
    m = sets["pickup_mday"]["shares"]
    assert len(set(m[:28])) == 1 and m[28] > m[29] > m[30]
    mo = sets["pickup_month"]["shares"]
    assert max(mo) / min(mo) < 1.25
    dist = sets["dist_miles"]["shares"]
    assert all(abs(dist[k + 1] / dist[k] - 0.8) < 0.02
               for k in range(1, 40))


# -- (iii) the controls ------------------------------------------------------

@pytest.mark.parametrize("control", sorted(controls.READ))
def test_the_control_comes_out_as_not_correct_on_the_new_cell(control):
    cell = manifest.cell(CELL)
    pool = traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(cell["config"]), SEED)
    calls, index = pool.distinct_calls()
    totals = None
    for s in range(N_SHARDS):
        data = cell["generate"](cell["config"]["dataset"], SEED, s)
        totals = queries.combine(
            totals, [queries.partial(c, data) for c in calls])
    expected = [[queries.finish(calls[i], totals[i]) for i in ids]
                for ids in index]
    sound = [[(int(rid), 0.0, 0.01, 200,
               json.dumps({"results": expected[rid]}).encode())
              for rid in pool.client_order(0)[:270]]]
    assert load.judge(sound, expected)["wrong"] == 0
    broken = controls.READ[control](sound, cell, pool, calls, index, totals,
                                   SEED, N_SHARDS)
    verdict = load.judge(broken, expected)
    assert verdict["attempted"] == 270
    assert verdict["wrong"] >= 135, verdict["first_wrong"]


# -- (i) the rehearsal under four virtual devices ----------------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh_cell")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearse", "--shards", "2",
         "--out", str(tmp / "out")],
        env=_child_env(tmp, devices=4), cwd=REPO, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(tmp / "out" / "record.json") as fh:
        record = json.load(fh)
    with open(tmp / "out" / "server.log", errors="replace") as fh:
        log = fh.read()
    return line, record, log


def test_the_cell_rehearses_through_the_mesh_to_a_correct_line(rehearsal):
    line, record, log = rehearsal
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 9             # at least one whole round
    assert line["device"]["count"] == 4 and line["rehearsal"] is True
    assert line["reduced"] == ["shards 2 of 1049 (--shards)"]
    assert line["samples"]["compiles_in_window"] == 0
    assert "mesh: sharding over 4 devices" in log
    assert record["plane_cache"]["meshed"] is True
    assert record["plane_cache"]["evictions"] == 0
    # two shards pad to four: every plane entry carries two pad shards
    assert all(c["value"] <= c["limit"] for c in line["compared"].values()
               if "limit" in c)
    # the metrics without a ``workloads`` list report here as they are
    for name in ("executor.plan_ms", "fused.dispatch_ms",
                 "executor.read_ms", "planes.build_s"):
        assert name in line["metrics"], name
    for name in MESH_METRICS:      # once BENCHMARK.json lists them
        if name in line["metrics"]:
            assert line["metrics"][name]["value"] >= 0.0


# -- the mesh's metric files against a live meshed server --------------------

@pytest.mark.parametrize("name", MESH_METRICS)
def test_mesh_metric_file_declares_a_reader_over_the_generic_leaves(name):
    decl = manifest.metric(name)
    assert decl["name"] == name and decl["workloads"] == [CELL]
    bench = manifest.benchmark_json()
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert decl["moves"] in e2e
    layers = {m["layer"] for m in bench["per_layer"]}
    assert decl["layer"] in layers           # a layer the benchmark names
    assert decl["source"] in ("program_span", "program_counter")
    for entry in bench["per_layer"]:         # wherever it is listed
        if entry["name"] == name:
            assert {k: v for k, v in decl.items() if k != "reader"} == entry


def _ctx(status_before, status_after, prom_before, prom_after, requests):
    return {"status_before": status_before, "status_after": status_after,
            "prom_before": prom_before, "prom_after": prom_after,
            "client": {"requests": requests}, "run": {}, "trace": None,
            "device_kind": "cpu"}


def test_a_program_without_the_series_leaves_the_mesh_metrics_out():
    """The parent has a ``mesh`` block without ``launchWait`` and no
    ``mesh_launches_total``: two metrics are left out, none raises; the
    build rate reads from what ``planeBuild`` always had."""
    parent = {"mesh": {"devices": 4, "perDeviceBytes": {}, "paddedShards": 3},
              "storage": {"planeBuild": {"buildBytes": 4e9,
                                         "buildSeconds": 16.0}}}
    ctx = _ctx(parent, parent, {"plan_cache_hits": 5.0},
               {"plan_cache_hits": 9.0}, 100)
    values = {n: readers.evaluate(manifest.metric(n)["reader"], ctx)
              for n in MESH_METRICS}
    assert values == {"mesh.launch_wait_ms": None,
                      "mesh.launches_per_request": None,
                      "planes.build_gb_per_s": 0.25}
    one_chip = {"storage": {"planeBuild": {"buildBytes": 0,
                                           "buildSeconds": 0.0}}}
    ctx = _ctx(one_chip, one_chip, {}, {}, 100)
    assert all(readers.evaluate(manifest.metric(n)["reader"], ctx) is None
               for n in MESH_METRICS)


def test_the_mesh_metrics_read_a_live_meshed_servers_status(tmp_path):
    """A ``python -m pilosa_tpu.cli server`` child on four virtual
    devices over the cell's own index at two shards: ``/status``
    ``mesh`` carries ``launchWait``, ``maxDeviceBytes`` and
    ``minDeviceBytes``, and the three files evaluate against it."""
    cell = manifest.cell(CELL)
    config = cell["config"]
    pool = traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(config), SEED)
    env = _child_env(tmp_path, devices=4)
    os.makedirs(tmp_path / "data")
    os.makedirs(tmp_path / "out")
    saved = dict(os.environ)
    os.environ.clear()
    os.environ.update(env)      # the harness hands its own to its children
    server = None
    try:
        expected, *_ = bench_run.write_index(cell, pool, str(tmp_path),
                                             SEED, N_SHARDS)
        server = Server(str(tmp_path / "data"), str(tmp_path / "out"),
                        config["server_env"])
        server.wait_up()
        sent = list(pool.cover[:40:3])

        def send():
            for rid in sent:
                got = bench_run.one_request(server, config["index"],
                                            pool.requests[rid]["pql"])
                assert got == expected[rid]
        send()                                   # builds and compiles
        before, prom_before = server.status(), server.metrics()
        send()
        after, prom_after = server.status(), server.metrics()
    finally:
        if server is not None:
            assert server.stop() == 0
        os.environ.clear()
        os.environ.update(saved)
    mesh = after["mesh"]
    assert mesh["devices"] == 4 and len(after["devices"]) == 4
    assert mesh["maxDeviceBytes"] == max(mesh["perDeviceBytes"].values())
    assert mesh["minDeviceBytes"] == min(mesh["perDeviceBytes"].values()) > 0
    assert mesh["paddedShards"] > 0
    launches = prom_after["mesh_launches_total"]
    assert mesh["launchWait"]["count"] == launches
    assert launches > prom_before["mesh_launches_total"] > 0
    assert after["storage"]["planeBuild"]["meshed"] is True
    ctx = _ctx(before, after, prom_before, prom_after, len(sent))
    values = {n: readers.evaluate(manifest.metric(n)["reader"], ctx)
              for n in MESH_METRICS}
    assert values["mesh.launches_per_request"] == \
        (launches - prom_before["mesh_launches_total"]) / len(sent)
    assert values["mesh.launches_per_request"] >= 1.0
    assert 0.0 <= values["mesh.launch_wait_ms"] < 50.0
    assert values["planes.build_gb_per_s"] > 0.0
