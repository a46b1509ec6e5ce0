"""The arithmetic of the yardstick: percentiles and rates over all the
requests of a window, the generic readers on canned snapshots, the
trace reduction on synthetic event lists and on a CPU trace, the bytes
a request must read, the peaks table."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import load, manifest, readers, roofline, tracered

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _window(stalled: bool):
    """One client, 10 ms a request for 4 s; the stalled window has one
    request that takes a whole second."""
    rec, t = [], 0.0
    while t < 4.0 - 1e-9:
        d = 1.0 if stalled and abs(t - 2.0) < 1e-9 else 0.010
        rec.append((0, t, t + d, 200, b'{"results": [1]}'))
        t += d
    return [rec]


def test_percentile_is_nearest_rank_over_all_values():
    v = sorted(float(x) for x in range(1, 101))
    assert load.percentile(v, 0.50) == 50.0
    assert load.percentile(v, 0.95) == 95.0
    assert load.percentile([7.0], 0.95) == 7.0
    assert load.percentile(sorted(range(1, 1001)), 0.95) == 950
    with pytest.raises(ValueError):
        load.percentile([], 0.5)


def test_one_stalled_second_moves_the_rate_and_the_p95_of_a_short_window():
    steady, stalled = _window(False), _window(True)
    a = load.window_stats(steady, [True] * len(steady[0]), 0.0, 4.0)
    b = load.window_stats(stalled, [True] * len(stalled[0]), 0.0, 4.0)
    assert a["requests_per_s"] == pytest.approx(100.0)
    assert b["requests_per_s"] == pytest.approx(75.25)
    assert a["latency_p50_ms"] == pytest.approx(10.0)
    assert b["latency_max_ms"] == pytest.approx(1000.0)
    # the stalled second shows in the per-second series, and the series
    # adds up to the rate's numerator
    assert len(b["completed_per_second"]) == 4
    assert min(b["completed_per_second"]) <= 1 < 99 <= \
        max(b["completed_per_second"])
    assert sum(b["completed_per_second"]) == b["completed_correct_in_window"]
    # 16 stalled clients in a window of 16: the tail is all of them
    many = [_window(True)[0] for _ in range(16)]
    flat_ok = [True] * sum(len(r) for r in many)
    c = load.window_stats(many, flat_ok, 0.0, 4.0)
    assert c["latency_p95_ms"] == pytest.approx(10.0)   # 16 of 4,816
    few = [[(0, 0.0, 1.0, 200, b""), *[(0, 1.0 + i * .01, 1.01 + i * .01,
                                        200, b"") for i in range(9)]]]
    d = load.window_stats(few, [True] * 10, 0.0, 4.0)
    assert d["latency_p95_ms"] == pytest.approx(1000.0)


def test_failures_wrong_and_late_answers_are_not_counted_as_completed():
    expected = [[1]]
    rec = [[(0, 0.0, 0.1, 200, b'{"results": [1]}'),
            (0, 0.1, 0.2, 200, b'{"results": [2]}'),      # wrong
            (0, 0.2, 0.3, 503, b"shed"),                  # failed
            (0, 0.3, 0.4, 200, b'{"results": [1]}'),
            (0, 0.9, 1.5, 200, b'{"results": [1]}')]]     # late: after close
    verdict = load.judge(rec, expected)
    assert (verdict["attempted"], verdict["wrong"], verdict["failed"]) == \
        (5, 1, 1)
    stats = load.window_stats(rec, verdict["ok"], 0.0, 1.0)
    assert stats["completed_correct_in_window"] == 2
    assert stats["requests_per_s"] == pytest.approx(2.0)
    # the late answer is late, not wrong: its wait is in the percentiles
    assert stats["latency_max_ms"] == pytest.approx(600.0)
    assert stats["requests"] == 5


CTX = {
    "status_before": {"costs": {"windows": 10, "soloDispatches": 5,
                                "compileCount": 40},
                      "queryStages": {"stage=read": {"count": 100,
                                                     "sum": 1.0}}},
    "status_after": {"costs": {"windows": 30, "soloDispatches": 5,
                               "compileCount": 40},
                     "queryStages": {"stage=read": {"count": 300, "sum": 2.0},
                                     "stage=admit": {"count": 200,
                                                     "sum": 0.5}},
                     "storage": {"planeBuild": {"buildSeconds": 6.5}}},
    "prom_before": {'pallas_fallback_total{kind="count"}': 1.0},
    "prom_after": {'pallas_fallback_total{kind="count"}': 4.0},
    "client": {"requests": 200, "latency_mean_ms": 12.0,
               "turnaround_ms": None},
    "run": {"boot_to_serving_s": 14.0},
    "trace": None,
    "device_kind": "TPU v5 lite",
}


@pytest.mark.parametrize("reader,want", [
    ({"status_delta": ["costs", "windows"]}, 20.0),
    ({"status_delta": ["costs", "absent"]}, None),
    ({"status_delta": ["costs", "absent"], "default": 0}, 0),
    ({"status_mean": ["queryStages", "stage=read"]}, 0.005),
    ({"status_mean": ["queryStages", "stage=admit"]}, 0.0025),
    ({"status_mean": ["queryStages", "stage=parse"]}, None),
    ({"status_end": ["storage", "planeBuild", "buildSeconds"]}, 6.5),
    ({"prom_delta": 'pallas_fallback_total{kind="count"}'}, 3.0),
    ({"prom_delta": "absent_total"}, None),
    ({"client": "turnaround_ms"}, None),
    ({"run": "boot_to_serving_s"}, 14.0),
    ({"trace": "busy_s"}, None),
    ({"div": [{"client": "requests"},
              {"add": [{"status_delta": ["costs", "windows"]},
                       {"status_delta": ["costs", "soloDispatches"]}]}]},
     10.0),
    ({"div": [1, {"status_delta": ["costs", "compileCount"]}]}, None),
    ({"mul": [100, {"div": [{"trace": "busy_s"}, 2]}]}, None),
    ({"peak": "hbm_bytes_per_s"}, 819e9),
])
def test_readers_on_canned_snapshots(reader, want):
    got = readers.evaluate(reader, CTX)
    assert got == want if want is None else got == pytest.approx(want)


def test_every_metric_file_evaluates_and_needs_a_trace_where_it_says_so():
    traced = dict(CTX, trace={"busy_s": 0.3, "window_s": 3.0,
                              "idle_share_pct": 90.0,
                              "requests_captured": 480,
                              "required_bytes": 480 * 1.5 * 125042688})
    seen = set()
    for entry in manifest.benchmark_json()["per_layer"]:
        m = manifest.metric(entry["name"])
        with_trace = readers.evaluate(m["reader"], traced)
        without = readers.evaluate(m["reader"], CTX)
        if m["source"] == "device_trace":
            # nothing is read off a device without a device trace
            assert without is None and with_trace is not None
            seen.add(m["name"])
    assert seen == {"kernel.busy_ms_per_request", "device.idle_share",
                    "kernel.count_scan_roofline"}
    # a share of a roofline: bytes over the peak over the device's busy
    # time, never without a trace, never for an unknown device
    share = manifest.metric("kernel.count_scan_roofline")["reader"]
    assert readers.evaluate(share, CTX) is None
    assert readers.evaluate(share, traced) == pytest.approx(
        100 * 480 * 1.5 * 125042688 / 819e9 / 0.3)
    with pytest.raises(KeyError):
        readers.evaluate(share, dict(traced, device_kind="cpu"))
    no_bytes = dict(traced, trace=dict(traced["trace"], required_bytes=None))
    assert readers.evaluate(share, no_bytes) is None


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peak("TPU v9", "hbm_bytes_per_s")
    with pytest.raises(KeyError):
        readers.evaluate({"peak": "hbm_bytes_per_s"},
                         dict(CTX, device_kind="cpu"))


@pytest.mark.parametrize("calls,rows", [
    ([{"call": "Count", "of": {"row": ["f", 3]}}], 1),
    ([{"call": "Count", "of": {"op": "Intersect", "args": [
        {"row": ["f", 3]}, {"row": ["f", 9]}]}}], 2),
    ([{"call": "Count", "of": {"op": "Intersect", "args": [
        {"row": ["f", 3]}, {"row": ["f", 3]}]}}], 1),
    ([{"call": "Count", "of": {"op": "Not", "args": [{"row": ["f", 3]}]}}], 2),
])
def test_row_bytes_of_the_point_templates(calls, rows):
    assert roofline.required_row_bytes(calls, 954) == rows * 125_042_688
    with pytest.raises(ValueError):
        roofline.required_row_bytes([{"call": "TopN", "field": "f"}], 954)


def test_union_of_overlapping_ops_and_the_gaps_between_them():
    busy, gaps = tracered.union_seconds(
        [(0, 100), (50, 150), (150, 200), (1_000, 1_100), (400, 500)])
    assert busy == pytest.approx(400e-9)
    assert [g for g, _ in gaps] == pytest.approx([200e-9, 500e-9])


def test_reduction_on_synthetic_events():
    s = 10 ** 9
    events = {"/device:TPU:0": [("fusion.1", 0, s // 2),
                                ("fusion.1", s // 4, s // 2),     # overlaps
                                ("copy.2", 2 * s, s // 4)],
              "/device:TPU:1": [("fusion.1", 0, s // 4)]}
    out = tracered.reduce_events(events, capture_seconds=3.0)
    assert out["busy_s"] == pytest.approx((0.75 + 0.25 + 0.25) / 2)
    assert out["window_s"] == pytest.approx(3.0)
    assert out["idle_share_pct"] == pytest.approx(100 * (1 - 0.625 / 3))
    assert out["breakdown"]["device_ops"][0] == ["fusion.1",
                                                 pytest.approx(0.625)]
    assert out["breakdown"]["idle_gaps"][0] == ["unattributed_1",
                                                pytest.approx(1.25)]
    assert out["chips_traced"] == 2 and out["device_events"] == 4
    # a window longer than the asked capture is the events' own span
    long = tracered.reduce_events({"/device:TPU:0": [("a", 0, s),
                                                     ("a", 4 * s, s)]}, 3.0)
    assert long["window_s"] == pytest.approx(5.0)
    for empty in ({}, {"/device:TPU:0": []}):
        with pytest.raises(tracered.NoDevicePlane):
            tracered.reduce_events(empty, 3.0)


@pytest.mark.parametrize("event_name,short", [
    ("%convert_reduce_fusion.4 = s32[954]{0:T(1024)} fusion(u32[954,32768]"
     "{1,0:T(8,128)} %ls_0_.1, u32[954,32768]{1,0:T(8,128)} %ls_1_.1), "
     "kind=kLoop, calls=%fused_computation.4",
     "convert_reduce_fusion s32[954]"),
    ("%fusion.1 = (s32[318,10]{0,1:T(8,128)S(1)}, s32[318,10]{0,1}) "
     "fusion(u32[318,12,32768]{2,0,1} %p.1)",
     "fusion (s32[318,10], s32[318,10])"),
    ("%reshape.1 = u32[10,64]{1,0} reshape(u32[640]{0} %reduce.2)",
     "reshape u32[10,64]"),
    ("fusion.3", "fusion.3"), ("jit_count", "jit_count")])
def test_an_op_is_named_for_its_program_not_for_its_copy(event_name, short):
    assert tracered.short_name(event_name) == short
    s = 10 ** 9
    twice = tracered.reduce_events({"/device:TPU:0": [
        (event_name, 0, s), (event_name.replace(".4 =", ".5 ="), 2 * s, s)]},
        4.0)
    assert twice["breakdown"]["device_ops"] == [[short, pytest.approx(2.0)]]


def test_a_cpu_trace_has_no_tpu_plane(tmp_path):
    """Recorded and read in children (this process never profiles): the
    reduction reports that nothing ran on a chip."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env["JAX_PLATFORMS"] = "cpu"
    record = ("import jax, jax.numpy as jnp, sys\n"
              "jax.profiler.start_trace(sys.argv[1])\n"
              "(jnp.ones((256, 256)) @ jnp.ones((256, 256)))"
              ".block_until_ready()\n"
              "jax.profiler.stop_trace()\n")
    subprocess.run([sys.executable, "-c", record, str(tmp_path / "t")],
                   env=env, check=True, timeout=300, capture_output=True)
    out = tmp_path / "reduced.json"
    subprocess.run([sys.executable,
                    os.path.join(REPO, "benchmark", "tracered.py"),
                    str(tmp_path / "t"), str(out), "1.0"],
                   env=env, check=True, timeout=300, capture_output=True,
                   cwd=REPO)
    reduced = json.loads(out.read_text())
    assert "reduced" not in reduced
    assert "no TPU device plane" in reduced["no_device_plane"]
    assert any(p.startswith("/host:") for p in reduced["planes"])
    assert reduced["layout"] == []
