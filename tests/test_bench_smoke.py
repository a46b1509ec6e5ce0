"""The headline bench is the driver's round artifact: a code change
that breaks it costs the round its benchmark.  Run it end-to-end at toy
scale (raw tier + product tier + REST variant) on CPU and assert the
one-JSON-line contract."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_end_to_end_toy_scale():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", PILOSA_BENCH_SHARDS="2",
               PILOSA_BENCH_ROWS="4")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert set(out) == {"metric", "value", "unit", "vs_baseline",
                        "regressions"}
    assert out["unit"] == "qps" and out["value"] > 0
    assert isinstance(out["regressions"], list)
    # the headline is ALWAYS the product path, and only the 954-shard
    # scale may call itself "1B cols"
    assert out["metric"] == "product_count_qps_2_shards_cpu"
    assert "1B cols" not in proc.stderr


def test_bench_failing_phase_exits_nonzero():
    """One process, no salvage, no demotion: a tier that fails makes
    bench.py exit non-zero and print no result line."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", PILOSA_BENCH_SHARDS="1",
               PILOSA_BENCH_ROWS="2")
    # break the product tier's Executor — the raw tier has already
    # measured by then, which is exactly when the old salvage fired
    proc = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys\n"
         "import pilosa_tpu.exec.executor as ex\n"
         "def boom(*a, **k): raise RuntimeError('product tier down')\n"
         "ex.Executor.__init__ = boom\n"
         "sys.argv = ['bench.py']\n"
         f"runpy.run_path({os.path.join(REPO, 'bench.py')!r}, "
         "run_name='__main__')\n"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode != 0
    assert "product tier down" in proc.stderr
    assert proc.stdout.strip() == ""


def test_regression_guard_flags_and_clears(tmp_path, monkeypatch):
    """The guard compares only same-metric rounds, flags drops past
    REGRESSION_RATIO with the prior round's figure attached, and stays
    quiet within tolerance or when no comparable round exists."""
    # bench.py (the headline script) is shadowed by the bench/ config
    # package on import; load the file explicitly
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    art = tmp_path / "BENCH_r07.json"
    art.write_text(json.dumps({
        "parsed": {"metric": "product_count_qps_1b_cols_tpu",
                   "value": 2000.0}}))
    # older round with a HIGHER figure: newest round must win the compare
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "parsed": {"metric": "product_count_qps_1b_cols_tpu",
                   "value": 9999.0}}))
    monkeypatch.setenv("PILOSA_BENCH_BASELINE_DIR", str(tmp_path))
    flagged = bench.regression_guard("product_count_qps_1b_cols_tpu", 500.0)
    assert len(flagged) == 1
    assert flagged[0]["previous"] == 2000.0
    assert flagged[0]["previous_round"] == "BENCH_r07.json"
    assert flagged[0]["ratio"] == 0.25
    # within tolerance: clean
    assert bench.regression_guard("product_count_qps_1b_cols_tpu",
                                  1900.0) == []
    # different metric (e.g. CPU smoke vs TPU rounds): no comparison
    assert bench.regression_guard("product_count_qps_1b_cols_cpu",
                                  1.0) == []
    # a malformed newest artifact must not raise — the guard falls
    # through to the next-most-recent comparable round
    art.write_text("not json")
    flagged = bench.regression_guard("product_count_qps_1b_cols_tpu", 1.0)
    assert flagged and flagged[0]["previous_round"] == "BENCH_r03.json"
    (tmp_path / "BENCH_r03.json").write_text("also not json")
    assert bench.regression_guard("product_count_qps_1b_cols_tpu",
                                  1.0) == []


def test_detail_regression_guard_tracks_sub_metrics(tmp_path,
                                                    monkeypatch):
    """r17 satellite: the guard also tracks named values INSIDE a
    config's detail payload (the solo single-stream floor, per-kind
    kernel GB/s) against the newest same-metric round that recorded
    detail — so re-serializing readback fails the guard even while
    the best-chain headline hides it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    tracked = {
        "single_stream_qps": ("solo", "fastlane_qps"),
        "kernel_bandwidth_gbps_rowcounts":
            ("kinds", "rowcounts", "after_gbps"),
    }
    prior_detail = {"solo": {"fastlane_qps": 600.0},
                    "kinds": {"rowcounts": {"after_gbps": 500.0}}}
    (tmp_path / "BENCH_r08.json").write_text(json.dumps({
        "parsed": {"metric": "kernel_roofline_gbps_tpu",
                   "value": 550.0, "detail": prior_detail}}))
    # an older round WITHOUT detail (pre-r17 artifact shape) is
    # skipped by the detail guard, not an error
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "parsed": {"metric": "kernel_roofline_gbps_tpu",
                   "value": 470.0}}))
    monkeypatch.setenv("PILOSA_BENCH_BASELINE_DIR", str(tmp_path))
    # a solo-floor slide past REGRESSION_RATIO flags with the prior
    # round's figure; the healthy kind stays quiet
    cur = {"solo": {"fastlane_qps": 290.0},
           "kinds": {"rowcounts": {"after_gbps": 520.0}}}
    flagged = bench.detail_regression_guard(
        "kernel_roofline_gbps_tpu", cur, tracked)
    assert len(flagged) == 1
    assert flagged[0]["metric"] == "single_stream_qps"
    assert flagged[0]["previous"] == 600.0
    assert flagged[0]["previous_round"] == "BENCH_r08.json"
    # all healthy: clean
    healthy = {"solo": {"fastlane_qps": 650.0},
               "kinds": {"rowcounts": {"after_gbps": 510.0}}}
    assert bench.detail_regression_guard(
        "kernel_roofline_gbps_tpu", healthy, tracked) == []
    # no prior round with detail at all: skipped, never raises
    assert bench.detail_regression_guard(
        "some_other_metric", cur, tracked) == []
    # current detail missing a tracked path: that row is skipped
    assert bench.detail_regression_guard(
        "kernel_roofline_gbps_tpu", {"solo": {}}, tracked) == []


def test_product_raw_ratio_guard():
    """ISSUE 7 satellite: any full-scale round serving under 0.95x of
    the raw-kernel ceiling lands in the `regressions` list; toy-scale
    smoke rounds and rounds missing a tier stay clean."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # the r05 shape: product 2263 vs raw 5472 at full scale -> flagged
    flagged = bench.ratio_guard(2263.0, 5472.0, n_shards=954)
    assert len(flagged) == 1
    assert flagged[0]["metric"] == "product_raw_ratio"
    assert flagged[0]["value"] == 0.414
    assert flagged[0]["floor"] == bench.PRODUCT_RAW_RATIO_FLOOR == 0.95
    # healthy full-scale round: clean
    assert bench.ratio_guard(5460.0, 5472.0, n_shards=954) == []
    # boundary: exactly at the floor is clean
    assert bench.ratio_guard(950.0, 1000.0, n_shards=954) == []
    # toy-scale smoke (env-overridden shards): never judged
    assert bench.ratio_guard(1.0, 1000.0, n_shards=2) == []
    # a missing tier is reported elsewhere, not as a ratio regression
    assert bench.ratio_guard(None, 5472.0, n_shards=954) == []
    assert bench.ratio_guard(100.0, None, n_shards=954) == []


def test_config23_roofline_smoke():
    """bench/config23 (per-kernel roofline: chain GB/s, selected-row
    gather widths, multi-query single-stream sweep, batched-readback
    proof) in --smoke mode: tiny plane, CPU — runs under tier-1 so the
    bench can never bitrot.  The multi-query gain bar and the
    one-packed-read property are asserted INSIDE the bench while
    measuring."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config23_roofline.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("kernel_roofline_gbps")
    assert out["unit"] == "GBps" and out["value"] > 0
    detail = out["detail"]
    # GB/s per kernel shape is a first-class metric now
    assert set(detail["chain"]) == {"1", "8", "32"}
    assert all(v["gbps"] > 0 for v in detail["chain"].values())
    assert all(v["gbps"] > 0 for v in detail["selected"].values())
    # r17: the donated ping-pong chain sweeps the same depths, and the
    # per-kind before/after receipts are recorded both sides
    assert set(detail["chain_donated"]) == {"1", "8", "32"}
    assert all(v["gbps"] > 0 for v in detail["chain_donated"].values())
    assert set(detail["kinds"]) == {"rowcounts", "selected_gather"}
    assert all(v["before_gbps"] > 0 and v["after_gbps"] > 0
               for v in detail["kinds"].values())
    # the multi-query width sweep demonstrates the single-stream gain
    assert detail["multiquery_gain"] >= 1.2
    assert out["vs_baseline"] == detail["multiquery_gain"]
    # r17 solo fast lane: engaged (asserted in-bench via its counter)
    # and measured against the windowed path
    assert detail["solo"]["fastlane_qps"] > 0
    assert detail["solo"]["windowed_qps"] > 0
    # the whole mixed-kind window came back in one packed read
    assert detail["readback"]["packed_windows"] >= 1
    assert detail["readback"]["groups_packed"] >= 2


def test_config18_concurrency_gap_smoke():
    """bench/config18 (the product/raw concurrency-gap attribution
    bench) in --smoke mode: tiny plane, CPU, sweep 1/2/4 — runs under
    tier-1 so the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config18_concurrency_gap.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("concurrency_gap_ratio")
    assert out["unit"] == "ratio" and out["value"] > 0
    # the per-stage attribution must be present for every swept level
    stages = out["detail"]["stages"]
    assert set(stages) == {"1", "2", "4"}
    assert all({"dispatch", "read", "deliver"} <= set(s)
               for s in stages.values())
    # under four clients the window is open: its wait is a stage of
    # its own (`queue`), no longer a part of `read`
    assert stages["4"]["queue"]["n"] >= 1


def test_config20_tracing_smoke():
    """bench/config20 (sampled-tracing overhead vs tracing-off on the
    config18 concurrency workload) in --smoke mode: tiny plane, CPU,
    sweep 1/2/4, trace-id + ring-residency asserted while measuring —
    runs under tier-1 so the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config20_tracing.py"), "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("tracing_overhead_pct")
    assert out["unit"] == "pct" and out["vs_baseline"] > 0
    # both tiers measured at every swept level, every trace retained
    assert set(out["detail"]["qps_off"]) == {"1", "2", "4"}
    assert set(out["detail"]["qps_on"]) == {"1", "2", "4"}
    # rate=1.0 retains every query of the sweep (3 iterations at each
    # of the three levels, per client) and the newest is resolvable in
    # the ring (asserted inside the bench)
    assert out["detail"]["sampled_traces"] >= 3 * (1 + 2 + 4)
    # the default tier (rate 0.01) was driven too; its speed against
    # tracing-off is reported, and judged only in full runs on a
    # machine of its own (smoke shares its CPU with the other workers)
    assert out["detail"]["default_ratio"] > 0
    assert out["detail"]["default_ratio_bar"] is None


def test_config21_plane_build_smoke():
    """bench/config21 (cold vs warm plane build MB/s) in --smoke mode:
    tiny plane, CPU, cold build + sidecar-warm rebuild, Count answers
    oracle-exact on both paths, regression-guard verdict attached —
    runs under tier-1 so the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config21_plane_build.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("plane_build_cold_mbps")
    assert out["unit"] == "MBps" and out["value"] > 0
    assert out["vs_baseline"] > 0  # warm MB/s
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)
    # the warm path must have come from sidecars, not a re-expansion
    assert out["detail"]["warm_hits"] == out["detail"]["shards"]


def test_config19_backup_smoke():
    """bench/config19 (backup/restore MB/s) in --smoke mode: tiny
    plane, CPU, full + incremental + restore with an oracle check —
    runs under tier-1 so the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config19_backup.py"), "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("backup_mbps")
    assert out["unit"] == "MBps" and out["value"] > 0
    assert out["detail"]["restore_mbps"] > 0
    # the incremental property is asserted inside the bench; its
    # figures must surface in the artifact detail
    assert out["detail"]["incremental_transferred"] == 1
    assert out["detail"]["incremental_skipped"] == \
        out["detail"]["fragments"] - 1


def test_config22_availability_smoke():
    """bench/config22 (read availability through a kill -9 + rejoin) in
    --smoke mode: 3-process cluster, replicas=2, a replica-holding node
    killed MID-SERVE — the headline acceptance bar is pinned here:
    availability 1.0, i.e. ZERO failed or wrong reads through the
    failure window (replica failover + breakers), and the rejoin window
    serves clean too — runs under tier-1 so the bench can never
    bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config22_availability.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("read_availability_node_kill")
    assert out["unit"] == "ratio"
    # the acceptance criterion: zero query failures through the kill
    assert out["value"] == 1.0, out["detail"]["failure"]
    assert out["detail"]["failure"]["failed"] == 0
    assert out["detail"]["rejoin"]["failed"] == 0
    # the failure window actually exercised the failover machinery
    assert out["detail"]["failover_total"] >= 1
    assert out["detail"]["breaker_transitions_total"] >= 1
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config24_write_availability_smoke():
    """bench/config24 (WRITE availability through a kill -9 + rejoin,
    r13 hinted handoff) in --smoke mode: 3-process cluster,
    replicas=2, a replica-holding node killed MID-SERVE under mixed
    95/5 and 80/20 read/write load — the headline acceptance bar is
    pinned here: write availability 1.0 (ZERO refused or failed
    writes through the failure window), reads stay clean too, the
    rejoined node's hint backlog drains, and every node answers the
    write lanes exactly (no lost op, no resurrected clear) — runs
    under tier-1 so the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config24_write_availability.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("write_availability_node_kill")
    assert out["unit"] == "ratio"
    # the acceptance criterion: zero failed WRITES through the kill,
    # for BOTH mixes
    assert out["value"] == 1.0, out["detail"]["mixes"]
    for mix in ("95/5", "80/20"):
        m = out["detail"]["mixes"][mix]
        assert m["failure"]["writes"]["failed"] == 0, m["failure"]
        assert m["failure"]["reads"]["failed"] == 0, m["failure"]
        assert m["rejoin"]["writes"]["failed"] == 0
        # the kill actually produced hints, and they drained
        assert m["hint_backlog_ops"] >= 1
        assert m["exactness_checks"] > 0
    assert out["detail"]["hint_replay_total"] >= 1
    assert out["detail"]["hint_handoff_total"] >= 1
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config25_observability_smoke():
    """bench/config25 (full-instrumentation overhead vs metrics-off on
    the config18 concurrency workload, r14) in --smoke mode: tiny
    plane, CPU, sweep 1/2/4 — the r14 emission semantics (stage-
    histogram exemplars, window occupancy/fill, per-kernel scan bytes,
    live bandwidth gauge) are asserted INSIDE the bench while the cost
    is measured, so the <3% full-scale bar can never report a number
    for instrumentation that stopped emitting — runs under tier-1 so
    the bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config25_observability.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("observability_overhead_pct")
    assert out["unit"] == "pct" and out["vs_baseline"] > 0
    # both tiers measured at every swept level
    assert set(out["detail"]["qps_off"]) == {"1", "2", "4"}
    assert set(out["detail"]["qps_full"]) == {"1", "2", "4"}
    # the semantics the overhead pays for actually fired
    assert out["detail"]["exemplar_buckets"] > 0
    assert out["detail"]["kernel_bytes_scanned"] > 0
    assert out["detail"]["kernel_bandwidth_gbps"] > 0


def test_config26_ingest_serving_smoke():
    """bench/config26 (read qps under sustained ingest — delta planes,
    r15) in --smoke mode: one server process, 95/5 and 80/20 bulk-
    import mixes into the SAME plane the readers scan.  The ingest
    acceptance criteria are pinned here on every run: reads stay
    oracle-exact LIVE (read rows bit-exact, write row never below the
    acked-import floor — base⊕delta serving truth), quiesced write-row
    counts equal every acked column, ZERO base-plane rebuilds during
    the mixed phases, and the delta overlay actually absorbed writes.
    The qps ratio itself is gated at full scale only (CPU smoke noise)
    but must be wired through the regression guard."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config26_ingest_serving.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("read_qps_under_ingest_ratio")
    assert out["unit"] == "ratio" and out["value"] > 0
    d = out["detail"]
    # the no-rebuild-stalls criterion: hard zero at full scale (the
    # bench asserts it); at SMOKE on a fully loaded tier-1 box a
    # starved fold can exhaust its bounded race retries and fall back
    # to a legitimate rebuild (the PR 11 flake class) — mirror the
    # bench's load-tolerant smoke bar instead of re-flaking here
    assert d["plane_rebuilds_during_serving"] <= 3
    # delta overlays served the writes (absorbs moved; compactions may
    # or may not fire inside a short smoke window)
    assert d["ingest_status"]["absorbs"] >= 1
    assert d["ingest_status"]["importedBits"] > 0
    for mix in ("95/5", "80/20"):
        m = d["mixes"][mix]["under_ingest"]
        assert m["reads"]["failed"] == 0, m["reads"]
        assert m["writes"]["failed"] == 0, m["writes"]
        assert m["writes"]["bits"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config27_compound_smoke():
    """bench/config27 (compound-query compilation, r16) in --smoke
    mode: the depth-2..4 segmentation mix measured fused vs
    op-at-a-time on the same data.  Pinned on every run: every answer
    in BOTH modes oracle-exact, the tree path actually engaged (tree
    programs built — a silent fallback would make the comparison
    vacuous), and the concurrency multiplier holds the noise-adjusted
    smoke bar (>= 1.5x; full scale gates 2.0x concurrent and 1.3x
    single-stream inside the bench)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config27_compound.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("fused_tree_qps_compound_mix")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    assert d["tree_programs_built"] >= 1
    assert d["ratio_concurrent"] >= 1.5
    for mode in ("fused", "op_at_a_time"):
        assert d["modes"][mode]["concurrent"]["ok"] > 0
        assert d["modes"][mode]["single_stream"]["ok"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config28_pipeline_resilience_smoke():
    """bench/config28 (serving through a sick device, r18) in --smoke
    mode: an injected dispatch hang on one plane while unaffected
    traffic keeps flowing.  Pinned on every run — the bench itself
    asserts them while measuring: availability == 1.0 for the
    unaffected work, the wedged caller's structured 504/500 names the
    stalled stage within deadline + one watchdog period + grace, the
    governor walks degraded→healthy, and zero pipeline threads leak
    after recovery."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config28_pipeline_resilience.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("pipeline_resilience_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # the acceptance bar: a stall on one plane costs unaffected work
    # NOTHING — asserted in-bench too, re-checked here on the artifact
    assert d["stall"]["availability"] == 1.0
    assert d["stall"]["caller_status"] in (500, 504)
    assert d["stall"]["caller_stage"] in ("dispatch", "queued",
                                          "readback")
    assert d["stall"]["caller_seconds"] is not None
    assert d["healthy"]["qps"] > 0 and d["degraded"]["qps"] > 0
    assert d["degraded"]["qps_ratio"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config29_storage_integrity_smoke():
    """bench/config29 (storage integrity, r19) in --smoke mode: the
    scrub-on vs scrub-off overhead sweep (bounded at smoke; the 3%
    bar asserts at full scale) plus the measured corruption drill —
    the bench itself asserts read availability == 1.0 through a
    byte-flipped snapshot, a completed replica repair (MTTR
    reported), and a zero-divergence forced AAE round."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config29_storage_integrity.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("storage_integrity_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # the acceptance bars, asserted in-bench and re-checked here on
    # the artifact: zero read failures through the corruption window,
    # and the repair actually completed (MTTR measured)
    assert d["drill"]["availability"] == 1.0
    assert d["drill"]["mttr_seconds"] > 0
    assert d["drill"]["reads_served"] >= 8
    assert "overhead_pct" in d
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config30_pql_surface_smoke():
    """bench/config30 (full PQL surface, r20) in --smoke mode:
    per-shape qps + GB/s for Count/Range/Sum/Min/Max/GroupBy/TopN
    through the product path, then mixed-shape serving under
    sustained BSI ingest.  The ISSUE 15 acceptance bars are asserted
    IN-BENCH while measuring — oracle-exact answers live and
    quiesced, ZERO base-plane rebuilds (the BSI overlay absorbs every
    write), and same-plane aggregates provably co-batching
    (bsi_batch_hits_total > 0) — and re-checked here on the
    artifact."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config30_pql_surface.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("pql_surface_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # the whole surface measured: every shape has qps and scanned GB/s
    assert set(d["shapes"]) == {"count", "range", "sum", "min", "max",
                                "groupby", "topn"}
    assert all(v["qps"] > 0 for v in d["shapes"].values())
    assert all(v["gbps"] >= 0 for v in d["shapes"].values())
    # the r20 contracts, re-checked on the artifact
    assert d["plane_rebuilds_during_serving"] == 0
    assert d["mixed_under_ingest"]["qps"] > 0
    assert d["mixed_under_ingest"]["write_batches"] > 0
    assert d["delta_absorbs"] >= 1
    assert d["bsi_batch_hits"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config31_mesh_serving_smoke():
    """bench/config31 (mesh-sharded fused serving, r16) in --smoke
    mode: config30's mixed workload on a 1-device executor vs an
    8-device virtual CPU mesh over the same holder.  The ISSUE 16
    acceptance bars are asserted IN-BENCH — oracle-exact answers on
    sharded planes live and quiesced, ZERO base-plane rebuilds under
    sustained ingest (the replicated overlay absorbs every write),
    co-batching + one packed readback per window on the meshed
    pipeline — and re-checked here on the artifact."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config31_mesh_serving.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("mesh_serving_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # both tables measured: every shape has qps on 1 chip AND 8 chips
    for table in ("single", "mesh"):
        assert set(d[table]) == {"count", "range", "sum", "min", "max",
                                 "groupby", "topn"}
        assert all(v["qps"] > 0 for v in d[table].values())
        assert all(v["gbps"] >= 0 for v in d[table].values())
    # the r16 contracts, re-checked on the artifact
    assert d["mesh_devices"] == 8
    assert d["padded_shards"] > 0  # shard count not divisible by 8
    assert d["plane_rebuilds_during_serving"] == 0
    assert d["mixed_under_ingest"]["qps"] > 0
    assert d["mixed_under_ingest"]["write_batches"] > 0
    assert d["delta_absorbs"] >= 1
    assert d["bsi_batch_hits"] > 0
    assert d["packed_readbacks"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config32_multitenant_smoke():
    """bench/config32 (zipfian many-tenant serving under an HBM
    economy, r17) in --smoke mode: 6 tenants whose combined plane
    working set is >= 2x the budget, served through paged residency.
    The ISSUE 17 acceptance bars are asserted IN-BENCH — every read
    oracle-exact through cache churn, no tenant's availability below
    1.0, ZERO full plane rebuilds once warm (page-ins only) — and
    re-checked here on the artifact."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config32_multitenant.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("multitenant_zipf_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # the r17 acceptance bars, re-checked on the artifact
    assert d["working_set_over_budget"] >= 2.0
    assert d["plane_rebuilds_during_measurement"] == 0
    assert d["mix"]["aggregate"]["failed"] == 0
    for t, pt in d["mix"]["per_tenant"].items():
        if pt["attempts"]:
            assert pt["availability"] == 1.0, (t, pt)
    ten = d["tenancy"]
    assert ten["paging"] is True
    assert ten["pageIns"] >= d["tenants"]   # paging actually engaged
    assert ten["evictions"] >= 1            # ...and the cache churned
    # worst-tenant p99 is wired through the detail guard (inverted —
    # the guard assumes higher-is-better)
    assert d["worst_tenant_p99_inv"] is not None
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config33_event_analytics_smoke():
    """bench/config33 (event analytics over time-view planes, ISSUE
    18) in --smoke mode: recency/retention/sliding-window shapes plus
    the drained unfusable tail (Shift/Limit/ConstRow) and time-
    filtered Rows/GroupBy, then the mixed shape set under sustained
    time-bucketed ingest.  The ISSUE 18 acceptance bars are asserted
    IN-BENCH while measuring — every answer bit-exact against the
    op-at-a-time oracle live AND quiesced, ZERO time-plane rebuilds
    during mixed serving (the per-(row,bucket) overlay absorbs every
    write), the fused time-range path provably engaged
    (time_range_cover_size observed) and the static tree ops counted
    (tree_static_ops_total > 0, i.e. no silent eager fallback) — and
    re-checked here on the artifact."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config33_event_analytics.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("event_analytics_qps")
    assert out["unit"] == "qps" and out["value"] > 0
    d = out["detail"]
    # the whole surface measured: every shape has qps
    assert set(d["shapes"]) == {"recency", "retention", "sliding",
                                "rows_time", "groupby_time", "shift",
                                "limit", "constrow"}
    assert all(v["qps"] > 0 for v in d["shapes"].values())
    # the ISSUE 18 contracts, re-checked on the artifact
    assert d["plane_rebuilds_during_serving"] == 0
    assert d["delta_absorbs"] >= 1
    assert d["time_range_scans"] > 0
    assert d["tree_static_ops"] > 0
    assert d["mixed_under_ingest"]["qps"] > 0
    # the same-metric history guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config34_cost_observability_smoke():
    """bench/config34 (cost-ledger + flight-recorder overhead vs
    cost_observability=False on the config18 concurrency workload,
    ISSUE 19) in --smoke mode: tiny plane, CPU, sweep 1/2/4 — the r19
    attribution semantics (per-tenant/shape/plane rollups re-adding to
    device totals, lifecycle events in the flight ring, the compile
    family booked) are asserted INSIDE the bench while the cost is
    measured, so the <3% full-scale bar can never report a number for
    attribution that stopped attributing — runs under tier-1 so the
    bench can never bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config34_cost_observability.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("cost_observability_overhead_pct")
    assert out["unit"] == "pct" and out["vs_baseline"] > 0
    d = out["detail"]
    # both tiers measured at every swept level
    assert set(d["qps_off"]) == {"1", "2", "4"}
    assert set(d["qps_on"]) == {"1", "2", "4"}
    assert d["qps_ratio_on_off"] > 0
    # the semantics the overhead pays for actually fired
    assert d["device_seconds"] > 0
    assert d["windows"] + d["solo_dispatches"] > 0
    assert d["flight_events"] > 0 and d["flight_last_seq"] > 0
    # the detail guard must be wired (list, possibly empty)
    assert isinstance(out["regressions"], list)


def test_config35_kernel_tier_smoke():
    """bench/config35 (kernel-tier harness, r24) in --smoke mode: the
    per-tier per-kind GB/s table (pallas column interpreter-mode on
    CPU), the loop-fusion proof (a window of 8 same-shape items must
    collapse into ONE loop dispatch) and the warm-up proof (zero
    serving-path compiles on the first post-ingest serve) are asserted
    INSIDE the bench — runs under tier-1 so the bench can never
    bitrot."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "bench", "config35_kernel_tier.py"),
         "--smoke"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert len(lines) == 1, lines  # exactly ONE JSON line on stdout
    out = json.loads(lines[0])
    assert out["metric"].startswith("kernel_tier_gbps")
    assert out["unit"] == "GBps" and out["value"] > 0
    d = out["detail"]
    # both tiers measured on every kind, oracle-checked in-bench
    # (the selected-row gather has no Pallas form)
    assert set(d["tiers"]["xla"]) == {"rowcounts", "count", "selected"}
    assert set(d["tiers"]["pallas"]) == {"rowcounts", "count"}
    for tier in ("xla", "pallas"):
        assert all(v["gbps"] > 0 for v in d["tiers"][tier].values())
    assert d["pallas_mode"] == "interpret"  # CPU: the escape hatch
    # the r24 contracts, re-checked on the artifact
    assert d["loop"]["items"] == 8
    assert d["loop"]["loop_dispatches"] == 1
    assert d["loop"]["groups_fused"] == 8
    assert d["warmup"]["programs_warmed"] > 0
    assert d["warmup"]["serving_path_builds_after_ingest"] == 0
    # the detail guard (XLA oracle kinds) must be wired
    assert isinstance(out["regressions"], list)
