"""Kernel-tier integration tests (r24): the ``kernel_tier="pallas"``
serving tier must be bit-exact against the XLA oracle tier THROUGH the
executor and batcher — clean planes, delta overlays under interleaved
ingest, governor-degraded fallback, and a LOUD (logged with the
compiler's message, counted) XLA fallback on a lowering failure.  On
CPU the pallas tier runs interpret-mode via the test-only
``PILOSA_PALLAS_INTERPRET`` escape hatch; without it, asking for the
tier where it cannot run raises at ``Executor`` construction.  Also covers the r24 dispatch-loop fusion
(one jitted loop per same-shape window) and the compile-ladder
warm-up (zero serving-path compiles after ingest).
"""

import threading

import pytest

from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import Stats
from pilosa_tpu.store import FieldOptions, Holder


def make_env(tmp_path, name, **kw):
    holder = Holder(str(tmp_path / name)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("amount",
                     FieldOptions(type="int", min=-1000, max=1000))
    return Executor(holder, **kw)


def seed(ex):
    for c in range(60):
        ex.execute("i", f"Set({c}, f={c % 5})")
        if c % 2 == 0:
            ex.execute("i", f"Set({c}, g={c % 3})")
    for c in range(20):
        ex.execute("i", f"Set({c}, amount={c * 7 - 30})")


# every wired fused family: selected counts (clean + boolean trees),
# whole-plane rowcounts (TopN), count chains, BSI presence scans
FAMILY_QUERIES = (
    "Count(Row(f=1))",
    "Count(Row(f=4))",
    "Count(Intersect(Row(f=1), Row(g=1)))",
    "Count(Union(Row(f=0), Row(f=2), Row(f=3)))",
    "Count(Difference(Row(f=1), Row(g=0)))",
    "TopN(f, n=5)",
    "Distinct(field=amount)",
    "Sum(field=amount)",
)


class TestTierResolution:
    def test_default_is_xla(self, tmp_path):
        ex = make_env(tmp_path, "x")
        assert ex.fused.kernel_tier == "xla"
        assert ex.fused.effective_tier == "xla"

    def test_pallas_off_tpu_raises_at_construction(self, tmp_path,
                                                   monkeypatch):
        # no TPU backend and no interpret escape hatch: a start-up
        # error naming the backend, never a quiet XLA serve
        monkeypatch.delenv("PILOSA_PALLAS_INTERPRET", raising=False)
        with pytest.raises(ValueError, match="needs the TPU backend.*cpu"):
            make_env(tmp_path, "x", kernel_tier="pallas")

    def test_pallas_with_placement_raises_at_construction(
            self, tmp_path, monkeypatch):
        # Mosaic calls cannot be auto-partitioned over a mesh: until
        # the kernels are wrapped in shard_map, the combination is a
        # start-up error even where the tier itself could run
        from pilosa_tpu.parallel import MeshPlacement
        monkeypatch.setenv("PILOSA_PALLAS_INTERPRET", "1")
        with pytest.raises(ValueError, match="mesh placement"):
            make_env(tmp_path, "x", kernel_tier="pallas",
                     placement=MeshPlacement())

    def test_unknown_tier_raises(self, tmp_path):
        with pytest.raises(ValueError, match="kernel_tier"):
            make_env(tmp_path, "x", kernel_tier="mosaic")

    def test_interpret_escape_hatch(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PILOSA_PALLAS_INTERPRET", "1")
        ex = make_env(tmp_path, "x", kernel_tier="pallas")
        assert ex.fused.effective_tier == "pallas-interpret"

    def test_status_carries_tier_and_warmup(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PILOSA_PALLAS_INTERPRET", "1")
        ex = make_env(tmp_path, "x", kernel_tier="pallas")
        health = ex.device_health()
        assert health["kernelTier"] == "pallas-interpret"
        assert health["warmup"]["enabled"] is False
        # batcher-less executor: trivial branch carries the same keys
        ex2 = make_env(tmp_path, "y", count_batch_window=0)
        h2 = ex2.device_health()
        assert h2["kernelTier"] == "xla" and "warmup" in h2


class TestTierParity:
    """Same data, same queries, one executor per tier — answers must be
    bit-identical through the full executor+batcher path."""

    @pytest.fixture
    def pair(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PILOSA_PALLAS_INTERPRET", "1")
        ex_x = make_env(tmp_path, "xla", kernel_tier="xla")
        ex_p = make_env(tmp_path, "pallas", kernel_tier="pallas")
        seed(ex_x)
        seed(ex_p)
        return ex_x, ex_p

    def test_all_families_clean_and_delta(self, pair):
        ex_x, ex_p = pair
        for pql in FAMILY_QUERIES:
            assert ex_x.execute("i", pql) == ex_p.execute("i", pql), pql
        # interleaved ingest: writes land in the device-side delta
        # overlay and the base⊕delta program must stay one tier-routed
        # dispatch with identical answers
        for step in range(3):
            for ex in (ex_x, ex_p):
                ex.execute("i", f"Set({900 + step}, f=1)")
                ex.execute("i", f"Set({940 + step}, g={step % 3})")
            for pql in FAMILY_QUERIES:
                assert ex_x.execute("i", pql) == ex_p.execute("i", pql), \
                    f"{pql} diverged at ingest step {step}"
        assert ex_p.fused.effective_tier == "pallas-interpret"
        assert ex_p.fused.pallas_fallbacks == 0
        # the pallas cache keyed its programs under the tier token, so
        # the key spaces never collide with the oracle tier's
        assert any("pallas" in str(k) for k in ex_p.fused._programs)
        assert not any("pallas" in str(k) for k in ex_x.fused._programs)

    def test_degraded_governor_fallback_parity(self, pair):
        ex_x, ex_p = pair
        want = [ex_x.execute("i", pql) for pql in FAMILY_QUERIES]
        # trip the watchdog breaker: DEGRADED serving executes per
        # item on the proven op-at-a-time XLA fallback whatever the
        # configured tier — answers must not move
        ex_p.batcher.governor.record_trip()
        assert ex_p.device_health()["state"] == "degraded"
        got = [ex_p.execute("i", pql) for pql in FAMILY_QUERIES]
        assert got == want


class TestLoweringFallback:
    def test_loud_xla_fallback_and_counter(self, tmp_path, monkeypatch):
        import logging
        monkeypatch.setenv("PILOSA_PALLAS_INTERPRET", "1")
        stats = Stats()
        ex = make_env(tmp_path, "p", stats=stats, kernel_tier="pallas")
        seed(ex)
        want = make_env(tmp_path, "x")
        seed(want)

        from pilosa_tpu.engine import pallas_kernels

        def boom(*a, **kw):
            raise RuntimeError("Mosaic lowering failed (simulated)")

        monkeypatch.setattr(pallas_kernels, "row_counts", boom)
        errors = []

        class Collect(logging.Handler):
            def emit(self, record):
                errors.append(record.getMessage())

        handler = Collect(level=logging.ERROR)
        log = logging.getLogger("pilosa_tpu.exec")
        log.addHandler(handler)
        try:
            # the query still answers — the family re-dispatches
            # through the XLA oracle program — but the failure is
            # counted AND logged with the compiler's own message
            assert ex.execute("i", "TopN(f, n=3)") == \
                want.execute("i", "TopN(f, n=3)")
            # the shape is marked bad: the next serve skips pallas
            # without re-failing (no new fallback tick, no new line)
            ex.execute("i", "TopN(f, n=2)")
        finally:
            log.removeHandler(handler)
        assert ex.fused.pallas_fallbacks == 1
        fb = stats.snapshot()["counters"].get("pallas_fallback_total", {})
        assert sum(fb.values()) == 1
        assert any("lowering" in str(k) for k in fb)
        assert len(errors) == 1
        assert "Mosaic lowering failed (simulated)" in errors[0]
        assert "rowcounts" in errors[0]


class TestLoopFusion:
    def test_window_collapses_to_one_loop_dispatch(self, tmp_path):
        stats = Stats()
        ex = make_env(tmp_path, "loop", stats=stats,
                      dispatch_loop_fusion=True, solo_fastlane=False,
                      count_batch_window=0.05)
        # identical row geometry => identical plane shapes, the
        # grouping rule's fusion signature
        for r in range(5):
            for c in range(3 * (r + 1)):
                ex.execute("i", f"Set({c}, f={r})")
                ex.execute("i", f"Set({c}, g={r})")
        # residency first: the selected-row gather family (the one the
        # loop fuses) serves only over resident whole-field planes
        ex.execute("i", "TopN(f, n=3)")
        ex.execute("i", "TopN(g, n=3)")
        want_f = {r: ex.execute("i", f"Count(Row(f={r}))")[0]
                  for r in range(5)}
        want_g = {r: ex.execute("i", f"Count(Row(g={r}))")[0]
                  for r in range(5)}
        assert ex.batcher.loop_fusion

        fused_seen = False
        for _ in range(12):
            errors = []
            start = threading.Barrier(8)

            def worker(i):
                try:
                    start.wait()
                    fld = "f" if i % 2 else "g"
                    want = want_f if i % 2 else want_g
                    for r in range(5):
                        got = ex.execute("i", f"Count(Row({fld}={r}))")[0]
                        assert got == want[r], (fld, r, got)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors[:2]
            hist = stats.histogram_summary("dispatch_loop_iters")
            if hist.get("total", {}).get("count", 0) >= 1:
                fused_seen = True
                break
        assert fused_seen, \
            "same-shape selcounts window never fused into a loop dispatch"
        # every loop dispatch covered >= 2 groups in ONE program launch
        hist = stats.histogram_summary("dispatch_loop_iters")
        assert hist["total"]["sum"] >= 2 * hist["total"]["count"]


class TestCompileLadderWarmup:
    def test_first_post_ingest_serve_is_compile_free(self, tmp_path):
        stats = Stats()
        ex = make_env(tmp_path, "warm", stats=stats, fused_warmup=True)
        seed(ex)
        # residency: a whole-plane query pages the standard plane in,
        # which queues its shape on the warmer
        ex.execute("i", "TopN(f, n=3)")
        ex.execute("i", "Count(Row(f=1))")
        assert ex.warmer is not None
        assert ex.warmer.wait_idle(timeout=300)
        snap = stats.snapshot()["counters"]
        warmed = sum(snap.get("fused_warmup_programs_total", {}).values())
        assert warmed > 0
        built_before = sum(
            snap.get("fused_programs_built_total", {}).values())
        hp = ex.device_health()["warmup"]
        assert hp["enabled"] and hp["programsWarmed"] == warmed
        assert hp["shapesWarmed"] >= 1 and hp["pending"] == 0
        secs = stats.snapshot()["counters"]
        hist = stats.histogram_summary("fused_warmup_compile_seconds")
        assert hist["total"]["count"] >= 1 and hist["total"]["sum"] > 0
        del secs
        # ingest then serve: the delta-aware program the first
        # post-ingest query needs was pre-compiled off the serving
        # path — ZERO new fused program builds
        ex.execute("i", "Set(901, f=1)")
        assert ex.execute("i", "Count(Row(f=1))") == [13]
        built_after = sum(stats.snapshot()["counters"]
                          .get("fused_programs_built_total", {}).values())
        assert built_after == built_before, \
            "post-ingest serve compiled on the serving path"

    def test_warmup_disabled_under_placement_and_by_default(self, tmp_path):
        ex = make_env(tmp_path, "off")
        assert ex.warmer is None
        assert ex.device_health()["warmup"]["enabled"] is False
