"""The compile-ladder warm-up (r24, ``fused_warmup``): delta-aware
fused programs for a newly resident plane shape pre-compile off the
serving path, so the first post-ingest serve builds nothing; off by
default and under a placement.  Also pins the fused program KEYS: the
persistent compile cache and ``fused.compiles_in_window`` depend on a
key meaning the same program from one commit to the next.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.fused import FusedCache
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


@pytest.mark.parametrize("kw", [{}, {"count_batch_window": 0}],
                         ids=["batcher", "no-batcher"])
def test_status_carries_warmup(tmp_path, kw):
    # with and without a batcher, deviceHealth carries the same keys
    health = make_env(tmp_path, "x", **kw).device_health()
    assert health["state"] == "healthy"
    assert health["warmup"] == {"enabled": False, "shapesWarmed": 0,
                                "programsWarmed": 0,
                                "compileSeconds": 0.0, "pending": 0}
    assert "kernelTier" not in health


# the keys the commit before the Pallas tier went (f73c938) built for
# the same calls, copied as literals: a key that moves is a new entry
# in every deployment's persistent compile cache
_NODES = (("and", (("leaf", 0), ("leaf", 1))), ("leaf", 0))
PROGRAM_KEYS = {
    "count-batch":
        ((_NODES, False, None), "count-batch"),
    "rowcounts-batch":
        ((False, True), (2, 8, 64), None, False, "rowcounts-batch"),
    "rowcounts-delta":
        (("rowcounts-delta", (2, 8, 64), None, 4, True, True), "count"),
}


@pytest.mark.parametrize("family", sorted(PROGRAM_KEYS))
def test_program_key_is_the_parents(family):
    fc = FusedCache()
    row = jnp.asarray(np.arange(2 * 64, dtype=np.uint32).reshape(2, 64))
    ones = jnp.ones((2, 64), jnp.uint32)
    plane = jnp.ones((2, 8, 64), jnp.uint32)
    if family == "count-batch":
        out = fc.run_count_batch(_NODES, (row, ones))
        assert np.asarray(out).tolist() == [[32, 32], [192, 256]]
    elif family == "rowcounts-batch":
        out = fc.run_rowcounts_batch((False, True), (plane, plane, ones))
        assert np.asarray(out).tolist() == [[128] * 8, [128] * 8]
    else:
        from pilosa_tpu.ingest.delta import DeltaOverlay
        # one live cell (shard 0, row 3, word 0 now empty), three pads
        delta = DeltaOverlay(jnp.asarray([3, 16, 16, 16], jnp.int32),
                             jnp.zeros((4,), jnp.int32),
                             jnp.zeros((4,), jnp.uint32), n=1, bits=0)
        out = fc.run_rowcounts_delta(plane, delta, filter_words=ones)
        assert np.asarray(out).tolist() == [128] * 3 + [127] + [128] * 4
    assert list(fc._programs) == [PROGRAM_KEYS[family]]


def test_warm_ladder_keys_are_the_serving_keys():
    # the warmer's rungs for one shape x overlay bucket, as the parent
    # keyed them: what it compiles IS what the serving path looks up
    keys = [job[0] for job in FusedCache()._warm_jobs((2, 8, 64), 4)]
    assert keys == [
        (("rowcounts-delta", (2, 8, 64), None, 4, False, True), "count"),
        (("rowcounts-delta", (2, 8, 64), None, 4, True, True), "count"),
        (("selcounts-delta", (2, 8, 64), None, 1, 4, True, False),
         "count"),
        (("selcounts-delta", (2, 8, 64), None, 1, 4, True, True),
         "count"),
    ]


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
        hist = stats.histogram_summary("fused_warmup_compile_seconds")
        assert hist["total"]["count"] >= 1 and hist["total"]["sum"] > 0
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
