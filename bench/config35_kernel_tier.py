"""Config #35: kernel-tier harness (r24) — per-tier per-kind GB/s,
the on-device dispatch-loop proof, and the compile-ladder warm-up
proof.

r24 adds the ``kernel_tier="pallas"`` serving tier (hand-written
Pallas kernels for the hottest fused families, XLA kept as the
correctness oracle and governor fallback), batcher loop fusion (a
collection window's same-shape selected-count groups collapse into
ONE jitted on-device loop), and the compile-ladder warmer (the
delta-aware program ladder pre-compiles at plane-residency time, off
the serving path).  This config measures and PROVES all three:

- **tier table**: each kernel kind (whole-plane ``row_counts``, the
  ``count`` chain, and — XLA only, it has no Pallas form — the
  selected-row gather) timed per tier on the config23 plane shapes →
  GB/s side by side.  On CPU the pallas tier
  runs interpreter mode — the table proves the contract, not HBM;
  the real bandwidth column lands with the TPU round;
- **loop-fusion proof**: a collection window of 8 same-shape
  selected-count items (8 fields, identical plane geometry) must
  collapse into ONE loop dispatch — asserted via the
  ``dispatch_loop_iters`` histogram (one observation, sum 8), with
  every answer oracle-exact;
- **warm-up proof**: after plane residency + warmer drain, the first
  post-ingest (delta-overlay) serve must add ZERO fused program
  builds — the ladder pre-compiled it off the serving path.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards × 8 rows on CPU —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot.  Both proofs are asserted IN-BENCH at every scale.

Prints ONE JSON line: best GB/s across the tier table;
``vs_baseline`` = pallas/xla rowcounts ratio (1.0 when the pallas
column is interpreter-mode).  ``regressions`` carries the shared
headline guard plus detail guards on the XLA kinds (the oracle tier
must not slide while the pallas tier lands).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

SMOKE = ("--smoke" in sys.argv
         or os.environ.get("PILOSA_BENCH_SMOKE") == "1")
N_SHARDS = 2 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "954"))
N_ROWS = 8 if SMOKE else int(os.environ.get("PILOSA_BENCH_ROWS", "32"))
WORDS = 32768  # words per shard (2^20 bits / 32)
INDEX = "i"
ITERS = 3 if SMOKE else 5
N_SEL = 4  # selected-gather width for the tier table
# the proofs are CONTRACT checks, not bandwidth measures — they run at
# a fixed small geometry at every scale
PROOF_SHARDS, PROOF_ROWS = 2, 8
LOOP_FIELDS = 8  # the window of 8 same-shape items the proof collapses


def popcount(a: np.ndarray) -> np.ndarray:
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(a).astype(np.int64)
    return np.unpackbits(a.view(np.uint8), bitorder="little").reshape(
        *a.shape, 32).sum(-1).astype(np.int64)


def write_field(holder_dir: str, field: str, plane: np.ndarray) -> None:
    """One field's fragments from a packed plane (the config18
    recipe)."""
    from pilosa_tpu.store import roaring

    frag_dir = os.path.join(holder_dir, INDEX, field, "views", "standard",
                            "fragments")
    os.makedirs(frag_dir, exist_ok=True)
    for s in range(plane.shape[0]):
        with open(os.path.join(frag_dir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))


def timed(fn, nbytes: int) -> dict:
    """Warm once, then best-of-ITERS wall time → GB/s over nbytes."""
    np.asarray(fn())  # warm/compile
    best = None
    for _ in range(ITERS):
        t0 = time.perf_counter()
        np.asarray(fn())
        t = time.perf_counter() - t0
        best = t if best is None else min(best, t)
    return {"ms": round(best * 1e3, 3),
            "gbps": round(nbytes / best / 1e9, 3)}


def tier_table(plane: np.ndarray, use_pallas: bool,
               interpret: bool) -> dict:
    """GB/s per kernel kind per tier on the config23 plane shape."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import kernels, pallas_kernels

    d = jax.device_put(plane)
    flat = jax.device_put(plane.reshape(plane.shape[0], -1))
    idx = jax.device_put(
        np.linspace(0, plane.shape[1] - 1, N_SEL).astype(np.int32))
    jax.block_until_ready((d, flat, idx))
    oracle_rows = popcount(plane).sum(axis=(0, 2))

    tiers: dict = {}
    xla = {
        "rowcounts": jax.jit(kernels.row_counts),
        "count": jax.jit(kernels.count),
        "selected": jax.jit(lambda p, ix: kernels.selected_row_counts(
            p, ix, sorted_idx=True)),
    }
    plk = {
        "rowcounts": jax.jit(lambda p: pallas_kernels.row_counts(
            p, interpret=interpret)),
        "count": jax.jit(lambda w: pallas_kernels.count(
            w, interpret=interpret)),
    }
    for tier, kit in (("xla", xla),) + ((("pallas", plk),)
                                        if use_pallas else ()):
        tiers[tier] = {
            "rowcounts": timed(lambda: kit["rowcounts"](d), plane.nbytes),
            "count": timed(lambda: kit["count"](flat), plane.nbytes),
        }
        # every tier oracle-exact on the same draw
        got = np.asarray(kit["rowcounts"](d)).sum(0, dtype=np.int64)
        assert (got == oracle_rows).all(), f"{tier} rowcounts diverged"
        if "selected" in kit:
            sel_bytes = plane.shape[0] * N_SEL * WORDS * 4
            tiers[tier]["selected"] = timed(
                lambda: kit["selected"](d, idx), sel_bytes)
            got = np.asarray(kit["selected"](d, idx)).sum(
                0, dtype=np.int64)
            assert (got == oracle_rows[np.asarray(idx)]).all(), \
                f"{tier} selected gather diverged"
        log(f"tier {tier}: " + "  ".join(
            f"{k}={v['gbps']:.2f} GB/s" for k, v in tiers[tier].items()))
    del d, flat
    return tiers


def loop_fusion_proof(data_dir: str, planes: dict) -> dict:
    """A window of LOOP_FIELDS same-shape selected-count items must
    collapse into ONE loop dispatch (``dispatch_loop_iters``: one
    observation covering all groups), answers oracle-exact."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder

    holder = Holder(data_dir).open()
    stats = Stats()
    ex = Executor(holder, stats=stats, dispatch_loop_fusion=True,
                  solo_fastlane=False, count_batch_window=0.25)
    fields = sorted(planes)
    oracle = {f: popcount(planes[f]).sum(axis=(0, 2)) for f in fields}
    # residency: the selected-row gather family serves only over
    # resident whole-field planes
    for f in fields:
        ex.execute(INDEX, f"TopN({f}, n=2)")
        got = ex.execute(INDEX, f"Count(Row({f}=0))")[0]
        assert got == int(oracle[f][0]), f
    proof = None
    for attempt in range(10):
        before = stats.histogram_summary("dispatch_loop_iters") \
            .get("total", {"count": 0, "sum": 0.0})
        errors: list = []
        start = threading.Barrier(LOOP_FIELDS)

        def worker(f):
            try:
                start.wait()
                got = ex.execute(INDEX, f"Count(Row({f}=1))")[0]
                assert got == int(oracle[f][1]), (f, got)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(f,))
                   for f in fields]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[:2]
        after = stats.histogram_summary("dispatch_loop_iters") \
            .get("total", {"count": 0, "sum": 0.0})
        d_count = after["count"] - before["count"]
        d_sum = after["sum"] - before["sum"]
        if d_count == 1 and d_sum == LOOP_FIELDS:
            proof = {"items": LOOP_FIELDS, "loop_dispatches": d_count,
                     "groups_fused": int(d_sum), "attempts": attempt + 1}
            break
    holder.close()
    assert proof is not None, \
        (f"window of {LOOP_FIELDS} same-shape items never collapsed "
         f"into one loop dispatch")
    log(f"loop fusion: {LOOP_FIELDS} items -> 1 dispatch "
        f"({proof['groups_fused']} groups) on attempt "
        f"{proof['attempts']}")
    return proof


def warmup_proof(data_dir: str, plane: np.ndarray, field: str) -> dict:
    """After residency + warmer drain, the first post-ingest serve
    (base⊕delta) must add ZERO fused program builds."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder

    holder = Holder(data_dir).open()
    stats = Stats()
    ex = Executor(holder, stats=stats, fused_warmup=True)
    oracle = popcount(plane).sum(axis=(0, 2))
    ex.execute(INDEX, f"TopN({field}, n=2)")  # plane residency
    assert ex.warmer is not None and ex.warmer.wait_idle(timeout=600), \
        "warmer never drained"
    snap = stats.snapshot()["counters"]
    warmed = sum(snap.get("fused_warmup_programs_total", {}).values())
    assert warmed > 0, "warmer drained without compiling anything"
    built_before = sum(snap.get("fused_programs_built_total", {}).values())
    # ingest: the write lands in the device-side delta overlay; the
    # very next serve needs the delta-aware program the ladder
    # pre-compiled
    row = plane[0, 1]
    w = int(np.argmax(row != 0xFFFFFFFF))
    bit = int(np.argmin((row[w] >> np.arange(32, dtype=np.uint32)) & 1))
    ex.execute(INDEX, f"Set({w * 32 + bit}, {field}=1)")
    t0 = time.perf_counter()
    got = ex.execute(INDEX, f"Count(Row({field}=1))")[0]
    first_ms = (time.perf_counter() - t0) * 1e3
    assert got == int(oracle[1]) + 1, got
    built_after = sum(stats.snapshot()["counters"]
                      .get("fused_programs_built_total", {}).values())
    serving_builds = built_after - built_before
    holder.close()
    assert serving_builds == 0, \
        (f"first post-ingest serve compiled {serving_builds} program(s) "
         f"on the serving path — the ladder should have covered it")
    hp = ex.device_health()["warmup"]
    log(f"warm-up: {warmed} programs in {hp['compileSeconds']:.1f}s "
        f"off-path; first post-ingest serve {first_ms:.1f} ms with "
        f"0 serving-path builds")
    return {"programs_warmed": warmed,
            "compile_seconds": hp["compileSeconds"],
            "serving_path_builds_after_ingest": serving_builds,
            "first_post_ingest_serve_ms": round(first_ms, 1)}


def main() -> None:
    import jax

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(42)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    log(f"plane: {plane.nbytes / 1e9:.2f} GB, {N_ROWS} rows x "
        f"{N_SHARDS} shards on {platform}")

    # the pallas column: real Mosaic lowering on TPU; interpreter mode
    # on CPU only at smoke scale (the interpreter walks the grid in
    # Python — full-scale planes would take hours to say nothing new)
    on_tpu = platform == "tpu"
    use_pallas = on_tpu or SMOKE
    tiers = tier_table(plane, use_pallas, interpret=not on_tpu)

    data_dir = tempfile.mkdtemp(prefix="pilosa_c35_")
    try:
        from pilosa_tpu.store import Holder

        h = Holder(data_dir).open()
        idx = h.create_index(INDEX, track_existence=False)
        proof_planes = {}
        for k in range(LOOP_FIELDS):
            f = f"f{k}"
            idx.create_field(f)
            proof_planes[f] = rng.integers(
                0, 1 << 32, size=(PROOF_SHARDS, PROOF_ROWS, WORDS),
                dtype=np.uint32)
        idx.create_field("w")
        warm_plane = rng.integers(
            0, 1 << 32, size=(PROOF_SHARDS, PROOF_ROWS, WORDS),
            dtype=np.uint32)
        h.close()
        for f, p in proof_planes.items():
            write_field(data_dir, f, p)
        write_field(data_dir, "w", warm_plane)

        loop = loop_fusion_proof(data_dir, proof_planes)
        warm = warmup_proof(data_dir, warm_plane, "w")
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    best_gbps = max(v["gbps"] for kinds in tiers.values()
                    for v in kinds.values())
    # vs_baseline: the tier gain on the headline kind.  Interpreter
    # mode measures the contract, not bandwidth — report 1.0 so the
    # round-over-round compare only moves when a real TPU column lands
    gain = (round(tiers["pallas"]["rowcounts"]["gbps"]
                  / tiers["xla"]["rowcounts"]["gbps"], 3)
            if on_tpu and "pallas" in tiers else 1.0)

    metric = f"kernel_tier_gbps_{platform}"
    detail = {"tiers": tiers, "pallas_mode": (
        "mosaic" if on_tpu else "interpret" if use_pallas else "off"),
        "loop": loop, "warmup": warm}
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_headline",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # headline + detail guard on the XLA oracle kinds: the pallas tier
    # landing must not slide the tier every fallback depends on
    regressions = (
        mod.regression_guard(metric, best_gbps)
        + mod.detail_regression_guard(metric, detail, {
            "tier_xla_rowcounts_gbps": ("tiers", "xla", "rowcounts",
                                        "gbps"),
            "tier_xla_count_gbps": ("tiers", "xla", "count", "gbps"),
            "tier_xla_selected_gbps": ("tiers", "xla", "selected",
                                       "gbps"),
        }))
    print(json.dumps({
        "metric": metric,
        "value": round(best_gbps, 3), "unit": "GBps",
        "vs_baseline": gain,
        "regressions": regressions,
        "detail": detail}))


if __name__ == "__main__":
    main()
