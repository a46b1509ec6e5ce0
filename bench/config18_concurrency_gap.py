"""Config #18: the product/raw CONCURRENCY GAP, attributed per stage.

BENCH_r05 measured the rebuild's kernels at 5,473 count-qps (32-way, 1B
cols) while the product path (PQL → Executor → fused dispatch → read)
served 2,263 qps at the same concurrency — ratio 0.41, with per-query
LATENCY within ±8% of the read floor.  The missing 59% is therefore
per-request host work that serializes under concurrency; this config
measures it instead of guessing:

- sweep 1..64 concurrent clients over (a) the RAW jitted count-batch
  program (device ceiling) and (b) the PRODUCT path (`API.query`),
  every product response oracle-verified;
- print qps and the product/raw ratio per concurrency level;
- dump the request's per-stage clock (admit / plan_cache / parse /
  plan / queue / dispatch / read / deliver / assemble,
  ``query_stage_seconds``) per level, so the residual gap is
  attributed per stage — the batcher's window wait (``queue``) apart
  from the program enqueue and the device→host read.

The r6 serving-spine work this config exists to measure: the query-plan
cache (repeat shapes skip parse/plan), the default-on adaptive batcher
(N concurrent requests of a dense family pay one dispatch + one read),
and the lock-free fused/plane cache hit paths.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): tiny plane (2 shards × 4 rows)
on CPU, sweep 1/2/4 — tier-1 runs it (tests/test_bench_smoke.py) so
this bench can never bitrot.

Prints ONE JSON line: product/raw ratio at the widest level,
vs_baseline = the product qps there.
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
N_ROWS = 4 if SMOKE else int(os.environ.get("PILOSA_BENCH_ROWS", "32"))
SWEEP = ((1, 2, 4) if SMOKE else (1, 2, 4, 8, 16, 32, 64))
ITERS = 3 if SMOKE else 6
WORDS = 32768  # words per shard (2^20 bits / 32)
INDEX, FIELD = "i", "f"

STAGES = ("admit", "plan_cache", "parse", "plan", "queue", "dispatch",
          "read", "deliver", "assemble")


def write_index(plane: np.ndarray, data_dir: str) -> None:
    """A REAL on-disk index from the packed plane: schema through the
    Holder, one roaring snapshot file per shard (the bench.py product
    writer's recipe)."""
    from pilosa_tpu.store import Holder, roaring

    h = Holder(data_dir).open()
    idx = h.create_index(INDEX, track_existence=False)
    idx.create_field(FIELD)
    h.close()
    frag_dir = os.path.join(data_dir, INDEX, FIELD, "views", "standard",
                            "fragments")
    os.makedirs(frag_dir, exist_ok=True)
    for s in range(plane.shape[0]):
        with open(os.path.join(frag_dir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))


def burst(fn, n_threads: int, iters: int, queries_per_call: int):
    """n_threads concurrent clients each calling fn() iters times;
    returns qps (raises on any worker error — a wrong answer under
    concurrency is a failure, not a statistic)."""
    barrier = threading.Barrier(n_threads + 1)
    errors: list = []

    def worker():
        barrier.wait()
        for _ in range(iters):
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — surface after join
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise AssertionError(f"burst errors: {errors[:3]}")
    return queries_per_call * iters * n_threads / dt


def stage_delta(stats, before: dict) -> dict:
    """Per-stage (count, mean_ms) since ``before`` (a prior summary)."""
    now = stats.histogram_summary("query_stage_seconds")
    out = {}
    for label, cur in now.items():
        stage = label.split("=", 1)[-1]
        prev = before.get(label, {"count": 0, "sum": 0.0})
        n = cur["count"] - prev["count"]
        s = cur["sum"] - prev["sum"]
        if n > 0:
            out[stage] = {"n": n, "mean_ms": round(s / n * 1e3, 3),
                          "total_s": round(s, 3)}
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.api import API
    from pilosa_tpu.engine import kernels
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(42)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    oracle = (np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)
              if hasattr(np, "bitwise_count") else
              np.array([int(np.unpackbits(
                  plane[:, r].reshape(-1).view(np.uint8)).sum())
                  for r in range(N_ROWS)], dtype=np.int64))
    want = [int(c) for c in oracle]

    # ---------------------------------------------------------- raw tier
    d = jax.device_put(plane)
    jax.block_until_ready(d)

    @jax.jit
    def count_batch(p):
        return jnp.sum(kernels.row_counts(p), axis=0, dtype=jnp.int32)

    got = np.asarray(count_batch(d)).astype(np.int64)
    np.testing.assert_array_equal(got, oracle)

    def raw_call():
        if not np.array_equal(np.asarray(count_batch(d)).astype(np.int64),
                              oracle):
            raise AssertionError("raw count mismatch")

    raw_qps = {}
    for c in SWEEP:
        raw_qps[c] = burst(raw_call, c, ITERS, N_ROWS)
        log(f"raw   {c:>2} clients: {raw_qps[c]:,.1f} qps")

    # ------------------------------------------------------ product tier
    data_dir = tempfile.mkdtemp(prefix="pilosa_c18_")
    try:
        write_index(plane, data_dir)
        holder = Holder(data_dir).open()
        stats = Stats()
        api = API(holder, Executor(holder, stats=stats))
        pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))

        t0 = time.perf_counter()
        assert api.query(INDEX, pql)["results"] == want, \
            "product counts diverge from oracle"
        log(f"first product query (plane build + compile): "
            f"{time.perf_counter() - t0:.1f}s")
        # second query = plan-cache hit; assert the cache engaged
        assert api.query(INDEX, pql)["results"] == want
        hits = stats.snapshot()["counters"].get("plan_cache_hits", {})
        assert sum(hits.values()) >= 1, "plan cache never hit"

        def product_call():
            if api.query(INDEX, pql)["results"] != want:
                raise AssertionError("product count mismatch")

        prod_qps = {}
        stages_by_c = {}
        for c in SWEEP:
            before = stats.histogram_summary("query_stage_seconds")
            prod_qps[c] = burst(product_call, c, ITERS, N_ROWS)
            stages_by_c[c] = stage_delta(stats, before)
            ratio = prod_qps[c] / raw_qps[c]
            log(f"prod  {c:>2} clients: {prod_qps[c]:,.1f} qps "
                f"(product/raw {ratio:.2f})")
            per_stage = ", ".join(
                f"{s} {stages_by_c[c][s]['mean_ms']:.2f}ms"
                for s in STAGES if s in stages_by_c[c])
            log(f"      stages: {per_stage}")

        top = SWEEP[-1]
        ratio = prod_qps[top] / raw_qps[top]
        pc = stats.snapshot()["counters"]
        log(f"plan cache: hits={sum(pc.get('plan_cache_hits', {}).values())}"
            f" misses={sum(pc.get('plan_cache_misses', {}).values())}"
            f" invalidations="
            f"{sum(pc.get('plan_cache_invalidations', {}).values())}")
        log(f"batcher window now: "
            f"{api.executor.batcher.current_window * 1e3:.2f} ms"
            if api.executor.batcher is not None else "batcher: off")
        log(f"product/raw ratio at {top} clients: {ratio:.2f} "
            f"(was 0.41 pre-r6, BENCH_r05)")
        holder.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    print(json.dumps({
        "metric": f"concurrency_gap_ratio_{platform}",
        "value": round(ratio, 3), "unit": "ratio",
        "vs_baseline": round(prod_qps[top], 1),
        "detail": {"raw_qps": {str(k): round(v, 1)
                               for k, v in raw_qps.items()},
                   "product_qps": {str(k): round(v, 1)
                                   for k, v in prod_qps.items()},
                   "stages": {str(k): v for k, v in stages_by_c.items()}}}))


if __name__ == "__main__":
    main()
