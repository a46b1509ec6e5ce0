"""Config #20: sampled-tracing overhead on the concurrent serving path.

r9 makes tracing always-on: every query runs under a per-request span
tree (root ``query`` span + executor call spans + ``stage.*`` children
from the StageTimer marks), responses carry ``X-Pilosa-Trace-Id``, and
``trace_sample_rate`` decides which trees are RETAINED in the
``/internal/traces`` ring.  That machinery rides the per-request hot
path, so its cost must be measured, not assumed: this config reruns the
config18 concurrency workload (the product path, oracle-verified every
call) twice —

- **off**: ``trace_sample_rate=0``, ``slow_query_threshold=0`` (trace
  built, nothing retained — the new serving default floor);
- **on**: ``trace_sample_rate=1.0`` (EVERY query retained in the ring,
  the pathological ceiling), trace-id presence and ring residency
  asserted while measuring.

The acceptance bar: sampled-on throughput within 3% of tracing-off at
the widest concurrency level, asserted in full runs.  ``--smoke``
asserts counts only — one JSON line, both tiers at every level, the
sampler fired, the sampled trace resolvable in the ring: a speed ratio
between two bursts on a CPU that the test shares with five other test
workers proves nothing, and what the instrumentation costs is measured
once, on the chip (PERF.md, PR 26).

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards × 4 rows, sweep 1/2/4 —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot.

Prints ONE JSON line: overhead percent at the widest level,
vs_baseline = sampled-on qps there.
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
MAX_OVERHEAD = 0.03  # the r9 acceptance bar (full runs)


def write_index(plane: np.ndarray, data_dir: str) -> None:
    """A REAL on-disk index from the packed plane (the config18
    recipe): schema through the Holder, one roaring snapshot per
    shard."""
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


def measure(api, want, label: str, check_trace: bool) -> dict:
    """Sweep the concurrency levels over ``api.query``; with
    ``check_trace``, assert every response carries a resolvable trace
    id (the tracing semantics are measured WITH their cost, not
    separately)."""
    from pilosa_tpu.obs import GLOBAL_TRACER

    pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))
    assert api.query(INDEX, pql)["results"] == want, \
        f"{label}: counts diverge from oracle"

    def call():
        out = api.query(INDEX, pql)
        if out["results"] != want:
            raise AssertionError(f"{label}: count mismatch")
        if check_trace and not out.get("traceId"):
            raise AssertionError(f"{label}: response missing trace id")

    qps = {}
    for c in SWEEP:
        qps[c] = burst(call, c, ITERS, N_ROWS)
        log(f"{label:>3} {c:>2} clients: {qps[c]:,.1f} qps")
    if check_trace:
        # rate=1.0: the most recent query's trace must be resolvable
        # from the ring (the /internal/traces?trace_id= contract)
        out = api.query(INDEX, pql)
        tid = out["traceId"]
        hits = [s for s in GLOBAL_TRACER.finished() if s.trace_id == tid]
        assert len(hits) == 1, f"sampled trace {tid} not in the ring"
    return qps


def main() -> None:
    import jax

    from pilosa_tpu.api import API
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

    data_dir = tempfile.mkdtemp(prefix="pilosa_c20_")
    try:
        write_index(plane, data_dir)
        holder = Holder(data_dir).open()
        stats = Stats()
        executor = Executor(holder, stats=stats)
        # one executor (plane cache + plan cache warm once) behind two
        # API facades: the ONLY difference between the tiers is the
        # tracing retention policy under measurement
        api_off = API(holder, executor, trace_sample_rate=0.0,
                      slow_query_threshold=0.0)
        api_on = API(holder, executor, trace_sample_rate=1.0,
                     slow_query_threshold=0.0)

        t0 = time.perf_counter()
        pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))
        assert api_off.query(INDEX, pql)["results"] == want
        log(f"first product query (plane build + compile): "
            f"{time.perf_counter() - t0:.1f}s")

        qps_off = measure(api_off, want, "off", check_trace=False)
        qps_on = measure(api_on, want, "on", check_trace=True)

        top = SWEEP[-1]
        # the r05 pin (ISSUE 7): the SERVING DEFAULT — tracing
        # infrastructure on, sample rate 0.01, production slow
        # threshold — must hold >=0.95x of tracing-off.  r05 fell to
        # 0.41 exactly here: the default config materialized a span
        # tree per query regardless of the retention decision.
        # Interleaved best-of-5 bursts at the widest level filter
        # scheduler noise; full runs hold the 0.95 acceptance bar,
        # smoke runs one burst of each and reports the ratio unjudged.
        default_bar = None if SMOKE else 0.95
        api_default = API(holder, executor, trace_sample_rate=0.01,
                          slow_query_threshold=1.0)

        def one(api_):
            def call():
                if api_.query(INDEX, pql)["results"] != want:
                    raise AssertionError("default-tier count mismatch")
            return burst(call, top, ITERS * 3, N_ROWS)

        runs_off, runs_def = [], []
        for _ in range(1 if SMOKE else 5):
            runs_off.append(one(api_off))
            runs_def.append(one(api_default))
        best_off = max(runs_off)
        best_def = max(runs_def)
        default_ratio = best_def / best_off
        log(f"default-config tracing ratio at {top} clients: "
            f"{default_ratio:.3f} (default {best_def:,.1f} qps / off "
            f"{best_off:,.1f} qps; bar {default_bar})")
        assert SMOKE or default_ratio >= default_bar, \
            (f"default tracing config serves {default_ratio:.2f}x of "
             f"tracing-off; the r05-regression pin is {default_bar}x")
        overhead = 1.0 - qps_on[top] / qps_off[top]
        sampled = sum(stats.snapshot()["counters"]
                      .get("trace_sampled_total", {}).values())
        assert sampled >= len(SWEEP) * ITERS, \
            f"sampler never fired at rate=1.0 (counted {sampled})"
        log(f"tracing overhead at {top} clients: {overhead * 100:.2f}% "
            f"(off {qps_off[top]:,.1f} qps / on {qps_on[top]:,.1f} qps; "
            f"{sampled} traces retained)")
        assert SMOKE or overhead < MAX_OVERHEAD, \
            (f"sampled tracing costs {overhead:.2%} at {top} "
             f"clients; the r9 bar is {MAX_OVERHEAD:.0%}")
        holder.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    print(json.dumps({
        "metric": f"tracing_overhead_pct_{platform}",
        "value": round(overhead * 100, 2), "unit": "pct",
        "vs_baseline": round(qps_on[top], 1),
        "detail": {"qps_off": {str(k): round(v, 1)
                               for k, v in qps_off.items()},
                   "qps_on": {str(k): round(v, 1)
                              for k, v in qps_on.items()},
                   "default_ratio": round(default_ratio, 3),
                   "default_ratio_bar": default_bar,
                   "sampled_traces": sampled}}))


if __name__ == "__main__":
    main()
