"""Config #34: cost-ledger + flight-recorder overhead on the hot path.

r19 attaches per-window device-cost attribution (the ledger: every
dispatch's wall + bytes apportioned per tenant/shape/plane) and an
always-on flight recorder (a preallocated ring of lifecycle events) to
the dispatch spine.  Both were designed to stay off the healthy hot
path — plain counters, per-group dict stamps, lock-free ring writes —
and that claim must be measured, not assumed: this config reruns the
config18 concurrency workload (the config25 contract) twice —

- **off**: ``cost_observability=False`` — null ledger + null flight
  recorder end to end (the attribution floor);
- **on**: the default — real ledger and ring, with the attribution
  semantics asserted WHILE measuring (per-tenant/shape/plane rollups
  present and re-adding to totals, lifecycle events in the ring, the
  compile family booked) so the cost figure covers what it claims.

Both tiers run a real ``Stats`` registry and identical lite tracing:
the ONLY delta under measurement is the r19 cost plane.

Acceptance: within 3% of off at the widest concurrency level in full
runs; ``--smoke`` (tiny planes, CPU, fixed costs dominate) only
sanity-bounds the ratio and asserts the semantics.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards × 4 rows, sweep 1/2/4 —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot.

Prints ONE JSON line: overhead percent at the widest level,
vs_baseline = fully-attributed qps there.
"""

from __future__ import annotations

import importlib.util
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
MAX_OVERHEAD = 0.03  # the r19 acceptance bar (full runs)


def regression_guards(metric: str, detail: dict) -> list:
    """The round-over-round guard (bench.py machinery): the tracked
    sub-metric is the on/off qps RATIO — overhead creeping up shrinks
    it, so a future change that quietly fattens the cost plane fails
    the guard even while absolute qps wanders run to run."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.detail_regression_guard(
        metric, detail,
        {"cost_obs_qps_ratio": ("qps_ratio_on_off",)}, ratio=0.9)


def write_index(plane: np.ndarray, data_dir: str) -> None:
    """A REAL on-disk index from the packed plane (the config18
    recipe)."""
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


def measure(api, want, label: str) -> dict:
    pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))
    assert api.query(INDEX, pql)["results"] == want, \
        f"{label}: counts diverge from oracle"

    def call():
        if api.query(INDEX, pql)["results"] != want:
            raise AssertionError(f"{label}: count mismatch")

    qps = {}
    for c in SWEEP:
        qps[c] = burst(call, c, ITERS, N_ROWS)
        log(f"{label:>4} {c:>2} clients: {qps[c]:,.1f} qps")
    return qps


def assert_r19_attribution(ex) -> dict:
    """The semantics the overhead figure pays for, asserted on the
    attributed tier AFTER measurement: the ledger saw the traffic and
    its rollups re-add to totals; the flight ring holds lifecycle
    events; the compile family was booked."""
    costs = ex.cost_status()
    assert costs["deviceSecondsTotal"] > 0, "ledger charged nothing"
    assert costs["bytesScannedTotal"] > 0, "no bytes attributed"
    assert INDEX in costs["tenants"], "tenant rollup missing"
    assert costs["tenants"][INDEX]["items"] > 0
    assert costs["trackedShapes"] >= 1, "shape rollup missing"
    assert costs["trackedPlanes"] >= 1, "plane rollup missing"
    # the per-tenant device seconds re-add to the total (one tenant
    # here, so exactly)
    ten_s = sum(row[0] for row in ex.ledger._tenants.values())
    assert abs(ten_s - ex.ledger.total_seconds) < 1e-9, \
        "tenant rollup diverged from the device total"
    assert costs["compileCount"] >= 1, "no compile was booked"
    snap = ex.flight.snapshot()
    kinds = {e["kind"] for e in snap["events"]}
    assert "compile" in kinds, f"no compile flight event: {kinds}"
    # windowed serving leaves dispatch/deliver pairs; solo fast-lane
    # traffic may serve everything inline — require lifecycle coverage
    # only when windows actually formed
    if costs["windows"]:
        assert "dispatch" in kinds and "deliver" in kinds, \
            f"window lifecycle events missing from the ring: {kinds}"
    return {"device_seconds": round(costs["deviceSecondsTotal"], 4),
            "windows": costs["windows"],
            "solo_dispatches": costs["soloDispatches"],
            "flight_events": len(snap["events"]),
            "flight_last_seq": snap["lastSeq"]}


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

    data_dir = tempfile.mkdtemp(prefix="pilosa_c34_")
    try:
        write_index(plane, data_dir)
        holder = Holder(data_dir).open()
        # two executors over ONE holder; both run a real registry so
        # the only delta is the cost plane itself
        ex_off = Executor(holder, stats=Stats(),
                          cost_observability=False)
        ex_on = Executor(holder, stats=Stats())
        api_off = API(holder, ex_off, trace_sample_rate=0.0,
                      slow_query_threshold=0.0)
        api_on = API(holder, ex_on, trace_sample_rate=0.0,
                     slow_query_threshold=0.0)

        pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))
        t0 = time.perf_counter()
        assert api_off.query(INDEX, pql)["results"] == want
        assert api_on.query(INDEX, pql)["results"] == want
        log(f"first product queries (plane build + compile): "
            f"{time.perf_counter() - t0:.1f}s")

        qps_off = measure(api_off, want, "off")
        qps_on = measure(api_on, want, "on")

        top = SWEEP[-1]
        overhead = 1.0 - qps_on[top] / qps_off[top]
        attribution = assert_r19_attribution(ex_on)
        # the off tier really was off
        assert ex_off.cost_status()["deviceSecondsTotal"] == 0.0
        assert ex_off.flight.snapshot()["events"] == []
        log(f"cost-observability overhead at {top} clients: "
            f"{overhead * 100:.2f}% (off {qps_off[top]:,.1f} qps / on "
            f"{qps_on[top]:,.1f} qps; {attribution})")
        if SMOKE:
            # toy scale: fixed per-query costs dominate and run-to-run
            # noise far exceeds 3% — bound catastrophe only
            assert overhead < 0.5, \
                f"smoke cost-observability overhead {overhead:.2%} " \
                f"is pathological"
        else:
            assert overhead < MAX_OVERHEAD, \
                (f"cost observability costs {overhead:.2%} at {top} "
                 f"clients; the r19 bar is {MAX_OVERHEAD:.0%}")
        holder.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    metric = f"cost_observability_overhead_pct_{platform}"
    detail = {"qps_off": {str(k): round(v, 1)
                          for k, v in qps_off.items()},
              "qps_on": {str(k): round(v, 1)
                         for k, v in qps_on.items()},
              "qps_ratio_on_off": round(qps_on[top] / qps_off[top], 4),
              **attribution}
    print(json.dumps({
        "metric": metric,
        "value": round(overhead * 100, 2), "unit": "pct",
        "vs_baseline": round(qps_on[top], 1),
        "detail": detail,
        "regressions": regression_guards(metric, detail)}))


if __name__ == "__main__":
    main()
