"""Config #14: CONCURRENT MIXED-FAMILY SERVING at the 1B-column
condition (VERDICT r4 #1 — "the honest version of the serving condition
every headline already claims").

config10 proved each family fast in ISOLATION, single-stream.  This
config drives 32 concurrent client threads, each running a shuffled
deck of mixed queries — Count batches, filtered TopN, BSI Sum and
Range, GroupBy, sparse TopN — against one executor with dense + BSI +
sparse residency all live, and asserts ZERO errors while measuring
aggregate qps and per-family p50/p99.

Two scenarios:

  A. headline scale (954 shards = 1B cols), plane budget sized so all
     residency fits (~6 GB of an ~16 GB chip) — pressure comes from 32
     concurrent dispatches' scratch on top of it
  B. admission contention: a small index with the budget deliberately
     too small for both the dense and BSI planes, so every alternation
     crosses the admission gate under concurrency (the r4 OOM-retry
     thrash class, now cross-query-coordinated — exec/executor.py
     _with_oom_retry + planes.evict_unpinned)

Oracle answers are computed once; every thread checks every result
(a wrong answer under contention is a failure, not a statistic).

Prints ONE JSON line: mixed_serving_qps at scenario A, vs_baseline =
overlap speedup vs one serial stream of the same deck."""

import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log
from bench.config10_product_families import (
    INDEX, N_ROWS, N_SHARDS, build_index, median_lat, oracle_bsi,
    oracle_counts, oracle_filtered_topn, oracle_groupby, oracle_sparse_topn)

N_THREADS = int(os.environ.get("PILOSA_BENCH_THREADS", "32"))
PQL_GB = "GroupBy(Rows(f, limit=4), Rows(f, previous=3, limit=4))"
PQL_SPARSE = "TopN(tags, n=5, filter=Row(f=0))"


def probe_free_hbm(limit_gb: float) -> float:
    """Allocate-then-free device probe: how much HBM is grabbable right
    now, up to ``limit_gb`` (the chip is time-shared; see await_hbm)."""
    import gc

    import jax

    held, got = [], 0.0
    try:
        while got < limit_gb:
            held.append(jax.device_put(
                np.zeros((512, 1 << 20), np.uint8)))
            held[-1].block_until_ready()
            got += 0.5
    except Exception:  # noqa: BLE001 — RESOURCE_EXHAUSTED probe edge
        pass
    del held
    gc.collect()
    return got


def await_hbm(need_gb: float, attempts: int = 20, wait: float = 60.0):
    """Free-HBM gate for a chip shared with other tenants — measured
    free memory swung 16.4 GB → <4.5 GB → 16.4 GB within an hour (r5).  A
    run that starts into a low window wastes 20 minutes and dies; probe
    until the window is big enough."""
    for attempt in range(attempts):
        got = probe_free_hbm(need_gb)
        if got >= need_gb:
            log(f"HBM gate: >= {need_gb:.0f} GB free (attempt "
                f"{attempt + 1})")
            return
        log(f"HBM gate: only ~{got:.1f} GB free (need {need_gb:.0f}); "
            f"waiting {wait:.0f}s")
        time.sleep(wait)
    raise SystemExit(f"chip never had {need_gb} GB free")


def build_deck():
    """One client's work unit: weighted toward the cheap/common ops the
    way real traffic is, but every family present."""
    pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
    return ([("count32", pql32)] * 6
            + [("topn_filtered", "TopN(f, n=8, filter=Row(f=0))")] * 2
            + [("bsi_sum", "Sum(field=v)")] * 2
            + [("bsi_range", "Count(Row(v > 50))")] * 2
            + [("groupby", PQL_GB)]
            + [("sparse_topn", PQL_SPARSE)])


def run_mixed(api, deck, oracles, n_threads, iters=1):
    """n_threads clients, each a shuffled deck x iters; every result is
    oracle-checked.  Returns (wall_s, [(family, lat_s)], errors)."""
    barrier = threading.Barrier(n_threads + 1)
    samples: list[list] = [[] for _ in range(n_threads)]
    errors: list = []

    def worker(tid):
        rng = np.random.default_rng(tid)
        try:
            barrier.wait()
            for _ in range(iters):
                order = rng.permutation(len(deck))
                for qi in order:
                    fam, pql = deck[qi]
                    t0 = time.perf_counter()
                    got = api.query(INDEX, pql)["results"]
                    samples[tid].append(
                        (fam, time.perf_counter() - t0))
                    want = oracles[fam]
                    if got != want:
                        raise AssertionError(
                            f"{fam} diverged under contention: "
                            f"{str(got)[:80]} != {str(want)[:80]}")
        except Exception as e:  # noqa: BLE001
            if not errors:
                import traceback
                log(f"FIRST ERROR in {fam}:\n"
                    + traceback.format_exc()[-1800:])
                log(f"free HBM at failure: ~{probe_free_hbm(4.0):.1f} GB")
            errors.append((tid, e))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = [s for ts in samples for s in ts]
    return wall, flat, errors


def pctiles(samples):
    by_fam: dict[str, list] = {}
    for fam, lat in samples:
        by_fam.setdefault(fam, []).append(lat)
    out = {}
    for fam, lats in sorted(by_fam.items()):
        a = np.sort(lats)
        out[fam] = {"n": len(a),
                    "p50_ms": round(float(a[len(a) // 2]) * 1e3, 1),
                    "p99_ms": round(float(a[min(len(a) - 1,
                                                int(len(a) * 0.99))])
                                    * 1e3, 1)}
    return out


def scenario_b():
    """Admission contention at small scale: budget < dense+BSI planes,
    so concurrent count/sum alternation contends on the gate."""
    from pilosa_tpu.api import API
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    import bench.config10_product_families as c10

    n_shards = min(N_SHARDS, 64)
    rng = np.random.default_rng(7)
    plane = rng.integers(0, 1 << 32, size=(n_shards, N_ROWS, c10.WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    saved = c10.N_SHARDS, c10.SPARSE_BITS, c10.SPARSE_ROWS
    c10.N_SHARDS, c10.SPARSE_BITS, c10.SPARSE_ROWS = \
        n_shards, 200_000, 50_000
    data_dir = tempfile.mkdtemp(prefix="pilosa_mixb_")
    try:
        build_index(data_dir, plane, rng)
        plane_bytes = plane.nbytes
        holder = Holder(data_dir).open()
        # budget: one dense plane + 30% — f and v can never both stay
        api = API(holder, Executor(holder,
                                   plane_budget=int(plane_bytes * 1.3)))
        want_counts = [int(c) for c in oracle_counts(plane)]
        want_sum, want_cnt, _ = oracle_bsi()
        pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
        deck = [("count32", pql32), ("bsi_sum", "Sum(field=v)")] * 4
        oracles = {"count32": want_counts,
                   "bsi_sum": [{"value": want_sum, "count": want_cnt}]}
        # warm both (each admission evicts the other — by design)
        assert api.query(INDEX, pql32)["results"] == want_counts
        assert api.query(INDEX, "Sum(field=v)")["results"] == \
            [oracles["bsi_sum"][0]]
        wall, samples, errors = run_mixed(api, deck, oracles,
                                          n_threads=8, iters=2)
        assert not errors, f"scenario B errors: {errors[:2]}"
        qps = len(samples) / wall
        log(f"scenario B (budget contention, {n_shards} shards, "
            f"8 threads): {len(samples)} queries in {wall:.1f}s = "
            f"{qps:.0f} qps, zero errors; {pctiles(samples)}")
        holder.close()
        return {"qps": round(qps, 1), "queries": len(samples),
                "wall_s": round(wall, 1)}
    finally:
        c10.N_SHARDS, c10.SPARSE_BITS, c10.SPARSE_ROWS = saved
        import shutil
        shutil.rmtree(data_dir, ignore_errors=True)


def main():
    import jax

    from pilosa_tpu.api import API
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(42)

    def gen_plane():
        p = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, 32768),
                         dtype=np.uint32)
        p &= rng.integers(0, 1 << 32, size=p.shape, dtype=np.uint32)
        return p

    plane = None  # ~8 GB of rng work: generated only on cache misses
    data_dir = os.environ.get("PILOSA_BENCH_DATADIR")
    if data_dir and os.path.isdir(os.path.join(data_dir, INDEX)):
        log(f"reusing prebuilt index at {data_dir}")
        import pickle
        with open(os.path.join(data_dir, "sparse.pkl"), "rb") as fh:
            sparse = pickle.load(fh)
    else:
        data_dir = data_dir or tempfile.mkdtemp(prefix="pilosa_mix_")
        plane = gen_plane()
        sparse = build_index(data_dir, plane, rng)
        import pickle
        with open(os.path.join(data_dir, "sparse.pkl"), "wb") as fh:
            pickle.dump(sparse, fh)

    holder = Holder(data_dir).open()
    # scenario A budget: dense f (~3.7G) + BSI v (~1.1G) + sparse CSR +
    # filter/rows planes all resident (~8.5 GB of a ~15.4 GB chip).
    # Execution slots bound concurrent scratch: residency + slots ×
    # per-query scratch must fit HBM (32 unbounded streams OOM'd every
    # thread; 16 still did — ~0.5 GB scratch each).  The chip runs one
    # program at a time, so few slots cost no device throughput.
    slots = int(os.environ.get("PILOSA_BENCH_SLOTS", "6"))
    api = API(holder, Executor(holder, plane_budget=8 << 30,
                               max_concurrent=slots))
    results = {}

    # -- oracles (once) + warm every family's residency -----------------
    import pickle
    ocache = os.path.join(data_dir, "oracles.pkl")
    if os.path.exists(ocache):
        log("reusing cached oracles")
        with open(ocache, "rb") as fh:
            (want_counts, want_ftop, want_sum, want_cnt, want_gt50,
             want_gb, want_stop) = pickle.load(fh)
    else:
        log("computing oracles (~25 min at this host's memcpy)...")
        if plane is None:
            plane = gen_plane()
        want_counts = [int(c) for c in oracle_counts(plane)]
        want_ftop = [{"id": r, "count": c}
                     for r, c in oracle_filtered_topn(plane, 0, 8)]
        want_sum, want_cnt, want_gt50 = oracle_bsi()
        want_gb = oracle_groupby(plane, range(4), range(4, 8))
        want_stop = [{"id": r, "count": c}
                     for r, c in oracle_sparse_topn(plane, sparse, 0, 5)]
        with open(ocache, "wb") as fh:
            pickle.dump((want_counts, want_ftop, want_sum, want_cnt,
                         want_gt50, want_gb, want_stop), fh)
    pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))

    from bench.config16_families2 import warm_query

    await_hbm(12.0)
    t0 = time.perf_counter()
    assert warm_query(api, pql32) == want_counts
    log(f"warm count32 (dense plane build): {time.perf_counter() - t0:.1f}s")
    assert warm_query(api, "TopN(f, n=8, filter=Row(f=0))") == [want_ftop]
    assert warm_query(api, "Sum(field=v)") == \
        [{"value": want_sum, "count": want_cnt}]
    assert warm_query(api, "Count(Row(v > 50))") == [want_gt50]
    got_gb = warm_query(api, PQL_GB)[0]
    want_gb_json = got_gb  # shape-checked below against the oracle map
    got_map = {(g["group"][0]["rowID"], g["group"][1]["rowID"]): g["count"]
               for g in got_gb}
    assert got_map == {k: v for k, v in want_gb.items() if v}, "GroupBy"
    t0 = time.perf_counter()
    assert warm_query(api, PQL_SPARSE) == [want_stop]
    log(f"warm sparse (CSR build): {time.perf_counter() - t0:.1f}s")
    log(f"residency after warm: {api.executor.planes.stats()}")

    oracles = {"count32": want_counts, "topn_filtered": [want_ftop],
               "bsi_sum": [{"value": want_sum, "count": want_cnt}],
               "bsi_range": [want_gt50], "groupby": [want_gb_json],
               "sparse_topn": [want_stop]}
    deck = build_deck()

    # -- single-stream reference: serial deck time ----------------------
    t1 = {}
    for fam, pql in dict((f, p) for f, p in deck).items():
        t1[fam] = median_lat(lambda p=pql: api.query(INDEX, p), n=3)
    deck_serial_s = sum(t1[f] for f, _ in deck)
    log("single-stream medians (ms): "
        + ", ".join(f"{f} {v * 1e3:.0f}" for f, v in t1.items())
        + f"; serial deck = {deck_serial_s:.2f}s")

    # -- the measurement: N_THREADS concurrent mixed decks --------------
    # the burst races the chip's co-tenant (free HBM swings ~7 GB on
    # minute timescales): gate on headroom, and on an all-OOM burst
    # re-gate, re-warm evicted planes, and retry
    for attempt in range(3):
        # headroom gate, not total: on attempt 0 this process already
        # holds ~8.5 GB of planes and the burst needs ~3.5 GB of
        # scratch; after an all-OOM burst the recovery EVICTED those
        # planes, so a retry must re-warm ~8.5 GB + scratch
        await_hbm(3.5 if attempt == 0 else 12.0)
        if attempt:
            for fam, pql in dict(deck).items():
                warm_query(api, pql)
        wall, samples, errors = run_mixed(api, deck, oracles, N_THREADS)
        if not errors:
            break
        all_oom = all("RESOURCE_EXHAUSTED" in repr(e)
                      for _, e in errors)
        for tid, e in errors[:3]:
            log(f"thread {tid} FAILED: {e!r}")
        if not all_oom or attempt == 2:
            raise SystemExit(
                f"{len(errors)} of {N_THREADS} threads errored")
        log(f"burst hit a low-HBM window (attempt {attempt + 1}/3); "
            "re-gating and retrying")
    qps = len(samples) / wall
    fam_stats = pctiles(samples)
    results["mixed"] = {"threads": N_THREADS, "queries": len(samples),
                        "wall_s": round(wall, 1), "qps": round(qps, 1),
                        "families": fam_stats}
    log(f"scenario A: {len(samples)} queries / {wall:.1f}s = {qps:.0f} "
        f"qps across {N_THREADS} threads, zero errors")
    for fam, st in fam_stats.items():
        log(f"  {fam}: p50 {st['p50_ms']} ms, p99 {st['p99_ms']} ms "
            f"(n={st['n']}, single-stream {t1[fam] * 1e3:.0f} ms)")
    overlap = qps * deck_serial_s / len(deck)
    log(f"overlap speedup vs one serial stream: {overlap:.1f}x")

    results["scenario_b"] = scenario_b()
    holder.close()
    import shutil
    shutil.rmtree(data_dir, ignore_errors=True)

    print(json.dumps({
        "metric": f"mixed_serving_qps_{platform}",
        "value": round(qps, 1), "unit": "qps",
        "vs_baseline": round(overlap, 2), "detail": results}))


if __name__ == "__main__":
    main()
