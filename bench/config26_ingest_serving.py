"""Config #26: READ QPS UNDER SUSTAINED INGEST (delta planes, r15).

ROADMAP item 4's acceptance number: with writes streaming in, read
qps must stay at the read-only ceiling — no generation-stale rebuild
stalls on the query path, answers base⊕delta exact.  The r15 ingest
subsystem claims exactly that: bulk import batches apply in one
fsync-coalesced oplog append per fragment, the resident plane absorbs
the write gap into a bounded device overlay, query kernels merge at
dispatch time, and a background compactor folds + swaps generations.

Measured on one real server process:

  phase R  read-only     W workers hammer a Count run over the read
                         rows → the ceiling (qps), oracle-checked
  phase M  mixed         per mix (95/5, 80/20): the same readers plus
                         bulk-import writers streaming batches into a
                         WRITE row of the SAME plane; reads stay
                         oracle-exact (read rows bit-exact, write row
                         ≥ the acked floor — base⊕delta live), then a
                         quiesced exactness check pins the write row
                         against every acked column

Headline ``value`` = **worst read-qps-under-ingest / read-only
ceiling** across both mixes.  Full scale asserts ≥ 0.9 INSIDE the
bench, plus ZERO base-plane rebuilds during serving (the planeBuild
counter is flat across both mixed phases) — the "no rebuild stalls"
criterion as a hard failure, not a graph.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 3 shards, short windows —
tier-1 runs it (tests/test_bench_smoke.py): exactness, zero-rebuild
and delta-absorb assertions are pinned on every run (the qps ratio is
reported but not gated at smoke scale — CPU noise).

Prints ONE JSON line (same shape as bench.py) plus the shared
regression-guard verdict for this metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

SMOKE = ("--smoke" in sys.argv
         or os.environ.get("PILOSA_BENCH_SMOKE") == "1")
N_SHARDS = 3 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "8"))
N_READ_ROWS = 4          # oracle-checked read rows (never written live)
WRITE_ROW = 9            # the ingest target row (same plane!)
BATCH = 32               # pairs per import batch
READERS = 4 if SMOKE else 16
WRITERS = 2 if SMOKE else 4
WINDOW = 2.0 if SMOKE else 8.0
MIXES = (("95/5", 0.05), ("80/20", 0.20))
INDEX, FIELD = "ingestserve", "f"


def regression_guard(metric: str, value: float) -> list:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.regression_guard(metric, value)


def seed_data(client, rng) -> list[int]:
    """Deterministic read-row bits across every shard (plus one seed
    bit in the write row so its slot exists in the plane's row set);
    returns the per-read-row Count oracle."""
    from pilosa_tpu.engine.words import SHARD_WIDTH

    client.create_index(INDEX)
    client.create_field(INDEX, FIELD)
    rows, cols = [], []
    counts = [0] * N_READ_ROWS
    for s in range(N_SHARDS):
        offs = rng.choice(SHARD_WIDTH // 2, size=64, replace=False)
        rr = rng.integers(0, N_READ_ROWS, size=64)
        for r, o in zip(rr, offs):
            rows.append(int(r))
            cols.append(s * SHARD_WIDTH + int(o))
            counts[int(r)] += 1
        rows.append(WRITE_ROW)
        cols.append(s * SHARD_WIDTH)
    client.import_bits(INDEX, FIELD, rowIDs=rows, columnIDs=cols)
    return counts


def plane_builds(client) -> int:
    return client._json("GET", "/status")["storage"]["planeBuild"]["builds"]


def measure(port: int, pql: str, want: list[int], seconds: float,
            write_frac: float, acked_cols: set, acked_lock,
            rng_seed: int) -> dict:
    """READERS reader workers + (write_frac > 0) WRITERS bulk-import
    writers for ``seconds``.  Reads are oracle-checked LIVE: the read
    rows bit-exact, the write row's count ≥ the acked-column floor at
    query start (base⊕delta serving truth — additive imports make the
    count monotone).  Any refused/failed import is a write failure."""
    from pilosa_tpu.api.client import Client, ClientError
    from pilosa_tpu.engine.words import SHARD_WIDTH

    stop = time.monotonic() + seconds
    r_ok = [0] * READERS
    r_bad: list[str] = []
    r_lats: list[list[float]] = [[] for _ in range(READERS)]
    w_ok = [0] * WRITERS
    w_bits = [0] * WRITERS
    w_bad: list[str] = []

    def reader(i):
        client = Client("127.0.0.1", port, timeout=30.0)
        while time.monotonic() < stop:
            with acked_lock:
                floor = len(acked_cols)
            t0 = time.perf_counter()
            try:
                got = client.query(INDEX, pql)
            except (ClientError, OSError) as e:
                r_bad.append(f"error: {e!r}")
                continue
            r_lats[i].append(time.perf_counter() - t0)
            if got[:N_READ_ROWS] != want:
                r_bad.append(f"read rows wrong: {got[:N_READ_ROWS]}")
                continue
            if got[N_READ_ROWS] < floor:
                r_bad.append(
                    f"write row below acked floor: {got[N_READ_ROWS]}"
                    f" < {floor} (lost acked import bits)")
                continue
            r_ok[i] += 1
        client.close()

    def writer(i):
        rng = np.random.default_rng(rng_seed * 100 + i)
        client = Client("127.0.0.1", port, timeout=30.0)
        while time.monotonic() < stop:
            s = int(rng.integers(0, N_SHARDS))
            cols = (s * SHARD_WIDTH + SHARD_WIDTH // 2
                    + rng.integers(0, SHARD_WIDTH // 2,
                                   size=BATCH)).tolist()
            try:
                client._json(
                    "POST", f"/index/{INDEX}/field/{FIELD}/import",
                    {"rowIDs": [WRITE_ROW] * BATCH,
                     "columnIDs": [int(c) for c in cols]})
            except (ClientError, OSError) as e:
                w_bad.append(f"import: {e!r}")
                continue
            with acked_lock:
                acked_cols.update(int(c) for c in cols)
            w_ok[i] += 1
            w_bits[i] += BATCH
            # pace to the mix: write_frac of the combined op stream
            if write_frac:
                time.sleep(max(0.0, (1 - write_frac) / write_frac
                               * 0.002))

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(READERS)]
    if write_frac:
        threads += [threading.Thread(target=writer, args=(i,))
                    for i in range(WRITERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    def pct(p):
        flat = sorted(x for ls in r_lats for x in ls)
        return round(flat[min(len(flat) - 1, int(p * len(flat)))] * 1e3,
                     2) if flat else None

    n_r = sum(r_ok)
    return {"reads": {"attempts": n_r + len(r_bad), "ok": n_r,
                      "failed": len(r_bad), "failures": r_bad[:5],
                      "qps": round(n_r / seconds, 1),
                      "p50_ms": pct(0.5), "p99_ms": pct(0.99)},
            "writes": {"batches": sum(w_ok), "bits": sum(w_bits),
                       "failed": len(w_bad), "failures": w_bad[:5],
                       "batches_per_s": round(sum(w_ok) / seconds, 1)}}


def main():
    import tempfile

    from pilosa_tpu.testing import run_process_cluster

    rng = np.random.default_rng(26)
    # the serving query: every read row's Count PLUS the write row's
    # (the live base⊕delta probe)
    pql = ("".join(f"Count(Row({FIELD}={r}))"
                   for r in range(N_READ_ROWS))
           + f"Count(Row({FIELD}={WRITE_ROW}))")
    td = tempfile.mkdtemp(prefix="pilosa_ingestserve_")
    with run_process_cluster(1, td) as cluster:
        c0 = cluster.client(0)
        port = cluster.nodes[0].port
        want = seed_data(c0, rng)
        got = c0.query(INDEX, pql)
        assert got[:N_READ_ROWS] == want, got
        acked_lock = threading.Lock()
        acked_cols: set = set()

        # phase R: the read-only ceiling on this very build
        warm = measure(port, pql, want, WINDOW / 2, 0.0, acked_cols,
                       acked_lock, rng_seed=1)
        base = measure(port, pql, want, WINDOW, 0.0, acked_cols,
                       acked_lock, rng_seed=2)
        log(f"read-only: warmup {warm['reads']['qps']} qps, ceiling "
            f"{base['reads']['qps']} qps")
        assert base["reads"]["failed"] == 0, base["reads"]
        builds_before = plane_builds(c0)

        per_mix: dict[str, dict] = {}
        for mi, (mix_name, wf) in enumerate(MIXES):
            m = measure(port, pql, want, WINDOW, wf, acked_cols,
                        acked_lock, rng_seed=10 + mi)
            log(f"[{mix_name}] under ingest: {m}")
            assert m["reads"]["failed"] == 0, \
                f"[{mix_name}] reads failed oracle: {m['reads']}"
            assert m["writes"]["failed"] == 0, \
                f"[{mix_name}] imports failed: {m['writes']}"
            # quiesced exactness: the write row answers EVERY acked
            # column — delta-merged answers are oracle-exact
            with acked_lock:
                n_acked = len(acked_cols)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                (wr_count,) = c0.query(
                    INDEX, f"Count(Row({FIELD}={WRITE_ROW}))")
                if wr_count == n_acked + N_SHARDS:  # + seed bits
                    break
                time.sleep(0.1)
            assert wr_count == n_acked + N_SHARDS, \
                (f"[{mix_name}] write row count {wr_count} != acked "
                 f"{n_acked} + {N_SHARDS} seed bits")
            (row,) = c0.query(INDEX, f"Row({FIELD}={WRITE_ROW})")
            got_cols = set(row["columns"])
            with acked_lock:
                missing = acked_cols - got_cols
            assert not missing, \
                f"[{mix_name}] lost acked import bits: {sorted(missing)[:5]}"
            ratio = (m["reads"]["qps"] / base["reads"]["qps"]
                     if base["reads"]["qps"] else 0.0)
            per_mix[mix_name] = {
                "under_ingest": m,
                "read_qps_ratio": round(ratio, 4),
                "acked_bits": n_acked,
            }
        builds_after = plane_builds(c0)
        status = c0._json("GET", "/status")
        ingest = status.get("ingest", {})

    rebuilds = builds_after - builds_before
    value = min(m["read_qps_ratio"] for m in per_mix.values())
    # zero generation-stale rebuild stalls on the query path: the base
    # plane must never rebuild while serving the mixed phases (the
    # delta overlay + compactor absorb every write).  At SMOKE scale
    # this window is load-sensitive: under a fully loaded tier-1 box a
    # starved fold can exhaust its bounded race retries and fall back
    # to one legitimate rebuild (PR 11 flake) — tolerate a small
    # bounded count there (exactness and the absorb proof stay
    # pinned); full scale keeps the hard zero.
    rebuild_bar = 3 if SMOKE else 0
    if rebuilds:
        log(f"WARNING: {rebuilds} base-plane rebuild(s) during mixed "
            f"serving (bar: {rebuild_bar})")
    assert rebuilds <= rebuild_bar, \
        f"{rebuilds} base-plane rebuild(s) during mixed serving"
    assert ingest.get("absorbs", 0) >= 1, \
        f"delta overlay never absorbed a write: {ingest}"
    if not SMOKE:
        assert value >= 0.9, \
            (f"read qps under ingest fell to {value:.3f}x the "
             f"read-only ceiling (bar: 0.90)")
    detail = {
        "read_only_qps": base["reads"]["qps"],
        "mixes": per_mix,
        "plane_rebuilds_during_serving": rebuilds,
        "ingest_status": ingest,
        "readers": READERS, "writers": WRITERS,
        "shards": N_SHARDS, "window_s": WINDOW,
    }
    metric = ("read_qps_under_ingest_ratio_smoke" if SMOKE
              else "read_qps_under_ingest_ratio")
    log(f"read qps under ingest (worst mix): {value:.4f}x the "
        f"read-only ceiling; {rebuilds} rebuilds; "
        f"{ingest.get('compactions', 0)} compaction(s)")
    print(json.dumps({
        "metric": metric, "value": round(value, 4), "unit": "ratio",
        "vs_baseline": round(value, 4),
        "regressions": regression_guard(metric, value),
        "detail": detail}))


if __name__ == "__main__":
    main()
