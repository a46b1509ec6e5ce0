"""Config #12: a NUMBER for the L3 cluster fan-out layer (VERDICT r3
#8 — upstream's value proposition is mapReduce scaling, SURVEY.md §4.2,
and the rebuild had no datum behind "the HTTP fan-out is cheap").

In-process clusters of 1 / 2 / 4 nodes at 16M columns (16 shards),
CPU-only (the bypass env — this config quantifies HOST-side fan-out
cost: HTTP loopback, JSON, partial-result merge; device compute is
identical across cluster sizes, so the DELTA vs 1 node is the L3
overhead).  Caveat printed with every number: this host has ONE core,
so n-node wall-clock here is an upper bound on fan-out cost — real
deployments put nodes on separate machines.

Measured per cluster size, all through the coordinator's REST surface
and oracle-verified:
  - Count(Row) latency + qps (8 concurrent clients)
  - TopN(n=8) latency
  - GroupBy 2-level latency
  - per-node /internal/query round-trip cost (the raw fan-out RPC)
  - merge_results cost in isolation (captured partials, host-only)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

N_SHARDS = 16
N_ROWS = 32
INDEX = "bench"


def median_lat(fn, n=9):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def concurrent_qps(fn, n_threads=8, iters=4, per_call=1):
    import threading
    barrier = threading.Barrier(n_threads + 1)
    errs = []

    def worker():
        barrier.wait()
        for _ in range(iters):
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in ts:
        t.join()
    dt = time.perf_counter() - t0
    assert not errs, errs[:3]
    return n_threads * iters * per_call / dt


def _workload():
    from pilosa_tpu.engine.words import SHARD_WIDTH

    rng = np.random.default_rng(12)
    # data: 32 rows x 16M cols, ~3% density so JSON row payloads stay
    # realistic (Count responses are scalars either way)
    n_bits = 2_000_000
    rows = rng.integers(0, N_ROWS, size=n_bits).astype(np.uint64)
    cols = rng.integers(0, N_SHARDS * SHARD_WIDTH,
                        size=n_bits).astype(np.uint64)
    key = np.unique((rows << np.uint64(40)) | cols)
    rows = (key >> np.uint64(40)).astype(np.uint64)
    cols = key & np.uint64((1 << 40) - 1)
    return rows, cols


def measure_one(n_nodes: int, proc: bool = False) -> dict:
    """One cluster size, in a FRESH process (threads/caches left by a
    previous in-process cluster measured a ~1 ms loopback RPC as
    ~100 ms on this one-core host).  ``proc=True`` boots each node as
    a separate OS process (VERDICT r4 #6: in-process nodes share one
    GIL, so node-side work could not genuinely overlap; OS processes
    overlap everything but this host's single core)."""
    import tempfile

    from pilosa_tpu.testing import run_cluster, run_process_cluster

    rows, cols = _workload()
    oracle_counts = np.bincount(rows.astype(np.int64), minlength=N_ROWS)
    order = np.lexsort((np.arange(N_ROWS), -oracle_counts))
    want_topn = [{"id": int(r), "count": int(oracle_counts[r])}
                 for r in order[:8]]
    pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
    want_counts = [int(c) for c in oracle_counts]

    harness = run_process_cluster if proc else run_cluster
    with tempfile.TemporaryDirectory() as td, \
            harness(n_nodes, td, replicas=1,
                    anti_entropy=0.0) as tc:
        c = tc.client(0)
        c.create_index(INDEX)
        c.create_field(INDEX, "f")
        t0 = time.perf_counter()
        for a in range(0, len(rows), 100_000):
            c.import_bits(INDEX, "f",
                          rowIDs=rows[a:a + 100_000].tolist(),
                          columnIDs=cols[a:a + 100_000].tolist())
        t_load = time.perf_counter() - t0

        assert c.query(INDEX, pql32) == want_counts
        # settle: the import queues background fragment compaction on
        # this one-core host
        time.sleep(2.0)
        rpc = rpc_null = None
        if n_nodes > 1 and not proc:
            cl = tc.servers[0].cluster
            peer = next(nid for nid in cl.alive_ids()
                        if nid != cl.node_id)
            rpc = median_lat(lambda: cl.internal_query(
                peer, INDEX, "Count(Row(f=0))", [0]))
            rpc_null = median_lat(lambda: cl.internal_query(
                peer, INDEX, "Count(Row(f=999999999))", [0]))
        elif n_nodes > 1:
            # raw /internal/query RPC against a real peer PROCESS,
            # keep-alive connection (the fan-out's unit cost)
            peer_client = tc.client(1)
            rpc = median_lat(lambda: peer_client._do(
                "POST", f"/internal/query?index={INDEX}&shards=0",
                b"Count(Row(f=0))"))
            rpc_null = median_lat(lambda: peer_client._do(
                "POST", f"/internal/query?index={INDEX}&shards=0",
                b"Count(Row(f=999999999))"))
        lat_count = median_lat(lambda: c.query(INDEX, pql32))
        qps = concurrent_qps(lambda: c.query(INDEX, pql32),
                             per_call=N_ROWS)
        got = c.query(INDEX, "TopN(f, n=8)")[0]
        assert got == want_topn, f"TopN mismatch at {n_nodes} nodes"
        lat_topn = median_lat(
            lambda: c.query(INDEX, "TopN(f, n=8)"))
        pql_gb = ("GroupBy(Rows(f, limit=4), "
                  "Rows(f, previous=3, limit=4))")
        lat_gb = median_lat(lambda: c.query(INDEX, pql_gb))

        out = {
            "load_s": round(t_load, 1),
            "count32_ms": round(lat_count * 1e3, 1),
            "count_qps_8cli": round(qps, 1),
            "topn_ms": round(lat_topn * 1e3, 1),
            "groupby_ms": round(lat_gb * 1e3, 1),
            "internal_rpc_ms": (round(rpc * 1e3, 2)
                                if rpc is not None else None),
            "internal_rpc_null_ms": (round(rpc_null * 1e3, 2)
                                     if rpc_null is not None
                                     else None),
        }
        log(f"{n_nodes} node(s): count32 {lat_count * 1e3:.1f} ms, "
            f"{qps:,.0f} qps@8cli, TopN {lat_topn * 1e3:.1f} ms, "
            f"GroupBy {lat_gb * 1e3:.1f} ms"
            + (f", internal RPC {rpc * 1e3:.2f} ms "
               f"(null-op {rpc_null * 1e3:.2f} ms)" if rpc else ""))
        return out


def main():
    import subprocess

    if len(sys.argv) > 1 and sys.argv[1] in ("--one", "--one-proc"):
        print(json.dumps(measure_one(int(sys.argv[2]),
                                     proc=sys.argv[1] == "--one-proc")))
        return

    rng = np.random.default_rng(12)
    results = {}
    proc_results = {}
    for flag, sink in (("--one", results), ("--one-proc", proc_results)):
        for n_nodes in (1, 2, 4):
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), flag,
                 str(n_nodes)],
                capture_output=True, timeout=900)
            sys.stderr.buffer.write(proc.stderr)
            if proc.returncode != 0:
                raise RuntimeError(f"{n_nodes}-node {flag} child rc="
                                   f"{proc.returncode}")
            sink[n_nodes] = json.loads(
                proc.stdout.decode().strip().splitlines()[-1])
        log(("in-process" if flag == "--one" else "OS-process")
            + " mode done: "
            + ", ".join(f"{n}n count32 {d['count32_ms']}ms"
                        for n, d in sink.items()))

    # merge cost in isolation: synthesize per-node TopN/GroupBy partials
    # and time merge_results (pure host work, no sockets)
    from pilosa_tpu.cluster.dist import merge_results
    from pilosa_tpu.pql.parser import parse

    topn_call = parse("TopN(f, n=8)").calls[0]
    partials = [[{"id": int(r), "count": int(cn)}
                 for r, cn in enumerate(rng.integers(1, 10 ** 6, 5000))]
                for _ in range(4)]
    t_merge_topn = median_lat(lambda: merge_results(topn_call, partials))
    gb_call = parse("GroupBy(Rows(a), Rows(b))").calls[0]
    gb_partials = []
    for _ in range(4):
        ids = rng.integers(0, 200, size=(20000, 2))
        gb_partials.append([
            {"group": [{"field": "a", "rowID": int(a)},
                       {"field": "b", "rowID": int(b)}],
             "count": int(cn)}
            for (a, b), cn in zip(ids, rng.integers(1, 1000, 20000))])
    t_merge_gb = median_lat(
        lambda: merge_results(gb_call, gb_partials), n=5)
    log(f"merge cost (host-only, 4 partials): TopN 5k pairs/node "
        f"{t_merge_topn * 1e3:.1f} ms; GroupBy 20k groups/node "
        f"{t_merge_gb * 1e3:.1f} ms")

    d1, d4 = proc_results[1], proc_results[4]
    overhead_ms = d4["count32_ms"] - d1["count32_ms"]
    log(f"fan-out overhead, OS-process nodes (4 vs 1, one-core host, "
        f"same device work): +{overhead_ms:.1f} ms per 32-Count request")
    print(json.dumps({
        "metric": "cluster_fanout_overhead_ms_4n_vs_1n_cpu",
        "value": round(overhead_ms, 2), "unit": "ms",
        "vs_baseline": 1.0,
        "detail": {str(k): v for k, v in results.items()}
        | {f"proc_{k}": v for k, v in proc_results.items()}
        | {"merge_topn_ms": round(t_merge_topn * 1e3, 2),
           "merge_groupby_20k_ms": round(t_merge_gb * 1e3, 2)}}))


if __name__ == "__main__":
    main()
