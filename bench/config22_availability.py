"""Config #22: READ AVAILABILITY through a node kill and rejoin.

The r11 availability layer claims the distributed read path survives
node death without failing queries: transport-failed fan-out legs
retry on the shards' next live replicas, per-peer circuit breakers
take the dead peer out of routing after a few failures, and the
replica-bound shard-universe rule keeps strict reads serving while the
corpse is still inside the suspect horizon.  This bench measures that
claim as a serving number, on a real 3-process cluster (replicas=2):

  phase A  baseline     W workers hammer one survivor with an
                        oracle-checked multi-Count query
  phase B  failure      kill -9 a replica-holding node MID-PHASE and
                        keep serving through the corpse
  phase C  rejoin       restart the node, wait for membership+resize,
                        measure again

Headline ``value`` = **read availability during failure** — the
fraction of phase-B reads that answered AND answered oracle-exact.
The acceptance bar is 1.0: zero failed or wrong reads through the
kill.  ``vs_baseline`` = phase-B qps / phase-A qps (the serving cost
of dying).  p50/p99 latency per phase, failover/breaker counters and
recovery seconds ride in ``detail``.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 3 shards, short windows —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot, and so the zero-failed-reads bar is pinned on every run.

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
N_SHARDS = 3 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "6"))
N_ROWS = 4 if SMOKE else 8
WORKERS = 4 if SMOKE else 8
# (baseline, failure, rejoin) measurement windows, seconds
WINDOWS = (2.0, 4.0, 2.0) if SMOKE else (5.0, 8.0, 5.0)
KILL_AT = 0.5  # seconds into the failure window (mid-serve, not between)
INDEX, FIELD = "avail", "f"


def regression_guard(metric: str, value: float) -> list:
    """bench.py's same-metric history guard (the module file is
    shadowed by the bench/ package on import; load it explicitly)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.regression_guard(metric, value)


def seed_data(client, rng) -> list[int]:
    """Deterministic bits across every shard; returns the per-row
    Count oracle."""
    from pilosa_tpu.engine.words import SHARD_WIDTH

    client.create_index(INDEX)
    client.create_field(INDEX, FIELD)
    rows, cols = [], []
    counts = [0] * N_ROWS
    for s in range(N_SHARDS):
        offs = rng.choice(SHARD_WIDTH, size=64, replace=False)
        rr = rng.integers(0, N_ROWS, size=64)
        for r, o in zip(rr, offs):
            rows.append(int(r))
            cols.append(s * SHARD_WIDTH + int(o))
            counts[int(r)] += 1
    client.import_bits(INDEX, FIELD, rowIDs=rows, columnIDs=cols)
    return counts


def measure(port: int, pql: bytes, want: list[int], seconds: float,
            kill_fn=None) -> dict:
    """W workers against one node for ``seconds``; every response is
    oracle-checked (a wrong answer counts as a failure).  ``kill_fn``
    runs KILL_AT seconds in, on a side thread — mid-serve, the way
    nodes actually die."""
    from pilosa_tpu.api.client import Client, ClientError

    stop = time.monotonic() + seconds
    ok = [0] * WORKERS
    bad: list[str] = []
    lats: list[list[float]] = [[] for _ in range(WORKERS)]

    def worker(i):
        client = Client("127.0.0.1", port, timeout=30.0)
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            try:
                got = client.query(INDEX, pql.decode())
            except (ClientError, OSError) as e:
                bad.append(f"error: {e!r}")
                continue
            lats[i].append(time.perf_counter() - t0)
            if got != want:
                bad.append(f"wrong answer: {got}")
                continue
            ok[i] += 1
        client.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(WORKERS)]
    killer = None
    if kill_fn is not None:
        killer = threading.Timer(KILL_AT, kill_fn)
        killer.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if killer is not None:
        killer.join()
    flat = sorted(x for ls in lats for x in ls)
    n_ok = sum(ok)
    attempts = n_ok + len(bad)

    def pct(p):
        return round(flat[min(len(flat) - 1, int(p * len(flat)))] * 1e3,
                     2) if flat else None

    return {"attempts": attempts, "ok": n_ok, "failed": len(bad),
            "failures": bad[:5],
            "qps": round(n_ok / seconds, 1),
            "p50_ms": pct(0.50), "p99_ms": pct(0.99)}


def main():
    import tempfile

    from pilosa_tpu.testing import run_process_cluster

    rng = np.random.default_rng(22)
    pql = "".join(f"Count(Row({FIELD}={r}))"
                  for r in range(N_ROWS)).encode()
    td = tempfile.mkdtemp(prefix="pilosa_avail_")
    with run_process_cluster(3, td, replicas=2,
                             anti_entropy=0.0) as cluster:
        c0 = cluster.client(0)
        want = seed_data(c0, rng)
        assert c0.query(INDEX, pql.decode()) == want
        # victim: a replica-holding non-coordinator; entry: any other
        status = c0._json("GET", "/status")
        primary = next(nd["id"] for nd in status["nodes"]
                       if nd.get("isPrimary"))
        coord_i = next(i for i, nd in enumerate(cluster.nodes)
                       if f"127.0.0.1:{nd.port}" == primary)
        victim_i = next(i for i in range(3) if i != coord_i)
        entry_i = next(i for i in range(3) if i != victim_i)
        entry_port = cluster.nodes[entry_i].port
        log(f"cluster up: coordinator node{coord_i}, victim "
            f"node{victim_i}, entry node{entry_i}; oracle {want}")

        a = measure(entry_port, pql, want, WINDOWS[0])
        log(f"baseline: {a}")

        b = measure(entry_port, pql, want, WINDOWS[1],
                    kill_fn=cluster.nodes[victim_i].kill9)
        log(f"failure window (kill -9 at t+{KILL_AT}s): {b}")

        # recovery: restart + membership + resize back to NORMAL
        t0 = time.perf_counter()
        node = cluster.nodes[victim_i]
        node.stop()
        node.start()
        node.await_up()
        cluster.await_membership(3, timeout=120)
        recovery_s = time.perf_counter() - t0
        log(f"node restarted and rejoined in {recovery_s:.1f}s")

        cr = measure(entry_port, pql, want, WINDOWS[2])
        log(f"rejoin window: {cr}")

        entry_metrics = cluster.client(entry_i).metrics_text()

    def counter(name: str) -> float:
        from pilosa_tpu.fault.chaos import prom_counter_total
        return prom_counter_total(entry_metrics, name)

    availability = (b["ok"] / b["attempts"]) if b["attempts"] else 0.0
    detail = {
        "baseline": a, "failure": b, "rejoin": cr,
        "recovery_s": round(recovery_s, 1),
        "failover_total": counter("read_failover_total"),
        "breaker_transitions_total":
            counter("breaker_transitions_total"),
        "workers": WORKERS, "shards": N_SHARDS,
        "windows_s": list(WINDOWS),
    }
    metric = ("read_availability_node_kill_smoke" if SMOKE
              else "read_availability_node_kill")
    vs = round(b["qps"] / a["qps"], 3) if a["qps"] else 0.0
    log(f"availability during failure: {availability:.4f} "
        f"({b['ok']}/{b['attempts']}); failure-qps/baseline-qps {vs}")
    print(json.dumps({
        "metric": metric, "value": round(availability, 4),
        "unit": "ratio", "vs_baseline": vs,
        "regressions": regression_guard(metric, availability),
        "detail": detail}))


if __name__ == "__main__":
    main()
