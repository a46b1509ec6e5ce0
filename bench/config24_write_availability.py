"""Config #24: WRITE AVAILABILITY through a node kill and rejoin
(durable hinted handoff, r13).

The r13 handoff layer claims writes keep serving at availability 1.0
through node death, with exactness preserved: a write that finds a
replica down applies on the live owners and is durably hinted for the
dead one, the hint log drains in order on rejoin, and anti-entropy
defers union-merge for hinted peers so a replayed Clear can never be
resurrected.  This bench measures that claim as a serving number on a
real 3-process cluster (replicas=2), for TWO mixed workloads —
95/5 and 80/20 read/write — each driven through a full
kill -9 → serve → restart → hint-drain cycle:

  phase A  baseline     W workers run the mix against one survivor;
                        reads are oracle-checked, writes are
                        tracked Set/Clear ops in per-worker col lanes
  phase B  failure      kill -9 a replica-holding node MID-PHASE and
                        keep serving through the corpse
  drain                 restart the node, wait for membership, then
                        time the hint backlog draining to zero
  phase C  rejoin       measure again, then verify EXACTNESS: every
                        node answers the write lanes' expected state
                        (no lost op, no resurrected clear)

Headline ``value`` = **write availability during failure** — the worst
fraction, across both mixes, of phase-B writes that ACKED.  The
acceptance bar is 1.0: zero refused or failed writes through the kill.
Read availability, per-phase qps/latency, hint-drain seconds and
replay counters ride in ``detail``.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 3 shards, short windows —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot, and so the availability-1.0 bar is pinned on every run.

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
N_READ_ROWS = 4          # read-only rows: concurrent-safe oracle
WRITE_ROW = 9            # the write lanes' row (never read-checked live)
LANE = 64                # cols per worker per shard (disjoint lanes)
WORKERS = 4 if SMOKE else 8
# (baseline, failure, rejoin) measurement windows, seconds
WINDOWS = (1.5, 3.0, 1.5) if SMOKE else (4.0, 8.0, 4.0)
KILL_AT = 0.5  # seconds into the failure window (mid-serve)
MIXES = (("95/5", 0.05), ("80/20", 0.20))
INDEX, FIELD = "wavail", "f"


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
    """Deterministic read-row bits across every shard; returns the
    per-read-row Count oracle."""
    from pilosa_tpu.engine.words import SHARD_WIDTH

    client.create_index(INDEX)
    client.create_field(INDEX, FIELD)
    rows, cols = [], []
    counts = [0] * N_READ_ROWS
    for s in range(N_SHARDS):
        offs = rng.choice(SHARD_WIDTH, size=48, replace=False)
        rr = rng.integers(0, N_READ_ROWS, size=48)
        for r, o in zip(rr, offs):
            rows.append(int(r))
            cols.append(s * SHARD_WIDTH + int(o))
            counts[int(r)] += 1
    client.import_bits(INDEX, FIELD, rowIDs=rows, columnIDs=cols)
    return counts


class WriteLanes:
    """Each worker owns a disjoint column lane per shard and tracks the
    expected final presence of every col it touched — the exactness
    oracle checked on every node after the hint drain."""

    def __init__(self):
        # worker -> {col: expected-present-after-its-last-op}
        self.expected: dict[int, dict[int, bool]] = {}

    def cols_of(self, worker: int) -> dict[int, bool]:
        return self.expected.setdefault(worker, {})


def measure(port: int, pql: bytes, want: list[int], seconds: float,
            write_frac: float, lanes: WriteLanes, rng_seed: int,
            kill_fn=None) -> dict:
    """W workers run the read/write mix against one node for
    ``seconds``.  Reads are oracle-checked (wrong = failed).  Writes
    alternate Set/Clear inside the worker's lane; an errored or
    refused write is a write failure — the availability headline."""
    from pilosa_tpu.api.client import Client, ClientError
    from pilosa_tpu.engine.words import SHARD_WIDTH

    stop = time.monotonic() + seconds
    r_ok = [0] * WORKERS
    w_ok = [0] * WORKERS
    r_bad: list[str] = []
    w_bad: list[str] = []
    r_lats: list[list[float]] = [[] for _ in range(WORKERS)]
    w_lats: list[list[float]] = [[] for _ in range(WORKERS)]

    def worker(i):
        rng = np.random.default_rng(rng_seed * 1000 + i)
        client = Client("127.0.0.1", port, timeout=30.0)
        mine = lanes.cols_of(i)
        while time.monotonic() < stop:
            if rng.random() < write_frac:
                s = int(rng.integers(0, N_SHARDS))
                col = (s * SHARD_WIDTH + i * LANE
                       + int(rng.integers(0, LANE)))
                set_it = bool(rng.random() < 0.6)
                op = (f"Set({col}, {FIELD}={WRITE_ROW})" if set_it
                      else f"Clear({col}, {FIELD}={WRITE_ROW})")
                t0 = time.perf_counter()
                try:
                    client.query(INDEX, op)
                except (ClientError, OSError) as e:
                    w_bad.append(f"{op}: {e!r}")
                    continue
                w_lats[i].append(time.perf_counter() - t0)
                mine[col] = set_it
                w_ok[i] += 1
            else:
                t0 = time.perf_counter()
                try:
                    got = client.query(INDEX, pql.decode())
                except (ClientError, OSError) as e:
                    r_bad.append(f"error: {e!r}")
                    continue
                r_lats[i].append(time.perf_counter() - t0)
                if got != want:
                    r_bad.append(f"wrong answer: {got}")
                    continue
                r_ok[i] += 1
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

    def pct(lats, p):
        flat = sorted(x for ls in lats for x in ls)
        return round(flat[min(len(flat) - 1, int(p * len(flat)))] * 1e3,
                     2) if flat else None

    n_r, n_w = sum(r_ok), sum(w_ok)
    return {"reads": {"attempts": n_r + len(r_bad), "ok": n_r,
                      "failed": len(r_bad), "failures": r_bad[:5],
                      "qps": round(n_r / seconds, 1),
                      "p50_ms": pct(r_lats, 0.5),
                      "p99_ms": pct(r_lats, 0.99)},
            "writes": {"attempts": n_w + len(w_bad), "ok": n_w,
                       "failed": len(w_bad), "failures": w_bad[:5],
                       "qps": round(n_w / seconds, 1),
                       "p50_ms": pct(w_lats, 0.5),
                       "p99_ms": pct(w_lats, 0.99)}}


def check_exactness(cluster, lanes: WriteLanes) -> int:
    """After the drain: every node answers the write lanes' expected
    final state — no lost acked op, no resurrected clear.  Returns the
    number of (node, col) checks that held; raises on the first that
    does not."""
    checked = 0
    for i in range(3):
        (got,) = cluster.client(i).query(
            INDEX, f"Row({FIELD}={WRITE_ROW})")
        present = set(got["columns"])
        for w, mine in lanes.expected.items():
            for col, want_set in mine.items():
                if want_set and col not in present:
                    raise AssertionError(
                        f"node {i}: LOST acked Set({col}) [worker {w}]")
                if not want_set and col in present:
                    raise AssertionError(
                        f"node {i}: RESURRECTED cleared col {col} "
                        f"[worker {w}]")
                checked += 1
    return checked


def await_drained(client, timeout: float = 60.0) -> float:
    """Seconds until the hint backlog reads zero on ``client``."""
    t0 = time.perf_counter()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not client.write_health().get("hintBacklogOps"):
            return time.perf_counter() - t0
        time.sleep(0.1)
    raise AssertionError("hint backlog never drained")


def main():
    import tempfile

    from pilosa_tpu.api.client import ClientError
    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.fault.chaos import prom_counter_total

    from pilosa_tpu.testing import run_process_cluster

    rng = np.random.default_rng(24)
    pql = "".join(f"Count(Row({FIELD}={r}))"
                  for r in range(N_READ_ROWS)).encode()
    td = tempfile.mkdtemp(prefix="pilosa_wavail_")
    per_mix: dict[str, dict] = {}
    with run_process_cluster(3, td, replicas=2,
                             anti_entropy=0.0) as cluster:
        c0 = cluster.client(0)
        want = seed_data(c0, rng)
        assert c0.query(INDEX, pql.decode()) == want
        status = c0._json("GET", "/status")
        primary = next(nd["id"] for nd in status["nodes"]
                       if nd.get("isPrimary"))
        coord_i = next(i for i, nd in enumerate(cluster.nodes)
                       if f"127.0.0.1:{nd.port}" == primary)
        victim_i = next(i for i in range(3) if i != coord_i)
        entry_i = next(i for i in range(3) if i != victim_i)
        entry_port = cluster.nodes[entry_i].port
        entry = cluster.client(entry_i)
        log(f"cluster up: coordinator node{coord_i}, victim "
            f"node{victim_i}, entry node{entry_i}; read oracle {want}")

        for mi, (mix_name, wf) in enumerate(MIXES):
            lanes = WriteLanes()
            a = measure(entry_port, pql, want, WINDOWS[0], wf, lanes,
                        rng_seed=100 + mi)
            log(f"[{mix_name}] baseline: {a}")
            b = measure(entry_port, pql, want, WINDOWS[1], wf, lanes,
                        rng_seed=200 + mi,
                        kill_fn=cluster.nodes[victim_i].kill9)
            log(f"[{mix_name}] failure window (kill -9 at "
                f"t+{KILL_AT}s): {b}")
            # Under full-suite load the failure window can land few or
            # no writes after the kill; top up on a dedicated lane
            # (worker index WORKERS, disjoint from the measure
            # workers) until at least one op is hinted so the drain
            # path below is actually exercised.
            topup = lanes.cols_of(WORKERS)
            topup_deadline = time.monotonic() + 30.0
            seq = 0
            while (entry.write_health().get("hintBacklogOps", 0) < 1
                   and time.monotonic() < topup_deadline):
                s = seq % N_SHARDS
                col = (s * SHARD_WIDTH + WORKERS * LANE
                       + (seq // N_SHARDS) % LANE)
                seq += 1
                try:
                    entry.query(INDEX, f"Set({col}, {FIELD}={WRITE_ROW})")
                except (ClientError, OSError):
                    time.sleep(0.2)
                    continue
                topup[col] = True
                time.sleep(0.05)
            backlog = entry.write_health().get("hintBacklogOps", 0)
            # restart + membership, then time the hint drain
            t0 = time.perf_counter()
            node = cluster.nodes[victim_i]
            node.stop()
            node.start()
            node.await_up()
            cluster.await_membership(3, timeout=120)
            rejoin_s = time.perf_counter() - t0
            drain_s = await_drained(entry)
            log(f"[{mix_name}] rejoined in {rejoin_s:.1f}s; "
                f"{backlog} hinted op(s) drained in {drain_s:.2f}s")
            cr = measure(entry_port, pql, want, WINDOWS[2], wf, lanes,
                         rng_seed=300 + mi)
            log(f"[{mix_name}] rejoin window: {cr}")
            checked = check_exactness(cluster, lanes)
            log(f"[{mix_name}] exactness: {checked} (node, col) "
                f"checks held on all 3 nodes")
            wav = (b["writes"]["ok"] / b["writes"]["attempts"]
                   if b["writes"]["attempts"] else 0.0)
            rav = (b["reads"]["ok"] / b["reads"]["attempts"]
                   if b["reads"]["attempts"] else 0.0)
            per_mix[mix_name] = {
                "baseline": a, "failure": b, "rejoin": cr,
                "write_availability": round(wav, 4),
                "read_availability": round(rav, 4),
                "hint_backlog_ops": backlog,
                "hint_drain_s": round(drain_s, 2),
                "rejoin_s": round(rejoin_s, 1),
                "exactness_checks": checked,
            }
        entry_metrics = entry.metrics_text()

    availability = min(m["write_availability"] for m in per_mix.values())
    detail = {
        "mixes": per_mix,
        "read_availability_min":
            min(m["read_availability"] for m in per_mix.values()),
        "hint_drain_s_max":
            max(m["hint_drain_s"] for m in per_mix.values()),
        "hint_replay_total":
            prom_counter_total(entry_metrics, "hint_replay_total"),
        "hint_handoff_total":
            prom_counter_total(entry_metrics, "hint_handoff_total"),
        "workers": WORKERS, "shards": N_SHARDS,
        "windows_s": list(WINDOWS),
    }
    metric = ("write_availability_node_kill_smoke" if SMOKE
              else "write_availability_node_kill")
    base_qps = per_mix["80/20"]["baseline"]["writes"]["qps"]
    fail_qps = per_mix["80/20"]["failure"]["writes"]["qps"]
    vs = round(fail_qps / base_qps, 3) if base_qps else 0.0
    log(f"write availability during failure (worst mix): "
        f"{availability:.4f}; hint drain max "
        f"{detail['hint_drain_s_max']}s")
    print(json.dumps({
        "metric": metric, "value": round(availability, 4),
        "unit": "ratio", "vs_baseline": vs,
        "regressions": regression_guard(metric, availability),
        "detail": detail}))


if __name__ == "__main__":
    main()
