"""Config #32: ZIPFIAN MANY-TENANT SERVING UNDER AN HBM ECONOMY (r17).

The r17 tenancy subsystem's headline proof: dozens of tenants (one
index+field each) whose COMBINED plane working set is a multiple of
the configured HBM budget, served through paged plane residency — only
each tenant's hot pages are device-resident, the ResidencyGovernor
churns the cold tail (tenant byte quotas + cache budget evictions),
and non-resident pages answer from the fragment directory oracle.

Measured on one real server process (small PILOSA_PLANE_BUDGET_BYTES /
PILOSA_TENANT_BYTE_QUOTA / PILOSA_PLANE_PAGE_BYTES so every tenant's
plane is over-quota and the combined set is ≥ 2x the budget):

  phase W  warm      one sweep over every tenant pages the hot set in
  phase M  measure   READER workers each pick a tenant per query from
                     a zipf(1.1) popularity curve and run its Count
                     batch; every answer is oracle-checked LIVE

Hard assertions INSIDE the bench (every run, smoke and full):

- every read oracle-exact while pages churn (page-ins + evictions > 0)
- no tenant's availability < 1.0 (nothing sheds — no qps/slot quotas
  here; a failed read is a bench failure, not a shed)
- ZERO full plane rebuilds once warm: the planeBuild counter is flat
  across the measurement phase — residency moves ONLY by sidecar-warm
  page-ins, never whole-plane rebuilds

Headline ``value`` = aggregate qps across the zipfian mix.  ``detail``
carries the worst-tenant p99 (raw ms, plus ``worst_tenant_p99_inv`` =
1000/p99 so the higher-is-better detail guard can gate it) and the
final /status tenancy block.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 6 tenants x 3 shards, short
window — tier-1 runs it (tests/test_bench_smoke.py).

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
N_TENANTS = 6 if SMOKE else 24
N_SHARDS = 3 if SMOKE else 8       # per tenant
N_ROWS = 4                          # oracle-checked rows per tenant
READERS = 4 if SMOKE else 16
WINDOW = 2.0 if SMOKE else 8.0
ZIPF_S = 1.1                        # popularity skew across tenants

# the HBM economy: per-shard slab at r_pad(4 rows) is 512 KiB, so a
# tenant's plane is N_SHARDS x 512 KiB.  The budget holds a fraction
# of the combined set and each tenant's byte quota holds ~2 pages —
# every tenant is over-quota (paged) and the cache must churn.
SLAB = 4 * 32768 * 4                                  # 512 KiB
BUDGET = (4 << 20) if SMOKE else (32 << 20)
TENANT_QUOTA = int(2.2 * SLAB)                        # ~2 pages
PAGE_BYTES = 1 << 20


def tenant(i: int) -> str:
    return f"ten{i:02d}"


def regression_guard(metric: str, value: float, detail: dict,
                     tracked: dict) -> list:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return (mod.regression_guard(metric, value)
            + mod.detail_regression_guard(metric, detail, tracked))


def seed_tenant(client, idx: str, rng) -> list[int]:
    """Deterministic bits across every shard; returns the per-row
    Count oracle for this tenant."""
    from pilosa_tpu.engine.words import SHARD_WIDTH

    client.create_index(idx)
    client.create_field(idx, "f")
    rows, cols = [], []
    counts = [0] * N_ROWS
    for s in range(N_SHARDS):
        offs = rng.choice(SHARD_WIDTH // 2, size=48, replace=False)
        rr = rng.integers(0, N_ROWS, size=48)
        for r, o in zip(rr, offs):
            rows.append(int(r))
            cols.append(s * SHARD_WIDTH + int(o))
            counts[int(r)] += 1
    client.import_bits(idx, "f", rowIDs=rows, columnIDs=cols)
    return counts


def plane_builds(client) -> int:
    return client._json("GET", "/status")["storage"]["planeBuild"]["builds"]


def zipf_weights(n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** ZIPF_S
    return w / w.sum()


def measure(port: int, oracles: dict[str, list[int]],
            seconds: float) -> dict:
    """READERS workers, each picking a tenant per query from the
    zipfian popularity curve; every answer oracle-checked live."""
    from pilosa_tpu.api.client import Client, ClientError

    names = sorted(oracles)
    weights = zipf_weights(len(names))
    pql = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
    stop = time.monotonic() + seconds
    ok: dict[str, int] = {t: 0 for t in names}
    bad: dict[str, list[str]] = {t: [] for t in names}
    lats: dict[str, list[float]] = {t: [] for t in names}
    lock = threading.Lock()

    def reader(i):
        rng = np.random.default_rng(1000 + i)
        client = Client("127.0.0.1", port, timeout=30.0)
        while time.monotonic() < stop:
            t = names[int(rng.choice(len(names), p=weights))]
            t0 = time.perf_counter()
            try:
                got = client.query(t, pql)
            except (ClientError, OSError) as e:
                with lock:
                    bad[t].append(f"error: {e!r}")
                continue
            dt = time.perf_counter() - t0
            with lock:
                if got != oracles[t]:
                    bad[t].append(f"wrong counts: {got}")
                else:
                    ok[t] += 1
                    lats[t].append(dt)
        client.close()

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(READERS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    def pct(ls, p):
        s = sorted(ls)
        return round(s[min(len(s) - 1, int(p * len(s)))] * 1e3, 2) \
            if s else None

    per_tenant = {}
    for t in names:
        att = ok[t] + len(bad[t])
        per_tenant[t] = {
            "attempts": att, "ok": ok[t], "failed": len(bad[t]),
            "failures": bad[t][:3],
            "availability": round(ok[t] / att, 4) if att else None,
            "p50_ms": pct(lats[t], 0.5), "p99_ms": pct(lats[t], 0.99)}
    total_ok = sum(ok.values())
    all_lats = [x for ls in lats.values() for x in ls]
    return {"per_tenant": per_tenant,
            "aggregate": {"ok": total_ok,
                          "failed": sum(len(b) for b in bad.values()),
                          "qps": round(total_ok / seconds, 1),
                          "p50_ms": pct(all_lats, 0.5),
                          "p99_ms": pct(all_lats, 0.99)}}


def main():
    import tempfile

    from pilosa_tpu.testing import run_process_cluster

    rng = np.random.default_rng(32)
    td = tempfile.mkdtemp(prefix="pilosa_multitenant_")
    extra_env = {
        "PILOSA_PLANE_BUDGET_BYTES": str(BUDGET),
        "PILOSA_TENANT_BYTE_QUOTA": str(TENANT_QUOTA),
        "PILOSA_PLANE_PAGE_BYTES": str(PAGE_BYTES),
    }
    combined = N_TENANTS * N_SHARDS * SLAB
    log(f"{N_TENANTS} tenants x {N_SHARDS} shards: combined working "
        f"set {combined >> 20} MiB vs budget {BUDGET >> 20} MiB "
        f"({combined / BUDGET:.1f}x), tenant quota "
        f"{TENANT_QUOTA >> 10} KiB")
    assert combined >= 2 * BUDGET, "working set must dwarf the budget"
    with run_process_cluster(1, td, extra_env=extra_env) as cluster:
        c0 = cluster.client(0)
        port = cluster.nodes[0].port
        oracles = {tenant(i): seed_tenant(c0, tenant(i), rng)
                   for i in range(N_TENANTS)}
        pql = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
        # phase W: one warm sweep pages every tenant's hot set in (and
        # proves cold exactness tenant by tenant)
        for t, want in oracles.items():
            got = c0.query(t, pql)
            assert got == want, f"[{t}] cold counts wrong: {got}"
        builds_warm = plane_builds(c0)
        ten0 = c0._json("GET", "/status")["tenancy"]
        log(f"warm: {ten0['residentPages']} resident pages, "
            f"{ten0['pageIns']} page-ins, {ten0['evictions']} "
            f"evictions after the sweep")

        # phase M: the zipfian mix
        m = measure(port, oracles, WINDOW)
        builds_after = plane_builds(c0)
        status = c0._json("GET", "/status")
        ten = status["tenancy"]

    agg = m["aggregate"]
    rebuilds = builds_after - builds_warm
    log(f"zipfian mix: {agg['qps']} qps aggregate, p99 "
        f"{agg['p99_ms']} ms; {ten['pageIns']} page-ins, "
        f"{ten['evictions']} evictions, {ten['oracleServes']} oracle "
        f"serves, {rebuilds} full rebuilds during measurement")
    # --- the r17 acceptance bars, hard on every run ---
    assert agg["failed"] == 0, \
        f"reads failed oracle: {[b for t in m['per_tenant'].values() for b in t['failures']]}"
    for t, pt in m["per_tenant"].items():
        if pt["attempts"]:
            assert pt["availability"] == 1.0, \
                f"[{t}] availability {pt['availability']}: {pt['failures']}"
    assert rebuilds == 0, \
        f"{rebuilds} full plane rebuild(s) during measurement — " \
        f"residency must move by page-ins only"
    assert ten["pageIns"] >= N_TENANTS, \
        f"paging never engaged: {ten['pageIns']} page-ins"
    assert ten["evictions"] >= 1, \
        f"the cache never churned: {ten}"

    worst = max((pt["p99_ms"] for pt in m["per_tenant"].values()
                 if pt["p99_ms"] is not None), default=None)
    value = agg["qps"]
    detail = {
        "mix": m,
        "aggregate_qps": value,
        "worst_tenant_p99_ms": worst,
        # the detail guard assumes higher-is-better: gate the INVERSE
        "worst_tenant_p99_inv": round(1000.0 / worst, 3) if worst else None,
        "tenancy": ten,
        "plane_rebuilds_during_measurement": rebuilds,
        "tenants": N_TENANTS, "shards_per_tenant": N_SHARDS,
        "readers": READERS, "window_s": WINDOW,
        "budget_bytes": BUDGET, "tenant_quota_bytes": TENANT_QUOTA,
        "working_set_over_budget": round(combined / BUDGET, 2),
    }
    metric = ("multitenant_zipf_qps_smoke" if SMOKE
              else "multitenant_zipf_qps")
    tracked = {"aggregate_qps": ("aggregate_qps",),
               "worst_tenant_p99_inv": ("worst_tenant_p99_inv",)}
    print(json.dumps({
        "metric": metric, "value": round(value, 1), "unit": "qps",
        "vs_baseline": round(value, 1),
        "regressions": regression_guard(metric, value, detail, tracked),
        "detail": detail}))


if __name__ == "__main__":
    main()
