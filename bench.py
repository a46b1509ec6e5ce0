"""Headline benchmark: Count(Row) throughput on a 1B-column index,
measured THROUGH THE PRODUCT PATH (real on-disk index -> Holder ->
Executor -> fused count batch -> API), with the raw-kernel roofline
alongside for the breakdown.

BASELINE.json north star: ">=10x CPU QPS on Intersect+Count at 1B
columns".  1B columns = 954 shards x 2^20; a 32-row field plane is
resident in HBM and one fused XLA program answers 32 Count queries (the
per-row popcount matrix reduced over shards) with a single host read.

Two measurement tiers, same data, same concurrency:

- **raw kernel**: jitted count over an in-memory device plane — the
  device ceiling.
- **product**: the index is written to disk as real roaring fragment
  snapshot files, opened through Holder (mmap + directory parse),
  served via ``API.query`` running 32-Count PQL requests through the
  executor's fused count-batch (one program + one read per request),
  every response verified against the numpy oracle.  A REST variant
  (HTTP server, JSON) is timed for the wire overhead figure.

Every timing covers execution + the device->host result read together
(a dispatch without a read only measures the enqueue), in the batched
form — K queries per dispatch, one read — with values verified against
a numpy oracle.  One process, no retries: any phase that fails fails
the run with a non-zero exit.

The baseline column is the CPU stand-in for the reference's Go roaring
executor: numpy popcount over the same packed words on this host.

Prints exactly ONE JSON line:
    {"metric": ..., "value": qps, "unit": "qps", "vs_baseline": ratio,
     "regressions": [...]}

``regressions`` is the regression guard: the headline is compared
against the most recent ``BENCH_r*.json`` round artifact carrying the
SAME metric name; a drop past REGRESSION_RATIO lands in the list (with
the prior round's figure) so a 2.4×-class product-path slide can never
again go unremarked in the round record.  ``PILOSA_BENCH_BASELINE_DIR``
overrides where prior rounds are read from (the smoke test uses it).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# headline scale: 954 shards = ceil(1e9 / 2^20) -> 1.0003e9 columns;
# env overrides exist for small-scale smoke tests of the serving
# pipeline (never set by the driver)
HEADLINE_SHARDS = 954
N_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", str(HEADLINE_SHARDS)))
N_ROWS = int(os.environ.get("PILOSA_BENCH_ROWS", "32"))  # queries/dispatch
WORDS = 32768
N_THREADS = 32  # concurrent clients, both tiers
# "1B cols" names the headline scale only; any other shard count says
# what it ran at, in the metric name and in the log
SCALE_TAG = ("1b_cols" if N_SHARDS == HEADLINE_SHARDS
             else f"{N_SHARDS}_shards")
SCALE_LABEL = ("1B cols" if N_SHARDS == HEADLINE_SHARDS
               else f"{N_SHARDS} shards")

# HBM bandwidth by ``device_kind`` (Google Cloud "TPU v5e": 819 GB/s);
# a kind not listed prints no spec figure beside the chain throughput
HBM_SPEC_GBPS = {"TPU v5 lite": 819}

INDEX = "bench"
FIELD = "f"

# headline drops below this fraction of the last recorded round flag a
# regression in the output JSON (0.8 = tolerate run-to-run wander,
# catch the 2.4x-class slides that motivated the guard)
REGRESSION_RATIO = 0.8

# the product path must serve at the raw-kernel ceiling: a full-scale
# round whose product/raw ratio falls under this lands in the
# `regressions` list (the r05 slide was 0.41 and went unremarked for a
# round — never again).  Toy-scale smoke runs skip the check: per-query
# fixed host costs dominate there and the ratio measures nothing.
PRODUCT_RAW_RATIO_FLOOR = 0.95
FULL_SCALE_SHARDS = 64  # below this the run is a smoke/toy override


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _prior_rounds(metric: str):
    """Yield prior ``BENCH_r*.json`` ``parsed`` payloads carrying the
    SAME metric name, newest first (a CPU smoke run never judges
    itself against a TPU round).  Malformed artifacts are skipped —
    they must not cost the round its benchmark."""
    import glob
    import re

    base_dir = os.environ.get("PILOSA_BENCH_BASELINE_DIR") or \
        os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for path in glob.glob(os.path.join(base_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    for _, path in sorted(rounds, reverse=True):
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
            if parsed.get("metric") != metric:
                continue
        except (OSError, ValueError, TypeError, AttributeError):
            continue  # malformed artifact: try the next round
        yield os.path.basename(path), parsed


def _dig(tree, path: tuple):
    """Walk nested dicts by key path; None on any miss / non-number."""
    cur = tree
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return float(cur) if isinstance(cur, (int, float)) else None


def detail_regression_guard(metric: str, detail: dict, tracked: dict,
                            ratio: float = REGRESSION_RATIO) -> list[dict]:
    """Sub-metric regression guard (r17): compare named values INSIDE
    a config's ``detail`` payload against the newest prior round of
    the same headline metric that recorded a detail.  ``tracked`` maps
    a label to its key path in the detail tree, e.g.
    ``{"single_stream_qps": ("solo", "fastlane_qps")}`` — so a future
    change that tanks the solo floor or one kernel kind's GB/s fails
    the guard even while the concurrent headline hides it.  Rounds
    whose artifacts carry no detail (pre-r17) simply don't match;
    never raises."""
    prev_detail = None
    prev_name = None
    for name, parsed in _prior_rounds(metric):
        d = parsed.get("detail")
        if isinstance(d, dict) and any(
                _dig(d, path) is not None for path in tracked.values()):
            prev_detail, prev_name = d, name
            break
    if prev_detail is None:
        log(f"detail guard: no prior round carries detail for "
            f"{metric!r}; skipped")
        return []
    out = []
    for label, path in tracked.items():
        cur = _dig(detail, path)
        prev = _dig(prev_detail, path)
        if cur is None or not prev or prev <= 0:
            continue
        r = cur / prev
        if r < ratio:
            log(f"REGRESSION: {label} {cur:,.1f} is {r:.2f}x of "
                f"{prev_name}'s {prev:,.1f}")
            out.append({"metric": label, "value": round(cur, 2),
                        "previous": round(prev, 2),
                        "previous_round": prev_name,
                        "ratio": round(r, 3)})
        else:
            log(f"detail guard: {label} at {r:.2f}x of {prev_name} "
                f"— OK")
    return out


def regression_guard(metric: str, value: float) -> list[dict]:
    """Compare the headline against the newest prior ``BENCH_r*.json``
    whose recorded metric matches ``metric`` exactly.  Returns the
    (possibly empty) ``regressions`` list for the output JSON; never
    raises."""
    for path_name, parsed in _prior_rounds(metric):
        try:
            prev = float(parsed.get("value") or 0)
        except (ValueError, TypeError):
            continue
        if prev <= 0:
            continue
        ratio = value / prev
        if ratio < REGRESSION_RATIO:
            log(f"REGRESSION: {metric} {value:,.1f} qps is "
                f"{ratio:.2f}x of {path_name}'s {prev:,.1f} qps")
            return [{"metric": metric, "value": round(value, 2),
                     "previous": round(prev, 2),
                     "previous_round": path_name,
                     "ratio": round(ratio, 3)}]
        log(f"regression guard: {metric} at {ratio:.2f}x of "
            f"{path_name} — OK")
        return []
    log(f"regression guard: no prior round carries {metric!r}; skipped")
    return []


def ratio_guard(prod_qps: float | None, raw_qps: float | None,
                n_shards: int | None = None) -> list[dict]:
    """Product/raw ratio regression entry (empty list when healthy).

    Flags any FULL-SCALE round serving under ``PRODUCT_RAW_RATIO_FLOOR``
    of the raw-kernel ceiling at the same concurrency; toy-scale smoke
    rounds (shards < FULL_SCALE_SHARDS) and rounds missing either tier
    return clean — absence of a measurement is reported elsewhere, not
    as a ratio regression."""
    n_shards = N_SHARDS if n_shards is None else n_shards
    if (prod_qps is None or not raw_qps
            or n_shards < FULL_SCALE_SHARDS):
        return []
    ratio = prod_qps / raw_qps
    if ratio >= PRODUCT_RAW_RATIO_FLOOR:
        return []
    log(f"REGRESSION: product/raw ratio {ratio:.2f} is under the "
        f"{PRODUCT_RAW_RATIO_FLOOR} floor (product {prod_qps:,.1f} qps "
        f"vs raw {raw_qps:,.1f} qps)")
    return [{"metric": "product_raw_ratio", "value": round(ratio, 3),
             "floor": PRODUCT_RAW_RATIO_FLOOR,
             "product_qps": round(prod_qps, 2),
             "raw_qps": round(raw_qps, 2)}]


def cpu_counts(plane: np.ndarray) -> np.ndarray:
    return np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)


def median_serve(run_once, label: str, max_runs: int = 5,
                 min_runs: int = 3, budget_s: float = 180.0):
    """Median-of-N burst qps: concurrent throughput wanders run to run
    (r2 saw +-36% on one shot), so one JSON line must not be a dice
    roll.  Every individual run goes to stderr."""
    runs: list[float] = []
    deadline = time.monotonic() + budget_s
    for rep in range(max_runs):
        runs.append(run_once())
        log(f"{label} run {rep + 1}: {runs[-1]:,.1f} qps")
        if time.monotonic() > deadline and len(runs) >= min_runs:
            break
    return float(np.median(runs)), runs


def concurrent_burst(fn_verify, n_threads: int, iters: int,
                     queries_per_call: int) -> float:
    """Run ``fn_verify()`` (one batched dispatch + oracle check) from
    ``n_threads`` concurrent clients; returns qps.  Any client error
    fails the run."""
    barrier = threading.Barrier(n_threads + 1)
    errors: list[str] = []

    def worker():
        barrier.wait()
        for _ in range(iters):
            try:
                fn_verify()
            except Exception as e:  # noqa: BLE001 — raised after join
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
        raise RuntimeError(f"{len(errors)} of {n_threads} burst clients "
                           f"failed: {errors[:3]}")
    return queries_per_call * iters * n_threads / dt


# ---------------------------------------------------------------------------
# tier 1: raw kernel (device ceiling)
# ---------------------------------------------------------------------------


def raw_kernel_tier(plane: np.ndarray, oracle: np.ndarray):
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import kernels

    dev = jax.devices()[0]
    platform = dev.platform
    t0 = time.perf_counter()
    d = jax.device_put(plane)
    jax.block_until_ready(d)
    log(f"host->HBM {plane.nbytes / 1e9:.1f}GB: "
        f"{time.perf_counter() - t0:.2f}s")

    @jax.jit
    def count_batch(p):
        # 32 Count(Row) queries in one program: per-row popcounts
        # reduced over the shard axis (ICI collective when meshed)
        return jnp.sum(kernels.row_counts(p), axis=0, dtype=jnp.int32)

    # warm (compile) + verify
    got = np.asarray(count_batch(d)).astype(np.int64)
    np.testing.assert_array_equal(got, oracle)
    log("raw-kernel counts verified against numpy oracle")

    lat = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(count_batch(d))  # execute + read
        lat.append(time.perf_counter() - t0)
    p50 = float(np.median(lat))
    log(f"single-stream: {N_ROWS} queries in {p50 * 1e3:.1f} ms -> "
        f"{N_ROWS / p50:,.1f} qps (one dispatch + one read per request)")

    # device-only roofline: N in-order dispatches, ONE final read —
    # amortizes enqueue/read overhead to expose the kernel's own
    # throughput (device executes the queue in order; the final read
    # waits for it all)
    spec = HBM_SPEC_GBPS.get(dev.device_kind)
    spec_note = (f" (HBM spec ~{spec} GB/s on {dev.device_kind})"
                 if spec else "")
    for n_chain in (8, 32):
        t0 = time.perf_counter()
        outs = [count_batch(d) for _ in range(n_chain)]
        np.asarray(outs[-1])
        t = time.perf_counter() - t0
        log(f"roofline chain n={n_chain}: {t / n_chain * 1e3:.2f} "
            f"ms/dispatch = {plane.nbytes / (t / n_chain) / 1e9:.0f} GB/s "
            f"device throughput{spec_note}")

    def one_call():
        got = np.asarray(count_batch(d)).astype(np.int64)
        if not np.array_equal(got, oracle):
            raise AssertionError("count mismatch")

    qps, runs = median_serve(
        lambda: concurrent_burst(one_call, N_THREADS, iters=6,
                                 queries_per_call=N_ROWS),
        "raw-kernel")
    log(f"raw kernel ({platform}): {N_THREADS}-way concurrent batched "
        f"counts -> median {qps:,.1f} qps @ {SCALE_LABEL} over "
        f"{len(runs)} runs (spread {min(runs):,.0f}-{max(runs):,.0f})")
    del d
    return platform, qps


# ---------------------------------------------------------------------------
# tier 2: product path (Holder -> Executor -> API [-> REST])
# ---------------------------------------------------------------------------


def write_product_index(plane: np.ndarray, data_dir: str) -> None:
    """Write the plane as a REAL on-disk index: schema through the
    Holder, one pilosa-format roaring snapshot file per shard
    (vectorized bulk writer ``roaring.serialize_dense``)."""
    from pilosa_tpu.store import Holder, roaring

    t0 = time.perf_counter()
    h = Holder(data_dir).open()
    idx = h.create_index(INDEX, track_existence=False)
    idx.create_field(FIELD)
    h.close()
    frag_dir = os.path.join(data_dir, INDEX, FIELD, "views", "standard",
                            "fragments")
    os.makedirs(frag_dir, exist_ok=True)
    total = 0
    for s in range(plane.shape[0]):
        blob = roaring.serialize_dense(plane[s])
        total += len(blob)
        with open(os.path.join(frag_dir, str(s)), "wb") as fh:
            fh.write(blob)
    log(f"product index written: {plane.shape[0]} fragment snapshots, "
        f"{total / 1e9:.2f} GB in {time.perf_counter() - t0:.1f}s")


def product_tier(data_dir: str, oracle: np.ndarray) -> float:
    from pilosa_tpu.api import API, Server
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.pql.parser import parse
    from pilosa_tpu.store import Holder

    t0 = time.perf_counter()
    holder = Holder(data_dir).open()
    log(f"holder cold open: {(time.perf_counter() - t0) * 1e3:.0f} ms")
    api = API(holder, Executor(holder))

    pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(N_ROWS))
    t0 = time.perf_counter()
    parse(pql)
    log(f"PQL parse ({N_ROWS} calls): "
        f"{(time.perf_counter() - t0) * 1e3:.2f} ms/request")

    # decomposed warmup: host plane assembly + HBM transfer first,
    # then the first query (compile + dispatch + read) on top
    ex = api.executor
    idx = holder.index(INDEX)
    fld = idx.field(FIELD)
    shards = tuple(idx.available_shards())
    t0 = time.perf_counter()
    ps = ex.planes.field_plane(INDEX, fld, "standard", shards)
    import jax as _jax
    _jax.block_until_ready(ps.plane)
    log(f"plane build (mmap expand + device_put): "
        f"{time.perf_counter() - t0:.1f}s")

    want = [int(c) for c in oracle]
    t0 = time.perf_counter()
    res = api.query(INDEX, pql)["results"]
    log(f"first product query (compile + dispatch + read): "
        f"{time.perf_counter() - t0:.1f}s")
    assert res == want, "product-path counts diverge from oracle"
    log("product-path counts verified against numpy oracle")

    def one_call():
        if api.query(INDEX, pql)["results"] != want:
            raise AssertionError("product count mismatch")

    def burst():
        return concurrent_burst(one_call, N_THREADS, iters=6,
                                queries_per_call=N_ROWS)

    qps, runs = median_serve(burst, "product")
    log(f"product path: {N_THREADS}-way concurrent 32-Count PQL "
        f"requests -> median {qps:,.1f} qps @ {SCALE_LABEL} over "
        f"{len(runs)} runs (spread {min(runs):,.0f}-{max(runs):,.0f})")

    # REST variant: same workload over HTTP, JSON and protobuf wires
    # (VERDICT r3 #4: is the REST gap JSON marshalling or socket cost?)
    import urllib.request

    from pilosa_tpu.api import proto

    srv = Server(api, host="127.0.0.1", port=0)
    st = threading.Thread(target=srv.serve_forever, daemon=True)
    st.start()
    url = f"http://127.0.0.1:{srv.address[1]}/index/{INDEX}/query"
    jbody = pql.encode()
    pbody = proto.encode_query_request(pql)

    def rest_json():
        req = urllib.request.Request(url, data=jbody, method="POST")
        with urllib.request.urlopen(req) as resp:
            if json.loads(resp.read())["results"] != want:
                raise AssertionError("REST count mismatch")

    def rest_proto():
        req = urllib.request.Request(
            url, data=pbody, method="POST",
            headers={"Content-Type": proto.CONTENT_TYPE,
                     "Accept": proto.CONTENT_TYPE})
        with urllib.request.urlopen(req) as resp:
            got = proto.decode_query_response(resp.read())["results"]
            if got != want:
                raise AssertionError("REST proto count mismatch")

    try:
        for name, call in (("JSON", rest_json), ("proto", rest_proto)):
            call()  # warm
            rest = concurrent_burst(call, N_THREADS, iters=3,
                                    queries_per_call=N_ROWS)
            log(f"REST {name}: {N_THREADS}-way concurrent -> "
                f"{rest:,.1f} qps")
    finally:
        srv.close()
        holder.close()
    return qps


def main() -> None:
    rng = np.random.default_rng(42)
    # ~25% density rows
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    log(f"plane: {plane.nbytes / 1e9:.2f} GB, {N_ROWS} rows x "
        f"{SCALE_LABEL}")

    t0 = time.perf_counter()
    oracle = cpu_counts(plane)
    t_cpu_total = time.perf_counter() - t0
    cpu_qps = N_ROWS / t_cpu_total
    log(f"cpu stand-in reference: {cpu_qps:,.2f} count-queries/s @ "
        f"{SCALE_LABEL}")

    platform, raw_qps = raw_kernel_tier(plane, oracle)

    data_dir = tempfile.mkdtemp(prefix="pilosa_bench_")
    try:
        write_product_index(plane, data_dir)
        del plane  # holder/mmap is the source of truth from here on
        prod_qps = product_tier(data_dir, oracle)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    # headline: the product path IS the database (VERDICT r2 #1); the
    # stderr log carries the raw-kernel figure for the breakdown
    log(f"product/raw ratio: {prod_qps / raw_qps:.2f} "
        f"(product serves {prod_qps / raw_qps * 100:.0f}% of the "
        f"raw-kernel ceiling at the same concurrency)")
    metric = f"product_count_qps_{SCALE_TAG}_{platform}"
    print(json.dumps({
        "metric": metric,
        "value": round(prod_qps, 2),
        "unit": "qps",
        "vs_baseline": round(prod_qps / cpu_qps, 3),
        # two independent guards: headline vs the newest same-metric
        # round, and the product/raw ratio vs its floor (full scale)
        "regressions": (regression_guard(metric, prod_qps)
                        + ratio_guard(prod_qps, raw_qps)),
    }))


if __name__ == "__main__":
    main()
