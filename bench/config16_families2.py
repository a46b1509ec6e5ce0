"""Config #16: the five PQL families config10 left unmeasured at the
1B-column serving condition (VERDICT r4 #4 — "r3→r4 proved twice that
unmeasured families hide multi-second host-path regressions").

Same recipe as config10: real on-disk roaring index → Holder →
Executor → API, every result oracle-verified against numpy over the
same data, product latency vs the raw device-program ceiling measured
back-to-back in the same process.

  - Distinct(field=v) and Distinct(Row(f=0), field=v) — BSI presence
    scatter (executor._execute_distinct; reference: v2
    ``executeDistinctShard``)
  - Percentile(field=v, nth=99) — on-device binary search
    (``bsi.percentile_search``; FeatureBase-era Percentile)
  - Extract(Limit(Row(f=0), limit=1000), Rows(f)) — columnar extract
    (reference: ``executor.go#executeExtract``)
  - Rows(f) and Rows(f, column=c) — row-id enumeration with a
    column-bits probe (reference: ``fragment.rows``)
  - Count(Row(ts=r, from=, to=)) — time-quantum view union over hourly
    views (reference: ``viewsByTimeRange``, SURVEY.md §3.1)

Scale via PILOSA_BENCH_SHARDS (default 954 = 1B cols)."""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log
from bench.config10_product_families import (
    INDEX, KNUTH, N_ROWS, N_SHARDS, WORDS, bsi_values, median_lat,
    pack_bits)

TS_ROWS = 4
HOURS = ["2017010200", "2017010201", "2017010202", "2017010203"]


def build_index(data_dir: str, plane: np.ndarray, ts_planes: dict,
                rng) -> None:
    """f (dense 32-row) + v (BSI, every column) + ts (4-row time field,
    4 hourly views + standard union)."""
    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.store import FieldOptions, Holder, roaring

    t0 = time.perf_counter()
    h = Holder(data_dir).open()
    idx = h.create_index(INDEX, track_existence=False)
    idx.create_field("f")
    vf = idx.create_field("v", FieldOptions(type="int", min=-125, max=125))
    assert vf.options.base == 0 and vf.options.bit_depth == 7
    idx.create_field("ts", FieldOptions(type="time", time_quantum="YMDH"))
    h.close()

    fdir = os.path.join(data_dir, INDEX, "f", "views", "standard",
                        "fragments")
    os.makedirs(fdir, exist_ok=True)
    for s in range(N_SHARDS):
        with open(os.path.join(fdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))

    vdir = os.path.join(data_dir, INDEX, "v", "views", "bsi_v",
                        "fragments")
    os.makedirs(vdir, exist_ok=True)
    ones = np.full(WORDS, 0xFFFFFFFF, np.uint32)
    for s in range(N_SHARDS):
        cols = (np.arange(SHARD_WIDTH, dtype=np.uint64)
                + np.uint64(s * SHARD_WIDTH))
        v = bsi_values(cols)
        mag = np.abs(v).astype(np.uint32)
        rows = [ones, pack_bits(v < 0)]
        row_ids = [0, 1]
        for b in range(7):
            rows.append(pack_bits(((mag >> b) & 1).astype(bool)))
            row_ids.append(2 + b)
        with open(os.path.join(vdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(
                np.stack(rows), np.array(row_ids, np.uint64)))

    # time field: one dense TS_ROWS-row plane per hourly view, plus the
    # standard view as their union (a timestamped write lands in
    # standard + every quantum view — store/timeq.views_by_time)
    std = None
    for hour, tsp in ts_planes.items():
        tdir = os.path.join(data_dir, INDEX, "ts", "views",
                            f"standard_{hour}", "fragments")
        os.makedirs(tdir, exist_ok=True)
        for s in range(N_SHARDS):
            with open(os.path.join(tdir, str(s)), "wb") as fh:
                fh.write(roaring.serialize_dense(tsp[s]))
        std = tsp if std is None else std | tsp
    sdir = os.path.join(data_dir, INDEX, "ts", "views", "standard",
                        "fragments")
    os.makedirs(sdir, exist_ok=True)
    for s in range(N_SHARDS):
        with open(os.path.join(sdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(std[s]))
    log(f"index built (f + bsi v + ts x {len(HOURS)} hourly views, "
        f"{N_SHARDS} shards): {time.perf_counter() - t0:.1f}s")


def oracle_percentile(nth: float):
    """Exact nth percentile of bsi_values over all 1B columns: value v
    with count(<= v) >= ceil(nth% of total), plus count(== v)."""
    total = N_SHARDS * (WORDS * 32)
    counts = np.zeros(251, np.int64)
    chunk = 1 << 24
    for a in range(0, total, chunk):
        cols = np.arange(a, min(a + chunk, total), dtype=np.uint64)
        res = ((cols * np.uint64(KNUTH)) % np.uint64(251)).astype(np.int64)
        counts += np.bincount(res, minlength=251)
    # residue r maps to value r - 125; values ascend with residue
    cum = np.cumsum(counts)
    threshold = int(np.ceil(total * nth / 100.0))
    idx = int(np.searchsorted(cum, threshold))
    return idx - 125, int(counts[idx]), total


def warm_query(api, pql, attempts=5, wait=45.0):
    """First (residency-building) query of a family, with patience:
    a chip shared with other tenants can refuse a GB-scale device_put
    that succeeds minutes later (r5) — back off and retry instead of
    failing the whole bench."""
    for attempt in range(attempts):
        try:
            return api.query(INDEX, pql)["results"]
        except Exception as e:  # noqa: BLE001
            if "RESOURCE_EXHAUSTED" not in repr(e) or \
                    attempt == attempts - 1:
                raise
            log(f"device OOM warming {pql[:40]!r} (attempt "
                f"{attempt + 1}/{attempts}); waiting {wait:.0f}s")
            time.sleep(wait)


def main():
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.api import API
    from pilosa_tpu.engine import bsi as bsik
    from pilosa_tpu.engine import kernels
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(16)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    ts_planes = {}
    for hour in HOURS:
        tsp = rng.integers(0, 1 << 32, size=(N_SHARDS, TS_ROWS, WORDS),
                           dtype=np.uint32)
        tsp &= rng.integers(0, 1 << 32, size=tsp.shape, dtype=np.uint32)
        tsp &= rng.integers(0, 1 << 32, size=tsp.shape, dtype=np.uint32)
        ts_planes[hour] = tsp
    data_dir = os.environ.get("PILOSA_BENCH_DATADIR")
    if data_dir and os.path.isdir(os.path.join(data_dir, INDEX)):
        log(f"reusing prebuilt index at {data_dir}")
    else:
        data_dir = data_dir or tempfile.mkdtemp(prefix="pilosa_fam2_")
        build_index(data_dir, plane, ts_planes, rng)

    holder = Holder(data_dir).open()
    api = API(holder, Executor(holder, plane_budget=8 << 30))
    ex = api.executor
    results = {}

    def family(name, product_s, raw_s):
        ratio = raw_s / product_s if product_s else 0.0
        results[name] = {"product_ms": round(product_s * 1e3, 1),
                         "raw_ms": round(raw_s * 1e3, 1),
                         "raw_over_product": round(ratio, 2)}
        log(f"{name}: product {product_s * 1e3:.0f} ms vs raw "
            f"{raw_s * 1e3:.0f} ms ({ratio:.2f}x of ceiling)")

    fld = holder.index(INDEX).field("f")
    vf = holder.index(INDEX).field("v")
    shards = tuple(holder.index(INDEX).available_shards())

    # ---- Distinct -------------------------------------------------------
    want = [v for v in range(-125, 126)]
    got = warm_query(api, "Distinct(field=v)")[0]
    assert got == {"values": want}, f"Distinct: {str(got)[:60]}..."
    t0 = time.perf_counter()
    api.query(INDEX, "Distinct(field=v)")
    log(f"distinct first (BSI plane build + transfer): "
        f"{time.perf_counter() - t0:.1f}s")
    prod = median_lat(lambda: api.query(INDEX, "Distinct(field=v)"))
    vps = ex.planes.bsi_plane(INDEX, vf, shards)

    def raw_distinct():
        pos, neg = bsik.distinct_presence(vps.plane, None)
        np.asarray(pos), np.asarray(neg)

    raw_distinct()
    family("distinct", prod, median_lat(raw_distinct))

    # filtered Distinct: values among row-0 columns — row 0 is a ~25%
    # random mask over 1B columns, so all 251 values survive
    got = warm_query(api, "Distinct(Row(f=0), field=v)")[0]
    assert got == {"values": want}, "filtered Distinct diverged"
    prod_fd = median_lat(
        lambda: api.query(INDEX, "Distinct(Row(f=0), field=v)"))
    results["distinct_filtered"] = {"product_ms": round(prod_fd * 1e3, 1)}
    log(f"distinct_filtered: product {prod_fd * 1e3:.0f} ms")

    # ---- Percentile -----------------------------------------------------
    want_val, want_cnt, total = oracle_percentile(99.0)
    got = api.query(INDEX, "Percentile(field=v, nth=99)")["results"][0]
    assert got == {"value": want_val, "count": want_cnt}, \
        f"Percentile: {got} != value={want_val} count={want_cnt}"
    prod = median_lat(
        lambda: api.query(INDEX, "Percentile(field=v, nth=99)"))

    def raw_pct():
        out, tot = ex.fused.run_percentile(vps.plane, None, 99.0)
        np.asarray(out)

    raw_pct()
    family("percentile", prod, median_lat(raw_pct))

    # ---- Extract --------------------------------------------------------
    # first 1000 columns of row 0 (shard 0), membership across 32 rows
    r0 = np.nonzero(
        np.unpackbits(plane[0, 0].view(np.uint8), bitorder="little"))[0]
    cols1k = r0[:1000]
    want_ext = {int(c): [int(r) for r in range(N_ROWS)
                         if (plane[0, r, c >> 5] >> (c & 31)) & 1]
                for c in cols1k}
    pql_ext = "Extract(Limit(Row(f=0), limit=1000), Rows(f))"
    got = warm_query(api, pql_ext)[0]
    got_map = {c["column"]: c["rows"][0] for c in got["columns"]}
    assert got_map == want_ext, "Extract diverged"
    prod = median_lat(lambda: api.query(INDEX, pql_ext))
    results["extract_1k"] = {"product_ms": round(prod * 1e3, 1)}
    log(f"extract_1k: product {prod * 1e3:.0f} ms (host column-bits "
        "gather over 32 rows x 1000 cols)")

    # ---- Rows -----------------------------------------------------------
    got = api.query(INDEX, "Rows(f)")["results"][0]
    assert got == {"rows": list(range(N_ROWS))}, f"Rows: {got}"
    prod = median_lat(lambda: api.query(INDEX, "Rows(f)"))
    results["rows"] = {"product_ms": round(prod * 1e3, 1)}
    log(f"rows: product {prod * 1e3:.0f} ms")

    col = int(r0[0])  # a column known to hold row 0
    want_rc = [int(r) for r in range(N_ROWS)
               if (plane[0, r, col >> 5] >> (col & 31)) & 1]
    got = api.query(INDEX, f"Rows(f, column={col})")["results"][0]
    assert got == {"rows": want_rc}, f"Rows(column): {got}"
    prod = median_lat(
        lambda: api.query(INDEX, f"Rows(f, column={col})"))
    results["rows_column"] = {"product_ms": round(prod * 1e3, 1)}
    log(f"rows_column: product {prod * 1e3:.0f} ms")

    # ---- time-quantum Range ---------------------------------------------
    # [00:00, 02:00) covers exactly the first two hourly views
    union2 = ts_planes[HOURS[0]] | ts_planes[HOURS[1]]
    want_t = int(np.bitwise_count(union2[:, 1, :]).sum(dtype=np.int64))
    pql_t = ("Count(Row(ts=1, from=2017-01-02T00:00, "
             "to=2017-01-02T02:00))")
    got = warm_query(api, pql_t)[0]
    assert got == want_t, f"time Range: {got} != {want_t}"
    prod = median_lat(lambda: api.query(INDEX, pql_t))

    tsf = holder.index(INDEX).field("ts")
    p0 = ex.planes.field_plane(INDEX, tsf, f"standard_{HOURS[0]}", shards)
    p1 = ex.planes.field_plane(INDEX, tsf, f"standard_{HOURS[1]}", shards)

    @jax.jit
    def raw_range(a, b):
        return kernels.count(a[:, 1, :] | b[:, 1, :])

    np.asarray(raw_range(p0.plane, p1.plane))
    family("time_range_2h", prod,
           median_lat(lambda: np.asarray(raw_range(p0.plane, p1.plane))))

    holder.close()
    import shutil
    if not os.environ.get("PILOSA_BENCH_DATADIR"):
        shutil.rmtree(data_dir, ignore_errors=True)

    worst = min((f["raw_over_product"] for f in results.values()
                 if f.get("raw_over_product")), default=0.0)
    print(json.dumps({
        "metric": f"product_families2_worst_ratio_{platform}",
        "value": round(worst, 3), "unit": "raw/product",
        "vs_baseline": 1.0, "families": results}))


if __name__ == "__main__":
    main()
