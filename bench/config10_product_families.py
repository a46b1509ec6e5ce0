"""Config #10: the WHOLE PQL surface at the 1B-column serving condition,
THROUGH THE PRODUCT PATH (on-disk roaring index -> Holder -> Executor ->
API), each family oracle-verified and compared to its raw-kernel
ceiling measured in the same process.

Rationale (VERDICT r3 weak #1): the r3 headline proved Count(Row) at
1.00x of the raw ceiling, but the count path needed four profiled fixes
to get there (0.24x -> 1.00x) — so every OTHER call family's product
overhead was an unmeasured risk.  This config measures them:

  - TopN (unfiltered: host directory sums; filtered: fused device
    program) on the 32-row field at 1B cols
  - BSI aggregates (Sum / Min / Max / Range+Count) over a depth-8 int
    field with values on ALL 1B columns
  - GroupBy 4x4 rows at 1B cols (whole combination tree, one program)
  - sparse filtered TopN over a 5M-distinct-row field (20M bits spread
    over all 954 shards, container-blocked CSR residency)
  - REST variants (JSON and application/x-protobuf) for Count and TopN

Every op here is one device dispatch + one host read, so the raw
ceiling for a single-stream call is approximately the fixed per-read
cost; the product number is honest if it sits within ~15% of its raw tier
measured back-to-back in the same process.

Scale via PILOSA_BENCH_SHARDS (default 954 = 1B cols; smoke tests use
a handful)."""

import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

N_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", "954"))
N_ROWS = 32
WORDS = 32768  # uint32 words per shard row (2^20 bits)
SPARSE_ROWS = 5_000_000
SPARSE_BITS = 20_000_000
KNUTH = 2654435761

INDEX = "bench"


def median_lat(fn, n=5):
    """Median seconds over n calls (call must include its host read)."""
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def bsi_values(cols: np.ndarray) -> np.ndarray:
    """Deterministic per-column value in [-125, 125]."""
    return ((cols.astype(np.uint64) * np.uint64(KNUTH))
            % np.uint64(251)).astype(np.int64) - 125


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """bool[SHARD_WIDTH] -> uint32[WORDS] little-endian packed."""
    return np.packbits(bits, bitorder="little").view(np.uint32)


# ---------------------------------------------------------------------------
# index construction (real on-disk roaring snapshots)
# ---------------------------------------------------------------------------


def build_index(data_dir: str, plane: np.ndarray, rng) -> dict:
    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.store import FieldOptions, Holder, roaring

    t0 = time.perf_counter()
    h = Holder(data_dir).open()
    idx = h.create_index(INDEX, track_existence=False)
    idx.create_field("f")
    vf = idx.create_field("v", FieldOptions(type="int", min=-125, max=125))
    # base 0 (min < 0 < max), magnitude 7 bits, sign row for negatives
    assert vf.options.base == 0 and vf.options.bit_depth == 7
    idx.create_field("tags").import_bits(
        np.array([0], np.uint64), np.array([0], np.uint64))
    h.close()

    # dense 32-row field f
    fdir = os.path.join(data_dir, INDEX, "f", "views", "standard",
                        "fragments")
    os.makedirs(fdir, exist_ok=True)
    for s in range(N_SHARDS):
        with open(os.path.join(fdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))

    # BSI field v: values on every column (store/field.py layout:
    # EXISTS=0, SIGN=1, magnitude bit b of |v - base| at 2+b; base 0)
    vdir = os.path.join(data_dir, INDEX, "v", "views", "bsi_v",
                        "fragments")
    os.makedirs(vdir, exist_ok=True)
    ones = np.full(WORDS, 0xFFFFFFFF, np.uint32)
    for s in range(N_SHARDS):
        cols = (np.arange(SHARD_WIDTH, dtype=np.uint64)
                + np.uint64(s * SHARD_WIDTH))
        v = bsi_values(cols)
        mag = np.abs(v).astype(np.uint32)
        rows = [ones,  # exists: every column
                pack_bits(v < 0)]  # sign
        row_ids = [0, 1]
        for b in range(7):
            rows.append(pack_bits(((mag >> b) & 1).astype(bool)))
            row_ids.append(2 + b)
        words = np.stack(rows)
        with open(os.path.join(vdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(
                words, np.array(row_ids, np.uint64)))

    # sparse field tags: SPARSE_BITS bits over SPARSE_ROWS rows, spread
    # across every shard
    srows = rng.integers(0, SPARSE_ROWS, size=SPARSE_BITS).astype(np.uint64)
    scols = rng.integers(0, N_SHARDS * SHARD_WIDTH,
                         size=SPARSE_BITS).astype(np.uint64)
    # dedupe (row, col) pairs: the roaring snapshot stores a set, the
    # oracle must count the same set (cols < 2^40, rows < 2^24)
    key = np.unique((srows << np.uint64(40)) | scols)
    srows = (key >> np.uint64(40)).astype(np.uint64)
    scols = key & np.uint64((1 << 40) - 1)
    tdir = os.path.join(data_dir, INDEX, "tags", "views", "standard",
                        "fragments")
    shard_of = scols // np.uint64(SHARD_WIDTH)
    order = np.argsort(shard_of, kind="stable")
    srows, scols, shard_of = srows[order], scols[order], shard_of[order]
    bounds = np.searchsorted(shard_of, np.arange(N_SHARDS + 1))
    for s in range(N_SHARDS):
        a, b = bounds[s], bounds[s + 1]
        if a == b:
            continue
        pos = (srows[a:b] * np.uint64(SHARD_WIDTH)
               + (scols[a:b] % np.uint64(SHARD_WIDTH)))
        with open(os.path.join(tdir, str(s)), "wb") as fh:
            fh.write(roaring.serialize(pos))
    op0 = os.path.join(tdir, "0.oplog")
    if os.path.exists(op0):
        os.remove(op0)
    log(f"index built (f + bsi v + sparse tags, {N_SHARDS} shards): "
        f"{time.perf_counter() - t0:.1f}s")
    return {"rows": srows, "cols": scols}


# ---------------------------------------------------------------------------
# oracles (numpy over the same data)
# ---------------------------------------------------------------------------


def oracle_counts(plane):
    return np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)


def oracle_filtered_topn(plane, filter_row: int, n: int):
    flt = plane[:, filter_row, :]
    cnt = np.bitwise_count(plane & flt[:, None, :]).sum(
        axis=(0, 2), dtype=np.int64)
    order = np.lexsort((np.arange(len(cnt)), -cnt))[:n]
    return [(int(r), int(cnt[r])) for r in order]


def oracle_bsi(chunk=1 << 22):
    """Sum / count(v > 50) over all columns, chunked (1B values)."""
    total_cols = N_SHARDS * (WORDS * 32)
    s = 0
    gt50 = 0
    for a in range(0, total_cols, chunk):
        cols = np.arange(a, min(a + chunk, total_cols), dtype=np.uint64)
        v = bsi_values(cols)
        s += int(v.sum())
        gt50 += int((v > 50).sum())
    return s, total_cols, gt50


def oracle_groupby(plane, rows_a, rows_b):
    out = {}
    for i in rows_a:
        pi = plane[:, i, :]
        for j in rows_b:
            out[(i, j)] = int(np.bitwise_count(
                pi & plane[:, j, :]).sum(dtype=np.int64))
    return out


def oracle_sparse_topn(plane, sparse, filter_row: int, n: int):
    from pilosa_tpu.engine.words import SHARD_WIDTH
    flt = plane[:, filter_row, :]  # uint32[S, WORDS]
    cols = sparse["cols"]
    shard = (cols // np.uint64(SHARD_WIDTH)).astype(np.int64)
    off = (cols % np.uint64(SHARD_WIDTH)).astype(np.int64)
    hit = (flt[shard, off >> 5] >> (off & 31)) & 1
    cnt = np.bincount(sparse["rows"][hit.astype(bool)].astype(np.int64),
                      minlength=SPARSE_ROWS)
    order = np.lexsort((np.arange(len(cnt)), -cnt))[:n]
    return [(int(r), int(cnt[r])) for r in order]


# ---------------------------------------------------------------------------


def main():
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.api import API, Server
    from pilosa_tpu.engine import kernels
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(42)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    log(f"dense plane: {plane.nbytes / 1e9:.2f} GB "
        f"({N_SHARDS} shards x {N_ROWS} rows)")

    data_dir = tempfile.mkdtemp(prefix="pilosa_fam_")
    sparse = build_index(data_dir, plane, rng)

    holder = Holder(data_dir).open()
    api = API(holder, Executor(holder))
    ex = api.executor
    results = {}

    def family(name, product_s, raw_s):
        ratio = raw_s / product_s if product_s else 0.0
        results[name] = {"product_ms": round(product_s * 1e3, 1),
                         "raw_ms": round(raw_s * 1e3, 1),
                         "raw_over_product": round(ratio, 2)}
        log(f"{name}: product {product_s * 1e3:.0f} ms vs raw "
            f"{raw_s * 1e3:.0f} ms ({ratio:.2f}x of ceiling)")

    # ---- Count sanity + warm the f plane --------------------------------
    want_counts = oracle_counts(plane)
    pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
    t0 = time.perf_counter()
    got = api.query(INDEX, pql32)["results"]
    log(f"first count query (plane build + transfer + compile): "
        f"{time.perf_counter() - t0:.1f}s")
    assert got == [int(c) for c in want_counts], "count oracle mismatch"
    prod_count = median_lat(lambda: api.query(INDEX, pql32))
    fld = holder.index(INDEX).field("f")
    shards = tuple(holder.index(INDEX).available_shards())
    ps = ex.planes.field_plane(INDEX, fld, "standard", shards)

    @jax.jit
    def raw_counts(p):
        return jnp.sum(kernels.row_counts(p), axis=0, dtype=jnp.int32)

    np.asarray(raw_counts(ps.plane))  # compile
    family("count32", prod_count,
           median_lat(lambda: np.asarray(raw_counts(ps.plane))))

    # ---- TopN -----------------------------------------------------------
    order = np.lexsort((np.arange(N_ROWS), -want_counts))
    want_topn = [{"id": int(r), "count": int(want_counts[r])}
                 for r in order[:8]]
    got = api.query(INDEX, "TopN(f, n=8)")["results"][0]
    assert got == want_topn, f"TopN oracle mismatch: {got[:2]}"
    # unfiltered TopN on an under-budget field rides the resident dense
    # plane (one dispatch + read); the zero-device host-directory path
    # only serves over-budget fields (executor._execute_topn branch 2)
    prod_unf = median_lat(lambda: api.query(INDEX, "TopN(f, n=8)"))
    log(f"topn_unfiltered: product {prod_unf * 1e3:.1f} ms "
        "(resident dense plane, one dispatch)")
    results["topn_unfiltered"] = {"product_ms": round(prod_unf * 1e3, 1),
                                  "raw_ms": 0.0, "raw_over_product": 0.0}

    want_ftop = [{"id": r, "count": c}
                 for r, c in oracle_filtered_topn(plane, 0, 8)]
    got = api.query(INDEX, "TopN(f, n=8, filter=Row(f=0))")["results"][0]
    assert got == want_ftop, f"filtered TopN mismatch: {got[:2]}"
    prod_ftop = median_lat(
        lambda: api.query(INDEX, "TopN(f, n=8, filter=Row(f=0))"))

    @jax.jit
    def raw_ftop(p):
        flt = p[:, 0, :]
        cnt = jnp.sum(kernels.row_counts(p & flt[:, None, :]), axis=0,
                      dtype=jnp.int32)
        return jax.lax.top_k(cnt, 8)

    jax.tree.map(np.asarray, raw_ftop(ps.plane))
    family("topn_filtered", prod_ftop,
           median_lat(lambda: jax.tree.map(np.asarray,
                                           raw_ftop(ps.plane))))

    # ---- BSI aggregates -------------------------------------------------
    want_sum, want_cnt, want_gt50 = oracle_bsi()
    got = api.query(INDEX, "Sum(field=v)")["results"][0]
    assert got == {"value": want_sum, "count": want_cnt}, f"Sum: {got}"
    prod_sum = median_lat(lambda: api.query(INDEX, "Sum(field=v)"))
    vf = holder.index(INDEX).field("v")
    vps = ex.planes.bsi_plane(INDEX, vf, shards)

    # raw tier: the exact fused program the executor dispatches
    def raw_sum():
        return np.asarray(ex.fused.run_sum_batch((False,), (vps.plane,)))

    raw_sum()
    family("bsi_sum", prod_sum, median_lat(raw_sum))

    got = api.query(INDEX, "Min(field=v)")["results"][0]
    assert got["value"] == -125, f"Min: {got}"
    prod_min = median_lat(lambda: api.query(INDEX, "Min(field=v)"))
    got = api.query(INDEX, "Max(field=v)")["results"][0]
    assert got["value"] == 125, f"Max: {got}"
    log(f"bsi_min/bsi_max: product {prod_min * 1e3:.0f} ms (same "
        "one-dispatch shape as Sum; raw tier shared)")
    results["bsi_minmax"] = {"product_ms": round(prod_min * 1e3, 1)}

    got = api.query(INDEX, "Count(Row(v > 50))")["results"][0]
    assert got == want_gt50, f"Range count: {got} != {want_gt50}"
    prod_rng = median_lat(lambda: api.query(INDEX, "Count(Row(v > 50))"))
    results["bsi_range_count"] = {"product_ms": round(prod_rng * 1e3, 1)}
    log(f"bsi_range_count: product {prod_rng * 1e3:.0f} ms")

    # ---- GroupBy 4x4 at 1B cols ----------------------------------------
    want_gb = oracle_groupby(plane, range(4), range(4, 8))
    pql_gb = "GroupBy(Rows(f, limit=4), Rows(f, previous=3, limit=4))"
    got = api.query(INDEX, pql_gb)["results"][0]
    got_map = {(g["group"][0]["rowID"], g["group"][1]["rowID"]):
               g["count"] for g in got}
    assert got_map == {k: v for k, v in want_gb.items() if v}, "GroupBy"
    prod_gb = median_lat(lambda: api.query(INDEX, pql_gb), n=5)

    from pilosa_tpu.exec import groupby as gb
    specs = []
    for rows in (np.arange(4, dtype=np.uint64),
                 np.arange(4, 8, dtype=np.uint64)):
        rp = ex.planes.rows_plane(INDEX, fld, "standard", rows, shards)
        specs.append((fld, rows, rp))

    def raw_gb():
        for _combo, out in gb.iter_blocks(specs, None, None, None):
            np.asarray(out["counts"])

    raw_gb()
    family("groupby_4x4", prod_gb, median_lat(raw_gb, n=5))

    # raw tiers are done: DROP this process's plane references.  The
    # bench is an unusual client — holding ps/vps/specs pins ~6.5 GB
    # that the executor's OOM evict-and-retry cannot free, which is the
    # bench's leak, not the server's (a real server's in-flight queries
    # release their planes when they return).
    import gc
    del ps, vps, specs, rp, rows  # rp still pins the last rows_plane
    gc.collect()

    # ---- sparse filtered TopN ------------------------------------------
    want_stop = oracle_sparse_topn(plane, sparse, 0, 5)
    t0 = time.perf_counter()
    got = api.query(INDEX, "TopN(tags, n=5, filter=Row(f=0))")["results"][0]
    log(f"sparse first query (CSR residency build): "
        f"{time.perf_counter() - t0:.1f}s")
    got_pairs = [(g["id"], g["count"]) for g in got]
    assert got_pairs == want_stop, \
        f"sparse TopN: {got_pairs[:3]} != {want_stop[:3]}"
    prod_stop = median_lat(
        lambda: api.query(INDEX, "TopN(tags, n=5, filter=Row(f=0))"))
    results["sparse_topn"] = {"product_ms": round(prod_stop * 1e3, 1)}
    log(f"sparse_topn_filtered: product {prod_stop * 1e3:.0f} ms "
        "(gather-bound; BASELINE.md r2 floor analysis)")

    # ---- REST: JSON vs protobuf on the query endpoint -------------------
    import urllib.request

    from pilosa_tpu.api import proto
    from pilosa_tpu.obs.logging import get_logger

    log(f"plane cache before REST phase: {ex.planes.stats()}")
    srv = Server(api, host="127.0.0.1", port=0,
                 logger=get_logger(verbose=True))
    st = threading.Thread(target=srv.serve_forever, daemon=True)
    st.start()
    url = f"http://127.0.0.1:{srv.address[1]}/index/{INDEX}/query"

    def rest_json(pql):
        req = urllib.request.Request(url, data=pql.encode(), method="POST")
        with urllib.request.urlopen(req) as resp:
            return json.loads(resp.read())["results"]

    def rest_proto(pql):
        req = urllib.request.Request(
            url, data=proto.encode_query_request(pql), method="POST",
            headers={"Content-Type": proto.CONTENT_TYPE,
                     "Accept": proto.CONTENT_TYPE})
        with urllib.request.urlopen(req) as resp:
            return proto.decode_query_response(resp.read())["results"]

    try:
        assert rest_json(pql32) == [int(c) for c in want_counts]
        assert rest_proto(pql32) == [int(c) for c in want_counts]
        rj = median_lat(lambda: rest_json(pql32))
        rp = median_lat(lambda: rest_proto(pql32))
        results["rest_count32"] = {"json_ms": round(rj * 1e3, 1),
                                   "proto_ms": round(rp * 1e3, 1)}
        log(f"REST count32: JSON {rj * 1e3:.1f} ms, "
            f"proto {rp * 1e3:.1f} ms")
    except Exception as e:  # noqa: BLE001 — keep later families alive
        results["rest_count32"] = {"error": repr(e)}
        log(f"REST count32 FAILED: {e!r}")
    try:
        got = rest_json("TopN(f, n=8, filter=Row(f=0))")[0]
        assert got == want_ftop, "REST TopN diverged"
        tj = median_lat(
            lambda: rest_json("TopN(f, n=8, filter=Row(f=0))"))
        results["rest_topn"] = {"json_ms": round(tj * 1e3, 1)}
        log(f"REST filtered TopN (JSON): {tj * 1e3:.1f} ms")
    except Exception as e:  # noqa: BLE001
        results["rest_topn"] = {"error": repr(e)}
        log(f"REST filtered TopN FAILED: {e!r}")
    srv.close()
    holder.close()

    import shutil
    shutil.rmtree(data_dir, ignore_errors=True)

    worst = min((f["raw_over_product"] for f in results.values()
                 if f.get("raw_over_product")), default=0.0)
    print(json.dumps({
        "metric": f"product_families_worst_ratio_{platform}",
        "value": round(worst, 3), "unit": "raw/product",
        "vs_baseline": 1.0, "families": results}))


if __name__ == "__main__":
    main()
