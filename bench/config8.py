"""Config #8 (extra): TopN over a HIGH-ROW-CARDINALITY field — the
SURVEY.md §8 "dense blowup" case.

Part A — 5M distinct rows, ~20M bits, one shard.  Dense plane would be
~1TB (8M-row bucket × 128KB); the container-blocked sparse residency
(engine/sparse.py) is ~384MB: built once from the mmap'd snapshot blob,
cached in HBM, every filtered TopN is ONE gather+segment-sum program.
The field is bulk-loaded as a roaring snapshot and cold-opened lazily —
no per-row host objects anywhere on the path.

Part B — 200k rows, where round 1's per-query row-block streaming
fallback is actually runnable: sparse-resident vs streaming, same query,
measured speedup.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import emit, log


def build_snapshot_field(data_dir, index, fname, positions, g_cols=None):
    """Create index/field and drop a pre-serialized roaring snapshot in
    place (the ImportRoaring-style bulk load), then reopen lazily."""
    from pilosa_tpu.store import Holder, roaring

    h = Holder(data_dir).open()
    idx = (h.index(index) or h.create_index(index, track_existence=False))
    f = idx.create_field(fname)
    f.import_bits(np.array([0], np.uint64), np.array([0], np.uint64))
    if g_cols is not None:
        idx.create_field("g").import_bits(
            np.ones(len(g_cols), np.uint64), g_cols)
        idx.note_columns(g_cols)
    h.close()
    frag_path = os.path.join(data_dir, index, fname, "views", "standard",
                             "fragments", "0")
    blob = roaring.serialize(positions)
    with open(frag_path, "wb") as fh:
        fh.write(blob)
    oplog = frag_path + ".oplog"
    if os.path.exists(oplog):
        os.remove(oplog)
    return len(blob)


def main():
    import tempfile

    import jax

    from pilosa_tpu.engine.words import SHARD_WIDTH
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(8)
    platform = jax.devices()[0].platform

    # ---- Part A: 5M distinct rows ------------------------------------
    n_rows, bits_per_row = 5_000_000, 4
    rows = np.repeat(np.arange(n_rows, dtype=np.uint64), bits_per_row)
    cols = rng.integers(0, SHARD_WIDTH, size=len(rows)).astype(np.uint64)
    positions = np.unique(rows * np.uint64(SHARD_WIDTH) + cols)
    g_cols = rng.choice(SHARD_WIDTH, size=200_000, replace=False).astype(
        np.uint64)

    d = tempfile.mkdtemp()
    t0 = time.perf_counter()
    blob_len = build_snapshot_field(d, "big", "f", positions, g_cols)
    log(f"A: built {len(positions) / 1e6:.1f}M-bit snapshot "
        f"({blob_len / 1e6:.0f} MB) in {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    h = Holder(d).open()
    ex = Executor(h)  # default 4GB budget: dense ~1TB is out, sparse fits
    t_open = time.perf_counter() - t0

    pql = "TopN(f, filter=Row(g=1), n=10)"
    t0 = time.perf_counter()
    (first,) = ex.execute("big", pql)
    t_first = time.perf_counter() - t0  # sparse build + compile + query
    t0 = time.perf_counter()
    for _ in range(5):
        (res,) = ex.execute("big", pql)
    t_warm = (time.perf_counter() - t0) / 5
    log(f"A: cold open {t_open * 1e3:.0f} ms; first TopN "
        f"{t_first:.1f}s (builds sparse residency); warm TopN "
        f"{t_warm * 1e3:.0f} ms over {n_rows / 1e6:.0f}M rows")

    # numpy oracle on the filtered counts
    fmask = np.zeros(SHARD_WIDTH, bool)
    fmask[g_cols] = True
    o_rows = (positions // SHARD_WIDTH).astype(np.int64)
    o_cols = (positions % SHARD_WIDTH).astype(np.int64)
    o_counts = np.bincount(o_rows[fmask[o_cols]], minlength=n_rows)
    top_counts = np.sort(o_counts)[::-1][:10]
    got_counts = np.array(sorted((p.count for p in res.pairs),
                                 reverse=True))
    assert list(got_counts) == list(top_counts), \
        (list(got_counts), list(top_counts))
    for p in res.pairs:  # every returned id's count must be exact
        assert o_counts[p.id] == p.count, (p.id, p.count)
    log("A: oracle verified")

    # ---- Part B: sparse vs per-query streaming (200k rows) -----------
    n_rows_b = 200_000
    rows_b = np.repeat(np.arange(n_rows_b, dtype=np.uint64), 4)
    cols_b = rng.integers(0, SHARD_WIDTH, size=len(rows_b)).astype(np.uint64)
    pos_b = np.unique(rows_b * np.uint64(SHARD_WIDTH) + cols_b)
    d2 = tempfile.mkdtemp()
    build_snapshot_field(d2, "mid", "f", pos_b, g_cols)
    h2 = Holder(d2).open()
    # sparse: bits×12 ≈ 10MB fits a 64MB budget; dense 256k-row plane
    # (32GB) does not
    sparse_ex = Executor(h2, plane_budget=64 << 20)
    # streaming: budget below the sparse footprint forces the fallback
    stream_ex = Executor(h2, plane_budget=4 << 20)

    sparse_ex.execute("mid", pql)
    t0 = time.perf_counter()
    (a,) = sparse_ex.execute("mid", pql)
    t_sparse = time.perf_counter() - t0
    if platform == "cpu":
        stream_ex.execute("mid", pql)
        t0 = time.perf_counter()
        (b,) = stream_ex.execute("mid", pql)
        t_stream = time.perf_counter() - t0
        assert [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]
        how = "measured"
    else:
        # full streaming is thousands of chunk round trips (the very
        # failure mode sparse residency removes):
        # time 3 chunks, extrapolate, label as such
        import math

        from pilosa_tpu.engine import kernels
        f_mid = h2.index("mid").field("f")
        fw = np.zeros((1, 32768), np.uint32)
        for c in g_cols:
            fw[0, int(c) >> 5] |= np.uint32(1) << np.uint32(int(c) & 31)
        dfw = jax.device_put(fw)
        block = 64
        n_chunks = 0
        t0 = time.perf_counter()
        for chunk_rows, chunk_plane in stream_ex.planes.iter_row_blocks(
                f_mid, "standard", (0,), block):
            np.asarray(kernels.row_counts(chunk_plane, dfw))
            n_chunks += 1
            if n_chunks == 3:
                break
        per_chunk = (time.perf_counter() - t0) / n_chunks
        total_chunks = math.ceil(n_rows_b / block)
        t_stream = per_chunk * total_chunks
        how = f"extrapolated from {n_chunks} of {total_chunks} chunks"
    log(f"B: warm TopN @ 200k rows — sparse {t_sparse * 1e3:.0f} ms vs "
        f"streaming {t_stream * 1e3:.0f} ms ({how}; "
        f"{t_stream / t_sparse:.1f}x)")

    # ---- Part C: mesh-sharded sparse residency -----------------------
    # The r2 gather floor (~50M gathers/s single-chip) divides by the
    # device count under the device-blocked CSR layout: each chip
    # gathers only its shard-local bits and counts merge with one psum.
    # Virtual CPU devices share this host's one core, so wall-clock is
    # not a scaling proxy (see config5's r2 retraction) — this part
    # proves EXACTNESS at every mesh width and reports the per-device
    # gather volume, which is the quantity the floor divides by.
    if jax.device_count() >= 2:
        from pilosa_tpu.parallel import MeshPlacement

        n_shards_c, n_rows_c = 8, 100_000
        rows_c = np.repeat(np.arange(n_rows_c, dtype=np.uint64), 8)
        cols_c = rng.integers(0, n_shards_c * SHARD_WIDTH,
                              size=len(rows_c)).astype(np.uint64)
        d3 = tempfile.mkdtemp()
        h3 = Holder(d3).open()
        idx3 = h3.create_index("wide", track_existence=False)
        idx3.create_field("f")
        idx3.create_field("g")
        idx3.field("f").import_bits(rows_c, cols_c)
        gc = np.unique(rng.choice(n_shards_c * SHARD_WIDTH, size=400_000,
                                  replace=False).astype(np.uint64))
        idx3.field("g").import_bits(np.ones(len(gc), np.uint64), gc)
        idx3.note_columns(cols_c)

        flat_ex = Executor(h3, plane_budget=64 << 20)
        (want_c,) = flat_ex.execute("wide", pql)
        canon = lambda pairs: sorted(((p.count, p.id) for p in pairs),
                                     key=lambda t: (-t[0], t[1]))
        flat_ss = [v[1] for k, v in flat_ex.planes._entries.items()
                   if k[0] == "sparse"][0]
        flat_bits = int(flat_ss.word_idx.shape[-1])
        for ndev in (2, 4, 8):
            if jax.device_count() < ndev:
                continue
            mex = Executor(h3, plane_budget=64 << 20,
                           placement=MeshPlacement(jax.devices()[:ndev]))
            (got_c,) = mex.execute("wide", pql)
            assert canon(got_c.pairs) == canon(want_c.pairs), ndev
            ss = [v[1] for k, v in mex.planes._entries.items()
                  if k[0] == "sparse"][0]
            per_dev = int(ss.word_idx.shape[-1])
            log(f"C: mesh x{ndev}: exact; per-device gather volume "
                f"{per_dev / 1e3:.0f}k bits vs {flat_bits / 1e3:.0f}k "
                f"flat ({flat_bits / per_dev:.1f}x less per chip)")
    else:
        log("C: mesh-sharded sparse skipped (single device; run under "
            "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8 for the simulated-mesh leg)")

    emit(f"sparse_topn_warm_ms_5m_rows_{platform}", t_warm * 1e3, "ms",
         t_stream / t_sparse)


if __name__ == "__main__":
    main()
