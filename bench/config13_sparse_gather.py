"""Config #13: attack the sparse-gather floor (VERDICT r3 #6).

The sparse filtered-TopN path is bound by one op: for E sparse entries,
gather ``filter_words[word_idx[e]]`` then popcount(mask & word) —
measured ~50M gathered words/s on the v5e regardless of table size
(BASELINE.md r2), with the floor claim resting on the pallas guide's
"no arbitrary per-lane VMEM gather" note rather than on measured
alternatives.  This config records actual numbers for the candidate
formulations:

  1. flat gather, VMEM-sized table (32 KB) vs HBM-sized table (128 MB)
     — is the floor residency-dependent at all?
  2. sorted vs random indices — does XLA's TPU gather exploit locality?
  3. two-level container-bucketed gather: table reshaped [B, 8192],
     entries pre-bucketed by block (host-side, amortized into the CSR
     build), per-block take_along_axis — each block's sub-table is
     VMEM-sized by construction
  4. one-hot matmul membership (int8): chunked onehot(idx) @ bit-matrix
     rides the MXU instead of the gather unit — FLOP-rich but
     gather-free
  5. (reference point) the fused production kernel
     ``engine.sparse.sparse_row_counts`` at the same E

Every variant is verified against numpy before timing.  Output: one
JSON line with words/s per variant; the best wins a follow-up
integration, or the numbers close the floor claim empirically."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

E = int(os.environ.get("SPARSE_E", str(4 << 20)))  # entries to gather
BLK = 8192  # words per block in the two-level form


def bench(fn, *args, n=5, chain=8):
    """(result, read-inclusive median s, chained per-dispatch s).

    The chained figure enqueues ``chain`` dispatches and reads once —
    the device executes the queue in order, so total/chain isolates
    kernel time from the fixed per-read cost (the same roofline
    technique as bench.py)."""
    import jax
    out = jax.tree.map(np.asarray, fn(*args))  # compile + warm
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.tree.map(np.asarray, fn(*args))
        lat.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(chain)]
    jax.tree.map(np.asarray, outs[-1])
    per_dispatch = (time.perf_counter() - t0) / chain
    return out, float(np.median(lat)), per_dispatch


def main():
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(13)
    results = {}

    def record(name, secs, chained, e=E):
        rate = e / chained  # kernel rate from the chained form
        results[name] = round(rate / 1e6, 1)
        log(f"{name}: {secs * 1e3:.1f} ms read-incl / "
            f"{chained * 1e3:.1f} ms chained for {e / 1e6:.0f}M entries "
            f"-> {rate / 1e6:.1f}M words/s kernel rate")

    # ---- 1. flat gather: VMEM-size vs HBM-size tables -------------------
    for label, n_words in (("flat_gather_32KB_table", 8192),
                           ("flat_gather_128MB_table", 32 << 20)):
        table = rng.integers(0, 1 << 32, size=n_words, dtype=np.uint32)
        idx = rng.integers(0, n_words, size=E, dtype=np.int32)
        d_t, d_i = jax.device_put(table), jax.device_put(idx)

        @jax.jit
        def flat(t, i):
            return jnp.sum(
                jnp.bitwise_count(jnp.take(t, i)).astype(jnp.int32),
                dtype=jnp.int32)

        out, secs, ch = bench(flat, d_t, d_i)
        want = int(np.bitwise_count(table[idx]).sum(dtype=np.int64))
        assert int(out) == want, label
        record(label, secs, ch)

        if n_words == 32 << 20:
            # ---- 2. sorted indices on the HBM-sized table --------------
            sidx = np.sort(idx)
            out, secs, ch = bench(flat, d_t, jax.device_put(sidx))
            assert int(out) == want
            record("flat_gather_128MB_sorted", secs, ch)

            # ---- 3. two-level container-bucketed gather ----------------
            # host-side bucketing (amortized into the CSR build in the
            # real path): entries grouped by block, padded to the max
            # block population (pad entries point at word 0 with mask 0)
            blocks = n_words // BLK
            blk_of = sidx // BLK
            loc_of = (sidx % BLK).astype(np.int32)
            counts = np.bincount(blk_of, minlength=blocks)
            width = int(counts.max())
            loc_mat = np.zeros((blocks, width), np.int32)
            valid = np.zeros((blocks, width), bool)
            pos_in_blk = np.arange(E) - np.repeat(
                np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
            loc_mat[blk_of, pos_in_blk] = loc_of
            valid[blk_of, pos_in_blk] = True
            t2 = jax.device_put(table.reshape(blocks, BLK))
            d_loc = jax.device_put(loc_mat)
            d_val = jax.device_put(valid)

            @jax.jit
            def two_level(t, loc, val):
                g = jnp.take_along_axis(t, loc, axis=1)
                return jnp.sum(
                    jnp.bitwise_count(g).astype(jnp.int32)
                    * val.astype(jnp.int32), dtype=jnp.int32)

            out, secs, ch = bench(two_level, t2, d_loc, d_val)
            assert int(out) == want, "two-level mismatch"
            record(f"two_level_{BLK}w_blocks_pad{width}", secs, ch,
                   e=E)  # rate in REAL entries; padding overhead inside
            log(f"  (two-level padding: {blocks}x{width} slots for "
                f"{E} entries = {blocks * width / E:.2f}x work)")

    # ---- 4. one-hot matmul membership (int8, chunked) -------------------
    n_words = 8192
    table = rng.integers(0, 1 << 32, size=n_words, dtype=np.uint32)
    idx = rng.integers(0, n_words, size=E, dtype=np.int32)
    # bits of the table as an int8 matrix [n_words, 32]
    tbits = ((table[:, None] >> np.arange(32, dtype=np.uint32)) & 1
             ).astype(np.int8)
    d_tb = jax.device_put(tbits)
    d_i = jax.device_put(idx)
    CH = 1 << 15

    @jax.jit
    def onehot_mm(tb, i):
        def chunk(carry, ic):
            oh = jax.nn.one_hot(ic, n_words, dtype=jnp.int8)
            bits = jax.lax.dot_general(
                oh, tb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            return carry + jnp.sum(bits, dtype=jnp.int32), None

        total, _ = jax.lax.scan(chunk, jnp.int32(0),
                                i.reshape(E // CH, CH))
        return total

    out, secs, ch = bench(onehot_mm, d_tb, d_i)
    want = int(np.bitwise_count(table[idx]).sum(dtype=np.int64))
    assert int(out) == want, "one-hot mismatch"
    record("onehot_matmul_int8_32KB_table", secs, ch)

    # ---- 5. the fused production kernel at the same E -------------------
    from pilosa_tpu.engine import sparse as sp

    n_rows = 1 << 20
    n_words = 32768
    fw = rng.integers(0, 1 << 32, size=n_words, dtype=np.uint32)
    word_idx = np.sort(rng.integers(0, n_words, size=E).astype(np.int32))
    masks = rng.integers(1, 1 << 32, size=E, dtype=np.uint32)
    rows = np.sort(rng.integers(0, n_rows, size=E).astype(np.int32))
    row_ptr = np.searchsorted(rows, np.arange(n_rows + 1),
                              side="left").astype(np.int32)
    d = [jax.device_put(x) for x in (fw, word_idx, masks, row_ptr)]

    @jax.jit
    def prod(fw_, wi, mk, rp):
        return sp.sparse_row_counts(fw_, wi, mk, rp)

    out, secs, ch = bench(prod, *d)
    # production entries are single-bit memberships: hit iff the
    # gathered filter word intersects the entry mask (engine.sparse)
    cnt_oracle = np.bincount(
        rows, weights=((fw[word_idx] & masks) != 0).astype(np.int64),
        minlength=n_rows).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64)[:n_rows],
                                  cnt_oracle)
    record("production_sparse_row_counts", secs, ch)

    # ---- 5b. production-kernel breakdown --------------------------------
    # where does sparse_row_counts lose vs the bare gather?  time its
    # stages in isolation: (a) gather+mask-test only, (b) cumsum of a
    # precomputed hits vector + boundary diff, (c) segment-sum form.
    d_fw, d_wi, d_mk, d_rp = d

    @jax.jit
    def stage_gather(fw_, wi, mk):
        hits = (jnp.bitwise_and(jnp.take(fw_, wi), mk) != 0)
        return jnp.sum(hits.astype(jnp.int32), dtype=jnp.int32)

    _, secs, ch = bench(stage_gather, d_fw, d_wi, d_mk)
    record("stage_gather_masktest_only", secs, ch)

    hits_host = ((fw[word_idx] & masks) != 0).astype(np.int32)
    d_hits = jax.device_put(hits_host)

    @jax.jit
    def stage_cumsum(h, rp):
        cum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(h, dtype=jnp.int32)])
        return cum[rp[1:]] - cum[rp[:-1]]

    _, secs, ch = bench(stage_cumsum, d_hits, d_rp)
    record("stage_cumsum_boundary_only", secs, ch)

    row_of = jax.device_put(rows)

    @jax.jit
    def seg_sum(fw_, wi, mk, ro):
        hits = (jnp.bitwise_and(jnp.take(fw_, wi), mk) != 0)
        return jax.ops.segment_sum(hits.astype(jnp.int32), ro,
                                   num_segments=n_rows)

    out, secs, ch = bench(seg_sum, d_fw, d_wi, d_mk, row_of)
    np.testing.assert_array_equal(
        np.asarray(out).astype(np.int64),
        np.bincount(rows, weights=hits_host,
                    minlength=n_rows).astype(np.int64))
    record("stage_segment_sum_form", secs, ch)

    # ---- 5c. 2D lane-parallel prefix: cumsum(hits) reformulated as a
    # [R, C] row-wise scan (parallel over R sublanes) + a short scan of
    # R block totals + boundary reconstruction.  The 1D cumsum over E
    # elements is the production kernel's loss vs the bare gather.
    C2 = 2048
    R2 = E // C2

    @jax.jit
    def prod_v2(fw_, wi, mk, rp):
        hits = (jnp.bitwise_and(jnp.take(fw_, wi), mk)
                != 0).astype(jnp.int32)
        h2 = hits.reshape(R2, C2)
        intra = jnp.cumsum(h2, axis=1)              # parallel over rows
        block = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(intra[:, -1], dtype=jnp.int32)])
        # prefix[p] = block[p // C2] + intra[p // C2, p % C2 - 1]
        def prefix(p):  # p int32[...] in [0, E]
            pm1 = p - 1
            blk = pm1 // C2
            off = pm1 % C2
            intra_v = jnp.where(
                p > 0, intra[jnp.maximum(blk, 0), off], 0)
            return jnp.where(p > 0, block[jnp.maximum(blk, 0)], 0) \
                + intra_v
        return prefix(rp[1:]) - prefix(rp[:-1])

    out, secs, ch = bench(prod_v2, *d)
    np.testing.assert_array_equal(
        np.asarray(out).astype(np.int64),
        np.bincount(rows, weights=hits_host,
                    minlength=n_rows).astype(np.int64))
    record("prod_v2_2d_prefix", secs, ch)

    best = max(results, key=results.get)
    log(f"best: {best} at {results[best]}M words/s")
    print(json.dumps({
        "metric": f"sparse_gather_best_mwords_s_{platform}",
        "value": results[best], "unit": "Mwords/s",
        "vs_baseline": round(results[best] / 50.0, 2),
        "variants": results}))


if __name__ == "__main__":
    main()
