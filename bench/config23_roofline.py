"""Config #23: per-kernel roofline harness — GB/s by kernel shape,
chain depth, donation, and multi-query width (ROADMAP item 4).

Bench rounds r01–r16 showed dispatch chains at 462–477 GB/s device
throughput (~57% of the v5e HBM spec) and a single-stream floor of
~287–300 qps — one device→host read per dispatch.  r17 attacks
both ends (donated ping-pong chains, solo fast lane, popcount-chain
layout) and this config measures every piece:

- **chain roofline**: the whole-plane ``row_counts`` program at chain
  depths 1/8/32 (N in-order dispatches, ONE final read) → GB/s per
  dispatch — plus the DONATED ping-pong variant of the same chain
  (retired outputs re-enter as donated scratch, so chained dispatches
  stop allocating);
- **per-kernel before/after** (r17 roofline chase): each tuned kernel
  kind (tiled popcount emit in the ``(rows, words)`` scan, sorted
  ascending-stride ``selected_row_counts`` gather) measured against
  its pre-r17 reference form, GB/s both sides;
- **selected-row gather** width sweep → GB/s over only the gathered
  rows' memory, oracle-checked;
- **multi-query single-stream**: ONE client issuing W-Count requests
  through the PRODUCT path — best width ≥1.5× the width-1 floor;
- **solo fast lane**: width-1 qps through the product path with the
  r17 fast lane on vs off (windowed), fast-lane engagement asserted
  via ``solo_fastlane_hits_total``.  Full scale on TPU asserts the
  acceptance bar: fast-lane solo ≥ 2× the recorded ~287–300 qps
  floor, and best chain ≥ 550 GB/s;
- **batched readback**: a mixed-kind collection window must pack into
  ONE device→host read (measured with the fast lane OFF — the proof
  pins the windowed path).

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards × 8 rows on CPU —
tier-1 runs it (tests/test_bench_smoke.py) so this bench can never
bitrot.

Prints ONE JSON line: best chain GB/s; vs_baseline = the multi-query
single-stream gain over the width-1 floor.  ``regressions`` carries
the shared headline guard plus the r17 DETAIL guard rows
(``single_stream_qps``, per-kind ``*_gbps``) so a future PR that
re-serializes readback or slides one kernel kind fails the guard even
while the headline hides it.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

SMOKE = ("--smoke" in sys.argv
         or os.environ.get("PILOSA_BENCH_SMOKE") == "1")
N_SHARDS = 2 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "954"))
N_ROWS = 8 if SMOKE else int(os.environ.get("PILOSA_BENCH_ROWS", "32"))
WORDS = 32768  # words per shard (2^20 bits / 32)
INDEX, FIELD = "i", "f"
CHAIN_DEPTHS = (1, 8, 32)
ITERS = 3 if SMOKE else 5
# the acceptance bar: best multi-query width vs the width-1 floor
MULTIQ_GAIN_BAR = 1.2 if SMOKE else 1.5
# r17 acceptance (ISSUE 12), asserted in-bench at full scale on TPU:
# the recorded solo floor (~287–300 qps, one RPC per query) must at
# least double through the fast lane, and the dispatch chain must
# close the roofline gap past 550 GB/s (from 462–477)
SOLO_FLOOR_QPS = 300.0
SOLO_GAIN_BAR = 2.0
CHAIN_GBPS_BAR = 550.0


def write_index(plane: np.ndarray, data_dir: str) -> None:
    """A REAL on-disk index from the packed plane (the config18
    recipe)."""
    from pilosa_tpu.store import Holder, roaring

    h = Holder(data_dir).open()
    idx = h.create_index(INDEX, track_existence=False)
    idx.create_field(FIELD)
    h.close()
    frag_dir = os.path.join(data_dir, INDEX, FIELD, "views", "standard",
                            "fragments")
    os.makedirs(frag_dir, exist_ok=True)
    for s in range(plane.shape[0]):
        with open(os.path.join(frag_dir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))


def chain_roofline(d, plane_bytes: int) -> dict:
    """GB/s per dispatch at each chain depth: N in-order dispatches of
    the whole-plane count program, one final read — amortizing
    enqueue/read overhead exposes the kernel's own memory throughput."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import kernels

    @jax.jit
    def count_batch(p):
        return jnp.sum(kernels.row_counts(p), axis=0, dtype=jnp.int32)

    np.asarray(count_batch(d))  # warm/compile
    out = {}
    for depth in CHAIN_DEPTHS:
        best = None
        for _ in range(ITERS):
            t0 = time.perf_counter()
            outs = [count_batch(d) for _ in range(depth)]
            np.asarray(outs[-1])
            t = (time.perf_counter() - t0) / depth
            best = t if best is None else min(best, t)
        gbps = plane_bytes / best / 1e9
        out[str(depth)] = {"ms_per_dispatch": round(best * 1e3, 3),
                           "gbps": round(gbps, 1)}
        log(f"chain depth {depth:>2}: {best * 1e3:.2f} ms/dispatch = "
            f"{gbps:.0f} GB/s (HBM spec ~819 GB/s on v5e)")
    return out


def chain_donated(d, plane_bytes: int) -> dict:
    """The same dispatch chain with DONATED ping-pong outputs: each
    dispatch hands the output buffer of two dispatches ago back as
    donated scratch, so the chain re-uses two standing output slots
    instead of allocating one per link (ping-pong keeps the buffer a
    reader might still hold out of the donation)."""
    import functools

    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import kernels

    @functools.partial(jax.jit, donate_argnums=(1,))
    def count_donated(p, scratch):
        return jnp.sum(kernels.row_counts(p), axis=0, dtype=jnp.int32)

    def fresh_pair():
        a = jax.device_put(np.zeros(N_ROWS, np.int32))
        b = jax.device_put(np.zeros(N_ROWS, np.int32))
        jax.block_until_ready((a, b))
        return [a, b]

    np.asarray(count_donated(d, fresh_pair()[0]))  # warm/compile
    out = {}
    for depth in CHAIN_DEPTHS:
        best = None
        for _ in range(ITERS):
            slots = fresh_pair()
            t0 = time.perf_counter()
            outs = list(slots)
            for i in range(depth):
                outs.append(count_donated(d, outs[i]))
            np.asarray(outs[-1])
            t = (time.perf_counter() - t0) / depth
            best = t if best is None else min(best, t)
        gbps = plane_bytes / best / 1e9
        out[str(depth)] = {"ms_per_dispatch": round(best * 1e3, 3),
                           "gbps": round(gbps, 1)}
        log(f"donated chain n={depth:>2}: {best * 1e3:.2f} ms/dispatch "
            f"= {gbps:.0f} GB/s")
    return out


def kernel_kinds_before_after(d, oracle: np.ndarray) -> dict:
    """The r17 roofline chase receipts: each tuned kernel kind vs its
    pre-r17 reference form, GB/s both sides, answers oracle-checked.

    - ``rowcounts``: flat single-pass popcount reduce (before) vs the
      tiled two-stage emit (after) over the whole (rows, words) scan;
    - ``selected_gather``: request-order gather + flat reduce (before)
      vs sorted ascending-stride gather + tiled reduce (after).
    """
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.engine import kernels

    def timed(fn, *args, nbytes: int) -> float:
        np.asarray(fn(*args))  # warm/compile
        best = None
        for _ in range(ITERS):
            t0 = time.perf_counter()
            np.asarray(fn(*args))
            t = time.perf_counter() - t0
            best = t if best is None else min(best, t)
        return nbytes / best / 1e9

    out = {}

    @jax.jit
    def rows_before(p):
        return jnp.sum(kernels.count_ref(p), axis=0, dtype=jnp.int32)

    @jax.jit
    def rows_after(p):
        return jnp.sum(kernels.count(p), axis=0, dtype=jnp.int32)

    got = np.asarray(rows_after(d)).astype(np.int64)
    np.testing.assert_array_equal(got, oracle)
    plane_bytes = N_SHARDS * N_ROWS * WORDS * 4
    out["rowcounts"] = {
        "before_gbps": round(timed(rows_before, d,
                                   nbytes=plane_bytes), 2),
        "after_gbps": round(timed(rows_after, d,
                                  nbytes=plane_bytes), 2)}

    width = max(2, N_ROWS // 2)
    rng = np.random.default_rng(5)
    sel = np.sort(rng.choice(N_ROWS, size=width, replace=False))
    permuted = jnp.asarray(rng.permutation(sel).astype(np.int32))
    sorted_idx = jnp.asarray(sel.astype(np.int32))

    @jax.jit
    def sel_before(p, ix):
        return jnp.sum(kernels.count_ref(jnp.take(p, ix, axis=-2)),
                       axis=0, dtype=jnp.int32)

    @jax.jit
    def sel_after(p, ix):
        return jnp.sum(kernels.selected_row_counts(p, ix,
                                                   sorted_idx=True),
                       axis=0, dtype=jnp.int32)

    got = np.asarray(sel_after(d, sorted_idx)).astype(np.int64)
    np.testing.assert_array_equal(got, oracle[sel])
    sel_bytes = N_SHARDS * width * WORDS * 4
    out["selected_gather"] = {
        "before_gbps": round(timed(sel_before, d, permuted,
                                   nbytes=sel_bytes), 2),
        "after_gbps": round(timed(sel_after, d, sorted_idx,
                                  nbytes=sel_bytes), 2)}
    for kind, v in out.items():
        log(f"kind {kind}: {v['before_gbps']} -> {v['after_gbps']} "
            f"GB/s (before -> after)")
    return out


def solo_lane(api, executor, stats, oracle: np.ndarray) -> dict:
    """Width-1 product-path single-stream qps with the r17 solo fast
    lane ON vs OFF — the head-on attack on the one-RPC-per-query
    floor.  Fast-lane engagement is asserted via its counter, answers
    via the oracle on every request."""
    batcher = executor.batcher
    assert batcher is not None, "solo lane needs the batcher on"
    pql = f"Count(Row({FIELD}=0))"
    want = [int(oracle[0])]

    def measure(seconds: float) -> float:
        n = 0
        stop = time.monotonic() + seconds
        while time.monotonic() < stop:
            if api.query(INDEX, pql)["results"] != want:
                raise AssertionError("solo count diverges from oracle")
            n += 1
        return n / seconds

    def hits() -> int:
        return int(sum(stats.snapshot()["counters"]
                       .get("solo_fastlane_hits_total", {}).values()))

    window = 1.0 if SMOKE else 5.0
    measure(window / 4)  # warm both paths' programs
    before = hits()
    fast_qps = measure(window)
    assert hits() > before, "solo fast lane never engaged"
    batcher.solo_fastlane = False
    try:
        windowed_qps = measure(window)
    finally:
        batcher.solo_fastlane = True
    gain = fast_qps / max(1e-9, windowed_qps)
    log(f"solo lane: {fast_qps:,.1f} qps fast lane vs "
        f"{windowed_qps:,.1f} qps windowed ({gain:.2f}x); "
        f"vs recorded floor {SOLO_FLOOR_QPS:.0f} qps: "
        f"{fast_qps / SOLO_FLOOR_QPS:.2f}x")
    return {"fastlane_qps": round(fast_qps, 1),
            "windowed_qps": round(windowed_qps, 1),
            "gain": round(gain, 3),
            "vs_recorded_floor": round(fast_qps / SOLO_FLOOR_QPS, 3)}


def selected_roofline(d, oracle: np.ndarray) -> dict:
    """The multi-query fused popcount at each width: GB/s over ONLY the
    gathered rows' memory (the whole point — a W-row ask stops paying
    the full plane scan), every width verified against the numpy
    oracle."""
    from pilosa_tpu.exec.fused import FusedCache

    fused = FusedCache()
    widths, w = [], 1
    while w <= N_ROWS:
        widths.append(w)
        w *= 2
    out = {}
    for width in widths:
        slots = tuple(range(width))
        got = np.asarray(
            fused.run_selected_counts(d, slots)).astype(np.int64)[:width]
        np.testing.assert_array_equal(got, oracle[:width])
        nbytes = N_SHARDS * width * WORDS * 4
        best = None
        for _ in range(ITERS):
            t0 = time.perf_counter()
            np.asarray(fused.run_selected_counts(d, slots))
            t = time.perf_counter() - t0
            best = t if best is None else min(best, t)
        out[str(width)] = {"ms": round(best * 1e3, 3),
                           "gbps": round(nbytes / best / 1e9, 2),
                           "qps": round(width / best, 1)}
        log(f"selected width {width:>3}: {best * 1e3:.2f} ms = "
            f"{nbytes / best / 1e9:.1f} GB/s over the gathered rows "
            f"({width / best:,.0f} qps single-stream)")
    return out


def multiquery_single_stream(api, oracle: np.ndarray) -> dict:
    """ONE client, W Counts per request, through the product path: W
    answers per host read.  This is the attack on the ~290 qps
    one-read-per-dispatch floor — qps scales with width until the scan
    itself dominates."""
    out = {}
    widths, w = [], 1
    while w <= N_ROWS:
        widths.append(w)
        w *= 2
    for width in widths:
        pql = "".join(f"Count(Row({FIELD}={r}))" for r in range(width))
        want = [int(c) for c in oracle[:width]]
        assert api.query(INDEX, pql)["results"] == want, \
            f"width {width}: product counts diverge from oracle"
        lat = []
        for _ in range(max(ITERS, 3)):
            t0 = time.perf_counter()
            if api.query(INDEX, pql)["results"] != want:
                raise AssertionError(f"width {width}: count mismatch")
            lat.append(time.perf_counter() - t0)
        p50 = float(np.median(lat))
        out[str(width)] = {"ms_per_request": round(p50 * 1e3, 3),
                           "qps": round(width / p50, 1)}
        log(f"multi-query width {width:>3}: {p50 * 1e3:.2f} ms/request "
            f"= {width / p50:,.1f} qps single-stream")
    return out


def readback_pack_proof(executor, ps, stats, oracle: np.ndarray) -> dict:
    """Land a mixed-kind window (selected counts + whole-plane
    rowcounts) in the batcher and assert the whole window came back in
    ONE packed device→host read — with BOTH groups' answers checked
    against the oracle, pinning the cross-group slice offsets."""
    batcher = executor.batcher
    assert batcher is not None, "batcher must be on for the readback proof"
    before = sum(stats.snapshot()["counters"]
                 .get("batcher_readback_packed", {}).values())
    packed = 0
    for _ in range(20):  # the threads must land in ONE window; retry
        barrier = threading.Barrier(3)
        errs = []

        def sel():
            try:
                barrier.wait()
                got = np.asarray(batcher.submit_selected(ps.plane, (0, 1)))
                np.testing.assert_array_equal(got, oracle[[0, 1]])
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        def rows():
            try:
                barrier.wait()
                got = np.asarray(batcher.submit_rowcounts(ps.plane))
                np.testing.assert_array_equal(got[:N_ROWS], oracle)
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        ts = [threading.Thread(target=sel), threading.Thread(target=rows)]
        for t in ts:
            t.start()
        barrier.wait()
        for t in ts:
            t.join()
        assert not errs, errs
        packed = sum(stats.snapshot()["counters"]
                     .get("batcher_readback_packed", {}).values()) - before
        if packed >= 1:
            break
    assert packed >= 1, \
        "mixed-kind window never packed into one readback"
    groups = sum(stats.snapshot()["counters"]
                 .get("batcher_readback_groups", {}).values())
    log(f"batched readback: {packed} packed window(s), "
        f"{groups} groups served by single reads")
    return {"packed_windows": packed, "groups_packed": groups}


def main() -> None:
    import jax

    from pilosa_tpu.api import API
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder
    from pilosa_tpu.store.view import VIEW_STANDARD

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(42)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    oracle = (np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)
              if hasattr(np, "bitwise_count") else
              np.array([int(np.unpackbits(
                  plane[:, r].reshape(-1).view(np.uint8)).sum())
                  for r in range(N_ROWS)], dtype=np.int64))
    log(f"plane: {plane.nbytes / 1e9:.2f} GB, {N_ROWS} rows x "
        f"{N_SHARDS} shards on {platform}")

    d = jax.device_put(plane)
    jax.block_until_ready(d)
    chain = chain_roofline(d, plane.nbytes)
    donated = chain_donated(d, plane.nbytes)
    kinds = kernel_kinds_before_after(d, oracle)
    selected = selected_roofline(d, oracle)
    del d

    data_dir = tempfile.mkdtemp(prefix="pilosa_c23_")
    try:
        write_index(plane, data_dir)
        del plane
        holder = Holder(data_dir).open()
        stats = Stats()
        executor = Executor(holder, stats=stats)
        api = API(holder, executor)
        # warm: plane residency + plan cache before the timed sweeps
        warm_pql = "".join(f"Count(Row({FIELD}={r}))"
                           for r in range(N_ROWS))
        t0 = time.perf_counter()
        assert api.query(INDEX, warm_pql)["results"] == \
            [int(c) for c in oracle]
        log(f"first product query (plane build + compile): "
            f"{time.perf_counter() - t0:.1f}s")
        # the width sweep measures the WINDOWED floor-amortization
        # curve (W answers per host read) — the fast lane would move
        # the width-1 floor the gain bar and round-over-round
        # vs_baseline are computed against; solo_lane below measures
        # the lane explicitly, against that same windowed floor
        executor.batcher.solo_fastlane = False
        try:
            multiq = multiquery_single_stream(api, oracle)
        finally:
            executor.batcher.solo_fastlane = True
        solo = solo_lane(api, executor, stats, oracle)
        idx = holder.index(INDEX)
        fld = idx.field(FIELD)
        shards = tuple(idx.available_shards())
        ps = executor.planes.field_plane(INDEX, fld, VIEW_STANDARD, shards)
        # the pack proof pins the WINDOWED path: the fast lane would
        # peel one of the two concurrent items out of the window
        executor.batcher.solo_fastlane = False
        try:
            readback = readback_pack_proof(executor, ps, stats, oracle)
        finally:
            executor.batcher.solo_fastlane = True
        holder.close()
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    floor_qps = multiq["1"]["qps"]
    best_width = max(multiq, key=lambda k: multiq[k]["qps"])
    best_qps = multiq[best_width]["qps"]
    gain = best_qps / floor_qps
    log(f"multi-query gain: width {best_width} serves {best_qps:,.1f} "
        f"qps single-stream = {gain:.2f}x the width-1 floor "
        f"({floor_qps:,.1f} qps)")
    assert gain >= MULTIQ_GAIN_BAR, \
        (f"multi-query width {best_width} gains only {gain:.2f}x over "
         f"the one-RPC-per-query floor; the bar is {MULTIQ_GAIN_BAR}x")

    best_gbps = max(v["gbps"] for vs in (chain, donated)
                    for v in vs.values())
    # r17 acceptance bars, asserted in-bench at full scale on the
    # real device (CPU smoke measures dispatch overhead, not HBM)
    if not SMOKE and platform == "tpu":
        assert solo["fastlane_qps"] >= SOLO_GAIN_BAR * SOLO_FLOOR_QPS, \
            (f"solo fast lane serves {solo['fastlane_qps']:,.1f} qps; "
             f"the bar is {SOLO_GAIN_BAR}x the recorded "
             f"{SOLO_FLOOR_QPS:.0f} qps floor")
        assert best_gbps >= CHAIN_GBPS_BAR, \
            (f"best dispatch chain {best_gbps:.0f} GB/s under the "
             f"{CHAIN_GBPS_BAR:.0f} GB/s bar")

    metric = f"kernel_roofline_gbps_{platform}"
    detail = {"chain": chain, "chain_donated": donated,
              "kinds": kinds, "selected": selected,
              "multiquery_single_stream": multiq,
              "multiquery_gain": round(gain, 3),
              "solo": solo,
              "readback": readback}
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_headline",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # headline + r17 detail guard: the solo floor and each kernel
    # kind's GB/s are tracked round over round, so re-serializing
    # readback or sliding one kind fails the guard even while the
    # best-chain headline hides it
    regressions = (
        mod.regression_guard(metric, best_gbps)
        + mod.detail_regression_guard(metric, detail, {
            "single_stream_qps": ("solo", "fastlane_qps"),
            "kernel_bandwidth_gbps_rowcounts":
                ("kinds", "rowcounts", "after_gbps"),
            "kernel_bandwidth_gbps_selected":
                ("kinds", "selected_gather", "after_gbps"),
            "chain32_gbps": ("chain", "32", "gbps"),
        }))
    print(json.dumps({
        "metric": metric,
        "value": round(best_gbps, 1), "unit": "GBps",
        "vs_baseline": round(gain, 3),
        "regressions": regressions,
        "detail": detail}))


if __name__ == "__main__":
    main()
