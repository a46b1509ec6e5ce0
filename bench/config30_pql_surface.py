"""Config #30: FULL PQL SURFACE AT DEVICE SPEED (r20, ISSUE 15).

ROADMAP item 2's acceptance numbers: per-shape qps + GB/s for the
whole serving surface — Count, BSI Range-count, Sum, Min, Max,
GroupBy, TopN — through the product path (batcher windows, fused
per-plane programs, packed readback), plus a MIXED-shape phase under
sustained BSI ingest proving the r20 contracts as hard assertions:

  - answers oracle-exact for every shape, live and quiesced;
  - ZERO base-plane rebuilds while values stream in (the BSI overlay
    absorbs every write batch: ``absorbs`` must move);
  - concurrent same-plane aggregates CO-BATCH (``bsi_batch_hits_total``
    > 0 — the window-fill proof).

Phases (in-process executor, W worker threads per phase):

  S  per-shape     W workers hammer one shape for WINDOW seconds →
                   qps + GB/s (kernel_bytes_scanned_total delta /
                   wall) per shape, oracle-checked per read
  M  mixed+ingest  all shapes round-robin across workers while
                   writers stream import_values batches into the SAME
                   BSI field; live reads assert monotone floors, a
                   quiesced pass asserts exactness against the acked
                   value map

Headline ``value`` = aggregate mixed-phase qps.  Detail carries the
per-shape table the README references and rides the shared
detail-regression guard (per-shape qps tracked round over round).

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards, short windows —
tier-1 runs it (tests/test_bench_smoke.py): exactness, zero-rebuild,
absorb and co-batch assertions are pinned on every run (qps itself is
reported but not gated at smoke scale — CPU noise).

Prints ONE JSON line (same shape as bench.py) plus the shared
regression-guard verdicts for this metric.
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
N_SHARDS = 2 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "8"))
N_SEG_ROWS = 4
N_VALUED = 64            # columns carrying a BSI value per shard
WORKERS = 4 if SMOKE else 8
WRITERS = 1 if SMOKE else 2
WINDOW = 1.0 if SMOKE else 6.0
BATCH = 16               # values per import batch
INDEX = "pqlsurface"

SHAPES = ("count", "range", "sum", "min", "max", "groupby", "topn")


def regression_guards(metric: str, value: float, detail: dict) -> list:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.regression_guard(metric, value)
    tracked = {f"pql_surface_qps_{s}": ("shapes", s, "qps")
               for s in SHAPES}
    out += mod.detail_regression_guard(metric, detail, tracked)
    return out


class Truth:
    """The python oracle: seg row membership + the BSI value map.
    Static during phase S; during phase M writers OVERWRITE a bounded
    column window with strictly positive values (steady-state ingest:
    the overlay's touched-column set — and with it the compiled
    program bucket — stabilizes after the first cycle), so the acked
    map mutates under ``lock`` while the live floors (non-null count,
    count of values > 0) stay monotone."""

    WRITE_COLS = 128  # recycled write-window columns per shard

    def __init__(self, rng):
        from pilosa_tpu.engine.words import SHARD_WIDTH
        self.lock = threading.Lock()
        self.seg: dict[int, set] = {r: set() for r in range(N_SEG_ROWS)}
        self.vals: dict[int, int] = {}
        self.write_base = [s * SHARD_WIDTH + SHARD_WIDTH // 2
                           for s in range(N_SHARDS)]
        for s in range(N_SHARDS):
            base = s * SHARD_WIDTH
            for i in range(N_VALUED):
                col = base + i
                self.seg[i % N_SEG_ROWS].add(col)
                self.vals[col] = int(rng.integers(-500, 500))

    def floors(self):
        with self.lock:
            vals = list(self.vals.values())
        return {"count": len(vals), "sum": sum(vals),
                "gt0": sum(1 for v in vals if v > 0)}


def seed(holder, truth: Truth):
    from pilosa_tpu.store import FieldOptions
    idx = holder.create_index(INDEX)
    idx.create_field("seg")
    idx.create_field("amount",
                     FieldOptions(type="int", min=-1000, max=1000))
    rows, cols = [], []
    for r, cset in truth.seg.items():
        for c in cset:
            rows.append(r)
            cols.append(c)
    idx.field("seg").import_bits(np.array(rows, np.uint64),
                                 np.array(cols, np.uint64))
    idx.field("amount").import_values(
        np.array(list(truth.vals), np.uint64),
        list(truth.vals.values()))
    idx.note_columns(np.array(cols, np.uint64))
    return idx


def shape_pql(shape: str) -> str:
    return {
        "count": "Count(Row(seg=1))",
        "range": "Count(Row(amount > 0))",
        "sum": "Sum(field=amount)",
        "min": "Min(field=amount)",
        "max": "Max(field=amount)",
        "groupby": "GroupBy(Rows(seg), aggregate=Sum(field=amount))",
        "topn": "TopN(seg)",
    }[shape]


def check(shape: str, out, truth: Truth, live: bool,
          fl0: dict | None = None) -> str | None:
    """Oracle check for one read; ``live`` = ingest running and
    ``fl0`` is the acked floor snapshot taken BEFORE the read
    (additive imports make every floor metric monotone, so the
    answer must be >= it).  Returns an error string or None."""
    fl = fl0 if live else truth.floors()
    if shape == "count":
        want = len(truth.seg[1])
        if out != want:
            return f"count {out} != {want}"
    elif shape == "range":
        if live:
            if out < fl["gt0"]:
                return f"range {out} below acked floor {fl['gt0']}"
        elif out != fl["gt0"]:
            return f"range {out} != {fl['gt0']}"
    elif shape == "sum":
        if out.count < fl["count"]:
            return f"sum count {out.count} below acked floor " \
                   f"{fl['count']}"
        if not live and (out.value, out.count) != (fl["sum"],
                                                   fl["count"]):
            return f"sum {(out.value, out.count)} != " \
                   f"{(fl['sum'], fl['count'])}"
    elif shape in ("min", "max"):
        if out.count <= 0:
            return f"{shape} empty"
    elif shape == "groupby":
        got = {tuple(fr.row_id for fr in gc.group): gc.count
               for gc in out.groups}
        for r in range(N_SEG_ROWS):
            if got.get((r,), 0) < len(truth.seg[r]):
                return f"groupby row {r}: {got.get((r,))} < " \
                       f"{len(truth.seg[r])}"
    elif shape == "topn":
        counts = {p.id: p.count for p in out.pairs}
        for r in range(N_SEG_ROWS):
            if counts.get(r, 0) < len(truth.seg[r]):
                return f"topn row {r} below floor"
    return None


def scanned_bytes(stats) -> int:
    snap = stats.snapshot()["counters"].get("kernel_bytes_scanned_total",
                                            {})
    return int(sum(snap.values()))


def run_phase(ex, shapes: list[str], truth: Truth, seconds: float,
              idx=None, rng_seed: int = 0) -> dict:
    """W readers round-robin over ``shapes``; with ``idx`` set,
    WRITERS stream import_values into fresh columns of the same BSI
    field (live ingest)."""
    from pilosa_tpu.engine.words import SHARD_WIDTH
    stop = time.monotonic() + seconds
    ok = [0] * WORKERS
    errs: list[str] = []
    live = idx is not None
    writes = [0]

    def reader(i):
        k = 0
        while time.monotonic() < stop:
            shape = shapes[(i + k) % len(shapes)]
            k += 1
            fl0 = truth.floors() if live else None
            (out,) = ex.execute(INDEX, shape_pql(shape))
            e = check(shape, out, truth, live, fl0)
            if e is not None:
                errs.append(f"{shape}: {e}")
                continue
            ok[i] += 1

    def writer(w):
        rng = np.random.default_rng(rng_seed * 100 + w)
        f = idx.field("amount")
        while time.monotonic() < stop:
            s = int(rng.integers(0, N_SHARDS))
            # overwrite within the bounded write window, POSITIVE
            # values only — the non-null and >0 floors stay monotone
            # under overwrites, so live reads assert them exactly
            offs = rng.choice(truth.WRITE_COLS, size=BATCH,
                              replace=False)
            cols = [truth.write_base[s] + int(o) for o in offs]
            vals = [int(v) for v in rng.integers(1, 500, BATCH)]
            f.import_values(np.array(cols, np.uint64), vals)
            idx.note_columns(np.array(cols, np.uint64))
            with truth.lock:
                truth.vals.update(zip(cols, vals))
            writes[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(WORKERS)]
    if live:
        threads += [threading.Thread(target=writer, args=(w,))
                    for w in range(WRITERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, f"oracle failures: {errs[:5]}"
    return {"qps": round(sum(ok) / seconds, 1), "reads": sum(ok),
            "write_batches": writes[0]}


def main():
    import tempfile

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(30)
    truth = Truth(rng)
    td = tempfile.mkdtemp(prefix="pilosa_pqlsurface_")
    holder = Holder(td).open()
    idx = seed(holder, truth)
    stats = Stats()
    ex = Executor(holder, stats=stats, max_concurrent=32)

    # warm every shape (compiles + planes) before measuring
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"warmup {s}: {e}"

    shapes_detail: dict[str, dict] = {}
    for s in SHAPES:
        b0 = scanned_bytes(stats)
        t0 = time.perf_counter()
        r = run_phase(ex, [s], truth, WINDOW)
        wall = time.perf_counter() - t0
        gb = (scanned_bytes(stats) - b0) / wall / 1e9
        shapes_detail[s] = {"qps": r["qps"],
                            "gbps": round(gb, 3)}
        log(f"[{s}] {r['qps']} qps, {gb:.3f} GB/s scanned")

    # unmeasured ingest warm-up: dirty the ENTIRE recycled write
    # window in one import, then run each shape once — the overlay's
    # touched-column set (and with it each delta-aware family's
    # compiled pow2 bucket) reaches its steady-state size before any
    # measurement, so the mixed phase reuses warm programs instead of
    # serializing behind the compile ladder (multi-second XLA
    # compiles head-of-line-block the dispatch collector)
    wcols, wvals = [], []
    for s in range(N_SHARDS):
        for o in range(truth.WRITE_COLS):
            wcols.append(truth.write_base[s] + o)
            wvals.append(int(rng.integers(1, 500)))
    idx.field("amount").import_values(np.array(wcols, np.uint64),
                                      wvals)
    idx.note_columns(np.array(wcols, np.uint64))
    truth.vals.update(zip(wcols, wvals))
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"delta warmup {s}: {e}"
    # mixed-shape serving under sustained BSI ingest
    builds0 = ex.planes.builds
    absorbs0 = ex.planes.delta_absorbs
    mixed = run_phase(ex, list(SHAPES), truth, WINDOW, idx=idx,
                      rng_seed=7)
    rebuilds = ex.planes.builds - builds0
    absorbs = ex.planes.delta_absorbs - absorbs0
    # quiesced exactness: every acked value visible, every shape exact
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        (s,) = ex.execute(INDEX, "Sum(field=amount)")
        fl = truth.floors()
        if (s.value, s.count) == (fl["sum"], fl["count"]):
            break
        time.sleep(0.1)
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"quiesced {s}: {e}"
    log(f"[mixed+ingest] {mixed['qps']} qps over "
        f"{mixed['write_batches']} write batches; {rebuilds} rebuilds, "
        f"{absorbs} absorbs")
    # r20 hard assertions: zero rebuilds, overlay live
    assert rebuilds == 0, \
        f"{rebuilds} base-plane rebuild(s) during mixed serving"
    if mixed["write_batches"]:
        assert absorbs >= 1, \
            "BSI overlay never absorbed a write during mixed serving"
    # co-batch proof: concurrent same-plane aggregates shared windows
    hits = stats.snapshot()["counters"].get("bsi_batch_hits_total", {})
    cobatch = int(sum(hits.values()))
    log(f"bsi_batch_hits_total = {cobatch}")
    assert cobatch > 0, \
        "same-plane aggregates never co-batched (window fill stuck at 1)"

    value = mixed["qps"]
    detail = {
        "shapes": shapes_detail,
        "mixed_under_ingest": mixed,
        "plane_rebuilds_during_serving": rebuilds,
        "delta_absorbs": absorbs,
        "bsi_batch_hits": cobatch,
        "workers": WORKERS, "writers": WRITERS,
        "shards": N_SHARDS, "window_s": WINDOW,
    }
    metric = ("pql_surface_qps_smoke" if SMOKE else "pql_surface_qps")
    print(json.dumps({
        "metric": metric, "value": round(value, 1), "unit": "qps",
        "vs_baseline": round(value, 1),
        "regressions": regression_guards(metric, value, detail),
        "detail": detail}))


if __name__ == "__main__":
    main()
