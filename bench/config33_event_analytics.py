"""Config #33: EVENT ANALYTICS ON TIME-VIEW PLANES (r23, ISSUE 18).

The r23 tentpole gives time-quantum views a first-class bucketed
device plane: "row seen in [t0, t1)" answers as ONE fused OR-scan over
a contiguous bucket range instead of a host loop unioning one device
row fetch per cover view, and time-bucketed ingest absorbs into the
(row, bucket)-keyed delta overlay — zero base rebuilds.  This bench
drives the event-analytics shapes that surface buys — recency
segmentation, retention cohorts, sliding windows, time-filtered
Rows/GroupBy — plus the formerly-unfusable postfix tail (Shift /
Limit / ConstRow as static tree ops), with the r20 contracts as hard
assertions:

  - answers oracle-exact for every shape, live and quiesced (the
    in-bench Truth map IS the oracle: per-(row, col) event-hour sets);
  - ZERO time-plane rebuilds while events stream into EXISTING
    buckets (``delta_absorbs`` must move);
  - the fused surfaces actually engage: ``time_range_cover_size``
    observed (time planes served range scans) and
    ``tree_static_ops_total`` counted (Shift/Limit ran inside fused
    tree programs), not silently falling back.

Phases (in-process executor, W worker threads per phase):

  S  per-shape     W workers hammer one shape for WINDOW seconds →
                   qps per shape, oracle-checked per read
  M  mixed+ingest  all shapes round-robin while writers stream
                   import_bits batches into EXISTING hour buckets of
                   the SAME time field; live reads assert monotone
                   floors, a quiesced pass asserts exactness

Headline ``value`` = aggregate mixed-phase qps.  Detail carries the
per-shape table and rides the shared detail-regression guard.

``--smoke`` (or PILOSA_BENCH_SMOKE=1): 2 shards, short windows —
tier-1 runs it (tests/test_bench_smoke.py): exactness, zero-rebuild,
absorb and engagement assertions are pinned on every run (qps itself
is not gated at smoke scale — CPU noise).

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
from datetime import datetime, timedelta

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

SMOKE = ("--smoke" in sys.argv
         or os.environ.get("PILOSA_BENCH_SMOKE") == "1")
N_SHARDS = 2 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "8"))
N_EVENT_ROWS = 4         # event types
N_HOURS = 48             # hourly buckets on the timeline
N_COLS = 64              # seeded actor columns per shard
WORKERS = 4 if SMOKE else 8
WRITERS = 1 if SMOKE else 2
WINDOW = 1.0 if SMOKE else 6.0
BATCH = 16               # bits per import batch
INDEX = "events"
T0 = datetime(2021, 1, 1)

SHAPES = ("recency", "retention", "sliding", "rows_time",
          "groupby_time", "shift", "limit", "constrow")


def ts(h: int) -> str:
    return (T0 + timedelta(hours=h)).strftime("%Y-%m-%dT%H:%M")


def regression_guards(metric: str, value: float, detail: dict) -> list:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.regression_guard(metric, value)
    tracked = {f"event_analytics_qps_{s}": ("shapes", s, "qps")
               for s in SHAPES}
    out += mod.detail_regression_guard(metric, detail, tracked)
    return out


class Truth:
    """The python oracle: per (event row, column) the set of hour
    indexes the event was seen in.  Static during phase S; during
    phase M writers ADD events for existing rows into EXISTING hour
    buckets at fresh columns of a bounded per-shard window (Set is
    additive, so every time-range count is monotone) under ``lock``.
    Every hour in [0, N_HOURS) is seeded, so mixed-phase ingest never
    creates a bucket — the zero-rebuild bar is meaningful."""

    WRITE_COLS = 128  # recycled write-window columns per shard

    def __init__(self, rng):
        from pilosa_tpu.engine.words import SHARD_WIDTH
        self.lock = threading.Lock()
        # hours[row] : {col: set(hour index)}
        self.hours: dict[int, dict[int, set]] = {
            r: {} for r in range(N_EVENT_ROWS)}
        self.write_base = [s * SHARD_WIDTH + SHARD_WIDTH // 2
                           for s in range(N_SHARDS)]
        for s in range(N_SHARDS):
            base = s * SHARD_WIDTH
            for i in range(N_COLS):
                col = base + i
                r = i % N_EVENT_ROWS
                # 1-3 deterministic event hours per actor, spread so
                # every hour bucket exists before the bench starts
                hs = {(i * 7 + k * 13) % N_HOURS for k in range(1 + i % 3)}
                self.hours[r][col] = set(hs)
        # guarantee full bucket coverage for row 0 from one column
        self.hours[0].setdefault(0, set()).update(range(N_HOURS))

    def range_cols(self, row: int, h0: int | None, h1: int | None):
        """Columns with a ``row`` event in hour range [h0, h1)."""
        lo = 0 if h0 is None else h0
        hi = N_HOURS if h1 is None else h1
        with self.lock:
            return {c for c, hs in self.hours[row].items()
                    if any(lo <= h < hi for h in hs)}

    def rows_in_range(self, h0: int, h1: int):
        with self.lock:
            return sorted(r for r in range(N_EVENT_ROWS)
                          if any(any(h0 <= h < h1 for h in hs)
                                 for hs in self.hours[r].values()))


def seed(holder, truth: Truth):
    from pilosa_tpu.store import FieldOptions
    idx = holder.create_index(INDEX)
    idx.create_field("ev", FieldOptions(type="time", time_quantum="YMDH"))
    rows, cols, stamps = [], [], []
    for r, per_col in truth.hours.items():
        for c, hs in per_col.items():
            for h in hs:
                rows.append(r)
                cols.append(c)
                stamps.append(T0 + timedelta(hours=h))
    idx.field("ev").import_bits(np.array(rows, np.uint64),
                                np.array(cols, np.uint64), stamps)
    idx.note_columns(np.array(cols, np.uint64))
    return idx


# fixed query windows (deterministic per shape so reads oracle-check)
RECENT = (N_HOURS - 12, N_HOURS)           # "last 12 hours"
COHORT_A = (0, 12)
COHORT_B = (24, 48)
SLIDES = [(h, h + 8) for h in (0, 8, 16, 24, 32, 40)]


def shape_pql(shape: str, k: int = 0) -> str:
    if shape == "recency":
        return f"Count(Row(ev=1, from={ts(RECENT[0])}, to={ts(RECENT[1])}))"
    if shape == "retention":
        return (f"Count(Intersect("
                f"Row(ev=1, from={ts(COHORT_A[0])}, to={ts(COHORT_A[1])}), "
                f"Row(ev=1, from={ts(COHORT_B[0])}, to={ts(COHORT_B[1])})))")
    if shape == "sliding":
        h0, h1 = SLIDES[k % len(SLIDES)]
        return f"Count(Row(ev=2, from={ts(h0)}, to={ts(h1)}))"
    if shape == "rows_time":
        return f"Rows(ev, from={ts(0)}, to={ts(24)})"
    if shape == "groupby_time":
        return f"GroupBy(Rows(ev, from={ts(0)}, to={ts(24)}))"
    if shape == "shift":
        return f"Count(Shift(Row(ev=1, from={ts(0)}, to={ts(N_HOURS)}), n=1))"
    if shape == "limit":
        return "Count(Limit(Row(ev=0), limit=8, offset=2))"
    if shape == "constrow":
        return "Count(Intersect(Row(ev=0), ConstRow(columns=[0, 1, 2])))"
    raise ValueError(shape)


def check(shape: str, out, truth: Truth, live: bool, k: int = 0,
          fl0: int | None = None) -> str | None:
    """Oracle check for one read; ``live`` = ingest running and
    ``fl0`` the count floor snapshotted BEFORE the read (additive
    event ingest keeps every count monotone)."""
    def cmp_count(want: int) -> str | None:
        if live:
            if out < (fl0 or 0):
                return f"{shape} {out} below acked floor {fl0}"
        elif out != want:
            return f"{shape} {out} != {want}"
        return None

    if shape == "recency":
        return cmp_count(len(truth.range_cols(1, *RECENT)))
    if shape == "retention":
        return cmp_count(len(truth.range_cols(1, *COHORT_A)
                             & truth.range_cols(1, *COHORT_B)))
    if shape == "sliding":
        return cmp_count(len(truth.range_cols(2, *SLIDES[k % len(SLIDES)])))
    if shape == "rows_time":
        want = truth.rows_in_range(0, 24)
        got = sorted(int(r) for r in out.rows)
        if got != want:
            return f"rows_time {got} != {want}"
        return None
    if shape == "groupby_time":
        want = truth.rows_in_range(0, 24)
        got = sorted(gc.group[0].row_id for gc in out.groups)
        if got != want:
            return f"groupby_time rows {got} != {want}"
        return None
    if shape == "shift":
        # Shift drops bits crossing a shard boundary; seeded/write
        # columns never sit on one, so count is preserved
        return cmp_count(len(truth.range_cols(1, None, None)))
    if shape == "limit":
        want = min(8, max(0, len(truth.range_cols(0, None, None)) - 2))
        if live:
            # under additive ingest the truncated count can only grow
            # toward the cap
            if out > 8:
                return f"limit {out} > cap 8"
            return None
        return cmp_count(want)
    if shape == "constrow":
        want = len(truth.range_cols(0, None, None) & {0, 1, 2})
        return cmp_count(want)
    return None


def floor_of(shape: str, truth: Truth, k: int) -> int | None:
    """Monotone count floor snapshotted before a live read."""
    if shape == "recency":
        return len(truth.range_cols(1, *RECENT))
    if shape == "retention":
        return len(truth.range_cols(1, *COHORT_A)
                   & truth.range_cols(1, *COHORT_B))
    if shape == "sliding":
        return len(truth.range_cols(2, *SLIDES[k % len(SLIDES)]))
    if shape == "shift":
        return len(truth.range_cols(1, None, None))
    if shape == "constrow":
        return len(truth.range_cols(0, None, None) & {0, 1, 2})
    return None


def run_phase(ex, shapes: list[str], truth: Truth, seconds: float,
              idx=None, rng_seed: int = 0) -> dict:
    """W readers round-robin over ``shapes``; with ``idx`` set,
    WRITERS stream import_bits batches into existing hour buckets of
    the same time field (live ingest)."""
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
            fl0 = floor_of(shape, truth, k) if live else None
            (out,) = ex.execute(INDEX, shape_pql(shape, k))
            e = check(shape, out, truth, live, k, fl0)
            if e is not None:
                errs.append(f"{shape}: {e}")
                continue
            ok[i] += 1

    def writer(w):
        rng = np.random.default_rng(rng_seed * 100 + w)
        f = idx.field("ev")
        while time.monotonic() < stop:
            s = int(rng.integers(0, N_SHARDS))
            # existing rows, EXISTING hour buckets, recycled columns:
            # pure delta-absorb territory (no bucket, no new row)
            offs = rng.choice(truth.WRITE_COLS, size=BATCH, replace=False)
            cols = [truth.write_base[s] + int(o) for o in offs]
            rows = [int(r) for r in rng.integers(0, N_EVENT_ROWS, BATCH)]
            hs = [int(h) for h in rng.integers(0, N_HOURS, BATCH)]
            f.import_bits(np.array(rows, np.uint64),
                          np.array(cols, np.uint64),
                          [T0 + timedelta(hours=h) for h in hs])
            idx.note_columns(np.array(cols, np.uint64))
            with truth.lock:
                for r, c, h in zip(rows, cols, hs):
                    truth.hours[r].setdefault(c, set()).add(h)
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


def counter_total(stats, name: str) -> int:
    snap = stats.snapshot()["counters"].get(name, {})
    return int(sum(snap.values()))


def main():
    import tempfile

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(33)
    truth = Truth(rng)
    td = tempfile.mkdtemp(prefix="pilosa_events_")
    holder = Holder(td).open()
    idx = seed(holder, truth)
    stats = Stats()
    ex = Executor(holder, stats=stats, max_concurrent=32)

    # warm every shape (compiles + the time plane) before measuring
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"warmup {s}: {e}"

    shapes_detail: dict[str, dict] = {}
    for s in SHAPES:
        r = run_phase(ex, [s], truth, WINDOW)
        shapes_detail[s] = {"qps": r["qps"]}
        log(f"[{s}] {r['qps']} qps")

    # unmeasured ingest warm-up: dirty the ENTIRE recycled write
    # window once so the time plane's (row × bucket) slot set and the
    # overlay's compiled pow2 bucket reach steady state before any
    # measurement (same rationale as config30's delta warm-up)
    wrows, wcols, wstamps = [], [], []
    for s in range(N_SHARDS):
        for o in range(truth.WRITE_COLS):
            col = truth.write_base[s] + o
            r = o % N_EVENT_ROWS
            h = o % N_HOURS
            wrows.append(r)
            wcols.append(col)
            wstamps.append(T0 + timedelta(hours=h))
            truth.hours[r].setdefault(col, set()).add(h)
    idx.field("ev").import_bits(np.array(wrows, np.uint64),
                                np.array(wcols, np.uint64), wstamps)
    idx.note_columns(np.array(wcols, np.uint64))
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"delta warmup {s}: {e}"
    # mixed-shape serving under sustained time-bucketed ingest
    builds0 = ex.planes.builds
    absorbs0 = ex.planes.delta_absorbs
    mixed = run_phase(ex, list(SHAPES), truth, WINDOW, idx=idx,
                      rng_seed=7)
    rebuilds = ex.planes.builds - builds0
    absorbs = ex.planes.delta_absorbs - absorbs0
    # quiesced exactness: every acked event visible, every shape exact
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        (c,) = ex.execute(INDEX, shape_pql("recency"))
        if check("recency", c, truth, live=False) is None:
            break
        time.sleep(0.1)
    for s in SHAPES:
        (out,) = ex.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"quiesced {s}: {e}"
    log(f"[mixed+ingest] {mixed['qps']} qps over "
        f"{mixed['write_batches']} write batches; {rebuilds} rebuilds, "
        f"{absorbs} absorbs")
    # r23 hard assertions: zero rebuilds under in-bucket ingest, the
    # overlay live, and the fused surfaces actually engaged
    assert rebuilds == 0, \
        f"{rebuilds} plane rebuild(s) during mixed serving"
    if mixed["write_batches"]:
        assert absorbs >= 1, \
            "time-plane overlay never absorbed a write during mixed serving"
    covers = stats.histogram_summary("time_range_cover_size")
    cover_n = int(sum(v["count"] for v in covers.values()))
    static_ops = counter_total(stats, "tree_static_ops_total")
    log(f"time_range_cover_size observations = {cover_n}; "
        f"tree_static_ops_total = {static_ops}")
    assert cover_n > 0, \
        "time plane never served a range scan (fell back to span oracle)"
    assert static_ops > 0, \
        "Shift/Limit never ran as static ops inside fused tree programs"

    value = mixed["qps"]
    detail = {
        "shapes": shapes_detail,
        "mixed_under_ingest": mixed,
        "plane_rebuilds_during_serving": rebuilds,
        "delta_absorbs": absorbs,
        "time_range_scans": cover_n,
        "tree_static_ops": static_ops,
        "workers": WORKERS, "writers": WRITERS,
        "shards": N_SHARDS, "window_s": WINDOW, "hours": N_HOURS,
    }
    metric = ("event_analytics_qps_smoke" if SMOKE
              else "event_analytics_qps")
    print(json.dumps({
        "metric": metric, "value": round(value, 1), "unit": "qps",
        "vs_baseline": round(value, 1),
        "regressions": regression_guards(metric, value, detail),
        "detail": detail}))


if __name__ == "__main__":
    main()
