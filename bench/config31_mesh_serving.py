"""Config #31: MESH-SHARDED FUSED SERVING (r16, ISSUE 16).

Config30's mixed PQL workload run twice over the SAME holder — once on
a single-device executor, once over an 8-device virtual CPU mesh
(``virtmesh.force_virtual_cpu_mesh``) with every plane's shard axis
sharded via ``MeshPlacement`` — so the headline is the meshed serving
rate and the detail carries the 1-chip-vs-8-chip per-shape table.

The r16 acceptance contracts ride as HARD assertions on the meshed
mixed+ingest phase:

  - answers oracle-exact for every shape, live and quiesced, on
    sharded planes (the cross-shard reduce is compiled INTO each
    fused program — no host combine);
  - ZERO base-plane rebuilds while values stream in: the BSI overlay
    (replicated across the mesh) absorbs every write batch
    (``absorbs`` must move, ``builds`` must not);
  - one dispatch per window: concurrent same-plane aggregates
    co-batch (``bsi_batch_hits_total`` > 0) and windows answer
    through ONE packed readback (``batcher_readback_packed`` > 0)
    whose wall time lands in ``mesh_collective_seconds``.

Phases (in-process, W worker threads per phase):

  S1 per-shape @ 1 device   qps + GB/s per shape (baseline table)
  S8 per-shape @ 8 devices  same shapes over the sharded planes
  M8 mixed+ingest @ 8       all shapes round-robin while writers
                            stream import_values into the same BSI
                            field; live floors + quiesced exactness

Headline ``value`` = meshed mixed-phase qps.  ``--smoke`` (or
PILOSA_BENCH_SMOKE=1): fewer shards, short windows — tier-1 runs it
(tests/test_bench_smoke.py); the exactness / zero-rebuild / absorb /
one-dispatch assertions are pinned on every run (qps not gated at
smoke scale — CPU noise).

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
MESH_DEVICES = 8
# not a multiple of the mesh width — pad shards stay on the hot path
N_SHARDS = 4 if SMOKE else int(os.environ.get("PILOSA_BENCH_SHARDS", "12"))
N_SEG_ROWS = 4
N_VALUED = 64            # columns carrying a BSI value per shard
WORKERS = 4 if SMOKE else 8
WRITERS = 1 if SMOKE else 2
WINDOW = 1.0 if SMOKE else 6.0
BATCH = 16               # values per import batch
INDEX = "meshserve"

SHAPES = ("count", "range", "sum", "min", "max", "groupby", "topn")


def regression_guards(metric: str, value: float, detail: dict) -> list:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_headline", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.regression_guard(metric, value)
    tracked = {f"mesh_serving_qps_{s}": ("mesh", s, "qps")
               for s in SHAPES}
    out += mod.detail_regression_guard(metric, detail, tracked)
    return out


class Truth:
    """Python oracle (config30's): seg row membership + the BSI value
    map; writers overwrite a bounded column window with strictly
    positive values so the live floors stay monotone."""

    WRITE_COLS = 128

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
    """Oracle check for one read (config30's contract): ``live`` =
    ingest running, ``fl0`` the acked floor snapshot taken BEFORE the
    read."""
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


def counter_total(stats, name: str) -> int:
    snap = stats.snapshot()["counters"].get(name, {})
    return int(sum(snap.values()))


def run_phase(ex, shapes: list[str], truth: Truth, seconds: float,
              idx=None, rng_seed: int = 0) -> dict:
    """W readers round-robin over ``shapes``; with ``idx`` set,
    WRITERS stream import_values into the bounded write window of the
    same BSI field (live ingest)."""
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


def shape_table(ex, stats, truth: Truth, tag: str) -> dict:
    out: dict[str, dict] = {}
    for s in SHAPES:
        b0 = scanned_bytes(stats)
        t0 = time.perf_counter()
        r = run_phase(ex, [s], truth, WINDOW)
        wall = time.perf_counter() - t0
        gb = (scanned_bytes(stats) - b0) / wall / 1e9
        out[s] = {"qps": r["qps"], "gbps": round(gb, 3)}
        log(f"[{tag}:{s}] {r['qps']} qps, {gb:.3f} GB/s scanned")
    return out


def main():
    import tempfile

    # the mesh must exist before any backend initializes
    from pilosa_tpu.virtmesh import force_virtual_cpu_mesh
    assert force_virtual_cpu_mesh(MESH_DEVICES), \
        f"could not provision a {MESH_DEVICES}-device virtual CPU mesh"
    import jax

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.parallel import MeshPlacement
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(31)
    truth = Truth(rng)
    td = tempfile.mkdtemp(prefix="pilosa_meshserve_")
    holder = Holder(td).open()
    idx = seed(holder, truth)

    # ---- S1: the single-device baseline over the same holder
    stats1 = Stats()
    ex1 = Executor(holder, stats=stats1, max_concurrent=32)
    for s in SHAPES:
        (out,) = ex1.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"warmup-1dev {s}: {e}"
    single = shape_table(ex1, stats1, truth, "1dev")

    # ---- S8: sharded planes over the virtual mesh
    stats8 = Stats()
    ex8 = Executor(holder, placement=MeshPlacement(jax.devices()),
                   stats=stats8, max_concurrent=32)
    for s in SHAPES:
        (out,) = ex8.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"warmup-mesh {s}: {e}"
    mesh = shape_table(ex8, stats8, truth, "mesh")

    # unmeasured ingest warm-up (config30's steady-state trick): dirty
    # the ENTIRE recycled write window once so each delta-aware
    # family's compiled pow2 bucket reaches steady state before the
    # measured mixed phase
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
        (out,) = ex8.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"delta warmup {s}: {e}"

    # ---- M8: mixed-shape serving under sustained BSI ingest, meshed
    builds0 = ex8.planes.builds
    absorbs0 = ex8.planes.delta_absorbs
    mixed = run_phase(ex8, list(SHAPES), truth, WINDOW, idx=idx,
                      rng_seed=7)
    rebuilds = ex8.planes.builds - builds0
    absorbs = ex8.planes.delta_absorbs - absorbs0
    # quiesced exactness: every acked value visible, every shape exact
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        (sv,) = ex8.execute(INDEX, "Sum(field=amount)")
        fl = truth.floors()
        if (sv.value, sv.count) == (fl["sum"], fl["count"]):
            break
        time.sleep(0.1)
    for s in SHAPES:
        (out,) = ex8.execute(INDEX, shape_pql(s))
        e = check(s, out, truth, live=False)
        assert e is None, f"quiesced {s}: {e}"
    log(f"[mesh mixed+ingest] {mixed['qps']} qps over "
        f"{mixed['write_batches']} write batches; {rebuilds} rebuilds, "
        f"{absorbs} absorbs")

    # window-join proof: barrier-synced DIFFERENT-kind aggregates over
    # the same planes must collect into one window answered by ONE
    # packed device->host read — the multi-group half of the
    # one-dispatch-per-window contract (the mixed phase may serve
    # single-group windows only, depending on thread timing, so this
    # burst pins it deterministically; bounded attempts absorb
    # scheduler noise)
    packed0 = counter_total(stats8, "batcher_readback_packed")
    burst_shapes = ("sum", "min", "count")
    for _ in range(20):
        barrier = threading.Barrier(2 * len(burst_shapes))

        def burst(shape):
            barrier.wait()
            for _ in range(4):
                ex8.execute(INDEX, shape_pql(shape))

        ts = [threading.Thread(target=burst, args=(s,))
              for s in burst_shapes for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if counter_total(stats8, "batcher_readback_packed") > packed0:
            break

    # ---- r16 hard assertions on the meshed phase
    assert rebuilds == 0, \
        f"{rebuilds} base-plane rebuild(s) during meshed serving"
    if mixed["write_batches"]:
        assert absorbs >= 1, \
            "overlay never absorbed a write on the meshed executor"
    cobatch = counter_total(stats8, "bsi_batch_hits_total")
    packed = counter_total(stats8, "batcher_readback_packed")
    log(f"bsi_batch_hits_total={cobatch} batcher_readback_packed={packed}")
    assert cobatch > 0, \
        "same-plane aggregates never co-batched on the mesh"
    assert packed > 0, \
        "no window answered through one packed readback on the mesh"
    coll = stats8.histogram_summary("mesh_collective_seconds")
    assert coll, "mesh_collective_seconds never observed"
    ms = ex8.mesh_status()
    assert ms is not None and ms["devices"] == MESH_DEVICES, ms

    value = mixed["qps"]
    detail = {
        "single": single,
        "mesh": mesh,
        "mixed_under_ingest": mixed,
        "mesh_devices": MESH_DEVICES,
        "padded_shards": ms["paddedShards"],
        "plane_rebuilds_during_serving": rebuilds,
        "delta_absorbs": absorbs,
        "bsi_batch_hits": cobatch,
        "packed_readbacks": packed,
        "workers": WORKERS, "writers": WRITERS,
        "shards": N_SHARDS, "window_s": WINDOW,
    }
    metric = ("mesh_serving_qps_smoke" if SMOKE else "mesh_serving_qps")
    print(json.dumps({
        "metric": metric, "value": round(value, 1), "unit": "qps",
        "vs_baseline": round(value, 1),
        "regressions": regression_guards(metric, value, detail),
        "detail": detail}))


if __name__ == "__main__":
    main()
