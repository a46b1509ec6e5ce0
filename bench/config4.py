"""Config #4 (BASELINE.md): BSI int field — Range + Sum/Min/Max over
10M records end-to-end through the executor, vs numpy int64 array
operations as the CPU stand-in.

Shape note: BASELINE.json says "10M rows" in the database sense —
10M records, which in pilosa's data model are 10M COLUMNS of a 20-bit
BSI field (a BSI field's rows are bit positions, ~21 of them).  The
benched shape matches the baseline's intent; earlier rounds' "cols vs
rows" label mismatch is resolved here, not by changing the shape.

Two serving modes:
- single-stream: one query at a time (pays the fixed per-read cost
  in full);
- 8-way concurrent with cross-request batching (the realistic serving
  condition): Sum/Min/Max/Range+Count coalesce into one program + one
  read per window (exec/batcher.py), amortizing the floor.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import emit, log, time_wall


def main():
    import tempfile

    import jax

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import FieldOptions, Holder

    rng = np.random.default_rng(4)
    n_cols = 10_000_000
    cols = np.arange(n_cols, dtype=np.uint64)
    vals = rng.integers(-500_000, 500_000, size=n_cols, dtype=np.int64)

    h = Holder(tempfile.mkdtemp()).open()
    idx = h.create_index("bench", track_existence=False)
    f = idx.create_field("amount", FieldOptions(
        type="int", min=-500_000, max=500_000))
    t0 = time.perf_counter()
    f.import_values(cols, vals)
    log(f"import of {n_cols / 1e6:.0f}M values: "
        f"{time.perf_counter() - t0:.1f}s")
    ex = Executor(h)

    (s,) = ex.execute("bench", "Sum(field=amount)")
    assert (s.value, s.count) == (int(vals.sum()), n_cols)
    (r,) = ex.execute("bench", "Count(Row(amount > 250000))")
    assert r == int((vals > 250_000).sum())
    (mn,) = ex.execute("bench", "Min(field=amount)")
    assert mn.value == int(vals.min())
    (mx,) = ex.execute("bench", "Max(field=amount)")
    assert mx.value == int(vals.max())
    (p50v,) = ex.execute("bench", "Percentile(field=amount, nth=50)")
    assert p50v.value == int(np.sort(vals)[
        max(0, int(np.ceil(0.5 * n_cols)) - 1)])

    t_cpu_sum = time_wall(lambda: vals.sum(), 20)
    t_cpu_rng = time_wall(lambda: (vals > 250_000).sum(), 20)
    t_cpu_min = time_wall(lambda: vals.min(), 20)
    t_cpu_pct = time_wall(lambda: np.percentile(vals, 50), 5)

    for pql in ("Sum(field=amount)", "Count(Row(amount > 250000))",
                "Min(field=amount)", "Max(field=amount)",
                "Percentile(field=amount, nth=50)"):
        ex.execute("bench", pql)  # compile warmup — keep it out of means
    t_sum = time_wall(lambda: ex.execute("bench", "Sum(field=amount)"), 50)
    t_rng = time_wall(
        lambda: ex.execute("bench", "Count(Row(amount > 250000))"), 50)
    t_min = time_wall(lambda: ex.execute("bench", "Min(field=amount)"), 50)
    t_max = time_wall(lambda: ex.execute("bench", "Max(field=amount)"), 50)
    t_pct = time_wall(
        lambda: ex.execute("bench", "Percentile(field=amount, nth=50)"), 20)
    platform = jax.devices()[0].platform
    log(f"single-stream: Sum {t_sum * 1e3:.2f} ms | Range+Count "
        f"{t_rng * 1e3:.2f} ms | Min {t_min * 1e3:.2f} ms | Max "
        f"{t_max * 1e3:.2f} ms | Percentile {t_pct * 1e3:.2f} ms  (cpu: "
        f"sum {t_cpu_sum * 1e3:.2f}, range {t_cpu_rng * 1e3:.2f}, min "
        f"{t_cpu_min * 1e3:.2f}, pct {t_cpu_pct * 1e3:.2f})")

    # 8-way concurrent with the cross-request batcher: the serving-path
    # number — per-request latency when the read floor is shared
    exb = Executor(h, count_batch_window=0.004)
    exb.execute("bench", "Sum(field=amount)")  # warm the programs
    exb.execute("bench", "Min(field=amount)")
    exb.execute("bench", "Count(Row(amount > 250000))")
    queries = ["Sum(field=amount)", "Min(field=amount)",
               "Max(field=amount)", "Count(Row(amount > 250000))"] * 2
    iters = 6

    def clients():
        errs = []
        barrier = threading.Barrier(len(queries))

        def worker(q):
            barrier.wait()
            try:
                for _ in range(iters):
                    exb.execute("bench", q)
            except Exception as e:  # noqa: BLE001 — surface after join
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(q,)) for q in queries]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs
        return (time.perf_counter() - t0) / iters / len(queries)

    t_warmup = clients()  # compile the batch-bucket programs (one-time)
    t_conc = clients()
    log(f"8-way concurrent batched: {t_conc * 1e3:.2f} ms/query "
        f"({1.0 / t_conc:.0f} qps aggregate; first-burst incl. bucket "
        f"compiles: {t_warmup * 1e3:.0f} ms/query)")

    emit(f"bsi_agg_concurrent_ms_10m_{platform}", t_conc * 1e3, "ms",
         t_cpu_sum / t_conc)


if __name__ == "__main__":
    main()
