"""Config #7 (extra): GroupBy over the full combination tree — 3 Rows
fields x 50 rows each = 125,000 groups, end-to-end through the executor.

Round 1 ran one device dispatch (each with its own host read) per prefix
combination: 2,500 dispatches for this shape.
Round 2 compiles the whole tree into ONE program (``exec.groupby``:
``lax.map`` over prefix combos, vectorized innermost level) — O(1)
dispatches/reads regardless of level count."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import emit, log


def main():
    import tempfile

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    rng = np.random.default_rng(7)
    holder = Holder(tempfile.mkdtemp()).open()
    idx = holder.create_index("bench", track_existence=False)
    # dense enough that most of the 125k combination cells are non-zero
    n_rows, n_bits, n_cols = 50, 300_000, 1 << 16
    oracle = {}
    for fld in ("a", "b", "c"):
        idx.create_field(fld)
        rows = rng.integers(0, n_rows, size=n_bits).astype(np.uint64)
        cols = rng.integers(0, n_cols, size=n_bits).astype(np.uint64)
        idx.field(fld).import_bits(rows, cols)
        idx.note_columns(cols)
        m = np.zeros((n_rows, n_cols), dtype=bool)
        m[rows, cols] = True
        oracle[fld] = np.packbits(m, axis=-1, bitorder="little")
    ex = Executor(holder)

    t0 = time.perf_counter()
    (g,) = ex.execute("bench", "GroupBy(Rows(a), Rows(b), Rows(c))")
    t_first = time.perf_counter() - t0  # includes compile
    t0 = time.perf_counter()
    (g,) = ex.execute("bench", "GroupBy(Rows(a), Rows(b), Rows(c))")
    t_warm = time.perf_counter() - t0
    # the serving edge pays JSON materialization from the columnar
    # result — time it too so the headline is end-to-end honest
    t0 = time.perf_counter()
    blob = g.to_json()
    t_json = time.perf_counter() - t0
    t_warm += t_json
    log(f"groups: {len(blob)}; first {t_first:.2f}s, "
        f"warm {t_warm:.2f}s (of which to_json {t_json:.2f}s)")

    # CPU oracle stand-in: same combination tree with numpy popcounts
    t0 = time.perf_counter()
    expect = []
    pa, pb, pc = oracle["a"], oracle["b"], oracle["c"]
    for i in range(n_rows):
        for j in range(n_rows):
            pre = pa[i] & pb[j]
            if not pre.any():
                continue
            cnts = np.bitwise_count(pc & pre).sum(axis=1)
            for k in range(n_rows):
                if cnts[k]:
                    expect.append((i, j, k, int(cnts[k])))
    t_cpu = time.perf_counter() - t0
    log(f"cpu oracle: {t_cpu:.2f}s ({len(expect)} groups)")

    got = [(gc.group[0].row_id, gc.group[1].row_id, gc.group[2].row_id,
            gc.count) for gc in g.groups]
    assert got == expect, "GroupBy mismatch vs numpy oracle"

    emit("groupby_3x50_warm_s", t_warm, "s", t_cpu / t_warm)


if __name__ == "__main__":
    main()
