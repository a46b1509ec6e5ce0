"""Executor integration tests: table-driven PQL → expected results against
a temp-dir holder, mirroring the reference's ``executor_test.go`` strategy
(SURVEY.md §5)."""

import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import ExecutionError, Executor
from pilosa_tpu.store import FieldOptions, Holder


@pytest.fixture
def env(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("amount", FieldOptions(type="int", min=-1000, max=1000))
    ex = Executor(holder)
    return holder, idx, ex


def q(ex, pql, index="i", shards=None):
    return ex.execute(index, pql, shards=shards)


class TestDeviceOomRetry:
    def test_oom_evicts_planes_and_retries(self, env, monkeypatch):
        """Device RESOURCE_EXHAUSTED on a call must evict unpinned
        planes and retry, not surface a 500 (regression: REST filtered
        TopN OOM'd at 1B cols after BSI+sparse residency filled HBM;
        r5 narrows the eviction to unpinned entries so
        concurrent queries' planes stay resident)."""
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1)")

        class XlaRuntimeError(Exception):
            pass

        calls = {"n": 0}
        evicted = {"n": 0}
        real = ex._execute_count

        def flaky(ctx, call):
            calls["n"] += 1
            if calls["n"] == 1:
                raise XlaRuntimeError(
                    "RESOURCE_EXHAUSTED: TPU backend error")
            return real(ctx, call)

        real_evict = ex.planes.evict_unpinned

        def spy_evict():
            evicted["n"] += 1
            return real_evict()

        monkeypatch.setattr(ex, "_execute_count", flaky)
        monkeypatch.setattr(ex.planes, "evict_unpinned", spy_evict)
        assert q(ex, "Count(Row(f=1))") == [2]
        assert calls["n"] == 2 and evicted["n"] == 1

    def test_non_oom_errors_propagate_without_retry(self, env,
                                                    monkeypatch):
        _, _, ex = env
        calls = {"n": 0}

        def boom(ctx, call):
            calls["n"] += 1
            raise RuntimeError("INTERNAL: something else")

        monkeypatch.setattr(ex, "_execute_count", boom)
        with pytest.raises(RuntimeError, match="something else"):
            q(ex, "Count(Row(f=1))")
        assert calls["n"] == 1


class TestBitmapCalls:
    def test_row_and_set(self, env):
        _, _, ex = env
        assert q(ex, "Set(10, f=1)") == [True]
        assert q(ex, "Set(10, f=1)") == [False]  # already set
        (r,) = q(ex, "Row(f=1)")
        np.testing.assert_array_equal(r.columns, [10])

    def test_cross_shard_row(self, env):
        _, _, ex = env
        c2 = SHARD_WIDTH + 7
        q(ex, f"Set(3, f=1) Set({c2}, f=1)")
        (r,) = q(ex, "Row(f=1)")
        np.testing.assert_array_equal(r.columns, [3, c2])

    def test_boolean_algebra(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1) Set(3, f=1)"
              "Set(2, g=1) Set(3, g=1) Set(4, g=1)")
        (i,) = q(ex, "Intersect(Row(f=1), Row(g=1))")
        np.testing.assert_array_equal(i.columns, [2, 3])
        (u,) = q(ex, "Union(Row(f=1), Row(g=1))")
        np.testing.assert_array_equal(u.columns, [1, 2, 3, 4])
        (d,) = q(ex, "Difference(Row(f=1), Row(g=1))")
        np.testing.assert_array_equal(d.columns, [1])
        (x,) = q(ex, "Xor(Row(f=1), Row(g=1))")
        np.testing.assert_array_equal(x.columns, [1, 4])

    def test_not_and_all(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1) Set(5, g=1)")
        (n,) = q(ex, "Not(Row(f=1))")
        np.testing.assert_array_equal(n.columns, [5])
        (a,) = q(ex, "All()")
        np.testing.assert_array_equal(a.columns, [1, 2, 5])

    def test_count(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1) Set(2, g=1)")
        assert q(ex, "Count(Row(f=1))") == [2]
        assert q(ex, "Count(Intersect(Row(f=1), Row(g=1)))") == [1]

    def test_missing_row_is_empty(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1)")
        (r,) = q(ex, "Row(f=99)")
        assert len(r.columns) == 0

    def test_unknown_field_errors(self, env):
        _, _, ex = env
        with pytest.raises(ExecutionError):
            q(ex, "Row(nope=1)")

    def test_clear_and_clearrow(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1)")
        assert q(ex, "Clear(1, f=1)") == [True]
        assert q(ex, "Clear(1, f=1)") == [False]
        (r,) = q(ex, "Row(f=1)")
        np.testing.assert_array_equal(r.columns, [2])
        assert q(ex, "ClearRow(f=1)") == [True]
        assert q(ex, "Count(Row(f=1))") == [0]

    def test_store(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1)")
        assert q(ex, "Store(Row(f=1), g=7)") == [True]
        (r,) = q(ex, "Row(g=7)")
        np.testing.assert_array_equal(r.columns, [1, 2])


class TestBSI:
    def test_range_operators(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=-42) Set(2, amount=0) Set(3, amount=7)"
              "Set(4, amount=977)")
        cases = {
            "Row(amount > 0)": [3, 4],
            "Row(amount >= 0)": [2, 3, 4],
            "Row(amount < 0)": [1],
            "Row(amount <= 7)": [1, 2, 3],
            "Row(amount == 7)": [3],
            "Row(amount != 7)": [1, 2, 4],
            "Row(0 < amount < 100)": [3],
            "Row(0 <= amount <= 7)": [2, 3],
        }
        for pql, expect in cases.items():
            (r,) = q(ex, pql)
            np.testing.assert_array_equal(r.columns, expect, err_msg=pql)

    def test_range_saturation(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=5)")
        (r,) = q(ex, "Row(amount < 100000000)")
        np.testing.assert_array_equal(r.columns, [1])
        (r,) = q(ex, "Row(amount > 100000000)")
        assert len(r.columns) == 0
        (r,) = q(ex, "Row(amount > -100000000)")
        np.testing.assert_array_equal(r.columns, [1])

    def test_sum_min_max(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=-42) Set(2, amount=0) Set(3, amount=7)"
              "Set(4, amount=977)")
        (s,) = q(ex, "Sum(field=amount)")
        assert (s.value, s.count) == (-42 + 0 + 7 + 977, 4)
        (mn,) = q(ex, "Min(field=amount)")
        assert (mn.value, mn.count) == (-42, 1)
        (mx,) = q(ex, "Max(field=amount)")
        assert (mx.value, mx.count) == (977, 1)

    def test_sum_with_filter(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=10) Set(2, amount=20) Set(1, f=1)")
        (s,) = q(ex, "Sum(Row(f=1), field=amount)")
        assert (s.value, s.count) == (10, 1)

    def test_cross_shard_bsi(self, env):
        _, _, ex = env
        c2 = SHARD_WIDTH + 1
        q(ex, f"Set(1, amount=5) Set({c2}, amount=9)")
        (s,) = q(ex, "Sum(field=amount)")
        assert (s.value, s.count) == (14, 2)
        (r,) = q(ex, "Row(amount > 6)")
        np.testing.assert_array_equal(r.columns, [c2])

    def test_row_equals_on_bsi(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=7)")
        (r,) = q(ex, "Row(amount=7)")
        np.testing.assert_array_equal(r.columns, [1])


class TestTopNRowsGroupBy:
    def test_topn(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=10)"
              "Set(1, f=20) Set(2, f=20) Set(9, f=30)")
        (p,) = q(ex, "TopN(f, n=2)")
        assert [(x.id, x.count) for x in p.pairs] == [(10, 3), (20, 2)]
        (p_all,) = q(ex, "TopN(f)")
        assert [(x.id, x.count) for x in p_all.pairs] == [
            (10, 3), (20, 2), (30, 1)]

    def test_topn_with_filter(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(2, f=20) Set(2, g=1)")
        (p,) = q(ex, "TopN(f, filter=Row(g=1), n=5)")
        assert [(x.id, x.count) for x in p.pairs] == [(10, 1), (20, 1)]

    def test_topn_ids_restriction(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=20)")
        (p,) = q(ex, "TopN(f, ids=[20])")
        assert [(x.id, x.count) for x in p.pairs] == [(20, 1)]

    def test_topn_cross_shard_merge(self, env):
        _, _, ex = env
        c2 = SHARD_WIDTH
        q(ex, f"Set(1, f=10) Set({c2}, f=10) Set({c2 + 1}, f=20)")
        (p,) = q(ex, "TopN(f, n=1)")
        assert [(x.id, x.count) for x in p.pairs] == [(10, 2)]

    def test_topn_tanimoto(self, env):
        # tanimoto = 100·|row∧src| / |row∪src| (fragment.go#top):
        # src={1,2,3,4}; row10={1..5} → 80; row20={1,2,9} → 40; row30 → 0
        _, _, ex = env
        q(ex, "Set(1, g=1) Set(2, g=1) Set(3, g=1) Set(4, g=1)")
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=10) Set(4, f=10)"
              "Set(5, f=10)")
        q(ex, "Set(1, f=20) Set(2, f=20) Set(9, f=20)")
        q(ex, "Set(7, f=30)")
        (p,) = q(ex, "TopN(f, filter=Row(g=1), tanimoto=50)")
        assert [(x.id, x.count) for x in p.pairs] == [(10, 4)]
        (p,) = q(ex, "TopN(f, filter=Row(g=1), tanimoto=40)")  # 40 inclusive
        assert [(x.id, x.count) for x in p.pairs] == [(10, 4), (20, 2)]
        (p,) = q(ex, "TopN(f, filter=Row(g=1), tanimoto=81)")
        assert p.pairs == []

    def test_topn_tanimoto_cross_shard(self, env):
        # bits split across shards: the ratio must use global counts
        _, _, ex = env
        c2 = SHARD_WIDTH
        q(ex, f"Set(1, g=1) Set({c2 + 1}, g=1)")
        q(ex, f"Set(1, f=10) Set({c2 + 1}, f=10) Set({c2 + 2}, f=10)")
        (p,) = q(ex, "TopN(f, filter=Row(g=1), tanimoto=66)")
        assert [(x.id, x.count) for x in p.pairs] == [(10, 2)]  # 2/3 ≈ 66.7

    def test_topn_tanimoto_errors(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10)")
        with pytest.raises(ExecutionError):
            q(ex, "TopN(f, tanimoto=50)")  # requires a filter
        with pytest.raises(ExecutionError):
            q(ex, "TopN(f, filter=Row(g=1), tanimoto=0)")
        with pytest.raises(ExecutionError):
            q(ex, "TopN(f, filter=Row(g=1), tanimoto=101)")

    def test_rows(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(1, f=20) Set(2, f=30)")
        (r,) = q(ex, "Rows(f)")
        np.testing.assert_array_equal(r.rows, [10, 20, 30])
        (r,) = q(ex, "Rows(f, limit=2)")
        np.testing.assert_array_equal(r.rows, [10, 20])
        (r,) = q(ex, "Rows(f, previous=10)")
        np.testing.assert_array_equal(r.rows, [20, 30])
        (r,) = q(ex, "Rows(f, column=2)")
        np.testing.assert_array_equal(r.rows, [30])

    def test_groupby(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(1, g=5) Set(2, g=6)")
        (g,) = q(ex, "GroupBy(Rows(f), Rows(g))")
        got = [([fr.row_id for fr in gc.group], gc.count) for gc in g.groups]
        assert got == [([10, 5], 1), ([10, 6], 1)]

    def test_groupby_large_row_ids(self, env):
        # row ids live in uint64 space (capped at 2^40 by the
        # fragment position encoding — fragment._check_rows, mirroring
        # the upstream bound); the columnar assembly keeps them exact
        # end to end in uint64
        _, _, ex = env
        big = (1 << 39) + 5
        q(ex, f"Set(1, f={big}) Set(2, f={big}) Set(1, g=7) Set(2, g=8)")
        (g,) = q(ex, "GroupBy(Rows(f), Rows(g))")
        got = [([fr.row_id for fr in gc.group], gc.count) for gc in g.groups]
        assert got == [([big, 7], 1), ([big, 8], 1)]
        blob = g.to_json()
        assert blob[0]["group"][0]["rowID"] == big

    def test_groupby_filter_and_aggregate(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(1, amount=100) Set(2, amount=50)")
        (g,) = q(ex, "GroupBy(Rows(f), filter=Row(amount > 60),"
                     "aggregate=Sum(field=amount))")
        assert len(g.groups) == 1
        gc = g.groups[0]
        assert gc.count == 1 and gc.agg == 100

    def test_groupby_having_count(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=20)")
        (g,) = q(ex, "GroupBy(Rows(f), having=Condition(count > 1))")
        assert [(gc.group[0].row_id, gc.count) for gc in g.groups] == \
            [(10, 2)]
        (g,) = q(ex, "GroupBy(Rows(f), having=Condition(count == 1))")
        assert [(gc.group[0].row_id, gc.count) for gc in g.groups] == \
            [(20, 1)]
        # between form
        (g,) = q(ex, "GroupBy(Rows(f), having=Condition(1 <= count <= 1))")
        assert [(gc.group[0].row_id, gc.count) for gc in g.groups] == \
            [(20, 1)]

    def test_groupby_having_sum(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=20)"
              "Set(1, amount=100) Set(2, amount=-30) Set(3, amount=5)")
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Sum(field=amount),"
                     "having=Condition(sum > 60))")
        assert [(gc.group[0].row_id, gc.count, gc.agg)
                for gc in g.groups] == [(10, 2, 70)]
        # having applies BEFORE limit
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Sum(field=amount),"
                     "having=Condition(sum < 60), limit=1)")
        assert [(gc.group[0].row_id, gc.agg) for gc in g.groups] == \
            [(20, 5)]

    def test_groupby_having_validation(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10)")
        with pytest.raises(ExecutionError):
            q(ex, "GroupBy(Rows(f), having=Condition(sum > 1))")  # no Sum
        with pytest.raises(ExecutionError):
            q(ex, "GroupBy(Rows(f), having=Condition(nope > 1))")
        with pytest.raises(ExecutionError):
            q(ex, "GroupBy(Rows(f), having=Row(f=1))")

    def test_groupby_count_min_max_aggregates(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=20)"
              "Set(1, amount=-5) Set(2, amount=8) Set(3, amount=3)")
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Count())")
        assert [(gc.group[0].row_id, gc.count, gc.agg) for gc in g.groups] \
            == [(10, 2, 2), (20, 1, 1)]
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Min(field=amount))")
        assert [(gc.group[0].row_id, gc.agg) for gc in g.groups] \
            == [(10, -5), (20, 3)]
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Max(field=amount))")
        assert [(gc.group[0].row_id, gc.agg) for gc in g.groups] \
            == [(10, 8), (20, 3)]

    def test_groupby_minmax_agg_empty_group_cells(self, env):
        # a group with no non-null aggregate columns reports agg=None
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=20) Set(2, amount=7)")
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Min(field=amount))")
        got = {gc.group[0].row_id: gc.agg for gc in g.groups}
        assert got == {10: None, 20: 7}

    def test_groupby_three_levels_oracle(self, env):
        holder, idx, ex = env
        idx.create_field("h")
        rng = np.random.default_rng(11)
        oracle: dict[str, dict[int, set[int]]] = {"f": {}, "g": {}, "h": {}}
        stmts = []
        for fld in ("f", "g", "h"):
            for _ in range(60):
                r, c = int(rng.integers(1, 5)), int(rng.integers(0, 200))
                oracle[fld].setdefault(r, set()).add(c)
                stmts.append(f"Set({c}, {fld}={r})")
        q(ex, " ".join(stmts))
        (g,) = q(ex, "GroupBy(Rows(f), Rows(g), Rows(h))")
        expect = []
        for rf in sorted(oracle["f"]):
            for rg in sorted(oracle["g"]):
                for rh in sorted(oracle["h"]):
                    n = len(oracle["f"][rf] & oracle["g"][rg]
                            & oracle["h"][rh])
                    if n:
                        expect.append(([rf, rg, rh], n))
        got = [([fr.row_id for fr in gc.group], gc.count) for gc in g.groups]
        assert got == expect

    def test_groupby_blocked_matches_unblocked(self, env, monkeypatch):
        # force tiny combination blocks: results must equal the
        # single-block run (and limit= stops the stream early)
        from pilosa_tpu.exec import groupby as gb
        _, _, ex = env
        rng = np.random.default_rng(13)
        stmts = []
        for fld in ("f", "g"):
            for _ in range(80):
                stmts.append(f"Set({int(rng.integers(0, 300))}, "
                             f"{fld}={int(rng.integers(1, 8))})")
        for col in range(0, 300, 3):
            stmts.append(f"Set({col}, amount={int(rng.integers(-50, 50))})")
        q(ex, " ".join(stmts))
        pql = "GroupBy(Rows(f), Rows(g), aggregate=Sum(field=amount))"
        (full,) = q(ex, pql)
        monkeypatch.setattr(gb, "BLOCK_OUT_BYTES", 1)  # 1 combo per block
        (blocked,) = q(ex, pql)
        as_tuples = lambda g: [([fr.row_id for fr in gc.group], gc.count,
                                gc.agg) for gc in g.groups]
        assert as_tuples(full) == as_tuples(blocked)
        (lim,) = q(ex, "GroupBy(Rows(f), Rows(g), limit=3)")
        assert len(lim.groups) == 3
        assert as_tuples(lim) == [t[:2] + (None,)
                                  for t in as_tuples(full)[:3]]

    def test_groupby_cross_shard_aggregate(self, env):
        # min/max must reduce across shards, not per shard
        _, _, ex = env
        c2 = SHARD_WIDTH + 1
        q(ex, f"Set(1, f=10) Set({c2}, f=10)"
              f"Set(1, amount=9) Set({c2}, amount=-4)")
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Min(field=amount))")
        assert [(gc.group[0].row_id, gc.count, gc.agg)
                for gc in g.groups] == [(10, 2, -4)]
        (g,) = q(ex, "GroupBy(Rows(f), aggregate=Sum(field=amount))")
        assert g.groups[0].agg == 5


class TestTimeFields:
    def test_time_range_row(self, env):
        holder, idx, ex = env
        idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        q(ex, "Set(1, t=1, 2017-01-02T00:00)"
              "Set(2, t=1, 2017-03-05T00:00)"
              "Set(3, t=1, 2018-01-01T00:00)")
        (r,) = q(ex, "Row(t=1, from=2017-01-01T00:00, to=2017-12-31T00:00)")
        np.testing.assert_array_equal(r.columns, [1, 2])
        (r_all,) = q(ex, "Row(t=1)")
        np.testing.assert_array_equal(r_all.columns, [1, 2, 3])


class TestKeys:
    def test_keyed_index_and_field(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("k", keys=True)
        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        assert q(ex, 'Set("alice", f="admin")', index="k") == [True]
        assert q(ex, 'Set("bob", f="admin")', index="k") == [True]
        (r,) = q(ex, 'Row(f="admin")', index="k")
        assert sorted(r.keys) == ["alice", "bob"]
        (p,) = q(ex, "TopN(f)", index="k")
        assert [(x.key, x.count) for x in p.pairs] == [("admin", 2)]

    def test_missing_key_reads_empty(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("k", keys=True)
        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        q(ex, 'Set("alice", f="admin")', index="k")
        (r,) = q(ex, 'Row(f="nosuch")', index="k")
        assert r.keys == []

    def test_type_mismatch_errors(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("k", keys=True)
        idx.create_field("f")
        ex = Executor(holder)
        with pytest.raises(ExecutionError):
            q(ex, "Set(1, f=1)", index="k")  # int col on keyed index


class TestPersistenceAcrossReopen:
    def test_query_after_reopen(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        ex = Executor(holder)
        q(ex, "Set(1, f=1) Set(2, f=1)")
        holder.close()

        h2 = Holder(str(tmp_path)).open()
        ex2 = Executor(h2)
        assert q(ex2, "Count(Row(f=1))") == [2]

    def test_plane_cache_invalidation(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1)")
        assert q(ex, "Count(Row(f=1))") == [1]
        q(ex, "Set(2, f=1)")  # mutation bumps generation → rebuild
        assert q(ex, "Count(Row(f=1))") == [2]


class TestTimeRangeClamping:
    def test_open_ended_range_terminates(self, env):
        """Regression: omitted from/to used year-1/year-9999 sentinels and
        enumerated the whole calendar at the finest quantum."""
        holder, idx, ex = env
        idx.create_field("td", FieldOptions(type="time", time_quantum="YMDH"))
        q(ex, "Set(1, td=1, 2020-01-02T03:00) Set(2, td=1, 2020-06-01T00:00)")
        (r,) = q(ex, "Row(td=1, from=2020-01-01T00:00)")
        np.testing.assert_array_equal(r.columns, [1, 2])
        (r,) = q(ex, "Row(td=1, to=2020-05-01T00:00)")
        np.testing.assert_array_equal(r.columns, [1])

    def test_range_on_field_without_views(self, env):
        holder, idx, ex = env
        idx.create_field("t2", FieldOptions(type="time", time_quantum="D"))
        (r,) = q(ex, "Row(t2=1, from=2020-01-01T00:00, to=2021-01-01T00:00)")
        assert len(r.columns) == 0


class TestParityBatch:
    def test_shift(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(40, f=1)")
        (r,) = q(ex, "Shift(Row(f=1), n=1)")
        np.testing.assert_array_equal(r.columns, [2, 41])
        (r2,) = q(ex, "Shift(Row(f=1), n=40)")  # crosses word boundary
        np.testing.assert_array_equal(r2.columns, [41, 80])
        assert q(ex, "Count(Shift(Row(f=1), n=1))") == [2]

    def test_shift_drops_at_shard_boundary(self, env):
        _, _, ex = env
        last = SHARD_WIDTH - 1
        q(ex, f"Set({last}, f=1) Set(0, f=1)")
        (r,) = q(ex, "Shift(Row(f=1), n=1)")
        np.testing.assert_array_equal(r.columns, [1])

    def test_union_rows(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=20) Set(3, f=30) Set(2, g=1)")
        (r,) = q(ex, "UnionRows(Rows(f))")
        np.testing.assert_array_equal(r.columns, [1, 2, 3])
        (r2,) = q(ex, "UnionRows(Rows(f, limit=2))")
        np.testing.assert_array_equal(r2.columns, [1, 2])
        assert q(ex, "Count(Intersect(UnionRows(Rows(f)), Row(g=1)))") == [1]

    def test_all_limit_offset(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1) Set(3, f=1) Set(4, f=1)")
        (r,) = q(ex, "All(limit=2)")
        np.testing.assert_array_equal(r.columns, [1, 2])
        (r2,) = q(ex, "All(limit=2, offset=1)")
        np.testing.assert_array_equal(r2.columns, [2, 3])

    def test_profile_spans(self, tmp_path):
        from pilosa_tpu.api import API
        from pilosa_tpu.store import Holder
        holder = Holder(str(tmp_path)).open()
        holder.create_index("i").create_field("f")
        api = API(holder)
        api.query("i", "Set(1, f=1)")
        out = api.query("i", "Count(Row(f=1)) Row(f=1)", profile=True)
        assert out["results"][0] == 1
        # ONE tree per query (r9): a "query" root span wraps the
        # executor call spans (+ stage.* attribution children)
        (root,) = out["profile"]
        assert root["name"] == "query" and root["tags"]["node"] == "local"
        names = [c["name"] for c in root["children"]
                 if c["name"].startswith("executor.")]
        assert names == ["executor.Count", "executor.Row"]
        assert any(c["name"].startswith("stage.")
                   for c in root["children"])
        assert root["durationUs"] >= 0
        assert out["traceId"] == root["traceId"]


class TestCountBatching:
    def test_batched_counts_match_individual(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1) Set(2, g=1) Set(3, g=1)"
              "Set(1, amount=5) Set(2, amount=-3)")
        batch = q(ex, "Count(Row(f=1)) Count(Row(g=1)) "
                      "Count(Intersect(Row(f=1), Row(g=1))) "
                      "Count(Row(amount > 0))")
        assert batch == [2, 2, 1, 1]
        # individually identical
        for pql, expect in [("Count(Row(f=1))", 2), ("Count(Row(g=1))", 2)]:
            assert q(ex, pql) == [expect]

    def test_writes_between_counts_stay_ordered(self, env):
        _, _, ex = env
        out = q(ex, "Set(1, f=1) Count(Row(f=1)) Set(2, f=1) Count(Row(f=1))")
        assert out == [True, 1, True, 2]

    def test_one_program_for_the_batch(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(1, g=1)")
        before = len(ex.fused._programs)
        q(ex, "Count(Row(f=1)) Count(Row(g=1))")
        after = len(ex.fused._programs)
        assert after == before + 1  # one count-batch program, not two
        # repeat hits the cache
        q(ex, "Count(Row(g=1)) Count(Row(f=1))")
        q(ex, "Count(Row(f=1)) Count(Row(g=1))")
        assert len(ex.fused._programs) <= after + 1


class TestParityBatch2:
    def test_groupby_previous_paging(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(1, f=20) Set(1, g=5) Set(1, g=6)")
        (all_g,) = q(ex, "GroupBy(Rows(f), Rows(g))")
        combos = [tuple(fr.row_id for fr in gc.group) for gc in all_g.groups]
        assert combos == [(10, 5), (10, 6), (20, 5), (20, 6)]
        (page,) = q(ex, "GroupBy(Rows(f), Rows(g), previous=[10, 6], limit=1)")
        assert [tuple(fr.row_id for fr in gc.group)
                for gc in page.groups] == [(20, 5)]

    def test_rows_like(self, tmp_path):
        from pilosa_tpu.store import FieldOptions, Holder
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f", FieldOptions(keys=True))
        ex = Executor(holder)
        ex.execute("i", 'Set(1, f="apple") Set(2, f="apricot") Set(3, f="banana")')
        (r,) = ex.execute("i", 'Rows(f, like="ap%")')
        assert sorted(r.keys) == ["apple", "apricot"]
        (r2,) = ex.execute("i", 'Rows(f, like="_anana")')
        assert r2.keys == ["banana"]

    def test_rows_like_requires_keys(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1)")
        with pytest.raises(ExecutionError):
            q(ex, 'Rows(f, like="x%")')

    def test_exclude_columns(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1)")
        (r,) = q(ex, "Options(Row(f=1), excludeColumns=true)")
        assert len(r.columns) == 0


class TestDistinct:
    def test_distinct_values(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=5) Set(2, amount=-3) Set(3, amount=5)"
              "Set(4, amount=0) Set(5, amount=977)")
        (d,) = q(ex, "Distinct(field=amount)")
        assert d.values == [-3, 0, 5, 977]

    def test_distinct_with_filter(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=5) Set(2, amount=9) Set(1, f=1)")
        (d,) = q(ex, "Distinct(Row(f=1), field=amount)")
        assert d.values == [5]

    def test_distinct_cross_shard(self, env):
        _, _, ex = env
        c2 = SHARD_WIDTH + 1
        q(ex, f"Set(1, amount=7) Set({c2}, amount=7) Set({c2 + 1}, amount=9)")
        (d,) = q(ex, "Distinct(field=amount)")
        assert d.values == [7, 9]

    def test_distinct_decimal(self, tmp_path):
        from pilosa_tpu.store import FieldOptions, Holder
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("d", FieldOptions(type="decimal", scale=2))
        ex = Executor(holder)
        ex.execute("i", "Set(1, d=1.25) Set(2, d=-0.5)")
        (r,) = ex.execute("i", "Distinct(field=d)")
        assert r.values == [-0.5, 1.25]


class TestLegacyRangeSyntax:
    def test_positional_time_range(self, env):
        holder, idx, ex = env
        idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        q(ex, "Set(1, t=1, 2017-01-02T00:00) Set(2, t=1, 2017-05-01T00:00)"
              "Set(3, t=1, 2018-06-01T00:00)")
        (r,) = q(ex, "Range(t=1, 2017-01-01T00:00, 2017-12-31T00:00)")
        np.testing.assert_array_equal(r.columns, [1, 2])
        # round-trips through the printer too
        from pilosa_tpu.pql import parse
        src = "Range(t=1, 2017-01-01T00:00, 2017-12-31T00:00)"
        assert parse(str(parse(src))) == parse(src)


class TestStreamingTopN:
    def test_streamed_matches_resident(self, tmp_path, rng):
        """Force the streaming path with a tiny plane budget; results
        must match a resident-plane executor exactly."""
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        n = 4000
        rows = rng.integers(0, 500, size=n).astype(np.uint64)
        cols = rng.choice(2 * SHARD_WIDTH, size=n, replace=False).astype(np.uint64)
        idx.field("f").import_bits(rows, cols)
        idx.note_columns(cols)

        resident = Executor(holder)
        # budget too small for the ~500-row plane -> streaming path
        streaming = Executor(holder, plane_budget=8 << 20)
        for pql in ["TopN(f, n=10)", "TopN(f)", "TopN(f, ids=[3, 7, 9])"]:
            (a,) = resident.execute("i", pql)
            (b,) = streaming.execute("i", pql)
            assert [(p.id, p.count) for p in a.pairs] == \
                   [(p.id, p.count) for p in b.pairs], pql

    def test_streamed_with_filter(self, tmp_path, rng):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        rows = rng.integers(0, 300, size=2000).astype(np.uint64)
        cols = rng.choice(SHARD_WIDTH, size=2000, replace=False).astype(np.uint64)
        idx.field("f").import_bits(rows, cols)
        idx.field("g").import_bits(np.ones(1000, np.uint64), cols[:1000])
        idx.note_columns(cols)
        resident = Executor(holder)
        streaming = Executor(holder, plane_budget=4 << 20)
        (a,) = resident.execute("i", "TopN(f, filter=Row(g=1), n=5)")
        (b,) = streaming.execute("i", "TopN(f, filter=Row(g=1), n=5)")
        assert [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]

    def test_streamed_tanimoto_matches_resident(self, tmp_path, rng):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        rows = rng.integers(0, 300, size=2000).astype(np.uint64)
        cols = rng.choice(SHARD_WIDTH, size=2000, replace=False).astype(np.uint64)
        idx.field("f").import_bits(rows, cols)
        # small source row so |row∧src|/|row∪src| clears a 1% threshold
        idx.field("g").import_bits(np.ones(50, np.uint64), cols[:50])
        idx.note_columns(cols)
        resident = Executor(holder)
        streaming = Executor(holder, plane_budget=4 << 20)
        pql = "TopN(f, filter=Row(g=1), tanimoto=1)"
        (a,) = resident.execute("i", pql)
        (b,) = streaming.execute("i", pql)
        assert a.pairs and [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]


class TestConstRowLimitExtract:
    """v2 PQL parity: ConstRow / Limit / Extract."""

    def test_constrow(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(5, f=10) Set(9, f=10)")
        (r,) = q(ex, "ConstRow(columns=[1, 9, 77])")
        np.testing.assert_array_equal(r.columns, [1, 9, 77])
        (r,) = q(ex, "Intersect(Row(f=10), ConstRow(columns=[1, 9, 77]))")
        np.testing.assert_array_equal(r.columns, [1, 9])
        assert q(ex, "Count(ConstRow(columns=[]))") == [0]

    def test_limit(self, env):
        _, _, ex = env
        c2 = SHARD_WIDTH + 3
        q(ex, f"Set(1, f=10) Set(5, f=10) Set(9, f=10) Set({c2}, f=10)")
        (r,) = q(ex, "Limit(Row(f=10), limit=2)")
        np.testing.assert_array_equal(r.columns, [1, 5])
        (r,) = q(ex, "Limit(Row(f=10), limit=2, offset=1)")
        np.testing.assert_array_equal(r.columns, [5, 9])
        (r,) = q(ex, "Limit(Row(f=10), offset=3)")  # crosses shards
        np.testing.assert_array_equal(r.columns, [c2])
        assert q(ex, "Count(Limit(Row(f=10), limit=3))") == [3]
        with pytest.raises(ExecutionError):
            q(ex, "Limit(Row(f=10), limit=-1)")

    def test_extract(self, env):
        holder, idx, ex = env
        q(ex, "Set(1, f=10) Set(1, f=20) Set(2, f=10) Set(3, g=7)"
              "Set(1, amount=-5) Set(3, amount=8)")
        (r,) = q(ex, "Extract(ConstRow(columns=[1, 2, 3]),"
                     "Rows(f), Rows(g), Rows(amount))")
        assert r.field_specs == [("f", "set"), ("g", "set"),
                                 ("amount", "int")]
        assert r.columns == [
            (1, [[10, 20], [], -5]),
            (2, [[10], [], None]),
            (3, [[], [7], 8]),
        ]

    def test_extract_with_limit_filter(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=10)")
        (r,) = q(ex, "Extract(Limit(Row(f=10), limit=2), Rows(f))")
        assert [c for c, _ in r.columns] == [1, 2]

    def test_extract_column_cap(self, env, monkeypatch):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=10)")
        monkeypatch.setattr(Executor, "MAX_EXTRACT_COLUMNS", 2)
        with pytest.raises(ExecutionError):
            q(ex, "Extract(Row(f=10), Rows(f))")

    def test_extract_bool_and_mutex(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("b", FieldOptions(type="bool"))
        idx.create_field("m", FieldOptions(type="mutex"))
        ex = Executor(holder)
        q(ex, "Set(1, b=true) Set(2, b=false) Set(1, m=5) Set(1, m=9)")
        (r,) = q(ex, "Extract(ConstRow(columns=[1, 2, 4]),"
                     "Rows(b), Rows(m))")
        assert r.columns == [
            (1, [True, 9]),   # mutex: last Set wins
            (2, [False, None]),
            (4, [None, None]),
        ]


class TestSparseTopN:
    """Container-blocked sparse residency (engine/sparse.py): fields too
    big for a dense plane stay device-resident as per-bit triplets; every
    representation must agree with the dense resident path."""

    def _setup(self, tmp_path, rng, n_rows=500, n_bits=4000):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        idx.create_field("g")
        rows = rng.integers(0, n_rows, size=n_bits).astype(np.uint64)
        cols = rng.choice(SHARD_WIDTH + 1000, size=n_bits,
                          replace=False).astype(np.uint64)
        idx.field("f").import_bits(rows, cols)  # spans 2 shards
        # a few columns in a second row of f: a single-valued field of
        # this width is held as a bit-sliced code, not sparse triplets
        idx.field("f").import_bits((rows[:8] + 1) % n_rows, cols[:8])
        idx.field("g").import_bits(np.ones(n_bits // 2, np.uint64),
                                   cols[: n_bits // 2])
        idx.create_field("h")  # small source row: tanimoto can pass
        idx.field("h").import_bits(np.ones(40, np.uint64), cols[:40])
        idx.note_columns(cols)
        resident = Executor(holder)
        # dense (512-row bucket × 2 shards = 128MB) over budget;
        # sparse (4000 bits × 12B) well under → sparse path
        sparse_ex = Executor(holder, plane_budget=1 << 20)
        return resident, sparse_ex

    def test_sparse_matches_resident(self, tmp_path, rng):
        resident, sparse_ex = self._setup(tmp_path, rng)
        for pql in ["TopN(f, filter=Row(g=1), n=10)",
                    "TopN(f, filter=Row(g=1))",
                    "TopN(f, filter=Row(g=1), ids=[3, 7, 9])",
                    "TopN(f, filter=Row(h=1), tanimoto=1)"]:
            (a,) = resident.execute("i", pql)
            (b,) = sparse_ex.execute("i", pql)
            assert a.pairs, pql  # must exercise non-empty results
            assert [(p.id, p.count) for p in a.pairs] == \
                   [(p.id, p.count) for p in b.pairs], pql
        # the sparse residency is cached on device, not per-query
        assert any(k[0] == "sparse" for k in sparse_ex.planes._entries)

    def test_unfiltered_uses_host_cards(self, tmp_path, rng):
        resident, sparse_ex = self._setup(tmp_path, rng)
        (a,) = resident.execute("i", "TopN(f, n=20)")
        (b,) = sparse_ex.execute("i", "TopN(f, n=20)")
        assert [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]
        # no device representation needed for unfiltered TopN
        assert not any(k[0] in ("sparse", "plane")
                       for k in sparse_ex.planes._entries)

    def test_streaming_when_sparse_over_budget(self, tmp_path, rng):
        resident, _ = self._setup(tmp_path, rng)
        holder = resident.holder
        tiny = Executor(holder, plane_budget=16 << 10)  # < bits × 12
        (a,) = resident.execute("i", "TopN(f, filter=Row(g=1), n=10)")
        (b,) = tiny.execute("i", "TopN(f, filter=Row(g=1), n=10)")
        assert [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]

    def test_sparse_invalidates_on_mutation(self, tmp_path, rng):
        resident, sparse_ex = self._setup(tmp_path, rng)
        pql = "TopN(f, filter=Row(g=1), n=5)"
        sparse_ex.execute("i", pql)
        # mutate: a column of g's row 1 gains an f bit in a fresh row
        resident.execute("i", "Set(0, g=1) Set(0, f=499)")
        (a,) = resident.execute("i", pql)
        (b,) = sparse_ex.execute("i", pql)
        assert [(p.id, p.count) for p in a.pairs] == \
               [(p.id, p.count) for p in b.pairs]


class TestReservedKeyScoping:
    def test_field_named_like_option(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("n", FieldOptions(type="int", min=0, max=1000))
        idx.create_field("limit")
        ex = Executor(holder)
        assert ex.execute("i", "Set(5, n=777)") == [True]
        (s,) = ex.execute("i", "Sum(field=n)")
        assert (s.value, s.count) == (777, 1)
        assert ex.execute("i", "Set(5, limit=3)") == [True]
        (r,) = ex.execute("i", "Row(limit=3)")
        np.testing.assert_array_equal(r.columns, [5])

    def test_ambiguous_args_is_query_error(self, env):
        _, _, ex = env
        with pytest.raises(ExecutionError):
            q(ex, "Set(5, f=1, g=2)")


class TestPercentile:
    def test_percentiles(self, env):
        _, _, ex = env
        vals = list(range(1, 101))  # 1..100 on cols 1..100
        sets = " ".join(f"Set({c}, amount={v})"
                        for c, v in zip(range(1, 101), vals))
        q(ex, sets)
        (p50,) = q(ex, "Percentile(field=amount, nth=50)")
        assert p50.value == 50
        (p99,) = q(ex, "Percentile(field=amount, nth=99)")
        assert p99.value == 99
        (p100,) = q(ex, "Percentile(field=amount, nth=100)")
        assert p100.value == 100

    def test_percentile_negative_and_filter(self, env):
        _, _, ex = env
        q(ex, "Set(1, amount=-10) Set(2, amount=0) Set(3, amount=10)"
              "Set(1, f=1) Set(2, f=1)")
        (p,) = q(ex, "Percentile(field=amount, nth=50)")
        assert p.value == 0
        (pf,) = q(ex, "Percentile(Row(f=1), field=amount, nth=100)")
        assert pf.value == 0  # among cols {1, 2}: values {-10, 0}

    def test_percentile_empty(self, env):
        _, _, ex = env
        (p,) = q(ex, "Percentile(field=amount, nth=50)")
        assert (p.value, p.count) == (0, 0)

    def test_percentile_decimal(self, tmp_path):
        from pilosa_tpu.store import FieldOptions, Holder
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("d", FieldOptions(type="decimal", scale=1))
        ex = Executor(holder)
        ex.execute("i", "Set(1, d=1.5) Set(2, d=2.5) Set(3, d=9.5)")
        (p,) = ex.execute("i", "Percentile(field=d, nth=50)")
        assert p.value == 2.5


class TestIncludesColumn:
    def test_includes(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=1) Set(2, f=1)")
        assert q(ex, "IncludesColumn(Row(f=1), column=1)") == [True]
        assert q(ex, "IncludesColumn(Row(f=1), column=3)") == [False]
        assert q(ex, "IncludesColumn(Intersect(Row(f=1), Row(g=1)), column=1)") == [False]


class TestExtractBsiDevicePath:
    """VERDICT r2 #6: BSI Extract values come off the resident bit-plane
    in one device program — oracle: per-column ``field.value`` reads."""

    def test_bulk_int_extract_matches_field_value(self, tmp_path, rng):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("v", FieldOptions(type="int", min=-100_000,
                                           max=100_000))
        n = 3000
        # columns spread over 3 shards; ~1/3 of probed columns null
        cols = np.unique(rng.choice(3 * SHARD_WIDTH, size=n,
                                    replace=False)).astype(np.uint64)
        vals = rng.integers(-100_000, 100_000, size=len(cols))
        idx.field("v").import_values(cols, vals)
        probe = np.unique(np.concatenate(
            [cols[::2],
             rng.choice(3 * SHARD_WIDTH, size=n // 2).astype(np.uint64)]))
        idx.note_columns(probe)  # make probed columns extractable
        ex = Executor(holder)
        cols_pql = ",".join(str(int(c)) for c in probe)
        (r,) = ex.execute("i", f"Extract(ConstRow(columns=[{cols_pql}]),"
                               "Rows(v))")
        field = idx.field("v")
        got = {c: v[0] for c, v in r.columns}
        for c in probe:
            v, ok = field.value(int(c))
            assert got[int(c)] == (v if ok else None), int(c)

    def test_decimal_and_timestamp_extract(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("d", FieldOptions(type="decimal", scale=2))
        idx.create_field("t", FieldOptions(type="timestamp"))
        idx.field("d").import_values(np.array([1, 2], np.uint64),
                                     [3.25, -0.5])
        idx.field("t").import_values(np.array([1], np.uint64),
                                     ["2021-06-01T12:00:00"])
        idx.note_columns(np.array([1, 2, 3], np.uint64))
        ex = Executor(holder)
        (r,) = ex.execute("i", "Extract(ConstRow(columns=[1, 2, 3]),"
                               "Rows(d), Rows(t))")
        by_col = {c: v for c, v in r.columns}
        dfield, tfield = idx.field("d"), idx.field("t")
        for c in (1, 2, 3):
            dv, dok = dfield.value(c)
            tv, tok = tfield.value(c)
            assert by_col[c][0] == (dv if dok else None)
            assert by_col[c][1] == (tv if tok else None)


class TestCountBatchPlanePath:
    """The same-field Count-batch whole-plane fast path must be
    indistinguishable from per-call execution."""

    def test_batched_counts_match_individual(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10) Set(3, f=20)"
              f"Set({SHARD_WIDTH + 4}, f=20) Set(5, f=30)")
        pql = ("Count(Row(f=10)) Count(Row(f=20)) Count(Row(f=30))"
               "Count(Row(f=99))")  # 99: absent row counts 0
        batched = q(ex, pql)
        singles = [q(ex, p)[0] for p in
                   ["Count(Row(f=10))", "Count(Row(f=20))",
                    "Count(Row(f=30))", "Count(Row(f=99))"]]
        assert batched == singles == [2, 2, 1, 0]

    def test_mixed_fields_fall_back(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, g=7) Set(3, amount=5)")
        assert q(ex, "Count(Row(f=10)) Count(Row(g=7))"
                     "Count(Row(amount > 0))") == [1, 1, 1]

    def test_write_between_counts_stays_ordered(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10)")
        out = q(ex, "Count(Row(f=10)) Set(2, f=10) Count(Row(f=10))")
        assert out == [1, True, 2]

    def test_empty_shard_restriction(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) Set(2, f=10)")
        # shards=[]: both the batched and single forms answer zeros,
        # never a ZeroDivisionError (review r3 finding)
        assert q(ex, "Count(Row(f=10)) Count(Row(f=10))",
                 shards=[]) == [0, 0]
        assert q(ex, "Count(Row(f=10))", shards=[]) == [0]


class TestRowAttrsOnRowResults:
    def test_row_result_carries_row_attrs(self, env):
        _, _, ex = env
        q(ex, "Set(1, f=10) SetRowAttrs(f, 10, team=\"infra\", rank=3)")
        (r,) = q(ex, "Row(f=10)")
        assert r.row_attrs == {"team": "infra", "rank": 3}
        # excludeRowAttrs suppresses (reference: QueryRequest flag)
        (r2,) = q(ex, "Row(f=10, excludeRowAttrs=true)")
        assert r2.row_attrs is None
        # rows with no attrs attach nothing
        (r3,) = q(ex, "Row(f=99)")
        assert r3.row_attrs is None
        # composite calls don't attach
        (r4,) = q(ex, "Union(Row(f=10))")
        assert r4.row_attrs is None

    def test_read_never_creates_attr_store(self, env):
        import os
        holder, idx, ex = env
        q(ex, "Set(1, g=5)")
        (r,) = q(ex, "Row(g=5)")
        assert r.row_attrs is None
        assert not os.path.exists(
            os.path.join(idx.field("g").path, "_attrs.db"))


# -- a request's calls group by family (PR 31) ---------------------------------

N_F_ROWS = 8


@pytest.fixture(scope="module")
def grouped_env(tmp_path_factory):
    """Three shards: set field ``f`` of eight rows, int fields ``a``
    (negatives too) and ``b``; one executor per serving mode over it
    and an op-at-a-time reference (batcher off, one call a request)."""
    from pilosa_tpu.api import API
    from pilosa_tpu.obs import Stats
    holder = Holder(str(tmp_path_factory.mktemp("grouped"))).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("a", FieldOptions(type="int", min=-500, max=500))
    idx.create_field("b", FieldOptions(type="int", min=0, max=1000))
    ref = Executor(holder, count_batch_window=0)
    api = API(holder, ref)
    rng = np.random.default_rng(31)
    width = 3 * SHARD_WIDTH
    data = {"f": {}}
    for row in range(N_F_ROWS):
        cols = rng.choice(width, 300, replace=False)
        data["f"][row] = set(cols.tolist())
        api.import_bits("i", "f", row_ids=[row] * len(cols),
                        col_ids=cols.tolist())
    for name, lo, hi in (("a", -500, 500), ("b", 0, 1000)):
        cols = rng.choice(width, 1500, replace=False)
        # every ``f`` column carries a value, so filtered sums are not 0
        cols = np.union1d(cols, np.fromiter(
            set().union(*data["f"].values()), dtype=np.int64))
        vals = rng.integers(lo, hi, len(cols))
        data[name] = dict(zip(cols.tolist(), vals.tolist()))
        api.import_values("i", name, col_ids=cols.tolist(),
                          values=vals.tolist())
    executors = {}

    def executor(mode: str):
        if mode not in executors:
            executors[mode] = Executor(
                holder, stats=Stats(),
                count_batch_window=0 if mode == "off" else "adaptive")
        return executors[mode]

    yield executor, ref, data
    holder.close()


def _grouped_request(k: int) -> list[str]:
    """Interleaved calls of five families and a TopN: K filtered Sums
    over ``a`` (filters repeat past eight), K Counts, Min / Max over
    ``a`` with and without a filter, unfiltered Sums over a second BSI
    field, an absent row, a TopN in the middle."""
    calls = []
    for i in range(k):
        row = i % N_F_ROWS
        calls.append(f"Sum(Row(f={row}), field=a)")
        calls.append(f"Count(Row(f={row}))")
        if i % 3 == 0:
            calls.append(f"Min(Row(f={row}), field=a)")
        if i % 3 == 1:
            calls.append("Max(field=a)")
        if i % 4 == 2:
            calls.append("Sum(field=b)")
        if i == 0:
            calls += ["Sum(Row(f=99), field=a)", "Count(Row(f=99))"]
        if i == 1:
            calls.append("TopN(f, n=3)")
    return calls


def _family_sizes(calls: list[str]) -> dict:
    """{family: calls} of the families that hold more than one call."""
    sizes: dict = {}
    for c in calls:
        fam = ("count" if c.startswith("Count") else
               "sum" if c.startswith("Sum") else
               "minmax" if c.startswith(("Min", "Max")) else None)
        key = (fam, "b" if "field=b" in c else "a")
        if fam is not None:
            sizes[key] = sizes.get(key, 0) + 1
    return {k: n for k, n in sizes.items() if n > 1}


def _counter(ex, name: str) -> dict:
    return {dict(k)["family"]: v for k, v in
            ex.stats.snapshot()["counters"].get(name, {}).items()}


def _hold_fast_lane(batcher):
    """A second thread takes the solo fast lane and keeps it, so the
    request under test meets a busy lane and enqueues into the
    window.  (Once a window has carried several items the adaptive
    window is open and the lane is shut to every caller: the same
    path, nothing to hold.)  Returns the function that lets go."""
    import threading
    entered, release = threading.Event(), threading.Event()

    def hold():
        held = batcher._fl_try_enter()
        assert held or batcher._win > 0
        entered.set()
        release.wait(120)
        if held:
            batcher._fl_leave()
    t = threading.Thread(target=hold, daemon=True)
    t.start()
    assert entered.wait(30)

    def let_go():
        release.set()
        t.join(30)
    return let_go


@pytest.mark.parametrize("shards", [None, [0, 2]],
                         ids=["all_shards", "shards_0_2"])
@pytest.mark.parametrize("mode", ["off", "lane", "window"])
@pytest.mark.parametrize("k", [2, 3, 10, 17])
def test_a_read_only_request_groups_its_calls_by_family(
        k, mode, shards, grouped_env):
    """Call for call, and in call order, the grouped request answers
    what the op-at-a-time reference answers — with the batcher off, on
    the solo fast lane, and in the window when the lane is busy."""
    from pilosa_tpu.exec import result_to_json
    executor, ref, data = grouped_env
    ex = executor(mode)
    calls = _grouped_request(k)
    want = [result_to_json(ref.execute("i", c, shards=shards)[0])
            for c in calls]
    if shards is None:
        # the reference itself, against the data it was built from
        cols = data["f"][1]
        i = calls.index("Sum(Row(f=1), field=a)")
        assert want[i] == {"value": sum(data["a"][c] for c in cols),
                           "count": len(cols)}
        assert want[calls.index("Count(Row(f=1))")] == len(cols)
    groups0 = _counter(ex, "request_call_groups_total")
    carried0 = _counter(ex, "request_grouped_calls_total")
    lanes0 = ex.ledger.solo_dispatches
    let_go = _hold_fast_lane(ex.batcher) if mode == "window" else None
    try:
        got = ex.execute("i", " ".join(calls), shards=shards)
    finally:
        if let_go is not None:
            let_go()
    assert [result_to_json(r) for r in got] == want
    sizes = _family_sizes(calls)
    groups = _counter(ex, "request_call_groups_total")
    carried = _counter(ex, "request_grouped_calls_total")
    for fam in ("count", "sum", "minmax"):
        mine = [n for (f, _), n in sizes.items() if f == fam]
        assert groups[fam] - groups0[fam] == len(mine), (fam, sizes)
        assert carried[fam] - carried0[fam] == sum(mine), (fam, sizes)
    if mode == "window":
        # nothing of this request took the lane another thread held
        assert ex.ledger.solo_dispatches == lanes0
    if mode == "lane":
        # one solo launch per group and per call that ran alone
        alone = len(calls) - sum(sizes.values())
        assert ex.ledger.solo_dispatches - lanes0 == len(sizes) + alone


@pytest.mark.parametrize("read,write,before,after", [
    ("Sum(field=amount)", "Set(3, amount=7)",
     {"value": 5, "count": 1}, {"value": 12, "count": 2}),
    ("Count(Row(f=1))", "Set(3, f=1)", 1, 2),
    ("Min(field=amount)", "Set(3, amount=-9)",
     {"value": 5, "count": 1}, {"value": -9, "count": 1}),
])
def test_a_write_between_two_reads_keeps_the_request_ordered(
        read, write, before, after, env):
    from pilosa_tpu.exec import result_to_json
    _, _, ex = env
    q(ex, "Set(1, f=1) Set(1, amount=5)")
    moved0 = ex.stats.snapshot()["counters"].get(
        "request_call_groups_total")
    out = q(ex, f"{read} {write} {read}")
    assert [result_to_json(r) for r in out] == [before, True, after]
    # the two reads are one family, but a write stands between them
    assert ex.stats.snapshot()["counters"].get(
        "request_call_groups_total") == moved0


@pytest.mark.parametrize("pql,builds_plane", [
    # the Sums come first: their filters put rows 1 and 2 on the
    # device one by one, and the Count group counts those
    ("Sum(Row(f=1), field=amount) Count(Row(f=1)) "
     "Sum(Row(f=2), field=amount) Count(Row(f=2))", False),
    # nothing resident yet: the Count group admits the whole plane
    ("Count(Row(f=1)) Count(Row(f=2))", True),
])
def test_a_count_group_builds_no_plane_beside_resident_rows(
        pql, builds_plane, env):
    from pilosa_tpu.store.view import VIEW_STANDARD
    _, idx, ex = env
    q(ex, "Set(1, f=1) Set(2, f=1) Set(2, f=2) "
          "Set(1, amount=5) Set(2, amount=7)")
    shards = ex._shards_for(idx, None, None)
    for _ in range(2):
        out = q(ex, pql)
        ex.planes.wait_builds()
    assert [r for r in out if isinstance(r, int)] == [2, 1]
    assert ex.planes.has_entry("i", idx.field("f"), VIEW_STANDARD,
                               shards) is builds_plane


def test_calls_under_one_row_filter_share_one_scan(env):
    """A bare ``Row`` filter is the resident row itself (no identity
    program, no copy), so the calls that name it are ONE item of the
    group's program: two Sums and a Max under ``Row(f=1)`` and a Sum
    under ``Row(f=2)`` compile a two-item Sum program and a one-item
    Min/Max program."""
    _, _, ex = env
    q(ex, "Set(1, f=1) Set(2, f=1) Set(2, f=2) "
          "Set(1, amount=5) Set(2, amount=7)")
    out = q(ex, "Sum(Row(f=1), field=amount) Sum(Row(f=2), field=amount) "
                "Sum(Row(f=1), field=amount) Max(Row(f=1), field=amount) "
                "Min(Row(f=1), field=amount)")
    assert [(r.value, r.count) for r in out] == [
        (12, 2), (7, 1), (12, 2), (7, 1), (5, 1)]
    widths = {key[0][0]: len(key[0][3]) for key in ex.fused._programs
              if key[0][0] in ("sum-plane", "minmax-plane")}
    assert widths == {"sum-plane": 2, "minmax-plane": 1}


def test_a_grouped_request_reports_the_earliest_failing_call(env):
    """The Count group runs first and its last member names a field
    that does not exist; in call order the Sum's missing field fails
    before it, and that is the error the request reports."""
    _, _, ex = env
    q(ex, "Set(1, f=1)")
    with pytest.raises(ExecutionError, match="nosuch_a"):
        q(ex, "Count(Row(f=1)) Sum(field=nosuch_a) "
              "Count(Row(nosuch_b=1))")
