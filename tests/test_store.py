"""Storage tree tests: RowBits, fragment persistence + op-log replay,
field types (set/int/time/mutex/bool), holder reopen — the rebuild's
equivalent of ``fragment_test.go`` / ``field_test.go`` temp-dir fixtures
with crash-replay (SURVEY.md §5)."""

import os
from datetime import datetime

import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.store import (EXISTENCE_FIELD, FieldOptions, Fragment, Holder,
                              RowBits)
from pilosa_tpu.store import timeq
from pilosa_tpu.store.oplog import OpLog, OP_SET_BITS


class TestRowBits:
    def test_add_remove(self):
        r = RowBits()
        assert r.add(np.array([1, 5, 9])) == 3
        assert r.add(np.array([5, 7])) == 1
        assert r.cardinality == 4
        assert r.remove(np.array([5, 100])) == 1
        np.testing.assert_array_equal(r.columns(), [1, 7, 9])

    def test_dense_conversion(self, rng):
        cols = rng.choice(SHARD_WIDTH, size=40000, replace=False)
        r = RowBits.from_columns(cols)
        assert r._words is not None  # crossed DENSE_THRESHOLD
        np.testing.assert_array_equal(r.columns(), np.sort(cols))
        assert r.contains(int(cols[0]))

    def test_dense_mutation(self, rng):
        cols = rng.choice(SHARD_WIDTH, size=40000, replace=False)
        r = RowBits.from_columns(cols)
        extra = np.setdiff1d(np.arange(50000, 50100, dtype=np.uint32), cols)
        assert r.add(extra) == len(extra)
        assert r.remove(extra) == len(extra)
        np.testing.assert_array_equal(r.columns(), np.sort(cols))

    def test_words_round_trip(self, rng):
        cols = rng.choice(SHARD_WIDTH, size=1000, replace=False)
        r = RowBits.from_columns(cols)
        r2 = RowBits.from_words(r.words())
        np.testing.assert_array_equal(r2.columns(), np.sort(cols))

    def test_out_of_range(self):
        with pytest.raises(ValueError):
            RowBits.from_columns(np.array([SHARD_WIDTH]))


class TestFragment:
    def test_set_clear_persist(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        assert f.set_bit(3, 100)
        assert not f.set_bit(3, 100)  # already set
        assert f.set_bit(7, 200)
        assert f.clear_bit(7, 200)
        f.close()

        g = Fragment(path, 0).open()
        assert g.row(3).contains(100)
        assert not g.row(7).any()
        assert g.row_ids() == [3]

    def test_oplog_replay_without_snapshot(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bits(np.array([1, 1, 2], np.uint64), np.array([10, 11, 12], np.uint64))
        # no close/snapshot — simulate crash; oplog alone must restore
        g = Fragment(path, 0).open()
        assert g.row(1).cardinality == 2
        assert g.row(2).contains(12)

    def test_torn_oplog_tail(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bit(1, 1)
        f.set_bit(2, 2)
        with open(path + ".oplog", "ab") as fh:
            fh.write(b"\x01\x02\x03")  # torn partial record
        g = Fragment(path, 0).open()
        assert g.row(1).contains(1) and g.row(2).contains(2)

    def test_auto_snapshot_at_max_op_n(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0, max_op_n=10).open()
        for i in range(12):
            f.set_bit(0, i)
        assert f.op_n <= 10
        assert os.path.exists(path)
        g = Fragment(path, 0).open()
        assert g.row(0).cardinality == 12

    def test_set_row_and_clear_row(self, tmp_path):
        f = Fragment(str(tmp_path / "0"), 0).open()
        f.set_bits(np.array([5, 5, 5], np.uint64), np.array([1, 2, 3], np.uint64))
        assert f.set_row(5, np.array([2, 9]))
        np.testing.assert_array_equal(f.row(5).columns(), [2, 9])
        assert f.clear_row(5) == 2
        assert not f.row(5).any()

    def test_blocks_checksums(self, tmp_path):
        f = Fragment(str(tmp_path / "a"), 0).open()
        g = Fragment(str(tmp_path / "b"), 0).open()
        f.set_bit(5, 100)
        g.set_bit(5, 100)
        assert f.blocks() == g.blocks()
        g.set_bit(205, 1)  # different block
        bf, bg = f.blocks(), g.blocks()
        assert bf[0] == bg[0] and 2 in bg and 2 not in bf

    def test_import_roaring(self, tmp_path):
        from pilosa_tpu.store import roaring
        f = Fragment(str(tmp_path / "0"), 0).open()
        positions = np.array([0, 1, SHARD_WIDTH + 5], np.uint64)  # rows 0,1
        assert f.import_roaring(roaring.serialize(positions)) == 3
        assert f.row(1).contains(5)

    def test_rows_containing(self, tmp_path, rng):
        # sparse + dense rows, against a per-row contains() oracle;
        # the cache must invalidate on mutation
        f = Fragment(str(tmp_path / "0"), 0).open()
        n = 5000
        rows = rng.integers(0, 200, size=n).astype(np.uint64)
        cols = rng.integers(0, 1 << 14, size=n).astype(np.uint64)
        f.set_bits(rows, cols)
        f.set_bits(np.full(6000, 201, np.uint64),  # one dense row
                   rng.choice(SHARD_WIDTH, 6000, replace=False).astype(np.uint64))
        for col in [int(cols[0]), int(cols[7]), 12345, 0]:
            expect = sorted(r for r in f.row_ids()
                            if f.row(r).contains(col))
            np.testing.assert_array_equal(
                f.rows_containing(col), np.array(expect, np.uint64),
                err_msg=f"col {col}")
        probe = int(cols[0])
        before = f.rows_containing(probe)
        f.set_bit(199, probe)
        after = f.rows_containing(probe)
        assert 199 in after and set(map(int, before)) - {199} \
            == set(map(int, after)) - {199}

    def test_rows_containing_over_cap_fallback(self, tmp_path,
                                               monkeypatch, rng):
        monkeypatch.setattr(Fragment, "COLINDEX_MAX_BITS", 100)
        f = Fragment(str(tmp_path / "0"), 0).open()
        rows = np.arange(300, dtype=np.uint64)
        f.set_bits(rows, np.full(300, 77, np.uint64))
        np.testing.assert_array_equal(f.rows_containing(77), rows)
        assert f.rows_containing(78).size == 0

    def test_lazy_snapshot_open(self, tmp_path, rng):
        # reopen must NOT expand bits eagerly (mmap FromBuffer path);
        # reads materialize on demand and stay correct
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        n = 3000
        rows = rng.integers(0, 50, size=n).astype(np.uint64)
        cols = rng.choice(1 << 16, size=n, replace=False).astype(np.uint64)
        f.set_bits(rows, cols)
        card = f.cardinality()
        ids = f.row_ids()
        row7 = f.row(7).columns().copy()
        f.close()

        g = Fragment(path, 0).open()
        assert g._snap_dir is not None and len(g._snap_pending) > 0
        assert not g.rows, "no row may be materialized at open"
        assert g.row_ids() == ids          # directory-only
        assert g.cardinality() == card     # directory-only
        assert 7 in g._snap_pending
        np.testing.assert_array_equal(g.row(7).columns(), row7)
        assert 7 not in g._snap_pending    # materialized on touch

        # mutations against still-lazy rows
        some = int(ids[3])
        before = g.row(some).cardinality
        assert g.set_bit(some, 1 << 17)
        assert g.row(some).cardinality == before + 1
        assert g.clear_row(int(ids[4])) > 0
        assert int(ids[4]) not in g.row_ids()
        g.close()

        h = Fragment(path, 0).open()
        assert int(ids[4]) not in h.row_ids()
        np.testing.assert_array_equal(h.row(7).columns(), row7)
        assert h.cardinality() == len(h.positions())

    def test_blocks_and_rows_containing_stay_lazy(self, tmp_path,
                                                  monkeypatch, rng):
        # AAE checksums + Rows(column=) on a lazy fragment must not
        # materialize the row set; results equal the materialized truth
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        n = 4000
        rows = rng.integers(0, 500, size=n).astype(np.uint64)
        cols = rng.integers(0, 1 << 14, size=n).astype(np.uint64)
        f.set_bits(rows, cols)
        truth_blocks = f.blocks()
        probe = int(cols[0])
        truth_rows = f.rows_containing(probe)
        truth_bp = f.block_positions(2)
        f.close()

        g = Fragment(path, 0).open()
        # force the no-materialize positions-scan regime
        monkeypatch.setattr(Fragment, "COLINDEX_MAX_ROWS", 10)
        monkeypatch.setattr(Fragment, "COLINDEX_CONTAINS_MAX_ROWS", 0)
        assert g.blocks() == truth_blocks
        np.testing.assert_array_equal(g.rows_containing(probe), truth_rows)
        np.testing.assert_array_equal(g.block_positions(2), truth_bp)
        assert not g.rows, "lazy reads must not materialize rows"

    def test_auto_snapshot_keeps_lazy_rows_visible(self, tmp_path):
        # compaction during serving must not lose snapshot-resident
        # rows that were never materialized: after snapshot() the
        # fragment re-opens the new blob as its lazy backing
        path = str(tmp_path / "0")
        f = Fragment(path, 0, max_op_n=5).open()
        f.set_bits(np.arange(50, dtype=np.uint64),
                   np.arange(50, dtype=np.uint64))
        f.close()

        g = Fragment(path, 0, max_op_n=5).open()
        assert len(g._snap_pending) == 50
        for i in range(8):  # crosses max_op_n -> auto snapshot
            g.set_bit(100 + i, 7)
        assert g.op_n <= 5
        assert g.cardinality() == 58
        assert g.row(3).contains(3)          # pre-compaction lazy row
        assert len(g.row_ids()) == 58
        # and the new backing file is the merged truth
        g.close()
        h = Fragment(path, 0).open()
        assert h.cardinality() == 58 and h.row(105).contains(7)

    def test_grouped_mutation_on_lazy_rows(self, tmp_path):
        # set_bits_grouped / clear_bits_grouped (the BSI import path)
        # must materialize snapshot-resident rows before mutating
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bits(np.array([3, 3, 3], np.uint64),
                   np.array([10, 11, 12], np.uint64))
        f.close()

        g = Fragment(path, 0).open()
        assert 3 in g._snap_pending
        assert g.set_bits_grouped([(3, np.array([12, 13], np.uint32))]) == 1
        np.testing.assert_array_equal(g.row(3).columns(), [10, 11, 12, 13])
        assert g.cardinality() == 4
        g.close()
        h = Fragment(path, 0).open()
        assert 3 in h._snap_pending
        assert h.clear_bits_grouped([(3, np.array([10, 99], np.uint32))]) == 1
        np.testing.assert_array_equal(h.row(3).columns(), [11, 12, 13])
        # Store() no-op check against a still-lazy row
        h.close()
        k = Fragment(path, 0).open()
        assert not k.set_row(3, np.array([11, 12, 13]))  # identical: no-op
        assert k.set_row(3, np.array([11]))

    def test_plane_rows_matches_words(self, tmp_path, rng):
        # plane assembly from the mmap blob (native fast path when
        # built) must equal per-row words() materialization
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        n = 4000
        rows = rng.integers(0, 40, size=n).astype(np.uint64)
        cols = rng.choice(1 << 15, size=n, replace=False).astype(np.uint64)
        f.set_bits(rows, cols)
        # one dense row to cross representations
        f.set_bits(np.full(5000, 41, np.uint64),
                   rng.choice(SHARD_WIDTH, 5000, replace=False).astype(np.uint64))
        f.close()

        g = Fragment(path, 0).open()
        ids = g.row_ids()
        from pilosa_tpu.engine.words import WORDS_PER_SHARD
        out = np.zeros((len(ids), WORDS_PER_SHARD), np.uint32)
        g.plane_rows(ids, out)
        # compare against materialized truth, and overlay precedence
        for i, r in enumerate(ids):
            np.testing.assert_array_equal(out[i], g.row(r).words(),
                                          err_msg=f"row {r}")
        g.set_bit(int(ids[0]), 3)  # overlay row 0; rebuild
        out2 = np.zeros_like(out)
        g.plane_rows(ids, out2)
        np.testing.assert_array_equal(out2[0], g.row(int(ids[0])).words())
        g.close()


class TestSnapshotQueue:
    def test_background_compaction(self, tmp_path):
        import time

        from pilosa_tpu.store.holder import SnapshotQueue
        q = SnapshotQueue()
        f = Fragment(str(tmp_path / "0"), 0, max_op_n=10,
                     snapshot_submit=q.submit).open()
        for i in range(25):
            f.set_bit(0, i)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and f.op_n > 10:
            time.sleep(0.02)
        assert f.op_n <= 10, "background queue never compacted"
        assert os.path.exists(str(tmp_path / "0"))
        assert f.cardinality() == 25
        q.close()
        # queue closed: the write path falls back to inline compaction
        for i in range(25, 45):
            f.set_bit(0, i)
        assert f.op_n <= 10
        f.close()
        g = Fragment(str(tmp_path / "0"), 0).open()
        assert g.cardinality() == 45

    def test_holder_wires_the_queue(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i", track_existence=False)
        f = idx.create_field("f")
        frag = f.view("standard", create=True).fragment(0, create=True)
        assert frag._snapshot_submit is not None
        h.close()
        h2 = Holder(str(tmp_path), async_snapshots=False).open()
        frag2 = (h2.index("i").field("f").view("standard", create=True)
                 .fragment(0, create=True))
        assert frag2._snapshot_submit is None
        h2.close()


class TestOpLog:
    def test_crc_rejects_corruption(self, tmp_path):
        path = str(tmp_path / "log")
        log = OpLog(path)
        log.append(OP_SET_BITS, 0, np.array([1, 2, 3], np.uint64))
        log.close()
        data = bytearray(open(path, "rb").read())
        data[10] ^= 0xFF
        open(path, "wb").write(bytes(data))
        assert list(OpLog(path).replay()) == []


class TestField:
    def make(self, tmp_path, **opts):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        return h, idx

    def test_set_field(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("f")
        idx.set_bit("f", 1, 10)
        idx.set_bit("f", 1, SHARD_WIDTH + 3)  # second shard
        assert f.available_shards() == [0, 1]
        assert idx.existence_field.available_shards() == [0, 1]

    def test_int_field_round_trip(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("amount", FieldOptions(type="int", min=-1000, max=1000))
        idx.set_value("amount", 5, -42)
        idx.set_value("amount", 9, 977)
        assert f.value(5) == (-42, True)
        assert f.value(9) == (977, True)
        assert f.value(6) == (0, False)
        # overwrite clears stale bits
        idx.set_value("amount", 5, 7)
        assert f.value(5) == (7, True)

    def test_int_field_bit_depth_growth(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("n", FieldOptions(type="int"))
        f.set_value(1, 3)
        d1 = f.options.bit_depth
        f.set_value(2, 1 << 20)
        assert f.options.bit_depth > d1
        assert f.value(2) == (1 << 20, True)
        assert f.value(1) == (3, True)

    def test_bounds_enforced(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("n", FieldOptions(type="int", min=0, max=10))
        with pytest.raises(ValueError):
            f.set_value(1, 11)

    def test_mutex_field(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("m", FieldOptions(type="mutex"))
        f.set_bit(1, 100)
        f.set_bit(2, 100)  # must clear row 1
        assert not f.standard_view().fragment(0).row(1).contains(100)
        assert f.standard_view().fragment(0).row(2).contains(100)

    def test_bool_field(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("b", FieldOptions(type="bool"))
        f.set_bit(1, 7)
        f.set_bit(0, 7)
        frag = f.standard_view().fragment(0)
        assert frag.row(0).contains(7) and not frag.row(1).contains(7)
        with pytest.raises(ValueError):
            f.set_bit(2, 7)

    def test_time_field_views(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("t", FieldOptions(type="time", time_quantum="YMD"))
        f.set_bit(1, 5, timestamp=datetime(2017, 1, 2))
        names = set(f.views.keys())
        assert {"standard", "standard_2017", "standard_201701",
                "standard_20170102"} <= names

    def test_decimal_field(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("d", FieldOptions(type="decimal", scale=2))
        f.set_value(1, 12.34)
        assert f.value(1) == (12.34, True)

    def test_timestamp_field(self, tmp_path):
        h, idx = self.make(tmp_path)
        f = idx.create_field("ts", FieldOptions(type="timestamp"))
        f.set_value(1, "2020-06-01T12:00:00")
        stored, ok = f.value(1)
        assert ok and stored == int(datetime(2020, 6, 1, 12).timestamp())


class TestHolder:
    def test_reopen_preserves_everything(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("myidx", keys=False)
        idx.create_field("f")
        idx.create_field("amount", FieldOptions(type="int", min=0, max=100))
        idx.set_bit("f", 1, 10)
        idx.set_value("amount", 10, 55)
        h.close()

        h2 = Holder(str(tmp_path)).open()
        idx2 = h2.index("myidx")
        assert idx2 is not None
        assert idx2.field("f").standard_view().fragment(0).row(1).contains(10)
        assert idx2.field("amount").value(10) == (55, True)
        assert idx2.field("amount").options.type == "int"
        assert EXISTENCE_FIELD in idx2.fields

    def test_schema_dump_apply(self, tmp_path):
        h = Holder(str(tmp_path / "a")).open()
        idx = h.create_index("i1", keys=True)
        idx.create_field("f1", FieldOptions(type="time", time_quantum="YM"))
        schema = h.schema()

        h2 = Holder(str(tmp_path / "b")).open()
        h2.apply_schema(schema)
        assert h2.index("i1").keys
        assert h2.index("i1").field("f1").options.time_quantum == "YM"

    def test_delete_index(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        h.create_index("gone")
        h.delete_index("gone")
        assert h.index("gone") is None
        assert not os.path.exists(os.path.join(str(tmp_path), "gone"))

    def test_invalid_names(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        for bad in ("Upper", "1num", "sp ace", ""):
            with pytest.raises(ValueError):
                h.create_index(bad)


class TestTimeQuantum:
    def test_views_by_time(self):
        t = datetime(2017, 1, 2, 3)
        assert timeq.views_by_time("standard", t, "YMDH") == [
            "standard_2017", "standard_201701", "standard_20170102",
            "standard_2017010203"]

    def test_range_cover_exact(self):
        views = timeq.views_by_time_range(
            "standard", datetime(2016, 11, 2), datetime(2017, 2, 3), "YMD")
        assert views == [
            "standard_20161102", "standard_20161103", "standard_20161104",
            "standard_20161105", "standard_20161106", "standard_20161107",
            "standard_20161108", "standard_20161109", "standard_20161110",
            "standard_20161111", "standard_20161112", "standard_20161113",
            "standard_20161114", "standard_20161115", "standard_20161116",
            "standard_20161117", "standard_20161118", "standard_20161119",
            "standard_20161120", "standard_20161121", "standard_20161122",
            "standard_20161123", "standard_20161124", "standard_20161125",
            "standard_20161126", "standard_20161127", "standard_20161128",
            "standard_20161129", "standard_20161130", "standard_201612",
            "standard_201701", "standard_20170201", "standard_20170202"]

    def test_range_cover_uses_coarse_middle(self):
        views = timeq.views_by_time_range(
            "standard", datetime(2016, 1, 1), datetime(2018, 1, 1), "YMDH")
        assert views == ["standard_2016", "standard_2017"]

    def test_invalid_quantum(self):
        with pytest.raises(ValueError):
            timeq.validate_quantum("YD")


class TestReviewRegressions:
    """Regressions for the round-1 code-review findings."""

    def test_unsorted_set_bits(self, tmp_path):
        from pilosa_tpu.store import Fragment
        f = Fragment(str(tmp_path / "0"), 0).open()
        assert f.set_bits(np.array([2, 1], np.uint64),
                          np.array([5, 6], np.uint64)) == 2
        np.testing.assert_array_equal(f.row(1).columns(), [6])
        np.testing.assert_array_equal(f.row(2).columns(), [5])
        # replay must agree with memory
        g = Fragment(str(tmp_path / "0"), 0).open()
        np.testing.assert_array_equal(g.row(1).columns(), [6])
        np.testing.assert_array_equal(g.row(2).columns(), [5])

    def test_bsi_overwrite_reports_changed(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("n", FieldOptions(type="int", min=0, max=100))
        assert f.set_value(7, 5)
        assert f.set_value(7, 9)      # overwrite: different value → changed
        assert not f.set_value(7, 9)  # same value → unchanged
        assert f.value(7) == (9, True)

    def test_empty_store_on_empty_row_is_noop(self, tmp_path):
        from pilosa_tpu.store import Fragment
        f = Fragment(str(tmp_path / "0"), 0).open()
        assert not f.set_row(1, np.empty(0, np.uint32))
        assert f.op_n == 0

    def test_schema_preserves_timestamp_options(self, tmp_path):
        h = Holder(str(tmp_path / "a")).open()
        idx = h.create_index("i")
        idx.create_field("ts", FieldOptions(type="timestamp", time_unit="ms",
                                            epoch="2020-01-01T00:00:00"))
        h2 = Holder(str(tmp_path / "b")).open()
        h2.apply_schema(h.schema())
        o = h2.index("i").field("ts").options
        assert o.time_unit == "ms" and o.epoch == "2020-01-01T00:00:00"

    def test_mutex_bulk_import(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("m", FieldOptions(type="mutex"))
        cols = np.arange(500, dtype=np.uint64)
        f.import_bits(np.ones(500, np.uint64), cols)          # all row 1
        f.import_bits(np.full(250, 2, np.uint64), cols[:250])  # move half
        frag = f.standard_view().fragment(0)
        assert frag.row(1).cardinality == 250
        assert frag.row(2).cardinality == 250

    def test_crash_before_first_snapshot_is_durable(self, tmp_path):
        """Regression: a fragment whose only on-disk state is the op-log
        (crash before any snapshot) must be discovered on reopen."""
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        idx.set_bit("f", 1, 10)   # 1 op; far below MAX_OP_N, no snapshot
        # no h.close() — simulate crash
        h2 = Holder(str(tmp_path)).open()
        frag = h2.index("i").field("f").standard_view().fragment(0)
        assert frag is not None and frag.row(1).contains(10)

    def test_pending_tier_semantics(self, tmp_path, rng):
        """The r5 pending tier (fragment LSM buffer) must be invisible:
        exact changed counts including duplicate probes, pending-aware
        reads, and crash replay of un-flushed pending (the op-log write
        precedes the buffer append)."""
        from pilosa_tpu.store.fragment import Fragment
        f = Fragment(str(tmp_path / "0"), 0).open()
        rows = rng.integers(0, 40, size=2000).astype(np.uint64)
        cols = rng.integers(0, SHARD_WIDTH, size=2000).astype(np.uint64)
        uniq = len({(int(r), int(c)) for r, c in zip(rows, cols)})
        assert f.set_bits(rows, cols) == uniq
        # re-setting the same bits: exact zero changed, all from probes
        assert f.set_bits(rows, cols) == 0
        assert len(f._pend_pos) > 0, "bits should still be pending"
        # pending-aware reads without flushing
        assert f.cardinality() == uniq
        ids, cards = f.row_cardinalities()
        assert int(cards.sum()) == uniq
        assert f.present
        # crash now (no close/flush): replay must rebuild everything
        g = Fragment(str(tmp_path / "0"), 0).open()
        assert g.cardinality() == uniq
        np.testing.assert_array_equal(g.positions(), f.positions())
        # reads flush; post-flush truth identical
        probe_row = int(rows[0])
        np.testing.assert_array_equal(
            g.row(probe_row).columns(), f.row(probe_row).columns())
        assert len(f._pend_pos) == 0, "row() read must flush"

    def test_reset_after_clear_with_stale_probe_cache(self, tmp_path):
        """Regression (r5 review): a duplicates-only batch leaves the
        probe cache built with EMPTY pending; a clear through the
        classic path must invalidate that cache or the following re-set
        is silently dropped as 'already present' — a lost acknowledged
        write."""
        from pilosa_tpu.store.fragment import Fragment
        f = Fragment(str(tmp_path / "0"), 0).open()
        r = np.array([3], np.uint64)
        c = np.array([77], np.uint64)
        assert f.set_bits(r, c) == 1
        assert f.set_bits(r, c) == 0   # builds probe cache, pending empty
        assert f.clear_bits(r, c) == 1  # classic path mutates merged truth
        assert f.set_bits(r, c) == 1, "re-set after clear must land"
        assert f.row(3).contains(77)
        # same for row-level ops
        assert f.set_bits(r, c) == 0
        f.clear_row(3)
        assert f.set_bits(r, c) == 1
        assert f.cardinality() == 1

    def test_pending_tier_interleaved_with_clears(self, tmp_path, rng):
        """Clears and row ops force a flush and stay exact against a
        position-set oracle under interleaving."""
        from pilosa_tpu.store.fragment import Fragment
        f = Fragment(str(tmp_path / "0"), 0).open()
        oracle: set[tuple[int, int]] = set()
        for step in range(30):
            r = int(rng.integers(0, 8))
            cs = rng.integers(0, 4096, size=50).astype(np.uint64)
            if step % 3 == 2:
                got = f.clear_bits(np.full(50, r, np.uint64), cs)
                want = len({(r, int(c)) for c in cs} & oracle)
                oracle -= {(r, int(c)) for c in cs}
            else:
                got = f.set_bits(np.full(50, r, np.uint64), cs)
                want = len({(r, int(c)) for c in cs} - oracle)
                oracle |= {(r, int(c)) for c in cs}
            assert got == want, f"step {step}"
        expect = np.array(sorted(r * SHARD_WIDTH + c for r, c in oracle),
                          np.uint64)
        np.testing.assert_array_equal(f.positions(), expect)
        # crash replay of the interleaved log
        g = Fragment(str(tmp_path / "0"), 0).open()
        np.testing.assert_array_equal(g.positions(), expect)

    def test_crash_replay_bsi_grouped(self, tmp_path):
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i", track_existence=False)
        f = idx.create_field("n", FieldOptions(type="int", min=-10, max=10))
        f.import_values(np.array([1, 2], np.uint64), [5, -3])
        h2 = Holder(str(tmp_path)).open()
        f2 = h2.index("i").field("n")
        assert f2.value(1) == (5, True)
        assert f2.value(2) == (-3, True)

    def test_recreated_index_fresh_keys(self, tmp_path):
        """Single-node: deleting an index must drop cached key logs so a
        recreated index starts from empty key state."""
        from pilosa_tpu.exec import Executor
        h = Holder(str(tmp_path)).open()
        h.create_index("k", keys=True)
        h.index("k").create_field("f", FieldOptions(keys=True))
        from pilosa_tpu.api import API
        api = API(h)
        api.query("k", 'Set("alice", f="admin")')
        api.delete_index("k")
        h.create_index("k", keys=True)
        h.index("k").create_field("f", FieldOptions(keys=True))
        log = api.executor.translate.columns("k")
        assert log.translate(["alice"], create=False) == [None]


class TestSetRowAtomicity:
    """Row replacement must be ONE op-log record (round-2 advisory: a
    crash between a CLEAR_ROW and SET_BITS pair replayed as a cleared
    row with the replacement lost)."""

    def test_set_row_is_single_oplog_record(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bits(np.array([5, 5], np.uint64), np.array([1, 2], np.uint64))
        n_before = sum(1 for _ in OpLog(path + ".oplog").replay())
        assert f.set_row(5, np.array([7, 8, 9]))
        n_after = sum(1 for _ in OpLog(path + ".oplog").replay())
        assert n_after == n_before + 1

    def test_set_row_crash_replay(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bits(np.array([5, 5], np.uint64), np.array([1, 2], np.uint64))
        assert f.set_row(5, np.array([7, 8, 9]))
        # no close/snapshot — simulate crash; replay must see the NEW row
        g = Fragment(path, 0).open()
        np.testing.assert_array_equal(g.row(5).columns(), [7, 8, 9])

    def test_set_row_to_empty_crash_replay(self, tmp_path):
        path = str(tmp_path / "0")
        f = Fragment(path, 0).open()
        f.set_bits(np.array([5], np.uint64), np.array([1], np.uint64))
        assert f.set_row(5, np.empty(0, np.uint32))
        g = Fragment(path, 0).open()
        assert not g.row(5).any()


class TestColdReopenShardDiscovery:
    def test_available_shards_after_snapshot_reopen(self, tmp_path):
        """Lazily-opened snapshot fragments (no overlay rows yet) must
        still count as available — before the fix, a cold-reopened
        multi-shard index reported no shards and the executor silently
        fell back to shard 0 only."""
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        cols = np.array([5, SHARD_WIDTH + 6, 2 * SHARD_WIDTH + 7],
                        np.uint64)
        f.import_bits(np.array([1, 1, 1], np.uint64), cols)
        for s in (0, 1, 2):
            f.view("standard").fragment(s).snapshot()
        h.close()

        h2 = Holder(str(tmp_path)).open()
        try:
            idx2 = h2.index("i")
            assert idx2.available_shards() == (0, 1, 2)
            # end-to-end: a shard-unrestricted Count must cover them all
            from pilosa_tpu.exec import Executor
            ex = Executor(h2)
            assert ex.execute("i", "Count(Row(f=1))") == [3]
        finally:
            h2.close()


class TestSyswrapMapCap:
    def test_holder_survives_more_fragments_than_map_cap(self, tmp_path):
        """syswrap parity (reference: syswrap maxMapCount): open far
        more snapshot fragments than the live-map cap; LRU fragments
        demote to heap copies, every query stays exact, and the live
        map count respects the cap."""
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.store import syswrap

        n_shards, cap = 120, 10
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        cols = (np.arange(n_shards, dtype=np.uint64) * SHARD_WIDTH + 7)
        f.import_bits(np.ones(n_shards, np.uint64), cols)
        for s in range(n_shards):
            f.view("standard").fragment(s).snapshot()
        h.close()

        old_max = syswrap.GLOBAL.max_maps
        syswrap.GLOBAL.set_max(cap)
        try:
            h2 = Holder(str(tmp_path)).open()
            frags = [h2.index("i").field("f").view("standard").fragment(s)
                     for s in range(n_shards)]
            live = sum(1 for fr in frags if fr._snap_mm is not None)
            assert live <= cap, live
            assert syswrap.GLOBAL.live <= cap
            # demoted fragments answer from their heap copy
            ex = Executor(h2)
            assert ex.execute("i", "Count(Row(f=1))") == [n_shards]
            (row,) = ex.execute("i", "Row(f=1)")
            np.testing.assert_array_equal(row.columns, cols)
            h2.close()
        finally:
            syswrap.GLOBAL.set_max(old_max)

    def test_demoted_fragment_still_mutates(self, tmp_path):
        from pilosa_tpu.store import syswrap
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        f.import_bits(np.array([1], np.uint64), np.array([5], np.uint64))
        frag = f.view("standard").fragment(0)
        frag.snapshot()
        h.close()
        h2 = Holder(str(tmp_path)).open()
        frag2 = h2.index("i").field("f").view("standard").fragment(0)
        assert frag2._snap_mm is not None
        frag2._demote_map()
        assert frag2._snap_mm is None
        assert frag2.set_bit(1, 9)
        np.testing.assert_array_equal(frag2.row(1).columns(), [5, 9])
        h2.close()

    def test_demotion_races_concurrent_readers(self, tmp_path):
        """Readers holding views over the mmap while the pool demotes:
        results stay exact and nothing deadlocks (the demote uses a
        timed lock acquire; failed victims stay tracked)."""
        import threading

        from pilosa_tpu.store import syswrap

        n_frags, cap = 24, 4
        h = Holder(str(tmp_path)).open()
        idx = h.create_index("i")
        f = idx.create_field("f")
        cols = (np.arange(n_frags, dtype=np.uint64) * SHARD_WIDTH + 3)
        f.import_bits(np.ones(n_frags, np.uint64), cols)
        for s in range(n_frags):
            f.view("standard").fragment(s).snapshot()
        h.close()

        old_max = syswrap.GLOBAL.max_maps
        syswrap.GLOBAL.set_max(cap)
        try:
            h2 = Holder(str(tmp_path)).open()
            frags = [h2.index("i").field("f").view("standard").fragment(s)
                     for s in range(n_frags)]
            errors = []

            def reader():
                out = np.zeros((1, 32768), np.uint32)
                for _ in range(50):
                    for fr in frags:
                        out[:] = 0
                        fr.plane_rows([1], out, slots=[0])
                        if int(np.bitwise_count(out).sum()) != 1:
                            errors.append("bad bits")
                            return

            def demoter():
                for _ in range(100):
                    for fr in frags:
                        fr._demote_map()

            threads = ([threading.Thread(target=reader) for _ in range(3)]
                       + [threading.Thread(target=demoter)])
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
                assert not t.is_alive(), "deadlock"
            assert not errors, errors
            h2.close()
        finally:
            syswrap.GLOBAL.set_max(old_max)
