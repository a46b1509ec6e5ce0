"""r18 tentpole: deadline-aware, self-healing dispatch pipeline.

The batcher is one shared device stream — these tests pin the three
r18 guarantees against injected sickness:

- **deadlines reach the window**: a caller's expiry mid-window raises
  a structured ``QueryTimeoutError`` naming the stage, the abandoned
  item is skipped by the shared readback, and co-batched callers'
  answers are untouched;
- **watchdog + quarantine**: a hung dispatch or readback is bounded —
  the stuck window's items fail with ``PipelineStalledError`` naming
  the stage, the wedged worker is superseded, the queue keeps
  draining, and no threads leak once the hang resolves;
- **health governor**: consecutive dispatch faults degrade serving to
  the per-item fallback path (answers stay exact), probing restores
  healthy.

Plus the knob-off regression pin: ``dispatch_pipeline_depth<=1`` +
``dispatch_watchdog_seconds=0`` restores the exact pre-r18 inline
contract (no reader, no watchdog thread, same answers).
"""

import os
import threading
import time

import numpy as np
import pytest

from pilosa_tpu import fault
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.executor import (PipelineStalledError,
                                      QueryTimeoutError)
from pilosa_tpu.exec.health import DeviceHealthGovernor
from pilosa_tpu.obs import Stats
from pilosa_tpu.store import Holder

WORDS = SHARD_WIDTH // 32


def _np_row_counts(plane: np.ndarray) -> np.ndarray:
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)
    return np.array([int(np.unpackbits(
        plane[:, r].reshape(-1).view(np.uint8)).sum())
        for r in range(plane.shape[1])], dtype=np.int64)


def _counter(stats, name: str) -> int:
    return int(sum(stats.snapshot()["counters"].get(name, {}).values()))


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


@pytest.fixture
def served_index(tmp_path):
    """A 2-shard, 16-row on-disk field (the test_multiquery recipe)."""
    from pilosa_tpu.store import roaring

    n_shards, n_rows = 2, 16
    rng = np.random.default_rng(23)
    plane = rng.integers(0, 1 << 32, size=(n_shards, n_rows, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    h = Holder(str(tmp_path)).open()
    idx = h.create_index("i", track_existence=False)
    idx.create_field("f")
    h.close()
    frag_dir = os.path.join(str(tmp_path), "i", "f", "views", "standard",
                            "fragments")
    os.makedirs(frag_dir, exist_ok=True)
    for s in range(n_shards):
        with open(os.path.join(frag_dir, str(s)), "wb") as fh:
            fh.write(roaring.serialize_dense(plane[s]))
    holder = Holder(str(tmp_path)).open()
    yield holder, _np_row_counts(plane), n_rows
    holder.close()


def _resident_plane(ex, holder):
    idx = holder.index("i")
    fld = idx.field("f")
    shards = tuple(idx.available_shards())
    return ex.planes.field_plane("i", fld, "standard", shards)


def _pipeline_census() -> dict:
    """Process-wide batcher thread counts by name prefix.  Other
    tests' executors leave parked collectors behind (pre-existing:
    daemon threads holding their batcher alive), so assertions compare
    against a BASELINE taken inside each test, never absolutes."""
    names = [t.name for t in threading.enumerate()]
    return {n: sum(1 for x in names if x.startswith(n))
            for n in ("pilosa-count-batcher", "pilosa-batch-readback",
                      "pilosa-pipeline-watchdog")}


def _await_census_back_to(baseline: dict,
                          timeout: float = 20.0) -> dict:
    """Poll until the census is back at (or under) the baseline —
    quarantine zombies exit on their own schedule once a hang
    resolves, so this trades latency, never signal."""
    deadline = time.monotonic() + timeout
    census = {}
    while time.monotonic() < deadline:
        census = _pipeline_census()
        if all(census[k] <= baseline[k] for k in baseline):
            return census
        time.sleep(0.2)
    return census


class TestDeadlinePropagation:
    def test_expired_deadline_refused_before_dispatch(self, served_index):
        """The fast-lane/enqueue guard: a deadline already in the past
        never occupies a window slot — it fails up front, naming the
        stage."""
        holder, oracle, _ = served_index
        ex = Executor(holder, stats=Stats())
        ps = _resident_plane(ex, holder)
        with pytest.raises(QueryTimeoutError) as ei:
            ex.batcher.submit_rowcounts(
                ps.plane, deadline=time.monotonic() - 1.0)
        assert ei.value.stage == "dispatch"

    def test_wait_deadline_boundary_never_returns_none(self):
        """The deadline/delivery boundary, both interleavings: a late
        deliverer that observed the abandon mark leaves nothing stored
        (wait must raise, NEVER return None as the answer), while a
        store that landed first is a real answer (wait returns it)."""
        from pilosa_tpu.exec.batcher import CountBatcher, _Pending
        p = _Pending("count", None, (None,),
                     deadline=time.monotonic() - 0.01)
        p.abandoned = True          # as wait() sets at its timeout
        CountBatcher._deliver(p, [42])  # skips the store, sets event
        assert p.event.is_set() and p.result is None
        with pytest.raises(QueryTimeoutError):
            CountBatcher.wait(None, p)
        q = _Pending("count", None, (None,),
                     deadline=time.monotonic() - 0.01)
        CountBatcher._deliver(q, [42])  # the store landed first
        assert CountBatcher.wait(None, q) == [42]

    def test_deadline_expiry_mid_window_leaves_cobatched_exact(
            self, served_index):
        """One caller's expiry mid-window must not corrupt co-batched
        answers: the abandoned item is skipped by the shared finish,
        the surviving caller's answer stays oracle-exact, and the
        expired caller's error names the stage."""
        holder, oracle, n_rows = served_index
        ex = Executor(holder, stats=Stats(), count_batch_window=0.005,
                      solo_fastlane=False,
                      dispatch_watchdog_seconds=0)  # deadline, not
        # quarantine, must be what fails the expiring caller here
        ps = _resident_plane(ex, holder)
        batcher = ex.batcher
        # the window's dispatch stalls 0.4s — caller A (deadline 0.1s)
        # expires mid-window; caller B (no deadline) rides it out
        fault.set_fault("exec.dispatch_hang", "delay", times=1,
                        match={"kind": "rowcounts"},
                        args={"seconds": 0.4})
        results = {}
        errors = {}
        start = threading.Barrier(2)

        def caller(name, deadline):
            try:
                start.wait()
                results[name] = np.asarray(batcher.submit_rowcounts(
                    ps.plane, deadline=deadline))
            except Exception as e:  # noqa: BLE001
                errors[name] = e

        t_a = threading.Thread(
            target=caller, args=("a", time.monotonic() + 0.15))
        t_b = threading.Thread(target=caller, args=("b", None))
        t_a.start()
        t_b.start()
        t_a.join(timeout=30)
        t_b.join(timeout=30)
        assert "a" in errors, "expiring caller should have timed out"
        assert isinstance(errors["a"], QueryTimeoutError)
        assert errors["a"].stage in ("queued", "dispatch", "readback")
        assert "b" in results, f"survivor failed: {errors.get('b')!r}"
        np.testing.assert_array_equal(results["b"][:n_rows], oracle)
        # the pipeline is unharmed: a fresh submit answers exactly
        got = np.asarray(batcher.submit_rowcounts(ps.plane))
        np.testing.assert_array_equal(got[:n_rows], oracle)

    def test_mixed_kinds_with_deadline_churn_interleaved_ingest(
            self, tmp_path):
        """32-way acceptance pin (r18 satellite): mixed-kind readers
        (counts, selected counts, compound trees) stay oracle-exact
        while DOOMED callers churn tiny deadlines through the same
        windows and writers stream bits into the same plane.  A doomed
        caller either times out (QueryTimeoutError) or answers exactly
        — never a wrong answer, never a foreign error."""
        holder = Holder(str(tmp_path)).open()
        idx = holder.create_index("i")
        idx.create_field("f")
        stats = Stats()
        ex = Executor(holder, stats=stats, delta_cells=32)
        n_read_rows = 4
        write_row = 9
        rng = np.random.default_rng(17)
        counts = [0] * n_read_rows
        f = holder.index("i").field("f")
        rows_l, cols_l = [], []
        for s in range(2):
            offs = rng.choice(SHARD_WIDTH // 2, size=64, replace=False)
            rr = rng.integers(0, n_read_rows, size=64)
            for r, o in zip(rr, offs):
                rows_l.append(int(r))
                cols_l.append(s * SHARD_WIDTH + int(o))
                counts[int(r)] += 1
        f.import_bits(np.asarray(rows_l, np.uint64),
                      np.asarray(cols_l, np.uint64))
        holder.index("i").note_columns(np.asarray(cols_l, np.uint64))
        tree_pql = ("Count(Intersect(Row(f=0), "
                    "Union(Row(f=1), Row(f=2))))")
        sets = [set() for _ in range(n_read_rows)]
        for r, c in zip(rows_l, cols_l):
            if r < n_read_rows:
                sets[r].add(c)
        tree_want = len(sets[0] & (sets[1] | sets[2]))
        for r in range(n_read_rows):
            assert ex.execute("i", f"Count(Row(f={r}))") == [counts[r]]
        assert ex.execute("i", tree_pql) == [tree_want]

        errors: list = []
        timeouts = [0]
        stop = time.monotonic() + 2.5
        start = threading.Barrier(33)

        def reader(i):
            kind = i % 2
            try:
                start.wait()
                while time.monotonic() < stop:
                    if kind == 0:
                        r = i % n_read_rows
                        got = ex.execute("i", f"Count(Row(f={r}))")
                        assert got == [counts[r]], got
                    else:
                        got = ex.execute("i", tree_pql)
                        assert got == [tree_want], got
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        def doomed(i):
            try:
                start.wait()
                while time.monotonic() < stop:
                    r = i % n_read_rows
                    try:
                        got = ex.execute(
                            "i", f"Count(Row(f={r}))",
                            deadline=time.monotonic() + 0.002)
                    except QueryTimeoutError:
                        timeouts[0] += 1
                        continue
                    assert got == [counts[r]], \
                        f"doomed caller got a WRONG answer: {got}"
            except Exception as e:  # noqa: BLE001
                errors.append(f"doomed: {e!r}")

        def writer(w):
            wrng = np.random.default_rng(100 + w)
            try:
                start.wait()
                while time.monotonic() < stop:
                    s = int(wrng.integers(0, 2))
                    c = (s * SHARD_WIDTH + SHARD_WIDTH // 2
                         + int(wrng.integers(0, SHARD_WIDTH // 2)))
                    ex.execute("i", f"Set({c}, f={write_row})")
            except Exception as e:  # noqa: BLE001
                errors.append(f"writer: {e!r}")

        threads = ([threading.Thread(target=reader, args=(i,))
                    for i in range(22)]
                   + [threading.Thread(target=doomed, args=(i,))
                      for i in range(8)]
                   + [threading.Thread(target=writer, args=(w,))
                      for w in range(2)])
        for t in threads:
            t.start()
        start.wait()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors[:5]
        # fresh reads after the churn: still exact
        for r in range(n_read_rows):
            assert ex.execute("i", f"Count(Row(f={r}))") == [counts[r]]
        holder.close()


class TestWatchdogQuarantine:
    def test_hung_dispatch_quarantined_and_recovers(self, served_index):
        """A hung single-group dispatch: the watchdog quarantines the
        window (structured error naming the stage), a fresh collector
        keeps serving, the governor degrades then probes back, and the
        zombie thread exits once the hang resolves."""
        holder, oracle, n_rows = served_index
        stats = Stats()
        # warm with a GENEROUS bound (a first-time XLA compile is a
        # legitimate multi-hundred-ms dispatch), then shrink the knob
        # at runtime — the monitor re-derives its tick every sweep
        ex = Executor(holder, stats=stats, count_batch_window=0.002,
                      solo_fastlane=False,
                      dispatch_watchdog_seconds=5.0,
                      device_health_probe_seconds=0.1)
        assert ex.execute("i", "Count(Row(f=3))") == [int(oracle[3])]
        baseline = _pipeline_census()
        ex.batcher.watchdog_s = 0.1
        fault.set_fault("exec.dispatch_hang", "delay", times=1,
                        match={"kind": "count"}, args={"seconds": 3.0})
        t0 = time.monotonic()
        with pytest.raises(PipelineStalledError) as ei:
            ex.execute("i", "Count(Row(f=3))")
        elapsed = time.monotonic() - t0
        assert ei.value.stage == "dispatch"
        assert "quarantin" in str(ei.value)
        # bounded by the watchdog (plus one stale 1s monitor tick from
        # before the runtime shrink), far under the 3s hang
        assert elapsed < 2.5, \
            f"caller held {elapsed:.2f}s — the watchdog never fired"
        assert _counter(stats, "pipeline_watchdog_trips_total") >= 1
        assert _counter(stats, "pipeline_quarantined_windows_total") >= 1
        # the tight bound was for the injected hang alone: an honest
        # recovery dispatch (a first compile of the probe window's
        # shape) must not be quarantined by it
        ex.batcher.watchdog_s = 5.0
        # the queue keeps draining on the fresh collector (degraded
        # serving answers exactly), and probing restores healthy
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            assert ex.execute("i", "Count(Row(f=5))") == \
                [int(oracle[5])]
            if ex.batcher.governor.state == "healthy":
                break
            time.sleep(0.05)
        assert ex.batcher.governor.state == "healthy"
        # zombie collector exits once the 3s delay resolves
        census = _await_census_back_to(baseline)
        assert census["pilosa-count-batcher"] <= \
            baseline["pilosa-count-batcher"], (census, baseline)

    def test_hung_readback_quarantined(self, served_index):
        """A wedged device→host read: the readback-stage watchdog
        fails the window (stage=readback), supersedes the reader, and
        subsequent queries answer exactly."""
        holder, oracle, n_rows = served_index
        stats = Stats()
        ex = Executor(holder, stats=stats, count_batch_window=0.002,
                      solo_fastlane=False, dispatch_pipeline_depth=2,
                      dispatch_watchdog_seconds=5.0,
                      device_health_probe_seconds=0.1)
        assert ex.execute("i", "Count(Row(f=1))") == [int(oracle[1])]
        baseline = _pipeline_census()
        ex.batcher.watchdog_s = 0.1
        fault.set_fault("exec.readback_hang", "delay", times=1,
                        args={"seconds": 3.0})
        with pytest.raises(PipelineStalledError) as ei:
            ex.execute("i", "Count(Row(f=1))")
        assert ei.value.stage == "readback"
        ex.batcher.watchdog_s = 5.0  # the tight bound was the hang's
        # recovery: fresh reader, exact answers, healthy again
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            assert ex.execute("i", "Count(Row(f=2))") == \
                [int(oracle[2])]
            if ex.batcher.governor.state == "healthy":
                break
            time.sleep(0.05)
        assert ex.batcher.governor.state == "healthy"
        census = _await_census_back_to(baseline)
        assert census["pilosa-batch-readback"] <= \
            baseline["pilosa-batch-readback"], (census, baseline)

    def test_finish_window_failure_fails_items_not_wedges(
            self, served_index):
        """r18 satellite fix: an exception escaping _finish_window
        OUTSIDE _readback's per-item fallbacks used to leave every
        _Pending.event unset forever — now it fails the whole window
        loudly."""
        holder, oracle, _ = served_index
        ex = Executor(holder, stats=Stats(), count_batch_window=0.002,
                      solo_fastlane=False, dispatch_pipeline_depth=2)
        ps = _resident_plane(ex, holder)
        batcher = ex.batcher
        orig = batcher._readback
        batcher._readback = lambda w: (_ for _ in ()).throw(
            RuntimeError("synthetic readback explosion"))
        try:
            with pytest.raises(PipelineStalledError) as ei:
                batcher.submit_rowcounts(ps.plane)
            assert ei.value.stage == "readback"
            assert "synthetic readback explosion" in str(ei.value)
        finally:
            batcher._readback = orig
        got = np.asarray(batcher.submit_rowcounts(ps.plane))
        np.testing.assert_array_equal(got[:16], oracle)

    def test_collector_death_fails_backlog_immediately(
            self, served_index):
        """r18 satellite fix: a collector that dies with items queued
        fails the backlog with structured errors and keeps serving —
        the items are never orphaned until the next enqueue."""
        holder, oracle, _ = served_index
        ex = Executor(holder, stats=Stats(), count_batch_window=0.002,
                      solo_fastlane=False)
        ps = _resident_plane(ex, holder)
        batcher = ex.batcher
        orig = batcher._collect_once
        died = []

        def dying_collect():
            batcher._kick.wait()
            if not died:
                died.append(True)
                raise RuntimeError("synthetic collector death")
            return orig()

        batcher._collect_once = dying_collect
        try:
            h = batcher.enqueue_rowcounts(ps.plane)
            with pytest.raises(PipelineStalledError) as ei:
                batcher.wait(h)
            assert ei.value.stage == "collect"
            assert "collector failed" in str(ei.value)
        finally:
            batcher._collect_once = orig
        # the same worker thread survived and keeps serving
        got = np.asarray(batcher.submit_rowcounts(ps.plane))
        np.testing.assert_array_equal(got[:16], oracle)

    def test_no_thread_leak_after_repeated_quarantines(
            self, served_index):
        """The thread-leak pin extended to the r18 machinery: three
        quarantine-and-recover cycles must not accumulate collector /
        readback / watchdog threads."""
        holder, oracle, _ = served_index
        ex = Executor(holder, stats=Stats(), count_batch_window=0.002,
                      solo_fastlane=False,
                      dispatch_watchdog_seconds=5.0,
                      device_health_probe_seconds=0.05)
        assert ex.execute("i", "Count(Row(f=0))") == [int(oracle[0])]
        baseline_census = _pipeline_census()
        baseline = threading.active_count()
        for _ in range(3):
            # the tight bound holds around the injected hang only
            ex.batcher.watchdog_s = 0.08
            fault.set_fault("exec.dispatch_hang", "delay", times=1,
                            match={"kind": "count"},
                            args={"seconds": 2.0})
            with pytest.raises(PipelineStalledError):
                ex.execute("i", "Count(Row(f=0))")
            ex.batcher.watchdog_s = 5.0
            # serve back to healthy before the next cycle
            deadline = time.monotonic() + 10
            while (ex.batcher.governor.state != "healthy"
                   and time.monotonic() < deadline):
                ex.execute("i", "Count(Row(f=1))")
                time.sleep(0.02)
        census = _await_census_back_to(baseline_census)
        for name, count in baseline_census.items():
            assert census[name] <= count, (census, baseline_census)
        # zombies drain on their own schedule; poll, don't sleep-assert
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            leaked = threading.active_count() - baseline
            if leaked <= 2:
                break
            time.sleep(0.2)
        assert leaked <= 2, \
            f"{leaked} threads leaked across 3 quarantine cycles"


class TestGovernor:
    def test_unit_transitions(self):
        g = DeviceHealthGovernor(probe_after_s=0.05)
        assert g.state == "healthy" and g.admit() and g.fastlane_ok()
        g.record_fault()
        g.record_fault()
        assert g.state == "healthy"  # below threshold
        g.record_success()
        g.record_fault()
        g.record_fault()
        assert g.state == "healthy"  # streak was reset
        g.record_fault()
        assert g.state == "degraded"
        assert not g.admit() and not g.fastlane_ok()
        assert not g.pipelining_ok()
        time.sleep(0.06)
        assert g.admit()  # the probe window
        assert g.state == "probing"
        assert not g.admit()  # only ONE probe at a time
        g.record_fault()  # probe failed
        assert g.state == "degraded"
        time.sleep(0.06)
        assert g.admit()
        g.record_success()  # probe succeeded
        assert g.state == "healthy" and g.admit()
        # a watchdog trip degrades immediately, regardless of streak
        g.record_trip()
        assert g.state == "degraded"
        payload = g.payload()
        assert payload["state"] == "degraded"
        assert payload["watchdogTrips"] == 1

    def test_degraded_serving_stays_exact_then_reprobes(
            self, served_index):
        """Consecutive dispatch faults degrade the governor; every
        answer through the episode is exact (per-item fallback), and
        once the fault schedule exhausts a probe restores healthy."""
        holder, oracle, _ = served_index
        stats = Stats()
        ex = Executor(holder, stats=stats, count_batch_window=0.002,
                      solo_fastlane=False,
                      device_health_probe_seconds=0.05)
        assert ex.execute("i", "Count(Row(f=0))") == [int(oracle[0])]
        fault.set_fault("exec.dispatch_error", "error", times=4)
        saw_degraded = False
        deadline = time.monotonic() + 20
        i = 0
        while time.monotonic() < deadline:
            r = i % 8
            i += 1
            assert ex.execute("i", f"Count(Row(f={r}))") == \
                [int(oracle[r])]
            state = ex.batcher.governor.state
            if state in ("degraded", "probing"):
                saw_degraded = True
            elif state == "healthy" and saw_degraded:
                break
            time.sleep(0.01)
        assert saw_degraded, "governor never degraded"
        assert ex.batcher.governor.state == "healthy"
        # the deviceHealth surface carries the episode
        dh = ex.device_health()
        assert dh["state"] == "healthy"
        assert dh["faultsTotal"] >= 3

    def test_fastlane_gated_off_while_degraded(self, served_index):
        holder, oracle, _ = served_index
        stats = Stats()
        ex = Executor(holder, stats=stats)  # adaptive + fast lane on
        assert ex.execute("i", "Count(Row(f=2))") == [int(oracle[2])]
        base_hits = _counter(stats, "solo_fastlane_hits_total")
        assert base_hits >= 1
        ex.batcher.governor.record_trip()  # force degraded
        assert ex.execute("i", "Count(Row(f=2))") == [int(oracle[2])]
        assert _counter(stats, "solo_fastlane_hits_total") == base_hits, \
            "fast lane admitted a dispatch while degraded"


class TestKnobOffContract:
    def test_depth_one_watchdog_off_restores_inline_contract(
            self, served_index):
        """pipeline_depth<=1 + watchdog off = the pre-r18 inline loop:
        no reader thread, no watchdog thread, no window registry
        churn, identical answers."""
        holder, oracle, n_rows = served_index
        ex = Executor(holder, stats=Stats(), count_batch_window=0.001,
                      dispatch_pipeline_depth=1,
                      dispatch_watchdog_seconds=0)
        for r in (2, 9):
            assert ex.execute("i", f"Count(Row(f={r}))") == \
                [int(oracle[r])]
        b = ex.batcher
        assert b._readq is None
        assert b._read_thread is None
        # knob off = THIS batcher never started a monitor (other
        # tests' executors may still be draining theirs process-wide)
        assert b._watchdog is None
        assert not b._windows
        # the governor exists but never intervened
        assert b.governor.state == "healthy"
        assert ex.device_health()["watchdogSeconds"] == 0.0

    def test_watchdog_on_happy_path_answers_unchanged(
            self, served_index):
        """The monitor must cost nothing on the happy path: with the
        watchdog armed tight, a clean serve pattern never trips it."""
        holder, oracle, n_rows = served_index
        stats = Stats()
        ex = Executor(holder, stats=stats, count_batch_window=0.002,
                      solo_fastlane=False,
                      dispatch_watchdog_seconds=0.5)
        for _ in range(3):
            for r in range(n_rows):
                assert ex.execute("i", f"Count(Row(f={r}))") == \
                    [int(oracle[r])]
        assert _counter(stats, "pipeline_watchdog_trips_total") == 0
        assert _counter(stats,
                        "pipeline_quarantined_windows_total") == 0
        assert ex.batcher.governor.state == "healthy"
