"""Concurrency stress: writers + readers racing on one holder/executor.

The reference runs its whole suite under ``go test -race`` (SURVEY.md
§5/§6); Python has no TSAN, so the mitigation is lock discipline
(per-fragment RLock, plane-cache generation invalidation) exercised
here under real thread contention: no exceptions, no torn reads, exact
final counts."""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu.exec import Executor
from pilosa_tpu.store import FieldOptions, Holder


@pytest.mark.parametrize("n_writers,n_readers", [(4, 4)])
def test_concurrent_writes_and_queries(tmp_path, n_writers, n_readers):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("amount", FieldOptions(type="int", min=0, max=10**6))
    ex = Executor(holder)

    per_writer = 300
    errors: list[Exception] = []
    start = threading.Barrier(n_writers + n_readers)

    def writer(wid: int):
        try:
            start.wait()
            rng = np.random.default_rng(wid)
            for i in range(per_writer):
                col = wid * per_writer + i
                ex.execute("i", f"Set({col}, f={wid})")
                if i % 7 == 0:
                    ex.execute("i", f"Set({col}, amount={int(rng.integers(1000))})")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            start.wait()
            for _ in range(50):
                (n,) = ex.execute("i", "Count(All())")
                assert 0 <= n <= n_writers * per_writer
                ex.execute("i", "TopN(f, n=3)")
                ex.execute("i", "Sum(field=amount)")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    threads += [threading.Thread(target=reader) for _ in range(n_readers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]

    # exact final state
    for w in range(n_writers):
        (cnt,) = ex.execute("i", f"Count(Row(f={w}))")
        assert cnt == per_writer, f"writer {w}"
    (total,) = ex.execute("i", "Count(All())")
    assert total == n_writers * per_writer


def test_concurrent_fragment_mutation(tmp_path):
    """Many threads hammering one fragment: bits must be a clean union."""
    from pilosa_tpu.store.fragment import Fragment
    frag = Fragment(str(tmp_path / "0"), 0, max_op_n=50).open()
    errors = []

    def worker(wid: int):
        try:
            cols = np.arange(wid * 1000, (wid + 1) * 1000, dtype=np.uint64)
            for chunk in np.array_split(cols, 10):
                frag.set_bits(np.full(len(chunk), 1, np.uint64), chunk)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert frag.row(1).cardinality == 8000
    # crash-replay under the concurrent op-log interleaving
    g = Fragment(str(tmp_path / "0"), 0).open()
    assert g.row(1).cardinality == 8000


def test_parallel_holder_open(tmp_path):
    h = Holder(str(tmp_path)).open()
    for i in range(5):
        idx = h.create_index(f"idx{i}")
        idx.create_field("f")
        idx.set_bit("f", 1, i * 10)
    h.close()
    h2 = Holder(str(tmp_path)).open()  # concurrent index opens
    assert sorted(h2.indexes) == [f"idx{i}" for i in range(5)]
    ex = Executor(h2)
    for i in range(5):
        assert ex.execute(f"idx{i}", "Count(Row(f=1))") == [1]


def test_kill9_server_durability(tmp_path):
    """Full-process crash: start a real server, write over HTTP, SIGKILL
    it mid-life, restart on the same data dir — everything written and
    acknowledged must still be there (snapshot + op-log replay)."""
    import os
    import signal
    import subprocess
    import sys
    import time
    import urllib.request

    from pilosa_tpu.api.client import Client

    data = str(tmp_path / "data")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # ask the OS for a free port first
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = subprocess.Popen(
        [sys.executable, "-m", "pilosa_tpu.cli", "server",
         "--data-dir", data, "--bind", f"127.0.0.1:{port}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        cl = Client("127.0.0.1", port, timeout=5)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                cl.version()
                break
            except Exception:
                time.sleep(0.2)
        else:
            raise TimeoutError("server did not come up")
        cl.create_index("i")
        cl.create_field("i", "f")
        cl.create_field("i", "n", {"type": "int", "min": 0, "max": 1000})
        cl.import_bits("i", "f", rowIDs=[1, 2, 3], columnIDs=[10, 20, 30])
        cl.query("i", "Set(40, f=1) Set(5, n=777)")
        assert cl.query("i", "Count(Row(f=1))") == [2]
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

    # reopen the data dir in-process: acknowledged writes must survive
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder
    h = Holder(data).open()
    ex = Executor(h)
    assert ex.execute("i", "Count(Row(f=1))") == [2]
    (r,) = ex.execute("i", "Row(f=1)")
    assert list(r.columns) == [10, 40]
    (s_,) = ex.execute("i", "Sum(field=n)")
    assert (s_.value, s_.count) == (777, 1)


class XlaRuntimeError(Exception):
    """Shape of jax's device-OOM error (_is_device_oom matches on the
    type NAME + RESOURCE_EXHAUSTED in the message)."""


def _pressure_fixture(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    ex = Executor(holder)
    for r in range(1, 6):
        for c in range(10 * r):
            ex.execute("i", f"Set({c}, f={r})")
    for c in range(25):
        ex.execute("i", f"Set({c}, g=1)")
    return holder, ex


def test_oom_recovery_under_concurrency(tmp_path):
    """Concurrent queries each hitting a device OOM must ALL recover
    and answer exactly — no 5xx, no thrash (r5: the r4 evict-all retry
    ping-ponged under concurrent over-budget load and a second OOM
    escaped as 500)."""
    _, ex = _pressure_fixture(tmp_path)
    expected = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs

    real_build = ex.planes._build_plane
    seen: set[int] = set()
    inject = threading.Lock()

    def flaky(field, view_name, shards):
        with inject:
            first = threading.get_ident() not in seen
            seen.add(threading.get_ident())
        if first:
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory "
                                  "allocating plane")
        return real_build(field, view_name, shards)

    ex.planes.invalidate()
    ex.planes._build_plane = flaky
    results, errors = {}, []
    start = threading.Barrier(8)

    def worker(i):
        try:
            start.wait()
            results[i] = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    assert len(seen) >= 1  # at least one thread took the OOM path
    assert all(results[i] == expected for i in range(8))
    # the recovery must leave no in-flight bookkeeping behind
    assert ex._inflight == 0
    assert not ex.planes._leases


def test_oom_exclusive_stage_recovers(tmp_path):
    """A query whose stage-1 retry ALSO OOMs drains to exclusivity,
    drops all residency, and still answers (r4: the second OOM was a
    500)."""
    _, ex = _pressure_fixture(tmp_path)
    expected = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs
    ex.planes.invalidate()

    real_build = ex.planes._build_plane
    fails = {"n": 2}

    def flaky(field, view_name, shards):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return real_build(field, view_name, shards)

    ex.planes._build_plane = flaky
    got = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs
    assert got == expected
    assert fails["n"] == 0
    assert ex._inflight == 0


def test_leased_planes_survive_unpinned_eviction(tmp_path):
    """Stage-1 eviction frees only planes NO in-flight query holds:
    evicting leased entries frees no HBM (live refs) and forces
    mid-flight rebuilds."""
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder)
    ex.execute("i", "Set(1, f=1)")
    field = idx.field("f")
    cache = ex.planes

    cache.begin_query()
    try:
        cache.field_plane("i", field, "standard", (0,))
        assert cache.has_plane("i", field, "standard", (0,))
        cache.evict_unpinned()
        assert cache.has_plane("i", field, "standard", (0,)), \
            "leased plane must survive unpinned eviction"
    finally:
        cache.end_query()
    cache.evict_unpinned()
    assert not cache.has_plane("i", field, "standard", (0,))


def test_cross_request_count_batching(tmp_path):
    """Concurrent Counts through a batching executor coalesce into few
    programs with exact results."""
    import threading

    from pilosa_tpu.store import Holder
    from pilosa_tpu.exec import Executor

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder, count_batch_window=0.01)
    for r in range(1, 9):
        for c in range(r):
            ex.execute("i", f"Set({c}, f={r})")

    results = {}
    start = threading.Barrier(8)

    def worker(r):
        start.wait()
        (cnt,) = ex.execute("i", f"Count(Row(f={r}))")
        results[r] = cnt

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(1, 9)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert results == {r: r for r in range(1, 9)}
    # coalesced: far fewer programs than counts (8 concurrent -> 1-2
    # batch programs; exact number depends on arrival timing)
    batch_programs = [k for k in ex.fused._programs
                     if k[1] == "count-batch"]
    assert 1 <= len(batch_programs) <= 4


def test_cross_request_bsi_aggregate_batching(tmp_path):
    """Concurrent Sum/Min/Max join the same batcher window as Counts
    (VERDICT r1: BSI paths must amortize the per-read floor too)."""
    import threading

    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import FieldOptions, Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=-100, max=100))
    ex = Executor(holder, count_batch_window=0.01)
    vals = {1: -42, 2: 17, 3: 5, 4: 99}
    for c, v in vals.items():
        ex.execute("i", f"Set({c}, v={v})")
    ex.execute("i", "Set(2, f=1) Set(3, f=1)")

    results = {}
    start = threading.Barrier(8)

    def worker(i, pql):
        start.wait()
        (r,) = ex.execute("i", pql)
        results[i] = r

    cases = ["Sum(field=v)", "Min(field=v)", "Max(field=v)",
             "Sum(Row(f=1), field=v)", "Min(Row(f=1), field=v)",
             "Max(Row(f=1), field=v)", "Count(Row(f=1))",
             "Count(Row(v > 10))"]
    threads = [threading.Thread(target=worker, args=(i, p))
               for i, p in enumerate(cases)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert (results[0].value, results[0].count) == (sum(vals.values()), 4)
    assert (results[1].value, results[1].count) == (-42, 1)
    assert (results[2].value, results[2].count) == (99, 1)
    assert (results[3].value, results[3].count) == (22, 2)
    assert (results[4].value, results[4].count) == (5, 1)
    assert (results[5].value, results[5].count) == (17, 1)
    assert results[6] == 2
    assert results[7] == 2
    agg_programs = [k for k in ex.fused._programs
                    if isinstance(k[0], tuple)
                    and k[0][0] in ("sum-plane", "minmax-plane")]
    assert agg_programs, "aggregates must run through the batch programs"


def test_oom_matcher_catches_async_read_valueerror(tmp_path):
    """A PJRT plug-in backend may surface an async execution's device
    OOM at the HOST READ as a plain ValueError carrying
    RESOURCE_EXHAUSTED (not XlaRuntimeError) — config14 r5: the typed
    matcher missed it and 32 concurrent streams all answered 500 with
    zero recovery attempts."""
    _, ex = _pressure_fixture(tmp_path)
    expected = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs

    real_build = ex.planes._build_plane
    hits = []

    def flaky(field, view_name, shards):
        if not hits:
            hits.append(1)
            raise ValueError(
                "RESOURCE_EXHAUSTED: TPU backend error (ResourceExhausted).")
        return real_build(field, view_name, shards)

    ex.planes.invalidate()
    ex.planes._build_plane = flaky
    got = ex.execute("i", "TopN(f, Row(g=1), n=3)")[0].pairs
    assert got == expected and hits


def test_bounded_concurrency_queues_excess_queries(tmp_path):
    """max_concurrent admission: with 2 slots and 6 clients, no more
    than 2 queries EXECUTE at once; all 6 answer exactly."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder, max_concurrent=2)
    for c in range(50):
        ex.execute("i", f"Set({c}, f={c % 3})")
    want = ex.execute("i", "Count(Row(f=1))")[0]

    active = [0]
    peak = [0]
    gate = threading.Lock()
    real = ex._execute_calls

    def spy(*a, **kw):
        with gate:
            active[0] += 1
            peak[0] = max(peak[0], active[0])
        try:
            time.sleep(0.05)
            return real(*a, **kw)
        finally:
            with gate:
                active[0] -= 1

    ex._execute_calls = spy
    errors, results = [], []

    def worker():
        try:
            results.append(ex.execute("i", "Count(Row(f=1))")[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:2]
    assert results == [want] * 6
    assert peak[0] <= 2, f"peak concurrent executions {peak[0]}"


def test_admission_slot_survives_setup_failure(tmp_path):
    """ADVICE r5: the admission semaphore used to leak its slot when
    begin_query() raised after acquisition — max_concurrent such
    failures turned into a permanent 180s-timeout outage.  Force the
    failure max_concurrent times; queries must still admit."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder, max_concurrent=2)
    for c in range(10):
        ex.execute("i", f"Set({c}, f=1)")

    real = ex.planes.begin_query
    failures = [0]

    def flaky():
        if failures[0] < 2:  # == max_concurrent
            failures[0] += 1
            raise RuntimeError("injected begin_query failure")
        return real()

    ex.planes.begin_query = flaky
    for _ in range(2):
        with pytest.raises(RuntimeError):
            ex.execute("i", "Count(Row(f=1))")
    # both slots must have been released: this admits immediately
    # (a leak would park it behind the 180s acquire timeout)
    assert ex.execute("i", "Count(Row(f=1))")[0] == 10
    assert failures[0] == 2


def test_adaptive_batcher_default_on_no_solo_window(tmp_path):
    """The batcher is the default serving spine with an ADAPTIVE
    window: solo traffic must never wait out a collection window (the
    window stays 0), and sequential queries answer exactly."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    ex = Executor(holder)  # default: count_batch_window="adaptive"
    assert ex.batcher is not None and ex.batcher.adaptive
    for c in range(7):
        ex.execute("i", f"Set({c}, f=1)")
    t0 = time.perf_counter()
    for _ in range(10):
        assert ex.execute("i", "Count(Row(f=1))")[0] == 7
    solo = (time.perf_counter() - t0) / 10
    # the window never opened for solo traffic…
    assert ex.batcher.current_window == 0.0
    # …and per-query latency is nowhere near the max window (50ms is
    # generous vs ADAPT_MAX=5ms: a regression that waits the window
    # per solo query would trip this on any CI box)
    assert solo < 0.05, f"solo count took {solo * 1e3:.1f} ms"


def test_adaptive_batcher_window_grows_and_decays(tmp_path):
    """Under queue pressure the window opens (requests coalesce into
    shared batches); once traffic is solo again it decays back to 0."""
    from pilosa_tpu.obs import Stats
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    stats = Stats()
    ex = Executor(holder, stats=stats)
    for r in range(1, 9):
        for c in range(r):
            ex.execute("i", f"Set({c}, f={r})")

    coalesced = False
    for _ in range(3):  # retry: arrival overlap is scheduler-dependent
        start = threading.Barrier(8)
        errors = []

        def worker(r):
            try:
                start.wait()
                for _ in range(4):
                    assert ex.execute("i", f"Count(Row(f={r}))")[0] == r
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[:2]
        counters = stats.snapshot()["counters"]
        items = sum(counters.get("batcher_items", {}).values())
        batches = sum(counters.get("batcher_batches", {}).values())
        if items > batches:
            coalesced = True
            break
    assert coalesced, "concurrent counts never coalesced"
    # solo traffic decays the window back to zero
    for _ in range(12):
        assert ex.execute("i", "Count(Row(f=3))")[0] == 3
    assert ex.batcher.current_window == 0.0


def test_topn_and_distinct_coalesce(tmp_path):
    """The remaining one-dispatch-one-read families ride the batcher:
    concurrent dense TopN shares a rowcounts program (identical planes
    dedupe), Distinct shares a presence scan — all answers exact."""
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.store import FieldOptions, Holder

    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=0, max=200))
    ex = Executor(holder)
    for r in range(1, 5):
        for c in range(r * 3):
            ex.execute("i", f"Set({c}, f={r})")
    for c in range(12):
        ex.execute("i", f"Set({c}, v={(c % 3) * 7})")

    want_topn = ex.execute("i", "TopN(f, n=4)")[0].pairs
    want_distinct = ex.execute("i", "Distinct(field=v)")[0].values
    assert want_distinct == [0, 7, 14]

    errors = []
    start = threading.Barrier(8)

    def worker(i):
        try:
            start.wait()
            for _ in range(3):
                if i % 2:
                    got = ex.execute("i", "TopN(f, n=4)")[0].pairs
                    assert got == want_topn
                else:
                    got = ex.execute("i", "Distinct(field=v)")[0].values
                    assert got == want_distinct
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:3]
    # the dense TopN counts ran through the batched rowcounts program
    assert any(isinstance(k, tuple) and k[-1] == "rowcounts-batch"
               for k in ex.fused._programs), \
        "TopN never used the coalesced rowcounts program"
