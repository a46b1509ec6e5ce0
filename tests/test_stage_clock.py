"""The stage clock from socket to socket (``obs.metrics.StageTimer``):
every served request is charged to a named stage on every path, the
batcher's wait is split from the read, the same stages land in the
profiler's trace while a ``/debug/profile`` capture is open, and
``obs/gaps.py`` puts the device's idle time down to them.  Toy sizes on
the CPU: answers and counts, never speeds."""

import glob
import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from pilosa_tpu import fault
from pilosa_tpu.api import API, Server
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import Stats, gaps
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.obs.metrics import StageTimer
from pilosa_tpu.store import FieldOptions, Holder

EDGE_STAGES = {"http_in", "admit", "plan", "assemble", "encode", "http_out"}
ALL_STAGES = EDGE_STAGES | {"plan_cache", "parse", "queue", "dispatch",
                            "read", "deliver"}


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _stages(stats) -> dict:
    return {k.split("=", 1)[1]: (v["count"], v["sum"]) for k, v in
            stats.histogram_summary("query_stage_seconds").items()}


def _delta(before: dict, after: dict) -> dict:
    """{stage: (observations, seconds)} of the stages observed between
    two readings."""
    out = {}
    for stage, (n, s) in after.items():
        n0, s0 = before.get(stage, (0, 0.0))
        if n != n0:
            out[stage] = (n - n0, s - s0)
    return out


def _handler_seconds(stats) -> float:
    return sum(v["sum"] for k, v in
               stats.histogram_summary("http_request_seconds").items()
               if k == "method=POST")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A three-shard index behind a real HTTP server: set fields ``f``
    and ``g`` of four rows, an int field ``v``; every plane resident
    and every program compiled before a test reads the clock."""
    tmp = tmp_path_factory.mktemp("stage_clock")
    holder = Holder(str(tmp)).open()
    stats = Stats()
    ex = Executor(holder, stats=stats, count_batch_window="adaptive")
    api = API(holder, ex, trace_sample_rate=0.0)
    server = Server(api, "127.0.0.1", 0, stats=stats).start()
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("v", FieldOptions(type="int", min=0, max=1000))
    rng = np.random.default_rng(26)
    for field in ("f", "g"):
        for row in range(4):
            cols = rng.choice(3_000_000, 400, replace=False)
            api.import_bits("i", field, row_ids=[row] * len(cols),
                            col_ids=cols.tolist())
    cols = list(range(0, 3_000_000, 7000))
    api.import_values("i", "v", col_ids=cols,
                      values=[c % 1000 for c in cols])
    conn = http.client.HTTPConnection("127.0.0.1", server.address[1])

    def query(pql: str, path: str = "/index/i/query"):
        conn.request("POST", path, pql.encode())
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200, body
        return json.loads(body)

    yield query, stats, ex, server
    conn.close()
    server.close()
    holder.close()


# -- (a) closure ---------------------------------------------------------------

@pytest.mark.parametrize("pql", [
    "Count(Row(f=1))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Sum(field=v)",
    "TopN(f, n=2)",
    "GroupBy(Rows(f), Rows(g))",
])
def test_every_served_request_is_charged_to_named_stages(pql, served):
    query, stats, _, _ = served
    for _ in range(3):      # planes resident, programs compiled
        want = query(pql)["results"]
    time.sleep(0.05)        # the last handler's finally has run
    before, wall0 = _stages(stats), _handler_seconds(stats)
    n = 5
    for _ in range(n):
        assert query(pql)["results"] == want
    time.sleep(0.05)
    got = _delta(before, _stages(stats))
    wall = _handler_seconds(stats) - wall0
    assert set(got) <= ALL_STAGES
    # every request enters the edge stages once (plan … assemble once
    # per call, and these requests hold one call)
    for stage in EDGE_STAGES:
        assert got[stage][0] == n, (stage, got)
    # the books close: the stages cover the handler's own wall time
    # (they start earlier, at the request line, so they may exceed it)
    assert sum(s for _, s in got.values()) >= 0.95 * wall, (got, wall)
    # solo traffic rides the fast lane: nothing waited for a window
    assert {"dispatch", "read", "deliver"} <= set(got)
    assert "queue" not in got


def test_a_multi_call_request_repeats_plan_to_assemble_per_call(served):
    query, stats, _, _ = served
    pql = "Sum(field=v) TopN(f, n=2) TopN(g, n=2)"
    for _ in range(2):
        query(pql)
    time.sleep(0.05)
    before = _stages(stats)
    query(pql)
    time.sleep(0.05)
    got = _delta(before, _stages(stats))
    assert got["plan"][0] == 3 and got["dispatch"][0] == 3
    assert got["http_in"][0] == got["encode"][0] == got["http_out"][0] == 1


def _grouped_counters(stats) -> dict:
    snap = stats.snapshot()["counters"]
    return {name: sum(snap.get(name, {}).values()) for name in
            ("request_call_groups_total", "request_grouped_calls_total")}


def test_a_grouped_request_passes_the_stages_once_per_group(served):
    """Twenty interleaved Sum / Count calls are two groups: the clock
    enters ``dispatch`` and ``read`` twice, the stages still cover the
    handler's wall time, and the two solo launches make
    ``batcher.requests_per_dispatch`` read 0.5 for this request."""
    query, stats, ex, _ = served
    rows = [("f", r) for r in range(4)] + [("g", r) for r in range(4)] \
        + [("f", 0), ("g", 3)]
    calls = [c for fld, r in rows for c in
             (f"Sum(Row({fld}={r}), field=v)", f"Count(Row({fld}={r}))")]
    pql = " ".join(calls)
    for _ in range(3):      # planes resident, programs compiled
        want = query(pql)["results"]
    # in call order, what each call answers alone
    assert want == [query(c)["results"][0] for c in calls]
    time.sleep(0.05)
    before, wall0 = _stages(stats), _handler_seconds(stats)
    grouped0 = _grouped_counters(stats)
    costs0 = ex.ledger.payload()
    assert query(pql)["results"] == want
    time.sleep(0.05)
    got = _delta(before, _stages(stats))
    wall = _handler_seconds(stats) - wall0
    for stage in ("plan", "dispatch", "read", "deliver"):
        assert got[stage][0] == 2, (stage, got)
    assert "queue" not in got
    for stage in EDGE_STAGES - {"plan", "assemble"}:
        assert got[stage][0] == 1, (stage, got)
    assert sum(s for _, s in got.values()) >= 0.95 * wall, (got, wall)
    grouped = _grouped_counters(stats)
    assert grouped["request_call_groups_total"] \
        - grouped0["request_call_groups_total"] == 2
    assert grouped["request_grouped_calls_total"] \
        - grouped0["request_grouped_calls_total"] == 20
    costs = ex.ledger.payload()
    assert costs["soloDispatches"] - costs0["soloDispatches"] == 2
    assert costs["windows"] == costs0["windows"]


def test_an_in_process_execute_keeps_its_own_clock(served):
    _, stats, ex, _ = served
    ex.execute("i", "Count(Row(f=3))")
    before = _stages(stats)
    ex.execute("i", "Count(Row(f=3))")
    got = _delta(before, _stages(stats))
    assert {"admit", "plan", "dispatch", "read", "assemble"} <= set(got)
    assert not {"http_in", "encode", "http_out"} & set(got)
    assert obs_metrics.current_timer() is None  # nothing left behind


def test_stages_are_forward_charged_and_close_on_finish():
    stats = Stats()
    timer = StageTimer(stats, "http_in")
    time.sleep(0.01)
    timer.enter("plan")
    timer.enter("plan")             # entering the open stage: no-op
    time.sleep(0.01)
    t0 = time.perf_counter()
    timer.enter("queue", at=t0)
    time.sleep(0.03)
    # three threads' stamps re-cut the blocked interval; a stamp that
    # was never reached (None) hands its time to the stage before it
    timer.recut((("dispatch", t0 + 0.01), ("read", None),
                 ("deliver", t0 + 0.02)))
    assert timer.stage == "deliver"
    timer.enter("assemble")
    timer.finish()
    got = _stages(stats)
    assert set(got) == {"http_in", "plan", "queue", "dispatch", "deliver",
                        "assemble"}
    assert all(n == 1 for n, _ in got.values())
    assert got["queue"][1] == pytest.approx(0.01, abs=2e-3)
    assert got["dispatch"][1] == pytest.approx(0.01, abs=2e-3)
    assert got["deliver"][1] >= 0.009


# -- (b) the window path: queue / dispatch / read / deliver -------------------

def _windowed_executor(tmp_path):
    holder = Holder(str(tmp_path)).open()
    idx = holder.create_index("i")
    idx.create_field("f")
    stats = Stats()
    ex = Executor(holder, stats=stats, count_batch_window=0.02,
                  solo_fastlane=False)
    api = API(holder, ex)
    rng = np.random.default_rng(7)
    for row in range(4):
        cols = rng.choice(2_000_000, 300, replace=False)
        api.import_bits("i", "f", row_ids=[row] * len(cols),
                        col_ids=cols.tolist())
    return holder, stats, ex


def _two_callers(ex) -> list:
    out, errs = [None, None], []

    def call(i):
        try:
            out[i] = ex.execute("i", f"Count(Row(f={i + 1}))")
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)
    threads = [threading.Thread(target=call, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs and not any(t.is_alive() for t in threads), errs
    return out


@pytest.mark.parametrize("failpoint,lands_in", [
    ("exec.dispatch_hang", "dispatch"),
    ("exec.readback_hang", "read"),
])
def test_the_window_wait_is_split_from_dispatch_and_read(
        failpoint, lands_in, tmp_path):
    holder, stats, ex = _windowed_executor(tmp_path)
    try:
        want = _two_callers(ex)     # plane resident, program compiled
        _two_callers(ex)
        before = _stages(stats)
        assert _two_callers(ex) == want
        got = _delta(before, _stages(stats))
        # every stage of the window path is observed on its own
        assert {"queue", "dispatch", "read", "deliver"} <= set(got), got
        quiet = {s: got[s][1] for s in ("queue", "dispatch", "read")}
        fault.set_fault(failpoint, "delay", times=1,
                        args={"seconds": 0.4})
        before = _stages(stats)
        assert _two_callers(ex) == want
        got = _delta(before, _stages(stats))
        per_caller = {s: got[s][1] / got[s][0]
                      for s in ("queue", "dispatch", "read")}
        # the injected stall is in the stage it was injected into …
        assert per_caller[lands_in] >= 0.35, per_caller
        # … and in neither of the others: `read` no longer holds the
        # window wait, `queue` no longer hides a slow dispatch
        for stage in {"queue", "dispatch", "read"} - {lands_in}:
            assert per_caller[stage] < 0.2, (stage, per_caller, quiet)
    finally:
        holder.close()


SUMS = "Sum(Row(f=0), field=v) Sum(Row(f=1), field=v) Sum(Row(g=2), field=v)"


@pytest.fixture
def slow_decodes(served, monkeypatch):
    """The K-item Sum's decodes each sleep ``SLOW`` seconds inside a
    ``test.decode`` annotation: host work after the one read."""
    _, _, ex, _ = served
    fused = ex.batcher.fused
    real = fused.run_agg_plane_batch
    calls = []

    def slow(*a, **k):
        out, assign, decode = real(*a, **k)

        def decode_slowly(row):
            import jax.profiler
            with jax.profiler.TraceAnnotation("test.decode"):
                calls.append(row)
                time.sleep(SLOW)
                return decode(row)
        return out, assign, decode_slowly
    monkeypatch.setattr(fused, "run_agg_plane_batch", slow)
    return calls


SLOW = 0.03


def test_a_k_item_sums_decodes_are_booked_after_the_read(served,
                                                          slow_decodes):
    """``_fastlane_agg`` ends ``read`` when the value is on the host:
    the K decodes that follow are ``deliver``'s, not the device's."""
    _, stats, ex, _ = served
    want = ex.execute("i", SUMS)           # planes resident, compiled
    slow_decodes.clear()
    before = _stages(stats)
    assert ex.execute("i", SUMS) == want
    got = _delta(before, _stages(stats))
    assert len(slow_decodes) == 3          # one launch, three decodes
    assert got["read"][0] == 1 and got["deliver"][0] == 1, got
    assert got["deliver"][1] >= 3 * SLOW
    assert got["read"][1] < SLOW, got


def test_a_captured_read_event_ends_before_the_decodes(served, slow_decodes,
                                                       tmp_path,
                                                       monkeypatch):
    import jax.profiler
    from jax.profiler import ProfileData
    _, _, ex, _ = served
    want = ex.execute("i", SUMS)
    jax.profiler.start_trace(str(tmp_path))
    monkeypatch.setattr(obs_metrics, "capture_open", True)
    try:
        assert ex.execute("i", SUMS) == want
    finally:
        monkeypatch.setattr(obs_metrics, "capture_open", False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    reads, decodes, delivers, stages = [], [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                lo, hi = e.start_ns, e.start_ns + e.duration_ns
                {"pilosa.read": reads, "test.decode": decodes,
                 "pilosa.deliver": delivers}.get(e.name, []).append((lo, hi))
                if e.name.startswith("pilosa."):
                    stages.append((lo, hi))
    # one request's stage events leave no hole between them: each
    # opens before the one it follows has closed
    stages.sort()
    assert len(stages) >= 5
    assert all(b_lo <= a_hi for (_, a_hi), (b_lo, _) in zip(stages,
                                                             stages[1:]))
    assert len(reads) == 1 and len(decodes) == 3 and delivers, \
        (reads, decodes, delivers)
    # the read event closed before the first decode began, and the
    # decodes lie inside the deliver event that followed it
    assert reads[0][1] <= min(lo for lo, _ in decodes)
    assert any(d_lo <= min(lo for lo, _ in decodes)
               and max(hi for _, hi in decodes) <= d_hi
               for d_lo, d_hi in delivers)


def test_the_http_in_event_starts_where_its_stage_does(served, tmp_path,
                                                       monkeypatch):
    """The stage clock dates ``http_in`` from the request line; under a
    capture its event opens there too, so the header parsing is in it
    and not in the no-request time between two requests."""
    import http.server

    import jax.profiler
    from jax.profiler import ProfileData
    query, _, _, _ = served
    query("Count(Row(f=1))")
    real = http.server.BaseHTTPRequestHandler.parse_request

    def slow_headers(self):
        time.sleep(0.03)
        return real(self)
    monkeypatch.setattr(http.server.BaseHTTPRequestHandler,
                        "parse_request", slow_headers)
    jax.profiler.start_trace(str(tmp_path))
    monkeypatch.setattr(obs_metrics, "capture_open", True)
    try:
        query("Count(Row(f=1))")
    finally:
        monkeypatch.setattr(obs_metrics, "capture_open", False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    http_in = [e.duration_ns for plane in ProfileData.from_file(path).planes
               for line in plane.lines for e in line.events
               if e.name == "pilosa.http_in"]
    assert len(http_in) == 1 and http_in[0] >= 0.03e9, http_in


# -- (c) a plan that serves nothing -------------------------------------------

def test_a_plan_whose_plane_is_not_resident_falls_through_once(tmp_path):
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        idx.create_field("f")
        stats = Stats()
        ex = Executor(holder, stats=stats, count_batch_window="adaptive")
        api = API(holder, ex)
        api.import_bits("i", "f", row_ids=[1, 1, 2], col_ids=[5, 9, 5])

        def counter(name):
            return sum(stats.snapshot()["counters"].get(name, {}).values())
        assert counter("plan_cache_fallthrough_total") == 0  # registered
        text = stats.prometheus_text()
        assert "plan_cache_fallthrough_total 0" in text
        pql = "Count(Row(f=1))"
        assert ex.execute("i", pql) == [2]      # builds + caches the plan
        ex.planes.invalidate()                  # … whose plane is gone
        ex.planes.wait_builds()
        real_has_plane = ex.planes.has_plane
        ex.planes.has_plane = lambda *a, **k: False
        slow_plan = ex._run_plan_inner

        def stalled(ctx, entry):
            time.sleep(0.05)                    # the failed attempt
            return slow_plan(ctx, entry)
        ex._run_plan_inner = stalled
        before = _stages(stats)
        hits0, falls0 = counter("plan_cache_hits"), \
            counter("plan_cache_fallthrough_total")
        try:
            assert ex.execute("i", pql) == [2]
        finally:
            ex.planes.has_plane = real_has_plane
            ex._run_plan_inner = slow_plan
        got = _delta(before, _stages(stats))
        assert counter("plan_cache_hits") == hits0 + 1
        assert counter("plan_cache_fallthrough_total") == falls0 + 1
        # the attempt's time is the plan cache's, not the parser's
        assert got["plan_cache"][1] >= 0.05
        assert got["parse"][1] < 0.02 and got["plan"][0] >= 1
        # and the slow log names the path that answered
        assert ex.serving_path() == "generic per-row"
        ex.planes.wait_builds()
    finally:
        holder.close()


def test_a_plan_whose_field_stays_per_row_is_planned_once(tmp_path):
    """The twin of the fall-through above: one row of a 32-row field is
    a tiny slice of a huge row set, so the selectivity rule keeps the
    field per-row and there is no admission decision to leave to the
    un-cached path — the hit is answered by the entry's per-row form,
    ``plan_cache`` and ``plan`` are entered once each, and nothing is
    parsed."""
    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("i")
        idx.create_field("f")
        stats = Stats()
        ex = Executor(holder, stats=stats, count_batch_window="adaptive")
        api = API(holder, ex)
        api.import_bits("i", "f", row_ids=list(range(32)) + [1],
                        col_ids=list(range(32)) + [99])

        def counter(name):
            return sum(stats.snapshot()["counters"].get(name, {}).values())
        assert counter("plan_cache_row_serves_total") == 0  # registered
        assert "plan_cache_row_serves_total 0" in stats.prometheus_text()
        for pql, want in (("Count(Row(f=1))", [2]),
                          ("Count(Intersect(Row(f=1), Row(f=2)))", [0])):
            assert ex.execute("i", pql) == want     # builds + serves
            before = _stages(stats)
            hits0, falls0, serves0 = (counter("plan_cache_hits"),
                                      counter("plan_cache_fallthrough_total"),
                                      counter("plan_cache_row_serves_total"))
            assert ex.execute("i", pql) == want
            got = _delta(before, _stages(stats))
            assert counter("plan_cache_hits") == hits0 + 1
            assert counter("plan_cache_fallthrough_total") == falls0
            assert counter("plan_cache_row_serves_total") == serves0 + 1
            assert got["plan_cache"][0] == 1 and got["plan"][0] == 1
            assert "parse" not in got
            assert {"dispatch", "read", "assemble"} <= set(got)
            assert ex.serving_path() == "plan-cached per-row"
        assert ex.planes.builds == 0
    finally:
        holder.close()


# -- (d) the second sink: the profiler's trace --------------------------------

def _trace_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names, with_trace_id = [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                names.append(e.name)
                if e.name.startswith("pilosa.") and \
                        dict(e.stats).get("trace_id"):
                    with_trace_id += 1
    return names, with_trace_id


def test_a_capture_holds_the_stages_and_no_python_calls(served, tmp_path):
    query, _, _, server = served
    query("Count(Row(f=1))")
    port = server.address[1]
    reply = {}

    def capture():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", f"/debug/profile?seconds=0.5&dir={tmp_path}")
        resp = conn.getresponse()
        reply.update(status=resp.status, body=json.loads(resp.read()))
        conn.close()
    t = threading.Thread(target=capture)
    t.start()
    deadline = time.monotonic() + 60
    while not obs_metrics.capture_open and time.monotonic() < deadline \
            and t.is_alive():
        time.sleep(0.01)
    while obs_metrics.capture_open:
        query("Count(Row(f=1))")
        query("Count(Intersect(Row(f=1), Row(f=2)))")
    t.join(timeout=120)
    assert not t.is_alive() and reply["status"] == 200, reply
    assert obs_metrics.capture_open is False
    names, with_trace_id = _trace_events(str(tmp_path))
    seen = {n for n in names if n.startswith("pilosa.")}
    for stage in ("http_in", "admit", "plan", "dispatch", "read", "deliver",
                  "assemble", "encode", "http_out"):
        assert "pilosa." + stage in seen, (stage, sorted(seen))
    assert with_trace_id > 0    # the request's trace id rides its spans
    # the Python tracer is off: no event per Python call (its events
    # are named "$<file>:<line> <function>")
    assert not [n for n in names if n.startswith("$")][:5]


def test_with_no_capture_open_no_annotation_is_constructed(
        served, monkeypatch):
    import jax.profiler
    query, _, _, _ = served
    built = []
    real = jax.profiler.TraceAnnotation

    def counting(*a, **k):
        built.append(a)
        return real(*a, **k)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    assert obs_metrics.capture_open is False
    for pql in ("Count(Row(f=1))", "Sum(field=v)", "TopN(f, n=2)"):
        query(pql)
    assert obs_metrics.swap_span(None, "plan") is None
    with obs_metrics.span("compile", family="count"):
        pass
    assert built == []
    monkeypatch.setattr(obs_metrics, "capture_open", True)
    span = obs_metrics.swap_span(None, "plan", "abc")
    assert span is not None and obs_metrics.swap_span(span, None) is None
    assert [a[0] for a in built] == ["pilosa.plan"]


# -- (e) obs/gaps.py on synthetic events --------------------------------------

MS = 1_000_000


def test_gaps_are_put_down_to_what_covered_them():
    host = [("pilosa.http_in", 0, 1 * MS), ("pilosa.plan", 1 * MS, 3 * MS),
            ("pilosa.dispatch", 3 * MS, 4 * MS),
            ("pilosa.read", 4 * MS, 8 * MS),
            ("pilosa.assemble", 8 * MS, 9 * MS),
            # a batcher phase wins over the serving thread's stage
            ("pilosa.batcher.collect", 2 * MS, 2.5 * MS),
            # the next request arrives 3 ms after the last one left
            ("pilosa.http_in", 12 * MS, 13 * MS)]
    device = [(3.5 * MS, 7 * MS), (6 * MS, 7.5 * MS)]  # overlapping ops
    r = gaps.reduce_events({"/device:TPU:0": device}, host)
    assert r["window_s"] == pytest.approx(0.013)
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["idle_s"] == pytest.approx(0.009)
    by = r["idle_by_name"]
    assert by == pytest.approx({
        "no_request": 0.003, "pilosa.http_in": 0.002,
        "pilosa.plan": 0.0015, "pilosa.assemble": 0.001,
        "pilosa.batcher.collect": 0.0005, "pilosa.dispatch": 0.0005,
        "pilosa.read": 0.0005})
    # every idle second has a name, and no_request's share is reported
    assert sum(by.values()) == pytest.approx(r["idle_s"])
    assert r["no_request_share"] == pytest.approx(1 / 3)
    longest = r["longest_gaps"][0]
    assert longest["seconds"] == pytest.approx(0.0055)
    assert longest["names"][0] == ["no_request", pytest.approx(0.003)]
    # the serving thread's seconds under the batcher's phase are seen
    assert r["serving_thread_seconds_in_idle"]["pilosa.plan"] == \
        pytest.approx(0.002)
    assert r["ops_started_inside_an_event_share"] == 1.0
    assert "pilosa.batcher.collect" in gaps.render(r)


def test_gaps_with_many_serving_threads_names_the_latest_stage():
    host = [("pilosa.admit", 0, 10 * MS), ("pilosa.plan", 2 * MS, 4 * MS),
            ("pilosa.compile", 3 * MS, 3.5 * MS)]
    parts = gaps.attribute([(0, 10 * MS)], host)[0]
    assert parts == [("pilosa.admit", 2 * MS), ("pilosa.plan", 1 * MS),
                     ("pilosa.compile", 0.5 * MS), ("pilosa.plan", 0.5 * MS),
                     ("pilosa.admit", 6 * MS)]
    assert gaps.idle_gaps(gaps.union([(1, 3), (2, 5), (8, 9)]), (0, 10)) \
        == [(0, 1), (5, 8), (9, 10)]
