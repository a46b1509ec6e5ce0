"""End-to-end distributed query tracing (r9 tentpole): traceparent
validation, always-on sampled tracing with `X-Pilosa-Trace-Id` +
`/internal/traces?trace_id=` lookup, slow-query capture behind
`/debug/slow`, and the headline claim — a 3-node `profile=true` query
returns ONE span tree containing node-tagged spans from every node,
with per-stage children and intact parent linkage."""

import json
import urllib.request

import pytest

from pilosa_tpu.api import API, Client, Server
from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.obs import Stats, Tracer, parse_traceparent
from pilosa_tpu.store import Holder
from pilosa_tpu.testing import run_cluster


def walk(span: dict):
    yield span
    for child in span.get("children", []):
        yield from walk(child)


class TestTraceparentValidation:
    """Satellite: Tracer.extract must treat any malformed traceparent
    as absent — fresh root span, never an exception, never a fabricated
    trace identity."""

    @pytest.mark.parametrize("bad", [
        None, "", "00-aaaa-bbbb",            # too few segments
        "00-aaaa-bbbb-01-ff",                # too many segments
        "00--bbbb-01", "00-aaaa--01",        # empty ids
        "00-zzzz-bbbb-01", "00-aaaa-qqqq-01",  # non-hex ids
        # int(x, 16) literal quirks are NOT hex ids: underscores,
        # signs, surrounding whitespace
        "00-1_f-bbbb-01", "00-aaaa-+2a-01", "00- 2a -bbbb-01",
    ])
    def test_malformed_rejected(self, bad):
        assert parse_traceparent(bad) is None

    def test_wellformed_accepted(self):
        assert parse_traceparent("00-deadbeef-cafe-01") == \
            ("deadbeef", "cafe", "01")
        # flags ride through verbatim (the retain decision)
        assert parse_traceparent("00-deadbeef-cafe-00")[2] == "00"

    @pytest.mark.parametrize("header", [
        "00-aaaa-bbbb-01-junk", "garbage", "00-xyzw-bbbb-01",
    ])
    def test_extract_falls_back_to_fresh_root(self, header):
        t = Tracer()
        with t.extract({"Traceparent": header}, "server-side") as s:
            assert s.parent_id is None     # fresh root, not continuation
            assert s.trace_id not in ("aaaa", "xyzw")
        (root,) = t.finished()
        assert root.name == "server-side"

    def test_extract_garbage_never_raises_or_pollutes(self):
        t = Tracer()
        with t.extract({"Traceparent": "1-2"}, "a"):
            pass
        # the thread-local stack is balanced after a malformed header
        # (a stale synthetic parent would corrupt every later trace)
        with t.span("clean") as s:
            assert s.parent_id is None


@pytest.fixture
def traced_srv(tmp_path):
    holder = Holder(str(tmp_path)).open()
    api = API(holder, trace_sample_rate=1.0, slow_query_threshold=0.0)
    server = Server(api, "127.0.0.1", 0, stats=Stats()).start()
    client = Client("127.0.0.1", server.address[1])
    client.create_index("i")
    client.create_field("i", "f")
    client.query("i", "Set(1, f=1)")
    yield api, server, client
    server.close()
    holder.close()


def _post_query(port, pql, qs=""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/index/i/query{qs}",
        data=pql.encode(), method="POST")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read()), dict(resp.headers)


class TestSampledTracing:
    def test_trace_id_header_on_every_response(self, traced_srv):
        _, server, _ = traced_srv
        body, headers = _post_query(server.address[1], "Count(Row(f=1))")
        assert body == {"results": [1]}  # trace id rides a HEADER only
        assert headers["X-Pilosa-Trace-Id"]

    def test_sampled_trace_resolvable_by_id(self, traced_srv):
        _, server, c = traced_srv
        _, headers = _post_query(server.address[1], "Count(Row(f=1))")
        tid = headers["X-Pilosa-Trace-Id"]
        traces = c._json("GET",
                         f"/internal/traces?trace_id={tid}")["traces"]
        assert len(traces) == 1
        spans = list(walk(traces[0]))
        assert traces[0]["traceId"] == tid
        assert any(s["name"] == "executor.Count" for s in spans)
        assert any(s["name"].startswith("stage.") for s in spans)

    def test_unsampled_not_retained(self, traced_srv):
        api, server, c = traced_srv
        api.trace_sample_rate = 0.0
        _, headers = _post_query(server.address[1], "Count(Row(f=1))")
        tid = headers["X-Pilosa-Trace-Id"]  # header still present
        assert c._json("GET",
                       f"/internal/traces?trace_id={tid}")["traces"] == []

    def test_sampled_counter_on_metrics(self, tmp_path):
        from pilosa_tpu.exec import Executor
        holder = Holder(str(tmp_path)).open()
        stats = Stats()
        api = API(holder, Executor(holder, stats=stats),
                  trace_sample_rate=1.0, slow_query_threshold=0.0)
        server = Server(api, "127.0.0.1", 0, stats=stats).start()
        c = Client("127.0.0.1", server.address[1])
        try:
            c.create_index("i")
            c.create_field("i", "f")
            c.query("i", "Count(Row(f=1))")
            assert "trace_sampled_total 1" in c.metrics_text()
        finally:
            server.close()
            holder.close()

    def test_proto_response_carries_trace_header(self, traced_srv):
        from pilosa_tpu.api import proto
        _, server, _ = traced_srv
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.address[1]}/index/i/query",
            data=b"Count(Row(f=1))", method="POST",
            headers={"Accept": proto.CONTENT_TYPE})
        with urllib.request.urlopen(req) as resp:
            assert resp.headers["X-Pilosa-Trace-Id"]
            assert proto.decode_query_response(resp.read())["results"] \
                == [1]


class TestSlowQueryCapture:
    def test_slow_query_recorded_with_span_tree(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        stats = Stats()
        from pilosa_tpu.exec import Executor
        api = API(holder, Executor(holder, stats=stats),
                  trace_sample_rate=0.0, slow_query_threshold=1e-9)
        server = Server(api, "127.0.0.1", 0, stats=stats).start()
        c = Client("127.0.0.1", server.address[1])
        try:
            c.create_index("i")
            c.create_field("i", "f")
            c.query("i", "Set(1, f=1)")
            c.query("i", "Count(Row(f=1))", )
            slow = c._json("GET", "/debug/slow")
            assert slow["thresholdSeconds"] == 1e-9
            assert slow["total"] >= 2 and slow["kept"] >= 2
            entry = slow["slow"][0]  # newest first
            assert entry["pql"] == "Count(Row(f=1))"
            assert entry["index"] == "i" and entry["durationMs"] > 0
            assert entry["traceId"]
            # r19 satellite: every slow entry names which path
            # answered — triage starts with "was this on the fast
            # path at all"
            assert entry["path"] in (
                "fused", "generic per-row", "op-at-a-time fallback",
                "paged", "row-directory oracle", "degraded governor")
            spans = list(walk(entry["profile"]))
            assert any(s["name"] == "executor.Count" for s in spans)
            # slow traces are retained: the id resolves in the ring
            got = c._json(
                "GET",
                f"/internal/traces?trace_id={entry['traceId']}")["traces"]
            assert len(got) == 1
            # counter + /status visibility
            text = c.metrics_text()
            assert "slow_query_total" in text
            st = c.status()
            assert st["slowQueries"]["total"] >= 2
            assert st["slowQueries"]["slowestMs"] > 0
        finally:
            server.close()
            holder.close()

    def test_threshold_zero_disables(self, traced_srv):
        api, server, c = traced_srv
        assert api.slow_query_threshold == 0.0
        _post_query(server.address[1], "Count(Row(f=1))")
        assert c._json("GET", "/debug/slow")["total"] == 0

    def test_slow_ring_is_bounded(self):
        from pilosa_tpu.obs import SlowQueryLog
        log = SlowQueryLog(keep=4)
        for i in range(10):
            log.record({"durationMs": float(i)})
        s = log.summary()
        assert s["total"] == 10 and s["kept"] == 4
        assert [e["durationMs"] for e in log.entries()] == \
            [9.0, 8.0, 7.0, 6.0]

    def test_diagnostics_payload_carries_slow_summary(self, tmp_path):
        from pilosa_tpu.obs import SlowQueryLog
        from pilosa_tpu.obs.diagnostics import build_payload
        h = Holder(str(tmp_path)).open()
        log = SlowQueryLog()
        log.record({"durationMs": 12.0})
        p = build_payload(h, slow_log=log)
        assert p["slowQueries"]["total"] == 1
        h.close()


class TestLiteTracePath:
    """ISSUE 7 satellite: the retention decision (sampling / profile /
    slow-hunt floor) is made BEFORE any span materializes — an
    unsampled, unprofiled query must never build a span tree, while
    keeping its X-Pilosa-Trace-Id and slow-query capture."""

    @pytest.fixture
    def api_holder(self, tmp_path):
        holder = Holder(str(tmp_path)).open()
        api = API(holder, trace_sample_rate=0.0,
                  slow_query_threshold=0.0)
        yield api, holder
        holder.close()

    def _seed(self, api):
        api.create_index("i")
        api.create_field("i", "f")
        api.query("i", "Set(1, f=1)")

    def test_unsampled_query_builds_no_spans(self, api_holder,
                                             monkeypatch):
        """Pin the structural fix: Tracer.span (the tree builder) is
        never entered for an unsampled, unprofiled query — but the
        response still carries a trace id."""
        import pilosa_tpu.obs.tracing as tr
        api, _ = api_holder
        self._seed(api)
        calls = []
        orig = tr.Tracer.span

        def counting(self, name, **tags):
            calls.append(name)
            return orig(self, name, **tags)

        monkeypatch.setattr(tr.Tracer, "span", counting)
        out = api.query("i", "Count(Row(f=1))")
        assert out["results"] == [1] and out["traceId"]
        assert calls == [], f"unsampled query materialized: {calls}"
        # the SAME query profiled builds the full tree
        out = api.query("i", "Count(Row(f=1))", profile=True)
        assert any(n == "query" for n in calls)
        assert any(n.startswith("executor.") for n in calls)
        spans = list(walk(out["profile"][0]))
        assert any(s["name"].startswith("stage.") for s in spans)

    def test_slow_hunt_threshold_materializes_full_trees(self,
                                                         api_holder):
        """slow_query_threshold at/under SLOW_TRACE_FLOOR = the
        operator is slow-hunting: full executor trees on capture (the
        pre-r12 slow-capture contract, unchanged)."""
        api, _ = api_holder
        self._seed(api)
        api.slow_query_threshold = 1e-9
        assert api.slow_query_threshold <= api.SLOW_TRACE_FLOOR
        api.query("i", "Count(Row(f=1))")
        entry = api.slow_log.entries()[0]
        spans = list(walk(entry["profile"]))
        assert any(s["name"].startswith("executor.") for s in spans)

    def test_lite_slow_capture_has_stage_breakdown(self, api_holder):
        """A slow query on the LITE path (threshold above the floor)
        is still captured — PQL, duration, trace id, and a root with
        the per-stage breakdown — and its id resolves in the ring;
        only the per-call executor spans are absent (they were never
        built)."""
        from pilosa_tpu.obs import GLOBAL_TRACER
        api, _ = api_holder
        self._seed(api)
        api.slow_query_threshold = 1e-9
        api.SLOW_TRACE_FLOOR = 0.0  # instance override: stay lite
        out = api.query("i", "Count(Row(f=1))")
        entry = api.slow_log.entries()[0]
        assert entry["pql"] == "Count(Row(f=1))"
        assert entry["durationMs"] > 0
        assert entry["traceId"] == out["traceId"]
        root = entry["profile"]
        assert root["tags"].get("liteTrace") is True
        names = {s["name"] for s in walk(root)}
        assert any(n.startswith("stage.") for n in names)
        assert not any(n.startswith("executor.") for n in names)
        assert any(s.trace_id == out["traceId"]
                   for s in GLOBAL_TRACER.finished())

    def test_lite_trace_id_unique_per_request(self, api_holder):
        api, _ = api_holder
        self._seed(api)
        ids = {api.query("i", "Count(Row(f=1))")["traceId"]
               for _ in range(16)}
        assert len(ids) == 16


class TestDistributedProfile:
    """Acceptance: a 3-node profile=true query returns a SINGLE span
    tree containing node-tagged spans from all 3 nodes, with per-stage
    children, and remote spans parent-linked to the coordinator's
    cluster.* span."""

    @staticmethod
    def _write_until_all_nodes_own(cl, c, want_nodes: int) -> int:
        """Grow the shard set until every node owns at least one shard
        (ownership is hash-placed over random test ports, so a fixed
        shard count would flake); returns the shard count."""
        n_shards = 0
        while True:
            n_shards += 8
            assert n_shards <= 64, "placement never covered every node"
            c.query("i", "".join(f"Set({s * SHARD_WIDTH + 1}, f=1)"
                                 for s in range(n_shards)))
            groups = cl.servers[0].cluster.group_shards_by_node(
                "i", tuple(range(n_shards)))
            if len(groups) == want_nodes:
                return n_shards

    def test_three_node_single_tree(self, tmp_path):
        with run_cluster(3, str(tmp_path)) as cl:
            c = cl.client(0)
            c.create_index("i")
            c.create_field("i", "f")
            n_shards = self._write_until_all_nodes_own(cl, c, 3)
            port = cl.servers[0].http.address[1]
            body, headers = _post_query(port, "Count(Row(f=1))",
                                        qs="?profile=true")
            assert body["results"] == [n_shards]
            (root,) = body["profile"]          # ONE tree
            assert root["name"] == "query"
            spans = list(walk(root))
            by_id = {s["spanId"]: s for s in spans}
            node_ids = set(cl.node_ids())
            seen_nodes = {s["tags"].get("node") for s in spans
                          if s["tags"].get("node")}
            assert seen_nodes == node_ids, \
                f"spans missing nodes: {node_ids - seen_nodes}"
            # one trace id spans the whole tree, and it is the header's
            assert {s["traceId"] for s in spans} == \
                {headers["X-Pilosa-Trace-Id"]}
            # remote continuation spans hang off the coordinator's
            # cluster.* span: parent linkage intact across the wire
            remotes = [s for s in spans if s["name"] == "internal.query"]
            assert len(remotes) >= 2  # both peers contributed
            for r in remotes:
                parent = by_id.get(r["parentId"])
                assert parent is not None and \
                    parent["name"].startswith("cluster."), \
                    f"remote span not grafted under cluster.*: {r}"
                # per-stage children on the REMOTE side too
                sub = list(walk(r))
                assert any(s["name"].startswith("stage.") for s in sub)
                assert any(s["name"].startswith("executor.")
                           for s in sub)
            # per-stage children on the coordinator side
            assert any(s["name"].startswith("stage.") for s in spans)

    def test_remote_node_ring_keeps_its_fragment(self, tmp_path):
        """Every involved node can resolve the trace id for ITS spans
        via /internal/traces?trace_id= (the runbook's per-node view)."""
        with run_cluster(2, str(tmp_path)) as cl:
            c = cl.client(0)
            c.create_index("i")
            c.create_field("i", "f")
            self._write_until_all_nodes_own(cl, c, 2)
            port = cl.servers[0].http.address[1]
            body, headers = _post_query(port, "Count(Row(f=1))",
                                        qs="?profile=true")
            tid = headers["X-Pilosa-Trace-Id"]
            spans = [s for root in body["profile"] for s in walk(root)]
            peer_id = cl.servers[1].cluster.node_id
            assert any(s["tags"].get("node") == peer_id for s in spans)
            got = cl.client(1)._json(
                "GET", f"/internal/traces?trace_id={tid}")["traces"]
            assert got and all(t["traceId"] == tid for t in got)
            assert any(s["name"].startswith("executor.")
                       for t in got for s in walk(t))

    def test_unsampled_legs_do_not_churn_peer_ring(self, tmp_path):
        """A lite-path query (rate=0, no profile, no slow-hunt
        threshold) propagates its trace IDENTITY with flags "00":
        peers build NO subtree and must NOT record anything into
        their own 128-slot ring (at serving rates that churn would
        evict every trace an operator is actually chasing).  Full
        remote subtrees require the materialize decision — sampling,
        profile, or a slow-hunt threshold at/under SLOW_TRACE_FLOOR,
        which flips the flags to "01"."""
        with run_cluster(2, str(tmp_path), trace_sample_rate=0.0,
                         slow_query_threshold=0.0) as cl:
            c = cl.client(0)
            c.create_index("i")
            c.create_field("i", "f")
            self._write_until_all_nodes_own(cl, c, 2)
            port = cl.servers[0].http.address[1]
            _, headers = _post_query(port, "Count(Row(f=1))")
            tid = headers["X-Pilosa-Trace-Id"]
            for i in (0, 1):
                assert cl.client(i)._json(
                    "GET",
                    f"/internal/traces?trace_id={tid}")["traces"] == []
            # a slow-HUNT threshold (<= SLOW_TRACE_FLOOR) promotes
            # queries to the materializing path with flags "02": slow
            # captures carry the peers' remote subtrees, but peers
            # STILL don't churn their rings — at serving rates that
            # churn would evict the very traces being chased
            cl.servers[0].api.slow_query_threshold = 1e-9
            body, headers = _post_query(port, "Count(Row(f=1))")
            slow = c._json("GET", "/debug/slow")["slow"][0]
            peer_id = cl.servers[1].cluster.node_id
            assert any(s["tags"].get("node") == peer_id
                       for s in walk(slow["profile"]))
            # the coordinator's slow retention legitimately records
            # the "query" root (nodes share one in-process ring here);
            # what must NOT appear is a peer-side "internal.query"
            # continuation root — that's what flags "01" would have
            # ring-retained and "02" must not
            got = cl.client(1)._json(
                "GET",
                f"/internal/traces?trace_id={slow['traceId']}")["traces"]
            assert not any(t["name"] == "internal.query" for t in got)
            # lite-path queries on a CLUSTER still accumulate per-call
            # marks (dist records them on the LiteTracer), so a lite
            # slow capture has a breakdown even when the coordinator
            # owns no shards
            from pilosa_tpu.obs import LiteTracer
            lt = LiteTracer()
            cl.servers[0].cluster.dist.execute_json(
                "i", "Count(Row(f=1))", tracer=lt)
            assert any(n.startswith("cluster.") for n, _ in lt.marks)


class TestSinglePaneJoin:
    """r14 acceptance: a slow query is traceable end-to-end — a
    ``query_stage_seconds`` exemplar → ``/internal/traces?trace_id=`` →
    JSON log lines carrying the same trace id."""

    def _boot(self, tmp_path, **api_kw):
        from pilosa_tpu.exec import Executor
        holder = Holder(str(tmp_path)).open()
        stats = Stats()
        api = API(holder, Executor(holder, stats=stats), **api_kw)
        server = Server(api, "127.0.0.1", 0, stats=stats).start()
        return holder, server, Client("127.0.0.1", server.address[1])

    def test_exemplar_trace_and_logs_join_on_one_id(self, tmp_path):
        import io
        import logging as _logging
        holder, server, c = self._boot(
            tmp_path, trace_sample_rate=0.0, slow_query_threshold=1e-9)
        # route the pilosa_tpu logger through the JSON formatter into a
        # buffer (fresh handler so other tests' config can't interfere)
        from pilosa_tpu.obs import get_logger
        logger = _logging.getLogger("pilosa_tpu")
        saved = logger.handlers[:]
        logger.handlers = []
        buf = io.StringIO()
        get_logger(stream=buf, fmt="json")
        try:
            c.create_index("i")
            c.create_field("i", "f")
            c.query("i", "Set(1, f=1)")
            _, headers = _post_query(server.address[1], "Count(Row(f=1))")
            tid = headers["X-Pilosa-Trace-Id"]
            # leg 1: a latency bucket's exemplar names the trace (the
            # Count is the LATEST observation of every stage series,
            # so its id is the one the exemplars carry)
            assert [ln for ln in
                    c.metrics_text(openmetrics=True).splitlines()
                    if ln.startswith("query_stage_seconds_bucket")
                    and f'trace_id="{tid}"' in ln]
            # the classic 0.0.4 rendering must NOT carry the exemplar
            # (its parser rejects the suffix and fails the scrape)
            assert "trace_id" not in c.metrics_text()
            # leg 2: the id resolves to the retained span tree
            traces = c._json(
                "GET", f"/internal/traces?trace_id={tid}")["traces"]
            assert traces and traces[0]["traceId"] == tid
            # leg 3: the slow-capture log line carries the same id
            recs = [json.loads(ln)
                    for ln in buf.getvalue().splitlines()]
            slow = [r for r in recs if "slow query" in r["message"]]
            assert any(r.get("traceId") == tid for r in slow)
        finally:
            logger.handlers = saved
            server.close()
            holder.close()

    def test_lite_path_exemplar_carries_cheap_id(self, tmp_path):
        """The zero-span serving path still feeds exemplars: the
        LiteTracer's cheap id rides every stage observation (the
        config20 overhead bar holds because nothing else changes)."""
        holder, server, c = self._boot(
            tmp_path, trace_sample_rate=0.0, slow_query_threshold=1.0)
        try:
            c.create_index("i")
            c.create_field("i", "f")
            c.query("i", "Set(1, f=1)")
            _, headers = _post_query(server.address[1], "Count(Row(f=1))")
            tid = headers["X-Pilosa-Trace-Id"]
            assert [ln for ln in
                    c.metrics_text(openmetrics=True).splitlines()
                    if ln.startswith("query_stage_seconds_bucket")
                    and f'trace_id="{tid}"' in ln]
        finally:
            server.close()
            holder.close()
