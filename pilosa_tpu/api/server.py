"""REST server over the API façade.

Reference: ``http/handler.go`` (SURVEY.md §3.3).  Routes (same
surface; query and import endpoints content-negotiate JSON or
``application/x-protobuf`` per ``api/internal.proto``):

    POST   /index/{i}/query                     PQL body -> {"results": [...]}
    POST   /index/{i}                           create index
    DELETE /index/{i}
    POST   /index/{i}/field/{f}                 create field
    DELETE /index/{i}/field/{f}
    POST   /index/{i}/field/{f}/import          bulk bits (JSON|proto)
    POST   /index/{i}/field/{f}/importValue     bulk values (JSON|proto)
    POST   /index/{i}/field/{f}/import-roaring/{shard}   binary roaring
    GET    /export?index=i&field=f              CSV
    GET    /schema | /status | /info | /version | /metrics
    GET    /metrics/cluster | /status/cluster   fleet fan-in (one scrape
                                                sees every live node)
    POST   /internal/*                          node-to-node (cluster layer)

Implementation is stdlib ``ThreadingHTTPServer`` — the control plane is
host-side Python; all data-plane math stays on device.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from pilosa_tpu import __version__, fault
from pilosa_tpu.api.api import API, ApiError
from pilosa_tpu.obs import metrics as _metrics
from pilosa_tpu.obs.metrics import (StageTimer, enter_stage,
                                    set_current_timer, swap_span)
from pilosa_tpu.store.health import StorageFaultError as _StorageFaultError


def parse_timeout_param(raw: str) -> float:
    """Validate a ``?timeout=`` value (public and internal handlers
    share one rule set): NaN would poison every deadline comparison
    into False (silently unlimited); negatives are nonsense — 400 on
    both.  0 falls back to the server's query-timeout cap (unlimited
    only when no cap is configured) — API.query clamps every request
    to the cap by design."""
    import math
    try:
        timeout = float(raw)
    except ValueError:
        timeout = None
    if timeout is None or not math.isfinite(timeout) or timeout < 0:
        raise ApiError(f"bad timeout param {raw!r}")
    return timeout


# /debug/profile capture bounds: a capture shorter than the profiler's
# startup cost is noise; one longer than a minute holds the device
# profiler (and the handler thread) hostage
PROFILE_SECONDS_MIN = 0.1
PROFILE_SECONDS_MAX = 60.0


def clamp_profile_seconds(seconds: float) -> float:
    """Clamp a ``?seconds=`` jax-profiler capture window to
    [PROFILE_SECONDS_MIN, PROFILE_SECONDS_MAX]."""
    return min(max(seconds, PROFILE_SECONDS_MIN), PROFILE_SECONDS_MAX)


class Router:
    def __init__(self):
        self.routes: list[tuple[str, re.Pattern, object]] = []

    def add(self, method: str, pattern: str, fn) -> None:
        # '{name}' segments -> named groups
        regex = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        self.routes.append((method, re.compile("^" + regex + "$"), fn))

    def match(self, method: str, path: str):
        for m, rx, fn in self.routes:
            if m != method:
                continue
            hit = rx.match(path)
            if hit:
                return fn, hit.groupdict()
        return None, None


class Handler(BaseHTTPRequestHandler):
    """One instance per request; server state lives on ``self.server``."""

    protocol_version = "HTTP/1.1"
    server_version = "pilosa-tpu/" + __version__
    # socket read timeout (StreamRequestHandler applies it per
    # connection): reclaims handler threads from clients that stall
    # mid-handshake or idle forever without closing
    timeout = 120
    # the request's http_in event while a capture is open (parse_request)
    _span = None
    # TCP_NODELAY on every accepted connection (StreamRequestHandler
    # applies it in setup()): with keep-alive clients the response's
    # small writes otherwise collide with Nagle + the peer's delayed
    # ACK — a measured ~40 ms stall per RPC on loopback (one-shot
    # connections never showed it because close() flushes immediately)
    disable_nagle_algorithm = True

    # -- plumbing ------------------------------------------------------------

    def log_message(self, fmt, *args):  # route through our logger
        logger = getattr(self.server, "logger", None)
        if logger is not None:
            logger.debug("http: " + fmt % args)

    def parse_request(self) -> bool:
        # the request line has just been read off the socket: where a
        # served query's stage clock starts (_dispatch), so that header
        # parsing is http_in's and not nobody's; so is the event of a
        # capture, which the stage clock adopts
        self._t_request = time.perf_counter()
        self._span = (swap_span(None, "http_in") if _metrics.capture_open
                      else None)
        if super().parse_request():
            return True
        self._span = swap_span(self._span, None)
        return False

    def _body(self) -> bytes:
        # read-once, cached: _dispatch drains the body for EVERY
        # request — a handler that replies without reading it would
        # otherwise leave the bytes in the keep-alive stream, where
        # they prefix the NEXT request's method line (seen in r5 as
        # 501 "Unsupported method ('{}GET')" corrupting the peer's
        # shard-universe fetch; one-shot connections masked the class)
        if not hasattr(self, "_body_cache"):
            n = int(self.headers.get("Content-Length") or 0)
            self._body_cache = self.rfile.read(n) if n else b""
        return self._body_cache

    def _json_body(self) -> dict:
        raw = self._body()
        if not raw:
            return {}
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid JSON body: {e}")

    def _reply(self, obj, status: int = 200,
               content_type: str = "application/json",
               headers: dict | None = None) -> None:
        if getattr(self, "_fault_drop_response", False):
            # drop-response failpoint: the handler RAN (state mutated,
            # side effects happened) but the peer never hears back —
            # its retry is a duplicate delivery.  Severing the
            # connection makes the client see a reset, not a timeout.
            self.close_connection = True
            return
        enter_stage("encode")
        data = (obj if isinstance(obj, bytes)
                else json.dumps(obj).encode())
        enter_stage("http_out")
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _dispatch(self, method: str) -> None:
        span, self._span = self._span, None
        parsed = urllib.parse.urlparse(self.path)
        self.query = urllib.parse.parse_qs(parsed.query)
        # one handler instance serves every request on a keep-alive
        # connection: reset, then always drain (see _body)
        self.__dict__.pop("_body_cache", None)
        if "chunked" in (self.headers.get("Transfer-Encoding")
                         or "").lower():
            # the drain below only understands Content-Length; an
            # undrained chunked payload would corrupt the keep-alive
            # stream, so refuse and drop the connection
            self.close_connection = True
            swap_span(span, None)
            self._reply({"error": "chunked transfer encoding not "
                                  "supported; send Content-Length"}, 411)
            return
        self._body()
        fn, params = self.server.router.match(method, parsed.path)
        srv = self.server
        self._fault_drop_response = False
        if fault.ACTIVE and fn is not None:
            spec = fault.fire("server.response", method=method,
                              path=parsed.path)
            if spec is not None and spec["action"] == "drop_response":
                self._fault_drop_response = True
        # the stage clock of a served query starts at the socket (the
        # request line's stamp): the executor and the batcher charge
        # their stages to this timer (obs.metrics.StageTimer), _reply
        # enters encode and http_out, the finally below closes it
        timer = None
        if fn is Handler.h_query:
            timer = StageTimer(srv.api.executor.stats, "http_in",
                               at=self._t_request, span=span)
            set_current_timer(timer)
        else:
            swap_span(span, None)
        t0 = time.perf_counter()
        code = 200
        try:
            if fn is None:
                code = 404
                self._reply({"error": f"no route {method} {parsed.path}"}, 404)
                return
            fn(self, **params)
        except (ApiError, _StorageFaultError) as e:
            if isinstance(e, _StorageFaultError):
                # storage-integrity refusal (r19) escaping ANY handler
                # — import endpoints, hint replay, fragment merge,
                # internal query: map it once to the structured
                # 507/503 shape instead of a generic 500, then share
                # the ApiError reply path
                e = ApiError.storage_fault(e)
            code = e.status
            hdrs = None
            if e.retry_after is not None:
                # 503 shedding: tell well-behaved clients when to come
                # back instead of letting them hammer the queue
                hdrs = {"Retry-After": str(max(1, int(e.retry_after)))}
            # structured error fields (e.g. the 504 timeout block) ride
            # the body next to "error"
            self._reply({"error": str(e), **(e.extra or {})}, e.status,
                        headers=hdrs)
        except BrokenPipeError:
            code = 499
        except Exception as e:  # noqa: BLE001 — server must not die
            code = 500
            if getattr(srv, "logger", None):
                srv.logger.exception("http 500: %s %s", method, parsed.path)
            try:
                self._reply({"error": f"internal error: {e}"}, 500)
            except BrokenPipeError:
                pass
        finally:
            stats = getattr(srv, "stats", None)
            if stats is not None:
                stats.count("http_requests_total", 1,
                            method=method, status=str(code))
                stats.observe("http_request_seconds",
                              time.perf_counter() - t0, method=method)
            if timer is not None:
                # last: http_out runs until the handler returns, so the
                # stages cover all of http_request_seconds
                set_current_timer(None)
                timer.finish()

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    # -- handlers -------------------------------------------------------------

    def h_query(self, index: str) -> None:
        # content negotiation (reference: http/handler.go JSON/protobuf):
        # Content-Type picks the request decoding, Accept the response
        from pilosa_tpu.api import proto
        body = self._body()
        want_proto = proto.CONTENT_TYPE in (self.headers.get("Accept") or "")
        if proto.CONTENT_TYPE in (self.headers.get("Content-Type") or ""):
            try:
                pql, shards = proto.decode_query_request(body)
            except ValueError as e:
                raise ApiError(f"bad protobuf request: {e}")
        else:
            pql = body.decode()
            shards = None
        if "shards" in self.query:
            try:
                shards = [int(s) for s in
                          self.query["shards"][0].split(",") if s]
            except ValueError:
                raise ApiError(f"bad shards param "
                               f"{self.query['shards'][0]!r}")
        profile = "profile" in self.query
        timeout = None
        if "timeout" in self.query:
            timeout = parse_timeout_param(self.query["timeout"][0])
        if not want_proto:
            out = self.server.api.query(index, pql, shards=shards,
                                        profile=profile,
                                        timeout=timeout)
            # the per-request trace identity rides a header, not the
            # body (resolvable via /internal/traces?trace_id=)
            tid = out.pop("traceId", None)
            self._reply(out, headers={"X-Pilosa-Trace-Id": tid}
                        if tid else None)
            return
        if profile:
            # QueryResponse has no profile field; fail loudly rather
            # than silently dropping the span tree the caller asked for
            # (pinned by tests/test_proto.py; documented in the README
            # observability runbook — use the JSON surface to profile)
            raise ApiError("?profile is not supported with "
                           "application/x-protobuf responses")
        # errors keep the proto body (so the caller can decode them) but
        # carry the same HTTP status the JSON surface would — status-code
        # behavior must not diverge by content type
        status = 200
        trace_id = None
        try:
            res = self.server.api.query(index, pql, shards=shards,
                                        timeout=timeout)
        except ApiError as e:
            raw = proto.encode_query_response(err=str(e))
            status = e.status
        else:
            trace_id = res.pop("traceId", None)
            enter_stage("encode")
            try:
                raw = proto.encode_query_response(res["results"])
            except ValueError as e:  # result shape has no proto encoding
                # a client error (asked for proto on an Extract), and
                # answered IN proto so the caller can decode it
                raw = proto.encode_query_response(err=str(e))
                status = 400
        self._reply(raw, status=status, content_type=proto.CONTENT_TYPE,
                    headers={"X-Pilosa-Trace-Id": trace_id}
                    if trace_id else None)

    def h_create_index(self, index: str) -> None:
        body = self._json_body()
        self.server.api.create_index(index, body.get("options"))
        self._reply({"success": True})

    def h_delete_index(self, index: str) -> None:
        self.server.api.delete_index(index)
        self._reply({"success": True})

    def h_create_field(self, index: str, field: str) -> None:
        body = self._json_body()
        self.server.api.create_field(index, field, body.get("options"))
        self._reply({"success": True})

    def h_delete_field(self, index: str, field: str) -> None:
        self.server.api.delete_field(index, field)
        self._reply({"success": True})

    @property
    def _direct(self) -> bool:
        """Forwarded-batch marker: skip cluster re-routing."""
        return self.headers.get("X-Pilosa-Direct") == "1"

    @property
    def _op_id(self) -> str | None:
        """Bulk-op dedup identity (r15): forwarded import batches
        carry it so duplicate delivery — internode retries, replayed
        hints — is a no-op against the durable IdWindow."""
        return self.headers.get("X-Pilosa-Op-Id") or None

    def h_import(self, index: str, field: str) -> None:
        # content negotiation like the query endpoint: protobuf bodies
        # carry 100k-batch id arrays at a fraction of the JSON
        # encode/decode cost (reference: internal/internal.proto
        # ImportRequest on the import + internal wire)
        from pilosa_tpu.api import proto
        if proto.CONTENT_TYPE in (self.headers.get("Content-Type") or ""):
            try:
                b = proto.decode_import_request(self._body())
            except ValueError as e:
                raise ApiError(f"bad protobuf import: {e}")
            kw = dict(row_ids=b["row_ids"], col_ids=b["col_ids"],
                      row_keys=b["row_keys"], col_keys=b["col_keys"],
                      timestamps=b["timestamps"],
                      clear=b["clear"] or "clear" in self.query)
        else:
            b = self._json_body()
            kw = dict(row_ids=b.get("rowIDs"), col_ids=b.get("columnIDs"),
                      row_keys=b.get("rowKeys"),
                      col_keys=b.get("columnKeys"),
                      timestamps=b.get("timestamps"),
                      clear=b.get("clear", False) or "clear" in self.query)
        changed = self.server.api.import_bits(index, field,
                                              direct=self._direct,
                                              op_id=self._op_id, **kw)
        self._reply_import(changed)

    def h_import_value(self, index: str, field: str) -> None:
        from pilosa_tpu.api import proto
        if proto.CONTENT_TYPE in (self.headers.get("Content-Type") or ""):
            try:
                b = proto.decode_import_value_request(self._body())
            except ValueError as e:
                raise ApiError(f"bad protobuf import: {e}")
            kw = dict(col_ids=b["col_ids"], col_keys=b["col_keys"],
                      values=b["values"])
        else:
            b = self._json_body()
            kw = dict(col_ids=b.get("columnIDs"),
                      col_keys=b.get("columnKeys"), values=b.get("values"))
        changed = self.server.api.import_values(index, field,
                                                direct=self._direct, **kw)
        self._reply_import(changed)

    def _reply_import(self, changed: int) -> None:
        from pilosa_tpu.api import proto
        if proto.CONTENT_TYPE in (self.headers.get("Accept") or ""):
            self._reply(proto.encode_import_response(changed),
                        content_type=proto.CONTENT_TYPE)
        else:
            self._reply({"changed": changed})

    def h_import_roaring(self, index: str, field: str, shard: str) -> None:
        view = self.query.get("view", ["standard"])[0]
        clear = "clear" in self.query
        changed = self.server.api.import_roaring(
            index, field, int(shard), self._body(), view=view, clear=clear,
            direct=self._direct, op_id=self._op_id)
        self._reply({"changed": changed})

    def h_export(self) -> None:
        index = self.query.get("index", [None])[0]
        field = self.query.get("field", [None])[0]
        if not index or not field:
            raise ApiError("export requires ?index= and ?field=")
        csv = self.server.api.export_csv(index, field)
        self._reply(csv.encode(), content_type="text/csv")

    def h_schema(self) -> None:
        self._reply({"indexes": self.server.api.schema()})

    def h_get_index(self, index: str) -> None:
        for spec in self.server.api.schema():
            if spec["name"] == index:
                self._reply(spec)
                return
        raise ApiError(f"index {index!r} not found", 404)

    def h_get_field(self, index: str, field: str) -> None:
        for spec in self.server.api.schema():
            if spec["name"] == index:
                for f in spec["fields"]:
                    if f["name"] == field:
                        self._reply(f)
                        return
                raise ApiError(f"field {field!r} not found", 404)
        raise ApiError(f"index {index!r} not found", 404)

    def h_status(self) -> None:
        self._reply(self.server.api.status())

    def h_info(self) -> None:
        self._reply(self.server.api.info())

    def h_version(self) -> None:
        self._reply({"version": __version__})

    def _refresh_scrape_gauges(self) -> None:
        """Refresh point-in-time gauges at scrape time — shared by
        ``/metrics``, ``/internal/metrics/snapshot`` (each node
        refreshes before answering the cluster fan-in) and
        ``/metrics/cluster``."""
        stats = getattr(self.server, "stats", None)
        if stats is None:
            return
        # device working-set gauges
        ex = self.server.api.executor
        pc = ex.planes.stats()
        stats.gauge("plane_cache_bytes", pc["bytes"])
        stats.gauge("plane_cache_budget_bytes", pc["budgetBytes"])
        stats.gauge("plane_cache_entries", pc["entries"])
        stats.gauge("plane_cache_incremental_refreshes",
                    pc["incrementalRefreshes"])
        # HBM residency (r14): what eviction can and cannot reclaim
        # right now, plus how often the serving path finds its plane
        # already resident
        stats.gauge("plane_cache_pinned_entries", pc["pinnedEntries"])
        stats.gauge("plane_lease_count", pc["leases"])
        stats.gauge("plane_cache_hit_ratio", pc["hitRatio"])
        # live row sets from the generation-checked memo vs walked
        stats.gauge("plane_cache_row_set_hits", pc["rowSetHits"])
        stats.gauge("plane_cache_row_set_misses", pc["rowSetMisses"])
        # ingest overlays (r15): set bits pending in device delta
        # overlays — base⊕delta serving depth before compaction folds
        stats.gauge("delta_overlay_bits",
                    pc.get("delta", {}).get("deltaOverlayBits", 0))
        # serving-spine gauges (r6): plan-cache occupancy and the
        # batcher's current adaptive window
        stats.gauge("plan_cache_entries", len(ex._plans))
        stats.gauge("fused_program_count", ex.fused.program_count)
        if ex.batcher is not None:
            stats.gauge("count_batcher_window_seconds",
                        ex.batcher.current_window)
        # self-healing pipeline (r18): governor state at scrape time
        # (0 healthy, 1 degraded, 2 probing) — transitions also set
        # this gauge the moment they happen
        stats.gauge("device_health_state",
                    ex.device_health()["stateCode"])
        # admission / shedding visibility (VERDICT advice #6): how
        # full the executor is right now, next to the shed counter
        # and queue-wait histogram fire() maintains
        stats.gauge("query_slots_in_use", ex.slots_in_use)
        stats.gauge("query_slots_max", ex.max_concurrent)
        # storage growth visibility (r8): op-log bytes are what the
        # snapshot queue + backup are supposed to bound — an
        # operator watching oplog_bytes climb knows compaction has
        # fallen behind before recovery time blows up
        st = self.server.api.storage_stats()
        stats.gauge("oplog_bytes", st["oplogBytes"])
        stats.gauge("fragment_count", st["fragmentCount"])
        stats.gauge("snapshot_bytes", st["snapshotBytes"])
        # storage integrity (r19): governor state + quarantine depth
        # at scrape time (transitions also set both the moment they
        # happen — this keeps a restarted scraper consistent)
        sh = getattr(self.server.api.holder, "storage_health", None)
        if sh is not None:
            pay = sh.payload()
            stats.gauge("disk_health_state", pay["stateCode"])
            stats.gauge("storage_fragment_quarantined",
                        len(pay["quarantined"]))

    # scrapers negotiating this media type get OpenMetrics output —
    # the only exposition format in which exemplars are legal (a
    # 0.0.4 parser rejects the `# {...}` suffix and fails the scrape)
    OPENMETRICS_TYPE = "application/openmetrics-text"

    def h_metrics(self) -> None:
        stats = getattr(self.server, "stats", None)
        self._refresh_scrape_gauges()
        om = self.OPENMETRICS_TYPE in (self.headers.get("Accept") or "")
        text = (stats.prometheus_text(openmetrics=om)
                if stats is not None else "")
        self._reply(text.encode(),
                    content_type=(self.OPENMETRICS_TYPE
                                  + "; version=1.0.0; charset=utf-8"
                                  if om else "text/plain; version=0.0.4"))

    def h_metrics_snapshot(self) -> None:
        """Node-to-node leg of the cluster metrics fan-in: the whole
        registry (counters, gauges, histograms with raw bucket counts)
        as JSON, gauges refreshed exactly like a direct scrape."""
        stats = getattr(self.server, "stats", None)
        self._refresh_scrape_gauges()
        cluster = self.server.api.cluster
        self._reply({
            "node": cluster.node_id if cluster is not None else "local",
            "snapshot": (stats.full_snapshot() if stats is not None
                         else {"counters": {}, "gauges": {},
                               "histograms": {}})})

    def h_metrics_cluster(self) -> None:
        """One Prometheus document for the fleet: fan out to live
        peers (breaker-aware), merge with the local registry, answer
        partial + ``cluster_metrics_node_up 0`` rows for unreachable
        nodes — a dead peer degrades the scrape, never fails it."""
        from pilosa_tpu.obs.metrics import render_cluster_metrics
        stats = getattr(self.server, "stats", None)
        self._refresh_scrape_gauges()
        local = (stats.full_snapshot() if stats is not None
                 else {"counters": {}, "gauges": {}, "histograms": {}})
        cluster = self.server.api.cluster
        if cluster is None:
            snaps, stale = {"local": local}, []
        else:
            snaps, stale = cluster.metrics_snapshots()
            snaps[cluster.node_id] = local
        # staleNodes ride a header too (the document's node_up 0 rows
        # carry the same fact inside the Prometheus text)
        self._reply(render_cluster_metrics(snaps, stale).encode(),
                    content_type="text/plain; version=0.0.4",
                    headers=({"X-Pilosa-Stale-Nodes": ",".join(stale)}
                             if stale else None))

    def h_status_cluster(self) -> None:
        """Every node's ``/status`` in one document, keyed by node id,
        with a ``staleNodes`` list for peers that could not answer
        (same partial-result contract as ``/metrics/cluster``)."""
        local = self.server.api.status()
        cluster = self.server.api.cluster
        if cluster is None:
            self._reply({"nodes": {"local": local}, "staleNodes": []})
            return
        snaps, stale = cluster.status_snapshots()
        snaps[cluster.node_id] = local
        self._reply({"nodes": snaps, "staleNodes": stale,
                     "coordinator": cluster.coordinator_id()})

    # -- fault injection (live control surface) -----------------------------

    def h_fault_list(self) -> None:
        self._reply({"faults": fault.list_faults(),
                     "triggered": [{"site": s, "action": a, "count": n}
                                   for (s, a), n
                                   in sorted(fault.triggered_total()
                                             .items())]})

    def h_fault_set(self) -> None:
        """Arm a failpoint on this node:
        ``{"site": ..., "action": ..., "nth"|"prob"|"seed"|"times"|
        "match"|"args": ...}`` — same spec shape as ``PILOSA_FAULTS``."""
        b = self._json_body()
        if not b.get("site") or not b.get("action"):
            raise ApiError("fault spec requires site and action")
        try:
            spec = fault.set_fault(
                b["site"], b["action"], nth=b.get("nth"),
                prob=b.get("prob"), seed=b.get("seed"),
                times=b.get("times"), match=b.get("match"),
                args=b.get("args"))
        except ValueError as e:
            raise ApiError(str(e))
        logger = getattr(self.server, "logger", None)
        if logger is not None:
            logger.warning("fault armed via /internal/fault: %s", spec)
        self._reply({"armed": spec})

    def h_fault_clear(self) -> None:
        """Disarm ``{"site": ...}`` (or every failpoint with no body)."""
        b = self._json_body()
        self._reply({"cleared": fault.clear(b.get("site"))})

    def h_backup(self) -> None:
        """Tar the whole data dir (reference: ``pilosa backup`` tars over
        HTTP; SURVEY.md §6 checkpoint/resume).  Fragments snapshot first
        so the tar is self-consistent."""
        self._reply(self.server.api.backup_tar(),
                    content_type="application/x-tar")

    def h_restore(self) -> None:
        self.server.api.restore_tar(self._body())
        self._reply({"success": True})

    def h_traces(self) -> None:
        """Recent retained traces (sampled / slow / profiled queries,
        plus this node's continuation spans of distributed queries);
        ``?trace_id=`` narrows to one trace."""
        from pilosa_tpu.obs import GLOBAL_TRACER
        spans = GLOBAL_TRACER.finished()
        want = self.query.get("trace_id", [None])[0]
        if want:
            spans = [s for s in spans if s.trace_id == want]
        self._reply({"traces": [s.to_json() for s in spans]})

    def h_debug_slow(self) -> None:
        """The slow-query ring: queries over ``slow_query_threshold``
        with PQL, shards, duration and the full span tree."""
        api = self.server.api
        self._reply({"thresholdSeconds": api.slow_query_threshold,
                     **api.slow_log.summary(),
                     "slow": api.slow_log.entries()})

    def h_debug_flight(self) -> None:
        """The dispatch flight recorder (r19): the last N lifecycle
        events (enqueue/dispatch/readback/deliver, governor moves,
        watchdog trips, quarantines, evictions, page-ins, compiles)
        straight from the in-memory ring — no dump file needed.
        ``?limit=`` trims to the newest N events; ``?cluster=1`` fans
        in every peer's ring (same partial-result contract as
        ``/status/cluster``)."""
        ex = getattr(self.server.api, "executor", None)
        flight = getattr(ex, "flight", None)
        raw = self.query.get("limit", ["0"])[0]
        try:
            limit = int(raw)
        except ValueError:
            raise ApiError(f"bad limit param {raw!r}")
        local = (flight.snapshot(limit=limit or None)
                 if flight is not None
                 else {"events": [], "lastSeq": 0, "capacity": 0,
                       "dumps": []})
        cluster = self.server.api.cluster
        if self.query.get("cluster", ["0"])[0] not in ("1", "true"):
            self._reply(local)
            return
        if cluster is None:
            self._reply({"nodes": {"local": local}, "staleNodes": []})
            return
        snaps, stale = cluster.flight_snapshots(limit=limit)
        snaps[cluster.node_id] = local
        self._reply({"nodes": snaps, "staleNodes": stale})

    def h_debug_threads(self) -> None:
        """Python stack dump of every thread — the rebuild's
        /debug/pprof (reference mounts net/http/pprof; SURVEY.md §6)."""
        import sys
        import threading
        import traceback
        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for ident, frame in sys._current_frames().items():
            out.append(f"Thread {names.get(ident, '?')} ({ident}):")
            out.extend(line.rstrip()
                       for line in traceback.format_stack(frame))
            out.append("")
        self._reply("\n".join(out).encode(), content_type="text/plain")

    def h_debug_profile(self) -> None:
        """Capture a jax device profile for ?seconds= (default 3,
        clamped — see :func:`clamp_profile_seconds`) into ?dir=
        (default under the data dir) — TensorBoard-readable
        (SURVEY.md §6: expose jax.profiler traces).  While it is open
        every request stage and batcher phase is a ``pilosa.*`` event
        in the same trace: ``python -m pilosa_tpu.obs.gaps <dir>``
        puts the device's idle time down to them."""
        import time as _time

        import jax
        raw = self.query.get("seconds", ["3"])[0]
        try:
            seconds = float(raw)
        except ValueError:
            # a garbage ?seconds= is a client mistake, not a 500
            raise ApiError(f"bad seconds param {raw!r}")
        seconds = clamp_profile_seconds(seconds)
        out_dir = self.query.get("dir", [None])[0] or \
            self.server.api.holder.path + "/_profiles"
        # device ops, XLA's host events and the program's own stages
        # (obs.metrics.swap_span) — not every Python call of every
        # thread, which is jax's default and stops the server
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=options)
        _metrics.capture_open = True
        try:
            _time.sleep(seconds)
        finally:
            # the device's ops are recorded until stop_trace has
            # stopped its tracer: the stages go on being events as long
            try:
                jax.profiler.stop_trace()
            finally:
                _metrics.capture_open = False
        self._reply({"traceDir": out_dir, "seconds": seconds})


def build_router() -> Router:
    r = Router()
    r.add("POST", "/index/{index}/query", Handler.h_query)
    r.add("POST", "/index/{index}/field/{field}/import", Handler.h_import)
    r.add("POST", "/index/{index}/field/{field}/importValue",
          Handler.h_import_value)
    r.add("POST", "/index/{index}/field/{field}/import-roaring/{shard}",
          Handler.h_import_roaring)
    r.add("POST", "/index/{index}/field/{field}", Handler.h_create_field)
    r.add("DELETE", "/index/{index}/field/{field}", Handler.h_delete_field)
    r.add("POST", "/index/{index}", Handler.h_create_index)
    r.add("DELETE", "/index/{index}", Handler.h_delete_index)
    r.add("GET", "/index/{index}/field/{field}", Handler.h_get_field)
    r.add("GET", "/index/{index}", Handler.h_get_index)
    r.add("GET", "/export", Handler.h_export)
    r.add("GET", "/schema", Handler.h_schema)
    r.add("GET", "/status", Handler.h_status)
    r.add("GET", "/info", Handler.h_info)
    r.add("GET", "/version", Handler.h_version)
    r.add("GET", "/metrics", Handler.h_metrics)
    # cluster observability pane (r14): one scrape sees the fleet
    r.add("GET", "/metrics/cluster", Handler.h_metrics_cluster)
    r.add("GET", "/status/cluster", Handler.h_status_cluster)
    r.add("GET", "/internal/metrics/snapshot", Handler.h_metrics_snapshot)
    r.add("GET", "/internal/fault", Handler.h_fault_list)
    r.add("POST", "/internal/fault", Handler.h_fault_set)
    r.add("POST", "/internal/fault/clear", Handler.h_fault_clear)
    r.add("GET", "/internal/backup", Handler.h_backup)
    r.add("POST", "/internal/restore", Handler.h_restore)
    r.add("GET", "/internal/traces", Handler.h_traces)
    r.add("GET", "/debug/slow", Handler.h_debug_slow)
    r.add("GET", "/debug/flight", Handler.h_debug_flight)
    r.add("GET", "/debug/threads", Handler.h_debug_threads)
    r.add("POST", "/debug/profile", Handler.h_debug_profile)
    # node-to-node surface (deferred import: cluster depends on this
    # module for Handler/Router; a build without the cluster package
    # still serves single-node)
    try:
        from pilosa_tpu.cluster.internal import register_internal_routes
    except ImportError:
        pass
    else:
        register_internal_routes(r)
    # backup/restore surface (same deferred-import contract)
    try:
        from pilosa_tpu.backup.endpoints import register_backup_routes
    except ImportError:
        pass
    else:
        register_backup_routes(r)
    return r


class _HTTPServer(ThreadingHTTPServer):
    """Tracks live connections so ``close`` can sever them: with
    HTTP/1.1 keep-alive clients, ``shutdown()`` only stops the accept
    loop — handler threads parked on persistent connections would keep
    answering (a "closed" node would still heartbeat as alive)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._conns: set = set()
        self._conns_lock = threading.Lock()

    def process_request(self, request, client_address):
        with self._conns_lock:
            self._conns.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._conns_lock:
            self._conns.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self) -> None:
        import socket as _socket
        with self._conns_lock:
            conns = list(self._conns)
        for s in conns:
            try:
                s.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def handle_error(self, request, client_address):
        # failed TLS handshakes (plaintext probes, port scanners) and
        # client disconnects are per-connection noise, not server
        # errors — log at debug instead of dumping tracebacks
        import sys
        exc = sys.exc_info()[1]
        if isinstance(exc, OSError):  # incl. ssl.SSLError, disconnects
            logger = getattr(self, "logger", None)
            if logger is not None:
                logger.debug("http connection error from %s: %r",
                             client_address, exc)
            return
        super().handle_error(request, client_address)


class Server:
    """HTTP server wrapper: ``serve_forever`` on a background thread
    (reference: ``server.go#Server.Open`` / handler listen-serve)."""

    def __init__(self, api: API, host: str = "127.0.0.1", port: int = 10101,
                 stats=None, logger=None, ssl_context=None):
        _HTTPServer.request_queue_size = 64  # concurrent clients
        self.httpd = _HTTPServer((host, port), Handler)
        if ssl_context is not None:
            # TLS terminates here (reference: server/config.go tls
            # section).  do_handshake_on_connect=False: the handshake
            # runs in the per-connection handler thread on first read,
            # NOT in the accept loop — a client that connects and never
            # sends a ClientHello would otherwise wedge accept() and
            # with it the whole HTTP surface (and this node's liveness)
            self.httpd.socket = ssl_context.wrap_socket(
                self.httpd.socket, server_side=True,
                do_handshake_on_connect=False)
        self.httpd.api = api
        self.httpd.router = build_router()
        self.httpd.stats = stats
        self.httpd.logger = logger
        if stats is not None:
            # fault triggers surface as fault_triggered_total on THIS
            # registry's /metrics (process-global sink: one serving
            # server per process in production)
            fault.set_stats(stats)
        self._thread: threading.Thread | None = None

    @property
    def address(self) -> tuple[str, int]:
        return self.httpd.server_address[:2]

    def start(self) -> "Server":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        name="pilosa-tpu-http", daemon=True)
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.httpd.close_all_connections()
        if self._thread is not None:
            self._thread.join(timeout=5)
