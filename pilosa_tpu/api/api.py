"""Programmatic API façade.

Reference: ``api.go`` (SURVEY.md §3.3) — the validation + orchestration
layer used by both the HTTP handler and (upstream v2) gRPC: index/field
CRUD, query execution, bulk import routing, schema and status
introspection.  Both the REST server (:mod:`pilosa_tpu.api.server`) and
the CLI drive this class; it owns nothing itself — holder for storage,
executor for queries.
"""

from __future__ import annotations

import io
import logging
import os
import random
import time as _time
from datetime import datetime

import numpy as np

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor, result_to_json
from pilosa_tpu.exec.executor import (ExecutionError,
                                      ExecutorSaturatedError,
                                      PipelineStalledError,
                                      QueryTimeoutError,
                                      WriteUnavailableError)
from pilosa_tpu.pql.parser import ParseError
from pilosa_tpu.store import FieldOptions, Holder
from pilosa_tpu.store.field import BSI_TYPES
from pilosa_tpu.store.health import StorageFaultError
from pilosa_tpu.store.view import VIEW_STANDARD
from pilosa_tpu.tenancy import TenantThrottledError


class ApiError(Exception):
    def __init__(self, msg: str, status: int = 400,
                 retry_after: float | None = None,
                 extra: dict | None = None):
        super().__init__(msg)
        self.status = status
        # seconds for a Retry-After response header (load shedding:
        # a 503 should tell the client when to come back)
        self.retry_after = retry_after
        # structured fields merged into the JSON error body next to
        # "error" (e.g. the 504 timeout block: elapsed, deadline,
        # shards outstanding)
        self.extra = extra

    @classmethod
    def timeout(cls, exc, elapsed: float,
                deadline: float | None) -> "ApiError":
        """The deadline-exceeded contract, shared by the public and
        ``/internal/query`` edges: HTTP 504 with a structured body —
        how long the query ran, what the budget was, how many shards
        never answered."""
        return cls(str(exc), 504, extra={"timeout": {
            "elapsedSeconds": round(elapsed, 6),
            "deadlineSeconds": deadline or None,
            "shardsOutstanding": getattr(exc, "shards_outstanding",
                                         None),
            # r18: when the deadline expired while blocked on the
            # dispatch pipeline, name the stage (queued/dispatch/
            # readback) so a wedged caller's 504 says WHAT stalled
            "stage": getattr(exc, "stage", None)}})

    @classmethod
    def pipeline_stall(cls, exc) -> "ApiError":
        """The quarantined-window contract (r18), shared by the public
        and ``/internal/query`` edges: HTTP 500 with a structured
        ``pipelineStall`` body naming the stalled stage and how long
        the watchdog let it age — a sick device costs the wedged
        caller a loud, attributable error, never a hung thread."""
        return cls(str(exc), 500, extra={"pipelineStall": {
            "stage": getattr(exc, "stage", None),
            "elapsedSeconds": round(getattr(exc, "elapsed", 0.0), 3)}})

    @classmethod
    def write_unavailable(cls, exc) -> "ApiError":
        """The write-unavailability contract (r13), shared by the
        public and ``/internal/query`` edges: HTTP 503 + Retry-After
        with a structured body naming the op, the down replica, and
        why hinted handoff could not cover it (``replica_down`` —
        handoff disabled, ``hint_overflow`` — backlog older than
        hint_max_age, ``no_live_replica``, ``replica_busy`` — an
        alive replica shed the op).  Mirrors the 504 timeout
        block: unavailability is never a generic 400/500.  (The r19
        disk-full refusal has its own 507 shape — see
        :meth:`storage_fault`.)"""
        return cls(str(exc), 503,
                   retry_after=getattr(exc, "retry_after", 1.0),
                   extra={"writeUnavailable": {
                       "op": exc.op, "replica": exc.replica,
                       "reason": exc.reason}})

    @classmethod
    def tenant_throttled(cls, exc) -> "ApiError":
        """A per-tenant QoS shed (r17 tenancy): the tenant exceeded
        ITS qps/slot quota — same 503 + Retry-After contract as
        executor saturation, but with a structured
        ``tenantThrottled{tenant, quota, kind}`` body so the client
        can tell its own quota from server overload."""
        return cls(str(exc), 503,
                   retry_after=getattr(exc, "retry_after", 1.0),
                   extra={"tenantThrottled": {
                       "tenant": exc.tenant, "quota": exc.quota,
                       "kind": exc.kind}})

    @classmethod
    def storage_fault(cls, exc) -> "ApiError":
        """The storage-integrity contract (r19), applied by the
        request dispatcher to ANY surface a
        :class:`~pilosa_tpu.store.health.StorageFaultError` escapes
        from: ``disk_full`` answers a 507-style structured
        ``writeUnavailable{reason: "disk_full"}`` (the node is
        READ-ONLY; reads keep serving; peers hint the missed copies),
        anything else (quarantined corrupt/io_error fragment) answers
        503 with a structured ``storageFault{path, kind}`` naming the
        sick fragment — storage unavailability is never a generic
        500."""
        kind = getattr(exc, "kind", "unknown")
        retry = getattr(exc, "retry_after", 1.0)
        if kind == "disk_full":
            return cls(str(exc), 507, retry_after=retry,
                       extra={"writeUnavailable": {
                           "op": None, "replica": None,
                           "reason": "disk_full"}})
        return cls(str(exc), 503, retry_after=retry,
                   extra={"storageFault": {
                       "path": getattr(exc, "path", None),
                       "kind": kind}})


def field_options_from_json(o: dict) -> FieldOptions:
    """REST field-options body -> FieldOptions (reference:
    ``http/handler.go`` postFieldRequest decoding)."""
    return FieldOptions(
        type=o.get("type", "set"), keys=o.get("keys", False),
        cache_type=o.get("cacheType", "ranked"),
        cache_size=o.get("cacheSize", 50000),
        time_quantum=o.get("timeQuantum", ""),
        min=o.get("min"), max=o.get("max"), base=o.get("base", 0),
        bit_depth=o.get("bitDepth", 0), scale=o.get("scale", 0),
        epoch=o.get("epoch", ""), time_unit=o.get("timeUnit", "s"),
    )


class API:
    # span trees are materialized only for queries that can be
    # retained: sampled, profiled, or slow-HUNTED — an operator who
    # sets slow_query_threshold at/under this floor is asking for full
    # trees on (nearly) every query and gets them; above it, slow
    # captures carry the root + per-stage breakdown instead (the lite
    # path never builds the tree, which is what restored the r05
    # product/raw ratio)
    SLOW_TRACE_FLOOR = 0.05

    def __init__(self, holder: Holder, executor: Executor | None = None,
                 cluster=None, query_timeout: float = 0.0,
                 trace_sample_rate: float = 0.01,
                 slow_query_threshold: float = 1.0):
        from pilosa_tpu.obs import SlowQueryLog
        self.holder = holder
        self.executor = executor or Executor(holder)
        self.cluster = cluster  # set by the cluster layer when distributed
        self.query_timeout = query_timeout  # seconds; 0 = unlimited
        # always-on sampled tracing: this fraction of ordinary queries
        # is retained in the finished-trace ring without the caller
        # asking (profile=true and slow queries always retain)
        self.trace_sample_rate = min(max(float(trace_sample_rate), 0.0), 1.0)
        # queries slower than this (seconds) are captured — PQL, index,
        # shards, duration, full span tree — in the bounded ring behind
        # GET /debug/slow; 0 disables
        self.slow_query_threshold = float(slow_query_threshold)
        self.slow_log = SlowQueryLog()

    # -- schema -------------------------------------------------------------

    def create_index(self, name: str, options: dict | None = None):
        options = options or {}
        try:
            idx = self.holder.create_index(
                name, keys=options.get("keys", False),
                track_existence=options.get("trackExistence", True))
        except ValueError as e:
            raise ApiError(str(e), 409 if "exists" in str(e) else 400)
        if self.cluster is not None:
            self.cluster.broadcast_schema()
        return idx

    def delete_index(self, name: str, direct: bool = False) -> None:
        try:
            self.holder.delete_index(name)
        except KeyError:
            raise ApiError(f"index {name!r} not found", 404)
        self.executor.planes.invalidate(name)
        self.executor.invalidate_plans(name)
        # the index dir (incl. _keys/) is gone; cached logs must go too
        self.executor.translate.drop(name)
        if self.cluster is not None and not direct:
            self.cluster.broadcast_delete(name, None)

    def create_field(self, index: str, name: str, options: dict | None = None):
        idx = self._index(index)
        try:
            f = idx.create_field(
                name, field_options_from_json(options or {}))
        except ValueError as e:
            raise ApiError(str(e), 409 if "exists" in str(e) else 400)
        if self.cluster is not None:
            self.cluster.broadcast_schema()
        return f

    def delete_field(self, index: str, name: str,
                     direct: bool = False) -> None:
        idx = self._index(index)
        try:
            idx.delete_field(name)
        except KeyError:
            raise ApiError(f"field {name!r} not found", 404)
        self.executor.planes.invalidate(index)
        self.executor.invalidate_plans(index)
        # field delete leaves <index>/_keys/<field>.keys behind: remove
        # it so a recreated field starts with fresh key state
        self.executor.translate.drop(index, name, remove_files=True)
        if self.cluster is not None and not direct:
            self.cluster.broadcast_delete(index, name)

    def schema(self) -> list[dict]:
        return self.holder.schema()

    def apply_schema(self, schema: list[dict]) -> None:
        self.holder.apply_schema(schema)

    # -- query --------------------------------------------------------------

    def query(self, index: str, pql: str,
              shards: list[int] | None = None,
              profile: bool = False,
              timeout: float | None = None) -> dict:
        """``profile=True`` attaches the per-call span tree to the
        response (reference: query ``profile`` option, SURVEY.md §6).
        ``timeout`` (seconds) bounds execution — the deadline analogue
        of upstream's request-context cancellation; expiry answers
        HTTP 504 with a structured ``timeout`` body (elapsed, deadline,
        shards outstanding).  The server's ``query_timeout`` config is a CAP, not
        just a default: per-request values clamp to it (otherwise any
        caller could disable the operator's protection with
        ?timeout=0).

        Tracing identity is always on — every REST response carries
        ``X-Pilosa-Trace-Id`` — but the retention decision is made
        BEFORE any span materializes (r12 hot-path fix; this ordering
        is what keeps the product path at the raw-kernel ceiling):

        - sampled (``trace_sample_rate``), profiled, or slow-HUNTED
          (``slow_query_threshold`` at/under :data:`SLOW_TRACE_FLOOR`)
          queries run under a per-request tracer with a node-tagged
          ``query`` root and the full span tree, RETAINED in the
          process ring (``/internal/traces?trace_id=``);
        - every other query runs under a :class:`LiteTracer`: a trace
          id and per-stage marks, zero span objects — if such a query
          still comes in over ``slow_query_threshold`` it lands in
          ``/debug/slow`` with a root + ``stage.*`` breakdown (its
          PQL, shards and duration intact; full executor trees need
          sampling/profile/floor)."""
        from pilosa_tpu.obs import GLOBAL_TRACER, LiteTracer, Tracer
        from pilosa_tpu.obs.metrics import current_timer
        from pilosa_tpu.obs.tracing import set_current_trace_id
        self._index(index)
        cap = self.query_timeout
        if timeout is None or timeout == 0:
            timeout = cap
        elif cap:
            timeout = min(timeout, cap)
        deadline = (_time.monotonic() + timeout) if timeout else None
        sampled = (self.trace_sample_rate > 0
                   and random.random() < self.trace_sample_rate)
        # the materialization decision, ahead of ANY span allocation
        trace = (profile or sampled
                 or 0 < self.slow_query_threshold <= self.SLOW_TRACE_FLOOR)
        stats = self.executor.stats
        timer = current_timer()  # the HTTP edge's stage clock, if any
        if not trace:
            tracer = LiteTracer()
            if timer is not None:
                timer.attach(tracer)
                timer.enter("admit")
            # publish the id as this thread's ACTIVE trace id so log
            # lines emitted while serving join the query's exemplar
            # (one thread-local write — the lite path stays lite)
            set_current_trace_id(tracer.trace_id)
            t0 = _time.perf_counter()
            try:
                out, err = self._run_query(index, pql, shards, tracer,
                                           deadline, timeout, t0)
            finally:
                set_current_trace_id(None)
            duration = _time.perf_counter() - t0
            if (self.slow_query_threshold > 0
                    and duration >= self.slow_query_threshold):
                # slow capture on the lite path: root + stage.*
                # children reconstructed from the timer marks (rare by
                # construction — the threshold is above the floor)
                node = (self.cluster.node_id if self.cluster is not None
                        else "local")
                root = tracer.slow_root("query", duration, index=index,
                                        node=node, liteTrace=True)
                if err is not None:
                    root.tags["error"] = str(err)
                stats.count("slow_query_total", 1)
                self.slow_log.record(self._slow_entry(
                    index, pql, shards, duration, root, err))
                GLOBAL_TRACER.record(root)
                self._log_slow(index, pql, duration, tracer.trace_id)
            if err is not None:
                raise err
            out["traceId"] = tracer.trace_id
            return out
        tracer = Tracer()
        # the fan-out propagates this as the traceparent flags
        # segment: sampled/profiled queries send "01" (peers build +
        # ship their subtree AND keep a ring copy); slow-hunted
        # queries send "02" (build + ship — a slow capture needs the
        # subtrees — but do NOT churn peer rings at serving rate);
        # lite-path queries send "00" and peers skip trees entirely
        tracer.sampled = sampled or profile
        node = (self.cluster.node_id if self.cluster is not None
                else "local")
        t0 = _time.perf_counter()
        with tracer.span("query", index=index, node=node) as root:
            set_current_trace_id(root.trace_id)
            if timer is not None:
                timer.attach(tracer)
                timer.enter("admit")
            try:
                out, err = self._run_query(index, pql, shards, tracer,
                                           deadline, timeout, t0)
            finally:
                set_current_trace_id(None)
            if err is not None:
                root.tags["error"] = str(err)
        duration = _time.perf_counter() - t0
        # device-time join (r19): the cost ledger's measured device
        # seconds charged to THIS trace land on the profiled query's
        # root — the span tree then shows how much of the wall was
        # device work versus queueing/host time
        dev_s = self.executor.ledger.trace_seconds(root.trace_id)
        if dev_s is not None and dev_s > 0:
            root.tags["deviceSeconds"] = round(dev_s, 6)
            if duration > 0:
                root.tags["deviceShare"] = round(
                    min(1.0, dev_s / duration), 4)
        slow = (self.slow_query_threshold > 0
                and duration >= self.slow_query_threshold)
        if sampled:
            stats.count("trace_sampled_total", 1)
        if slow:
            stats.count("slow_query_total", 1)
            self.slow_log.record(self._slow_entry(
                index, pql, shards, duration, root, err))
            self._log_slow(index, pql, duration, root.trace_id)
        if sampled or slow or profile:
            # publish into the process ring so the trace id resolves
            # via GET /internal/traces?trace_id= after the request
            GLOBAL_TRACER.record(root)
        if err is not None:
            raise err
        out["traceId"] = root.trace_id
        if profile:
            out["profile"] = [s.to_json() for s in tracer.finished()]
        return out

    def _run_query(self, index: str, pql: str, shards, tracer,
                   deadline, timeout, t0) -> tuple[dict, ApiError | None]:
        """Execute + error-classify (shared by the lite and traced
        paths): returns (response dict, ApiError-or-None) — the caller
        owns raise/capture ordering."""
        try:
            if self.cluster is not None:
                return {"results": self.cluster.dist.execute_json(
                    index, pql, shards=shards, tracer=tracer,
                    deadline=deadline)}, None
            results = self.executor.execute(index, pql, shards=shards,
                                            tracer=tracer,
                                            deadline=deadline)
            return {"results": [result_to_json(r) for r in results]}, None
        except QueryTimeoutError as e:
            # a deadline-exceeded query is its own failure class —
            # never a generic 500, and distinct from client errors
            return {}, ApiError.timeout(e, _time.perf_counter() - t0,
                                        timeout)
        except PipelineStalledError as e:
            # a quarantined dispatch-pipeline window (r18): server-side
            # unavailability with a structured body naming the stalled
            # stage — distinct from client errors AND from timeouts
            # (the caller's own budget may not have expired yet)
            return {}, ApiError.pipeline_stall(e)
        except ExecutorSaturatedError as e:
            # admission shedding (VERDICT advice #6): a saturated
            # executor is overload, not a client mistake — 503 with a
            # Retry-After hint, never a generic 500/400
            return {}, ApiError(str(e), 503, retry_after=e.retry_after)
        except WriteUnavailableError as e:
            # a replica-down write refusal (handoff disabled/overflow/
            # no live replica) is unavailability, not a client error:
            # 503 + Retry-After with the structured writeUnavailable
            # body naming the down replica (r13)
            return {}, ApiError.write_unavailable(e)
        except StorageFaultError as e:
            # the storage layer refused (node read-only on disk-full,
            # or the target fragment quarantined): structured 507/503,
            # never a generic 500 (r19)
            return {}, ApiError.storage_fault(e)
        except TenantThrottledError as e:
            # the tenant's OWN quota shed this query (r17): 503 +
            # Retry-After with the structured tenantThrottled body —
            # never the generic 400 below (it is not a client mistake)
            # and never confusable with whole-server saturation
            return {}, ApiError.tenant_throttled(e)
        except (ParseError, ExecutionError) as e:
            return {}, ApiError(str(e), 400)

    def _slow_entry(self, index: str, pql: str, shards, duration: float,
                    root, err) -> dict:
        return {
            "ts": _time.time(), "index": index,
            "pql": pql if len(pql) <= 4096 else pql[:4096] + "…",
            "shards": list(shards) if shards is not None else None,
            "durationMs": round(duration * 1e3, 3),
            "traceId": root.trace_id,
            # which path answered (r19 satellite): fused /
            # op-at-a-time fallback / paged / row-directory oracle /
            # degraded governor — the first triage question for any
            # slow entry is "was this even on the fast path"
            "path": self.executor.serving_path(),
            "error": str(err) if err is not None else None,
            "profile": root.to_json()}

    def _log_slow(self, index: str, pql: str, duration: float,
                  trace_id: str) -> None:
        """One WARNING log line per slow-query capture, carrying the
        query's trace id as a record attribute (the JSON formatter
        emits it as ``traceId``): the correlated-logs leg of the
        observability pane — a p99 bucket's exemplar, the retained
        trace at ``/internal/traces?trace_id=``, and this line join on
        one id."""
        logging.getLogger("pilosa_tpu.api").warning(
            "slow query %.3fs index=%s pql=%s",
            duration, index, pql if len(pql) <= 200 else pql[:200] + "…",
            extra={"traceId": trace_id})

    # -- imports ------------------------------------------------------------

    def import_bits(self, index: str, field: str, *,
                    row_ids=None, col_ids=None, row_keys=None, col_keys=None,
                    timestamps=None, clear: bool = False,
                    direct: bool = False, op_id: str | None = None) -> int:
        """Bulk bit import (reference: ``API.Import``): ID or key form;
        timestamps are epoch-seconds or ISO strings.  In cluster mode
        batches route through the breaker-aware bulk-import coordinator
        (:class:`pilosa_tpu.ingest.BulkImporter` — hinted handoff and
        op-id dedup cover bulk ops, r15); ``direct`` marks an
        already-routed forwarded batch, ``op_id`` its dedup identity
        (a re-delivered batch is a no-op).  Local applies are
        oplog-batched: one fsync-coalesced append per batch per
        fragment, counted on ``ingest_bits_total`` and timed on
        ``import_batch_seconds``."""
        t0 = _time.perf_counter()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field {field!r} not found", 404)
        rows = self._translate_rows(idx, f, row_ids, row_keys, direct)
        cols = self._translate_cols(idx, col_ids, col_keys, direct)
        if len(rows) != len(cols):
            raise ApiError("rows and columns length mismatch")
        stats = self.executor.stats
        if self.cluster is not None and not direct:
            changed = self._bulk().import_bits(index, field, rows, cols,
                                               timestamps, clear)
            stats.observe("import_batch_seconds",
                          _time.perf_counter() - t0)
            return changed
        if (op_id is not None and self.cluster is not None
                and op_id in self.cluster.applied_ops):
            return 0  # duplicate delivery (retry / replayed hint)
        ts = self._parse_timestamps(timestamps, len(cols))
        from pilosa_tpu.store.oplog import SyncBatch
        sb = SyncBatch()
        if clear:
            changed = f.clear_import(rows, cols, sync_batch=sb)
        else:
            changed = f.import_bits(rows, cols, ts, sync_batch=sb)
            idx.note_columns(cols)
        sb.flush()
        if op_id is not None and self.cluster is not None:
            self.cluster.applied_ops.add(op_id)
        if changed:
            stats.count("ingest_bits_total", changed)
        stats.observe("import_batch_seconds", _time.perf_counter() - t0)
        return changed

    def _bulk(self):
        """The cluster bulk-import coordinator (lazy: the cluster is
        attached after construction)."""
        bulk = getattr(self, "_bulk_importer", None)
        if bulk is None or bulk.cluster is not self.cluster:
            from pilosa_tpu.ingest import BulkImporter
            bulk = self._bulk_importer = BulkImporter(self, self.cluster)
        return bulk

    def import_values(self, index: str, field: str, *,
                      col_ids=None, col_keys=None, values=None,
                      direct: bool = False) -> int:
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field {field!r} not found", 404)
        if f.options.type not in BSI_TYPES:
            raise ApiError(f"field {field!r} is not an int field")
        cols = self._translate_cols(idx, col_ids, col_keys, direct)
        if values is None or len(values) != len(cols):
            raise ApiError("columns and values length mismatch")
        if self.cluster is not None and not direct:
            return self._route_import_values(index, field, cols, values)
        try:
            changed = f.import_values(cols, values)
        except ValueError as e:
            raise ApiError(str(e))
        idx.note_columns(cols)
        return changed

    def _route_to_owners(self, index: str, shard: int, local_fn,
                         remote_fn) -> int:
        """Apply a write on every replica owner of a shard; returns the
        primary's changed count (reference: ``API.Import`` routing to
        shard-owning nodes, SURVEY.md §4.5).  ``local_fn()`` applies
        locally; ``remote_fn(client)`` forwards with the direct flag."""
        primary_changed = None
        for owner in self.cluster.shard_owners(index, shard):
            if owner == self.cluster.node_id:
                got = local_fn()
            else:
                got = remote_fn(self.cluster._client(owner))
            if primary_changed is None:
                primary_changed = got
        return primary_changed or 0

    @staticmethod
    def _proto_or_json_forward(path: str, encode, json_body):
        """Forwarded import batches ride the protobuf wire (packed
        varint id arrays, SURVEY.md §3.3 internal proto), encoded
        LAZILY on the first remote owner — all-local routing
        (single-node clusters, owner-local shards) must not pay the
        encode.  Inputs the codec refuses (heterogeneous timestamps,
        out-of-int64 values: ValueError) fall back to JSON, which
        allows them."""
        from pilosa_tpu.api import proto
        cache: list = []

        def remote(client):
            if not cache:
                try:
                    cache.append((encode(), True))
                except ValueError:
                    cache.append((None, False))
            body, is_proto = cache[0]
            if is_proto:
                return client._do(
                    "POST", path, body, content_type=proto.CONTENT_TYPE,
                    headers={"X-Pilosa-Direct": "1"})["changed"]
            return client._json("POST", path, json_body(),
                                headers={"X-Pilosa-Direct": "1"})["changed"]
        return remote

    def _route_import_values(self, index: str, field: str, cols,
                             values) -> int:
        from pilosa_tpu.api import proto
        shards = cols // np.uint64(SHARD_WIDTH)
        changed = 0
        for shard in np.unique(shards):
            m = shards == shard
            sub_cols = [int(c) for c in cols[m]]
            sub_vals = [values[i] for i in np.nonzero(m)[0]]
            remote = self._proto_or_json_forward(
                f"/index/{index}/field/{field}/importValue",
                lambda: proto.encode_import_value_request(
                    col_ids=sub_cols, values=sub_vals),
                lambda: {"columnIDs": sub_cols, "values": sub_vals})
            changed += self._route_to_owners(
                index, int(shard),
                lambda: self.import_values(
                    index, field, col_ids=sub_cols, values=sub_vals,
                    direct=True),
                remote)
        return changed

    def import_roaring(self, index: str, field: str, shard: int, blob: bytes,
                       view: str = VIEW_STANDARD, clear: bool = False,
                       direct: bool = False,
                       op_id: str | None = None) -> int:
        """Pre-encoded roaring import — the bulk-loader fast path
        (reference: ``API.ImportRoaring``, SURVEY.md §4.5).  Cluster
        routing, op-id dedup and fsync coalescing mirror
        :meth:`import_bits` (r15)."""
        t0 = _time.perf_counter()
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field {field!r} not found", 404)
        if f.options.type not in ("set", "time"):
            # raw fragment unions skip field-type semantics (mutex
            # last-write-wins, bool row validation, BSI encoding) —
            # same restriction as upstream API.ImportRoaring
            raise ApiError(
                "import-roaring supports set/time fields, not "
                f"{f.options.type!r}; use the pair import", 400)
        stats = self.executor.stats
        if self.cluster is not None and not direct:
            changed = self._bulk().import_roaring(index, field, shard,
                                                  blob, view, clear)
            stats.observe("import_batch_seconds",
                          _time.perf_counter() - t0)
            return changed
        if (op_id is not None and self.cluster is not None
                and op_id in self.cluster.applied_ops):
            return 0  # duplicate delivery (retry / replayed hint)
        from pilosa_tpu.store.oplog import SyncBatch
        sb = SyncBatch()
        frag = f.view(view, create=True).fragment(shard, create=True)
        try:
            changed = f_changed = frag.import_roaring(blob, clear=clear,
                                                      sync_batch=sb)
        except ValueError as e:
            raise ApiError(f"bad roaring payload: {e}")
        if f_changed and idx.track_existence and not clear:
            from pilosa_tpu.store import roaring as rc
            positions = rc.deserialize(blob)
            cols = (np.unique(positions % np.uint64(SHARD_WIDTH))
                    + np.uint64(shard * SHARD_WIDTH))
            idx.note_columns(cols)
        sb.flush()
        if op_id is not None and self.cluster is not None:
            self.cluster.applied_ops.add(op_id)
        if changed:
            stats.count("ingest_bits_total", changed)
        stats.observe("import_batch_seconds", _time.perf_counter() - t0)
        return changed

    # -- export -------------------------------------------------------------

    def export_csv(self, index: str, field: str) -> str:
        """CSV of (row,col) pairs (reference: ``API.ExportCSV``), keys
        translated when the index/field is keyed."""
        idx = self._index(index)
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field {field!r} not found", 404)
        out = io.StringIO()
        col_log = (self.executor.translate.columns(index)
                   if idx.keys else None)

        def col_repr(c: int):
            return col_log.key_of(int(c)) if col_log else int(c)

        if f.options.type in BSI_TYPES:
            # BSI export: one "column,value" line per non-null column
            # (reference: ExportCSV over int fields)
            from pilosa_tpu.engine.bsi import (EXISTS_ROW, OFFSET_ROW,
                                               SIGN_ROW)
            view = f.bsi_view()
            if view is not None:
                for shard in sorted(view.fragments):
                    frag = view.fragment(shard)
                    exists = frag.row(EXISTS_ROW).columns()
                    if len(exists) == 0:
                        continue
                    vals = np.zeros(len(exists), dtype=np.int64)
                    for b in range(f.options.bit_depth):
                        hit = np.isin(exists,
                                      frag.row(OFFSET_ROW + b).columns())
                        vals[hit] += 1 << b
                    neg = np.isin(exists, frag.row(SIGN_ROW).columns())
                    vals[neg] = -vals[neg]
                    vals += f.options.base
                    base_col = np.uint64(shard * SHARD_WIDTH)
                    for c, v in zip(exists, vals):
                        out.write(f"{col_repr(int(c) + int(base_col))},"
                                  f"{f.from_stored(int(v))}\n")
            return out.getvalue()

        row_log = (self.executor.translate.rows(index, field)
                   if f.options.keys else None)
        view = f.standard_view()
        if view is not None:
            for shard in sorted(view.fragments):
                frag = view.fragment(shard)
                for r in frag.row_ids():
                    cols = frag.row(r).columns().astype(np.uint64) + \
                        np.uint64(shard * SHARD_WIDTH)
                    rkey = row_log.key_of(r) if row_log else r
                    for c in cols:
                        out.write(f"{rkey},{col_repr(int(c))}\n")
        return out.getvalue()

    # -- backup / restore ---------------------------------------------------

    def backup_tar(self) -> bytes:
        """Consistent tar of the data dir (reference: ``ctl/backup``):
        snapshot every open fragment so snapshots subsume op-logs, then
        tar snapshot + meta + key files."""
        import tarfile
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for frag in v.fragments.values():
                        if frag.op_n > 0:
                            frag.snapshot()
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w") as tar:
            tar.add(self.holder.path, arcname="data",
                    filter=lambda ti: None if ti.name.endswith(".oplog")
                    else ti)
        return buf.getvalue()

    def restore_tar(self, blob: bytes) -> None:
        """Restore a backup tar into the data dir and reopen the holder.
        Refuses when indexes already exist (as upstream restore does)."""
        import tarfile
        if self.holder.indexes:
            raise ApiError("restore requires an empty holder", 409)
        buf = io.BytesIO(blob)
        with tarfile.open(fileobj=buf) as tar:
            for member in tar.getmembers():
                name = member.name
                if not name.startswith("data/") and name != "data":
                    raise ApiError(f"unexpected tar entry {name!r}")
            import tempfile
            with tempfile.TemporaryDirectory() as tmp:
                tar.extractall(tmp, filter="data")
                import shutil
                src = f"{tmp}/data"
                for entry in sorted(os.listdir(src)):
                    shutil.move(f"{src}/{entry}",
                                f"{self.holder.path}/{entry}")
        self.holder.close()
        self.holder.open()
        self.executor.planes.invalidate()
        self.executor.invalidate_plans()
        self.executor.translate.close()

    # -- introspection ------------------------------------------------------

    def storage_stats(self) -> dict:
        """Aggregate storage footprint: fragment count, op-log bytes
        (un-compacted write-ahead growth) and snapshot bytes.  Cheap
        (stat calls only); the ``/metrics`` gauges and the ``/status``
        storage block both read this."""
        frags = oplog = snap = 0
        for idx in list(self.holder.indexes.values()):
            for f in list(idx.fields.values()):
                for v in list(f.views.values()):
                    for frag in list(v.fragments.values()):
                        frags += 1
                        try:
                            oplog += os.path.getsize(frag._oplog.path)
                        except OSError:
                            pass
                        try:
                            snap += os.path.getsize(frag.path)
                        except OSError:
                            pass
        return {"fragmentCount": frags, "oplogBytes": oplog,
                "snapshotBytes": snap}

    def status(self) -> dict:
        import jax
        devices = [{"id": d.id, "platform": d.platform, "kind": d.device_kind}
                   for d in jax.devices()]
        state = "NORMAL"
        nodes = [{"id": "local", "uri": "", "state": state, "isPrimary": True}]
        cluster_health = None
        write_health = None
        if self.cluster is not None:
            nodes = self.cluster.nodes_status()
            state = self.cluster.state
            # serving-through-failure visibility: per-peer last-seen
            # age, suspect verdict, breaker state
            cluster_health = self.cluster.health_payload()
            # writes-through-failure visibility (r13): hint backlog,
            # oldest age vs the hint_max_age bound, per-peer drains
            write_health = self.cluster.write_health_payload()
        ex = self.executor
        snap_counters = ex.stats.snapshot()["counters"]
        shed = snap_counters.get("query_shed_total", {})
        pc = ex.planes.stats()
        delta = pc.get("delta", {})
        ingested = snap_counters.get("ingest_bits_total", {})
        # storage-integrity pane (r19): disk governor state, the
        # quarantine registry, scrub progress, last replica repair
        storage_health = None
        sh = getattr(self.holder, "storage_health", None)
        if sh is not None:
            storage_health = sh.payload()
            scrubber = getattr(self, "scrubber", None)
            if scrubber is not None:
                storage_health["scrub"] = scrubber.payload()
        return {"state": state, "nodes": nodes,
                **({"storageHealth": storage_health}
                   if storage_health is not None else {}),
                # ingest visibility (r15): device delta overlays
                # (fill %, compaction backlog + last duration) and
                # bulk-import volume — the mixed read/write serving
                # pane
                "ingest": {
                    "deltaFillRatio": delta.get("deltaFillRatio", 0.0),
                    "deltaCells": delta.get("deltaCells", 0),
                    "deltaCap": delta.get("deltaCap", 0),
                    "deltaOverlayBits": delta.get("deltaOverlayBits", 0),
                    "absorbs": delta.get("absorbs", 0),
                    "compactions": delta.get("compactions", 0),
                    "pendingCompactions": delta.get(
                        "pendingCompactions", 0),
                    "lastCompactionSeconds": delta.get(
                        "lastCompactionSeconds", 0.0),
                    "importedBits": int(sum(ingested.values())),
                    "importBatch": ex.stats.histogram_summary(
                        "import_batch_seconds")},
                # self-healing pipeline visibility (r18): governor
                # state (healthy/degraded/probing), watchdog knob,
                # quarantine counts — the serving-through-a-sick-device
                # pane
                "deviceHealth": ex.device_health(),
                # mesh serving (ISSUE 16): device count, shard axis,
                # per-device resident plane bytes, padded shards —
                # only present when a placement is wired
                **({"mesh": mesh_block}
                   if (mesh_block := ex.mesh_status()) is not None
                   else {}),
                **({"clusterHealth": cluster_health}
                   if cluster_health is not None else {}),
                **({"writeHealth": write_health}
                   if write_health is not None else {}),
                "localShardCount": sum(len(i.available_shards())
                                       for i in self.holder.indexes.values()),
                "devices": devices,
                # admission/shedding visibility: current slot occupancy,
                # the cap, total sheds, and the queue-wait distribution
                "admission": {
                    "slotsInUse": ex.slots_in_use,
                    "maxConcurrent": ex.max_concurrent,
                    "shedTotal": int(sum(shed.values())),
                    "queueWait": ex.stats.histogram_summary(
                        "query_queue_wait_seconds")},
                # on-disk footprint: what backup archives and the
                # snapshot queue compacts (oplogBytes growth = log
                # compaction falling behind), plus the plane-build
                # pipeline's health (r10): cold-build volume, failures
                # (a wedged background build is otherwise invisible),
                # and the dense-sidecar warm cache's hit ratio
                "storage": {
                    **self.storage_stats(),
                    "planeBuild": {
                        k: pc[k]
                        for k in ("builds", "buildSeconds", "buildBytes",
                                  "buildFailures", "warmHits",
                                  "warmMisses", "meshed")}},
                # slow-query visibility: ring totals + the configured
                # threshold (full records behind GET /debug/slow)
                "slowQueries": {
                    **self.slow_log.summary(),
                    "thresholdSeconds": self.slow_query_threshold},
                # HBM working set (reference: /status occupancy; the
                # device plane cache is the resident working set here)
                "planeCache": pc,
                # multi-tenant economy (r17): paging state, per-tenant
                # residency/hit-ratio/page-ins/sheds, QoS quotas,
                # eviction reasons
                "tenancy": ex.tenancy_status(),
                # device-cost ledger (r19): measured device seconds /
                # bytes scanned attributed per tenant, per query
                # shape, per plane (top-K + other), compile totals
                "costs": ex.cost_status(),
                # time-view planes (r23): which time fields serve range
                # queries from a resident bucketed plane (device speed)
                # vs the span-union fallback
                "timeViews": ex.time_status(),
                # per-stage overhead attribution (parse/plan/admit/
                # dispatch/read/assemble) — the diagnostics dump behind
                # a concurrency-gap breakdown
                "queryStages": self.executor.stats.histogram_summary(
                    "query_stage_seconds")}

    def info(self) -> dict:
        import os
        return {"shardWidth": SHARD_WIDTH,
                "cpuPhysicalCores": os.cpu_count(),
                "memory": _total_memory_bytes()}

    # -- internal -----------------------------------------------------------

    def _index(self, name: str):
        idx = self.holder.index(name)
        if idx is None:
            raise ApiError(f"index {name!r} not found", 404)
        return idx

    def _translate_rows(self, idx, f, row_ids, row_keys,
                        direct: bool = False) -> np.ndarray:
        if row_keys is not None:
            if not f.options.keys:
                raise ApiError(f"field {f.name!r} is not keyed")
            if self.cluster is not None:
                ids = self.cluster.translate_keys(idx.name, f.name,
                                                  list(row_keys), create=True)
                return np.array(ids, dtype=np.uint64)
            log = self.executor.translate.rows(idx.name, f.name)
            return np.array(log.translate(list(row_keys), create=True),
                            dtype=np.uint64)
        if row_ids is None:
            raise ApiError("missing rowIDs/rowKeys")
        if f.options.keys and not direct:
            # forwarded cluster batches (direct) are pre-translated IDs
            raise ApiError(f"field {f.name!r} is keyed; use rowKeys")
        return np.asarray(row_ids, dtype=np.uint64)

    def _translate_cols(self, idx, col_ids, col_keys,
                        direct: bool = False) -> np.ndarray:
        if col_keys is not None:
            if not idx.keys:
                raise ApiError(f"index {idx.name!r} is not keyed")
            if self.cluster is not None:
                ids = self.cluster.translate_keys(idx.name, None,
                                                  list(col_keys), create=True)
                return np.array(ids, dtype=np.uint64)
            log = self.executor.translate.columns(idx.name)
            return np.array(log.translate(list(col_keys), create=True),
                            dtype=np.uint64)
        if col_ids is None:
            raise ApiError("missing columnIDs/columnKeys")
        if idx.keys and not direct:
            raise ApiError(f"index {idx.name!r} is keyed; use columnKeys")
        return np.asarray(col_ids, dtype=np.uint64)

    @staticmethod
    def _parse_timestamps(timestamps, n: int):
        if timestamps is None:
            return None
        out = []
        for t in timestamps:
            if t in (None, 0, ""):
                out.append(None)
            elif isinstance(t, str):
                from pilosa_tpu.store.timeq import parse_pql_time
                out.append(parse_pql_time(t))
            else:
                out.append(datetime.utcfromtimestamp(int(t)))
        return out


def _total_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0
