"""Executor: PQL AST → jitted TPU kernels over the holder.

Reference: ``executor.go`` (SURVEY.md §3.2, §4.2–§4.5) — per-call
dispatch (``executeCall`` → ``executeIntersect/executeTopN/…``) with a
per-shard map-reduce over cluster nodes.  The TPU rebuild replaces the
fan-out/merge entirely: every resident shard is one slice of a batched
device array (``uint32[n_shards, W]``), one XLA program evaluates the
call tree for all shards at once, and cross-shard reduction is a dense
``sum``/``top_k`` — compiled to ICI collectives when the shard axis is
sharded over a mesh (see ``pilosa_tpu.parallel``), not an HTTP merge.

Key translation happens on ingress (args) and egress (results), as in
the reference (``executor.Execute`` translate steps).
"""

from __future__ import annotations

import math
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import fault
from pilosa_tpu.engine import bsi as bsik
from pilosa_tpu.engine import kernels
from pilosa_tpu.engine.words import SHARD_WIDTH, WORDS_PER_SHARD, unpack_columns
from pilosa_tpu.exec.planes import (CODED_ROWS_OVER, PAD_SHARD, PlaneCache,
                                   PlaneSet)
from pilosa_tpu.exec.result import (ExtractResult, GroupCountsResult,
                                    Pair, PairsResult, RowIdsResult,
                                    RowResult, ValCount)
from pilosa_tpu.obs import metrics as _metrics
from pilosa_tpu.obs.ledger import (clear_query_context,
                                   set_query_context)
from pilosa_tpu.obs.metrics import (StageTimer, current_timer,
                                    set_current_timer)
from pilosa_tpu.obs.metrics import enter_stage as _stage  # per-request clock
from pilosa_tpu.obs.tracing import current_trace_id
from pilosa_tpu.pql import parse_cached
from pilosa_tpu.pql.ast import (BETWEEN_OPS, Call, Condition, Query,
                                between_cmp_ops)
from pilosa_tpu.store.field import BSI_TYPES, Field
from pilosa_tpu.store.holder import Holder
from pilosa_tpu.store.index import Index
from pilosa_tpu.store.timeq import (parse_pql_time, view_span,
                                    views_by_time_range)
from pilosa_tpu.store.translate import TranslateStore
from pilosa_tpu.store.view import VIEW_STANDARD

# option keys that are never field names in call args.  Reservation is
# PER CALL: a field named "n" must still work in Set(5, n=777) even
# though TopN reserves n= (the upstream grammar scopes options the same
# way).  RESERVED_KEYS is the superset default for option-heavy calls.
RESERVED_KEYS = frozenset({
    "from", "to", "limit", "offset", "n", "field", "ids", "filter", "column",
    "like", "previous", "aggregate", "sort", "shards", "index",
    "attrName", "attrValue", "columnAttrs", "excludeColumns", "tanimoto",
    "excludeRowAttrs",
})

# uint32[S, W] -> int32[S] set bits per shard (the Limit/Extract
# push-down's shard cutoff; one S-int read instead of the bitmap)
_shard_popcounts = jax.jit(kernels.count)


_CALL_RESERVED = {
    "Row": frozenset({"from", "to", "excludeRowAttrs"}),
    "Range": frozenset({"from", "to"}),
    "Set": frozenset(),
    "Clear": frozenset(),
    "ClearRow": frozenset(),
    "Store": frozenset(),
}


def reserved_for(call_name: str) -> frozenset:
    return _CALL_RESERVED.get(call_name, RESERVED_KEYS)


def _field_arg(call: Call):
    """Per-call-scoped field_arg with query-error (not 500) semantics."""
    try:
        return call.field_arg(reserved_for(call.name))
    except ValueError as e:
        raise ExecutionError(str(e))

_BITMAP_CALLS = frozenset({
    "Row", "Intersect", "Union", "Difference", "Xor", "Not", "All", "Range",
    "Shift", "UnionRows", "ConstRow", "Limit",
})

# every call that writes nothing: the bitmap calls and the handlers
# that only read.  A request made of these alone may execute its calls
# in any order (``Executor._execute_calls``); Set, Clear, ClearRow,
# Store, the attribute writes and any call this list does not know
# keep the request strictly ordered.
_READ_CALLS = _BITMAP_CALLS | {
    "Count", "Sum", "Min", "Max", "Percentile", "Distinct",
    "IncludesColumn", "TopN", "Rows", "GroupBy", "Extract",
}

# request-level batch families -> the span / ``query_seconds`` name
_FAMILY_SPAN = {"count": "CountBatch", "sum": "SumBatch",
                "minmax": "MinMaxBatch"}


def _is_read(call: Call) -> bool:
    if call.name == "Options" and len(call.children) == 1:
        call = call.children[0]
    return call.name in _READ_CALLS


def _call_family(call: Call) -> tuple | None:
    """What a top-level call can execute together with, read from the
    call itself: every single-child ``Count`` (``_count_batch``: one
    program whatever the fields); ``Sum`` calls over one BSI field;
    ``Min`` / ``Max`` calls over one BSI field (``_agg_batch``: one
    K-item program over the field's plane).  None: the call runs
    alone (TopN, GroupBy, Rows, an ``Options`` wrapper, ...)."""
    if call.name == "Count":
        return ("count",) if len(call.children) == 1 else None
    if call.name in ("Sum", "Min", "Max"):
        fname = call.args.get("field") or call.args.get("_field")
        if isinstance(fname, str):
            return ("sum" if call.name == "Sum" else "minmax", fname)
    return None


_SCALAR_TO_KEY = {"<": "lt", "<=": "le", ">": "gt", ">=": "ge",
                  "==": "eq", "!=": "ne"}

# eager word-wise kernels by the canonical op token
# (pql.ast.BOOL_CALLS names → tokens; exec.tree.fold_bool_call folds)
_EAGER_OPS = {"or": kernels.union, "and": kernels.intersect,
              "andnot": kernels.difference, "xor": kernels.xor}


# leaf-spec kinds (``Executor._plan_spec``) that bake nothing a write
# can stale: rows, the existence row and BSI planes re-fetch through
# the plane cache on every hit, consts are predicate masks.  ``zeros``
# (an absent keyed row) and ``bsi-exists`` (a saturation verdict) are
# verdicts about the data: they stay generation-checked.
_REFETCHED_LEAVES = frozenset({"row", "exists", "const", "bsi-plane"})


def _bsi_signature(options) -> tuple:
    """Everything a baked BSI predicate depends on.  A cached plan
    resolved its offsets (``to_stored(value) - base``) and saturation
    verdicts against these options, so validity must drop the plan
    when ANY of them changes — comparing ``bit_depth`` alone misses a
    drop + recreate with the same depth but a different
    base/scale/epoch, which would serve skewed predicates forever on
    entries that skip the generation compare."""
    return (options.type, options.bit_depth, options.base,
            options.scale, options.epoch, options.time_unit)


def _is_device_oom(e: Exception) -> bool:
    """XLA device-memory exhaustion, by status string.  jax wraps the
    status as XlaRuntimeError/JaxRuntimeError on direct dispatch, but
    an async execution that fails on device surfaces at the host READ
    as a plain ValueError carrying the same RESOURCE_EXHAUSTED text
    (seen from a PJRT plug-in backend under 32-way concurrency, r5).
    The type gate stays: an ExecutionError merely QUOTING user input
    (e.g. PQL ``RESOURCE_EXHAUSTED()``) must not trigger a
    cache-dropping recovery."""
    return ("RESOURCE_EXHAUSTED" in str(e)
            and type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError",
                                     "ValueError"))


def _lex_gt(mat: np.ndarray, prev: tuple) -> np.ndarray:
    """Rows of ``mat`` strictly greater than ``prev`` in lexicographic
    order (GroupBy ``previous=`` paging, vectorized)."""
    gt = np.zeros(len(mat), bool)
    eq = np.ones(len(mat), bool)
    for lvl, p in enumerate(prev):
        col = mat[:, lvl]
        gt |= eq & (col > p)
        eq &= col == p
    return gt


class ExecutionError(Exception):
    pass


class ExecutorSaturatedError(ExecutionError):
    """Admission timed out: every execution slot stayed busy for the
    whole wait budget.  The API edge maps this to HTTP 503 with a
    ``Retry-After`` hint (load shedding, VERDICT advice #6) — overload
    is not a client error and must not surface as 500/400."""

    def __init__(self, msg: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = retry_after


class WriteUnavailableError(ExecutionError):
    """A write cannot serve right now: a replica is down and durable
    hinted handoff cannot cover it — handoff disabled
    (``hint_max_age <= 0``), the peer's hint backlog overflowed past
    ``hint_max_age``, or no live replica remains to apply the op at
    all.  The API edge maps this to HTTP 503 + ``Retry-After`` with a
    structured ``writeUnavailable`` body naming the down replica
    (r13; mirrors the 504 timeout treatment) — unavailability is not a
    client error and must not surface as a generic 400/500.

    ``reason`` is one of ``"replica_down"`` (handoff disabled — the
    pre-r13 strict contract), ``"hint_overflow"`` (the boundedness
    rule fired), ``"no_live_replica"`` (every owner of some shard is
    unreachable), or ``"replica_busy"`` (an alive replica shed the op
    pre-execution — saturation is transient, so it is never hinted)."""

    def __init__(self, msg: str, op: str, replica: str | None,
                 reason: str, retry_after: float = 1.0):
        super().__init__(msg)
        self.op = op
        self.replica = replica
        self.reason = reason
        self.retry_after = retry_after


# negative plan-cache entry: this query shape is structurally outside
# the plan cache (not all-Count, time ranges, …) — skip re-walking it
_UNPLANNABLE = object()


@dataclass
class _PlanEntry:
    """One cached serving plan for an all-Count query (r6 tentpole).

    ``kind``:

    - ``"plane"`` — same-field plain-row Count batch: answered by ONE
      whole-plane ``row_counts`` program over the resident plane
      (``row_ids`` are the per-call resolved rows; slots come fresh
      from the PlaneSet each hit).
    - ``"generic"`` — arbitrary fusable Count trees: ``nodes`` (leaf
      indices local to ``leaf_specs``) re-materialize through the
      plane cache each hit.
    - ``"tree"`` — compound boolean trees compiled whole (r16):
      ``tree_specs`` are canonical :class:`exec.tree.TreeSpec`\\ s;
      rows re-resolve to plane slots and extras re-materialize per
      hit, and the anchor plane's delta overlay keeps answers fresh
      under sustained ingest.

    A ``"plane"`` or ``"tree"`` entry whose calls also lower through
    ``_plan_spec`` carries the ``"generic"`` form beside its own
    (``nodes`` / ``leaf_specs``; ``deps`` / ``gens`` / ``bsi_sigs``
    cover both): where the whole-field plane is not resident and the
    selectivity rule says it will not become so (a tiny slice of a
    huge row set), the hit is answered by that per-row form instead
    of falling through (``Executor._run_plan_inner``).

    Validity: ``shards`` must equal the current shard set and ``gens``
    must equal the dependency views' generations — a write to any
    source fragment (including creating a row key that planned as a
    zeros leaf) invalidates on the next hit.  Leaf ARRAYS are never
    cached here; they come from the PlaneCache, which revalidates
    independently."""

    kind: str
    shards: tuple
    deps: tuple            # ((field_name | "\x00exists", view_name), ...)
    gens: tuple            # per-dep generation tuples (None = view absent)
    n_calls: int
    nodes: tuple = ()
    leaf_specs: tuple = ()
    field_name: str | None = None
    row_ids: tuple = ()
    # (field_name, _bsi_signature(options)) per BSI field whose
    # predicate masks / saturation verdicts the plan baked: depth can
    # GROW via a write OUTSIDE this entry's shard subset (generations
    # over entry.shards won't see it), and a drop + recreate with the
    # SAME depth but a different base/scale/epoch would silently skew
    # every baked offset on entries that skip the gens compare — so
    # validity re-checks the full predicate-relevant option signature
    bsi_sigs: tuple = ()
    # "plane"/"tree" plans over UNKEYED fields bake nothing a write
    # can stale: row ids are the literal PQL integers and the PlaneSet
    # revalidates its own generations (delta overlays absorb writes,
    # r15).  Such entries skip the per-hit generation compare — under
    # sustained ingest the generations move every batch, and dropping
    # the plan per write put parse+plan back on every request.
    # ``unkeyed_fields`` lists the set fields whose identity (exists,
    # unkeyed, non-BSI) the per-hit validity check re-verifies so a
    # drop + recreate under the same name still kills the entry.
    unkeyed_plane: bool = False
    unkeyed_fields: tuple = ()
    # "tree" entries: canonical specs, one per Count call (r16)
    tree_specs: tuple = ()
    # "bsirange" entries (r20): per call (field_name, op_keys,
    # offsets) — BSI range Counts served through the batcher's
    # bsirange family (plane fetched delta-aware per hit, so the
    # entry survives sustained ingest like the unkeyed-plane kinds;
    # ``bsi_sigs`` pins depth/base so the baked offsets stay valid)
    range_items: tuple = ()


class _PlanHit:
    """One plan-cache hit's scratch, for an entry that carries a
    per-row form: the generation tuple of each (field, view) the hit
    reads — swept once, shared by the selectivity rule's ``plane_bytes``
    and every row fetch — and ``by_rule``: whether what kept the
    whole-plane form from running was the selectivity rule (so the
    per-row form IS the resident form and serves) rather than an
    admission decision, which stays on the un-cached path."""

    __slots__ = ("gens", "by_rule")

    def __init__(self):
        self.gens: dict[tuple, tuple] = {}
        self.by_rule = False


class QueryTimeoutError(ExecutionError):
    """Query deadline exceeded (reference: upstream threads request
    context cancellation through the executor; deadlines are the
    equivalent for a compiled-dispatch engine — checked at block
    boundaries, between calls, before each streamed row block, and —
    r18 — while blocked on the dispatch pipeline, where ``stage``
    names what the query was waiting on when the clock ran out
    (queued/dispatch/readback); it rides the structured 504 body)."""

    def __init__(self, msg: str, stage: str | None = None):
        super().__init__(msg)
        self.stage = stage


class PipelineStalledError(ExecutionError):
    """A dispatch-pipeline window exceeded the watchdog bound and was
    quarantined (r18): the caller's work was failed loudly — naming
    the stalled stage — instead of wedging a serving thread forever
    behind a sick device.  Maps to a structured HTTP 500
    (``pipelineStall`` body) at the public and internal edges."""

    def __init__(self, msg: str, stage: str = "dispatch",
                 elapsed: float = 0.0):
        super().__init__(msg)
        self.stage = stage
        self.elapsed = elapsed


@dataclass
class _Ctx:
    index: Index
    shards: tuple[int, ...]
    translate_output: bool = True
    deadline: float | None = None  # time.monotonic() cutoff

    def check_deadline(self) -> None:
        if self.deadline is not None and time.monotonic() > self.deadline:
            raise QueryTimeoutError("query timeout exceeded")


class Executor:
    MAX_PLANS = 512  # plan-cache entries (user-controlled keys: bounded)
    # admission wait budget before shedding with 503 (class attr so
    # saturation tests shrink it without touching live config)
    SLOT_TIMEOUT_S = 180.0

    def __init__(self, holder: Holder, translate: TranslateStore | None = None,
                 place=None, plane_budget: int | None = None, placement=None,
                 stats=None, tracer=None,
                 count_batch_window: float | str = "adaptive",
                 max_concurrent: int = 8, plane_sidecars: bool = True,
                 delta_cells: int = 65536,
                 delta_compact_fraction: float = 0.5,
                 tree_fusion: bool = True,
                 dispatch_pipeline_depth: int = 2,
                 solo_fastlane: bool = True,
                 dispatch_watchdog_seconds: float = 30.0,
                 device_health_probe_seconds: float = 5.0,
                 plane_paging: bool = True,
                 plane_page_bytes: int = 64 << 20,
                 tenant_byte_quota: int = 0,
                 tenant_qps_quota: float = 0.0,
                 tenant_slot_quota: int = 0,
                 tenant_device_seconds_quota: float = 0.0,
                 fused_warmup: bool = False):
        """``placement`` (a :class:`pilosa_tpu.parallel.MeshPlacement`)
        shards every plane's leading axis over the device mesh and pads
        shard lists to the mesh size; without it, planes live on the
        default device.  ``max_concurrent`` bounds simultaneously
        EXECUTING queries (scratch admission; 0 disables) — excess
        clients queue at the executor, not in device memory.
        ``count_batch_window``: ``"adaptive"`` (default) coalesces
        concurrent dense reads with a window that grows under queue
        pressure and shrinks to 0 when solo; a float fixes the window
        (pre-r6 behavior); 0 disables coalescing.
        ``dispatch_pipeline_depth`` (r17): dispatched-but-unread
        collection windows the batcher may run ahead (window N's
        compute overlaps window N-1's readback); <=1 restores serial
        dispatch->read.  ``solo_fastlane`` (r17): width-1 requests
        with no queue pressure dispatch inline on the caller thread
        over donated ping-pong chains instead of forming a window.
        ``dispatch_watchdog_seconds`` (r18): per-stage age bound on
        in-flight batcher windows — a window stalled past it is
        quarantined (items failed with a structured error naming the
        stage, pipeline slot reclaimed, wedged worker superseded);
        0 disables the monitor entirely (pre-r18 contract).
        ``device_health_probe_seconds`` (r18): how long degraded
        serving (per-item fallback execution after consecutive
        dispatch faults / watchdog trips) lasts before one window
        probes the fused pipeline again.

        Tenancy (r17 — tenant = index name): ``plane_paging`` turns
        over-budget plain-Row Count planes into PAGED residency
        (``tenancy.PlanePager`` — only hot shard pages device-resident,
        the host oracle covers the rest, bit-exact); single-device
        only, a mesh placement disables it.  ``plane_page_bytes``
        sizes one page.  ``tenant_byte_quota`` caps one tenant's
        resident plane/page bytes (0 = off); ``tenant_qps_quota`` /
        ``tenant_slot_quota`` shed an over-quota tenant's queries with
        a structured 503 BEFORE they take an executor slot (0 = off).
        ``tenant_device_seconds_quota`` (r19): cap a tenant's RECENT
        measured device seconds (the cost ledger's decayed window,
        ~60s half-life) — sheds by what queries actually COST on
        device, not how many arrived (0 = off).

        ``fused_warmup`` (r24) runs the compile-ladder warmer:
        delta-aware fused programs for a newly resident plane shape
        pre-compile on a background thread so the first post-ingest
        query serves from a warm cache (single-device only — disabled
        under a mesh placement)."""
        self.holder = holder
        self.translate = translate or TranslateStore(
            holder.path, health=getattr(holder, "storage_health", None))
        self.placement = placement
        if placement is not None and place is None:
            place = placement.place
        kw = {"budget_bytes": plane_budget} if plane_budget else {}
        from pilosa_tpu.obs import GLOBAL_TRACER, NopStats
        from pilosa_tpu.tenancy import (PlanePager, ResidencyGovernor,
                                        TenantQos)
        self.stats = stats or NopStats()
        # a validated plan that served nothing (the un-cached path had
        # an admission decision to make: see _execute_planned), and one
        # that answered by its per-row form (_run_plan_inner);
        # registered at 0 so the series print before the first one
        self.stats.count("plan_cache_fallthrough_total", 0)
        self.stats.count("plan_cache_row_serves_total", 0)
        # same-family calls of one request launched as one program
        # (_execute_calls), and the calls those groups carried
        for kind in _FAMILY_SPAN:
            self.stats.count("request_call_groups_total", 0, family=kind)
            self.stats.count("request_grouped_calls_total", 0,
                             family=kind)
        # GroupBy blocks handed on (_execute_groupby), by how their
        # counts are computed (exec.groupby.block_form)
        for form in ("pair", "mapped"):
            self.stats.count("groupby_blocks_total", 0, form=form)
        # the combinations a GroupBy dispatched, after its levels were
        # cut to the rows its filter reaches, and the rows so cut
        self.stats.count("groupby_combinations_total", 0)
        self.stats.count("groupby_rows_pruned_total", 0)
        # device-cost ledger + flight recorder (r19): one ledger and
        # one event ring per executor, threaded into every layer that
        # spends device time (planes, pager, fused cache, batcher,
        # governor) — attribution and incident capture are always on.
        # Flight dumps land under the holder's data dir.
        from pilosa_tpu.obs import CostLedger, FlightRecorder
        self.ledger = CostLedger(stats=self.stats)
        self.flight = FlightRecorder(
            dump_dir=f"{holder.path}/_flight", stats=self.stats)
        from pilosa_tpu.exec.fused import FusedCache
        self.fused = FusedCache(stats=self.stats,
                                mesh_guard=placement is not None,
                                ledger=self.ledger, flight=self.flight)
        # tenancy (r17): the governor is always attached — with no
        # quotas and no telemetry its eviction ordering degrades to
        # the stamped LRU exactly, so the single-tenant default pays
        # nothing.  The pager is single-device only: a partial page
        # plane has no meaning under a mesh-sharded placement.
        self.governor = ResidencyGovernor(byte_quota=tenant_byte_quota)
        self.planes = PlaneCache(place, placement=placement,
                                 stats=self.stats,
                                 sidecars=plane_sidecars,
                                 delta_cells=delta_cells,
                                 delta_compact_fraction=(
                                     delta_compact_fraction),
                                 governor=self.governor,
                                 flight=self.flight, **kw)
        self.pager = (PlanePager(self.planes, self.governor,
                                 page_bytes=plane_page_bytes,
                                 stats=self.stats, flight=self.flight)
                      if plane_paging and placement is None else None)
        self.qos = TenantQos(tenant_qps_quota, tenant_slot_quota,
                             stats=self.stats,
                             device_seconds_quota=(
                                 tenant_device_seconds_quota),
                             ledger=self.ledger)
        self.tracer = tracer or GLOBAL_TRACER
        # compile-ladder warm-up (r24): single-device only — warmed
        # keys carry shard=None, which is exactly the serve-time
        # sharding_key of single-device operands; under a placement
        # the keys would never match, so the warmer stays off.
        self.warmer = None
        if fused_warmup and placement is None:
            from pilosa_tpu.exec.warmup import ProgramWarmer
            self.warmer = ProgramWarmer(self.fused, stats=self.stats,
                                        ledger=self.ledger,
                                        flight=self.flight)
            self.planes.warmer = self.warmer
        # whole-tree compilation (r16): compound boolean Counts gather
        # rows from the resident plane and fold a postfix program in
        # one fused XLA dispatch.  Off (`tree_fusion=False`) restores
        # the pre-r16 op-at-a-time/generic path — the bench baseline
        # and the escape hatch the runbook documents.
        self.tree_fusion = tree_fusion
        from pilosa_tpu.obs.metrics import DEPTH_BUCKETS
        self.stats.set_buckets("tree_fusion_depth", DEPTH_BUCKETS)
        # cross-request coalescing is the DEFAULT serving spine (r6):
        # the adaptive window costs a solo request nothing, and under
        # concurrency every dense family pays one dispatch + one read
        # per collection window instead of one per request
        self.batcher = None
        window = count_batch_window
        if isinstance(window, str):
            w = window.strip().lower()
            if w == "adaptive":
                window = "adaptive"
            elif w in ("", "0", "off", "none", "false"):
                window = 0.0
            else:
                try:
                    window = float(w)
                except ValueError:
                    raise ValueError(
                        f"count_batch_window: expected 'adaptive', a "
                        f"number of seconds, or 'off', got {window!r}")
        if window == "adaptive" or window > 0:
            from pilosa_tpu.exec.batcher import CountBatcher
            self.batcher = CountBatcher(
                self.fused, window_s=window, stats=self.stats,
                pipeline_depth=dispatch_pipeline_depth,
                solo_fastlane=solo_fastlane,
                watchdog_s=dispatch_watchdog_seconds,
                probe_after_s=device_health_probe_seconds,
                placement_key=(getattr(placement, "key", None)
                               if placement is not None else None),
                ledger=self.ledger, flight=self.flight)
        # mesh serving telemetry (ISSUE 16): how many chips the plane
        # axis spans (1 = single-device serving)
        self.stats.gauge(
            "mesh_devices",
            int(getattr(placement, "n_devices", 1)
                * getattr(placement, "words_size", 1))
            if placement is not None else 1)
        # query-plan cache (r6 tentpole): (index, normalized PQL,
        # shards, translate flag) -> planned tree + leaf specs, so a
        # repeated serving shape skips parse AND plan entirely (PQL
        # parse alone measured 1.09 ms/request ≈ 2.4× the device budget
        # at 5k qps, BENCH_r05)
        self._plans: OrderedDict = OrderedDict()
        self._plans_lock = threading.Lock()
        # index name -> (its kept shard tuple, that tuple as served:
        # (0,) when empty, padded under a placement) — see _shards_for
        self._served_shards: dict[str, tuple] = {}
        # cross-query OOM recovery (r4 → r5): one recovery at a time
        # through the gate; the in-flight count lets the exclusive
        # stage drain concurrent queries instead of evicting the
        # planes under them
        self._oom_gate = threading.Lock()
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._tls = threading.local()
        # closed (cleared) only while a stage-2 recovery drains to
        # exclusivity: new arrivals park here instead of entering the
        # in-flight count and starving the drain forever
        self._recovery_open = threading.Event()
        self._recovery_open.set()
        self._exec_slots = (threading.BoundedSemaphore(max_concurrent)
                            if max_concurrent else None)
        self.max_concurrent = max_concurrent
        self.slot_timeout_s = self.SLOT_TIMEOUT_S

    @property
    def slots_in_use(self) -> int:
        """Admitted top-level queries currently executing (the
        /metrics ``query_slots_in_use`` gauge)."""
        return self._inflight

    def _query_deadline(self) -> float | None:
        """The serving thread's current query deadline (set by the
        outermost :meth:`execute`) — what every batcher submit
        carries so pipeline waits stay bounded (r18)."""
        return getattr(self._tls, "deadline", None)

    # -- serving-path attribution (r19 satellite) ----------------------------

    def _admission_path(self) -> str:
        """The serving path this query starts on: the fused pipeline,
        the op-at-a-time fallback (no batcher), or degraded-governor
        per-item serving.  Down-stack sites refine it (paged /
        row-directory oracle)."""
        if self.batcher is None:
            return "op-at-a-time fallback"
        if self.batcher.governor.state != "healthy":
            return "degraded governor"
        return "fused"

    def _note_path(self, path: str) -> None:
        self._tls.spath = path

    def serving_path(self) -> str:
        """Which path answered the serving thread's LAST query —
        ``fused`` / ``plan-cached per-row`` (a cached plane or tree
        plan answered by its per-row form: the whole-field plane is
        not resident, by the selectivity rule) / ``generic per-row``
        (a cached plan fell through to the un-cached path, which had
        an admission decision to make) / ``op-at-a-time fallback`` /
        ``paged`` / ``row-directory oracle`` / ``degraded governor``.
        Read by the slow-query log so every slow entry names its
        path."""
        return getattr(self._tls, "spath", "fused")

    def device_health(self) -> dict:
        """The ``/status`` deviceHealth block: the batcher's governor
        state, watchdog knob and quarantine counts (a batcher-less
        executor is trivially healthy — there is no shared pipeline
        to stall)."""
        warm = (self.warmer.payload() if self.warmer is not None
                else {"enabled": False, "shapesWarmed": 0,
                      "programsWarmed": 0, "compileSeconds": 0.0,
                      "pending": 0})
        if self.batcher is None:
            return {"state": "healthy", "stateCode": 0,
                    "watchdogSeconds": 0.0, "quarantinedWindows": 0,
                    "inflightWindows": 0, "consecutiveFaults": 0,
                    "watchdogTrips": 0,
                    "warmup": warm}
        payload = self.batcher.health_payload()
        payload["warmup"] = warm
        return payload

    def mesh_status(self) -> dict | None:
        """The ``/status`` ``mesh`` block (ISSUE 16): device count,
        shard axis, per-device resident plane bytes and padded-shard
        count, and the launch lock's wait (``launchWait``: every
        meshed launch's time from the call to ``_MESH_LAUNCH_LOCK``'s
        acquisition) — None when serving single-device."""
        block = self.planes.mesh_stats()
        if block is not None:
            block["launchWait"] = self.stats.histogram_summary(
                "mesh_launch_wait_seconds").get(
                    "total", {"count": 0, "sum": 0.0, "mean": 0.0})
        return block

    def time_status(self) -> dict:
        """The ``/status`` ``timeViews`` block (r23): resident
        bucketed time planes (index/field/bucket/byte geometry, delta
        overlay state) — which time fields answer range queries at
        device speed versus the span-union fallback."""
        planes = self.planes.time_plane_status()
        return {"planes": planes,
                "residentBytes": sum(p["bytes"] for p in planes),
                "buckets": sum(p["buckets"] for p in planes)}

    def cost_status(self) -> dict:
        """The ``/status`` ``costs`` block (r19): the device-cost
        ledger's rollups — measured device seconds and bytes scanned
        attributed per tenant, per query shape, and per plane (top-K
        with an ``other`` fold), plus compile totals."""
        return self.ledger.payload()

    def tenancy_status(self) -> dict:
        """The ``/status`` ``tenancy`` block (r17): knobs, per-tenant
        residency/hit-ratio/page-in/shed counts, QoS state, eviction
        reasons.  Refreshes the ``plane_resident_pages`` gauge at
        scrape time (pager payload)."""
        planes = self.planes
        out = {"paging": self.pager is not None,
               "tenantByteQuota": self.governor.byte_quota,
               "evictions": planes.evictions,
               "evictionsByReason": dict(planes._evictions_by_reason),
               "qos": self.qos.payload()}
        if self.pager is not None:
            pg = self.pager.payload()
            tenants = pg.pop("tenants")
            out.update(pg)
        else:
            tenants = {}
            with planes._lock:
                for k, v in planes._entries.items():
                    d = tenants.setdefault(
                        k[1], {"residentBytes": 0, "residentPages": 0,
                               "residentEntries": 0})
                    d["residentBytes"] += v[2]
                    d["residentEntries"] += 1
        sheds = out["qos"]["sheds"]
        for t, n in sheds.items():
            tenants.setdefault(
                t, {"residentBytes": 0, "residentPages": 0,
                    "residentEntries": 0})
        for t, d in tenants.items():
            d["sheds"] = sheds.get(t, 0)
        out["tenants"] = tenants
        return out

    # -- in-flight accounting (OOM recovery) --------------------------------

    def _enter_inflight(self) -> None:
        with self._inflight_cv:
            self._inflight += 1

    def _leave_inflight(self) -> None:
        with self._inflight_cv:
            self._inflight -= 1
            self._inflight_cv.notify_all()

    def _drain_to_exclusive(self, timeout: float = 120.0) -> bool:
        """Wait until this query is the only one in flight (other
        queries finish or park at the OOM gate).  Bounded: a hung peer
        must not pin recovery forever — on timeout the retry proceeds
        anyway and may still fail, which is then an honest answer."""
        with self._inflight_cv:
            end = time.monotonic() + timeout
            while self._inflight > 1:
                remaining = end - time.monotonic()
                if remaining <= 0:
                    return False
                self._inflight_cv.wait(remaining)
        return True

    # ------------------------------------------------------------------ api

    def execute(self, index_name: str, query: str | Query,
                shards: list[int] | None = None,
                translate_output: bool = True, tracer=None,
                deadline: float | None = None) -> list:
        """Run every top-level call; returns one result per call
        (reference: ``Executor.Execute`` → ``QueryResponse.Results``).

        ``translate_output=False`` leaves raw IDs in results — used by
        the cluster layer, which merges partials from many nodes first
        and key-translates once at the edge.  ``tracer`` overrides the
        shared tracer (the ``profile=true`` path uses a per-request one
        so concurrent queries' spans don't interleave).  ``deadline``
        (``time.monotonic()`` cutoff) aborts with
        :class:`QueryTimeoutError` at call/block boundaries."""
        index = self.holder.index(index_name)
        if index is None:
            raise ExecutionError(f"index {index_name!r} not found")
        # outermost call only (nested execute — e.g. resolved Limit
        # subtrees — shares the outer query's lease set and in-flight
        # slot): register for OOM-recovery coordination
        depth = getattr(self._tls, "depth", 0)
        own_timer = False
        qos_held = False
        if depth == 0:
            # the stage clock: the HTTP edge's when the request came in
            # over it, else this call's own.  Stages double as
            # `stage.*` child spans on the traced query (per-request
            # tracer when given, else the shared one)
            timer = current_timer()
            own_timer = timer is None
            if own_timer:
                timer = StageTimer(self.stats, "admit",
                                   tracer=tracer or self.tracer)
            else:
                timer.attach(tracer or self.tracer)
                timer.enter("admit")
            # per-tenant QoS FIRST (r17 tenancy): an over-quota tenant
            # sheds with a structured 503 BEFORE taking an executor
            # slot, so its retries queue at the client — never in
            # front of in-quota tenants' admissions
            if self.qos.enabled:
                self.qos.admit(index_name)  # raises TenantThrottledError
                qos_held = True
            # bounded concurrency FIRST: each executing query holds
            # live device scratch (program temps, per-query outputs);
            # with residency near budget, unbounded client threads
            # multiply scratch past HBM headroom (32 streams OOM'd
            # every thread at 8.5 GB resident, r5).  Queries
            # queue here — the chip serializes execution anyway, so a
            # bounded pool costs no throughput.  Timed: a wedged
            # recovery holding every slot must not refuse service
            # silently forever
            if self._exec_slots is not None:
                t_wait = time.perf_counter()
                acquired = self._exec_slots.acquire(
                    timeout=self.slot_timeout_s)
                self.stats.observe("query_queue_wait_seconds",
                                   time.perf_counter() - t_wait)
                if not acquired:
                    self.stats.count("query_shed_total", 1)
                    if qos_held:
                        self.qos.release(index_name)
                    raise ExecutorSaturatedError(
                        f"executor at max concurrent queries "
                        f"({self.max_concurrent}) for "
                        f"{self.slot_timeout_s:.0f}s; retry later",
                        retry_after=1.0)
            # slot held: from here, ANY setup failure must release it —
            # a leaked slot is permanent, and max_concurrent leaks turn
            # into a total outage behind the 180s-timeout error
            # (ADVICE r5, the admission-slot leak)
            try:
                # park while a stage-2 OOM recovery drains to
                # exclusivity — without this, steady arrivals keep the
                # in-flight count above 1 and the drain can never
                # finish.  AFTER the slot: a thread that waited out a
                # long acquire must still honor a recovery that started
                # meanwhile.  Bounded: a wedged recovery must not
                # refuse service forever
                self._recovery_open.wait(timeout=180.0)
                self._enter_inflight()
                try:
                    self.planes.begin_query()
                except BaseException:
                    self._leave_inflight()
                    raise
            except BaseException:
                if self._exec_slots is not None:
                    self._exec_slots.release()
                if qos_held:
                    self.qos.release(index_name)
                raise
            if own_timer:
                set_current_timer(timer)
            # deadline propagation (r18): remember this query's cutoff
            # on the serving thread so every batcher submit down-stack
            # carries it — wait() then blocks with a BOUNDED timeout
            # instead of forever behind a sick device
            self._tls.deadline = deadline
            # cost-ledger attribution context (r19): tenant + trace on
            # the serving thread — batcher items and fast-lane solo
            # dispatches stamp their charges from this, and the plane
            # cache fills in the plane as the query touches it
            set_query_context(index_name, trace_id=current_trace_id())
            # serving-path tag (r19 satellite): which path answered —
            # refined down-stack (paged / oracle / op-at-a-time), read
            # by the slow-query log after execute returns
            self._tls.spath = self._admission_path()
        self._tls.depth = depth + 1
        try:
            if depth == 0 and fault.ACTIVE:
                # post-admission failpoint: `delay` holds a slot open
                # (how saturation tests wedge the executor), `error`
                # fails the query after admission.  Inside the main
                # try: a raise here must still release the slot.
                fault.fire("exec.execute", index=index_name)
            if isinstance(query, str):
                if depth == 0:
                    # plan-cache fast path: a repeated all-Count serving
                    # shape skips parse AND plan (r6 tentpole)
                    _stage("plan_cache")
                    out = self._execute_planned(
                        index, index_name, query, shards, translate_output,
                        tracer, deadline)
                    if out is not None:
                        return out
                    _stage("parse")
                # memoized: repeated serving shapes skip the parser (the
                # AST is never mutated in place — rewriters copy first)
                query = parse_cached(query)
            return self._execute_calls(index, index_name, query, shards,
                                       translate_output, tracer, deadline)
        finally:
            self._tls.depth = depth
            if depth == 0:
                if own_timer:
                    set_current_timer(None)
                    timer.finish()
                self._tls.deadline = None
                # ledger context clears here; the serving-path tag
                # survives until the NEXT admission on this thread —
                # the API layer reads it after execute returns
                clear_query_context()
                self.planes.end_query()
                self._leave_inflight()
                if self._exec_slots is not None:
                    self._exec_slots.release()
                if qos_held:
                    self.qos.release(index_name)

    def _execute_calls(self, index, index_name: str, query: Query,
                       shards, translate_output: bool, tracer,
                       deadline: float | None) -> list:
        """One result per call, in call order.  Calls of one *family*
        (:func:`_call_family`) execute together as ONE program with
        one result read.  In a request made only of reads a call joins
        its family's group wherever it stands — reads have no side
        effects, so their order of execution is free; in any other
        request only calls that directly follow one another group, so
        a write between two reads stays between them."""
        calls = query.calls
        if len(calls) > 1 and all(_is_read(c) for c in calls):
            try:
                return self._run_calls(index, index_name, calls, shards,
                                       translate_output, tracer, deadline,
                                       anywhere=True)
            except ExecutionError as e:
                if isinstance(e, (QueryTimeoutError, PipelineStalledError,
                                  ExecutorSaturatedError)):
                    raise
                # a call failed, maybe not the first that would have
                # in call order: run the request again in that order
                # (safe: nothing was written), so that the error
                # reported is the earliest failing call's
        return self._run_calls(index, index_name, calls, shards,
                               translate_output, tracer, deadline,
                               anywhere=False)

    def _run_calls(self, index, index_name: str, calls: list[Call],
                   shards, translate_output: bool, tracer,
                   deadline: float | None, anywhere: bool) -> list:
        tracer = tracer or self.tracer
        # groups in order of their first call: (family, call indexes)
        groups: list[tuple] = []
        open_group: dict[tuple, list[int]] = {}
        for i, call in enumerate(calls):
            family = _call_family(call)
            idxs = open_group.get(family)
            if idxs is not None and (anywhere or idxs[-1] == i - 1):
                idxs.append(i)
                continue
            groups.append((family, [i]))
            if family is not None:
                open_group[family] = groups[-1][1]
        results: list = [None] * len(calls)
        # spans per call + per-call-type latency counters (reference:
        # executor span/stats emission, SURVEY.md §3.3 / §6); a group
        # is one span, one ``query_seconds`` observation and one pass
        # through the stage clock's plan … assemble
        for family, idxs in groups:
            if len(idxs) > 1:
                _stage("plan")
                ctx = _Ctx(index, self._shards_for(index, shards, None),
                           translate_output, deadline=deadline)
                ctx.check_deadline()
                kind = family[0]
                name = _FAMILY_SPAN[kind]
                run = (self._count_batch if kind == "count"
                       else self._agg_batch)
                members = [calls[i] for i in idxs]
                with tracer.span("executor." + name, index=index_name,
                                 calls=len(idxs), shards=len(ctx.shards)):
                    t0 = time.perf_counter()
                    batched = self._with_oom_retry(
                        lambda: run(ctx, members))
                    self.stats.timing("query_seconds",
                                      time.perf_counter() - t0, call=name)
                if batched is not None:
                    self.stats.count("request_call_groups_total", 1,
                                     family=kind)
                    self.stats.count("request_grouped_calls_total",
                                     len(idxs), family=kind)
                    for i, result in zip(idxs, batched):
                        results[i] = result
                    continue
            # a call alone, or a Count group that is no fusable batch
            for i in idxs:
                _stage("plan")
                call = calls[i]
                ctx = _Ctx(index, self._shards_for(index, shards, call),
                           translate_output, deadline=deadline)
                ctx.check_deadline()
                with tracer.span("executor." + call.name,
                                 index=index_name,
                                 shards=len(ctx.shards)):
                    t0 = time.perf_counter()
                    results[i] = self._with_oom_retry(
                        lambda: self._call(ctx, call))
                    self.stats.timing("query_seconds",
                                      time.perf_counter() - t0,
                                      call=call.name)
        return results

    def _count_batch(self, ctx: _Ctx, calls: list[Call]) -> list[int] | None:
        """Plan every Count child, concatenate leaf lists, run one
        program -> int32[K, S], host-finish each row.  Returns None if
        any child is unfusable (caller falls back to per-call)."""
        fast = self._count_batch_plane(ctx, calls)
        if fast is not None:
            return fast
        fast = self._count_batch_bsi(ctx, calls)
        if fast is not None:
            return fast
        fast = self._count_batch_tree(ctx, calls)
        if fast is not None:
            return fast
        from pilosa_tpu.exec.fused import Unfusable, shift_leaves
        nodes, all_leaves = [], []
        try:
            for call in calls:
                leaves: list = []
                node = self._plan(ctx, call.children[0], leaves)
                nodes.append(shift_leaves(node, len(all_leaves)))
                all_leaves.extend(leaves)
        except Unfusable:
            return None
        return self._dispatch_count_run(tuple(nodes), tuple(all_leaves))

    def _dispatch_count_run(self, nodes: tuple, leaves: tuple) -> list[int]:
        """One request's planned Count run → per-call totals (the one
        dispatch tail shared by the plan-cached and freshly-planned
        paths).  With the batcher, the whole request is ONE batch item:
        concurrent requests share a dispatch + read.  Every batcher
        submit books queue / dispatch / read / deliver on the caller's
        stage clock and returns in ``assemble``."""
        if self.batcher is not None:
            return self.batcher.submit_many(
                nodes, leaves, deadline=self._query_deadline())
        _stage("dispatch")
        per_shard = self.fused.run_count_batch(nodes, leaves)
        _stage("read")
        host = np.asarray(per_shard)  # one read
        _stage("assemble")
        return [int(row.sum()) for row in host.astype(np.int64)]

    def _count_batch_plane(self, ctx: _Ctx, calls: list[Call]) \
            -> list[int] | None:
        """Same-field plain-row Count batches execute as ONE whole-plane
        popcount program (``kernels.row_counts`` over the resident
        ``uint32[S, R, W]`` field plane) — one input array, one fused
        reduce, one read.  The generic batch builds K separate per-row
        leaf arrays and K reduce kernels, which measured ~4× slower at
        the 1B-col serving condition (BASELINE.md r3).  Returns None
        when the batch doesn't match (mixed fields, conditions, time
        ranges, over-budget plane, a tiny slice of a huge row set —
        whole-plane counting would waste bandwidth there — or rows that
        are resident one by one while the plane is not).  A plane
        past the HBM budget (or its tenant's byte quota) no longer
        dead-ends: it reroutes to the PAGED residency path (r17) —
        resident shard pages answer on device, the host oracle covers
        the rest, bit-exact."""
        hit = self._plain_row_parse(ctx, calls)
        if hit is None:
            return None
        field, values = hit
        if self.planes.has_code(ctx.index.name, field, ctx.shards):
            return None  # rows derived from the code, one by one
        row_ids = [self._row_id(ctx, field, v, create=False)
                   for v in values]
        if not self.planes.has_plane(ctx.index.name, field, VIEW_STANDARD,
                                     ctx.shards):
            # the rows asked for are on the device one by one already
            # (filters of this request's aggregates, earlier Counts):
            # a whole plane built beside them would hold the same
            # words twice, and the generic batch counts the resident
            # rows in one program and one read all the same
            live = [r for r in row_ids if r is not None]
            if live and self.planes.has_rows(ctx.index.name, field,
                                             VIEW_STANDARD, live,
                                             ctx.shards):
                return None
            # admission decision only when the plane isn't resident yet:
            # plane_bytes walks every fragment's row set — O(shards)
            # host work that must stay OFF the per-request path (it
            # capped serving at ~1.1k qps on the 954-shard bench)
            est = self.planes.plane_bytes(field, VIEW_STANDARD,
                                          ctx.shards)
            if self._paging_engaged(est):
                return self._paged_count(ctx, field, values)
            if est > self.planes.budget:
                return None
            if self._tiny_slice(est, len(ctx.shards), len(calls)):
                return None
        # nowait: while the whole-field plane builds in the background
        # the generic per-row path serves (bounded per-row transfers)
        # instead of this batch stalling on full residency
        ps = self.planes.field_plane_nowait(ctx.index.name, field,
                                            VIEW_STANDARD, ctx.shards)
        if ps is None:
            return None
        return self._plane_count_rows(ps, row_ids)

    @staticmethod
    def _tiny_slice(est: int, n_shards: int, n_rows: int) -> bool:
        """The selectivity rule: a request touching ``n_rows`` rows of
        a field whose whole plane is estimated at ``est`` bytes reads
        a tiny slice of a huge row set — whole-plane residency would
        waste bandwidth and HBM, so per-row entries are the resident
        form (the plane, tree and plan-cache paths all ask here)."""
        r_est = max(1, est // (n_shards * WORDS_PER_SHARD * 4))
        return max(1, n_rows) * 4 < r_est

    def _plain_row_parse(self, ctx: _Ctx, calls: list[Call]):
        """``(field, values)`` when every call is ``Count(Row(f=v))``
        over ONE non-BSI field with plain scalar rows (no conditions,
        no time ranges) — the shape both the whole-plane batch and the
        paged path serve.  None otherwise."""
        fname = None
        values = []
        for call in calls:
            child = call.children[0]
            if child.name != "Row" or child.children:
                return None
            hit = _field_arg(child)
            if hit is None:
                return None
            f, v = hit
            if isinstance(v, (Condition, Call)):
                return None
            if ("from" in child.args or "to" in child.args
                    or "_timestamp" in child.args):
                return None
            if fname is None:
                fname = f
            elif f != fname:
                return None
            values.append(v)
        if fname is None:
            return None
        field = self._field(ctx, fname)
        if field.options.type in BSI_TYPES:
            return None
        if not ctx.shards:  # shards=[]: generic path answers zeros
            return None
        return field, values

    # ------------------------------------------------ paged residency (r17)

    def _paging_engaged(self, est: int) -> bool:
        """Whether a plane of ``est`` bytes serves PAGED: a pager
        exists (single-device serving) and the plane exceeds the HBM
        budget or its tenant's byte quota.  Under both limits the
        whole-plane path keeps its exact pre-r17 behavior."""
        if self.pager is None:
            return False
        limit = self.planes.budget
        if self.governor.byte_quota > 0:
            limit = min(limit, self.governor.byte_quota)
        return est > limit

    def _count_batch_paged(self, ctx: _Ctx,
                           calls: list[Call]) -> list[int] | None:
        """Solo-path entry to paged counting: engages only for the
        plain-Row shape on a plane past the budget/quota limit —
        everything else falls through to the existing paths."""
        if self.pager is None or not ctx.shards:
            return None
        hit = self._plain_row_parse(ctx, calls)
        if hit is None:
            return None
        field, values = hit
        if self.planes.has_plane(ctx.index.name, field, VIEW_STANDARD,
                                 ctx.shards) or self.planes.has_code(
                ctx.index.name, field, ctx.shards):
            return None  # whole plane resident, or a coded field
        est = self.planes.plane_bytes(field, VIEW_STANDARD, ctx.shards)
        if not self._paging_engaged(est):
            return None
        return self._paged_count(ctx, field, values)

    def _paged_count(self, ctx: _Ctx, field: Field,
                     values: list) -> list[int] | None:
        """Per-call totals for an over-limit plane via paged residency:
        each shard page is either RESIDENT (answered on device — the
        same selected-gather/whole-plane kernels, delta overlays and
        all), PAGED IN on demand (sidecar-warm partial expansion,
        admitted against the tenant's byte quota), or covered by the
        host ORACLE (``row_cardinalities`` directory sums).  Totals sum
        per row across pages — bit-exact regardless of the residency
        mix.  None = the shard axis doesn't split (single page)."""
        pages = self.pager.partition(field, VIEW_STANDARD, ctx.shards)
        if pages is None:
            return None
        self._note_path("paged")
        row_ids = [self._row_id(ctx, field, v, create=False)
                   for v in values]
        totals = [0] * len(row_ids)
        for page_shards in pages:
            _stage("plan")  # residency / page-in of the next page
            ps = self.pager.resident_page(ctx.index.name, field,
                                          VIEW_STANDARD, page_shards)
            if ps is None:
                ps = self.pager.page_in(ctx.index.name, field,
                                        VIEW_STANDARD, page_shards)
            if ps is not None:
                part = self._plane_count_rows(ps, row_ids)
            else:
                # quota denied the page-in: host truth answers this
                # page exactly (directory sums, no bit expansion)
                self._note_path("row-directory oracle")
                part = self.pager.oracle_counts(
                    field, VIEW_STANDARD, page_shards, row_ids)
            for i, v in enumerate(part):
                totals[i] += int(v)
        _stage("assemble")
        return totals

    # -------------------------------------------------- BSI range (r20)

    def _bsirange_item(self, ctx: _Ctx, child: Call):
        """Lower ``Count(Row(field op p))`` / the between forms to a
        batcher ``bsirange`` item: ``(field, op_keys, offsets)``.
        None = not a simple BSI range count (compound children, time
        args, non-BSI field, or a saturated predicate whose trivial
        answer the generic path lowers without a kernel)."""
        if child.name not in ("Row", "Range") or child.children:
            return None
        hit = _field_arg(child)
        if hit is None:
            return None
        fname, value = hit
        field = ctx.index.field(str(fname))
        if field is None or field.options.type not in BSI_TYPES:
            return None
        if ("from" in child.args or "to" in child.args
                or "_timestamp" in child.args):
            return None
        cond = (value if isinstance(value, Condition)
                else Condition("==", value))
        if isinstance(cond.value, Call) or (
                cond.op not in _SCALAR_TO_KEY
                and cond.op not in BETWEEN_OPS):
            return None
        opts = field.options
        depth = opts.bit_depth
        bound = (1 << depth) - 1
        if cond.op in BETWEEN_OPS:
            lo_op, hi_op = between_cmp_ops(cond.op)
            pairs = [(lo_op, cond.value[0]), (hi_op, cond.value[1])]
        else:
            pairs = [(_SCALAR_TO_KEY[cond.op], cond.value)]
        op_keys, offsets = [], []
        for op_key, v in pairs:
            offset = field.to_stored(v) - opts.base
            if offset > bound or offset < -bound:
                return None  # saturated: trivial, no kernel needed
            op_keys.append(op_key)
            offsets.append(int(offset))
        return field, tuple(op_keys), tuple(offsets)

    def _bsirange_operands(self, field: Field, offsets: tuple) -> tuple:
        depth = field.options.bit_depth
        ops = []
        for offset in offsets:
            ops.append(jnp.asarray(bsik.predicate_masks(abs(offset),
                                                        depth)))
            ops.append(jnp.asarray(offset < 0))
        return tuple(ops)

    def _count_batch_bsi(self, ctx: _Ctx,
                         calls: list[Call]) -> list[int] | None:
        """A request of simple BSI range Counts through the batcher's
        ``bsirange`` family (r20): every call enqueues into ONE
        collection window, same-plane items across concurrent requests
        co-batch into one fused program (identical predicates dedupe),
        and the plane arrives DELTA-AWARE (``bsi_plane_delta``) — no
        fold, no rebuild under sustained ingest.  None = some call
        isn't this shape (fall through to tree/generic)."""
        if self.batcher is None or not ctx.shards:
            return None
        if len(ctx.shards) > self._REDUCE_SHARD_MAX:
            return None  # device int32 shard reduce must stay exact
        items = []
        for call in calls:
            it = self._bsirange_item(ctx, call.children[0])
            if it is None:
                return None
            field, op_keys, offsets = it
            items.append((field, op_keys, offsets,
                          self._bsirange_operands(field, offsets)))
        return self._run_bsirange_items(ctx, items)

    def _run_bsirange_items(self, ctx: _Ctx, items: list) -> list[int]:
        """Dispatch resolved bsirange items — ``(field, op_keys,
        offsets, operands)`` per Count — through the batcher: the one
        place that builds the batcher's spec/sig tuples and decides
        solo (blocking submit → fast lane) vs windowed (enqueue ALL
        before waiting on any).  Planes resolve up front, so a
        failing resolution can never abandon already-enqueued
        neighbors in the window."""
        _stage("plan")
        deadline = self._query_deadline()
        planes: dict[str, object] = {}
        for field, _ops, _offs, _operands in items:
            if field.name not in planes:
                planes[field.name] = self.planes.bsi_plane_delta(
                    ctx.index.name, field, ctx.shards)
        if len(items) == 1:
            field, op_keys, offsets, operands = items[0]
            ps = planes[field.name]
            out = [self.batcher.submit_bsirange(
                ps.plane, (op_keys, False), operands,
                (op_keys, offsets, 0), delta=ps.delta,
                deadline=deadline)]
        else:
            handles = []
            for field, op_keys, offsets, operands in items:
                ps = planes[field.name]
                handles.append(self.batcher.enqueue_bsirange(
                    ps.plane, (op_keys, False), operands,
                    (op_keys, offsets, 0), delta=ps.delta,
                    deadline=deadline))
            out = [self.batcher.wait(h) for h in handles]
        return out

    # -------------------------------------------------- whole-tree (r16)

    def _count_batch_tree(self, ctx: _Ctx,
                          calls: list[Call]) -> list[int] | None:
        """Compound Count runs through the whole-tree compiler (r16
        tentpole): every child lowers to a canonical
        :class:`exec.tree.TreeSpec` and the request's trees dispatch
        as batcher items sharing ONE collection window — one gather of
        the slot union per anchor plane, one packed readback joined
        with any concurrent requests' trees.  None = not a tree shape
        or not runnable right now (anchor plane not resident /
        admittable) — callers fall through to the generic fused path,
        which answers identically."""
        from pilosa_tpu.exec import tree as treemod
        from pilosa_tpu.exec.fused import Unfusable
        if not self.tree_fusion or not ctx.shards:
            return None
        if not any(c.children[0].name in treemod.TREE_CALLS
                   for c in calls):
            return None
        try:
            specs = [treemod.lower_count_tree(self, ctx, c.children[0])
                     for c in calls]
        except Unfusable:
            return None
        return self._run_tree_specs(ctx, specs)

    def _tree_stats(self, spec) -> None:
        self.stats.observe("tree_fusion_depth", float(spec.depth))
        if spec.cse_hits:
            self.stats.count("tree_cse_hits_total", spec.cse_hits)
        if spec.static_ops:
            self.stats.count("tree_static_ops_total", spec.static_ops)

    def _run_tree_specs(self, ctx: _Ctx, specs,
                        hit: "_PlanHit | None" = None) -> list[int] | None:
        """Materialize + dispatch lowered tree specs: row ids resolve
        to plane slots FRESH per hit (so plan-cached specs keep
        serving current truth), extras re-fetch through the plane
        cache, and a delta-dirty anchor plane answers base⊕delta
        inside the same program.  None = an anchor plane isn't
        resident/admittable or a field vanished — admission decisions
        stay on the un-cached path; ``hit`` (a plan-cache hit whose
        entry has a per-row form) learns whether it was the
        selectivity rule instead."""
        resolved = []
        for spec in specs:
            item = self._tree_item(ctx, spec, hit)
            if item is None:
                return None
            resolved.append(item)
        # runnable: what the plan cache spent until here was its own
        # (a cached plan's residency checks); from here it is planning
        _stage("plan")
        for spec in specs:
            self._tree_stats(spec)
        if self.batcher is not None:
            if len(resolved) == 1:
                # single tree: the blocking submit rides the solo fast
                # lane when traffic is solo (inline dispatch, no window)
                ps, item = resolved[0]
                out = [self.batcher.submit_tree(
                    ps.plane, *item, delta=ps.delta,
                    deadline=self._query_deadline())]
            else:
                # enqueue ALL trees before waiting on any: the whole
                # request lands in one collection window
                handles = [self.batcher.enqueue_tree(
                    ps.plane, *item, delta=ps.delta,
                    deadline=self._query_deadline())
                           for ps, item in resolved]
                out = [self.batcher.wait(h) for h in handles]
            return out
        # no batcher: one fused program per (plane, overlay) group
        from pilosa_tpu.exec.tree import assemble_items
        groups: dict[tuple, list[int]] = {}
        group_ps: dict[tuple, object] = {}
        for i, (ps, _item) in enumerate(resolved):
            k = (id(ps.plane),
                 id(ps.delta) if ps.delta is not None else 0)
            groups.setdefault(k, []).append(i)
            group_ps[k] = ps
        out = [0] * len(resolved)
        for k, idxs in groups.items():
            ps = group_ps[k]
            slots, progs, extras = assemble_items(
                [resolved[i][1] for i in idxs])
            _stage("dispatch")
            dev = self.fused.run_tree_counts(ps.plane, slots, progs,
                                             extras, delta=ps.delta)
            _stage("read")
            vals = np.asarray(dev)
            _stage("assemble")
            for j, i in enumerate(idxs):
                out[i] = int(vals[j])
        return out

    def _tree_item(self, ctx: _Ctx, spec, hit: "_PlanHit | None" = None):
        """One spec's runtime form: ``(PlaneSet, (slots, prog,
        extras))`` with PUSH args rewritten against the LIVE slot map
        (absent rows become zero pushes) and extra operands
        materialized.  None = not runnable on the device path right
        now (caller falls back / invalidates); ``hit.by_rule`` is set
        where the selectivity rule, not admission, is why."""
        from pilosa_tpu.engine.kernels import TREE_PUSH, TREE_ZERO
        field = ctx.index.field(spec.field)
        if field is None or field.options.type in BSI_TYPES:
            return None
        if len(ctx.shards) > self._REDUCE_SHARD_MAX:
            return None  # device int32 shard reduce must stay exact
        if self.planes.has_code(ctx.index.name, field, ctx.shards):
            return None  # a coded field's rows come one by one
        if not self.planes.has_plane(ctx.index.name, field,
                                     VIEW_STANDARD, ctx.shards):
            # admission mirrors _count_batch_plane: budget walk only
            # when the plane isn't resident, and skip whole-plane
            # residency for a tiny slice of a huge row set
            est = self.planes.plane_bytes(
                field, VIEW_STANDARD, ctx.shards,
                gens=self._hit_gens(ctx, hit, field, VIEW_STANDARD))
            if est > self.planes.budget:
                return None
            if self._tiny_slice(est, len(ctx.shards), len(spec.rows)):
                if hit is not None:
                    hit.by_rule = True
                return None
        ps = self.planes.field_plane_nowait(ctx.index.name, field,
                                            VIEW_STANDARD, ctx.shards)
        if ps is None:
            return None
        slots: list[int] = []
        slot_arg: list[int | None] = []
        for s in ps.slots_for(spec.rows):
            if s is None:
                slot_arg.append(None)
            else:
                slot_arg.append(len(slots))
                slots.append(s)
        extras = []
        for espec in spec.extras:
            arr = self._tree_extra(ctx, espec)
            if arr is None:
                return None
            extras.append(arr)
        prog: list[tuple] = []
        for op, arg in spec.prog:
            if op == TREE_PUSH:
                new = slot_arg[arg]
                if new is None:  # row has no bits anywhere → empty
                    prog.append((TREE_ZERO, 0))
                    continue
                arg = new
            prog.append((op, arg))
        return ps, (tuple(slots), tuple(prog), tuple(extras))

    def _tree_extra(self, ctx: _Ctx, spec) -> "jax.Array | None":
        """Materialize one extra tree operand (uint32[S, W]): the
        existence row, another set field's row, or a BSI predicate
        bitmap (masks re-derive from the spec's baked offset and the
        CURRENT bit depth — the plan validity rules pin the depth)."""
        kind = spec[0]
        if kind == "exists":
            return self._exists(ctx)
        if kind == "row":
            _, fname, vname, rid = spec
            field = ctx.index.field(fname)
            if field is None or field.options.type in BSI_TYPES:
                return None
            return self.planes.row_words(ctx.index.name, field, vname,
                                         rid, ctx.shards)
        if kind == "trange":
            # time-range leaf inside a compound tree (r23): the words
            # come from the fused bucket-range scan when the time plane
            # resides, else the span oracle — the TREE stays fused
            # either way (this is one extra operand)
            _, fname, rid, frm, to = spec
            field = ctx.index.field(fname)
            if field is None or not field.options.time_quantum:
                return None
            start = parse_pql_time(frm) if frm is not None else None
            end = parse_pql_time(to) if to is not None else None
            words = self._time_range_words(ctx, field, rid, start, end)
            if words is None:
                words = self._time_row_span(ctx, field, rid, start, end)
            return words
        if kind == "constrow":
            return self._const_row_cols(ctx, spec[1])
        fname = spec[1]
        field = ctx.index.field(fname)
        if field is None or field.options.type not in BSI_TYPES:
            return None
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        if kind == "bsi-exists":
            return ps.plane[..., bsik.EXISTS_ROW, :]
        _, _, op_key, offset = spec
        masks = jnp.asarray(bsik.predicate_masks(
            abs(offset), field.options.bit_depth))
        # one cached predicate program per op_key; masks/sign are
        # traced, so any offset of the same comparison reuses it
        return self.fused.run(("bsi", 0, 1, 2, op_key),
                              (ps.plane, masks, jnp.asarray(offset < 0)),
                              "words")

    # int32 cross-shard reduce stays exact while n_shards·2^20 < 2^31
    _REDUCE_SHARD_MAX = (1 << 31) // SHARD_WIDTH - 1

    # selected-row gather beats the whole-plane scan when the request
    # touches at most this fraction of the (padded) row axis: the
    # gather's memory traffic is n_sel/R_pad of the plane, but it
    # cannot dedupe as aggressively as identical whole-plane items
    # (which collapse to ONE scan per window), so the cutover is
    # conservative
    _SELECTED_ROWS_FRACTION = 4  # use gather when n_sel * 4 <= R_pad

    def _plane_count_rows(self, ps, row_ids) -> list[int]:
        """Per-call totals for resolved ``row_ids`` (None = absent row
        -> 0) over a resident plane, choosing between the two
        multi-query fused kernels:

        - **selected-row gather** (r12): when the request touches a
          small fraction of a wide plane, one pass over just those
          rows' memory — N answers per gather, coalesced across
          concurrent requests by slot-union in the batcher;
        - **whole-plane row_counts**: otherwise — identical concurrent
          requests dedupe to ONE scan per window, the headline serving
          spine."""
        slots = [ps.slot_of.get(int(r)) if r is not None else None
                 for r in row_ids]
        live = list(dict.fromkeys(s for s in slots if s is not None))
        r_pad = ps.plane.shape[-2]
        if (live and len(ps.shards) <= self._REDUCE_SHARD_MAX
                and len(live) * self._SELECTED_ROWS_FRACTION <= r_pad):
            by_slot = self._plane_selected_totals(ps, tuple(live))
            return [int(by_slot[s]) if s is not None else 0
                    for s in slots]
        totals = self._plane_totals(ps)
        return [int(totals[s]) if s is not None else 0 for s in slots]

    def _plane_selected_totals(self, ps, slots: tuple) -> dict:
        """slot -> int64 total for the selected plane rows: one
        row-gather + popcount program, shard axis reduced on device
        (callers gate on ``_REDUCE_SHARD_MAX``), coalesced across
        concurrent requests via the batcher.  A delta-dirty plane
        (``ps.delta``, r15 ingest) answers base⊕delta in the same
        program — writes never force a rebuild here."""
        if self.batcher is not None:
            vals = self.batcher.submit_selected(
                ps.plane, slots, delta=ps.delta,
                deadline=self._query_deadline())
        else:
            _stage("dispatch")
            out = self.fused.run_selected_counts(ps.plane, slots,
                                                 delta=ps.delta)
            _stage("read")
            vals = np.asarray(out)[:len(slots)]
        _stage("assemble")
        return dict(zip(slots, (int(v) for v in vals)))

    def _plane_totals(self, ps) -> np.ndarray:
        """Whole-plane per-row totals int64[R_pad]: one program + one
        read, coalesced ACROSS concurrent requests via the batcher
        (identical planes dedupe to one computation per window).

        Cross-shard reduce on DEVICE when int32 stays exact
        (n_shards * 2^20 < 2^31): the read shrinks from int32[S, R] to
        int32[R] — on transports with per-read costs the smaller
        payload is the serving hot path.  Wider shard sets keep
        per-shard counts and finish in int64 on host (engine int32
        policy)."""
        small = len(ps.shards) <= self._REDUCE_SHARD_MAX
        delta = ps.delta
        if self.batcher is not None and small:
            return self.batcher.submit_rowcounts(
                ps.plane, delta=delta, deadline=self._query_deadline())
        _stage("dispatch")
        if small:
            if delta is not None:
                out = self.fused.run_rowcounts_delta(ps.plane, delta)
            else:
                key = (("countbatch-plane-reduced", ps.plane.shape),
                       "count")
                fn = self.fused._cached(
                    key, lambda: (lambda p: jnp.sum(
                        kernels.row_counts(p), axis=0, dtype=jnp.int32)))
                out = fn(ps.plane)
            _stage("read")
            totals = np.asarray(out)  # one read
        else:
            if delta is not None:
                out = self.fused.run_rowcounts_delta(ps.plane, delta,
                                                     reduce=False)
            else:
                key = (("countbatch-plane", ps.plane.shape), "count")
                fn = self.fused._cached(key, lambda: kernels.row_counts)
                out = fn(ps.plane)
            _stage("read")
            totals = np.asarray(out)
        _stage("assemble")
        totals = totals.astype(np.int64)
        return totals if small else totals.sum(axis=0)

    # ---------------------------------------------------------- plan cache

    def invalidate_plans(self, index: str | None = None) -> None:
        """Drop cached plans (all, or one index's) — schema deletions
        must not leave plans resolving against a recreated namesake."""
        with self._plans_lock:
            if index is None:
                self._plans.clear()
                return
            for key in [k for k in self._plans if k[0] == index]:
                del self._plans[key]

    def _execute_planned(self, index, index_name: str, query: str, shards,
                         translate_output: bool, tracer,
                         deadline: float | None) -> list | None:
        """Plan-cache fast path for all-Count queries (the dominant
        serving family).  Returns the results list, or None to fall
        through to the parse path: an unplannable shape, a stale
        entry, or a plane that isn't resident where the un-cached path
        has something to DO about it (admit and start the build, page,
        refuse on budget) — admission decisions stay there.  A plane
        that isn't resident because the selectivity rule keeps the
        field per-row is no fall-through: the entry's per-row form
        answers (:meth:`_run_plan_inner`)."""
        # strip() only — whitespace INSIDE the query can be inside a
        # quoted row key, where collapsing it would alias two distinct
        # queries onto one plan (wrong answers, not a perf bug)
        skey = (index_name, query.strip(),
                tuple(shards) if shards is not None else None,
                translate_output)
        with self._plans_lock:
            entry = self._plans.get(skey)
            if entry is not None:
                self._plans.move_to_end(skey)
        if entry is _UNPLANNABLE:
            return None
        if entry is None:
            self.stats.count("plan_cache_misses", 1)
            # build TWICE and require identical generation snapshots:
            # generations are monotonic, so equal snapshots bracket the
            # second walk — a write racing the build (e.g. creating a
            # row the first walk resolved as absent, THEN snapshotting
            # the post-write generations) cannot produce a stale plan
            # that validates as fresh.  Under hot writes we just don't
            # cache this request; the normal path serves it.
            first = self._build_plan(index, query, shards,
                                     translate_output)
            entry = None
            if first is not None:
                second = self._build_plan(index, query, shards,
                                          translate_output)
                if second is not None and second.gens == first.gens:
                    entry = second
            if first is not None and entry is None:
                return None  # racing writes: retry on the next request
            with self._plans_lock:
                self._plans[skey] = (entry if entry is not None
                                     else _UNPLANNABLE)
                while len(self._plans) > self.MAX_PLANS:
                    self._plans.popitem(last=False)
            if entry is None:
                return None
        else:
            self.stats.count("plan_cache_hits", 1)
        # validity: current shard set + dependency generations must
        # match what the plan was built against — a write to any source
        # fragment (or a shard appearing) invalidates here, and the
        # normal path re-plans on the next request.  Unkeyed-plane
        # entries skip the generation compare (nothing in them can
        # stale; the PlaneSet revalidates independently) so the plan
        # cache keeps hitting under sustained ingest.
        now = self._shards_for(index, shards, None)
        if ((now is not entry.shards and now != entry.shards)
                or (not entry.unkeyed_plane
                    and self._dep_gens(index, entry.deps,
                                       entry.shards) != entry.gens)
                or (entry.unkeyed_plane
                    # every baked field must still be the unkeyed set
                    # field the plan resolved literal row ids against
                    # — a drop + recreate as keyed/BSI at the same
                    # name would otherwise keep serving those literals
                    and any((pf := index.field(fn)) is None
                            or pf.options.keys
                            or pf.options.type in BSI_TYPES
                            for fn in entry.unkeyed_fields))
                or any((f := index.field(fname)) is None
                       or _bsi_signature(f.options) != sig
                       for fname, sig in entry.bsi_sigs)):
            self._drop_plan(skey, entry)
            return None
        out = self._run_plan(index, index_name, entry, translate_output,
                             tracer, deadline)
        if out is None:
            # a validated plan that served nothing (its plane is not
            # resident and admission has a say, or it has no per-row
            # form): the un-cached path plans this request again, and
            # what answers is its generic per-row program unless a
            # path down-stack says otherwise
            self.stats.count("plan_cache_fallthrough_total", 1)
            self._note_path("generic per-row")
        return out

    def _drop_plan(self, skey, entry) -> None:
        self.stats.count("plan_cache_invalidations", 1)
        with self._plans_lock:
            if self._plans.get(skey) is entry:
                del self._plans[skey]

    def _build_plan(self, index, query: str, shards,
                    translate_output: bool) -> "_PlanEntry | None":
        from pilosa_tpu.exec.fused import Unfusable
        try:
            query_ast = parse_cached(query)
        except Exception:  # noqa: BLE001 — errors surface on normal path
            return None
        calls = query_ast.calls
        if not calls or any(c.name != "Count" or len(c.children) != 1
                            for c in calls):
            return None
        ctx = _Ctx(index, self._shards_for(index, shards, None),
                   translate_output)
        try:
            entry = (self._plan_plane_entry(ctx, calls)
                     or self._plan_bsirange_entry(ctx, calls)
                     or self._plan_tree_entry(ctx, calls))
        except (Unfusable, ExecutionError):
            return None
        if entry is not None and entry.kind == "bsirange":
            return entry
        specs: list = []
        deps: dict[tuple, None] = dict.fromkeys(entry.deps) if entry else {}
        depths: dict[str, tuple] = dict(entry.bsi_sigs) if entry else {}
        try:
            nodes = tuple(self._plan_spec(ctx, call.children[0], specs,
                                          deps, depths)
                          for call in calls)
        except (Unfusable, ExecutionError):
            # no per-row form (time ranges, ConstRow, data-dependent
            # row sets): a plane / tree entry keeps its own form only.
            # Without one either, execution errors re-raise identically
            # on the normal path; a later schema change that would
            # make the query plannable is served (correctly) by the
            # normal path forever — a perf-only conservatism
            return entry
        if entry is None:
            entry = _PlanEntry("generic", ctx.shards, (), (), len(calls))
        elif entry.unkeyed_plane and not all(
                spec[0] in _REFETCHED_LEAVES for spec in specs):
            # the entry skips the per-hit generation compare: a per-row
            # form may ride on it only where every leaf re-fetches
            # through the plane cache (which revalidates each row's
            # generations itself) or is a pure function of the query
            # text under the pinned ``bsi_sigs``
            return entry
        # the per-row form, under the union of both forms' validity
        entry.nodes, entry.leaf_specs = nodes, tuple(specs)
        entry.deps = tuple(deps)
        entry.gens = self._dep_gens(index, entry.deps, ctx.shards)
        entry.bsi_sigs = tuple(depths.items())
        return entry

    def _plan_plane_entry(self, ctx: _Ctx, calls) -> "_PlanEntry | None":
        """Match the same-field plain-row batch shape that
        :meth:`_count_batch_plane` serves with ONE whole-plane program
        (the BENCH headline family)."""
        fname = None
        values = []
        for call in calls:
            child = call.children[0]
            if child.name != "Row" or child.children:
                return None
            hit = _field_arg(child)
            if hit is None:
                return None
            f, v = hit
            if isinstance(v, (Condition, Call)):
                return None
            if ("from" in child.args or "to" in child.args
                    or "_timestamp" in child.args):
                return None
            if fname is None:
                fname = f
            elif f != fname:
                return None
            values.append(v)
        if fname is None or not ctx.shards:
            return None
        field = ctx.index.field(str(fname))
        if field is None or field.options.type in BSI_TYPES:
            return None
        row_ids = tuple(
            int(r) if (r := self._row_id(ctx, field, v,
                                         create=False)) is not None else None
            for v in values)
        deps = ((field.name, VIEW_STANDARD),)
        return _PlanEntry("plane", ctx.shards, deps,
                          self._dep_gens(ctx.index, deps, ctx.shards),
                          len(calls), field_name=field.name,
                          row_ids=row_ids,
                          unkeyed_plane=not field.options.keys,
                          unkeyed_fields=(field.name,))

    def _plan_bsirange_entry(self, ctx: _Ctx,
                             calls) -> "_PlanEntry | None":
        """Match an all-BSI-range-count request (r20): the entry bakes
        only (field, op keys, offsets) — the plane arrives delta-aware
        per hit and the predicate masks re-derive from the pinned
        depth, so the entry SURVIVES sustained ingest (no per-hit
        generation compare; ``bsi_sigs`` re-verifies depth/base)."""
        if self.batcher is None or not ctx.shards:
            return None
        if len(ctx.shards) > self._REDUCE_SHARD_MAX:
            return None
        items = []
        sigs: dict[str, tuple] = {}
        deps: dict[tuple, None] = {}
        for call in calls:
            it = self._bsirange_item(ctx, call.children[0])
            if it is None:
                return None
            field, op_keys, offsets = it
            # operands baked DEVICE-resident (like the generic plan's
            # const leaves): masks depend only on offset and the
            # depth the bsi_sigs check pins, so a cache hit re-binds
            # zero operands
            items.append((field.name, op_keys, offsets,
                          self._bsirange_operands(field, offsets)))
            sigs[field.name] = _bsi_signature(field.options)
            deps[(field.name, field.bsi_view_name)] = None
        deps = tuple(deps)
        return _PlanEntry("bsirange", ctx.shards, deps,
                          self._dep_gens(ctx.index, deps, ctx.shards),
                          len(calls), range_items=tuple(items),
                          bsi_sigs=tuple(sigs.items()),
                          unkeyed_plane=True)

    def _plan_tree_entry(self, ctx: _Ctx, calls) -> "_PlanEntry | None":
        """Tree-shaped plans (r16): every Count child lowers to a
        canonical :class:`exec.tree.TreeSpec` — the plan cache's unit
        for arbitrary compound shapes.  Survival under writes mirrors
        the r15 unkeyed-plane rule: literal-int rows over unkeyed set
        fields re-resolve against planes that absorb writes into
        delta overlays (BSI predicates re-derive from the depth the
        ``bsi_sigs`` check pins; exists/other-field rows re-fetch
        fresh), so such entries skip the per-hit generation compare
        and parse+plan stays off every request under sustained
        ingest.  Keyed rows and data-dependent row sets (UnionRows)
        stay generation-checked."""
        from pilosa_tpu.exec import tree as treemod
        from pilosa_tpu.exec.fused import Unfusable
        if not self.tree_fusion or not ctx.shards:
            return None
        if not any(c.children[0].name in treemod.TREE_CALLS
                   for c in calls):
            return None
        try:
            specs = tuple(treemod.lower_count_tree(self, ctx,
                                                   c.children[0])
                          for c in calls)
        except Unfusable:
            return None
        index = ctx.index
        deps: dict[tuple, None] = {}
        sigs: dict[str, tuple] = {}
        set_fields: dict[str, None] = {}
        survivable = True
        for spec in specs:
            set_fields[spec.field] = None
            deps[(spec.field, VIEW_STANDARD)] = None
            if spec.volatile or spec.keyed_rows:
                survivable = False
            for fname, _depth in spec.bsi_depths:
                f = index.field(fname)
                if f is None:
                    return None
                sigs[fname] = _bsi_signature(f.options)
                deps[(fname, f.bsi_view_name)] = None
            for espec in spec.extras:
                if espec[0] == "exists":
                    deps[("\x00exists", VIEW_STANDARD)] = None
                elif espec[0] == "row":
                    set_fields[espec[1]] = None
                    deps[(espec[1], espec[2])] = None
                elif espec[0] == "trange":
                    # every timestamped write also lands in the
                    # standard view (store.field fan-out), so its
                    # generations are a faithful write proxy for the
                    # bucket views; the cover itself is re-derived per
                    # hit, but new VIEWS appearing (first write in a
                    # fresh period) don't bump generations the entry
                    # tracks — stay generation-checked, not survivable
                    deps[(espec[1], VIEW_STANDARD)] = None
                    survivable = False
                elif espec[0] == "constrow":
                    pass  # literal columns: nothing to depend on
        for fname in set_fields:
            f = index.field(fname)
            if f is None:
                return None
            if f.options.keys:
                survivable = False
        deps = tuple(deps)
        return _PlanEntry("tree", ctx.shards, deps,
                          self._dep_gens(index, deps, ctx.shards),
                          len(calls), tree_specs=specs,
                          bsi_sigs=tuple(sigs.items()),
                          unkeyed_plane=survivable,
                          unkeyed_fields=tuple(set_fields))

    def _dep_gens(self, index, deps: tuple, shards: tuple) -> tuple:
        out = []
        for fname, vname in deps:
            f = (index.existence_field if fname == "\x00exists"
                 else index.field(fname))
            view = f.views.get(vname) if f is not None else None
            out.append(view.generations_fast(shards)
                       if view is not None else None)
        return tuple(out)

    def _plan_spec(self, ctx: _Ctx, call: Call, specs: list,
                   deps: dict, depths: dict):
        """Mirror of :meth:`_plan` that records hashable LEAF SPECS
        instead of arrays — the cached form re-materializes through
        the plane cache on every hit (arrays are never cached here;
        predicate masks, which are pure functions of the query text,
        are)."""
        from pilosa_tpu.exec.fused import Unfusable
        name = call.name

        def leaf(spec) -> tuple:
            specs.append(spec)
            return ("leaf", len(specs) - 1)

        if name in ("Row", "Range"):
            hit = _field_arg(call)
            if hit is None:
                raise ExecutionError(f"{name}: missing field argument")
            fname, value = hit
            field = self._field(ctx, fname)
            if isinstance(value, Condition) \
                    or field.options.type in BSI_TYPES:
                cond = (value if isinstance(value, Condition)
                        else Condition("==", value))
                return self._plan_spec_bsi(ctx, field, cond, specs, deps,
                                           depths, leaf)
            if ("from" in call.args or "to" in call.args
                    or "_timestamp" in call.args):
                raise Unfusable("time-range rows are not plan-cached")
            deps[(field.name, VIEW_STANDARD)] = None
            row_id = self._row_id(ctx, field, value, create=False)
            if row_id is None:
                return leaf(("zeros",))
            return leaf(("row", field.name, VIEW_STANDARD, int(row_id)))
        if name == "All":
            deps[("\x00exists", VIEW_STANDARD)] = None
            return leaf(("exists",))
        from pilosa_tpu.exec.tree import fold_bool_call, is_not_bool

        def exists_spec() -> int:
            deps[("\x00exists", VIEW_STANDARD)] = None
            specs.append(("exists",))
            return len(specs) - 1

        out = fold_bool_call(
            call,
            recurse=lambda c: self._plan_spec(ctx, c, specs, deps,
                                              depths),
            zeros=lambda: leaf(("zeros",)),
            exists=exists_spec,
            combine=lambda op, kids: (op, tuple(k() for k in kids)),
            complement=lambda exists, child:
                (lambda ch: ("not", ch, exists()))(child()))
        if not is_not_bool(out):
            return out
        if name == "Shift":
            if len(call.children) != 1:
                raise ExecutionError("Shift: exactly one child required")
            n = self._shift_n(call)
            return ("shift",
                    self._plan_spec(ctx, call.children[0], specs, deps,
                                    depths), n)
        raise Unfusable(f"{name} is not plan-cached")

    def _plan_spec_bsi(self, ctx: _Ctx, field: Field, cond: Condition,
                       specs: list, deps: dict, depths: dict, leaf):
        if field.options.type not in BSI_TYPES:
            raise ExecutionError(
                f"field {field.name!r}: condition on non-BSI field")
        deps[(field.name, field.bsi_view_name)] = None
        depths[field.name] = _bsi_signature(field.options)
        if cond.op in BETWEEN_OPS:
            lo_op, hi_op = between_cmp_ops(cond.op)
            lo = self._plan_spec_bsi_cmp(field, lo_op, cond.value[0],
                                         specs, leaf)
            hi = self._plan_spec_bsi_cmp(field, hi_op, cond.value[1],
                                         specs, leaf)
            return ("and", (lo, hi))
        return self._plan_spec_bsi_cmp(field, _SCALAR_TO_KEY[cond.op],
                                       cond.value, specs, leaf)

    def _plan_spec_bsi_cmp(self, field: Field, op_key: str, value,
                           specs: list, leaf):
        opts = field.options
        depth = opts.bit_depth
        offset = field.to_stored(value) - opts.base
        bound = (1 << depth) - 1
        if offset > bound or offset < -bound:
            all_hit = ((op_key in ("lt", "le", "ne")) if offset > bound
                       else (op_key in ("gt", "ge", "ne")))
            # depth growth (which shifts the saturation bound) bumps the
            # bsi view's generations, invalidating the entry
            return leaf(("bsi-exists", field.name, bool(all_hit)))
        specs.append(("bsi-plane", field.name))
        i_plane = len(specs) - 1
        specs.append(("const",
                      jnp.asarray(bsik.predicate_masks(abs(offset), depth))))
        i_masks = len(specs) - 1
        specs.append(("const", jnp.asarray(offset < 0)))
        i_neg = len(specs) - 1
        return ("bsi", i_plane, i_masks, i_neg, op_key)

    def _hit_gens(self, ctx: _Ctx, hit: _PlanHit | None, field: Field,
                  view_name: str) -> tuple | None:
        """The (field, view)'s generation tuple, swept once per hit
        (None without one: the plane cache then sweeps for itself)."""
        if hit is None:
            return None
        key = (field.name, view_name)
        gens = hit.gens.get(key)
        if gens is None:
            gens = hit.gens[key] = self.planes.generations(
                field, view_name, ctx.shards)
        return gens

    def _leaves_from_specs(self, ctx: _Ctx, specs: tuple,
                           hit: _PlanHit | None = None) -> list | None:
        """Materialize plan-cached leaf specs through the plane cache
        (each fetch revalidates its row's generations — against the
        hit's one sweep of the view where ``hit`` is given).  None = a
        spec no longer resolves (field gone) — caller invalidates."""
        out: list = []
        bsi_cache: dict = {}
        for spec in specs:
            kind = spec[0]
            if kind == "row":
                _, fname, vname, rid = spec
                field = ctx.index.field(fname)
                if field is None:
                    return None
                out.append(self.planes.row_words(
                    ctx.index.name, field, vname, rid, ctx.shards,
                    gens=self._hit_gens(ctx, hit, field, vname)))
            elif kind == "zeros":
                out.append(self._zeros(ctx))
            elif kind == "exists":
                out.append(self._exists(ctx))
            elif kind == "const":
                out.append(spec[1])
            else:  # "bsi-plane" | "bsi-exists"
                fname = spec[1]
                ps = bsi_cache.get(fname)
                if ps is None:
                    field = ctx.index.field(fname)
                    if field is None or field.options.type not in BSI_TYPES:
                        return None
                    ps = self.planes.bsi_plane(ctx.index.name, field,
                                               ctx.shards)
                    bsi_cache[fname] = ps
                if kind == "bsi-plane":
                    out.append(ps.plane)
                else:
                    exists = ps.plane[..., bsik.EXISTS_ROW, :]
                    out.append(exists if spec[2]
                               else jnp.zeros_like(exists))
        return out

    def _run_plan(self, index, index_name: str, entry: "_PlanEntry",
                  translate_output: bool, tracer,
                  deadline: float | None) -> list | None:
        """Run a validated plan; None = not runnable right now (plane
        not resident, and either admission has a say or the entry has
        no per-row form) — the caller falls through to the normal
        path, keeping admission decisions there."""
        ctx = _Ctx(index, entry.shards, translate_output,
                   deadline=deadline)
        ctx.check_deadline()
        tracer = tracer or self.tracer
        with tracer.span("executor.PlanCached", index=index_name,
                         calls=entry.n_calls, shards=len(ctx.shards)):
            t0 = time.perf_counter()
            out = self._with_oom_retry(
                lambda: self._run_plan_inner(ctx, entry))
            if out is not None:
                self.stats.timing("query_seconds",
                                  time.perf_counter() - t0,
                                  call="CountBatch")
        return out

    def _run_plan_inner(self, ctx: _Ctx, entry: "_PlanEntry") -> list | None:
        """Pick the entry's form by what can be observed.  A plane or
        tree entry runs its whole-plane program where the plane is
        resident (or delta-dirty and absorbable), exactly as before it
        carried a second form.  Not resident, and the selectivity rule
        (:meth:`_tiny_slice`, under the budget and no paging) says it
        never will be: the per-row entries ARE the resident form, and
        the entry's per-row form answers — same ``nodes`` as
        :meth:`_plan` gives, same ``_dispatch_count_run`` tail, so the
        same compiled program as the un-cached path.  Not resident and
        the un-cached path would act (admit and build, page, refuse):
        None.  A residency check that fails returns None with the
        clock still in ``plan_cache``: an attempt that serves nothing
        is the plan cache's cost.  ``plan`` is entered where the leaf
        fetch begins."""
        if entry.kind == "bsirange":
            if self.batcher is None:  # knob flipped after caching
                return None
            items = []
            for fname, op_keys, offsets, operands in entry.range_items:
                field = ctx.index.field(fname)
                if field is None:
                    return None
                items.append((field, op_keys, offsets, operands))
            return self._run_bsirange_items(ctx, items)
        hit = None
        if entry.kind != "generic":
            hit = _PlanHit() if entry.nodes else None
            out = self._run_whole_plane(ctx, entry, hit)
            if out is not None or hit is None or not hit.by_rule:
                return out
        _stage("plan")  # leaf fetch through the plane cache
        leaves = self._leaves_from_specs(ctx, entry.leaf_specs, hit)
        if leaves is None:
            return None
        out = self._dispatch_count_run(entry.nodes, tuple(leaves))
        if hit is not None:
            self.stats.count("plan_cache_row_serves_total", 1)
            self._note_path("plan-cached per-row")
        return out

    def _run_whole_plane(self, ctx: _Ctx, entry: "_PlanEntry",
                         hit: "_PlanHit | None") -> list | None:
        """A plane / tree entry's own program over the resident
        whole-field plane.  None = not runnable right now; where the
        entry has a per-row form, ``hit.by_rule`` says whether the
        selectivity rule is why."""
        if entry.kind == "tree":
            if not self.tree_fusion:  # knob flipped after caching
                return None
            return self._run_tree_specs(ctx, list(entry.tree_specs), hit)
        field = ctx.index.field(entry.field_name)
        if field is None:
            return None
        # residency only — admission (budget walks) stays on the
        # un-cached path, exactly like _count_batch_plane
        if self.planes.has_code(ctx.index.name, field, ctx.shards):
            # a coded field answers by the entry's per-row form
            if hit is not None:
                hit.by_rule = True
            return None
        if not self.planes.has_plane(ctx.index.name, field,
                                     VIEW_STANDARD, ctx.shards):
            if hit is not None:
                est = self.planes.plane_bytes(
                    field, VIEW_STANDARD, ctx.shards,
                    gens=self._hit_gens(ctx, hit, field, VIEW_STANDARD))
                hit.by_rule = (
                    not self._paging_engaged(est)
                    and est <= self.planes.budget
                    and self._tiny_slice(est, len(ctx.shards),
                                         len(entry.row_ids)))
            return None
        ps = self.planes.field_plane_nowait(ctx.index.name, field,
                                            VIEW_STANDARD, ctx.shards)
        if ps is None:
            return None
        _stage("plan")
        return self._plane_count_rows(ps, entry.row_ids)

    def _shards_for(self, index: Index, shards,
                    call: Call | None) -> tuple[int, ...]:
        """The shards a call covers: ``Options(shards=[...])``, else an
        explicit ``shards`` argument, else the index's kept shard set —
        the same tuple object on every call until a write changes the
        set (``Index.available_shards``), padded for a placement once
        per such change."""
        opts = (call.args.get("shards")
                if call is not None and call.name == "Options" else None)
        if opts is not None:
            out = tuple(int(s) for s in opts)
        elif shards is not None:
            out = tuple(shards)
        else:
            avail = index.available_shards()
            if avail and self.placement is None:
                return avail
            served = self._served_shards.get(index.name)
            if served is None or served[0] is not avail:
                served = (avail, self._pad(avail or (0,)))
                self._served_shards[index.name] = served
            return served[1]
        return self._pad(out)

    def _pad(self, shards: tuple[int, ...]) -> tuple[int, ...]:
        if self.placement is None:
            return shards
        return self.placement.pad_shards(shards)

    # ------------------------------------------------------------- dispatch

    def _call(self, ctx: _Ctx, call: Call):
        if call.name == "Options":
            if len(call.children) != 1:
                raise ExecutionError("Options: exactly one child required")
            result = self._call(ctx, call.children[0])
            # columnAttrs=true attaches column attribute maps to a row
            # result (reference: QueryRequest.ColumnAttrs)
            if call.args.get("columnAttrs") and isinstance(result, RowResult):
                store = ctx.index.column_attrs
                result.attrs = {int(c): a for c, a in
                                zip(result.columns,
                                    store.attrs_many(result.columns))
                                if a}
            if call.args.get("excludeColumns") and isinstance(result,
                                                             RowResult):
                # reference: QueryRequest.ExcludeColumns — materialize
                # nothing columnar in the response
                result.columns = np.empty(0, np.uint64)
                if result.keys is not None:
                    result.keys = []
            return result
        if call.name in _BITMAP_CALLS:
            words = self._fused_bitmap(ctx, call)
            result = self._to_row_result(ctx, words)
            if call.name == "Row":
                self._attach_row_attrs(ctx, call, result)
            if call.name == "All":
                # All(limit=, offset=) pages the column list (v2 parity)
                offset = int(call.args.get("offset", 0))
                limit = call.args.get("limit")
                if offset or limit is not None:
                    end = None if limit is None else offset + int(limit)
                    result.columns = result.columns[offset:end]
                    if result.keys is not None:
                        result.keys = result.keys[offset:end]
            return result
        handler = getattr(self, "_execute_" + call.name.lower(), None)
        if handler is None:
            raise ExecutionError(f"unknown call {call.name!r}")
        return handler(ctx, call)

    def _with_oom_retry(self, fn):
        """Run ``fn``; on device RESOURCE_EXHAUSTED, recover in stages
        that coordinate across concurrent queries (r5 redesign of the
        r4 evict-all-and-retry, which thrashed under concurrent
        over-budget load: two queries needing disjoint residency would
        ping-pong global eviction, and a second OOM propagated as 500).

        Stage 1 (serialized by the gate): evict only UNPINNED planes —
        entries no in-flight query leases — and retry.  Evicting leased
        planes frees no HBM (the queries' frames hold live refs) and
        forces mid-flight rebuilds, so they stay.

        Stage 2 (still under the gate): drain to exclusivity — wait for
        every other query to finish or park at the gate (parked queries
        leave the in-flight count, so this cannot deadlock; their OOM
        unwound their device refs already), then drop ALL residency and
        run alone.  At most 3 attempts per query, one recovery at a
        time: no retry storm.

        Covers EVERY execute path — fused count batches and bitmap fast
        paths included, not just per-call handlers."""
        try:
            if fault.ACTIVE:
                # `oom` raises the RESOURCE_EXHAUSTED shape this very
                # wrapper classifies — injected device OOM drives the
                # real staged recovery below, not a simulation of it
                fault.fire("exec.oom")
            return fn()
        except Exception as e:  # noqa: BLE001 — filtered below
            if not _is_device_oom(e):
                raise
        import gc
        self.stats.count("device_oom_retries", 1)
        # park OUTSIDE the in-flight count while waiting for the gate:
        # the active recovery may need to drain to exclusivity, and a
        # queue of OOM'd queries still counted in-flight would wedge it
        self._leave_inflight()
        try:
            with self._oom_gate:
                self._enter_inflight()
                try:
                    self.planes.evict_unpinned()
                    gc.collect()
                    try:
                        return fn()
                    except Exception as e:  # noqa: BLE001
                        if not _is_device_oom(e):
                            raise
                    self.stats.count("device_oom_exclusive_retries", 1)
                    self._recovery_open.clear()  # park new arrivals
                    try:
                        self._drain_to_exclusive()
                        # background plane builds hold device memory the
                        # cache can't see yet — join them before the
                        # exclusive retry sizes itself against free HBM
                        self.planes.wait_builds()
                        self.planes.invalidate()
                        gc.collect()
                        return fn()
                    finally:
                        self._recovery_open.set()
                finally:
                    self._leave_inflight()
        finally:
            self._enter_inflight()

    def _attach_row_attrs(self, ctx: _Ctx, call: Call,
                          result: "RowResult") -> None:
        """A plain ``Row(field=row)`` result carries the row's
        attributes (reference: v1 ``Row.Attrs`` in the JSON response;
        suppressed with ``excludeRowAttrs=true``)."""
        if call.args.get("excludeRowAttrs"):
            return
        hit = _field_arg(call)
        if hit is None:
            return
        fname, value = hit
        if isinstance(value, (Condition, Call)):
            return
        field = ctx.index.field(str(fname))
        if field is None or field.options.type in BSI_TYPES:
            return
        if not field.has_row_attrs:  # never CREATE a store on a read
            return
        row_id = self._row_id(ctx, field, value, create=False)
        if row_id is None:
            return
        attrs = field.row_attrs.attrs(int(row_id))
        if attrs:
            result.row_attrs = {str(k): v for k, v in attrs.items()}

    # -- bitmap calls -------------------------------------------------------

    def _fused_bitmap(self, ctx: _Ctx, call: Call, want: str = "words"):
        """Evaluate a bitmap call tree as ONE compiled program (SURVEY.md
        §8 "one compiled function per call-shape"); falls back to the
        eager per-op path for shapes the planner doesn't cover."""
        from pilosa_tpu.exec.fused import Unfusable
        from pilosa_tpu.exec import tree as treemod
        if (self.tree_fusion and ctx.shards
                and call.name in treemod.TREE_CALLS):
            # bitmap-valued compound trees ride the whole-tree program
            # too: one in-program gather from the resident plane, one
            # postfix fold — no per-leaf arrays (r16)
            hit = None
            try:
                spec = treemod.lower_count_tree(self, ctx, call)
                hit = self._tree_item(ctx, spec)
            except Unfusable:
                hit = None
            if hit is not None:
                ps, (slots, prog, extras) = hit
                self._tree_stats(spec)
                words = self.fused.run_tree_words(
                    ps.plane, slots, prog, extras, delta=ps.delta)
                if want == "count":
                    return kernels.count(words)
                return words
        try:
            leaves: list = []
            node = self._plan(ctx, call, leaves)
        except Unfusable:
            words = self._bitmap(ctx, call)
            if want == "count":
                return kernels.count(words)
            return words
        return self.fused.run(node, tuple(leaves), want)

    def _plan(self, ctx: _Ctx, call: Call, leaves: list):
        """Mirror of :meth:`_bitmap` that collects leaf arrays and
        returns a hashable structure tree for the fused compiler."""
        name = call.name

        def leaf(arr) -> tuple:
            leaves.append(arr)
            return ("leaf", len(leaves) - 1)

        if name in ("Row", "Range"):
            return self._plan_row(ctx, call, leaves, leaf)
        if name == "All":
            return leaf(self._exists(ctx))
        from pilosa_tpu.exec.tree import fold_bool_call, is_not_bool

        def exists_leaf() -> int:
            leaves.append(self._exists(ctx))
            return len(leaves) - 1

        out = fold_bool_call(
            call,
            recurse=lambda c: self._plan(ctx, c, leaves),
            zeros=lambda: leaf(self._zeros(ctx)),
            exists=exists_leaf,
            # ONE flat n-ary node — a nested pair per child would
            # recurse once per child in _build/shift_leaves and blow
            # the recursion limit on wide flat Unions
            combine=lambda op, kids: (op, tuple(k() for k in kids)),
            complement=lambda exists, child:
                (lambda ch: ("not", ch, exists()))(child()))
        if not is_not_bool(out):
            return out
        if name == "Shift":
            if len(call.children) != 1:
                raise ExecutionError("Shift: exactly one child required")
            n = self._shift_n(call)
            return ("shift", self._plan(ctx, call.children[0], leaves), n)
        if name == "UnionRows":
            # UnionRows(Rows(f)): OR of every row the Rows call selects
            # (reference: v2 executeUnionRows)
            return leaf(self._union_rows(ctx, call))
        if name == "ConstRow":
            return leaf(self._const_row(ctx, call))
        if name == "Limit":
            # order-based truncation needs a host column pass; keep it
            # out of the fused program (the caller falls back to eager)
            from pilosa_tpu.exec.fused import Unfusable
            raise Unfusable("Limit is host-ordered")
        raise ExecutionError(f"not a bitmap call: {name}")

    def _const_row(self, ctx: _Ctx, call: Call) -> jax.Array:
        """ConstRow(columns=[...]): a literal bitmap (reference: v2
        ``executeConstRow``).  Unknown keys resolve to nothing.
        Columns whose shard is outside the queried shard set drop —
        execution is per-shard over the index's shards, exactly as a
        ConstRow column in a data-less shard drops upstream."""
        cols = call.args.get("columns")
        if cols is None:
            raise ExecutionError("ConstRow: missing columns argument")
        return self._const_row_cols(ctx, cols)

    def _const_row_cols(self, ctx: _Ctx, cols) -> jax.Array:
        host = np.zeros((len(ctx.shards), WORDS_PER_SHARD), np.uint32)
        shard_slot = {s: si for si, s in enumerate(ctx.shards)}
        for c in cols:
            cid = self._col_id(ctx, c, create=False)
            if cid is None:
                continue
            si = shard_slot.get(cid // SHARD_WIDTH)
            if si is None:
                continue
            off = cid % SHARD_WIDTH
            host[si, off >> 5] |= np.uint32(1) << np.uint32(off & 31)
        return self.planes.place(host)

    def _limit_bitmap(self, ctx: _Ctx, call: Call) -> jax.Array:
        """Limit(bitmap, limit=, offset=): truncate the ascending column
        list (reference: v2 ``executeLimitCall``) — inherently ordered,
        so the column set round-trips through the host."""
        if len(call.children) != 1:
            raise ExecutionError("Limit: exactly one bitmap child required")
        offset = int(call.args.get("offset", 0))
        limit = call.args.get("limit")
        if offset < 0 or (limit is not None and int(limit) < 0):
            raise ExecutionError("Limit: limit/offset must be >= 0")
        words = self._fused_bitmap(ctx, call.children[0])
        end = None if limit is None else offset + int(limit)
        if end is None:
            host = np.asarray(words)
            n_shards = len(ctx.shards)
        else:
            # push the truncation down: per-shard popcounts (one tiny
            # device read) say how many leading shards can contain the
            # first offset+limit columns — read and unpack ONLY those.
            # (Unbounded materialization of a 25% row at 1B cols cost
            # ~70 s/call on this host: 125 MB read + 250M-column
            # unpack/concat for a limit=1000 answer — r5.)
            counts = np.asarray(_shard_popcounts(words))
            cum = np.cumsum(counts)
            n_shards = int(np.searchsorted(cum, end)) + 1
            n_shards = min(n_shards, len(ctx.shards))
            host = np.asarray(words[:n_shards])
        parts = [offs.astype(np.uint64) + np.uint64(s * SHARD_WIDTH)
                 for _, s, offs in self._shard_offsets(
                     ctx, host, limit_shards=n_shards)]
        all_cols = (np.concatenate(parts) if parts
                    else np.empty(0, np.uint64))
        sel = all_cols[offset:end]
        out = np.zeros((len(ctx.shards), WORDS_PER_SHARD), np.uint32)
        if len(sel):
            shard_slot = {s: si for si, s in enumerate(ctx.shards)}
            si_arr = np.array([shard_slot[int(c) // SHARD_WIDTH]
                               for c in sel])
            offs = (sel % np.uint64(SHARD_WIDTH)).astype(np.int64)
            np.bitwise_or.at(
                out, (si_arr, offs >> 5),
                (np.uint32(1) << (offs & 31).astype(np.uint32)))
        return self.planes.place(out)

    @staticmethod
    def _shift_n(call: Call) -> int:
        try:
            n = int(call.args.get("n", 1))
        except (TypeError, ValueError):
            raise ExecutionError(f"Shift: bad n {call.args.get('n')!r}")
        if not 0 <= n < SHARD_WIDTH:
            raise ExecutionError(f"Shift: n must be in [0, 2^20), got {n}")
        return n

    def _union_rows(self, ctx: _Ctx, call: Call) -> jax.Array:
        bad = [c.name for c in call.children if c.name != "Rows"]
        if bad:
            raise ExecutionError(
                f"UnionRows: children must be Rows calls, got {bad}")
        rows_calls = call.children
        if not rows_calls:
            raise ExecutionError("UnionRows: Rows children required")
        acc = self._zeros(ctx)
        for rc in rows_calls:
            fname = rc.args.get("_field") or rc.args.get("field")
            field = self._field(ctx, str(fname))
            rows = self._rows_of(ctx, field, rc)
            if len(rows) == 0:
                continue
            # plane over the SELECTED rows only (memory bounded by the
            # selection, not the field's row cardinality)
            ps = self.planes.rows_plane(ctx.index.name, field,
                                        VIEW_STANDARD, rows, ctx.shards)
            mask = np.zeros(ps.plane.shape[-2], dtype=bool)
            mask[:len(rows)] = True
            acc = kernels.union(acc, kernels.union_rows(
                ps.plane, jnp.asarray(mask)))
        return acc

    def _plan_row(self, ctx: _Ctx, call: Call, leaves: list, leaf):
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError(f"{call.name}: missing field argument")
        fname, value = hit
        field = self._field(ctx, fname)
        if isinstance(value, Condition) or field.options.type in BSI_TYPES:
            cond = (value if isinstance(value, Condition)
                    else Condition("==", value))
            return self._plan_bsi(ctx, field, cond, leaves, leaf)
        row_id = self._row_id(ctx, field, value, create=False)
        if row_id is None:
            return leaf(self._zeros(ctx))
        if ("from" in call.args or "to" in call.args
                or "_timestamp" in call.args):
            # time-range rows stay eager (variable view counts would
            # explode the program cache); wrap the result as one leaf
            return leaf(self._time_row(ctx, field, row_id, call))
        return leaf(self.planes.row_words(ctx.index.name, field,
                                          VIEW_STANDARD, row_id, ctx.shards))

    def _plan_bsi(self, ctx: _Ctx, field: Field, cond: Condition,
                  leaves: list, leaf):
        if field.options.type not in BSI_TYPES:
            raise ExecutionError(
                f"field {field.name!r}: condition on non-BSI field")
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        if cond.op in BETWEEN_OPS:
            lo_op, hi_op = between_cmp_ops(cond.op)
            lo = self._plan_bsi_cmp(ctx, field, ps, lo_op, cond.value[0],
                                    leaves, leaf)
            hi = self._plan_bsi_cmp(ctx, field, ps, hi_op, cond.value[1],
                                    leaves, leaf)
            return ("and", (lo, hi))
        return self._plan_bsi_cmp(ctx, field, ps,
                                  _SCALAR_TO_KEY[cond.op], cond.value,
                                  leaves, leaf)

    def _plan_bsi_cmp(self, ctx: _Ctx, field: Field, ps, op_key: str,
                      value, leaves: list, leaf):
        opts = field.options
        depth = opts.bit_depth
        offset = field.to_stored(value) - opts.base
        bound = (1 << depth) - 1
        if offset > bound or offset < -bound:
            # saturated: trivially everything-not-null or nothing
            exists = ps.plane[..., bsik.EXISTS_ROW, :]
            all_hit = (op_key in ("lt", "le", "ne")) if offset > bound \
                else (op_key in ("gt", "ge", "ne"))
            return leaf(exists if all_hit else jnp.zeros_like(exists))
        leaves.append(ps.plane)
        i_plane = len(leaves) - 1
        leaves.append(jnp.asarray(bsik.predicate_masks(abs(offset), depth)))
        i_masks = len(leaves) - 1
        leaves.append(jnp.asarray(offset < 0))
        i_neg = len(leaves) - 1
        return ("bsi", i_plane, i_masks, i_neg, op_key)

    def _bitmap(self, ctx: _Ctx, call: Call) -> jax.Array:
        """Evaluate a bitmap-valued call to uint32[n_shards, W]."""
        name = call.name
        if name == "Row" or name == "Range":  # Range is the legacy alias
            return self._row_bitmap(ctx, call)
        if name == "All":
            return self._exists(ctx)
        from pilosa_tpu.exec.tree import fold_bool_call, is_not_bool
        def eager_fold(op, kids):
            acc = kids[0]()
            for child in kids[1:]:
                acc = _EAGER_OPS[op](acc, child())
            return acc

        out = fold_bool_call(
            call,
            recurse=lambda c: self._bitmap(ctx, c),
            zeros=lambda: self._zeros(ctx),
            exists=lambda: self._exists(ctx),
            combine=eager_fold,
            complement=lambda exists, child: kernels.complement(
                child(), exists()))
        if not is_not_bool(out):
            return out
        kids = call.children
        if name == "Shift":
            if len(kids) != 1:
                raise ExecutionError("Shift: exactly one child required")
            return kernels.shift(self._bitmap(ctx, kids[0]),
                                 self._shift_n(call))
        if name == "UnionRows":
            return self._union_rows(ctx, call)
        if name == "ConstRow":
            return self._const_row(ctx, call)
        if name == "Limit":
            return self._limit_bitmap(ctx, call)
        raise ExecutionError(f"not a bitmap call: {name}")

    def _row_bitmap(self, ctx: _Ctx, call: Call) -> jax.Array:
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError(f"{call.name}: missing field argument")
        fname, value = hit
        field = self._field(ctx, fname)
        if isinstance(value, Condition):
            return self._bsi_condition(ctx, field, value)
        if field.options.type in BSI_TYPES:
            # Row(amount=5) on BSI ≡ amount == 5
            return self._bsi_condition(ctx, field, Condition("==", value))
        row_id = self._row_id(ctx, field, value, create=False)
        if row_id is None:
            return self._zeros(ctx)
        if ("from" in call.args or "to" in call.args
                or "_timestamp" in call.args):
            return self._time_row(ctx, field, row_id, call)
        return self.planes.row_words(ctx.index.name, field, VIEW_STANDARD,
                                     row_id, ctx.shards)

    def _time_row(self, ctx: _Ctx, field: Field, row_id: int,
                  call: Call) -> jax.Array:
        q = field.options.time_quantum
        if not q:
            raise ExecutionError(f"field {field.name!r} is not a time field")
        # legacy positional form: Range(f=1, <from-ts>, <to-ts>)
        frm = call.args.get("from", call.args.get("_timestamp"))
        to = call.args.get("to", call.args.get("_timestamp2"))
        start = parse_pql_time(str(frm)) if frm is not None else None
        end = parse_pql_time(str(to)) if to is not None else None
        words = self._time_range_words(ctx, field, row_id, start, end)
        if words is not None:
            return words
        return self._time_row_span(ctx, field, row_id, start, end)

    def _time_range_words(self, ctx: _Ctx, field: Field, row_id: int,
                          start, end) -> "jax.Array | None":
        """Fused time-range path (r23): answer ``row seen in [start,
        end)`` as ONE OR-scan over the contiguous bucket slot range of
        the field's resident :class:`timeviews.TimePlaneSet` —
        equivalent bit for bit to the mixed-granularity cover union
        (finest views carry every bit; ``tests/test_timeviews.py``
        pins it).  None = not runnable at device speed right now
        (degraded device, plane over budget / not built, too many
        shards) — the caller stays on the op-at-a-time span oracle."""
        if (self.batcher is not None
                and not self.batcher.governor.fastlane_ok()):
            return None
        if len(ctx.shards) > self._REDUCE_SHARD_MAX:
            return None
        tps = self.planes.time_plane_nowait(ctx.index.name, field,
                                            ctx.shards)
        if tps is None:
            return None
        idx = tps.slot_of.get(int(row_id))
        if idx is None:
            return self._zeros(ctx)
        b0, b1 = tps.bucket_range(start, end)
        if b1 <= b0:
            return self._zeros(ctx)
        self.stats.observe("time_range_cover_size", float(b1 - b0))
        return self.fused.run_time_range(
            tps.plane, idx * tps.n_buckets + b0, b1 - b0,
            delta=tps.delta)

    def _time_row_span(self, ctx: _Ctx, field: Field, row_id: int,
                       start, end) -> jax.Array:
        """Op-at-a-time time-range oracle: union one device row fetch
        per minimal-cover view.  Kept as the correctness oracle the
        fused path is pinned against and as the serving fallback when
        the time plane isn't residing (budget, degraded device)."""
        self._note_path("op-at-a-time fallback")
        q = field.options.time_quantum
        # clamp the range to the span actually covered by existing views:
        # an omitted bound would otherwise enumerate views unit-by-unit
        # across the whole calendar
        spans = []
        prefix = VIEW_STANDARD + "_"
        for vname in field.views:
            if vname.startswith(prefix):
                try:
                    spans.append(view_span(vname[len(prefix):]))
                except ValueError:
                    continue
        if not spans:
            return self._zeros(ctx)
        vmin = min(s for s, _ in spans)
        vmax = max(e for _, e in spans)
        start = vmin if start is None else max(start, vmin)
        end = vmax if end is None else min(end, vmax)
        acc = self._zeros(ctx)
        for vname in views_by_time_range(VIEW_STANDARD, start, end, q):
            if field.view(vname) is None:
                continue
            acc = kernels.union(acc, self.planes.row_words(
                ctx.index.name, field, vname, row_id, ctx.shards))
        return acc

    def _time_cover_views(self, field: Field, frm, to) -> list[str]:
        """Existing view names minimally covering a Rows/GroupBy time
        filter, with the oracle's span clamping — the shared answer to
        "which views can contribute rows in [from, to)"."""
        q = field.options.time_quantum
        if not q:
            raise ExecutionError(f"field {field.name!r} is not a time field")
        spans = []
        prefix = VIEW_STANDARD + "_"
        for vname in field.views:
            if vname.startswith(prefix):
                try:
                    spans.append(view_span(vname[len(prefix):]))
                except ValueError:
                    continue
        if not spans:
            return []
        vmin = min(s for s, _ in spans)
        vmax = max(e for _, e in spans)
        start = vmin if frm is None else max(parse_pql_time(str(frm)), vmin)
        end = vmax if to is None else min(parse_pql_time(str(to)), vmax)
        return [vname
                for vname in views_by_time_range(VIEW_STANDARD, start, end, q)
                if field.view(vname) is not None]

    def _bsi_condition(self, ctx: _Ctx, field: Field,
                       cond: Condition) -> jax.Array:
        if field.options.type not in BSI_TYPES:
            raise ExecutionError(
                f"field {field.name!r}: condition on non-BSI field")
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        if cond.op in BETWEEN_OPS:
            lo_op, hi_op = between_cmp_ops(cond.op)
            lo = self._bsi_cmp(field, ps, lo_op, cond.value[0])
            hi = self._bsi_cmp(field, ps, hi_op, cond.value[1])
            return kernels.intersect(lo, hi)
        return self._bsi_cmp(field, ps, _SCALAR_TO_KEY[cond.op], cond.value)

    def _bsi_cmp(self, field: Field, ps, op_key: str, value) -> jax.Array:
        """One signed comparison with out-of-depth predicate saturation
        (everything/nothing cases need no kernel; see
        ``engine.bsi.predicate_masks``)."""
        return self._bsi_cmp_offset(
            field, ps, op_key,
            field.to_stored(value) - field.options.base)

    def _bsi_cmp_offset(self, field: Field, ps, op_key: str,
                        offset: int) -> jax.Array:
        """Comparison against a base-relative stored offset (used by
        Percentile's binary search, which walks stored space directly)."""
        opts = field.options
        depth = opts.bit_depth
        exists = ps.plane[..., bsik.EXISTS_ROW, :]
        bound = (1 << depth) - 1
        if offset > bound:
            if op_key in ("lt", "le", "ne"):
                return exists
            return jnp.zeros_like(exists)
        if offset < -bound:
            if op_key in ("gt", "ge", "ne"):
                return exists
            return jnp.zeros_like(exists)
        masks = bsik.predicate_masks(abs(offset), depth)
        cmp = bsik.range_cmp(ps.plane, jnp.asarray(masks),
                             jnp.asarray(offset < 0))
        return cmp[op_key]

    # -- helpers ------------------------------------------------------------

    def _field(self, ctx: _Ctx, name: str) -> Field:
        field = ctx.index.field(name)
        if field is None:
            raise ExecutionError(
                f"field {name!r} not found in index {ctx.index.name!r}")
        return field

    def _row_id(self, ctx: _Ctx, field: Field, value,
                create: bool) -> int | None:
        if isinstance(value, bool):
            return int(value)
        if isinstance(value, str):
            if not field.options.keys:
                raise ExecutionError(
                    f"field {field.name!r}: string row on unkeyed field")
            log = self.translate.rows(field.index_name, field.name)
            return log.translate([value], create=create)[0]
        # raw mode (translate_output=False): the cluster layer pre-
        # translated keys to IDs at the edge; integer rows are expected
        if field.options.keys and ctx.translate_output:
            raise ExecutionError(
                f"field {field.name!r}: integer row on keyed field")
        return int(value)

    def _col_id(self, ctx: _Ctx, value, create: bool) -> int | None:
        if isinstance(value, str):
            if not ctx.index.keys:
                raise ExecutionError(
                    f"index {ctx.index.name!r}: string column on unkeyed index")
            log = self.translate.columns(ctx.index.name)
            return log.translate([value], create=create)[0]
        if ctx.index.keys and ctx.translate_output:
            raise ExecutionError(
                f"index {ctx.index.name!r}: integer column on keyed index")
        return int(value)

    def _exists(self, ctx: _Ctx) -> jax.Array:
        ef = ctx.index.existence_field
        if ef is None:
            raise ExecutionError(
                f"index {ctx.index.name!r} does not track existence "
                "(required for Not/All)")
        return self.planes.row_words(ctx.index.name, ef, VIEW_STANDARD, 0,
                                     ctx.shards)

    def _zeros(self, ctx: _Ctx) -> jax.Array:
        return self.planes.zeros(len(ctx.shards))

    def _shard_offsets(self, ctx: _Ctx, host: np.ndarray,
                       limit_shards: int | None = None):
        """Unpack a host bitmap (n_shards, W) into non-empty per-shard
        ascending column offsets: [(slot, shard, offsets uint)] — the one
        owner of the words→columns idiom (RowResult/Limit/Extract).
        ``limit_shards`` stops after the first N shard slots (the Limit
        push-down passes a host slice of just those rows)."""
        out = []
        for si, s in enumerate(ctx.shards):
            if limit_shards is not None and si >= limit_shards:
                break
            if s == PAD_SHARD:
                continue
            offs = unpack_columns(host[si])
            if len(offs):
                out.append((si, s, offs))
        return out

    def _to_row_result(self, ctx: _Ctx, words: jax.Array) -> RowResult:
        _stage("read")
        host = np.asarray(words)
        _stage("assemble")
        parts = [offs.astype(np.uint64) + np.uint64(s * SHARD_WIDTH)
                 for _, s, offs in self._shard_offsets(ctx, host)]
        columns = (np.concatenate(parts) if parts
                   else np.empty(0, np.uint64))
        if ctx.index.keys and ctx.translate_output:
            log = self.translate.columns(ctx.index.name)
            return RowResult(keys=log.keys_of(columns))
        return RowResult(columns=columns)

    def _filter_words(self, ctx: _Ctx, call: Call) -> jax.Array | None:
        """Optional bitmap-call filter child (TopN/Sum/Rows/GroupBy)."""
        flt = call.args.get("filter")
        if flt is None and call.children:
            flt = call.children[0]
        if flt is None:
            return None
        if not isinstance(flt, Call):
            raise ExecutionError("filter must be a bitmap call")
        return self._fused_bitmap(ctx, flt)

    # -- scalar / aggregate calls ------------------------------------------

    def _execute_count(self, ctx: _Ctx, call: Call) -> int:
        if len(call.children) != 1:
            raise ExecutionError("Count: exactly one child required")
        # over-budget/over-quota plain-Row planes serve PAGED (r17):
        # resident shard pages on device, host oracle for the rest —
        # without this, a too-big field never reached device speed
        paged = self._count_batch_paged(ctx, [call])
        if paged is not None:
            return paged[0]
        # simple BSI range counts ride the bsirange family (r20):
        # delta-aware plane, same-plane co-batching, solo fast lane
        fast = self._count_batch_bsi(ctx, [call])
        if fast is not None:
            return fast[0]
        # compound boolean trees compile whole (r16): one in-program
        # row gather + postfix fold, windowed with concurrent requests
        fused_tree = self._count_batch_tree(ctx, [call])
        if fused_tree is not None:
            return fused_tree[0]
        if self.batcher is not None:
            # cross-request coalescing: plan here, let the batcher run
            # one program + one read for every concurrent Count
            from pilosa_tpu.exec.fused import Unfusable
            try:
                leaves: list = []
                node = self._plan(ctx, call.children[0], leaves)
                return self.batcher.submit(
                    node, leaves, deadline=self._query_deadline())
            except Unfusable:
                pass
        # fused: bitwise tree + per-shard popcount in one XLA program;
        # the tiny cross-shard total finishes in int64 on host
        per_shard = self._fused_bitmap(ctx, call.children[0], want="count")
        _stage("read")
        total = int(kernels.shard_totals(per_shard))
        _stage("assemble")
        return total

    def _execute_distinct(self, ctx: _Ctx, call: Call):
        """Distinct(filter?, field=f): sorted distinct values of a BSI
        field among (filtered) columns — device presence-bitmap scatter
        instead of the reference's per-shard value-set walk
        (``executor.go`` v2 ``executeDistinctShard``)."""
        from pilosa_tpu.exec.result import DistinctResult
        field, filter_words = self._agg_args(ctx, call)
        if field.options.bit_depth > 24:
            raise ExecutionError(
                "Distinct: bit depth > 24 not supported (presence array "
                "would exceed 16M entries)")
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        if self.batcher is not None:
            # concurrent identical Distincts share one presence scan
            # through the coalescing window (dedupe, not stacking —
            # the scan is a multi-dispatch block loop)
            pos, neg = self.batcher.submit_distinct(
                ps.plane, filter_words, deadline=self._query_deadline())
        else:
            pos, neg = bsik.distinct_presence(ps.plane, filter_words)
        pos = np.nonzero(np.asarray(pos))[0]
        neg = np.nonzero(np.asarray(neg))[0]
        base = field.options.base
        stored = sorted({int(v) + base for v in pos}
                        | {-int(v) + base for v in neg})
        return DistinctResult([field.from_stored(v) for v in stored])

    def _execute_includescolumn(self, ctx: _Ctx, call: Call) -> bool:
        """IncludesColumn(Row(...), column=c) -> bool (v2 parity)."""
        if len(call.children) != 1:
            raise ExecutionError(
                "IncludesColumn: exactly one bitmap child required")
        column = call.args.get("column")
        if column is None:
            raise ExecutionError("IncludesColumn: missing column argument")
        col_id = self._col_id(ctx, column, create=False)
        if col_id is None:
            return False
        shard, off = col_id // SHARD_WIDTH, col_id % SHARD_WIDTH
        if shard not in ctx.shards:
            return False
        # evaluate only over the owning shard (reference:
        # executeIncludesColumnCall runs on that shard alone)
        one = _Ctx(ctx.index, (shard,), ctx.translate_output)
        words = self._fused_bitmap(one, call.children[0])
        word = int(np.asarray(words[0, off >> 5]))
        return bool((word >> (off & 31)) & 1)

    def _execute_percentile(self, ctx: _Ctx, call: Call) -> ValCount:
        """Percentile(field=f, nth=99.9, filter?): the smallest stored
        value v with count(values <= v) >= nth% of non-null columns.
        The binary search runs ON DEVICE (``lax.while_loop`` in
        ``bsi.percentile_search``): two dispatches + two reads total
        (count, then search with an exact host-computed rank), vs
        ~2·bit_depth round trips for a host-driven search
        (FeatureBase-era Percentile parity)."""
        field, filter_words = self._agg_args(ctx, call)
        nth = call.args.get("nth")
        if nth is None:
            raise ExecutionError("Percentile: missing nth argument")
        nth = float(nth)
        if not 0 <= nth <= 100:
            raise ExecutionError("Percentile: nth must be in [0, 100]")
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        out, total = self.fused.run_percentile(ps.plane, filter_words, nth)
        if total == 0:
            return ValCount(0, 0)
        out = np.asarray(out)
        value = int(out[0]) + field.options.base
        return ValCount(value=field.from_stored(value), count=int(out[1]))

    def _execute_sum(self, ctx: _Ctx, call: Call) -> ValCount:
        return self._agg_batch(ctx, [call])[0]

    _execute_min = _execute_max = _execute_sum

    def _agg_batch(self, ctx: _Ctx, calls: list[Call]) -> list[ValCount]:
        """K ``Sum`` calls — or K ``Min`` / ``Max`` calls — over ONE
        BSI field as one K-item aggregate: the field and its plane
        resolve once, every call plans its own filter, and one program
        over the resident plane answers them all with one read
        (identical filters share a scan; a ``Min`` and a ``Max`` under
        one filter are the same item).  A single call is K = 1."""
        args = [self._agg_args(ctx, call) for call in calls]
        field = args[0][0]
        filters = [filter_words for _field, filter_words in args]
        kind = "sum" if calls[0].name == "Sum" else "minmax"
        # delta-aware plane (r20): sustained ingest absorbs into the
        # plane's BsiOverlay and the aggregate kernels answer
        # base⊕delta — no fold, no rebuild on the query path
        ps = self.planes.bsi_plane_delta(ctx.index.name, field,
                                         ctx.shards)
        if self.batcher is not None:
            # one launch + one read on the solo fast lane; under
            # concurrency the items join the collection window, where
            # same-plane aggregates of other requests co-batch
            vals = self.batcher.submit_aggs(
                kind, ps.plane, filters, delta=ps.delta,
                deadline=self._query_deadline())
        else:
            # same compiled one-read program (eager bit_counts would
            # pay one dispatch per op + 3 reads)
            _stage("dispatch")
            out, assign, decode = self.fused.run_agg_plane_batch(
                kind, ps.plane, filters, delta=ps.delta)
            _stage("read")
            host = np.asarray(out)
            _stage("assemble")
            vals = [decode(host[slot]) for slot in assign]
        if kind == "sum":
            return [self._sum_result(field, *val) for val in vals]
        return [self._min_max_result(field, val, call.name == "Min")
                for call, val in zip(calls, vals)]

    @staticmethod
    def _sum_result(field, total: int, cnt: int) -> ValCount:
        value = total + field.options.base * cnt
        return ValCount(value=field.from_stored(value) if cnt else 0,
                        count=cnt)

    @staticmethod
    def _min_max_result(field, per_shard, want_min: bool) -> ValCount:
        # reduce across the shard axis on host (one tuple per shard;
        # a delta-dirty plane appends one zero-or-live tuple per
        # overlay-touched word column — same combine)
        live = [(mn, mn_c, mx, mx_c)
                for mn, mn_c, mx, mx_c in per_shard
                if (mn_c if want_min else mx_c) > 0]
        if not live:
            return ValCount(0, 0)
        if want_min:
            best = min(mn for mn, *_ in live)
            total = sum(mn_c for mn, mn_c, *_ in live if mn == best)
        else:
            best = max(mx for _, _, mx, _ in live)
            total = sum(mx_c for _, _, mx, mx_c in live if mx == best)
        value = best + field.options.base
        return ValCount(value=field.from_stored(value), count=total)

    def _agg_args(self, ctx: _Ctx, call: Call):
        fname = call.args.get("field") or call.args.get("_field")
        if fname is None:
            raise ExecutionError(f"{call.name}: missing field argument")
        field = self._field(ctx, str(fname))
        if field.options.type not in BSI_TYPES:
            raise ExecutionError(f"{call.name}: field {fname!r} is not BSI")
        return field, self._filter_words(ctx, call)

    # -- TopN ---------------------------------------------------------------

    def _execute_topn(self, ctx: _Ctx, call: Call) -> PairsResult:
        fname = call.args.get("_field") or call.args.get("field")
        if fname is None:
            raise ExecutionError("TopN: missing field argument")
        field = self._field(ctx, str(fname))
        n = call.args.get("n")
        filter_words = self._filter_words(ctx, call)
        # tanimoto= threshold (reference: ``fragment.go#top`` tanimoto
        # arg): keep rows whose tanimoto coefficient against the filter
        # (source) row, 100·|row∧src| / (|src|+|row|−|row∧src|), meets
        # the threshold.  ``_rowCounts=1`` is the internal cluster
        # fan-out mode: return per-row intersection AND full counts plus
        # |src| so the coordinator can apply the threshold on GLOBAL
        # sums (per-node ratios don't merge).
        tanimoto = call.args.get("tanimoto")
        want_partial = bool(call.args.get("_rowCounts"))
        if tanimoto is not None:
            tanimoto = float(tanimoto)
            if not 0 < tanimoto <= 100:
                raise ExecutionError("TopN: tanimoto must be in (0, 100]")
        need_row_counts = want_partial or tanimoto is not None
        if need_row_counts and filter_words is None:
            raise ExecutionError(
                "TopN: tanimoto requires a filter row (source bitmap)")
        # |src| counts even when this node holds no rows of the target
        # field — the coordinator's global tanimoto union needs every
        # node's share of the source row
        src_count = 0
        if need_row_counts:
            src_count = int(kernels.shard_totals(
                kernels.count(filter_words)))
        # Representation choice (SURVEY.md §8 "dense blowup"):
        # 1. dense resident plane when it fits the device budget;
        # 2. no filter → exact counts from host fragment metadata,
        #    no device at all;
        # 3. sparse (container-blocked) residency when 12 B/bit fits —
        #    high-row-cardinality fields stay device-resident and one
        #    gather+segment-sum program answers each filtered TopN
        #    (engine/sparse.py), no per-query re-streaming;
        # 4. last resort: stream fixed-shape row blocks per query.
        row_totals = None
        ps = None
        tried_nowait = False
        # 0. a coded field: its row counts are the histogram of its
        # codes (asked only where no dense plane of it is resident)
        code = (None if self.planes.has_entry(
            ctx.index.name, field, VIEW_STANDARD, ctx.shards)
            else self.planes.code_plane(ctx.index.name, field, ctx.shards))
        if code is None and self.planes.has_entry(
                ctx.index.name, field, VIEW_STANDARD, ctx.shards):
            # a resident entry (fresh or delta-dirty) serves without
            # the per-request plane_bytes fragment walk — under
            # sustained ingest the generations move every batch and
            # the walk would land on every TopN (the r3 warm-path
            # metadata class)
            ps = self.planes.field_plane_nowait(ctx.index.name, field,
                                                VIEW_STANDARD, ctx.shards)
            tried_nowait = True
        if ps is None and code is None:
            est = self.planes.plane_bytes(field, VIEW_STANDARD,
                                          ctx.shards)
            if est <= self.planes.budget and not tried_nowait:
                # nowait: while a big plane builds in the background
                # (serve-while-build, VERDICT r4 weak #6) this query
                # falls through to the streaming path instead of
                # stalling minutes
                ps = self.planes.field_plane_nowait(
                    ctx.index.name, field, VIEW_STANDARD, ctx.shards)
        if code is not None:
            totals = self.planes.code_counts(code, filter_words)
            if need_row_counts:
                row_totals = self.planes.code_counts(code, None)
            all_rows = code.row_ids
        elif ps is not None:
            if ps.n_rows == 0:
                return ({"pairs": [], "srcCount": src_count} if want_partial
                        else PairsResult([]))
            if (self.batcher is not None
                    and len(ctx.shards) <= self._REDUCE_SHARD_MAX):
                # dense TopN joins the coalescing window: concurrent
                # requests over the same resident plane share one
                # program and one read (unfiltered requests dedupe
                # outright; the int32 device reduce needs the same
                # shard bound as _plane_totals).  Both reads enqueue
                # BEFORE either wait, so a tanimoto request pays one
                # collection window, not two in series.  A delta-dirty
                # plane (r15 ingest) answers base⊕delta in-window.
                if need_row_counts:
                    h1 = self.batcher.enqueue_rowcounts(
                        ps.plane, filter_words, delta=ps.delta,
                        deadline=self._query_deadline())
                    h2 = self.batcher.enqueue_rowcounts(
                        ps.plane, delta=ps.delta,
                        deadline=self._query_deadline())
                    totals = self.batcher.wait(h1)[:ps.n_rows]
                    row_totals = self.batcher.wait(h2)[:ps.n_rows]
                else:
                    # single-read TopN (the common shape) goes through
                    # the blocking submit so a solo request rides the
                    # width-1 fast lane (r20 satellite: inline
                    # dispatch, no window formation) — under
                    # concurrency it lands in the window and dedupes
                    # exactly like the enqueue form
                    totals = self.batcher.submit_rowcounts(
                        ps.plane, filter_words, delta=ps.delta,
                        deadline=self._query_deadline())[:ps.n_rows]
            elif ps.delta is not None:
                counts = self.fused.run_rowcounts_delta(
                    ps.plane, ps.delta, filter_words=filter_words,
                    reduce=False)
                totals = kernels.shard_totals(counts)[:ps.n_rows]
                if need_row_counts:
                    row_totals = kernels.shard_totals(
                        self.fused.run_rowcounts_delta(
                            ps.plane, ps.delta,
                            reduce=False))[:ps.n_rows]
            else:
                counts = kernels.row_counts(ps.plane, filter_words)
                totals = kernels.shard_totals(counts)[:ps.n_rows]
                if need_row_counts:
                    row_totals = kernels.shard_totals(
                        kernels.row_counts(ps.plane, None))[:ps.n_rows]
            all_rows = ps.row_ids
        elif filter_words is None:
            # unfiltered: row cardinalities are host truth (directory
            # sums + overlay) — exact, zero device work
            all_rows, totals = self._host_row_cards(ctx, field)
            if len(all_rows) == 0:
                return PairsResult([])
        elif (est > self.planes.budget  # while the dense plane builds,
              # stream — don't ALSO build sparse residency for a field
              # about to be dense-resident
              and self.planes.sparse_bytes(field, VIEW_STANDARD,
                                           ctx.shards)
              <= self.planes.budget):
            from pilosa_tpu.engine import sparse as sparsek
            ss = self.planes.sparse_plane(ctx.index.name, field,
                                          VIEW_STANDARD, ctx.shards)
            if ss.n_rows == 0:
                return ({"pairs": [], "srcCount": src_count} if want_partial
                        else PairsResult([]))
            if (n is not None and tanimoto is None and not want_partial
                    and call.args.get("ids") is None
                    and call.args.get("attrName") is None):
                # plain TopN(n, filter): device top_k, read only k pairs
                # instead of the full (possibly millions-long) counts
                k = min(int(n), ss.n_rows)
                k_pad = min(ss.n_rows_pad,
                            1 << max(0, (k - 1).bit_length()))
                if ss.mesh is not None:
                    vals, slots = sparsek.topn_sparse_meshed(
                        ss.mesh, ss.axis, filter_words, ss.word_idx,
                        ss.mask, ss.row_ptr, k_pad)
                else:
                    vals, slots = sparsek.topn_sparse(
                        filter_words, ss.word_idx, ss.mask, ss.row_ptr,
                        k_pad)
                vals = np.asarray(vals)[:k]
                slots = np.asarray(slots)[:k]
                live = vals > 0
                row_ids = ss.row_ids[slots[live]]
                vals = vals[live]
                if field.options.keys and ctx.translate_output:
                    log = self.translate.rows(ctx.index.name, field.name)
                    return PairsResult(
                        [Pair(key=k_, count=int(c)) for k_, c in
                         zip(log.keys_of(row_ids, strict=False), vals)])
                return PairsResult([Pair(id=int(r), count=int(c))
                                    for r, c in zip(row_ids, vals)])
            if ss.mesh is not None:
                counts = sparsek.sparse_row_counts_meshed(
                    ss.mesh, ss.axis, filter_words, ss.word_idx,
                    ss.mask, ss.row_ptr)
            else:
                counts = sparsek.sparse_row_counts(
                    filter_words, ss.word_idx, ss.mask, ss.row_ptr)
            totals = np.asarray(counts).astype(np.int64)[:ss.n_rows]
            all_rows = ss.row_ids
            if need_row_counts:
                row_totals = ss.row_cards  # host truth, no second pass
        else:
            block = max(64, int(self.planes.budget
                                // (len(ctx.shards) * WORDS_PER_SHARD * 4
                                    * 4)))  # /4: chunk + staging headroom
            parts_rows, parts_totals, parts_row_totals = [], [], []
            for chunk_rows, chunk_plane in self.planes.iter_row_blocks(
                    field, VIEW_STANDARD, ctx.shards, block):
                ctx.check_deadline()  # streaming can run for minutes
                counts = kernels.row_counts(chunk_plane, filter_words)
                parts_totals.append(
                    kernels.shard_totals(counts)[:len(chunk_rows)])
                if need_row_counts:
                    parts_row_totals.append(kernels.shard_totals(
                        kernels.row_counts(chunk_plane, None))
                        [:len(chunk_rows)])
                parts_rows.append(chunk_rows)
            if not parts_rows:
                return ({"pairs": [], "srcCount": src_count} if want_partial
                        else PairsResult([]))
            all_rows = np.concatenate(parts_rows)
            totals = np.concatenate(parts_totals)
            if need_row_counts:
                row_totals = np.concatenate(parts_row_totals)
        ids_arg = call.args.get("ids")
        attr_name = call.args.get("attrName")
        if attr_name is not None:
            # restrict to rows whose attr matches (reference:
            # ``fragment.top`` attrName/attrValue filtering)
            ids_arg = list(ids_arg or []) + field.row_attrs.find_ids(
                str(attr_name), call.args.get("attrValue"))
            if not ids_arg:
                return PairsResult([])
        if ids_arg is not None:
            wanted = {int(r) for r in ids_arg}
            keep = np.array([int(r) in wanted for r in all_rows])
            totals = np.where(keep, totals, 0)
        if want_partial:
            live = row_totals > 0
            return {"pairs": [
                {"id": int(r), "count": int(c), "rowCount": int(rc)}
                for r, c, rc in zip(all_rows[live], totals[live],
                                    row_totals[live])],
                "srcCount": src_count}
        if tanimoto is not None:
            union = src_count + row_totals - totals
            keep = (totals > 0) & (100.0 * totals >= tanimoto * union)
            totals = np.where(keep, totals, 0)
        k = len(all_rows) if n is None else min(int(n), len(all_rows))
        slots = np.argsort(-totals, kind="stable")[:k]
        vals = totals[slots]
        live = vals > 0
        row_ids = all_rows[slots[live]]
        vals = vals[live]
        if field.options.keys and ctx.translate_output:
            log = self.translate.rows(ctx.index.name, field.name)
            return PairsResult([Pair(key=log.key_of(int(r)), count=int(c))
                                for r, c in zip(row_ids, vals)])
        return PairsResult([Pair(id=int(r), count=int(c))
                            for r, c in zip(row_ids, vals)])

    # -- Extract ------------------------------------------------------------

    # Extract materializes per-column values; wrap wide selections in
    # Limit(...) — the cap keeps one call from expanding a billion rows
    MAX_EXTRACT_COLUMNS = 100_000

    def _execute_extract(self, ctx: _Ctx, call: Call) -> ExtractResult:
        """Extract(bitmap, Rows(f), ...): per selected column, each
        field's value(s) (reference: v2 ``executeExtract`` /
        ``ExtractedTable``).  Set-like fields answer with ONE device
        gather program (``kernels.column_bits``) over the resident
        plane; BSI fields read per-column host values."""
        if not call.children:
            raise ExecutionError("Extract: bitmap filter child required")
        flt, *field_calls = call.children
        bad = [c.name for c in field_calls if c.name != "Rows"]
        if bad:
            raise ExecutionError(
                f"Extract: field children must be Rows calls, got {bad}")
        fields = []
        for fc in field_calls:
            fname = fc.args.get("_field") or fc.args.get("field")
            if fname is None:
                raise ExecutionError("Extract: Rows child missing field")
            fields.append(self._field(ctx, str(fname)))

        words = self._fused_bitmap(ctx, flt)
        # per-shard popcounts first (one tiny read): enforce the cap
        # BEFORE materializing anything, then pull only the non-empty
        # shard rows — an Extract filter is sparse by contract, and a
        # full-bitmap read moves 125 MB to the host at 954 shards
        counts = np.asarray(_shard_popcounts(words))
        total = int(counts.sum())
        if total > self.MAX_EXTRACT_COLUMNS:
            raise ExecutionError(
                f"Extract: {total} columns selected; cap is "
                f"{self.MAX_EXTRACT_COLUMNS} — narrow the filter or wrap "
                "it in Limit(...)")
        nz = np.nonzero(counts)[0]
        col_parts = []
        if len(nz):
            host_rows = np.asarray(words[jnp.asarray(nz)])
            for j, si in enumerate(nz):
                si = int(si)
                if ctx.shards[si] == PAD_SHARD:
                    continue
                col_parts.append((si, ctx.shards[si],
                                  unpack_columns(host_rows[j])))
        columns = (np.concatenate(
            [offs.astype(np.uint64) + np.uint64(s * SHARD_WIDTH)
             for _, s, offs in col_parts])
            if col_parts else np.empty(0, np.uint64))

        per_field = [self._extract_field(ctx, f, col_parts, len(columns))
                     for f in fields]
        if ctx.index.keys and ctx.translate_output:
            log = self.translate.columns(ctx.index.name)
            col_out = log.keys_of(columns, strict=False)
        else:
            col_out = [int(c) for c in columns]
        return ExtractResult(
            field_specs=[(f.name, f.options.type) for f in fields],
            columns=[(c, [vals[i] for vals in per_field])
                     for i, c in enumerate(col_out)])

    def _extract_field(self, ctx: _Ctx, field: Field, col_parts,
                       n_cols: int) -> list:
        """One field's value per selected column (list of length n_cols).
        col_parts: [(si, shard, offsets ascending)]."""
        opts = field.options
        if opts.type in BSI_TYPES:
            return self._extract_bsi(ctx, field, col_parts, n_cols)
        out: list = [None] * n_cols
        key_log = (self.translate.rows(ctx.index.name, field.name)
                   if opts.keys and ctx.translate_output else None)
        est = self.planes.plane_bytes(field, VIEW_STANDARD, ctx.shards)
        if est > self.planes.budget:
            # huge-cardinality field: per-column inverted check on host
            # (generation-cached CSR scan) instead of a plane build
            view = field.view(VIEW_STANDARD)
            pos = 0
            for _, s, offs in col_parts:
                frag = view.fragment(s) if view is not None else None
                for off in offs:
                    rows = (frag.rows_containing(int(off))
                            if frag is not None else np.empty(0, np.uint64))
                    out[pos] = self._extract_cell(opts, key_log, rows)
                    pos += 1
            return out
        # set-like: membership of each column in every row, one device
        # gather program per shard plane
        ps = self.planes.field_plane(ctx.index.name, field, VIEW_STANDARD,
                                     ctx.shards)
        pos = 0
        for si, s, offs in col_parts:
            k = len(offs)
            if ps.n_rows == 0:
                rows_by_col = [np.empty(0, np.int64)] * k
            else:
                # pow2-pad k: one compiled program per bucket, not per
                # distinct selected-column count (the CountBatcher
                # recompile-storm lesson)
                k_pad = 1 << max(0, (k - 1).bit_length())
                word_idx = np.zeros(k_pad, np.int32)
                bit_idx = np.zeros(k_pad, np.uint32)
                word_idx[:k] = (offs.astype(np.int64) >> 5)
                bit_idx[:k] = (offs.astype(np.int64) & 31)
                key = (("colbits", ps.plane.shape, k_pad), "extract")
                fn = self.fused._cached(
                    key, lambda: kernels.column_bits)
                bits = np.asarray(fn(ps.plane[si:si + 1],
                                     jnp.asarray(word_idx),
                                     jnp.asarray(bit_idx)))[0]  # (R, k_pad)
                rows_by_col = [ps.row_ids[np.nonzero(
                    bits[:ps.n_rows, j])[0]] for j in range(k)]
            for j in range(k):
                out[pos] = self._extract_cell(opts, key_log,
                                              rows_by_col[j])
                pos += 1
        return out

    def _extract_bsi(self, ctx: _Ctx, field: Field, col_parts,
                     n_cols: int) -> list:
        """BSI column values straight off the resident bit-plane: ONE
        ``column_bits_grouped`` program gathers every selected column's
        exists/sign/magnitude bits across all shards (VERDICT r2 #6 —
        the previous form walked ``field.value`` per column on host:
        O(cols·depth) fragment probes at the 100k column cap)."""
        from pilosa_tpu.engine.bsi import EXISTS_ROW, OFFSET_ROW, SIGN_ROW
        opts = field.options
        depth = opts.bit_depth
        ps = self.planes.bsi_plane(ctx.index.name, field, ctx.shards)
        k_max = max((len(offs) for _, _, offs in col_parts), default=0)
        if k_max == 0:
            return [None] * n_cols
        # pow2-pad the per-shard column count: one compiled program per
        # (plane shape, bucket), not per distinct selection size
        k_pad = 1 << max(0, (k_max - 1).bit_length())
        n_sh = ps.plane.shape[0]
        word_idx = np.zeros((n_sh, k_pad), np.int32)
        bit_idx = np.zeros((n_sh, k_pad), np.uint32)
        for si, _, offs in col_parts:
            k = len(offs)
            word_idx[si, :k] = offs.astype(np.int64) >> 5
            bit_idx[si, :k] = offs.astype(np.int64) & 31
        key = (("colbits-grouped", ps.plane.shape, k_pad), "extract")
        fn = self.fused._cached(key, lambda: kernels.column_bits_grouped)
        bits = np.asarray(fn(ps.plane, jnp.asarray(word_idx),
                             jnp.asarray(bit_idx)))  # (S, R, k_pad)
        weights = (np.int64(1) << np.arange(depth, dtype=np.int64))
        out: list = [None] * n_cols
        pos = 0
        for si, _, offs in col_parts:
            k = len(offs)
            b = bits[si, :, :k].astype(np.int64)
            mags = weights @ b[OFFSET_ROW:OFFSET_ROW + depth]
            np.negative(mags, out=mags, where=b[SIGN_ROW] != 0)
            exists = b[EXISTS_ROW] != 0
            for j in range(k):
                if exists[j]:
                    out[pos] = field.from_stored(int(mags[j]) + opts.base)
                pos += 1
        return out

    @staticmethod
    def _extract_cell(opts, key_log, rows):
        """One (column, field) cell from the column's member row ids."""
        if opts.type == "bool":
            return bool(rows[-1]) if len(rows) else None
        if opts.type == "mutex":
            if not len(rows):
                return None
            r = int(rows[0])
            return key_log.key_of(r) if key_log else r
        if key_log is not None:
            return key_log.keys_of(rows, strict=False)
        return [int(r) for r in rows]

    def _host_row_cards(self, ctx: _Ctx, field: Field):
        """Exact per-row cardinalities merged across shards from host
        fragment metadata (directory sums + overlay) — the unfiltered
        TopN answer with zero device work."""
        from pilosa_tpu.exec.planes import merge_row_cards
        view = field.view(VIEW_STANDARD)
        frags = []
        if view is not None:
            for s in ctx.shards:
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is not None:
                    frags.append(frag)
        return merge_row_cards(frags)

    # -- Rows ---------------------------------------------------------------

    def _execute_rows(self, ctx: _Ctx, call: Call) -> RowIdsResult:
        fname = call.args.get("_field") or call.args.get("field")
        if fname is None:
            raise ExecutionError("Rows: missing field argument")
        field = self._field(ctx, str(fname))
        rows = self._rows_of(ctx, field, call)
        if field.options.keys and ctx.translate_output:
            log = self.translate.rows(ctx.index.name, field.name)
            return RowIdsResult(keys=log.keys_of(rows, strict=False))
        return RowIdsResult(rows=rows)

    def _rows_of(self, ctx: _Ctx, field: Field, call: Call) -> np.ndarray:
        """Row IDs with ≥1 bit, honoring column=, from=/to= (time
        fields: only rows seen in the range's minimal view cover),
        previous=, limit=."""
        frm = call.args.get("from")
        to = call.args.get("to")
        if frm is not None or to is not None:
            # time filter: the candidate views are the range's minimal
            # cover instead of the all-time standard view (r23) —
            # GroupBy time filters inherit this via its _rows_of calls
            views = [field.views.get(v)
                     for v in self._time_cover_views(field, frm, to)]
            views = [v for v in views if v is not None]
        else:
            views = ([field.standard_view()]
                     if field.standard_view() is not None else [])
        column = call.args.get("column")
        if column is not None:
            # column filter needs the bits: check membership per shard
            # on host (one column touches at most one shard)
            col_id = self._col_id(ctx, column, create=False)
            if col_id is None:
                return np.empty(0, np.uint64)
            shard, off = col_id // SHARD_WIDTH, col_id % SHARD_WIDTH
            if shard not in ctx.shards:
                return np.empty(0, np.uint64)
            # vectorized inverted check (generation-cached) instead of a
            # per-row contains() loop — 100k-row fields answer in ms
            row_set: set[int] = set()
            for view in views:
                frag = view.fragment(shard)
                if frag is not None:
                    row_set.update(int(r)
                                   for r in frag.rows_containing(off))
            rows = np.array(sorted(row_set), dtype=np.uint64)
        else:
            # live rows per view, memoised against the view's fragment
            # generations: unchanged data walks no fragment.  A memo
            # array is read-only; the filters below copy or slice it
            parts = [self.planes.live_rows(field, view.name, ctx.shards)
                     for view in views]
            rows = (parts[0] if len(parts) == 1
                    else np.unique(np.concatenate(parts)) if parts
                    else np.empty(0, np.uint64))
        like = call.args.get("like")
        if like is not None:
            # SQL-style pattern over row KEYS (reference: Rows like=,
            # FeatureBase era): % = any run, _ = one char.  One batched
            # key lookup + one compiled regex over all rows (not a
            # per-row key_of + fnmatch pair).
            if not field.options.keys:
                raise ExecutionError("Rows: like= requires a keyed field")
            import fnmatch
            import re
            pattern = (str(like).replace("*", "[*]").replace("?", "[?]")
                       .replace("%", "*").replace("_", "?"))
            rx = re.compile(fnmatch.translate(pattern))
            log = self.translate.rows(ctx.index.name, field.name)
            keys = log.keys_of(rows, strict=False)
            keep = [k is not None and rx.match(k) is not None for k in keys]
            rows = rows[np.array(keep, dtype=bool)] if len(rows) else rows
        prev = call.args.get("previous")
        if prev is not None:
            prev_id = self._row_id(ctx, field, prev, create=False)
            if prev_id is not None:
                rows = rows[rows > prev_id]
        limit = call.args.get("limit")
        if limit is not None:
            rows = rows[: int(limit)]
        return rows

    def _column_bitmap(self, ctx: _Ctx, col_id: int) -> jax.Array:
        host = np.zeros((len(ctx.shards), WORDS_PER_SHARD), dtype=np.uint32)
        shard, off = col_id // SHARD_WIDTH, col_id % SHARD_WIDTH
        for si, s in enumerate(ctx.shards):
            if s == shard:
                host[si, off >> 5] = np.uint32(1) << np.uint32(off & 31)
        return self.planes.place(host)

    # -- GroupBy ------------------------------------------------------------

    _GROUPBY_AGGS = {"Sum": "sum", "Count": None, "Min": "minmax",
                     "Max": "minmax"}

    @staticmethod
    def parse_having(having, agg_name: str | None):
        """``having=Condition(count > 10)`` / ``Condition(sum < 0)``
        (v2 surface: post-aggregate group filtering in
        ``executeGroupBy``).  Returns (metric, Condition)."""
        if not isinstance(having, Call) or having.name != "Condition":
            raise ExecutionError("GroupBy: having= must be Condition(...)")
        conds = [(k, v) for k, v in having.args.items()
                 if isinstance(v, Condition)]
        if len(conds) != 1 or conds[0][0] not in ("count", "sum"):
            raise ExecutionError("GroupBy: having supports exactly one "
                                 "condition on count or sum")
        metric, cond = conds[0]
        if metric == "sum" and agg_name != "Sum":
            raise ExecutionError(
                "GroupBy: having on sum requires aggregate=Sum(...)")
        return metric, cond

    def _execute_groupby(self, ctx: _Ctx, call: Call) -> GroupCountsResult:
        """Whole combination tree in ONE device program (O(1) dispatches
        regardless of level count — ``exec.groupby``), replacing the
        reference's per-combination recursion
        (``executor.go#executeGroupByShard``)."""
        from pilosa_tpu.exec import groupby as gb

        rows_calls = [c for c in call.children if c.name == "Rows"]
        if not rows_calls:
            raise ExecutionError("GroupBy: at least one Rows child required")
        filter_words = None
        flt = call.args.get("filter")
        if isinstance(flt, Call):
            filter_words = self._bitmap(ctx, flt)
        agg = call.args.get("aggregate")
        agg_field = None
        agg_name = None
        minmax_host = False
        if isinstance(agg, Call):
            if agg.name not in self._GROUPBY_AGGS:
                raise ExecutionError(
                    "GroupBy: aggregate must be Sum/Count/Min/Max")
            agg_name = agg.name
            if agg_name != "Count":
                aname = agg.args.get("field") or agg.args.get("_field")
                agg_field = self._field(ctx, str(aname))
                if agg_field.options.type not in BSI_TYPES:
                    raise ExecutionError(
                        f"GroupBy: aggregate field {aname!r} is not BSI")
                if (agg_name in ("Min", "Max")
                        and agg_field.options.bit_depth > gb.MINMAX_MAX_DEPTH):
                    # graceful fallback (r20 satellite): the in-program
                    # signed int32 reconstruction caps at 30 bits, so
                    # deeper fields run the combination counts on
                    # device and finish Min/Max per surviving group on
                    # the exact host path (bit descent + python-int
                    # reconstruction) instead of refusing the query
                    minmax_host = True
        if len(ctx.shards) > gb.MAX_SHARDS:
            raise ExecutionError(
                f"GroupBy: more than {gb.MAX_SHARDS} shards per node "
                "unsupported")

        # each level's rows, cut to those the filter reaches (a group
        # with a zero count is never returned, so the answer is the
        # same): a dense level by the row counts of its plane under the
        # filter, a coded level by the histogram of its codes.  A coded
        # level's reached rows are derived on the device in blocks
        # (``_groupby_chunks``).
        specs = []  # (field, row_ids, PlaneSet | CodeSet)
        pruned = 0
        with _metrics.span("groupby.reach"):
            for rc in rows_calls:
                f = self._field(ctx, str(rc.args.get("_field") or
                                         rc.args.get("field")))
                rows = self._rows_of(ctx, f, rc)
                if len(rows) == 0:
                    return GroupCountsResult([])  # no combinations
                # a level of at most CODED_ROWS_OVER rows is read dense
                # unless its field holds a code already (no sweep)
                code = (self.planes.code_plane(ctx.index.name, f, ctx.shards)
                        if len(rows) > CODED_ROWS_OVER or self.planes.has_code(
                            ctx.index.name, f, ctx.shards) else None)
                ps = code
                if code is not None:
                    counts = (self.planes.code_counts(code, filter_words)
                              if filter_words is not None else None)
                else:
                    # plane over the selected rows only — GroupBy
                    # memory scales with the Rows() selections, not
                    # field cardinality
                    ps = self.planes.rows_plane(ctx.index.name, f,
                                                VIEW_STANDARD, rows,
                                                ctx.shards)
                    counts = (np.asarray(gb.level_counts(ps.plane,
                                                         filter_words))
                              if filter_words is not None else None)
                if counts is not None:
                    slots = np.array([ps.slot_of.get(int(r), -1)
                                      for r in rows], np.int64)
                    keep = (slots >= 0) & (counts[slots] > 0)
                    pruned += len(rows) - int(keep.sum())
                    if not keep.all():
                        rows = rows[keep]
                        if len(rows) and code is None:
                            ps = self.planes.take_rows(ps, rows)
                if len(rows) == 0:
                    self.stats.count("groupby_rows_pruned_total", pruned)
                    return GroupCountsResult([])
                specs.append((f, rows, ps))
        self.stats.count("groupby_rows_pruned_total", pruned)
        self.stats.count("groupby_combinations_total",
                         math.prod(len(rows) for _, rows, _ in specs))
        # delta-aware agg plane (r20): sustained BSI ingest absorbs
        # into the overlay and the GroupBy program merges base⊕delta
        # in-program — no fold on the query path.  The depth>30 host
        # fallback needs a CLEAN plane (its bit descent reads the
        # plane directly), so it folds instead.
        agg_plane = None
        if agg_field is not None:
            agg_plane = (self.planes.bsi_plane(ctx.index.name,
                                               agg_field, ctx.shards)
                         if minmax_host else
                         self.planes.bsi_plane_delta(
                             ctx.index.name, agg_field, ctx.shards))

        having = call.args.get("having")
        having_metric = having_cond = None
        if having is not None:
            having_metric, having_cond = self.parse_having(having, agg_name)

        limit = call.args.get("limit")
        # previous=[rowID, ...] pages past an exact combination
        # (reference: GroupBy previous= paging); groups generate in
        # lexicographic row-id order, so skip while combo <= previous
        prev = call.args.get("previous")
        prev_tuple = (tuple(int(r) for r in prev)
                      if isinstance(prev, list) else None)
        if prev_tuple is not None and len(prev_tuple) != len(specs):
            raise ExecutionError(
                "GroupBy: previous= must list one row per Rows call")

        base = agg_field.options.base if agg_field is not None else 0
        # columnar accumulation: per block, fancy-index the surviving
        # (combo, last-row) cells straight into row-id/count/agg arrays.
        # The old per-group object loop was ~60% of warm GroupBy latency
        # at 125k groups (reference builds []GroupCount eagerly in
        # executor.go#executeGroupBy; we materialize objects lazily at
        # the result edge — see GroupCountsResult).
        acc_rows: list[np.ndarray] = []
        acc_counts: list[np.ndarray] = []
        acc_aggs: list[np.ndarray] = []
        acc_mask: list[np.ndarray] = []
        n_levels = len(specs)
        agg_kind = (None if minmax_host
                    else self._GROUPBY_AGGS.get(agg_name))
        submit = gb.run_block
        if (self.batcher is not None
                and len(ctx.shards) <= self._REDUCE_SHARD_MAX):
            # GroupBy blocks ride the window machinery (r20): the
            # flattened block program joins the collection window's
            # dispatch pool + packed readback alongside concurrent
            # Counts/aggregates, and identical concurrent GroupBys
            # (same planes, same combination block) dedupe to ONE
            # program via the digest
            import hashlib
            deadline = self._query_deadline()

            def submit(pl, ci, lp, fw, ap, agg, ad):
                _stage("plan")
                # ci arrives as the HOST combo array (see iter_blocks)
                # — the digest costs no device round trip
                meta = (math.prod(ci.shape[:-1]), int(lp.shape[1]),
                        int(ap.shape[1]) - 2 if ap is not None else 0)
                digest = hashlib.blake2b(
                    ci.tobytes(), digest_size=8).digest()
                return self.batcher.submit_groupby(
                    pl, ci, lp, fw, ap, agg, meta, digest, delta=ad,
                    deadline=deadline)

        def run(pl, ci, lp, fw, ap, agg, ad):
            self.stats.count("groupby_blocks_total", 1,
                             form=gb.block_form(pl, agg))
            return submit(pl, ci, lp, fw, ap, agg, ad)
        chunks = self._groupby_chunks(specs)
        # more than one block of a coded level: the blocks' groups
        # interleave, so they are sorted once all are in
        stream = len(chunks) == 1
        for chunk in chunks:
            specs = [(f, rows, ps if isinstance(ps, PlaneSet)
                      else self.planes.code_rows(ps, rows))
                     for f, rows, ps in chunk]
            if self._groupby_block_loop(
                    ctx, specs, filter_words, agg_plane, agg_kind, agg_name,
                    minmax_host, base, having_metric, having_cond,
                    prev_tuple, limit if stream else None, run,
                    acc_rows, acc_counts, acc_aggs, acc_mask):
                break
        if not acc_rows:
            return GroupCountsResult([])
        row_ids = np.concatenate(acc_rows)
        counts = np.concatenate(acc_counts)
        agg_col = np.concatenate(acc_aggs) if acc_aggs else None
        mask_col = np.concatenate(acc_mask) if acc_mask else None
        if not stream:
            order = np.lexsort(row_ids.T[::-1])
            row_ids, counts = row_ids[order], counts[order]
            if agg_col is not None:
                agg_col, mask_col = agg_col[order], mask_col[order]
        if limit is not None:
            row_ids = row_ids[: int(limit)]
            counts = counts[: int(limit)]
            if agg_col is not None:
                agg_col = agg_col[: int(limit)]
                mask_col = mask_col[: int(limit)]
        # keyed fields translate ONCE per level over the unique row ids
        # (was one KeyLog lookup per group member)
        row_keys: list = [None] * n_levels
        for lvl, (f, _, _) in enumerate(specs):
            if f.options.keys and ctx.translate_output:
                klog = self.translate.rows(ctx.index.name, f.name)
                uniq, inv = np.unique(row_ids[:, lvl], return_inverse=True)
                # strict=False: an id the translate log has not seen yet
                # falls back to its numeric form (matches the Rows()
                # output path, _execute_rows)
                keys = klog.keys_of(uniq, strict=False)
                row_keys[lvl] = [keys[i] for i in inv]
        return GroupCountsResult(
            fields=[f.name for f, _, _ in specs], row_ids=row_ids,
            row_keys=row_keys if any(k is not None for k in row_keys)
            else None,
            counts=counts, aggs=agg_col, agg_mask=mask_col)

    # bytes of a GroupBy's coded levels derived at once: past it a
    # coded level's rows go in blocks (``_groupby_chunks``)
    GROUPBY_CODED_BYTES = 2 << 30

    def _groupby_chunks(self, specs: list) -> list:
        """The GroupBy's levels as blocks to run in turn: each coded
        level's rows cut so that the rows derived at once from the
        codes stay within ``GROUPBY_CODED_BYTES``, every combination
        of blocks in lexicographic order; one block when they fit."""
        from itertools import product
        coded = [i for i, (_, _, ps) in enumerate(specs)
                 if not isinstance(ps, PlaneSet)]
        options = [[spec] for spec in specs]
        for i in coded:
            f, rows, code = specs[i]
            row_bytes = len(code.shards) * WORDS_PER_SHARD * 4
            per = max(1, self.GROUPBY_CODED_BYTES
                      // (len(coded) * row_bytes))
            options[i] = [(f, rows[j:j + per], code)
                          for j in range(0, len(rows), per)]
        return [list(c) for c in product(*options)]

    def _groupby_block_loop(self, ctx: _Ctx, specs: list, filter_words,
                            agg_plane, agg_kind, agg_name, minmax_host,
                            base, having_metric, having_cond, prev_tuple,
                            limit, run, acc_rows, acc_counts, acc_aggs,
                            acc_mask) -> bool:
        """The combination blocks over one set of level planes, their
        groups appended to the ``acc_*`` columns.  True once ``limit``
        groups are in."""
        from pilosa_tpu.exec import groupby as gb
        n_levels = len(specs)
        total = sum(len(r) for r in acc_rows)
        last_f, last_rows, last_ps = specs[-1]
        last_slots = [last_ps.slot_of[int(r)] for r in last_rows]
        last_rows_arr = np.asarray(last_rows, np.uint64)
        for combo_rows, out in gb.iter_blocks(
                specs, filter_words,
                None if minmax_host else agg_plane, agg_kind,
                limited=limit is not None, run=run,
                agg_delta=(None if minmax_host or agg_plane is None
                           else agg_plane.delta)):
            ctx.check_deadline()  # large combination trees stream
            _stage("assemble")
            counts = np.asarray(out["counts"])  # (C, slots)
            slots = np.asarray(last_slots, np.int64)
            sub = counts[:, slots].astype(np.int64)  # (C, L)
            # per-group aggregates computed VECTORIZED over the whole
            # block (the per-group Python bit-descent walked O(depth)
            # ints per group — a 125k-group GroupBy spent seconds there)
            aggs = None
            agg_ok = None
            if agg_name == "Count":
                aggs = sub
            elif agg_name == "Sum":
                pos = np.asarray(out["pos"])[:, slots].astype(np.int64)
                neg = np.asarray(out["neg"])[:, slots].astype(np.int64)
                acnt = np.asarray(out["cnt"])[:, slots].astype(np.int64)
                depth = pos.shape[-1]
                # int64 matmul only while provably exact: the weighted
                # bit sums are bounded by max_count·2^(depth+1) and the
                # base term by |base|·max_cnt.  Past the bound (deep
                # BSI × huge groups) fall back to exact Python big-int
                # accumulation, matching Sum's host-finish policy.
                max_count = int(max(np.abs(pos).max(initial=0),
                                    np.abs(neg).max(initial=0)))
                bound = (max_count << (depth + 1)) + \
                    abs(int(base)) * int(np.abs(acnt).max(initial=0))
                if depth <= 62 and bound < (1 << 62):
                    weights = np.int64(1) << np.arange(depth,
                                                       dtype=np.int64)
                    aggs = (pos - neg) @ weights + base * acnt
                else:
                    aggs = np.empty(pos.shape[:2], dtype=object)
                    for c in range(pos.shape[0]):
                        for li in range(pos.shape[1]):
                            aggs[c, li] = sum(
                                (int(pos[c, li, b]) - int(neg[c, li, b]))
                                << b for b in range(depth)) \
                                + base * int(acnt[c, li])
            elif agg_name in ("Min", "Max") and not minmax_host:
                key = "min" if agg_name == "Min" else "max"
                aggs = (np.asarray(out[key])[:, slots].astype(np.int64)
                        + base)
                agg_ok = np.asarray(out[key + "_cnt"])[:, slots] > 0
            keep = sub > 0
            if having_cond is not None:
                if having_metric == "count":
                    keep = keep & having_cond.matches_array(sub)
                elif aggs is None:
                    keep = np.zeros_like(keep)
                else:
                    # a group with no aggregate value (Min/Max over an
                    # empty cell) cannot pass a sum condition
                    if agg_ok is not None:
                        keep = keep & agg_ok
                    keep = keep & having_cond.matches_array(aggs)
            c_idx, l_idx = np.nonzero(keep)
            if c_idx.size == 0:
                continue
            rows_mat = np.empty((c_idx.size, n_levels), np.uint64)
            if n_levels > 1:
                rows_mat[:, :-1] = combo_rows[c_idx]
            rows_mat[:, -1] = last_rows_arr[l_idx]
            if prev_tuple is not None:
                after = _lex_gt(rows_mat, prev_tuple)
                if not after.all():
                    rows_mat = rows_mat[after]
                    c_idx, l_idx = c_idx[after], l_idx[after]
                    if c_idx.size == 0:
                        continue
            acc_rows.append(rows_mat)
            acc_counts.append(sub[c_idx, l_idx])
            if minmax_host:
                host_vals, host_ok = self._host_group_minmax(
                    ctx, specs, filter_words, agg_plane, rows_mat,
                    want_min=agg_name == "Min")
                acc_aggs.append(host_vals + base)
                acc_mask.append(host_ok)
            elif aggs is not None:
                acc_aggs.append(aggs[c_idx, l_idx])
                acc_mask.append(agg_ok[c_idx, l_idx]
                                if agg_ok is not None
                                else np.ones(c_idx.size, bool))
            total += c_idx.size
            if limit is not None and total >= int(limit):
                return True
        return False

    def _host_group_minmax(self, ctx: _Ctx, specs, filter_words,
                           agg_plane, rows_mat: np.ndarray,
                           want_min: bool):
        """Exact host Min/Max per surviving group for BSI depths past
        ``groupby.MINMAX_MAX_DEPTH`` (r20 satellite): the group's
        column bitmap intersects on device, then the full-depth bit
        descent + python-int reconstruction answers exactly — one
        dispatch per group, the correctness path for depth > 30
        fields, not the serving spine."""
        vals: list = []
        oks = np.zeros(len(rows_mat), bool)
        for g in range(len(rows_mat)):
            words = filter_words
            for lvl, (_f, _rows, ps) in enumerate(specs):
                row = ps.plane[:, ps.slot_of[int(rows_mat[g, lvl])], :]
                words = row if words is None \
                    else kernels.intersect(words, row)
            tuples = bsik.min_max(agg_plane.plane, words)
            live = [(mn, mc, mx, xc) for mn, mc, mx, xc in tuples
                    if (mc if want_min else xc) > 0]
            if not live:
                vals.append(0)
                continue
            vals.append(min(mn for mn, *_ in live) if want_min
                        else max(mx for _, _, mx, _ in live))
            oks[g] = True
        return np.array(vals), oks

    # -- writes -------------------------------------------------------------

    def _execute_set(self, ctx: _Ctx, call: Call) -> bool:
        col = call.args.get("_col")
        if col is None:
            raise ExecutionError("Set: missing column argument")
        col_id = self._col_id(ctx, col, create=True)
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError("Set: missing field=value argument")
        fname, value = hit
        field = self._field(ctx, fname)
        if field.options.type in BSI_TYPES:
            changed = field.set_value(col_id, value)
        else:
            row_id = self._row_id(ctx, field, value, create=True)
            ts = call.args.get("_timestamp")
            changed = field.set_bit(
                row_id, col_id,
                parse_pql_time(ts) if ts is not None else None)
        ctx.index.note_columns(np.array([col_id], np.uint64))
        return changed

    def _execute_clear(self, ctx: _Ctx, call: Call) -> bool:
        col = call.args.get("_col")
        if col is None:
            raise ExecutionError("Clear: missing column argument")
        col_id = self._col_id(ctx, col, create=False)
        if col_id is None:
            return False
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError("Clear: missing field argument")
        fname, value = hit
        field = self._field(ctx, fname)
        if field.options.type in BSI_TYPES:
            return field.clear_value(col_id)
        row_id = self._row_id(ctx, field, value, create=False)
        if row_id is None:
            return False
        return field.clear_bit(row_id, col_id)

    def _execute_clearrow(self, ctx: _Ctx, call: Call) -> bool:
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError("ClearRow: missing field=row argument")
        fname, value = hit
        field = self._field(ctx, fname)
        row_id = self._row_id(ctx, field, value, create=False)
        if row_id is None:
            return False
        view = field.standard_view()
        changed = 0
        if view is not None:
            for s in ctx.shards:
                if s == PAD_SHARD:
                    continue
                frag = view.fragment(s)
                if frag is not None:
                    changed += frag.clear_row(row_id)
        return changed > 0

    def _execute_setrowattrs(self, ctx: _Ctx, call: Call):
        """SetRowAttrs(f, row, k=v, ...) — reference: row AttrStore write
        (``executor.go#executeSetRowAttrs``)."""
        fname = call.args.get("_field")
        if fname is None:
            raise ExecutionError("SetRowAttrs: missing field")
        field = self._field(ctx, str(fname))
        row = call.args.get("_row")
        if row is None:
            raise ExecutionError("SetRowAttrs: missing row")
        row_id = self._row_id(ctx, field, row, create=True)
        attrs = {k: v for k, v in call.args.items()
                 if not k.startswith("_")}
        field.row_attrs.set_attrs(int(row_id), attrs)
        return None

    def _execute_setcolumnattrs(self, ctx: _Ctx, call: Call):
        col = call.args.get("_col")
        if col is None:
            raise ExecutionError("SetColumnAttrs: missing column")
        col_id = self._col_id(ctx, col, create=True)
        attrs = {k: v for k, v in call.args.items()
                 if not k.startswith("_")}
        ctx.index.column_attrs.set_attrs(int(col_id), attrs)
        return None

    def _execute_store(self, ctx: _Ctx, call: Call) -> bool:
        if len(call.children) != 1:
            raise ExecutionError("Store: exactly one bitmap child required")
        hit = _field_arg(call)
        if hit is None:
            raise ExecutionError("Store: missing field=row argument")
        fname, value = hit
        field = self._field(ctx, fname)
        row_id = self._row_id(ctx, field, value, create=True)
        words = np.asarray(self._bitmap(ctx, call.children[0]))
        view = field.standard_view(create=True)
        changed = False
        for si, s in enumerate(ctx.shards):
            if s == PAD_SHARD:
                continue
            frag = view.fragment(s, create=True)
            cols = unpack_columns(words[si]).astype(np.uint32)
            changed |= frag.set_row(row_id, cols)
        return changed
