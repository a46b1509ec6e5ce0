"""Cross-request coalescing: the concurrent serving spine.

Within-request batching (executor count runs) amortizes fixed
per-dispatch/per-read costs across one query string; this batcher does
the same ACROSS concurrent requests: server threads submit planned
work items, a collector waits a tiny window, and one fused program per
(kind, shape) group answers the whole batch with a single device read.

Motivation: every synchronous device->host read has a fixed cost
whatever its payload.  When reads SERIALIZE, N coalesced items pay
that cost once instead of N times — and the batcher funnels any number
of HTTP clients through ONE device stream.

r6 changes (the concurrency-gap work, ISSUE 1):

- **default-on with an ADAPTIVE window**: the window grows under queue
  pressure (concurrent submitters pile into one dispatch) and shrinks
  to zero when traffic is solo, so a lone request pays no collection
  wait.  ``count_batch_window=adaptive`` is the server default; a
  numeric value keeps the old fixed-window behavior, 0 disables.
- **every one-dispatch-one-read dense family coalesces**: Counts (any
  fusable tree, BSI conditions included), BSI Sum/Min/Max, whole-plane
  row counts (same-field Count batches and dense TopN — deduplicated:
  N concurrent requests over the SAME resident plane share one
  program and one read instead of stacking N copies of a multi-GB
  popcount), and Distinct presence scans (deduplicated likewise).

r12 changes (the roofline work, ISSUE 7):

- **selected-row counts** (``submit_selected``): the multi-query fused
  popcount — concurrent requests' row slots union into ONE gather +
  popcount pass over just those rows' memory;
- **batched readback**: every one-program kind dispatches async and
  the window's outputs pack into ONE device array read with ONE
  device->host transfer — the window pays the fixed per-read cost
  once total, not once per kind/shape group.

r17 changes (the solo-floor/roofline work, ISSUE 12):

- **pipelined readback**: the collector hands each dispatched window
  to a dedicated readback worker, so window N's device compute
  overlaps window N-1's packed read instead of serializing behind it
  (``pipeline_depth`` bounds run-ahead; ``dispatch_pipeline_depth`` /
  ``readback_overlap_ratio`` on /metrics);
- **solo fast lane**: a width-1 request with no queue pressure skips
  window formation and dispatches inline on the CALLER's thread over
  pre-bound operands (``solo_fastlane_hits_total``) — the attack on
  the one-RPC-per-query solo floor;
- **donated ping-pong chains**: the window and fast-lane dispatch
  paths pass retired output buffers back as donated scratch
  (``fused.PingPong``), so consecutive dispatches re-use two standing
  output slots instead of allocating per window, and the selcounts
  union gathers in SORTED slot order (ascending memory stride).

r18 changes (the self-healing pipeline, ISSUE 13):

- **deadline propagation**: every ``submit_*``/``enqueue_*`` carries
  the query's monotonic deadline; :meth:`wait` blocks with a BOUNDED
  timeout and raises ``QueryTimeoutError`` (naming the item's stage)
  on expiry, marking the item ABANDONED so the group's shared
  readback skips finishing it without disturbing co-batched answers.
  The solo fast lane checks the deadline before dispatching.
- **pipeline watchdog + window quarantine**: a monitor thread bounds
  each in-flight window's dispatch and readback age
  (``dispatch_watchdog_seconds``).  A stuck window is QUARANTINED:
  its unfinished items fail with a structured
  ``PipelineStalledError`` naming the stalled stage, its pipeline
  slot is reclaimed, and the wedged stage worker is superseded by a
  fresh thread (the zombie exits when the hang resolves) so the
  queue keeps draining.  In a multi-group window, each group's
  dispatch is bounded individually — a hung group fails alone while
  the window's other groups (other planes, other kinds) proceed.
  ``pipeline_watchdog_trips_total{stage}`` /
  ``pipeline_quarantined_windows_total`` on /metrics.
- **device health governor** (``exec.health``): consecutive dispatch
  faults or watchdog trips flip serving to DEGRADED — fast lane off,
  pipelining off, every window executed inline per item on the
  proven op-at-a-time fallback path — then probe back to HEALTHY.
  ``device_health_state`` gauge + ``deviceHealth`` on /status.
- Two wedge classes fixed: a readback failure OUTSIDE ``_readback``'s
  per-item fallbacks now fails every unfinished item in the window
  (no ``_Pending.event`` left unset forever), and a collector death
  with items queued fails the backlog immediately instead of
  orphaning it until the next enqueue.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from pilosa_tpu import fault
from pilosa_tpu.engine import kernels
# attribution context (r19): submits run on the CALLER's thread, so
# the executor's thread-local (tenant, plane, trace) is read once at
# _Pending construction and rides the item through the window
from pilosa_tpu.obs import metrics as _metrics
from pilosa_tpu.obs.ledger import query_context as _query_ctx
from pilosa_tpu.obs.metrics import current_timer as _current_timer
from pilosa_tpu.obs.metrics import enter_stage as _stage


_phase_tls = threading.local()


def _phase(name: str | None, items=None) -> None:
    """The phase of the batcher thread's loop that starts here, as a
    ``pilosa.batcher.<name>`` event in the profiler's trace while a
    ``/debug/profile`` capture is open (None: the thread idles, nothing
    is open).  ``items``: the window's items — their requests' trace
    ids ride the event, so a request's spans on its serving thread and
    on the batcher's threads can be joined."""
    open_span = getattr(_phase_tls, "span", None)
    if open_span is None and not _metrics.capture_open:
        return
    traces = ""
    if items and _metrics.capture_open:
        traces = ",".join(sorted({p.trace for p in items if p.trace}))
    _phase_tls.span = _metrics.swap_span(
        open_span, name and "batcher." + name, traces)


def _stall_error(msg: str, stage: str, elapsed: float = 0.0):
    # lazy: executor imports this module lazily and vice versa
    from pilosa_tpu.exec.executor import PipelineStalledError
    return PipelineStalledError(msg, stage=stage, elapsed=elapsed)


class _Pending:
    __slots__ = ("kind", "nodes", "leaves", "delta", "event", "result",
                 "error", "deadline", "abandoned", "stage", "delivered",
                 "tenant", "plane", "trace", "t_queued", "t_dispatch",
                 "t_readback", "t_done")

    def __init__(self, kind, nodes, leaves, delta=None, deadline=None):
        self.kind = kind      # "count" | "sum" | "minmax" | "rowcounts"
        #                       | "selcounts" | "tree" | "distinct"
        #                       | "bsirange" | "groupby" (r20)
        self.nodes = nodes    # count: tuple of plan trees;
        #                       selcounts: tuple of plane row slots;
        #                       tree: (slots, postfix prog, extras);
        #                       bsirange: (spec, operands, sig);
        #                       groupby: (args, static, sig);
        #                       others: None
        self.leaves = leaves  # count: plan leaves; others: plane[, filter]
        self.delta = delta    # rowcounts/selcounts: the plane's
        #                       DeltaOverlay (base⊕delta merge, r15);
        #                       sum/minmax/bsirange: the BSI plane's
        #                       BsiOverlay (r20)
        self.event = threading.Event()
        self.result = None
        self.error: Exception | None = None
        # deadline-aware waiting (r18): the query's time.monotonic()
        # cutoff.  On expiry the caller marks the item ABANDONED and
        # leaves — the group's shared finish skips it, co-batched
        # items are untouched.  ``stage`` names where the item
        # currently is (queued → dispatch → readback) so a timeout or
        # quarantine error can say what stalled.
        self.deadline = deadline
        self.abandoned = False
        self.stage = "queued"
        # perf_counter() where ``stage`` changes, and where the answer
        # (or error) is stored: wait() hands them to the caller's
        # StageTimer, which books queue / dispatch / read / deliver
        # from them
        self.t_queued = time.perf_counter()
        self.t_dispatch = self.t_readback = self.t_done = None
        # True once a result/error was actually STORED — the event
        # alone cannot distinguish "answered" from "abandoned item
        # acknowledged" at the deadline boundary (see wait())
        self.delivered = False
        # cost-ledger attribution (r19), stamped here because the
        # submit runs on the caller thread: who pays for this item's
        # share of its window, which plane it scanned, and the trace
        # to exemplar the hottest shape bucket with
        self.tenant, self.plane, self.trace = _query_ctx()


class _Window:
    """One dispatched collection window's lifecycle record: what the
    watchdog ages, what quarantine fails, what owns a pipeline slot."""

    __slots__ = ("wid", "items", "stage", "t0", "pending",
                 "distinct_futs", "win_bytes", "slot_held", "inflight",
                 "done", "faulted", "bounded", "charge")

    def __init__(self, wid: int, items: list, slot_held: bool):
        self.wid = wid
        self.items = items          # every _Pending popped into this window
        self.stage = "dispatch"     # "dispatch" -> "readback"
        self.t0 = time.monotonic()  # current STAGE's start (reset on
        #                             progress so the watchdog bounds
        #                             stall time, not total time)
        self.pending: list = []     # dispatched (key, group, out, finish)
        self.distinct_futs: list = []
        self.win_bytes = 0
        self.slot_held = slot_held  # owns a _pipe_slots token
        self.inflight = False       # counted in _inflight_windows
        self.done = False           # closed (finished or quarantined)
        self.faulted = False        # any group fell back this window
        # True while the collector bounds this window's group joins
        # ITSELF (the multi-group fut.result(watchdog) path): the
        # whole-window watchdog defers, so a single hung group can
        # never take co-batched innocents down with it
        self.bounded = False
        # cost-ledger entries (r19): (tenant, shape, plane, byte
        # share, trace) per item, built alongside the win_bytes loop
        # so the charge reuses the already-computed group bytes; the
        # window's measured seconds apportion over these at readback
        self.charge: list = []


class CountBatcher:
    """Cross-request coalescing for Count, the BSI aggregates
    (Sum/Min/Max), whole-plane row counts, and Distinct — each
    kind/shape group in one collection window runs as one fused
    program + one read."""

    # adaptive-window bounds: MIN is the smallest non-zero window (below
    # it the window snaps to 0 — solo traffic must not wait at all);
    # MAX bounds queue-pressure growth so a burst can't add visible
    # latency to its own tail
    ADAPT_MIN = 0.0005
    ADAPT_MAX = 0.005

    def __init__(self, fused, window_s="adaptive", max_batch: int = 64,
                 stats=None, pipeline_depth: int = 2,
                 solo_fastlane: bool = True,
                 watchdog_s: float = 5.0,
                 probe_after_s: float = 5.0,
                 placement_key=None,
                 ledger=None, flight=None):
        from pilosa_tpu.exec.fused import PingPong
        from pilosa_tpu.exec.health import DeviceHealthGovernor
        from pilosa_tpu.obs import NULL_FLIGHT, NULL_LEDGER, NopStats
        from pilosa_tpu.obs.metrics import (BYTE_BUCKETS, COUNT_BUCKETS,
                                            RATIO_BUCKETS)
        self.fused = fused
        # placement identity (ISSUE 16 mesh serving): joins every batch
        # group key, so co-batching / slot unions / plan-cache survival
        # decisions can never mix items compiled against different
        # placements.  None single-device — group keys unchanged.
        self.placement_key = placement_key
        self.adaptive = window_s == "adaptive"
        self.window_s = 0.0 if self.adaptive else float(window_s)
        self._win = 0.0 if self.adaptive else self.window_s
        self.max_batch = max_batch
        self.stats = stats or NopStats()
        # device-plane telemetry (r14): window occupancy and fill are
        # item counts / ratios, not latencies — declare their bucket
        # sets up front (idempotent; see Stats.set_buckets)
        self.stats.set_buckets("batcher_window_items", COUNT_BUCKETS)
        self.stats.set_buckets("batcher_window_fill_ratio", RATIO_BUCKETS)
        self.stats.set_buckets("kernel_window_bytes", BYTE_BUCKETS)
        self.stats.set_buckets("readback_overlap_ratio", RATIO_BUCKETS)
        # per-SHAPE window fill (r20): how many items each kind's
        # group actually coalesced per window — the attribution the
        # PQL-surface bench reasons about (a kind stuck at 1 under
        # concurrency is not co-batching)
        self.stats.set_buckets("pipeline_window_fill", COUNT_BUCKETS)
        # lifetime co-batched BSI aggregate items (mirror of the
        # bsi_batch_hits_total counter) for /status
        self._bsi_batch_hits = 0
        self._queue: list[_Pending] = []
        self._lock = threading.Lock()
        self._kick = threading.Event()
        self._thread: threading.Thread | None = None
        self._pool = None  # persistent group-dispatch pool (lazy)
        # pipelined readback (r17 tentpole): the collector hands each
        # dispatched window to a dedicated readback worker, so window
        # N's device compute overlaps window N-1's packed device->host
        # read instead of serializing behind it.  ``pipeline_depth``
        # bounds dispatched-but-unread windows via the _pipe_slots
        # semaphore (taken before a window dispatches, released when
        # its readback finishes); depth <= 1 restores the pre-r17
        # inline readback.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._readq: queue.Queue | None = (
            queue.Queue() if self.pipeline_depth > 1 else None)
        # the actual run-ahead bound: a slot is taken BEFORE a
        # window's groups dispatch and released when its readback
        # finishes (or when quarantine reclaims it — r18), so
        # dispatched-but-unread windows can never exceed pipeline_depth
        self._pipe_slots = threading.Semaphore(self.pipeline_depth)
        self._read_thread: threading.Thread | None = None
        # dispatched-but-unread windows; collector increments, reader
        # decrements — locked, a lost update would permanently skew
        # the depth gauge and the overlap observations
        self._inflight_windows = 0
        self._pipe_lock = threading.Lock()
        # pipeline watchdog + window quarantine (r18 tentpole): every
        # dispatched window registers here; the monitor thread bounds
        # each window's per-STAGE age by ``watchdog_s`` and
        # quarantines overage — items failed with a structured error
        # naming the stage, pipeline slot reclaimed, the wedged stage
        # worker superseded.  0 disables (the pre-r18 contract: no
        # monitor thread, unbounded dispatch waits).
        self.watchdog_s = max(0.0, float(watchdog_s))
        self._windows: dict[int, _Window] = {}
        self._win_seq = 0
        self._watchdog: threading.Thread | None = None
        self._busy = 0  # collector cycles mid-batch (watchdog idleness)
        self._trips = 0        # watchdog trips (mirror of the counter)
        self._quarantined = 0  # quarantined windows/groups
        # device-cost ledger + pipeline flight recorder (r19): the
        # ledger apportions each window's measured seconds/bytes to
        # the items it served; the flight recorder rings every
        # lifecycle event and dumps on incidents.  Both default to
        # null objects so standalone batchers pay nothing.
        self.ledger = ledger or NULL_LEDGER
        self.flight = flight or NULL_FLIGHT
        # per-group dispatch seconds captured in _dispatch_one and
        # popped into the window charge at readback (id(group) keys —
        # plain dict writes, GIL-atomic, no lock on the dispatch path)
        self._disp_s: dict[int, float] = {}
        # device health governor (r18): healthy→degraded→probing
        # breaker fed by dispatch faults + watchdog trips; degraded
        # serving runs windows on the per-item fallback path
        self.governor = DeviceHealthGovernor(
            stats=self.stats, probe_after_s=probe_after_s,
            flight=self.flight)
        # solo fast lane (r17 tentpole): with no queue pressure, a
        # width-1 request skips window formation entirely and rides a
        # pre-bound dispatch chain on the CALLER's thread — no enqueue,
        # no worker wakeup, no cross-thread event round-trip
        self.solo_fastlane = bool(solo_fastlane)
        # concurrent fast-lane dispatches in flight: the lane admits
        # only when it is ZERO, so overlapping callers fall into the
        # collection window instead — that pile-up is the adaptive
        # window's pressure signal, and coalescing (dedupe + one scan
        # per window) must keep winning under real concurrency
        self._fl_active = 0
        self._fl_lock = threading.Lock()
        # donated ping-pong output chains shared by the windowed and
        # fast-lane dispatch paths (see fused.PingPong)
        self._pp = PingPong()

    def _group_pool(self):
        # persistent: a pool built and torn down per collection window
        # would put thread churn back on the very hot loop this
        # batcher exists to strip of per-request overhead
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="pilosa-batch-group")
        return self._pool

    @property
    def current_window(self) -> float:
        return self._win

    def health_payload(self) -> dict:
        """The ``/status`` deviceHealth block: governor state plus the
        watchdog's knobs and lifetime trip/quarantine counts."""
        out = self.governor.payload()
        out.update({
            "watchdogSeconds": self.watchdog_s,
            "quarantinedWindows": self._quarantined,
            "inflightWindows": self._inflight_windows,
            # r20: lifetime BSI-aggregate items that co-batched into
            # an existing same-plane group (the window-fill proof)
            "bsiBatchHits": self._bsi_batch_hits,
        })
        return out

    # -- item delivery (r18) -------------------------------------------------
    #
    # Every result/error hand-off routes through these two, so an item
    # can never be finished twice (quarantine racing a late readback)
    # and an ABANDONED item (deadline expired, caller gone) is skipped
    # without disturbing its co-batched neighbors.

    @staticmethod
    def _deliver(p: _Pending, value) -> None:
        if not (p.abandoned or p.event.is_set()):
            p.result = value
            p.delivered = True
            p.t_done = time.perf_counter()
        p.event.set()

    @staticmethod
    def _deliver_error(p: _Pending, err: Exception) -> None:
        if not (p.abandoned or p.event.is_set()):
            p.error = err
            p.delivered = True
            p.t_done = time.perf_counter()
        p.event.set()

    @staticmethod
    def _skip(p: _Pending) -> bool:
        """True when a finish loop should not compute this item's
        answer (abandoned by its caller, or already settled by
        quarantine)."""
        if p.abandoned or p.event.is_set():
            p.event.set()
            return True
        return False

    @staticmethod
    def _check_deadline(deadline: float | None,
                        stage: str = "dispatch") -> None:
        """Refuse work whose deadline already passed — the solo fast
        lane's pre-dispatch check, and the enqueue guard that keeps an
        expired caller from occupying a window slot at all."""
        if deadline is not None and time.monotonic() > deadline:
            from pilosa_tpu.exec.executor import QueryTimeoutError
            raise QueryTimeoutError(
                f"query deadline expired before {stage}", stage=stage)

    def _ensure_worker(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._run_collector,
                                            name="pilosa-count-batcher",
                                            daemon=True)
            self._thread.start()
        self._ensure_watchdog()

    def _enqueue(self, p: _Pending) -> _Pending:
        self.flight.record("enqueue", p.tenant, p.kind)
        with self._lock:
            self._queue.append(p)
            self._ensure_worker()
        self._kick.set()
        return p

    def wait(self, p: _Pending):
        """Block on an enqueued item's result (pairs with the
        ``enqueue_*`` methods — a caller that needs several items can
        enqueue them ALL into one collection window before waiting on
        any, instead of serializing one window per item).

        Deadline-aware (r18): an item carrying a deadline waits with a
        BOUNDED timeout; on expiry it is marked abandoned (the shared
        readback skips it) and ``QueryTimeoutError`` names the stage
        the item was in when the clock ran out.

        The one place a caller resumes: the time it spent blocked is
        booked on its stage clock as ``queue`` (enqueued → the window's
        dispatch begins), ``dispatch``, ``read`` and ``deliver`` (value
        on the host → this thread runs again), cut at the stamps the
        batcher's threads left on the item."""
        timer = _current_timer()
        if timer is not None:
            timer.enter("queue", at=p.t_queued)
        try:
            return CountBatcher._wait(p)
        finally:
            if timer is not None and p.t_done is not None:
                timer.recut((("dispatch", p.t_dispatch),
                             ("read", p.t_readback),
                             ("deliver", p.t_done)))
                timer.enter("assemble")

    @staticmethod
    def _wait(p: _Pending):
        if p.deadline is None:
            p.event.wait()
        else:
            remaining = p.deadline - time.monotonic()
            if remaining <= 0 or not p.event.wait(remaining):
                p.abandoned = True
                # boundary race: a deliverer between our timeout and
                # the abandon mark may have STORED the answer (then
                # p.delivered is True — return it) or may observe the
                # mark and skip (event set, nothing stored — the event
                # alone cannot tell the two apart, so only `delivered`
                # decides; a timeout here while a late store lands is
                # an honest timeout either way)
                if not p.delivered:
                    from pilosa_tpu.exec.executor import QueryTimeoutError
                    raise QueryTimeoutError(
                        "query deadline expired in the dispatch "
                        f"pipeline (stage={p.stage})", stage=p.stage)
        if p.error is not None:
            raise p.error
        return p.result

    def _submit(self, p: _Pending):
        return self.wait(self._enqueue(p))

    # -- solo fast lane (r17) ------------------------------------------------

    def _fl_try_enter(self) -> bool:
        """Atomically admit ONE fast-lane dispatch: fast lane enabled,
        device HEALTHY (a degraded device must not dispatch inline on
        caller threads, r18), adaptive window currently snapped to 0
        (traffic is solo — under queue pressure the window grows and
        coalescing wins), nothing already queued to join, and no other
        fast-lane dispatch in flight — the admission check and the
        in-flight increment happen under one lock, so two simultaneous
        callers can never both take the lane (the loser lands in the
        window, which is the adaptive pressure signal).  A True return
        must be paired with :meth:`_fl_leave`."""
        if not (self.solo_fastlane and self.adaptive
                and self._win == 0.0 and not self._queue
                and self.governor.fastlane_ok()):
            return False
        with self._fl_lock:
            if self._fl_active:
                return False
            self._fl_active += 1
        return True

    def _fl_leave(self) -> None:
        with self._fl_lock:
            self._fl_active -= 1

    def _fastlane_done(self, kind: str, nbytes: int,
                       wall: float = 0.0) -> None:
        # NO kernel_dispatch_seconds here: that family observes
        # enqueue-only on the windowed path (the read is deferred to
        # the packed readback), while a fast-lane call spans dispatch
        # PLUS the host read — mixing the two would corrupt the
        # compile-spike/enqueue-floor analysis the metric exists for.
        # Fast-lane latency is visible end-to-end in query_seconds /
        # query_stage_seconds.
        self.stats.count("solo_fastlane_hits_total", 1, kind=kind)
        if nbytes:
            self.stats.count("kernel_bytes_scanned_total", nbytes,
                             kind=kind)
        # solo item = whole charge (r19): the lane spans dispatch plus
        # the host read on the caller thread, so ``wall`` IS the
        # item's device cost — no apportioning needed
        tenant, plane, trace = _query_ctx()
        self.ledger.charge_solo(tenant, kind, plane, wall, nbytes,
                                trace_id=trace)
        _stage("assemble")

    # Every lane below runs on the caller's thread, so it enters
    # ``dispatch``, ``read`` and ``deliver`` on the caller's stage clock
    # directly (no ``queue``: nothing waited for a window), and
    # _fastlane_done leaves it in ``assemble``.

    def _fastlane_counts(self, nodes: tuple, leaves: tuple):
        """One request's Count run dispatched inline on the caller
        thread: same padding rule as the windowed `_dispatch_counts`
        (offset-0 single item), donated ping-pong scratch for the
        int32[K_pad, S] output.  None = fall back to the window."""
        from pilosa_tpu.exec.fused import pow2_bucket
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            padded = tuple(nodes) + (nodes[0],) * (
                pow2_bucket(len(nodes)) - len(nodes))
            scratch = self._pp.scratch(
                (len(padded), leaves[0].shape[0]), "int32")
            out = self.fused.run_count_batch(padded, leaves,
                                             scratch=scratch)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
            host = host.astype(np.int64)
            self._pp.retire(out)
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        self._fastlane_done("count",
                            sum(getattr(a, "nbytes", 0) for a in leaves),
                            wall=time.perf_counter() - t0)
        return [int(row.sum()) for row in host[:len(nodes)]]

    def _fastlane_selected(self, plane, slots: tuple, delta):
        """Width-N selected counts inline: sorted-unique slot gather
        (ascending stride), pre-bound device slot indices, donated
        int32[bucket] output slot.  None = fall back to the window."""
        from pilosa_tpu.exec.fused import pow2_bucket
        order = sorted(set(slots))
        pos = {s: i for i, s in enumerate(order)}
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            scratch = self._pp.scratch(
                (pow2_bucket(len(order)),), "int32")
            out = self.fused.run_selected_counts(
                plane, tuple(order), delta=delta, scratch=scratch,
                sorted_idx=True)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
            host = host.astype(np.int64)
            self._pp.retire(out)
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        nbytes = (len(order) * plane.shape[0] * plane.shape[-1] * 4
                  + (delta.nbytes if delta is not None else 0))
        self._fastlane_done("selcounts", nbytes,
                            wall=time.perf_counter() - t0)
        return host[[pos[s] for s in slots]]

    def _fastlane_rowcounts(self, plane, filter_words, delta):
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            if delta is not None:
                out = self.fused.run_rowcounts_delta(
                    plane, delta, filter_words=filter_words)
                _stage("read")
                host = np.asarray(out)
                _stage("deliver")
                host = host.astype(np.int64)
            else:
                flags = (filter_words is not None,)
                leaves = ((plane,) if filter_words is None
                          else (plane, filter_words))
                scratch = self._pp.scratch((1, plane.shape[-2]),
                                           "int32")
                out = self.fused.run_rowcounts_batch(flags, leaves,
                                                     scratch=scratch)
                _stage("read")
                host = np.asarray(out)
                _stage("deliver")
                host = host[0].astype(np.int64)
                self._pp.retire(out)
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        self._fastlane_done(
            "rowcounts",
            plane.nbytes + (getattr(filter_words, "nbytes", 0) or 0)
            + (delta.nbytes if delta is not None else 0),
            wall=time.perf_counter() - t0)
        return host

    def _fastlane_tree(self, plane, slots: tuple, prog: tuple,
                       extras: tuple, delta):
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            out = self.fused.run_tree_counts(plane, tuple(slots),
                                             (tuple(prog),),
                                             tuple(extras), delta=delta)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
            val = int(host[0])
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        nbytes = (len(slots) * plane.shape[0] * plane.shape[-1] * 4
                  + sum(getattr(a, "nbytes", 0) for a in extras)
                  + (delta.nbytes if delta is not None else 0))
        self._fastlane_done("tree", nbytes,
                            wall=time.perf_counter() - t0)
        return val

    @staticmethod
    def _agg_bytes(plane, extra, delta) -> int:
        return (plane.nbytes + extra
                + (delta.nbytes if delta is not None else 0))

    def _fastlane_agg(self, kind: str, plane, filters: list, delta):
        """One request's K BSI Sum (or Min/Max) items over one plane
        dispatched inline on the caller thread: ONE program at the
        request's own width (no pow2 padding: a padded item is one more
        filter pass of a Sum, one more plane scan of a Min/Max), one
        read, K decodes.  None = fall back to the window."""
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            out, assign, decode = self.fused.run_agg_plane_batch(
                kind, plane, filters, delta=delta)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
            vals = [decode(host[slot]) for slot in assign]
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        # the K Sums read the plane once (bsi.sum_pair_counts); each
        # distinct Min/Max item descends it under its own filter
        scanned = {id(f): getattr(f, "nbytes", 0) for f in filters}
        reads = 1 if kind == "sum" else len(scanned)
        self._fastlane_done(
            kind, reads * plane.nbytes + sum(scanned.values())
            + (delta.nbytes if delta is not None else 0),
            wall=time.perf_counter() - t0)
        return vals

    def _fastlane_bsirange(self, plane, spec: tuple, operands: tuple,
                           delta):
        """One BSI Range-count inline: batch of one through
        ``run_range_batch``.  None = fall back to the window."""
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            out = self.fused.run_range_batch(plane, (spec,),
                                             tuple(operands),
                                             delta=delta)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
            val = int(host[0])
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        self._fastlane_done("bsirange", self._agg_bytes(plane, 0, delta),
                            wall=time.perf_counter() - t0)
        return val

    def _fastlane_groupby(self, args: tuple, agg_kind, meta: tuple):
        """One GroupBy block inline on the caller thread.  None =
        fall back to the window."""
        from pilosa_tpu.exec import groupby as gb
        planes, ci, lp, fw, ap, dl = args
        _stage("dispatch")
        t0 = time.perf_counter()
        try:
            out = self.fused.run_groupby_batch(planes, ci, lp, fw, ap,
                                               agg_kind, delta=dl)
            _stage("read")
            host = np.asarray(out)
            _stage("deliver")
        except Exception:  # noqa: BLE001 — windowed path is the fallback
            self.governor.record_fault()
            return None
        self._fastlane_done("groupby", self._groupby_bytes(args),
                            wall=time.perf_counter() - t0)
        return gb.unflatten_block(host, *meta, agg_kind)

    @staticmethod
    def _groupby_bytes(args: tuple) -> int:
        planes, _ci, lp, fw, ap, dl = args
        return (sum(getattr(p, "nbytes", 0) for p in planes)
                + lp.nbytes + (getattr(fw, "nbytes", 0) or 0)
                + (ap.nbytes if ap is not None else 0)
                + (dl.nbytes if dl is not None else 0))

    # -- blocking submits ----------------------------------------------------

    def submit(self, node, leaves, deadline: float | None = None) -> int:
        """Block until the coalesced batch containing this Count runs;
        returns the host-finished int64 total."""
        return self.submit_many((node,), leaves, deadline=deadline)[0]

    def submit_many(self, nodes, leaves,
                    deadline: float | None = None) -> list[int]:
        """A whole request's Count run as ONE batch item (the nodes
        share one leaf list); N concurrent requests coalesce into one
        program regardless of how many Counts each carries."""
        nodes, leaves = tuple(nodes), tuple(leaves)
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_counts(nodes, leaves)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        return self._submit(_Pending("count", nodes, leaves,
                                     deadline=deadline))

    def submit_aggs(self, kind: str, plane, filters: list, delta=None,
                    deadline: float | None = None) -> list:
        """One request's K BSI ``"sum"`` | ``"minmax"`` items over ONE
        plane, each given by its filter (None = unfiltered); one
        answer per item, in order — ``(sum of offsets, non-null
        count)`` for a Sum; for Min/Max the ``(min, min_cnt, max,
        max_cnt)`` tuples, one per shard plus one per overlay-touched
        word column when the plane carries a delta (zero-count
        entries; the host combine drops them).  On the fast lane the
        K items are one launch and one read; otherwise they enqueue
        TOGETHER (before any wait) and co-batch with concurrent
        same-plane items in one collection window.  Identical filters
        dedupe to one scan either way; ``delta`` (a ``BsiOverlay``,
        r20) merges the plane's pending write columns at dispatch —
        base⊕delta exact, no fold on the query path."""
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_agg(kind, plane, filters, delta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        handles = [self._enqueue(_Pending(
            kind, None, (plane,) if f is None else (plane, f),
            delta=delta, deadline=deadline)) for f in filters]
        return [self.wait(h) for h in handles]

    def submit_bsirange(self, plane, spec: tuple, operands: tuple,
                        sig: tuple, delta=None,
                        deadline: float | None = None) -> int:
        """One BSI Range-count (``Count(Row(field op p))`` and the
        between forms) as a first-class batch item: the window's
        range counts over the SAME (plane, overlay) pair fuse into
        one program referencing the plane once, and identical
        predicates (same ``sig``: op keys, offsets, filter identity)
        dedupe to a single comparison chain.  ``spec`` is the item's
        static shape, ``operands`` its traced masks/sign/filter
        arrays (see ``fused.run_range_batch``)."""
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_bsirange(plane, spec, operands,
                                              delta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        return self.wait(self.enqueue_bsirange(plane, spec, operands,
                                               sig, delta,
                                               deadline=deadline))

    def enqueue_bsirange(self, plane, spec: tuple, operands: tuple,
                         sig: tuple, delta=None,
                         deadline: float | None = None) -> _Pending:
        """Non-blocking :meth:`submit_bsirange`: a request carrying K
        range Counts enqueues them ALL into one collection window
        before waiting on any."""
        self._check_deadline(deadline, stage="queued")
        return self._enqueue(_Pending(
            "bsirange", (spec, tuple(operands), sig), (plane,),
            delta=delta, deadline=deadline))

    def submit_groupby(self, planes: tuple, combo_idx, last_plane,
                       filter_words, agg_plane, agg_kind,
                       meta: tuple, digest, delta=None,
                       deadline: float | None = None) -> dict:
        """One GroupBy combination block through the window machinery
        (r20): identical concurrent blocks (same planes, same
        combinations — ``digest`` hashes the combo slots) dedupe to
        ONE program, and any block shares its collection window's
        dispatch pool + packed readback with concurrent Counts and
        aggregates.  ``delta``: the agg plane's ``BsiOverlay`` —
        aggregate GroupBys answer base⊕delta in-program.  ``meta`` =
        (n_combos, n_last, depth) for the unflatten; returns the
        block's output dict of host arrays."""
        self._check_deadline(deadline)
        args = (planes, combo_idx, last_plane, filter_words, agg_plane,
                delta)
        if self._fl_try_enter():
            try:
                out = self._fastlane_groupby(args, agg_kind, meta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        sig = (tuple(id(p) for p in planes), id(last_plane),
               id(filter_words) if filter_words is not None else 0,
               id(agg_plane) if agg_plane is not None else 0,
               id(delta) if delta is not None else 0,
               agg_kind, digest)
        return self._submit(_Pending(
            "groupby", (args, (agg_kind, meta), sig), (last_plane,),
            deadline=deadline))

    def submit_rowcounts(self, plane, filter_words=None,
                         delta=None,
                         deadline: float | None = None) -> np.ndarray:
        """Whole-plane per-row totals int64[R_pad] (cross-shard reduce
        on device — callers gate on the int32-exact shard bound).
        Identical concurrent items (same plane/filter objects) share
        one computation.  ``delta`` (the plane's DeltaOverlay) makes
        the answer base⊕delta — items over the same (plane, overlay)
        pair still dedupe to one scan."""
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_rowcounts(plane, filter_words,
                                               delta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        return self.wait(self.enqueue_rowcounts(plane, filter_words,
                                                delta, deadline=deadline))

    def enqueue_rowcounts(self, plane, filter_words=None,
                          delta=None,
                          deadline: float | None = None) -> _Pending:
        """Non-blocking variant: returns a handle for :meth:`wait`, so
        a request needing several row-count reads (filtered TopN with
        tanimoto) lands them all in ONE collection window."""
        self._check_deadline(deadline, stage="queued")
        leaves = (plane,) if filter_words is None else (plane, filter_words)
        return self._enqueue(_Pending("rowcounts", None, leaves,
                                      delta=delta, deadline=deadline))

    def submit_selected(self, plane, slots: tuple,
                        delta=None,
                        deadline: float | None = None) -> np.ndarray:
        """Selected-row Counts (the multi-query fused popcount): the
        window's items over the SAME resident plane merge into one
        row-gather + popcount program — one pass over the UNION of
        requested rows, N accumulators — and the per-item answers come
        back int64[len(slots)] in the caller's slot order.  Duplicate
        slots across concurrent requests are computed once.  ``delta``
        merges the plane's pending write overlay at dispatch time."""
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_selected(plane, tuple(slots),
                                              delta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        return self._submit(_Pending("selcounts", tuple(slots), (plane,),
                                     delta=delta, deadline=deadline))

    def submit_tree(self, plane, slots: tuple, prog: tuple,
                    extras: tuple = (), delta=None,
                    deadline: float | None = None) -> int:
        """One compound-tree Count (whole-tree compilation, r16): the
        window's tree items over the SAME (plane, overlay) pair union
        their gathered row slots into ONE in-program gather and fold
        every item's postfix program in one fused dispatch — N
        concurrent compound queries cost one memory pass and join the
        window's single packed readback."""
        self._check_deadline(deadline)
        if self._fl_try_enter():
            try:
                out = self._fastlane_tree(plane, slots, prog, extras,
                                          delta)
            finally:
                self._fl_leave()
            if out is not None:
                return out
        return self.wait(self.enqueue_tree(plane, slots, prog, extras,
                                           delta, deadline=deadline))

    def enqueue_tree(self, plane, slots: tuple, prog: tuple,
                     extras: tuple = (), delta=None,
                     deadline: float | None = None) -> _Pending:
        """Non-blocking :meth:`submit_tree`: a request carrying K
        compound Counts enqueues them ALL into one collection window
        before waiting on any."""
        self._check_deadline(deadline, stage="queued")
        return self._enqueue(_Pending(
            "tree", (tuple(slots), tuple(prog), tuple(extras)),
            (plane,), delta=delta, deadline=deadline))

    def submit_distinct(self, plane, filter_words,
                        deadline: float | None = None):
        """BSI Distinct presence: host (pos bool[2^d], neg bool[2^d]).
        Coalescing here is DEDUPLICATION only — the presence scan is a
        multi-dispatch block loop, so stacking would multiply compute;
        identical concurrent requests share one scan."""
        self._check_deadline(deadline)
        return self._submit(_Pending("distinct", None,
                                     (plane,) if filter_words is None
                                     else (plane, filter_words),
                                     deadline=deadline))

    # -- collector -----------------------------------------------------------

    def _superseded(self) -> bool:
        """True when a fresh collector replaced this thread (the
        quarantine restart, r18): the zombie must stop touching the
        shared queue the moment it notices."""
        return self._thread is not threading.current_thread()

    def _run_collector(self) -> None:
        """Collector main: one window cycle per loop, wrapped so a
        cycle failure can never kill the worker silently — before r18
        a collector death with items already queued orphaned them
        until the NEXT enqueue happened to call ``_ensure_worker``;
        now the queued backlog is failed with structured errors and
        the same thread keeps serving."""
        while True:
            if self._superseded():
                return
            try:
                self._collect_once()
            except Exception as e:  # noqa: BLE001 — worker must survive
                self._fail_backlog(e)

    def _fail_backlog(self, exc: Exception) -> None:
        """Collector-death path: every queued item is failed loudly
        (structured error naming the stage) instead of wedging until a
        future enqueue restarts the worker."""
        with self._lock:
            batch = self._queue[:]
            self._queue.clear()
            self._kick.clear()
        err = _stall_error(
            f"dispatch collector failed; {len(batch)} queued item(s) "
            f"aborted: {exc!r}", stage="collect")
        err.__cause__ = exc
        for p in batch:
            self._deliver_error(p, err)

    def _collect_once(self) -> None:
        _phase("collect")  # waiting for items, or for the window to close
        self._kick.wait()
        if self._superseded():
            return
        # collection window: let concurrent submitters pile in.
        # Adaptive mode keeps it at 0 for solo traffic and grows it
        # only while batches actually coalesce.
        win = self._win if self.adaptive else self.window_s
        if win > 0:
            time.sleep(win)
        with self._lock:
            backlog = len(self._queue)
            batch = self._queue[: self.max_batch]
            del self._queue[: len(batch)]
            if not self._queue:
                self._kick.clear()
        if not batch:
            return
        # busy marker: the idle-exiting watchdog must outlive every
        # popped-but-not-yet-registered batch (see _watchdog_loop)
        with self._lock:
            self._busy += 1
        try:
            self._process_batch(batch, backlog)
        finally:
            with self._lock:
                self._busy -= 1

    def _process_batch(self, batch: list, backlog: int) -> None:
        _phase("group", batch)
        if self.adaptive:
            if len(batch) > 1 or backlog > len(batch):
                self._win = min(max(self._win * 2, self.ADAPT_MIN),
                                self.ADAPT_MAX)
            elif self._win:
                nxt = self._win / 2
                self._win = 0.0 if nxt < self.ADAPT_MIN else nxt
        self.stats.count("batcher_batches", 1)
        self.stats.count("batcher_items", len(batch))
        self.stats.gauge("batcher_window_seconds", self._win)
        # window occupancy + fill ratio (r14 device telemetry):
        # the coalescing histograms a roofline reading reasons
        # about — how many items a window actually collects and
        # how close it runs to max_batch
        self.stats.observe("batcher_window_items", float(len(batch)))
        self.stats.observe("batcher_window_fill_ratio",
                           len(batch) / self.max_batch)
        # stacked outputs need uniform shapes: group by kind + the
        # output-shaping leaf dimension (counts: n_shards — mixed
        # row/plane leaf ranks fuse fine, only the int32[S] outputs
        # must stack; aggregates/rowcounts: the full plane shape;
        # selcounts: the plane IDENTITY — one gather per plane)
        groups: dict[tuple, list[_Pending]] = {}
        for p in batch:
            if p.kind == "count":
                key = ("count", p.leaves[0].shape[0])
            elif p.kind == "selcounts":
                # delta identity joins the key: items over the
                # same (plane, overlay) pair slot-union into one
                # gather; a fresher overlay is a different answer
                key = ("selcounts", id(p.leaves[0]),
                       id(p.delta) if p.delta is not None else 0)
            elif p.kind == "tree":
                # same (plane, overlay) pair → one gather of the
                # slot UNION serves every item's program
                key = ("tree", id(p.leaves[0]),
                       id(p.delta) if p.delta is not None else 0)
            elif p.kind == "rowcounts" and p.delta is not None:
                key = ("rowcounts-delta", id(p.leaves[0]),
                       id(p.delta),
                       id(p.leaves[1]) if len(p.leaves) == 2 else 0)
            elif p.kind in ("sum", "minmax", "bsirange"):
                # BSI aggregates group by plane IDENTITY (r20): the
                # window's same-plane aggregates co-batch into one
                # program referencing the plane once, and identical
                # items (same filter / predicate signature) dedupe to
                # one scan inside the dispatch.  The overlay identity
                # joins the key like selcounts — a fresher overlay is
                # a different answer.
                key = (p.kind, id(p.leaves[0]),
                       id(p.delta) if p.delta is not None else 0)
            elif p.kind == "groupby":
                # identical concurrent GroupBy blocks (same planes,
                # same combination block — the sig carries a digest)
                # dedupe to ONE program; distinct blocks still share
                # the window's dispatch pool and packed readback
                key = ("groupby",) + p.nodes[2]
            else:
                key = (p.kind, p.leaves[0].shape)
            # placement identity rides every group key (kind stays at
            # key[0] — fallback routing and fill attribution key on it)
            groups.setdefault(key + (self.placement_key,),
                              []).append(p)
        # per-shape coalescing attribution (r20): window fill by kind,
        # plus the lifetime count of BSI-aggregate items that joined
        # an existing same-plane group (the co-batch proof counter)
        for key, group in groups.items():
            self.stats.observe("pipeline_window_fill",
                               float(len(group)), kind=key[0])
            if key[0] in ("sum", "minmax", "bsirange") \
                    and len(group) > 1:
                self.stats.count("bsi_batch_hits_total",
                                 len(group) - 1, kind=key[0])
                self._bsi_batch_hits += len(group) - 1
        # DEGRADED serving (r18 governor): the device is suspect —
        # every group runs inline per item on the proven op-at-a-time
        # fallback path (answers stay exact; throughput, not
        # correctness, is what degrades).  No pipeline, no fast lane,
        # no shared readback to stall.
        if not self.governor.admit():
            _phase("dispatch", batch)
            now = time.perf_counter()
            for p in batch:
                p.stage = "dispatch"
                p.t_dispatch = now
            for key, group in groups.items():
                if key[0] == "distinct":
                    self._run_distinct(group)
                else:
                    self._run_fallback(key, group)
            return
        self._dispatch_window(batch, groups)

    def _dispatch_window(self, batch: list, groups: dict) -> None:
        """The fused pipeline: one dispatch per group, the window's
        outputs packed into one readback (handed to the readback
        worker when pipelining is on).  Registered with the watchdog
        for the whole dispatch→readback lifetime."""
        # BATCHED READBACK (r12): every one-program kind dispatches
        # asynchronously, then the whole window's outputs are
        # packed into ONE device array and read with ONE
        # device->host transfer — the window pays the fixed
        # per-read cost once total, not once per kind/shape
        # group.  Distinct stays on
        # the pool: its presence scan is a multi-dispatch host
        # loop that cannot join a single readback.
        _phase("dispatch", batch)
        pending = []
        distinct_futs = []
        program_groups = []
        for key, group in groups.items():
            if key[0] == "distinct":
                distinct_futs.append(self._group_pool().submit(
                    self._run_distinct, group))
            else:
                program_groups.append((key, group))
        # run-ahead bound BEFORE dispatching: at pipeline_depth
        # dispatched-but-unread windows the collector waits here,
        # so device output held by in-flight windows never exceeds
        # the documented knob.  Quarantine reclaims a stuck window's
        # slot, so this acquire cannot deadlock behind a wedge.
        slot_held = False
        use_pipe = (self._readq is not None
                    and self.governor.pipelining_ok())
        if use_pipe and (program_groups or distinct_futs):
            self._pipe_slots.acquire()
            slot_held = True
        w = self._register_window(batch, slot_held)
        now = time.perf_counter()
        for p in batch:
            p.stage = "dispatch"
            p.t_dispatch = now
        if len(program_groups) == 1:
            # the common (and solo-path) case skips the pool
            # round-trip: one group, dispatch inline — a hang here
            # wedges the collector, which the watchdog resolves by
            # quarantining the window and superseding this thread
            key, group = program_groups[0]
            try:
                pending.append((key, group)
                               + self._dispatch_one(key, group))
            except Exception:  # noqa: BLE001 — per-item fallback
                w.faulted = True
                self.governor.record_fault()
                if not w.done:
                    # the fallback gets its OWN stage budget: aging it
                    # against the failed dispatch's t0 would let the
                    # watchdog quarantine a legitimately progressing
                    # per-item recovery
                    w.t0 = time.monotonic()
                    self._run_fallback(key, group)
        elif program_groups:
            # dispatch groups CONCURRENTLY (a first-time compile
            # in one group must not stall the others' warm
            # dispatches), then join for the window's single
            # packed readback.  Each group's join is bounded by the
            # watchdog (r18): a hung group fails ALONE — the other
            # groups' (other planes', other kinds') items proceed.
            from concurrent.futures import TimeoutError as _FutTimeout
            futs = [(key, group, self._group_pool().submit(
                self._dispatch_one, key, group))
                for key, group in program_groups]
            bound = self.watchdog_s if self.watchdog_s > 0 else None
            # the collector bounds each join ITSELF here, so the
            # whole-window watchdog defers (w.bounded): a single hung
            # group fails alone — co-batched groups of other kinds /
            # planes proceed, and innocents are never quarantined
            w.bounded = True
            for key, group, fut in futs:
                try:
                    pending.append((key, group) + fut.result(bound))
                except _FutTimeout:
                    self._fail_stalled_group(key, group, bound)
                    w.faulted = True
                except Exception:  # noqa: BLE001 — per-item fallback
                    w.faulted = True
                    self.governor.record_fault()
                    if not w.done:
                        # hand the inline fallback BACK to the
                        # watchdog with a fresh budget: under
                        # w.bounded it would otherwise run unwatched —
                        # a fallback that hangs on the same sick
                        # device must still be quarantinable
                        w.t0 = time.monotonic()
                        w.bounded = False
                        try:
                            self._run_fallback(key, group)
                        finally:
                            w.bounded = True
                # progress heartbeat: the watchdog bounds STALL time
                # per stage, not the sum of a wide window's joins
                w.t0 = time.monotonic()
            w.bounded = False
        if w.done:
            # quarantined mid-dispatch: items already failed, slot
            # already reclaimed, a fresh collector owns the queue —
            # this (zombie) thread drops everything on the floor
            return
        # bytes the window's fused programs read from HBM (r14):
        # per-kind scan-volume counters feed capacity math, and
        # bytes / (readback-start -> readback-complete) is the
        # LIVE bandwidth a device trace measures offline — the
        # gauge tracks how far serving sits from that roof (see
        # _finish_window for why the clock starts at the read, not
        # the dispatch)
        win_bytes = 0
        for key, group, _, _ in pending:
            nbytes = self._group_bytes(key[0], group)
            if nbytes:
                self.stats.count("kernel_bytes_scanned_total",
                                 nbytes, kind=key[0])
                win_bytes += nbytes
            # ledger entries (r19) built here so the charge reuses the
            # group-bytes estimate: each item's weight is its equal
            # split of its group's scan (the group's items share one
            # fused pass — the plane is read once for all of them)
            share = nbytes / max(1, len(group))
            for p in group:
                w.charge.append((p.tenant, p.kind, p.plane, share,
                                 p.trace))
        w.pending = pending
        w.distinct_futs = distinct_futs
        w.win_bytes = win_bytes
        if not (pending or distinct_futs):
            # every dispatch fell back or was failed: nothing to read
            if self._window_done(w):
                self.flight.record("deliver", f"w{w.wid}", "",
                                   float(len(w.items)))
            return
        with self._pipe_lock:
            w.stage = "readback"
            w.t0 = time.monotonic()
        self.flight.record("readback", f"w{w.wid}")
        now = time.perf_counter()
        for p in batch:
            p.stage = "readback"
            p.t_readback = now
        if slot_held:
            # PIPELINED READBACK (r17): hand the dispatched window
            # to the readback worker and immediately collect the
            # next one — window N's device compute overlaps window
            # N-1's packed device->host read.
            with self._pipe_lock:
                if w.done:
                    return
                overlapped = self._inflight_windows > 0
                self._inflight_windows += 1
                w.inflight = True
                depth = self._inflight_windows
            self.stats.observe("readback_overlap_ratio",
                               1.0 if overlapped else 0.0)
            self.stats.gauge("dispatch_pipeline_depth", depth)
            self._ensure_reader()
            self._readq.put(w)
        else:
            err = None
            try:
                self._finish_window(w)
            except Exception as e:  # noqa: BLE001 — final guard (r18):
                err = e            # fail, never wedge, the whole window
            if err is not None:
                self._fail_window_items(
                    w, _wrap_readback_error(err))
            if self._window_done(w):
                self.flight.record("deliver", f"w{w.wid}", "",
                                   float(len(w.items)))
                if err is None and not w.faulted:
                    self.governor.record_success()

    def _fail_stalled_group(self, key, group, bound: float) -> None:
        """One group's dispatch exceeded the watchdog bound while the
        rest of the window proceeded: fail ONLY its items (structured,
        naming the stage) and notify the governor — the wedged pool
        worker parks until the hang resolves."""
        self._trips += 1
        self._quarantined += 1
        self.stats.count("pipeline_watchdog_trips_total", 1,
                         stage="dispatch")
        self.stats.count("pipeline_quarantined_windows_total", 1)
        # flight events name the SAME stage the structured error below
        # carries — the dump's quarantine line and the caller's
        # exception must agree on what stalled (pinned in tests).
        # Recorded + dumped BEFORE the governor trip so the governor's
        # own degrade incident cannot dump first and rate-limit the
        # quarantine artifact away.
        self.flight.record("watchdog_trip", key[0], "dispatch", bound)
        self.flight.record("quarantine", key[0], "dispatch", bound)
        self.flight.incident("quarantine", key[0], "dispatch")
        self.governor.record_trip()
        err = _stall_error(
            f"{key[0]} dispatch stalled past the "
            f"{bound:g}s watchdog bound and was quarantined "
            f"(dispatch_watchdog_seconds)", stage="dispatch",
            elapsed=bound)
        for p in group:
            self._deliver_error(p, err)

    # -- window registry + watchdog (r18) ------------------------------------

    def _register_window(self, batch: list, slot_held: bool) -> _Window:
        with self._pipe_lock:
            self._win_seq += 1
            w = _Window(self._win_seq, batch, slot_held)
            if self.watchdog_s > 0:
                self._windows[w.wid] = w
        self.flight.record("dispatch", f"w{w.wid}", "",
                           float(len(batch)))
        return w

    def _window_done(self, w: _Window) -> bool:
        """Idempotently close a window: unregister it, release its
        pipeline slot, settle the depth gauge.  Returns False when the
        window was already closed (quarantined, or a zombie worker
        finishing late) — the caller must not treat it as its own."""
        with self._pipe_lock:
            if w.done:
                return False
            w.done = True
            self._windows.pop(w.wid, None)
            depth = None
            if w.inflight:
                w.inflight = False
                self._inflight_windows -= 1
                depth = self._inflight_windows
            slot = w.slot_held
            w.slot_held = False
        if depth is not None:
            self.stats.gauge("dispatch_pipeline_depth", depth)
        if slot:
            self._pipe_slots.release()
        # belt: a quarantined window never reaches _finish_window's
        # pop, so its captured group dispatch seconds drain here
        for _k, g, _o, _f in w.pending:
            self._disp_s.pop(id(g), None)
        return True

    def _fail_window_items(self, w: _Window, err: Exception) -> None:
        """Fail every UNFINISHED item in the window (finished and
        abandoned ones are skipped by the delivery guard)."""
        for p in w.items:
            self._deliver_error(p, err)

    def _ensure_watchdog(self) -> None:
        if self.watchdog_s <= 0:
            return  # knob off: the exact pre-r18 thread census
        if self._watchdog is None or not self._watchdog.is_alive():
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                name="pilosa-pipeline-watchdog", daemon=True)
            self._watchdog.start()

    # consecutive idle ticks after which the monitor thread parks
    # itself (restarted by the next enqueue): a short-lived executor
    # must not leak a polling thread for the process lifetime
    WATCHDOG_IDLE_TICKS = 8

    def _watchdog_loop(self) -> None:
        """Monitor thread: bound every in-flight window's per-stage
        age; quarantine overage.  Happy-path cost is one short sleep
        and a scan of at most pipeline_depth+1 dict entries per tick —
        nothing touches the dispatch hot path.  Windows whose group
        joins the collector is bounding itself (``w.bounded``) are
        skipped: their per-group timeout is the enforcer there, and a
        whole-window quarantine would take co-batched innocents down.
        Exits after WATCHDOG_IDLE_TICKS quiet ticks (the next enqueue
        revives it) so an idle batcher costs no polling."""
        idle = 0
        while True:
            # interval re-derived per tick so a runtime watchdog_s
            # change (tests, live tuning) takes effect without a
            # thread restart
            time.sleep(max(0.02, min(self.watchdog_s / 4.0, 1.0))
                       if self.watchdog_s > 0 else 0.25)
            if self.watchdog_s <= 0:
                with self._lock:
                    if self._watchdog is threading.current_thread():
                        self._watchdog = None
                return
            now = time.monotonic()
            with self._pipe_lock:
                stuck = [w for w in self._windows.values()
                         if not w.done and not w.bounded
                         and now - w.t0 > self.watchdog_s]
            for w in stuck:
                self._quarantine(w, now - w.t0)
            # dead-worker sweep (belt over the _run_collector wrapper):
            # a collector that died with items queued is restarted NOW,
            # not at the next enqueue
            with self._lock:
                backlog = bool(self._queue)
                t = self._thread
                quiet = (not self._queue and not self._busy
                         and not self._windows)
                if quiet:
                    idle += 1
                    if (idle >= self.WATCHDOG_IDLE_TICKS
                            and self._watchdog
                            is threading.current_thread()):
                        # park: _ensure_worker (under this same lock)
                        # restarts the monitor before any new item can
                        # enqueue, so no window ever runs unwatched
                        self._watchdog = None
                        return
                else:
                    idle = 0
            if backlog and t is not None and not t.is_alive():
                self._restart_collector()

    def _quarantine(self, w: _Window, age: float) -> None:
        """A window exceeded the watchdog bound in ``w.stage``: fail
        its unfinished items with a structured error naming the stage,
        reclaim its pipeline slot, and supersede the wedged stage
        worker with a fresh thread so the queue keeps draining (the
        zombie exits on its own when the hang resolves)."""
        stage = w.stage
        # read BEFORE _window_done clears it: was the window handed to
        # the readback worker, or was it finishing INLINE on the
        # collector (probe windows, depth<=1 fallbacks)?  The restart
        # must supersede whichever thread is actually wedged.
        handed = w.inflight
        if not self._window_done(w):
            return  # finished while we decided: no trip
        self._trips += 1
        self._quarantined += 1
        self.stats.count("pipeline_watchdog_trips_total", 1, stage=stage)
        self.stats.count("pipeline_quarantined_windows_total", 1)
        # same-stage contract as _fail_stalled_group: the quarantine
        # flight event's detail is the stage the error names.  Flight
        # events + incident dump run BEFORE the governor hears about
        # the trip: its own degrade incident would otherwise dump
        # first and rate-limit this one away — the artifact must carry
        # the quarantine line (pinned in tests)
        self.flight.record("watchdog_trip", f"w{w.wid}", stage, age)
        self.flight.record("quarantine", f"w{w.wid}", stage, age)
        self.flight.incident("quarantine", f"w{w.wid}", stage)
        self.governor.record_trip()
        err = _stall_error(
            f"dispatch-pipeline window stalled in {stage} for "
            f"{age:.2f}s (dispatch_watchdog_seconds="
            f"{self.watchdog_s:g}); the window was quarantined and "
            f"its pipeline slot reclaimed", stage=stage, elapsed=age)
        self._fail_window_items(w, err)
        if stage == "readback" and handed and self._readq is not None:
            self._restart_reader()
        else:
            self._restart_collector()

    def _restart_collector(self) -> None:
        self._thread = threading.Thread(target=self._run_collector,
                                        name="pilosa-count-batcher",
                                        daemon=True)
        self._thread.start()
        # wake a zombie parked on the kick (it exits on supersession)
        # and hand any backlog straight to the fresh worker
        self._kick.set()

    def _restart_reader(self) -> None:
        self._read_thread = threading.Thread(
            target=self._read_loop, name="pilosa-batch-readback",
            daemon=True)
        self._read_thread.start()
        # a parked zombie (defensive: restarts normally happen while
        # the old reader is wedged mid-window) wakes on the sentinel
        # and exits on supersession
        self._readq.put(None)

    # -- readback worker -----------------------------------------------------

    def _ensure_reader(self) -> None:
        if self._read_thread is None or not self._read_thread.is_alive():
            self._read_thread = threading.Thread(
                target=self._read_loop, name="pilosa-batch-readback",
                daemon=True)
            self._read_thread.start()

    def _read_loop(self) -> None:
        while True:
            if self._read_thread is not threading.current_thread():
                return  # superseded by a quarantine restart (r18)
            _phase(None)
            w = self._readq.get()
            if w is None or w.done:
                continue  # wake sentinel / already-quarantined window
            err = None
            try:
                self._finish_window(w)
            except Exception as e:  # noqa: BLE001 — final guard (r18):
                err = e
            if err is not None:
                # before r18 this swallow could leave a window's
                # _Pending.event unset forever when _finish_window
                # raised OUTSIDE _readback's per-item fallbacks; now
                # every unfinished item is failed loudly
                self._fail_window_items(w, _wrap_readback_error(err))
            if self._window_done(w):
                self.flight.record("deliver", f"w{w.wid}", "",
                                   float(len(w.items)))
                if err is None and not w.faulted:
                    self.governor.record_success()

    def _finish_window(self, w: _Window) -> None:
        """Read one dispatched window back and finish its items — the
        half of the old loop tail that runs on the readback worker
        when pipelining is on (inline when off)."""
        _phase("read", w.items)
        if fault.ACTIVE:
            # chaos seam (r18): a stalled device→host read
            fault.fire("exec.readback_hang")
        # bandwidth wall clock starts HERE, not at dispatch: a
        # pipelined window's queue wait overlaps the previous window's
        # read (the feature working as intended) and must not deflate
        # the gauge — the read itself still blocks on any residual
        # compute, so bytes/wall remains the live achieved bandwidth
        t0 = time.perf_counter()
        self._readback(w)
        wall = time.perf_counter() - t0
        # cost-ledger charge (r19): this window's measured device time
        # = per-group dispatch seconds (captured in _dispatch_one) +
        # the packed readback wall, apportioned to the items by their
        # bytes-scanned weight.  Exact-sum split — the ledger pins
        # sum(shares) == window total bit-for-bit.
        if w.charge:
            disp = 0.0
            for _key, group, _out, _fin in w.pending:
                disp += self._disp_s.pop(id(group), 0.0)
            self.ledger.charge_window(disp + wall, w.charge)
        if self.placement_key is not None and w.pending:
            # meshed window: the packed read blocks on the program's
            # residual compute INCLUDING its cross-shard collectives,
            # so the readback wall is the observable collective +
            # transfer cost per window on the mesh
            self.stats.observe("mesh_collective_seconds", wall)
        if w.win_bytes:
            # per-window scan-volume distribution (byte-scale
            # buckets) + the live bandwidth the window achieved
            self.stats.observe("kernel_window_bytes",
                               float(w.win_bytes))
            if wall > 0:
                self.stats.gauge("kernel_bandwidth_gbps",
                                 round(w.win_bytes / wall / 1e9, 4))
        for f in w.distinct_futs:
            try:
                f.result()
            except Exception:  # noqa: BLE001 — _run_distinct sets its
                pass           # items' events/errors itself

    def _dispatch_one(self, key, group):
        """Build + enqueue one group's fused program; returns
        ``(device_out, finish)`` with the device->host read deferred to
        the window's single packed readback.  Raises on dispatch
        failure (the caller falls back per item).  Dispatch time is
        observed per kind — a first-time XLA compile shows up as a
        spike in ``kernel_dispatch_seconds{kind=...}``, warm dispatches
        as the enqueue floor."""
        t0 = time.perf_counter()
        kind = key[0]
        if fault.ACTIVE:
            # chaos seams (r18): a hung XLA compile / stalled dispatch
            # (delay action) and a faulting dispatch (error action) —
            # the sites the watchdog, quarantine and governor are
            # proven against
            fault.fire("exec.dispatch_hang", kind=kind)
            fault.fire("exec.dispatch_error", kind=kind)
        if kind == "count":
            ret = self._dispatch_counts(group)
        elif kind == "rowcounts":
            ret = self._dispatch_rowcounts(group)
        elif kind == "rowcounts-delta":
            ret = self._dispatch_rowcounts_delta(group)
        elif kind == "selcounts":
            ret = self._dispatch_selcounts(group)
        elif kind == "tree":
            ret = self._dispatch_tree(group)
        elif kind == "bsirange":
            ret = self._dispatch_bsirange(group)
        elif kind == "groupby":
            ret = self._dispatch_groupby(group)
        else:
            ret = self._dispatch_aggs(kind, group)
        elapsed = time.perf_counter() - t0
        self.stats.observe("kernel_dispatch_seconds", elapsed,
                           kind=kind)
        # the window charge picks this up at readback (keyed by group
        # identity — the pending tuples carry the same list object)
        self._disp_s[id(group)] = elapsed
        return ret

    @staticmethod
    def _group_bytes(kind: str, group: list[_Pending]) -> int:
        """Estimated HBM bytes one group's fused program reads.  count
        leaves each enter the program (sum of leaf footprints);
        selcounts gathers only the UNION of requested rows; the
        dedup'd kinds (rowcounts/sum/minmax/distinct) scan each unique
        plane[, filter] once however many items share it."""
        if kind == "selcounts":
            plane = group[0].leaves[0]
            rows = {s for p in group for s in p.nodes}
            return len(rows) * plane.shape[0] * plane.shape[-1] * 4
        if kind == "tree":
            # one gather of the slot UNION + each unique extra once
            plane = group[0].leaves[0]
            rows = {s for p in group for s in p.nodes[0]}
            extras = {id(a): a for p in group for a in p.nodes[2]}
            d = group[0].delta
            return (len(rows) * plane.shape[0] * plane.shape[-1] * 4
                    + sum(getattr(a, "nbytes", 0)
                          for a in extras.values())
                    + (d.nbytes if d is not None else 0))
        if kind == "rowcounts-delta":
            # one base scan + the overlay gather per unique (plane,
            # overlay, filter) key — items in this group are identical
            p0 = group[0]
            d = p0.delta
            return (sum(getattr(a, "nbytes", 0) for a in p0.leaves)
                    + (d.nbytes if d is not None else 0))
        if kind == "count":
            return sum(getattr(a, "nbytes", 0)
                       for p in group for a in p.leaves)
        if kind == "bsirange":
            # one plane pass per unique predicate signature + the
            # overlay gather once
            plane = group[0].leaves[0]
            d = group[0].delta
            return (len({p.nodes[2] for p in group}) * plane.nbytes
                    + (d.nbytes if d is not None else 0))
        if kind == "groupby":
            return CountBatcher._groupby_bytes(group[0].nodes[0])
        seen: set = set()
        total = 0
        for p in group:
            k = tuple(id(a) for a in p.leaves)
            if k in seen:
                continue
            seen.add(k)
            total += sum(getattr(a, "nbytes", 0) for a in p.leaves)
        d = group[0].delta
        if kind in ("sum", "minmax") and d is not None:
            total += d.nbytes
        return total

    def _run_fallback(self, key, group):
        if key[0] == "count":
            self._fallback_counts(group)
        elif key[0] in ("rowcounts", "rowcounts-delta"):
            self._fallback_rowcounts(group)
        elif key[0] == "selcounts":
            self._fallback_selcounts(group)
        elif key[0] == "tree":
            self._fallback_tree(group)
        elif key[0] == "bsirange":
            self._fallback_bsirange(group)
        elif key[0] == "groupby":
            self._fallback_groupby(group)
        else:
            self._fallback_aggs(key[0], group)

    def _readback(self, w: _Window) -> None:
        """One device->host transfer for the whole collection window:
        pack every group's int32 output into a single flat array, read
        it once, slice per group.  A single-group window reads its
        output directly (the pack would only add a dispatch); any pack
        or finish failure degrades to per-group reads, then to the
        per-item fallbacks."""
        pending = w.pending
        if not pending:
            return
        if len(pending) == 1:
            key, group, out, finish = pending[0]
            try:
                host = np.asarray(out)
                _phase("deliver", group)
                finish(host)
            except Exception:  # noqa: BLE001 — per-item fallback
                w.faulted = True
                self.governor.record_fault()
                w.t0 = time.monotonic()  # fresh budget for the fallback
                self._run_fallback(key, group)
            else:
                # only after a delivered finish (which copied): a
                # retire failure must never re-run a group whose
                # results callers are already reading
                self._pp.retire(out)
            return
        # canonical pack order: groups arrive in batch order, so the
        # same kinds in a different order would otherwise compile a
        # fresh concatenate program per PERMUTATION of shapes —
        # churning the shared program LRU for zero benefit
        pending.sort(key=lambda item: (item[0][0], str(item[2].shape)))
        packed_dev = None
        try:
            total = sum(int(np.prod(out.shape, dtype=np.int64))
                        for _, _, out, _ in pending)
            packed_dev = self.fused.run_readback_pack(
                tuple(out for _, _, out, _ in pending),
                scratch=self._pp.scratch((total,), "int32"))
            packed = np.asarray(packed_dev)
        except Exception:  # noqa: BLE001 — per-group reads
            packed = packed_dev = None
        _phase("deliver", w.items)
        if packed is not None:
            self.stats.count("batcher_readback_packed", 1)
            self.stats.count("batcher_readback_groups", len(pending))
        off = 0
        for key, group, out, finish in pending:
            try:
                if packed is None:
                    host = np.asarray(out)
                else:
                    size = int(np.prod(out.shape, dtype=np.int64))
                    host = packed[off:off + size].reshape(out.shape)
                    off += size
                finish(host)
            except Exception:  # noqa: BLE001 — per-item fallback
                w.faulted = True
                self.governor.record_fault()
                w.t0 = time.monotonic()  # fresh budget for the fallback
                self._run_fallback(key, group)
        # every finish copied out of `packed` (astype/int/fancy-index),
        # so the packed device buffer can re-enter the donated chain
        self._pp.retire(packed_dev)

    def _dispatch_counts(self, group: list[_Pending]):
        from pilosa_tpu.exec.fused import pow2_bucket, shift_leaves
        all_nodes, all_leaves, spans = [], [], []
        for p in group:
            start = len(all_nodes)
            for node in p.nodes:
                all_nodes.append(shift_leaves(node, len(all_leaves)))
            all_leaves.extend(p.leaves)
            spans.append((start, len(all_nodes)))
        # pad the NODE count to a pow2 bucket by repeating node 0
        # (already leaf-shifted; see fused.pow2_bucket)
        n = len(all_nodes)
        all_nodes.extend([all_nodes[0]] * (pow2_bucket(n) - n))
        per_shard = self.fused.run_count_batch(
            tuple(all_nodes), tuple(all_leaves),
            scratch=self._pp.scratch(
                (len(all_nodes), group[0].leaves[0].shape[0]),
                "int32"))

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for p, (a, b) in zip(group, spans):
                if self._skip(p):
                    continue
                self._deliver(p, [int(row.sum()) for row in host[a:b]])
        return per_shard, finish

    def _fallback_counts(self, group: list[_Pending]) -> None:
        for p in group:
            if self._skip(p):
                continue
            try:
                self._deliver(p, [
                    int(kernels.shard_totals(
                        self.fused.run(node, p.leaves, "count")))
                    for node in p.nodes])
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    def _dispatch_selcounts(self, group: list[_Pending]):
        """The window's selected-row Counts over one plane: gather the
        UNION of every item's requested slots once (N concurrent
        requests over overlapping rows pay one pass over the union,
        the multi-query analogue of the rowcounts dedup), popcount,
        reduce shards on device.  The group key carries the delta
        identity, so every item here shares one (plane, overlay) pair
        and the merge happens once for the union.  The union gathers
        in SORTED slot order (ascending memory stride, r17) with a
        donated ping-pong output slot."""
        from pilosa_tpu.exec.fused import pow2_bucket
        plane = group[0].leaves[0]
        order = sorted({s for p in group for s in p.nodes})
        pos = {s: i for i, s in enumerate(order)}
        out = self.fused.run_selected_counts(
            plane, tuple(order), delta=group[0].delta,
            scratch=self._pp.scratch((pow2_bucket(len(order)),),
                                     "int32"),
            sorted_idx=True)

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for p in group:
                if self._skip(p):
                    continue
                self._deliver(p, host[[pos[s] for s in p.nodes]])
        return out, finish

    def _dispatch_tree(self, group: list[_Pending]):
        """The window's compound-tree Counts over one (plane, overlay)
        pair: union every item's gathered slots and extra operands
        (``exec.tree.assemble_items``), remap the postfix programs
        into the shared operand space and run ONE fused program — one
        memory pass over the union, K answers, packed readback."""
        from pilosa_tpu.exec.tree import assemble_items
        plane = group[0].leaves[0]
        slots, progs, extras = assemble_items([p.nodes for p in group])
        out = self.fused.run_tree_counts(plane, slots, progs, extras,
                                         delta=group[0].delta)

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for k, p in enumerate(group):
                if self._skip(p):
                    continue
                self._deliver(p, int(host[k]))
        return out, finish

    def _fallback_tree(self, group: list[_Pending]) -> None:
        for p in group:
            if self._skip(p):
                continue
            try:
                slots, prog, extras = p.nodes
                out = self.fused.run_tree_counts(
                    p.leaves[0], slots, (prog,), extras, delta=p.delta)
                self._deliver(p, int(np.asarray(out).astype(np.int64)[0]))
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    def _dispatch_rowcounts_delta(self, group: list[_Pending]):
        """Whole-plane row counts of base⊕delta: the group key is the
        (plane, overlay, filter) identity triple, so the whole group
        is ONE scan + one overlay adjustment shared by every item."""
        p0 = group[0]
        flt = p0.leaves[1] if len(p0.leaves) == 2 else None
        out = self.fused.run_rowcounts_delta(p0.leaves[0], p0.delta,
                                             filter_words=flt)

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for p in group:
                if self._skip(p):
                    continue
                self._deliver(p, host)
        return out, finish

    def _fallback_selcounts(self, group: list[_Pending]) -> None:
        import jax.numpy as jnp
        for p in group:
            if self._skip(p):
                continue
            try:
                idx = jnp.asarray(p.nodes, dtype=jnp.int32)
                if p.delta is not None:
                    from pilosa_tpu.ingest.delta import \
                        adjusted_selected_counts
                    d = p.delta
                    self._deliver(p, np.asarray(adjusted_selected_counts(
                        p.leaves[0], idx, d.rows, d.words,
                        d.vals)).astype(np.int64))
                else:
                    self._deliver(p, kernels.shard_totals(
                        kernels.selected_row_counts(p.leaves[0], idx)))
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    @staticmethod
    def _dedupe(group: list[_Pending]):
        """Unique items by leaf identity + the caller index of each
        item's unique representative — N requests over the same
        resident plane compute once and share the read."""
        uniq: dict[tuple, int] = {}
        items: list[_Pending] = []
        assign: list[int] = []
        for p in group:
            k = tuple(id(a) for a in p.leaves)
            slot = uniq.get(k)
            if slot is None:
                slot = uniq[k] = len(items)
                items.append(p)
            assign.append(slot)
        return items, assign

    def _dispatch_rowcounts(self, group: list[_Pending]):
        from pilosa_tpu.exec.fused import pow2_bucket
        items, assign = self._dedupe(group)
        # canonical flag order + pow2 pad (repeating item 0): bounded
        # program set per plane shape, like the aggregate batches
        order = sorted(range(len(items)), key=lambda i: len(items[i].leaves))
        items = [items[i] for i in order]
        back = {old: new for new, old in enumerate(order)}
        assign = [back[a] for a in assign]
        padded = items + [items[0]] * (pow2_bucket(len(items))
                                       - len(items))
        flags = tuple(len(p.leaves) == 2 for p in padded)
        leaves = tuple(a for p in padded for a in p.leaves)
        out = self.fused.run_rowcounts_batch(
            flags, leaves,
            scratch=self._pp.scratch(
                (len(flags), leaves[0].shape[-2]), "int32"))

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for p, slot in zip(group, assign):
                if self._skip(p):
                    continue
                self._deliver(p, host[slot])
        return out, finish

    def _fallback_rowcounts(self, group: list[_Pending]) -> None:
        for p in group:
            if self._skip(p):
                continue
            try:
                flt = p.leaves[1] if len(p.leaves) == 2 else None
                if p.delta is not None:
                    from pilosa_tpu.ingest.delta import \
                        adjusted_row_counts
                    d = p.delta
                    self._deliver(p, np.asarray(adjusted_row_counts(
                        p.leaves[0], d.rows, d.words, d.vals, flt,
                        reduce_shards=False)).astype(np.int64).sum(
                            axis=0))
                else:
                    self._deliver(p, kernels.shard_totals(
                        kernels.row_counts(p.leaves[0], flt)))
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    def _run_distinct(self, group: list[_Pending]) -> None:
        from pilosa_tpu.engine import bsi as bsik
        t0 = time.perf_counter()
        items, assign = self._dedupe(group)
        results: list = [None] * len(items)
        errors: list = [None] * len(items)

        def scan(i: int) -> None:
            p = items[i]
            try:
                flt = p.leaves[1] if len(p.leaves) == 2 else None
                pos, neg = bsik.distinct_presence(p.leaves[0], flt)
                results[i] = (np.asarray(pos), np.asarray(neg))
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        if len(items) == 1:
            scan(0)
        else:
            # NON-identical items (different planes/filters) keep the
            # pre-batcher concurrency: the scans are multi-dispatch
            # block loops, so running them serially in this worker
            # would make the last caller wait out every other scan.
            # Plain threads, NOT _group_pool: this method itself runs
            # inside that pool, and a nested map could deadlock with
            # every pool worker occupied by group runs; thread spawn
            # is noise next to a presence scan.
            ts = [threading.Thread(target=scan, args=(i,))
                  for i in range(len(items))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        for p, slot in zip(group, assign):
            if errors[slot] is not None:
                self._deliver_error(p, errors[slot])
            else:
                self._deliver(p, results[slot])
        # distinct can't join the packed readback (multi-dispatch host
        # loop), so its dispatch observation covers the whole scan —
        # read included — and its bytes land on the same counter
        self.stats.observe("kernel_dispatch_seconds",
                           time.perf_counter() - t0, kind="distinct")
        nbytes = self._group_bytes("distinct", group)
        if nbytes:
            self.stats.count("kernel_bytes_scanned_total", nbytes,
                             kind="distinct")

    @staticmethod
    def _dedupe_pad(items: list[_Pending], assign: list[int],
                    key_rank) -> tuple[list[_Pending], list[int]]:
        """Canonical-order + pow2-pad a deduped item list (the
        range-count dispatch; the Sum/Min/Max families keep theirs in
        ``fused.run_agg_plane_batch``): sort unique items by
        ``key_rank`` so the static program shape is order-independent,
        remap the caller assignment, pad by repeating item 0."""
        from pilosa_tpu.exec.fused import pow2_bucket
        order = sorted(range(len(items)), key=lambda i: key_rank(items[i]))
        items = [items[i] for i in order]
        back = {old: new for new, old in enumerate(order)}
        assign = [back[a] for a in assign]
        padded = items + [items[0]] * (pow2_bucket(len(items))
                                       - len(items))
        return padded, assign

    def _dispatch_aggs(self, kind: str, group: list[_Pending]):
        """The window's BSI Sum/Min/Max items over ONE (plane,
        overlay) pair (the group key carries both identities, r20):
        identical items (same filter) dedupe to one scan, distinct
        filters fuse into one program referencing the plane ONCE, and
        a pending overlay merges in-program (base side excludes the
        touched word columns; the mini side answers them) — aggregates
        stay rebuild- and fold-free under sustained BSI ingest."""
        out, assign, decode = self.fused.run_agg_plane_batch(
            kind, group[0].leaves[0],
            [p.leaves[1] if len(p.leaves) == 2 else None for p in group],
            delta=group[0].delta, bucket=True)

        def finish(host: np.ndarray) -> None:
            for p, slot in zip(group, assign):
                if self._skip(p):
                    continue
                self._deliver(p, decode(host[slot]))
        return out, finish

    def _dispatch_bsirange(self, group: list[_Pending]):
        """The window's BSI Range-counts over one (plane, overlay)
        pair: dedupe by predicate signature, one fused program with
        the plane as a single operand, int32[K] totals into the
        window's packed readback."""
        plane = group[0].leaves[0]
        delta = group[0].delta
        uniq: dict[tuple, int] = {}
        items: list[_Pending] = []
        assign: list[int] = []
        for p in group:
            sig = p.nodes[2]
            slot = uniq.get(sig)
            if slot is None:
                slot = uniq[sig] = len(items)
                items.append(p)
            assign.append(slot)
        padded, assign = self._dedupe_pad(items, assign,
                                          lambda p: p.nodes[2])
        specs = tuple(p.nodes[0] for p in padded)
        operands = tuple(a for p in padded for a in p.nodes[1])
        out = self.fused.run_range_batch(plane, specs, operands,
                                         delta=delta)

        def finish(host: np.ndarray) -> None:
            host = host.astype(np.int64)
            for p, slot in zip(group, assign):
                if self._skip(p):
                    continue
                self._deliver(p, int(host[slot]))
        return out, finish

    def _dispatch_groupby(self, group: list[_Pending]):
        """One GroupBy block per group (the key's sig dedupes
        identical concurrent blocks to a single program); the flat
        int32 output joins the window's packed readback and every
        item unflattens the same host arrays."""
        from pilosa_tpu.exec import groupby as gb
        p0 = group[0]
        args, (agg_kind, meta), _sig = p0.nodes
        planes, ci, lp, fw, ap, dl = args
        out = self.fused.run_groupby_batch(planes, ci, lp, fw, ap,
                                           agg_kind, delta=dl)

        def finish(host: np.ndarray) -> None:
            d = gb.unflatten_block(host, *meta, agg_kind)
            for p in group:
                if self._skip(p):
                    continue
                self._deliver(p, d)
        return out, finish

    def _fallback_groupby(self, group: list[_Pending]) -> None:
        from pilosa_tpu.exec import groupby as gb
        for p in group:
            if self._skip(p):
                continue
            try:
                args, (agg_kind, _meta), _sig = p.nodes
                planes, ci, lp, fw, ap, dl = args
                out = gb.run_block(planes, ci, lp, fw, ap, agg_kind, dl)
                self._deliver(p, {k: np.asarray(v)
                                  for k, v in out.items()})
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    def _fallback_bsirange(self, group: list[_Pending]) -> None:
        """Per-item eager range count (base/mini split applied with
        eager jnp ops — no fused program involved)."""
        import jax.numpy as jnp

        from pilosa_tpu.engine import bsi as bsik
        for p in group:
            if self._skip(p):
                continue
            try:
                (op_keys, has_filter), operands, _sig = p.nodes
                preds = [(operands[2 * i], operands[2 * i + 1], k)
                         for i, k in enumerate(op_keys)]
                flt = operands[-1] if has_filter else None
                from pilosa_tpu.ingest.delta import bsi_sides
                sides = bsi_sides(p.leaves[0], flt, p.delta)
                total = 0
                for pl, fw in sides:
                    words = None
                    for masks, neg, okey in preds:
                        cmp = bsik.range_cmp(pl, masks, neg, fw)[okey]
                        words = cmp if words is None \
                            else jnp.bitwise_and(words, cmp)
                    total += int(kernels.shard_totals(
                        kernels.count(words)))
                self._deliver(p, total)
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)

    def _fallback_aggs(self, kind: str, group: list[_Pending]) -> None:
        from pilosa_tpu.engine import bsi as bsik
        for p in group:
            if self._skip(p):
                continue
            try:
                flt = p.leaves[1] if len(p.leaves) == 2 else None
                from pilosa_tpu.ingest.delta import bsi_sides
                sides = bsi_sides(p.leaves[0], flt, p.delta)
                if kind == "sum":
                    total = cnt = 0
                    for pl, fw in sides:
                        t, c = bsik.sum_count(pl, fw)
                        total += t
                        cnt += c
                    self._deliver(p, (total, cnt))
                else:
                    tuples = []
                    for pl, fw in sides:
                        tuples.extend(bsik.min_max(pl, fw))
                    self._deliver(p, tuples)
            except Exception as e2:  # noqa: BLE001
                self._deliver_error(p, e2)


def _wrap_readback_error(exc: Exception) -> Exception:
    """A failure escaping ``_finish_window`` OUTSIDE the per-item
    fallbacks: wrap as a structured stall error (stage=readback) so
    the window's unfinished items fail loudly instead of wedging."""
    err = _stall_error(f"window readback failed: {exc!r}",
                       stage="readback")
    err.__cause__ = exc
    return err
