"""Device health governor: breaker-style healthy→degraded→probing
state for the execution pipeline (r18).

The dispatch pipeline (exec/batcher.py) is one shared device stream —
a sick device (hung XLA compile, stalled dispatch, flaky readback)
poisons every request riding it.  The governor watches the two fault
signals the batcher produces (consecutive dispatch faults, pipeline
watchdog trips) and flips the batcher into DEGRADED serving: solo fast
lane off, readback pipelining off, every collection window executed
inline per item on the proven op-at-a-time fallback path.  After
``probe_after_s`` of degradation, exactly one window is admitted back
onto the fused pipeline as a PROBE — success returns the governor to
HEALTHY, failure re-degrades and schedules the next probe.

State is exported as the ``device_health_state`` gauge (0 healthy,
1 degraded, 2 probing) and the ``deviceHealth`` block on ``/status``.

The happy path is lock-free: ``admit``/``fastlane_ok``/
``record_success`` read one attribute (GIL-atomic) and return when the
state is HEALTHY with no faults outstanding — the governor must cost
the fused pipeline nothing while the device is well.
"""

from __future__ import annotations

import threading
import time

HEALTHY = "healthy"
DEGRADED = "degraded"
PROBING = "probing"

# gauge encoding for device_health_state (documented in the README
# metrics inventory; the /status block carries the string)
STATE_CODE = {HEALTHY: 0, DEGRADED: 1, PROBING: 2}


class DeviceHealthGovernor:
    """Consecutive-fault breaker for the batcher's device pipeline.

    - ``record_fault()``: one fused dispatch failed (fell back per
      item).  ``FAULT_THRESHOLD`` consecutive faults degrade; a fault
      during a probe re-degrades immediately.
    - ``record_trip()``: the pipeline watchdog quarantined a stalled
      window — degrade immediately (a hang is worse than an error).
    - ``record_success()``: a fused window completed cleanly.  Resets
      the consecutive-fault count; a successful PROBE window restores
      HEALTHY.
    - ``admit()``: may this collection window use the fused pipeline?
      HEALTHY → yes.  DEGRADED → no, until ``probe_after_s`` has
      passed, when ONE window is admitted as the probe (state flips to
      PROBING; concurrent windows keep the fallback until the probe's
      verdict).
    """

    FAULT_THRESHOLD = 3

    def __init__(self, stats=None, probe_after_s: float = 5.0,
                 flight=None):
        from pilosa_tpu.obs import NULL_FLIGHT, NopStats
        self._stats = stats or NopStats()
        # flight recorder (r19): every state transition lands on the
        # incident timeline; a degrade ALSO triggers the ring dump —
        # the run-up to the breaker opening is the postmortem
        self.flight = flight or NULL_FLIGHT
        self.probe_after_s = max(0.05, float(probe_after_s))
        self._state = HEALTHY
        self._consecutive = 0
        self._since = time.monotonic()  # last transition
        self._trips = 0
        self._faults_total = 0
        self._lock = threading.Lock()

    # -- hot-path reads (lock-free: single attribute loads) ------------------

    @property
    def state(self) -> str:
        return self._state

    def fastlane_ok(self) -> bool:
        """Solo fast lane admits only while HEALTHY — a degraded or
        probing device must not dispatch inline on caller threads
        (the one place a hang wedges a thread the watchdog cannot
        reclaim)."""
        return self._state == HEALTHY

    def pipelining_ok(self) -> bool:
        """Readback run-ahead is a HEALTHY-only optimization: degraded
        and probe windows finish inline so a stall surfaces (and is
        bounded) one window at a time."""
        return self._state == HEALTHY

    # -- events --------------------------------------------------------------

    def _transition(self, to: str) -> None:
        """Caller holds the lock."""
        came = self._state
        self._state = to
        self._since = time.monotonic()
        self._stats.gauge("device_health_state", STATE_CODE[to])
        self.flight.record("governor", "device", f"{came}->{to}")
        if to == DEGRADED:
            # incident capture: the moment the breaker opens is
            # exactly when the preceding pipeline timeline matters
            self.flight.incident("governor_degrade", "device",
                                 f"from {came}")

    def record_fault(self) -> None:
        with self._lock:
            self._consecutive += 1
            self._faults_total += 1
            if self._state == PROBING:
                # the probe window itself faulted: the device is still
                # sick — re-degrade and schedule the next probe
                self._transition(DEGRADED)
            elif (self._state == HEALTHY
                  and self._consecutive >= self.FAULT_THRESHOLD):
                self._transition(DEGRADED)

    def record_trip(self) -> None:
        with self._lock:
            self._trips += 1
            self._consecutive = 0  # a hang resets the error streak
            if self._state != DEGRADED:
                self._transition(DEGRADED)

    def record_success(self) -> None:
        if self._state == HEALTHY and self._consecutive == 0:
            return  # lock-free happy path
        with self._lock:
            self._consecutive = 0
            if self._state == PROBING:
                self._transition(HEALTHY)

    def admit(self) -> bool:
        """True = this collection window may use the fused pipeline."""
        if self._state == HEALTHY:
            return True  # lock-free happy path
        with self._lock:
            if self._state == HEALTHY:
                return True
            if (self._state == DEGRADED
                    and time.monotonic() - self._since
                    >= self.probe_after_s):
                self._transition(PROBING)
                return True  # this window IS the probe
            return False

    # -- introspection -------------------------------------------------------

    def payload(self) -> dict:
        """The ``/status`` deviceHealth block."""
        with self._lock:
            return {
                "state": self._state,
                "stateCode": STATE_CODE[self._state],
                "consecutiveFaults": self._consecutive,
                "faultsTotal": self._faults_total,
                "watchdogTrips": self._trips,
                "sinceSeconds": round(
                    time.monotonic() - self._since, 3),
                "probeAfterSeconds": self.probe_after_s,
                "faultThreshold": self.FAULT_THRESHOLD,
            }
