"""The closed-loop load and its arithmetic.

Each client is one thread with one persistent connection; it sends its
next request when the previous reply is complete.  Nothing is parsed or
compared inside the loop: a record is (request id, send time, done
time, HTTP status, body), and every body is compared with the oracle
once the window has closed.

Where the walk holds the write template's slot (``traffic.WRITE``) the
client sends the next write request of ``Writes`` and records it under
the id ``-1 - k``.  The oracle then has a state (``Oracle``): the judge
replays the one client's record in order, a write that was acknowledged
adds what it wrote, and every read is compared with the state at its
send time — so the first read after a write must already see it."""

from __future__ import annotations

import http.client
import json
import math
import statistics
import threading
import time

from benchmark import queries


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile over ALL the values given."""
    if not sorted_values:
        raise ValueError("percentile of no values")
    n = len(sorted_values)
    return sorted_values[max(0, min(n - 1, math.ceil(q * n - 1e-9) - 1))]


class Writes:
    """The write requests of one run, in the order they are sent:
    ``calls_of(k)`` is the ``k``-th, and ``take`` hands out the next one
    rendered (warm-up and window share the count, so no column is
    written twice)."""

    AHEAD = 512     # rendered before a loop starts, not inside it

    def __init__(self, calls_of):
        self.calls_of = calls_of
        self.sent = 0
        self._bodies: list = []

    def render_ahead(self) -> None:
        while len(self._bodies) < self.sent + self.AHEAD:
            self._bodies.append(queries.render(
                self.calls_of(len(self._bodies))).encode())

    def take(self) -> tuple:
        k = self.sent
        if k >= len(self._bodies):
            self.render_ahead()
        self.sent += 1
        return k, self._bodies[k]


class Oracle:
    """What every read must say while writes land.  ``expected[rid]``
    is request ``rid``'s results from the per-call totals as they stand;
    ``absorb(k)`` adds what write request ``k`` wrote (``added(k,
    lose)``: per distinct call, the partial over the request's new
    columns, less the rides in ``lose``)."""

    def __init__(self, calls: list, index: list, totals: list, added):
        self.calls, self.index, self.added = calls, index, added
        self.totals = list(totals)
        self.writes: list = []          # absorbed, in order
        self._answers: dict = {}

    def copy(self) -> "Oracle":
        twin = Oracle(self.calls, self.index, self.totals, self.added)
        twin.writes = list(self.writes)
        return twin

    def absorb(self, k: int, lose: tuple = ()) -> None:
        self.totals = queries.combine(self.totals, self.added(k, lose))
        self.writes.append(k)
        self._answers = {}

    def __getitem__(self, rid: int) -> list:
        if rid not in self._answers:
            self._answers[rid] = [queries.finish(self.calls[i],
                                                 self.totals[i])
                                  for i in self.index[rid]]
        return self._answers[rid]


class Load:
    """Run ``clients`` closed loops against ``server`` for ``seconds``
    (or, in a warm-up, until each has sent ``requests``).
    ``orders[c]`` is client c's walk over request ids and ``bodies[r]``
    request r's PQL bytes; ``writes`` serves the walk's write slots."""

    def __init__(self, server, index: str, orders: list, bodies: list,
                 writes: Writes | None = None):
        self.server = server
        self.path = f"/index/{index}/query"
        self.orders, self.bodies, self.writes = orders, bodies, writes
        self.records: list = [[] for _ in orders]
        self.gaps: list = [[] for _ in orders]
        self.errors: list = []

    def _client(self, c: int, start: threading.Barrier,
                seconds: float, requests: float) -> None:
        order, bodies, path = self.orders[c], self.bodies, self.path
        rec, gaps, writes = self.records[c], self.gaps[c], self.writes
        conn = self.server.connect()
        try:
            conn.connect()
            start.wait()
            deadline = self.t0 + seconds
            i, t_done = 0, None
            while True:
                rid = int(order[i % len(order)])
                t_send = time.perf_counter()
                if t_send >= deadline or i >= requests:
                    break
                if t_done is not None:
                    gaps.append(t_send - t_done)
                if rid < 0:
                    k, body = writes.take()
                    rid = -1 - k
                else:
                    body = bodies[rid]
                try:
                    conn.request("POST", path, body=body)
                    resp = conn.getresponse()
                    body, status = resp.read(), resp.status
                except (OSError, http.client.HTTPException) as e:
                    body, status = repr(e).encode(), 0
                    conn.close()
                    conn = self.server.connect()
                t_done = time.perf_counter()
                rec.append((rid, t_send, t_done, status, body))
                i += 1
        except Exception as e:  # noqa: BLE001 — re-raised by run()
            self.errors.append(e)
            start.abort()
        finally:
            conn.close()

    def run(self, seconds: float, during=None,
            requests: float = math.inf) -> None:
        """``during(t0)`` runs on the caller's thread while the clients
        loop (the traced run's profile capture)."""
        n = len(self.orders)
        if self.writes is not None:
            self.writes.render_ahead()
        start = threading.Barrier(n + 1)
        self.t0 = float("inf")
        threads = [threading.Thread(target=self._client,
                                    args=(c, start, seconds, requests),
                                    name=f"client-{c}") for c in range(n)]
        for t in threads:
            t.start()
        # the window opens when every client is connected
        self.t0 = time.perf_counter() + 0.05
        try:
            start.wait()
        except threading.BrokenBarrierError:
            pass
        try:
            if during is not None:
                during(self.t0)
        finally:
            for t in threads:
                t.join()
        if self.errors:
            raise self.errors[0]


def judge(records: list, expected, write_calls: int = 0) -> dict:
    """Compare every response with the oracle.  -> attempted, failed
    (no 200), wrong (a 200 that says the wrong thing), first_wrong.

    ``expected`` is the list of every request's results or, for a mix
    that writes, the ``Oracle`` at the state the record starts from (it
    is left at the state the record ends in).  A write request's right
    answer is ``write_calls`` acknowledgements, each ``true``: one that
    has them is absorbed (``acked``), one answered 200 without them
    counts in ``acks_wrong``, and one that failed leaves its columns
    unknown — neither is assumed absent or present, the run is then
    not correct."""
    attempted = failed = wrong = acked = acks_wrong = 0
    first = None
    ok = []
    for rec in records:
        for rid, t_send, t_done, status, body in rec:
            attempted += 1
            good = False
            want = expected[rid] if rid >= 0 else [True] * write_calls
            if status != 200:
                failed += 1
                first = first or f"request {rid}: HTTP {status} {body[:300]!r}"
            else:
                try:
                    good = json.loads(body).get("results") == want
                except ValueError:
                    good = False
                if not good:
                    first = first or (f"request {rid}: got {body[:300]!r} "
                                      f"want {json.dumps(want)[:300]}")
                if rid >= 0:
                    wrong += not good
                elif good:
                    acked += 1
                    expected.absorb(-1 - rid)
                else:
                    acks_wrong += 1
            ok.append(good)
    return {"attempted": attempted, "failed": failed, "wrong": wrong,
            "acked": acked, "acks_wrong": acks_wrong,
            "first_wrong": first, "ok": ok}


def window_stats(records: list, ok: list, t0: float, seconds: float) -> dict:
    """End-to-end arithmetic over ALL requests of the window: the rate
    counts the correct answers complete by the window's close over the
    window's whole length; the percentiles are over every request sent
    in it, however late it finished."""
    flat = [r for rec in records for r in rec]
    t_end = t0 + seconds
    lat = sorted((t_done - t_send) * 1e3 for _, t_send, t_done, _, _ in flat)
    # correct answers complete in each whole second of the window: says
    # whether a run's rate wandered inside the run or between runs
    per_second = [0] * max(1, math.ceil(seconds))
    for r, good in zip(flat, ok):
        if good and r[2] <= t_end:
            per_second[min(len(per_second) - 1, int(r[2] - t0))] += 1
    done_in = sum(per_second)
    # the write requests, and the first read after each (one client)
    writes = [i for i, r in enumerate(flat) if r[0] < 0]
    after = [i + 1 for i in writes if i + 1 < len(flat)
             and flat[i + 1][0] >= 0]

    def mean_ms(at: list):
        return statistics.fmean((flat[i][2] - flat[i][1]) * 1e3
                                for i in at) if at else None
    return {"requests": len(flat), "completed_correct_in_window": done_in,
            "writes": len(writes), "write_ms": mean_ms(writes),
            "read_after_write_ms": mean_ms(after),
            "completed_per_second": per_second,
            "requests_per_s": done_in / seconds,
            "latency_p50_ms": percentile(lat, 0.50),
            "latency_p95_ms": percentile(lat, 0.95),
            "latency_mean_ms": statistics.fmean(lat),
            "latency_max_ms": lat[-1]}
