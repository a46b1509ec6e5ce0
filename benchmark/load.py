"""The closed-loop load and its arithmetic.

Each client is one thread with one persistent connection; it sends its
next request when the previous reply is complete.  Nothing is parsed or
compared inside the loop: a record is (request id, send time, done
time, HTTP status, body), and every body is compared with the oracle
once the window has closed."""

from __future__ import annotations

import http.client
import json
import math
import statistics
import threading
import time


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile over ALL the values given."""
    if not sorted_values:
        raise ValueError("percentile of no values")
    n = len(sorted_values)
    return sorted_values[max(0, min(n - 1, math.ceil(q * n - 1e-9) - 1))]


class Load:
    """Run ``clients`` closed loops against ``server`` for ``seconds``.
    ``orders[c]`` is client c's walk over request ids and ``bodies[r]``
    request r's PQL bytes."""

    def __init__(self, server, index: str, orders: list, bodies: list):
        self.server = server
        self.path = f"/index/{index}/query"
        self.orders, self.bodies = orders, bodies
        self.records: list = [[] for _ in orders]
        self.gaps: list = [[] for _ in orders]
        self.errors: list = []

    def _client(self, c: int, start: threading.Barrier,
                seconds: float) -> None:
        order, bodies, path = self.orders[c], self.bodies, self.path
        rec, gaps = self.records[c], self.gaps[c]
        conn = self.server.connect()
        try:
            conn.connect()
            start.wait()
            deadline = self.t0 + seconds
            i, t_done = 0, None
            while True:
                rid = int(order[i % len(order)])
                t_send = time.perf_counter()
                if t_send >= deadline:
                    break
                if t_done is not None:
                    gaps.append(t_send - t_done)
                try:
                    conn.request("POST", path, body=bodies[rid])
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

    def run(self, seconds: float, during=None) -> None:
        """``during(t0)`` runs on the caller's thread while the clients
        loop (the traced run's profile capture)."""
        n = len(self.orders)
        start = threading.Barrier(n + 1)
        self.t0 = float("inf")
        threads = [threading.Thread(target=self._client,
                                    args=(c, start, seconds),
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


def judge(records: list, expected: list) -> dict:
    """Compare every response with the oracle.  -> attempted, failed
    (no 200), wrong (a 200 that says the wrong thing), first_wrong."""
    attempted = failed = wrong = 0
    first = None
    ok = []
    for rec in records:
        for rid, t_send, t_done, status, body in rec:
            attempted += 1
            good = False
            if status != 200:
                failed += 1
                first = first or f"request {rid}: HTTP {status} {body[:300]!r}"
            else:
                try:
                    good = json.loads(body).get("results") == expected[rid]
                except ValueError:
                    good = False
                if not good:
                    wrong += 1
                    first = first or (f"request {rid}: got {body[:300]!r} "
                                      f"want {json.dumps(expected[rid])[:300]}")
            ok.append(good)
    return {"attempted": attempted, "failed": failed, "wrong": wrong,
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
    return {"requests": len(flat), "completed_correct_in_window": done_in,
            "completed_per_second": per_second,
            "requests_per_s": done_in / seconds,
            "latency_p50_ms": percentile(lat, 0.50),
            "latency_p95_ms": percentile(lat, 0.95),
            "latency_mean_ms": statistics.fmean(lat),
            "latency_max_ms": lat[-1]}
