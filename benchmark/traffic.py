"""The one general traffic generator.  A traffic mix is a JSON file of
parameters (``benchmark/traffic/<mix>.json``):

    loop        "closed" — each client sends its next request when the
                previous reply is complete
    clients     closed-loop callers, one thread and one connection each
    connection  "keepalive" (one persistent connection per client)
    pool        number of pool entries drawn from the seed
    trace_seconds   length of the profiler capture in a traced run
    templates   each {"name", "weight"?, "params"?, "foreach"?, "calls"}

A template's ``calls`` use the request model of ``queries.py``; a row
id written ``"$a"`` is a parameter.  ``params`` gives each parameter the
field whose rows are its domain and its distribution (``uniform``, or
``zipf`` with exponent ``s`` over the rows in id order); ``foreach``
repeats the calls once for every row of a field.  Pool entry ``i`` is an
instance of template ``i mod len(cycle)`` (so the shares are exact for
every seed) with parameters drawn from the seed.  Every client walks
the pool in rounds of ``len(cycle)`` requests: a round holds one entry of
every slot of the cycle, in an order of its own, and over the walk each
slot goes through its entries in a seeded permutation.  So a window of
any length sends the same shares of the templates whatever the seed —
the seed decides parameters and order, never the amount of work — and
the walk repeats after a pool's worth of requests.

Besides the pool, ``cover`` lists for every template one request per
value of its widest parameter, each parameter stepping through its own
domain at its own offset: between them they name every row that the
template can name in every position, so that the warm-up can touch all
of them whatever the seed drew.
"""

from __future__ import annotations

import copy

import numpy as np

from benchmark import queries


def _substitute(node, binding: dict):
    if isinstance(node, dict):
        return {k: _substitute(v, binding) for k, v in node.items()}
    if isinstance(node, list):
        return [_substitute(v, binding) for v in node]
    if isinstance(node, str) and node.startswith("$"):
        return binding[node[1:]]
    return node


def _draw(spec: dict, n_rows: int, rng: np.random.Generator) -> int:
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return int(rng.integers(0, n_rows))
    if dist == "zipf":
        w = 1.0 / np.arange(1, n_rows + 1) ** float(spec["s"])
        return int(rng.choice(n_rows, p=w / w.sum()))
    raise ValueError(f"unknown parameter distribution {dist!r}")


def instantiate(template: dict, field_rows: dict,
                rng: np.random.Generator | None, step: int = 0) -> list:
    """One concrete request (a list of calls) of a template: its
    parameters drawn from ``rng``, or without one the ``step``-th
    request of the template's cover."""
    params = template.get("params", {})
    if rng is not None:
        binding = {name: _draw(spec, field_rows[spec["field"]], rng)
                   for name, spec in params.items()}
    else:
        binding = {name: (step + 7 * i) % field_rows[spec["field"]]
                   for i, (name, spec) in enumerate(sorted(params.items()))}
    bindings = [binding]
    for name, spec in template.get("foreach", {}).items():
        bindings = [dict(b, **{name: r}) for b in bindings
                    for r in range(field_rows[spec["field"]])]
    return [_substitute(copy.deepcopy(c), b)
            for b in bindings for c in template["calls"]]


class Pool:
    """``entries[i]`` indexes ``requests`` (the distinct requests, each
    a list of calls with its PQL)."""

    def __init__(self, mix: dict, field_rows: dict, seed: int):
        cycle = [t for t in mix["templates"]
                 for _ in range(int(t.get("weight", 1)))]
        rng = np.random.default_rng([seed, 1])
        self.requests: list = []          # {"template", "calls", "pql"}
        self.entries: list = []
        self.cover: list = []
        by_pql: dict = {}

        def request_id(t: dict, calls: list) -> int:
            pql = queries.render(calls)
            if pql not in by_pql:
                by_pql[pql] = len(self.requests)
                self.requests.append({"template": t["name"], "calls": calls,
                                      "pql": pql})
            return by_pql[pql]

        self.cycle_len = len(cycle)
        if int(mix["pool"]) < len(cycle):
            raise ValueError(f"traffic mix {mix.get('name')!r}: a pool of "
                             f"{mix['pool']} holds no whole round of "
                             f"{len(cycle)} templates")
        for i in range(int(mix["pool"])):
            t = cycle[i % len(cycle)]
            self.entries.append(
                request_id(t, instantiate(t, field_rows, rng)))
        for t in mix["templates"]:
            widest = max([field_rows[p["field"]]
                          for p in t.get("params", {}).values()] or [1])
            for step in range(widest):
                rid = request_id(t, instantiate(t, field_rows, None, step))
                if rid not in self.cover:
                    self.cover.append(rid)
        self.seed = seed

    def client_order(self, client: int) -> np.ndarray:
        """Client ``client``'s walk: request ids, round after round;
        every round holds each slot of the cycle once (the pool's tail
        beyond whole rounds is left out)."""
        rng = np.random.default_rng([self.seed, 2, client])
        n, rounds = self.cycle_len, len(self.entries) // self.cycle_len
        # walk[r, k] = entry of slot k sent in round r
        walk = np.stack([rng.permutation(rounds) * n + k for k in range(n)],
                        axis=1)
        walk = rng.permuted(walk, axis=1)
        return np.asarray(self.entries)[walk.reshape(-1)]

    def distinct_calls(self) -> tuple:
        """(calls, index) — the distinct calls over all requests, and
        for each request the positions of its calls in that list."""
        calls, where, index = [], {}, []
        for r in self.requests:
            ids = []
            for c in r["calls"]:
                key = queries.render_call(c)
                if key not in where:
                    where[key] = len(calls)
                    calls.append(c)
                ids.append(where[key])
            index.append(ids)
        return calls, index
