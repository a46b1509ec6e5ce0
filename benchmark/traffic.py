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

A **write template** has ``"write"`` in place of ``calls``:

    {"name": ..., "write": {"rides": N, "set_fields": [F, ...],
                            "int_fields": [INT_FIELD, ...]}}

One request of it appends ``N`` new columns ("rides"): for each, one
``Set`` in every set field and one ``SetValue`` in every int field.  It
holds a slot of the cycle like any template, but no entry of the pool:
the *k*-th time the client reaches the slot it writes columns
``first_column + k * N`` and up (``first_column`` is the first column
the loaded data does not hold), so nothing is ever written twice, with
rows and values drawn from ``(seed, k)`` by the ``shares`` / ``edges``
that the configuration states for the loaded data.  A walk shows the
slot as ``WRITE``.  A mix with a write template and more than one
client, or with ``Not`` in a read template, is refused (``MixError``):
with two clients a read in flight beside a write has more than one
right answer, and in a part-filled shard ``Not(x)`` is not ``~x``.
"""

from __future__ import annotations

import copy

import numpy as np

from benchmark import queries

WRITE = -1      # a walk's entry for the write template's slot


class MixError(ValueError):
    """The traffic mix asks for what the harness cannot judge."""


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


def _names_not(node) -> bool:
    if isinstance(node, dict):
        return node.get("op") == "Not" or any(map(_names_not, node.values()))
    return isinstance(node, list) and any(map(_names_not, node))


def write_calls(write: dict, dataset: dict, seed: int, k: int,
                first_column: int) -> list:
    """The ``k``-th request of a write template: ride by ride, a ``Set``
    in every set field, then a ``SetValue`` in every int field."""
    rng = np.random.default_rng([seed, 3, k])
    n = int(write["rides"])
    rows, values = {}, {}
    for f in write["set_fields"]:
        w = np.asarray(dataset["set_fields"][f]["shares"], np.float64)
        rows[f] = rng.choice(len(w), size=n, p=w / w.sum())
    for f in write["int_fields"]:
        spec = dataset["int_fields"][f]
        w = np.asarray(spec["shares"], np.float64)
        edges = np.asarray(spec["edges"], np.int64)
        bucket = rng.choice(len(w), size=n, p=w / w.sum())
        values[f] = rng.integers(edges[:-1][bucket], edges[1:][bucket])
    calls = []
    for j in range(n):
        column = first_column + k * n + j
        calls += [{"call": "Set", "field": f, "column": column,
                   "row": int(rows[f][j])} for f in write["set_fields"]]
        calls += [{"call": "SetValue", "field": f, "column": column,
                   "value": int(values[f][j])} for f in write["int_fields"]]
    return calls


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

        writes = [t for t in mix["templates"] if "write" in t]
        self.write = writes[0]["write"] if writes else None
        if len(writes) > 1 or (writes and int(mix["clients"]) > 1):
            raise MixError(f"traffic mix {mix.get('name')!r}: one write "
                           f"template and one client at the most (the "
                           f"judge has no interval rule yet)")
        if writes and _names_not(mix["templates"]):
            raise MixError(f"traffic mix {mix.get('name')!r}: Not beside a "
                           f"write template (the oracle does not track "
                           f"existence in a part-filled shard)")
        self.cycle_len = len(cycle)
        if int(mix["pool"]) < len(cycle):
            raise ValueError(f"traffic mix {mix.get('name')!r}: a pool of "
                             f"{mix['pool']} holds no whole round of "
                             f"{len(cycle)} templates")
        for i in range(int(mix["pool"])):
            t = cycle[i % len(cycle)]
            self.entries.append(
                WRITE if "write" in t else
                request_id(t, instantiate(t, field_rows, rng)))
        for t in mix["templates"]:
            if "write" in t:
                continue
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
        beyond whole rounds is left out); ``WRITE`` stands where the
        write template's slot comes."""
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
