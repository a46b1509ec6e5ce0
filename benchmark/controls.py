"""Controls: the reference put in the program's place with ONE stated
guarantee broken.  Each takes the window's records and returns records
whose bodies are the control's answers; the run's comparison must then
come out as not correct.  The benchmark's own runs never use them."""

from __future__ import annotations

import json

from benchmark import queries


def _answers(records: list, results_of) -> list:
    return [[(rid, t0, t1, 200, json.dumps({"results": results_of(rid)})
              .encode()) for rid, t0, t1, _, _ in rec] for rec in records]


def lost_shard(records, cell, pool, calls, call_index, totals, seed,
               n_shards) -> list:
    """Durability / staleness broken: exact answers over an index that
    lacks its last shard (acknowledged columns that are not read)."""
    last = cell["generate"](cell["config"]["dataset"], seed, n_shards - 1)
    stale = [t - queries.partial(c, last) for c, t in zip(calls, totals)]
    return _answers(records, lambda rid: [
        queries.finish(calls[i], stale[i]) for i in call_index[rid]])


def approximate(records, cell, pool, calls, call_index, totals, seed,
                n_shards) -> list:
    """Exactness broken: every count kept to four significant digits (an
    approximate answer where the configuration states an exact one)."""
    def coarse(x):
        if isinstance(x, dict):
            return {k: coarse(v) if k in ("count", "value") else v
                    for k, v in x.items()}
        if isinstance(x, list):
            return [coarse(v) for v in x]
        if isinstance(x, int) and abs(x) >= 10 ** 4:
            scale = 10 ** (len(str(abs(x))) - 4)
            return x // scale * scale
        return x
    return _answers(records, lambda rid: [
        coarse(queries.finish(calls[i], totals[i]))
        for i in call_index[rid]])


ALL = {"lost_shard": lost_shard, "approximate": approximate}
