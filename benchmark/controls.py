"""Controls: the reference put in the program's place with ONE stated
guarantee broken.  Each takes the window's records and returns records
whose bodies are the control's answers; the run's comparison must then
come out as not correct.  The benchmark's own runs never use them.

The controls of a mix that writes (``WRITE``) take the window's records,
the oracle at the state the window started from and the number of calls
in a write request, and return the control's records and the control's
own final state, from which it answers after the restart."""

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
    """Exactness broken: every count, and every sum, kept to four
    significant digits (an approximate answer where the configuration
    states an exact one)."""
    def coarse(x):
        if isinstance(x, dict):
            return {k: coarse(v) if k in ("count", "value", "agg") else v
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


def _replay(records: list, state, write_calls: int, lose_a_ride=False,
            stale=False, forget_last=False) -> tuple:
    """The reference run over the client's record: every write
    acknowledged and absorbed, every read answered from the state."""
    acks = json.dumps({"results": [True] * write_calls}).encode()
    out, before_last = [], state
    for rec in records:
        new, before = [], None
        for rid, t0, t1, _, _ in rec:
            if rid < 0:
                before_last = state.copy()
                before = before_last if stale else None
                state.absorb(-1 - rid, lose=(0,) if lose_a_ride else ())
                lose_a_ride = False
                body = acks
            else:
                src = state if before is None else before
                body = json.dumps({"results": src[rid]}).encode()
                before = None
            new.append((rid, t0, t1, 200, body))
        out.append(new)
    return out, before_last if forget_last else state


def lost_write(records, state, write_calls) -> tuple:
    """Durability broken inside the window: the first write request is
    acknowledged whole and one of its rides is never stored."""
    return _replay(records, state, write_calls, lose_a_ride=True)


def stale_read(records, state, write_calls) -> tuple:
    """Read-your-writes broken: the first read after every write is
    answered from the state before it."""
    return _replay(records, state, write_calls, stale=True)


def lost_after_restart(records, state, write_calls) -> tuple:
    """Durability broken across the restart: exact answers all through
    the window, and the last acknowledged write request is gone once
    the server has been stopped and booted again."""
    return _replay(records, state, write_calls, forget_last=True)


READ = {"lost_shard": lost_shard, "approximate": approximate}
WRITE = {"lost_write": lost_write, "stale_read": stale_read,
         "lost_after_restart": lost_after_restart}
ALL = {**READ, **WRITE}
