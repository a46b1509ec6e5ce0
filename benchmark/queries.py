"""The request model: one JSON shape that is both rendered to PQL and
evaluated by the numpy oracle (which shares no code with pilosa_tpu).

A *call* is one of

    {"call": "Count",   "of": BITMAP}
    {"call": "TopN",    "field": F, "n": N?, "filter": BITMAP?}
    {"call": "Sum",     "field": INT_FIELD, "filter": BITMAP?}
    {"call": "GroupBy", "fields": [F1, F2, ...]}

and a BITMAP is ``{"row": [field, id]}`` or ``{"op": OP, "args":
[BITMAP, ...]}`` with OP one of Intersect / Union / Difference / Xor /
Not.  Every column of both datasets exists, so ``Not(x)`` is ``~x``.

``partial(call, shard)`` is what one shard contributes, ``combine`` adds
the partials of every call, ``finish`` turns the total into the JSON the server must
return for that call.
"""

from __future__ import annotations

import numpy as np

from benchmark.bitmaps import unpack_bits

_OPS = ("Intersect", "Union", "Difference", "Xor", "Not")


def render_bitmap(b: dict) -> str:
    if "row" in b:
        field, row = b["row"]
        return f"Row({field}={row})"
    if b["op"] not in _OPS:
        raise ValueError(f"unknown bitmap op {b['op']!r}")
    return f"{b['op']}({', '.join(render_bitmap(a) for a in b['args'])})"


def render_call(c: dict) -> str:
    kind = c["call"]
    if kind == "Count":
        return f"Count({render_bitmap(c['of'])})"
    if kind == "TopN":
        parts = [c["field"]]
        if c.get("filter"):
            parts.append(render_bitmap(c["filter"]))
        if c.get("n"):
            parts.append(f"n={c['n']}")
        return f"TopN({', '.join(parts)})"
    if kind == "Sum":
        parts = [render_bitmap(c["filter"])] if c.get("filter") else []
        return f"Sum({', '.join(parts + ['field=' + c['field']])})"
    if kind == "GroupBy":
        return "GroupBy(" + ", ".join(f"Rows({f})" for f in c["fields"]) + ")"
    raise ValueError(f"unknown call {kind!r}")


def render(calls: list) -> str:
    return "".join(render_call(c) for c in calls)


def _rows(shard: dict, field: str) -> np.ndarray:
    """The field's rows as uint64[R, W/2]: half the elements to AND and
    to popcount (the view is made once per shard)."""
    cache = shard.setdefault("_u64", {})
    if field not in cache:
        cache[field] = shard["sets"][field].view(np.uint64)
    return cache[field]


def _popcount(words: np.ndarray):
    """Set bits along the last axis (exact: a shard has 2^20 columns)."""
    return np.bitwise_count(words).sum(axis=-1, dtype=np.uint32) \
        .astype(np.int64)


def eval_bitmap(b: dict, shard: dict) -> np.ndarray:
    """-> uint64[W/2]"""
    if "row" in b:
        field, row = b["row"]
        return _rows(shard, field)[row]
    args = [eval_bitmap(a, shard) for a in b["args"]]
    op = b["op"]
    if op == "Not":
        return ~args[0]
    out = args[0]
    for a in args[1:]:
        if op == "Intersect":
            out = out & a
        elif op == "Union":
            out = out | a
        elif op == "Difference":
            out = out & ~a
        elif op == "Xor":
            out = out ^ a
        else:
            raise ValueError(f"unknown bitmap op {op!r}")
    return out


def partial(c: dict, shard: dict):
    kind = c["call"]
    if kind == "Count":
        return int(_popcount(eval_bitmap(c["of"], shard)))
    if kind == "TopN":
        rows = _rows(shard, c["field"])
        if c.get("filter"):
            rows = rows & eval_bitmap(c["filter"], shard)
        return _popcount(rows)
    if kind == "Sum":
        vals = shard["ints"][c["field"]]
        if c.get("filter"):
            vals = vals[unpack_bits(eval_bitmap(c["filter"], shard)
                                    .view(np.uint32))]
        return np.array([vals.sum(dtype=np.int64), vals.size], np.int64)
    if kind == "GroupBy":
        planes = [_rows(shard, f) for f in c["fields"]]
        acc = planes[0]
        for p in planes[1:]:
            # [..., W] x [R, W] -> [..., R, W]
            acc = acc[..., None, :] & p
        return _popcount(acc)
    raise ValueError(f"unknown call {kind!r}")


def combine(totals: list | None, parts: list) -> list:
    """Per-call partials of one more shard (or chunk of shards) added
    to the running totals; ``None`` starts the sum."""
    if totals is None:
        return parts
    return [a + b for a, b in zip(totals, parts)]


def finish(c: dict, total):
    """The server's JSON for this call, from the summed partials."""
    kind = c["call"]
    if kind == "Count":
        return int(total)
    if kind == "TopN":
        counts = [int(x) for x in total]
        order = sorted(range(len(counts)), key=lambda r: (-counts[r], r))
        if c.get("n"):
            order = order[:c["n"]]
        return [{"id": r, "count": counts[r]} for r in order
                if counts[r] > 0]
    if kind == "Sum":
        return {"value": int(total[0]), "count": int(total[1])}
    if kind == "GroupBy":
        out = []
        for idx in np.ndindex(*total.shape):
            if total[idx] > 0:
                out.append({"group": [{"field": f, "rowID": int(r)}
                                      for f, r in zip(c["fields"], idx)],
                            "count": int(total[idx])})
        return out
    raise ValueError(f"unknown call {kind!r}")
