"""The request model: one JSON shape that is both rendered to PQL and
evaluated by the numpy oracle (which shares no code with pilosa_tpu).

A *call* is one of

    {"call": "Count",   "of": BITMAP}
    {"call": "TopN",    "field": F, "n": N?, "filter": BITMAP?}
    {"call": "Sum",     "field": INT_FIELD, "filter": BITMAP?}
    {"call": "GroupBy", "fields": [F1, F2, ...]}
    {"call": "Set",      "field": F, "column": C, "row": R}
    {"call": "SetValue", "field": INT_FIELD, "column": C, "value": V}

and a BITMAP is ``{"row": [field, id]}`` or ``{"op": OP, "args":
[BITMAP, ...]}`` with OP one of Intersect / Union / Difference / Xor /
Not.  Every column of both datasets exists, so ``Not(x)`` is ``~x``.

``partial(call, shard)`` is what one shard contributes, ``combine`` adds
the partials of every call, ``finish`` turns the total into the JSON the server must
return for that call.

The two write calls render to ``Set(C, F=R)`` and ``Set(C, INT_FIELD=V)``;
the result of each is its acknowledgement, ``true`` for a bit or a value
that changed.  Every read call is a sum over columns, so what a write
request adds to a read's answer is ``partial(call, written(calls, ...))``:
``written`` packs the request's columns, which must all be new, into a
narrow shard of their own.
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
    if kind == "Set":
        return f"Set({c['column']}, {c['field']}={c['row']})"
    if kind == "SetValue":
        return f"Set({c['column']}, {c['field']}={c['value']})"
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
            # a narrow shard (``written``) is padded to whole words
            vals = vals[unpack_bits(eval_bitmap(c["filter"], shard)
                                    .view(np.uint32))[:vals.size]]
        return np.array([vals.sum(dtype=np.int64), vals.size], np.int64)
    if kind == "GroupBy":
        planes = [_rows(shard, f) for f in c["fields"]]
        acc = planes[0]
        for p in planes[1:]:
            # [..., W] x [R, W] -> [..., R, W]
            acc = acc[..., None, :] & p
        return _popcount(acc)
    raise ValueError(f"unknown call {kind!r}")


def written(calls: list, field_rows: dict, int_fields: list) -> dict:
    """The columns that one write request creates, as a shard of their
    own, as wide as they are many (padded to whole 64-bit words with
    columns that hold nothing).  Each column must be new — written once,
    with one value in every int field — so that the request ADDS this
    shard's partials to every read: anything else is refused."""
    columns = sorted({c["column"] for c in calls})
    at = {col: i for i, col in enumerate(columns)}
    words = 2 * -(-len(columns) // 64)
    sets = {f: np.zeros((r, words), np.uint32) for f, r in field_rows.items()}
    ints = {f: np.full(len(columns), -1, np.int64) for f in int_fields}
    for c in calls:
        i = at[c["column"]]
        if c["call"] == "Set":
            row = sets[c["field"]][c["row"]]
            if row[i // 32] >> (i % 32) & 1:
                raise ValueError(f"bit written twice: {render_call(c)}")
            row[i // 32] |= np.uint32(1 << (i % 32))
        elif c["call"] == "SetValue":
            if ints[c["field"]][i] >= 0 or c["value"] < 0:
                raise ValueError(f"value written twice, or negative: "
                                 f"{render_call(c)}")
            ints[c["field"]][i] = c["value"]
        else:
            raise ValueError(f"not a write call: {c['call']!r}")
    if any((v < 0).any() for v in ints.values()):
        raise ValueError("a written column lacks a value in an int field")
    return {"sets": sets, "ints": {f: v.astype(np.int32)
                                   for f, v in ints.items()}}


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
