"""The request model: one JSON shape that is both rendered to PQL and
evaluated by the numpy oracle (which shares no code with pilosa_tpu).

A *call* is one of

    {"call": "Count",   "of": BITMAP}
    {"call": "TopN",    "field": F, "n": N?, "filter": BITMAP?}
    {"call": "Sum",     "field": INT_FIELD, "filter": BITMAP?}
    {"call": "GroupBy", "fields": [F1, F2, ...], "filter": BITMAP?,
                        "aggregate": {"sum": INT_FIELD}?}
    {"call": "Set",      "field": F, "column": C, "row": R}
    {"call": "SetValue", "field": INT_FIELD, "column": C, "value": V}

and a BITMAP is ``{"row": [field, id]}``, a range row over an int field
``{"cond": [INT_FIELD, CMP, V]}`` (CMP one of < <= > >= == !=) or
``{"cond": [INT_FIELD, "between", LO, HI]}`` (both ends closed), or
``{"op": OP, "args": [BITMAP, ...]}`` with OP one of Intersect / Union /
Difference / Xor / Not.  Every column of the datasets exists and holds
a value in every int field, so ``Not(x)`` is ``~x`` and ``!=`` is the
complement of ``==``.

``partial(call, shard)`` is what one shard contributes, ``combine`` adds
the partials of every call, ``finish`` turns the total into the JSON the server must
return for that call.  A GroupBy's partial is the int64 counts of every
combination, ``[R1, R2, ...]``, and under an aggregate the counts and
the sums stacked, ``[2, R1, R2, ...]``.  It is taken one of two ways,
which must agree (the tests hold them equal): ``groupby_by_planes``
ANDs every combination's rows, whatever the fields hold, and
``groupby_by_codes`` gives every column the code of its group and
counts the codes, where no column of the shard is in two rows of one
field (looked up on the shard, not assumed).  ``partial`` takes the
second where it applies to a GroupBy with a filter or an aggregate —
the AND of 250 x 250 x 7 combinations is 57 GB a shard — and the first
for a bare one, which is cheaper so while its combinations are few.

The two write calls render to ``Set(C, F=R)`` and ``Set(C, INT_FIELD=V)``;
the result of each is its acknowledgement, ``true`` for a bit or a value
that changed.  Every read call is a sum over columns, so what a write
request adds to a read's answer is ``partial(call, written(calls, ...))``:
``written`` packs the request's columns, which must all be new, into a
narrow shard of their own.
"""

from __future__ import annotations

import operator

import numpy as np

from benchmark.bitmaps import unpack_bits

_OPS = ("Intersect", "Union", "Difference", "Xor", "Not")
_CMPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
         ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def render_bitmap(b: dict) -> str:
    if "row" in b:
        field, row = b["row"]
        return f"Row({field}={row})"
    if "cond" in b:
        field, op, *values = b["cond"]
        if op == "between" and len(values) == 2:
            return f"Row({values[0]} <= {field} <= {values[1]})"
        if op not in _CMPS or len(values) != 1:
            raise ValueError(f"unknown condition {b['cond']!r}")
        return f"Row({field} {op} {values[0]})"
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
        parts = [f"Rows({f})" for f in c["fields"]]
        if c.get("filter"):
            parts.append("filter=" + render_bitmap(c["filter"]))
        if c.get("aggregate"):
            parts.append(f"aggregate=Sum(field={c['aggregate']['sum']})")
        return f"GroupBy({', '.join(parts)})"
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


def _pack64(bits: np.ndarray) -> np.ndarray:
    """bool[n] -> uint64[ceil(n / 64)], the columns past n clear."""
    packed = np.packbits(bits, bitorder="little")
    return np.pad(packed, (0, -len(packed) % 8)).view(np.uint64)


def _columns(words: np.ndarray) -> np.ndarray:
    """uint64[W/2] -> bool[32 W]."""
    return unpack_bits(words.view(np.uint32))


def _eval_cond(cond: list, shard: dict) -> np.ndarray:
    """A range row: the columns whose value meets the condition.  A
    narrow shard (``written``) is padded to whole words with columns
    that hold no value and meet none."""
    field, op, *values = cond
    vals = shard["ints"][field]
    if op == "between":
        lo, hi = values
        return _pack64((vals >= lo) & (vals <= hi))
    (v,) = values
    return _pack64(_CMPS[op](vals, v))


def eval_bitmap(b: dict, shard: dict) -> np.ndarray:
    """-> uint64[W/2]"""
    if "row" in b:
        field, row = b["row"]
        return _rows(shard, field)[row]
    if "cond" in b:
        return _eval_cond(b["cond"], shard)
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
            vals = vals[_columns(eval_bitmap(c["filter"], shard))
                        [:vals.size]]
        return np.array([vals.sum(dtype=np.int64), vals.size], np.int64)
    if kind == "GroupBy":
        # either path is right for any GroupBy where both apply; a bare
        # one keeps the planes for its cost alone: the entered cells' 10
        # x 8 groups are 4 ms a shard by planes and 80 by codes (sandbox
        # CPU, PR 34), 318 and 1,049 times over in every set-up
        by_codes = groupby_by_codes(c, shard) \
            if c.get("filter") or c.get("aggregate") else None
        return groupby_by_planes(c, shard) if by_codes is None else by_codes
    raise ValueError(f"unknown call {kind!r}")


def groupby_by_planes(c: dict, shard: dict) -> np.ndarray:
    """Every combination's rows ANDed and counted: right whatever the
    fields hold, and as large as the combinations are many."""
    planes = [_rows(shard, f) for f in c["fields"]]
    acc = planes[0]
    if c.get("filter"):
        acc = acc & eval_bitmap(c["filter"], shard)
    for p in planes[1:]:
        # [..., W] x [R, W] -> [..., R, W]
        acc = acc[..., None, :] & p
    counts = _popcount(acc)
    if not c.get("aggregate"):
        return counts
    # bit b of the values as a bitmap: a group's sum is its count under
    # each, weighted (values are never negative: ``written`` refuses one)
    vals = shard["ints"][c["aggregate"]["sum"]].astype(np.int64)
    sums = np.zeros_like(counts)
    for b in range(int(vals.max(initial=0)).bit_length()):
        sums += _popcount(acc & _pack64((vals >> b & 1).astype(bool))) << b
    return np.stack([counts, sums])


def _codes(shard: dict, field: str):
    """int32[32 W]: the row that holds each column, -1 where none does;
    None where some column is in two rows of the field."""
    cache = shard.setdefault("_codes", {})
    if field not in cache:
        rows = _rows(shard, field)
        codes = np.full(rows.shape[1] * 64, -1, np.int32)
        for r, row in enumerate(rows):
            columns = np.flatnonzero(_columns(row))
            if (codes[columns] >= 0).any():
                codes = None
                break
            codes[columns] = r
        cache[field] = codes
    return cache[field]


def groupby_by_codes(c: dict, shard: dict):
    """Every column given the code of its combination, and the codes
    counted: one pass over the columns however many the combinations.
    None where a field of the shard holds a column in two rows."""
    codes = [_codes(shard, f) for f in c["fields"]]
    if any(x is None for x in codes):
        return None
    shape = tuple(len(_rows(shard, f)) for f in c["fields"])
    keep = np.ones(codes[0].size, bool)
    if c.get("filter"):
        keep &= _columns(eval_bitmap(c["filter"], shard))
    for x in codes:
        keep &= x >= 0
    columns = np.flatnonzero(keep)
    group = np.ravel_multi_index([x[columns] for x in codes], shape)
    n_groups = int(np.prod(shape))
    counts = np.bincount(group, minlength=n_groups).astype(np.int64)
    if not c.get("aggregate"):
        return counts.reshape(shape)
    sums = np.zeros(n_groups, np.int64)
    np.add.at(sums, group, shard["ints"][c["aggregate"]["sum"]][columns])
    return np.stack([counts, sums]).reshape((2,) + shape)


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
        counts, sums = total if c.get("aggregate") else (total, None)
        out = []
        for idx in map(tuple, np.argwhere(counts > 0)):  # row-id order
            group = {"group": [{"field": f, "rowID": int(r)}
                               for f, r in zip(c["fields"], idx)],
                     "count": int(counts[idx])}
            if sums is not None:
                group["agg"] = int(sums[idx])
            out.append(group)
        return out
    raise ValueError(f"unknown call {kind!r}")
