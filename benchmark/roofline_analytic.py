"""The bytes an analytic request must read once, whatever implements
it: a floor under the time of a GroupBy / Sum / range-row request, as
``roofline.required_row_bytes`` is for a Count.

Counted once a request, in rows of one shard (``WORDS * 4`` bytes):

- a set field: the rows the request names of it (a ``Row`` leaf names
  one, a GroupBy level all of the field's rows), or the field's code
  width (``ceil(log2 R)`` bit rows and an existence row) where that is
  fewer — the field cannot be read in fewer rows either way;
- an int field named anywhere (a range row, a ``Sum``, an aggregate):
  its bit rows, sign and existence (``bsi_depth(max) + 2``);
- ``Not``: the existence row.

No metric reads it yet: ``run.py`` asks ``roofline.required_row_bytes``
only.

    python3 -m benchmark.roofline_analytic <config.json> <traffic.json>

prints each template's bytes at the configuration's shard count."""

from __future__ import annotations

import json
import math
import sys

from benchmark.bitmaps import WORDS, bsi_depth


def _leaves(bitmap: dict, rows: dict, ints: set) -> None:
    if "row" in bitmap:
        field, row = bitmap["row"]
        rows.setdefault(field, set()).add(row)
    elif "cond" in bitmap:
        ints.add(bitmap["cond"][0])
    else:
        if bitmap["op"] == "Not":
            rows.setdefault("_exists", set()).add(0)
        for a in bitmap["args"]:
            _leaves(a, rows, ints)


def code_width(n_rows: int) -> int:
    return max(1, math.ceil(math.log2(n_rows))) + 1


def required_rows(calls: list, dataset: dict) -> int:
    """Rows of one shard the request must read once."""
    field_rows = {f: len(s["shares"])
                  for f, s in dataset.get("set_fields", {}).items()}
    rows: dict = {}
    ints: set = set()
    whole: set = set()
    for c in calls:
        kind = c["call"]
        for key in ("of", "filter"):
            if c.get(key):
                _leaves(c[key], rows, ints)
        if kind == "GroupBy":
            whole.update(c["fields"])
            if c.get("aggregate"):
                ints.add(c["aggregate"]["sum"])
        elif kind == "Sum":
            ints.add(c["field"])
        elif kind == "TopN":
            whole.add(c["field"])
        elif kind != "Count":
            raise ValueError(f"no byte count is defined for {kind}")
    total = 0
    for field in set(rows) | whole:
        if field == "_exists":
            total += 1
            continue
        n = field_rows[field]
        named = n if field in whole else len(rows[field])
        total += min(named, code_width(n))
    for field in ints:
        total += bsi_depth(dataset["int_fields"][field]["max"]) + 2
    return total


def required_bytes(calls: list, dataset: dict, n_shards: int) -> int:
    return required_rows(calls, dataset) * n_shards * WORDS * 4


if __name__ == "__main__":
    with open(sys.argv[1]) as fh:
        config = json.load(fh)
    with open(sys.argv[2]) as fh:
        mix = json.load(fh)
    for t in mix["templates"]:
        n = required_bytes(t["calls"], config["dataset"], config["shards"])
        print(f"{t['name']}: {n:,} B")
