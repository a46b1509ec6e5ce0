"""The table of peaks and the bytes a request must read, whatever
implements it."""

from __future__ import annotations

import json
import os

from benchmark.bitmaps import WORDS

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def load_peaks() -> dict:
    with open(_PEAKS) as fh:
        return json.load(fh)


def peak(device_kind: str, name: str) -> float:
    """An unknown device kind is an error, not a default."""
    peaks = load_peaks()
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in the peaks "
                       f"table ({sorted(peaks)})")
    return float(peaks[device_kind][name])


def _leaves(bitmap: dict, rows: set) -> None:
    if "row" in bitmap:
        rows.add(tuple(bitmap["row"]))
        return
    if "cond" in bitmap:
        raise ValueError("no byte count is defined for a range row")
    if bitmap["op"] == "Not":
        rows.add(("_exists", 0))  # Not(x) = exists & ~x
    for a in bitmap["args"]:
        _leaves(a, rows)


def required_row_bytes(calls: list, n_shards: int) -> int:
    """Distinct plane rows the request must read once x the bytes of a
    row.  Defined for Count requests only: a row read by two calls of
    one request is counted once, nothing is counted for scratch."""
    rows: set = set()
    for c in calls:
        if c["call"] != "Count":
            raise ValueError(f"no byte count is defined for {c['call']}")
        _leaves(c["of"], rows)
    return len(rows) * n_shards * WORDS * 4
