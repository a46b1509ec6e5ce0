"""Per-layer metrics are data: ``benchmark/metrics/<name>.json`` holds
the metric's declaration and a ``reader`` — a small expression over
generic leaves, evaluated against what one run collected:

    {"status_delta": [path...], "default": x?}   /status after - before
    {"status_mean": [path...]}    a {count, sum} node: dsum / dcount
    {"status_end": [path...]}     /status after the window
    {"prom_delta": "series{labels}"}             /metrics after - before
    {"client": name}   the load generator's own arithmetic
    {"run": name}      the harness's own timings
    {"trace": name}    the device-trace reduction (absent without one)
    {"peak": name}     the peaks table, by the server's device kind
    {"add"|"sub"|"mul"|"div": [expr or number, ...]}

A leaf that finds nothing to read yields None, and so does every
expression over it: the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import functools
import operator

from benchmark import roofline

_ARITHMETIC = {"add": operator.add, "sub": operator.sub,
               "mul": operator.mul, "div": operator.truediv}


def _dig(tree, path: list):
    for key in path:
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def evaluate(expr, ctx: dict):
    """``ctx``: status_before, status_after, prom_before, prom_after,
    client, run, trace (or None), device_kind."""
    if isinstance(expr, (int, float)):
        return float(expr)
    (kind, arg), = ((k, v) for k, v in expr.items() if k != "default")
    if kind in _ARITHMETIC:
        vals = [evaluate(a, ctx) for a in arg]
        if any(v is None for v in vals):
            return None
        if kind == "div" and 0 in vals[1:]:
            return None
        return functools.reduce(_ARITHMETIC[kind], vals)
    if kind == "status_delta":
        a, b = _dig(ctx["status_before"], arg), _dig(ctx["status_after"], arg)
        if a is None and b is None:
            return expr.get("default")
        return float(b or 0) - float(a or 0)
    if kind == "status_mean":
        a = _dig(ctx["status_before"], arg) or {"count": 0, "sum": 0.0}
        b = _dig(ctx["status_after"], arg)
        if b is None or b["count"] == a["count"]:
            return None
        return (b["sum"] - a["sum"]) / (b["count"] - a["count"])
    if kind == "status_end":
        v = _dig(ctx["status_after"], arg)
        return None if v is None else float(v)
    if kind == "prom_delta":
        if arg not in ctx["prom_after"]:
            return None
        return ctx["prom_after"][arg] - ctx["prom_before"].get(arg, 0.0)
    if kind in ("client", "run", "trace"):
        src = ctx.get(kind)
        if not src or src.get(arg) is None:
            return None
        return float(src[arg])
    if kind == "peak":
        return roofline.peak(ctx["device_kind"], arg)
    raise ValueError(f"unknown reader {kind!r}")
