"""The reduction from a profiler trace to numbers.  It knows nothing of
cells.  ``reduce_events`` is pure (tested on synthetic event lists);
``read_xplane`` needs ``jax.profiler.ProfileData`` and so runs in a
child process started with ``JAX_PLATFORMS=cpu``:

    python benchmark/tracered.py <trace_dir> <out.json>
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
# the device plane's lines that hold executed operations; "Steps" and
# "XLA Modules" span the operations they contain and would hide gaps
OP_LINES = ("XLA Ops",)


class NoDevicePlane(RuntimeError):
    """The trace has no TPU device plane: nothing ran on a chip that
    the profiler saw."""


def union_seconds(intervals: list) -> tuple:
    """(busy seconds, gaps) of [(start_ns, end_ns)]: the union of the
    intervals, and the idle gaps between them as (seconds, after)."""
    busy, gaps = 0, []
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None:
            cur_lo, cur_hi = lo, hi
        elif lo <= cur_hi:
            cur_hi = max(cur_hi, hi)
        else:
            busy += cur_hi - cur_lo
            gaps.append(((lo - cur_hi) / 1e9, cur_hi))
            cur_lo, cur_hi = lo, hi
    if cur_hi is not None:
        busy += cur_hi - cur_lo
    return busy / 1e9, gaps


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(event_name: str) -> str:
    """An XLA op's event name is its whole HLO line; keep the op's name
    without its ``.N`` suffix and the shape it produces without the
    layouts, so that the copies of one program's op add up under one
    name: ``%convert_reduce_fusion.4 = s32[954]{0:T(1024)} fusion(...)``
    -> ``convert_reduce_fusion s32[954]``.  Any other name is kept."""
    head, eq, rest = event_name.partition(" = ")
    if not eq or not head.startswith("%"):
        return event_name
    base = re.sub(r"\.\d+$", "", head[1:])
    shape = _LAYOUT.sub("", rest)
    if shape.startswith("("):            # a tuple of shapes
        shape = shape[:shape.index(")") + 1] if ")" in shape else shape
    else:
        shape = shape.split(" ", 1)[0]
    return f"{base} {shape}"[:160]


def reduce_events(device_events: dict, capture_seconds: float) -> dict:
    """``device_events``: {device plane: [(name, start_ns, duration_ns)]}
    of executed operations.  Busy is the union of the intervals in
    which an operation ran, averaged over the chips; the window is the
    capture's length, or the span of the device's events where that is
    longer."""
    if not device_events:
        raise NoDevicePlane("no TPU device plane in the trace")
    busy, spans, by_name, all_gaps = [], [], collections.Counter(), []
    n_events = 0
    for plane, events in device_events.items():
        iv = [(s, s + d) for _, s, d in events]
        b, gaps = union_seconds(iv)
        busy.append(b)
        spans.append((max(hi for _, hi in iv) - min(lo for lo, _ in iv))
                     / 1e9 if iv else 0.0)
        all_gaps += [g for g, _ in gaps]
        n_events += len(events)
        for name, _, d in events:
            by_name[short_name(name)] += d
    if n_events == 0:
        raise NoDevicePlane("the TPU device plane holds no operation")
    busy_s = sum(busy) / len(busy)
    window_s = max([capture_seconds] + spans)
    chips = len(device_events)
    top = [[name, ns / 1e9 / chips] for name, ns in by_name.most_common(10)]
    longest = sorted(all_gaps, reverse=True)[:10]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_events": n_events, "chips_traced": chips,
            "idle_gap_count": len(all_gaps),
            "idle_gap_total_s": sum(all_gaps) / chips,
            "breakdown": {
                "device_ops": top,
                # until the program writes its stage spans into the
                # profiler's trace no gap can be put to a host activity
                "idle_gaps": [[f"unattributed_{i + 1}", g]
                              for i, g in enumerate(longest)]}}


def read_xplane(trace_dir: str) -> dict:
    """-> {"device_events", "planes", "layout"} from the newest
    .xplane.pb under ``trace_dir``; only device planes are walked."""
    import jax
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise NoDevicePlane(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    device_events, layout, planes = {}, [], []
    for plane in data.planes:
        planes.append(plane.name)
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            if line.name in OP_LINES:
                device_events.setdefault(plane.name, []).extend(events)
            layout.append({"plane": plane.name, "line": line.name,
                           "events": len(events),
                           "seconds": sum(d for _, _, d in events) / 1e9})
    return {"device_events": device_events, "planes": planes,
            "layout": layout, "file_bytes": os.path.getsize(files[-1])}


def main(argv: list) -> int:
    trace_dir, out_path, capture_seconds = argv[0], argv[1], float(argv[2])
    raw = read_xplane(trace_dir)
    out = {k: raw[k] for k in ("planes", "layout", "file_bytes")}
    try:
        out["reduced"] = reduce_events(raw["device_events"], capture_seconds)
    except NoDevicePlane as e:
        out["no_device_plane"] = str(e)
    with open(out_path, "w") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
