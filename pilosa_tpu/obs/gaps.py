"""What the host was doing while the device idled.

    curl -XPOST 'localhost:10101/debug/profile?seconds=3&dir=/tmp/prof'
    JAX_PLATFORMS=cpu python -m pilosa_tpu.obs.gaps /tmp/prof

While a ``/debug/profile`` capture is open the server writes every
request stage (``pilosa.http_in`` … ``pilosa.http_out``, see
``obs.metrics.StageTimer``) and every phase of the batcher's threads
(``pilosa.batcher.collect`` / ``group`` / ``dispatch`` / ``read`` /
``deliver``) into the profiler's trace, on the profiler's clock, beside
the device's ops.  This reads the newest ``.xplane.pb`` under the
directory, takes the union of the device planes' ``XLA Ops``, and puts
every idle instant down to the ``pilosa.*`` event that covers it:

- a ``pilosa.batcher.*`` phase where a batcher thread has one open,
- else the stage of a serving thread (``pilosa.compile`` and
  ``pilosa.plane_build``, nested in a stage, win over it),
- else ``no_request``: the server was waiting for its clients.

Where several events of one class are open at once (32 serving
threads), the one that began last names the instant; the second table
counts every serving thread's stage seconds that overlap idle time, so
threads that the first table cannot show are still seen.

CPU only: imports ``jax.profiler.ProfileData`` to read the file and
nothing else of the program.  ``attribute`` and ``reduce_events`` are
pure (tested on synthetic event lists).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)  # "Steps" / "XLA Modules" span their ops
PREFIX = "pilosa."
BATCHER_PREFIX = "pilosa.batcher."
NO_REQUEST = "no_request"


def union(intervals: list) -> list:
    """Sorted, merged [(lo, hi)] of [(lo, hi)]."""
    out: list = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def idle_gaps(busy: list, window: tuple) -> list:
    """The parts of ``window`` = (lo, hi) that no interval of the
    merged ``busy`` list covers."""
    gaps, at = [], window[0]
    for lo, hi in busy:
        if lo > at:
            gaps.append((at, min(lo, window[1])))
        at = max(at, hi)
        if at >= window[1]:
            break
    if at < window[1]:
        gaps.append((at, window[1]))
    return [(lo, hi) for lo, hi in gaps if hi > lo]


def _name_of(active: list) -> str:
    """The event that names an instant: batcher phases before stages,
    and within a class the one that began last."""
    best = None
    for name, lo, _hi in active:
        rank = (name.startswith(BATCHER_PREFIX), lo)
        if best is None or rank > best[0]:
            best = (rank, name)
    return best[1] if best else NO_REQUEST


def attribute(gaps: list, host: list) -> list:
    """``gaps``: sorted disjoint [(lo, hi)]; ``host``: [(name, lo, hi)].
    -> per gap, [(name, ns)] in time order: the gap cut wherever the
    event that names it changes."""
    host = sorted(host, key=lambda e: e[1])
    out, nxt, active = [], 0, []
    for g_lo, g_hi in gaps:
        while nxt < len(host) and host[nxt][1] <= g_lo:
            active.append(host[nxt])
            nxt += 1
        active = [e for e in active if e[2] > g_lo]
        cuts = {g_lo, g_hi}
        cuts.update(e[2] for e in active if e[2] < g_hi)
        j = nxt
        while j < len(host) and host[j][1] < g_hi:
            cuts.add(host[j][1])
            if host[j][2] < g_hi:
                cuts.add(host[j][2])
            j += 1
        parts: list = []
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            while nxt < len(host) and host[nxt][1] <= a:
                active.append(host[nxt])
                nxt += 1
            active = [e for e in active if e[2] > a]
            name = _name_of(active)
            if parts and parts[-1][0] == name:
                parts[-1] = (name, parts[-1][1] + (b - a))
            else:
                parts.append((name, b - a))
        out.append(parts)
    return out


def overlap_seconds(gaps: list, host: list) -> dict:
    """{name: thread-seconds} of every non-batcher event's overlap
    with the idle gaps (an instant counts once per thread in it)."""
    out: collections.Counter = collections.Counter()
    starts = [lo for lo, _ in gaps]
    for name, lo, hi in host:
        if name.startswith(BATCHER_PREFIX):
            continue
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(gaps) and gaps[i][0] < hi:
            ov = min(hi, gaps[i][1]) - max(lo, gaps[i][0])
            if ov > 0:
                out[name] += ov
            i += 1
    return {k: v / 1e9 for k, v in out.items()}


def _totals(parts) -> collections.Counter:
    """{name: ns} of (name, ns) pairs."""
    out: collections.Counter = collections.Counter()
    for name, ns in parts:
        out[name] += ns
    return out


def reduce_events(device_ops: list, host: list) -> dict:
    """``device_ops``: [(lo_ns, hi_ns)] of executed device operations
    (all chips); ``host``: [(name, lo_ns, hi_ns)] of ``pilosa.*``
    events.  The window is the span of the host events (the capture as
    the program saw it), or of the device's ops where there are none."""
    span = [(lo, hi) for _, lo, hi in host] or device_ops
    if not span:
        raise ValueError("the trace holds no pilosa.* event and no "
                         "device op")
    window = (min(lo for lo, _ in span), max(hi for _, hi in span))
    busy = union(device_ops)
    gaps = idle_gaps(busy, window)
    named = attribute(gaps, host)
    by_name = _totals(p for parts in named for p in parts)
    idle_ns = sum(hi - lo for lo, hi in gaps)
    busy_ns = sum(min(hi, window[1]) - max(lo, window[0])
                  for lo, hi in busy
                  if hi > window[0] and lo < window[1])
    longest = sorted(zip(gaps, named),
                     key=lambda gp: gp[0][0] - gp[0][1])[:10]
    covered = union([(lo, hi) for _, lo, hi in host])
    ends = [hi for _, hi in covered]
    inside = 0
    for lo, _ in device_ops:
        i = bisect.bisect_right(ends, lo)
        inside += i < len(covered) and covered[i][0] <= lo
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_s": idle_ns / 1e9,
        "idle_by_name": {k: v / 1e9 for k, v in by_name.most_common()},
        "no_request_share": (by_name[NO_REQUEST] / idle_ns
                             if idle_ns else 0.0),
        "serving_thread_seconds_in_idle": dict(sorted(
            overlap_seconds(gaps, host).items(),
            key=lambda kv: -kv[1])),
        "longest_gaps": [
            {"seconds": (hi - lo) / 1e9,
             "at_s": (lo - window[0]) / 1e9,
             "names": [[n, ns / 1e9]
                       for n, ns in _totals(parts).most_common()]}
            for (lo, hi), parts in longest],
        "device_ops": len(device_ops),
        "host_events": len(host),
        # the two planes share a clock if the device's ops start
        # inside the requests that launched them
        "ops_started_inside_an_event_share": (
            inside / len(device_ops) if device_ops else None)}


def read_xplane(trace_dir: str) -> tuple:
    """-> (device_ops, host_events, file) from the newest .xplane.pb
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device_ops, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in OP_LINES:
                    device_ops += [(e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith(PREFIX)]
    return device_ops, host, files[-1]


def render(r: dict) -> str:
    idle = r["idle_s"] or 1.0
    out = [f"window {r['window_s']:.3f} s, device busy {r['busy_s']:.3f} s, "
           f"idle {r['idle_s']:.3f} s "
           f"({100 * r['idle_s'] / r['window_s']:.1f} %); "
           f"{r['device_ops']} device ops, {r['host_events']} pilosa.* "
           f"events",
           "", "idle seconds by what covered them:"]
    for name, s in r["idle_by_name"].items():
        out.append(f"  {name:<28} {s:9.4f} s  {100 * s / idle:5.1f} %")
    out += ["", "serving threads' stage seconds inside idle time "
                "(every thread counted):"]
    for name, s in r["serving_thread_seconds_in_idle"].items():
        out.append(f"  {name:<28} {s:9.4f} s")
    out += ["", "longest gaps:"]
    for g in r["longest_gaps"]:
        names = ", ".join(f"{n} {s * 1e3:.3f} ms" for n, s in g["names"][:4])
        out.append(f"  {g['seconds'] * 1e3:9.3f} ms at {g['at_s']:.3f} s: "
                   f"{names}")
    share = r["ops_started_inside_an_event_share"]
    if share is not None:
        out += ["", f"clock check: {100 * share:.1f} % of the device's ops "
                    f"start inside a pilosa.* event"]
    return "\n".join(out)


def main(argv: list) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    device_ops, host, path = read_xplane(argv[0])
    print(path)
    if not device_ops:
        print("no TPU device plane in the trace: nothing ran on a chip "
              "that the profiler saw; the pilosa.* events follow")
        for name, ns in _totals(
                (name, hi - lo) for name, lo, hi in host).most_common():
            print(f"  {name:<28} {ns / 1e9:9.4f} s")
        return 1
    print(render(reduce_events(device_ops, host)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
