"""What the host was doing while the device idled.

    curl -XPOST 'localhost:10101/debug/profile?seconds=3&dir=/tmp/prof'
    JAX_PLATFORMS=cpu python -m pilosa_tpu.obs.gaps /tmp/prof [seconds]

While a ``/debug/profile`` capture is open the server writes every
request stage (``pilosa.http_in`` … ``pilosa.http_out``, see
``obs.metrics.StageTimer``) and every phase of the batcher's threads
(``pilosa.batcher.collect`` / ``group`` / ``dispatch`` / ``read`` /
``deliver``) into the profiler's trace, on the profiler's clock, beside
the device's ops.  This reads the newest ``.xplane.pb`` under the
directory, takes each chip's union of its device plane's ``XLA Ops``,
and puts every idle instant of every chip down to the ``pilosa.*``
event that covers it:

- a ``pilosa.batcher.*`` phase where a batcher thread has one open,
- else the stage of a serving thread (``pilosa.compile``,
  ``pilosa.plane_build``, ``pilosa.mesh.launch_wait``,
  ``pilosa.groupby.reach`` and ``pilosa.planes.code_rows``, nested in a
  stage, win over it),
- else ``no_request``: the server was waiting for its clients.

Where several events of one class are open at once (32 serving
threads), the one that began last names the instant; the second table
counts every serving thread's stage seconds that overlap idle time, so
threads that the first table cannot show are still seen.

The window is the capture's length (or the longest chip's span of ops,
where that is longer) from the capture's first event, moved where a
chip's ops would fall outside it: a chip's idle seconds are then the
window less its busy seconds, and seconds are averaged over the chips.
Three groups sum to that idle time: ``read`` (``pilosa.read`` and
``pilosa.batcher.read``: a thread blocked on the device → host read),
``no_request`` and ``host`` (every other name).

The profiler's device clock is not the host's (see
:func:`clock_shifts`), and the trace fixes the offset only between two
anchors: a program cannot start before the host enqueued it, nor end
after the runtime's host thread saw it end.  The device is put at the
first (the launch latency taken as 0); the second says how far off
that can be (``bracket_ns`` of :func:`read_xplane`: launch latency +
completion notice, which the trace cannot split).  So a read's tail after its last device op is
given as bounds: ``read_after_completion_s_mean`` (from the runtime's
completion event, host clock only) and ``read_tail_s_upper``.  The
attribution is given only where both placements of the device pass
the clock check: at least ``CLOCK_GATE`` of its ops start inside some
``pilosa.*`` event.

Reading the file takes ``jax.profiler.ProfileData`` and nothing else
of the program; ``attribute`` and ``reduce_events`` are pure (tested
on synthetic event lists).
"""

from __future__ import annotations

import bisect
import collections
import glob
import math
import os
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)  # "Steps" / "XLA Modules" span their ops
MODULE_LINE = "XLA Modules"  # one event a program, its flow id
SHIFT_WINDOW = 64
PREFIX = "pilosa."
BATCHER_PREFIX = "pilosa.batcher."
NO_REQUEST = "no_request"
AFTER_EVENTS = "after_the_last_event"
READ_NAMES = ("pilosa.read", "pilosa.batcher.read")
# below this share of device ops starting inside a pilosa.* event the
# host and device clocks disagree, and no idle instant is named
CLOCK_GATE = 0.95
# what a reduction under the completion anchor reports beside the other
LATE_KEYS = ("ops_started_inside_an_event_share", "idle_read_s",
             "idle_host_s", "idle_no_request_s")


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


def group_of(name: str) -> str:
    """``read``, ``no_request`` or ``host``: the three parts of the
    idle time (no event open is ``no_request``, after the last too)."""
    if name in READ_NAMES:
        return "read"
    return NO_REQUEST if name in (NO_REQUEST, AFTER_EVENTS) else "host"


def _after(lo: float, parts: list, last_event: float) -> list:
    """A gap's ``parts`` from ``lo`` on, the no-request time after the
    capture's last host event named ``AFTER_EVENTS``: the device's
    tracer stops some tens of ms after the host's, and what the host
    did then was not recorded."""
    out, at = [], lo
    for name, ns in parts:
        cut = min(max(last_event - at, 0.0), ns)
        if name != NO_REQUEST or cut == ns:
            out.append((name, ns))
        else:
            out += [(name, cut)] * (cut > 0) + [(AFTER_EVENTS, ns - cut)]
        at += ns
    return out


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


def _window(busy: list, start: float, length: float) -> tuple:
    """(lo, hi) of ``length`` from ``start``, moved to hold every
    interval of the merged ``busy`` list (``length`` is at least their
    span)."""
    lo = min(start, busy[0][0]) if busy else start
    if busy and lo + length < busy[-1][1]:
        lo = busy[-1][1] - length
    return lo, lo + length


def read_tails(host: list, device_ops: dict) -> list:
    """Per read event, ns from the last device op that ends inside it
    (on any chip) to its end; the whole event where none does."""
    ends = sorted(hi for ops in device_ops.values() for _, hi in ops)
    out = []
    for name, lo, hi in host:
        if name not in READ_NAMES:
            continue
        i = bisect.bisect_right(ends, hi)
        last = ends[i - 1] if i and ends[i - 1] >= lo else lo
        out.append(hi - last)
    return out


def read_after_completion(host: list, completions: list) -> list:
    """Per read event that the runtime saw a program end inside, ns
    from the last such completion (sorted host-clock times) to its end:
    the part of the read after the device, on the host's clock alone.
    A program ends before its completion is seen, so this is at most
    the read's tail after its last device op."""
    out = []
    for name, lo, hi in host:
        if name not in READ_NAMES:
            continue
        i = bisect.bisect_right(completions, hi)
        if i and completions[i - 1] >= lo:
            out.append(hi - completions[i - 1])
    return out


def reduce_events(device_ops: dict, host: list,
                  capture_seconds: float | None = None,
                  completions: list | None = None,
                  late_ops: dict | None = None) -> dict:
    """``device_ops``: {device plane: [(lo_ns, hi_ns)]} of executed
    operations; ``host``: [(name, lo_ns, hi_ns)] of ``pilosa.*``
    events.  The window is ``capture_seconds`` long (the span of the
    host events where it is None), or a chip's span of ops where that
    is longer; it starts at the capture's first event.
    ``completions``: sorted host-clock times at which the runtime saw
    a program end; ``late_ops``: ``device_ops`` placed by the
    completion anchor instead, reduced too (``late_anchor``) and held
    to the clock check as well."""
    chips = {plane: union(ops) for plane, ops in device_ops.items()}
    span = [(lo, hi) for _, lo, hi in host] or \
        [iv for busy in chips.values() for iv in busy]
    if not span:
        raise ValueError("the trace holds no pilosa.* event and no "
                         "device op")
    start = min(lo for lo, _ in span)
    length = max([capture_seconds * 1e9 if capture_seconds is not None
                  else max(hi for _, hi in span) - start]
                 + [busy[-1][1] - busy[0][0] for busy in chips.values()
                    if busy])
    by_name: collections.Counter = collections.Counter()
    serving: collections.Counter = collections.Counter()
    busy_ns = idle_ns = 0.0
    named_gaps, inner_gaps = [], []
    last_event = max((hi for _, _, hi in host), default=math.inf)
    for busy in chips.values():
        window = _window(busy, start, length)
        gaps = idle_gaps(busy, window)
        named = [_after(g[0], parts, last_event)
                 for g, parts in zip(gaps, attribute(gaps, host))]
        by_name.update(_totals(p for parts in named for p in parts))
        serving.update(overlap_seconds(gaps, host))
        busy_ns += sum(hi - lo for lo, hi in busy)
        idle_ns += sum(hi - lo for lo, hi in gaps)
        named_gaps += zip(gaps, named)
        # between two ops, as the benchmark's own reduction lists them
        inner_gaps += [(g, p) for g, p in zip(gaps, named)
                       if window[0] < g[0] and g[1] < window[1]]
    n = max(1, len(chips))
    covered = union([(lo, hi) for _, lo, hi in host])
    ends = [hi for _, hi in covered]
    starts = [lo for busy in device_ops.values() for lo, _ in busy]
    by_end = sorted(host, key=lambda e: e[2])
    last_ends = [hi for _, _, hi in by_end]
    inside, outside = 0, collections.Counter()
    for lo in starts:
        i = bisect.bisect_right(ends, lo)
        if i < len(covered) and covered[i][0] <= lo:
            inside += 1
            continue
        # where the clocks part: the event that ended last before it
        j = bisect.bisect_right(last_ends, lo)
        outside[by_end[j - 1][0] if j else "before_the_first_event"] += 1
    share = inside / len(starts) if starts else None

    def longest(gaps: list) -> list:
        return sorted(gaps, key=lambda gp: gp[0][0] - gp[0][1])[:10]

    out = {
        "window_s": length / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "idle_s": idle_ns / n / 1e9,
        "chips": len(chips),
        "idle_by_name": {k: v / n / 1e9 for k, v in by_name.most_common()},
        "no_request_share": (by_name[NO_REQUEST] / idle_ns
                             if idle_ns else 0.0),
        "serving_thread_seconds_in_idle": {
            k: v / n for k, v in serving.most_common()},
        "longest_gaps": [
            {"seconds": (hi - lo) / 1e9,
             "at_s": (lo - start) / 1e9,
             "names": [[name, ns / 1e9]
                       for name, ns in _totals(parts).most_common()]}
            for (lo, hi), parts in longest(named_gaps)],
        "device_ops": len(starts),
        "host_events": len(host),
        # the two planes share a clock if the device's ops start
        # inside the requests that launched them
        "ops_started_inside_an_event_share": share,
        "ops_outside_after": dict(outside.most_common(5))}
    checks = [share]
    if late_ops is not None:
        late = reduce_events(late_ops, host, capture_seconds)
        out["late_anchor"] = {k: late.get(k) for k in LATE_KEYS}
        checks.append(late["ops_started_inside_an_event_share"])
    if completions is not None:
        after = read_after_completion(host, completions)
        out.update(read_after_completion_s_mean=(
            sum(after) / len(after) / 1e9 if after else None),
            reads_with_completion=len(after))
    if host and all(c is not None and c >= CLOCK_GATE for c in checks):
        groups: collections.Counter = collections.Counter()
        for name, s in out["idle_by_name"].items():
            groups[group_of(name)] += s
        tails = read_tails(host, device_ops)
        out.update(
            idle_by_activity=out["idle_by_name"],
            idle_read_s=groups["read"], idle_host_s=groups["host"],
            idle_no_request_s=groups[NO_REQUEST],
            # with the launch latency taken as 0 the device's ops sit
            # as early as they can: the tail is at most this
            read_tail_s_upper=(sum(tails) / len(tails) / 1e9
                               if tails else None),
            reads_captured=len(tails),
            # the ten longest gaps between two ops, each named for the
            # activity that covered most of it
            idle_gaps=[[f"{_totals(parts).most_common(1)[0][0]}_{i + 1}",
                        (hi - lo) / 1e9]
                       for i, ((lo, hi), parts)
                       in enumerate(longest(inner_gaps))])
    return out


def _fit(raw: list, upper: bool) -> list:
    """sorted [(start_ns, d_ns)] -> [(start_ns, shift_ns)], each the
    upper (or lower) quartile of the ``SHIFT_WINDOW`` d's around it."""
    half, k = SHIFT_WINDOW // 2, 3 if upper else 1
    out = []
    for i, (t, _) in enumerate(raw):
        near = sorted(d for _, d in raw[max(0, i - half):i + half + 1])
        out.append((t, near[k * len(near) // 4]))
    return out


def clock_shifts(modules: list, enqueued: dict) -> list:
    """The profiler's device clock stands off the host's by up to two
    milliseconds (0.3-2.2 ms on a v5e, another offset each capture,
    with steps of ~0.15 ms inside one), more than a request's stages
    last.  Each program on a device plane names, by flow id, the host
    event that enqueued it; taking the launch latency as 0, an idle
    device starts a program as its enqueue ends.  ``modules``:
    [(start_ns, end_ns, flow id)] of one chip's programs;
    ``enqueued``: {flow id: end_ns} of host events.  -> sorted
    [(start_ns, shift_ns)], each the upper quartile of (enqueue end -
    start) over the ``SHIFT_WINDOW`` programs around it (a program
    that waited for the device reads low, an enqueue the host was
    preempted in reads high).  The device's ops then sit as early as
    the enqueues let them."""
    return _fit(sorted((lo, enqueued[f] - lo) for lo, _, f in modules
                       if f in enqueued), upper=True)


def completion_shifts(modules: list, completed: dict) -> list:
    """The other anchor: the same flow id names the runtime's host
    event that saw the program end (``CompleteCallbacks``), which
    cannot come before it.  ``completed``: {flow id: start_ns}.  ->
    sorted [(start_ns, shift_ns)], each the lower quartile of
    (completion - end) around it: the device's ops as late as the
    completions let them sit.  Less :func:`clock_shifts`, it is the
    launch latency plus the completion's notice."""
    return _fit(sorted((lo, completed[f] - hi) for lo, hi, f in modules
                       if f in completed), upper=False)


def align(ops: list, shifts: list) -> list:
    """``ops`` [(lo, hi)] of one chip, on the host's clock: each moved
    by the shift of the last program that started at or before it."""
    if not shifts:
        return list(ops)
    starts = [t for t, _ in shifts]
    out = []
    for lo, hi in ops:
        d = shifts[max(0, bisect.bisect_right(starts, lo) - 1)][1]
        out.append((lo + d, hi + d))
    return out


def _spread(values: list) -> list | None:
    """[min, median, max] of ``values``, None where empty."""
    v = sorted(values)
    return [v[0], v[len(v) // 2], v[-1]] if v else None


def read_xplane(trace_dir: str) -> dict:
    """The newest .xplane.pb under ``trace_dir`` -> {``file``,
    ``host``: pilosa.* events, ``device_ops`` and ``late_ops``: each
    chip's ops on the host's clock by the enqueue and by the
    completion anchor, ``completions``: sorted host times the runtime
    saw a program end, ``shift_ns``: [min, median, max] of the
    enqueue anchor's shifts, ``bracket_ns``: of the two anchors'
    difference a program} (the last two None without flows).  A chip
    whose programs name no flow borrows the shifts of the chip with
    the most."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    device_ops, modules, host, enqueued, completed = {}, {}, [], {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name in OP_LINES:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif line.name == MODULE_LINE:
                    modules[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, flow)
                        for e in line.events
                        if (flow := dict(e.stats).get("_c")) is not None]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIX):
                        host.append((e.name, e.start_ns, end))
                        continue
                    stats = dict(e.stats)
                    if (flow := stats.get("_p")) is not None:
                        enqueued[flow] = end
                    if (flow := stats.get("_c")) is not None:
                        completed[flow] = min(completed.get(flow, end),
                                              e.start_ns)
    early = {plane: clock_shifts(m, enqueued) for plane, m in modules.items()}
    late = {plane: completion_shifts(m, completed)
            for plane, m in modules.items()}
    best_early = max(early.values(), key=len, default=[])
    best_late = max(late.values(), key=len, default=[])
    shifts, bracket = [], []
    for plane in device_ops:
        e = early.get(plane) or best_early
        by_start = dict(late.get(plane) or best_late)
        shifts += [d for _, d in e]
        bracket += [by_start[t] - d for t, d in e if t in by_start]
    flows = {f for m in modules.values() for _, _, f in m}
    return {
        "file": files[-1], "host": host,
        "device_ops": {plane: align(ops, early.get(plane) or best_early)
                       for plane, ops in device_ops.items()},
        "late_ops": {plane: align(ops, late.get(plane) or best_late)
                     for plane, ops in device_ops.items()},
        "completions": sorted(completed[f] for f in flows if f in completed),
        "shift_ns": _spread(shifts), "bracket_ns": _spread(bracket)}


def render(r: dict) -> str:
    idle = r["idle_s"] or 1.0
    out = [f"window {r['window_s']:.3f} s, device busy {r['busy_s']:.3f} s, "
           f"idle {r['idle_s']:.3f} s "
           f"({100 * r['idle_s'] / r['window_s']:.1f} %) a chip over "
           f"{r['chips']} chip(s); {r['device_ops']} device ops, "
           f"{r['host_events']} pilosa.* events",
           "", "idle seconds by what covered them:"]
    for name, s in r["idle_by_name"].items():
        out.append(f"  {name:<28} {s:9.4f} s  {100 * s / idle:5.1f} %")
    if "idle_read_s" in r:
        out.append(f"  = read {r['idle_read_s']:.4f} s, host "
                   f"{r['idle_host_s']:.4f} s, no request "
                   f"{r['idle_no_request_s']:.4f} s")
    late = r.get("late_anchor")
    if late and late.get("idle_read_s") is not None:
        out.append(f"  by the completion anchor: read "
                   f"{late['idle_read_s']:.4f} s, host "
                   f"{late['idle_host_s']:.4f} s, no request "
                   f"{late['idle_no_request_s']:.4f} s")
    lower = r.get("read_after_completion_s_mean")
    upper = r.get("read_tail_s_upper")
    if lower is not None or upper is not None:
        out.append("  a read ends "
                   + (f"{lower * 1e3:.3f} ms after the runtime saw its "
                      f"program end ({r['reads_with_completion']} reads)"
                      if lower is not None else "")
                   + (", " if lower is not None and upper is not None
                      else "")
                   + (f"at most {upper * 1e3:.3f} ms after its last device "
                      f"op ({r['reads_captured']} reads)"
                      if upper is not None else ""))
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
        checks = f"{100 * share:.1f} %"
        if late and late["ops_started_inside_an_event_share"] is not None:
            checks += (f" (by the completion anchor "
                       f"{100 * late['ops_started_inside_an_event_share']:.1f}"
                       f" %)")
        out += ["", f"clock check: {checks} of the device's ops start "
                    f"inside a pilosa.* event"
                    + ("" if "idle_read_s" in r else
                       f" (under {100 * CLOCK_GATE:.0f} %: no group "
                       f"totals)")]
    return "\n".join(out)


def main(argv: list) -> int:
    if len(argv) not in (1, 2):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    cap = read_xplane(argv[0])
    print(cap["file"])
    if cap["shift_ns"] is not None:
        print("device clock moved onto the host's by "
              + " / ".join(f"{d / 1e6:.3f}" for d in cap["shift_ns"])
              + " ms (min / median / max), the launch latency taken as 0")
    if cap["bracket_ns"] is not None:
        print("the completion anchor puts the device "
              + " / ".join(f"{d / 1e6:.3f}" for d in cap["bracket_ns"])
              + " ms later: launch latency + completion notice, unsplit")
    if not cap["device_ops"]:
        print("no TPU device plane in the trace: nothing ran on a chip "
              "that the profiler saw; the pilosa.* events follow")
        for name, ns in _totals((name, hi - lo)
                                for name, lo, hi in cap["host"]).most_common():
            print(f"  {name:<28} {ns / 1e9:9.4f} s")
        return 1
    seconds = float(argv[1]) if len(argv) == 2 else None
    print(render(reduce_events(cap["device_ops"], cap["host"], seconds,
                               cap["completions"], cap["late_ops"])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
