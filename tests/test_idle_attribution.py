"""The device's idle time put down to the host's names
(``pilosa_tpu.obs.gaps``): per chip, over exactly the window less the
chip's busy time, split into ``read`` / ``host`` / ``no_request``, the
tail of a read after its last device op and after the runtime saw its
program end, the two anchors of the device's clock, the clock gate and
the longest gaps named.  Synthetic events in milliseconds; a CPU trace
has no device plane, so the server's own capture is read for its host
events only."""

import glob
import http.client
import json
import os

import numpy as np
import pytest

from pilosa_tpu.api import API, Server
from pilosa_tpu.exec import Executor
from pilosa_tpu.obs import Stats, gaps
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.store import Holder

MS = 1_000_000
ATTRIBUTION = ("idle_read_s", "idle_host_s", "idle_no_request_s",
               "idle_by_activity", "read_tail_s_upper", "reads_captured",
               "idle_gaps")


def _ms(*events):
    return [(name, lo * MS, hi * MS) for name, lo, hi in events]


def one_client():
    """Two requests of one closed-loop client, one launch each, its op
    inside the read; a 20 ms capture from the first event."""
    host = _ms(("pilosa.http_in", 1, 2), ("pilosa.admit", 2, 2.2),
               ("pilosa.plan", 2.2, 3), ("pilosa.dispatch", 3, 3.5),
               ("pilosa.read", 3.5, 6), ("pilosa.deliver", 6, 6.5),
               ("pilosa.assemble", 6.5, 7), ("pilosa.encode", 7, 7.3),
               ("pilosa.http_out", 7.3, 8),
               ("pilosa.http_in", 10, 11), ("pilosa.plan", 11, 12),
               ("pilosa.dispatch", 12, 12.5), ("pilosa.read", 12.5, 16),
               ("pilosa.deliver", 16, 17), ("pilosa.http_out", 17, 18))
    device = {"/device:TPU:0": [(3.6 * MS, 5.0 * MS),
                                (12.6 * MS, 15.2 * MS)]}
    return device, host, 0.020


def batcher_beside_32():
    """32 serving threads blocked in ``queue`` while the batcher's
    thread collects, dispatches, reads and delivers one window."""
    host = []
    for k in range(32):
        host += _ms(("pilosa.queue", 0.1 * k, 9), ("pilosa.assemble", 9, 9.5))
    host += _ms(("pilosa.batcher.collect", 0, 2),
                ("pilosa.batcher.group", 2, 2.2),
                ("pilosa.batcher.dispatch", 2.2, 3),
                ("pilosa.batcher.read", 3, 7),
                ("pilosa.batcher.deliver", 7, 8),
                ("pilosa.batcher.collect", 8, 12))
    return {"/device:TPU:0": [(2.5 * MS, 6.0 * MS)]}, host, None


def four_chips():
    """One meshed launch: each chip's two ops end at its own time and
    leave the same 2 ms gap inside the read on every chip."""
    host = _ms(("pilosa.http_in", 0, 1), ("pilosa.plan", 1, 2),
               ("pilosa.dispatch", 2, 3),
               ("pilosa.mesh.launch_wait", 2.2, 2.4),
               ("pilosa.read", 3, 10), ("pilosa.deliver", 10, 11),
               ("pilosa.http_out", 11, 12))
    device = {f"/device:TPU:{c}": [((3 + 0.1 * c) * MS, 5 * MS),
                                   (7 * MS, (9 + 0.1 * c) * MS)]
              for c in range(4)}
    return device, host, 0.012


CASES = {"one_client": one_client, "batcher_beside_32": batcher_beside_32,
         "four_chips": four_chips}


def _per_chip_partition(device, host, seconds):
    """Each chip alone: its three parts add up to the window less its
    busy time."""
    for plane, ops in device.items():
        r = gaps.reduce_events({plane: ops}, host, seconds)
        parts = r["idle_read_s"] + r["idle_host_s"] + r["idle_no_request_s"]
        assert parts == pytest.approx(r["window_s"] - r["busy_s"],
                                      abs=1e-9), plane
        assert sum(r["idle_by_activity"].values()) == \
            pytest.approx(parts, abs=1e-9)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_three_parts_are_the_idle_time_of_every_chip(case):
    device, host, seconds = CASES[case]()
    _per_chip_partition(device, host, seconds)
    r = gaps.reduce_events(device, host, seconds)
    assert r["idle_read_s"] + r["idle_host_s"] + r["idle_no_request_s"] == \
        pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    assert r["ops_started_inside_an_event_share"] == 1.0
    assert r["chips"] == len(device)


def test_one_client_path():
    r = gaps.reduce_events(*one_client())
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["idle_read_s"] == pytest.approx(0.002)       # 1.1 + 0.9 ms
    assert r["idle_no_request_s"] == pytest.approx(0.005)  # 8-10, 18-21
    assert r["idle_host_s"] == pytest.approx(0.009)
    # each read ends 1.0 and 0.8 ms after its op
    assert r["read_tail_s_upper"] == pytest.approx(0.0009)
    assert r["reads_captured"] == 2
    # the one gap between the two ops: the client's 2 ms are its most
    assert r["idle_gaps"] == [["no_request_1", pytest.approx(0.0076)]]


def test_a_batcher_thread_beside_32_serving_threads():
    r = gaps.reduce_events(*batcher_beside_32())
    assert r["window_s"] == pytest.approx(0.012)        # the host's span
    # a batcher phase is open at every idle instant: it names them all
    assert r["idle_by_activity"] == pytest.approx({
        "pilosa.batcher.collect": 0.006, "pilosa.batcher.group": 0.0002,
        "pilosa.batcher.dispatch": 0.0003, "pilosa.batcher.read": 0.001,
        "pilosa.batcher.deliver": 0.001})
    assert r["idle_read_s"] == pytest.approx(0.001)
    assert r["idle_host_s"] == pytest.approx(0.0075)
    assert r["idle_no_request_s"] == 0.0
    # the serving threads the first table cannot show, every one counted
    seen = r["serving_thread_seconds_in_idle"]
    assert seen["pilosa.queue"] == pytest.approx(0.1285)
    assert seen["pilosa.assemble"] == pytest.approx(0.016)
    assert r["read_tail_s_upper"] == pytest.approx(0.001)


def test_four_chips_with_a_gap_on_every_chip():
    r = gaps.reduce_events(*four_chips())
    assert r["busy_s"] == pytest.approx(0.004)      # each chip's 4 ms
    assert r["idle_read_s"] == pytest.approx(0.003)
    assert r["idle_host_s"] == pytest.approx(0.005)
    assert r["idle_no_request_s"] == 0.0
    # the nested launch wait, begun last, wins over its dispatch
    assert r["idle_by_activity"]["pilosa.mesh.launch_wait"] == \
        pytest.approx(0.0002)
    assert r["idle_by_activity"]["pilosa.dispatch"] == pytest.approx(0.0008)
    assert [name for name, _ in r["idle_gaps"]] == \
        [f"pilosa.read_{i}" for i in range(1, 5)]
    assert all(s == pytest.approx(0.002) for _, s in r["idle_gaps"])
    # the read ends 0.7 ms after the last op of the slowest chip
    assert r["read_tail_s_upper"] == pytest.approx(0.0007)


def test_a_read_with_no_op_ending_inside_it_is_all_tail():
    host = _ms(("pilosa.read", 3.5, 6), ("pilosa.read", 10, 11))
    device = {"/device:TPU:0": [(3.6 * MS, 6.5 * MS), (10 * MS, 10.4 * MS)]}
    assert gaps.read_tails(host, device) == \
        pytest.approx([2.5 * MS, 0.6 * MS])
    r = gaps.reduce_events(device, host, None)
    assert r["read_tail_s_upper"] == pytest.approx(0.00155)


def test_under_the_clock_gate_nothing_is_named():
    device, host, seconds = one_client()
    # two ops between the requests, where no event is open: 2 of 4
    device["/device:TPU:0"] += [(8.5 * MS, 8.6 * MS), (9 * MS, 9.1 * MS)]
    r = gaps.reduce_events(device, host, seconds)
    assert r["ops_started_inside_an_event_share"] == 0.5
    assert not set(ATTRIBUTION) & set(r)
    assert r["busy_s"] == pytest.approx(0.0042)      # still measured
    assert "under 95 %" in gaps.render(r)
    # no pilosa.* event at all: the same
    r = gaps.reduce_events(device, [], seconds)
    assert not set(ATTRIBUTION) & set(r)
    assert r["ops_started_inside_an_event_share"] == 0.0


def test_the_window_holds_every_op_of_every_chip():
    """A chip whose ops began before the capture's first event: its
    window starts at its first op, so its idle time is still the window
    less its busy time."""
    host = _ms(("pilosa.read", 1, 5))
    device = {"/device:TPU:0": [(0.5 * MS, 2 * MS)],
              "/device:TPU:1": [(1.5 * MS, 2 * MS)]}
    r = gaps.reduce_events(device, host, 0.004)
    assert r["window_s"] == pytest.approx(0.004)
    assert r["busy_s"] == pytest.approx((0.0015 + 0.0005) / 2)
    assert r["idle_s"] == pytest.approx(0.004 - r["busy_s"])


def test_a_capture_through_the_server_is_read_back(tmp_path, capsys):
    """On the CPU the trace has host events and no TPU plane: the
    reader finds the capture's file, flows and no device op, and says
    so."""
    holder = Holder(str(tmp_path / "data")).open()
    try:
        ex = Executor(holder, stats=Stats())
        api = API(holder, ex, trace_sample_rate=0.0)
        idx = holder.create_index("i")
        idx.create_field("f")
        rng = np.random.default_rng(39)
        cols = rng.choice(2_000_000, 300, replace=False).tolist()
        api.import_bits("i", "f", row_ids=[1] * len(cols), col_ids=cols)
        server = Server(api, "127.0.0.1", 0, stats=Stats()).start()
        try:
            port = server.address[1]
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            prof = tmp_path / "prof"
            conn.request("POST", f"/debug/profile?seconds=0.2&dir={prof}")
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()
            assert json.loads(resp.read())["traceDir"] == str(prof)
            assert obs_metrics.capture_open is False
            conn.close()
        finally:
            server.close()
    finally:
        holder.close()
    assert glob.glob(os.path.join(str(prof), "plugins", "profile", "*",
                                  "*.xplane.pb"))
    cap = gaps.read_xplane(str(prof))
    assert cap["device_ops"] == {} and cap["late_ops"] == {}
    assert cap["shift_ns"] is None and cap["bracket_ns"] is None
    assert gaps.main([str(prof)]) == 1
    assert "no TPU device plane" in capsys.readouterr().out


def test_the_device_clock_is_moved_onto_the_hosts_by_the_enqueues():
    """Each program names the host event that enqueued it: with the
    launch latency taken as 0 an idle device starts it as the enqueue
    ends.  The shift is the upper
    quartile of its neighbours' (one program that waited for a busy
    device reads low), and each op moves with the program it is in."""
    # the device's clock stands 1.7 ms early; program 3 waited 0.5 ms
    # behind program 2, and the enqueue of program 5 was preempted
    modules = [(t * MS, (t + 0.2) * MS, f) for t, f in
               ((1.0, "a"), (3.0, "b"), (5.0, "c"), (5.5, "d"), (8.0, "e"),
                (9.0, "unpaired"))]
    enqueued = {"a": 2.7 * MS, "b": 4.7 * MS, "c": 6.7 * MS,
                "d": 6.7 * MS, "e": 10.0 * MS}
    shifts = gaps.clock_shifts(modules, enqueued)
    assert [t for t, _ in shifts] == [m[0] for m in modules[:5]]
    assert all(d == pytest.approx(1.7 * MS) for _, d in shifts)
    ops = [(1.1 * MS, 1.5 * MS), (5.6 * MS, 5.9 * MS), (9.2 * MS, 9.3 * MS),
           (0.5 * MS, 0.6 * MS)]
    assert gaps.align(ops, shifts) == pytest.approx(
        [(2.8 * MS, 3.2 * MS), (7.3 * MS, 7.6 * MS), (10.9 * MS, 11.0 * MS),
         (2.2 * MS, 2.3 * MS)])
    assert gaps.align(ops, []) == ops      # no flows: the clock as read
    # a step of the device's clock inside a capture is followed
    step = [(i * MS, (i + 0.1) * MS, i) for i in range(200)]
    enq = {i: (i + (1.8 if i < 100 else 1.65)) * MS for i in range(200)}
    got = dict(gaps.clock_shifts(step, enq))
    assert got[10 * MS] == pytest.approx(1.8 * MS)
    assert got[190 * MS] == pytest.approx(1.65 * MS)


def test_the_completion_anchor_brackets_the_device_clock():
    """The device's clock stands 1.7 ms early; each program starts 0.1
    ms after its enqueue ends (the launch latency) and the runtime sees
    it end 0.3 ms after it does (the notice).  The enqueue anchor puts
    the device 0.1 ms early, the completion anchor 0.3 ms late: the
    true clock lies between them, 0.4 ms apart, and neither tells the
    two latencies apart.  A program that waited behind another (5) and
    a completion seen late by a preempted host (7) move neither."""
    progs = [(1.0 + 2 * k, 1.5 + 2 * k, k) for k in range(20)]
    modules = [(lo * MS, hi * MS, f) for lo, hi, f in progs]
    enqueued = {f: (lo + 1.7 - 0.1) * MS for lo, _, f in progs}
    completed = {f: (hi + 1.7 + 0.3) * MS for _, hi, f in progs}
    enqueued[5] -= 1.0 * MS
    completed[7] += 2.0 * MS
    early = gaps.clock_shifts(modules, enqueued)
    late = gaps.completion_shifts(modules, completed)
    assert [t for t, _ in late] == [m[0] for m in modules]
    assert all(d == pytest.approx(1.6 * MS) for _, d in early)
    assert all(d == pytest.approx(2.0 * MS) for _, d in late)
    op = [(1.1 * MS, 1.4 * MS)]
    true = 1.1 + 1.7
    assert gaps.align(op, early)[0][0] < true * MS < gaps.align(op, late)[0][0]


def test_a_reads_part_after_the_runtime_saw_its_program_end():
    """The runtime's completion event is on the host's clock: what a
    read lasts after it needs no device clock, and it is at most the
    read's tail after its last device op."""
    host = _ms(("pilosa.read", 3.5, 6), ("pilosa.deliver", 6, 7),
               ("pilosa.batcher.read", 10, 11), ("pilosa.read", 20, 21))
    completions = [x * MS for x in (5.2, 5.6, 6.5, 10.7, 30)]
    # the last completion inside each read; the third read saw none
    assert gaps.read_after_completion(host, completions) == \
        pytest.approx([0.4 * MS, 0.3 * MS])
    device, host, seconds = one_client()
    r = gaps.reduce_events(device, host, seconds, [5.3 * MS, 15.5 * MS])
    assert r["read_after_completion_s_mean"] == pytest.approx(0.0006)
    assert r["reads_with_completion"] == 2
    assert r["read_after_completion_s_mean"] <= r["read_tail_s_upper"]
    r = gaps.reduce_events(device, host, seconds, [])
    assert r["read_after_completion_s_mean"] is None
    assert "read_after_completion_s_mean" not in \
        gaps.reduce_events(device, host, seconds)


def test_both_placements_of_the_device_are_held_to_the_clock_check():
    device, host, seconds = one_client()
    # by the completion anchor the ops sit 0.3 ms later, still inside
    # their reads: the same split
    late = {p: [(lo + 0.3 * MS, hi + 0.3 * MS) for lo, hi in ops]
            for p, ops in device.items()}
    r = gaps.reduce_events(device, host, seconds, None, late)
    assert r["late_anchor"] == pytest.approx({
        "ops_started_inside_an_event_share": 1.0,
        "idle_read_s": r["idle_read_s"], "idle_host_s": r["idle_host_s"],
        "idle_no_request_s": r["idle_no_request_s"]})
    assert "completion anchor" in gaps.render(r)
    # 5 ms later the first op starts between two requests: the gate
    # closes on the one placement as on the other
    late = {p: [(lo + 5 * MS, hi + 5 * MS) for lo, hi in ops]
            for p, ops in device.items()}
    r = gaps.reduce_events(device, host, seconds, None, late)
    assert r["late_anchor"]["ops_started_inside_an_event_share"] == 0.5
    assert r["ops_started_inside_an_event_share"] == 1.0
    assert not set(ATTRIBUTION) & set(r)


def test_idle_after_the_hosts_last_event_is_named_apart():
    """The window of ``one_client`` runs 3 ms past the last event (the
    device's tracer outlives the host's): no request is open there, and
    the host was not recorded."""
    r = gaps.reduce_events(*one_client())
    assert r["idle_by_activity"][gaps.AFTER_EVENTS] == pytest.approx(0.003)
    assert r["idle_by_activity"]["no_request"] == pytest.approx(0.002)
    assert r["idle_no_request_s"] == pytest.approx(0.005)
    # a gap that straddles the last event is cut there
    parts = [("pilosa.read", 1 * MS), ("no_request", 3 * MS)]
    assert gaps._after(10 * MS, parts, 12 * MS) == [
        ("pilosa.read", 1 * MS), ("no_request", 1 * MS),
        (gaps.AFTER_EVENTS, 2 * MS)]
    assert gaps._after(13 * MS, parts, 12 * MS) == [
        ("pilosa.read", 1 * MS), (gaps.AFTER_EVENTS, 3 * MS)]
    assert gaps._after(0, parts, 12 * MS) == parts
