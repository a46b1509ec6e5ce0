"""The operator's reduction of a capture (``pilosa_tpu.obs.gaps``) and
the benchmark's (``benchmark/tracered.py``) are held to the same
window, busy time and gaps on the same events, so the idle seconds that
``gaps`` names are the idle time that ``device.idle_share`` counts."""

import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import tracered  # noqa: E402
from pilosa_tpu.obs import gaps  # noqa: E402

MS = 1_000_000
STAGES = ("http_in", "plan", "dispatch", "read", "deliver", "assemble",
          "http_out")


def _random_capture(chips: int, seed: int) -> tuple:
    """One serving thread's requests back to back, a client's pause
    between some, and per chip ops that start inside a request."""
    rng = random.Random(seed)
    host, at = [], 0.0
    while at < 40 * MS:
        for stage in STAGES:
            d = rng.uniform(0.05, 1.5) * MS
            host.append(("pilosa." + stage, at, at + d))
            at += d
        at += rng.choice((0.0, rng.uniform(0.1, 2.0) * MS))
    covered = gaps.union([(lo, hi) for _, lo, hi in host])
    device = {}
    for c in range(chips):
        ops = []
        for _ in range(60):
            lo, hi = rng.choice(covered)
            start = rng.uniform(lo, hi)
            ops.append((start, start + rng.uniform(0.01, 0.8) * MS))
        device[f"/device:TPU:{c}"] = ops
    return device, host


@pytest.mark.parametrize("chips,seed", [(1, 1), (1, 2), (4, 3), (4, 4)])
def test_the_program_and_the_benchmark_reduce_to_the_same_window(chips,
                                                                 seed):
    device, host = _random_capture(chips, seed)
    capture_s = 0.045
    bench = tracered.reduce_events(
        {plane: [("fusion", lo, hi - lo) for lo, hi in ops]
         for plane, ops in device.items()}, capture_s)
    prog = gaps.reduce_events(device, host, capture_s)
    assert prog["busy_s"] == pytest.approx(bench["busy_s"], rel=1e-12)
    assert prog["window_s"] == pytest.approx(bench["window_s"], rel=1e-12)
    assert prog["device_ops"] == bench["device_events"]
    assert prog["chips"] == bench["chips_traced"]
    # the same ten longest gaps between two ops, in the same order;
    # only the name says what covered each
    assert [s for _, s in prog["idle_gaps"]] == pytest.approx(
        [s for _, s in bench["breakdown"]["idle_gaps"]], rel=1e-12)
    assert all(not name.startswith("unattributed")
               for name, _ in prog["idle_gaps"])
    # the three parts are the idle time the benchmark's share counts
    idle = (prog["idle_read_s"] + prog["idle_host_s"]
            + prog["idle_no_request_s"])
    assert idle == pytest.approx(
        bench["window_s"] * bench["idle_share_pct"] / 100, abs=1e-9)
