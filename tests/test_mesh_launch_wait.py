"""The launch lock of meshed programs, measured (PR 28): every launch
that ``mesh_serialized`` lets through is counted
(``mesh_launches_total``) and its wait for ``_MESH_LAUNCH_LOCK`` is
observed (``mesh_launch_wait_seconds``, ``/status``
``mesh.launchWait``); a single-device executor moves neither series.
Beside them, ``/status`` ``mesh`` names the fullest and the emptiest
chip as scalars.  Counts only: a CPU's seconds are not speeds."""

import threading
import time

import jax
import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.exec import Executor, fused
from pilosa_tpu.obs import Stats
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.parallel import MeshPlacement
from pilosa_tpu.store import FieldOptions, Holder

N_SHARDS = 6    # pads to 8 over four devices
INDEX = "i"
QUERIES = ("Count(Row(seg=1))", "TopN(seg)", "TopN(seg, Row(g=1), n=2)",
           "Sum(Row(seg=2), field=amount)",
           "Count(Intersect(Row(seg=1), Row(g=1)))",
           "GroupBy(Rows(seg), Rows(g))")


@pytest.fixture(scope="module")
def placement():
    assert jax.device_count() >= 4, "conftest must force the CPU devices"
    return MeshPlacement(jax.devices()[:4])


@pytest.fixture
def holder(tmp_path, rng):
    h = Holder(str(tmp_path)).open()
    idx = h.create_index(INDEX)
    idx.create_field("seg")
    idx.create_field("g")
    idx.create_field("amount", FieldOptions(type="int", min=0, max=999))
    cols = rng.choice(N_SHARDS * SHARD_WIDTH, size=4000,
                      replace=False).astype(np.uint64)
    idx.field("seg").import_bits(
        rng.integers(0, 4, size=len(cols)).astype(np.uint64), cols)
    idx.field("g").import_bits(np.ones(2000, np.uint64), cols[:2000])
    idx.field("amount").import_values(cols[:1000],
                                      rng.integers(0, 999, size=1000))
    idx.note_columns(cols)
    yield h
    h.close()


def _series(stats):
    counters = stats.snapshot()["counters"]
    launches = sum(counters.get("mesh_launches_total", {}).values())
    wait = stats.histogram_summary("mesh_launch_wait_seconds").get(
        "total", {"count": 0, "sum": 0.0})
    return launches, wait


def test_every_meshed_launch_is_counted_and_its_wait_observed(
        holder, placement):
    stats = Stats()
    ex = Executor(holder, placement=placement, stats=stats)
    seen = 0
    for q in QUERIES:
        ex.execute(INDEX, q)
        launches, wait = _series(stats)
        assert launches > seen, f"{q} launched no meshed program"
        assert wait["count"] == launches
        seen = launches
    block = ex.mesh_status()
    assert block["launchWait"]["count"] == seen
    assert block["launchWait"]["sum"] >= 0.0
    # one caller: the lock is never contended, the waits are its cost
    assert block["launchWait"]["sum"] < 0.05 * seen


def test_a_launch_held_off_by_the_lock_shows_its_wait(holder, placement):
    stats = Stats()
    ex = Executor(holder, placement=placement, stats=stats)
    ex.execute(INDEX, "TopN(seg)")                  # compiled, resident
    _, before = _series(stats)
    done = []
    with fused._MESH_LAUNCH_LOCK:
        t = threading.Thread(
            target=lambda: done.append(ex.execute(INDEX, "TopN(seg)")))
        t.start()
        time.sleep(0.3)
        assert not done, "a meshed launch went past a held launch lock"
    t.join(timeout=60)
    assert done
    _, after = _series(stats)
    assert after["count"] > before["count"]
    assert after["sum"] - before["sum"] >= 0.2


def test_a_single_device_executor_moves_neither_series(holder):
    stats = Stats()
    ex = Executor(holder, stats=stats)
    for q in QUERIES:
        ex.execute(INDEX, q)
    assert _series(stats) == (0, {"count": 0, "sum": 0.0})
    assert ex.mesh_status() is None


def test_status_names_the_fullest_and_the_emptiest_chip(holder, placement):
    ex = Executor(holder, placement=placement, stats=Stats())
    empty = ex.mesh_status()
    assert empty["maxDeviceBytes"] == empty["minDeviceBytes"] == 0
    assert empty["launchWait"] == {"count": 0, "sum": 0.0, "mean": 0.0}
    ex.execute(INDEX, "TopN(seg)")
    ex.execute(INDEX, "Sum(field=amount)")
    block = ex.mesh_status()
    per = block["perDeviceBytes"]
    assert len(per) == block["devices"] == 4
    assert block["maxDeviceBytes"] == max(per.values()) > 0
    assert block["minDeviceBytes"] == min(per.values()) > 0
    assert block["paddedShards"] > 0           # 6 shards pad to 8


def test_the_wrapper_counts_times_and_releases_on_a_raise():
    stats = Stats()
    calls = []

    def program(x):
        calls.append(x)
        if x < 0:
            raise ValueError("boom")
        return x + 1

    run = fused.mesh_serialized(program, stats)
    assert run(1) == 2 and run(2) == 3
    with pytest.raises(ValueError):
        run(-1)
    assert calls == [1, 2, -1]
    assert not fused._MESH_LAUNCH_LOCK.locked()
    launches, wait = _series(stats)
    assert launches == 3 and wait["count"] == 3
    # without a registry the wrapper still serialises and returns
    assert fused.mesh_serialized(program)(5) == 6


def test_the_wait_is_a_span_in_the_profilers_trace_while_a_capture_is_open(
        monkeypatch):
    names = []

    class _Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            names.append(self.name)
            assert not fused._MESH_LAUNCH_LOCK.locked()

        def __exit__(self, *exc):
            # the span covers the wait and ends with the acquisition
            assert fused._MESH_LAUNCH_LOCK.locked()

    monkeypatch.setattr(obs_metrics, "span",
                        lambda name, **meta: _Span(name))
    assert fused.mesh_serialized(lambda: 7, Stats())() == 7
    assert names == ["mesh.launch_wait"]
    assert not fused._MESH_LAUNCH_LOCK.locked()
