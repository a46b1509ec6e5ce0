"""The one command, end to end on the CPU at two shards: every cell
rehearses to a correct result line with exactly the contract's keys,
and without ``--rehearse`` a machine without a chip gets no line."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(*argv, timeout, cache_dir):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir), BENCH_RUN="7",
               TF_CPP_MIN_LOG_LEVEL="3")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), *argv],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_rehearses_to_a_correct_line(cell, tmp_path):
    proc = run_cell("--workload", cell, "--seed", "2400000017", "--seconds",
                    "2", "--trace", "0", "--rehearse", "--shards", "2",
                    timeout=600, cache_dir=tmp_path / "jaxcache")
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)
    assert list(line)[-1] == "compared"          # the comparison comes last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["rehearsal"] is True
    with open(os.path.join(REPO, "benchmark", "workloads",
                           f"{cell}.json")) as fh:
        config = json.load(fh)["config"]
    with open(os.path.join(REPO, "benchmark", "configs",
                           f"{config}.json")) as fh:
        shards = json.load(fh)["shards"]
    assert line["reduced"] == [f"shards 2 of {shards} (--shards)"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    bench = _bench()
    want = {m["name"]: m["unit"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    for c in line["compared"].values():
        assert ("limit" in c and c["value"] <= c["limit"]) or \
            c["value"] >= c["at_least"]
    # every compared number is printed beside its limit, last on stderr
    tail = proc.stderr.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert tail[-1] == "correct: True"
    assert all(ln.startswith("compared ") for ln in tail[:-1])
    assert any((tmp_path / "jaxcache").iterdir())


def test_without_rehearse_a_machine_without_a_chip_gets_no_result_line(
        tmp_path):
    proc = run_cell("--workload", "pibench1b.point_c1", "--seed", "1",
                    "--seconds", "1", "--trace", "0", timeout=600,
                    cache_dir=tmp_path / "jaxcache")
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr and "'cpu'" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_shards_is_refused_without_rehearse_and_an_unknown_cell_is_named(
        tmp_path):
    proc = run_cell("--workload", "pibench1b.point_c1", "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--shards", "2",
                    timeout=120, cache_dir=tmp_path / "jaxcache")
    assert proc.returncode != 0 and "--rehearse only" in proc.stderr
    proc = run_cell("--workload", "no.such_cell", "--seed", "1", "--seconds",
                    "1", "--trace", "0", timeout=120,
                    cache_dir=tmp_path / "jaxcache")
    assert proc.returncode != 0
    assert "benchmark/workloads/no.such_cell.json" in proc.stderr
    assert proc.stdout.strip() == ""
