"""``correct`` can fail.  The controls are the reference put in the
program's place with one stated guarantee broken; the fault drives a
whole rehearsal with an answer altered underneath the harness.  Both
must come out as not correct (on the chip the controls were read at the
cells' own size: PERF.md)."""

import http.client
import json
import os
import re

import pytest

from benchmark import controls, load, loader, manifest, queries, traffic
from benchmark import run as bench_run
from benchmark import server as bench_server

CELLS = ("pibench1b.point_c1", "pibench1b.intersect_c32", "taxi333m.dash_c1",
         "pibench1b.trees_c32", "taxi333m.dash_c8", "taxi333m.analytic_c1")
SEED, N_SHARDS = 2_400_000_029, 2


def _oracle(cell_name):
    cell = manifest.cell(cell_name)
    pool = traffic.Pool(cell["traffic"],
                        loader.dataset_field_rows(cell["config"]), SEED)
    calls, index = pool.distinct_calls()
    totals = None
    for s in range(N_SHARDS):
        data = cell["generate"](cell["config"]["dataset"], SEED, s)
        totals = queries.combine(
            totals, [queries.partial(c, data) for c in calls])
    expected = [[queries.finish(calls[i], totals[i]) for i in ids]
                for ids in index]
    return cell, pool, calls, index, totals, expected


@pytest.mark.parametrize("control", sorted(controls.READ))
@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_comes_out_as_not_correct(cell_name, control):
    cell, pool, calls, index, totals, expected = _oracle(cell_name)
    sound = [[(int(rid), 0.0, 0.01, 200,
               json.dumps({"results": expected[rid]}).encode())
              for rid in pool.client_order(0)[:300]]]
    assert load.judge(sound, expected)["wrong"] == 0
    broken = controls.READ[control](sound, cell, pool, calls, index, totals,
                                   SEED, N_SHARDS)
    verdict = load.judge(broken, expected)
    assert verdict["attempted"] == 300
    # most answers of the control are off even at two shards, not a stray one
    assert verdict["wrong"] >= 150, verdict["first_wrong"]


class _AlteringConnection(http.client.HTTPConnection):
    """Every 7th query answer has its last digit changed on the way
    back: an answer altered where it is produced."""
    seen = 0

    def getresponse(self):
        resp = super().getresponse()
        body = resp.read()
        if body.startswith(b'{"results"'):
            type(self).seen += 1
            if type(self).seen % 7 == 0:
                body = re.sub(rb"(\d)(\D*)$", lambda m: bytes(
                    [48 + (m.group(1)[0] - 47) % 10]) + m.group(2), body)
        resp.read = lambda *a: body
        return resp


def test_an_altered_answer_makes_a_whole_run_incorrect(tmp_path, monkeypatch,
                                                       capsys):
    for key in list(os.environ):
        if key.startswith(("XLA_", "TPU_", "LIBTPU")):
            monkeypatch.delenv(key)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(
        bench_server.Server, "connect",
        lambda self, timeout=300.0: _AlteringConnection(
            "127.0.0.1", self.port, timeout=timeout))
    monkeypatch.setattr(bench_run, "WARMUP_TIMEOUT_S", 120.0)
    rc = bench_run.main(["--workload", "pibench1b.point_c1", "--seed",
                         str(SEED), "--seconds", "2", "--trace", "0",
                         "--rehearse", "--shards", "2"])
    out = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["wrong_answers"]["value"] >= 1
    assert line["failed"] >= line["compared"]["wrong_answers"]["value"]
    assert out.err.strip().splitlines()[-1] == "correct: False"
