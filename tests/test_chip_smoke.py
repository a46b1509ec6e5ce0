"""``chip_smoke.py`` is the first thing run on the chip after any change
to start-up, kernels or launchers, and the driver runs it on every PR —
so it must not bitrot between chip runs.  The CPU rehearsal drives the
same two legs (cold boot + writes, warm restart) through the same
server children at two shards; the
un-rehearsed command must refuse a machine without a chip."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout, cache_dir=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("XLA_", "TPU_", "LIBTPU"))}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir is not None:
        # the suite runs with the compile cache off (conftest); the
        # smoke's warm-restart check needs it, placed from outside
        env.update(JAX_ENABLE_COMPILATION_CACHE="true",
                   JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_rehearsal_passes_on_cpu(tmp_path):
    proc = _run("--rehearse", "--shards", "2", "--burst-seconds", "1",
                timeout=600, cache_dir=tmp_path / "jaxcache")
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert last["reduced"] == ["shards 2 of 954 (--shards)"]
    # both legs ran and no third, and the warm boot compiled nothing new
    boots = [ln for ln in proc.stdout.splitlines() if ln.endswith("] boot")]
    assert boots == ["[cold] boot", "[warm] boot"]
    assert f"compile cache: {tmp_path / 'jaxcache'}" in proc.stdout
    assert "compile cache gained 0 entries" in proc.stdout
    assert any((tmp_path / "jaxcache").iterdir())


def test_without_rehearse_refuses_a_machine_without_a_chip():
    proc = _run("--shards", "1", timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr + proc.stdout
    # no result line: nothing on stdout parses as the ok object
    last = proc.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")
