"""The driver's multichip gate: ``dryrun_multichip`` must self-provision.

Round 1's gate failed because the entrypoint assumed the caller supplied
>=8 devices and bound the default backend.  This test reproduces the driver's invocation — a fresh interpreter with NO
cpu-forcing env — and fails if the self-provisioning regresses.
(SURVEY.md §5 simulated-mesh lesson.)
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_self_provisions():
    # Scrub the cpu-forcing vars conftest set for THIS process so the
    # child sees what the driver's child would see.
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "dryrun_multichip(8): OK" in proc.stdout, proc.stdout
