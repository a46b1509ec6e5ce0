"""Test harness configuration.

Forces CPU JAX with 8 virtual devices *before* jax initializes, so the full
mesh/collective distribution path runs in pytest without TPU hardware —
the rebuild's equivalent of the reference's in-process multi-node cluster
harness (``test/cluster.go#MustRunCluster``; SURVEY.md §5).
"""

# Unit tests are CPU-only by design (a chip belongs to one process, and
# the suite starts many); the shared recipe lives in
# pilosa_tpu/virtmesh.py (also used by the driver gate
# __graft_entry__.dryrun_multichip).
import os

# The persistent compile cache is on for every process
# (engine/_jaxcfg.py).  The suite runs with it OFF, as it did before the
# cache rule existed: no on-disk state shared between tests, processes
# or runs, and none of the two ~4 KB spurious cpu_aot_loader ERROR lines
# the CPU backend logs on every cache HIT (they bury a failing test's
# own output).  Cold, on and off cost the same wall (257 s vs 255 s on
# the subprocess-heavy files); a warm cache is ~15 % faster, so
# `JAX_ENABLE_COMPILATION_CACHE=true pytest ...` is there for local
# re-runs.  Children inherit the env; the tests of the cache rule and
# the chip_smoke rehearsal turn it back on for theirs.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

from pilosa_tpu.virtmesh import force_virtual_cpu_mesh  # noqa: E402

if not force_virtual_cpu_mesh(8):
    raise RuntimeError(
        "could not provision the 8-device virtual CPU mesh for tests — "
        "a non-CPU jax backend initialized before conftest ran")

import faulthandler  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# CI hang watchdog (r18): the tier-1 runner kills the suite at 870s —
# if any test wedges (the exact hang class the self-healing pipeline
# work hunts), dump every thread's traceback to stderr shortly BEFORE
# the kill so the wedge is attributable instead of silent.  exit=False:
# the dump is diagnostics, the runner's timeout stays the enforcer.
_WATCHDOG_S = float(os.environ.get("PILOSA_TEST_WATCHDOG_S", "840"))
if _WATCHDOG_S > 0:
    faulthandler.dump_traceback_later(_WATCHDOG_S, exit=False)


@pytest.fixture
def rng():
    return np.random.default_rng(42)
