"""Virtual CPU-mesh provisioning (SURVEY.md §5 simulated-mesh recipe).

Multi-chip TPU hardware is not assumed anywhere: the distribution path is
validated on an n-device *virtual* CPU mesh, the rebuild's analogue of the
reference's in-process multi-node test cluster (``test/cluster.go#
MustRunCluster``).  The device count is an XLA flag read when the CPU
backend initializes, so the recipe must run *before any backend
initializes*.  This is the single shared implementation of it, used by
both ``tests/conftest.py`` and the driver gate
``__graft_entry__.dryrun_multichip``.

This module must stay a leaf: importing it (and the ``pilosa_tpu``
package ``__init__``) must not create any jax device value, or the
default backend would initialize before the recipe can retarget the
process — see tests/test_import_hygiene.py.
"""

from __future__ import annotations

import os


def force_virtual_cpu_mesh(n_devices: int) -> bool:
    """Best-effort in-process provisioning of an ``n_devices`` virtual CPU
    mesh.  Returns True when a CPU backend with at least ``n_devices``
    devices is usable in this process.

    Mutates env/config only when no backend has initialized yet; if one
    has, reports whether it already satisfies the request so callers can
    fall back (e.g. to a fresh subprocess) without this process's env
    having been polluted.
    """
    import jax
    from jax._src import xla_bridge as _xb

    try:
        initialized = _xb.backends_are_initialized()
    except Exception:
        initialized = True  # unknown — don't risk retargeting a live backend
    if initialized:
        try:
            return (jax.default_backend() == "cpu"
                    and len(jax.devices("cpu")) >= n_devices)
        except Exception:
            return False

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    # replace any pre-existing (possibly smaller) count rather than
    # deferring to it — once the CPU backend initializes with too few
    # devices this process can never be re-provisioned
    kept = [f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    existing = [f for f in flags.split() if f not in kept]
    count = n_devices
    for f in existing:
        try:
            count = max(count, int(f.split("=", 1)[1]))
        except (IndexError, ValueError):
            pass
    kept.append(f"--xla_force_host_platform_device_count={count}")
    os.environ["XLA_FLAGS"] = " ".join(kept)

    # jax read JAX_PLATFORMS when it was imported (above, or earlier by
    # the caller), so the live config must follow the env
    jax.config.update("jax_platforms", "cpu")
    try:
        return len(jax.devices("cpu")) >= n_devices
    except Exception:
        return False
