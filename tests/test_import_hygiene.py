"""Importing the library must not initialize a jax backend.

The virtual-mesh recipe (pilosa_tpu/virtmesh.py) can only retarget a
process to the 8-device CPU mesh while NO backend has initialized; a
module-level jnp constant anywhere in the import graph silently binds
the default backend at import time and breaks both the
test harness and the driver's multichip gate.  Round 2 hit exactly this
(`_FULL = jnp.uint32(...)` in engine/bsi.py); this test keeps it fixed.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHECK = """
import jax
from jax._src import xla_bridge as xb
import pilosa_tpu
import pilosa_tpu.exec
import pilosa_tpu.parallel
import pilosa_tpu.cluster
import pilosa_tpu.store.holder
import pilosa_tpu.pql
import pilosa_tpu.virtmesh
assert not xb.backends_are_initialized(), (
    "importing pilosa_tpu initialized a jax backend — a module-level "
    "device constant crept in")
print("import-hygiene OK")
"""


def test_import_does_not_initialize_backend():
    # CPU-forced env so a violation fails the assert instead of
    # claiming a chip another process may hold.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CHECK], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "import-hygiene OK" in proc.stdout


def test_stale_native_codec_is_an_error_not_a_fallback(tmp_path,
                                                       monkeypatch):
    """A codec library that is THERE but unusable (built elsewhere,
    older than the loader's symbol list) must fail the load with a
    rebuild hint — never a silent drop to the Python codec."""
    import pytest

    from pilosa_tpu.store import native
    bad = tmp_path / "libroaring_codec.so"
    bad.write_bytes(b"not an ELF object")
    monkeypatch.setattr(native, "_LIB_PATH", str(bad))
    monkeypatch.delenv("PILOSA_NO_NATIVE", raising=False)
    with pytest.raises(ImportError, match="make -C native"):
        native._load()
