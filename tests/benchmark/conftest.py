"""A fresh checkout collects the tests a built one collects.

``benchmark/run.py`` builds the program's native codec in the checkout
(``build_native``, 2 s), so after the first rehearsal
``native/libroaring_codec.so`` is there — but every worker has collected
the whole suite by then, and ``tests/test_native.py`` decides at its
import whether its 13 tests skip ("native codec not built").  This
directory is collected before it, so the same ``make -C native`` runs
here, once, before any test module is imported; where ``make`` fails the
suite runs as before, those tests skipped.

``probes/`` holds cells that are inputs of these tests and nothing
else: a workload and a traffic file each, laid out as under
``benchmark/``, which rehearse a part of the request model that no
entered cell sends yet.  Their traffic has no public source, so they are
never files of ``benchmark/`` and never entries of ``BENCHMARK.json``;
here ``manifest`` finds them by name beside its own.
"""

import fcntl
import json
import os
import subprocess

from benchmark import manifest

PROBES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "probes")

_NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")


def _build_native_once() -> None:
    lib = os.path.join(_NATIVE, "libroaring_codec.so")
    if os.path.exists(lib):
        return
    # the workers of one run collect at the same time: one builds, the
    # others wait for it (the lock is on the Makefile, no file is added)
    with open(os.path.join(_NATIVE, "Makefile")) as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(lib):
            subprocess.run(["make", "-C", _NATIVE], check=False,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)


_build_native_once()


def _probes_too(load):
    def _load(kind: str, name: str, asked_by: str) -> dict:
        path = os.path.join(PROBES, kind, f"{name}.json")
        if os.path.isfile(path):
            with open(path) as fh:
                return json.load(fh)
        return load(kind, name, asked_by)
    return _load


manifest._load = _probes_too(manifest._load)
