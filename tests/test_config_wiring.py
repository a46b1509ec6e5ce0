"""Config-wiring drift check, in the manner of test_metrics_inventory.

A knob is a promise: a ``Config`` field nothing reads, or an
``Executor`` keyword the server never passes, is an option that
configures nothing.  One case per ``Config`` field — something in
``pilosa_tpu/`` reads it off a config object — and one per
``Executor.__init__`` keyword — ``Server`` passes it from a ``Config``
field, or it is one of the constructor's collaborators.  The walk is
over the ``ast``: a name in a comment or a docstring is not a reader.
"""

import ast
import dataclasses
import functools
import inspect
from pathlib import Path

import pytest

from pilosa_tpu.cli.config import Config
from pilosa_tpu.exec.executor import Executor

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "pilosa_tpu"
CONFIG_PY = PKG / "cli" / "config.py"

FIELDS = [f.name for f in dataclasses.fields(Config)]
KEYWORDS = [p for p in inspect.signature(Executor.__init__).parameters
            if p != "self"]

# what the server hands the executor that is not a setting
COLLABORATORS = {"holder", "translate", "place", "placement", "stats",
                 "tracer"}

# debts, by ROADMAP name: whoever closes one deletes its mark
UNREAD_FIELDS = {
    "name": "D2: Config.name is set by load() and read by nothing — a "
            "node's id is always host:port (cluster/cluster.py)",
}
UNWIRED_KEYWORDS = {
    "tenant_device_seconds_quota":
        "D7: no Config field sets tenant_device_seconds_quota; a "
        "served program always runs with 0.0",
}


def _tail(node) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _reads(tree, receivers) -> set:
    """Field names read off a config object: ``<receiver>.name`` in a
    load context, or ``getattr(<receiver>, "name", ...)``."""
    out = set()
    for n in ast.walk(tree):
        if (isinstance(n, ast.Attribute) and isinstance(n.ctx, ast.Load)
                and _tail(n.value) in receivers):
            out.add(n.attr)
        elif (isinstance(n, ast.Call) and _tail(n.func) == "getattr"
              and len(n.args) >= 2
              and isinstance(n.args[1], ast.Constant)
              and _tail(n.args[0]) in receivers):
            out.add(n.args[1].value)
    return out


@functools.cache
def config_readers() -> frozenset:
    read = set()
    for path in PKG.rglob("*.py"):
        if path != CONFIG_PY:
            read |= _reads(ast.parse(path.read_text()),
                           {"cfg", "config"})
    # config.py itself: ``load`` is the WRITER (reading a field back
    # to normalise it is not a use); the class's own derived
    # properties and the helpers that turn fields into objects are
    for node in ast.parse(CONFIG_PY.read_text()).body:
        if isinstance(node, ast.ClassDef) and node.name == "Config":
            read |= _reads(node, {"self"})
        elif isinstance(node, ast.FunctionDef) and node.name != "load":
            read |= _reads(node, {"cfg"})
    return frozenset(read)


@functools.cache
def server_executor_keywords() -> dict:
    """keyword -> the ``Config`` field ``Server`` passes for it (None
    when the value is not ``self.cfg.<field>``)."""
    tree = ast.parse((PKG / "server.py").read_text())
    calls = [n for n in ast.walk(tree)
             if isinstance(n, ast.Call) and _tail(n.func) == "Executor"]
    assert len(calls) == 1, "Server builds exactly one Executor"
    out = {}
    for kw in calls[0].keywords:
        v = kw.value
        from_cfg = (isinstance(v, ast.Attribute)
                    and _tail(v.value) == "cfg")
        out[kw.arg] = v.attr if from_cfg else None
    return out


def _marked(names, debts):
    return [pytest.param(n, marks=pytest.mark.xfail(
                strict=True, reason=debts[n])) if n in debts else n
            for n in names]


def test_counts():
    # the numbers ROADMAP D2 tracks; a new knob moves them on purpose
    assert len(FIELDS) == 55
    assert len(KEYWORDS) == 24


@pytest.mark.parametrize("field", _marked(FIELDS, UNREAD_FIELDS))
def test_config_field_is_read(field):
    assert field in config_readers(), (
        f"Config.{field} is read by nothing in pilosa_tpu/: a knob "
        f"that configures nothing — wire it or delete it")


@pytest.mark.parametrize("keyword",
                         _marked(KEYWORDS, UNWIRED_KEYWORDS))
def test_executor_keyword_is_wired(keyword):
    if keyword in COLLABORATORS:
        return
    passed = server_executor_keywords()
    assert keyword in passed, (
        f"Server never passes Executor({keyword}=...): a served "
        f"program always runs with the default")
    assert passed[keyword] in FIELDS, (
        f"Server passes Executor({keyword}=...) from something that "
        f"is not a Config field")
