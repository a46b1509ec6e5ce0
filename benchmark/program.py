"""The only file of the benchmark that imports the program.  Run as a
child process, never imported by the harness:

    program.py schema <data_dir> <config.json>
        create the index and its fields with the program's own store
        (the fragment files are the benchmark's to write)
    program.py serve <memory_out.json> <cli args ...>
        ``python -m pilosa_tpu.cli <cli args>`` in this process — the
        README's Quickstart server — with two things the program does
        not offer: once it has shut down cleanly, the peak device
        memory as this process's JAX reports it; and a profiler that
        leaves Python calls out.  ``POST /debug/profile`` starts
        ``jax.profiler`` with its default options, which trace every
        Python call of every thread: on the chip the server then
        answered 2 requests a second (PERF.md, PR 25) and the trace
        measured the tracer.  Device and XLA host events are recorded
        as before.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def schema(data_dir: str, config_path: str) -> int:
    from pilosa_tpu.store import FieldOptions, Holder
    with open(config_path) as fh:
        config = json.load(fh)
    ds = config["dataset"]
    h = Holder(data_dir).open()
    idx = h.create_index(config["index"])  # tracks existence: Not()
    for f in ds.get("set_fields", {ds.get("field"): None}):
        idx.create_field(f)
    for f, spec in ds.get("int_fields", {}).items():
        idx.create_field(f, FieldOptions(type="int", min=0,
                                         max=spec["max"]))
    h.close()
    return 0


def _profile_without_python_calls() -> None:
    import jax
    start_trace = jax.profiler.start_trace

    def start(log_dir, *args, **kwargs):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        return start_trace(log_dir, profiler_options=options)
    jax.profiler.start_trace = start


def serve(memory_out: str, argv: list) -> int:
    from pilosa_tpu.cli.main import main
    _profile_without_python_calls()
    try:
        return main(argv)
    finally:
        import jax
        peaks = []
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use"))
        with open(memory_out, "w") as fh:
            json.dump({"peak_bytes_in_use": peaks}, fh)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    if sys.argv[1] == "schema":
        sys.exit(schema(sys.argv[2], sys.argv[3]))
    if sys.argv[1] == "serve":
        sys.exit(serve(sys.argv[2], sys.argv[3:]))
    sys.exit(f"program.py: unknown command {sys.argv[1]!r}")
