#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 benchmark/run.py --workload <cell> --seed 1 \\
        --seconds 2 --trace 0 --rehearse --shards 2      # no chip

Writes the cell's index from ``--seed``, boots the server child, warms
the cell's own shapes, measures a closed-loop window from the client's
side, compares EVERY response with the numpy oracle, and prints the
contract's one JSON line last.  A cell whose mix writes is also stopped
and booted a second time on the same data directory, and every read of
its pool is compared once more.  See ``benchmark/README.md``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (controls, load, loader, manifest, queries,  # noqa: E402
                       readers, roofline, traffic)
from benchmark.bitmaps import SHARD_WIDTH  # noqa: E402
from benchmark.server import (HarnessError, Server, check_device,  # noqa: E402
                              child_env, device_of, health_facts)

WARMUP_TIMEOUT_S = 600.0
# concurrent warm-up rounds go on until one compiles nothing, or this
# long: the window mix of a concurrent cell can keep meeting new shapes
# (fused.compiles_in_window then says how many the window met)
CONCURRENT_WARMUP_MAX_S = 12.0
CONCURRENT_ROUND_S = 3.0
LADDER_ROUND_S = 1.0          # a round of 1, 2, 4, ... clients
TRACE_START_S = 2.0           # into the window
# a mix that writes: this many whole rounds of the client's own walk,
# the write slot in each.  A delta overlay only grows (a compaction is
# many minutes of such traffic away) and each doubling of it is a new
# bucket and a new program, so no warm-up can meet them all: a fixed
# count opens every run's window on the same overlay, 1 + 64 write
# requests old, and a window of fewer than 191 more stays inside two
# doublings of it (``fused.compiles_in_window`` says what it met)
WRITE_WARMUP_ROUNDS = 64
# /status planeCache counters that stand still when every plane asked
# for was resident and nothing was built
PLANE_KEYS = ("misses", "builds", "bytes", "entries")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_native() -> None:
    """What the program builds, it builds in the checkout (2 s)."""
    res = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                         stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    if res.returncode != 0:
        raise HarnessError("make -C native failed:\n"
                           + res.stderr.decode()[-2000:])


def write_index(cell: dict, pool: traffic.Pool, data_dir: str, seed: int,
                n_shards: int) -> tuple:
    """Schema by the program's own store (a child), fragments and
    oracle by the benchmark.  -> (expected results per request, bytes
    written, per-call totals, distinct calls, call index)."""
    config = cell["config"]
    config_path = os.path.join(data_dir, "_config.json")
    with open(config_path, "w") as fh:
        json.dump(config, fh)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "program.py"),
         "schema", os.path.join(data_dir, "data"), config_path],
        cwd=ROOT, env=child_env({"JAX_PLATFORMS": "cpu"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if res.returncode != 0:
        raise HarnessError("schema child failed:\n"
                           + res.stdout.decode()[-2000:])
    calls, index = pool.distinct_calls()
    workers = max(1, min(12, (os.cpu_count() or 2) - 1))
    totals, written = loader.load(config, os.path.join(data_dir, "data"),
                                  seed, n_shards, calls, workers)
    expected = [[queries.finish(calls[i], totals[i]) for i in ids]
                for ids in index]
    return expected, written, totals, calls, index


def write_state(pool: traffic.Pool, config: dict, seed: int, n_shards: int,
                calls: list, call_index: list, totals: list) -> tuple:
    """A mix that writes -> (its write requests in the order they will
    be sent, the calls in each, the oracle with a state).  New columns
    start where the loaded shards end."""
    write, dataset = pool.write, config["dataset"]
    field_rows = loader.dataset_field_rows(config)
    first_column = n_shards * SHARD_WIDTH
    per_ride = len(write["set_fields"]) + len(write["int_fields"])

    def calls_of(k: int) -> list:
        return traffic.write_calls(write, dataset, seed, k, first_column)

    def added(k: int, lose: tuple) -> list:
        kept = [c for i, c in enumerate(calls_of(k))
                if i // per_ride not in lose]
        shard = queries.written(kept, field_rows, write["int_fields"])
        return [queries.partial(c, shard) for c in calls]

    return (load.Writes(calls_of), per_ride * int(write["rides"]),
            load.Oracle(calls, call_index, totals, added))


def after_restart(tmp: str, out_dir: str, config: dict, pool: traffic.Pool,
                  expected, control_state, run_times: dict) -> tuple:
    """The second boot of a cell that writes, on the data directory the
    first child was stopped on: every distinct read of the pool once,
    against the oracle's final state.  -> (what it adds to ``compared``,
    that child's peak device memory).  Under a control the reference's
    own final state answers in the program's place."""
    server = Server(os.path.join(tmp, "data"), out_dir, config["server_env"],
                    name="restart")
    try:
        server.wait_up()
        run_times["restart_to_serving_s"] = \
            time.perf_counter() - server.t_spawn
        n = wrong = failed = 0
        for rid, request in enumerate(pool.requests):
            try:
                got = control_state[rid] if control_state is not None else \
                    one_request(server, config["index"], request["pql"])
            except (HarnessError, OSError, ValueError) as e:
                failed += 1
                say(f"after the restart, request {rid}: {e}")
                continue
            n += 1
            if got != expected[rid]:
                if not wrong:
                    say(f"first wrong answer after the restart: request "
                        f"{rid} {request['pql'][:120]}: got "
                        f"{json.dumps(got)[:300]} want "
                        f"{json.dumps(expected[rid])[:300]}")
                wrong += 1
        run_times["restart_to_read_back_s"] = \
            time.perf_counter() - server.t_spawn
        health = health_facts(server.status(), server.metrics())
        rc = server.stop()
        if rc != 0:
            raise HarnessError(f"the restarted server exited rc={rc} on "
                               f"SIGTERM; log tail:\n{server.log_tail()}")
        memory_peak = server.memory_peak_bytes()
        server = None
    finally:
        if server is not None:
            server.stop()
    out = {"after_restart_compared": {"value": n, "at_least": 1},
           "after_restart_wrong": {"value": wrong, "limit": 0},
           "after_restart_failed": {"value": failed, "limit": 0}}
    out.update({f"after_restart_{k}": {"value": v, "limit": lim}
                for k, (v, lim) in health.items()})
    return out, memory_peak


def one_request(server: Server, index: str, pql: str):
    body = server.request(f"/index/{index}/query", pql.encode())
    return json.loads(body).get("results")


def wait_fused(server: Server, index: str, request: dict, want) -> tuple:
    """Send the cell's first template until it is answered correctly
    from resident planes: every plane it asked the cache for was a hit
    and nothing was being built.  -> (time of that answer, wrong
    answers seen on the way)."""
    deadline = time.monotonic() + WARMUP_TIMEOUT_S
    wrong = 0
    while True:
        before = server.status()["planeCache"]
        got = one_request(server, index, request["pql"])
        t_done = time.perf_counter()
        after = server.status()["planeCache"]
        if got != want:
            wrong += 1
        steady = all(after[k] == before[k] for k in PLANE_KEYS)
        if got == want and steady and after["hits"] > before["hits"]:
            return t_done, wrong
        if after["buildFailures"]:
            raise HarnessError(f"a plane build failed: {after}")
        if time.monotonic() > deadline:
            raise HarnessError(
                f"the first template was not served from resident planes "
                f"after {WARMUP_TIMEOUT_S:.0f}s: {after}")


def warm_up(server: Server, cell: dict, pool: traffic.Pool, expected,
            orders: list, bodies: list, writes=None,
            write_calls: int = 0) -> dict:
    """Only the cell's own shapes: the pool's cover solo (every row a
    template can name, in every position) until a round compiles
    nothing, builds nothing and misses no plane; every other request of
    the pool once, so that the window meets no query for the first time
    (the program caches what it has parsed); then concurrent rounds of
    1, 2, 4, ... clients and full ones, until two full rounds in a row
    are as still.  A mix that writes: ``WRITE_WARMUP_ROUNDS`` rounds of
    the one client's walk, the write slot in each as in the window."""
    index = cell["config"]["index"]
    wrong = rounds = 0
    deadline = time.monotonic() + WARMUP_TIMEOUT_S

    def moving(before: dict, after: dict) -> bool:
        return (after["costs"]["compileCount"]
                != before["costs"]["compileCount"]
                or any(after["planeCache"][k] != before["planeCache"][k]
                       for k in PLANE_KEYS))

    def until_still(one_round, give_up_after: float | None = None) -> bool:
        nonlocal rounds
        still, t_first = 0, time.monotonic()
        while True:
            before = server.status()
            one_round()
            rounds += 1
            still = 0 if moving(before, server.status()) else still + 1
            if still >= 2:
                return True
            if give_up_after is not None and \
                    time.monotonic() - t_first > give_up_after:
                return False
            if time.monotonic() > deadline:
                raise HarnessError("warm-up did not settle in "
                                   f"{WARMUP_TIMEOUT_S:.0f}s")

    def solo(rids=pool.cover) -> None:
        nonlocal wrong
        for rid in rids:
            got = one_request(server, index, pool.requests[rid]["pql"])
            wrong += got != expected[rid]

    def together(clients: int | None = None,
                 seconds: float = CONCURRENT_ROUND_S,
                 requests: float = math.inf) -> None:
        nonlocal wrong
        ld = load.Load(server, index, orders[:clients], bodies, writes)
        ld.run(seconds, requests=requests)
        verdict = load.judge(ld.records, expected, write_calls)
        wrong += verdict["wrong"] + verdict["failed"] + verdict["acks_wrong"]

    until_still(solo)
    solo(sorted(set(range(len(pool.requests))) - set(pool.cover)))
    rounds += 1
    settled = True
    if len(orders) > 1:
        # the batcher pads a window's items to a power of two: meet
        # every width on the way up, then the full rounds
        k = 1
        while k < len(orders):
            together(k, LADDER_ROUND_S)
            rounds += 1
            k *= 2
        settled = until_still(together, CONCURRENT_WARMUP_MAX_S)
        if not settled:
            say(f"warm-up: concurrent rounds still compiled after "
                f"{CONCURRENT_WARMUP_MAX_S:.0f} s; going on")
    if writes is not None:
        together(1, math.inf, WRITE_WARMUP_ROUNDS * pool.cycle_len)
        rounds += WRITE_WARMUP_ROUNDS
    return {"wrong": wrong, "rounds": rounds, "settled": settled}


def capture_trace(server: Server, trace_dir: str, seconds: float, t0: float,
                  window: float, marks: dict) -> None:
    delay = min(TRACE_START_S, window / 4)
    time.sleep(max(0.0, t0 + delay - time.perf_counter()))
    marks["t_post"] = time.perf_counter()
    server.request(f"/debug/profile?seconds={seconds}&dir={trace_dir}", b"",
                   timeout=300)
    marks["t_reply"] = time.perf_counter()


def reduce_trace(trace_dir: str, seconds: float, out_path: str) -> dict:
    """In a child held to the CPU: this process stays off JAX."""
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tracered.py"),
         trace_dir, out_path, str(seconds)],
        cwd=ROOT, env=child_env({"JAX_PLATFORMS": "cpu"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if res.returncode != 0:
        raise HarnessError("trace reduction failed:\n"
                           + res.stdout.decode()[-3000:])
    with open(out_path) as fh:
        return json.load(fh)


def read_trace(trace_dir: str, seconds: float, out_dir: str, marks: dict,
               records: list, pool: traffic.Pool, n_shards: int,
               rehearse: bool):
    """The reduced trace, with what the client's clock adds: the
    requests completed in the capture and the bytes they had to read.
    The capture is asked for at ``t_post`` and starts some tens of
    milliseconds later; at a steady rate the requests completed in the
    capture's length from ``t_post`` on are as many as it saw."""
    raw = reduce_trace(trace_dir, seconds,
                       os.path.join(out_dir, "trace_reduced.json"))
    if "reduced" not in raw:
        if not rehearse:
            raise HarnessError(f"the traced run saw no device: "
                               f"{raw['no_device_plane']}")
        say(f"rehearsal: {raw['no_device_plane']}; no trace metric")
        return None
    trace = raw["reduced"]
    lo, hi = marks["t_post"], marks["t_post"] + seconds
    captured = [rid for rec in records for rid, _, t_done, _, _ in rec
                if lo <= t_done < hi]
    trace["requests_captured"] = len(captured) or None
    try:
        if min(captured, default=0) < 0:
            raise ValueError("a write request reads no rows")
        trace["required_bytes"] = float(sum(
            roofline.required_row_bytes(pool.requests[rid]["calls"], n_shards)
            for rid in captured)) or None
    except ValueError:
        pass  # no byte count is defined for this traffic
    return trace


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="relax ONLY the device check (CPU rehearsal)")
    ap.add_argument("--shards", type=int, default=None,
                    help="rehearsal only: fewer shards than the config")
    ap.add_argument("--control", default=None, choices=sorted(controls.ALL),
                    help="judge the reference with one guarantee broken "
                         "in the program's place: correct must read false")
    ap.add_argument("--out", default=None,
                    help="keep the server log and the run's record here")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except (HarnessError, manifest.ManifestError, traffic.MixError) as e:
        say(f"benchmark: {e}")
        return 1


def run(args) -> int:
    t_start = time.perf_counter()
    bench = manifest.benchmark_json()
    cell = manifest.cell(args.workload)
    config, mix = cell["config"], cell["traffic"]
    if args.shards is not None and not args.rehearse:
        raise HarnessError("--shards is for --rehearse only")
    n_shards = args.shards or config["shards"]
    reduced = [] if n_shards == config["shards"] else \
        [f"shards {n_shards} of {config['shards']} (--shards)"]
    peaks = roofline.load_peaks()
    build_native()

    tmp = tempfile.mkdtemp(prefix="pilosa_bench_")
    out_dir = args.out or os.path.join(tmp, "_out")
    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(os.path.join(tmp, "data"))
    server = None
    run_times: dict = {}
    try:
        field_rows = loader.dataset_field_rows(config)
        pool = traffic.Pool(mix, field_rows, args.seed)
        if args.control in controls.WRITE and pool.write is None:
            raise HarnessError(f"--control {args.control} is for a mix "
                               f"that writes; {mix['name']} does not")
        t0 = time.perf_counter()
        expected, written, totals, calls, call_index = write_index(
            cell, pool, tmp, args.seed, n_shards)
        run_times["index_write_s"] = time.perf_counter() - t0
        say(f"index: {n_shards} shards, {written / 1e9:.2f} GB of fragments, "
            f"{len(pool.requests)} distinct requests, "
            f"{run_times['index_write_s']:.1f} s")

        writes, write_calls = None, 0
        if pool.write is not None:
            writes, write_calls, expected = write_state(
                pool, config, args.seed, n_shards, calls, call_index, totals)

        server = Server(os.path.join(tmp, "data"), out_dir,
                        config["server_env"])
        server.wait_up()
        run_times["boot_to_serving_s"] = time.perf_counter() - server.t_spawn
        device = device_of(server.status())
        check_device(device, cell["workload"]["chips"], peaks, args.rehearse)

        index = config["index"]
        wrong_early = 0
        if writes is not None:
            # the first write opens the shard that every later one lands
            # in, before any plane is built: no plane is then built for
            # a shard set the window does not use
            k, body = writes.take()
            reply = server.request(f"/index/{index}/query", body)
            verdict = load.judge([[(-1 - k, 0.0, 0.0, 200, reply)]], expected,
                                 write_calls)
            wrong_early += verdict["acks_wrong"]
            if verdict["first_wrong"]:
                say(f"the first write: {verdict['first_wrong']}")
        first = pool.cover[0]
        t_fused, wrong = wait_fused(server, index, pool.requests[first],
                                    expected[first])
        wrong_early += wrong
        run_times["boot_to_fused_s"] = t_fused - server.t_spawn
        orders = [pool.client_order(c) for c in range(int(mix["clients"]))]
        bodies = [r["pql"].encode() for r in pool.requests]
        warm = warm_up(server, cell, pool, expected, orders, bodies, writes,
                       write_calls)
        run_times["setup_s"] = time.perf_counter() - t_start
        say(f"set-up {run_times['setup_s']:.1f} s: boot->serving "
            f"{run_times['boot_to_serving_s']:.1f} s, ->fused "
            f"{run_times['boot_to_fused_s']:.1f} s, {warm['rounds']} "
            f"warm-up rounds")

        # -- the window ---------------------------------------------------
        status_before, prom_before = server.status(), server.metrics()
        at_start = expected.copy() if writes is not None else None
        ld = load.Load(server, index, orders, bodies, writes)
        marks: dict = {}
        trace_dir = os.path.join(tmp, "trace")
        trace_seconds = min(float(mix["trace_seconds"]), args.seconds / 2)
        during = None
        if args.trace:
            def during(t0):
                capture_trace(server, trace_dir, trace_seconds, t0,
                              args.seconds, marks)
        ld.run(args.seconds, during=during)
        status_after, prom_after = server.status(), server.metrics()
        health = health_facts(status_after, prom_after)
        rc = server.stop()
        if rc != 0:
            raise HarnessError(f"server exited rc={rc} on SIGTERM; log "
                               f"tail:\n{server.log_tail()}")
        memory_peak = server.memory_peak_bytes()
        server = None

        # -- after the window: compare every answer ------------------------
        records, control_state = ld.records, None
        if args.control in controls.READ:
            records = controls.READ[args.control](
                records, cell, pool, calls, call_index, totals, args.seed,
                n_shards)
        elif args.control:
            records, control_state = controls.WRITE[args.control](
                records, at_start, write_calls)
        verdict = load.judge(records, expected, write_calls)
        stats = load.window_stats(ld.records, verdict["ok"], ld.t0,
                                  args.seconds)
        gaps = [g for per in ld.gaps for g in per]
        client = dict(stats, clients=len(orders),
                      turnaround_ms=(sum(gaps) / len(gaps) * 1e3
                                     if gaps else None))
        compared = {
            "answers_compared": {"value": verdict["attempted"],
                                 "at_least": 1},
            "wrong_answers": {"value": verdict["wrong"], "limit": 0},
            "failed_requests": {"value": verdict["failed"], "limit": 0},
            "warmup_wrong_answers": {"value": wrong_early + warm["wrong"],
                                     "limit": 0}}
        compared.update({k: {"value": v, "limit": lim}
                         for k, (v, lim) in health.items()})
        if writes is not None:
            # the oracle now stands at the record's end: stop, boot
            # again on the same data directory, read everything once
            compared.update(
                acked_writes={"value": verdict["acked"], "at_least": 1},
                write_acks_wrong={"value": verdict["acks_wrong"],
                                  "limit": 0})
            restart, restart_peak = after_restart(
                tmp, out_dir, config, pool, expected, control_state, run_times)
            compared.update(restart)
            memory_peak = max(memory_peak or 0, restart_peak or 0) or None
        correct = all(
            c["value"] <= c["limit"] if "limit" in c
            else c["value"] >= c["at_least"] for c in compared.values())
        if verdict["first_wrong"]:
            say(f"first wrong answer: {verdict['first_wrong']}")

        trace = None
        if args.trace:
            trace = read_trace(trace_dir, trace_seconds, out_dir, marks,
                               ld.records, pool, n_shards, args.rehearse)

        ctx = {"status_before": status_before, "status_after": status_after,
               "prom_before": prom_before, "prom_after": prom_after,
               "client": client, "run": run_times, "trace": trace,
               "device_kind": device["kind"]}
        e2e, per_layer = manifest.metrics_of(args.workload, bench)
        metrics = {}
        if args.trace:
            for m in per_layer:
                value = readers.evaluate(manifest.metric(m["name"])["reader"],
                                         ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            values = dict(run_times, **stats)
            for m in e2e:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

        dev = dict(device, memory_peak_bytes=memory_peak or 0)
        if trace:
            dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line = {"correct": bool(correct), "attempted": verdict["attempted"],
                "failed": verdict["failed"] + verdict["wrong"],
                "metrics": metrics, "device": dev}
        if trace:
            line["breakdown"] = trace["breakdown"]
        if args.rehearse:
            line["rehearsal"] = True
        if reduced:
            line["reduced"] = reduced
        if args.control:
            line["control"] = args.control
        compiles = (status_after["costs"]["compileCount"]
                    - status_before["costs"]["compileCount"])
        line["samples"] = {"requests": stats["requests"],
                           "compiles_in_window": compiles,
                           "completed_per_second":
                               stats["completed_per_second"],
                           "bytes_written": written}
        if writes is not None:
            line["samples"].update(
                writes=stats["writes"], write_ms=stats["write_ms"],
                read_after_write_ms=stats["read_after_write_ms"],
                restart_to_serving_s=run_times["restart_to_serving_s"],
                restart_to_read_back_s=run_times["restart_to_read_back_s"])
        line["compared"] = compared
        record = {"args": vars(args), "line": line, "run": run_times,
                  "client": client,
                  "marks": marks, "trace": trace, "warmup": warm,
                  "costs_before": {k: v for k, v in
                                   status_before["costs"].items()
                                   if not isinstance(v, dict)},
                  "costs_after": {k: v for k, v in
                                  status_after["costs"].items()
                                  if not isinstance(v, dict)},
                  "plane_cache": status_after["planeCache"],
                  "query_stages": status_after["queryStages"]}
        with open(os.path.join(out_dir, "record.json"), "w") as fh:
            json.dump(record, fh, indent=1, default=str)
        if args.out:
            # every request of the window: [client, request id, seconds
            # from the window's start to the send, latency in ms]
            with open(os.path.join(out_dir, "requests.json"), "w") as fh:
                json.dump([[c, rid, round(t_send - ld.t0, 6),
                            round((t_done - t_send) * 1e3, 4)]
                           for c, rec in enumerate(ld.records)
                           for rid, t_send, t_done, _, _ in rec], fh)
        say(f"window: {stats['requests']} requests in {args.seconds:g} s "
            f"({len(orders)} clients), percentiles over all of them; "
            f"{compiles} compile(s) inside it")
        for name, c in compared.items():
            lim = f"limit {c['limit']}" if "limit" in c \
                else f"at least {c['at_least']}"
            say(f"compared {name}: {c['value']} ({lim})")
        say(f"correct: {bool(correct)}")
        print(json.dumps(line), flush=True)
        return 0
    finally:
        if server is not None:
            server.stop()
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
