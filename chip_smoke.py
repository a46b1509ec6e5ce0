#!/usr/bin/env python3
"""chip_smoke.py — does the served path still start, and answer exactly,
on the chip?

Drives the system the way a user does — ``python -m pilosa_tpu.cli
server`` children, HTTP to ``/index/{i}/query`` and ``/status`` — over a
1B-column index (954 shards: a 32-row set field, a 10-bit int field and
the existence row; ~5.6 GB of device planes), and compares every answer
with a numpy oracle accumulated shard by shard while the data is
generated from ``--seed``.  It asserts WHICH path answered from what the
program already exposes (``/status``, ``?profile=true``, ``/debug/slow``,
``/metrics``), so a pass with the device idle is not possible.

One process per chip: this script is the client and the oracle and never
initialises a JAX backend.  Each leg (cold boot + writes, warm restart)
is one server child that owns the chip alone and is stopped with SIGTERM
before the next starts.

    python chip_smoke.py                 # one chip, the whole smoke
    python chip_smoke.py --chips 4       # one index meshed over a host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --shards 2

Any failed comparison, assertion, phase or child exits non-zero and
prints no result line.  On success the LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
with the device as the server's JAX reports it.  Phase seconds are
printed as observations (labelled with the device), never compared with
anything.  The full report and the server logs go to
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# device kinds this smoke knows how to judge; an unknown kind is an
# error, not a default
KNOWN_DEVICE_KINDS = {"TPU v5 lite": "v5e, 16 GB HBM"}

DEFAULT_SHARDS = 954          # ceil(1e9 / 2^20): BASELINE.json's "1B cols"
SHARD_WIDTH = 1 << 20
WORDS = SHARD_WIDTH // 32
INDEX = "smoke"
N_ROWS = 32                   # set field f: rows 0..31 at ~25 % density
V_MAX = 999                   # int field v: values in [0, 1000)
V_DEPTH = 10
BSI_ROWS = 2 + V_DEPTH        # exists + sign + magnitude bits
PLANE_BUDGET_BYTES = 8 << 30  # both planes resident whole on a 16 GB chip

# fixed query operands (rows of f, thresholds of v)
INTER = (1, 2)
FILTER_ROW = 7
TOPN_FILTER_ROW = 0
GT_K = 500
BETWEEN = (200, 700)          # 200 <= v < 700
TREE_PQL = ("Count(Union(Difference(Row(f=2), Row(f=3)), "
            "Xor(Row(f=4), Row(f=5)), Not(Row(f=6))))")

REQUEST_TIMEOUT_S = 900.0


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data + oracle (numpy only; independent of the code under test)
# ---------------------------------------------------------------------------


def gen_shard(seed: int, shard: int):
    """One shard's data, a pure function of (seed, shard): the set
    field's packed rows ``uint32[32, W]``, and per column the int
    field's value and whether it has one."""
    rng = np.random.default_rng([seed, shard])
    f = rng.integers(0, 1 << 32, size=(N_ROWS, WORDS), dtype=np.uint32)
    f &= rng.integers(0, 1 << 32, size=(N_ROWS, WORDS), dtype=np.uint32)
    vals = rng.integers(0, V_MAX + 1, size=SHARD_WIDTH, dtype=np.uint16)
    has = rng.random(SHARD_WIDTH) < 0.75
    vals[~has] = 0
    return f, vals, has


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """bool[2^20] -> uint32[W]; column c is bit c % 32 of word c // 32."""
    return np.packbits(bits, bitorder="little").view("<u4")


def unpack_bits(words: np.ndarray) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), bitorder="little") \
        .astype(bool)


def bsi_rows(vals: np.ndarray, has: np.ndarray) -> np.ndarray:
    """The int field's bit-sliced plane for one shard: row 0 exists,
    row 1 sign (never set: values are non-negative, base 0), rows 2+b
    bit b of the value."""
    rows = np.zeros((BSI_ROWS, WORDS), np.uint32)
    rows[0] = pack_bits(has)
    for b in range(V_DEPTH):
        rows[2 + b] = pack_bits(((vals >> b) & 1).astype(bool) & has)
    return rows


def _agg(vals: np.ndarray, mask: np.ndarray) -> tuple:
    """(sum, count, min, n_min, max, n_max) of vals[mask]."""
    sel = vals[mask].astype(np.int64)
    if sel.size == 0:
        return (0, 0, None, 0, None, 0)
    lo, hi = int(sel.min()), int(sel.max())
    return (int(sel.sum()), int(sel.size), lo, int((sel == lo).sum()),
            hi, int((sel == hi).sum()))


def shard_partials(f: np.ndarray, vals: np.ndarray,
                   has: np.ndarray) -> dict:
    """Everything the query set needs from one shard."""
    pc = np.bitwise_count
    not6 = ~f[6]  # every column exists (the existence row is all ones)
    tree = (f[2] & ~f[3]) | (f[4] ^ f[5]) | not6
    flt = unpack_bits(f[FILTER_ROW]) & has
    return {
        "rc": pc(f).sum(axis=1, dtype=np.int64),
        "inter": int(pc(f[INTER[0]] & f[INTER[1]]).sum()),
        "tree": int(pc(tree).sum()),
        "topn_f": pc(f & f[TOPN_FILTER_ROW]).sum(axis=1, dtype=np.int64),
        "agg": _agg(vals, has),
        "agg_f": _agg(vals, flt),
        "gt": int((has & (vals > GT_K)).sum()),
        "between": int((has & (vals >= BETWEEN[0])
                        & (vals < BETWEEN[1])).sum()),
    }


def _reduce_agg(parts: list) -> dict:
    total = sum(p[0] for p in parts)
    count = sum(p[1] for p in parts)
    lo = min(p[2] for p in parts if p[2] is not None)
    hi = max(p[4] for p in parts if p[4] is not None)
    return {"sum": {"value": total, "count": count},
            "min": {"value": lo,
                    "count": sum(p[3] for p in parts if p[2] == lo)},
            "max": {"value": hi,
                    "count": sum(p[5] for p in parts if p[4] == hi)}}


class Oracle:
    """Per-shard partials, reduced on demand; a write re-derives the
    touched shards' partials from their (mutated) data."""

    def __init__(self, n_shards: int):
        self.parts: list = [None] * n_shards

    def reduce(self) -> dict:
        ps = self.parts
        return {"rc": sum(p["rc"] for p in ps).tolist(),
                "inter": sum(p["inter"] for p in ps),
                "tree": sum(p["tree"] for p in ps),
                "topn_f": sum(p["topn_f"] for p in ps).tolist(),
                "agg": _reduce_agg([p["agg"] for p in ps]),
                "agg_f": _reduce_agg([p["agg_f"] for p in ps]),
                "gt": sum(p["gt"] for p in ps),
                "between": sum(p["between"] for p in ps)}


def top_pairs(counts: list, n: int) -> list:
    order = sorted(range(len(counts)), key=lambda r: (-counts[r], r))
    return [{"id": r, "count": counts[r]} for r in order[:n]
            if counts[r] > 0]


def queries(o: dict) -> list:
    """(name, PQL, expected results) — the five BASELINE.json configs'
    families.  ``o`` is a reduced oracle."""
    a, b = INTER
    return [
        ("count32", "".join(f"Count(Row(f={r}))" for r in range(N_ROWS)),
         o["rc"]),
        ("intersect", f"Count(Intersect(Row(f={a}), Row(f={b})))",
         [o["inter"]]),
        ("tree", TREE_PQL, [o["tree"]]),
        ("topn", "TopN(f, n=10)", [top_pairs(o["rc"], 10)]),
        ("topn_filtered", f"TopN(f, Row(f={TOPN_FILTER_ROW}), n=10)",
         [top_pairs(o["topn_f"], 10)]),
        ("bsi_agg", "Sum(field=v) Min(field=v) Max(field=v)",
         [o["agg"]["sum"], o["agg"]["min"], o["agg"]["max"]]),
        ("bsi_agg_filtered",
         f"Sum(Row(f={FILTER_ROW}), field=v) "
         f"Min(Row(f={FILTER_ROW}), field=v) "
         f"Max(Row(f={FILTER_ROW}), field=v)",
         [o["agg_f"]["sum"], o["agg_f"]["min"], o["agg_f"]["max"]]),
        ("bsi_gt", f"Count(Row(v > {GT_K}))", [o["gt"]]),
        ("bsi_between",
         f"Count(Row({BETWEEN[0]} <= v < {BETWEEN[1]}))", [o["between"]]),
    ]


# ---------------------------------------------------------------------------
# index write (fragment files, shard by shard — the host never holds a
# whole plane)
# ---------------------------------------------------------------------------


def write_index(data_dir: str, n_shards: int, seed: int) -> Oracle:
    from pilosa_tpu.store import FieldOptions, Holder, roaring

    h = Holder(data_dir).open()
    idx = h.create_index(INDEX)  # tracks existence: Not() needs it
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type="int", min=0, max=V_MAX))
    h.close()

    def frag_dir(field: str, view: str) -> str:
        d = os.path.join(data_dir, INDEX, field, "views", view, "fragments")
        os.makedirs(d, exist_ok=True)
        return d

    dirs = {"f": frag_dir("f", "standard"), "v": frag_dir("v", "bsi_v"),
            "e": frag_dir("_exists", "standard")}
    all_ones = roaring.serialize_dense(
        np.full((1, WORDS), 0xFFFFFFFF, np.uint32))
    oracle = Oracle(n_shards)

    def one(shard: int) -> int:
        f, vals, has = gen_shard(seed, shard)
        blobs = {"f": roaring.serialize_dense(f),
                 "v": roaring.serialize_dense(bsi_rows(vals, has)),
                 "e": all_ones}
        for k, blob in blobs.items():
            with open(os.path.join(dirs[k], str(shard)), "wb") as fh:
                fh.write(blob)
        oracle.parts[shard] = shard_partials(f, vals, has)
        return sum(len(b) for b in blobs.values())

    workers = max(1, min(8, (os.cpu_count() or 2) - 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        total = sum(pool.map(one, range(n_shards)))
    say(f"  wrote {n_shards} shards x 3 fragments, {total / 1e9:.2f} GB "
        f"on disk ({workers} writer threads)")
    return oracle


# ---------------------------------------------------------------------------
# server child + HTTP client
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One ``python -m pilosa_tpu.cli server`` child, started the way
    the README's Quickstart starts it.  Owns the chip while it lives."""

    def __init__(self, name: str, data_dir: str, extra_env: dict):
        self.name = name
        self.port = free_port()
        self.log_path = os.path.join(OUT_DIR, f"server_{name}.log")
        env = dict(os.environ,
                   PILOSA_PLANE_BUDGET_BYTES=str(PLANE_BUDGET_BYTES),
                   **extra_env)
        self._log = open(self.log_path, "wb")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--data-dir", data_dir, "--bind", f"127.0.0.1:{self.port}"],
            cwd=REPO, env=env, stdout=self._log, stderr=self._log)

    def request(self, path: str, body: bytes | None = None,
                timeout: float = REQUEST_TIMEOUT_S):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=body,
            method="POST" if body is not None else "GET")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            raise RuntimeError(
                f"{self.name}: HTTP {e.code} on {path}: "
                f"{e.read()[:2000]!r}") from e

    def query(self, pql: str, profile: bool = False) -> dict:
        path = f"/index/{INDEX}/query" + ("?profile=true" if profile
                                          else "")
        return json.loads(self.request(path, pql.encode()))

    def status(self) -> dict:
        return json.loads(self.request("/status", timeout=60))

    def wait_up(self, timeout: float = 300.0) -> None:
        deadline = time.monotonic() + timeout
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name}: server exited rc={self.proc.returncode} "
                    f"before serving; log tail:\n{self.log_tail()}")
            try:
                self.request("/version", timeout=5)
                return
            except (urllib.error.URLError, ConnectionError, TimeoutError):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"{self.name}: not serving after {timeout:.0f}s; "
                        f"log tail:\n{self.log_tail()}")
                time.sleep(0.25)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as fh:
            return fh.read()

    def log_tail(self, n: int = 30) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def stop(self) -> int:
        """SIGTERM and wait: the CLI closes cleanly on it and logs
        ``shutting down``.  The signal is sent again only while the
        child has NOT said so (its check-then-pause loop can miss
        one) — never after, because interpreter shutdown restores the
        default handler and a second SIGTERM then kills a process
        that was closing cleanly.  SIGKILL only after two minutes — a
        process that holds the chip should never need it."""
        deadline = time.monotonic() + 120
        while self.proc.poll() is None:
            if "shutting down" not in self.log_tail(5):
                self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                if time.monotonic() > deadline:
                    self.proc.kill()
        self._log.close()
        return self.proc.returncode


def check(name: str, got, want) -> None:
    if got != want:
        raise AssertionError(
            f"{name}: answer differs from the numpy oracle\n"
            f"  got  {json.dumps(got)[:600]}\n  want {json.dumps(want)[:600]}")


def run_queries(srv: Server, oracle: dict, profile: bool,
                only: tuple = ()) -> dict:
    """Issue the query set in order, each compared exactly; returns
    per-query wall seconds (and, when profiled, the device seconds the
    cost ledger charged to the query's trace)."""
    out = {}
    for name, pql, want in queries(oracle):
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        res = srv.query(pql, profile=profile)
        wall = time.perf_counter() - t0
        check(name, res["results"], want)
        out[name] = {"seconds": round(wall, 4)}
        if profile:
            tags = res["profile"][0].get("tags", {})
            out[name]["deviceSeconds"] = tags.get("deviceSeconds", 0.0)
        say(f"    {name:<18} exact   {wall:8.3f} s"
            + (f"   device {out[name]['deviceSeconds']:.4f} s"
               if profile else ""))
    return out


def cache_entries(cache_dir: str) -> set:
    try:
        return set(os.listdir(cache_dir))
    except FileNotFoundError:
        return set()


# ---------------------------------------------------------------------------
# assertions over what the program exposes
# ---------------------------------------------------------------------------


def device_check(status: dict, args) -> dict:
    devs = status["devices"]
    device = {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
              "count": len(devs)}
    say(f"  device: {json.dumps(device)}")
    if args.rehearse:
        say(f"  rehearsal: device check relaxed (platform "
            f"{device['platform']!r} is not judged)")
    else:
        if device["platform"] != "tpu":
            raise SystemExit(
                f"chip_smoke: no accelerator — the server's JAX reports "
                f"platform {device['platform']!r}, need 'tpu' (use "
                f"--rehearse for a CPU rehearsal)")
        if device["kind"] not in KNOWN_DEVICE_KINDS:
            raise SystemExit(
                f"chip_smoke: unknown device kind {device['kind']!r}; "
                f"known: {sorted(KNOWN_DEVICE_KINDS)}")
    if args.chips is not None and device["count"] != args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but the host has "
            f"{device['count']} device(s)")
    return device


def assert_healthy(srv: Server, status: dict) -> None:
    """Nothing fell back, degraded, paged, shed or failed to build."""
    dh = status["deviceHealth"]
    ten = status["tenancy"]
    facts = {
        "deviceHealth.state": (dh["state"], "healthy"),
        "deviceHealth.faultsTotal": (dh.get("faultsTotal", 0), 0),
        "deviceHealth.watchdogTrips": (dh["watchdogTrips"], 0),
        "deviceHealth.quarantinedWindows": (dh["quarantinedWindows"], 0),
        "storage.planeBuild.buildFailures":
            (status["storage"]["planeBuild"]["buildFailures"], 0),
        "tenancy.pageIns": (ten.get("pageIns", 0), 0),
        "tenancy.residentPages": (ten.get("residentPages", 0), 0),
        "tenancy.oracleServes": (ten.get("oracleServes", 0), 0),
        "admission.shedTotal": (status["admission"]["shedTotal"], 0),
    }
    bad = {k: got for k, (got, want) in facts.items() if got != want}
    if bad:
        raise AssertionError(
            f"{srv.name}: a path other than the device's fused path "
            f"served, or a fault was counted: {bad}")


def check_mesh(mesh: dict | None, device: dict) -> None:
    """One index over every chip: the mesh spans the host and the
    resident plane bytes are balanced to within one shard's slab."""
    if mesh is None or mesh["devices"] != device["count"]:
        raise AssertionError(
            f"/status mesh does not span the host's {device['count']} "
            f"devices: {mesh}")
    per = mesh["perDeviceBytes"]
    slab = (N_ROWS + BSI_ROWS + 1) * WORDS * 4
    if len(per) != device["count"] or \
            max(per.values()) - min(per.values()) > slab:
        raise AssertionError(
            f"resident plane bytes are not balanced over the mesh "
            f"(one shard's slab = {slab} B): {per}")
    say(f"  mesh: {mesh['devices']} devices, per-device bytes "
        f"{sorted(per.values())}, padded shards {mesh['paddedShards']}")


def plane_bytes(n_shards: int, rows: int) -> int:
    return n_shards * rows * WORDS * 4


def wait_resident(srv: Server, n_shards: int, timeout: float) -> dict:
    """Poll /status until the set field's whole plane is resident (the
    int plane builds inside the first aggregate that needs it)."""
    want = plane_bytes(n_shards, N_ROWS)
    deadline = time.monotonic() + timeout
    while True:
        st = srv.status()
        pc = st["planeCache"]
        if pc["builds"] >= 1 and pc["bytes"] >= want:
            return st
        if st["storage"]["planeBuild"]["buildFailures"]:
            raise AssertionError(f"{srv.name}: plane build failed: {pc}")
        if time.monotonic() > deadline:
            raise AssertionError(
                f"{srv.name}: set-field plane not resident after "
                f"{timeout:.0f}s: {pc}")
        time.sleep(0.5)


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------


def serve_pass(srv: Server, oracle: dict, n_shards: int,
               report: dict) -> dict:
    """First answers (planes may still be building) → resident → the
    whole query set again on the fused path, with the path asserted.
    Returns the /status taken after the resident pass."""
    say("  first answers (planes build in the background):")
    # count32 is what starts the set plane's build (a same-field batch
    # of plain Counts); until it lands the generic per-row path answers
    early = run_queries(srv, oracle, profile=False, only=("count32",))
    report["boot_to_first_answer_s"] = round(
        time.perf_counter() - srv.t_start, 3)
    early.update(run_queries(srv, oracle, profile=False,
                             only=("intersect", "bsi_agg")))
    report["early_queries"] = early

    st = wait_resident(srv, n_shards, timeout=600)
    report["boot_to_resident_s"] = round(
        time.perf_counter() - srv.t_start, 3)
    report["plane_build"] = st["storage"]["planeBuild"]
    say(f"  resident after {report['boot_to_resident_s']} s "
        f"(planeBuild {json.dumps(st['storage']['planeBuild'])})")

    before = srv.status()["costs"]
    t_resident = time.time()
    say("  resident pass (first fused query of each family = compile):")
    first = run_queries(srv, oracle, profile=True)
    say("  resident pass again (warm programs):")
    warm = run_queries(srv, oracle, profile=True)
    report["first_fused_queries"] = first
    report["warm_queries"] = warm

    st = srv.status()
    assert_healthy(srv, st)
    # the fused path charges device time to the query's trace; a
    # host-side path (generic per-row, paged, oracle) charges none
    idle = [n for n, q in warm.items() if not q["deviceSeconds"] > 0]
    if idle:
        raise AssertionError(
            f"{srv.name}: no device time was charged to {idle} — they "
            f"did not run on the fused device path")
    costs = st["costs"]
    # count32 (all 32 rows) and the unfiltered TopN each read the whole
    # set-field plane: four such requests ran in the two passes
    grew = costs["bytesScannedTotal"] - before["bytesScannedTotal"]
    need = 4 * plane_bytes(n_shards, N_ROWS)
    if grew < need:
        raise AssertionError(
            f"{srv.name}: costs.bytesScannedTotal grew {grew} B over the "
            f"resident passes, under the {need} B of four whole-plane "
            f"requests")
    slow = json.loads(srv.request("/debug/slow", timeout=60))["slow"]
    # the first answers, before the plane was resident, honestly name
    # the generic per-row path; from the resident passes on, none may
    off_path = [(e["pql"][:60], e["path"]) for e in slow
                if e["path"] != "fused" and e["ts"] >= t_resident]
    if off_path:
        raise AssertionError(
            f"{srv.name}: slow-query ring names a non-fused path: "
            f"{off_path}")
    report["costs"] = {k: costs[k] for k in (
        "windows", "soloDispatches", "deviceSecondsTotal",
        "bytesScannedTotal", "compileSecondsTotal", "compileCount")}
    report["slow_ring"] = [{"pql": e["pql"][:60], "ms": e["durationMs"],
                            "path": e["path"]} for e in slow]
    return st


def spans(span: dict):
    """A profile span and all its descendants."""
    yield span
    for child in span.get("children", ()):
        yield from spans(child)


def single_count_latency(srv: Server, oracle: dict, report: dict) -> None:
    """Round trip of ONE ``Count(Row)`` — one scalar device→host read
    per request: the client's HTTP wall and the server's own
    ``stage.read`` span, medians of 30."""
    pql, want = "Count(Row(f=3))", [oracle["rc"][3]]
    wall, read = [], []
    for _ in range(30):
        t0 = time.perf_counter()
        res = srv.query(pql, profile=True)
        wall.append(time.perf_counter() - t0)
        check("single_count", res["results"], want)
        read.append(sum(s["durationUs"] for s in spans(res["profile"][0])
                        if s["name"] == "stage.read") / 1e6)
    report["single_count_http_ms_median"] = float(np.median(wall)) * 1e3
    report["single_count_stage_read_ms_median"] = \
        float(np.median(read)) * 1e3
    say(f"  single Count(Row): HTTP median "
        f"{report['single_count_http_ms_median']:.3f} ms, server "
        f"stage.read median "
        f"{report['single_count_stage_read_ms_median']:.3f} ms")


def burst(srv: Server, oracle: dict, seconds: float, report: dict) -> None:
    """32 closed-loop clients on the 32-Count request, every response
    checked."""
    _, pql, want = queries(oracle)[0]
    body = pql.encode()
    path = f"/index/{INDEX}/query"
    done, errors = [], []
    start = threading.Barrier(33)

    def client():
        n = 0
        start.wait()
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            got = json.loads(srv.request(path, body))["results"]
            if got != want:
                raise AssertionError("burst: answer differs from oracle")
            n += 1
        done.append(n)

    def guarded():
        try:
            client()
        except Exception as e:  # noqa: BLE001 — re-raised after join
            errors.append(e)
            try:
                start.abort()
            except threading.BrokenBarrierError:
                pass

    threads = [threading.Thread(target=guarded) for _ in range(32)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    if errors:
        raise errors[0]
    n = sum(done)
    report["burst"] = {"clients": 32, "seconds": round(dt, 3),
                       "requests": n,
                       "count_queries_per_s": N_ROWS * n / dt}
    say(f"  burst: 32 clients, {n} requests ({N_ROWS} Counts each) in "
        f"{dt:.2f} s, all exact -> {N_ROWS * n / dt:,.1f} count-qps")


class Writes:
    """A few thousand Set/Clear on a handful of shards, in rounds.  Each
    round is one request (acknowledged op by op) followed AT ONCE by
    reads that must already see it.  A round stays under the 128-op
    journal each fragment keeps for its resident plane, so the reads
    are answered base⊕delta from the device overlay, not by a rebuild.
    The same ops replay on numpy copies of the touched shards — the
    oracle for everything read back, now and after the restart."""

    F_OPS_PER_ROUND = 64
    V_OPS_PER_ROUND = 12

    def __init__(self, seed: int, n_shards: int, oracle: Oracle):
        self.oracle = oracle
        pick = np.random.default_rng([seed, 1 << 20])
        extra = pick.choice(n_shards, size=min(6, n_shards),
                            replace=False).tolist()
        self.shards = sorted({0, n_shards - 1, *extra})
        self.data = {s: gen_shard(seed, s) for s in self.shards}
        self.f_ops, self.v_ops = {}, {}
        for s in self.shards:
            rng = np.random.default_rng([seed, 1 << 20, s])
            f = self.data[s][0]
            cols = rng.choice(SHARD_WIDTH, size=512, replace=False).tolist()
            rows = rng.integers(0, N_ROWS, size=512).tolist()
            was_set = np.flatnonzero(unpack_bits(f[5]))
            self.f_ops[s] = (
                [("Set", c, r) for c, r in zip(cols, rows)]
                # clear bits the data had set, then some of those just set
                + [("Clear", c, 5) for c in
                   rng.choice(was_set, size=128, replace=False).tolist()]
                + [("Clear", c, r) for c, r in zip(cols[:64], rows[:64])])
            # overwrite and create int values
            self.v_ops[s] = list(zip(
                rng.choice(SHARD_WIDTH, size=128, replace=False).tolist(),
                rng.integers(0, V_MAX + 1, size=128).tolist()))
        self.n_rounds = -(-len(self.f_ops[self.shards[0]])
                          // self.F_OPS_PER_ROUND)
        self.n_ops = 0

    def _round(self, k: int) -> str:
        """Round k's PQL; the ops are applied to the numpy copies as
        they are written out."""
        pql = []
        for s in self.shards:
            f, vals, has = self.data[s]
            base = s * SHARD_WIDTH
            lo = k * self.F_OPS_PER_ROUND
            for op, c, r in self.f_ops[s][lo:lo + self.F_OPS_PER_ROUND]:
                pql.append(f"{op}({base + c}, f={r})")
                bit = np.uint32(1 << (c & 31))
                if op == "Set":
                    f[r, c >> 5] |= bit
                else:
                    f[r, c >> 5] &= ~bit
            lo = k * self.V_OPS_PER_ROUND
            for c, v in self.v_ops[s][lo:lo + self.V_OPS_PER_ROUND]:
                pql.append(f"Set({base + c}, v={v})")
                vals[c], has[c] = v, True
            self.oracle.parts[s] = shard_partials(f, vals, has)
        self.n_ops += len(pql)
        return "".join(pql)

    def apply(self, srv: Server) -> float:
        t0 = time.perf_counter()
        for k in range(self.n_rounds):
            pql = self._round(k)
            acks = srv.query(pql)["results"]
            if len(acks) != pql.count("("):
                raise AssertionError(
                    f"write round {k}: {pql.count('(')} ops sent, "
                    f"{len(acks)} acknowledged")
            now = self.oracle.reduce()
            for name, q, want in queries(now):
                if name in ("count32", "bsi_agg"):
                    check(f"round {k} read-back {name}",
                          srv.query(q)["results"], want)
        return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def preflight() -> dict:
    """Before the chip is touched: the native codec from the committed
    sources, the compile-cache directory, the versions."""
    import importlib.metadata as md
    subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                   check=True, stdout=subprocess.DEVNULL)
    from pilosa_tpu.store import native
    if not native.available():
        raise SystemExit("chip_smoke: native codec built but not loadable")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        from pilosa_tpu.engine._jaxcfg import DEFAULT_COMPILE_CACHE_DIR
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
    versions = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = "absent"
    say(f"  versions: {json.dumps(versions)}")
    say(f"  native codec: loaded; compile cache: {cache_dir} "
        f"({len(cache_entries(cache_dir))} entries)")
    return {"versions": versions, "cache_dir": cache_dir}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=DEFAULT_SHARDS,
                    help="CPU rehearsal only; the default is the smoke")
    ap.add_argument("--chips", type=int, default=None,
                    help="devices the host must have (default: any)")
    ap.add_argument("--rehearse", action="store_true",
                    help="relax ONLY the device check (CPU rehearsal)")
    ap.add_argument("--burst-seconds", type=float, default=5.0)
    args = ap.parse_args()

    t_all = time.perf_counter()
    os.makedirs(OUT_DIR, exist_ok=True)
    report: dict = {"args": vars(args), "reduced": [], "phases": {}}
    if args.shards != DEFAULT_SHARDS:
        report["reduced"].append(
            f"shards {args.shards} of {DEFAULT_SHARDS} (--shards)")
    say(f"chip_smoke: {args.shards} shards = "
        f"{args.shards * SHARD_WIDTH:,} columns, seed {args.seed}; "
        f"PILOSA_PLANE_BUDGET_BYTES={PLANE_BUDGET_BYTES} so the "
        f"{plane_bytes(args.shards, N_ROWS) / 1e9:.2f} GB set plane and "
        f"the {plane_bytes(args.shards, BSI_ROWS) / 1e9:.2f} GB int "
        f"plane are both resident whole")

    say("[preflight]")
    report.update(preflight())
    cache_dir = report["cache_dir"]

    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
    servers: list[Server] = []

    def boot(name: str, **env) -> Server:
        say(f"[{name}] boot")
        srv = Server(name, data_dir, env)
        servers.append(srv)
        srv.wait_up()
        ph = report["phases"][name] = {"boot_to_serving_s": round(
            time.perf_counter() - srv.t_start, 3)}
        say(f"  serving after {ph['boot_to_serving_s']} s")
        return srv

    def stop(srv: Server) -> None:
        rc = srv.stop()
        if rc != 0:
            raise AssertionError(
                f"{srv.name}: server exited rc={rc} on SIGTERM; log "
                f"tail:\n{srv.log_tail()}")
        log = srv.log_text()
        report["phases"][srv.name]["donation_warnings"] = log.count(
            "Some donated buffers were not usable")
        report["phases"][srv.name]["error_log_lines"] = sum(
            1 for ln in log.splitlines() if " E pilosa_tpu" in ln)

    try:
        say("[index write]")
        t0 = time.perf_counter()
        oracle = write_index(data_dir, args.shards, args.seed)
        report["index_write_s"] = round(time.perf_counter() - t0, 3)
        say(f"  index write: {report['index_write_s']} s")
        base = oracle.reduce()

        # -- leg 1: cold boot, reads, burst, writes read back -------------
        cache_0 = cache_entries(cache_dir)
        srv = boot("cold")
        ph = report["phases"]["cold"]
        device = report["device"] = device_check(srv.status(), args)
        st = serve_pass(srv, base, args.shards, ph)
        if device["count"] > 1:
            check_mesh(st.get("mesh"), device)
            ph["mesh"] = st["mesh"]
        single_count_latency(srv, base, ph)
        burst(srv, base, args.burst_seconds, ph)
        assert_healthy(srv, srv.status())

        say("  writes (delta-overlay path), read back at once:")
        builds_before = srv.status()["storage"]["planeBuild"]["builds"]
        writes = Writes(args.seed, args.shards, oracle)
        ph["write_s"] = round(writes.apply(srv), 3)
        after = oracle.reduce()
        ph["after_writes"] = run_queries(srv, after, profile=False)
        st = srv.status()
        assert_healthy(srv, st)
        ph["writes"] = {
            "ops": writes.n_ops, "shards": writes.shards,
            "planeBuilds_before": builds_before,
            "planeBuilds_after": st["storage"]["planeBuild"]["builds"],
            "ingest": {k: st["ingest"][k] for k in (
                "deltaCells", "absorbs", "compactions")}}
        say(f"  {writes.n_ops} acknowledged writes in "
            f"{writes.n_rounds} rounds on shards {writes.shards}, each "
            f"round read back exact at once; planeBuild.builds "
            f"{builds_before} -> {ph['writes']['planeBuilds_after']} "
            f"(a rebuild here is reported, not failed)")
        stop(srv)
        cache_1 = cache_entries(cache_dir)
        ph["cache_entries_new"] = len(cache_1 - cache_0)

        # -- leg 2: warm restart on the same data dir and cache dir -------
        srv = boot("warm")
        ph = report["phases"]["warm"]
        serve_pass(srv, after, args.shards, ph)
        stop(srv)
        new = sorted(cache_entries(cache_dir) - cache_1)
        ph["cache_entries_new"] = len(new)
        say(f"  warm boot: written bits survived, answers unchanged; "
            f"compile cache gained {len(new)} entries")
        if new:
            raise AssertionError(
                f"warm boot compiled {len(new)} programs the cold boot "
                f"had not cached: {new[:8]}")
    finally:
        for s in servers:
            s.stop()
        shutil.rmtree(data_dir, ignore_errors=True)
        # also after a failure: what was read before it is the evidence
        report["total_s"] = round(time.perf_counter() - t_all, 3)
        with open(os.path.join(OUT_DIR, "report.json"), "w") as fh:
            json.dump(report, fh, indent=1, default=str)

    label = (f"{device['platform']} / {device['kind']} x "
             f"{device['count']}")
    say(f"[observations on {label} — smoke readings, not metrics]")
    for leg, ph in report["phases"].items():
        say(f"  {leg}: boot->serving {ph['boot_to_serving_s']} s, "
            f"->first answer {ph['boot_to_first_answer_s']} s, "
            f"->resident {ph['boot_to_resident_s']} s; first fused "
            f"count32 {ph['first_fused_queries']['count32']['seconds']} s, "
            f"warm {ph['warm_queries']['count32']['seconds']} s; "
            f"new compile-cache entries {ph['cache_entries_new']}; "
            f"donation warnings {ph['donation_warnings']}")
    cold = report["phases"]["cold"]
    say(f"  cold: single Count(Row) HTTP median "
        f"{cold['single_count_http_ms_median']:.3f} ms (server "
        f"stage.read {cold['single_count_stage_read_ms_median']:.3f} ms); "
        f"burst {cold['burst']['count_queries_per_s']:,.1f} count-qps")
    say(f"  index write {report['index_write_s']} s; total "
        f"{report['total_s']} s; report: {OUT_DIR}/report.json")
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if report["reduced"]:
        result["reduced"] = report["reduced"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
