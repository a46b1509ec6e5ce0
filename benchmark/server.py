"""The server child and what the harness reads from it over HTTP
(copied from ``chip_smoke.py``: ``Server``, ``assert_healthy``,
``device_check``).  The child owns the chip while it lives; this
process never initialises a JAX backend."""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REQUEST_TIMEOUT_S = 300.0


class HarnessError(RuntimeError):
    """The run cannot produce a result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(extra: dict) -> dict:
    """The environment of a child that runs the program: the compile
    cache at the fixed path inside the checkout unless the machine
    names one (``pilosa_tpu/engine/_jaxcfg.py``'s rule, said aloud so a
    changed default cannot move it)."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    env.update(extra)
    return env


class Server:
    """One ``python -m pilosa_tpu.cli server`` (through
    ``program.py serve``, which adds only the peak-memory reading at
    exit).  A run's second child on the same data directory (the
    restart of a write cell) has a ``name`` of its own."""

    def __init__(self, data_dir: str, out_dir: str, extra_env: dict,
                 name: str = "server"):
        self.port = free_port()
        self.log_path = os.path.join(out_dir, f"{name}.log")
        self.memory_path = os.path.join(
            out_dir, "memory.json" if name == "server"
            else f"{name}_memory.json")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "program.py"),
             "serve", self.memory_path, "server", "--data-dir", data_dir,
             "--bind", f"127.0.0.1:{self.port}"],
            cwd=ROOT, env=child_env(extra_env), stdout=self._log,
            stderr=self._log)

    def connect(self, timeout: float = REQUEST_TIMEOUT_S):
        return http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)

    def request(self, path: str, body: bytes | None = None,
                timeout: float = REQUEST_TIMEOUT_S) -> bytes:
        conn = self.connect(timeout)
        try:
            conn.request("POST" if body is not None else "GET", path,
                         body=body)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise HarnessError(f"HTTP {resp.status} on {path}: "
                               f"{data[:2000]!r}")
        return data

    def status(self) -> dict:
        return json.loads(self.request("/status", timeout=60))

    def metrics(self) -> dict:
        """The Prometheus text as {series-with-labels: value}."""
        out = {}
        for line in self.request("/metrics", timeout=60).decode() \
                .splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                out[series] = float(value)
        return out

    def wait_up(self, timeout: float = 600.0) -> None:
        deadline = time.monotonic() + timeout
        while True:
            if self.proc.poll() is not None:
                raise HarnessError(
                    f"server exited rc={self.proc.returncode} before "
                    f"serving; log tail:\n{self.log_tail()}")
            try:
                self.request("/version", timeout=5)
                return
            except (OSError, http.client.HTTPException):
                if time.monotonic() > deadline:
                    raise HarnessError(
                        f"server not serving after {timeout:.0f}s; log "
                        f"tail:\n{self.log_tail()}")
                time.sleep(0.1)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as fh:
            return fh.read()

    def log_tail(self, n: int = 30) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def stop(self) -> int:
        """SIGTERM and wait.  The signal is re-sent only while the
        child has NOT logged ``shutting down`` — after that a second
        SIGTERM would kill a process that is closing cleanly (learned
        on four chips).  SIGKILL only after two minutes."""
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

    def memory_peak_bytes(self):
        """Peak on the fullest chip, read by the child at exit; None
        where the backend reports none (the CPU)."""
        with open(self.memory_path) as fh:
            peaks = [p for p in json.load(fh)["peak_bytes_in_use"]
                     if p is not None]
        return max(peaks) if peaks else None


def device_of(status: dict) -> dict:
    devs = status["devices"]
    return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
            "count": len(devs)}


def check_device(device: dict, chips: int, peaks: dict,
                 rehearse: bool) -> None:
    """No accelerator, an unknown kind or fewer chips than the cell
    asks for: no result line.  ``--rehearse`` relaxes only this."""
    if rehearse:
        return
    if device["platform"] != "tpu":
        raise HarnessError(
            f"no accelerator: the server's JAX reports platform "
            f"{device['platform']!r}, need 'tpu' (--rehearse for a CPU "
            f"rehearsal)")
    if device["kind"] not in peaks:
        raise HarnessError(f"unknown device kind {device['kind']!r}; "
                           f"the peaks table knows {sorted(peaks)}")
    if device["count"] != chips:
        raise HarnessError(f"the cell asks for {chips} chip(s), the "
                           f"host has {device['count']}")


def health_facts(status: dict, metrics: dict) -> dict:
    """name -> (reading, limit): nothing fell back, degraded, paged,
    shed or failed to build.  Every limit is exact."""
    dh, ten = status["deviceHealth"], status["tenancy"]
    fallback = sum(v for k, v in metrics.items()
                   if k.split("{", 1)[0].endswith("pallas_fallback_total"))
    facts = {
        "device_state_code": dh.get("stateCode", -1),
        "device_faults": dh.get("faultsTotal", 0),
        "watchdog_trips": dh["watchdogTrips"],
        "quarantined_windows": dh["quarantinedWindows"],
        "plane_build_failures":
            status["storage"]["planeBuild"]["buildFailures"],
        "page_ins": ten.get("pageIns", 0),
        "resident_pages": ten.get("residentPages", 0),
        "oracle_serves": ten.get("oracleServes", 0),
        "shed": status["admission"]["shedTotal"],
        "plane_evictions": status["planeCache"]["evictions"],
        "pallas_fallbacks": fallback,
    }
    return {k: (v, 0) for k, v in facts.items()}
