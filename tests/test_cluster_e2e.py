"""Process-level cluster fault injection (reference: v2
``internal/clustertests/`` — the docker node-kill suite, SURVEY.md §5).

Three REAL OS processes on localhost sockets, replicas=2.  One node is
SIGKILLed mid-query-stream; serving must stay correct off the surviving
replicas, a write during the outage must land, and after the node
restarts anti-entropy must repair every fragment copy byte-identical.

The in-process harness (`pilosa_tpu.testing.run_cluster`) simulates
node loss by stopping heartbeats; this file is the one place node death
is a dead PID, crossing real process/socket boundaries."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

from pilosa_tpu.engine.words import SHARD_WIDTH


from pilosa_tpu.testing import free_ports as _free_ports


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        ctype = r.headers.get("Content-Type", "")
        data = r.read()
    return json.loads(data) if ctype.startswith("application/json") else data


def _post(port, path, body=b"", timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class _Node:
    def __init__(self, port, data_dir, seed_port=None):
        self.port = port
        self.data_dir = data_dir
        self.seed_port = seed_port
        self.proc = None

    def start(self):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",  # a chip belongs to ONE process
            PILOSA_CLUSTER_ENABLED="1",
            PILOSA_REPLICAS="2",
            PILOSA_HEARTBEAT_INTERVAL="0.3",
            PILOSA_ANTI_ENTROPY_INTERVAL="1.5",
            PILOSA_MESH="0",
        )
        if self.seed_port is not None:
            env["PILOSA_SEEDS"] = f"127.0.0.1:{self.seed_port}"
        self.log = open(self.data_dir + ".log", "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--bind", f"127.0.0.1:{self.port}",
             "--data-dir", self.data_dir, "--verbose"],
            env=env, stdout=self.log, stderr=self.log)
        return self

    def await_up(self, timeout=60):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"node :{self.port} exited rc={self.proc.returncode}")
            try:
                _get(self.port, "/status")
                return self
            except Exception:
                time.sleep(0.25)
        raise TimeoutError(f"node :{self.port} never served /status")

    def kill9(self):
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=10)

    def stop(self):
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if getattr(self, "log", None) is not None:
            self.log.close()


def _await_membership(ports, n, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            states = [_get(p, "/status") for p in ports]
            if all(len([nd for nd in s["nodes"]
                        if nd["state"] == "NORMAL"]) == n
                   and s["state"] == "NORMAL" for s in states):
                return
        except Exception:
            pass
        time.sleep(0.3)
    raise TimeoutError(f"cluster never reached {n} NORMAL members")


def _fragment_copies(ports, index, field, shard):
    """(port, bytes) for every live node holding the shard."""
    out = []
    for p in ports:
        try:
            shards = _get(p, f"/internal/shards?index={index}")["shards"]
        except Exception:
            continue
        if shard in shards:
            blob = _get(p, (f"/internal/fragment/data?index={index}"
                            f"&field={field}&view=standard&shard={shard}"))
            out.append((p, blob))
    return out


def test_kill9_failover_and_aae_repair(tmp_path):
    ports = _free_ports(3)
    nodes = [
        _Node(ports[0], str(tmp_path / "n0")),
        _Node(ports[1], str(tmp_path / "n1"), seed_port=ports[0]),
        _Node(ports[2], str(tmp_path / "n2"), seed_port=ports[0]),
    ]
    try:
        nodes[0].start().await_up()
        for nd in nodes[1:]:
            nd.start()
        for nd in nodes[1:]:
            nd.await_up()
        _await_membership(ports, 3)

        _post(ports[0], "/index/i", b"{}")
        _post(ports[0], "/index/i/field/f", b"{}")
        # 4 shards of data so every node owns some of it (replicas=2)
        n_shards = 4
        pql = "".join(
            f"Set({s * SHARD_WIDTH + c}, f=1)"
            for s in range(n_shards) for c in (3, 7, 11))
        _post(ports[0], "/index/i/query", pql.encode())
        want = [3 * n_shards]
        for p in ports:
            assert _post(p, "/index/i/query",
                         b"Count(Row(f=1))")["results"] == want

        # query stream against node 0 while node 2 dies
        errors, wrong = [], []
        stop = threading.Event()

        def stream():
            while not stop.is_set():
                try:
                    got = _post(ports[0], "/index/i/query",
                                b"Count(Row(f=1))", timeout=15)["results"]
                    if got != want:
                        wrong.append(got)
                except Exception as e:  # noqa: BLE001 — tallied below
                    errors.append(repr(e))
                time.sleep(0.05)

        t = threading.Thread(target=stream)
        t.start()
        time.sleep(1.0)
        nodes[2].kill9()
        time.sleep(4.0)  # well past the 3-beat suspect horizon
        stop.set()
        t.join()

        # a stale fan-out may transiently error while the dead node is
        # still listed; results that DO come back must never be wrong
        assert not wrong, f"stale/incorrect counts served: {wrong[:3]}"
        live = [_post(p, "/index/i/query", b"Count(Row(f=1))")["results"]
                for p in ports[:2]]
        assert live == [want, want], "degraded serving diverged"

        # write during the outage: lands on the surviving replica(s)
        down_col = 2 * SHARD_WIDTH + 99
        _post(ports[0], "/index/i/query",
              f"Set({down_col}, f=1)".encode())
        want2 = [want[0] + 1]
        assert _post(ports[1], "/index/i/query",
                     b"Count(Row(f=1))")["results"] == want2

        # restart the killed node on its old data dir; membership and
        # anti-entropy must converge every fragment copy byte-identical
        nodes[2].start().await_up()
        _await_membership(ports, 3)
        deadline = time.monotonic() + 120
        while True:
            copies = {s: _fragment_copies(ports, "i", "f", s)
                      for s in range(n_shards)}
            # every shard's live copies byte-identical (incl. the
            # outage write), and the restarted node serves the full
            # post-outage truth
            synced = (
                all(len({blob for _, blob in cps}) == 1
                    for cps in copies.values() if cps)
                and _post(ports[2], "/index/i/query",
                          b"Count(Row(f=1))")["results"] == want2)
            if synced:
                break
            if time.monotonic() > deadline:
                sizes = {s: [(p, len(b)) for p, b in cps]
                         for s, cps in copies.items()}
                raise AssertionError(
                    f"AAE did not converge: {sizes}")
            time.sleep(1.0)
    finally:
        for nd in nodes:
            nd.stop()
