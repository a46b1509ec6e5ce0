"""Multi-node cluster harnesses for tests and benches.

Reference: ``test/cluster.go#MustRunCluster`` (SURVEY.md §5) — the most
load-bearing fixture upstream: n real servers in one process, real
executors/holders, loopback HTTP between them.  Heartbeat intervals are
cranked down so liveness converges inside test timeouts.

:func:`run_process_cluster` is the OS-process variant (reference: the
v2 ``clustertests`` docker harness) — each node is a separate
``python -m pilosa_tpu.cli server`` process, so node work genuinely
overlaps (no shared GIL) and kill -9 is a real crash.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from contextlib import contextmanager

from pilosa_tpu.api.client import Client
from pilosa_tpu.cli.config import Config
from pilosa_tpu.server import PilosaTPUServer


class TestCluster:
    __test__ = False  # not a pytest collectable

    def __init__(self, servers: list[PilosaTPUServer]):
        self.servers = servers
        self._ssl_by_server: dict[PilosaTPUServer, object] = {}

    @property
    def clients(self) -> list[Client]:
        return [Client("127.0.0.1", s.http.address[1],
                       ssl_context=self._client_ssl(s))
                for s in self.servers]

    def _client_ssl(self, s: PilosaTPUServer):
        # one context per server (tests poll .clients in loops; rebuilding
        # re-reads the PEM files every time)
        if s not in self._ssl_by_server:
            from pilosa_tpu.cli.config import client_ssl_of
            self._ssl_by_server[s] = client_ssl_of(s.cfg)
        return self._ssl_by_server[s]

    def client(self, i: int = 0) -> Client:
        return self.clients[i]

    def node_ids(self) -> list[str]:
        return [s.cluster.node_id for s in self.servers]

    def server_for(self, node_id: str) -> PilosaTPUServer:
        for s in self.servers:
            if s.cluster.node_id == node_id:
                return s
        raise KeyError(node_id)

    def await_membership(self, n: int, timeout: float = 10.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(len(s.cluster.alive_ids()) == n for s in self.servers):
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"cluster did not reach {n} members: "
            f"{[s.cluster.alive_ids() for s in self.servers]}")

    def await_state(self, state: str, timeout: float = 10.0,
                    stable_for: float = 0.3) -> None:
        """Wait until every node reports ``state`` AND it stays that way
        for ``stable_for`` seconds — a join-triggered resize may start a
        beat after the first NORMAL reading."""
        deadline = time.monotonic() + timeout
        stable_since = None
        while time.monotonic() < deadline:
            if all(s.cluster.state == state for s in self.servers):
                if stable_since is None:
                    stable_since = time.monotonic()
                elif time.monotonic() - stable_since >= stable_for:
                    return
            else:
                stable_since = None
            time.sleep(0.05)
        raise TimeoutError(
            f"cluster states {[s.cluster.state for s in self.servers]}")

    def close(self) -> None:
        for s in self.servers:
            s.close()


@contextmanager
def run_cluster(n: int, base_dir: str, replicas: int = 1,
                heartbeat: float = 0.2, anti_entropy: float = 0.0,
                mesh: bool = False, **cfg_kwargs):
    """Boot an n-node in-process cluster; yields a :class:`TestCluster`.
    Extra ``cfg_kwargs`` (e.g. a tls block) apply to every node."""
    servers: list[PilosaTPUServer] = []
    try:
        seed_bind = None
        for i in range(n):
            cfg = Config(
                bind="127.0.0.1:0",
                data_dir=f"{base_dir}/node{i}",
                seeds=[seed_bind] if seed_bind else [],
                replicas=replicas,
                cluster_enabled=True,
                heartbeat_interval=heartbeat,
                anti_entropy_interval=anti_entropy,
                mesh=mesh,
                **cfg_kwargs,
            )
            srv = PilosaTPUServer(cfg).open()
            servers.append(srv)
            if seed_bind is None:
                seed_bind = srv.cluster.node_id
        cluster = TestCluster(servers)
        cluster.await_membership(n)
        cluster.await_state("NORMAL")  # join-triggered resizes settled
        yield cluster
    finally:
        for s in servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass


def rss_mb() -> float:
    """Current process resident set (MB) — the soak probes' shared
    helper."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class ProcessNode:
    """One cluster node as a real OS process, on the CPU platform (a
    chip belongs to one process; a cluster harness starts several)."""

    def __init__(self, port: int, data_dir: str, seed_port: int | None,
                 replicas: int, heartbeat: float, anti_entropy: float,
                 extra_env: dict[str, str] | None = None):
        self.port = port
        self.data_dir = data_dir
        self.seed_port = seed_port
        self.replicas = replicas
        self.heartbeat = heartbeat
        self.anti_entropy = anti_entropy
        # extra env for this node — e.g. PILOSA_FAULTS to arm boot-time
        # failpoints (chaos schedules that must fire during replay/join)
        self.extra_env = dict(extra_env or {})
        self.proc: subprocess.Popen | None = None
        self._log = None

    def start(self) -> "ProcessNode":
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            PILOSA_CLUSTER_ENABLED="1",
            PILOSA_REPLICAS=str(self.replicas),
            PILOSA_HEARTBEAT_INTERVAL=str(self.heartbeat),
            PILOSA_ANTI_ENTROPY_INTERVAL=str(self.anti_entropy),
            PILOSA_MESH="0",
        )
        if self.seed_port is not None:
            env["PILOSA_SEEDS"] = f"127.0.0.1:{self.seed_port}"
        env.update(self.extra_env)
        self._log = open(self.data_dir + ".log", "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--bind", f"127.0.0.1:{self.port}",
             "--data-dir", self.data_dir, "--verbose"],
            env=env, stdout=self._log, stderr=self._log)
        return self

    def await_up(self, timeout: float = 60.0) -> "ProcessNode":
        client = Client("127.0.0.1", self.port, timeout=5.0)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"node :{self.port} exited rc={self.proc.returncode}")
            try:
                client._do("GET", "/status")
                return self
            except Exception:  # noqa: BLE001 — still booting
                time.sleep(0.25)
        raise TimeoutError(f"node :{self.port} never served /status")

    def kill9(self) -> None:
        self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=10)

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self._log is not None:
            self._log.close()
            self._log = None


class ProcessCluster:
    __test__ = False

    def __init__(self, nodes: list[ProcessNode]):
        self.nodes = nodes
        self._clients: dict[int, Client] = {}

    def client(self, i: int = 0) -> Client:
        if i not in self._clients:
            self._clients[i] = Client("127.0.0.1", self.nodes[i].port,
                                      timeout=60.0)
        return self._clients[i]

    def await_membership(self, n: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            dead = [node for node in self.nodes
                    if node.proc.poll() is not None]
            if dead:
                raise RuntimeError(
                    "node(s) died awaiting membership: " + ", ".join(
                        f":{d.port} rc={d.proc.returncode} "
                        f"(log {d.data_dir}.log)" for d in dead))
            try:
                states = [self.client(i)._do("GET", "/status")
                          for i in range(len(self.nodes))]
                if all(s["state"] == "NORMAL"
                       and len([nd for nd in s["nodes"]
                                if nd["state"] == "NORMAL"]) == n
                       for s in states):
                    return
            except Exception:  # noqa: BLE001 — node still joining
                pass
            time.sleep(0.3)
        raise TimeoutError(f"cluster never reached {n} NORMAL members")

    def close(self) -> None:
        for c in self._clients.values():
            c.close()
        for node in self.nodes:
            node.stop()


@contextmanager
def run_process_cluster(n: int, base_dir: str, replicas: int = 1,
                        heartbeat: float = 0.3,
                        anti_entropy: float = 0.0,
                        extra_env: dict[str, str] | None = None):
    """Boot an n-node cluster of separate OS processes; yields a
    :class:`ProcessCluster` once all members are NORMAL.  ``extra_env``
    applies to every node (e.g. ``PILOSA_FAULTS`` chaos schedules)."""
    nodes: list[ProcessNode] = []
    cluster = None
    try:
        for attempt in (0, 1):
            ports = free_ports(n)
            nodes = []
            try:
                for i, port in enumerate(ports):
                    node = ProcessNode(port, f"{base_dir}/node{i}",
                                       seed_port=ports[0] if i else None,
                                       replicas=replicas,
                                       heartbeat=heartbeat,
                                       anti_entropy=anti_entropy,
                                       extra_env=extra_env)
                    nodes.append(node.start())
                    node.await_up()
                break
            except RuntimeError:
                # free_ports probes then closes — another process can
                # steal a port before the node binds it.  One re-roll.
                for node in nodes:
                    node.stop()
                if attempt:
                    raise
        cluster = ProcessCluster(nodes)
        cluster.await_membership(n)
        yield cluster
    finally:
        if cluster is not None:
            try:
                cluster.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        else:
            for node in nodes:
                try:
                    node.stop()
                except Exception:  # noqa: BLE001
                    pass
