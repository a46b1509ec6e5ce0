"""Multi-host execution: TWO controller processes joined via
``jax.distributed`` (DCN analogue; SURVEY.md §3.6) running one sharded
query program over the union of their devices.

The reference scales across hosts with memberlist gossip + HTTP fan-out;
the rebuild's host-level cluster does that part (tests/test_cluster.py).
THIS test exercises the other axis — one *pod slice* spanning hosts,
where every process joins a single JAX runtime and collectives ride
ICI/DCN — through the real server config path
(``Config.jax_coordinator`` → ``PilosaTPUServer.open``).

Runs on CPU: each child forces 4 virtual CPU devices, so the global
mesh has 8 devices across 2 processes.
"""

import os
import socket
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared between both child scripts: one psum program over the union
# of both processes' devices (every process holds 4 of the 8 shard
# blocks; same seed everywhere = shared oracle).  Defines psum_check()
# returning the verified global count.
PSUM_SNIPPET = r"""
def psum_check(pid, seed, width):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from pilosa_tpu.parallel import spmd

    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << 32, size=(8, width), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=(8, width), dtype=np.uint32)
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    sh = NamedSharding(mesh, P("shard", None))
    lo = pid * 4
    da = jax.make_array_from_process_local_data(sh, a[lo:lo + 4])
    db = jax.make_array_from_process_local_data(sh, b[lo:lo + 4])
    got = int(spmd.make_intersect_count_psum(mesh)(da, db))
    expect = int(np.unpackbits((a & b).view(np.uint8)).sum())
    assert got == expect, (got, expect)
    return got
"""

CHILD = PSUM_SNIPPET + r"""
import sys
pid, coord, data_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

from pilosa_tpu.cli.config import Config
from pilosa_tpu.server import PilosaTPUServer

cfg = Config(bind="127.0.0.1:0", data_dir=data_dir,
             jax_coordinator=coord, jax_num_processes=2,
             jax_process_id=pid, mesh=False,
             anti_entropy_interval=0.0)
srv = PilosaTPUServer(cfg).open()
try:
    import jax

    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 4
    assert jax.device_count() == 8

    got = psum_check(pid, seed=0, width=256)
    print(f"MULTIHOST_OK {pid} {got}", flush=True)
finally:
    srv.close()
"""


from pilosa_tpu.testing import free_ports as _free_ports


def _free_port() -> int:
    return _free_ports(1)[0]


# holder + cluster layers UNDER a multi-process jax runtime (VERDICT r3
# weak #6: the psum smoke alone left those layers unexercised): the two
# processes form a real HTTP cluster (schema broadcast, shard-routed
# writes, distributed query fan-out) while sharing one jax.distributed
# runtime whose mesh spans both processes' devices.
CHILD_CLUSTER = PSUM_SNIPPET + r"""
import os, sys, time
pid, coord, data_dir, p0, p1, barrier_dir = (
    int(sys.argv[1]), sys.argv[2], sys.argv[3], int(sys.argv[4]),
    int(sys.argv[5]), sys.argv[6])

from pilosa_tpu.cli.config import Config
from pilosa_tpu.server import PilosaTPUServer

cfg = Config(bind=f"127.0.0.1:{p0 if pid == 0 else p1}",
             data_dir=data_dir,
             jax_coordinator=coord, jax_num_processes=2,
             jax_process_id=pid, mesh=False,
             cluster_enabled=True,
             seeds=[] if pid == 0 else [f"127.0.0.1:{p0}"],
             # generous beats: two jax processes share ONE core here,
             # and a several-second XLA compile on a peer's main thread
             # starves its heartbeat loop past a tight suspect horizon
             heartbeat_interval=2.0, anti_entropy_interval=0.0)
srv = PilosaTPUServer(cfg).open()
try:
    import jax
    import numpy as np

    assert jax.process_count() == 2
    # psum FIRST, straight after jax.distributed init while both
    # processes are at the same point: the first collective builds the
    # Gloo context with a 30s rendezvous window, and running it after
    # the (single-core, wall-clock-heavy) cluster phase made the two
    # processes arrive far enough apart to flake the timeout
    got_c = psum_check(pid, seed=1, width=128)

    from pilosa_tpu.api.client import Client
    from pilosa_tpu.engine.words import SHARD_WIDTH

    me = Client("127.0.0.1", cfg.port)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        nodes = me.status()["nodes"]
        if len([n for n in nodes if n["state"] == "NORMAL"]) == 2:
            break
        time.sleep(0.1)
    else:
        raise TimeoutError(f"membership never converged: {nodes}")

    cols = [1, SHARD_WIDTH + 2, 2 * SHARD_WIDTH + 3, 3 * SHARD_WIDTH + 4]
    if pid == 0:
        me.create_index("mi")
        me.create_field("mi", "f")
        # shard-routed writes cross the process boundary over HTTP
        me.query("mi", "".join(f"Set({c}, f=1)" for c in cols))
    want = [len(cols)]
    deadline = time.monotonic() + 60
    got = None
    last_err = None
    while time.monotonic() < deadline:
        try:
            got = me.query("mi", "Count(Row(f=1))")
            if got == want:
                break
        except Exception as e:  # schema may not have propagated yet
            last_err = e
        time.sleep(0.2)
    assert got == want, (got, want, repr(last_err))
    # exit barrier: this node's server must stay up until the PEER'S
    # checks pass too (the fast child exiting first tears down half
    # the cluster under the slow child's queries)
    open(os.path.join(barrier_dir, f"done-{pid}"), "w").close()
    other = os.path.join(barrier_dir, f"done-{1 - pid}")
    deadline = time.monotonic() + 120
    while not os.path.exists(other):
        if time.monotonic() > deadline:
            raise TimeoutError("peer never finished")
        time.sleep(0.1)
    print(f"MULTIHOST_CLUSTER_OK {pid} {got[0]} {got_c}", flush=True)
finally:
    srv.close()
"""


def test_cluster_layer_over_multiprocess_jax(tmp_path):
    cport, p0, p1 = _free_ports(3)
    coord = f"127.0.0.1:{cport}"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    procs = []
    for pid in range(2):
        data = tmp_path / f"c{pid}"
        data.mkdir()
        procs.append(subprocess.Popen(
            [sys.executable, "-c", CHILD_CLUSTER, str(pid), coord,
             str(data), str(p0), str(p1), str(tmp_path)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    seen = set()
    for rc, out, err in outs:
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
        line = [l for l in out.splitlines()
                if l.startswith("MULTIHOST_CLUSTER_OK")]
        assert line, out
        seen.add(tuple(line[0].split()[2:]))
    assert len(seen) == 1  # both processes agree on count and psum


def test_two_process_jax_distributed(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=ROOT)
    procs = []
    for pid in range(2):
        data = tmp_path / f"n{pid}"
        data.mkdir()
        procs.append(subprocess.Popen(
            [sys.executable, "-c", CHILD, str(pid), coord, str(data)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    counts = set()
    for rc, out, err in outs:
        assert rc == 0, f"child failed rc={rc}\nstdout:{out}\nstderr:{err}"
        line = [l for l in out.splitlines() if l.startswith("MULTIHOST_OK")]
        assert line, out
        counts.add(line[0].split()[2])
    assert len(counts) == 1  # both processes agree on the global count
