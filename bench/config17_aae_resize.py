"""Config #17: ANTI-ENTROPY and RESIZE cost at the 954-shard / 4 GB
headline index (VERDICT r4 #7 — "AAE/resize have correctness tests but
zero cost numbers at headline scale").

Host-only (CPU bypass env): both subsystems are pure host + loopback
HTTP work — checksums, roaring serialization, fragment streaming — so
the one-core wall-clock here is an upper bound with no device variable.

Measured on an in-process 2-node cluster (replicas=2) seeded with
byte-identical copies of the 954-shard dense field:

  1. no-op AAE round: full block-checksum sweep of every replicated
     fragment against the peer, zero repairs (the steady-state cost,
     reference: holder syncer, SURVEY §4.6)
  2. repair round: D fragments deleted on node1 → one round restores
     them; time + streamed bytes + byte-identical convergence check
  3. serving impact: 8-client Count qps during a no-op round vs idle
  4. node-add resize: a 3rd node joins; time to NORMAL across all
     nodes, fragment copies moved, effective stream throughput
     (reference: ResizeJob, SURVEY §3.3); Count correctness polled
     THROUGHOUT the resize

Scale via PILOSA_BENCH_SHARDS (default 954)."""

import json
import os
import shutil
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np

from bench._util import log

N_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", "954"))
N_ROWS = 32
WORDS = 32768
DIRTY = 32
INDEX = "bench"


def build_node_dir(data_dir: str, plane: np.ndarray) -> int:
    """One node's on-disk tree: index + dense field fragments.
    Returns total fragment bytes."""
    from pilosa_tpu.store import Holder, roaring

    h = Holder(data_dir).open()
    h.create_index(INDEX, track_existence=False)
    h.index(INDEX).create_field("f")
    h.close()
    fdir = os.path.join(data_dir, INDEX, "f", "views", "standard",
                        "fragments")
    os.makedirs(fdir, exist_ok=True)
    total = 0
    for s in range(N_SHARDS):
        blob = roaring.serialize_dense(plane[s])
        total += len(blob)
        with open(os.path.join(fdir, str(s)), "wb") as fh:
            fh.write(blob)
    return total


def frag_path(base: str, node: int, shard: int) -> str:
    return os.path.join(base, f"node{node}", INDEX, "f", "views",
                        "standard", "fragments", str(shard))


def main():
    import tempfile

    from pilosa_tpu.testing import TestCluster, run_cluster

    rng = np.random.default_rng(17)
    plane = rng.integers(0, 1 << 32, size=(N_SHARDS, N_ROWS, WORDS),
                         dtype=np.uint32)
    plane &= rng.integers(0, 1 << 32, size=plane.shape, dtype=np.uint32)
    want_counts = [int(c) for c in
                   np.bitwise_count(plane).sum(axis=(0, 2), dtype=np.int64)]
    pql32 = "".join(f"Count(Row(f={r}))" for r in range(N_ROWS))
    results = {}

    td = tempfile.mkdtemp(prefix="pilosa_aae_")
    t0 = time.perf_counter()
    frag_bytes = build_node_dir(os.path.join(td, "node0"), plane)
    # node1: byte-identical replica, minus DIRTY fragments it must
    # repair later (deleted AFTER the clean phases)
    shutil.copytree(os.path.join(td, "node0"), os.path.join(td, "node1"))
    log(f"two byte-identical {frag_bytes / 1e9:.2f} GB node trees: "
        f"{time.perf_counter() - t0:.1f}s")

    def cquery(client, pql):
        """Query with a 900s deadline SHIPPED in the request: cold
        planes take minutes to build on this host, and the internode
        fan-out leg derives its socket timeout from the shipped budget
        (without it, remote legs cap at the 60s client default)."""
        return client._do(
            "POST", f"/index/{INDEX}/query?timeout=900",
            pql.encode(), timeout=900.0)["results"]

    with run_cluster(2, td, replicas=2, anti_entropy=0.0) as tc:
        c = tc.client(0)
        assert cquery(c, pql32) == want_counts
        node0 = tc.servers[0].cluster

        # -- 1. no-op AAE rounds: cold (checksum everything) then warm
        # (generation-cached — the steady-state sweep cost) -------------
        t0 = time.perf_counter()
        repaired = node0.sync_once()
        noop_s = time.perf_counter() - t0
        assert repaired == 0, f"clean replicas repaired {repaired}"
        t0 = time.perf_counter()
        assert node0.sync_once() == 0
        noop_warm_s = time.perf_counter() - t0
        results["aae_noop"] = dict(
            cold_s=round(noop_s, 1), warm_s=round(noop_warm_s, 2),
            fragments=N_SHARDS,
            cold_ms_per_fragment=round(noop_s / N_SHARDS * 1e3, 2),
            warm_ms_per_fragment=round(noop_warm_s / N_SHARDS * 1e3, 2))
        log(f"no-op AAE round ({N_SHARDS} fragments x 1 peer): cold "
            f"{noop_s:.1f}s ({noop_s / N_SHARDS * 1e3:.0f} ms/frag), "
            f"warm {noop_warm_s:.2f}s "
            f"({noop_warm_s / N_SHARDS * 1e3:.1f} ms/frag)")

        # -- 2. serving impact during AAE ------------------------------
        def qps_for(seconds: float) -> float:
            stop = time.monotonic() + seconds
            done = [0] * 8
            def worker(i):
                while time.monotonic() < stop:
                    assert cquery(c, pql32) == want_counts
                    done[i] += 1
            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return sum(done) * N_ROWS / seconds

        idle_qps = qps_for(6.0)
        aae_thread = threading.Thread(target=node0.sync_once)
        aae_thread.start()
        during_qps = qps_for(min(noop_s * 0.8, 20.0))
        aae_thread.join()
        results["serving"] = dict(idle_qps=round(idle_qps),
                                  during_aae_qps=round(during_qps),
                                  ratio=round(during_qps / idle_qps, 2))
        log(f"8-client Count qps: idle {idle_qps:,.0f}, during AAE "
            f"{during_qps:,.0f} ({during_qps / idle_qps:.2f}x)")

        # -- 3. repair round -------------------------------------------
        dirty = rng.choice(N_SHARDS, size=min(DIRTY, N_SHARDS // 2),
                           replace=False)
        holder1 = tc.servers[1].api.holder
        idx1 = holder1.index(INDEX)
        f1 = idx1.field("f")
        view1 = f1.views["standard"]
        for s in dirty:
            frag = view1.fragments.pop(int(s), None)
            if frag is not None:
                frag.close()
            os.remove(frag_path(td, 1, int(s)))
        n_dirty = len(dirty)
        moved = n_dirty * frag_bytes // N_SHARDS
        t0 = time.perf_counter()
        repaired = node0.sync_once()
        repair_s = time.perf_counter() - t0
        assert repaired > 0, "dirty replicas repaired nothing"
        stream_s = max(repair_s - noop_warm_s, 1e-3)
        results["aae_repair"] = dict(
            s=round(repair_s, 1), dirty_fragments=n_dirty,
            blocks=repaired, mb_streamed=round(moved / 2**20, 1),
            mb_per_s=round(moved / 2**20 / stream_s, 1))
        log(f"repair round ({n_dirty} missing fragments, {repaired} "
            f"blocks): {repair_s:.1f}s — ~{moved / 2**20 / stream_s:.0f} "
            "MB/s stream (above the warm sweep)")
        view0 = tc.servers[0].api.holder.index(INDEX).field("f") \
            .views["standard"]
        for s in dirty[:4]:  # logical convergence spot check
            pa = view0.fragment(int(s)).positions()
            pb = f1.view("standard").fragment(int(s)).positions()
            assert np.array_equal(pa, pb), f"shard {s} diverged"
        assert cquery(c, pql32) == want_counts

        # -- 4. node-add resize ----------------------------------------
        from pilosa_tpu.cli.config import Config
        from pilosa_tpu.server import PilosaTPUServer

        seed = tc.servers[0].cluster.node_id
        err = []
        polls = [0]

        def poll_queries():
            while not stop_poll.is_set():
                try:
                    if cquery(c, pql32) != want_counts:
                        err.append("wrong counts mid-resize")
                except Exception as e:  # noqa: BLE001
                    err.append(repr(e))
                polls[0] += 1

        stop_poll = threading.Event()
        poller = threading.Thread(target=poll_queries)
        poller.start()
        t0 = time.perf_counter()
        cfg = Config(bind="127.0.0.1:0", data_dir=f"{td}/node2",
                     seeds=[seed], replicas=2, cluster_enabled=True,
                     heartbeat_interval=0.2, anti_entropy_interval=0.0)
        srv2 = PilosaTPUServer(cfg).open()
        tc3 = TestCluster(tc.servers + [srv2])
        try:
            tc3.await_membership(3, timeout=600)
            tc3.await_state("NORMAL", timeout=3600)
            resize_s = time.perf_counter() - t0
            stop_poll.set()
            poller.join()
            assert not err, err[:3]
            n2_frags = sum(
                len(v.fragments)
                for f in srv2.api.holder.index(INDEX).fields.values()
                for v in f.views.values())
            moved_mb = n2_frags * frag_bytes / N_SHARDS / 2**20
            results["resize_add_node"] = dict(
                s=round(resize_s, 1), fragments_to_new_node=n2_frags,
                mb_moved=round(moved_mb, 1),
                mb_per_s=round(moved_mb / resize_s, 1),
                queries_served_during=polls[0])
            log(f"node-add resize: {resize_s:.1f}s, {n2_frags} fragments "
                f"({moved_mb:.0f} MB) to the new node = "
                f"{moved_mb / resize_s:.0f} MB/s; {polls[0]} correct "
                "32-Count queries served during")
            assert cquery(c, pql32) == want_counts
        finally:
            stop_poll.set()
            srv2.close()

    shutil.rmtree(td, ignore_errors=True)
    print(json.dumps({
        "metric": "aae_noop_round_s_954_shards_cpu",
        "value": results["aae_noop"]["cold_s"], "unit": "s",
        "vs_baseline": 1.0, "detail": results}))


if __name__ == "__main__":
    main()
