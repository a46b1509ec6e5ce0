"""Multi-node cluster tests over the in-process harness — the rebuild of
the reference's ``test.MustRunCluster``-based executor/cluster tests
(SURVEY.md §5): distributed queries, schema broadcast, key translation
replication, replica failover, AAE repair, resize migration."""

import numpy as np
import pytest

from pilosa_tpu.engine.words import SHARD_WIDTH
from pilosa_tpu.testing import run_cluster


@pytest.fixture
def three_nodes(tmp_path):
    with run_cluster(3, str(tmp_path)) as c:
        yield c


def spread_bits(client, n_shards=6, per_shard=50, seed=7):
    """Import bits across n_shards shards; returns oracle (row -> col set)."""
    rng = np.random.default_rng(seed)
    oracle: dict[int, set[int]] = {}
    rows, cols = [], []
    for s in range(n_shards):
        base = s * SHARD_WIDTH
        cs = rng.choice(SHARD_WIDTH, size=per_shard, replace=False)
        rs = rng.integers(1, 4, size=per_shard)
        for r, cc in zip(rs, cs):
            oracle.setdefault(int(r), set()).add(base + int(cc))
            rows.append(int(r))
            cols.append(base + int(cc))
    client.create_index("i")
    client.create_field("i", "f")
    client.import_bits("i", "f", rowIDs=rows, columnIDs=cols)
    return oracle


class TestMembership:
    def test_three_nodes_form(self, three_nodes):
        c = three_nodes
        st = c.client(0).status()
        assert st["state"] == "NORMAL"
        assert len(st["nodes"]) == 3
        assert sum(n["isPrimary"] for n in st["nodes"]) == 1

    def test_consistent_coordinator(self, three_nodes):
        coords = {s.cluster.coordinator_id() for s in three_nodes.servers}
        assert len(coords) == 1

    def test_unknown_heartbeat_sender_pulls_full_state(self, three_nodes):
        """Regression (r13): membership re-learn must not depend on a
        NEWER placementVersion.  Two nodes cold-restarted together
        (the seed plus a peer, kill -9'd in the same failure) each
        come back knowing only themselves while the PERSISTED
        placement version equals their peers' — the version-gated
        pull never fired, each re-learned only nodes that heartbeat
        THEM, and the two restarts never learned each other: an
        asymmetric membership split that wedged forever (surfaced by
        chaos ``coordinator_crash_hint_log``).  An UNKNOWN heartbeat
        sender is itself proof the receiver's view is stale and must
        trigger the full-state pull, same version or not."""
        import time
        cl = three_nodes.servers[0].cluster
        peer = three_nodes.servers[1].cluster.node_id
        third = three_nodes.servers[2].cluster.node_id
        # simulate the cold restart: node0 lost everyone but itself,
        # placement version unchanged (it persists across restarts)
        with cl._lock:
            cl.nodes.pop(peer, None)
            cl.nodes.pop(third, None)
            cl._last_seen.pop(peer, None)
            cl._last_seen.pop(third, None)
        assert cl.member_ids() == [cl.node_id]
        # one heartbeat from node1 at the SAME placement version must
        # re-teach the full membership — node2 included — via the pull
        cl.handle_heartbeat(peer, "NORMAL",
                            placement_version=cl.placement_version)
        want = {cl.node_id, peer, third}
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if set(cl.member_ids()) == want:
                break
            time.sleep(0.05)
        assert set(cl.member_ids()) == want


class TestDistributedQueries:
    def test_schema_broadcast(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f", {"type": "int", "min": 0,
                                            "max": 100})
        for cl in c.clients:
            schema = cl.schema()
            assert schema[0]["name"] == "i"
            assert schema[0]["fields"][0]["options"]["type"] == "int"

    def test_counts_from_every_node(self, three_nodes):
        c = three_nodes
        oracle = spread_bits(c.client(0))
        total = sum(len(v) for v in oracle.values())
        for cl in c.clients:
            (got,) = cl.query("i", "Count(All())")
            assert got == total
            for r, cols in oracle.items():
                (cnt,) = cl.query("i", f"Count(Row(f={r}))")
                assert cnt == len(cols), f"row {r}"

    def test_row_columns_and_algebra(self, three_nodes):
        c = three_nodes
        oracle = spread_bits(c.client(0))
        (r1,) = c.client(1).query("i", "Row(f=1)")
        assert r1["columns"] == sorted(oracle[1])
        (ri,) = c.client(2).query("i", "Intersect(Row(f=1), Row(f=2))")
        assert ri["columns"] == sorted(oracle[1] & oracle[2])
        (ru,) = c.client(0).query("i", "Union(Row(f=1), Row(f=2))")
        assert ru["columns"] == sorted(oracle[1] | oracle[2])

    def test_topn_merged(self, three_nodes):
        c = three_nodes
        oracle = spread_bits(c.client(0))
        expect = sorted(((r, len(cols)) for r, cols in oracle.items()),
                        key=lambda kv: (-kv[1], kv[0]))[:2]
        (top,) = c.client(1).query("i", "TopN(f, n=2)")
        assert [(p["id"], p["count"]) for p in top] == expect

    def test_topn_tanimoto_distributed(self, three_nodes):
        # the tanimoto threshold must apply on GLOBAL counts: nodes ship
        # intersection+row counts and |src|; per-node ratios would merge
        # wrong when a row's bits spread across nodes
        c = three_nodes
        oracle = spread_bits(c.client(0))
        c.client(0).create_field("i", "g")
        src = sorted(oracle[1])[::2] + [4 * SHARD_WIDTH + 123]
        c.client(0).import_bits("i", "g", rowIDs=[1] * len(src),
                                columnIDs=src)
        srcset = set(src)
        thr = 30.0
        expect = sorted(
            ((r, len(cols & srcset)) for r, cols in oracle.items()
             if len(cols & srcset) > 0
             and 100.0 * len(cols & srcset) >= thr * len(cols | srcset)),
            key=lambda kv: (-kv[1], kv[0]))
        for cl in c.clients:
            (top,) = cl.query("i", "TopN(f, filter=Row(g=1), tanimoto=30)")
            assert [(p["id"], p["count"]) for p in top] == expect
        assert expect, "test must exercise a non-empty threshold pass"

    def test_tanimoto_src_on_fieldless_node(self, three_nodes):
        # |src| bits live on shards where the TARGET field has no rows:
        # those nodes must still report their srcCount share or the
        # global union is undercounted and the threshold over-admits
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        c.client(0).create_field("i", "g")
        c.client(0).import_bits("i", "f", rowIDs=[10, 10, 10],
                                columnIDs=[1, 2, 3])
        src_cols = [1] + [s * SHARD_WIDTH + 9 for s in range(1, 6)]
        c.client(0).import_bits("i", "g", rowIDs=[1] * len(src_cols),
                                columnIDs=src_cols)
        # |src|=6, inter=1, row=3 → union=8, ratio 12.5% < 30
        for cl in c.clients:
            (top,) = cl.query("i", "TopN(f, filter=Row(g=1), tanimoto=30)")
            assert top == []
            (top,) = cl.query("i", "TopN(f, filter=Row(g=1), tanimoto=12)")
            assert [(p["id"], p["count"]) for p in top] == [(10, 1)]

    def test_tanimoto_invalid_threshold_distributed(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        c.client(0).create_field("i", "g")
        c.client(0).query("i", "Set(1, f=10) Set(1, g=1)")
        for bad in (0, 101, -3):
            with pytest.raises(Exception):
                c.client(1).query(
                    "i", f"TopN(f, filter=Row(g=1), tanimoto={bad})")

    def test_bsi_distributed(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "amount", {"type": "int",
                                                 "min": -1000, "max": 1000})
        cols = [0, SHARD_WIDTH + 1, 2 * SHARD_WIDTH + 2, 3 * SHARD_WIDTH + 3]
        vals = [10, -20, 30, 40]
        c.client(0).import_values("i", "amount", columnIDs=cols, values=vals)
        for cl in c.clients:
            (s,) = cl.query("i", "Sum(field=amount)")
            assert s == {"value": 60, "count": 4}
            (mn,) = cl.query("i", "Min(field=amount)")
            assert mn == {"value": -20, "count": 1}
            (r,) = cl.query("i", "Row(amount > 15)")
            assert r["columns"] == [2 * SHARD_WIDTH + 2, 3 * SHARD_WIDTH + 3]

    def test_writes_via_pql_from_any_node(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        far = 5 * SHARD_WIDTH + 123
        assert c.client(2).query("i", f"Set({far}, f=9)") == [True]
        assert c.client(1).query("i", f"Count(Row(f=9))") == [1]
        assert c.client(0).query("i", f"Clear({far}, f=9)") == [True]
        assert c.client(1).query("i", "Count(Row(f=9))") == [0]

    def test_groupby_distributed(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "a")
        c.client(0).create_field("i", "b")
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "a", rowIDs=[1, 1], columnIDs=[5, far])
        c.client(0).import_bits("i", "b", rowIDs=[2, 3], columnIDs=[5, far])
        (g,) = c.client(1).query("i", "GroupBy(Rows(a), Rows(b))")
        got = sorted((tuple(fr["rowID"] for fr in grp["group"]),
                      grp["count"]) for grp in g)
        assert got == [((1, 2), 1), ((1, 3), 1)]

    def test_column_attrs_distributed(self, three_nodes):
        # Options(columnAttrs=true): per-node attr maps union at merge
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "f", rowIDs=[1, 1],
                                columnIDs=[5, far])
        c.client(0).query("i", 'SetColumnAttrs(5, region="eu")')
        c.client(0).query("i", f'SetColumnAttrs({far}, region="us")')
        (r,) = c.client(1).query(
            "i", "Options(Row(f=1), columnAttrs=true)")
        assert r["columns"] == [5, far]
        assert r["attrs"] == {"5": {"region": "eu"},
                              str(far): {"region": "us"}}
        # keyed index: attr maps re-key to column keys
        c.client(0).create_index("ka", {"keys": True})
        c.client(0).create_field("ka", "f")
        c.client(0).query("ka", 'Set("alice", f=3)')
        c.client(0).query("ka", 'SetColumnAttrs("alice", region="eu")')
        (r,) = c.client(1).query(
            "ka", "Options(Row(f=3), columnAttrs=true)")
        assert r["keys"] == ["alice"]
        assert r["attrs"] == {"alice": {"region": "eu"}}

    def test_row_attrs_distributed_keyed(self, three_nodes):
        # keyed-index key translation must carry rowAttrs through
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f")
        c.client(0).query("k", 'Set("alice", f=3)')
        c.client(0).query("k", 'SetRowAttrs(f, 3, tier="gold")')
        (r,) = c.client(1).query("k", "Row(f=3)")
        assert r["keys"] == ["alice"]
        assert r.get("rowAttrs") == {"tier": "gold"}

    def test_row_attrs_distributed(self, three_nodes):
        # the merged Row result carries the row's attributes (attrs are
        # replicated, so any node's partial supplies them)
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "f", rowIDs=[1, 1],
                                columnIDs=[5, far])
        c.client(0).query("i", 'SetRowAttrs(f, 1, team="infra")')
        for cl in (c.client(1), c.client(2)):
            (r,) = cl.query("i", "Row(f=1)")
            assert r["columns"] == [5, far]
            assert r.get("rowAttrs") == {"team": "infra"}
            (r2,) = cl.query("i", "Row(f=1, excludeRowAttrs=true)")
            assert "rowAttrs" not in r2

    def test_groupby_having_distributed(self, three_nodes):
        # having thresholds apply to GLOBAL sums: each node alone sees
        # count 1 for row 1, so a local having(count > 1) would wrongly
        # drop it — the strip+merge path must keep it
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "a")
        c.client(0).create_field("i", "v", {"type": "int", "min": -100,
                                            "max": 100})
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "a", rowIDs=[1, 1, 2],
                                columnIDs=[5, far, 6])
        c.client(0).import_values("i", "v", columnIDs=[5, far, 6],
                                  values=[40, 30, 9])
        (g,) = c.client(1).query(
            "i", "GroupBy(Rows(a), having=Condition(count > 1))")
        assert [(grp["group"][0]["rowID"], grp["count"]) for grp in g] \
            == [(1, 2)]
        (g,) = c.client(2).query(
            "i", "GroupBy(Rows(a), aggregate=Sum(field=v),"
                 "having=Condition(sum >= 70))")
        assert [(grp["group"][0]["rowID"], grp["agg"]) for grp in g] \
            == [(1, 70)]

    def test_groupby_minmax_aggregate_distributed(self, three_nodes):
        # Min/Max aggregates merge as extrema of per-node extrema (not
        # sums); values live on different nodes' shards
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "a")
        c.client(0).create_field("i", "v", {"type": "int", "min": -100,
                                            "max": 100})
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "a", rowIDs=[1, 1], columnIDs=[5, far])
        c.client(0).import_values("i", "v", columnIDs=[5, far],
                                  values=[42, -7])
        for pql, want in [
            ("GroupBy(Rows(a), aggregate=Min(field=v))", -7),
            ("GroupBy(Rows(a), aggregate=Max(field=v))", 42),
            ("GroupBy(Rows(a), aggregate=Sum(field=v))", 35),
            ("GroupBy(Rows(a), aggregate=Count())", 2),
        ]:
            (g,) = c.client(1).query("i", pql)
            assert [(grp["group"][0]["rowID"], grp["count"], grp["agg"])
                    for grp in g] == [(1, 2, want)], pql


class TestKeyedCluster:
    def test_key_translation_replicated(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        # writes via different nodes: coordinator assigns, replicates
        assert c.client(1).query("k", 'Set("alice", f="admin")') == [True]
        assert c.client(2).query("k", 'Set("bob", f="admin")') == [True]
        for cl in c.clients:
            (r,) = cl.query("k", 'Row(f="admin")')
            assert sorted(r["keys"]) == ["alice", "bob"]
        (top,) = c.client(2).query("k", "TopN(f)")
        assert top == [{"key": "admin", "count": 2}]

    def test_unknown_key_reads_empty(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        c.client(0).query("k", 'Set("alice", f="admin")')
        (r,) = c.client(1).query("k", 'Row(f="nosuch")')
        assert r == {"keys": []}


class TestReplicationAndFailover:
    def test_replicated_write_lands_on_replicas(self, tmp_path):
        with run_cluster(3, str(tmp_path), replicas=2) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).import_bits("i", "f", rowIDs=[1], columnIDs=[42])
            owners = c.servers[0].cluster.shard_owners("i", 0)
            assert len(owners) == 2
            holders = 0
            for s in c.servers:
                idx = s.holder.index("i")
                f = idx.field("f") if idx else None
                v = f.standard_view() if f else None
                frag = v.fragment(0) if v else None
                if frag is not None and frag.row(1).contains(42):
                    holders += 1
            assert holders == 2

    def test_failover_query_after_node_loss(self, tmp_path):
        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH for s in range(6)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 6,
                                    columnIDs=cols)
            (before,) = c.client(0).query("i", "Count(Row(f=1))")
            assert before == 6
            # kill a non-coordinator node
            coord = c.servers[0].cluster.coordinator_id()
            victim = next(s for s in c.servers
                          if s.cluster.node_id != coord)
            survivor = next(s for s in c.servers if s is not victim)
            victim.close()
            # wait for liveness to notice
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(survivor.cluster.alive_ids()) == 2:
                    break
                time.sleep(0.05)
            assert len(survivor.cluster.alive_ids()) == 2
            from pilosa_tpu.api.client import Client
            cl = Client("127.0.0.1", survivor.http.address[1])
            (after,) = cl.query("i", "Count(Row(f=1))")
            assert after == 6


class TestClusterQueryTimeout:
    def test_timeout_enforced_through_fanout(self, tmp_path):
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).query("i", "Set(1, f=1)")
            with pytest.raises(ClientError) as ei:
                c.client(0)._do(
                    "POST", "/index/i/query?timeout=0.000001",
                    b"Count(Row(f=1))")
            assert ei.value.status == 504
            assert c.client(0)._do(
                "POST", "/index/i/query?timeout=30",
                b"Count(Row(f=1))")["results"] == [1]

    def test_deadline_ships_to_remote_nodes(self, tmp_path):
        """The remaining budget rides /internal/query and is enforced
        by the PEER's executor — not just by the coordinator's
        between-call checks (r4 review: the 1us test above expires
        before the first fan-out and proved nothing about peers)."""
        import time

        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path)) as c:
            coord = c.servers[0]
            peer = c.servers[1]
            cl = c.clients[0]
            cl.create_index("i")
            cl.create_field("i", "f")
            # a bit on a shard the PEER owns, so the read fans out
            shard = next(
                s for s in range(32)
                if coord.cluster.shard_owners("i", s)[0]
                == peer.cluster.node_id)
            from pilosa_tpu.engine.words import SHARD_WIDTH
            cl.query("i", f"Set({shard * SHARD_WIDTH + 1}, f=1)")

            slept = []
            real = peer.executor.execute

            def slow(*a, **kw):
                slept.append(1)
                time.sleep(0.4)
                return real(*a, **kw)

            peer.executor.execute = slow
            try:
                with pytest.raises(ClientError) as ei:
                    cl._do("POST",
                           "/index/i/query?timeout=0.2",
                           f"Count(Row(f=1))".encode())
                assert ei.value.status == 504
                assert slept, "query never reached the peer"
            finally:
                peer.executor.execute = real
            assert cl._do("POST", "/index/i/query?timeout=30",
                          b"Count(Row(f=1))")["results"] == [1]

    def test_internal_timeout_param_validated(self, tmp_path):
        """/internal/query validates ?timeout= like the public handler
        (ADVICE r4): malformed values answer 400, and NaN — which would
        silently disable the deadline — is rejected too."""
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            for bad in ("bogus", "nan", "-1", "inf"):
                with pytest.raises(ClientError) as ei:
                    c.client(0)._do(
                        "POST", f"/internal/query?index=i&timeout={bad}",
                        b"Count(Row(f=1))")
                assert ei.value.status == 400, bad

    def test_internal_socket_timeout_follows_budget(self, tmp_path):
        """A shipped deadline also drives the per-call SOCKET timeout:
        the Client's fixed 60s default must not cut off a remote leg
        whose query budget is longer (ADVICE r4 medium)."""
        import time

        with run_cluster(2, str(tmp_path)) as c:
            coord, peer = c.servers
            cl = c.clients[0]
            cl.create_index("i")
            cl.create_field("i", "f")
            cl.query("i", "Set(1, f=1)")
            client = coord.cluster._client(peer.cluster.node_id)
            seen = {}
            real = client._do

            def spy(method, path, body=None, **kw):
                if path.startswith("/internal/query"):
                    seen["timeout"] = kw.get("timeout")
                return real(method, path, body, **kw)

            client._do = spy
            try:
                coord.cluster.internal_query(
                    peer.cluster.node_id, "i", "Count(Row(f=1))", None,
                    deadline=time.monotonic() + 120)
            finally:
                client._do = real
            assert seen["timeout"] is not None
            assert 120 < seen["timeout"] < 140


class TestTransportErrorClassification:
    """ClientError.kind separates 'peer never saw it' from 'peer may
    still apply it' — write replication must not count a timed-out
    write as cleanly missed (ADVICE r4)."""

    def test_kinds_from_real_sockets(self):
        import socket
        import threading

        from pilosa_tpu.api.client import Client, ClientError

        # a server that accepts and never answers -> read timeout
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]
        conns = []
        t = threading.Thread(
            target=lambda: conns.append(srv.accept()), daemon=True)
        t.start()
        try:
            with pytest.raises(ClientError) as ei:
                Client("127.0.0.1", port, timeout=0.3)._json("GET", "/status")
            assert ei.value.kind == "timeout"
        finally:
            srv.close()
        # a closed port -> connection refused -> unreachable
        with pytest.raises(ClientError) as ei:
            Client("127.0.0.1", port, timeout=0.3)._json("GET", "/status")
        assert ei.value.kind == "unreachable"

    def test_write_timeout_propagates_state_unknown(self, tmp_path):
        """A best-effort Set that TIMES OUT on a replica must not be
        waved off as 'node down, AAE repairs it' — the replica may
        still apply the write; the op fails loudly with the replica
        named (ADVICE r4)."""
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=2) as c:
            coord, peer = c.servers
            cl = c.clients[0]
            cl.create_index("i")
            cl.create_field("i", "f")
            client = coord.cluster._client(peer.cluster.node_id)
            real = client._do

            def timeout_on_query(method, path, body=None, **kw):
                if path.startswith("/internal/query"):
                    raise ClientError("request timed out", kind="timeout")
                return real(method, path, body, **kw)

            client._do = timeout_on_query
            try:
                with pytest.raises(ClientError) as ei:
                    # route through the coordinator so the peer leg is
                    # the patched client
                    c.client(0).query("i", "Set(1, f=1)")
            finally:
                client._do = real
            assert ei.value.status == 400
            assert "state unknown" in str(ei.value)
            assert peer.cluster.node_id in str(ei.value)


class TestWriteSemanticsUnderNodeLoss:
    """r13 contract: EVERY write serves through a dead replica — the op
    applies on the live owners and the dead one's copy is durably
    hinted for ordered replay on rejoin.  With handoff disabled
    (hint_max_age=0) the legacy contract is pinned: Set best-effort,
    Clear-family strict fail-fast (a clear missed by a down replica
    would be resurrected by union-merge AAE)."""

    @staticmethod
    def _kill_non_coordinator(c):
        import time
        coord = c.servers[0].cluster.coordinator_id()
        victim = next(s for s in c.servers
                      if s.cluster.node_id != coord)
        victim_id = victim.cluster.node_id
        victim.close()
        survivor = next(s for s in c.servers if s is not victim)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if len(survivor.cluster.alive_ids()) == 2:
                return victim_id
            time.sleep(0.05)
        raise TimeoutError("node loss never detected")

    def test_writes_serve_through_dead_replica_with_hints(self, tmp_path):
        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH + 1 for s in range(6)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 6,
                                    columnIDs=cols)
            victim_id = self._kill_non_coordinator(c)
            alive = [s for s in c.servers
                     if s.cluster.node_id != victim_id]
            from pilosa_tpu.api.client import Client
            cl = Client("127.0.0.1", alive[0].http.address[1])
            # Sets succeed on every shard, including ones the dead
            # node owns (with 6 shards x replicas=2 over 3 nodes the
            # victim owns some)
            for s in range(6):
                assert cl.query(
                    "i", f"Set({s * SHARD_WIDTH + 7}, f=1)") == [True]
            assert cl.query("i", "Count(Row(f=1))") == [12]
            # Clear on a shard the dead node owns now SERVES: applied
            # on the live owner, hinted for the dead one
            victim_shards = [
                s for s in range(6) if victim_id in
                alive[0].cluster.shard_owners("i", s)]
            assert victim_shards, "victim owns no shard — test invalid"
            col = victim_shards[0] * SHARD_WIDTH + 7
            assert cl.query("i", f"Clear({col}, f=1)") == [True]
            assert cl.query("i", "Count(Row(f=1))") == [11]
            # the dead owner's copies are durably queued and visible
            wh = cl.write_health()
            assert wh["hintedHandoff"] is True
            assert wh["hintBacklogOps"] >= 1
            peers = {p["id"]: p for p in wh["peers"]}
            assert victim_id in peers
            assert peers[victim_id]["overflowed"] is False
            # the hinted peer is no longer write-reachable: new writes
            # to it keep appending BEHIND the older hints (ordering)
            entry = alive[0].cluster
            assert victim_id not in entry.dist._write_reachable()
            # hint metadata is advertised for AAE gating
            assert victim_id in entry.hinted_peers()

    def test_legacy_strictness_with_handoff_disabled(self, tmp_path):
        """hint_max_age=0 pins the pre-r13 contract: Set best-effort,
        Clear refused 503 with the structured writeUnavailable body
        naming the down replica."""
        from pilosa_tpu.api.client import ClientError

        with run_cluster(3, str(tmp_path), replicas=2, heartbeat=0.1,
                         hint_max_age=0.0) as c:
            assert c.servers[0].cluster.hints is None
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH + 1 for s in range(6)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 6,
                                    columnIDs=cols)
            victim_id = self._kill_non_coordinator(c)
            alive = [s for s in c.servers
                     if s.cluster.node_id != victim_id]
            from pilosa_tpu.api.client import Client
            cl = Client("127.0.0.1", alive[0].http.address[1])
            for s in range(6):
                assert cl.query(
                    "i", f"Set({s * SHARD_WIDTH + 7}, f=1)") == [True]
            victim_shards = [
                s for s in range(6) if victim_id in
                alive[0].cluster.shard_owners("i", s)]
            assert victim_shards, "victim owns no shard — test invalid"
            col = victim_shards[0] * SHARD_WIDTH + 7
            with pytest.raises(ClientError, match="resurrected") as ei:
                cl.query("i", f"Clear({col}, f=1)")
            assert ei.value.status == 503
            # on a fully-alive owner set, Clear still works
            healthy = [s for s in range(6) if s not in victim_shards]
            if healthy:
                hcol = healthy[0] * SHARD_WIDTH + 7
                assert cl.query("i", f"Clear({hcol}, f=1)") == [True]

    def test_refusal_body_names_replica_at_public_edge(self, tmp_path):
        """The 503 refusal carries Retry-After and the structured
        writeUnavailable body (op, replica, reason) — satellite 1."""
        import json as _json
        import urllib.error
        import urllib.request

        with run_cluster(2, str(tmp_path), replicas=2, heartbeat=0.1,
                         hint_max_age=0.0) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).query("i", "Set(1, f=1)")
            victim = c.servers[1]
            victim_id = victim.cluster.node_id
            victim.close()
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(c.servers[0].cluster.alive_ids()) == 1:
                    break
                time.sleep(0.05)
            port = c.servers[0].http.address[1]
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/index/i/query",
                data=b"Clear(1, f=1)", method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            err = ei.value
            assert err.code == 503
            assert err.headers.get("Retry-After") is not None
            body = _json.loads(err.read())
            wu = body["writeUnavailable"]
            assert wu["op"] == "Clear"
            assert wu["replica"] == victim_id
            assert wu["reason"] == "replica_down"
            assert victim_id in body["error"]

    def test_saturated_replica_is_not_hinted(self, tmp_path):
        """Regression (r13 review): an ALIVE replica that answered 503
        (admission shed — the op never executed there) must NOT be
        treated like a dead one and hinted.  The peer keeps serving
        reads, so hinting would ack a strict Clear that a read on that
        replica then contradicts — and would wrongly AAE-gate and
        write-block a merely-busy node.  Strict writes refuse with the
        structured 503 (``replica_busy``); best-effort Sets fall back
        to the legacy miss (AAE repairs), no hint either way."""
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            coord, peer = c.servers
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).query("i", "Set(1, f=1)")
            client = coord.cluster._client(peer.cluster.node_id)
            real = client._do

            def shed_queries(method, path, body=None, **kw):
                if path.startswith("/internal/query"):
                    raise ClientError("executor saturated", status=503)
                return real(method, path, body, **kw)

            client._do = shed_queries
            try:
                with pytest.raises(ClientError) as ei:
                    c.client(0).query("i", "Clear(1, f=1)")
                assert ei.value.status == 503
                assert "shed Clear" in str(ei.value)
                assert peer.cluster.node_id in str(ei.value)
                # the busy leg makes Set a best-effort miss, not a hint
                assert c.client(0).query("i", "Set(2, f=1)") == [True]
            finally:
                client._do = real
            hints = coord.cluster.hints
            assert hints is not None and not hints.pending_peers(), (
                "an answered 503 must never produce a hint")
            # nothing gated, peer still write-reachable once unpatched
            # (the returned changed-bool is the primary's, and the
            # primary may be the peer that missed the Set — assert the
            # end state, not the bool)
            c.client(0).query("i", "Clear(2, f=1)")
            for cl in c.clients:
                (row,) = cl.query("i", "Row(f=1)")
                assert 2 not in row["columns"]

    def test_all_targets_dead_mid_apply_refuses_not_acks(self, tmp_path):
        """Regression (r13 review): when a write's every live target
        dies MID-APPLY (each hinted via the handoff callback), nothing
        applied now — acking would claim otherwise.  The op refuses
        no_live_replica; the queued hint still replays once the peer
        answers again (at-least-once for the un-acked op)."""
        import time

        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=1,
                         heartbeat=0.1) as c:
            coord, peer = c.servers
            peer_id = peer.cluster.node_id
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            # a column whose ONLY owner (replicas=1) is the peer
            shard = next((s for s in range(32)
                          if coord.cluster.shard_owners("i", s)
                          == [peer_id]), None)
            assert shard is not None, "peer owns no shard — test invalid"
            col = shard * SHARD_WIDTH + 3
            assert c.client(0).query("i", f"Set({col}, f=1)") == [True]
            client = coord.cluster._client(peer_id)
            real = client._do

            def die(method, path, body=None, **kw):
                if (path.startswith("/internal/query")
                        or path.startswith("/internal/hints/replay")):
                    raise ClientError("connection reset", status=0,
                                      kind="unreachable")
                return real(method, path, body, **kw)

            client._do = die
            try:
                with pytest.raises(ClientError) as ei:
                    c.client(0).query("i", f"Clear({col}, f=1)")
                assert ei.value.status == 503
                assert "no live replica" in str(ei.value)
                # the mid-apply handoff durably queued the op anyway
                assert coord.cluster.hints.has_pending(peer_id)
            finally:
                client._do = real
            # peer answers again: the next heartbeat's drain delivers
            # the un-acked Clear (at-least-once), converging the bit
            deadline = time.monotonic() + 10
            while (coord.cluster.hints.has_pending(peer_id)
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert not coord.cluster.hints.has_pending(peer_id)
            (row,) = c.client(1).query("i", "Row(f=1)")
            assert col not in row["columns"]

    def test_clearrow_shard_without_live_apply_refuses(self, tmp_path):
        """Regression (r13 review): the same zero-live-applies rule
        per shard on the ClearRow/Store leg path — a shard whose only
        reachable owner died mid-apply has no live copy carrying the
        clear, so the op must refuse, not ack on the other legs."""
        import time

        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=1,
                         heartbeat=0.1) as c:
            coord, peer = c.servers
            peer_id = peer.cluster.node_id
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH + 1 for s in range(6)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 6,
                                    columnIDs=cols)
            owners = {s: coord.cluster.shard_owners("i", s)
                      for s in range(6)}
            assert any(o == [peer_id] for o in owners.values()), \
                "peer owns no shard — test invalid"
            client = coord.cluster._client(peer_id)
            real = client._do

            def die(method, path, body=None, **kw):
                if (path.startswith("/internal/query")
                        or path.startswith("/internal/hints/replay")):
                    raise ClientError("connection reset", status=0,
                                      kind="unreachable")
                return real(method, path, body, **kw)

            client._do = die
            try:
                with pytest.raises(ClientError) as ei:
                    c.client(0).query("i", "ClearRow(f=1)")
                assert ei.value.status == 503
                assert "no live replica" in str(ei.value)
                assert coord.cluster.hints.has_pending(peer_id)
            finally:
                client._do = real
            deadline = time.monotonic() + 10
            while (coord.cluster.hints.has_pending(peer_id)
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert not coord.cluster.hints.has_pending(peer_id)
            # the un-acked ClearRow converged everywhere: the shards
            # the coordinator cleared before refusing AND the hinted
            # peer's replayed shards
            for cl in c.clients:
                (row,) = cl.query("i", "Row(f=1)")
                assert row["columns"] == []

    def test_clearrow_applies_on_every_replica(self, tmp_path):
        with run_cluster(3, str(tmp_path), replicas=2) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).import_bits("i", "f", rowIDs=[1, 1],
                                    columnIDs=[3, 9])
            assert c.client(0).query("i", "ClearRow(f=1)") == [True]
            # no replica retains the row (previously only one owner
            # applied it and AAE would have resurrected the bits)
            for s in c.servers:
                idx = s.holder.index("i")
                f = idx.field("f") if idx else None
                v = f.standard_view() if f else None
                frag = v.fragment(0) if v else None
                if frag is not None:
                    assert not frag.row(1).contains(3)
                    assert not frag.row(1).contains(9)


class TestExtractLimitCluster:
    def test_extract_distributed(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        c.client(0).create_field("i", "v", {"type": "int", "min": -100,
                                            "max": 100})
        far = 4 * SHARD_WIDTH + 2
        c.client(0).import_bits("i", "f", rowIDs=[10, 20, 10],
                                columnIDs=[1, 1, far])
        c.client(0).import_values("i", "v", columnIDs=[1, far],
                                  values=[-7, 33])
        for cl in c.clients:
            (r,) = cl.query(
                "i", f"Extract(ConstRow(columns=[1, {far}, 99]),"
                     "Rows(f), Rows(v))")
            assert r["fields"] == [{"name": "f", "type": "set"},
                                   {"name": "v", "type": "int"}]
            assert r["columns"] == [
                {"column": 1, "rows": [[10, 20], -7]},
                {"column": 99, "rows": [[], None]},  # selected, no values
                {"column": far, "rows": [[10], 33]},
            ]

    def test_extract_keyed_distributed(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        c.client(0).create_field("k", "m")  # unkeyed alongside
        c.client(0).query("k", 'Set("alice", f="admin") '
                               'Set("alice", f="dev") '
                               'Set("bob", m=3)')
        for cl in c.clients[:2]:
            (r,) = cl.query(
                "k", 'Extract(Union(Row(f="admin"), Row(m=3)),'
                     'Rows(f), Rows(m))')
            by_key = {c_["key"]: c_["rows"] for c_ in r["columns"]}
            assert by_key["alice"] == [["admin", "dev"], []]
            assert by_key["bob"] == [[], [3]]

    def test_top_level_limit_distributed(self, three_nodes):
        # limit/offset stripped from fan-out, applied on the merged
        # ascending column list — exact across node boundaries
        c = three_nodes
        oracle = spread_bits(c.client(0))
        all_cols = sorted(set().union(*oracle.values()))
        (r,) = c.client(1).query("i", "Limit(All(), limit=7, offset=3)")
        assert r["columns"] == all_cols[3:10]

    def test_nested_limit_resolved_exactly(self, three_nodes):
        # nested Limits resolve as their own exact distributed reads
        # (ConstRow substitution, generalizing the Extract rewrite):
        # global column order must hold across node boundaries
        c = three_nodes
        oracle = spread_bits(c.client(0))
        all_cols = sorted(set().union(*oracle.values()))
        want = all_cols[:7]
        for cl in (c.client(0), c.client(1)):
            assert cl.query("i", "Count(Limit(All(), limit=7))") == \
                [len(want)]
            (r,) = cl.query(
                "i", "Intersect(Limit(All(), limit=7), All())")
            assert r["columns"] == want
        # Options(shards=) scopes nested-Limit resolution too: the
        # inner read must page over the restricted shard set only
        shard1 = sorted(c for c in all_cols
                        if SHARD_WIDTH <= c < 2 * SHARD_WIDTH)[:2]
        (r,) = c.client(0).query(
            "i", "Options(Intersect(Limit(All(), limit=2), All()),"
                 "shards=[1])")
        assert r["columns"] == shard1
        # doubly nested: inner Limit resolves before the outer one
        (r,) = c.client(2).query(
            "i", "Limit(Intersect(Limit(All(), limit=7), All()), limit=3)")
        assert r["columns"] == want[:3]

    def test_extract_limit_filter_distributed(self, three_nodes):
        # Extract(Limit(...)) rewrites to a resolved ConstRow fan-out:
        # exact global paging, then per-node extraction
        c = three_nodes
        oracle = spread_bits(c.client(0))
        all_cols = sorted(set().union(*oracle.values()))
        (r,) = c.client(1).query(
            "i", "Extract(Limit(All(), limit=3, offset=2), Rows(f))")
        got = [c_["column"] for c_ in r["columns"]]
        assert got == all_cols[2:5]


class TestResizeAbort:
    def test_abort_stops_at_copy_boundary_and_retrigger_converges(
            self, tmp_path):
        with run_cluster(2, str(tmp_path), replicas=2) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH for s in range(8)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 8,
                                    columnIDs=cols)
            coord = next(s for s in c.servers if s.cluster.is_coordinator())
            other = next(s for s in c.servers if s is not coord)
            # fabricate under-replication: drop several fragments from
            # the non-coordinator so a rebalance has >1 copy to make
            view = other.holder.index("i").field("f").standard_view()
            dropped = [sh for sh in list(view.fragments)[:4]]
            for sh in dropped:
                view.fragments[sh].clear_row(1)
            pushes = []
            orig = coord.cluster.push_fragment

            def aborting_push(*a, **kw):
                pushes.append(a)
                coord.cluster.abort_resize()  # abort after first copy
                return orig(*a, **kw)

            coord.cluster.push_fragment = aborting_push
            coord.cluster._resize_job()
            assert len(pushes) == 1  # stopped at the copy boundary
            assert coord.cluster.state == "NORMAL"
            # a fresh (unaborted) job completes the plan
            coord.cluster.push_fragment = orig
            coord.cluster._resize_job()
            for sh in dropped:
                assert view.fragments[sh].row(1).any()


class TestAntiEntropy:
    def test_repair_diverged_replica(self, tmp_path):
        with run_cluster(2, str(tmp_path), replicas=2) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).import_bits("i", "f", rowIDs=[1, 2],
                                    columnIDs=[10, 20])
            # fabricate divergence: drop a row on node 1's replica only
            frag_b = (c.servers[1].holder.index("i").field("f")
                      .standard_view().fragment(0))
            frag_b.clear_row(2)
            assert not frag_b.row(2).any()
            repaired = c.servers[0].cluster.sync_once()
            assert repaired > 0
            assert frag_b.row(2).contains(20)


class TestResize:
    def test_join_triggers_rebalance(self, tmp_path):
        with run_cluster(1, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH + 1 for s in range(8)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 8,
                                    columnIDs=cols)
            # join a second node
            from pilosa_tpu.cli.config import Config
            from pilosa_tpu.server import PilosaTPUServer
            cfg = Config(bind="127.0.0.1:0",
                         data_dir=str(tmp_path / "late"),
                         seeds=[c.servers[0].cluster.node_id],
                         cluster_enabled=True,
                         heartbeat_interval=0.2,
                         anti_entropy_interval=0.0,
                         mesh=False)
            late = PilosaTPUServer(cfg).open()
            try:
                c.servers.append(late)
                c.await_membership(2)
                # placement is VERSIONED (r5): the join changes
                # membership at once, but shard_owners only routes to
                # the late node after its resize completes and the new
                # topology activates — poll for that
                import time
                moved = []
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and not moved:
                    moved = [s for s in range(8)
                             if late.cluster.node_id
                             in late.cluster.shard_owners("i", s)]
                    if not moved:
                        time.sleep(0.05)
                assert moved, "placement should assign some shards to node 2"

                def migrated() -> bool:
                    idx = late.holder.index("i")
                    f = idx.field("f") if idx else None
                    v = f.standard_view() if f else None
                    if v is None:
                        return False
                    return all(
                        v.fragment(s) is not None and v.fragment(s).row(1).any()
                        for s in moved)

                import time
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline and not migrated():
                    time.sleep(0.05)
                assert migrated(), f"shards {moved} not migrated"
                # queries correct from both nodes
                from pilosa_tpu.api.client import Client
                cl = Client("127.0.0.1", late.http.address[1])
                assert cl.query("i", "Count(Row(f=1))") == [8]
                assert c.client(0).query("i", "Count(Row(f=1))") == [8]
            finally:
                if late in c.servers:
                    c.servers.remove(late)
                late.close()


class TestClusterReviewRegressions:
    def test_keyed_import_routed(self, three_nodes):
        """Regression: forwarded keyed batches carry pre-translated IDs
        and must bypass the keyed-input guard."""
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        changed = c.client(1).import_bits(
            "k", "f", rowKeys=["admin", "user"],
            columnKeys=["alice", "bob"])
        assert changed == 2
        for cl in c.clients:
            (r,) = cl.query("k", 'Row(f="admin")')
            assert r["keys"] == ["alice"]

    def test_unknown_key_does_not_veto_siblings(self, three_nodes):
        """Regression: a missing key is an empty row, not a query veto —
        cluster must match single-node semantics."""
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        c.client(0).query("k", 'Set("alice", f="admin")')
        (d,) = c.client(1).query(
            "k", 'Difference(Row(f="admin"), Row(f="nosuch"))')
        assert d["keys"] == ["alice"]
        (n,) = c.client(2).query("k", 'Not(Row(f="nosuch"))')
        assert n["keys"] == ["alice"]

    def test_clear_does_not_create_keys(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        assert c.client(0).query("k", 'Clear("ghost", f="nothing")') == [False]
        log = c.servers[0].executor.translate.columns("k")
        assert log.translate(["ghost"], create=False) == [None]


class TestNodeRemoval:
    def test_remove_rebalances_and_tombstones(self, tmp_path):
        with run_cluster(3, str(tmp_path), replicas=2, heartbeat=0.1) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH for s in range(6)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 6, columnIDs=cols)

            coord_id = c.servers[0].cluster.coordinator_id()
            coord = c.server_for(coord_id)
            victim = next(s for s in c.servers
                          if s.cluster.node_id != coord_id)
            victim_id = victim.cluster.node_id
            victim.close()

            from pilosa_tpu.api.client import Client
            host, port = coord_id.rsplit(":", 1)
            cl = Client(host, int(port))
            cl._json("DELETE", f"/cluster/node/{victim_id}")

            import time
            deadline = time.monotonic() + 10
            survivors = [s for s in c.servers if s is not victim]
            while time.monotonic() < deadline:
                if all(victim_id not in s.cluster.nodes for s in survivors) \
                        and all(s.cluster.state == "NORMAL"
                                for s in survivors):
                    break
                time.sleep(0.05)
            for s in survivors:
                assert victim_id not in s.cluster.nodes
            # replication factor restored: every shard has 2 live holders
            deadline = time.monotonic() + 10
            def fully_replicated():
                for shard in range(6):
                    holders = 0
                    for s in survivors:
                        idx = s.holder.index("i")
                        f = idx.field("f") if idx else None
                        v = f.standard_view() if f else None
                        frag = v.fragment(shard) if v else None
                        if frag is not None and frag.row(1).any():
                            holders += 1
                    if holders < 2:
                        return False
                return True
            while time.monotonic() < deadline and not fully_replicated():
                time.sleep(0.05)
            assert fully_replicated()
            (cnt,) = cl.query("i", "Count(Row(f=1))")
            assert cnt == 6

    def test_non_coordinator_remove_is_409(self, three_nodes):
        c = three_nodes
        coord = c.servers[0].cluster.coordinator_id()
        non = next(s for s in c.servers if s.cluster.node_id != coord)
        from pilosa_tpu.api.client import Client, ClientError
        host, port = non.cluster.node_id.rsplit(":", 1)
        cl = Client(host, int(port))
        other = next(i for i in c.node_ids()
                     if i not in (coord, non.cluster.node_id))
        with pytest.raises(ClientError) as e:
            cl._json("DELETE", f"/cluster/node/{other}")
        assert e.value.status == 409


class TestParityBatchCluster:
    def test_shift_and_unionrows_merge(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        far = 4 * SHARD_WIDTH
        c.client(0).import_bits("i", "f", rowIDs=[1, 2],
                                columnIDs=[5, far + 7])
        (r,) = c.client(1).query("i", "Shift(Row(f=1), n=1)")
        assert r["columns"] == [6]
        (u,) = c.client(2).query("i", "UnionRows(Rows(f))")
        assert u["columns"] == [5, far + 7]

    def test_all_paging_merged(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        cols = [1, 2, SHARD_WIDTH + 1, SHARD_WIDTH + 2, 3 * SHARD_WIDTH + 5]
        c.client(0).import_bits("i", "f", rowIDs=[1] * 5, columnIDs=cols)
        (r,) = c.client(1).query("i", "All(limit=3)")
        assert r["columns"] == sorted(cols)[:3]
        (r2,) = c.client(2).query("i", "All(limit=2, offset=2)")
        assert r2["columns"] == sorted(cols)[2:4]

    def test_shift_bad_n_is_400(self, three_nodes):
        from pilosa_tpu.api.client import ClientError
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        with pytest.raises(ClientError) as e:
            c.client(0).query("i", "Shift(Row(f=1), n=-1)")
        assert e.value.status == 400


class TestRejoinAfterRemoval:
    def test_removed_node_can_rejoin(self, tmp_path):
        with run_cluster(3, str(tmp_path), heartbeat=0.1) as c:
            coord_id = c.servers[0].cluster.coordinator_id()
            coord = c.server_for(coord_id)
            victim = next(s for s in c.servers
                          if s.cluster.node_id != coord_id)
            victim_id = victim.cluster.node_id
            victim_dir = victim.cfg.data_dir
            victim.close()
            coord.cluster.remove_node(victim_id)
            import time
            survivors = [s for s in c.servers if s is not victim]
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if all(victim_id not in s.cluster.nodes for s in survivors):
                    break
                time.sleep(0.05)
            # rejoin: a fresh server at a new port, seeded via NON-coord
            # peer (exercises tombstone-clear propagation)
            from pilosa_tpu.cli.config import Config
            from pilosa_tpu.server import PilosaTPUServer
            non_coord = next(s for s in survivors
                             if s.cluster.node_id != coord_id)
            cfg = Config(bind="127.0.0.1:0", data_dir=victim_dir + "b",
                         seeds=[non_coord.cluster.node_id],
                         cluster_enabled=True, heartbeat_interval=0.1,
                         anti_entropy_interval=0.0, mesh=False)
            back = PilosaTPUServer(cfg).open()
            try:
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if all(back.cluster.node_id in s.cluster.nodes
                           for s in survivors) \
                            and len(back.cluster.alive_ids()) == 3:
                        break
                    time.sleep(0.05)
                for s in survivors:
                    assert back.cluster.node_id in s.cluster.nodes
                    assert back.cluster.node_id not in s.cluster._removed
                # must stay in (heartbeats not bounced)
                time.sleep(0.5)
                assert len(back.cluster.nodes) == 3
            finally:
                back.close()


class TestDistinctCluster:
    def test_distinct_merged(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "amount",
                                 {"type": "int", "min": -100, "max": 100})
        cols = [1, SHARD_WIDTH + 1, 3 * SHARD_WIDTH + 1, 5 * SHARD_WIDTH]
        c.client(0).import_values("i", "amount", columnIDs=cols,
                                  values=[5, -3, 5, 42])
        for cl in c.clients:
            (d,) = cl.query("i", "Distinct(field=amount)")
            assert d == {"values": [-3, 5, 42]}


class TestClusterWithDeviceMesh:
    """Cluster fan-out AND per-node device-mesh sharding together: each
    node's executor shards its resident planes over the 8 simulated
    devices while queries also fan out across nodes."""

    def test_meshed_nodes_agree(self, tmp_path):
        with run_cluster(2, str(tmp_path), mesh=True) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).create_field("i", "amount",
                                     {"type": "int", "min": -100, "max": 100})
            cols = [s * SHARD_WIDTH + 3 for s in range(5)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 5, columnIDs=cols)
            c.client(1).import_values("i", "amount", columnIDs=cols[:3],
                                      values=[10, -20, 30])
            c.client(0).import_bits("i", "f", rowIDs=[2, 2],
                                    columnIDs=cols[:2])
            for cl in c.clients:
                assert cl.query("i", "Count(Row(f=1))") == [5]
                (r,) = cl.query("i", "Row(f=1)")
                assert r["columns"] == cols
                (s,) = cl.query("i", "Sum(field=amount)")
                assert s == {"value": 20, "count": 3}
                (t,) = cl.query("i", "TopN(f)")
                assert t == [{"id": 1, "count": 5}, {"id": 2, "count": 2}]
                # round-3 surfaces under cluster x mesh composition:
                # having= thresholds global counts; nested Limit
                # resolves exactly; BSI Extract reads off the plane
                (g,) = cl.query(
                    "i", "GroupBy(Rows(f), having=Condition(count > 2))")
                assert [(x["group"][0]["rowID"], x["count"])
                        for x in g] == [(1, 5)]
                assert cl.query(
                    "i", "Count(Limit(Row(f=1), limit=3))") == [3]
                (e,) = cl.query(
                    "i", f"Extract(ConstRow(columns=[{cols[0]},"
                         f"{cols[1]}]), Rows(amount))")
                by_col = {x["column"]: x["rows"][0]
                          for x in e["columns"]}
                assert by_col == {cols[0]: 10, cols[1]: -20}


class TestAttrValueNotTranslated:
    def test_attr_value_matching_keyed_field_name(self, tmp_path):
        """Regression: an attr VALUE that collides with a keyed field's
        name must be stored verbatim, not key-translated."""
        with run_cluster(2, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).create_field("i", "city", {"keys": True})
            c.client(1).query("i", 'SetRowAttrs(f, 1, city="NYC")')
            for s in c.servers:
                assert s.holder.index("i").field("f").row_attrs.attrs(1) \
                    == {"city": "NYC"}
            # and no bogus key was created in the city field's log
            log = c.servers[0].executor.translate.rows("i", "city")
            assert log.translate(["NYC"], create=False) == [None]


class TestPercentileCluster:
    def test_distributed_percentile(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "amount",
                                 {"type": "int", "min": 0, "max": 1000})
        # values spread across shards on different nodes
        cols = [s * SHARD_WIDTH + k for s in range(6) for k in range(10)]
        vals = list(range(1, 61))
        c.client(0).import_values("i", "amount", columnIDs=cols, values=vals)
        for cl in c.clients[:2]:
            (p,) = cl.query("i", "Percentile(field=amount, nth=50)")
            assert p == {"value": 30, "count": 1}
            (p99,) = cl.query("i", "Percentile(field=amount, nth=100)")
            assert p99 == {"value": 60, "count": 1}

    def test_distributed_percentile_keyed_filter(self, three_nodes):
        # the k-ary fan-out skips the per-call translate step, so the
        # percentile entry point must key-translate its filter once
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "grp", {"keys": True})
        c.client(0).create_field("k", "v", {"type": "int", "min": 0,
                                            "max": 100})
        for name, val, in_grp in [("a", 10, True), ("b", 20, True),
                                  ("c", 30, False), ("d", 40, True)]:
            c.client(0).query("k", f'Set("{name}", v={val})')
            if in_grp:
                c.client(0).query("k", f'Set("{name}", grp="one")')
        (p,) = c.client(1).query(
            "k", 'Percentile(Row(grp="one"), field=v, nth=50)')
        assert p == {"value": 20, "count": 1}


class TestCoordinatorFailover:
    def test_key_assignment_moves_to_new_coordinator(self, tmp_path):
        """Kill the coordinator: key creation must reroute to the next
        alive node (coordinator is computed over alive ids) and reads
        stay consistent."""
        with run_cluster(3, str(tmp_path), heartbeat=0.1) as c:
            c.client(0).create_index("k", {"keys": True})
            c.client(0).create_field("k", "f", {"keys": True})
            c.client(0).query("k", 'Set("alice", f="admin")')

            coord_id = c.servers[0].cluster.coordinator_id()
            coord = c.server_for(coord_id)
            survivors = [s for s in c.servers if s is not coord]
            coord.close()
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if all(len(s.cluster.alive_ids()) == 2 for s in survivors):
                    break
                time.sleep(0.05)
            new_coord = survivors[0].cluster.coordinator_id()
            assert new_coord != coord_id

            from pilosa_tpu.api.client import Client
            host, port = survivors[1].cluster.node_id.rsplit(":", 1)
            cl = Client(host, int(port))
            # new key creation routes to the NEW coordinator
            assert cl.query("k", 'Set("bob", f="admin")') == [True]
            (r,) = cl.query("k", 'Row(f="admin")')
            assert sorted(r["keys"]) == ["alice", "bob"]


class TestIncludesColumnCluster:
    def test_includes_column_merged(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        far = 4 * SHARD_WIDTH + 9
        c.client(0).query("i", f"Set({far}, f=1)")
        assert c.client(1).query(
            "i", f"IncludesColumn(Row(f=1), column={far})") == [True]
        assert c.client(2).query(
            "i", "IncludesColumn(Row(f=1), column=5)") == [False]


class TestFiveNodeCluster:
    def test_replicas3_failover_and_aae(self, tmp_path):
        with run_cluster(5, str(tmp_path), replicas=3, heartbeat=0.1) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH + 1 for s in range(10)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 10,
                                    columnIDs=cols)
            # every shard on 3 nodes
            for s in range(10):
                assert len(c.servers[0].cluster.shard_owners("i", s)) == 3
            # kill two non-coordinator nodes: still answerable
            coord = c.servers[0].cluster.coordinator_id()
            victims = [s for s in c.servers
                       if s.cluster.node_id != coord][:2]
            for v in victims:
                v.close()
            survivors = [s for s in c.servers if s not in victims]
            import time
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if all(len(s.cluster.alive_ids()) == 3 for s in survivors):
                    break
                time.sleep(0.05)
            from pilosa_tpu.api.client import Client
            host, port = survivors[-1].cluster.node_id.rsplit(":", 1)
            cl = Client(host, int(port))
            assert cl.query("i", "Count(Row(f=1))") == [10]


class TestSchemaDeletionBroadcast:
    def test_delete_field_and_index_propagate(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        c.client(0).create_field("i", "g")
        c.client(1).delete_field("i", "f")
        for s in c.servers:
            assert s.holder.index("i").field("f") is None
            assert s.holder.index("i").field("g") is not None
        c.client(2).delete_index("i")
        for s in c.servers:
            assert s.holder.index("i") is None


class TestImportRoaringCluster:
    def test_import_roaring_routed(self, three_nodes):
        from pilosa_tpu.store import roaring
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        shard = 4
        positions = np.array([3, 9], np.uint64)  # row 0, cols 3 and 9
        blob = roaring.serialize(positions)
        assert c.client(1).import_roaring("i", "f", shard, blob) == 2
        for cl in c.clients:
            (r,) = cl.query("i", "Row(f=0)")
            assert r["columns"] == [shard * SHARD_WIDTH + 3,
                                    shard * SHARD_WIDTH + 9]


class TestDeletionTombstones:
    def test_stale_peer_cannot_resurrect(self, three_nodes):
        """A full-schema push carrying a deleted index must not
        resurrect it; a genuine recreate (newer created_at) must."""
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        stale_schema = c.servers[0].api.schema()
        c.client(1).delete_index("i")
        for s in c.servers:
            assert s.holder.index("i") is None
        # stale push (as a lagging peer would send)
        c.servers[2].cluster._broadcast(
            "/internal/schema", {"schema": stale_schema}, "schema")
        import time
        time.sleep(0.3)
        for s in c.servers:
            assert s.holder.index("i") is None, "resurrected from stale push"
        # genuine recreate passes (newer created_at beats the tombstone)
        time.sleep(0.05)
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        for s in c.servers:
            assert s.holder.index("i") is not None

    def test_recreated_keyed_field_starts_fresh(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("k", {"keys": True})
        c.client(0).create_field("k", "f", {"keys": True})
        c.client(0).query("k", 'Set("alice", f="admin")')
        c.client(0).delete_field("k", "f")
        import time
        time.sleep(0.05)
        c.client(0).create_field("k", "f", {"keys": True})
        (r,) = c.client(0).query("k", 'Row(f="admin")')
        assert r == {"keys": []}  # no inherited rows or key state
        log = c.servers[0].executor.translate.rows("k", "f")
        assert log.translate(["admin"], create=False) == [None]


class TestOptionsShardsCluster:
    def test_options_shards_respected(self, three_nodes):
        c = three_nodes
        c.client(0).create_index("i")
        c.client(0).create_field("i", "f")
        c.client(0).import_bits("i", "f", rowIDs=[1, 1],
                                columnIDs=[5, 3 * SHARD_WIDTH + 5])
        assert c.client(1).query("i", "Count(Row(f=1))") == [2]
        (n,) = c.client(1).query(
            "i", "Options(Count(Row(f=1)), shards=[0])")
        assert n == 1

    def test_options_shards_with_replicas_not_double_counted(self, tmp_path):
        """Regression: the shards list must not be forwarded to nodes —
        each would re-apply the full list over its replicas and additive
        merges would over-count."""
        with run_cluster(3, str(tmp_path), replicas=2) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            cols = [s * SHARD_WIDTH for s in range(4)]
            c.client(0).import_bits("i", "f", rowIDs=[1] * 4,
                                    columnIDs=cols)
            (n,) = c.client(1).query(
                "i", "Options(Count(Row(f=1)), shards=[0, 1, 2, 3])")
            assert n == 4
            (n2,) = c.client(2).query(
                "i", "Options(Count(Row(f=1)), shards=[0, 2])")
            assert n2 == 2


class TestClusterSingleNodeEquivalence:
    """The strongest cluster invariant: ANY operation sequence must give
    identical query results on a 3-node cluster and a single-node
    holder (generated sequences, every query class checked)."""

    def test_random_ops_equivalent(self, tmp_path):
        from pilosa_tpu.api import API
        from pilosa_tpu.exec import Executor, result_to_json
        from pilosa_tpu.store import Holder

        rng = np.random.default_rng(123)
        solo_holder = Holder(str(tmp_path / "solo")).open()
        solo = API(solo_holder, Executor(solo_holder))

        with run_cluster(3, str(tmp_path / "cluster")) as c:
            # identical schema on both
            solo.create_index("i")
            solo.create_field("i", "f")
            solo.create_field("i", "amount",
                              {"type": "int", "min": -100, "max": 100})
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            c.client(0).create_field("i", "amount",
                                     {"type": "int", "min": -100,
                                      "max": 100})
            # random op sequence applied to BOTH, spread over 5 shards
            ops = []
            for _ in range(120):
                kind = rng.integers(0, 4)
                col = int(rng.integers(0, 5)) * SHARD_WIDTH \
                    + int(rng.integers(0, 50))
                if kind == 0:
                    ops.append(f"Set({col}, f={int(rng.integers(1, 6))})")
                elif kind == 1:
                    ops.append(f"Clear({col}, f={int(rng.integers(1, 6))})")
                elif kind == 2:
                    ops.append(
                        f"Set({col}, amount={int(rng.integers(-100, 101))})")
                else:
                    ops.append(f"Set({col}, f={int(rng.integers(1, 6))}, "
                               f"2019-0{int(rng.integers(1, 10))}-01T00:00)")
            pql_ops = " ".join(ops)
            solo.query("i", pql_ops)
            # spread writes across different cluster nodes
            third = len(ops) // 3
            c.client(0).query("i", " ".join(ops[:third]))
            c.client(1).query("i", " ".join(ops[third:2 * third]))
            c.client(2).query("i", " ".join(ops[2 * third:]))

            queries = [
                "Count(All())",
                "Count(Row(f=1))", "Count(Row(f=5))",
                "Row(f=2)", "Intersect(Row(f=1), Row(f=2))",
                "Union(Row(f=1), Row(f=3), Row(f=5))",
                "Xor(Row(f=2), Row(f=4))", "Not(Row(f=1))",
                "TopN(f)", "Rows(f)",
                "Sum(field=amount)", "Min(field=amount)",
                "Max(field=amount)", "Count(Row(amount > 0))",
                "Count(Row(-50 <= amount <= 50))",
                "Distinct(field=amount)",
                "Percentile(field=amount, nth=50)",
                "GroupBy(Rows(f))",
                # round-2 surface
                "TopN(f, filter=Row(f=1), tanimoto=10)",
                "GroupBy(Rows(f), aggregate=Min(field=amount))",
                "GroupBy(Rows(f), aggregate=Max(field=amount))",
                "GroupBy(Rows(f), aggregate=Count())",
                f"ConstRow(columns=[3, {SHARD_WIDTH + 7}, 99])",
                "Limit(Row(f=1), limit=5, offset=2)",
                "Extract(Limit(All(), limit=6), Rows(f), Rows(amount))",
            ]
            for pql in queries:
                (a,) = solo.query("i", pql)["results"]
                for cl in c.clients:
                    (b,) = cl.query("i", pql)
                    assert a == b, f"{pql}: solo={a} cluster={b}"


class TestInternodeRpcLatency:
    def test_no_delayed_ack_stall(self, tmp_path):
        """Regression: keep-alive internode sockets without TCP_NODELAY
        hit the classic Nagle + delayed-ACK interaction — a
        deterministic ~40 ms stall on EVERY persistent-connection RPC
        (found in r5; the whole suite passed with it).
        0.5 ms is typical on loopback; 20 ms leaves slack for a loaded
        host while still catching the 40 ms stall class."""
        import time

        import numpy as np

        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path), replicas=2) as tc:
            c = tc.client(0)
            c.create_index("i")
            c.create_field("i", "f")
            c.import_bits("i", "f", rowIDs=[0] * 10,
                          columnIDs=list(range(10)))
            cl = tc.servers[0].cluster
            peer = next(n for n in cl.alive_ids() if n != cl.node_id)
            cl.internal_query(peer, "i", "Count(Row(f=0))", [0])  # warm
            lat = []
            for _ in range(20):
                t0 = time.perf_counter()
                (n,) = cl.internal_query(peer, "i", "Count(Row(f=0))", [0])
                lat.append(time.perf_counter() - t0)
                assert n == 10
            # min, not median: host-load spikes only ADD latency, while
            # the Nagle stall is deterministic on EVERY rpc — the
            # fastest of 20 stays honest on a contended CI box
            assert min(lat) < 0.020, \
                f"internode RPC min {min(lat) * 1e3:.1f} ms"


class TestBatchedReadFanout:
    """The r5 batched read fan-out (dist._read_group): consecutive
    plain reads of MIXED call families ship as one multi-call query per
    node — per-call partial indexing, strip/merge, and write barriers
    must all survive the batching."""

    def test_heterogeneous_batch_matches_single_node(self, tmp_path):
        from pilosa_tpu.api import API
        from pilosa_tpu.exec import Executor
        from pilosa_tpu.store import Holder

        rng = np.random.default_rng(55)
        solo_holder = Holder(str(tmp_path / "solo")).open()
        solo = API(solo_holder, Executor(solo_holder))

        with run_cluster(3, str(tmp_path / "cluster")) as c:
            for api_like in (solo, None):
                mk = (solo if api_like is solo else c.client(0))
                mk.create_index("i")
                mk.create_field("i", "f")
                mk.create_field("i", "amount",
                                {"type": "int", "min": -100, "max": 100})
            rows = rng.integers(1, 8, 400).astype(np.uint64)
            cols = (rng.integers(0, 5, 400) * SHARD_WIDTH
                    + rng.integers(0, 64, 400)).astype(np.uint64)
            vals = rng.integers(-100, 100, 60)
            vcols = (rng.integers(0, 5, 60) * SHARD_WIDTH
                     + rng.integers(0, 64, 60)).astype(np.uint64)
            solo.import_bits("i", "f", row_ids=rows, col_ids=cols)
            solo.import_values("i", "amount", col_ids=vcols,
                               values=np.asarray(vals))
            c.client(0).import_bits("i", "f", rowIDs=rows.tolist(),
                                    columnIDs=cols.tolist())
            c.client(0)._json("POST", "/index/i/field/amount/importValue",
                              {"columnIDs": vcols.tolist(),
                               "values": vals.tolist()})

            # one query string per node: mixed read families, a write
            # in the middle (splits the batch, must keep relative
            # order), and a repeat read proving the write landed — the
            # written column differs per node so reruns stay comparable
            def pql(wcol: int) -> str:
                return ("Count(Row(f=1))"
                        "TopN(f, n=3)"
                        "Rows(f)"
                        "Sum(field=amount)"
                        "Count(Union(Row(f=1), Row(f=2)))"
                        "Min(field=amount)"
                        f"Set({wcol}, f=1)"
                        "Count(Row(f=1))"
                        "GroupBy(Rows(f, limit=3))")

            base = 3 * SHARD_WIDTH + 100_000
            for ci in range(3):
                q = pql(base + ci)
                want = solo.query("i", q)["results"]
                got = c.clients[ci].query("i", q)
                assert got == want, (
                    f"node {ci} diverged: {str(got)[:120]} != "
                    f"{str(want)[:120]}")


class TestAaeRepairsMissingFragment:
    def test_deleted_replica_fragment_restreams(self, tmp_path):
        """A replica that LOST a whole fragment (disk wipe, partial
        restore) must get it back from AAE: the peer's 404 means
        maximal divergence, not 'peer down' (config17 r5 — the
        swallowed 404 left deleted replicas unrepaired forever)."""
        import os

        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path), replicas=2) as tc:
            c = tc.client(0)
            c.create_index("i")
            c.create_field("i", "f")
            c.import_bits("i", "f", rowIDs=[1] * 50,
                          columnIDs=list(range(50)))
            # drop shard 0 entirely on node 1
            holder1 = tc.servers[1].api.holder
            view1 = holder1.index("i").field("f").views["standard"]
            frag = view1.fragments.pop(0, None)
            path = frag.path
            frag.close()
            for suffix in ("", ".oplog"):
                try:
                    os.remove(path + suffix)
                except OSError:
                    pass
            repaired = tc.servers[0].cluster.sync_once()
            assert repaired > 0
            restored = view1.fragment(0)
            assert restored is not None
            assert restored.row(1).cardinality == 50


class TestPlacementHeartbeat:
    """ADVICE r5: activated placement used to propagate only via one
    best-effort broadcast — a node that missed it routed by stale
    topology forever.  The placement version now rides every heartbeat
    both ways and the trailing side pulls."""

    def test_stale_node_pulls_on_heartbeat_response(self, tmp_path):
        import time as _time
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path)) as c:
            coord = c.server_for(
                c.servers[0].cluster.coordinator_id()).cluster
            other = next(s.cluster for s in c.servers
                         if s.cluster is not coord)
            # simulate a missed resize-completion broadcast: the
            # coordinator activates a new placement version silently
            with coord._lock:
                coord.placement_version = max(
                    _time.time(), coord.placement_version + 1.0)
                coord._save_placement()
            assert other.placement_version < coord.placement_version
            # one heartbeat round from the stale node: the response
            # carries the newer version and the stale side pulls
            other._heartbeat_once()
            assert other.placement_version == coord.placement_version
            assert other.placement_ids == coord.placement_ids

    def test_stale_node_pulls_when_heartbeated_at(self, tmp_path):
        import time as _time
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path)) as c:
            coord = c.server_for(
                c.servers[0].cluster.coordinator_id()).cluster
            other = next(s.cluster for s in c.servers
                         if s.cluster is not coord)
            with coord._lock:
                coord.placement_version = max(
                    _time.time(), coord.placement_version + 1.0)
            # the NEWER node heartbeats the stale one: the handler sees
            # the sender is ahead and pulls asynchronously
            coord._heartbeat_once()
            deadline = _time.monotonic() + 5.0
            while _time.monotonic() < deadline:
                if other.placement_version == coord.placement_version:
                    break
                _time.sleep(0.05)
            assert other.placement_version == coord.placement_version


class TestOrphanHandoff:
    """ADVICE r5 `_handoff_orphan` fixes: bits written between the
    push snapshot and the delete are re-pushed, not lost; empty
    orphans are deleted instead of re-scanned every round."""

    def _orphan_shard(self, cluster, index="i"):
        """A shard owned exclusively by the OTHER node (replicas=1)."""
        for s in range(64):
            owners = cluster.shard_owners(index, s)
            if cluster.node_id not in owners:
                return s, owners
        raise AssertionError("no foreign-owned shard in 0..63")

    def test_mutation_during_push_is_repushed_not_lost(self, tmp_path):
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            b = c.servers[1]
            shard, owners = self._orphan_shard(b.cluster)
            owner_srv = c.server_for(owners[0])
            base = shard * SHARD_WIDTH
            fld = b.api.holder.index("i").field("f")
            fld.set_bit(1, base + 5)  # orphan bit on the wrong node

            real_push = b.cluster.push_fragment
            raced = []

            def racy(index, field, view, shard_, dest):
                real_push(index, field, view, shard_, dest)
                if not raced:
                    raced.append(1)
                    # a Set routed here by a stale peer AFTER the push
                    # snapshot, BEFORE the delete (the lost-write race)
                    fld.set_bit(2, base + 7)

            b.cluster.push_fragment = racy
            b.cluster.sync_once()
            assert raced, "handoff never pushed"
            # the late bit reached the owner (re-push), nothing lost
            o_fld = owner_srv.api.holder.index("i").field("f")
            frag = o_fld.view("standard").fragment(shard)
            assert frag is not None
            assert list(frag.row(1).columns()) == [5]
            assert list(frag.row(2).columns()) == [7]
            # and the orphan is gone locally
            view = fld.view("standard")
            assert view is None or view.fragment(shard) is None

    def test_empty_orphan_is_deleted(self, tmp_path):
        import os
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path)) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            b = c.servers[1]
            shard, _ = self._orphan_shard(b.cluster)
            fld = b.api.holder.index("i").field("f")
            frag = fld.view("standard", create=True).fragment(shard,
                                                              create=True)
            path = frag.path
            b.cluster.sync_once()
            assert fld.view("standard").fragment(shard) is None, \
                "empty orphan must be dropped, not re-scanned forever"
            assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# serving through failure (r11): replica-failover reads, hedged fan-out,
# per-peer circuit breakers
# ---------------------------------------------------------------------------


class TestReadFailover:
    """A fan-out read leg that dies with a transport-class error must
    re-group its shards onto the next live replicas and still answer
    exactly — one dead or slow node must not fail every query that
    touches its shards."""

    def test_failed_leg_retries_on_replica(self, tmp_path):
        from pilosa_tpu import fault

        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=0.2) as c:
            oracle = spread_bits(c.client(0))
            entry = c.servers[0]
            # a peer the entry node actually routes legs to (placement
            # is hash-driven; a fixed pick could own no queried shard)
            groups = entry.cluster.group_shards_by_node(
                "i", tuple(range(6)))
            victim_id = next(n for n in groups
                             if n != entry.cluster.node_id)
            try:
                # every leg the entry node sends to the victim dies
                # (the dist.fanout failpoint models a leg lost
                # mid-flight); the victim process itself stays healthy
                fault.set_fault("dist.fanout", "error",
                                match={"peer": victim_id})
                for row, cols in oracle.items():
                    (got,) = c.client(0).query("i", f"Row(f={row})")
                    assert set(got["columns"]) == cols
                snap = entry.stats.snapshot()["counters"]
                total = sum(snap.get("read_failover_total", {}).values())
                assert total >= 1, "no leg ever failed over"
            finally:
                fault.clear()

    def test_failover_exhaustion_fails_loudly(self, tmp_path):
        """replicas=1: a dead leg has nowhere to go — the query fails
        with the unreachable error, never a silent partial answer."""
        from pilosa_tpu import fault
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=1) as c:
            spread_bits(c.client(0))
            peer = c.servers[1].cluster.node_id
            try:
                fault.set_fault("dist.fanout", "error",
                                match={"peer": peer})
                with pytest.raises(ClientError) as ei:
                    c.client(0).query("i", "Count(Row(f=1))")
                assert "unreachable" in str(ei.value)
            finally:
                fault.clear()

    def test_failover_lands_on_local_replica(self, tmp_path):
        """The next live replica may be the DISPATCHING node itself:
        the re-grouped shards execute locally, not through a loopback
        RPC."""
        from pilosa_tpu import fault

        with run_cluster(2, str(tmp_path), replicas=2) as c:
            oracle = spread_bits(c.client(0))
            # with replicas == nodes, every shard lives on both nodes:
            # the only failover target for a dead peer leg is local
            peer = c.servers[1].cluster.node_id
            try:
                fault.set_fault("dist.fanout", "error",
                                match={"peer": peer})
                for row, cols in oracle.items():
                    (got,) = c.client(0).query("i", f"Row(f={row})")
                    assert set(got["columns"]) == cols
            finally:
                fault.clear()

    def test_writes_hint_through_partition_and_drain_on_heal(
            self, tmp_path):
        """Reads fail over; writes now serve through the partition too
        (r13): ClearRow applies on the reachable owners, hints the
        severed one, and the hint drains once the partition heals —
        the cleared row stays cleared on EVERY node (no resurrection)."""
        import time

        from pilosa_tpu import fault

        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=0.2) as c:
            oracle = spread_bits(c.client(0))
            entry = c.servers[0]
            victim = next(s for s in c.servers
                          if s.cluster.node_id != entry.cluster.node_id)
            vid = victim.cluster.node_id
            try:
                # sever entry -> victim at the transport (both the
                # read legs and the write replication see it)
                fault.set_fault("client.send", "partition",
                                match={"peer": vid})
                # reads: exact through failover
                for row, cols in oracle.items():
                    (got,) = c.client(0).query("i", f"Row(f={row})")
                    assert set(got["columns"]) == cols
                # strict write: SERVES, hinting the severed replica
                assert c.client(0).query("i", "ClearRow(f=1)") == [True]
                wh = c.client(0).write_health()
                assert wh["hintBacklogOps"] >= 1
                assert vid in {p["id"] for p in wh["peers"]}
                (got,) = c.client(0).query("i", "Row(f=1)")
                assert got["columns"] == []
            finally:
                fault.clear()
            # heal: heartbeat-triggered drain replays the ClearRow on
            # the severed node; the row must be empty EVERYWHERE and
            # stay empty (AAE deferred while hints were pending)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if not c.client(0).write_health().get("hintBacklogOps"):
                    break
                time.sleep(0.1)
            else:
                raise AssertionError("hint backlog never drained")
            for cl in c.clients:
                (got,) = cl.query("i", "Row(f=1)")
                assert got["columns"] == []
            for srv in c.servers:
                srv.cluster.sync_once()
            for cl in c.clients:
                (got,) = cl.query("i", "Row(f=1)")
                assert got["columns"] == [], "AAE resurrected a clear"


class TestHedgedReads:
    def test_straggler_leg_hedges_to_replica(self, tmp_path):
        """A leg past hedge_after gets a duplicate on a live replica;
        the first answer wins, latency stays bounded by the hedge (not
        the straggler), and the winning subtree carries the hedged
        trace tag."""
        import time

        from pilosa_tpu import fault

        with run_cluster(3, str(tmp_path), replicas=2, heartbeat=0.2,
                         hedge_after=0.1) as c:
            oracle = spread_bits(c.client(0))
            entry = c.servers[0]
            # a shard NEITHER of whose owners is the entry node: the
            # primary leg is remote AND the hedge target is remote (a
            # self-targeted hedge is skipped by design)
            peer_shard = next(
                s for s in range(64)
                if entry.cluster.node_id
                not in entry.cluster.shard_owners("i", s))
            row = 1
            want = sum(1 for cc in oracle.get(row, ())
                       if cc // SHARD_WIDTH == peer_shard)
            try:
                # first leg for this index stalls 1.5 s (nth=1 fires
                # exactly once: the hedge leg sails through)
                fault.set_fault("dist.fanout", "delay", nth=1,
                                match={"index": "i"},
                                args={"seconds": 1.5})
                t0 = time.monotonic()
                resp = c.client(0)._do(
                    "POST",
                    f"/index/i/query?profile=true&shards={peer_shard}",
                    f"Count(Row(f={row}))".encode())
                elapsed = time.monotonic() - t0
            finally:
                fault.clear()
            assert resp["results"] == [want]
            assert elapsed < 1.2, \
                f"hedge did not bound the straggler: {elapsed:.2f}s"

            def walk(span):
                yield span
                for ch in span.get("children", []):
                    yield from walk(ch)

            spans = [s for root in resp["profile"] for s in walk(root)]
            assert any(s.get("tags", {}).get("hedged") for s in spans), \
                "winning subtree lost its hedged tag"
            snap = entry.stats.snapshot()["counters"]
            assert sum(snap.get("read_hedged_total", {}).values()) >= 1

    def test_hedging_off_by_default(self, tmp_path):
        """hedge_after=0 (the default): a slow leg is simply awaited —
        no duplicate legs, no hedge counter."""
        from pilosa_tpu import fault

        with run_cluster(2, str(tmp_path), replicas=2) as c:
            spread_bits(c.client(0))
            try:
                fault.set_fault("dist.fanout", "delay", nth=1,
                                match={"index": "i"},
                                args={"seconds": 0.3})
                assert c.client(0).query(
                    "i", "Count(Row(f=1))")  # exact, just slower
            finally:
                fault.clear()
            snap = c.servers[0].stats.snapshot()["counters"]
            assert not snap.get("read_hedged_total")


class TestPeerBreakers:
    def test_lifecycle_deterministic(self):
        """closed -(N consecutive transport failures)-> open
        -(heartbeat probe)-> half_open -> closed on success / straight
        back to open on failure; any answered request resets the
        streak."""
        from pilosa_tpu.cluster.breaker import BreakerBoard
        from pilosa_tpu.obs import Stats

        stats = Stats()
        b = BreakerBoard(threshold=3, stats=stats)
        p = "127.0.0.1:1"
        assert b.state(p) == "closed"
        b.record_failure(p)
        b.record_failure(p)
        # an answered request resets the consecutive count
        b.record_success(p)
        b.record_failure(p)
        b.record_failure(p)
        assert b.state(p) == "closed"
        b.record_failure(p)
        assert b.state(p) == "open"
        assert b.unhealthy_peers() == {p}
        # probe: half-open, then a failure re-opens immediately
        assert b.begin_probe(p) is True
        assert b.state(p) == "half_open"
        assert b.unhealthy_peers() == {p}  # still skipped for routing
        b.record_failure(p)
        assert b.state(p) == "open"
        # probe again, success closes
        assert b.begin_probe(p) is True
        b.record_success(p)
        assert b.state(p) == "closed"
        assert b.unhealthy_peers() == set()
        # exported: gauge tracks the state, transitions counted
        snap = stats.snapshot()
        assert snap["gauges"]["peer_breaker_state"][(("peer", p),)] == 0
        trans = snap["counters"]["breaker_transitions_total"]
        labels = {(dict(k)["from"], dict(k)["to"]): v
                  for k, v in trans.items()}
        assert labels[("closed", "open")] == 1
        assert labels[("open", "half_open")] == 2
        assert labels[("half_open", "open")] == 1
        assert labels[("half_open", "closed")] == 1

    def test_open_peer_skipped_at_routing(self, tmp_path):
        # heartbeat=5.0: the background probe must not close the
        # manually-opened breaker mid-assertion
        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            spread_bits(c.client(0))
            entry = c.servers[0]
            victim = c.servers[1].cluster.node_id
            for _ in range(entry.cluster.breakers.threshold):
                entry.cluster.breakers.record_failure(victim)
            assert entry.cluster.breakers.state(victim) == "open"
            groups = entry.cluster.group_shards_by_node(
                "i", tuple(range(6)))
            assert victim not in groups, \
                "open-breaker peer must be skipped while replicas exist"
            # and queries stay exact through the detour
            (n,) = c.client(0).query("i", "Count(Row(f=1))")
            assert n > 0

    def test_open_breaker_is_not_a_correctness_gate(self, tmp_path):
        """With no healthy replica left, the router falls back to the
        open peer rather than failing the query."""
        with run_cluster(2, str(tmp_path), replicas=1,
                         heartbeat=5.0) as c:
            spread_bits(c.client(0))
            entry = c.servers[0]
            peer = c.servers[1].cluster.node_id
            for _ in range(entry.cluster.breakers.threshold):
                entry.cluster.breakers.record_failure(peer)
            assert entry.cluster.breakers.state(peer) == "open"
            groups = entry.cluster.group_shards_by_node(
                "i", tuple(range(6)))
            assert peer in groups  # last resort: still routed
            (n,) = c.client(0).query("i", "Count(Row(f=1))")
            assert n > 0

    def test_heartbeat_probe_closes_breaker(self, tmp_path):
        """The half-open probe rides the heartbeat loop: one round
        against a healthy peer closes an open breaker."""
        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            entry = c.servers[0]
            peer = c.servers[1].cluster.node_id
            for _ in range(entry.cluster.breakers.threshold):
                entry.cluster.breakers.record_failure(peer)
            assert entry.cluster.breakers.state(peer) == "open"
            entry.cluster._heartbeat_once()
            assert entry.cluster.breakers.state(peer) == "closed"

    def test_answered_http_errors_never_open_the_breaker(self, tmp_path):
        """Only never-answered transport faults count toward opening —
        a peer whose heartbeat handler 500s is ALIVE (its query path
        may serve fine), and opening its breaker would wrongly refuse
        strict writes via _write_reachable."""
        from pilosa_tpu.api.client import ClientError

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            entry = c.servers[0]
            peer = c.servers[1].cluster.node_id
            client = entry.cluster._client(peer)
            real = client._json

            def http_500(method, path, obj=None, **kw):
                if path == "/internal/heartbeat":
                    raise ClientError("internal error", 500)
                return real(method, path, obj, **kw)

            client._json = http_500
            try:
                for _ in range(5):
                    entry.cluster._heartbeat_once()
            finally:
                client._json = real
            assert entry.cluster.breakers.state(peer) == "closed"

    def test_status_cluster_health_block(self, tmp_path):
        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            st = c.client(0).status()
            health = st["clusterHealth"]
            assert health["suspectAfterSeconds"] == pytest.approx(15.0)
            (peer,) = health["peers"]
            assert peer["id"] == c.servers[1].cluster.node_id
            assert peer["suspect"] is False
            assert peer["breaker"] == "closed"
            assert peer["lastSeenAgeSeconds"] is not None
            # open the breaker; the block must say so
            c.servers[0].cluster.breakers.record_failure(peer["id"])
            for _ in range(3):
                c.servers[0].cluster.breakers.record_failure(peer["id"])
            (peer,) = c.client(0).status()["clusterHealth"]["peers"]
            assert peer["breaker"] == "open"


class TestSuspectHorizonBoundary:
    """The failover layer depends on alive_ids being EXACT at the
    suspect horizon (SUSPECT_AFTER x heartbeat_interval): at the
    boundary a peer is suspect; any younger last-seen is alive."""

    def test_boundary_exact(self, tmp_path):
        import time

        from pilosa_tpu.cluster.cluster import SUSPECT_AFTER

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            cl = c.servers[0].cluster
            peer = c.servers[1].cluster.node_id
            horizon = SUSPECT_AFTER * cl.cfg.heartbeat_interval
            assert horizon == pytest.approx(15.0)
            now = time.monotonic()
            # exactly AT the horizon: suspect (strict <)
            with cl._lock:
                cl._last_seen[peer] = now - horizon
            assert peer not in cl.alive_ids()
            # comfortably inside: alive (5 s of slack >> test runtime)
            with cl._lock:
                cl._last_seen[peer] = time.monotonic() - horizon + 5.0
            assert peer in cl.alive_ids()
            # self is always alive regardless of bookkeeping
            assert cl.node_id in cl.alive_ids()

    def test_suspect_peer_not_routed(self, tmp_path):
        import time

        from pilosa_tpu.cluster.cluster import SUSPECT_AFTER

        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            spread_bits(c.client(0))
            cl = c.servers[0].cluster
            victim = c.servers[1].cluster.node_id
            horizon = SUSPECT_AFTER * cl.cfg.heartbeat_interval
            with cl._lock:
                cl._last_seen[victim] = time.monotonic() - horizon
            groups = cl.group_shards_by_node("i", tuple(range(6)))
            assert victim not in groups


class TestRejoinBecomesRoutable:
    def test_tombstone_cleared_rejoin_routes_again(self, tmp_path):
        """A tombstoned node whose id explicitly rejoins (the restart
        path: same id, same port) must become routable again — the
        tombstone clears, stale breaker history resets, and the shard
        router includes it.  The failover layer depends on all three:
        a rejoined replica that stays 'open' would silently halve the
        failover options forever."""
        import time

        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=0.2) as c:
            spread_bits(c.client(0))
            coord = next(s for s in c.servers
                         if s.cluster.is_coordinator())
            victim = next(s for s in c.servers if s is not coord)
            vid = victim.cluster.node_id
            entry = next(s for s in c.servers
                         if s is not victim)
            # worst-case stale state on a surviving peer: the node is
            # tombstoned AND its breaker is open
            with entry.cluster._lock:
                entry.cluster._removed[vid] = time.time()
            for _ in range(4):
                entry.cluster.breakers.record_failure(vid)
            assert entry.cluster.breakers.state(vid) == "open"
            # tombstoned: heartbeats bounce, the node is unroutable
            resp = entry.cluster.handle_heartbeat(vid, "NORMAL")
            assert resp.get("removed")
            # ... until the explicit rejoin lands on this peer
            entry.cluster.handle_join({"id": vid, "uri": vid})
            assert vid not in entry.cluster._removed
            assert entry.cluster.breakers.state(vid) == "closed", \
                "rejoin must reset stale breaker history"
            assert vid in entry.cluster.alive_ids()
            # routable: for a shard the rejoined node owns, it is the
            # router's pick once its co-owners are excluded (whether it
            # is any shard's FIRST choice is placement luck — exclusion
            # pins the property deterministically)
            shard = next(s for s in range(64)
                         if vid in entry.cluster.shard_owners("i", s))
            others = {s.cluster.node_id for s in c.servers} - {vid}
            groups = entry.cluster.group_shards_by_node(
                "i", (shard,), exclude=others)
            assert groups == {vid: (shard,)}, \
                "rejoined node must be routable"


class TestFanoutTeardown:
    def test_no_thread_leak_with_abandoned_legs(self, tmp_path):
        """After a leg raises (and with hedging multiplying in-flight
        legs), the fan-out pool must cancel queued futures and release
        every worker — repeated queries must not accumulate threads."""
        import threading
        import time

        from pilosa_tpu import fault
        from pilosa_tpu.api.client import ClientError

        with run_cluster(3, str(tmp_path), replicas=1,
                         hedge_after=0.05) as c:
            spread_bits(c.client(0))
            entry = c.servers[0]
            peers = [s.cluster.node_id for s in c.servers[1:]]
            # one shard per node so BOTH peers are guaranteed a leg
            # (placement is hash-driven over random ports)
            shard_of = {}
            for s in range(64):
                ((n, _),) = entry.cluster.group_shards_by_node(
                    "i", (s,)).items()
                shard_of.setdefault(n, s)
                if len(shard_of) == 3:
                    break
            assert set(peers) <= set(shard_of), "a peer owns nothing"
            qs = ",".join(str(s) for s in sorted(shard_of.values()))
            try:
                # one leg always dies (no replica: the query fails),
                # the other straggles — its abandoned future must not
                # pin a thread beyond its sleep
                fault.set_fault("dist.fanout", "error",
                                match={"peer": peers[0]})
                fault.set_fault("dist.fanout", "delay",
                                match={"peer": peers[1]},
                                args={"seconds": 0.1})
                for _ in range(3):  # warmup (lazy pools, keepalives)
                    with pytest.raises(ClientError):
                        c.client(0)._do(
                            "POST", f"/index/i/query?shards={qs}",
                            b"Count(Row(f=1))")
                time.sleep(0.5)
                baseline = threading.active_count()
                for _ in range(12):
                    with pytest.raises(ClientError):
                        c.client(0)._do(
                            "POST", f"/index/i/query?shards={qs}",
                            b"Count(Row(f=1))")
            finally:
                fault.clear()
            # stragglers drain and pool threads exit on their own
            # schedule; under full-suite load 1s was not always enough
            # (PR 11 flake) — poll with a generous deadline instead of
            # asserting against a fixed sleep.  A REAL leak never
            # drains, so the deadline only trades latency, not signal.
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                leaked = threading.active_count() - baseline
                if leaked <= 2:
                    break
                time.sleep(0.2)
            assert leaked <= 2, \
                f"{leaked} threads leaked across 12 failed fan-outs"


class TestShardUniverseReplicaBound:
    def test_one_dead_peer_with_replicas_stays_complete(self, tmp_path):
        """replicas=2: one unreachable peer cannot hide shards (every
        shard has another holder that was polled), so strict reads keep
        serving instead of refusing until the suspect horizon."""
        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=2.0) as c:
            spread_bits(c.client(0))
            survivor = c.servers[0]
            victim = c.servers[1]
            want = survivor.cluster.index_shards("i", strict=True)
            victim.close()
            # pre-horizon: the victim is still in alive_ids, its shard
            # list unreadable — the union over the other replica is
            # still the full universe
            assert victim.cluster.node_id in survivor.cluster.alive_ids()
            survivor.cluster._shard_cache.clear()
            got = survivor.cluster.index_shards("i", strict=True)
            assert got == want

    def test_suspect_member_counts_toward_the_bound(self, tmp_path):
        """A dead owner PAST the suspect horizon is never polled — it
        must still count as failed, or one transient fetch failure on
        its co-replica would declare the universe complete while both
        holders of a shard went unheard (review r11)."""
        import time

        from pilosa_tpu import fault
        from pilosa_tpu.cluster.cluster import SUSPECT_AFTER

        with run_cluster(3, str(tmp_path), replicas=2,
                         heartbeat=5.0) as c:
            spread_bits(c.client(0))
            survivor, victim, other = c.servers
            cl = survivor.cluster
            victim.close()
            horizon = SUSPECT_AFTER * cl.cfg.heartbeat_interval
            with cl._lock:
                cl._last_seen[victim.cluster.node_id] = \
                    time.monotonic() - horizon
            assert victim.cluster.node_id not in cl.alive_ids()
            try:
                fault.set_fault(
                    "client.send", "partition",
                    match={"peer": other.cluster.node_id,
                           "path": "/internal/shards"})
                cl._shard_cache.clear()
                with pytest.raises(RuntimeError, match="incomplete"):
                    cl.index_shards("i", strict=True)
            finally:
                fault.clear()
            # with the co-replica reachable again the universe is
            # complete (one dead peer < replicas)
            cl._shard_cache.clear()
            assert cl.index_shards("i", strict=True)

    def test_replicas1_still_strict(self, tmp_path):
        """replicas=1: an unreadable peer CAN hold exclusive shards —
        the strict universe must refuse exactly as before."""
        with run_cluster(2, str(tmp_path), replicas=1,
                         heartbeat=2.0) as c:
            spread_bits(c.client(0))
            survivor, victim = c.servers
            victim.close()
            assert victim.cluster.node_id in survivor.cluster.alive_ids()
            survivor.cluster._shard_cache.clear()
            with pytest.raises(RuntimeError, match="incomplete"):
                survivor.cluster.index_shards("i", strict=True)
