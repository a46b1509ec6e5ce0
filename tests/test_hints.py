"""Durable hinted handoff, exhaustively (ISSUE 8 satellite): the hint
log's crash recovery at EVERY record boundary and at mid-record
offsets — driven through the ``hints.append`` record-relative failpoint
and the shared ``sys.write`` seam, the same sites the chaos harness
tears on live nodes — plus the receiver-side op-id dedup window that
makes replay delivery idempotent (a re-sent batch must be a no-op, or
a replayed Clear could land after a newer direct Set and destroy it).
"""

import os

import pytest

from pilosa_tpu import fault
from pilosa_tpu.cluster.hints import HintBoard, HintLog
from pilosa_tpu.store.oplog import IdWindow


@pytest.fixture(autouse=True)
def _clean_registry():
    fault.clear()
    yield
    fault.clear()


def _payload(i: int) -> dict:
    return {"id": f"{i:032x}", "index": "i", "pql": f"Set({i}, f=0)",
            "op": "Set", "field": "f", "shards": [i % 3]}


PAYLOADS = [_payload(i) for i in range(4)]


def _record_bytes(seq: int, payload: dict) -> bytes:
    """One CRC-framed record exactly as HintLog.append lays it out."""
    import json
    import struct
    import time
    import zlib
    pb = json.dumps(payload, separators=(",", ":")).encode()
    body = struct.pack("<QdI", seq, time.time(), len(pb)) + pb
    return struct.pack("<I", zlib.crc32(body)) + body


def _write_torn_log(path: str, n_full: int, torn_offset: int) -> None:
    """A log holding PAYLOADS[:n_full] intact plus ``torn_offset``
    raw bytes of PAYLOADS[n_full]'s record — the on-disk state a
    coordinator crashed MID-APPEND leaves behind.  (Written directly:
    a failed append in a SURVIVING process truncates its own tear —
    see test_failed_append_truncates_tear — so only a real crash can
    leave these bytes.)"""
    log = HintLog(path)
    for p in PAYLOADS[:n_full]:
        log.append(p)
    log.close()
    with open(path, "ab") as f:
        f.write(_record_bytes(n_full + 1, PAYLOADS[n_full])[:torn_offset])


def _assert_clean_prefix(path: str, n_full: int) -> None:
    log = HintLog(path)
    assert [p for _s, _t, p in log.records] == PAYLOADS[:n_full], (
        f"recovery did not yield the clean {n_full}-record prefix")
    # recovery physically truncated the torn tail: appending again
    # yields a parseable log with exactly n_full + 1 records
    log.append({"id": "aa" * 16, "index": "i", "pql": "Set(9, f=0)",
                "op": "Set", "field": "f", "shards": [0]})
    log.close()
    re = HintLog(path)
    assert len(re.records) == n_full + 1
    assert re.records[-1][2]["pql"] == "Set(9, f=0)"
    re.close()
    os.remove(path)


class TestHintLogTornRecovery:
    """The crash-safety proof: a tear at any byte offset recovers to a
    replayable-or-cleanly-truncated log."""

    def test_torn_at_every_record_boundary(self, tmp_path):
        # offset 0 = crash BETWEEN records: the boundary case at every
        # prefix length, zero records through all of them
        for n_full in range(len(PAYLOADS)):
            path = str(tmp_path / f"b{n_full}.hints")
            _write_torn_log(path, n_full, torn_offset=0)
            _assert_clean_prefix(path, n_full)

    def test_torn_at_mid_record_offsets(self, tmp_path):
        # tears inside the 24-byte frame header and into the JSON
        # payload — every class must truncate cleanly
        for n_full in (0, 2):
            for offset in (1, 4, 12, 23, 24, 30, 60):
                path = str(tmp_path / f"m{n_full}_{offset}.hints")
                _write_torn_log(path, n_full, torn_offset=offset)
                _assert_clean_prefix(path, n_full)

    def test_truncated_at_every_byte(self, tmp_path):
        """Brute force: a log cut at EVERY byte offset recovers exactly
        the whole records that fit — no parse error, no phantom op."""
        full = str(tmp_path / "full.hints")
        log = HintLog(full)
        ends = []
        for p in PAYLOADS:
            log.append(p)
            ends.append(os.path.getsize(full))
        log.close()
        blob = open(full, "rb").read()
        for cut in range(len(blob) + 1):
            path = str(tmp_path / "cut.hints")
            with open(path, "wb") as f:
                f.write(blob[:cut])
            want = sum(1 for e in ends if e <= cut)
            re = HintLog(path)
            assert [p for _s, _t, p in re.records] == PAYLOADS[:want], (
                f"cut at byte {cut}: want prefix {want}")
            re.close()
            os.remove(path)

    def test_torn_via_sys_write_seam(self, tmp_path):
        """The shared ``sys.write`` failpoint tears hint appends too
        (chaos schedules that tear every durable writer at once)."""
        path = str(tmp_path / "sys.hints")
        log = HintLog(path)
        log.append(PAYLOADS[0])
        fault.set_fault("sys.write", "torn_write", nth=1,
                        args={"offset": 7})
        with pytest.raises(fault.FaultError):
            log.append(PAYLOADS[1])
        log.close()
        fault.clear()
        re = HintLog(path)
        assert [p for _s, _t, p in re.records] == PAYLOADS[:1]
        re.close()

    def test_failed_append_truncates_tear(self, tmp_path):
        """Regression (r13 review): a FAILED append in a SURVIVING
        process must not leave torn bytes in the file.  The op
        correctly fails to the client, but the process keeps serving —
        a later GOOD append landing BEHIND leftover torn bytes would
        be silently discarded (along with every acked hint after it)
        by clean-prefix recovery at the next boot, losing acked
        Clears to AAE resurrection."""
        path = str(tmp_path / "survive.hints")
        log = HintLog(path)
        log.append(PAYLOADS[0])
        clean = os.path.getsize(path)
        fault.set_fault("hints.append", "torn_write", nth=1,
                        args={"offset": 9})
        with pytest.raises(fault.FaultError):
            log.append(PAYLOADS[1])
        fault.clear()
        assert os.path.getsize(path) == clean  # tear truncated away
        # the next hint ACKS and SURVIVES a reboot
        assert log.append(PAYLOADS[2]) == 2
        log.close()
        re = HintLog(path)
        assert [p for _s, _t, p in re.records] == [PAYLOADS[0],
                                                   PAYLOADS[2]]
        re.close()

    def test_seq_monotonic_across_reopen(self, tmp_path):
        path = str(tmp_path / "seq.hints")
        log = HintLog(path)
        assert [log.append(p) for p in PAYLOADS[:3]] == [1, 2, 3]
        log.close()
        re = HintLog(path)
        assert re.append(PAYLOADS[3]) == 4
        re.close()


class TestHintBoard:
    def _board(self, tmp_path, **kw) -> HintBoard:
        return HintBoard(str(tmp_path / "_hints"), **kw)

    def test_add_ack_compacts_and_survives_reboot(self, tmp_path):
        b = self._board(tmp_path)
        for p in PAYLOADS:
            b.add("peer:1", p)
        assert b.pending_ops("peer:1") == 4
        assert b.pending_peers() == {"peer:1"}
        # ack through seq 2: the file compacts to the surviving suffix
        assert b.ack("peer:1", 2) == 2
        assert [p for _s, p in b.peek("peer:1", 10)] == PAYLOADS[2:]
        b.close()
        # boot recovery reloads the surviving log
        rb = self._board(tmp_path)
        assert rb.pending_ops("peer:1") == 2
        assert [p for _s, p in rb.peek("peer:1", 10)] == PAYLOADS[2:]
        # draining to empty drops the peer from the pending set
        rb.ack("peer:1", 10 ** 9)
        assert rb.pending_peers() == set()
        assert not rb.has_pending("peer:1")
        rb.close()

    def test_overflow_flips_after_max_age(self, tmp_path):
        import time

        b = self._board(tmp_path, max_age=0.05)
        b.add("peer:1", PAYLOADS[0])
        assert not b.overflowed("peer:1")
        time.sleep(0.08)
        assert b.overflowed("peer:1")
        assert b.summary()["peers"][0]["overflowed"] is True
        # never-hinted peers are not overflowed
        assert not b.overflowed("peer:2")
        b.close()

    def test_gated_fragment_covers_hinted_shards(self, tmp_path):
        b = self._board(tmp_path)
        b.add("peer:1", {"id": "00" * 16, "index": "i", "op": "Clear",
                         "pql": "Clear(1, f=0)", "field": "f",
                         "shards": [1, 2]})
        assert b.gated_fragment("i", "f", 1)
        assert b.gated_fragment("i", "f", 2)
        assert not b.gated_fragment("i", "f", 3)
        assert not b.gated_fragment("i", "g", 1)
        assert not b.gated_fragment("j", "f", 1)
        # shards=None (ClearRow-wide hint) gates every shard; a hint
        # with no field gates every field — conservative, never unsound
        b.add("peer:1", {"id": "01" * 16, "index": "j", "op": "Store",
                         "pql": "Store(Row(f=0), f=1)", "field": None,
                         "shards": None})
        assert b.gated_fragment("j", "anything", 7)
        # ack-compaction un-gates: the coverage summary must track
        # removals, not just appends
        b.ack("peer:1", 2)
        assert not b.gated_fragment("i", "f", 1)
        assert not b.gated_fragment("j", "anything", 7)
        b.close()

    def test_peer_filename_roundtrip_odd_ids(self, tmp_path):
        b = self._board(tmp_path)
        odd = "10.0.0.1:10101"
        b.add(odd, PAYLOADS[0])
        b.close()
        rb = self._board(tmp_path)
        assert rb.pending_peers() == {odd}
        rb.close()


class TestIdWindow:
    def test_dedup_and_persistence(self, tmp_path):
        path = str(tmp_path / "ids.log")
        w = IdWindow(path)
        assert w.add("a" * 32) is True
        assert w.add("a" * 32) is False  # dup
        assert w.add("b" * 32) is True
        assert "a" * 32 in w and "b" * 32 in w and "c" * 32 not in w
        w.close()
        rw = IdWindow(path)
        assert "a" * 32 in rw and "b" * 32 in rw
        assert rw.add("a" * 32) is False  # dedup survives reboot
        rw.close()

    def test_truncated_at_every_byte(self, tmp_path):
        full = str(tmp_path / "full.log")
        w = IdWindow(full)
        ids = [f"{i:032x}" for i in range(3)]
        ends = []
        for i in ids:
            w.add(i)
            ends.append(os.path.getsize(full))
        w.close()
        blob = open(full, "rb").read()
        for cut in range(len(blob) + 1):
            path = str(tmp_path / "cut.log")
            with open(path, "wb") as f:
                f.write(blob[:cut])
            want = sum(1 for e in ends if e <= cut)
            rw = IdWindow(path)
            assert len(rw) == want, f"cut at byte {cut}"
            assert all(i in rw for i in ids[:want])
            rw.close()
            os.remove(path)

    def test_compaction_keeps_newest_cap(self, tmp_path):
        path = str(tmp_path / "cap.log")
        w = IdWindow(path, cap=4)
        for i in range(12):  # > 2 * cap forces compaction
            w.add(f"{i:032x}")
        assert len(w) == 4
        assert f"{11:032x}" in w and f"{0:032x}" not in w
        w.close()
        rw = IdWindow(path, cap=4)
        assert len(rw) == 4
        assert f"{11:032x}" in rw
        rw.close()


class TestReplayEndpointIdempotent:
    """Duplicate replay delivery through the real endpoint is a no-op
    (op-id dedup pinned) — and a replayed Clear can never undo a Set
    it was already delivered before."""

    def test_double_replay_is_noop(self, tmp_path):
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            c.client(0).create_index("i")
            c.client(0).create_field("i", "f")
            ops = [
                {"id": "11" * 16, "index": "i", "op": "Set",
                 "pql": "Set(3, f=1)", "field": "f", "shards": [0]},
                {"id": "22" * 16, "index": "i", "op": "Clear",
                 "pql": "Clear(4, f=1)", "field": "f", "shards": [0]},
            ]
            first = c.client(0)._json("POST", "/internal/hints/replay",
                                      {"ops": ops})
            assert first == {"applied": 2, "deduped": 0, "dropped": 0}
            # the bit landed; now the cluster moves ON: a newer direct
            # write clears it
            c.client(0).query("i", "Clear(3, f=1)")
            # a duplicate batch delivery (lost ack, sender crash
            # mid-compaction) must dedup — NOT re-set the cleared bit
            second = c.client(0)._json("POST", "/internal/hints/replay",
                                       {"ops": ops})
            assert second == {"applied": 0, "deduped": 2, "dropped": 0}
            (got,) = c.client(0).query("i", "Row(f=1)")
            assert 3 not in got["columns"]

    def test_unreplayable_op_dropped_not_wedged(self, tmp_path):
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            ops = [{"id": "33" * 16, "index": "gone", "op": "Set",
                    "pql": "Set(1, f=0)", "field": "f", "shards": [0]}]
            out = c.client(0)._json("POST", "/internal/hints/replay",
                                    {"ops": ops})
            assert out["dropped"] == 1
            # the drop is remembered: redelivery dedups instead of
            # re-warning forever
            out2 = c.client(0)._json("POST", "/internal/hints/replay",
                                     {"ops": ops})
            assert out2 == {"applied": 0, "deduped": 1, "dropped": 0}

    def test_replay_defers_until_schema_settled(self, tmp_path):
        """Regression (r13 review): a drain racing a rejoiner's
        boot-time schema pull must not permanently drop an acked op
        for an index the receiver simply hasn't learned yet — inside
        the boot window a missing index answers 503 (the sender's
        drain retries next heartbeat) and the op is NOT consumed; a
        tombstoned deletion still drops even inside the window."""
        from pilosa_tpu.api.client import ClientError
        from pilosa_tpu.testing import run_cluster

        with run_cluster(2, str(tmp_path), replicas=2,
                         heartbeat=0.1) as c:
            ops = [{"id": "44" * 16, "index": "late", "op": "Set",
                    "pql": "Set(1, f=0)", "field": "f", "shards": [0]}]
            cl0 = c.servers[0].cluster
            cl0._schema_ready.clear()  # re-enter the boot window
            try:
                with pytest.raises(ClientError) as ei:
                    c.client(0)._json("POST", "/internal/hints/replay",
                                      {"ops": ops})
                assert ei.value.status == 503
            finally:
                cl0._schema_ready.set()
            # the deferred op was not consumed: once the schema lands
            # the very same batch applies
            c.client(0).create_index("late")
            c.client(0).create_field("late", "f")
            out = c.client(0)._json("POST", "/internal/hints/replay",
                                    {"ops": ops})
            assert out == {"applied": 1, "deduped": 0, "dropped": 0}
            # a recorded deletion is judged deleted even mid-boot
            c.client(0).delete_index("late")
            cl0._schema_ready.clear()
            try:
                ops2 = [{"id": "55" * 16, "index": "late",
                         "op": "Set", "pql": "Set(2, f=0)",
                         "field": "f", "shards": [0]}]
                out2 = c.client(0)._json(
                    "POST", "/internal/hints/replay", {"ops": ops2})
                assert out2["dropped"] == 1
            finally:
                cl0._schema_ready.set()


class TestAckedWriteReadBackThroughAKill:
    """The deterministic form of what ``bench/config24`` drove with
    timed windows (the script went in PR 30): three processes,
    replicas=2, one replica holder killed with kill -9.  From the kill
    on, every LIVE node answers the never-written rows exactly — for
    longer than the shard universe is cached, the stretch in which one
    run of that script once read a Count with a whole shard missing
    (ROADMAP F1) — an acknowledged ``Set`` is read back from every
    live node at once, and after the restart and the hint drain from
    all three."""

    N_SHARDS = 3
    READ_ROWS = 4
    WRITE_ROW = 9

    def _seed(self, client):
        import numpy as np

        from pilosa_tpu.engine.words import SHARD_WIDTH
        rng = np.random.default_rng(24)
        client.create_index("wb")
        client.create_field("wb", "f")
        rows, cols = [], []
        for s in range(self.N_SHARDS):
            offs = rng.choice(SHARD_WIDTH, size=48, replace=False)
            rr = rng.integers(0, self.READ_ROWS, size=48)
            rows += [int(r) for r in rr]
            cols += [s * SHARD_WIDTH + int(o) for o in offs]
        client.import_bits("wb", "f", rowIDs=rows, columnIDs=cols)
        return [rows.count(r) for r in range(self.READ_ROWS)]

    def test_every_live_node_exact_before_and_after_the_drain(
            self, tmp_path):
        import time

        from pilosa_tpu.engine.words import SHARD_WIDTH
        from pilosa_tpu.testing import run_process_cluster

        counts = "".join(f"Count(Row(f={r}))"
                         for r in range(self.READ_ROWS))
        written = f"Row(f={self.WRITE_ROW})"
        with run_process_cluster(3, str(tmp_path), replicas=2,
                                 anti_entropy=0.0) as cluster:
            want = self._seed(cluster.client(0))
            assert sum(want) == 48 * self.N_SHARDS
            for i in range(3):
                assert cluster.client(i).query("wb", counts) == want
            status = cluster.client(0)._json("GET", "/status")
            primary = next(nd["id"] for nd in status["nodes"]
                           if nd.get("isPrimary"))
            coord = next(i for i, nd in enumerate(cluster.nodes)
                         if f"127.0.0.1:{nd.port}" == primary)
            victim = next(i for i in range(3) if i != coord)
            live = [i for i in range(3) if i != victim]
            entry = cluster.client(live[0])

            cluster.nodes[victim].kill9()
            # acknowledged Sets in every shard while the corpse is
            # still a member: each is read back from every live node
            # AT ONCE, beside the rows no one writes
            acked = []
            deadline = time.monotonic() + 2.5  # > the universe's TTL
            k = 0
            while time.monotonic() < deadline or k < 2 * self.N_SHARDS:
                col = (k % self.N_SHARDS) * SHARD_WIDTH + 700 + k
                entry.query("wb", f"Set({col}, f={self.WRITE_ROW})")
                acked.append(col)
                k += 1
                for i in live:
                    c = cluster.client(i)
                    assert c.query("wb", counts) == want, (
                        f"node {i}: partial read {k} reads after the "
                        f"kill")
                    (got,) = c.query("wb", written)
                    assert set(acked) <= set(got["columns"]), (
                        f"node {i} lost an acknowledged Set")
            assert entry.write_health().get("hintBacklogOps", 0) >= 1

            node = cluster.nodes[victim]
            node.stop()
            node.start()
            node.await_up()
            cluster.await_membership(3, timeout=120)
            deadline = time.monotonic() + 60
            while entry.write_health().get("hintBacklogOps"):
                assert time.monotonic() < deadline, "hints never drained"
                time.sleep(0.1)
            for i in range(3):
                c = cluster.client(i)
                assert c.query("wb", counts) == want, f"node {i}"
                (got,) = c.query("wb", written)
                assert sorted(got["columns"]) == sorted(acked), (
                    f"node {i} after the drain")
