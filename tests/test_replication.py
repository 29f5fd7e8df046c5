"""Warm-standby device-owner replication (persist/replication.py).

Covers the frame codec (CRC, sequence, sections), the dirty-set diff, the
in-process primary -> standby stream (snapshot then deltas), epoch-fenced
promotion with the boot-style reconcile + lease floors, the client-driven
failover in SidecarEngineClient (breaker/exhaustion/stale-epoch), the
split-brain guard (pinned stale_epoch_rejected), the repl.degraded health
probe on both roles, and the single-address byte-identical rollback arm.
"""

from __future__ import annotations

import socket
import struct
import threading
import time

import numpy as np
import pytest

from api_ratelimit_tpu.backends.sidecar import (
    FLAG_EPOCH,
    MAGIC,
    OP_SUBMIT,
    STATUS_STALE_EPOCH,
    VERSION,
    SidecarEngineClient,
    SlabSidecarServer,
    _HDR,
    _recv_exact,
    encode_items,
)
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, _Item
from api_ratelimit_tpu.limiter.cache import CacheError
from api_ratelimit_tpu.persist import replication as repl_mod
from api_ratelimit_tpu.persist.replication import (
    KIND_DELTA,
    KIND_SNAPSHOT,
    ReplProtocolError,
    ReplicationCoordinator,
    diff_tables,
    encode_frame,
    pack_delta_payload,
    pack_snapshot_payload,
    read_frame,
    unpack_delta_payload,
    unpack_snapshot_payload,
)
from api_ratelimit_tpu.persist.snapshot import (
    LEASE_ROW_WIDTH,
    ROW_WIDTH,
)
from api_ratelimit_tpu.testing.faults import FaultInjector, parse_fault_spec
from api_ratelimit_tpu.utils import FakeTimeSource
from api_ratelimit_tpu.utils.timeutil import RealTimeSource

NOW = 1_700_000_000


def _reader(blob: bytes):
    pos = [0]

    def recv(n: int) -> bytes:
        chunk = blob[pos[0] : pos[0] + n]
        pos[0] += n
        return chunk

    return recv


def _make_engine(ts=None, n_slots=1 << 10):
    return SlabDeviceEngine(
        time_source=ts or RealTimeSource(),
        n_slots=n_slots,
        buckets=(128,),
        max_batch=1024,
        use_pallas=False,
        block_mode=True,
    )


def _items(fp=42, hits=1, limit=1_000_000, divider=3600):
    return [_Item(fp=fp, hits=hits, limit=limit, divider=divider, jitter=0)]


class TestFrameCodec:
    def test_frame_round_trip(self):
        payload = b"hello replication"
        blob = encode_frame(KIND_DELTA, epoch=7, seq=123, payload=payload)
        kind, epoch, seq, got = read_frame(_reader(blob))
        assert (kind, epoch, seq, got) == (KIND_DELTA, 7, 123, payload)

    def test_corrupt_payload_fails_crc(self):
        blob = bytearray(encode_frame(KIND_DELTA, 1, 1, b"x" * 64))
        blob[repl_mod._FRAME_HDR.size + 10] ^= 0xFF
        with pytest.raises(ReplProtocolError, match="CRC"):
            read_frame(_reader(bytes(blob)))

    def test_bad_magic_and_kind_rejected(self):
        blob = bytearray(encode_frame(KIND_SNAPSHOT, 1, 1, b""))
        blob[0] ^= 0xFF
        with pytest.raises(ReplProtocolError, match="magic"):
            read_frame(_reader(bytes(blob)))
        blob = bytearray(encode_frame(KIND_SNAPSHOT, 1, 1, b""))
        blob[4] = 99
        with pytest.raises(ReplProtocolError, match="kind"):
            read_frame(_reader(bytes(blob)))

    def test_snapshot_payload_round_trip(self):
        table = np.arange(8 * ROW_WIDTH, dtype=np.uint32).reshape(
            8, ROW_WIDTH
        )
        lease = np.ones((3, LEASE_ROW_WIDTH), dtype=np.uint32)
        payload = pack_snapshot_payload([table], lease, NOW, ways=4)
        tables, headers, lease_rows = unpack_snapshot_payload(payload)
        assert len(tables) == 1
        assert (tables[0] == table).all()
        assert headers[0].ways == 4
        assert headers[0].n_slots == 8
        assert (lease_rows == lease).all()

    def test_snapshot_section_corruption_detected(self):
        table = np.arange(8 * ROW_WIDTH, dtype=np.uint32).reshape(
            8, ROW_WIDTH
        )
        payload = bytearray(
            pack_snapshot_payload(
                [table], np.zeros((0, LEASE_ROW_WIDTH), np.uint32), NOW
            )
        )
        payload[-5] ^= 0xFF  # inside a section payload
        with pytest.raises(ReplProtocolError):
            unpack_snapshot_payload(bytes(payload))

    def test_delta_payload_round_trip(self):
        idxs = np.array([1, 5, 7], dtype=np.int64)
        rows = np.arange(3 * ROW_WIDTH, dtype=np.uint32).reshape(
            3, ROW_WIDTH
        )
        lease = np.full((2, LEASE_ROW_WIDTH), 9, dtype=np.uint32)
        payload = pack_delta_payload([(0, idxs, rows)], lease)
        dirty, lease_rows = unpack_delta_payload(payload, ROW_WIDTH)
        assert dirty[0][0] == 0
        assert (dirty[0][1] == idxs).all()
        assert (dirty[0][2] == rows).all()
        assert (lease_rows == lease).all()

    def test_empty_delta_is_a_valid_heartbeat(self):
        payload = pack_delta_payload(
            [], np.zeros((0, LEASE_ROW_WIDTH), np.uint32)
        )
        dirty, lease_rows = unpack_delta_payload(payload, ROW_WIDTH)
        assert dirty == [] and lease_rows.shape[0] == 0

    def test_truncated_delta_rejected(self):
        idxs = np.array([1], dtype=np.int64)
        rows = np.zeros((1, ROW_WIDTH), dtype=np.uint32)
        payload = pack_delta_payload(
            [(0, idxs, rows)], np.zeros((0, LEASE_ROW_WIDTH), np.uint32)
        )
        with pytest.raises(ReplProtocolError):
            unpack_delta_payload(payload[:-3], ROW_WIDTH)

    def test_diff_tables_finds_exactly_the_changed_rows(self):
        prev = np.zeros((16, ROW_WIDTH), dtype=np.uint32)
        cur = prev.copy()
        cur[3, 2] = 7
        cur[11] = 5
        idxs, rows = diff_tables(prev, cur)
        assert idxs.tolist() == [3, 11]
        assert (rows == cur[[3, 11]]).all()
        idxs, _ = diff_tables(cur, cur)
        assert idxs.size == 0


class _Cluster:
    """One in-process primary + standby pair over unix sockets."""

    def __init__(self, tmp_path, interval_ms=25.0, faults_p=None, faults_s=None):
        self.p_sock = str(tmp_path / "p.sock")
        self.s_sock = str(tmp_path / "s.sock")
        self.p_engine = _make_engine()
        self.p_coord = ReplicationCoordinator(
            self.p_engine,
            "primary",
            interval_ms=interval_ms,
            fault_injector=faults_p,
        )
        self.p_server = SlabSidecarServer(
            self.p_sock, self.p_engine, repl=self.p_coord
        )
        self.p_coord.start()
        self.s_engine = _make_engine()
        self.s_coord = ReplicationCoordinator(
            self.s_engine,
            "standby",
            peer_address=self.p_sock,
            interval_ms=interval_ms,
            fault_injector=faults_s,
        )
        self.s_server = SlabSidecarServer(
            self.s_sock, self.s_engine, repl=self.s_coord
        )
        self.s_coord.start()
        self.closed = set()

    def client(self, **kw):
        kw.setdefault("retries", 2)
        kw.setdefault("retry_backoff", 0.001)
        kw.setdefault("retry_backoff_max", 0.01)
        kw.setdefault("breaker_threshold", 2)
        kw.setdefault("breaker_reset", 0.05)
        return SidecarEngineClient([self.p_sock, self.s_sock], **kw)

    def wait_applied(self, n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while self.s_coord.frames_applied_total < n:
            assert time.monotonic() < deadline, (
                f"standby stuck at {self.s_coord.frames_applied_total} "
                f"applied frames (wanted {n})"
            )
            time.sleep(0.01)

    def wait_synced_count(self, fp, count, timeout=10.0):
        """Wait until the standby's replica holds `count` for `fp`."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            tables, _, _ = self.s_coord.replica_state()
            if tables is not None:
                rows = tables[0]
                hit = rows[rows[:, 0] == (fp & 0xFFFFFFFF)]
                if hit.shape[0] and int(hit[0, 2]) == count:
                    return
            time.sleep(0.01)
        raise AssertionError(f"standby never saw count {count} for fp {fp}")

    def kill_primary(self):
        if "p" not in self.closed:
            self.closed.add("p")
            self.p_server.close()
            self.p_coord.close()

    def close(self):
        self.kill_primary()
        if "s" not in self.closed:
            self.closed.add("s")
            self.s_server.close()
            self.s_coord.close()


@pytest.fixture
def cluster(tmp_path):
    c = _Cluster(tmp_path)
    yield c
    c.close()


class TestStreamAndPromotion:
    def test_standby_mirrors_traffic_then_promotion_continues_counters(
        self, cluster
    ):
        client = cluster.client()
        try:
            for i in range(10):
                assert client.submit(_items()) == [i + 1]
            # quiesce, then wait until the replica holds the full count —
            # convergence, not just "a frame arrived"
            cluster.wait_synced_count(42, 10)
            cluster.kill_primary()
            # zero failed requests: the next write fails over, promotes
            # the standby, and CONTINUES the replicated counter
            assert client.submit(_items()) == [11]
            assert cluster.s_coord.role == "primary"
            assert cluster.s_coord.epoch == 2
            assert cluster.s_coord.promotions_total == 1
            assert client.submit(_items()) == [12]
        finally:
            client.close()

    def test_promotion_drops_dead_rows(self, tmp_path):
        """The boot-style reconcile: rows whose window ended (and TTL
        passed) on the replica do not survive promotion."""
        ts = FakeTimeSource(NOW)
        engine = _make_engine(ts)
        coord = ReplicationCoordinator(
            engine,
            "standby",
            peer_address="/nonexistent",
            interval_ms=10,
            time_source=ts,
        )
        table = np.zeros((1 << 10, ROW_WIDTH), dtype=np.uint32)
        # a live row: window open, TTL ahead
        table[5] = (7, 0, 3, NOW - NOW % 3600, NOW + 600, 3600, 0, 0)
        # a dead row: TTL passed
        table[9] = (8, 0, 9, NOW - 7200, NOW - 100, 3600, 0, 0)
        # ways=0 (an "unknown layout" writer): promotion must rehash the
        # surviving rows into this engine's set geometry
        payload = pack_snapshot_payload(
            [table],
            np.zeros((0, LEASE_ROW_WIDTH), np.uint32),
            NOW,
            ways=0,
        )
        coord._apply_frame(KIND_SNAPSHOT, 1, 1, payload)
        assert coord.promote(reason="test") is True
        assert coord.promote(reason="twice") is False  # idempotent
        afters = engine.submit_block(
            np.array(
                [[7, 8], [0, 0], [1, 1], [100, 100], [3600, 3600], [0, 0]],
                dtype=np.uint32,
            )
        )
        # live row continued at 3 -> 4; dead row restarted at 1
        assert afters.tolist() == [4, 1]
        coord.close()

    def test_promotion_applies_lease_floors(self, tmp_path):
        """A replica slab older than a replicated grant must restore the
        counter AT the grant watermark — never double-grant."""
        ts = FakeTimeSource(NOW)
        engine = _make_engine(ts)
        coord = ReplicationCoordinator(
            engine,
            "standby",
            peer_address="/nonexistent",
            interval_ms=10,
            time_source=ts,
        )
        window = NOW - NOW % 3600
        table = np.zeros((1 << 10, ROW_WIDTH), dtype=np.uint32)
        # slab shows count 2, but a live liability floors it at 12
        table[3] = (21, 0, 2, window, NOW + 600, 3600, 0, 0)
        lease = np.zeros((1, LEASE_ROW_WIDTH), dtype=np.uint32)
        lease[0] = (21, 0, window, 10, 0, 12, NOW + 300, 0)
        payload = pack_snapshot_payload([table], lease, NOW, ways=0)
        coord._apply_frame(KIND_SNAPSHOT, 1, 1, payload)
        coord.promote(reason="test")
        afters = engine.submit_block(
            np.array(
                [[21], [0], [1], [1000], [3600], [0]], dtype=np.uint32
            )
        )
        assert afters.tolist() == [13]  # floored at 12, then +1
        _entries, tokens = engine.lease_registry.outstanding()
        assert tokens == 10  # the liability itself was re-seeded
        coord.close()

    def test_delta_sequence_gap_raises(self, tmp_path):
        ts = FakeTimeSource(NOW)
        engine = _make_engine(ts)
        coord = ReplicationCoordinator(
            engine, "standby", peer_address="/nonexistent", interval_ms=10
        )
        table = np.zeros((1 << 10, ROW_WIDTH), dtype=np.uint32)
        payload = pack_snapshot_payload(
            [table], np.zeros((0, LEASE_ROW_WIDTH), np.uint32), NOW
        )
        coord._apply_frame(KIND_SNAPSHOT, 1, 1, payload)
        delta = pack_delta_payload(
            [], np.zeros((0, LEASE_ROW_WIDTH), np.uint32)
        )
        coord._apply_frame(KIND_DELTA, 1, 2, delta)
        with pytest.raises(ReplProtocolError, match="gap"):
            coord._apply_frame(KIND_DELTA, 1, 4, delta)
        coord.close()

    def test_geometry_mismatch_is_a_loud_protocol_error(self, tmp_path):
        ts = FakeTimeSource(NOW)
        engine = _make_engine(ts, n_slots=1 << 10)
        coord = ReplicationCoordinator(
            engine, "standby", peer_address="/nonexistent", interval_ms=10
        )
        wrong = np.zeros((64, ROW_WIDTH), dtype=np.uint32)  # wrong n_slots
        payload = pack_snapshot_payload(
            [wrong], np.zeros((0, LEASE_ROW_WIDTH), np.uint32), NOW
        )
        with pytest.raises(ReplProtocolError, match="geometry"):
            coord._apply_frame(KIND_SNAPSHOT, 1, 1, payload)
        coord.close()


class TestSplitBrainGuard:
    def test_stale_primary_write_rejected_and_counted(self, cluster):
        """The pinned acceptance: a resurrected old primary rejects a
        write fenced on the promoted epoch, stale_epoch_rejected > 0, and
        the increment is NOT applied."""
        client = cluster.client()
        try:
            client.submit(_items())
            cluster.wait_synced_count(42, 1)
            cluster.kill_primary()
            assert client.submit(_items()) == [2]  # promoted standby
            assert client._epoch_known == 2

            # resurrect the old primary at the same address, epoch 1
            p2_engine = _make_engine()
            p2_coord = ReplicationCoordinator(
                p2_engine, "primary", interval_ms=25
            )
            p2_server = SlabSidecarServer(
                cluster.p_sock, p2_engine, repl=p2_coord
            )
            try:
                # a raw epoch-fenced write straight at the stale primary
                conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                conn.connect(cluster.p_sock)
                payload = encode_items(_items())
                conn.sendall(
                    _HDR.pack(MAGIC, VERSION, OP_SUBMIT, FLAG_EPOCH)
                    + payload
                    + struct.pack("<I", client._epoch_known)
                )
                status = _recv_exact(conn, 1)
                assert status == bytes([STATUS_STALE_EPOCH])
                (srv_epoch,) = struct.unpack("<I", _recv_exact(conn, 4))
                assert srv_epoch == 1
                conn.close()
                assert p2_coord.stale_epoch_rejected_total > 0
                # the write never touched the stale slab
                tables = p2_engine.export_tables()
                assert (tables[0][:, 0] == 42).sum() == 0
            finally:
                p2_server.close()
                p2_coord.close()
        finally:
            client.close()

    def test_repl_less_server_answers_epoch_zero(self, tmp_path):
        """A FLAG_EPOCH frame at a replication-less owner still works —
        the epoch answers 0 and the client ignores it."""
        engine = _make_engine()
        sock = str(tmp_path / "plain.sock")
        server = SlabSidecarServer(sock, engine)
        other = str(tmp_path / "other.sock")
        other_server = SlabSidecarServer(other, _make_engine())
        client = SidecarEngineClient(
            [sock, other], retries=0, breaker_threshold=0
        )
        try:
            assert client.submit(_items()) == [1]
            assert client._epoch_known == 0
        finally:
            client.close()
            server.close()
            other_server.close()


class TestClientFailover:
    def test_exhausted_retries_fail_over_with_zero_failures(self, cluster):
        client = cluster.client(retries=1)
        try:
            assert client.submit(_items()) == [1]
            cluster.wait_synced_count(42, 1)
            cluster.kill_primary()
            # every subsequent submit succeeds against the standby
            for i in range(5):
                assert client.submit(_items()) == [i + 2]
            assert client.active_address == cluster.s_sock
            assert client.failover_reason() is not None
            assert "standby" in client.failover_reason()
        finally:
            client.close()

    def test_breaker_open_triggers_failover_instead_of_fail_fast(
        self, cluster
    ):
        client = cluster.client(retries=0, breaker_threshold=1)
        try:
            client.submit(_items())
            cluster.wait_synced_count(42, 1)
            cluster.kill_primary()
            # first call exhausts retries (failing over inside the call);
            # any later call must not fail fast on an open breaker
            for i in range(3):
                assert client.submit(_items()) == [i + 2]
        finally:
            client.close()

    def test_failover_journey_flag_retained(self, cluster, test_store):
        from api_ratelimit_tpu.tracing import journeys

        store, _ = test_store
        recorder = journeys.JourneyRecorder(
            slow_ms=1e9, retain=8, ring=8
        )
        journeys.set_global_recorder(recorder)
        client = cluster.client()
        try:
            client.submit(_items())
            cluster.wait_synced_count(42, 1)
            cluster.kill_primary()
            journey = recorder.begin("request")
            client.submit(_items())
            recorder.finish(journey, 1.0)
            retained = recorder.retained()
            assert retained, "failover journey was not tail-sampled"
            assert journeys.FLAG_FAILOVER in retained[-1].flags
        finally:
            journeys.set_global_recorder(None)
            client.close()

    def test_failover_counter_and_gauge(self, cluster, test_store):
        store, _ = test_store
        client = cluster.client(scope=store.scope("ratelimit"))
        try:
            client.submit(_items())
            cluster.wait_synced_count(42, 1)
            cluster.kill_primary()
            client.submit(_items())
            snap = store.debug_snapshot()
            assert snap["ratelimit.sidecar.failover"] >= 1
            assert snap["ratelimit.sidecar.active_backend"] == 1
        finally:
            client.close()


class TestRollbackArm:
    """REPL_ROLE unset / single-address == the pre-replication protocol,
    byte for byte."""

    def _capture_frame(self, tmp_path, address_arg):
        """Boot a client against a capturing server; returns the raw
        SUBMIT frame bytes the client sent."""
        captured = []
        done = threading.Event()
        sock_path = str(tmp_path / "cap.sock")
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        srv.bind(sock_path)
        srv.listen(4)

        def serve():
            try:
                while not done.is_set():
                    conn, _ = srv.accept()
                    with conn:
                        while True:
                            hdr = _recv_exact(conn, _HDR.size)
                            magic, version, op, flags = _HDR.unpack(hdr)
                            if op == 2:  # PING
                                conn.sendall(b"\x00")
                                continue
                            body = b""
                            # read the item block
                            n_raw = _recv_exact(conn, 4)
                            (n,) = struct.unpack("<I", n_raw)
                            body = n_raw + _recv_exact(conn, 6 * n * 4)
                            if flags & FLAG_EPOCH:
                                body += _recv_exact(conn, 4)
                            captured.append(hdr + body)
                            out = np.ones(n, dtype=np.uint32)
                            if flags & FLAG_EPOCH:
                                conn.sendall(
                                    b"\x02"
                                    + struct.pack("<I", 0)
                                    + struct.pack("<I", n)
                                    + out.tobytes()
                                )
                            else:
                                conn.sendall(
                                    b"\x00"
                                    + struct.pack("<I", n)
                                    + out.tobytes()
                                )
            except (OSError, ConnectionError):
                return

        thread = threading.Thread(target=serve, daemon=True)
        thread.start()
        client = SidecarEngineClient(
            address_arg, retries=0, breaker_threshold=0
        )
        try:
            client.submit(_items())
        finally:
            client.close()
            done.set()
            srv.close()
        return captured[-1]

    def test_single_address_frames_are_byte_identical_legacy(self, tmp_path):
        frame = self._capture_frame(tmp_path, str(tmp_path / "cap.sock"))
        expected = (
            _HDR.pack(MAGIC, VERSION, OP_SUBMIT, 0) + encode_items(_items())
        )
        assert frame == expected

    def test_single_entry_list_is_also_legacy(self, tmp_path):
        frame = self._capture_frame(tmp_path, [str(tmp_path / "cap.sock")])
        expected = (
            _HDR.pack(MAGIC, VERSION, OP_SUBMIT, 0) + encode_items(_items())
        )
        assert frame == expected

    def test_multi_address_sets_the_epoch_flag(self, tmp_path):
        frame = self._capture_frame(
            tmp_path,
            [str(tmp_path / "cap.sock"), str(tmp_path / "unused.sock")],
        )
        _magic, _version, _op, flags = _HDR.unpack(frame[: _HDR.size])
        assert flags & FLAG_EPOCH
        # fixed u32 epoch trailer rides after the block
        assert len(frame) == _HDR.size + len(encode_items(_items())) + 4


class TestDegradedProbes:
    def test_primary_without_standby_reports_degraded_after_grace(self):
        engine = _make_engine()
        coord = ReplicationCoordinator(
            engine, "primary", interval_ms=10.0, max_lag_ms=30.0
        )
        coord.start()
        try:
            assert coord.degraded_reason() is None  # boot grace
            time.sleep(0.05)
            reason = coord.degraded_reason()
            assert reason is not None and "no standby" in reason
        finally:
            coord.close()

    def test_standby_stale_probe_raises_and_clears(self, tmp_path):
        cluster = _Cluster(tmp_path, interval_ms=20.0)
        try:
            cluster.wait_applied(1)
            # freshly applied: clear
            assert cluster.s_coord.degraded_reason() is None
            # primary stops shipping (killed): lag crosses 5x interval
            cluster.kill_primary()
            time.sleep(0.25)
            reason = cluster.s_coord.degraded_reason()
            assert reason is not None and "standby stale" in reason
        finally:
            cluster.close()

    def test_primary_with_standby_is_healthy(self, tmp_path):
        cluster = _Cluster(tmp_path, interval_ms=20.0)
        try:
            cluster.wait_applied(2)
            assert cluster.p_coord.degraded_reason() is None
        finally:
            cluster.close()

    def test_health_checker_integration(self, tmp_path):
        from api_ratelimit_tpu.server.health import HealthChecker

        cluster = _Cluster(tmp_path, interval_ms=20.0)
        try:
            cluster.wait_applied(1)
            health = HealthChecker(name="ratelimit-sidecar")
            health.add_degraded_probe(cluster.s_coord.degraded_reason)
            assert health.http_response() == (200, "OK")
            cluster.kill_primary()
            time.sleep(0.25)
            status, body = health.http_response()
            assert status == 200  # degraded never drains
            assert "repl.degraded" in body
        finally:
            cluster.close()


class TestResync:
    def test_ship_drop_fault_forces_resync_and_convergence(self, tmp_path):
        """repl.ship drop consumes sequence numbers without sending: the
        standby must detect the gap, resync off a fresh snapshot, and
        still converge on the primary's counters."""
        faults = FaultInjector(
            parse_fault_spec("repl.ship:drop:0.4"), seed=3
        )
        cluster = _Cluster(tmp_path, interval_ms=15.0, faults_p=faults)
        client = cluster.client()
        try:
            for _ in range(12):
                client.submit(_items())
            deadline = time.monotonic() + 10.0
            while cluster.s_coord.resyncs_total < 1:
                assert time.monotonic() < deadline, "no resync happened"
                time.sleep(0.01)
            faults.clear()  # outage ends; the stream heals
            cluster.wait_synced_count(42, 12)
        finally:
            client.close()
            cluster.close()

    def test_apply_corruption_forces_resync(self, tmp_path):
        class _OneShot(FaultInjector):
            def __init__(self):
                super().__init__(parse_fault_spec("repl.apply:torn_write:1.0"))
                self.shots = 1

            def fire(self, site):
                if self.shots <= 0:
                    return None
                action = super().fire(site)
                if action is not None:
                    self.shots -= 1
                return action

        faults = _OneShot()
        cluster = _Cluster(tmp_path, interval_ms=15.0, faults_s=faults)
        client = cluster.client()
        try:
            client.submit(_items())
            deadline = time.monotonic() + 10.0
            while cluster.s_coord.resyncs_total < 1:
                assert time.monotonic() < deadline, "no resync happened"
                time.sleep(0.01)
            cluster.wait_synced_count(42, 1)
        finally:
            client.close()
            cluster.close()

    def test_ship_delay_shows_up_as_primary_lag(self, tmp_path):
        faults = FaultInjector(
            parse_fault_spec("repl.ship:delay_ms:400")
        )
        cluster = _Cluster(tmp_path, interval_ms=20.0, faults_p=faults)
        try:
            # the first (snapshot) ship is itself delayed; by the time it
            # lands the next is already late — primary lag crosses 5x20ms
            time.sleep(0.3)
            reason = cluster.p_coord.degraded_reason()
            assert reason is not None and "repl.degraded" in reason
        finally:
            faults.clear()
            cluster.close()


class TestAutoRole:
    def test_auto_resolves_standby_when_peer_answers(self, tmp_path):
        cluster = _Cluster(tmp_path, interval_ms=20.0)
        auto_sock = str(tmp_path / "auto.sock")
        engine = _make_engine()
        coord = ReplicationCoordinator(
            engine, "auto", peer_address=cluster.p_sock, interval_ms=20.0
        )
        server = SlabSidecarServer(auto_sock, engine, repl=coord)
        try:
            coord.start()
            assert coord.role == "standby"
        finally:
            server.close()
            coord.close()
            cluster.close()

    def test_auto_resolves_primary_when_peer_dark(self, tmp_path):
        engine = _make_engine()
        coord = ReplicationCoordinator(
            engine,
            "auto",
            peer_address=str(tmp_path / "nobody.sock"),
            interval_ms=20.0,
        )
        try:
            coord.start()
            assert coord.role == "primary"
        finally:
            coord.close()

    def test_standby_refuses_subscribers(self, tmp_path):
        """Chained replication is not a thing: subscribing to a standby
        answers an error reply."""
        cluster = _Cluster(tmp_path, interval_ms=20.0)
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.connect(cluster.s_sock)
            from api_ratelimit_tpu.backends.sidecar import OP_REPL_SUBSCRIBE

            conn.sendall(
                _HDR.pack(MAGIC, VERSION, OP_REPL_SUBSCRIBE, 0)
                + struct.pack("<IQ", 0, 0)
            )
            assert _recv_exact(conn, 1) == b"\x01"
            conn.close()
        finally:
            cluster.close()
