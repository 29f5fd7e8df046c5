"""Hierarchical quota leasing (backends/lease.py): the two-tier limiter.

Covers the reservation contract end to end: grant riders through the real
engine, frontend-local decisions byte-identical to the device path
(LEASE_ENABLED=false rollback arm), adaptive sizing (grow on exhaustion-
renewal, shrink on unused expiry, shrink-toward-1 near the limit), the
wire codec + sidecar trailer, the lease-liability snapshot section with
boot-time reconcile + counter floors, and the differential-oracle
overshoot bound: total admitted <= limit + Σ(outstanding lease budgets)
with a device-owner restart mid-stream — and total admitted <= limit when
the liability section restores (a restart never double-grants).
"""

from __future__ import annotations

import random
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from api_ratelimit_tpu.backends.lease import (
    LEASE_ROW_WIDTH,
    LeaseOps,
    LeaseRegistry,
    LeaseTable,
    decode_lease_ops,
    encode_lease_ops,
)
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, TpuRateLimitCache
from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
from api_ratelimit_tpu.limiter.local_cache import LocalCache
from api_ratelimit_tpu.models import Code, Descriptor, RateLimitRequest
from api_ratelimit_tpu.service import RateLimitService
from api_ratelimit_tpu.stats import Store, TestSink
from api_ratelimit_tpu.utils import FakeTimeSource

LEASE_YAML = """\
domain: lease
descriptors:
  - key: api_key
    rate_limit: {unit: minute, requests_per_unit: 100}
  - key: open
    rate_limit: {unit: minute, requests_per_unit: 1000000}
"""


class _StaticRuntime:
    def __init__(self, text):
        self._t = text

    def snapshot(self):
        text = self._t

        class Snap:
            def keys(self):
                return ["config.lease"]

            def get(self, key):
                return text

        return Snap()

    def add_update_callback(self, cb):
        pass


def _engine(ts, n_slots=1 << 10):
    return SlabDeviceEngine(
        time_source=ts,
        n_slots=n_slots,
        use_pallas=False,
        buckets=(128,),
        batch_window_seconds=0.0,
    )


def _stack(
    ts,
    lease=True,
    store=None,
    local_cache=None,
    engine=None,
    lease_table=None,
    yaml_text=LEASE_YAML,
    **lease_kw,
):
    """(service, cache, lease_table, store) — direct-mode engine, fake
    clock, deterministic jitter."""
    if store is None:
        store = Store(TestSink())
    base = BaseRateLimiter(
        time_source=ts,
        jitter_rand=random.Random(0),
        expiration_jitter_max_seconds=0,
        local_cache=local_cache,
    )
    if lease and lease_table is None:
        lease_kw.setdefault("min_size", 4)
        lease_kw.setdefault("max_size", 64)
        lease_table = LeaseTable(
            base, scope=store.scope("ratelimit").scope("lease"), **lease_kw
        )
    if engine is None:
        engine = _engine(ts)
    cache = TpuRateLimitCache(base, engine=engine, lease_table=lease_table)
    service = RateLimitService(
        runtime=_StaticRuntime(yaml_text),
        cache=cache,
        stats_scope=store.scope("ratelimit").scope("service"),
        time_source=ts,
        lease=lease_table,
    )
    return service, cache, lease_table, store


def _req(value="hot", key="api_key", hits=1):
    return RateLimitRequest(
        domain="lease",
        descriptors=(Descriptor.of((key, value)),),
        hits_addend=hits,
    )


def _rec(fp=7, divider=60, limit=100):
    """A minimal ResolvedLimit stand-in for plan/register unit tests."""
    return SimpleNamespace(fp=fp, divider=divider, requests_per_unit=limit)


class TestWireCodec:
    def test_round_trip(self):
        ops = LeaseOps(
            grants=[(0, 8, 1_000_020, 15), (3, 64, 1_000_020, 15)],
            settles=[((123 << 32) | 456, 1_000_020, 7)],
        )
        raw = encode_lease_ops(ops)
        # length-prefixed trailer: the framing layer strips the prefix
        (length,) = np.frombuffer(raw[:4], dtype="<u4")
        assert int(length) == len(raw) - 4
        decoded = decode_lease_ops(raw[4:])
        assert decoded.grants == ops.grants
        assert decoded.settles == ops.settles

    def test_empty_ops(self):
        decoded = decode_lease_ops(encode_lease_ops(LeaseOps((), ()))[4:])
        assert decoded.grants == [] and decoded.settles == []

    def test_malformed_body_raises(self):
        with pytest.raises(ValueError):
            decode_lease_ops(b"\x01")
        raw = encode_lease_ops(LeaseOps([(0, 8, 1, 1)], ()))[4:]
        with pytest.raises(ValueError):
            decode_lease_ops(raw[:-4])  # counts disagree with body length


class TestLeaseTableUnit:
    def _table(self, ts=None, **kw):
        ts = ts or FakeTimeSource(1_000_000 - (1_000_000 % 60))
        base = BaseRateLimiter(ts, expiration_jitter_max_seconds=0)
        kw.setdefault("min_size", 4)
        kw.setdefault("max_size", 64)
        return LeaseTable(base, **kw), ts

    def test_junk_params_rejected(self):
        base = BaseRateLimiter(FakeTimeSource(0))
        with pytest.raises(ValueError, match="LEASE_MIN"):
            LeaseTable(base, min_size=0)
        with pytest.raises(ValueError, match="LEASE_MAX"):
            LeaseTable(base, min_size=8, max_size=4)
        with pytest.raises(ValueError, match="LEASE_TTL_FRACTION"):
            LeaseTable(base, ttl_fraction=0.0)
        with pytest.raises(ValueError, match="LEASE_NEAR_LIMIT_RATIO"):
            LeaseTable(base, near_limit_ratio=1.5)

    def test_grant_grows_on_exhaustion_renewal(self):
        table, ts = self._table()
        now = ts.unix_now()
        rec = _rec()
        p1 = table.plan_grant(rec, 1, now)
        assert p1.size == 4
        table.register_grant(p1, after_total=5)  # caller used 1, lease 4
        # exhaust the lease, then the renewal grant doubles
        lease = table._leases[(rec.fp, p1.window)]
        lease.consumed = lease.granted
        p2 = table.plan_grant(rec, 1, now)
        assert p2.size == 8

    def test_ttl_expiry_shrinks_mostly_unused(self):
        table, ts = self._table()
        rec = _rec()
        p1 = table.plan_grant(rec, 1, ts.unix_now())
        table.register_grant(p1, after_total=5)
        # grow the remembered size first
        table._sizes[rec.fp] = 32
        ts.advance(16)  # past the 15s TTL (60s window * 0.25)
        p2 = table.plan_grant(rec, 1, ts.unix_now())
        # the expired lease was 4 tokens, 0 consumed -> halve toward MIN
        assert table._sizes[rec.fp] == max(4, p1.size // 2)
        assert p2 is not None

    def test_lease_never_crosses_window_boundary(self):
        table, ts = self._table()
        window = ts.unix_now() - (ts.unix_now() % 60)
        ts.now = window + 55  # 5s left in the window
        planned = table.plan_grant(_rec(), 1, ts.unix_now())
        assert planned.expires_at == window + 60

    def test_near_limit_shrinks_toward_one(self):
        table, ts = self._table()
        now = ts.unix_now()
        rec = _rec(limit=100)
        window = (now // 60) * 60
        table._after_hint[rec.fp] = (window, 95)  # past 0.9 * 100
        planned = table.plan_grant(rec, 1, now)
        assert planned.size == 2  # headroom 5 // 2
        table.abort_grant(planned)  # release the in-flight mark
        table._after_hint[rec.fp] = (window, 99)
        planned = table.plan_grant(rec, 1, now)
        assert planned.size == 1
        table.abort_grant(planned)
        table._after_hint[rec.fp] = (window, 100)  # no headroom: no lease
        assert table.plan_grant(rec, 1, now) is None

    def test_inflight_guard_blocks_concurrent_riders(self):
        table, ts = self._table()
        now = ts.unix_now()
        planned = table.plan_grant(_rec(), 1, now)
        assert planned is not None
        # a second miss for the same key while the rider is out: no rider
        assert table.plan_grant(_rec(), 1, now) is None
        table.register_grant(planned, after_total=5)
        # a different key is unaffected
        assert table.plan_grant(_rec(fp=8), 1, now) is not None

    def test_degraded_probe_is_sticky_until_success(self):
        table, _ = self._table()
        assert table.degraded_reason() is None
        table.note_device_failure(RuntimeError("sidecar dark"))
        reason = table.degraded_reason()
        assert reason is not None and "lease.degraded" in reason
        table.note_device_failure(RuntimeError("still dark"))
        assert table.degraded
        table.note_success()
        assert table.degraded_reason() is None

    def test_settles_queue_and_requeue(self):
        table, ts = self._table()
        rec = _rec()
        planned = table.plan_grant(rec, 1, ts.unix_now())
        table.register_grant(planned, after_total=5)
        lease = table._leases[(rec.fp, planned.window)]
        lease.consumed = 2
        ts.advance(16)  # expire
        assert table.plan_grant(rec, 1, ts.unix_now()) is not None
        settles = table.drain_settles()
        assert settles == [(rec.fp, planned.window, 2)]
        assert table.drain_settles() == []
        table.requeue_settles(settles)
        assert table.drain_settles() == settles


class TestServiceLeaseLocal:
    def test_byte_identical_to_lease_off_arm(self):
        """The LEASE_ENABLED=false rollback pin: a sequential stream makes
        the SAME decisions leased and unleased — reservation leasing is an
        exact continuation of the device counter."""
        ts_on, ts_off = FakeTimeSource(1_000_000), FakeTimeSource(1_000_000)
        svc_on, cache_on, _, _ = _stack(ts_on, lease=True)
        svc_off, cache_off, _, _ = _stack(ts_off, lease=False)
        try:
            for i in range(130):  # crosses the 100/minute limit
                code_on, st_on, _ = svc_on.should_rate_limit(_req())
                code_off, st_off, _ = svc_off.should_rate_limit(_req())
                a, b = st_on[0], st_off[0]
                assert code_on == code_off, i
                assert (
                    a.code,
                    a.limit_remaining,
                    a.duration_until_reset,
                    a.current_limit,
                ) == (
                    b.code,
                    b.limit_remaining,
                    b.duration_until_reset,
                    b.current_limit,
                ), i
                if i % 40 == 0:
                    ts_on.advance(1)
                    ts_off.advance(1)
        finally:
            cache_on.close()
            cache_off.close()

    def test_hot_key_is_answered_frontend_locally(self):
        ts = FakeTimeSource(1_000_000)
        svc, cache, table, store = _stack(ts)
        try:
            for _ in range(50):
                code, _, _ = svc.should_rate_limit(_req(key="open"))
                assert code == Code.OK
            # grants ride the device; everything else answers locally
            device = cache.engine._decisions_total
            assert device < 10, device
            snap = store.debug_snapshot()
            assert snap["ratelimit.lease.local_hits"] == 50 - device
            assert snap["ratelimit.lease.decisions_seen"] == 50
            assert snap["ratelimit.lease.grants"] == device
            # the device-owner registry carries the matching liability
            entries, tokens = cache.engine.lease_registry.outstanding()
            assert entries == 1 and tokens > 0
            held, held_tokens = table.outstanding()
            assert held == 1 and held_tokens > 0
        finally:
            cache.close()

    def test_over_limit_lands_in_local_cache_not_lease(self):
        """Once a key crosses its limit the over-limit cache answers it —
        inside the lease decide path, still device-free — and no further
        budget is granted for it."""
        ts = FakeTimeSource(1_000_000)
        local_cache = LocalCache(max_entries=128, time_source=ts)
        svc, cache, _, store = _stack(ts, local_cache=local_cache)
        try:
            codes = [svc.should_rate_limit(_req())[0] for _ in range(120)]
            assert codes[-1] == Code.OVER_LIMIT
            assert sum(1 for c in codes if c == Code.OK) == 100
            device_at_over = cache.engine._decisions_total
            for _ in range(20):
                code, _, _ = svc.should_rate_limit(_req())
                assert code == Code.OVER_LIMIT
            # the tail was served by the over-limit cache: no device calls
            assert cache.engine._decisions_total == device_at_over
            assert store.debug_snapshot()["ratelimit.lease.cache_hits"] >= 20
        finally:
            cache.close()

    def test_multi_descriptor_partial_miss_rides_device(self):
        """A request mixing a leased and an unleased descriptor goes to the
        device whole — the leased descriptor's budget is NOT consumed (no
        torn half-local answers)."""
        ts = FakeTimeSource(1_000_000)
        svc, cache, table, _ = _stack(ts)
        try:
            svc.should_rate_limit(_req(value="a", key="open"))  # grant "a"
            held_before = table.outstanding()[1]
            request = RateLimitRequest(
                domain="lease",
                descriptors=(
                    Descriptor.of(("open", "a")),
                    Descriptor.of(("open", "brand-new")),
                ),
            )
            code, statuses, _ = svc.should_rate_limit(request)
            assert code == Code.OK and len(statuses) == 2
            # "a"'s lease budget untouched by the device-ridden request
            assert table.outstanding()[1] >= held_before
        finally:
            cache.close()

    def test_journey_marks_lease_local_stage(self):
        from api_ratelimit_tpu.tracing import journeys

        ts = FakeTimeSource(1_000_000)
        svc, cache, _, _ = _stack(ts)
        recorder = journeys.JourneyRecorder(slow_ms=1e9)
        journeys.set_global_recorder(recorder)
        try:
            svc.should_rate_limit(_req(key="open"))  # grant: device path
            svc.should_rate_limit(_req(key="open"))  # leased: local
            snap = recorder.snapshot()
            recent = [
                j
                for ring in snap["recent"].values()
                for j in ring
                if j["kind"] == "request"
            ]
            assert any(
                journeys.STAGE_LEASE_LOCAL in j["stages"] for j in recent
            )
        finally:
            journeys.set_global_recorder(None)
            cache.close()

    def test_concurrent_hot_key_never_over_admits(self):
        """Reservation exactness under concurrency: OK decisions for one
        key never exceed its limit, leases or not."""
        ts = FakeTimeSource(1_000_000)
        svc, cache, _, _ = _stack(ts)
        ok = []
        lock = threading.Lock()

        def worker():
            mine = 0
            for _ in range(60):
                code, _, _ = svc.should_rate_limit(_req())
                if code == Code.OK:
                    mine += 1
            with lock:
                ok.append(mine)

        try:
            threads = [threading.Thread(target=worker) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert sum(ok) <= 100  # the 100/minute rule
            assert sum(ok) >= 90  # and leasing didn't burn the window away
        finally:
            cache.close()


class TestSidecarLeaseWire:
    def test_grant_and_settle_ride_the_wire(self):
        from api_ratelimit_tpu.backends.sidecar import (
            SidecarEngineClient,
            SlabSidecarServer,
        )

        ts = FakeTimeSource(1_000_000)
        engine = SlabDeviceEngine(
            time_source=ts,
            n_slots=1 << 10,
            use_pallas=False,
            buckets=(128,),
            block_mode=True,
        )
        server = SlabSidecarServer("tcp://127.0.0.1:0", engine)
        try:
            client = SidecarEngineClient(
                f"tcp://127.0.0.1:{server.port}", breaker_threshold=0
            )
            block = np.zeros((6, 1), dtype=np.uint32)
            block[0, 0] = 99  # fp_lo
            block[2, 0] = 1 + 8  # hits + lease rider
            block[3, 0] = 1000  # limit
            block[4, 0] = 60  # divider
            window = (ts.unix_now() // 60) * 60
            afters = client.submit_rows(
                block,
                lease_ops=LeaseOps(
                    grants=[(0, 8, window, 15)], settles=()
                ),
            )
            assert int(afters[0]) == 9
            entries, tokens = engine.lease_registry.outstanding()
            assert (entries, tokens) == (1, 8)
            # settle closes the liability
            client.submit_rows(
                np.array(
                    [[99], [0], [1], [1000], [60], [0]], dtype=np.uint32
                ),
                lease_ops=LeaseOps(
                    grants=(), settles=[(99, window, 8)]
                ),
            )
            assert engine.lease_registry.outstanding() == (0, 0)
            client.close()
        finally:
            server.close()

    def test_sidecar_backed_service_offloads_via_leases(self):
        from api_ratelimit_tpu.backends.sidecar import (
            SidecarEngineClient,
            SlabSidecarServer,
        )

        ts = FakeTimeSource(1_000_000)
        owner = SlabDeviceEngine(
            time_source=ts,
            n_slots=1 << 10,
            use_pallas=False,
            buckets=(128,),
            block_mode=True,
        )
        server = SlabSidecarServer("tcp://127.0.0.1:0", owner)
        try:
            client = SidecarEngineClient(
                f"tcp://127.0.0.1:{server.port}", breaker_threshold=0
            )
            svc, cache, _, store = _stack(ts, engine=client)
            for _ in range(40):
                assert svc.should_rate_limit(_req(key="open"))[0] == Code.OK
            snap = store.debug_snapshot()
            assert snap["ratelimit.lease.local_hits"] >= 30
            # the OWNER process's registry tracks the liability
            entries, tokens = owner.lease_registry.outstanding()
            assert entries == 1 and tokens > 0
            client.close()
        finally:
            server.close()


class TestRegistrySnapshot:
    def test_row_layout_matches_persist_mirror(self):
        from api_ratelimit_tpu.backends import lease as lease_mod
        from api_ratelimit_tpu.persist import snapshot as snap_mod

        assert lease_mod.LEASE_ROW_WIDTH == snap_mod.LEASE_ROW_WIDTH
        for name in (
            "LEASE_COL_FP_LO",
            "LEASE_COL_FP_HI",
            "LEASE_COL_WINDOW",
            "LEASE_COL_GRANTED",
            "LEASE_COL_SETTLED",
            "LEASE_COL_FLOOR",
            "LEASE_COL_EXPIRE",
        ):
            assert getattr(lease_mod, name) == getattr(snap_mod, name), name

    def test_export_import_round_trip(self):
        ts = FakeTimeSource(1_000_000)
        registry = LeaseRegistry(ts)
        registry.grant(7, 999_960, 8, expires_at=1_000_015, floor=9)
        registry.grant(7, 999_960, 16, expires_at=1_000_020, floor=25)
        registry.settle(7, 999_960, 8)
        rows = registry.export_rows()
        assert rows.shape == (1, LEASE_ROW_WIDTH)
        other = LeaseRegistry(ts)
        assert other.import_rows(rows) == 1
        assert other.outstanding() == (1, 16)

    def test_ttl_sweep_drops_dead_liabilities(self):
        ts = FakeTimeSource(1_000_000)
        registry = LeaseRegistry(ts)
        registry.grant(7, 999_960, 8, expires_at=1_000_010, floor=9)
        ts.advance(11)
        assert registry.outstanding() == (0, 0)
        assert registry.export_rows().shape == (0, LEASE_ROW_WIDTH)

    def test_reconcile_and_floors(self):
        from api_ratelimit_tpu.persist.snapshot import (
            COL_COUNT,
            apply_lease_floors,
            reconcile_leases,
        )

        now = 1_000_000
        rows = np.zeros((3, LEASE_ROW_WIDTH), dtype=np.uint32)
        rows[0] = (7, 0, 999_960, 8, 0, 20, now + 10, 0)  # live
        rows[1] = (8, 0, 999_960, 8, 0, 30, now - 1, 0)  # TTL-dead
        rows[2] = (9, 0, 999_960, 8, 8, 40, now + 10, 0)  # fully settled
        kept, stats = reconcile_leases(rows, now)
        assert stats == {"restored": 1, "dropped": 2}
        # slab table: fp 7's counter restored LOWER than the grant floor
        slab = np.zeros((4, 8), dtype=np.uint32)
        slab[2] = (7, 0, 5, 999_960, now + 100, 60, 0, 0)
        floored, unmatched = apply_lease_floors([slab], kept)
        assert (floored, unmatched) == (1, 0)
        assert slab[2, COL_COUNT] == 20

    def test_snapshotter_writes_and_restores_lease_section(self, tmp_path):
        from api_ratelimit_tpu.persist.snapshotter import (
            SlabSnapshotter,
            lease_snapshot_path,
        )

        ts = FakeTimeSource(1_000_000)
        engine = _engine(ts)
        engine.lease_registry.grant(
            7, 999_960, 8, expires_at=1_000_015, floor=9
        )
        engine.lease_registry.grant(
            8, 999_960, 4, expires_at=1_000_002, floor=4
        )
        store = Store(TestSink())
        snap = SlabSnapshotter(
            engine,
            str(tmp_path),
            interval_ms=60_000.0,
            time_source=ts,
            scope=store.scope("ratelimit"),
        )
        assert snap.snapshot_once() > 0
        assert (tmp_path / "leases.snap").exists()
        assert lease_snapshot_path(str(tmp_path)) == str(
            tmp_path / "leases.snap"
        )

        # restore into a fresh engine a few seconds later: fp 8's lease is
        # TTL-dead and must drop (snapshot.restore_dropped_leases)
        ts2 = FakeTimeSource(1_000_005)
        engine2 = _engine(ts2)
        store2 = Store(TestSink())
        snap2 = SlabSnapshotter(
            engine2,
            str(tmp_path),
            interval_ms=60_000.0,
            time_source=ts2,
            scope=store2.scope("ratelimit"),
        )
        stats = snap2.restore()
        assert stats["restored_leases"] == 1
        assert stats["dropped_leases"] == 1
        assert engine2.lease_registry.outstanding() == (1, 8)
        snapshot = store2.debug_snapshot()
        assert snapshot["ratelimit.snapshot.restore_dropped_leases"] == 1
        assert snapshot["ratelimit.snapshot.restore_leases"] == 1

    def test_corrupt_lease_file_degrades_to_slab_only(self, tmp_path):
        from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter

        ts = FakeTimeSource(1_000_000)
        engine = _engine(ts)
        engine.lease_registry.grant(
            7, 999_960, 8, expires_at=1_000_015, floor=9
        )
        snap = SlabSnapshotter(
            engine, str(tmp_path), interval_ms=60_000.0, time_source=ts
        )
        snap.snapshot_once()
        lease_file = tmp_path / "leases.snap"
        lease_file.write_bytes(lease_file.read_bytes()[:-2] + b"xx")

        engine2 = _engine(ts)
        snap2 = SlabSnapshotter(
            engine2, str(tmp_path), interval_ms=60_000.0, time_source=ts
        )
        stats = snap2.restore()
        # the slab still restores; the lease section is rejected
        assert "reason" not in stats
        assert stats["restored_leases"] == 0
        assert snap2.load_rejected_total == 1
        assert engine2.lease_registry.outstanding() == (0, 0)

    def test_inspect_tool_renders_lease_section(self, tmp_path):
        import importlib.util
        import os

        spec = importlib.util.spec_from_file_location(
            "snapshot_inspect",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "tools",
                "snapshot_inspect.py",
            ),
        )
        snapshot_inspect = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(snapshot_inspect)

        from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter

        ts = FakeTimeSource(1_000_000)
        engine = _engine(ts)
        engine.lease_registry.grant(
            7, 999_960, 8, expires_at=1_000_015, floor=9
        )
        engine.lease_registry.settle(7, 999_960, 3)
        SlabSnapshotter(
            engine, str(tmp_path), interval_ms=60_000.0, time_source=ts
        ).snapshot_once()
        report = snapshot_inspect.inspect_file(
            str(tmp_path / "leases.snap"), now=1_000_000
        )
        assert report["kind"] == "leases"
        leases = report["leases"]
        assert leases["outstanding"] == 1
        assert leases["granted_tokens"] == 8
        assert leases["settled_tokens"] == 3
        assert leases["unsettled_tokens"] == 5
        assert leases["restorable"] == 1
        # the CLI accepts a mixed file set and exits 0
        rc = snapshot_inspect.main(
            [
                str(tmp_path / "slab.snap"),
                str(tmp_path / "leases.snap"),
                "--json",
                "--now",
                "1000000",
            ]
        )
        assert rc == 0


class TestOvershootBound:
    """The differential-oracle acceptance pin: under concurrent traffic,
    lease expiry, and a device-owner restart mid-stream, total admitted
    <= limit + Σ(outstanding lease budgets at the crash) — and with the
    liability section restored, total admitted <= limit exactly (a
    restart never double-grants)."""

    LIMIT = 100

    def _drive(self, svc, n, threads=3):
        ok = []
        lock = threading.Lock()

        def worker():
            mine = 0
            for _ in range(n):
                code, _, _ = svc.should_rate_limit(_req())
                if code == Code.OK:
                    mine += 1
            with lock:
                ok.append(mine)

        ts_threads = [
            threading.Thread(target=worker) for _ in range(threads)
        ]
        for t in ts_threads:
            t.start()
        for t in ts_threads:
            t.join()
        return sum(ok)

    def _crash_restart(self, tmp_path, restore_leases: bool):
        from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter

        ts = FakeTimeSource(1_000_000)
        store = Store(TestSink())
        base = BaseRateLimiter(
            ts, jitter_rand=random.Random(0), expiration_jitter_max_seconds=0
        )
        table = LeaseTable(base, min_size=4, max_size=32)
        engine1 = _engine(ts)
        cache1 = TpuRateLimitCache(base, engine=engine1, lease_table=table)
        svc1 = RateLimitService(
            runtime=_StaticRuntime(LEASE_YAML),
            cache=cache1,
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=ts,
            lease=table,
        )
        admitted = self._drive(svc1, 12)  # ~36 decisions, leases warm
        snapper = SlabSnapshotter(
            engine1, str(tmp_path), interval_ms=60_000.0, time_source=ts
        )
        snapper.snapshot_once()
        # outstanding budgets at the crash: what frontends may still admit
        # locally, and what an un-floored restart would re-admit
        _, outstanding = table.outstanding()
        _, registry_outstanding = engine1.lease_registry.outstanding()
        cache1.close()

        if not restore_leases:
            (tmp_path / "leases.snap").unlink()

        # the device owner restarts; the frontend (lease table) survives
        engine2 = _engine(ts)
        SlabSnapshotter(
            engine2, str(tmp_path), interval_ms=60_000.0, time_source=ts
        ).restore()
        cache2 = TpuRateLimitCache(base, engine=engine2, lease_table=table)
        svc2 = RateLimitService(
            runtime=_StaticRuntime(LEASE_YAML),
            cache=cache2,
            stats_scope=Store(TestSink()).scope("ratelimit").scope("service"),
            time_source=ts,
            lease=table,
        )
        # run well past the limit, including a lease-expiry boundary
        admitted += self._drive(svc2, 25)
        ts.advance(16)  # expire outstanding leases mid-stream
        admitted += self._drive(svc2, 15)
        cache2.close()
        return admitted, outstanding, registry_outstanding

    def test_liability_restore_never_double_grants(self, tmp_path):
        admitted, _, _ = self._crash_restart(tmp_path, restore_leases=True)
        assert admitted <= self.LIMIT

    def test_overshoot_without_liabilities_bounded_by_budgets(
        self, tmp_path
    ):
        admitted, outstanding, registry_outstanding = self._crash_restart(
            tmp_path, restore_leases=False
        )
        # the bound is the REGISTRY's view at the snapshot: granted minus
        # settled; the frontend's own outstanding is a subset of it
        assert outstanding <= registry_outstanding
        assert admitted <= self.LIMIT + registry_outstanding


class TestRunnerIntegration:
    """LEASE_ENABLED wiring end to end: the runner builds the lease table,
    hot keys answer locally, the degraded probe is on the health surface,
    and the default (disabled) boot wires nothing."""

    BASIC = (
        "domain: lease\n"
        "descriptors:\n"
        "  - key: api_key\n"
        "    rate_limit: {unit: hour, requests_per_unit: 1000000}\n"
    )

    def _settings(self, tmp_path, **kw):
        from api_ratelimit_tpu.settings import Settings

        config_dir = tmp_path / "current" / "rl" / "config"
        if not config_dir.exists():
            config_dir.mkdir(parents=True)
            (config_dir / "lease.yaml").write_text(self.BASIC)
        return Settings(
            port=0,
            grpc_port=0,
            debug_port=0,
            use_statsd=False,
            runtime_path=str(tmp_path / "current"),
            runtime_subdirectory="rl",
            backend_type="tpu",
            tpu_slab_slots=1 << 10,
            tpu_use_pallas=False,
            expiration_jitter_max_seconds=0,
            log_level="ERROR",
            **kw,
        )

    def test_disabled_by_default(self, tmp_path):
        from api_ratelimit_tpu.runner import Runner

        runner = Runner(self._settings(tmp_path), sink=TestSink())
        runner.run_background()
        assert runner.wait_ready(10.0)
        try:
            assert runner.lease_table is None
        finally:
            runner.stop()

    def test_enabled_serves_locally_and_probes_health(self, tmp_path):
        from api_ratelimit_tpu.runner import Runner

        runner = Runner(
            self._settings(tmp_path, lease_enabled=True, lease_min=4),
            sink=TestSink(),
        )
        runner.run_background()
        assert runner.wait_ready(10.0)
        try:
            assert runner.lease_table is not None
            for _ in range(20):
                code, _, _ = runner.service.should_rate_limit(_req())
                assert code == Code.OK
            held, tokens = runner.lease_table.outstanding()
            assert held == 1 and tokens > 0
            engine = runner.service._cache.engine
            assert engine.lease_registry.outstanding()[0] == 1
            # the degraded probe is wired into /healthcheck
            runner.lease_table.note_device_failure(RuntimeError("dark"))
            assert any(
                "lease.degraded" in r
                for r in runner.server.health.degraded_reasons()
            )
            runner.lease_table.note_success()
            assert runner.server.health.degraded_reasons() == []
        finally:
            runner.stop()


class TestDispatchLoopArm:
    def test_leases_ride_the_dispatch_loop(self):
        """Windowed mode (TPU_BATCH_WINDOW > 0): grant riders travel the
        submit rings like any other frame and the liability registers from
        the ticket's verdicts."""
        self._leases_ride(batch_window_seconds=0.0002)

    def test_leases_ride_direct_mode(self):
        """Direct mode (TPU_BATCH_WINDOW=0): the caller's own launch
        carries the grant riders and registers the liability."""
        self._leases_ride(batch_window_seconds=0.0)

    @staticmethod
    def _leases_ride(batch_window_seconds):
        ts = FakeTimeSource(1_000_000)
        engine = SlabDeviceEngine(
            time_source=ts,
            n_slots=1 << 10,
            use_pallas=False,
            buckets=(128,),
            batch_window_seconds=batch_window_seconds,
        )
        assert (engine.dispatch_loop is None) == (batch_window_seconds == 0)
        svc, cache, table, store = _stack(ts, engine=engine)
        try:
            for _ in range(40):
                assert svc.should_rate_limit(_req(key="open"))[0] == Code.OK
            snap = store.debug_snapshot()
            assert snap["ratelimit.lease.local_hits"] >= 30
            assert engine.lease_registry.outstanding()[0] == 1
        finally:
            cache.close()


# ---------------------------------------------------------------------------
# Leases x warm-standby failover (persist/replication.py): grants made by
# the old primary stay locally servable through a promotion, liabilities
# replicate so the promoted standby's floors prevent double-granting,
# settles land against the new epoch, and lease.degraded clears once the
# standby is serving.
# ---------------------------------------------------------------------------

FAILOVER_YAML = """\
domain: lease
descriptors:
  - key: api_key
    rate_limit: {unit: hour, requests_per_unit: 50}
"""


class TestLeaseAcrossFailover:
    INTERVAL_MS = 20.0

    def _owner(self, sock, role, peer=None, start_server=True):
        from api_ratelimit_tpu.backends.sidecar import SlabSidecarServer
        from api_ratelimit_tpu.persist.replication import (
            ReplicationCoordinator,
        )
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        engine = SlabDeviceEngine(
            time_source=RealTimeSource(),
            n_slots=1 << 10,
            use_pallas=False,
            buckets=(128,),
            block_mode=True,
        )
        coord = ReplicationCoordinator(
            engine,
            role,
            peer_address=peer,
            interval_ms=self.INTERVAL_MS,
        )
        server = (
            SlabSidecarServer(sock, engine, repl=coord)
            if start_server
            else None
        )
        coord.start()
        return engine, coord, server

    def _frontend(self, addrs, **client_kw):
        import time as time_mod

        from api_ratelimit_tpu.backends.sidecar import SidecarEngineClient
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        client_kw.setdefault("retries", 2)
        client_kw.setdefault("retry_backoff", 0.002)
        client_kw.setdefault("retry_backoff_max", 0.02)
        client_kw.setdefault("breaker_threshold", 2)
        client_kw.setdefault("breaker_reset", 0.05)
        client = SidecarEngineClient(addrs, **client_kw)
        store = Store(TestSink())
        base = BaseRateLimiter(
            time_source=RealTimeSource(),
            jitter_rand=random.Random(0),
            expiration_jitter_max_seconds=0,
        )
        table = LeaseTable(
            base,
            min_size=4,
            max_size=16,
            scope=store.scope("ratelimit").scope("lease"),
        )
        cache = TpuRateLimitCache(base, engine=client, lease_table=table)
        svc = RateLimitService(
            runtime=_StaticRuntime(FAILOVER_YAML),
            cache=cache,
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=RealTimeSource(),
            lease=table,
        )
        return svc, cache, client, table, store, time_mod

    @staticmethod
    def _wait(cond, timeout=10.0, what="condition"):
        import time as time_mod

        deadline = time_mod.monotonic() + timeout
        while not cond():
            assert time_mod.monotonic() < deadline, f"timed out: {what}"
            time_mod.sleep(0.01)

    def test_leases_survive_promotion_with_replicated_floors(self, tmp_path):
        """Grants from the old primary keep answering locally through the
        crash; the promoted standby's replicated liability floors mean
        total admitted NEVER exceeds the limit (no double-grant), and
        settles land in the NEW primary's registry."""
        p_sock = str(tmp_path / "p.sock")
        s_sock = str(tmp_path / "s.sock")
        p_engine, p_coord, p_server = self._owner(p_sock, "primary")
        s_engine, s_coord, s_server = self._owner(
            s_sock, "standby", peer=p_sock
        )
        svc, cache, client, table, store, time_mod = self._frontend(
            [p_sock, s_sock]
        )
        errors: list[Exception] = []
        admitted = [0]

        def drive(n):
            for _ in range(n):
                try:
                    code, _, _ = svc.should_rate_limit(_req())
                except Exception as e:  # noqa: BLE001 - asserted empty
                    errors.append(e)
                else:
                    if code == Code.OK:
                        admitted[0] += 1

        try:
            drive(20)
            held, outstanding = table.outstanding()
            assert held == 1 and outstanding > 0
            # quiesce until the liability AND the slab have replicated
            self._wait(
                lambda: s_coord.replica_state()[1].shape[0] >= 1,
                what="liability replication",
            )
            time_mod.sleep(3.0 * self.INTERVAL_MS / 1e3)

            p_server.close()
            p_coord.close()

            # the outstanding lease answers locally with the owner DEAD
            budget = outstanding
            before_local = admitted[0]
            drive(min(budget, 4))
            assert errors == []
            assert admitted[0] == before_local + min(budget, 4)

            # past the budget: renewal fails over, the standby promotes
            # with the replicated floors, traffic continues
            drive(60)
            assert errors == [], errors[:3]
            assert s_coord.role == "primary"
            assert s_coord.promotions_total == 1

            # never over-admit: floors make the failover invisible to the
            # limit (50/hour; 80 requests sent; burn only under-admits)
            assert admitted[0] <= 50
            assert admitted[0] >= 45  # and burn stays small

            # settles land against the new epoch's registry
            self._wait(
                lambda: s_engine.lease_registry.settles_total > 0,
                what="settles on the new primary",
            )
        finally:
            client.close()
            for closer in (s_server.close, s_coord.close):
                closer()

    def test_lease_degraded_clears_once_standby_serves(self, tmp_path):
        """The sticky lease.degraded probe: raised while BOTH owners are
        unreachable and the frontend serves from outstanding leases,
        cleared by the first successful device interaction after the
        standby comes up and promotes."""
        from api_ratelimit_tpu.backends.sidecar import SlabSidecarServer

        p_sock = str(tmp_path / "p.sock")
        s_sock = str(tmp_path / "s.sock")
        p_engine, p_coord, p_server = self._owner(p_sock, "primary")
        # the standby COORDINATOR subscribes, but its server is not up
        # yet — so after the primary dies there is nowhere to fail over
        s_engine, s_coord, _ = self._owner(
            s_sock, "standby", peer=p_sock, start_server=False
        )
        svc, cache, client, table, store, time_mod = self._frontend(
            [p_sock, s_sock], retries=0, breaker_threshold=0
        )
        try:
            assert svc.should_rate_limit(_req())[0] == Code.OK  # grant
            self._wait(
                lambda: s_coord.replica_state()[0] is not None,
                what="standby sync",
            )
            p_server.close()
            p_coord.close()

            # budget answers locally; exhausting it needs the device ->
            # CacheError (no fallback configured) + sticky lease.degraded
            saw_error = False
            for _ in range(12):
                try:
                    svc.should_rate_limit(_req())
                except Exception:  # noqa: BLE001 - expected while dark
                    saw_error = True
                    break
            assert saw_error
            assert table.degraded
            assert "lease.degraded" in table.degraded_reason()

            # the standby's server comes up; the next device write fails
            # over, promotes it, succeeds — and the probe clears
            s_server = SlabSidecarServer(s_sock, s_engine, repl=s_coord)
            try:
                code, _, _ = svc.should_rate_limit(_req())
                assert code in (Code.OK, Code.OVER_LIMIT)
                assert s_coord.role == "primary"
                assert not table.degraded
                assert table.degraded_reason() is None
            finally:
                s_server.close()
        finally:
            client.close()
            s_coord.close()
