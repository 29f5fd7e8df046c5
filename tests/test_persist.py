"""Warm-restart persistence: snapshot format, reconcile rules, snapshotter
lifecycle, engine export/import, chaos (fault-injected) rejection, and the
offline inspect CLI.

The durability contract under test: a valid snapshot restores live counters
exactly; ANY invalid snapshot (bad magic/version/CRC, torn payload, wrong
topology) is rejected and the slab boots cold — counted, logged, never a
crash. Every restore-time loss fails open (an undercount can only
under-enforce), matching the slab's documented lossy posture.
"""

import importlib.util
import json
import os
import sys
import zlib

import numpy as np
import pytest

from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, _Item
from api_ratelimit_tpu.persist.snapshot import (
    HEADER_SIZE,
    MAGIC,
    SNAPSHOT_VERSION,
    SnapshotError,
    load_snapshot,
    read_header,
    reconcile_rows,
    write_snapshot,
)
from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter, snapshot_paths
from api_ratelimit_tpu.stats import Store, TestSink
from api_ratelimit_tpu.testing.faults import FaultInjector
from api_ratelimit_tpu.utils import FakeTimeSource

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NOW = 1_700_000_000


def _table(n=64, rows=()):
    """A slab table with the given (slot, fp_lo, count, window, expire,
    divider) rows planted."""
    t = np.zeros((n, 8), dtype=np.uint32)
    for slot, fp_lo, count, window, expire, divider in rows:
        t[slot] = [fp_lo, fp_lo ^ 0xABCD, count, window, expire, divider, 0, 0]
    return t


def _row(slot, count=3, window=NOW - (NOW % 60), expire=NOW + 90, divider=60):
    return (slot, 0x1111 + slot, count, window, expire, divider)


class TestSnapshotFormat:
    def test_round_trip(self, tmp_path):
        table = _table(rows=[_row(3), _row(17, count=9)])
        path = str(tmp_path / "slab.snap")
        n = write_snapshot(path, table, created_at=NOW, shard_index=2,
                           shard_count=4)
        assert n == os.path.getsize(path) == HEADER_SIZE + table.nbytes
        header, got = load_snapshot(path)
        assert (header.version, header.created_at) == (SNAPSHOT_VERSION, NOW)
        assert (header.shard_index, header.shard_count) == (2, 4)
        assert (header.n_slots, header.row_width) == (64, 8)
        np.testing.assert_array_equal(got, table)

    def test_read_header_only(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        header = read_header(path)
        assert header.n_slots == 64
        assert header.payload_len == 64 * 8 * 4

    def test_atomic_write_leaves_no_temp(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        write_snapshot(path, _table(rows=[_row(1)]), created_at=NOW + 1)
        assert sorted(os.listdir(tmp_path)) == ["slab.snap"]
        assert read_header(path).created_at == NOW + 1

    def test_bad_magic_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        raw = bytearray(open(path, "rb").read())
        raw[:8] = b"NOTASNAP"
        open(path, "wb").write(bytes(raw))
        with pytest.raises(SnapshotError, match="magic"):
            load_snapshot(path)

    def test_version_mismatch_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        raw = bytearray(open(path, "rb").read())
        raw[8] = 99  # version field
        # re-stamp the header CRC so ONLY the version check can fire —
        # proving the version gate works even on an internally-consistent
        # future-format file
        import struct

        head = bytes(raw[:56])
        raw[56:60] = struct.pack("<I", zlib.crc32(head))
        open(path, "wb").write(bytes(raw))
        with pytest.raises(SnapshotError, match="version 99"):
            load_snapshot(path)

    def test_header_corruption_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        raw = bytearray(open(path, "rb").read())
        raw[20] ^= 0xFF  # inside created_at
        open(path, "wb").write(bytes(raw))
        with pytest.raises(SnapshotError, match="header CRC"):
            load_snapshot(path)

    def test_payload_corruption_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(rows=[_row(5)]), created_at=NOW)
        raw = bytearray(open(path, "rb").read())
        raw[HEADER_SIZE + 40] ^= 0x01
        open(path, "wb").write(bytes(raw))
        with pytest.raises(SnapshotError, match="payload CRC"):
            load_snapshot(path)

    def test_torn_payload_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(), created_at=NOW)
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 2])
        with pytest.raises(SnapshotError, match="torn"):
            load_snapshot(path)

    def test_truncated_header_rejected(self, tmp_path):
        path = str(tmp_path / "slab.snap")
        open(path, "wb").write(MAGIC)
        with pytest.raises(SnapshotError, match="truncated header"):
            load_snapshot(path)

    def test_missing_file_raises_snapshot_error(self, tmp_path):
        with pytest.raises(SnapshotError):
            load_snapshot(str(tmp_path / "nope.snap"))

    def test_column_constants_mirror_ops_slab(self):
        """persist redeclares the row format so offline tools skip the jax
        import; the mirror must never drift from the device layout."""
        from api_ratelimit_tpu.ops import slab as ops_slab
        from api_ratelimit_tpu.persist import snapshot as persist_snap

        assert persist_snap.ROW_WIDTH == ops_slab.ROW_WIDTH
        for col in ("COL_FP_LO", "COL_FP_HI", "COL_COUNT", "COL_WINDOW",
                    "COL_EXPIRE", "COL_DIVIDER"):
            assert getattr(persist_snap, col) == getattr(ops_slab, col), col


class TestReconcile:
    def test_live_row_inside_window_kept(self):
        table = _table(rows=[_row(3, count=7)])
        out, stats = reconcile_rows(table, NOW)
        assert stats == {"restored": 1, "dropped_expired": 0,
                         "dropped_window": 0}
        np.testing.assert_array_equal(out, table)

    def test_expired_row_dropped(self):
        table = _table(rows=[_row(3, expire=NOW - 1)])
        out, stats = reconcile_rows(table, NOW)
        assert stats["dropped_expired"] == 1 and stats["restored"] == 0
        assert not out.any()

    def test_window_ended_but_ttl_pinned_dropped(self):
        # jittered TTL still open, fixed window closed: the row carries no
        # decision state (next touch rolls to base 0), so restore drops it
        # — the same population the in-kernel eviction scan reclaims
        # ahead of any live-window row
        table = _table(rows=[_row(3, window=NOW - 120, expire=NOW + 200)])
        out, stats = reconcile_rows(table, NOW)
        assert stats["dropped_window"] == 1 and stats["restored"] == 0
        assert not out.any()

    def test_legacy_divider_zero_keeps_ttl_rule(self):
        table = _table(rows=[_row(3, window=NOW - 120, divider=0)])
        _out, stats = reconcile_rows(table, NOW)
        assert stats["restored"] == 1  # TTL-only rule for pre-divider rows

    def test_empty_rows_not_counted(self):
        out, stats = reconcile_rows(_table(), NOW)
        assert stats == {"restored": 0, "dropped_expired": 0,
                         "dropped_window": 0}
        assert not out.any()


def _engine(ts, n_slots=1 << 10):
    return SlabDeviceEngine(
        ts, n_slots=n_slots, use_pallas=False, buckets=(128,)
    )


def _hit(engine, fp=0xBEEF, n=1, limit=10, divider=1000):
    return engine.submit(
        [_Item(fp=fp, hits=1, limit=limit, divider=divider, jitter=0)] * n
    )


class TestSnapshotter:
    def test_snapshot_restore_round_trip(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=4)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                               time_source=ts)
        assert snap.snapshot_once() > 0
        assert snap.writes_total == 1
        assert os.path.exists(tmp_path / "slab.snap")

        eng2 = _engine(ts)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        stats = snap2.restore()
        assert stats["restored"] == 1  # one live slot row
        assert _hit(eng2) == [5]  # counter continues where eng left it

    def test_no_snapshot_boots_cold(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                               time_source=ts)
        assert snap.restore() == {"restored": False, "reason": "no snapshot"}
        assert snap.load_rejected_total == 0  # absence is not corruption

    def test_topology_mismatch_boots_cold(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts, n_slots=1 << 10)
        _hit(eng, n=3)
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts).snapshot_once()

        small = _engine(ts, n_slots=1 << 9)
        snap = SlabSnapshotter(small, str(tmp_path), interval_ms=1000,
                               time_source=ts)
        stats = snap.restore()
        assert stats["restored"] is False
        assert snap.load_rejected_total == 1
        assert _hit(small) == [1]  # cold

    def test_corrupt_snapshot_boots_cold(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=3)
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts).snapshot_once()
        path = tmp_path / "slab.snap"
        raw = bytearray(path.read_bytes())
        raw[HEADER_SIZE + 8] ^= 0xFF
        path.write_bytes(bytes(raw))

        eng2 = _engine(ts)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        assert snap2.restore()["restored"] is False
        assert snap2.load_rejected_total == 1
        assert _hit(eng2) == [1]

    def test_restore_reconciles_against_clock(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=4, divider=1000)
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts).snapshot_once()
        # restart far in the future: the window (and TTL) are long gone
        ts2 = FakeTimeSource(NOW + 5000)
        eng2 = _engine(ts2)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts2)
        stats = snap2.restore()
        # loaded fine ('reason' absent) but the row was reconciled away
        assert "reason" not in stats
        assert stats["restored"] == 0 and stats["dropped_expired"] == 1
        assert _hit(eng2) == [1]  # fresh window, fresh count

    def test_drain_takes_final_snapshot(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=2)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=60_000,
                               time_source=ts)
        assert snap.drain() > 0
        assert snap.writes_total == 1
        # the engine is quiesced: submits now fail (batcher drained)
        from api_ratelimit_tpu.limiter.cache import CacheError

        with pytest.raises(CacheError):
            _hit(eng)
        # and the next process warm-boots the drained state exactly
        eng2 = _engine(ts)
        SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                        time_source=ts).restore()
        assert _hit(eng2) == [3]

    def test_periodic_thread_writes(self, tmp_path):
        import time as _time

        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=20,
                               time_source=ts)
        snap.start()
        try:
            deadline = _time.monotonic() + 5.0
            while snap.writes_total < 2 and _time.monotonic() < deadline:
                _time.sleep(0.01)
        finally:
            snap.stop()
        assert snap.writes_total >= 2
        assert os.path.exists(tmp_path / "slab.snap")

    def test_stats_and_age(self, tmp_path):
        ts = FakeTimeSource(NOW)
        store = Store(TestSink())
        eng = _engine(ts)
        _hit(eng, n=2)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                               stale_after_ms=5000, time_source=ts,
                               scope=store.scope("ratelimit"))
        assert snap.age_seconds() == -1.0  # never started, never succeeded
        assert snap.stale_reason() is None
        snap.snapshot_once()
        gauges = store.metrics_snapshot()["gauges"]
        counters = store.metrics_snapshot()["counters"]
        assert counters["ratelimit.snapshot.writes"] == 1
        assert gauges["ratelimit.snapshot.bytes"] > 0
        ts.advance(3)
        store.flush()  # runs the age generator
        assert store.metrics_snapshot()["gauges"][
            "ratelimit.snapshot.age_seconds"
        ] == 3
        assert snap.stale_reason() is None
        ts.advance(10)  # past the 5s staleness budget
        reason = snap.stale_reason()
        assert reason is not None and "stale" in reason

        eng2 = _engine(ts)
        store2 = Store(TestSink())
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts,
                                scope=store2.scope("ratelimit"))
        snap2.restore()
        g2 = store2.metrics_snapshot()["gauges"]
        assert g2["ratelimit.snapshot.restore_rows"] == 1
        assert g2["ratelimit.snapshot.restore_dropped_expired"] == 0

    def test_snapshot_under_concurrent_traffic(self, tmp_path):
        """The quiesce-and-copy contract under fire: submits hammer the
        engine from several threads while a snapshot loop runs flat out.
        No crash, no lost increments (the copy never aliases a donated
        buffer), and the surviving file is itself valid and loadable."""
        import threading

        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=60_000,
                               time_source=ts)
        n_threads, per = 4, 50

        def worker():
            for _ in range(per):
                _hit(eng)

        stop = threading.Event()

        def snapper():
            while not stop.is_set():
                snap.snapshot_once()

        snapper_t = threading.Thread(target=snapper)
        workers = [threading.Thread(target=worker) for _ in range(n_threads)]
        snapper_t.start()
        for t in workers:
            t.start()
        for t in workers:
            t.join()
        stop.set()
        snapper_t.join()
        assert snap.writes_total > 0 and snap.write_errors_total == 0
        assert _hit(eng) == [n_threads * per + 1]  # every increment counted
        _header, table = load_snapshot(str(tmp_path / "slab.snap"))
        assert table.any()

    def test_shard_file_names(self, tmp_path):
        assert snapshot_paths("d", 1) == [os.path.join("d", "slab.snap")]
        assert snapshot_paths("d", 2) == [
            os.path.join("d", "slab.00-of-02.snap"),
            os.path.join("d", "slab.01-of-02.snap"),
        ]


class TestShardedSnapshot:
    @pytest.fixture()
    def mesh(self):
        import jax

        assert len(jax.devices()) == 8
        from api_ratelimit_tpu.parallel import make_mesh

        return make_mesh()

    @staticmethod
    def _packed(b, now=NOW):
        packed = np.zeros((7, b), dtype=np.uint32)
        ids = np.arange(b, dtype=np.uint64)
        packed[0] = (ids * 0x9E3779B185EBCA87 & 0xFFFFFFFF).astype(np.uint32)
        packed[1] = ((ids ^ 0x77) * 0xC2B2AE3D27D4EB4F & 0xFFFFFFFF).astype(
            np.uint32
        )
        packed[2] = 1
        packed[3] = 100
        packed[4] = 1000
        packed[6, 0] = np.uint32(now)
        packed[6, 1] = np.float32(0.8).view(np.uint32)
        return packed

    def test_per_shard_files_and_warm_continuation(self, tmp_path, mesh):
        from api_ratelimit_tpu.parallel import ShardedSlabEngine

        ts = FakeTimeSource(NOW)
        # ways pinned: the exact-continuation assert needs every key to
        # survive the single fresh 128-key batch, and this fixture's
        # synthetic fingerprints are spread for the 128-lane geometry
        # (at the CPU auto default of 8 they alias pairwise on the way
        # rotation and half the batch drops as counted way contention)
        eng = ShardedSlabEngine(mesh=mesh, n_slots_global=8 * 256, ways=128)
        packed = self._packed(128)
        first = np.asarray(eng.step_after_compact(packed.copy(), cap=0xFFFF))
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                               time_source=ts)
        snap.snapshot_once()
        files = sorted(os.listdir(tmp_path))
        assert files == [f"slab.{i:02d}-of-08.snap" for i in range(8)]

        eng2 = ShardedSlabEngine(mesh=mesh, n_slots_global=8 * 256, ways=128)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        assert snap2.restore()["restored"] == 128
        second = np.asarray(eng2.step_after_compact(packed.copy(), cap=0xFFFF))
        np.testing.assert_array_equal(second, first + 1)

    def test_one_bad_shard_rejects_whole_set(self, tmp_path, mesh):
        from api_ratelimit_tpu.parallel import ShardedSlabEngine

        ts = FakeTimeSource(NOW)
        eng = ShardedSlabEngine(mesh=mesh, n_slots_global=8 * 256)
        eng.step_after_compact(self._packed(64), cap=0xFFFF)
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts).snapshot_once()
        bad = tmp_path / "slab.03-of-08.snap"
        raw = bytearray(bad.read_bytes())
        raw[HEADER_SIZE + 4] ^= 0x55
        bad.write_bytes(bytes(raw))

        eng2 = ShardedSlabEngine(mesh=mesh, n_slots_global=8 * 256)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        assert snap2.restore()["restored"] is False
        assert snap2.load_rejected_total == 1
        assert eng2.health_snapshot(now=NOW)["live_slots"] == 0  # cold


class TestSnapshotFaultInjection:
    """The snapshot.write / snapshot.load chaos sites: a fault-injected bad
    snapshot must be REJECTED at load and fall back to a cold slab, counted
    in snapshot.load_rejected — never a crash, never a corrupt restore."""

    def test_write_error_counted_not_fatal(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng)
        faults = FaultInjector.from_spec("snapshot.write:error:1.0")
        snap = SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                               time_source=ts, fault_injector=faults)
        assert snap.snapshot_once() == 0
        assert snap.write_errors_total == 1
        assert not os.path.exists(tmp_path / "slab.snap")
        faults.clear()
        assert snap.snapshot_once() > 0  # outage over, writes recover

    def test_torn_write_rejected_at_load(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=2)
        faults = FaultInjector.from_spec("snapshot.write:torn_write:1.0")
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts,
                        fault_injector=faults).snapshot_once()
        assert faults.fired().get("snapshot.write:torn_write") == 1

        eng2 = _engine(ts)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        assert snap2.restore()["restored"] is False
        assert snap2.load_rejected_total == 1
        assert _hit(eng2) == [1]  # cold boot, service keeps working

    def test_corrupt_write_rejected_at_load(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=2)
        faults = FaultInjector.from_spec("snapshot.write:corrupt:1.0")
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts,
                        fault_injector=faults).snapshot_once()

        eng2 = _engine(ts)
        snap2 = SlabSnapshotter(eng2, str(tmp_path), interval_ms=1000,
                                time_source=ts)
        assert snap2.restore()["restored"] is False
        assert snap2.load_rejected_total == 1

    def test_load_faults_reject_good_file(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=2)
        SlabSnapshotter(eng, str(tmp_path), interval_ms=1000,
                        time_source=ts).snapshot_once()
        for spec in ("snapshot.load:error:1.0", "snapshot.load:corrupt:1.0"):
            eng2 = _engine(ts)
            snap2 = SlabSnapshotter(
                eng2, str(tmp_path), interval_ms=1000, time_source=ts,
                fault_injector=FaultInjector.from_spec(spec),
            )
            assert snap2.restore()["restored"] is False, spec
            assert snap2.load_rejected_total == 1, spec
            assert _hit(eng2) == [1], spec

    def test_new_fault_kinds_parse_and_junk_rejected(self):
        from api_ratelimit_tpu.testing.faults import parse_fault_spec

        rules = parse_fault_spec(
            "snapshot.write:torn_write:0.5,snapshot.load:corrupt:1.0"
        )
        assert [(r.site, r.kind) for r in rules] == [
            ("snapshot.write", "torn_write"),
            ("snapshot.load", "corrupt"),
        ]
        with pytest.raises(ValueError):
            parse_fault_spec("snapshot.write:torn_write:1.5")  # prob > 1
        with pytest.raises(ValueError):
            parse_fault_spec("snapshot.write:shred:1.0")  # unknown kind


def _load_inspect():
    spec = importlib.util.spec_from_file_location(
        "snapshot_inspect", os.path.join(REPO, "tools", "snapshot_inspect.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestSnapshotInspectCli:
    def test_reports_valid_file(self, tmp_path, capsys):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(rows=[_row(3, count=7), _row(9)]),
                       created_at=NOW)
        tool = _load_inspect()
        assert tool.main([path]) == 0
        out = capsys.readouterr().out
        assert "CRC OK" in out and "occupied=2" in out

    def test_json_mode(self, tmp_path, capsys):
        path = str(tmp_path / "slab.snap")
        write_snapshot(path, _table(rows=[_row(3, count=7)]), created_at=NOW,
                       shard_index=1, shard_count=2)
        tool = _load_inspect()
        assert tool.main(["--json", "--now", str(NOW), path]) == 0
        reports = json.loads(capsys.readouterr().out)
        assert reports[0]["valid"] is True
        assert reports[0]["shard"] == "1/2"
        assert reports[0]["rows"]["occupied"] == 1
        assert reports[0]["rows"]["restorable"] == 1
        assert reports[0]["rows"]["count_sum"] == 7

    def test_invalid_file_exits_nonzero(self, tmp_path, capsys):
        good = str(tmp_path / "good.snap")
        bad = str(tmp_path / "bad.snap")
        write_snapshot(good, _table(), created_at=NOW)
        write_snapshot(bad, _table(), created_at=NOW)
        raw = bytearray(open(bad, "rb").read())
        raw[HEADER_SIZE] ^= 0xFF
        open(bad, "wb").write(bytes(raw))
        tool = _load_inspect()
        assert tool.main(["--json", good, bad]) == 1
        reports = json.loads(capsys.readouterr().out)
        assert [r["valid"] for r in reports] == [True, False]
        assert "CRC" in reports[1]["error"]

    def test_cli_never_imports_jax(self):
        """Deploy tooling inspects snapshots on jax-less boxes; importing
        the CLI (and the persist package under it) must not pull jax in."""
        import subprocess

        code = (
            "import sys; sys.path.insert(0, %r); "
            "import tools.snapshot_inspect; "
            "assert 'jax' not in sys.modules, 'CLI imported jax'; "
            "print('ok')" % REPO
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == "ok"


class TestSetMigration:
    """The boot migration into the set-associative geometry: v1
    (open-addressed, PR 4-era) snapshots — and v2 snapshots written under
    a different SLAB_WAYS — are REHASHED into the running layout at
    restore, never rejected, with live counters preserved exactly."""

    def test_migrate_places_rows_by_set_index(self):
        from api_ratelimit_tpu.persist.snapshot import migrate_rows_to_sets

        # 64 rows / 8 ways = 8 sets; a row whose fp_lo selects set 3 sits
        # at (open-addressed) slot 0 and must land inside rows [24, 32)
        t = _table(rows=[(0, 0x13, 5, NOW - 30, NOW + 90, 60)])
        out, stats = migrate_rows_to_sets(t, ways=8)
        assert stats == {"placed": 1, "dropped_overflow": 0}
        placed = np.flatnonzero(out.any(axis=1))
        assert placed.tolist() == [(0x13 & 7) * 8]  # set 3, way 0
        np.testing.assert_array_equal(out[placed[0]], t[0])

    def test_overflowing_set_drops_lowest_counts(self):
        from api_ratelimit_tpu.persist.snapshot import migrate_rows_to_sets

        # 8 rows / 2 ways = 4 sets; six rows all hash to set 1 — the two
        # LOWEST counts are the overflow casualties (the same
        # least-valuable-first rule the in-kernel eviction applies)
        rows = [
            (slot, 0x10 * slot + 1, count, NOW - 30, NOW + 90, 60)
            for slot, count in zip(range(6), (4, 9, 1, 7, 2, 6))
        ]
        t = _table(n=8, rows=rows)
        out, stats = migrate_rows_to_sets(t, ways=2)
        assert stats == {"placed": 2, "dropped_overflow": 4}
        kept = sorted(out[out.any(axis=1)][:, 2].tolist())
        assert kept == [7, 9]

    def test_set_occupancy_histogram(self):
        from api_ratelimit_tpu.persist.snapshot import set_occupancy_histogram

        t = _table(
            n=16,
            rows=[
                (0, 1, 3, NOW - 30, NOW + 90, 60),
                (1, 2, 3, NOW - 30, NOW + 90, 60),
                (4, 3, 3, NOW - 30, NOW - 10, 60),  # expired
            ],
        )
        hist = set_occupancy_histogram(t, ways=4)  # 4 sets
        assert hist.tolist() == [2, 1, 1, 0, 0]  # by occupied rows
        hist_live = set_occupancy_histogram(t, ways=4, now=NOW)
        assert hist_live.tolist() == [3, 0, 1, 0, 0]

    def test_v1_snapshot_round_trips_through_boot_migration(self, tmp_path):
        """THE regression pin for the acceptance criterion: a PR 4-era v1
        fixture (row at its open-addressed probe slot, version 1, no ways
        stamp) restores through the migration with zero dropped live
        counters, and the counter continues where it left off."""
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)  # 1024 slots, auto ways (4 on the CPU suite)
        window = NOW - (NOW % 1000)
        table = np.zeros((1024, 8), dtype=np.uint32)
        # fp 0xBEEF's OLD home: probe candidate 0 = fp_lo mod n_slots —
        # NOT its set-associative home (set fp_lo mod n_sets)
        table[0xBEEF % 1024] = [0xBEEF, 0, 4, window, NOW + 1000, 1000, 0, 0]
        write_snapshot(
            str(tmp_path / "slab.snap"), table, created_at=NOW, version=1
        )
        header = read_header(str(tmp_path / "slab.snap"))
        assert header.version == 1 and header.ways == 0

        snap = SlabSnapshotter(
            eng, str(tmp_path), interval_ms=1000, time_source=ts
        )
        stats = snap.restore()
        assert "reason" not in stats  # loaded, not rejected
        assert stats["restored"] == 1
        assert stats["migrated"] == 1
        assert stats["dropped_overflow"] == 0  # zero dropped live counters
        assert _hit(eng) == [5]  # 4 restored + 1: the counter continued

    def test_v2_written_under_different_ways_rehashes(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = SlabDeviceEngine(
            ts, n_slots=1 << 10, ways=32, use_pallas=False, buckets=(128,)
        )
        _hit(eng, n=3)
        SlabSnapshotter(
            eng, str(tmp_path), interval_ms=1000, time_source=ts
        ).snapshot_once()
        assert read_header(str(tmp_path / "slab.snap")).ways == 32

        eng2 = _engine(ts)  # default ways=128: geometry changed
        stats = SlabSnapshotter(
            eng2, str(tmp_path), interval_ms=1000, time_source=ts
        ).restore()
        assert stats["restored"] == 1 and stats["migrated"] == 1
        assert _hit(eng2) == [4]

    def test_same_geometry_restore_skips_rehash(self, tmp_path):
        ts = FakeTimeSource(NOW)
        eng = _engine(ts)
        _hit(eng, n=2)
        SlabSnapshotter(
            eng, str(tmp_path), interval_ms=1000, time_source=ts
        ).snapshot_once()
        header = read_header(str(tmp_path / "slab.snap"))
        assert header.version == SNAPSHOT_VERSION and header.ways == eng.ways

        eng2 = _engine(ts)
        stats = SlabSnapshotter(
            eng2, str(tmp_path), interval_ms=1000, time_source=ts
        ).restore()
        assert stats["restored"] == 1 and stats["migrated"] == 0
        assert _hit(eng2) == [3]

    def test_restore_counts_set_overflow(self, tmp_path):
        """A v1 fixture denser than one set can hold: the lowest-count
        rows drop (counted as dropped_overflow), the highest survive."""
        ts = FakeTimeSource(NOW)
        eng = SlabDeviceEngine(
            ts, n_slots=8, ways=4, use_pallas=False, buckets=(8,)
        )
        window = NOW - (NOW % 1000)
        table = np.zeros((8, 8), dtype=np.uint32)
        # six live rows, all fp_lo even => all in set 0 of 2 (8 slots / 4)
        for slot, (fp_lo, count) in enumerate(
            [(2, 1), (4, 2), (6, 3), (8, 4), (10, 5), (12, 6)]
        ):
            table[slot] = [fp_lo, 0, count, window, NOW + 1000, 1000, 0, 0]
        write_snapshot(
            str(tmp_path / "slab.snap"), table, created_at=NOW, version=1
        )
        stats = SlabSnapshotter(
            eng, str(tmp_path), interval_ms=1000, time_source=ts
        ).restore()
        assert stats["restored"] == 6  # live rows in the file
        assert stats["migrated"] == 4  # what fit into the 4-way set
        assert stats["dropped_overflow"] == 2
        # survivors continue exactly; casualties fail open and restart
        assert _hit(eng, fp=12, divider=1000) == [7]
        assert _hit(eng, fp=2, divider=1000) == [1]


class TestSnapshotInspectSetView:
    def test_set_occupancy_section_renders(self, tmp_path, capsys):
        ts = FakeTimeSource(NOW)
        # explicit geometry so the rendered numbers are deterministic on
        # any platform (the engine default auto-selects by device)
        eng = SlabDeviceEngine(
            ts, n_slots=1 << 10, ways=128, use_pallas=False, buckets=(128,)
        )
        _hit(eng, n=2)
        SlabSnapshotter(
            eng, str(tmp_path), interval_ms=1000, time_source=ts
        ).snapshot_once()
        tool = _load_inspect()
        path = str(tmp_path / "slab.snap")
        assert tool.main(["--json", "--now", str(NOW), path]) == 0
        report = json.loads(capsys.readouterr().out)[0]
        assert report["version"] == SNAPSHOT_VERSION
        assert report["needs_migration"] is False
        sets = report["sets"]
        assert sets["ways"] == 128 and sets["n_sets"] == 8
        # one occupied row: 7 empty sets, 1 set holding 1 row
        assert sets["occupancy_histogram"] == {"0": 7, "1": 1}
        assert sets["full_sets"] == 0 and sets["max_set_occupancy"] == 1
        # the human rendering mentions the set geometry
        assert tool.main(["--now", str(NOW), path]) == 0
        out = capsys.readouterr().out
        assert "8 x 128-way" in out

    def test_v1_file_reports_migration_needed(self, tmp_path, capsys):
        table = np.zeros((64, 8), dtype=np.uint32)
        table[5] = [0x15, 0, 2, NOW - 30, NOW + 90, 60, 0, 0]
        path = str(tmp_path / "old.snap")
        write_snapshot(path, table, created_at=NOW, version=1)
        tool = _load_inspect()
        assert tool.main(["--json", "--now", str(NOW), path]) == 0
        report = json.loads(capsys.readouterr().out)[0]
        assert report["valid"] is True  # old versions load, never reject
        assert report["version"] == 1
        assert report["needs_migration"] is True
        assert report["sets"] is None  # placement is pre-migration
        assert report["rows"]["restorable"] == 1
