"""Routed per-shard batching + replicated hot-key tier, on the virtual
8-device CPU mesh.

Three contracts pinned here:

1. ROLLBACK PARITY — `routed=True` (SHARD_ROUTED_BATCHING) is byte-
   identical to the compact SPMD arm: same verdicts, same per-shard slab
   bytes, same health counters, on a mixed Zipf stream with advancing
   clock. `hot_tier=True` with an empty hot set passes the operand
   through UNTOUCHED (same object, no copy) — the HOT_TIER_ENABLED
   rollback arm never perturbs a launch.

2. SPLIT-QUOTA BOUND — the differential fuzz (>= 10k decisions vs
   testing/oracle.py VictimOracle) drives promotion, demotion and
   re-promotion mid-window and asserts false_over == 0 under the
   documented bound: a window FULLY covered by hot membership admits at
   most K*ceil(limit/K); a window where membership changed mid-flight
   admits at most limit + (K-1)*ceil(limit/K) (pre-change home
   admissions up to `limit` can stack with fresh slices 1..K-1 at
   ceil(limit/K) each; slice 0 IS the home row, so it admits ~nothing
   extra). When K divides the limit the fully-covered bound is exactly
   the limit: steady-state over-admission is zero.

3. EXACT SETTLEMENT — demotion folds every salted slice back into the
   home row with the keep-the-newest merge; the merged counter equals
   the unbounded oracle's current-window count exactly (the slab counts
   admitted AND rejected hits, same as the oracle).
"""

import random

import jax
import numpy as np
import pytest

from api_ratelimit_tpu.ops.hashing import hot_slice_fp, set_index
from api_ratelimit_tpu.ops.slab import (
    COL_COUNT,
    COL_FP_HI,
    COL_FP_LO,
    COL_WINDOW,
    find_row_host,
)
from api_ratelimit_tpu.parallel import ShardedSlabEngine, make_mesh
from api_ratelimit_tpu.testing.oracle import VictimOracle

N_DEV = 8
SLOTS = N_DEV * 4096


def _fmix32(x):
    """murmur3 finalizer — bijection on uint32 (the bench's id mixer)."""
    x = np.asarray(x, dtype=np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _packed(ids, now, limit=40, div=50, hits=1):
    ids = np.asarray(ids, dtype=np.uint32)
    b = ids.size
    p = np.zeros((7, b), dtype=np.uint32)
    p[0] = _fmix32(ids)
    p[1] = _fmix32(ids ^ np.uint32(0xA5A5A5A5))
    p[2] = hits
    p[3] = limit
    p[4] = div
    p[6, 0] = now
    p[6, 1] = np.float32(0.8).view(np.uint32)
    p[6, 2] = np.float32(1.0).view(np.uint32)
    return p


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must force the 8-device CPU mesh"
    return make_mesh()


def _zipf_batches(n_batches, b, n_keys=5000, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.zipf(1.1, size=(n_batches, b)) % n_keys).astype(np.uint32)


class TestRoutedParity:
    def test_routed_matches_compact_bytes(self, mesh):
        """The SHARD_ROUTED_BATCHING rollback contract: both arms produce
        the same verdicts AND the same per-shard slab bytes on a mixed
        Zipf stream with window rollover in the middle."""
        compact = ShardedSlabEngine(mesh=mesh, n_slots_global=SLOTS)
        routed = ShardedSlabEngine(mesh=mesh, n_slots_global=SLOTS, routed=True)
        ids = _zipf_batches(6, 512)
        now = 1_000_000
        for i in range(6):
            p = _packed(ids[i], now)
            after_c = compact.step_after_compact(p.copy(), 0xFFFF)
            after_r = routed.step_after_compact(p.copy(), 0xFFFF)
            np.testing.assert_array_equal(after_c, after_r)
            now += 17  # crosses the 50s window boundary mid-stream
        for tc, tr in zip(compact.export_tables(), routed.export_tables()):
            np.testing.assert_array_equal(tc, tr)
        assert compact.health_totals == routed.health_totals

    def test_empty_hot_set_passes_operand_through(self, mesh):
        """HOT_TIER_ENABLED rollback half: with no promoted key the salt
        stage returns the very same operand object — no copy, no byte
        can differ from the hot_tier=False arm."""
        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True
        )
        p = _packed(np.arange(64), 1_000_000)
        out, remap, _epoch = eng._salt_hot(p, np.arange(64))
        assert out is p and remap is None

    def test_hot_tier_without_routing_downgrades(self, mesh, caplog):
        """hot_tier needs routed batching; the engine downgrades with a
        warning instead of corrupting the compact arm."""
        with caplog.at_level("WARNING"):
            eng = ShardedSlabEngine(
                mesh=mesh, n_slots_global=SLOTS, hot_tier=True
            )
        assert eng.hot_tier_enabled is False
        assert any("hot-key tier" in r.message for r in caplog.records)

    def test_routed_rejects_replicated_verbs(self, mesh):
        eng = ShardedSlabEngine(mesh=mesh, n_slots_global=SLOTS, routed=True)
        with pytest.raises(RuntimeError):
            eng.step_packed(_packed(np.arange(8), 1_000_000))

    def test_routed_kills_padding_on_skew(self, mesh):
        """The headline effect, deterministically: one key owning half
        the batch pads every compact lane to its shard's rung; routing +
        the hot tier keeps dead lanes at least 4x lower."""
        compact = ShardedSlabEngine(mesh=mesh, n_slots_global=SLOTS)
        hot = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True
        )
        rng = np.random.default_rng(3)
        b = 4096
        ids = rng.integers(1, 3000, size=b, dtype=np.uint32)
        ids[: b // 2] = 7  # single hot key: 50% of the stream
        p = _packed(ids, 1_000_000)
        hot.promote_hot(int(p[0, 0]), int(p[1, 0]))
        for eng in (compact, hot):
            for _ in range(3):
                eng.step_after_compact(p.copy(), 0xFFFF)
        dead = {}
        for name, eng in (("compact", compact), ("hot", hot)):
            snap = eng.shard_routing_snapshot()
            dead[name] = snap["padded_lanes"] - snap["rows"]
            assert snap["launches"] == 3
            assert snap["rows"] == 3 * b
        assert dead["compact"] >= 4 * dead["hot"], dead

    def test_snapshot_shape(self, mesh):
        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True,
            hot_salt_ways=4,
        )
        eng.step_after_compact(_packed(np.arange(256), 1_000_000), 0xFFFF)
        snap = eng.shard_routing_snapshot()
        assert snap["enabled"] and snap["routed"]
        assert snap["shards"] == N_DEV
        assert len(snap["shard_rows"]) == N_DEV
        assert sum(snap["shard_rows"]) == snap["rows"] == 256
        assert snap["hot_tier"]["salt_ways"] == 4
        for stage in ("bucket_ns", "pad_ns", "launch_ns"):
            assert {"p50", "p99"} <= snap["stage_ns"][stage].keys()


class TestHotSliceFp:
    def test_slot0_is_identity(self):
        lo, hi = hot_slice_fp(0x1234, 0xABCD0001, 0, 8)
        assert (int(lo), int(hi)) == (0x1234, 0xABCD0001)

    def test_slices_cover_all_shards_same_set(self):
        lo0, hi0 = np.uint32(0xDEAD01), np.uint32(0xBEEF02)
        home = int(lo0 ^ hi0) % 8
        owners = set()
        for slot in range(8):
            lo, hi = hot_slice_fp(lo0, hi0, slot, 8)
            assert int(lo) == int(lo0)  # set index preserved
            assert int(set_index(lo, 512)) == int(set_index(lo0, 512))
            owner = int(lo ^ hi) % 8
            assert owner == (home + slot) % 8
            owners.add(owner)
        assert owners == set(range(8))

    def test_non_pow2_shards_rejected(self):
        with pytest.raises(ValueError):
            hot_slice_fp(1, 2, 0, 6)


class TestHotTierFuzz:
    """>= 10k-decision differential fuzz vs the unbounded VictimOracle,
    with promotion, demotion (exact settlement) and re-promotion all
    landing mid-window."""

    LIMIT, DIV, K = 40, 50, 8
    STEPS, B = 30, 400
    HOT_ID = 7  # its _fmix32 fingerprint is the fuzz's hot key

    def test_differential_fuzz(self, mesh):
        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True
        )
        oracle = VictimOracle()
        rng = random.Random(1234)
        q = -(-self.LIMIT // self.K)  # ceil(limit/K)

        hot_id = np.array([self.HOT_ID], dtype=np.uint32)
        hot_lo = int(_fmix32(hot_id)[0])
        hot_hi = int(_fmix32(hot_id ^ np.uint32(0xA5A5A5A5))[0])

        admitted: dict[int, int] = {}  # window -> engine admissions (hot key)
        event_windows: set = set()  # windows with a membership change
        hot_windows: set = set()  # windows that saw any hot-phase traffic
        decisions = 0
        is_hot = False
        now0 = 1_000_000

        for step in range(self.STEPS):
            now = now0 + 2 * step
            window = (now // self.DIV) * self.DIV
            ids = [
                self.HOT_ID if rng.random() < 0.4 else rng.randrange(10, 2010)
                for _ in range(self.B)
            ]
            p = _packed(np.array(ids, dtype=np.uint32), now, limit=self.LIMIT,
                        div=self.DIV)
            items = [
                (int(p[0, i]), int(p[1, i]), 1, self.LIMIT, self.DIV, 0)
                for i in range(self.B)
            ]
            after = eng.step_after_compact(p.copy(), 0xFFFF)
            want = oracle.step_batch(items, now)
            for i, key_id in enumerate(ids):
                got = 2 if int(after[i]) > self.LIMIT else 1
                decisions += 1
                if key_id != self.HOT_ID or not is_hot:
                    # cold rows — and the hot key while demoted — must
                    # match the oracle decision-for-decision
                    assert got == want[i], (step, i, key_id, got, want[i])
                else:
                    hot_windows.add(window)
                    if got == 1:
                        admitted[window] = admitted.get(window, 0) + 1

            if step == 5:
                assert eng.promote_hot(hot_lo, hot_hi)
                is_hot = True
                event_windows.add(window)
            elif step == 18:
                rep = eng.demote_hot(hot_lo, hot_hi, now=now)
                is_hot = False
                event_windows.add(window)
                # EXACT settlement: merged home counter == the unbounded
                # oracle's current-window count (slab counts admitted and
                # rejected hits alike)
                assert rep["demoted"] and rep["landed"], rep
                assert rep["count"] == oracle.count(hot_lo, hot_hi), rep
                home = (hot_lo ^ hot_hi) % N_DEV
                tab = eng.export_tables()[home]
                ridx = find_row_host(tab, hot_lo, hot_hi, eng.ways)
                assert ridx >= 0
                assert int(tab[ridx, COL_COUNT]) == rep["count"]
                assert int(tab[ridx, COL_WINDOW]) == window
                assert (int(tab[ridx, COL_FP_LO]), int(tab[ridx, COL_FP_HI])) \
                    == (hot_lo, hot_hi)
            elif step == 24:
                assert eng.promote_hot(hot_lo, hot_hi)
                is_hot = True
                event_windows.add(window)

        assert decisions >= 10_000

        # the split-quota bound, window by window: false_over == 0
        false_over = 0
        for window, n in admitted.items():
            if window in event_windows:
                bound = self.LIMIT + (self.K - 1) * q
            else:
                bound = self.K * q
            false_over += max(0, n - bound)
        assert false_over == 0, (admitted, event_windows)

        # at least one window was FULLY covered by hot membership, and it
        # admitted exactly the full split quota K*ceil(limit/K) — which
        # equals the limit itself here (K | limit): steady-state
        # over-admission is zero, and the tier is actually admitting
        full = [w for w in hot_windows if w not in event_windows]
        assert full, "fuzz never produced a fully-hot window"
        assert self.K * q == self.LIMIT  # K divides the limit by design
        for w in full:
            assert admitted[w] == self.K * q, (w, admitted)

        snap = eng.shard_routing_snapshot()["hot_tier"]
        assert snap == {
            "enabled": True,
            "salt_ways": self.K,
            "keys": 1,
            "epoch": 3,
            "promotions": 2,
            "demotions": 1,
            "settle_drops": 0,
        }


class TestSketchFedPromotion:
    """Satellite: the host-side top-K fallback feeds the tier — drains
    promote keys above hot_min_count and demote (with exact settlement)
    once they decay below the hysteresis band."""

    def test_drain_promotes_then_decay_demotes(self, mesh):
        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True,
            hotkey_lanes=32, hotkey_k=8, hot_min_count=100,
        )
        rng = np.random.default_rng(11)
        ids = rng.integers(100, 600, size=512, dtype=np.uint32)
        ids[:200] = 7
        p = _packed(ids, 1_000_000)
        eng.step_after_compact(p.copy(), 0xFFFF)

        seen = []
        eng.add_hotkey_listener(lambda top, fps: seen.append((top, fps)))
        top = eng.drain_hotkeys()
        assert top[0][2] >= 200 and len(seen) == 1
        hot_lo, hot_hi = top[0][0], top[0][1]
        assert eng.shard_routing_snapshot()["hot_tier"]["keys"] == 1
        assert ((hot_hi << 32) | hot_lo) in eng.hot_fps

        # decay with no refresh: 200 -> 100 -> 50 -> 25 drops the key
        # below hot_min_count // 2 and the drain demotes it
        for _ in range(4):
            eng.drain_hotkeys()
        snap = eng.shard_routing_snapshot()["hot_tier"]
        assert snap["keys"] == 0 and snap["demotions"] == 1

    def test_snapshot_matches_single_device_shape(self, mesh):
        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True,
            hotkey_lanes=32, hotkey_k=4,
        )
        assert eng.hotkeys_enabled
        eng.step_after_compact(_packed(np.full(64, 3), 1_000_000), 0xFFFF)
        eng.drain_hotkeys()
        snap = eng.hotkeys_snapshot()
        assert snap["enabled"] and snap["drains"] == 1
        assert snap["k"] == 4 and snap["lanes"] == 32
        assert snap["top"][0]["count"] == 64
        assert len(snap["top"][0]["fp"]) == 16


class TestShardRoutingStats:
    def test_gauges_export(self, mesh):
        from api_ratelimit_tpu.backends.dispatch import ShardRoutingStats
        from api_ratelimit_tpu.stats import Store, TestSink

        eng = ShardedSlabEngine(
            mesh=mesh, n_slots_global=SLOTS, routed=True, hot_tier=True
        )
        eng.step_after_compact(_packed(np.arange(300), 1_000_000), 0xFFFF)
        eng.promote_hot(1, 2)
        sink = TestSink()
        store = Store(sink)
        gen = ShardRoutingStats(
            eng.shard_routing_snapshot,
            store.scope("ratelimit").scope("shard"),
            N_DEV,
        )
        gen.generate_stats()
        store.flush()
        assert sink.gauges["ratelimit.shard.rows"] == 300
        assert sink.gauges["ratelimit.shard.launches"] == 1
        assert sink.gauges["ratelimit.shard.hot_keys"] == 1
        assert sink.gauges["ratelimit.shard.hot_epoch"] == 1
        assert "ratelimit.shard.padding_waste_pct" in sink.gauges
        per_shard = sum(
            sink.gauges[f"ratelimit.shard.rows.shard_{d}"]
            for d in range(N_DEV)
        )
        assert per_shard == 300
