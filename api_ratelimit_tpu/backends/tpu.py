"""BACKEND_TYPE=tpu — the flagship cache backend.

Replaces the reference's Redis hot path (src/redis/fixed_cache_impl.go) with
an in-process TPU device program: descriptors are fingerprinted on the host
(ops/hashing.py, xxhash), concurrent requests coalesce in the dispatch loop
(backends/dispatch.py — the TPU analog of implicit Redis pipelining), and one
jitted launch executes probe + window-reset + duplicate-serialized increment
against the HBM slab (ops/slab.py).

Division of labor (after-mode, ops/slab.py:slab_step_after): the device owns
the STATE — it returns only each item's post-increment counter, saturating-
cast to the narrowest dtype the batch's limits allow so the readback is one
byte or two per decision. The host then derives code/remaining/duration/
throttle and the near/over stats split by calling the SAME
BaseRateLimiter.get_response_descriptor_status oracle the memory backend
uses (limiter/base_limiter.py:92-142) — TPU-vs-oracle parity holds by
construction, exactly how both reference backends share base_limiter.go.

The local over-limit cache stays host-side in front of the device exactly
like the reference's freecache sits in front of Redis
(src/limiter/base_limiter.go:57-66): items already known to be over limit
never reach the batcher.

Single-chip by default; parallel/sharded_slab.py provides the multi-chip
variant (hash-sharded slab, decisions combined over ICI) behind `mesh=`.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import threading
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..assertx import assert_
from ..limiter.base_limiter import BaseRateLimiter, LimitInfo
from ..limiter.cache import CacheError
from ..limiter.cache_key import generate_cache_key
from ..models.config import (
    ALGO_ID_CONCURRENCY,
    ALGO_ID_FIXED_WINDOW,
    ALGO_ID_GCRA,
    RateLimit,
)
from ..tracing import journeys
from ..models.descriptors import RateLimitRequest
from ..models.response import DoLimitResponse
from ..models.units import unit_to_divider
from ..ops.hashing import fingerprint_many, split_fingerprints
from ..ops.slab import (
    ALGO_CONC_RELEASE,
    ALGO_SHIFT,
    COL_EXPIRE,
    COL_FP_HI,
    COL_FP_LO,
    HEALTH_ALGO_RESETS,
    HEALTH_DROPS,
    HEALTH_EVICT_EXPIRED,
    HEALTH_EVICT_LIVE,
    HEALTH_EVICT_WINDOW,
    ROW_WIDTH,
    ParkedHealth,
    make_slab,
    slab_export_copy,
    slab_import_rows,
    slab_live_slots,
    slab_promote_rows,
    slab_step_after,
    default_ways,
    validate_ways,
)
from ..tracing import host_span, install_gc_spans, tag_do_limit_start
from .batcher import MicroBatcher
from .lease import LeaseOps, LeaseRegistry, apply_lease_ops

_log = logging.getLogger(__name__)


def _loss_ppm(snap: dict) -> int:
    """Lossy events (live-row evictions + in-batch contention drops) per
    million decisions — the alarmable rate behind the fail-open contract
    (the reference documents the same trade as "the request is assumed
    allowed on error", README.md:567-568): every parity disagreement must
    trace to a counted lossy event, so this ratio rising is the early
    warning that parity is eroding. Expired/window-ended eviction reclaims
    deliberately do NOT count: they displace no observable state."""
    decisions = snap.get("decisions", 0)
    if not decisions:
        return 0
    return round(
        (snap["evictions_live"] + snap["drops"]) / decisions * 1_000_000
    )


# journey stage tags: which decision algorithm denied/decided a request —
# the flight recorder renders these so a slow or shed journey shows the
# algorithm class it hit (tracing/journeys.py)
ALGO_JOURNEY_STAGES = {
    0: "algo_fixed_window",
    1: "algo_sliding_window",
    2: "algo_gcra",
    3: "algo_concurrency",
}


@dataclasses.dataclass(slots=True)
class _Item:
    fp: int
    hits: int
    limit: int
    divider: int
    jitter: int


class SlabDeviceEngine:
    """The device driver: owns the slab state (single-chip or mesh-sharded)
    and the batching layer, and turns item batches into post-increment
    counters via one launch per batch. The narrow `submit(items) -> afters`
    verb set is the device analog of the reference's redis.Client interface
    (SURVEY.md §2.9); TpuRateLimitCache drives it in-process and the sidecar
    server (backends/sidecar.py) exposes the same verb over a local socket
    so many frontend processes can share ONE global slab."""

    def __init__(
        self,
        time_source,
        near_limit_ratio: float = 0.8,
        n_slots: int = 1 << 22,
        ways: int = 0,
        batch_window_seconds: float = 0.0,
        max_batch: int = 65536,
        buckets: Sequence[int] = (128, 1024, 8192, 65536),
        device=None,
        use_pallas: bool | None = None,
        mesh=None,
        block_mode: bool = False,
        scope=None,
        max_queue: int = 0,
        watermark_high: float = 0.0,
        overload=None,
        fault_injector=None,
        precompile: bool = False,
        gcra_burst_ratio: float = 1.0,
        partition: int = -1,
        hotkey_lanes: int = 0,
        hotkey_k: int = 16,
        victim_max_rows: int = 0,
        victim_watermark: float = 0.85,
        shard_routed_batching: bool = True,
        hot_tier_enabled: bool = True,
        hot_tier_salt_ways: int = 0,
    ):
        """hotkey_lanes: lanes of the in-kernel heavy-hitter sketch
        (ops/sketch.py; HOTKEY_LANES). 0 disables — the HOTKEYS_ENABLED=
        false arm: no sketch array enters the launch pytree, so the traced
        program is byte-identical to the pre-hotkeys engine. hotkey_k is
        the top-K size each drain reports (HOTKEY_K).

        victim_max_rows: row bound of the host-RAM victim tier
        (backends/victim.py; VICTIM_MAX_ROWS). 0 disables — the
        VICTIM_TIER_ENABLED=false arm: the launch compiles with
        victim=False (ops/slab.py static gate), so the traced program and
        the slab bytes are byte-identical to the pre-tier engine. When
        enabled, every launch's demoted live rows (the in-kernel
        eviction readback) drain into the tier and a key's reappearance
        re-promotes its row onto the slab mid-window via
        slab_promote_rows — live eviction stops being lossy.
        victim_watermark (VICTIM_WATERMARK) is the tier-occupancy
        fraction past which the sticky degraded probe raises
        (victim_watermark_reason).

        partition: which cluster partition this owner serves
        (cluster/; -1 = unpartitioned). Labeling only: the dispatch
        loop's arena-pressure telemetry exports partition-attributable
        names (backends/dispatch.py DispatchStats) so ring pressure on a
        K-partition host traces to the keyspace slice generating it.

        scope: optional stats Scope rooted at the service prefix (e.g.
        the runner's `ratelimit` scope). When set, the engine records the
        per-stage device histograms — <scope>.device.{pack_ms,launch_ms,
        readback_ms}, <scope>.slab.{lock_wait_ms,health_drain_ms,
        health_vectors} — and hands <scope>.batcher to the direct-mode
        batcher and <scope>.dispatch to the dispatch loop for their
        queue-wait/batch-size/depth telemetry. None (the default) keeps
        the hot path entirely free of stats work.

        precompile: compile the whole bucket ladder (every launch shape x
        readback dtype) at construction so no request ever rides a JIT
        compile (see precompile()).

        batch_window_seconds: 0 (direct mode) makes each caller execute
        its own launch under the direct lock (backends/batcher.py); > 0
        (windowed mode) runs the persistent device-owner dispatch loop
        (backends/dispatch.py): one thread owns every launch AND readback,
        fed by per-frontend-thread submit rings, with two batches
        double-buffered in flight.

        overload / fault_injector feed both modes' admission (brownout
        shedding + the batcher.submit chaos site); max_queue bounds the
        dispatch loop's ring backlog (direct mode holds no queue).

        ways: set associativity (SLAB_WAYS) — the slab is n_slots/ways
        sets of `ways` rows; a full set evicts its least-valuable way
        in-kernel (ops/slab.py), so occupancy degrades smoothly and there
        is no sweep pass or admission shed. 0 (the default) auto-selects
        by platform: 128 on TPU (one lane register per set), 4 on hosts
        (ops/slab.py default_ways). Power of two; clamped to n_slots for
        tiny test slabs.

        watermark_high: slab-occupancy fraction in (0, 1]; 0 disables.
        Evaluated on the health_snapshot (stats-flush) cadence — never per
        batch. Past it the degraded health probe raises (watermark_reason)
        so operators see sustained pressure; admission is never shed —
        collisions evict by value instead. (The old critical-watermark
        shed died with the open-addressed layout; SLAB_WATERMARK_CRITICAL
        is accepted-and-ignored at the settings layer with a deprecation
        warning.)"""
        self._time_source = time_source
        self._near_limit_ratio = float(near_limit_ratio)
        # GCRA burst tolerance knob (GCRA_BURST_RATIO): tau =
        # ratio * window_ms - T. Rides launch-operand scalar slot 2.
        self._gcra_burst_ratio = float(gcra_burst_ratio)
        # Sticky algorithms guard: the Mosaic kernels implement
        # fixed_window only, so the FIRST launch (or restored table) that
        # carries a non-fixed algorithm id flips this engine's launches to
        # the XLA twin permanently — an all-fixed config never flips it,
        # keeping the pallas rollback arm bit-identical.
        self._algos_seen = False
        if device is None and mesh is None:
            from ..utils.jaxsetup import serving_devices

            device = serving_devices(1)[0]
        elif device is None:
            device = mesh.devices.flat[0]
        # placement invariant: the slab state is committed to `device` once
        # (below); every launch donates it back, so jit keeps all compute
        # and the uncommitted numpy input blocks pinned there — no
        # per-launch device argument needed
        self._device = device
        if use_pallas is None:
            use_pallas = device.platform == "tpu"
        self._use_pallas = bool(use_pallas)
        if not ways:
            # SLAB_WAYS=0 (auto): platform-matched associativity — 128 on
            # TPU (one lane register per set, the Mosaic scan shape), 8 on
            # hosts (the scan is real per-item memory traffic there; see
            # ops/slab.py default_ways). Same auto-select precedent as
            # use_pallas above; snapshots rehash across geometry changes.
            ways = default_ways(device.platform)
        # mesh set => multi-chip: hash-sharded slab combined over ICI
        # (parallel/sharded_slab.py), same packed-block protocol.
        self._engine = None
        if mesh is not None:
            from ..parallel.sharded_slab import ShardedSlabEngine

            # mesh engines route per shard by default
            # (SHARD_ROUTED_BATCHING; the false arm is the byte-identical
            # global-bucket rollback) and take the hot-key tier + the
            # host-side top-K fallback in place of the device sketch
            self._engine = ShardedSlabEngine(
                mesh=mesh,
                n_slots_global=n_slots,
                ways=ways,
                use_pallas=self._use_pallas,
                routed=bool(shard_routed_batching),
                hot_tier=bool(hot_tier_enabled),
                hot_salt_ways=int(hot_tier_salt_ways),
                hotkey_lanes=int(hotkey_lanes),
                hotkey_k=int(hotkey_k),
            )
            self._state = None
            self._ways = self._engine.ways
        else:
            self._state = jax.device_put(make_slab(n_slots), device)
            self._ways = validate_ways(n_slots, ways)
        self._buckets = tuple(sorted(buckets))
        self._max_bucket = self._buckets[-1]
        self._n_slots = n_slots
        # heavy-hitter sketch (ops/sketch.py): a few uint32 lanes riding
        # every launch beside the slab; drained + halved on the stats
        # cadence (drain_hotkeys), never per launch. Single-device only:
        # the mesh engine's compacted per-shard launches would need a
        # per-shard sketch merge that nothing demands yet.
        self._hotkey_k = max(1, int(hotkey_k))
        self._sketch = None
        self._sketch_ways = 0
        self._hot_fps: frozenset = frozenset()
        self._last_topk: list[tuple[int, int, int]] = []
        self._hotkey_drains = 0
        self._hotkey_listeners: list = []
        if int(hotkey_lanes) > 0:
            if self._engine is not None:
                # mesh path: the device sketch stays single-device, but
                # the sharded engine carries its own host-side top-K
                # fallback (ops/sketch.py HostTopK) fed from the routed
                # batches — this backend just delegates the hotkeys
                # surface to it (drain_hotkeys & co below)
                pass
            else:
                from ..ops.sketch import make_sketch, sketch_ways

                self._sketch_ways = sketch_ways(self._ways, hotkey_lanes)
                self._sketch = jax.device_put(
                    make_sketch(hotkey_lanes), device
                )
        # host-RAM victim tier (backends/victim.py): where in-kernel live
        # evictions drain instead of vanishing, and where the promote
        # injection re-reads them from. Single-device only for the same
        # reason as the sketch: the mesh engine's compacted per-shard
        # launches would need per-shard victim readbacks nothing demands
        # yet. The fault injector is kept for the victim.demote /
        # victim.promote chaos sites (testing/faults.py).
        self._victim = None
        self._victim_lock = threading.Lock()
        # sketch-hot rows never demote: a hot row swept up in a live
        # eviction parks here and re-injects unconditionally on the very
        # next launch, immune to the tier's overflow valuation
        self._promote_pending: dict = {}
        self._victim_hot_refusals = 0
        self._victim_demote_errors = 0
        self._victim_promote_skips = 0
        self._fault = fault_injector
        if int(victim_max_rows) > 0:
            if mesh is not None:
                _log.warning(
                    "victim tier is single-device only; disabled on the "
                    "mesh-sharded engine"
                )
            else:
                from .victim import VictimTier

                self._victim = VictimTier(
                    int(victim_max_rows),
                    float(victim_watermark),
                    time_source,
                )
        # lossy-event counters (the eviction mix / in-batch contention
        # drops — ops/slab.py HEALTH_* layout): per-launch device health
        # vectors are parked un-fetched and drained on the stats-flush
        # cadence, fetched with _state_lock released (ops/slab.py
        # ParkedHealth). _state_lock serializes state rebinds (the steps
        # donate their input state) against the occupancy and sketch reads
        # from the stats thread.
        self._health = ParkedHealth()
        # decisions submitted to the device — the denominator that turns the
        # lossy-event counters into an alarmable RATE (VERDICT r4 weak #3:
        # absolute counts can triple silently; a ratio gauge cannot)
        self._decisions_total = 0
        # recent coalesced launch sizes (ring): lets operators/bench see how
        # much cross-request batching the window actually buys, and lets the
        # bench chain-time the device program at the batch size the service
        # path really ran (the device/host p99 split, VERDICT r4 weak #4)
        self.launch_sizes: collections.deque = collections.deque(maxlen=4096)
        self._state_lock = threading.Lock()
        # occupancy pressure watermark: a pure OBSERVABILITY threshold
        # driven on the health_snapshot cadence (_apply_watermarks) — it
        # raises the degraded health probe and nothing else. No sweep, no
        # admission shed: the set-associative scan absorbs pressure by
        # evicting least-valuable ways in-kernel.
        self._watermark_high = float(watermark_high)
        self._watermark_state = 0  # 0 normal / 1 high
        self._h_pack = self._h_launch = self._h_readback = None
        # the launch path's wait for _state_lock, and each health drain
        # (stats flush, or inline once 4,096 vectors are parked): its time
        # and the vectors it folded
        self._h_lock_wait = self._h_health_drain = None
        self._h_health_vectors = None
        batcher_scope = None
        if scope is not None:
            from ..stats.store import DEFAULT_SIZE_BUCKETS

            device_scope = scope.scope("device")
            self._h_pack = device_scope.histogram("pack_ms")
            self._h_launch = device_scope.histogram("launch_ms")
            self._h_readback = device_scope.histogram("readback_ms")
            slab_scope = scope.scope("slab")
            self._h_lock_wait = slab_scope.histogram("lock_wait_ms")
            self._h_health_drain = slab_scope.histogram("health_drain_ms")
            self._h_health_vectors = slab_scope.histogram(
                "health_vectors", boundaries=DEFAULT_SIZE_BUCKETS
            )
            batcher_scope = scope.scope("batcher")
        install_gc_spans()
        # Every engine is block-native internally: the submit unit is a
        # uint32[6, n] row block and the executors copy whole column spans
        # into the padded device block — the in-process frontend rides the
        # same zero-object machinery the sidecar server proved (8x at
        # aggregated load). block_mode only selects the PUBLIC verb set:
        # submit_block for the sidecar wire path vs submit/submit_rows for
        # in-process callers.
        self._block_batcher = bool(block_mode)
        # Padded-operand reuse (single device only): per-bucket ping-pong
        # pairs the launch path packs into instead of allocating fresh
        # zeros every launch. Safe because both modes bound un-redeemed
        # launches to 2 (the dispatch loop's double buffer, direct mode's
        # full serialization), so a buffer is only rewritten after the launch
        # 2-back has finished executing — its input can no longer be read
        # even if XLA aliased the host memory. Padding correctness: only
        # the hits row gates device writes (ops/slab.py), so the fill path
        # zeroes packed[2, n:] and leaves the other rows' stale lanes
        # alone.
        self._reuse_operands = self._engine is None
        self._operand_pool: dict = {}
        self._operand_lock = threading.Lock()
        # native row-block gather (rl_pack_rows) for the pack stage; None
        # keeps the numpy per-block copy loop (pure-Python fallback)
        try:
            from ..ops import native as _native

            self._pack_rows = _native.pack_rows if _native.available() else None
        except Exception:  # noqa: BLE001 - codec is strictly optional
            self._pack_rows = None
        # direct mode's batcher is built in both modes (it holds no
        # thread), so the batcher.* metric names are exported either way;
        # in windowed mode the dispatch loop takes every submit
        self._dispatch = None
        self._batcher = MicroBatcher(
            self._execute_blocks,
            scope=batcher_scope,
            overload=overload,
            fault_injector=fault_injector,
        )
        if batch_window_seconds > 0:
            from .dispatch import DispatchLoop

            self._dispatch = DispatchLoop(
                self._execute_blocks_launch,
                self._execute_blocks_collect,
                ready=self._launch_ready,
                window_seconds=batch_window_seconds,
                max_batch=max_batch,
                scope=scope,
                overload=overload,
                fault_injector=fault_injector,
                max_queue=max_queue,
                partition=partition,
            )
        # Device-owner lease liability registry (backends/lease.py): who
        # holds how much un-settled leased budget, and the counter
        # watermark each restored slab row must respect. Always built —
        # inert (empty) until lease traffic arrives; the snapshotter
        # persists it as leases.snap so a warm restart never double-grants.
        self.lease_registry = LeaseRegistry(time_source)
        # (bucket, readback dtype name) -> True for every launch shape
        # compiled ahead of traffic; the health/readiness test asserts the
        # ladder is covered before the server reports healthy.
        self.precompiled: dict = {}
        if precompile:
            self.precompile()

    def _drain_health(self) -> list[int]:
        """Fold the parked per-launch health vectors into the totals, with
        _state_lock held for the list swap alone; returns the totals.
        health_drain_ms and health_vectors take one sample per drain that
        found any parked (a flush after traffic stops finds none, and
        would only dilute the mean)."""
        with host_span("ratelimit.slab.health_drain"):
            t0 = time.perf_counter()
            n, totals = self._health.drain(self._state_lock)
            if n and self._h_health_drain is not None:
                self._h_health_drain.record((time.perf_counter() - t0) * 1e3)
                self._h_health_vectors.record(n)
        return totals

    @contextlib.contextmanager
    def _state_locked_for_launch(self):
        """Hold _state_lock for the launch path, timing the wait for it
        (the stats thread holds it for the occupancy and sketch reads)."""
        with host_span("ratelimit.slab.lock_wait"):
            t0 = time.perf_counter()
            self._state_lock.acquire()
            if self._h_lock_wait is not None:
                self._h_lock_wait.record((time.perf_counter() - t0) * 1e3)
        try:
            yield
        finally:
            self._state_lock.release()

    def health_snapshot(self) -> dict:
        """Slab health for the stats tree (VERDICT round 1 weak #5): the two
        documented fail-open behaviors plus occupancy. live_slots is an
        O(n_slots) device reduction — called on the stats-flush cadence.
        The watermark policy rides this cadence: occupancy drives the
        sweep/saturation state machine here, never in the hot path."""
        now = int(self._time_source.unix_now())
        if self._engine is not None:
            snap = self._engine.health_snapshot(now)
            with self._state_lock:
                snap["decisions"] = self._decisions_total
            snap["loss_ppm"] = _loss_ppm(snap)
            self._apply_watermarks(snap, now)
            return snap
        totals = self._drain_health()
        with self._state_lock:
            with host_span("ratelimit.slab.live_slots"):
                live = int(slab_live_slots(self._state, now))
            decisions = self._decisions_total
        snap = {
            "evictions_expired": totals[HEALTH_EVICT_EXPIRED],
            "evictions_window": totals[HEALTH_EVICT_WINDOW],
            "evictions_live": totals[HEALTH_EVICT_LIVE],
            "drops": totals[HEALTH_DROPS],
            "algo_resets": totals[HEALTH_ALGO_RESETS],
            "decisions": decisions,
            "live_slots": live,
            "occupancy": live / self._n_slots,
        }
        snap["loss_ppm"] = _loss_ppm(snap)
        self._apply_watermarks(snap, now)
        return snap

    def _apply_watermarks(self, snap: dict, now: int) -> None:
        """Occupancy -> pressure flag. Purely observational: past HIGH the
        degraded health probe raises so operators see sustained pressure
        building; admission and the launch path are untouched — the
        eviction scan is the relief valve, and its mix (evictions_live
        climbing) is the signal that pressure has started costing
        counters."""
        high = self._watermark_high
        occ = snap["occupancy"]
        state = 1 if (high > 0 and occ >= high) else 0
        if state != self._watermark_state:
            _log.warning(
                "slab watermark state %d -> %d (occupancy %.3f)",
                self._watermark_state,
                state,
                occ,
            )
        self._watermark_state = state
        snap["watermark"] = state

    def watermark_reason(self) -> str | None:
        """HealthChecker degraded-probe contract: a reason string while the
        slab sits past the pressure watermark, else None."""
        if self._watermark_state:
            return (
                f"slab pressure: occupancy >= high watermark "
                f"{self._watermark_high:g}; sets evicting by value"
            )
        return None

    def precompile(self) -> dict:
        """Dispatch-floor attack, part 1: compile every launch shape the
        bucket ladder can produce — each bucket size x each saturating
        readback dtype (u8/u16/u32) — BEFORE the first request, so a
        first-touch XLA compile (hundreds of ms to seconds) never rides a
        caller's deadline (nor the health drain's fold, which runs last,
        over the warm launches' vectors). Each shape is warmed with an
        all-padding (hits == 0) launch through the REAL donated-state
        chain: padding lanes write nothing (ops/slab.py, the hits > 0
        gates), so the slab is bit-identical afterwards, and warming
        through the actual jit call populates the dispatch cache the hot
        path hits (an AOT lower().compile() object would compile the same
        program but leave jit's own call cache cold). Returns the
        covered-shape map, also kept as `precompiled`. The mesh engine
        owns its own program cache and is skipped."""
        if self._engine is not None:
            _log.info("precompile: mesh engine manages its own programs")
            return self.precompiled
        # warm launches must not pollute the per-stage histograms: a
        # boot-time compile in launch_ms would own p99 forever
        saved = self._h_pack, self._h_launch, self._h_readback, self._h_lock_wait
        self._h_pack = self._h_launch = self._h_readback = self._h_lock_wait = None
        try:
            for bucket in self._buckets:
                packed = np.zeros((7, bucket), dtype=np.uint32)
                for cap, name in (
                    (0xFF, "uint8"),
                    (0xFFFF, "uint16"),
                    (0xFFFFFFFF, "uint32"),
                ):
                    self._collect_array(self._dispatch_packed(packed, 0, cap))
                    self.precompiled[(bucket, name)] = True
            self._health.drain(self._state_lock)
        finally:
            self._h_pack, self._h_launch, self._h_readback, self._h_lock_wait = saved
        return self.precompiled

    def profile_slab_split(
        self, scope=None, batch: int | None = None, iters: int = 30
    ) -> dict:
        """The `slab_split` stage baseline for future kernel work: times
        the slab step's three memory-system stages — contiguous set
        GATHER, W-wide SCAN arithmetic, one-row-per-way SCATTER — as
        standalone jitted programs over this engine's live geometry
        (ops/slab.py make_split_programs; each program IS the shipped
        helper the fused step compiles). Runs against a detached device
        copy of the table, so the donated-state chain and live counters
        are untouched. When `scope` is given every sample also lands in
        <scope>.split.{gather,scan,scatter}_ms histograms — bench.py and
        tools/hotpath_profile.py report from those same histograms, so
        the published baseline and /metrics cannot disagree. Returns
        {batch, gather_ns, scan_ns, scatter_ns} (per-launch p50); {} on
        the mesh engine (per-shard programs profile via
        tools/profile_engine.py)."""
        if self._engine is not None:
            return {}
        from ..ops.slab import make_split_programs

        b = int(batch or min(self._max_bucket, 8192))
        gather, scan, scatter = make_split_programs(self._ways)
        with self._state_lock:
            table = slab_export_copy(self._state)
        rng = np.random.default_rng(7)

        def u32(size):
            return jnp.asarray(
                rng.integers(0, 1 << 32, size=size, dtype=np.uint64).astype(
                    np.uint32
                )
            )

        fp_lo, fp_hi = u32(b), u32(b)
        now = jnp.int32(int(self._time_source.unix_now()))
        hists = {}
        if scope is not None:
            split_scope = scope.scope("split")
            hists = {
                k: split_scope.histogram(f"{k}_ms")
                for k in ("gather", "scan", "scatter")
            }

        def timed(name, fn) -> int:
            jax.block_until_ready(fn())  # compile + warm
            samples = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ms = (time.perf_counter() - t0) * 1e3
                samples.append(ms)
                if name in hists:
                    hists[name].record(ms)
            return round(float(np.median(samples)) * 1e6)

        rows = jax.block_until_ready(gather(table, fp_lo))
        result = {"batch": b}
        result["gather_ns"] = timed("gather", lambda: gather(table, fp_lo))
        result["scan_ns"] = timed(
            "scan", lambda: scan(rows, fp_lo, fp_hi, now)
        )
        # unique write targets (the fused step guarantees one writer per
        # way); lanes past the table drop, like padding lanes do
        idx = np.full(b, self._n_slots, dtype=np.int32)
        k = min(b, self._n_slots)
        idx[:k] = rng.permutation(self._n_slots)[:k].astype(np.int32)
        write_idx = jnp.asarray(idx)
        new_rows = u32((b, ROW_WIDTH))
        # the scatter donates its table (matching the hot path); rebind the
        # returned buffer each call — `table` is consumed by the first one
        sc_state = {"t": table}

        def sc():
            sc_state["t"] = scatter(sc_state["t"], write_idx, new_rows)
            return sc_state["t"]

        result["scatter_ns"] = timed("scatter", sc)
        return result

    def submit(self, items: list[_Item]) -> list[int]:
        """Batched fixed-window increment; returns each item's
        post-increment counter. Compatibility verb: the engine is
        block-native internally, so the _Item list is converted to one row
        block at the door (the conversion cost lands on this legacy path
        only — the zero-object pipeline calls submit_rows directly)."""
        if self._block_batcher:
            raise RuntimeError("engine is in block_mode; use submit_block")
        if not items:
            return []
        if self._dispatch is not None:
            return self._dispatch.submit(
                _items_to_block(items), owned=True, reuse_out=True
            ).tolist()
        return self._batcher.submit(_items_to_block(items)).tolist()

    def submit_rows(
        self, block: np.ndarray, lease_ops=None
    ) -> np.ndarray:
        """Zero-object verb: one uint32[6, n] row block (columns fp_lo,
        fp_hi, hits, limit, divider, jitter — the sidecar wire layout) ->
        uint32[n] post-increment counters. The caller may pass a reusable
        scratch block: both modes consume it before returning.

        lease_ops: optional backends.lease.LeaseOps piggybacked on this
        submit — grants registered against the liability registry with the
        rows' post-increment counters as floors, settles applied. The rows'
        INCRBY inflation is already in the hits column; this is only the
        host-side bookkeeping."""
        if block.shape[1] == 0:
            return np.empty(0, dtype=np.uint32)
        if self._dispatch is not None:
            # ring path: the frame is copied into this thread's submit
            # ring, and the verdicts come back in this thread's reusable
            # ticket buffer (valid until its next submit — the row path
            # consumes them immediately)
            afters = self._dispatch.submit(block, reuse_out=True)
        else:
            afters = self._batcher.submit(block)
        if lease_ops is not None:
            self.apply_lease_ops(block, afters, lease_ops)
        return afters

    def apply_lease_ops(self, block, afters, ops) -> None:
        """Register piggybacked lease grants/settles (backends/lease.py)
        against this engine's liability registry — called by submit_rows
        for in-process frontends and by the sidecar server after decoding
        a wire frame's lease trailer."""
        apply_lease_ops(
            self.lease_registry,
            block,
            afters,
            ops,
            int(self._time_source.unix_now()),
        )

    @property
    def dispatch_loop(self):
        """The device-owner dispatch loop, or None in direct mode
        (TPU_BATCH_WINDOW=0). The shm-ring control server
        (backends/shm_ring.py) attaches cross-process frontend rings
        here."""
        return self._dispatch

    def flush(self) -> None:
        if self._dispatch is not None:
            self._dispatch.flush()
        self._batcher.flush()

    def drain(self) -> None:
        """Graceful-drain quiesce: refuse new submits, finish everything
        already queued (dispatch rings, or direct mode's launch in
        progress). The warm-restart snapshotter calls this before its
        final snapshot so a planned restart hands over every admitted
        decision (persist/snapshotter.py)."""
        if self._dispatch is not None:
            self._dispatch.drain()
        self._batcher.drain()

    def close(self) -> None:
        if self._dispatch is not None:
            self._dispatch.close()
        self._batcher.close()

    # -- warm restart (persist/): per-shard slab export/import --

    @property
    def shard_count(self) -> int:
        """Snapshot shard layout: one file per device sub-table."""
        if self._engine is not None:
            return self._engine.shard_count
        return 1

    @property
    def shard_slots(self) -> int:
        """Rows per snapshot shard (the restore-time topology check)."""
        if self._engine is not None:
            return self._engine.shard_slots
        return self._n_slots

    @property
    def ways(self) -> int:
        """Set associativity — stamped into snapshot headers so a restore
        under a different SLAB_WAYS rehashes instead of misplacing rows."""
        return self._ways

    def export_tables(self) -> list[np.ndarray]:
        """Quiesce-and-copy for the snapshotter: under the state lock only
        a device-side copy is dispatched — it sequences after every
        in-flight launch on the device stream, so the launch pipeline
        never waits on the D2H drain, which happens against the detached
        copy after the lock is released."""
        if self._engine is not None:
            return self._engine.export_tables()
        with self._state_lock:
            copy = slab_export_copy(self._state)
        return [np.asarray(copy)]

    def import_tables(self, tables: list[np.ndarray]) -> None:
        """Boot-time restore upload: replace the slab with reconciled
        snapshot rows (persist/snapshotter.py validated shard layout and
        applied the expiry reconciliation before calling)."""
        if self._engine is not None:
            self._engine.import_tables(tables)
            if self._engine.algos_seen:
                # keep the backend's own sticky guard in sync so its
                # pre-launch check (and logging) agree with the engine
                self._algos_seen = True
            return
        if len(tables) != 1:
            raise ValueError(
                f"single-device slab restores from 1 shard, got {len(tables)}"
            )
        rows = np.asarray(tables[0], dtype=np.uint32)
        if rows.shape != (self._n_slots, ROW_WIDTH):
            raise ValueError(
                f"snapshot table shape {rows.shape} does not match the "
                f"configured slab ({self._n_slots}, {ROW_WIDTH})"
            )
        if not self._algos_seen and int(rows[:, 5].max(initial=0)) >= (
            1 << ALGO_SHIFT
        ):
            # restored rows carry non-fixed algorithms: the table is no
            # longer pallas-safe even before the first such launch
            self._algos_seen = True
        with self._state_lock:
            self._state = jax.device_put(
                slab_import_rows(rows), self._device
            )

    # -- partitioned cluster (cluster/): reshard streaming --

    def export_route_range(
        self, lo: int, hi: int, route_sets: int
    ) -> np.ndarray:
        """Occupied rows whose ROUTE INDEX — set_index(fp_lo, route_sets)
        at the cluster map's resolution (ops/hashing.py, the same split
        the router buckets by) — falls in [lo, hi): the reshard PULL.
        Rides the same quiesce-and-copy export the snapshotter and the
        replication ship loop use, so the launch pipeline never blocks.
        Returns a flat (n, ROW_WIDTH) row array (placement-free — the
        receiving owner re-places by its own geometry)."""
        from ..ops.hashing import set_index

        if route_sets <= 0 or route_sets & (route_sets - 1):
            raise ValueError(
                f"route_sets must be a power of two, got {route_sets}"
            )
        if not 0 <= lo < hi <= route_sets:
            raise ValueError(
                f"route range [{lo}, {hi}) outside [0, {route_sets})"
            )
        tables = [np.asarray(t) for t in self.export_tables()]
        flat = tables[0] if len(tables) == 1 else np.concatenate(tables)
        route = np.asarray(set_index(flat[:, 0], route_sets))
        mask = flat.any(axis=1) & (route >= lo) & (route < hi)
        return np.ascontiguousarray(flat[mask])

    def merge_rows(self, rows: np.ndarray) -> dict:
        """The reshard PUSH: merge streamed rows into the live slab by
        fingerprint, keep-the-newest (persist/snapshot.py
        merge_rows_into_table — greater window wins, equal windows keep
        the greater count), so a stage-then-drain double delivery
        converges upward toward the true counter instead of rolling an
        admission back. The whole export → host merge → upload runs
        UNDER the state lock: launches queue behind it for the few ms a
        reshard section takes, and in exchange no concurrent increment
        can fall between the copy and the upload — the merge is atomic
        against the launch path. Returns the merge stats dict."""
        from ..persist.snapshot import merge_rows_into_table

        rows = np.asarray(rows, dtype=np.uint32)
        if rows.size and rows.shape[1] != ROW_WIDTH:
            raise ValueError(
                f"merge rows must be (n, {ROW_WIDTH}), got {rows.shape}"
            )
        if self._engine is not None:
            raise CacheError(
                "mesh-sharded owners do not support in-place reshard "
                "merge; reshard a mesh partition via snapshot/restore"
            )
        with self._state_lock:
            table = np.asarray(slab_export_copy(self._state))
            merged, stats = merge_rows_into_table(table, rows, self._ways)
            if not self._algos_seen and int(
                merged[:, 5].max(initial=0)
            ) >= (1 << ALGO_SHIFT):
                # streamed rows may carry non-fixed algorithms: flip the
                # sticky guard before they can reach the Mosaic body
                self._algos_seen = True
            self._state = jax.device_put(
                slab_import_rows(merged), self._device
            )
        return stats

    # -- warm-standby replication (persist/replication.py) --

    def export_for_replication(self) -> tuple[list[np.ndarray], np.ndarray, int]:
        """One export for the replication ship loop: the slab shard
        tables (the same quiesce-and-copy path the snapshotter rides —
        only a device-side copy dispatches under the state lock, the D2H
        drain happens against the detached copy) plus the live
        lease-liability rows, stamped with one clock read so the standby
        reconciles slab and liabilities against the same instant."""
        tables = self.export_tables()
        now = int(self._time_source.unix_now())
        return tables, self.lease_registry.export_rows(now), now

    def apply_replicated(
        self, tables: list[np.ndarray], lease_rows: np.ndarray
    ) -> None:
        """Promotion upload: replace the slab with the reconciled replica
        tables (the coordinator already ran reconcile_rows + lease
        floors) and re-seed the liability registry — the same pair of
        moves the warm-restart boot restore makes."""
        self.import_tables(tables)
        self.lease_registry.import_rows(lease_rows)

    # -- device execution (dispatch owner thread / direct-mode caller only) --

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._max_bucket

    def _launch(self, items: list[_Item]) -> list[int]:
        """One synchronous device launch of an _Item list (tests/tools);
        rides the block executors like everything else."""
        return self._execute_blocks([_items_to_block(items)]).tolist()

    def _dispatch_packed(self, packed: np.ndarray, n: int, cap: int):
        """Dispatch one packed uint32[7, bucket] launch; returns the token
        the collect phase drains. Mesh mode owner-routes on the host and
        dispatches the compacted per-shard launch (each chip probes only
        the ~n/n_dev keys it owns — nothing replicated or psum'd on the
        result path). launch_ms times THIS host-side phase (async device
        dispatch, never the device execution — readback_ms carries the
        blocking wait)."""
        t_launch = time.perf_counter() if self._h_launch is not None else 0.0
        if n:  # precompile dispatches empty warmers; keep the ring honest
            self.launch_sizes.append(n)
            if not self._algos_seen and int(packed[4, :n].max()) >= (
                1 << ALGO_SHIFT
            ):
                # first non-fixed algorithm: route every launch from here
                # on through the XLA twin (the Mosaic kernels are
                # fixed_window-only). One .max() over a row slice — no
                # temporaries, sub-microsecond at any bucket size.
                self._algos_seen = True
                if self._engine is not None:
                    # mesh mode bakes use_pallas into the sharded step
                    # functions — flip them too, or sliding/GCRA/release
                    # rows would still run the fixed-window Mosaic body
                    self._engine.note_algos_seen()
                if self._use_pallas:
                    _log.info(
                        "non-fixed rate-limit algorithm on the wire: "
                        "launches now run the XLA kernels (the pallas "
                        "fixed-window kernels stay for all-fixed configs)"
                    )
        if self._engine is not None:
            token = self._engine.launch_after_compact(packed, cap)
            # counted after the launch returns, like the single-device path:
            # a failed launch must not inflate the loss_ppm denominator
            with self._state_locked_for_launch():
                self._decisions_total += n
            if self._h_launch is not None:
                self._h_launch.record((time.perf_counter() - t_launch) * 1e3)
            return token, n
        dtype = (
            jnp.uint8
            if cap == 0xFF
            else jnp.uint16 if cap == 0xFFFF else jnp.uint32
        )
        use_pallas = self._use_pallas and not self._algos_seen
        with self._state_locked_for_launch():
            # promote injection rides BEFORE the step so a demoted key's
            # reappearing batch sees its restored counter in this very
            # launch (the tier's rows resume mid-window, not next-launch)
            self._inject_promotes_locked(packed, n)
            # the numpy block rides the jit call directly — the committed
            # state array pins placement, and skipping the separate
            # device_put dispatch saves ~0.1ms of per-launch host overhead
            # (a third of the launch cost at small batches)
            # a kernel Mosaic rejects raises here (and so fails the boot
            # precompile): TPU_USE_PALLAS=false is the explicit XLA choice
            after_dev, health, victim_rows = self._step_after_locked(
                packed, dtype, use_pallas
            )
            self._health.park(health)
            self._decisions_total += n
        if self._health.full:
            self._drain_health()
        if victim_rows is not None:
            # demote drain OUTSIDE the state lock: the D2H wait on the
            # readback and the host-table inserts must not serialize the
            # next launch's dispatch
            with host_span("ratelimit.slab.victim_drain"):
                self._drain_victim(victim_rows)
        if self._h_launch is not None:
            self._h_launch.record((time.perf_counter() - t_launch) * 1e3)
        return after_dev, n

    def _step_after_locked(self, packed, dtype, use_pallas: bool):
        """One slab_step_after launch under the state lock, threading the
        hotkey sketch through its ping-pong rebind when enabled. With the
        sketch disabled the call compiles the byte-identical pre-hotkeys
        program (ops/slab.py's sketch=None gate — same static-gate
        discipline as multi_algo)."""
        outs = slab_step_after(
            self._state,
            packed,
            ways=self._ways,
            out_dtype=dtype,
            use_pallas=use_pallas,
            # static: until a non-fixed row appears, compile the exact
            # pre-algorithm program (zero added compute on the all-fixed
            # arm); the sticky flip recompiles once
            multi_algo=self._algos_seen,
            sketch=self._sketch,
            sketch_ways=self._sketch_ways,
            victim=self._victim is not None,
        )
        victim_rows = None
        if self._victim is not None:
            # the demoted-row readback rides LAST in the output tuple
            # (after the optional sketch element — ops/slab.py)
            *outs, victim_rows = outs
        if self._sketch is not None:
            self._state, after_dev, health, self._sketch = outs
        else:
            self._state, after_dev, health = outs
        return after_dev, health, victim_rows

    # -- heavy-hitter sketch drain (stats cadence; ops/sketch.py) --

    @property
    def hotkeys_enabled(self) -> bool:
        if self._engine is not None:
            return self._engine.hotkeys_enabled
        return self._sketch is not None

    @property
    def hot_fps(self) -> frozenset:
        """Combined 64-bit fingerprints of the keys the LAST drain ranked
        hot — the request path's journey-flag probe (a frozenset read, no
        lock: rebound atomically by drain_hotkeys)."""
        if self._engine is not None:
            return self._engine.hot_fps
        return self._hot_fps

    def add_hotkey_listener(self, fn) -> None:
        """fn(top, fps) called after every drain with the fresh top-K
        [(fp_lo, fp_hi, count)] and its combined-fp frozenset — the
        adaptive-lease pre-seeding hook (backends/lease.py note_hot_fps)."""
        if self._engine is not None:
            self._engine.add_hotkey_listener(fn)
            return
        self._hotkey_listeners.append(fn)

    def drain_hotkeys(self) -> list[tuple[int, int, int]]:
        """Pull the sketch planes to the host, rank the top-K, halve the
        counts and re-upload (ops/sketch.py sketch_decay — the head tracks
        current traffic, and the halving keeps counts below the kernels'
        int32-ordering contract). Called on the stats-flush cadence by
        HotkeyStats, never per launch: the D2H+H2D pair under the state
        lock costs what a health_snapshot's live_slots reduction does.

        Mesh path: delegates to the sharded engine's host-side top-K
        fallback (same return shape; the drain also feeds its hot tier).
        The local drain counter mirrors the engine's so HotkeyStats'
        counter stays monotone whichever engine serves it."""
        if self._engine is not None:
            top = self._engine.drain_hotkeys()
            self._hotkey_drains = self._engine._hotkey_drains
            return top
        if self._sketch is None:
            return []
        from ..ops.sketch import sketch_decay, sketch_topk

        with self._state_lock:
            planes = np.asarray(self._sketch).copy()
            top = sketch_topk(planes, self._hotkey_k)
            self._sketch = jax.device_put(
                jnp.asarray(sketch_decay(planes)), self._device
            )
        self._last_topk = top
        self._hot_fps = frozenset(
            (hi << 32) | lo for lo, hi, _cnt in top
        )
        self._hotkey_drains += 1
        for fn in self._hotkey_listeners:
            try:
                fn(top, self._hot_fps)
            except Exception:  # noqa: BLE001 - listeners must not break stats
                _log.exception("hotkey listener failed")
        return top

    def hotkeys_snapshot(self) -> dict:
        """The last drained top-K as a debug document — /debug/hotkeys
        without key resolution (the cache layer adds witness keys)."""
        if self._engine is not None:
            return self._engine.hotkeys_snapshot()
        return {
            "enabled": self._sketch is not None,
            "k": self._hotkey_k,
            "lanes": 0 if self._sketch is None else int(self._sketch.shape[1]),
            "drains": self._hotkey_drains,
            "top": [
                {"fp": f"{(hi << 32) | lo:016x}", "count": cnt}
                for lo, hi, cnt in self._last_topk
            ],
        }

    # -- per-shard routing telemetry (mesh engines only) --

    def shard_routing_snapshot(self) -> dict:
        """The mesh engine's cumulative routing mix — bucket/pad/launch
        stage split, per-shard row counts, padding waste, hot-tier state
        (parallel/sharded_slab.py shard_routing_snapshot). Single-device
        engines report disabled so the runner skips the gauges."""
        if self._engine is None:
            return {"enabled": False}
        return self._engine.shard_routing_snapshot()

    # -- victim tier: demote drain + promote injection (backends/victim.py) --

    @property
    def victim_enabled(self) -> bool:
        return self._victim is not None

    @property
    def victim_tier(self):
        """The VictimTier (or None) — the snapshotter's victim.snap hook
        (persist/snapshotter.py) and the debug/inspect surface."""
        return self._victim

    def _drain_victim(self, victim_rows) -> None:
        """Absorb one launch's demoted-live-row readback into the tier.
        Runs outside the state lock (the tier has its own). The readback
        is sorted order with non-demoted lanes zeroed, so the filter is
        just COL_EXPIRE != 0 — a live row always carries a TTL."""
        rows = np.asarray(victim_rows)
        rows = rows[rows[:, COL_EXPIRE] != 0]
        if not rows.shape[0]:
            return
        if self._fault is not None:
            action = self._fault.fire("victim.demote")
            if action == "drop":
                return  # rows silently vanish — the chaos arm's loss
            if action == "error":
                # fail open exactly like a live eviction without the tier:
                # the counters are lost, but counted — never block serving
                self._victim_demote_errors += 1
                return
        self._absorb_demoted(rows)

    def _absorb_demoted(self, rows: np.ndarray) -> None:
        """Route demoted rows: sketch-hot keys to the unconditional
        re-inject queue (hot keys never demote — their next launch is
        now), everything else into the bounded tier."""
        hot = self._hot_fps
        if hot:
            combined = (
                rows[:, COL_FP_HI].astype(np.uint64) << np.uint64(32)
            ) | rows[:, COL_FP_LO].astype(np.uint64)
            mask = np.fromiter(
                (int(fp) in hot for fp in combined), bool, rows.shape[0]
            )
            hot_rows = rows[mask]
            if hot_rows.shape[0]:
                self._victim_hot_refusals += int(hot_rows.shape[0])
                with self._victim_lock:
                    for r in hot_rows:
                        self._promote_pending[
                            (int(r[COL_FP_LO]), int(r[COL_FP_HI]))
                        ] = r.copy()
            rows = rows[~mask]
        if rows.shape[0]:
            self._victim.insert(rows, int(self._time_source.unix_now()))

    def _inject_promotes_locked(self, packed: np.ndarray, n: int) -> None:
        """Pre-step promote pass: any of this batch's fingerprints found
        in the victim tier (plus every parked hot row) re-enters the slab
        via slab_promote_rows, counter/divider/algorithm bits intact, so
        the step that follows sees the resumed row. Swap semantics: a row
        the promote displaces comes back in the `displaced` readback and
        re-demotes into the tier — the hierarchy loses nothing either
        direction. Holds the state lock (caller); the promote launch is
        a few-row program, cheap next to the step it precedes."""
        tier = self._victim
        if tier is None or n == 0:
            return
        with self._victim_lock:
            pending = list(self._promote_pending.values())
        if not tier.rows and not pending:
            return
        if self._fault is not None:
            action = self._fault.fire("victim.promote")
            if action in ("drop", "error"):
                # skip the injection: rows STAY in the tier (promotion is
                # retry-forever by construction — nothing is lost, the
                # key just keeps missing until the site heals)
                self._victim_promote_skips += 1
                return
        hits = tier.lookup_batch(packed[0, :n], packed[1, :n])
        n_hits = 0 if hits is None else hits.shape[0]
        if not n_hits and not pending:
            return
        parts = ([hits] if n_hits else []) + (
            [np.stack(pending)] if pending else []
        )
        rows = parts[0] if len(parts) == 1 else np.concatenate(parts)
        k = rows.shape[0]
        # pad to the bucket ladder so the promote program compiles a
        # handful of shapes, not one per row count
        size = max(self._bucket_for(k), k)
        padded = np.zeros((size, ROW_WIDTH), dtype=np.uint32)
        padded[:k] = rows
        now = int(packed[6, 0])
        self._state, landed_dev, displaced_dev = slab_promote_rows(
            self._state, padded, now, ways=self._ways
        )
        landed = np.asarray(landed_dev)[:k]
        if n_hits:
            tier.retire(rows[:n_hits], landed[:n_hits])
        if pending:
            with self._victim_lock:
                for row, ok in zip(pending, landed[n_hits:].tolist()):
                    if ok:
                        self._promote_pending.pop(
                            (int(row[COL_FP_LO]), int(row[COL_FP_HI])), None
                        )
        displaced = np.asarray(displaced_dev)
        displaced = displaced[displaced[:, COL_EXPIRE] != 0]
        if displaced.shape[0]:
            self._absorb_demoted(displaced)

    def victim_snapshot(self) -> dict:
        """Tier health for the stats tree (VictimStats — that generator IS
        the reclamation cadence, like HotkeyStats is the sketch drain):
        runs the TTL/window reclaim, then reports occupancy + counters."""
        tier = self._victim
        if tier is None:
            return {"enabled": False}
        now = int(self._time_source.unix_now())
        tier.reclaim(now)
        snap = tier.describe(now)
        snap["enabled"] = True
        snap["hot_refusals"] = self._victim_hot_refusals
        snap["demote_errors"] = self._victim_demote_errors
        snap["promote_skips"] = self._victim_promote_skips
        with self._victim_lock:
            snap["pending_hot"] = len(self._promote_pending)
        return snap

    def victim_debug(self) -> dict:
        """The GET /debug/victim document — victim_snapshot without the
        reclaim side effect (a debug poll must not advance tier state)."""
        tier = self._victim
        if tier is None:
            return {"enabled": False}
        snap = tier.describe(int(self._time_source.unix_now()))
        snap["enabled"] = True
        snap["hot_refusals"] = self._victim_hot_refusals
        snap["demote_errors"] = self._victim_demote_errors
        snap["promote_skips"] = self._victim_promote_skips
        with self._victim_lock:
            snap["pending_hot"] = len(self._promote_pending)
        return snap

    def victim_watermark_reason(self) -> str | None:
        """HealthChecker degraded-probe contract for the tier watermark —
        registered beside the slab's own watermark_reason (runner.py)."""
        if self._victim is None:
            return None
        return self._victim.watermark_reason()

    def _launch_ready(self, tokens) -> bool:
        """Non-blocking readiness probe for a launch token (the dispatch
        loop's overlap decision): True once every chunk's device result
        has materialized. Payloads without is_ready (mesh tokens, numpy
        results from the XLA twin) count as ready — the probe must only
        ever err toward redeeming."""
        for payload, _n in tokens:
            probe = getattr(payload, "is_ready", None)
            if probe is not None and not probe():
                return False
        return True

    def _collect_array(self, token) -> np.ndarray:
        """Blocking readback of one launch token. readback_ms covers the
        wait for device completion plus the D2H drain — the stage a slow
        link inflates (the co-located p99 estimate subtracts it)."""
        with host_span("ratelimit.device.readback"):
            t0 = time.perf_counter() if self._h_readback is not None else 0.0
            payload, n = token
            if self._engine is not None:
                out = self._engine.collect_after_compact(payload)[:n]
            else:
                out = np.asarray(payload)[:n]
            if self._h_readback is not None:
                self._h_readback.record((time.perf_counter() - t0) * 1e3)
        return out

    # -- block-native path (sidecar wire blocks; no per-item objects) --

    @property
    def block_mode(self) -> bool:
        """Public capability flag: the sidecar server routes wire payloads
        through submit_block when this is True (a private-attr getattr
        would silently fall back to the slow per-item path if the field
        were ever renamed)."""
        return self._block_batcher

    def submit_block(self, block: np.ndarray) -> np.ndarray:
        """Batched fixed-window increment over one uint32[6, n] column
        block (the sidecar wire layout: fp_lo, fp_hi, hits, limit, divider,
        jitter) — returns uint32[n] post-increment counters. At aggregated
        sidecar load the per-item path's decode + repack cost ~2.3us/item
        of pure Python (an ~0.4M items/s server ceiling at batch 8k,
        measured in PERF.md); this path goes wire block -> padded device
        block with numpy row copies only. Requires block_mode=True."""
        if not self._block_batcher:
            raise RuntimeError("engine not in block_mode")
        if self._dispatch is not None:
            # wire blocks are one-shot buffers: hand ownership to the ring
            # (no arena copy); results are owned arrays (the server may
            # serialize them after this thread's next frame)
            return self._dispatch.submit(block, owned=True)
        return self._batcher.submit(block)

    def _packed_operand(self, size: int) -> np.ndarray:
        """A (7, size) uint32 launch operand. Single-device engines reuse a
        per-bucket ping-pong pair (every launch arm bounds un-redeemed
        launches to 2, so the buffer handed out is never still readable by
        an in-flight execute); callers must zero the hits-row padding
        after filling. Mesh engines get fresh zeros (their host-side
        owner routing may hold the operand past launch return)."""
        if not self._reuse_operands:
            return np.zeros((7, size), dtype=np.uint32)
        with self._operand_lock:
            pair = self._operand_pool.get(size)
            if pair is None:
                pair = self._operand_pool[size] = [
                    np.zeros((7, size), dtype=np.uint32),
                    np.zeros((7, size), dtype=np.uint32),
                    0,
                ]
            buf = pair[pair[2]]
            pair[2] ^= 1
        return buf

    def _iter_block_chunks(self, blocks: list[np.ndarray]):
        """Yield (packed[7, bucket], n, cap) per max_bucket chunk of the
        submitted blocks. The common case (total fits one launch) gathers
        each block's columns straight into the padded device block — the
        native codec's rl_pack_rows when built, one numpy row copy per
        block otherwise; only an oversized aggregate pays a concatenate
        first. The cap bound uses max(limit)+max(hits) over the chunk — at
        least as wide as the per-item max the item path computes, so the
        saturating readback stays exact."""
        total = sum(b.shape[1] for b in blocks)
        if total <= self._max_bucket:
            size = self._bucket_for(total)
            packed = self._packed_operand(size)
            if self._pack_rows is not None and len(blocks) > 1:
                self._pack_rows(blocks, packed, total)
            else:
                off = 0
                for b in blocks:
                    packed[:6, off : off + b.shape[1]] = b
                    off += b.shape[1]
            # padding lanes: hits == 0 is the only gate the device reads
            packed[2, total:] = 0
            chunks = [(packed, total)]
        else:
            cat = np.concatenate(blocks, axis=1)
            chunks = []
            for off in range(0, total, self._max_bucket):
                chunk = cat[:, off : off + self._max_bucket]
                n = chunk.shape[1]
                packed = np.zeros((7, self._bucket_for(n)), dtype=np.uint32)
                packed[:6, :n] = chunk
                chunks.append((packed, n))
        now = np.uint32(self._time_source.unix_now())
        ratio = np.float32(self._near_limit_ratio).view(np.uint32)
        burst = np.float32(self._gcra_burst_ratio).view(np.uint32)
        for packed, n in chunks:
            maxv = int(packed[2, :n].max()) + int(packed[3, :n].max())
            cap = 0xFF if maxv < 255 else 0xFFFF if maxv < 65535 else 0xFFFFFFFF
            packed[6, 0] = now
            packed[6, 1] = ratio
            packed[6, 2] = burst  # GCRA burst-ratio scalar (ops/slab.py)
            yield packed, n, cap

    def _execute_blocks(self, blocks: list[np.ndarray]) -> np.ndarray:
        return self._execute_blocks_collect(self._execute_blocks_launch(blocks))

    def _execute_blocks_launch(self, blocks: list[np.ndarray]):
        try:
            with host_span("ratelimit.device.pack"):
                t0 = time.perf_counter() if self._h_pack is not None else 0.0
                chunks = list(self._iter_block_chunks(blocks))
                if self._h_pack is not None:
                    self._h_pack.record((time.perf_counter() - t0) * 1e3)
            return [
                self._dispatch_packed(packed, n, cap)
                for packed, n, cap in chunks
            ]
        except Exception as e:
            raise CacheError(f"tpu backend failure: {e}") from e

    def _execute_blocks_collect(self, tokens) -> np.ndarray:
        try:
            outs = [
                self._collect_array(t).astype(np.uint32, copy=False)
                for t in tokens
            ]
            return outs[0] if len(outs) == 1 else np.concatenate(outs)
        except CacheError:
            raise
        except Exception as e:
            raise CacheError(f"tpu backend failure: {e}") from e

def _block_to_items(block: np.ndarray) -> list[_Item]:
    """Inverse adapter for engines that only speak the _Item verb."""
    cols = block.T.tolist()
    return [
        _Item(
            fp=(hi << 32) | lo,
            hits=hits,
            limit=limit,
            divider=divider,
            jitter=jitter,
        )
        for lo, hi, hits, limit, divider, jitter in cols
    ]


def _items_to_block(items: list[_Item]) -> np.ndarray:
    """uint32[6, n] row block from an _Item list — the legacy-verb adapter
    into the block-native engine (wire layout: fp_lo, fp_hi, hits, limit,
    divider, jitter)."""
    n = len(items)
    block = np.empty((6, n), dtype=np.uint32)
    fp = np.fromiter((it.fp for it in items), dtype=np.uint64, count=n)
    block[0], block[1] = split_fingerprints(fp)
    block[2] = np.fromiter((it.hits for it in items), np.uint32, n)
    block[3] = np.fromiter((it.limit for it in items), np.uint32, n)
    block[4] = np.fromiter((it.divider for it in items), np.uint32, n)
    block[5] = np.fromiter((it.jitter for it in items), np.uint32, n)
    return block


class SlabHealthStats:
    """StatGenerator exporting the slab's health on every stats flush:

        ratelimit.slab.evictions.expired  in-kernel reclaims of expired
                                          (TTL-dead) ways — pure reuse
        ratelimit.slab.evictions.window   evictions of live ways whose
                                          fixed window had ended (no
                                          decision state displaced)
        ratelimit.slab.evictions.live     evictions of live in-window ways
                                          — the ONLY lossy tier (the
                                          evicted key fails open)
        ratelimit.slab.drops       cumulative in-batch contention drops
        ratelimit.slab.algo_resets rows reset because a config reload
                                   changed their rule's ALGORITHM mid-
                                   flight (fp matched, semantics did not)
        ratelimit.slab.decisions   cumulative decisions submitted on-device
        ratelimit.slab.loss_ppm    (evictions.live + drops) per million
                                   decisions over the window SINCE THE
                                   LAST FLUSH — the parity-erosion alarm
                                   gauge. A lifetime ratio would dilute
                                   with uptime (1e9 clean decisions hide a
                                   lost 100k-decision burst under
                                   ~100ppm); the per-window delta stays
                                   alarmable forever, and the cumulative
                                   counters are still exported for
                                   dashboards that prefer their own
                                   windows.
        ratelimit.slab.live_slots  currently live (unexpired) slots
        ratelimit.slab.occupancy   live fraction x 1e6 (gauges are ints) —
                                   a SMOOTH gauge all the way to 100%: the
                                   set scan absorbs pressure by value-
                                   ranked eviction, never by shedding
        ratelimit.slab.watermark   0 normal / 1 past SLAB_WATERMARK_HIGH
                                   (observability only)

    The lossy behaviors fail open (ops/slab.py docstring); these gauges
    make the loss rate operable instead of silent. Works for the
    in-process engine and the mesh-sharded engine alike (both expose
    health_snapshot())."""

    def __init__(self, engine, scope):
        self._engine = engine
        self._last = {
            "evictions_live": 0,
            "drops": 0,
            "decisions": 0,
        }
        # dotted literals (not a sub-scope): the metrics lint treats each
        # literal as a Prometheus family name, and bare "expired"/"window"
        # would collide with the lease counters of the same spelling
        self._gauges = {
            "evictions_expired": scope.gauge("evictions.expired"),
            "evictions_window": scope.gauge("evictions.window"),
            "evictions_live": scope.gauge("evictions.live"),
            "drops": scope.gauge("drops"),
            "algo_resets": scope.gauge("algo_resets"),
            "decisions": scope.gauge("decisions"),
            "loss_ppm": scope.gauge("loss_ppm"),
            "live_slots": scope.gauge("live_slots"),
            "occupancy": scope.gauge("occupancy"),
            "watermark": scope.gauge("watermark"),
        }

    def generate_stats(self) -> None:
        snap = self._engine.health_snapshot()
        for k in (
            "evictions_expired",
            "evictions_window",
            "evictions_live",
            "drops",
            "algo_resets",
        ):
            self._gauges[k].set(snap.get(k, 0))
        self._gauges["decisions"].set(snap.get("decisions", 0))
        delta = {k: snap.get(k, 0) - v for k, v in self._last.items()}
        self._last = {k: snap.get(k, 0) for k in self._last}
        self._gauges["loss_ppm"].set(_loss_ppm(delta))
        self._gauges["live_slots"].set(snap["live_slots"])
        self._gauges["occupancy"].set(int(snap["occupancy"] * 1_000_000))
        self._gauges["watermark"].set(snap.get("watermark", 0))


class HotkeyStats:
    """StatGenerator draining the heavy-hitter sketch on every stats flush
    (SlabDeviceEngine.drain_hotkeys — this generator IS the drain cadence):

        ratelimit.hotkeys.tracked    occupied top-K entries the last drain
                                     reported (<= HOTKEY_K)
        ratelimit.hotkeys.top_count  the hottest key's space-saving
                                     estimate at drain time — the sketch
                                     decays by half each drain, so this
                                     tracks the CURRENT traffic mix
        ratelimit.hotkeys.drains     cumulative drains (liveness: flat
                                     while traffic flows means the stats
                                     loop stalled, not the traffic)

    The ranked entries themselves ship via GET /debug/hotkeys (gauges
    cannot carry a keyed list); this exports the alarmable envelope."""

    def __init__(self, engine, scope):
        self._engine = engine
        self._g_tracked = scope.gauge("tracked")
        self._g_top = scope.gauge("top_count")
        self._c_drains = scope.counter("drains")
        self._drains_seen = 0

    def generate_stats(self) -> None:
        top = self._engine.drain_hotkeys()
        self._g_tracked.set(len(top))
        self._g_top.set(top[0][2] if top else 0)
        drains = self._engine._hotkey_drains
        self._c_drains.add(drains - self._drains_seen)
        self._drains_seen = drains


class VictimStats:
    """StatGenerator exporting the victim tier on every stats flush
    (SlabDeviceEngine.victim_snapshot — this generator IS the tier's
    TTL/window reclamation cadence, like HotkeyStats is the sketch
    drain):

        ratelimit.victim.rows            rows currently parked in the tier
        ratelimit.victim.demotes         cumulative demoted live rows
                                         absorbed from eviction readbacks
        ratelimit.victim.promotes        cumulative rows promoted back
                                         onto the slab (retired landed)
        ratelimit.victim.hot_refusals    sketch-hot rows that refused
                                         demotion (parked for next-launch
                                         re-inject instead)
        ratelimit.victim.reclaimed       rows dropped by TTL/window-aware
                                         reclamation (dead state, not loss)
        ratelimit.victim.overflow_drops  value-ranked losses past
                                         VICTIM_MAX_ROWS — the tier's ONLY
                                         lossy behavior
        ratelimit.victim.overflow_lost_count_sum
                                         sum of the counter values those
                                         drops forgot — the ledger the
                                         differential false-admit bound
                                         is stated against
                                         (tests/test_victim.py)
        ratelimit.victim.watermark       0 normal / 1 past VICTIM_WATERMARK
                                         (sticky degraded probe mirror)

    The full document (age histogram, capacity, fault-site counters)
    ships via GET /debug/victim; this exports the alarmable envelope."""

    def __init__(self, engine, scope):
        self._engine = engine
        self._gauges = {
            "rows": scope.gauge("rows"),
            "demotes": scope.gauge("demotes"),
            "promotes": scope.gauge("promotes"),
            "hot_refusals": scope.gauge("hot_refusals"),
            "reclaimed": scope.gauge("reclaimed"),
            "overflow_drops": scope.gauge("overflow_drops"),
            "overflow_lost_count_sum": scope.gauge("overflow_lost_count_sum"),
            "watermark": scope.gauge("watermark"),
        }

    def generate_stats(self) -> None:
        snap = self._engine.victim_snapshot()
        if not snap.get("enabled"):
            return
        for k, g in self._gauges.items():
            if k == "watermark":
                g.set(snap.get("watermark_state", 0))
            else:
                g.set(snap.get(k, 0))


class TpuRateLimitCache:
    """limiter.RateLimitCache implementation backed by the TPU slab."""

    def __init__(
        self,
        base_limiter: BaseRateLimiter,
        n_slots: int = 1 << 22,
        ways: int = 0,
        batch_window_seconds: float = 0.0,
        max_batch: int = 65536,
        buckets: Sequence[int] = (128, 1024, 8192, 65536),
        device=None,
        use_pallas: bool | None = None,
        mesh=None,
        engine=None,
        stats_scope=None,
        max_queue: int = 0,
        watermark_high: float = 0.0,
        overload=None,
        fault_injector=None,
        precompile: bool = False,
        lease_table=None,
        gcra_burst_ratio: float = 1.0,
        hotkey_lanes: int = 0,
        hotkey_k: int = 16,
        victim_max_rows: int = 0,
        victim_watermark: float = 0.85,
        shard_routed_batching: bool = True,
        hot_tier_enabled: bool = True,
        hot_tier_salt_ways: int = 0,
    ):
        """engine: anything with submit(items)->afters / flush / close —
        defaults to an in-process SlabDeviceEngine; the sidecar frontend
        passes a socket client instead (backends/sidecar.py). Engines
        additionally exposing submit_rows(uint32[6, n]) -> uint32[n] get
        the zero-object row path (do_limit_resolved).

        precompile: compile the in-process engine's whole bucket ladder at
        construction (SlabDeviceEngine.precompile) so no request rides a
        first-touch JIT compile.

        stats_scope: optional stats Scope (the runner's `ratelimit` root);
        forwarded to the in-process engine for device/batcher histograms.
        A caller-provided engine owns its own telemetry wiring.

        max_queue / watermark_* / overload / fault_injector: admission-
        control wiring for the in-process engine (see SlabDeviceEngine);
        ignored when a caller-provided engine is passed.

        lease_table: optional backends.lease.LeaseTable (LEASE_ENABLED).
        When set, do_limit_resolved plans a lease grant for each descriptor
        that missed the frontend-local decide path: the descriptor's row
        ships hits + lease_n (a batched INCRBY riding the normal launch),
        the returned counter registers the lease, and the caller's own
        decision uses after - lease_n. Queued settle records drain onto
        the same submits. Requires an engine whose submit_rows accepts
        lease_ops (the in-process engine and the sidecar client both do);
        silently disabled otherwise."""
        self._base = base_limiter
        # Prewarm the native host codec so the first request never pays the
        # on-demand g++ compile inside do_limit (ops/native.py ensure_built).
        from ..ops import native

        native.available()
        if engine is None:
            engine = SlabDeviceEngine(
                time_source=base_limiter.time_source,
                near_limit_ratio=base_limiter.near_limit_ratio,
                n_slots=n_slots,
                ways=ways,
                batch_window_seconds=batch_window_seconds,
                max_batch=max_batch,
                buckets=buckets,
                device=device,
                use_pallas=use_pallas,
                mesh=mesh,
                scope=stats_scope,
                max_queue=max_queue,
                watermark_high=watermark_high,
                overload=overload,
                fault_injector=fault_injector,
                precompile=precompile,
                gcra_burst_ratio=gcra_burst_ratio,
                hotkey_lanes=hotkey_lanes,
                hotkey_k=hotkey_k,
                victim_max_rows=victim_max_rows,
                victim_watermark=victim_watermark,
                shard_routed_batching=shard_routed_batching,
                hot_tier_enabled=hot_tier_enabled,
                hot_tier_salt_ways=hot_tier_salt_ways,
            )
        self._engine_core = engine
        # per-algorithm decision stats (ratelimit.algo.<name>.{decisions,
        # over_limit}): which decision kernel is carrying the traffic, and
        # which one is denying it — the per-rule stats can't answer that
        # without knowing every rule's algorithm by heart
        self._algo_stats = None
        if stats_scope is not None:
            algo_scope = stats_scope.scope("algo")
            self._algo_stats = {
                0: (
                    algo_scope.counter("fixed_window.decisions"),
                    algo_scope.counter("fixed_window.over_limit"),
                ),
                1: (
                    algo_scope.counter("sliding_window.decisions"),
                    algo_scope.counter("sliding_window.over_limit"),
                ),
                2: (
                    algo_scope.counter("gcra.decisions"),
                    algo_scope.counter("gcra.over_limit"),
                ),
                3: (
                    algo_scope.counter("concurrency.decisions"),
                    algo_scope.counter("concurrency.over_limit"),
                ),
            }
        # zero-object row verb when the engine has one (the in-process
        # engine and the sidecar client both do; exotic test engines fall
        # back to the _Item conversion)
        self._submit_rows = getattr(engine, "submit_rows", None)
        # hierarchical quota leasing (backends/lease.py): only engines with
        # the row verb can carry the grant riders, so exotic item-only test
        # engines quietly run unleased
        self._lease = lease_table if self._submit_rows is not None else None
        # per-thread scratch row block: do_limit_resolved fills columns in
        # place and the engine consumes it before the submit returns (the
        # dispatch loop copies it into this thread's submit ring; direct
        # mode executes it), so the steady-state request path allocates no
        # numpy buffers
        self._scratch = threading.local()
        # host-stage histograms (bench host_split + GET /metrics): the
        # descriptor-admission/key-compose loop and the status-build loop,
        # in sub-millisecond buckets (these stages run in microseconds)
        self._h_key_compose = self._h_response = None
        if stats_scope is not None:
            from ..stats.store import HOST_STAGE_BUCKETS_MS

            host_scope = stats_scope.scope("host")
            self._h_key_compose = host_scope.histogram(
                "key_compose_ms", boundaries=HOST_STAGE_BUCKETS_MS
            )
            self._h_response = host_scope.histogram(
                "response_ms", boundaries=HOST_STAGE_BUCKETS_MS
            )
        # (domain, entries, divider) -> fingerprint. Rate-limit traffic is
        # Zipfian (hot keys dominate), so memoizing descriptor hashes removes
        # the hashing cost for the hot set; clear-on-full bounds a hostile
        # key flood the same way the near-threshold memo does. (The legacy
        # do_limit path only — resolved records carry their fingerprint.)
        self._fp_cache: dict = {}
        self._fp_cache_max = 1 << 17
        # hotkeys witness cache: combined fp -> descriptor key prefix,
        # recorded at compose time so a drained fingerprint resolves back
        # to the human key in /debug/hotkeys. Bounded clear-on-full like
        # _fp_cache; None when the engine runs without a sketch (zero
        # hot-path cost on the HOTKEYS_ENABLED=false arm).
        self._witness: dict | None = (
            {} if getattr(engine, "hotkeys_enabled", False) else None
        )
        self._witness_max = 1 << 15
        # sketch-driven adaptive lease sizing: each drain pre-seeds the
        # lease table's size map for the ranked-hot keys, so a hot key's
        # FIRST grant of a window is already LEASE_MAX-bounded large
        # instead of climbing there through exhaustion-renewal doublings
        if self._witness is not None and self._lease is not None:
            engine.add_hotkey_listener(
                lambda _top, fps: self._lease.note_hot_fps(fps)
            )

    def victim_debug(self) -> dict:
        """The /debug/victim document: the engine's tier health snapshot
        (occupancy, counters, age histogram) — {"enabled": False} when
        the engine runs without a tier (sidecar clients, test engines)."""
        fn = getattr(self._engine_core, "victim_debug", None)
        if fn is None:
            return {"enabled": False}
        return fn()

    def hotkeys_debug(self) -> dict:
        """The /debug/hotkeys document: the engine's last drained top-K
        with each fingerprint resolved to its descriptor key where the
        witness cache saw one composed."""
        snap_fn = getattr(self._engine_core, "hotkeys_snapshot", None)
        if snap_fn is None:
            return {"enabled": False, "top": []}
        doc = snap_fn()
        witness = self._witness
        if witness is not None:
            for entry in doc["top"]:
                entry["key"] = witness.get(int(entry["fp"], 16))
        return doc

    @property
    def engine(self):
        """The device driver (SlabDeviceEngine, ShardedSlabEngine via its
        wrapper, or a SidecarEngineClient) — the runner hangs slab health
        stats off it when it exposes health_snapshot()."""
        return self._engine_core

    @property
    def _batcher(self):
        """Test seam: the in-process engine's direct-mode batcher."""
        return self._engine_core._batcher

    # -- RateLimitCache interface --

    def do_limit(
        self,
        request: RateLimitRequest,
        limits: Sequence[RateLimit | None],
    ) -> DoLimitResponse:
        hits_addend = max(1, request.hits_addend)
        cache_keys = self._base.generate_cache_keys(request, limits, hits_addend)

        span = tag_do_limit_start("tpu", len(limits), len(cache_keys))

        n = len(request.descriptors)
        over_local = [False] * n
        results = [0] * n

        pending: list[tuple[int, int, int]] = []  # (desc idx, divider, jitter)
        for i, cache_key in enumerate(cache_keys):
            if cache_key.key == "":
                continue
            if self._base.is_over_limit_with_local_cache(cache_key.key, limits[i]):
                over_local[i] = True
                continue
            divider = unit_to_divider(limits[i].unit)
            jitter = self._base.expiration_seconds(divider) - divider
            pending.append((i, divider, jitter))

        # fingerprints: memo hit for hot keys, one batched pass (native
        # codec when available) for the misses
        fp_cache = self._fp_cache
        fps: list[int] = [0] * len(pending)
        miss_pos: list[int] = []
        miss_keys: list[tuple] = []
        miss_records = []
        miss_seeds: list[int] = []
        for pos, (i, divider, _jitter) in enumerate(pending):
            entries = request.descriptors[i].entries
            cache_key = (request.domain, entries, divider)
            fp = fp_cache.get(cache_key)
            if fp is None:
                miss_pos.append(pos)
                miss_keys.append(cache_key)
                miss_records.append((request.domain, entries))
                miss_seeds.append(divider)
            else:
                fps[pos] = fp
        if miss_records:
            if len(fp_cache) + len(miss_records) > self._fp_cache_max:
                fp_cache.clear()
            for pos, key, fp in zip(
                miss_pos, miss_keys, fingerprint_many(miss_records, miss_seeds)
            ):
                fps[pos] = fp_cache[key] = int(fp)

        items = [
            _Item(
                fp=fp,
                hits=hits_addend,
                limit=limits[i].requests_per_unit,
                divider=divider,
                jitter=jitter,
            )
            for fp, (i, divider, jitter) in zip(fps, pending)
        ]
        item_slots = [i for i, _, _ in pending]  # descriptor index per item

        if span is not None:
            span.log_kv(event="lookup.start", batch_items=len(items))
        try:
            afters = self._engine_core.submit(items)
        except Exception as e:
            # error-tag the span here, where the failure happened: the
            # service boundary marks its own copy, but a do_limit driven
            # directly (tests, tools) must not leave a clean-looking span
            # for a failed lookup (QueueFullError, DeadlineExceededError,
            # CacheError all land here)
            if span is not None:
                span.set_error(e)
            raise
        for after, i in zip(afters, item_slots):
            results[i] = after
        if span is not None:
            span.log_kv(event="tpu.lookup.done", client="slab")

        response = DoLimitResponse()
        for i, cache_key in enumerate(cache_keys):
            limit = limits[i]
            info = (
                LimitInfo(limit, results[i] - hits_addend, results[i])
                if limit is not None
                else None
            )
            key = cache_key.key
            if (
                key != ""
                and not over_local[i]
                and self._base.local_cache is not None
                and limit is not None
                and not limit.shadow_mode
                and results[i] > limit.requests_per_unit
            ):
                # The batched decision may have landed in a LATER fixed
                # window than the one `key` was stamped with: re-stamp at the
                # current clock so the oracle's over-limit cache entry is the
                # one later requests will actually look up.
                key = generate_cache_key(
                    request.domain,
                    request.descriptors[i],
                    limit,
                    self._base.time_source.unix_now(),
                ).key
            response.descriptor_statuses.append(
                self._base.get_response_descriptor_status(
                    key, info, over_local[i], hits_addend, response
                )
            )
        assert_(len(response.descriptor_statuses) == n)
        return response

    def _scratch_block(self, n: int) -> np.ndarray:
        """This thread's reusable uint32[6, >=n] staging block."""
        block = getattr(self._scratch, "block", None)
        if block is None or block.shape[1] < n:
            block = self._scratch.block = np.empty(
                (6, max(64, n)), dtype=np.uint32
            )
        return block

    def do_limit_resolved(self, request, resolved) -> DoLimitResponse:
        """Zero-object hot path: one precompiled ResolvedLimit record per
        descriptor (config/compiled.py) instead of (limits, string keys,
        _Item objects). Per descriptor the admission loop does counter
        adds, the optional local-cache probe (key = precomputed prefix +
        window — no joins), and six uint32 column writes into this
        thread's scratch block; the whole request then submits as ONE row
        block to the engine. Decision-identical to do_limit by
        construction: the same BaseRateLimiter oracle builds every status
        (differential-tested in tests/test_compiled_matcher.py)."""
        base = self._base
        hits_addend = max(1, request.hits_addend)
        time_source = base.time_source
        now = time_source.unix_now()
        local_cache = base.local_cache
        n = len(resolved)
        span = tag_do_limit_start("tpu", n, n)

        h_key = self._h_key_compose
        t0 = time.perf_counter() if h_key is not None else 0.0
        block = self._scratch_block(n)
        pending_count = 0
        keys = [None] * n if local_cache is not None else None
        over_local: list[bool] | None = None
        lease = self._lease
        grants: list | None = None
        # hotkeys witness + journey flag (both None/empty on the disabled
        # arm — the probe below compiles out to two dict/set no-ops)
        witness = self._witness
        hot_fps = (
            self._engine_core.hot_fps if witness is not None else None
        )
        for i in range(n):
            rec = resolved[i]
            if rec is None:
                continue
            rec.stats.total_hits.add(hits_addend)
            if witness is not None:
                wfp = (rec.fp_hi << 32) | rec.fp_lo
                if wfp not in witness:
                    if len(witness) >= self._witness_max:
                        witness.clear()
                    witness[wfp] = rec.key_prefix
                if hot_fps and wfp in hot_fps:
                    # flight-recorder breadcrumb: this request touched a
                    # sketch-ranked hot key (tail-samples "slow AND hot")
                    journeys.note_flag(journeys.FLAG_HOTKEY)
            divider = rec.divider
            if local_cache is not None:
                key = rec.key_prefix + str((now // divider) * divider)
                keys[i] = key
                # shadow rules never consult the over-limit cache
                # (base_limiter.is_over_limit_with_local_cache rationale);
                # neither does any non-fixed algorithm — a denial is not
                # sticky for a window there: a Release can free a slot, a
                # GCRA TAT drains continuously, a sliding position decays
                if (
                    not rec.shadow_mode
                    and rec.algorithm == ALGO_ID_FIXED_WINDOW
                    and local_cache.contains(key)
                ):
                    if over_local is None:
                        over_local = [False] * n
                    over_local[i] = True
                    continue
            block[:, pending_count] = (
                rec.fp_lo,
                rec.fp_hi,
                hits_addend,
                rec.requests_per_unit,
                # window length + algorithm id in one word (precomposed;
                # == divider for fixed_window, so the default config's
                # wire frames are byte-identical)
                rec.wire_divider,
                base.expiration_seconds(divider) - divider,
            )
            if lease is not None:
                # lease grant rider: this descriptor missed the frontend-
                # local decide path, so its row carries the lease INCRBY —
                # hits + lease_n through the unmodified launch machinery
                planned = lease.plan_grant(rec, hits_addend, now)
                if planned is not None:
                    block[2, pending_count] = hits_addend + planned.size
                    if grants is None:
                        grants = []
                    grants.append((pending_count, planned))
            pending_count += 1
        if h_key is not None:
            h_key.record((time.perf_counter() - t0) * 1e3)

        lease_ops = None
        settles = ()
        if lease is not None and pending_count:
            settles = lease.drain_settles()
            if grants or settles:
                lease_ops = LeaseOps(
                    grants=[
                        (pos, p.size, p.window, p.ttl_s)
                        for pos, p in grants or ()
                    ],
                    settles=settles,
                )

        if span is not None:
            span.log_kv(event="lookup.start", batch_items=pending_count)
        try:
            if pending_count:
                if self._submit_rows is not None:
                    if lease_ops is not None:
                        afters = self._submit_rows(
                            block[:, :pending_count], lease_ops=lease_ops
                        ).tolist()
                    else:
                        afters = self._submit_rows(
                            block[:, :pending_count]
                        ).tolist()
                else:
                    afters = self._engine_core.submit(
                        _block_to_items(block[:, :pending_count])
                    )
            else:
                afters = ()
        except Exception as e:
            if settles:
                # the settle records never reached the owner; requeue for
                # the next successful submit (advisory, TTL-bounded)
                lease.requeue_settles(settles)
            if grants:
                # riders whose answer was lost: release the in-flight
                # marks so the next miss can plan a fresh grant
                for _pos, planned in grants:
                    lease.abort_grant(planned)
            # see do_limit: the exception path must error-tag the span
            if span is not None:
                span.set_error(e)
            raise
        if grants:
            # install each granted lease and strip its rider from the
            # caller's own post-increment position (after - lease_n)
            for pos, planned in grants:
                after_total = afters[pos]
                if (
                    int(block[4, pos]) >> ALGO_SHIFT
                ) == ALGO_ID_GCRA and after_total > int(block[3, pos]):
                    # a DENIED GCRA rider reserved nothing: denials never
                    # advance the TAT, so the slice does not exist —
                    # installing it would serve denials locally until its
                    # TTL even after the TAT drains. Abort instead; the
                    # next miss plans a fresh slice.
                    lease.abort_grant(planned)
                    afters[pos] = after_total - planned.size
                else:
                    afters[pos] = lease.register_grant(planned, after_total)
        if span is not None:
            span.log_kv(event="tpu.lookup.done", client="slab")

        t0 = time.perf_counter() if self._h_response is not None else 0.0
        response = DoLimitResponse()
        statuses = response.descriptor_statuses
        get_status = base.get_response_descriptor_status
        algo_stats = self._algo_stats
        pos = 0
        for i in range(n):
            rec = resolved[i]
            if rec is None:
                statuses.append(
                    get_status("", None, False, hits_addend, response)
                )
                continue
            limit = rec.limit
            if over_local is not None and over_local[i]:
                if algo_stats is not None:
                    dec_c, over_c = algo_stats[rec.algorithm]
                    dec_c.add(1)
                    over_c.add(1)
                statuses.append(
                    get_status(
                        keys[i],
                        LimitInfo(limit, -hits_addend, 0),
                        True,
                        hits_addend,
                        response,
                    )
                )
                continue
            after = afters[pos]
            pos += 1
            if algo_stats is not None:
                dec_c, over_c = algo_stats[rec.algorithm]
                dec_c.add(1)
                if after > rec.requests_per_unit:
                    over_c.add(1)
                    # flight-recorder breadcrumb: which algorithm decided
                    # this (possibly slow/shed) request's denial
                    journeys.mark(ALGO_JOURNEY_STAGES[rec.algorithm])
            info = LimitInfo(limit, after - hits_addend, after)
            if local_cache is not None:
                key = keys[i]
                if not rec.shadow_mode and after > rec.requests_per_unit:
                    # the batched decision may have landed in a LATER
                    # window than the key was stamped with (do_limit's
                    # re-stamp rationale)
                    now2 = time_source.unix_now()
                    key = rec.key_prefix + str(
                        (now2 // rec.divider) * rec.divider
                    )
            else:
                # no local cache: the key's only remaining job is the
                # non-empty "checked" marker — the prefix serves without
                # composing a window key
                key = rec.key_prefix
            statuses.append(
                get_status(key, info, False, hits_addend, response)
            )
        if self._h_response is not None:
            self._h_response.record((time.perf_counter() - t0) * 1e3)
        assert_(len(statuses) == n)
        return response

    def do_release(self, request, resolved) -> int:
        """Concurrency Release: one negative-rider row per resolved
        CONCURRENCY descriptor, riding the unmodified row-block/dispatch
        wire (algorithm id ALGO_CONC_RELEASE in the divider word — the
        sidecar and shm-ring paths carry it with zero format change). The
        device decrements the key's in-flight count, flooring at 0.
        Returns the number of release rows submitted; descriptors whose
        rule is not a concurrency cap are ignored. Callers that die
        without releasing are covered by the row's idle TTL
        (CONCURRENCY_TTL_S): an untouched key's whole row is reclaimed
        and its in-flight count restarts at zero."""
        hits_addend = max(1, request.hits_addend)
        base = self._base
        block = self._scratch_block(len(resolved))
        count = 0
        for rec in resolved:
            if rec is None or rec.algorithm != ALGO_ID_CONCURRENCY:
                continue
            block[:, count] = (
                rec.fp_lo,
                rec.fp_hi,
                hits_addend,
                rec.requests_per_unit,
                rec.divider | (ALGO_CONC_RELEASE << ALGO_SHIFT),
                base.expiration_seconds(rec.divider) - rec.divider,
            )
            count += 1
        if count:
            if self._submit_rows is not None:
                self._submit_rows(block[:, :count])
            else:
                self._engine_core.submit(_block_to_items(block[:, :count]))
        return count

    def flush(self) -> None:
        self._engine_core.flush()

    def close(self) -> None:
        self._engine_core.close()
