"""Hash-sharded slab: the multi-chip decision engine.

TPU-native analog of Redis Cluster mode (src/redis/driver_impl.go:104-110).
There, radix hashes each key to a cluster slot and sends the command to the
owning Redis node over TCP. Here:

  * The slab table `uint32[n_global, ROW_WIDTH]` is sharded along the slot
    axis over a 1-D `Mesh` axis ("shard"); each device holds an independent
    open-addressed sub-table (`n_global / n_devices` rows).
  * Each micro-batch (the packed uint32[7, b] block of ops/slab.py) is
    replicated to all devices — batches are a few KB while ICI all-to-all
    routing would need dynamic per-shard item counts, which XLA can't shape
    statically. Every device computes `owner = (fp_lo ^ fp_hi) mod n_dev`
    per lane and masks hits to 0 for lanes it does not own, so the existing
    padding machinery (hits == 0 => no probe, no write) skips them.
  * Each device runs the SAME single-device program (ops/slab.py) against
    its local shard — pure SPMD, one trace, no per-device code.
  * Lane outputs are zeroed on non-owners and combined with ONE
    `lax.psum` over the mesh axis; the result block is replicated, so any
    host/controller reads the full batch's decisions. This is the "per-window
    counts combined over ICI" north star (SURVEY.md section 2.8).

Service replication (nomad app_count = 2..3 against one shared Redis,
nomad/apigw-ratelimit/common.hcl:2) maps onto this too: N serving processes
feed batches into one mesh-wide program, and limits stay globally correct
because each key has exactly one owning shard — the same single-writer
property Redis Cluster gives the reference.

Window rollover, duplicate serialization, collision policy and decision math
are all inherited from ops/slab.py — the shard boundary only selects WHICH
table a key lives in, never changes the per-key algorithm, so single-chip
parity tests certify the sharded path as well.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..ops.hashing import hot_slice_fp
from ..ops.slab import (
    ALGO_SHIFT,
    COL_COUNT,
    COL_DIVIDER,
    COL_EXPIRE,
    COL_FP_HI,
    COL_FP_LO,
    COL_WINDOW,
    DEFAULT_WAYS,
    HEALTH_ALGO_RESETS,
    HEALTH_DROPS,
    HEALTH_EVICT_EXPIRED,
    HEALTH_EVICT_LIVE,
    HEALTH_EVICT_WINDOW,
    PACKED_OUT_ROWS,
    ROW_DIVIDER,
    ROW_FP_HI,
    ROW_FP_LO,
    ROW_HITS,
    ROW_LIMIT,
    ROW_SCALARS,
    ROW_WIDTH,
    ParkedHealth,
    SlabState,
    _slab_step_sorted,
    _slab_update_sorted,
    _unpack,
    _unsort,
    default_ways,
    find_row_host,
    live_slot_count,
    validate_ways,
)

_log = logging.getLogger(__name__)

SHARD_AXIS = "shard"


def make_mesh(devices=None, axis: str = SHARD_AXIS) -> Mesh:
    """1-D mesh over all (or the given) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def _owner_mask(fp_lo, fp_hi, axis: str):
    """Boolean[b]: does this device own each lane's key?

    Ownership bits are (fp_lo ^ fp_hi) mod n_dev — independent of the probe
    sequence (position fp_lo, stride fp_hi|1) so sharding does not bias the
    local probe distribution.
    """
    n_dev = jax.lax.psum(1, axis)
    me = jax.lax.axis_index(axis)
    owner = (fp_lo ^ fp_hi) % jnp.uint32(n_dev)
    return owner == me.astype(jnp.uint32)


def _sharded_body(table, packed, *, ways: int, use_pallas: bool, axis: str):
    """Per-device body under shard_map. table: local shard [n_local, ROW_WIDTH];
    packed: replicated uint32[7, b]. Returns (new local shard, replicated
    uint32[8, b] results in arrival order, uint32[2] mesh-wide health)."""
    batch, now, near_ratio, burst_ratio = _unpack(packed)

    owned = _owner_mask(batch.fp_lo, batch.fp_hi, axis)
    batch = batch._replace(hits=jnp.where(owned, batch.hits, jnp.uint32(0)))

    state, s_before, s_after, d, order, health = _slab_step_sorted(
        SlabState(table=table), batch, now, near_ratio, ways, use_pallas,
        burst_ratio=burst_ratio,
    )

    # Unsort ON DEVICE (the host-side unsort trick of slab_step_packed does
    # not compose with psum: each device has its own permutation).
    out = jnp.stack(
        [
            d.code.astype(jnp.uint32),
            d.limit_remaining,
            d.duration_until_reset.astype(jnp.uint32),
            d.throttle_millis,
            d.near_delta,
            d.over_delta,
            s_before,
            s_after,
        ]
    )
    out = _unsort(out.T, order).T
    out = jnp.where(owned[None, :], out, jnp.uint32(0))
    # non-owned lanes ride through with hits=0 (invalid), so each shard's
    # health already counts only its own keys; psum = mesh-wide totals
    return state.table, jax.lax.psum(out, axis), jax.lax.psum(health, axis)


def _sharded_body_after(
    table, packed, *, ways: int, cap: int, use_pallas: bool, axis: str
):
    """after-mode per-device body: stateful update only; psum the single
    saturating-cast post-increment row (see ops/slab.py compact modes) and
    the uint32[2] health vector."""
    batch, now, _near, burst_ratio = _unpack(packed)

    owned = _owner_mask(batch.fp_lo, batch.fp_hi, axis)
    batch = batch._replace(hits=jnp.where(owned, batch.hits, jnp.uint32(0)))

    state, _before, s_after, _inputs, order, health, _ = _slab_update_sorted(
        SlabState(table=table), batch, now, ways, use_pallas=use_pallas,
        burst_ratio=burst_ratio,
    )
    after = jnp.minimum(_unsort(s_after, order), jnp.uint32(cap))
    after = jnp.where(owned, after, jnp.uint32(0))
    # psum in uint32 (ICI collectives want word lanes), then narrow to the
    # smallest dtype cap fits so the host readback ships 1-2 bytes/item like
    # the single-chip path (ops/slab.py compact modes).
    summed = jax.lax.psum(after, axis)
    health = jax.lax.psum(health, axis)
    if cap <= 0xFF:
        return state.table, summed.astype(jnp.uint8), health
    if cap <= 0xFFFF:
        return state.table, summed.astype(jnp.uint16), health
    return state.table, summed, health


def _build_step(mesh: Mesh, body, out_spec: P, **kw):
    axis = mesh.axis_names[0]
    mapped = jax.shard_map(
        functools.partial(body, axis=axis, **kw),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(axis, None), out_spec, P(None)),
    )
    return jax.jit(mapped, donate_argnums=(0,))


def sharded_slab_step(mesh: Mesh, ways: int = DEFAULT_WAYS, use_pallas: bool = False):
    """Build the jitted mesh-wide full step: (state, packed) -> (state,
    out[8, b]). state is sharded P(axis, None); packed and out are
    replicated. Compiled once per batch-bucket shape (the backend pads to
    fixed buckets)."""
    return _build_step(
        mesh, _sharded_body, P(None, None), ways=ways, use_pallas=use_pallas
    )


def sharded_slab_step_after(
    mesh: Mesh, cap: int, ways: int = DEFAULT_WAYS, use_pallas: bool = False
):
    """Build the jitted mesh-wide after-mode step: (state, packed) ->
    (state, after[b] saturated at cap), the production readback path."""
    return _build_step(
        mesh,
        _sharded_body_after,
        P(None),
        ways=ways,
        cap=cap,
        use_pallas=use_pallas,
    )


# --- compacted per-shard mode ------------------------------------------------
#
# The replicated modes above ship the WHOLE batch to every device: correct,
# but each chip sorts/probes all b items and the full result block rides an
# ICI psum — adding chips adds slab capacity, not decisions/sec (VERDICT
# round 1 weak #4). The compacted mode is the true Redis-Cluster analog
# (src/redis/driver_impl.go:104-110: the CLIENT hashes each key and sends
# the command to its owning node): the HOST buckets items by owner shard
# into a statically-shaped uint32[n_dev, 7, bucket] block, places it
# sharded so each device receives ONLY its own bucket, and every chip
# sorts/probes ~b/n_dev items against its local sub-table. No psum on the
# result path at all — each lane is owned by exactly one shard and the
# host reassembles arrival order from the routing permutation it built.
# Bucket sizes round up to powers of two so XLA compiles a handful of
# shapes; a pathologically skewed batch just gets a bigger bucket (worst
# case b: one shard does all the work, which is what the data demanded).
#
# Scaling evidence + the skew caveat (measured, bench `per_device_cost`
# field and tests/test_sharded_slab.py::TestPerDeviceCostScaling): with
# balanced routing the per-chip compiled cost is ~1/N of the
# single-device program (0.1241 flops / 0.1303 bytes at N=8, ideal
# 0.125). Under single-key skew the hot shard used to set the bucket
# for ALL shards (SPMD: one program shape) — the bench's Zipf(1.1)
# stream puts ~54% of a batch on one shard, the hot-shard property the
# reference inherits from Redis Cluster (one key lives on one node).
# Two cures now ship, both host-side and both spy-pinned byte-identical
# to this arm when disabled:
#
#   * ROUTED PER-SHARD BATCHING (routed=True, SHARD_ROUTED_BATCHING):
#     each shard gets its OWN power-of-two bucket sized to its own row
#     count instead of one global bucket sized to the hottest shard,
#     dispatched as independent per-device launches (no shard_map, no
#     psum — jax's async dispatch overlaps the shards). A cold shard
#     pads to 128 lanes while the hot shard pads to its real load, so
#     Zipf padding waste collapses (the sharded_zipf bench prices it;
#     the ratelimit.shard.* gauges export it).
#   * REPLICATED HOT-KEY TIER (hot_tier=True, HOT_TIER_ENABLED): keys
#     the top-K summary flags as hot are salted across shards
#     (ops/hashing.py hot_slice_fp) so each shard holds a split-quota
#     slice (ceil(limit/K)); demotion settles the slices back into the
#     home row with the keep-the-newest merge. The single-owner counter
#     model is preserved for every non-hot key; a hot key trades a
#     provably bounded per-window false_over (<= K*ceil(limit/K) -
#     limit) for no longer pinning one shard.


def _sharded_body_after_compact(
    table, block, *, ways: int, cap: int, use_pallas: bool, axis: str
):
    """block: [1, 7, bucket] — this device's own bucket only. No owner
    masking needed: the host routed every item here because this shard owns
    it. Returns ([1, bucket] saturated counters, mesh-summed health)."""
    batch, now, _near, burst_ratio = _unpack(block[0])
    state, _before, s_after, _inputs, order, health, _ = _slab_update_sorted(
        SlabState(table=table), batch, now, ways, use_pallas=use_pallas,
        burst_ratio=burst_ratio,
    )
    after = jnp.minimum(_unsort(s_after, order), jnp.uint32(cap))
    health = jax.lax.psum(health, axis)
    if cap <= 0xFF:
        after = after.astype(jnp.uint8)
    elif cap <= 0xFFFF:
        after = after.astype(jnp.uint16)
    return state.table, after[None, :], health


def sharded_slab_step_after_compact(
    mesh: Mesh, cap: int, ways: int = DEFAULT_WAYS, use_pallas: bool = False
):
    """(state, blocks[n_dev, 7, bucket]) -> (state, after[n_dev, bucket],
    health[2]); state and blocks sharded on the leading axis, after sharded
    the same way (the host gathers and unscatters), health replicated."""
    axis = mesh.axis_names[0]
    mapped = jax.shard_map(
        functools.partial(
            _sharded_body_after_compact,
            axis=axis,
            ways=ways,
            cap=cap,
            use_pallas=use_pallas,
        ),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None, None)),
        out_specs=(P(axis, None), P(axis, None), P(None)),
    )
    return jax.jit(mapped, donate_argnums=(0,))


def _routed_body(table, block, *, ways: int, cap: int, use_pallas: bool):
    """Single-shard body of the ROUTED arm: identical math to
    _sharded_body_after_compact minus the mesh — no shard_map, no psum,
    no [1, ...] leading axis. block: uint32[7, bucket_d], this shard's
    own rows only. The health vector comes back per-shard; the host sums
    shards (the compact arm's psum, moved off the interconnect).

    Keeping this a twin of the compact body (same _slab_update_sorted
    call with the same defaults, same jnp.minimum(cap) then narrow) is
    what makes SHARD_ROUTED_BATCHING=false a byte-identical rollback
    arm: tests pin slab bytes, wire rows, and verdicts across the two."""
    batch, now, _near, burst_ratio = _unpack(block)
    state, _before, s_after, _inputs, order, health, _ = _slab_update_sorted(
        SlabState(table=table), batch, now, ways, use_pallas=use_pallas,
        burst_ratio=burst_ratio,
    )
    after = jnp.minimum(_unsort(s_after, order), jnp.uint32(cap))
    if cap <= 0xFF:
        after = after.astype(jnp.uint8)
    elif cap <= 0xFFFF:
        after = after.astype(jnp.uint16)
    return state.table, after, health


def _pcts(samples) -> dict:
    """p50/p99 of a timing deque (ns); zeros when empty."""
    if not samples:
        return {"p50": 0, "p99": 0}
    arr = np.fromiter(samples, dtype=np.int64)
    return {
        "p50": int(np.percentile(arr, 50)),
        "p99": int(np.percentile(arr, 99)),
    }


class _HotKey:
    """Hot-set entry: the key's fp halves, its promotion epoch, and the
    round-robin cursor that deals its rows across the K salted slices."""

    __slots__ = ("lo", "hi", "epoch", "rr")

    def __init__(self, lo: int, hi: int, epoch: int):
        self.lo = int(lo)
        self.hi = int(hi)
        self.epoch = int(epoch)
        self.rr = 0


class ShardedSlabEngine:
    """Drop-in device engine for TpuRateLimitCache: same packed block protocol
    as ops/slab.py's slab_step_packed, but state spans every device of a mesh.

    n_slots_global must split into a power-of-two number of rows per device.

    Two dispatch arms share the compact launch/collect API (the tokens
    are opaque to callers):

      * routed=False — the original shard_map SPMD arm: one global
        bucket sized to the hottest shard, state one P(axis, None) array.
      * routed=True — per-shard batching: state is one committed table
        per device, each launch pads each shard only to its OWN row
        count and dispatches independent jitted programs (jax async
        dispatch overlaps them). Byte-identical results by construction
        (_routed_body); the win is padding waste, which the
        shard_routing_snapshot() telemetry and the sharded_zipf bench
        price.

    hot_tier=True (routed arm only, power-of-two shard counts) arms the
    replicated hot-key tier: promote_hot/demote_hot salt a key across
    hot_salt_ways slices with split quotas ceil(limit/K); the readback
    remaps slice counters so callers' `after > limit` compare still
    yields the decision. hotkey_lanes > 0 arms the host-side top-K
    fallback (ops/sketch.py HostTopK) that feeds the tier and the
    ratelimit.hotkeys.* gauges on the mesh path."""

    def __init__(
        self,
        mesh: Mesh | None = None,
        n_slots_global: int = 1 << 22,
        ways: int = 0,
        use_pallas: bool = False,
        routed: bool = False,
        hot_tier: bool = False,
        hot_salt_ways: int = 0,
        hotkey_lanes: int = 0,
        hotkey_k: int = 16,
        hot_min_count: int = 4096,
    ):
        if mesh is None:
            mesh = make_mesh()
        self.mesh = mesh
        n_dev = mesh.devices.size
        n_local, rem = divmod(n_slots_global, n_dev)
        if rem or n_local & (n_local - 1):
            raise ValueError(
                f"n_slots_global={n_slots_global} must be n_devices "
                f"({n_dev}) x a power of two"
            )
        self.n_slots_global = n_slots_global
        # per-shard associativity: every SET lives wholly on one shard
        # (owner routing picks the shard, the set-index split then picks a
        # set within the shard's own flat table), so per-shard snapshots
        # stay flat (n_local, ROW_WIDTH) arrays and the v1->v2 rehash
        # migration applies per shard file. ways=0 auto-selects by the
        # mesh's device platform (ops/slab.py default_ways).
        if not ways:
            ways = default_ways(next(iter(mesh.devices.flat)).platform)
        self.ways = validate_ways(n_local, ways)
        axis = mesh.axis_names[0]
        self._devices = list(mesh.devices.flat)
        self._routed = bool(routed)
        self._state_sharding = NamedSharding(mesh, P(axis, None))
        self._batch_sharding = NamedSharding(mesh, P(None, None))
        self._use_pallas = use_pallas
        # Sticky algorithms guard, mesh edition (the single-device twin is
        # backends/tpu.py _algos_seen): the Mosaic kernels implement
        # fixed_window only, so the first launch or restored table that
        # carries a non-fixed algorithm id (divider-word bits 28-30)
        # rebuilds every cached step function on the XLA twin permanently.
        # An all-fixed config never flips, keeping the pallas arm intact.
        self._algos_seen = False
        self._after_steps: dict[int, object] = {}
        self._compact_steps: dict[int, object] = {}
        self._routed_steps: dict[int, object] = {}
        self._blocks_sharding = NamedSharding(mesh, P(axis, None, None))
        if self._routed:
            # per-shard batching: one committed table per device instead
            # of a shard_map'd global array — routed launches are plain
            # per-device jitted programs
            self._state = None
            self._tables = [
                jax.device_put(
                    jnp.zeros((n_local, ROW_WIDTH), dtype=jnp.uint32), d
                )
                for d in self._devices
            ]
            self._step = None
            self._live_slots = None
            self._live_one = jax.jit(live_slot_count)
        else:
            self._state = jax.device_put(
                jnp.zeros((n_slots_global, ROW_WIDTH), dtype=jnp.uint32),
                self._state_sharding,
            )
            self._tables = None
            self._step = sharded_slab_step(
                mesh, ways=self.ways, use_pallas=use_pallas
            )
            axis_name = axis
            self._live_slots = jax.jit(
                jax.shard_map(
                    lambda table, now: jax.lax.psum(
                        live_slot_count(table, now), axis_name
                    ),
                    mesh=mesh,
                    in_specs=(P(axis_name, None), P()),
                    out_specs=P(),
                )
            )
        # cumulative mesh-wide health: the eviction mix + contention drops
        # (ops/slab.py HEALTH_* layout), parked per launch and drained with
        # the state lock released (ops/slab.py ParkedHealth)
        self._health = ParkedHealth()
        # Serializes state rebinds (donating steps) against the occupancy
        # read — without it the stats thread can hit a donated buffer.
        self._state_lock = threading.Lock()

        # -- routing telemetry (both arms; shard_routing_snapshot) --
        self._launches = 0
        self._rows_routed = 0  # valid rows dispatched
        self._padded_lanes = 0  # lanes launched incl. padding
        self._shard_rows = [0] * n_dev
        self._t_bucket_ns: collections.deque = collections.deque(maxlen=4096)
        self._t_pad_ns: collections.deque = collections.deque(maxlen=4096)
        self._t_launch_ns: collections.deque = collections.deque(maxlen=4096)

        # -- replicated hot-key tier (routed arm only) --
        hot_tier = bool(hot_tier)
        if hot_tier and not self._routed:
            _log.warning(
                "hot-key tier needs routed per-shard batching; disabled "
                "(SHARD_ROUTED_BATCHING is off)"
            )
            hot_tier = False
        if hot_tier and n_dev & (n_dev - 1):
            # the salt redirects the owner hash by XOR on its low bits,
            # which is only a clean bijection when n_dev is a power of two
            _log.warning(
                "hot-key tier needs a power-of-two shard count, got %d; "
                "disabled",
                n_dev,
            )
            hot_tier = False
        self._hot_tier = hot_tier
        salt_ways = int(hot_salt_ways) or n_dev
        self._salt_ways = max(1, min(salt_ways, n_dev))
        self._hot_lock = threading.Lock()
        self._hot: dict[int, _HotKey] = {}  # combined uint64 fp -> entry
        self._hot_combined = np.empty(0, dtype=np.uint64)
        self._hot_epoch = 0
        self._hot_promotions = 0
        self._hot_demotions = 0
        self._hot_settle_drops = 0
        self._hot_min_count = max(0, int(hot_min_count))

        # -- host-side top-K fallback (the mesh path's sketch) --
        self._hotkey_k = max(1, int(hotkey_k))
        self._hotkey_lanes = int(hotkey_lanes)
        self._hostkeys = None
        if self._hotkey_lanes > 0:
            from ..ops.sketch import HostTopK

            self._hostkeys = HostTopK(self._hotkey_lanes)
        self._hotkeys_lock = threading.Lock()
        self._hot_fps: frozenset = frozenset()
        self._hotkey_drains = 0
        self._hotkey_listeners: list = []
        self._last_topk: list = []

    @property
    def algos_seen(self) -> bool:
        return self._algos_seen

    def note_algos_seen(self) -> None:
        """Flip the sticky algorithms guard: from here on every launch
        runs the XLA kernels. Idempotent; called by the backend when its
        own guard flips, by import_tables on a restored table carrying
        algorithm rows, and by _guard_algos on direct engine use."""
        if self._algos_seen:
            return
        self._algos_seen = True
        if self._use_pallas:
            self._use_pallas = False
            # rebuild the cached jitted steps on the XLA twin; jit is
            # lazy, so the one-time cost is the recompile at next launch
            if not self._routed:
                self._step = sharded_slab_step(
                    self.mesh, ways=self.ways, use_pallas=False
                )
            self._after_steps.clear()
            self._compact_steps.clear()
            self._routed_steps.clear()

    def _guard_algos(self, packed: np.ndarray) -> None:
        """Per-launch check for direct engine callers (the backend has
        already run its own before dispatching): any VALID lane (hits > 0
        — padding/garbage lanes never count) carrying a non-fixed
        algorithm id flips the guard before a step function is chosen."""
        if self._algos_seen:
            return
        valid = packed[ROW_HITS] > 0
        if valid.any() and int(
            packed[ROW_DIVIDER][valid].max()
        ) >= (1 << ALGO_SHIFT):
            self.note_algos_seen()

    def _require_replicated(self, what: str) -> None:
        if self._routed:
            raise RuntimeError(
                f"{what} is a replicated-arm (shard_map) path; the routed "
                f"engine serves launches through launch_after_compact/"
                f"collect_after_compact only"
            )

    def step_packed(self, packed: np.ndarray) -> np.ndarray:
        """One mesh-wide launch. packed: uint32[7, b] -> uint32[8, b] results
        in arrival order (no permutation row: unsorted on device pre-psum)."""
        self._require_replicated("step_packed")
        self._guard_algos(packed)
        packed_dev = jax.device_put(packed, self._batch_sharding)
        with self._state_lock:
            self._state, out, health = self._step(self._state, packed_dev)
            self._health.park(health)
        self._drain_health_if_full()
        return np.asarray(out)

    def step_after(self, packed: np.ndarray, cap: int = 0xFFFFFFFF) -> np.ndarray:
        """Production readback path: stateful update only, one saturated
        post-increment counter row back (caller guarantees cap > limit+hits;
        see ops/slab.py compact modes)."""
        self._require_replicated("step_after")
        self._guard_algos(packed)
        step = self._after_steps.get(cap)
        if step is None:
            step = sharded_slab_step_after(
                self.mesh, cap, ways=self.ways, use_pallas=self._use_pallas
            )
            self._after_steps[cap] = step
        packed_dev = jax.device_put(packed, self._batch_sharding)
        with self._state_lock:
            self._state, after, health = step(self._state, packed_dev)
            self._health.park(health)
        self._drain_health_if_full()
        return np.asarray(after)

    def step_after_compact(self, packed: np.ndarray, cap: int = 0xFFFFFFFF) -> np.ndarray:
        """Production mesh path: host-side owner routing + per-shard
        compacted compute (see module comment above). packed: uint32[7, b]
        -> uint32[b] post-increment counters in arrival order."""
        return self.collect_after_compact(self.launch_after_compact(packed, cap))

    def launch_after_compact(
        self, packed: np.ndarray, cap: int = 0xFFFFFFFF, min_bucket: int = 128
    ):
        """Async half of step_after_compact: owner-route on the host,
        dispatch the sharded launch, return a token WITHOUT blocking on the
        result. The device work chains through the donated state, so the
        backend's double-buffered dispatcher can launch batch k+1 (host
        routing + H2D included) while batch k's readback drains — the same
        split the single-device engine runs (backends/tpu.py).

        min_bucket floors the power-of-two bucket ladder: callers that know
        the shapes they will see (the bench pins one bucket across a block
        stream) can force a single compile instead of one per ladder rung."""
        self._guard_algos(packed)
        n_dev = int(self.mesh.devices.size)
        b = packed.shape[1]
        t0 = time.perf_counter_ns()
        hits = packed[ROW_HITS]
        valid_idx = np.flatnonzero(hits > 0)
        if valid_idx.size == 0:
            if self._routed:
                return {"mode": "routed", "afters": None, "b": b}
            return (None, None, None, None, b, None)

        # feed the host top-K fallback BEFORE any hot-tier salting —
        # detection must see home fingerprints, not slice aliases
        if self._hostkeys is not None:
            with self._hotkeys_lock:
                self._hostkeys.update(
                    packed[ROW_FP_LO, valid_idx],
                    packed[ROW_FP_HI, valid_idx],
                    packed[ROW_HITS, valid_idx],
                )

        hot_remap = None
        hot_epoch = 0
        if self._hot_tier:
            packed, hot_remap, hot_epoch = self._salt_hot(packed, valid_idx)

        # MUST mirror _owner_mask's device-side formula ((fp_lo ^ fp_hi) mod
        # n_dev) exactly — a mismatch silently routes keys to shards that
        # don't own them and corrupts counters.
        owner = (
            (packed[ROW_FP_LO, valid_idx] ^ packed[ROW_FP_HI, valid_idx])
            % np.uint32(n_dev)
        ).astype(np.int64)
        counts = np.bincount(owner, minlength=n_dev)
        route = np.argsort(owner, kind="stable")
        routed_idx = valid_idx[route]  # original positions, shard-grouped
        routed_owner = owner[route]
        starts = np.zeros(n_dev + 1, dtype=np.int64)
        starts[1:] = np.cumsum(counts)
        t1 = time.perf_counter_ns()

        if self._routed:
            return self._launch_routed(
                packed, cap, min_bucket, b, counts, routed_idx, starts,
                hot_remap, hot_epoch, t0, t1,
            )

        # power-of-two bucket >= the fullest shard (>=128 for lane alignment)
        bucket = 128
        while bucket < max(int(min_bucket), counts.max()):
            bucket <<= 1
        within = np.arange(routed_idx.size, dtype=np.int64) - starts[routed_owner]

        blocks = np.zeros((n_dev, 7, bucket), dtype=np.uint32)
        blocks[routed_owner, :, within] = packed[:, routed_idx].T
        # per-item columns carried garbage into the scalar row; restamp it
        blocks[:, ROW_SCALARS, 0] = packed[ROW_SCALARS, 0]
        blocks[:, ROW_SCALARS, 1] = packed[ROW_SCALARS, 1]
        blocks[:, ROW_SCALARS, 2] = packed[ROW_SCALARS, 2]
        t2 = time.perf_counter_ns()

        # one jit wrapper per cap; jax.jit itself retraces per bucket shape
        step = self._compact_steps.get(cap)
        if step is None:
            step = sharded_slab_step_after_compact(
                self.mesh,
                cap,
                ways=self.ways,
                use_pallas=self._use_pallas,
            )
            self._compact_steps[cap] = step
        blocks_dev = jax.device_put(blocks, self._blocks_sharding)
        with self._state_lock:
            self._state, after_blocks, health = step(self._state, blocks_dev)
            self._health.park(health)
            self._note_routing_locked(
                counts, n_dev * bucket, t0, t1, t2, time.perf_counter_ns()
            )
        self._drain_health_if_full()
        return (after_blocks, routed_idx, routed_owner, within, b, hot_remap)

    def _launch_routed(
        self, packed, cap, min_bucket, b, counts, routed_idx, starts,
        hot_remap, hot_epoch, t0, t1,
    ):
        """Routed-arm launch: one block per NON-EMPTY shard, each padded
        only to its own power-of-two rung, dispatched as independent
        per-device jitted calls. jax's async dispatch returns before any
        program finishes, so the K launches overlap on device exactly
        like the compact arm's single SPMD launch — minus the dead lanes.

        min_bucket keeps its compile-pinning meaning per shard, but the
        FLOOR stays 128 even when callers pass more: the whole point of
        this arm is that a cold shard must not inherit a hot shard's
        rung."""
        n_dev = len(self._devices)
        blocks: dict[int, np.ndarray] = {}
        for d in range(n_dev):
            c = int(counts[d])
            if not c:
                continue
            bucket = 128
            while bucket < max(int(min_bucket), c):
                bucket <<= 1
            blk = np.zeros((7, bucket), dtype=np.uint32)
            sel = routed_idx[starts[d] : starts[d] + c]
            blk[:, :c] = packed[:, sel]
            blk[ROW_SCALARS, 0] = packed[ROW_SCALARS, 0]
            blk[ROW_SCALARS, 1] = packed[ROW_SCALARS, 1]
            blk[ROW_SCALARS, 2] = packed[ROW_SCALARS, 2]
            # hot-set epoch rides the launch scalars (free col 3): the
            # device ignores it, but any captured operand pins which
            # hot-set version routed this batch
            blk[ROW_SCALARS, 3] = np.uint32(hot_epoch)
            blocks[d] = blk
        t2 = time.perf_counter_ns()

        step = self._routed_steps.get(cap)
        if step is None:
            step = jax.jit(
                functools.partial(
                    _routed_body,
                    ways=self.ways,
                    cap=cap,
                    use_pallas=self._use_pallas,
                ),
                donate_argnums=(0,),
            )
            self._routed_steps[cap] = step
        afters: dict[int, object] = {}
        with self._state_lock:
            for d, blk in blocks.items():
                table, after, health = step(self._tables[d], blk)
                self._tables[d] = table
                afters[d] = after
                self._health.park(health)
            self._note_routing_locked(
                counts,
                sum(blk.shape[1] for blk in blocks.values()),
                t0, t1, t2, time.perf_counter_ns(),
            )
        self._drain_health_if_full()
        return {
            "mode": "routed",
            "afters": afters,
            "routed_idx": routed_idx,
            "starts": starts,
            "counts": counts,
            "b": b,
            "hot_remap": hot_remap,
        }

    def collect_after_compact(self, token) -> np.ndarray:
        """Blocking half: drain the sharded result and unscatter it back to
        arrival order using the routing permutation built at launch."""
        if isinstance(token, dict):  # routed-arm token
            return self._collect_routed(token)
        after_blocks, routed_idx, routed_owner, within, b, hot_remap = token
        out = np.zeros(b, dtype=np.uint32)
        if after_blocks is None:  # launch saw no valid lanes
            return out
        after_np = np.asarray(after_blocks)
        out[routed_idx] = after_np[routed_owner, within].astype(np.uint32)
        self._remap_hot(out, hot_remap)
        return out

    def _collect_routed(self, token) -> np.ndarray:
        out = np.zeros(token["b"], dtype=np.uint32)
        afters = token["afters"]
        if afters is None:  # launch saw no valid lanes
            return out
        routed_idx = token["routed_idx"]
        starts = token["starts"]
        counts = token["counts"]
        for d, after in afters.items():
            c = int(counts[d])
            after_np = np.asarray(after)[:c].astype(np.uint32)
            out[routed_idx[starts[d] : starts[d] + c]] = after_np
        self._remap_hot(out, token["hot_remap"])
        return out

    @staticmethod
    def _remap_hot(out: np.ndarray, hot_remap) -> None:
        """Rewrite hot rows' slice counters so the caller's unchanged
        `after > limit` compare yields the slice's own verdict: an
        under-quota slice reports its raw count (<= quota <= limit), an
        over-quota slice reports limit + overshoot (> limit). In-place
        on the arrival-order result row."""
        if hot_remap is None:
            return
        sel, limits, quotas = hot_remap
        vals = out[sel]
        out[sel] = np.where(vals <= quotas, vals, limits + (vals - quotas))

    # -- replicated hot-key tier --------------------------------------

    def _salt_hot(self, packed: np.ndarray, valid_idx: np.ndarray):
        """Rewrite hot-key rows to their salted slice fingerprints and
        split quotas. Returns (packed', hot_remap, epoch); packed is
        copied only when a hot row is actually present, so the cold path
        (and the HOT_TIER_ENABLED=false arm) never touches the operand.

        Slice selection is a per-key round-robin over the K salt ways —
        deterministic, and it deals a batch's duplicate rows across
        DIFFERENT slices, which is the in-batch load spreading the tier
        exists for. Only fixed-window rows salt: a sliding/GCRA row's
        auxiliary state has no split-quota combine rule, so those ride
        their home shard untouched."""
        with self._hot_lock:
            if not self._hot_combined.size:
                return packed, None, self._hot_epoch
            lo = packed[ROW_FP_LO, valid_idx].astype(np.uint64)
            hi = packed[ROW_FP_HI, valid_idx].astype(np.uint64)
            combined = lo | (hi << np.uint64(32))
            mask = np.isin(combined, self._hot_combined)
            # fixed-window rows only (algorithm id bits 28-30 == 0)
            mask &= packed[ROW_DIVIDER, valid_idx] < np.uint32(1 << ALGO_SHIFT)
            if not mask.any():
                return packed, None, self._hot_epoch
            packed = packed.copy()
            K = self._salt_ways
            n_dev = len(self._devices)
            sel = valid_idx[mask]
            limits = packed[ROW_LIMIT, sel].copy()
            quotas = np.empty_like(limits)
            for i, (pos, comb) in enumerate(
                zip(sel.tolist(), combined[mask].tolist())
            ):
                entry = self._hot[comb]
                slot = entry.rr % K
                entry.rr += 1
                lo2, hi2 = hot_slice_fp(
                    packed[ROW_FP_LO, pos], packed[ROW_FP_HI, pos],
                    slot, n_dev,
                )
                packed[ROW_FP_LO, pos] = lo2
                packed[ROW_FP_HI, pos] = hi2
                q = -(-int(packed[ROW_LIMIT, pos]) // K)  # ceil(limit/K)
                packed[ROW_LIMIT, pos] = np.uint32(q)
                quotas[i] = q
            return packed, (sel, limits, quotas), self._hot_epoch

    @property
    def hot_tier_enabled(self) -> bool:
        return self._hot_tier

    def promote_hot(self, fp_lo: int, fp_hi: int) -> bool:
        """Admit a key into the replicated hot tier. Promotion is pure
        membership — no device traffic: slot 0's salt is the identity
        (ops/hashing.py hot_slice_fp), so the home row IS slice 0 and
        the current window's count carries into the tier intact; it just
        starts being enforced against the slice quota ceil(limit/K)
        (conservative — promotion can only tighten, never over-admit).
        Epoch-bumped so in-flight launches are attributable."""
        if not self._hot_tier:
            return False
        comb = (int(fp_lo) & 0xFFFFFFFF) | ((int(fp_hi) & 0xFFFFFFFF) << 32)
        with self._hot_lock:
            if comb in self._hot:
                return False
            self._hot_epoch += 1
            self._hot[comb] = _HotKey(fp_lo, fp_hi, self._hot_epoch)
            self._hot_combined = np.fromiter(
                self._hot.keys(), dtype=np.uint64, count=len(self._hot)
            )
            self._hot_promotions += 1
        return True

    def demote_hot(self, fp_lo: int, fp_hi: int, now: int | None = None) -> dict:
        """Remove a key from the hot tier and SETTLE: fold every salted
        slice's counter back into the home row so the key's next window
        — and any non-routed reader of the exported tables — sees one
        exact counter. Returns the settlement report."""
        comb = (int(fp_lo) & 0xFFFFFFFF) | ((int(fp_hi) & 0xFFFFFFFF) << 32)
        with self._hot_lock:
            entry = self._hot.pop(comb, None)
            if entry is None:
                return {"demoted": False}
            self._hot_epoch += 1
            self._hot_combined = np.fromiter(
                self._hot.keys(), dtype=np.uint64, count=len(self._hot)
            )
            self._hot_demotions += 1
        return self._settle_slices(int(fp_lo), int(fp_hi), now)

    def _settle_slices(self, fp_lo: int, fp_hi: int, now: int | None) -> dict:
        """Demotion settlement: pull each slice row host-side, merge with
        the keep-the-newest rule (the reshard/promote merge,
        ops/slab.py slab_promote_rows: greatest window wins; counts
        WITHIN the winning window sum, because each slice counted a
        disjoint share of that window's hits), zero the slice rows, and
        land the merged row at the home placement. Runs under the state
        lock — a few sets of host traffic per demotion, demotion-cadence
        only."""
        if now is None:
            from ..utils.timeutil import process_time_source

            now = process_time_source().unix_now()
        n_dev = len(self._devices)
        K = self._salt_ways
        report = {"demoted": True, "settled": 0, "count": 0, "landed": False}
        with self._state_lock:
            tables: dict[int, np.ndarray] = {}
            found: list[tuple[int, int, int]] = []  # (slot, shard, row)
            for slot in range(K):
                lo2, hi2 = hot_slice_fp(fp_lo, fp_hi, slot, n_dev)
                shard = int((int(lo2) ^ int(hi2)) % n_dev)
                tab = tables.get(shard)
                if tab is None:
                    tab = tables[shard] = np.asarray(self._tables[shard]).copy()
                ridx = find_row_host(tab, int(lo2), int(hi2), self.ways)
                if ridx >= 0:
                    found.append((slot, shard, ridx))
            if not found:
                return report
            rows = [tables[s][r].copy() for (_slot, s, r) in found]
            win = max(int(r[COL_WINDOW]) for r in rows)
            total = sum(
                int(r[COL_COUNT]) for r in rows if int(r[COL_WINDOW]) == win
            )
            # slot 0 (when live) carries the key's real metadata; any
            # slice works as the template otherwise — divider/expire are
            # identical across slices of one window
            template = next(
                (
                    tables[s][r].copy()
                    for (slot, s, r) in found
                    if slot == 0
                ),
                rows[0],
            )
            merged = template
            merged[COL_FP_LO] = np.uint32(fp_lo)
            merged[COL_FP_HI] = np.uint32(fp_hi)
            merged[COL_COUNT] = np.uint32(min(total, 0xFFFFFFFF))
            merged[COL_WINDOW] = np.uint32(win)
            merged[COL_EXPIRE] = np.uint32(
                max(int(r[COL_EXPIRE]) for r in rows)
            )
            for (_slot, s, r) in found:
                tables[s][r] = 0
            home_shard = int((fp_lo ^ fp_hi) % n_dev)
            htab = tables.get(home_shard)
            if htab is None:
                htab = tables[home_shard] = np.asarray(
                    self._tables[home_shard]
                ).copy()
            place = self._find_landing(htab, fp_lo, int(now))
            if place >= 0:
                htab[place] = merged
                report["landed"] = True
            else:
                # home set is full of other live keys: the merged counter
                # is dropped (fail-open at the key's next touch) — same
                # accounting class as a slab contention drop, counted so
                # the fuzz bound can price it
                self._hot_settle_drops += 1
            for shard, tab in tables.items():
                self._tables[shard] = jax.device_put(
                    jnp.asarray(tab), self._devices[shard]
                )
            report["settled"] = len(found)
            report["count"] = total
        return report

    def _find_landing(self, table: np.ndarray, fp_lo: int, now: int) -> int:
        """First free way of the key's home set: never-used/reclaimed
        first (expire == 0), then expired rows. -1 when every way holds
        another live key (the settle-drop case)."""
        from ..ops.hashing import set_index

        n_sets = table.shape[0] // self.ways
        base = int(set_index(np.uint32(fp_lo), n_sets)) * self.ways
        rows = table[base : base + self.ways]
        expire = rows[:, COL_EXPIRE]
        free = np.flatnonzero(expire == 0)
        if free.size:
            return base + int(free[0])
        dead = np.flatnonzero(expire.astype(np.int64) <= int(now))
        if dead.size:
            return base + int(dead[0])
        return -1

    # -- host-side top-K fallback (the mesh path's hotkeys surface) ----
    # Mirrors SlabDeviceEngine's sketch surface (backends/tpu.py) so
    # HotkeyStats, the journeys listener, and the lease pre-seed work
    # unchanged against a mesh engine.

    @property
    def hotkeys_enabled(self) -> bool:
        return self._hostkeys is not None

    @property
    def hot_fps(self) -> frozenset:
        """Most recent drain's head keys as combined (hi<<32|lo) ints."""
        return self._hot_fps

    def add_hotkey_listener(self, fn) -> None:
        """fn(top, fps) after every drain — same contract as the
        single-device sketch listeners."""
        self._hotkey_listeners.append(fn)

    def drain_hotkeys(self) -> list:
        """Drain the host top-K: read the head, decay, and — when the
        hot tier is armed — feed it: promote drained keys at or above
        hot_min_count, demote hot keys that decayed below half of it
        (hysteresis so a key flapping around the threshold doesn't churn
        settlement traffic)."""
        if self._hostkeys is None:
            return []
        with self._hotkeys_lock:
            top = self._hostkeys.topk(self._hotkey_k)
            self._hostkeys.decay()
            self._last_topk = top
            self._hot_fps = frozenset(
                (hi << 32) | lo for lo, hi, _cnt in top
            )
            self._hotkey_drains += 1
        if self._hot_tier and self._hot_min_count > 0:
            keep = set()
            for lo, hi, cnt in top:
                comb = (hi << 32) | lo
                if cnt >= self._hot_min_count:
                    keep.add(comb)
                    self.promote_hot(lo, hi)
                elif cnt >= self._hot_min_count // 2:
                    keep.add(comb)  # hysteresis band: keep, don't promote
            with self._hot_lock:
                cold = [c for c in self._hot if c not in keep]
            for comb in cold:
                self.demote_hot(comb & 0xFFFFFFFF, comb >> 32)
        for fn in list(self._hotkey_listeners):
            try:
                fn(top, self._hot_fps)
            except Exception:  # pragma: no cover - listener bugs stay local
                _log.exception("hotkey listener failed")
        return top

    def hotkeys_snapshot(self) -> dict:
        """Same debug shape as the single-device sketch snapshot."""
        with self._hotkeys_lock:
            top = list(self._last_topk)
            drains = self._hotkey_drains
        return {
            "enabled": self._hostkeys is not None,
            "k": self._hotkey_k,
            "lanes": self._hotkey_lanes,
            "drains": drains,
            "top": [
                {"fp": f"{(hi << 32) | lo:016x}", "count": cnt}
                for lo, hi, cnt in top
            ],
        }

    # -- routing telemetry ---------------------------------------------

    def _note_routing_locked(self, counts, padded_lanes, t0, t1, t2, t3):
        """Accumulate the per-launch routing mix (state lock held): the
        bucket stage is host owner-hash + argsort, pad is the block
        fill + H2D staging, launch is the device dispatch call(s)."""
        self._launches += 1
        n_rows = int(counts.sum())
        self._rows_routed += n_rows
        self._padded_lanes += int(padded_lanes)
        for d, c in enumerate(counts):
            self._shard_rows[d] += int(c)
        self._t_bucket_ns.append(t1 - t0)
        self._t_pad_ns.append(t2 - t1)
        self._t_launch_ns.append(t3 - t2)

    def shard_routing_snapshot(self) -> dict:
        """Cumulative routing mix + stage-split percentiles — the source
        for the ratelimit.shard.* gauges (backends/dispatch.py
        ShardRoutingStats) and hotpath_profile --shard-split.
        padding_waste_pct is dead lanes as a share of all launched
        lanes: the compact arm's number is the pathology, the routed
        arm's is the cure, and both arms report through this one
        surface so a rollback's before/after lives in the same scrape."""
        with self._state_lock:
            padded = self._padded_lanes
            rows = self._rows_routed
            waste = 100.0 * (padded - rows) / padded if padded else 0.0
            with self._hot_lock:
                hot = {
                    "enabled": self._hot_tier,
                    "salt_ways": self._salt_ways,
                    "keys": len(self._hot),
                    "epoch": self._hot_epoch,
                    "promotions": self._hot_promotions,
                    "demotions": self._hot_demotions,
                    "settle_drops": self._hot_settle_drops,
                }
            return {
                "enabled": True,
                "routed": self._routed,
                "shards": len(self._shard_rows),
                "launches": self._launches,
                "rows": rows,
                "padded_lanes": padded,
                "padding_waste_pct": round(waste, 3),
                "shard_rows": list(self._shard_rows),
                "hot_tier": hot,
                "stage_ns": {
                    "bucket_ns": _pcts(self._t_bucket_ns),
                    "pad_ns": _pcts(self._t_pad_ns),
                    "launch_ns": _pcts(self._t_launch_ns),
                },
            }

    # -- warm restart (persist/): per-shard slab export/import --

    @property
    def shard_count(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def shard_slots(self) -> int:
        return self.n_slots_global // self.shard_count

    def export_tables(self) -> list[np.ndarray]:
        """One host table per device sub-table, in shard order. Only the
        device-side copy happens under the state lock (it sequences after
        in-flight donating steps); the cross-device gather + D2H drain run
        against the detached copy outside the lock."""
        with self._state_lock:
            if self._routed:
                copies = [jnp.array(t, copy=True) for t in self._tables]
                return [np.asarray(c) for c in copies]
            copy = jnp.array(self._state, copy=True)
        full = np.asarray(copy)
        n_local = self.shard_slots
        # P(axis, None) shards rows contiguously: shard i owns rows
        # [i*n_local, (i+1)*n_local) — the same split import_tables inverts
        return [
            full[i * n_local : (i + 1) * n_local]
            for i in range(self.shard_count)
        ]

    def import_tables(self, tables: list[np.ndarray]) -> None:
        """Boot-time restore: reassemble the global table from per-shard
        files and upload it with the slab's row sharding."""
        n_dev = self.shard_count
        if len(tables) != n_dev:
            raise ValueError(
                f"mesh slab restores from {n_dev} shards, got {len(tables)}"
            )
        full = np.concatenate(
            [np.asarray(t, dtype=np.uint32) for t in tables], axis=0
        )
        if full.shape != (self.n_slots_global, ROW_WIDTH):
            raise ValueError(
                f"snapshot shards assemble to {full.shape}, slab is "
                f"({self.n_slots_global}, {ROW_WIDTH})"
            )
        if not self._algos_seen and int(
            full[:, COL_DIVIDER].max(initial=0)
        ) >= (1 << ALGO_SHIFT):
            # restored rows carry non-fixed algorithms: the table is no
            # longer pallas-safe even before the first such launch (the
            # same rule the single-device import applies)
            self.note_algos_seen()
        with self._state_lock:
            if self._routed:
                n_local = self.shard_slots
                self._tables = [
                    jax.device_put(
                        jnp.asarray(full[i * n_local : (i + 1) * n_local]),
                        self._devices[i],
                    )
                    for i in range(self.shard_count)
                ]
            else:
                self._state = jax.device_put(full, self._state_sharding)

    @property
    def health_totals(self) -> list[int]:
        return self._health.totals

    def _drain_health_if_full(self) -> None:
        """The launch path's inline drain, after it released _state_lock:
        only once the stats flush has left more than INLINE parked."""
        if self._health.full:
            self._health.drain(self._state_lock)

    def health_snapshot(self, now: int | None = None) -> dict:
        """Cumulative mesh-wide lossy-event counters + live-slot occupancy
        (an O(n_slots) mesh reduction — stats-flush cadence only). `now` is
        the caller's clock authority (the backend's time_source); wall clock
        is only the fallback for direct/bench use."""
        if now is None:
            from ..utils.timeutil import process_time_source

            now = process_time_source().unix_now()
        _, totals = self._health.drain(self._state_lock)
        with self._state_lock:
            if self._routed:
                live = sum(
                    int(self._live_one(t, now)) for t in self._tables
                )
            else:
                live = int(self._live_slots(self._state, now))
        return {
            "evictions_expired": totals[HEALTH_EVICT_EXPIRED],
            "evictions_window": totals[HEALTH_EVICT_WINDOW],
            "evictions_live": totals[HEALTH_EVICT_LIVE],
            "drops": totals[HEALTH_DROPS],
            "algo_resets": totals[HEALTH_ALGO_RESETS],
            "live_slots": live,
            "occupancy": live / self.n_slots_global,
        }

    # Matches TpuRateLimitCache._launch_packed's contract (rows 0..7, already
    # in arrival order) so the backend can swap engines transparently.
    out_rows = PACKED_OUT_ROWS - 1
