"""The HBM key slab: TPU-native replacement for Redis's INCRBY/EXPIRE engine.

The reference delegates its hot mutation path to an external Redis process
(src/redis/fixed_cache_impl.go:26-29: INCRBY + EXPIRE per key, one RTT per
pipeline). Here the counter store lives in device HBM and a whole micro-batch
of decisions executes as ONE jitted device program:

    set scan -> window-reset -> duplicate-serialized increment -> decide

Slab layout — a W-way SET-ASSOCIATIVE row table, `uint32[n_slots, ROW_WIDTH]`
viewed as `[n_sets, W, ROW_WIDTH]` (n_sets = n_slots / W, W = `ways`,
default 128 — one full TPU lane register per set):

    col 0: fp_lo      64-bit key fingerprint, low half
    col 1: fp_hi      high half
    col 2: count      fixed/sliding-window counter; concurrency in-flight
                      count; GCRA TAT headroom in emission intervals (the
                      eviction valuation — a live GCRA row's "count" is how
                      much of its burst budget is spoken for, not a window
                      counter)
    col 3: window     window start (unix s); GCRA: tat_sec - divider (so
                      window + divider <= now <=> TAT drained — the
                      window-ended eviction/reconcile rules classify a
                      drained TAT with zero new code); concurrency: last
                      touch (unix s)
    col 4: expire_at  slot reclaim time (window TTL + jitter; 2 windows for
                      sliding so the prev count survives into interpolation;
                      idle TTL for concurrency — the leak reclamation)
    col 5: divider    window length (s) in bits 0-27; the ALGORITHM id in
                      bits 28-30 (ALGO_* below — 0 = fixed_window, so every
                      pre-algorithm row and wire frame reads back unchanged)
    col 6: prev/tat   sliding: previous window's count; GCRA: TAT unix s
    col 7: aux        GCRA: TAT millisecond remainder (0..999)

A key lives ONLY in set `fp_lo mod n_sets` (ops/hashing.py set_index — the
set-index split of the fingerprint; the full (lo, hi) pair stays the stored
tag). Lookup/insert/evict is one bounded W-wide vector scan over that set —
the "limited associativity" design of PAPERS "Limited Associativity Makes
Concurrent Software Caches a Breeze" / "... Caching in the Data Plane",
shaped for the VPU: with W=128 a set is exactly one lane register, so the
scan's reductions (match any, victim argmin) are single cross-lane ops.

One row per key keeps the hot path at ONE gather and ONE scatter per batch
(the set gather is contiguous: W rows x 32 bytes per set). ROW_WIDTH=8
keeps rows 32-byte aligned.

A slot is LIVE while expire_at > now. A full set degrades SMOOTHLY: the
least-valuable way is evicted in place, in-kernel —

    1. dead ways first (expired TTL — a free reuse, not a loss),
    2. then live ways whose FIXED WINDOW already ended (they carry no
       decision state: the next touch would roll the window to base 0),
    3. then the lowest-count live way (the only lossy tier — the evicted
       key fails open and restarts, exactly the reference's posture on a
       lost counter, README.md:567-568),

and never a same-batch winner: within a batch, sort order places eviction
writes BEFORE fingerprint-match writes on the same way, so a key that
matched a live row this batch always outlives a colliding evictor (the
evictor's write drops, counted). Within a tier, ways are ranked by a
per-key rotation (fp_hi bits [log2 W, 2*log2 W) — disjoint from the mesh
owner hash's low bits) so concurrent inserts into one set spread
across free ways instead of racing for way 0. There is no watermark sweep
and no admission shed: occupancy is a smooth gauge, and the eviction mix
(`slab.evictions.{expired,window,live}`) is the pressure signal.

Algorithm per batch (vectorized; no data-dependent Python control flow):
  1. Set scan: gather the W ways of each item's set; first live fingerprint
     match wins, else the argmin of the eviction valuation above.
  2. Duplicate keys within a batch must serialize (the reference serializes
     via per-command Redis execution): lexicographic stable sort by
     (slot, matched, fp) groups each key; segment-exclusive prefix sums of
     hits give item i's in-batch predecessor total.
  3. Window rollover: stored window != item's current window => base 0.
  4. One row-scatter per slot (the slot's final segment writes; when two
     distinct keys contend for one way in a batch the loser's count is not
     persisted — it re-scans next batch; one-batch undercount, fails open).
  5. Fused decision math (ops/decide.py or the Pallas kernel) yields
     code/remaining/throttle and the near/over stats deltas the host adds to
     per-rule counters.

The batch dimension is padded to fixed bucket sizes by the backend so XLA
compiles a handful of shapes once.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .decide import DecideResult, decide, floor_div_exact_i32

ROW_WIDTH = 8
COL_FP_LO, COL_FP_HI, COL_COUNT, COL_WINDOW, COL_EXPIRE, COL_DIVIDER = range(6)
COL_PREV, COL_AUX = 6, 7

# --- sibling decision algorithms -------------------------------------------
#
# The per-rule algorithm id travels in bits 28-30 of the DIVIDER word — on
# the wire (row-block col 4) and in the slab row (col 5) alike, so the
# uint32[6, n] frame format, the shm rings, the sidecar wire, and the
# snapshot format all carry algorithms with zero layout change, and an
# all-fixed_window config (id 0) is bit-for-bit the pre-algorithm engine.
# Real dividers are <= a week (604800 s << 2^28), so the split is free.
#
#   fixed_window   the original: count per window, reset at rollover.
#   sliding_window current count in col 2, PREVIOUS window's count in col
#                  6; the effective position is cur + floor(prev * (div -
#                  elapsed) / div) — two-window linear interpolation, which
#                  kills the 2x boundary burst of fixed windows.
#   gcra           token bucket via theoretical arrival time: TAT stored as
#                  (unix seconds, ms remainder) in cols 6-7, emission
#                  interval T = div_ms / limit, admit while TAT - now <=
#                  tau (= burst_ratio * div_ms - T). Denials never advance
#                  the TAT. All math in int32 ms relative to `now`.
#   concurrency   col 2 counts in-flight acquisitions; admit while count +
#                  hits <= limit; a RELEASE row (id 4 on the wire, stored
#                  as 3) decrements. The divider carries the idle TTL: a
#                  key whose holders all died stops being touched, its
#                  expire_at passes, and the row is reclaimed — the
#                  TTL-based leak bound.
#
# Within one sorted segment (one key, one batch) decisions serialize
# exactly like the fixed path: GCRA admits are a PREFIX of the segment
# (the conforming test does not depend on hits, so the first denial makes
# every later item non-conforming too), and concurrency admits follow the
# prefix rule count0 + prior_acquire_hits + hits <= limit with same-batch
# releases applied after acquires. The host oracle
# (testing/oracle.py SetSlabOracle) is the executable spec for all of it.
ALGO_SHIFT = 28
ALGO_DIV_MASK = (1 << ALGO_SHIFT) - 1
(
    ALGO_FIXED_WINDOW,
    ALGO_SLIDING_WINDOW,
    ALGO_GCRA,
    ALGO_CONCURRENCY,
    ALGO_CONC_RELEASE,
) = range(5)
ALGO_NAMES = {
    ALGO_FIXED_WINDOW: "fixed_window",
    ALGO_SLIDING_WINDOW: "sliding_window",
    ALGO_GCRA: "gcra",
    ALGO_CONCURRENCY: "concurrency",
}
# GCRA fixed-point bounds: TAT offsets live in int32 milliseconds, capped
# ~12 days ahead of now; dividers are clamped before the *1000 so the ms
# math can never overflow int32 even on a hostile wire frame.
GCRA_TAT_CAP_MS = 1 << 30
GCRA_DIV_CAP_S = 1_000_000

# Default set associativity: one full VPU lane register per set — the
# Mosaic way-scan shape. The engine's SLAB_WAYS knob overrides it (power
# of two; auto-clamped to n_slots for tiny test slabs).
DEFAULT_WAYS = 128
# Host (non-TPU) default: on a CPU the W-wide scan is real per-item memory
# traffic — W=128 reads 4KB per decision (32x the old 4-probe layout's
# bytes) and measured ~5x slower end to end on the bench box. Measured
# engine-tier ladder on the r09 box (Zipf-10M, batch 8192, 2^18 slots):
# W=2 ~970k, W=4 ~910-940k, W=8 ~790-830k, W=16 ~700-740k dec/s vs the
# old 4-probe layout's ~880-930k on the same box class. W=4 (two cache
# lines per set — the old layout's probe budget) keeps its throughput
# with the same smooth-eviction semantics; W=2 buys ~5% for half the
# associativity, a bad trade (PERF.md round 9).
DEFAULT_WAYS_HOST = 4


def default_ways(platform: str) -> int:
    """Platform-matched set associativity for SLAB_WAYS=0 (auto): one
    lane register per set on TPU, a cache-line-scale set on hosts. Same
    precedent as the engine's pallas auto-select — the semantic contract
    (value-ranked in-kernel eviction, smooth occupancy) is identical at
    any W, and the snapshot layer rehashes across geometry changes
    (persist/snapshot.py migrate_rows_to_sets), so the knob is purely a
    per-platform performance shape."""
    return DEFAULT_WAYS if platform == "tpu" else DEFAULT_WAYS_HOST

# The uint32[HEALTH_WIDTH] per-launch health vector: the eviction mix plus
# the within-batch contention drop count. Only EVICT_LIVE and DROPS are
# lossy (they displace state a caller could still observe); EXPIRED and
# WINDOW reclaim rows that carry no decision state. ALGO_RESETS counts
# fingerprint-matched rows whose stored algorithm differed from the
# request's (a mid-window algorithm change on config reload): the old
# state resets to zero, counted so a reload's blast radius is observable.
(
    HEALTH_EVICT_EXPIRED,
    HEALTH_EVICT_WINDOW,
    HEALTH_EVICT_LIVE,
    HEALTH_DROPS,
    HEALTH_ALGO_RESETS,
) = range(5)
HEALTH_WIDTH = 5


# Parked health vectors one on-device fold adds up: every drain, whatever
# its length, runs this one compiled program (per sharding) on chunks of
# it, the last chunk padded with masked repeats.
HEALTH_FOLD = 256


@jax.jit
def _fold_health(n, *vectors):
    """The uint32 sum of the first n of the uint32[HEALTH_WIDTH] vectors
    (each entry is bounded by its launch's rows, so HEALTH_FOLD of them
    cannot wrap)."""
    stacked = jnp.stack(vectors)
    keep = jnp.arange(len(vectors))[:, None] < n
    return jnp.where(keep, stacked, jnp.zeros_like(stacked)).sum(
        0, dtype=jnp.uint32
    )


def fold_health_vectors(vectors: list) -> np.ndarray:
    """uint64[HEALTH_WIDTH] sum of parked health vectors: the device
    vectors of each sharding folded on the device HEALTH_FOLD at a time,
    then one batched transfer of the folds (and of any host arrays) and
    one numpy sum. A transfer per vector costs ~75 us on a v5e even when
    all are started at once."""
    groups: dict = {}
    for v in vectors:
        groups.setdefault(getattr(v, "sharding", None), []).append(v)
    parts = groups.pop(None, [])
    for group in groups.values():
        for i in range(0, len(group), HEALTH_FOLD):
            chunk = group[i : i + HEALTH_FOLD]
            pad = chunk[:1] * (HEALTH_FOLD - len(chunk))
            parts.append(_fold_health(np.int32(len(chunk)), *chunk, *pad))
    return np.stack(jax.device_get(parts)).sum(0, dtype=np.uint64)


class ParkedHealth:
    """Per-launch health vectors parked unread, and their running totals
    (reading 16 bytes inline would add a device round trip to every
    launch). Both engines (backends/tpu.py, parallel/sharded_slab.py)
    park and drain through this one object.

    A launch parks its vector under the engine's state lock: one list
    append, no device op. drain() takes the list under that lock for the
    swap alone and fetches and folds with it released, so no device read
    ever holds up a launch. The object's own lock serializes drains and
    guards the totals; it is taken before the state lock, never after."""

    # a launch that finds more than this many parked drains them itself,
    # once it has released the state lock (a stats flush running late)
    INLINE = 4096

    def __init__(self):
        self.totals = [0] * HEALTH_WIDTH
        self.pending: list = []
        self._lock = threading.Lock()

    def park(self, health) -> None:
        """The caller holds the state lock."""
        self.pending.append(health)

    @property
    def full(self) -> bool:
        return len(self.pending) > self.INLINE

    def drain(self, state_lock) -> tuple[int, list[int]]:
        """Fold every parked vector into the totals (fold_health_vectors);
        returns how many were folded and the totals after them. The caller
        does not hold state_lock. Under load most of a drain is releasing
        the drained device arrays as `pending` goes (~0.3 ms each on a v5e
        beside a saturated gRPC edge), which no lock waits for."""
        with self._lock:
            with state_lock:
                pending, self.pending = self.pending, []
            if pending:
                for i, v in enumerate(fold_health_vectors(pending).tolist()):
                    self.totals[i] += v
            return len(pending), list(self.totals)


def validate_ways(n_slots: int, ways: int) -> int:
    """Validate (and clamp) a set-associativity request against a slab
    size: ways must be a power of two; a slab smaller than one set runs
    fully associative (ways = n_slots — the tiny-test-slab case)."""
    ways = int(ways)
    if ways <= 0 or ways & (ways - 1):
        raise ValueError(f"ways must be a positive power of two, got {ways}")
    return min(ways, n_slots)


class SlabState(NamedTuple):
    table: jnp.ndarray  # uint32[n_slots, ROW_WIDTH]

    @property
    def n_slots(self) -> int:
        return self.table.shape[0]

    # debug/test views
    @property
    def count(self) -> jnp.ndarray:
        return self.table[:, COL_COUNT]

    @property
    def expire_at(self) -> jnp.ndarray:
        return self.table[:, COL_EXPIRE].astype(jnp.int32)


class SlabBatch(NamedTuple):
    """One micro-batch of decisions. hits == 0 marks padding."""

    fp_lo: jnp.ndarray  # uint32[b]
    fp_hi: jnp.ndarray  # uint32[b]
    hits: jnp.ndarray  # uint32[b]
    limit: jnp.ndarray  # uint32[b] requests_per_unit
    divider: jnp.ndarray  # int32[b] seconds per window
    jitter: jnp.ndarray  # int32[b] expiry jitter seconds


class SlabResult(NamedTuple):
    before: jnp.ndarray  # uint32[b]
    after: jnp.ndarray  # uint32[b]
    decision: DecideResult
    health: jnp.ndarray  # uint32[2]: (probe steals, contention drops)


def make_slab(n_slots: int, device=None) -> SlabState:
    if n_slots & (n_slots - 1):
        raise ValueError(f"n_slots must be a power of two, got {n_slots}")
    table = jnp.zeros((n_slots, ROW_WIDTH), dtype=jnp.uint32)
    if device is not None:
        table = jax.device_put(table, device)
    return SlabState(table=table)


# Eviction valuation tiers (see the module docstring): the per-way score is
# (tier << SCORE_TIER_SHIFT) | sub, argmin picks the victim. Scores are
# UNIQUE within a set because the low bits carry the per-key way rotation —
# a bijection over ways — so argmin has no tie to resolve.
SCORE_TIER_SHIFT = 28
TIER_DEAD, TIER_WINDOW_ENDED, TIER_LIVE = 0, 1, 2

# eviction classes reported per item by _choose_ways (0 = no eviction)
EVICT_NONE, EVICT_EXPIRED, EVICT_WINDOW, EVICT_LIVE = range(4)


def _gather_sets(state: SlabState, batch: SlabBatch, ways: int):
    """(int32[b] set index, uint32[b, W, ROW_WIDTH] each item's full set) —
    the ONE gather of the hot path; a set is W contiguous rows, so this is
    a block gather, not W random probes."""
    n = state.n_slots
    if n % ways:
        raise ValueError(f"n_slots {n} is not a multiple of ways {ways}")
    n_sets = n // ways
    # ops/hashing.py set_index — THE set-index split of the fingerprint
    # (shared with the snapshot rehash migration and the set-occupancy
    # tools so placement can never diverge between restore and runtime)
    set_idx = (batch.fp_lo & jnp.uint32(n_sets - 1)).astype(jnp.int32)
    rows = state.table.reshape(n_sets, ways, ROW_WIDTH)[set_idx]
    return set_idx, rows


def _scan_ways(rows, fp_lo, fp_hi, now, ways: int, multi_algo: bool = True):
    """The W-wide scan arithmetic on PRE-GATHERED sets — the XLA twin of
    pallas_way_scan (ops/pallas_slab.py swaps in for exactly this
    function): (int32[b] way, bool[b] match_any). Standalone so the
    slab_split stage baseline (bench.py / tools/hotpath_profile.py via
    make_split_programs) times the SHIPPED scan, not a reimplementation."""
    expire = rows[:, :, COL_EXPIRE].astype(jnp.int32)
    window = rows[:, :, COL_WINDOW].astype(jnp.int32)
    # mask off the algorithm id (bits 28-30): the window-ended valuation
    # must see the real window length. A no-op for fixed_window rows, so
    # the all-fixed scan is bit-identical to the pre-algorithm one; for
    # GCRA rows the stored window is tat_sec - divider, so the SAME rule
    # classifies a drained TAT as reclaimable ahead of any live row.
    raw_div = rows[:, :, COL_DIVIDER].astype(jnp.int32)
    divider = raw_div & jnp.int32(ALGO_DIV_MASK)
    count = rows[:, :, COL_COUNT]
    live = expire > now
    match = (
        live
        & (rows[:, :, COL_FP_LO] == fp_lo[:, None])
        & (rows[:, :, COL_FP_HI] == fp_hi[:, None])
    )
    if multi_algo:
        # sliding rows carry the count the NEXT window's interpolation
        # reads for one window past their own end (the 2-window
        # expire_at, expire_store below) — don't tier that state
        # reclaimable until the grace window also passed, or boundary
        # keys lose their 2x-burst protection to any colliding insert.
        # Static-gated so the all-fixed compiled program stays
        # byte-identical to the pre-algorithm engine (the rollback arm).
        algo = (raw_div >> jnp.int32(ALGO_SHIFT)) & jnp.int32(7)
        span = jnp.where(
            algo == jnp.int32(ALGO_SLIDING_WINDOW), divider * 2, divider
        )
    else:
        span = divider
    window_ended = live & (divider > 0) & (window + span <= now)

    way_bits = max(1, (ways - 1).bit_length())
    way_iota = jnp.arange(ways, dtype=jnp.int32)
    # rotation source: fp_hi bits [way_bits, 2*way_bits) — NOT the low
    # bits. The mesh owner hash ((fp_lo ^ fp_hi) mod n_dev,
    # parallel/sharded_slab.py) consumes fp_hi's LOW bits, so within
    # one (shard, set) cell those bits are fully determined and a
    # low-bit rotation would collide n_dev times more often than
    # chance. Bits [way_bits, 2*way_bits) stay disjoint from the owner
    # hash (n_dev <= 2^way_bits), from the set index (fp_lo), and from
    # the _sort_key tiebreaker (fp_hi's top bits, always >= bit 16).
    pref = ((fp_hi >> jnp.uint32(way_bits)) & jnp.uint32(ways - 1)).astype(
        jnp.int32
    )
    rot = (way_iota[None, :] - pref[:, None]) & jnp.int32(ways - 1)
    count_cap = (1 << (SCORE_TIER_SHIFT - way_bits)) - 1
    cnt = jnp.minimum(count, jnp.uint32(count_cap)).astype(jnp.int32)
    tier = jnp.where(
        live,
        jnp.where(window_ended, TIER_WINDOW_ENDED, TIER_LIVE),
        TIER_DEAD,
    )
    # dead ways rank purely by rotation; live tiers by (count, rotation)
    sub = jnp.where(live, (cnt << way_bits) | rot, rot)
    score = (tier << SCORE_TIER_SHIFT) | sub

    match_any = match.any(axis=1)
    match_way = jnp.argmax(match, axis=1).astype(jnp.int32)
    victim_way = jnp.argmin(score, axis=1).astype(jnp.int32)
    return jnp.where(match_any, match_way, victim_way), match_any


def _choose_ways(
    state: SlabState,
    batch: SlabBatch,
    now,
    ways: int,
    use_pallas: bool = False,
    interpret: bool = False,
    multi_algo: bool = True,
):
    """The W-wide set scan; returns (int32[b] chosen slot = set * W + way —
    n_slots for padding, int32[b] eviction class (EVICT_*), bool[b]
    matched, uint32[b, ROW_WIDTH] the chosen way's stored row). Returning
    the row spares the caller a second gather: the scan already fetched
    every way of the set, so the chosen one is a cheap in-register select.

    Victim valuation (no match): dead ways first, then live window-ended
    ways, then the lowest-count live way — each tier tiebroken by the
    per-key rotation (way - fp_hi) mod W, so same-batch inserts into one
    set spread across free ways instead of all racing for the same one.
    Scores are unique within a set (the rotation is a bijection over
    ways), so the argmin is deterministic with no tie to resolve.

    use_pallas swaps the scan arithmetic — ~20 elementwise HLOs plus the
    three cross-lane reductions — for the Mosaic kernel (ops/pallas_slab.py
    pallas_way_scan, one VMEM pass with a set per sublane row); the set
    gather and the picked-row select stay XLA in both paths (native
    dynamic-gather beats any kernel emulation). Non-128 ways fall back to
    the XLA scan: the kernel's lane dimension IS the set."""
    n = state.n_slots
    set_idx, rows = _gather_sets(state, batch, ways)

    if use_pallas and ways == 128:
        from .pallas_slab import pallas_way_scan

        way, match_any = pallas_way_scan(
            rows[:, :, COL_FP_LO],
            rows[:, :, COL_FP_HI],
            rows[:, :, COL_COUNT],
            rows[:, :, COL_WINDOW],
            rows[:, :, COL_EXPIRE],
            rows[:, :, COL_DIVIDER],
            batch.fp_lo,
            batch.fp_hi,
            now,
            interpret=interpret,
        )
    else:
        way, match_any = _scan_ways(
            rows, batch.fp_lo, batch.fp_hi, now, ways, multi_algo=multi_algo
        )
    chosen = set_idx * jnp.int32(ways) + way
    picked_rows = jnp.take_along_axis(rows, way[:, None, None], axis=1)[:, 0]

    p_expire = picked_rows[:, COL_EXPIRE].astype(jnp.int32)
    p_window = picked_rows[:, COL_WINDOW].astype(jnp.int32)
    p_raw_div = picked_rows[:, COL_DIVIDER].astype(jnp.int32)
    p_div = p_raw_div & jnp.int32(ALGO_DIV_MASK)
    if multi_algo:
        # the same sliding grace window the scan's tiering applies — the
        # eviction-mix health counters must classify what the scan saw
        p_algo = (p_raw_div >> jnp.int32(ALGO_SHIFT)) & jnp.int32(7)
        p_span = jnp.where(
            p_algo == jnp.int32(ALGO_SLIDING_WINDOW), p_div * 2, p_div
        )
    else:
        p_span = p_div
    p_live = p_expire > now
    p_window_ended = p_live & (p_div > 0) & (p_window + p_span <= now)
    valid = batch.hits > 0
    # classification of what the insert displaced: a never-written way
    # (expire_at == 0) is a fresh slot, not an eviction
    evict_class = jnp.where(
        match_any | ~valid,
        EVICT_NONE,
        jnp.where(
            p_live,
            jnp.where(p_window_ended, EVICT_WINDOW, EVICT_LIVE),
            jnp.where(p_expire > 0, EVICT_EXPIRED, EVICT_NONE),
        ),
    )
    return (
        jnp.where(valid, chosen, jnp.int32(n)),
        evict_class,
        match_any & valid,
        picked_rows,
    )


def _scatter_rows(table, write_idx, new_rows):
    """The ONE row-scatter of the hot path (the Pallas arm at ways == 128
    writes with pallas_slab_writeback instead; _finish_update). unique_indices:
    one writer per slot by construction; dropped rows use the out-of-bounds
    index n (mode='drop'). Without the flag XLA serializes the scatter.
    Standalone so the slab_split stage baseline times the SHIPPED scatter."""
    return table.at[write_idx].set(new_rows, mode="drop", unique_indices=True)


def _sort_key(
    chosen: jnp.ndarray, matched: jnp.ndarray, fp_hi: jnp.ndarray, n: int
) -> jnp.ndarray:
    """The packed uint32 sort key: slot index in the high bits (the padding
    sentinel n sorts last), ONE matched bit below it (eviction inserts
    sort BEFORE fingerprint matches on the same way, so the final — i.e.
    winning — write of a contended way is always the match: an in-batch
    winner is never evicted), then top fingerprint bits as the contention
    tiebreaker (see the commentary at the call site in
    _slab_update_sorted). Shared with tools/profile_engine.py so the
    profiled sort is always the shipped sort."""
    slot_bits = n.bit_length()  # chosen ranges 0..n inclusive
    fp_bits = max(0, min(16, 32 - slot_bits - 1))
    key = (chosen.astype(jnp.uint32) << 1) | matched.astype(jnp.uint32)
    if not fp_bits:  # slab so large slot + match fill the key
        return key
    return (key << fp_bits) | (fp_hi >> jnp.uint32(32 - fp_bits))


def _slab_update_sorted(
    state: SlabState,
    batch: SlabBatch,
    now: jnp.ndarray,  # int32 scalar
    ways: int,
    count_health: bool = True,
    use_pallas: bool = False,
    near_ratio: jnp.ndarray | None = None,  # float32 scalar, fused decide only
    fuse_decide: bool = False,
    lean_decide: bool = False,  # fused decide emits ONLY the code tile
    interpret: bool = False,
    burst_ratio: jnp.ndarray | None = None,  # float32 scalar, GCRA tau knob
    multi_algo: bool = True,  # static: compile the sibling-algorithm arms
    sketch: jnp.ndarray | None = None,  # hotkeys planes (None = gate off)
    sketch_ways: int = 0,  # static: sketch set associativity
    victim: bool = False,  # static: readback of evicted live rows
):
    """The stateful core: set scan, serialize duplicates, window-reset,
    increment, one row-scatter. Returns sorted before/after counters, the
    sorted per-item inputs the decision needs, the sort permutation, and a
    uint32[HEALTH_WIDTH] health vector (evictions by class + within-batch
    contention drops) — counted on device so the slab's lossy behaviors
    are observable instead of silent (VERDICT round 1 weak #5).
    count_health=False (static) skips the counting for callers whose
    jitted program would otherwise RETURN the vector (e.g.
    slab_step_decided); when a caller's jit drops the vector, XLA
    dead-code-eliminates the reductions anyway, so the flag is about
    making the cost explicit, not a hidden win. Production after-mode
    keeps counting on.
    use_pallas=True swaps the arithmetic between the gathers — the W-way
    scan (pallas_way_scan), the segmented scans, window rollover,
    increment, and (with fuse_decide) the decision — for the Mosaic
    kernels (ops/pallas_slab.py); the set gather, sort and picked-row
    select stay XLA in both paths (they compile to the TPU's native
    dynamic gather), and with ways == 128 the row write is the set-tile
    kernel (_finish_update). Returns an
    extra trailing element: the fused DecideResult (sorted order) when
    fuse_decide, else None.
    Without fuse_decide there is no decision math — callers either decide on
    device (_slab_step_sorted) or ship `after` to the host and reuse the
    BaseRateLimiter oracle."""
    n = state.n_slots
    now = now.astype(jnp.int32)

    chosen, evict_class, matched, picked_rows = _choose_ways(
        state, batch, now, ways, use_pallas=use_pallas, interpret=interpret,
        multi_algo=multi_algo,
    )

    b = chosen.shape[0]
    # ONE packed uint32 sort key instead of a 4-key 5-operand variadic sort:
    # slot in the high bits (padding's sentinel slot n sorts last), the
    # matched bit under it (evictors sort before matchers, so a contended
    # way's winning write is always the in-batch match — _sort_key), and a
    # fingerprint tiebreaker below so distinct keys contending for one way
    # still group their own duplicates contiguously. The sort is the hot
    # path's most expensive op (every bitonic stage moves every operand),
    # so everything not needed for ordering is gathered by the permutation
    # afterwards. Stability keeps same-key items in arrival order —
    # required for per-item parity at limit crossings. The tiebreaker must
    # be independent of way selection: the set index is a function of
    # fp_lo and the way rotation of fp_hi's MIDDLE bits (always below bit
    # 14 — _choose_ways), so the TOP fp_bits
    # of fp_hi never influence where a key lands — they are uncorrelated
    # with any contention event. Two distinct keys sharing a way AND these
    # fp_bits top bits in one batch could interleave and split a segment;
    # that undercounts (fails open, same class as the counted contention
    # drop) with probability 2^-fp_bits per contending pair.
    key = _sort_key(chosen, matched, batch.fp_hi, n)
    (_, order) = jax.lax.sort(
        (key, jnp.arange(b, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    s_slot = chosen[order]
    s_fp_lo = batch.fp_lo[order]
    s_fp_hi = batch.fp_hi[order]
    s_hits = batch.hits[order]
    s_div = batch.divider[order]
    s_jit = batch.jitter[order]
    s_limit = batch.limit[order]

    same_prev = (
        (s_slot[1:] == s_slot[:-1])
        & (s_fp_lo[1:] == s_fp_lo[:-1])
        & (s_fp_hi[1:] == s_fp_hi[:-1])
    )
    seg_start = jnp.concatenate([jnp.array([True]), ~same_prev])

    # --- stored slot rows: permute the probe's picked rows into sort order
    # (a dense permute of the (b, ROW_WIDTH) intermediate instead of a
    # second random gather over the whole table; padding rows are garbage
    # but their results are discarded) ---
    st_rows = picked_rows[order]

    decision = None
    if use_pallas:
        from .decide import DecideResult
        from .pallas_slab import pallas_slab_apply

        st_t = st_rows[:, : COL_EXPIRE + 1].T  # (5, b): fp_lo/hi/count/win/exp
        outs = pallas_slab_apply(
            s_fp_lo,
            s_fp_hi,
            s_hits,
            s_limit,
            s_div,
            s_jit,
            seg_start,
            st_t,
            now,
            jnp.float32(0.8) if near_ratio is None else near_ratio,
            decide=fuse_decide,
            lean=lean_decide,
            interpret=interpret,
        )
        s_before = outs[0].astype(jnp.uint32)
        s_after = outs[1].astype(jnp.uint32)
        cur_window = outs[2]
        expire_at = outs[3]
        # the Mosaic kernels implement fixed_window only; the sticky
        # algorithms guards (backends/tpu.py _algos_seen for the
        # single-device engine, parallel/sharded_slab.py note_algos_seen
        # for the mesh engine) route any launch that could see a
        # non-fixed row or request to the XLA twin below, so this branch
        # always runs with algo id 0 everywhere — the stores below are
        # the pre-algorithm bytes verbatim
        s_div_eff = s_div
        count_store = s_after
        window_store = cur_window
        expire_store = expire_at
        div_store = s_div
        prev_store = jnp.zeros_like(s_fp_lo)
        aux_store = jnp.zeros_like(s_fp_lo)
        algo_reset = jnp.zeros(s_fp_lo.shape[0], dtype=bool)
        if fuse_decide:
            if lean_decide:
                # code is the only real tile; pad with zero placeholders so
                # one constructor serves both modes (the caller drops them,
                # XLA DCEs them)
                zeros_i = jnp.zeros_like(outs[4])
                outs = (*outs, zeros_i, zeros_i, zeros_i, zeros_i, zeros_i)
            decision = DecideResult(
                code=outs[4],
                limit_remaining=outs[5].astype(jnp.uint32),
                duration_until_reset=outs[6],
                throttle_millis=outs[7].astype(jnp.uint32),
                near_delta=outs[8].astype(jnp.uint32),
                over_delta=outs[9].astype(jnp.uint32),
            )
    else:
        u0 = jnp.uint32(0)
        valid = s_hits > 0
        incl = jnp.cumsum(s_hits, dtype=jnp.uint32)
        excl = incl - s_hits
        # forward-fill each segment's starting exclusive-sum (excl is
        # nondecreasing, so a running max of masked values is a forward fill)
        seg_base_excl = jax.lax.cummax(jnp.where(seg_start, excl, jnp.uint32(0)))
        prior_in_batch = excl - seg_base_excl

        st_count = st_rows[:, COL_COUNT]
        st_window = st_rows[:, COL_WINDOW].astype(jnp.int32)
        st_expire = st_rows[:, COL_EXPIRE].astype(jnp.int32)
        st_fp_lo = st_rows[:, COL_FP_LO]
        st_fp_hi = st_rows[:, COL_FP_HI]
        if not multi_algo:
            # fixed_window-only program — the EXACT pre-algorithm value
            # graph (no divider masking, no algorithm arms): the engine
            # compiles this while its sticky guard has seen no non-fixed
            # row, so an all-default config pays zero compute for the
            # subsystem and its compiled program is byte-identical to the
            # pre-PR engine (the rollback arm, statically enforced).
            safe_div = jnp.maximum(s_div, 1)
            cur_window = floor_div_exact_i32(now, safe_div) * safe_div
            slot_live = st_expire > now
            fp_match = (
                slot_live
                & (st_fp_lo == s_fp_lo)
                & (st_fp_hi == s_fp_hi)
            )
            same_window = st_window == cur_window
            base = jnp.where(
                valid & fp_match & same_window, st_count, jnp.uint32(0)
            )
            s_before = base + prior_in_batch
            s_after = s_before + s_hits
            s_div_eff = s_div
            count_store = s_after
            window_store = cur_window
            expire_store = now + safe_div + s_jit
            div_store = s_div
            prev_store = jnp.zeros_like(s_fp_lo)
            aux_store = jnp.zeros_like(s_fp_lo)
            algo_reset = jnp.zeros(s_fp_lo.shape[0], dtype=bool)
            return _finish_update(
                state, n, order, s_slot, same_prev, evict_class,
                s_fp_lo, s_fp_hi, s_hits, s_limit, s_div_eff,
                s_before, s_after, count_store, window_store,
                expire_store, div_store, prev_store, aux_store,
                algo_reset, count_health, decision,
                sketch=sketch, sketch_ways=sketch_ways,
                use_pallas=use_pallas, interpret=interpret, ways=ways,
                victim=victim, st_rows=st_rows,
            )

        st_algo = (st_rows[:, COL_DIVIDER].astype(jnp.int32) >> ALGO_SHIFT) & 7
        st_prev = st_rows[:, COL_PREV]
        st_aux = st_rows[:, COL_AUX]

        # split the wire divider word: real window length low, algorithm
        # id high. A release row (wire id 4) mutates a stored CONCURRENCY
        # (3) row, so matching and the row write both use store_algo.
        algo = (s_div >> ALGO_SHIFT) & 7
        div = s_div & jnp.int32(ALGO_DIV_MASK)
        store_algo = jnp.where(
            algo == ALGO_CONC_RELEASE, ALGO_CONCURRENCY, algo
        )
        s_div_eff = div
        safe_div = jnp.maximum(div, 1)  # padding rows may carry divider 0
        # floor_div_exact_i32: a vector integer divide would expand into a
        # ~32-pass shift-subtract loop (~100ms at 2^20 on v5e — the r3 gap)
        cur_window = floor_div_exact_i32(now, safe_div) * safe_div
        slot_live = st_expire > now
        fp_match = slot_live & (st_fp_lo == s_fp_lo) & (st_fp_hi == s_fp_hi)
        # an fp match under a DIFFERENT stored algorithm (config reload
        # changed the rule's algorithm mid-flight) resets state to zero —
        # old windows/TATs are meaningless under the new semantics;
        # counted per winning write as HEALTH_ALGO_RESETS
        algo_same = st_algo == store_algo
        match_ok = fp_match & algo_same
        algo_reset = fp_match & ~algo_same
        same_window = st_window == cur_window

        # -- fixed / sliding shared windowed counter core --
        # the hits>0 gate keeps the padding contract (before = after = 0):
        # a padding lane can carry a real fingerprint (e.g. a non-owned lane
        # in the replicated mesh mode) and its probe row WOULD match
        base = jnp.where(
            valid & match_ok & same_window, st_count, jnp.uint32(0)
        )
        s_before_raw = base + prior_in_batch
        s_after_raw = s_before_raw + s_hits
        expire_at = now + safe_div + s_jit

        is_slide = algo == ALGO_SLIDING_WINDOW
        is_gcra = algo == ALGO_GCRA
        is_acq = algo == ALGO_CONCURRENCY
        is_rel = algo == ALGO_CONC_RELEASE
        is_conc = is_acq | is_rel

        # -- sliding window: two-window linear interpolation --
        # prev = last window's count: carried in col 6 while the row is in
        # the current window, or the stored count itself when the row last
        # wrote exactly one window ago. The interpolated position adds
        # floor(prev * (div - elapsed) / div); prev is clamped so the
        # int32 product prev * (div - elapsed) cannot overflow (the clamp
        # only binds past limit ~ 2^31/div — documented interpolation
        # error, mirrored exactly by the host oracle).
        prev_raw = jnp.where(
            match_ok & same_window,
            st_prev,
            jnp.where(
                match_ok & (st_window == cur_window - safe_div),
                st_count,
                u0,
            ),
        )
        elapsed = now - cur_window
        prev_cap = floor_div_exact_i32(
            jnp.full_like(safe_div, 0x7FFFFFFF), safe_div
        )
        prev_c = jnp.minimum(prev_raw.astype(jnp.int32), prev_cap)
        carried = floor_div_exact_i32(
            prev_c * (safe_div - elapsed), safe_div
        ).astype(jnp.uint32)

        # -- GCRA: int32 millisecond math relative to `now` --
        limit_c = jnp.maximum(s_limit.astype(jnp.int32), 1)
        div_ms = jnp.minimum(safe_div, GCRA_DIV_CAP_S) * 1000
        t_ms = jnp.maximum(floor_div_exact_i32(div_ms, limit_c), 1)
        ratio = (
            jnp.float32(1.0) if burst_ratio is None else burst_ratio
        )
        tau = jnp.maximum(
            jnp.floor(div_ms.astype(jnp.float32) * ratio).astype(jnp.int32)
            - t_ms,
            0,
        )
        tat_dsec = jnp.clip(
            st_prev.astype(jnp.int32) - now, -(1 << 20), 1 << 20
        )
        tat0 = jnp.maximum(tat_dsec * 1000 + st_aux.astype(jnp.int32), 0)
        tat0 = jnp.where(match_ok & is_gcra, tat0, 0)
        # admit <=> tat0 + prior*T <= tau <=> prior <= floor((tau-tat0)/T):
        # the conforming test ignores hits, so segment admits are a prefix
        # and the existing exclusive prefix sum IS the serialization
        q_admissible = floor_div_exact_i32(
            jnp.maximum(tau - tat0, 0), t_ms
        )
        admit_g = (
            valid & is_gcra & (tat0 <= tau)
            & (prior_in_batch <= q_admissible.astype(jnp.uint32))
        )
        # total admitted hits so far in the segment: running max of the
        # admitted inclusive prefix, floored at the segment base (incl is
        # globally nondecreasing, so earlier segments can never leak in)
        adm_run = jax.lax.cummax(
            jnp.maximum(
                jnp.where(admit_g, incl, u0),
                jnp.where(seg_start, excl, u0),
            )
        )
        adm_total_g = adm_run - seg_base_excl
        a_cap = floor_div_exact_i32(
            jnp.full_like(t_ms, GCRA_TAT_CAP_MS), t_ms
        )
        a_eff = jnp.minimum(adm_total_g.astype(jnp.int32), a_cap)
        tat_new = jnp.minimum(
            tat0 + a_eff * t_ms, jnp.int32(GCRA_TAT_CAP_MS)
        )
        tat_sec_new = now + floor_div_exact_i32(tat_new, jnp.full_like(tat_new, 1000))
        tat_frac = tat_new - (tat_sec_new - now) * 1000
        # synthesized counter position: ceil(tat0/T) "slots spoken for"
        # plus this segment's prefix — <= limit iff admitted (capped), so
        # the UNCHANGED host oracle / device decide derives the right code
        used0 = floor_div_exact_i32(tat0 + t_ms - 1, t_ms).astype(jnp.uint32)
        vafter = used0 + prior_in_batch + s_hits
        after_gcra = jnp.where(
            admit_g, jnp.minimum(vafter, s_limit), s_limit + s_hits
        )

        # -- concurrency: in-flight count, acquire/release --
        count0 = jnp.where(match_ok & is_conc, st_count, u0)
        hits_acq = jnp.where(is_acq & valid, s_hits, u0)
        hits_rel = jnp.where(is_rel & valid, s_hits, u0)
        incl_a = jnp.cumsum(hits_acq, dtype=jnp.uint32)
        excl_a = incl_a - hits_acq
        segbase_a = jax.lax.cummax(jnp.where(seg_start, excl_a, u0))
        prior_a = excl_a - segbase_a
        admit_c = (
            valid & is_acq & (count0 + prior_a + s_hits <= s_limit)
        )
        adm_run_c = jax.lax.cummax(
            jnp.maximum(
                jnp.where(admit_c, incl_a, u0),
                jnp.where(seg_start, excl_a, u0),
            )
        )
        adm_total_c = adm_run_c - segbase_a
        incl_r = jnp.cumsum(hits_rel, dtype=jnp.uint32)
        segbase_r = jax.lax.cummax(
            jnp.where(seg_start, incl_r - hits_rel, u0)
        )
        rel_total = incl_r - segbase_r
        # same-batch releases apply after acquires; the count floors at 0
        count_acq = count0 + adm_total_c
        count_conc = jnp.where(
            count_acq >= rel_total, count_acq - rel_total, u0
        )
        after_conc = jnp.where(
            is_rel,
            u0,
            jnp.where(admit_c, count0 + prior_a + s_hits, s_limit + s_hits),
        )

        # -- per-item result select (fixed_window is the default arm, so
        # an all-fixed batch computes exactly the pre-algorithm values) --
        s_after = jnp.where(
            is_slide,
            s_after_raw + carried,
            jnp.where(
                is_gcra,
                after_gcra,
                jnp.where(is_conc, after_conc, s_after_raw),
            ),
        )
        s_before = jnp.where(
            is_slide,
            s_before_raw + carried,
            jnp.where(
                is_gcra | is_conc,
                jnp.where(s_after >= s_hits, s_after - s_hits, u0),
                s_before_raw,
            ),
        )

        # -- row-write stores --
        count_store = jnp.where(
            is_gcra,
            jnp.minimum(
                floor_div_exact_i32(tat_new, t_ms), jnp.int32(ALGO_DIV_MASK)
            ).astype(jnp.uint32),
            jnp.where(is_conc, count_conc, s_after_raw),
        )
        window_store = jnp.where(
            is_gcra,
            tat_sec_new - safe_div,
            jnp.where(
                is_conc,
                # broadcast, not full_like: under shard_map `now` is
                # device-varying and full_like's fill is not
                jnp.broadcast_to(now, cur_window.shape).astype(cur_window.dtype),
                cur_window,
            ),
        )
        expire_store = jnp.where(
            is_slide,
            # sliding rows must outlive their window by one more so the
            # prev count survives into next-window interpolation
            expire_at + safe_div,
            jnp.where(
                is_gcra,
                # a GCRA TAT can extend past the window (burst debt):
                # keep the row alive until the TAT fully drains plus one
                # window, or expiry would forgive the debt mid-drain
                expire_at
                + floor_div_exact_i32(
                    tat_new + 999, jnp.full_like(tat_new, 1000)
                ),
                expire_at,
            ),
        )
        div_store = div | (store_algo << ALGO_SHIFT)
        prev_store = jnp.where(
            is_slide,
            prev_raw,
            jnp.where(is_gcra, tat_sec_new.astype(jnp.uint32), u0),
        )
        aux_store = jnp.where(is_gcra, tat_frac.astype(jnp.uint32), u0)

    return _finish_update(
        state, n, order, s_slot, same_prev, evict_class,
        s_fp_lo, s_fp_hi, s_hits, s_limit, s_div_eff,
        s_before, s_after, count_store, window_store, expire_store,
        div_store, prev_store, aux_store, algo_reset,
        count_health, decision,
        sketch=sketch, sketch_ways=sketch_ways,
        use_pallas=use_pallas, interpret=interpret, ways=ways,
        victim=victim, st_rows=st_rows,
    )


def _finish_update(
    state, n, order, s_slot, same_prev, evict_class,
    s_fp_lo, s_fp_hi, s_hits, s_limit, s_div_eff,
    s_before, s_after, count_store, window_store, expire_store,
    div_store, prev_store, aux_store, algo_reset,
    count_health, decision,
    sketch=None, sketch_ways=0, use_pallas=False, interpret=False, ways=0,
    victim=False, st_rows=None,
):
    """The shared tail of _slab_update_sorted — one row write per slot,
    the health reductions, and the return tuple — factored out so the
    three update bodies (pallas fixed, XLA fixed-only, XLA multi-
    algorithm) land in one place with their per-branch stores.

    sketch (static gate via pytree structure: None = off, and the traced
    program is byte-identical to the pre-sketch engine — the same
    rollback discipline as multi_algo) threads the heavy-hitter planes
    (ops/sketch.py) through the launch: one candidate per distinct-key
    segment, weighted by the segment's total hits, updates the sketch in
    the same program. When on, the return tuple grows ONE trailing
    element (the new sketch) — conditional arity keeps every existing
    destructuring call site untouched.

    victim (static gate, same discipline): True appends the EVICTED LIVE
    ROWS as one more trailing element — uint32[b, ROW_WIDTH] in sorted
    order, each lane either the full stored row a winning insert
    displaced from a live in-window way (the ONLY lossy eviction class)
    or all-zero. st_rows must be the sorted picked rows when on. This is
    the demote readback of the host-RAM victim tier
    (backends/victim.py): the engine drains the nonzero lanes into the
    host table instead of letting the counters vanish. False compiles
    the byte-identical no-readback program — the VICTIM_TIER_ENABLED
    rollback arm.

    use_pallas with ways == 128 writes the rows back with the set-tile
    kernel (ops/pallas_slab.py pallas_slab_writeback), whose work follows
    the rows written rather than the launch width; every other shape
    keeps the XLA row scatter."""
    # --- one row write per SLOT: the final item in the slot's run ---
    is_last = jnp.concatenate([s_slot[1:] != s_slot[:-1], jnp.array([True])])
    s_valid = s_hits > 0
    write_idx = jnp.where(is_last & s_valid, s_slot, jnp.int32(n))

    if count_health:
        # health: the eviction mix — what each WINNING insert displaced
        # (counted once per winning write; a losing evictor displaced
        # nothing) — plus drops = distinct-key segments whose write lost a
        # within-batch way contention (the doc'd fail-open undercount),
        # plus algorithm-change resets (counted per winning write).
        # Only evict_live and drops are lossy; expired/window reclaims
        # carry no decision state.
        seg_end = jnp.concatenate([~same_prev, jnp.array([True])])
        s_class = evict_class[order]
        win = s_valid & is_last
        counts = [
            jnp.sum(
                (win & (s_class == cls)).astype(jnp.uint32), dtype=jnp.uint32
            )
            for cls in (EVICT_EXPIRED, EVICT_WINDOW, EVICT_LIVE)
        ]
        drops = jnp.sum(
            (s_valid & seg_end & ~is_last).astype(jnp.uint32), dtype=jnp.uint32
        )
        resets = jnp.sum(
            (win & algo_reset).astype(jnp.uint32), dtype=jnp.uint32
        )
        health = jnp.stack([*counts, drops, resets])
    else:
        health = jnp.zeros((HEALTH_WIDTH,), dtype=jnp.uint32)

    new_rows = jnp.stack(
        [
            s_fp_lo,
            s_fp_hi,
            count_store,
            window_store.astype(jnp.uint32),
            expire_store.astype(jnp.uint32),
            # window length low + algorithm id high: lets the eviction
            # scan (and the restore-time reconcile, persist/snapshot.py)
            # classify rows whose window/TAT ended even though their
            # jittered TTL (expire_at) hasn't — those evict ahead of any
            # live-window row — and lets the inspector/restore classify
            # every row's algorithm
            div_store.astype(jnp.uint32),
            prev_store,
            aux_store,
        ],
        axis=1,
    )
    if use_pallas and ways == 128:
        from .pallas_slab import pallas_slab_writeback

        # padding lanes sort last (slot n): the kernel stops before them
        table = pallas_slab_writeback(
            state.table, write_idx, new_rows,
            jnp.sum(s_valid, dtype=jnp.int32), interpret=interpret,
        )
    else:
        table = _scatter_rows(state.table, write_idx, new_rows)
    base = (
        SlabState(table=table),
        s_before,
        s_after,
        (s_hits, s_limit, s_div_eff),
        order,
        health,
        decision,
    )
    if victim:
        # demote readback: the stored row each WINNING insert displaced
        # from a live in-window way, zero everywhere else. Sorted order —
        # the host only filters nonzero lanes, so no unsort is needed.
        # Recomputed from evict_class (not the count_health block, which
        # may be compiled out) so the readback never depends on the
        # health flag.
        demote = s_valid & is_last & (evict_class[order] == EVICT_LIVE)
        victim_rows = jnp.where(demote[:, None], st_rows, jnp.uint32(0))
    if sketch is None:
        return base if not victim else (*base, victim_rows)

    from .sketch import sketch_update

    # one candidate per distinct-key segment (padding segments carry
    # hits 0 at their end row and drop out), weighted by the segment's
    # TOTAL hits — the same cumsum/cummax forward-fill the serialization
    # uses, recomputed here so all three update bodies (including the
    # pallas arm, whose scans live inside its kernel) share one shape
    seg_start = jnp.concatenate([jnp.array([True]), ~same_prev])
    seg_last = jnp.concatenate([~same_prev, jnp.array([True])])
    incl = jnp.cumsum(s_hits, dtype=jnp.uint32)
    excl = incl - s_hits
    seg_base_excl = jax.lax.cummax(jnp.where(seg_start, excl, jnp.uint32(0)))
    weight = incl - seg_base_excl
    cand = seg_last & (s_hits > 0)
    new_sketch = sketch_update(
        sketch, s_fp_lo, s_fp_hi, weight, cand, sketch_ways,
        use_pallas=use_pallas, interpret=interpret,
    )
    out = (*base, new_sketch)
    return out if not victim else (*out, victim_rows)


def _slab_step_sorted(
    state: SlabState,
    batch: SlabBatch,
    now: jnp.ndarray,  # int32 scalar
    near_ratio: jnp.ndarray,  # float32 scalar
    ways: int,
    use_pallas: bool,
    count_health: bool = True,
    lean_decide: bool = False,
    interpret: bool = False,
    burst_ratio: jnp.ndarray | None = None,
    multi_algo: bool = True,
    sketch: jnp.ndarray | None = None,
    sketch_ways: int = 0,
):
    """Core step with on-device decision; returns results in slot-sorted
    order plus the permutation (callers unsort on device or on the host)
    and the uint32[HEALTH_WIDTH] health vector. use_pallas=True runs the
    Mosaic way-scan + fused INCRBY+decide kernels (ops/pallas_slab.py)
    for everything between the gathers; False runs the XLA twin with the
    jnp decide math. A non-None sketch appends the updated hotkey planes
    as one extra trailing element (conditional arity — _finish_update)."""
    now = now.astype(jnp.int32)
    outs = _slab_update_sorted(
        state,
        batch,
        now,
        ways,
        count_health,
        use_pallas=use_pallas,
        near_ratio=near_ratio,
        fuse_decide=use_pallas,
        lean_decide=lean_decide,
        interpret=interpret,
        burst_ratio=burst_ratio,
        multi_algo=multi_algo,
        sketch=sketch,
        sketch_ways=sketch_ways,
    )
    new_sketch = None
    if sketch is not None:
        *outs, new_sketch = outs
    state, s_before, s_after, (s_hits, s_limit, s_div), order, health, fused = outs

    if fused is not None:
        decision = fused
    else:
        decision = decide(
            before=s_before,
            after=s_after,
            hits=s_hits,
            limit=s_limit,
            divider=s_div,
            now=now,
            near_ratio=near_ratio,
        )
    base = (state, s_before, s_after, decision, order, health)
    return base if sketch is None else (*base, new_sketch)


def _slab_step(
    state: SlabState,
    batch: SlabBatch,
    now: jnp.ndarray,
    near_ratio: jnp.ndarray,
    ways: int = DEFAULT_WAYS,
    use_pallas: bool = False,
) -> tuple[SlabState, SlabResult]:
    state, s_before, s_after, s_dec, order, health = _slab_step_sorted(
        state, batch, now, near_ratio, ways, use_pallas
    )
    decision = DecideResult(*(_unsort(field, order) for field in s_dec))
    return state, SlabResult(
        before=_unsort(s_before, order),
        after=_unsort(s_after, order),
        decision=decision,
        health=health,
    )


slab_update_and_decide = functools.partial(
    jax.jit, static_argnames=("ways", "use_pallas"), donate_argnames=("state",)
)(_slab_step)


# --- packed single-transfer step -------------------------------------------
#
# The host <-> device boundary matters as much as the kernel: a naive step
# ships 6 input arrays and reads back 8 outputs, i.e. ~14 transfer round
# trips per launch. The packed step moves exactly ONE uint32[7, b] array in
# and ONE uint32[9, b] array out per launch (scalars ride in input row 6).
# Results come back in device sort order with the permutation as the last
# output row — the host unsorts with one numpy fancy-index, which is cheaper
# than an extra device-side scatter + gathers. This is the TPU-native
# equivalent of the reference writing all pipeline commands in one Redis
# flush (src/redis/driver_impl.go:153-164: one write + one read RTT per
# batch).

ROW_FP_LO, ROW_FP_HI, ROW_HITS, ROW_LIMIT, ROW_DIVIDER, ROW_JITTER, ROW_SCALARS = range(7)
PACKED_IN_ROWS = 7
# out rows: code, remaining, duration, throttle, near, over, before, after, order
OUT_CODE, OUT_REMAINING, OUT_DURATION, OUT_THROTTLE, OUT_NEAR, OUT_OVER, OUT_BEFORE, OUT_AFTER, OUT_ORDER = range(9)
PACKED_OUT_ROWS = 9


@functools.partial(
    jax.jit,
    static_argnames=("ways", "use_pallas", "multi_algo", "sketch_ways"),
    donate_argnames=("state", "sketch"),
)
def slab_step_packed(
    state: SlabState,
    packed: jnp.ndarray,  # uint32[7, b]; row 6: [now, bitcast(near_ratio), ...]
    ways: int = DEFAULT_WAYS,
    use_pallas: bool = False,
    multi_algo: bool = True,
    sketch: jnp.ndarray | None = None,
    sketch_ways: int = 0,
) -> tuple[SlabState, jnp.ndarray, jnp.ndarray]:
    # sketch=None is the HOTKEYS_ENABLED=false arm: no sketch leaves enter
    # the pytree, so the traced program is byte-identical to the
    # pre-hotkeys engine (same static-gate discipline as multi_algo); a
    # real sketch array appends the updated planes as a 4th return element
    batch, now, near_ratio, burst_ratio = _unpack(packed)
    outs = _slab_step_sorted(
        state, batch, now, near_ratio, ways, use_pallas,
        burst_ratio=burst_ratio, multi_algo=multi_algo,
        sketch=sketch, sketch_ways=sketch_ways,
    )
    new_sketch = None
    if sketch is not None:
        *outs, new_sketch = outs
    state, s_before, s_after, d, order, health = outs
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
            order.astype(jnp.uint32),
        ]
    )
    base = (state, out, health)
    return base if sketch is None else (*base, new_sketch)


# --- compact transfer modes -------------------------------------------------
#
# The packed step above ships 9 uint32 rows back per item. On transfer-
# constrained links (the PCIe DMA) the readback dominates the whole hot
# path, so two compact modes cut
# it to ONE row, or one BYTE, per item:
#
#   * after-mode (production): the device returns only the post-increment
#     counter, unsorted on device. code/remaining/duration/throttle and the
#     near/over stats split are all pure functions of (after, hits, limit,
#     unit, now) — the host derives them by calling the SAME
#     BaseRateLimiter.get_response_descriptor_status oracle the memory
#     backend uses (limiter/base_limiter.py:92-142), which makes TPU-vs-
#     oracle parity true by construction. Saturating u8/u16 casts are exact
#     as long as cap > limit + hits: a saturated value can only mean
#     "already far over limit", where the oracle's all-over branch
#     (before >= threshold) yields the same stats no matter the magnitude.
#
#   * decided-mode (bench / fire-and-forget): the decision runs on device
#     (Pallas kernel) and only the 1-byte code comes back.


def _unsort(values: jnp.ndarray, order: jnp.ndarray) -> jnp.ndarray:
    """Undo the slot sort on device: out[order[i]] = values[i] — one direct
    scatter (order is a permutation, so every slot is written exactly
    once); works for (b,) and (b, k) values alike."""
    return jnp.zeros_like(values).at[order].set(values, unique_indices=True)


def _unpack(packed: jnp.ndarray) -> tuple[SlabBatch, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    batch = SlabBatch(
        fp_lo=packed[ROW_FP_LO],
        fp_hi=packed[ROW_FP_HI],
        hits=packed[ROW_HITS],
        limit=packed[ROW_LIMIT],
        divider=packed[ROW_DIVIDER].astype(jnp.int32),
        jitter=packed[ROW_JITTER].astype(jnp.int32),
    )
    now = packed[ROW_SCALARS, 0].astype(jnp.int32)
    near_ratio = jax.lax.bitcast_convert_type(packed[ROW_SCALARS, 1], jnp.float32)
    # scalar slot 2: the GCRA burst-ratio knob (f32 bitcast). 0 means the
    # producer predates the slot (old packers zero-fill) — default 1.0, a
    # full-window burst; a zero ratio is meaningless so the sentinel is safe
    burst_raw = jax.lax.bitcast_convert_type(
        packed[ROW_SCALARS, 2], jnp.float32
    )
    burst_ratio = jnp.where(
        packed[ROW_SCALARS, 2] == 0, jnp.float32(1.0), burst_raw
    )
    return batch, now, near_ratio, burst_ratio


@functools.partial(
    jax.jit,
    static_argnames=(
        "ways", "out_dtype", "use_pallas", "multi_algo", "sketch_ways",
        "victim",
    ),
    donate_argnames=("state", "sketch"),
)
def slab_step_after(
    state: SlabState,
    packed: jnp.ndarray,  # uint32[7, b]
    ways: int = DEFAULT_WAYS,
    out_dtype=jnp.uint32,
    use_pallas: bool = False,
    multi_algo: bool = True,
    sketch: jnp.ndarray | None = None,
    sketch_ways: int = 0,
    victim: bool = False,
) -> tuple[SlabState, jnp.ndarray, jnp.ndarray]:
    """Stateful update only; returns (post-increment counters in arrival
    order, saturating-cast to out_dtype, uint32[HEALTH_WIDTH] health). The
    caller guarantees max(limit) + max(hits) < dtype max. use_pallas runs
    the Mosaic way-scan + fused INCRBY kernel (no decide outputs). A
    non-None sketch (the HOTKEYS_ENABLED arm) appends the updated hotkey
    planes as an extra return element; None compiles the byte-identical
    pre-hotkeys program (slab_step_packed's gate commentary). victim=True
    (the VICTIM_TIER_ENABLED arm) appends the evicted-live-rows readback
    — uint32[b, ROW_WIDTH], nonzero lanes are the full stored rows this
    launch displaced from live in-window ways (_finish_update) — as the
    LAST element; False compiles the byte-identical no-readback
    program."""
    batch, now, _, burst_ratio = _unpack(packed)
    outs = _slab_update_sorted(
        state, batch, now, ways, use_pallas=use_pallas,
        burst_ratio=burst_ratio, multi_algo=multi_algo,
        sketch=sketch, sketch_ways=sketch_ways, victim=victim,
    )
    victim_rows = None
    if victim:
        *outs, victim_rows = outs
    new_sketch = None
    if sketch is not None:
        *outs, new_sketch = outs
    state, _before, s_after, _inputs, order, health, _ = outs
    after = _unsort(s_after, order)
    cap = jnp.uint32(jnp.iinfo(out_dtype).max)
    base = (state, jnp.minimum(after, cap).astype(out_dtype), health)
    if sketch is not None:
        base = (*base, new_sketch)
    return base if not victim else (*base, victim_rows)


@functools.partial(
    jax.jit,
    static_argnames=("ways", "use_pallas", "count_health", "multi_algo", "sketch_ways"),
    donate_argnames=("state", "sketch"),
)
def slab_step_decided(
    state: SlabState,
    packed: jnp.ndarray,  # uint32[7, b]
    ways: int = DEFAULT_WAYS,
    use_pallas: bool = False,
    count_health: bool = True,
    multi_algo: bool = True,
    sketch: jnp.ndarray | None = None,
    sketch_ways: int = 0,
) -> tuple[SlabState, jnp.ndarray, jnp.ndarray]:
    """Full on-device decision; only the 1-byte code per item (1=OK,
    2=OVER_LIMIT, arrival order) plus the uint32[HEALTH_WIDTH] health come
    back. count_health=False skips the health reductions for
    fire-and-forget callers that drop the vector (the bench). The pallas
    kernel runs lean: only the code tile is computed and written (the XLA
    twin's unused decision fields are dead-code-eliminated by the
    compiler anyway). A non-None sketch appends the updated hotkey planes
    as a 4th return element (slab_step_packed's gate commentary)."""
    batch, now, near_ratio, burst_ratio = _unpack(packed)
    outs = _slab_step_sorted(
        state, batch, now, near_ratio, ways, use_pallas, count_health,
        lean_decide=use_pallas, burst_ratio=burst_ratio,
        multi_algo=multi_algo, sketch=sketch, sketch_ways=sketch_ways,
    )
    new_sketch = None
    if sketch is not None:
        *outs, new_sketch = outs
    state, _before, _after, d, order, health = outs
    base = (state, _unsort(d.code, order).astype(jnp.uint8), health)
    return base if sketch is None else (*base, new_sketch)


# --- warm-restart export/import (persist/) ----------------------------------
#
# The snapshot path must never stall the launch pipeline: export dispatches a
# DEVICE-SIDE copy (sequenced after every in-flight step on the device
# stream) and hands the detached buffer back — the caller blocks on the D2H
# drain outside any lock, while subsequent steps keep donating the live
# state. Import is the boot-time inverse: one H2D upload of a reconciled
# host table (persist/snapshot.py reconcile_rows applies the expiry rules on
# the host, where the restore-time clock lives).


def slab_export_copy(state: SlabState) -> jnp.ndarray:
    """Detached device-side copy of the row table (async dispatch; read it
    back with np.asarray outside the state lock)."""
    return jnp.array(state.table, copy=True)


def find_row_host(table, fp_lo: int, fp_hi: int, ways: int) -> int:
    """Host-side mirror of the device way-scan's fingerprint match: the
    row index of (fp_lo, fp_hi) in a HOST copy of a slab table, or -1.

    Used by the hot-tier demotion settlement
    (parallel/sharded_slab.py), which must locate a salted slice row in
    a pulled shard table at EXACTLY the placement the device used — so
    the set split is the one ops/hashing.py set_index definition, same
    as _gather_sets. Only live rows match: a reclaimed row is all-zero
    and carries no expiry, and a dead row's counter must not settle."""
    import numpy as np

    from .hashing import set_index

    table = np.asarray(table)
    n_slots = table.shape[0]
    ways = min(int(ways), n_slots)
    n_sets = n_slots // ways
    base = int(set_index(np.uint32(fp_lo), n_sets)) * ways
    rows = table[base : base + ways]
    hit = np.flatnonzero(
        (rows[:, COL_FP_LO] == np.uint32(fp_lo))
        & (rows[:, COL_FP_HI] == np.uint32(fp_hi))
        & (rows[:, COL_EXPIRE] != 0)
    )
    return base + int(hit[0]) if hit.size else -1


def slab_import_rows(rows, device=None) -> SlabState:
    """Upload a reconciled (n_slots, ROW_WIDTH) uint32 host table as fresh
    slab state; validates the shape so a wrong-topology snapshot can never
    masquerade as a slab."""
    import numpy as np

    rows = np.asarray(rows, dtype=np.uint32)
    if rows.ndim != 2 or rows.shape[1] != ROW_WIDTH:
        raise ValueError(
            f"slab rows must be (n_slots, {ROW_WIDTH}), got {rows.shape}"
        )
    n_slots = rows.shape[0]
    if n_slots & (n_slots - 1):
        raise ValueError(f"n_slots must be a power of two, got {n_slots}")
    table = jnp.asarray(rows)
    if device is not None:
        table = jax.device_put(table, device)
    return SlabState(table=table)


@functools.partial(
    jax.jit, static_argnames=("ways",), donate_argnames=("state",)
)
def slab_promote_rows(
    state: SlabState,
    rows: jnp.ndarray,  # uint32[k, ROW_WIDTH] victim-tier rows (0 = padding)
    now: jnp.ndarray,  # int32 scalar
    ways: int = DEFAULT_WAYS,
) -> tuple[SlabState, jnp.ndarray]:
    """Re-insert demoted rows from the host-RAM victim tier
    (backends/victim.py) into the slab ahead of a launch that is about to
    touch their keys — the promote half of the HBM<->host hierarchy. The
    row lands with its counter, window, divider, algorithm bits, and
    sliding/GCRA auxiliary words INTACT, so a demoted key resumes
    mid-window instead of resetting.

    Placement rides the SAME set scan as the hot path (_choose_ways), so
    a promoted row lands exactly where a request for its key will look.
    Promotion is a SWAP, not a polite insert: the engine only promotes
    keys present in the imminent batch, whose miss would evict the set's
    least-valuable way anyway — so the promote takes that same way
    up-front, and when the way held a LIVE in-window row the displaced
    row comes back in the `displaced` readback for the host to drain
    into the victim tier. Nothing is lost in either direction; the cost
    of a hot set is swap traffic, which the keyspace_overload bench
    prices. Per-lane outcomes:

      * fp match: the slab re-created the row while it sat demoted —
        keep-the-newest (persist/snapshot.py merge_rows_into_table rule:
        greater window wins, equal windows keep the greater count);
        either way the lane reports landed (the victim copy is consumed
        or provably stale);
      * no match: the row overwrites the scan's victim way; a displaced
        live in-window row is reported for re-demotion.

    Two lanes picking one slot serialize like the hot path: sort by
    (slot, matched), the run's last write wins; losers report landed
    False, stay in the tier, and retry on a later launch. Padding lanes
    (all-zero rows, or rows whose own expire_at already passed) drop
    with landed False — the tier's reclamation, not this kernel,
    retires them.

    Returns (state, bool[k] landed in arrival order, uint32[k,
    ROW_WIDTH] displaced rows — sorted order, nonzero lanes only, the
    same filter-don't-unsort contract as the demote readback)."""
    n = state.n_slots
    now = jnp.asarray(now).astype(jnp.int32)
    k = rows.shape[0]
    valid = rows[:, COL_EXPIRE].astype(jnp.int32) > now
    batch = SlabBatch(
        fp_lo=rows[:, COL_FP_LO],
        fp_hi=rows[:, COL_FP_HI],
        hits=valid.astype(jnp.uint32),
        limit=rows[:, COL_COUNT],
        divider=(rows[:, COL_DIVIDER] & jnp.uint32(ALGO_DIV_MASK)).astype(
            jnp.int32
        ),
        jitter=jnp.zeros((k,), dtype=jnp.int32),
    )
    chosen, evict_class, matched, picked_rows = _choose_ways(
        state, batch, now, ways
    )
    # keep-the-newest vs a matched live row (windows are unix-seconds
    # magnitudes, so the uint32 compare is exact)
    newer = (rows[:, COL_WINDOW] > picked_rows[:, COL_WINDOW]) | (
        (rows[:, COL_WINDOW] == picked_rows[:, COL_WINDOW])
        & (rows[:, COL_COUNT] > picked_rows[:, COL_COUNT])
    )
    stale = matched & valid & ~newer
    want_write = valid & ~stale
    # serialize same-slot collisions exactly like the hot path's sort
    # key: matched lanes order after evictor lanes, so the winning write
    # of a contended way is always the fp match
    key = (chosen.astype(jnp.uint32) << 1) | matched.astype(jnp.uint32)
    (_, order) = jax.lax.sort(
        (key, jnp.arange(k, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    s_chosen = chosen[order]
    is_last = jnp.concatenate(
        [s_chosen[1:] != s_chosen[:-1], jnp.array([True])]
    )
    s_wrote = want_write[order] & is_last
    write_idx = jnp.where(s_wrote, s_chosen, jnp.int32(n))
    table = _scatter_rows(state.table, write_idx, rows[order])
    # the swap's far side: a winning write over a live in-window way
    # (EVICT_LIVE implies no fp match) hands that row back for
    # re-demotion — the promote path's own never-lose-a-counter rule
    s_displaced = s_wrote & (evict_class[order] == EVICT_LIVE)
    displaced = jnp.where(
        s_displaced[:, None], picked_rows[order], jnp.uint32(0)
    )
    # landed = the tier may retire the row: written, or matched a row
    # that is already fresher than the victim copy
    s_landed = s_wrote | stale[order]
    landed = _unsort(s_landed, order)
    return SlabState(table=table), landed, displaced


def make_split_programs(ways: int):
    """Three standalone jitted programs for the `slab_split` stage
    baseline (SlabDeviceEngine.profile_slab_split -> bench.py
    slab_split block / tools/hotpath_profile.py --slab-split): the
    contiguous set GATHER, the W-wide SCAN arithmetic on pre-gathered
    rows, and the one-row-per-way SCATTER. Each calls the exact helper
    the fused step compiles (_gather_sets via the same reshape-gather,
    _scan_ways, _scatter_rows), so the published stage costs are the
    shipped kernel's stages — isolated only so they can be timed (the
    fused hot path never runs them separately). Returns
    (gather, scan, scatter) jitted callables:

        gather(table, fp_lo)                  -> uint32[b, W, ROW_WIDTH]
        scan(rows, fp_lo, fp_hi, now)         -> (way[b], match_any[b])
        scatter(table, write_idx, new_rows)   -> new table
    """

    @jax.jit
    def gather(table, fp_lo):
        state = SlabState(table=table)
        batch = SlabBatch(
            fp_lo=fp_lo,
            fp_hi=fp_lo,
            hits=fp_lo,
            limit=fp_lo,
            divider=fp_lo.astype(jnp.int32),
            jitter=fp_lo.astype(jnp.int32),
        )
        _set_idx, rows = _gather_sets(state, batch, ways)
        return rows

    @jax.jit
    def scan(rows, fp_lo, fp_hi, now):
        return _scan_ways(rows, fp_lo, fp_hi, now.astype(jnp.int32), ways)

    # donate the table: the fused step updates the slab in place via the
    # donated-state chain — without donation this would time a whole-table
    # copy, not the scatter (callers rebind: table = scatter(table, ...))
    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(table, write_idx, new_rows):
        return _scatter_rows(table, write_idx, new_rows)

    return gather, scan, scatter


def live_slot_count(table: jnp.ndarray, now) -> jnp.ndarray:
    """uint32 count of live (unexpired) rows — THE liveness definition,
    shared by the single-chip gauge below and the mesh-sharded reduction
    (parallel/sharded_slab.py) so the two occupancy gauges can't diverge."""
    return jnp.sum(
        (table[:, COL_EXPIRE].astype(jnp.int32) > jnp.int32(now)).astype(jnp.uint32),
        dtype=jnp.uint32,
    )


@jax.jit
def slab_live_slots(state: SlabState, now) -> jnp.ndarray:
    """Occupancy gauge: an O(n_slots) reduction, so it runs on the
    stats-flush cadence, never in the per-batch hot path. Under the
    set-associative layout this gauge is SMOOTH all the way to 100%:
    there is no watermark sweep and no admission shed — a full set evicts
    its least-valuable way in-kernel (see the module docstring), so the
    only pressure signals are this gauge and the slab.evictions.* mix.

    Window-ended-but-TTL-pinned rows still count as live here (they hold
    a way until evicted or expired), which is exactly the population the
    eviction scan reclaims ahead of any live-window row — the old
    stop-the-world slab_sweep_expired pass is gone because the scan does
    its job incrementally, per colliding insert."""
    return live_slot_count(state.table, now)
