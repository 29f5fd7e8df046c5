"""Pallas TPU kernel: the fused fixed-window INCRBY engine.

This is the "batched Pallas fixed-window INCRBY kernel" of the north star
(SURVEY.md:18): the stateful heart of the slab update — duplicate
serialization, window rollover, increment, and the full decision math —
executed as ONE kernel pass over VMEM-resident tiles.

Division of labor with XLA (ops/slab.py drives both):

  XLA owns the data movement: the K-way probe gather, the 3-key sort that
  groups duplicate keys, the stored-row gather, and the final row scatter.
  Those compile to the TPU's native dynamic-gather/scatter paths, which a
  hand-written kernel cannot beat — Pallas has no per-element HBM access;
  it would have to emulate gathers with thousands of tiny DMAs.

  This kernel owns everything BETWEEN the gathers: the two segmented
  prefix scans (exclusive cumsum of hits; running max of segment bases)
  that serialize duplicate keys, the window compare/reset, the increment,
  and the fused decision (code / remaining / duration / throttle /
  near & over stats deltas). In the XLA path these are ~30 HLO ops
  including two multi-pass scan lowerings; here they are one read of 12
  input tiles and one write of up to 10 output tiles per grid step.

How the scans cross grid steps: the TPU grid is SEQUENTIAL (one TensorCore
steps through it in order), so an SMEM scratch cell carries the running
totals from block to block — carry_sum for the hits cumsum, carry_max for
the segment-base forward fill. Within a tile the scans are Hillis-Steele:
log2(128) masked lane rolls, then log2(block_rows) masked sublane rolls on
the per-row totals (flat row-major order == lane order within a row, rows
in sequence).

Arithmetic is int32 (Mosaic's native lane type); u32 adds wrap identically
in two's complement, and comparisons only diverge past 2^31, which the
backend's saturating caps keep out of range — the same contract
ops/pallas_decide.py documents. Semantics are pinned bit-for-bit against
the XLA path by tests/test_pallas_slab.py over randomized batches with
duplicates, rollovers, collisions, and padding.

Reference semantics mirrored (via ops/slab.py): the per-key serialized
INCRBY of src/redis/fixed_cache_impl.go:26-29 and the decision math of
src/limiter/base_limiter.go:83-177.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decide import CODE_OK, CODE_OVER_LIMIT, floor_div_exact_i32

LANES = 128
# 256 x 128 = 32768 items per grid step: ~2.9MB of VMEM tiles per step (12
# in + up to 10 out), a 32-step grid at the bench's 2^20 batch — large
# enough to amortize per-step overhead, small enough for the pipeline to
# double-buffer tile DMAs comfortably inside ~16MB of VMEM headroom.
BLOCK_ROWS = 256


def out_vma(*xs) -> frozenset:
    """The mesh axes a kernel's outputs vary over: the union of its
    inputs'. Under shard_map (the compact mesh arm) pallas_call cannot
    infer it and must be told; outside one it is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _masked_roll(x, k: int, axis: int, identity):
    """rolled[i] = x[i-k] along axis, with the first k positions set to
    identity — the shift step of a Hillis-Steele inclusive scan."""
    rolled = pltpu.roll(x, k, axis=axis)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(idx >= k, rolled, identity)


def _flat_scan(x, op, identity, block_rows: int):
    """Inclusive scan of a (block_rows, 128) int32 tile in FLAT row-major
    order (lane l of row r is flat index r*128 + l). Returns the scanned
    tile; [-1, -1] holds the tile total."""
    # across lanes within each row
    k = 1
    while k < LANES:
        x = op(x, _masked_roll(x, k, axis=1, identity=identity))
        k <<= 1
    # per-row totals, scanned across rows, shifted to exclusive row bases
    totals = x[:, LANES - 1 :]  # (block_rows, 1) inclusive row totals
    k = 1
    while k < block_rows:
        totals = op(totals, _masked_roll(totals, k, axis=0, identity=identity))
        k <<= 1
    row_base = _masked_roll(totals, 1, axis=0, identity=identity)
    return op(x, row_base)


def _slab_apply_kernel(
    # scalar prefetch (SMEM)
    now_ref,
    near_ratio_ref,
    # inputs (VMEM tiles, slot-sorted flat order)
    # input VMEM tiles: fp_lo, fp_hi, hits, [limit — decide mode only],
    # div, jit, seg_start, st_fp_lo, st_fp_hi, st_count, st_window,
    # st_expire; then output VMEM tiles, then the SMEM carry scratch
    # ([0,0]=carry_sum, [0,1]=carry_max — persists across the sequential grid)
    *refs,
    decide: bool,
    lean: bool,
    block_rows: int,
):
    fp_lo_ref, fp_hi_ref, hits_ref = refs[0], refs[1], refs[2]
    if decide:
        limit_ref = refs[3]
        rest = refs[4:]
    else:
        limit_ref = None  # after-mode never reads limits; tile not shipped
        rest = refs[3:]
    (
        div_ref,
        jit_ref,
        seg_start_ref,
        st_fp_lo_ref,
        st_fp_hi_ref,
        st_count_ref,
        st_window_ref,
        st_expire_ref,
    ) = rest[:8]
    out_refs, carry_ref = rest[8:-1], rest[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0, 0] = jnp.int32(0)
        carry_ref[0, 1] = jnp.int32(0)

    now = now_ref[0]
    near_ratio = near_ratio_ref[0]

    hits = hits_ref[...]
    seg_start = seg_start_ref[...]

    # --- duplicate serialization: segmented exclusive prefix of hits ---
    incl = _flat_scan(hits, jnp.add, jnp.int32(0), block_rows) + carry_ref[0, 0]
    excl = incl - hits
    # forward-fill each segment's starting exclusive-sum: excl is
    # nondecreasing, so a running max of seg-start-masked values fills
    masked = jnp.where(seg_start > 0, excl, jnp.int32(0))
    seg_base = jnp.maximum(
        _flat_scan(masked, jnp.maximum, jnp.int32(0), block_rows),
        carry_ref[0, 1],
    )
    prior_in_batch = excl - seg_base

    carry_ref[0, 0] = incl[block_rows - 1, LANES - 1]
    carry_ref[0, 1] = seg_base[block_rows - 1, LANES - 1]

    # --- window compare / reset against the stored row ---
    safe_div = jnp.maximum(div_ref[...], 1)
    # floor_div_exact_i32: Mosaic expands a vector integer divide the same
    # ~32-pass way XLA does (~100ms/site at 2^20 — the r3 perf gap)
    cur_window = floor_div_exact_i32(now, safe_div) * safe_div
    slot_live = st_expire_ref[...] > now
    fp_match = (
        slot_live
        & (st_fp_lo_ref[...] == fp_lo_ref[...])
        & (st_fp_hi_ref[...] == fp_hi_ref[...])
    )
    # hits>0 gate: padding lanes may carry a real fingerprint whose probe
    # row matches — the contract is before = after = 0 for them (same gate
    # as the XLA twin in ops/slab.py)
    base = jnp.where(
        (hits > jnp.int32(0)) & fp_match & (st_window_ref[...] == cur_window),
        st_count_ref[...],
        jnp.int32(0),
    )

    # --- the increment ---
    before = base + prior_in_batch
    after = before + hits

    out_refs[0][...] = before
    out_refs[1][...] = after
    out_refs[2][...] = cur_window
    out_refs[3][...] = now + safe_div + jit_ref[...]  # slot reclaim time

    if not decide:
        return

    # --- fused decision math (the pallas_decide formulas, same i32 rules) ---
    limit = limit_ref[...]
    is_over = after > limit
    valid = hits > jnp.int32(0)

    out_refs[4][...] = jnp.where(
        is_over & valid, jnp.int32(CODE_OVER_LIMIT), jnp.int32(CODE_OK)
    )
    if lean:
        # decided-mode fire-and-forget callers read ONLY the code; the
        # other five decision tiles would be written to HBM and dropped
        # (an opaque kernel's outputs can't be dead-code-eliminated)
        return

    near_threshold = jnp.floor(
        limit.astype(jnp.float32) * near_ratio
    ).astype(jnp.int32)
    near_exceeded = after > near_threshold

    all_over = before >= limit
    over_delta_over = jnp.where(all_over, hits, after - limit)
    near_delta_over = jnp.where(
        all_over,
        jnp.zeros_like(hits),
        limit - jnp.maximum(near_threshold, before),
    )
    near_delta_ok = jnp.where(
        near_exceeded,
        jnp.where(before >= near_threshold, hits, after - near_threshold),
        jnp.zeros_like(hits),
    )

    window_end = cur_window + safe_div
    millis_remaining = (window_end - now) * 1000
    calls_remaining = jnp.maximum(limit - after, jnp.int32(1))
    zero = jnp.int32(0)

    out_refs[5][...] = jnp.where(valid & ~is_over, limit - after, zero)
    out_refs[6][...] = jnp.where(valid, window_end - now, zero)
    out_refs[7][...] = jnp.where(
        near_exceeded & ~is_over & valid,
        floor_div_exact_i32(millis_remaining, calls_remaining),
        zero,
    )
    out_refs[8][...] = jnp.where(
        valid, jnp.where(is_over, near_delta_over, near_delta_ok), zero
    )
    out_refs[9][...] = jnp.where(valid & is_over, over_delta_over, zero)


# --- the W-way set scan -----------------------------------------------------
#
# The set-associative layout (ops/slab.py) makes the lookup/insert/evict
# decision a bounded W-wide scan per item, and with W == LANES a set is
# EXACTLY one lane register: sets tile across the grid one per sublane row
# (tile = (block_rows, 128) — block_rows items' sets per grid step), and
# the scan's reductions (any(match), argmin(victim score), the picked-way
# select) are single cross-lane ops. XLA still owns the set gather that
# produces these tiles (contiguous W-row blocks ride the native dynamic
# gather); this kernel owns everything between gather and sort: liveness,
# tag match, the tiered eviction valuation, and the way choice.

# eviction tier packing — MUST mirror ops/slab.py (_choose_ways); the
# interpret-mode differential test pins the two scans bit-for-bit
_SCORE_TIER_SHIFT = 28
_TIER_WINDOW_ENDED, _TIER_LIVE = 1, 2


def _way_scan_kernel(
    now_ref,
    st_fp_lo_ref,
    st_fp_hi_ref,
    st_count_ref,
    st_window_ref,
    st_expire_ref,
    st_div_ref,
    q_fp_lo_ref,
    q_fp_hi_ref,
    out_ref,
):
    now = now_ref[0]
    expire = st_expire_ref[...]
    div = st_div_ref[...]
    count = st_count_ref[...]
    live = expire > now
    match = (
        live
        & (st_fp_lo_ref[...] == q_fp_lo_ref[...])
        & (st_fp_hi_ref[...] == q_fp_hi_ref[...])
    )
    window_ended = live & (div > 0) & (st_window_ref[...] + div <= now)

    lane = jax.lax.broadcasted_iota(jnp.int32, expire.shape, 1)
    way_bits = 7  # log2(LANES); this kernel is the ways == 128 shape
    # fp_hi bits [7, 14) — the same rotation source as the XLA scan
    # (ops/slab.py _choose_ways): low bits belong to the mesh owner hash,
    # top bits to the sort tiebreaker. The mask keeps the arithmetic
    # int32 shift exact.
    pref = (q_fp_hi_ref[...] >> jnp.int32(way_bits)) & jnp.int32(LANES - 1)
    rot = (lane - pref) & jnp.int32(LANES - 1)
    count_cap = (1 << (_SCORE_TIER_SHIFT - way_bits)) - 1
    cnt = jnp.minimum(count, jnp.int32(count_cap))
    tier = jnp.where(
        live,
        jnp.where(window_ended, _TIER_WINDOW_ENDED, _TIER_LIVE),
        0,
    )
    sub = jnp.where(live, (cnt << way_bits) | rot, rot)
    score = (tier << _SCORE_TIER_SHIFT) | sub

    # argmin via min + first-lane-at-min: scores are unique within a row
    # (rot is a bijection over lanes), so the select is exact
    min_score = jnp.min(score, axis=1, keepdims=True)
    victim = jnp.min(
        jnp.where(score == min_score, lane, jnp.int32(LANES)),
        axis=1,
        keepdims=True,
    )
    m_any = jnp.max(match.astype(jnp.int32), axis=1, keepdims=True)
    m_way = jnp.min(
        jnp.where(match, lane, jnp.int32(LANES)), axis=1, keepdims=True
    )
    way = jnp.where(m_any > 0, m_way, victim)

    # one output tile: lane 0 = chosen way, lane 1 = matched flag (the
    # caller slices; a (b, 2) output would fight the lane tiling)
    out_ref[...] = jnp.where(lane == 0, way, m_any)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_way_scan(
    st_fp_lo: jnp.ndarray,  # uint32[b, W] the gathered set planes
    st_fp_hi: jnp.ndarray,
    st_count: jnp.ndarray,
    st_window: jnp.ndarray,
    st_expire: jnp.ndarray,
    st_div: jnp.ndarray,
    q_fp_lo: jnp.ndarray,  # uint32[b] the querying items
    q_fp_hi: jnp.ndarray,
    now: jnp.ndarray,  # int32 scalar
    interpret: bool = False,
):
    """Run the W-way set scan over gathered set planes; returns
    (int32[b] chosen way, bool[b] matched) — bit-identical to the XLA
    scan in ops/slab.py _choose_ways (pinned by tests/test_pallas_slab.py).
    Requires W == LANES (= 128, the default SLAB_WAYS): a set per sublane
    row is the whole point of the shape."""
    b, w = st_fp_lo.shape
    if w != LANES:
        raise ValueError(f"pallas way scan needs ways == {LANES}, got {w}")
    block_rows = math.gcd(b, BLOCK_ROWS)

    as_i32 = lambda x: x.astype(jnp.int32)
    # per-item query words broadcast across the lane axis: the kernel has
    # no per-sublane scalar path, and the (b, W) planes it joins are the
    # dominant traffic anyway
    q_lo = jnp.broadcast_to(as_i32(q_fp_lo)[:, None], (b, w))
    q_hi = jnp.broadcast_to(as_i32(q_fp_hi)[:, None], (b, w))

    block = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // block_rows,),
        in_specs=[block] * 8,
        out_specs=[block],
        scratch_shapes=[],
    )
    planes = [
        as_i32(st_fp_lo),
        as_i32(st_fp_hi),
        as_i32(st_count),
        as_i32(st_window),
        as_i32(st_expire),
        as_i32(st_div),
        q_lo,
        q_hi,
    ]
    (out,) = pl.pallas_call(
        _way_scan_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, w), jnp.int32, vma=out_vma(now, *planes))
        ],
        interpret=interpret,
    )(now.astype(jnp.int32).reshape(1), *planes)
    return out[:, 0], out[:, 1] > 0


@functools.partial(
    jax.jit, static_argnames=("decide", "lean", "interpret")
)
def pallas_slab_apply(
    s_fp_lo: jnp.ndarray,  # uint32[b] slot-sorted
    s_fp_hi: jnp.ndarray,
    s_hits: jnp.ndarray,  # uint32[b]
    s_limit: jnp.ndarray,  # uint32[b]
    s_div: jnp.ndarray,  # int32[b]
    s_jit: jnp.ndarray,  # int32[b]
    seg_start: jnp.ndarray,  # bool[b] first item of each (slot, fp) group
    st_rows_t: jnp.ndarray,  # uint32[5, b]: stored fp_lo/fp_hi/count/window/expire
    now: jnp.ndarray,  # int32 scalar
    near_ratio: jnp.ndarray,  # float32 scalar
    decide: bool = True,
    lean: bool = False,
    interpret: bool = False,
):
    """Run the fused INCRBY(+decide) kernel over a slot-sorted batch.

    Returns (before, after, new_window, new_expire[, code, remaining,
    duration, throttle, near_delta, over_delta]) — all uint32[b]/int32[b]
    in the SORTED order of the inputs; ops/slab.py unsorts and scatters.
    lean=True (decide only): stop at the code — the five tiles after it
    are neither computed nor written (fire-and-forget decided mode).
    """
    (b,) = s_hits.shape
    if b % LANES:
        raise ValueError(f"batch size must be a multiple of {LANES}, got {b}")
    rows = b // LANES
    # largest power-of-two divisor of rows, capped at BLOCK_ROWS — any
    # 128-multiple batch gets a valid tiling (gcd with a power of two)
    block_rows = math.gcd(rows, BLOCK_ROWS)

    shape2d = (rows, LANES)
    as2d = lambda x: x.astype(jnp.int32).reshape(shape2d)
    inputs = (
        as2d(s_fp_lo),
        as2d(s_fp_hi),
        as2d(s_hits),
        # after-mode never reads limits: don't ship the tile (saves one
        # HBM->VMEM input plane per grid step on the production path)
        *((as2d(s_limit),) if decide else ()),
        as2d(s_div),
        as2d(s_jit),
        as2d(seg_start),
        as2d(st_rows_t[0]),  # fp_lo
        as2d(st_rows_t[1]),  # fp_hi
        as2d(st_rows_t[2]),  # count
        as2d(st_rows_t[3]),  # window
        as2d(st_rows_t[4]),  # expire
    )

    n_out = (5 if lean else 10) if decide else 4
    block = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows // block_rows,),
        in_specs=[block] * len(inputs),
        out_specs=[block] * n_out,
        scratch_shapes=[pltpu.SMEM((1, 2), jnp.int32)],
    )
    outs = pl.pallas_call(
        functools.partial(
            _slab_apply_kernel, decide=decide, lean=lean, block_rows=block_rows
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                shape2d, jnp.int32, vma=out_vma(now, near_ratio, *inputs)
            )
        ]
        * n_out,
        interpret=interpret,
    )(
        now.astype(jnp.int32).reshape(1),
        near_ratio.astype(jnp.float32).reshape(1),
        *inputs,
    )
    return tuple(o.reshape(b) for o in outs)
