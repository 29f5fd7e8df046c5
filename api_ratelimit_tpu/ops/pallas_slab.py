"""Pallas TPU kernel: the fused fixed-window INCRBY engine.

This is the "batched Pallas fixed-window INCRBY kernel" of the north star
(SURVEY.md:18): the stateful heart of the slab update — duplicate
serialization, window rollover, increment, and the full decision math —
executed as ONE kernel pass over VMEM-resident tiles.

Division of labor with XLA (ops/slab.py drives both):

  XLA owns the gathers and the sort: the K-way probe gather, the 3-key
  sort that groups duplicate keys, and the stored-row gather. Those
  compile to the TPU's native dynamic-gather paths — Pallas has no
  per-element HBM access; it would have to emulate gathers with thousands
  of tiny DMAs. The final row write is the exception (the set-tile
  write-back at the end of this module): XLA's scatter pays for every lane
  of the launch, padding included, while a launch's writes land in a few
  contiguous set tiles, one DMA each way.

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


# --- the set-tile write-back -------------------------------------------------
#
# The launch's one row write, for the ways == 128 shape. XLA's row scatter
# costs ~70 ns per lane of the launch whether the lane writes or not
# (padding carries the dropped index n), so a 65,536-wide launch paid for
# every lane. Here the work follows the rows written: the batch is sorted
# by slot = set * W + way, so the writes of one set are contiguous, and
# the table's stored layout (u32[n_slots, 8]{0,1:T(8,128)}) makes each set
# one contiguous (8, 128) tile — ROW_WIDTH sublanes by W ways. Per grid
# step of WRITEBACK_CHUNK sorted lanes:
#
#   1. one DMA reads the tile of each set the chunk writes, all in flight
#      at once (a (8, 1) column DMA per row would skip the read, but
#      Mosaic refuses a DMA slice narrower than the 128-lane tiling);
#   2. each written row is rotated into its way's lane of the tile, which
#      rides in a register from lane to lane;
#   3. one DMA writes each tile back.
#
# Lanes that write nothing (padding, contention losers, non-last
# duplicates) are masked out of step 2, and grid steps past the last
# non-padding lane do nothing. Step 2 is branch-free and unrolled
# WRITEBACK_UNROLL lanes deep so that the rotates of neighbouring lanes
# overlap: on one v5e this took the kernel from 2.35 to 1.36 ms at the
# owner's launch (12,566 sets of 22,863 lanes; PERF.md). The unrolling is
# left to the lowering (fori_loop unroll=True): a body traced once keeps
# the kernel's share of a program's trace and lowering time small.

WRITEBACK_CHUNK = 1024  # sorted lanes per grid step: 1024 tiles = 4 MiB VMEM
WRITEBACK_UNROLL = 16
WRITEBACK_WAIT_GROUP = 16  # tiles one DMA wait stands for


def _writeback_kernel(
    count_ref,  # SMEM int32[1]: lanes before the first padding lane
    idx_ref,  # SMEM int32[chunk]: the slot each lane writes, n if none
    rows_ref,  # VMEM uint32[chunk // 128, ROW_WIDTH, 128]: lane-major rows
    _table_in,  # aliased to table_ref
    table_ref,  # HBM uint32[n_sets, ROW_WIDTH, ways]
    tiles_ref,  # VMEM uint32[chunk + 1, ROW_WIDTH, ways]: a tile per set, and a spare
    sets_ref,  # SMEM int32[chunk]: the set each tile holds
    sems,  # DMA semaphores: [0] reads, [1] writes
    *,
    chunk: int,
    n_slots: int,
):
    way_bits = LANES.bit_length() - 1  # ways == LANES: a set is one lane row
    n_sets = n_slots // LANES
    spare = chunk  # the store target of lanes before the first tile opens
    live = jnp.minimum(count_ref[0] - pl.program_id(0) * chunk, chunk)

    def copy(k, read, size=None):
        hbm = table_ref.at[sets_ref[k]] if size is None else table_ref.at[pl.ds(0, size)]
        vmem = tiles_ref.at[k] if size is None else tiles_ref.at[pl.ds(0, size)]
        if read:
            return pltpu.make_async_copy(hbm, vmem, sems.at[0])
        return pltpu.make_async_copy(vmem, hbm, sems.at[1])

    def wait(n, read):
        # a DMA semaphore counts what landed: wait for n tiles, `group` at
        # a time and then one at a time (a chunk's tiles are distinct
        # sets, so n <= n_sets)
        group = 1 << (min(WRITEBACK_WAIT_GROUP, n_sets).bit_length() - 1)

        def waits(size):
            def body(i, c):
                copy(0, read, size).wait()
                return c

            return body

        jax.lax.fori_loop(0, n // group, waits(group), 0)
        jax.lax.fori_loop(0, n % group, waits(None), 0)

    @pl.when(live > 0)
    def _():
        def open_tile(j, carry):
            k, cur = carry
            slot = idx_ref[j]
            s = slot >> way_bits
            opens = (slot < n_slots) & (s != cur)

            @pl.when(opens)
            def _():
                sets_ref[k] = s
                copy(k, read=True).start()

            return k + opens.astype(jnp.int32), jnp.where(opens, s, cur)

        n_tiles, _ = jax.lax.fori_loop(
            0, live, open_tile, (jnp.int32(0), jnp.int32(-1))
        )
        wait(n_tiles, read=True)

        lane = jax.lax.broadcasted_iota(jnp.int32, tiles_ref.shape[1:], 1)

        def merge(j, carry):
            k, cur, tile = carry
            slot = idx_ref[j]
            written = (slot < n_slots) & (j < live)
            s = slot >> way_bits
            opens = written & (s != cur)
            k = k + opens.astype(jnp.int32)
            at = jnp.where(k > 0, k - 1, spare)
            tile = jnp.where(opens, tiles_ref[at], tile)
            way = slot & (LANES - 1)
            # lane j % LANES of its block holds lane j's row: rotate it to `way`
            row = pltpu.roll(rows_ref[j >> way_bits], (way - j) & (LANES - 1), axis=1)
            tile = jnp.where((lane == way) & written, row, tile)
            tiles_ref[at] = tile
            return k, jnp.where(opens, s, cur), tile

        def merge_block(t, carry):
            # unroll=True: traced once, unrolled by the lowering
            base = t * WRITEBACK_UNROLL
            return jax.lax.fori_loop(
                0, WRITEBACK_UNROLL, lambda u, c: merge(base + u, c), carry,
                unroll=True,
            )

        jax.lax.fori_loop(
            0, chunk // WRITEBACK_UNROLL, merge_block,
            (jnp.int32(0), jnp.int32(-1), jnp.zeros(tiles_ref.shape[1:], tiles_ref.dtype)),
        )

        def write_tile(k, c):
            copy(k, read=False).start()
            return c

        jax.lax.fori_loop(0, n_tiles, write_tile, 0)
        # the next grid step may reopen this step's last set: land every
        # write before it reads
        wait(n_tiles, read=False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_slab_writeback(
    table: jnp.ndarray,  # uint32[n_slots, ROW_WIDTH]
    write_idx: jnp.ndarray,  # int32[b] slot-sorted; n where the lane writes nothing
    new_rows: jnp.ndarray,  # uint32[b, ROW_WIDTH] the rows, same order
    count: jnp.ndarray,  # int32 scalar: lanes before the first padding lane
    interpret: bool = False,
):
    """table.at[write_idx].set(new_rows, mode="drop") for a slot-sorted
    launch on the ways == 128 geometry, in place (donate the table).
    Lanes at or past `count` are never read; every written slot must be
    unique (one writer per slot, as _finish_update guarantees).
    Bit-identical to ops/slab.py _scatter_rows (tests/test_slab_writeback.py)."""
    n, width = table.shape
    (b,) = write_idx.shape
    if b % LANES:
        raise ValueError(f"batch size must be a multiple of {LANES}, got {b}")
    n_sets = n // LANES
    chunk = math.gcd(b, WRITEBACK_CHUNK)
    # the stored layout's bitcast: one (ROW_WIDTH, ways) tile per set
    sets = table.reshape(n_sets, LANES, width).transpose(0, 2, 1)
    rows = new_rows.reshape(b // LANES, LANES, width).transpose(0, 2, 1)
    count = jnp.asarray(count, jnp.int32).reshape(1)

    def block(i, count_ref):
        # chunks past the padding repeat the last live block: no refetch
        return jnp.minimum(i, jnp.maximum(count_ref[0] - 1, 0) // chunk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // chunk,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda i, c: (block(i, c),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((chunk // LANES, width, LANES),
                         lambda i, c: (block(i, c), 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((chunk + 1, width, LANES), table.dtype),
            pltpu.SMEM((chunk,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_writeback_kernel, chunk=chunk, n_slots=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            sets.shape, sets.dtype, vma=out_vma(count, write_idx, rows, sets)
        ),
        input_output_aliases={3: 0},
        name="slab_writeback",
        interpret=interpret,
    )(count, write_idx.astype(jnp.int32), rows, sets)
    return out.transpose(0, 2, 1).reshape(n, width)
