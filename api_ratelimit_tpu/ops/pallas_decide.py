"""Pallas TPU kernel: fused fixed-window decision math.

The VPU twin of ops/decide.py — one kernel evaluates code / remaining /
duration / throttle / stats-deltas for a whole micro-batch without any
intermediate HBM round-trips. Semantically identical to decide(); the
randomized parity test (tests/test_pallas.py) pins kernel == jnp == the
scalar host oracle on every branch.

Layout: the batch is viewed as (rows, 128) int32/uint32/float32 tiles —
the natural VPU shape (8x128 lanes). The kernel runs on a 1-D grid over
row-blocks. Any power-of-two batch >= 128 (one lane row — the backend's
smallest launch bucket, backends/tpu.py) works: row counts <= the 64-row
block run as one smaller block, larger power-of-two counts divide evenly.
Non-power-of-two row counts that don't divide by the block raise — the
backend's buckets are always powers of two, so the constraint never fires
in production. now/near_ratio arrive as SMEM scalars.

Reference semantics mirrored (same as ops/decide.py):
src/limiter/base_limiter.go:83-86, :88, :107-109, :129-145, :154-165 and
src/utils/utilities.go:34-38.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decide import CODE_OK, CODE_OVER_LIMIT, DecideResult, floor_div_exact_i32
from .pallas_slab import out_vma

LANES = 128
BLOCK_ROWS = 64  # 64 x 128 = 8192 items per grid step


def _decide_kernel(
    # scalar prefetch (SMEM)
    now_ref,
    near_ratio_ref,
    # inputs (VMEM blocks)
    before_ref,
    after_ref,
    hits_ref,
    limit_ref,
    divider_ref,
    # outputs (VMEM blocks)
    code_ref,
    remaining_ref,
    duration_ref,
    throttle_ref,
    near_delta_ref,
    over_delta_ref,
):
    now = now_ref[0]
    near_ratio = near_ratio_ref[0]

    # All arithmetic is int32: Mosaic lacks uint32<->float32 casts and the
    # operands are < 2^31 in practice (counters within one window). The jnp
    # wrapper converts to/from uint32 at the boundary.
    before = before_ref[...]
    after = after_ref[...]
    hits = hits_ref[...]
    limit = limit_ref[...]
    divider = jnp.maximum(divider_ref[...], 1)

    over_threshold = limit
    near_threshold = jnp.floor(
        limit.astype(jnp.float32) * near_ratio
    ).astype(jnp.int32)

    is_over = after > over_threshold
    near_exceeded = after > near_threshold
    valid = hits > jnp.int32(0)

    # OVER branch stats split
    all_over = before >= over_threshold
    over_delta_over = jnp.where(all_over, hits, after - over_threshold)
    near_delta_over = jnp.where(
        all_over,
        jnp.zeros_like(hits),
        over_threshold - jnp.maximum(near_threshold, before),
    )

    # OK branch near accounting
    near_delta_ok = jnp.where(
        near_exceeded,
        jnp.where(before >= near_threshold, hits, after - near_threshold),
        jnp.zeros_like(hits),
    )

    # floor_div_exact_i32: vector idiv expands to a ~32-pass loop in Mosaic
    # exactly as in XLA (~100ms per site at batch 2^20 — the r3 perf gap)
    window_end = floor_div_exact_i32(now, divider) * divider + divider
    millis_remaining = (window_end - now) * 1000
    calls_remaining = jnp.maximum(over_threshold - after, jnp.int32(1))
    throttle = jnp.where(
        near_exceeded & ~is_over & valid,
        floor_div_exact_i32(millis_remaining, calls_remaining),
        jnp.int32(0),
    )

    zero = jnp.int32(0)
    code_ref[...] = jnp.where(
        is_over & valid, jnp.int32(CODE_OVER_LIMIT), jnp.int32(CODE_OK)
    )
    remaining_ref[...] = jnp.where(
        valid & ~is_over, over_threshold - after, zero
    )
    duration_ref[...] = jnp.where(valid, window_end - now, zero)
    throttle_ref[...] = throttle
    near_delta_ref[...] = jnp.where(
        valid, jnp.where(is_over, near_delta_over, near_delta_ok), zero
    )
    over_delta_ref[...] = jnp.where(valid & is_over, over_delta_over, zero)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_decide(
    before: jnp.ndarray,
    after: jnp.ndarray,
    hits: jnp.ndarray,
    limit: jnp.ndarray,
    divider: jnp.ndarray,
    now: jnp.ndarray,
    near_ratio: jnp.ndarray,
    interpret: bool = False,
) -> DecideResult:
    (b,) = before.shape
    if b % LANES:
        raise ValueError(f"batch size must be a multiple of {LANES}, got {b}")
    rows = b // LANES
    block_rows = min(BLOCK_ROWS, rows)
    if rows % block_rows:
        raise ValueError(f"rows {rows} not divisible by block {block_rows}")

    shape2d = (rows, LANES)
    as2d = lambda x, dt: x.astype(dt).reshape(shape2d)
    inputs = (
        as2d(before, jnp.int32),
        as2d(after, jnp.int32),
        as2d(hits, jnp.int32),
        as2d(limit, jnp.int32),
        as2d(divider, jnp.int32),
    )

    # with scalar prefetch, the index map receives (grid_idx, *scalar_refs)
    block = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    out_shapes = [
        jax.ShapeDtypeStruct(
            shape2d, jnp.int32, vma=out_vma(now, near_ratio, *inputs)
        )
    ] * 6

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows // block_rows,),
        in_specs=[block] * 5,
        out_specs=[block] * 6,
    )
    outs = pl.pallas_call(
        _decide_kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(
        now.astype(jnp.int32).reshape(1),
        near_ratio.astype(jnp.float32).reshape(1),
        *inputs,
    )
    code, remaining, duration, throttle, near_delta, over_delta = (
        o.reshape(b) for o in outs
    )
    return DecideResult(
        code=code,
        limit_remaining=remaining.astype(jnp.uint32),
        duration_until_reset=duration,
        throttle_millis=throttle.astype(jnp.uint32),
        near_delta=near_delta.astype(jnp.uint32),
        over_delta=over_delta.astype(jnp.uint32),
    )
