"""Close the bisect gap: real slab functions, incremental output variants.

bisect_step2 (inline ops, scalar output) = 0.11ms/step; engine_ab (real
functions, array outputs) = ~318ms/step — after the division fix. The delta
hides in what the bisect skipped: the real update's row stack, health
reductions, decide(), _unsort, the u8 cast, packbits, or ARRAY OUTPUTS
themselves. Each variant here uses the REAL shipped functions, chained
donated state, varied staged inputs, adding one suspect at a time.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _ab_common import NOW_LIT, downscale, make_expand, stage_zipf_ids


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--slots", type=int, default=1 << 23)
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--repeats", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.decide import decide
    from api_ratelimit_tpu.ops.slab import (
        SlabBatch,
        _slab_step_sorted,
        _slab_update_sorted,
        _unsort,
        make_slab,
    )

    device = jax.devices()[0]
    downscale(args, device.platform)
    b, n = args.batch, args.slots
    R = args.repeats
    now_lit = NOW_LIT

    expand = make_expand()

    print(f"[ab2] staging {R + 1} x {b * 4 >> 20}MB id arrays", file=sys.stderr, flush=True)
    staged = stage_zipf_ids(device, b, args.keys, R + 1)
    # Placement check: CPU step time at batch 8192 extrapolates to
    # ~346ms at 2^20 — almost exactly the on-chip ~318ms residual. If a
    # buffer or computation silently lands on the host, every
    # "device" measurement here is actually CPU speed;
    # make placement explicit in the log.
    print(f"[ab2] staged[0].devices = {staged[0].devices()}", file=sys.stderr, flush=True)
    print("[ab2] staging done", file=sys.stderr, flush=True)

    results: dict = {"platform": device.platform, "batch": b, "n_slots": n}

    def timed(label, step, raw_table=False):
        print(
            f"[ab2:{label}] staging {n * 32 >> 20}MB slab",
            file=sys.stderr,
            flush=True,
        )
        state = jax.device_put(make_slab(n), device)
        jax.block_until_ready(state)
        print(f"[ab2:{label}] slab staged; warmup compile", file=sys.stderr, flush=True)
        if raw_table:
            state = state.table
        out = step(state, staged[-1])
        state = out[0]
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        outs = []
        for i in range(R):
            out = step(state, staged[i])
            state = out[0]
            outs.append(out[1:])
        jax.block_until_ready(state)
        t_dev = time.perf_counter() - t0
        # device_get, not block_until_ready: the e2e leg must pay the
        # actual D2H readback (the ~280ms/step prime suspect) or
        # array-out variants would read as free.
        fetched = jax.device_get(outs)
        t_e2e = time.perf_counter() - t0
        leaf = jax.tree_util.tree_leaves(state)[0]
        results[label] = {
            "ms_device": round(t_dev / R * 1e3, 3),
            "ms_e2e": round(t_e2e / R * 1e3, 3),
            "state_devices": str(leaf.devices()),
        }
        print(f"[ab2:{label}] {results[label]}", file=sys.stderr, flush=True)

    # v0: the bisect's fastest inline program through THIS harness —
    # same probe/sort/permute/update/scatter, no floor_div, no decide,
    # no health, scalar out; rules out harness differences in one number
    from api_ratelimit_tpu.ops.slab import _choose_ways, _sort_key

    @functools.partial(jax.jit, donate_argnames=("state",))
    def v0(state, ids):
        import jax.numpy as jnp2

        batch = expand(ids)
        now = jnp.int32(now_lit)
        chosen, _cls, matched, picked_rows = _choose_ways(state, batch, now, 128)
        bsz = chosen.shape[0]
        key = _sort_key(chosen, matched, batch.fp_hi, state.n_slots)
        (_, order) = jax.lax.sort(
            (key, jnp.arange(bsz, dtype=jnp.int32)), num_keys=1, is_stable=True
        )
        s_slot = chosen[order]
        s_fp_lo = batch.fp_lo[order]
        s_fp_hi = batch.fp_hi[order]
        s_hits = batch.hits[order]
        st_rows = picked_rows[order]
        same_prev = (
            (s_slot[1:] == s_slot[:-1])
            & (s_fp_lo[1:] == s_fp_lo[:-1])
            & (s_fp_hi[1:] == s_fp_hi[:-1])
        )
        seg_start = jnp.concatenate([jnp.array([True]), ~same_prev])
        incl = jnp.cumsum(s_hits, dtype=jnp.uint32)
        excl = incl - s_hits
        seg_base = jax.lax.cummax(jnp.where(seg_start, excl, jnp.uint32(0)))
        prior = excl - seg_base
        base = jnp.where(
            (s_hits > 0)
            & (st_rows[:, 4].astype(jnp.int32) > now)
            & (st_rows[:, 0] == s_fp_lo)
            & (st_rows[:, 1] == s_fp_hi),
            st_rows[:, 2],
            jnp.uint32(0),
        )
        s_after = base + prior + s_hits
        is_last = jnp.concatenate([s_slot[1:] != s_slot[:-1], jnp.array([True])])
        write_idx = jnp.where(is_last, s_slot, jnp.int32(state.n_slots))
        new_rows = jnp.stack([s_fp_lo, s_fp_hi, s_after] + [s_fp_lo] * 5, axis=1)
        table = state.table.at[write_idx].set(
            new_rows, mode="drop", unique_indices=True
        )
        from api_ratelimit_tpu.ops.slab import SlabState

        return SlabState(table=table), s_after.sum()

    timed("v0_inline_nodivide", v0)

    # v00: byte-for-byte the bisect_step2 final program — RAW table arg
    # (not SlabState), donate_argnums, scalar out. If v00 is fast and v0
    # slow, the difference is the harness/pytree, not the program.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def v00(table, ids):
        from api_ratelimit_tpu.ops.slab import SlabState

        st = SlabState(table=table)
        batch = expand(ids)
        now = jnp.int32(now_lit)
        chosen, _cls, matched, picked_rows = _choose_ways(st, batch, now, 128)
        bsz = chosen.shape[0]
        key = _sort_key(chosen, matched, batch.fp_hi, n)
        (_, order) = jax.lax.sort(
            (key, jnp.arange(bsz, dtype=jnp.int32)), num_keys=1, is_stable=True
        )
        s_slot = chosen[order]
        s_fp_lo = batch.fp_lo[order]
        s_fp_hi = batch.fp_hi[order]
        s_hits = batch.hits[order]
        st_rows = picked_rows[order]
        seg_start = jnp.concatenate(
            [jnp.array([True]),
             ~((s_slot[1:] == s_slot[:-1])
               & (s_fp_lo[1:] == s_fp_lo[:-1])
               & (s_fp_hi[1:] == s_fp_hi[:-1]))]
        )
        incl = jnp.cumsum(s_hits, dtype=jnp.uint32)
        excl = incl - s_hits
        seg_base = jax.lax.cummax(jnp.where(seg_start, excl, jnp.uint32(0)))
        prior = excl - seg_base
        base = jnp.where(
            (s_hits > 0)
            & (st_rows[:, 4].astype(jnp.int32) > now)
            & (st_rows[:, 0] == s_fp_lo)
            & (st_rows[:, 1] == s_fp_hi),
            st_rows[:, 2],
            jnp.uint32(0),
        )
        s_after = base + prior + s_hits
        is_last = jnp.concatenate([s_slot[1:] != s_slot[:-1], jnp.array([True])])
        write_idx = jnp.where(is_last, s_slot, jnp.int32(n))
        new_rows = jnp.stack([s_fp_lo, s_fp_hi, s_after] + [s_fp_lo] * 5, axis=1)
        table = table.at[write_idx].set(new_rows, mode="drop", unique_indices=True)
        return table, s_after.sum()

    timed("v00_rawtable_bisect", v00, raw_table=True)

    # v1: REAL update (health off), scalar out
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v1(state, ids):
        state, _b, s_after, _i, order, health, _ = _slab_update_sorted(
            state, expand(ids), jnp.int32(now_lit), 4, count_health=False
        )
        return state, s_after.sum()

    timed("update_scalar", v1)

    # v2: + health reductions
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v2(state, ids):
        state, _b, s_after, _i, order, health, _ = _slab_update_sorted(
            state, expand(ids), jnp.int32(now_lit), 4, count_health=True
        )
        return state, s_after.sum() + health.sum()

    timed("update_health_scalar", v2)

    # v3: + unsort + u8 cast, still scalar out
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v3(state, ids):
        state, _b, s_after, _i, order, health, _ = _slab_update_sorted(
            state, expand(ids), jnp.int32(now_lit), 4, count_health=True
        )
        after = jnp.minimum(_unsort(s_after, order), jnp.uint32(255))
        return state, after.astype(jnp.uint8).sum() + health.sum()

    timed("after_scalar", v3)

    # v4: after-mode with the REAL array output (u8[b])
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v4(state, ids):
        state, _b, s_after, _i, order, health, _ = _slab_update_sorted(
            state, expand(ids), jnp.int32(now_lit), 4, count_health=True
        )
        after = jnp.minimum(_unsort(s_after, order), jnp.uint32(255))
        return state, after.astype(jnp.uint8), health

    timed("after_array", v4)

    # v4b: array output WITHOUT the u8 min/cast — splits "returning an
    # array" from "the narrowing cast" if v4 is slow
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v4b(state, ids):
        state, _b, s_after, _i, order, health, _ = _slab_update_sorted(
            state, expand(ids), jnp.int32(now_lit), 4, count_health=True
        )
        return state, _unsort(s_after, order), health

    timed("after_array_u32", v4b)

    # v5: + decide() on sorted results, scalar out
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v5(state, ids):
        state, _b, _a, d, order, health = _slab_step_sorted(
            state,
            expand(ids),
            jnp.int32(now_lit),
            jnp.float32(0.8),
            ways=128,
            use_pallas=False,
            count_health=True,
        )
        return state, d.code.sum() + health.sum()

    timed("decided_scalar", v5)

    # v6: + unsort(code) + ==2 + packbits (the real bench_step output)
    @functools.partial(jax.jit, donate_argnames=("state",))
    def v6(state, ids):
        state, _b, _a, d, order, health = _slab_step_sorted(
            state,
            expand(ids),
            jnp.int32(now_lit),
            jnp.float32(0.8),
            ways=128,
            use_pallas=False,
            count_health=True,
        )
        over = _unsort(d.code, order) == 2
        return state, jnp.packbits(over), health

    timed("decided_packbits", v6)

    # v7: same output bits via the multiply-add packer (ops/decide.py
    # packbits_muladd) — the candidate swap if v6 shows packbits' shift/or
    # lowering is another pathological vector op class (like division was)
    from api_ratelimit_tpu.ops.decide import packbits_muladd

    @functools.partial(jax.jit, donate_argnames=("state",))
    def v7(state, ids):
        state, _b, _a, d, order, health = _slab_step_sorted(
            state,
            expand(ids),
            jnp.int32(now_lit),
            jnp.float32(0.8),
            ways=128,
            use_pallas=False,
            count_health=True,
        )
        over = _unsort(d.code, order) == 2
        return state, packbits_muladd(over), health

    timed("decided_muladd_pack", v7)

    # v8: v6's program with use_pallas=True — the engine BENCH_r03's 4.0M
    # headline actually ran. Every other variant is the XLA twin; if the
    # residual lives in the Mosaic kernel (e.g. the SMEM-carry grid
    # serializing at 2^20/block_rows steps), only this row shows it.
    # TPU only: interpret-mode Pallas on CPU is minutes per step and the
    # CPU smoke run's job is validating the harness, not timing Mosaic.
    if device.platform == "tpu":

        @functools.partial(jax.jit, donate_argnames=("state",))
        def v8(state, ids):
            state, _b, _a, d, order, health = _slab_step_sorted(
                state,
                expand(ids),
                jnp.int32(now_lit),
                jnp.float32(0.8),
                ways=128,
                use_pallas=True,
                count_health=True,
            )
            over = _unsort(d.code, order) == 2
            return state, jnp.packbits(over), health

        timed("decided_pallas", v8)

    print(json.dumps(results))


if __name__ == "__main__":
    main()
