"""Trustworthy bisect of the slab step: the bench's own methodology.

Earlier microbenches gave contradictory numbers on the old remote chip —
closure-captured device scalars inflate a program by ~8ms+, and repeated
identical inputs may dedupe server-side. This bisect reproduces the EXACT
conditions of the real bench loop (the one methodology with a corroborated
artifact, BENCH_r03): donated state chained call-to-call, a distinct staged
ids array per call, every scalar a traced literal, block_until_ready on the
state chain. Each prefix of the step is timed that way, so consecutive
prefixes attribute cost to the op they add.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--slots", type=int, default=1 << 23)
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--repeats", type=int, default=8)
    ap.add_argument("--pallas", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.slab import (
        COL_COUNT,
        COL_EXPIRE,
        COL_FP_HI,
        COL_FP_LO,
        COL_WINDOW,
        SlabBatch,
        _sort_key,
    )

    device = jax.devices()[0]
    if device.platform != "tpu" and args.batch > (1 << 14):
        args.batch, args.slots, args.keys = 1 << 13, 1 << 18, 100_000

    b, n = args.batch, args.slots
    R = args.repeats
    rng = np.random.RandomState(0)
    ids_all = (
        rng.zipf(1.1, size=b * R).astype(np.uint64) % args.keys
    ).astype(np.uint32).reshape(R, b)
    staged = [jax.device_put(ids_all[i], device) for i in range(R)]
    for s in staged:
        s.block_until_ready()
    NOW = 1_700_000_000  # python literal -> traced constant

    def fmix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        return x ^ (x >> 16)

    def expand(ids):
        return SlabBatch(
            fp_lo=fmix(ids),
            fp_hi=fmix(ids ^ jnp.uint32(0x9E3779B9)),
            hits=jnp.ones_like(ids),
            limit=jnp.full_like(ids, 100),
            divider=jnp.full_like(ids, 1).astype(jnp.int32),
            jitter=jnp.zeros_like(ids).astype(jnp.int32),
        )

    def build(stop: str):
        """A state-chained step computing the slab program up to `stop`.
        Always returns (new_table, small_out) so the chain and timing match
        the real bench loop exactly. Stages not reached pass the table
        through untouched."""

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(table, ids):
            now = jnp.int32(NOW)
            batch = expand(ids)
            small = batch.fp_lo.sum()
            if stop == "expand":
                return table, small
            mask = jnp.uint32(n - 1)
            pstep = batch.fp_hi | jnp.uint32(1)
            j = jnp.arange(4, dtype=jnp.uint32)
            cand = (
                (batch.fp_lo[:, None] + j[None, :] * pstep[:, None]) & mask
            ).astype(jnp.int32)
            if stop == "cand":
                return table, small + cand.sum()
            rows = table[cand]
            if stop == "gather":
                return table, small + rows.sum()
            live = rows[:, :, COL_EXPIRE].astype(jnp.int32) > now
            match = (
                live
                & (rows[:, :, COL_FP_LO] == batch.fp_lo[:, None])
                & (rows[:, :, COL_FP_HI] == batch.fp_hi[:, None])
            )
            avail = ~live
            match_any = match.any(axis=1)
            avail_any = avail.any(axis=1)
            pick = jnp.where(
                match_any,
                jnp.argmax(match, axis=1),
                jnp.where(avail_any, jnp.argmax(avail, axis=1), 0),
            )
            chosen = jnp.take_along_axis(cand, pick[:, None], axis=1)[:, 0]
            if stop == "choose":
                return table, small + chosen.sum()
            picked_rows = jnp.take_along_axis(rows, pick[:, None, None], axis=1)[
                :, 0
            ]
            if stop == "pickrows":
                return table, small + picked_rows.sum()
            key = _sort_key(chosen, batch.fp_hi, n)
            (_, order) = jax.lax.sort(
                (key, jnp.arange(b, dtype=jnp.int32)), num_keys=1, is_stable=True
            )
            if stop == "sort":
                return table, small + order.sum()
            s_slot = chosen[order]
            s_fp_lo = batch.fp_lo[order]
            s_fp_hi = batch.fp_hi[order]
            s_hits = batch.hits[order]
            st_rows = picked_rows[order]
            if stop == "permute":
                return table, small + s_slot.sum() + st_rows.sum() + s_hits.sum()
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
            st_count = st_rows[:, COL_COUNT]
            st_window = st_rows[:, COL_WINDOW].astype(jnp.int32)
            st_expire = st_rows[:, COL_EXPIRE].astype(jnp.int32)
            fp_match = (
                (st_expire > now)
                & (st_rows[:, COL_FP_LO] == s_fp_lo)
                & (st_rows[:, COL_FP_HI] == s_fp_hi)
            )
            base = jnp.where(
                (s_hits > 0) & fp_match & (st_window == now), st_count, jnp.uint32(0)
            )
            s_after = base + prior + s_hits
            if stop == "update":
                return table, small + s_after.sum()
            is_last = jnp.concatenate(
                [s_slot[1:] != s_slot[:-1], jnp.array([True])]
            )
            write_idx = jnp.where(is_last, s_slot, jnp.int32(n))
            new_rows = jnp.stack(
                [s_fp_lo, s_fp_hi, s_after] + [s_fp_lo] * 5, axis=1
            )
            table = table.at[write_idx].set(
                new_rows, mode="drop", unique_indices=True
            )
            if stop == "scatter":
                return table, small + s_after.sum()
            unsorted = jnp.zeros_like(s_after).at[order].set(
                s_after, unique_indices=True
            )
            return table, small + unsorted.sum()

        return step

    def timeit(stop: str) -> float:
        step = build(stop)
        table = jax.device_put(np.zeros((n, 8), np.uint32), device)
        table, out = step(table, staged[-1])  # compile
        jax.block_until_ready((table, out))
        t0 = time.perf_counter()
        outs = []
        for i in range(R):
            table, out = step(table, staged[i])
            outs.append(out)
        jax.block_until_ready(table)
        jax.block_until_ready(outs)
        return round((time.perf_counter() - t0) / R * 1e3, 3)

    results: dict = {"platform": device.platform, "batch": b, "n_slots": n}
    for stop in (
        "expand",
        "cand",
        "gather",
        "choose",
        "pickrows",
        "sort",
        "permute",
        "update",
        "scatter",
        "unsort",
    ):
        results[stop + "_ms"] = timeit(stop)
        print(f"[bisect2] {stop}: {results[stop + '_ms']}ms", file=sys.stderr)

    print(json.dumps(results))


if __name__ == "__main__":
    main()
