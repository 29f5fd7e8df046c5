"""Microbenchmark of the slab step's table write-back, on the chip.

Times the two forms of the launch's one row write at a launch's shapes:

  * scatter  the XLA row scatter (ops/slab.py _scatter_rows)
  * kernel   the set-tile Pallas write-back (ops/pallas_slab.py
             pallas_slab_writeback)

Each case builds launches as the owner sees them: `valid` lanes of YCSB
zipfian keys (constant 0.99 over `records` keys) sorted by slot, one
written row per distinct slot (the last lane of its run), the rest of the
`lanes` padding. Both forms get the same launches and a donated table;
each is warmed, then timed over back-to-back calls ended by one
block_until_ready. Both tables must agree afterwards.

Usage:  python tools/microbench_writeback.py [--repeats 200] [--seed 7]
        (CPU smoke: JAX_PLATFORMS=cpu ... --interpret --scale 64 --repeats 2)
Prints one JSON object per case: ms per call of each form, and the
distinct slots and sets per launch.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, table slots, launch lanes, non-padding lanes, key universe)
CASES = (
    ("owner", 1 << 24, 65536, 22863, 10_000_000),
    ("owner_full", 1 << 24, 65536, 65536, 10_000_000),
    ("edge", 1 << 22, 128, 3, 1_000_000),
)
N_LAUNCHES = 8


def _zipf_cdf(n: int, s: float = 0.99) -> np.ndarray:
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    return cdf / cdf[-1]


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def make_launch(rng, cdf, slots, lanes, valid):
    """(write_idx int32[lanes], rows uint32[lanes, 8], count, distinct
    slots, distinct sets) of one slot-sorted launch."""
    ids = np.minimum(np.searchsorted(cdf, rng.random(valid), side="right"),
                     len(cdf) - 1).astype(np.uint32)
    n_sets = slots // 128
    fp_lo = _fmix32(ids + np.uint32(1))
    fp_hi = _fmix32(ids ^ np.uint32(0xA5A5A5A5))
    slot = (fp_lo & np.uint32(n_sets - 1)).astype(np.int64) * 128 + (
        (fp_hi >> np.uint32(7)) & np.uint32(127)
    )
    slot = np.sort(slot)
    last = np.r_[slot[1:] != slot[:-1], True]
    idx = np.full(lanes, slots, dtype=np.int32)
    idx[:valid] = np.where(last, slot, slots)
    rows = rng.integers(0, 2**32, (lanes, 8), dtype=np.uint64).astype(np.uint32)
    written = slot[last]
    return idx, rows, valid, len(written), len(np.unique(written // 128))


def run_case(name, slots, lanes, valid, records, repeats, seed, interpret=False):
    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.pallas_slab import pallas_slab_writeback
    from api_ratelimit_tpu.ops.slab import _scatter_rows

    rng = np.random.default_rng([seed, slots, lanes, valid])
    cdf = _zipf_cdf(records)
    launches = [make_launch(rng, cdf, slots, lanes, valid) for _ in range(N_LAUNCHES)]
    dev = [
        (jnp.asarray(i), jnp.asarray(r), jnp.int32(c)) for i, r, c, _, _ in launches
    ]

    scatter = jax.jit(lambda t, i, r, c: _scatter_rows(t, i, r), donate_argnums=0)
    kernel = jax.jit(
        functools.partial(pallas_slab_writeback, interpret=interpret),
        donate_argnums=0,
    )

    def fresh():
        key = jax.random.key(seed)
        return jax.random.bits(key, (slots, 8), jnp.uint32)

    out = {
        "case": name, "slots": slots, "lanes": lanes, "valid": valid,
        "distinct_slots": float(np.mean([x[3] for x in launches])),
        "distinct_sets": float(np.mean([x[4] for x in launches])),
    }
    tables = {}
    for form, fn in (("scatter", scatter), ("kernel", kernel)):
        table = fresh()
        for args in dev:  # warm: compile, and one pass over every launch
            table = fn(table, *args)
        table.block_until_ready()
        t0 = time.perf_counter()
        for k in range(repeats):
            table = fn(table, *dev[k % N_LAUNCHES])
        table.block_until_ready()
        out[f"{form}_ms"] = (time.perf_counter() - t0) / repeats * 1e3
        tables[form] = table
    out["equal"] = bool(jnp.array_equal(tables["scatter"], tables["kernel"]))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=200)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--cases", default=",".join(c[0] for c in CASES))
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in the Pallas interpreter (CPU smoke)")
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every case's sizes by this (CPU smoke)")
    args = ap.parse_args()
    import jax

    d = jax.devices()[0]
    want = set(args.cases.split(","))
    for name, slots, lanes, valid, records in CASES:
        if name in want:
            k = args.scale
            res = run_case(name, slots // k, max(lanes // k, 128), max(valid // k, 1),
                           records // k, args.repeats, args.seed, args.interpret)
            res["device"] = d.device_kind
            print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
