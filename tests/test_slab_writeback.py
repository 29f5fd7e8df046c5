"""The set-tile write-back (ops/pallas_slab.py pallas_slab_writeback) against
the XLA row scatter it replaces on the Pallas arm (ops/slab.py
_scatter_rows): bit-identical tables on slot-sorted launches (Pallas
interpret mode), the same through the whole update, the gate that keeps
every other shape on the scatter, and the owner's step compiled for a
described v5e chip with the kernel in place of the scatter and no copy of
the table.

The topology is described only inside the module fixture — never at
import, in a skipif or in a parametrize: only one process may load the
TPU library, and collection must be identical on every xdist worker.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from api_ratelimit_tpu.ops.pallas_slab import WRITEBACK_CHUNK, pallas_slab_writeback
from api_ratelimit_tpu.ops.slab import (
    ROW_WIDTH,
    SlabBatch,
    _scatter_rows,
    _slab_update_sorted,
    make_slab,
)

WAYS = 128
N_SLOTS = 16 * WAYS  # 16 sets


def _launch(rng, b, slots, n=N_SLOTS):
    """A slot-sorted launch of b lanes whose first len(slots) lanes carry
    `slots` (sorted here), the rest padding. As _finish_update writes: the
    last lane of each slot's run writes, every other lane carries n.
    Returns (write_idx, rows, count)."""
    slots = np.sort(np.asarray(slots, dtype=np.int64))
    last = np.r_[slots[1:] != slots[:-1], True] if len(slots) else slots
    idx = np.full(b, n, dtype=np.int32)
    idx[: len(slots)] = np.where(last, slots, n)
    rows = rng.integers(0, 2**32, (b, ROW_WIDTH), dtype=np.uint64).astype(np.uint32)
    return idx, rows, len(slots)


def _table(rng, n=N_SLOTS):
    return rng.integers(0, 2**32, (n, ROW_WIDTH), dtype=np.uint64).astype(np.uint32)


def _assert_same_as_scatter(table, idx, rows, count):
    want = _scatter_rows(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(rows))
    got = pallas_slab_writeback(
        jnp.asarray(table), jnp.asarray(idx), jnp.asarray(rows), jnp.int32(count),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return np.asarray(got)


def _random_slots(rng, valid, dup_share=0.3):
    """valid lanes over random slots, some slots repeated (non-last
    duplicates and contention losers both reach the write-back as lanes
    that carry n)."""
    distinct = rng.choice(N_SLOTS, size=max(1, int(valid * (1 - dup_share))), replace=False)
    return rng.choice(distinct, size=valid, replace=True)


@pytest.mark.parametrize("b,valid", [(256, 200), (1024, 37), (2048, 1500)])
def test_padding_at_the_tail(b, valid):
    rng = np.random.default_rng(b + valid)
    table = _table(rng)
    idx, rows, count = _launch(rng, b, _random_slots(rng, valid))
    got = _assert_same_as_scatter(table, idx, rows, count)
    assert (got != table).any()


def test_all_padding_launch_leaves_the_table():
    rng = np.random.default_rng(1)
    table = _table(rng)
    idx, rows, count = _launch(rng, 512, [])
    assert count == 0
    got = _assert_same_as_scatter(table, idx, rows, count)
    np.testing.assert_array_equal(got, table)


def test_full_launch_every_lane_written():
    """Every lane a distinct slot: 2,048 rows over every set, across two
    grid steps."""
    rng = np.random.default_rng(2)
    b = N_SLOTS
    assert b > WRITEBACK_CHUNK
    table = _table(rng)
    idx, rows, count = _launch(rng, b, rng.permutation(N_SLOTS))
    assert count == b and (idx < N_SLOTS).all()
    got = _assert_same_as_scatter(table, idx, rows, count)
    np.testing.assert_array_equal(got[idx], rows)


def test_several_ways_of_one_set():
    """Many ways of one set in one launch, beside single-way sets, with a
    run that straddles the grid-step boundary."""
    rng = np.random.default_rng(3)
    b = 2 * WRITEBACK_CHUNK
    set3 = 3 * WAYS + rng.choice(WAYS, 90, replace=False)
    set5 = 5 * WAYS + np.arange(WAYS)  # every way of set 5
    singles = [0, 9 * WAYS + 7, N_SLOTS - 1]
    slots = np.concatenate([set3, set5, set5, singles])  # set 5 twice: duplicates
    # put set 5's run across the lane WRITEBACK_CHUNK boundary
    pad_before = WRITEBACK_CHUNK - 100 - len(set3) - 1
    filler = 4 * WAYS + rng.integers(0, WAYS, pad_before)
    slots = np.concatenate([slots, filler])
    table = _table(rng)
    idx, rows, count = _launch(rng, b, slots)
    _assert_same_as_scatter(table, idx, rows, count)


def test_losers_and_duplicates_carry_n():
    """Lanes that carry n in the middle of the live prefix (contention
    losers, non-last duplicates) write nothing, whatever their row."""
    rng = np.random.default_rng(4)
    table = _table(rng)
    idx, rows, count = _launch(rng, 384, _random_slots(rng, 300, dup_share=0.7))
    loser = rng.choice(count, 40, replace=False)
    idx[loser] = N_SLOTS
    _assert_same_as_scatter(table, idx, rows, count)


def test_lanes_past_count_are_never_written():
    """count is the contract: lanes at or past it are padding, and the
    kernel stops before them."""
    rng = np.random.default_rng(5)
    table = _table(rng)
    idx, rows, count = _launch(rng, 256, _random_slots(rng, 100))
    want = _scatter_rows(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(rows))
    idx2 = idx.copy()
    idx2[count:] = rng.integers(0, N_SLOTS, 256 - count)  # never read
    got = pallas_slab_writeback(
        jnp.asarray(table), jnp.asarray(idx2), jnp.asarray(rows), jnp.int32(count),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the whole update, and the gate --


def _batch(rng, b, n_keys, pad):
    key = rng.integers(0, n_keys, b).astype(np.uint64)
    fp = key * np.uint64(0x9E3779B185EBCA87) + np.uint64(1)
    hits = rng.integers(1, 4, b).astype(np.uint32)
    hits[b - pad :] = 0
    return SlabBatch(
        fp_lo=jnp.asarray((fp & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        fp_hi=jnp.asarray((fp >> np.uint64(32)).astype(np.uint32)),
        hits=jnp.asarray(hits),
        limit=jnp.asarray(np.full(b, 100, np.uint32)),
        divider=jnp.asarray(rng.choice([1, 60], b).astype(np.int32)),
        jitter=jnp.asarray(rng.integers(0, 30, b).astype(np.int32)),
    )


def test_update_matches_the_xla_twin():
    """_slab_update_sorted with use_pallas (way scan, apply kernel and the
    write-back, interpreted) against the XLA twin over a stream that fills
    the slab past its sets: evictions, contention drops, duplicates and
    padding. Tables and health agree after every launch."""
    rng = np.random.default_rng(6)
    state_x = make_slab(N_SLOTS)
    state_p = make_slab(N_SLOTS)
    now = 1_000_000
    for step in range(4):
        batch = _batch(rng, 1536, n_keys=4000, pad=int(rng.integers(0, 400)))
        now += int(rng.integers(0, 2))
        state_x, _, ax, _, _, hx, _ = _slab_update_sorted(
            state_x, batch, jnp.int32(now), ways=WAYS
        )
        state_p, _, ap, _, _, hp, _ = _slab_update_sorted(
            state_p, batch, jnp.int32(now), ways=WAYS, use_pallas=True, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(ap), np.asarray(ax))
        np.testing.assert_array_equal(np.asarray(hp), np.asarray(hx))
        np.testing.assert_array_equal(
            np.asarray(state_p.table), np.asarray(state_x.table), f"step {step}"
        )
    assert int(hx[2]) + int(hx[3]) > 0  # the stream did evict or drop


def _update_jaxpr(ways, use_pallas):
    rng = np.random.default_rng(0)
    batch = _batch(rng, 256, n_keys=100, pad=10)
    return str(jax.make_jaxpr(
        lambda s, b: _slab_update_sorted(
            s, b, jnp.int32(0), ways=ways, use_pallas=use_pallas, interpret=True
        )
    )(make_slab(N_SLOTS), batch))


@pytest.mark.parametrize("ways,use_pallas,kernel", [
    (128, True, True),
    (64, True, False),
    (4, True, False),
    (128, False, False),
])
def test_only_the_pallas_128_way_arm_runs_the_kernel(ways, use_pallas, kernel):
    jaxpr = _update_jaxpr(ways, use_pallas)
    assert ("slab_writeback" in jaxpr) == kernel
    # the row scatter is the update's only scatter: exactly one of the two
    assert jaxpr.count("scatter[") == (0 if kernel else 1)


def test_smaller_ways_keep_the_scatter_bit_identical():
    """ways 4 on the Pallas arm writes back through the XLA scatter, and
    the tables still agree with the XLA twin."""
    rng = np.random.default_rng(8)
    batch = _batch(rng, 512, n_keys=300, pad=50)
    sx, *_ = _slab_update_sorted(make_slab(N_SLOTS), batch, jnp.int32(7), ways=4)
    sp, *_ = _slab_update_sorted(
        make_slab(N_SLOTS), batch, jnp.int32(7), ways=4, use_pallas=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(sp.table), np.asarray(sx.table))


# -- the owner's step, compiled for a described v5e chip --

OWNER_SLOTS = 1 << 24  # incrby_owner's TPU_SLAB_SLOTS
OWNER_LANES = 65536  # its TPU_BUCKETS
# temp_size_in_bytes of the same program with the XLA row scatter
SCATTER_TEMP_BYTES = 540_762_112


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(")
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def _big_shapes(text: str, elems: int) -> list:
    return [s for s in _SHAPE.findall(text)
            if np.prod([int(d) for d in s.split(",")]) >= elems]


def test_owner_step_writes_back_with_the_kernel_for_v5e(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from api_ratelimit_tpu.ops.sketch import SKETCH_PLANES, sketch_ways
    from api_ratelimit_tpu.ops.slab import SlabState, slab_step_after

    one = SingleDeviceSharding(topo.devices[0])

    def u32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = slab_step_after.lower(
            SlabState(table=u32((OWNER_SLOTS, ROW_WIDTH))), u32((7, OWNER_LANES)),
            ways=WAYS, out_dtype=jnp.uint8, use_pallas=True, multi_algo=False,
            sketch=u32((SKETCH_PLANES, 128)), sketch_ways=sketch_ways(WAYS, 128),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    table_elems = OWNER_SLOTS * ROW_WIDTH
    opcodes = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            opcodes.setdefault(m.group(2), []).append((m.group(1), line))
    kernels = [line for _t, line in opcodes.get("custom-call", [])
               if re.match(r"\s*(?:ROOT )?%slab_writeback", line)]
    assert len(kernels) == 1, "no slab_writeback custom call"
    assert _big_shapes(kernels[0].split(" custom-call(")[0], table_elems)
    # no scatter writes the table, and nothing copies or transposes it
    scatters = [t for t, _l in opcodes.get("scatter", []) + opcodes.get("fusion", [])
                if "16777216,8]" in t]
    assert not scatters, scatters
    for op in ("copy", "copy-start", "transpose"):
        for _t, line in opcodes.get(op, []):
            assert not _big_shapes(line, table_elems), line
    assert compiled.memory_analysis().temp_size_in_bytes <= SCATTER_TEMP_BYTES
