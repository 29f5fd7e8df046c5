"""AOT compiles for a described TPU v5e (no chip attached): the programs
chip_smoke.py runs, at their real sizes, through the chip's own compiler.

Interpret-mode tests cannot see what Mosaic refuses (unaligned slices,
VMEM overuse) nor tracing rules that only bite on the TPU lowering (the
compact mesh arm's Pallas kernels under shard_map needed their outputs'
`vma`). Each case asserts the kernel is really in the program
(`tpu_custom_call`) where Pallas is expected, and that the compiler's
memory analysis fits one chip's 16 GiB.

The topology is described only inside the module fixture — never at
import, in a skipif or in a parametrize: only one process may load the
TPU library, and collection must be identical on every xdist worker.
"""

import functools
import os

import numpy as np
import pytest

SLOTS = 1 << 22  # the deployment slab: 128 MiB, 32,768 sets x 128 ways
WAYS = 128
LANES = 128  # HOTKEY_LANES default
HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _u32(shape, sharding):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _check(compiled, pallas: bool) -> None:
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == pallas
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert 0 < total < HBM_BYTES, total


@pytest.mark.parametrize("bucket", [128, 1024, 8192])
def test_step_after_pallas_with_sketch(one_chip, bucket):
    """The served launch: after-mode step, Mosaic kernels, hot-key sketch."""
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.sketch import SKETCH_PLANES, sketch_ways
    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, SlabState, slab_step_after

    compiled = slab_step_after.lower(
        SlabState(table=_u32((SLOTS, ROW_WIDTH), one_chip)),
        _u32((7, bucket), one_chip),
        ways=WAYS,
        out_dtype=jnp.uint8,
        use_pallas=True,
        multi_algo=False,
        sketch=_u32((SKETCH_PLANES, LANES), one_chip),
        sketch_ways=sketch_ways(WAYS, LANES),
    ).compile()
    _check(compiled, pallas=True)


def test_step_decided_pallas(one_chip):
    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, SlabState, slab_step_decided

    compiled = slab_step_decided.lower(
        SlabState(table=_u32((SLOTS, ROW_WIDTH), one_chip)),
        _u32((7, 8192), one_chip),
        ways=WAYS,
        use_pallas=True,
        multi_algo=False,
    ).compile()
    _check(compiled, pallas=True)


def test_multi_algo_xla_twin(one_chip):
    """The sticky non-fixed-algorithm route: the XLA twin, no kernel."""
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, SlabState, slab_step_after

    compiled = slab_step_after.lower(
        SlabState(table=_u32((SLOTS, ROW_WIDTH), one_chip)),
        _u32((7, 8192), one_chip),
        ways=WAYS,
        out_dtype=jnp.uint16,
        use_pallas=False,
        multi_algo=True,
    ).compile()
    _check(compiled, pallas=False)


def test_compact_mesh_step_on_four_chips(topo):
    """SHARD_ROUTED_BATCHING=false: one shard_map program over the 2x2
    mesh, 2^22 global slots, Pallas inside every shard."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH
    from api_ratelimit_tpu.parallel.sharded_slab import (
        sharded_slab_step_after_compact,
    )

    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    step = sharded_slab_step_after_compact(mesh, 0xFF, ways=WAYS, use_pallas=True)
    compiled = step.lower(
        _u32((SLOTS, ROW_WIDTH), NamedSharding(mesh, P("shard", None))),
        _u32((4, 7, 8192), NamedSharding(mesh, P("shard", None, None))),
    ).compile()
    _check(compiled, pallas=True)


def test_routed_shard_step(topo):
    """SHARD_ROUTED_BATCHING=true (the default): the per-device program
    one shard of the 2^22-slot mesh runs, placed on the last chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH
    from api_ratelimit_tpu.parallel.sharded_slab import _routed_body

    last = SingleDeviceSharding(topo.devices[3])
    step = jax.jit(
        functools.partial(_routed_body, ways=WAYS, cap=0xFF, use_pallas=True),
        donate_argnums=(0,),
    )
    compiled = step.lower(
        _u32((SLOTS // 4, ROW_WIDTH), last), _u32((7, 8192), last)
    ).compile()
    _check(compiled, pallas=True)


@pytest.mark.parametrize("bucket", [16384, 32768])
def test_routed_shard_step_is_named(topo, bucket):
    """The program the routed engine launches per shard (slab_step_routed)
    at incrby_mesh4's shard size, 2^24 slots, and the rungs its 65,536-row
    launches pad to: its module is jit_slab_step_routed, the name the
    benchmark's trace readers find it by."""
    from jax.sharding import SingleDeviceSharding

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH
    from api_ratelimit_tpu.parallel.sharded_slab import slab_step_routed

    last = SingleDeviceSharding(topo.devices[3])
    compiled = slab_step_routed.lower(
        _u32(((1 << 26) // 4, ROW_WIDTH), last), _u32((7, bucket), last),
        ways=WAYS, cap=0xFF, use_pallas=True,
    ).compile()
    _check(compiled, pallas=True)
    assert compiled.as_text().startswith("HloModule jit_slab_step_routed")


def test_health_fold(one_chip):
    """The health drain's one on-device program: the first n of
    HEALTH_FOLD parked vectors summed, whatever the drain's length."""
    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.slab import HEALTH_FOLD, HEALTH_WIDTH, _fold_health

    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    vector = _u32((HEALTH_WIDTH,), one_chip)
    compiled = _fold_health.lower(n, *[vector] * HEALTH_FOLD).compile()
    _check(compiled, pallas=False)


def _owner_step(one_chip, slots, bucket, lanes):
    """The served after-mode step with a sketch of `lanes` lanes."""
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.sketch import SKETCH_PLANES, sketch_ways
    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, SlabState, slab_step_after

    compiled = slab_step_after.lower(
        SlabState(table=_u32((slots, ROW_WIDTH), one_chip)),
        _u32((7, bucket), one_chip),
        ways=WAYS,
        out_dtype=jnp.uint8,
        use_pallas=True,
        multi_algo=False,
        sketch=_u32((SKETCH_PLANES, lanes), one_chip),
        sketch_ways=sketch_ways(WAYS, lanes),
    ).compile()
    _check(compiled, pallas=True)
    return compiled.as_text()


def _defs(text):
    """HLO value name -> its output type, from the compiled text."""
    import re

    return dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+|\([^)]*\)) ", text, re.M))


def test_owner_sketch_is_one_kernel(one_chip):
    """incrby_owner's launch (2^24 slots, 65,536 lanes) on the default
    geometry: the one-set sketch runs as the sketch_match kernel, fed no
    per-item (65536, 128) copy of the set, and no scatter writes the
    u32[128] count plane."""
    import re

    text = _owner_step(one_chip, 1 << 24, 65536, LANES)
    calls = re.findall(r"^\s*(?:ROOT )?%sketch_match[\w.-]* = .*? custom-call\(([^)]*)\)", text, re.M)
    assert len(calls) == 1, calls
    defs = _defs(text)
    operands = re.findall(r"%[\w.-]+", calls[0])
    assert operands and all(o in defs for o in operands), (operands, calls)
    assert not [o for o in operands if "[65536,128]" in defs[o]], calls
    assert "pallas_sketch_scan" not in text
    assert not re.search(r"= u32\[128\]\S* scatter\(", text)


def test_several_sketch_sets_keep_the_set_scan(one_chip):
    """HOTKEY_LANES=256 gives two 128-way sets: each candidate's set is
    gathered and scanned in XLA and phase A scatter-adds into the u32[256]
    count plane; the one-set kernel is not in the program."""
    import re

    text = _owner_step(one_chip, SLOTS, 8192, 2 * LANES)
    assert re.search(r"= u32\[256\]\S* scatter\(", text)
    assert "sketch_match" not in text
    assert "pallas_sketch_scan" not in text



@pytest.mark.parametrize("items", [384, 1536])
def test_sketch_kernel_compiles_at_uneven_widths(one_chip, items):
    """A launch width whose rows of 128 items are neither a power of two
    nor a multiple of 8 (384: 3 rows; 1,536: 12) compiles the one-set
    sketch kernel: it pads its rows to whole (8, 128) tiles."""
    from api_ratelimit_tpu.ops.sketch import SKETCH_PLANES, pallas_sketch_match

    compiled = pallas_sketch_match.lower(
        _u32((SKETCH_PLANES, LANES), one_chip), *[_u32((items,), one_chip)] * 3
    ).compile()
    _check(compiled, pallas=True)
