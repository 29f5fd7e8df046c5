"""On-hardware Pallas certification (VERDICT r3 #4): the same differential
suite that pins the kernel against the XLA path in interpret mode
(test_pallas_slab.py) re-runs with the kernel COMPILED through Mosaic on a
real TPU, so a lowering bug can never hide behind the interpreter.

Run on a chip-attached host:

    TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py -v

(TPU_TESTS=1 makes conftest.py leave the real platform visible instead of
forcing the virtual CPU mesh; run only this module under that env — see
conftest.py. `make tests_tpu` wraps this.)

Skips cleanly when no TPU is attached, so it is safe in every suite run.
"""

from __future__ import annotations

import os

import pytest

pytestmark = pytest.mark.tpu

if os.environ.get("TPU_TESTS", "") != "1":
    pytest.skip(
        "on-chip suite: set TPU_TESTS=1 on a chip-attached host",
        allow_module_level=True,
    )

import jax  # noqa: E402

if jax.devices()[0].platform != "tpu":
    pytest.skip(
        f"TPU_TESTS=1 but jax sees {jax.devices()[0].platform!r}, not tpu",
        allow_module_level=True,
    )

# tests/ has no __init__.py: pytest's prepend import mode puts this dir on
# sys.path, so the sibling module imports by its bare name
from test_pallas_slab import (  # noqa: E402
    run_fused_decide_matches_xla_decide,
    run_in_batch_slot_collision_parity,
    run_lean_decide_matches_full,
    run_update_matches_xla_over_stream,
)
from test_hotkeys_fuzz import (  # noqa: E402
    ONE_SET_CASES,
    STREAMS,
    run_one_set_case,
    run_one_set_stream,
)


def test_update_matches_xla_on_chip():
    run_update_matches_xla_over_stream(interpret=False)


def test_fused_decide_matches_xla_on_chip():
    run_fused_decide_matches_xla_decide(interpret=False)


def test_lean_decide_on_chip():
    run_lean_decide_matches_full(interpret=False)


def test_in_batch_slot_collision_on_chip():
    run_in_batch_slot_collision_parity(interpret=False)


def test_floor_div_exact_on_chip():
    """The exact floor division under every device path (window starts,
    throttle pacing — ops/decide.py) contains no divide at all: it is a
    Newton-reciprocal built from mul/sub/bitcast, and its exactness
    depends on the chip's f32 multiply/rounding staying within the +-1
    band the integer fixup corrects. CPU tests pin the formula; this pins
    the hardware semantics (both XLA and Pallas paths share the helper,
    so on-chip parity tests alone cannot catch a TPU-specific f32
    multiply deviation)."""
    import numpy as np
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.decide import floor_div_exact_i32

    rng = np.random.RandomState(3)
    a = rng.randint(0, 2**31, size=1 << 16).astype(np.int32)
    b = rng.randint(1, 2**31, size=1 << 16).astype(np.int32)
    b[::2] = rng.choice([1, 60, 3600, 86400], size=(1 << 15)).astype(np.int32)
    # adversarial: quotients near exact multiples, max dividend
    a[:4] = [2**31 - 1, 2**31 - 1, 86400 * 19676 - 1, 86400 * 19676]
    b[:4] = [1, 3, 86400, 86400]
    got = np.asarray(jax.jit(floor_div_exact_i32)(jnp.asarray(a), jnp.asarray(b)))
    want = (a.astype(np.int64) // b.astype(np.int64)).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_packbits_muladd_on_chip():
    """Hardware parity pin for the multiply-add packbits twin
    (ops/decide.py packbits_muladd — the candidate swap if attribution shows
    packbits' shift/or lowering is pathological, like division was). The
    formula is pinned on CPU in tests/test_slab.py; this pins the chip's
    u32 multiply-add reduce lowering."""
    import numpy as np
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.decide import packbits_muladd

    rng = np.random.RandomState(17)
    for size in (128, 1 << 16, 1 << 20):
        mask = rng.rand(size) < 0.41
        got = np.asarray(jax.jit(packbits_muladd)(jnp.asarray(mask)))
        np.testing.assert_array_equal(got, np.packbits(mask))


@pytest.mark.parametrize("case", ONE_SET_CASES)
def test_sketch_match_edge_case_on_chip(case):
    """The one-set sketch kernel compiled through Mosaic: phase A against
    the gather-and-scan reference, the whole update against the XLA twin
    and the oracle (tests/test_hotkeys_fuzz.py's interpret-mode cases)."""
    run_one_set_case(case, interpret=False)


@pytest.mark.parametrize("width", [128, 384, 1024, 1536, 65536])
def test_sketch_match_streams_on_chip(width):
    """The zipf, uniform and churn streams through the compiled one-set
    kernel at the edge's launch width (128), the owner's (65,536), and
    widths whose rows of 128 the kernel pads to whole tiles."""
    for stream in sorted(STREAMS):
        for warm in (False, True):
            run_one_set_stream(
                stream, warm, width, launches=12 if width < 65536 else 4,
                interpret=False,
            )
