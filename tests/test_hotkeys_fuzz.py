"""Differential fuzz: the in-kernel heavy-hitter sketch vs the exact host
model (testing/oracle.py SketchOracle), bit-for-bit.

The sketch is deterministic by construction — content-based (weight,
fp_hi, fp_lo) insert rank, pre-launch argmin victim, drain-halving decay —
so the campaign holds the device planes to the numpy oracle EXACTLY after
every launch and every drain, across three arms: the XLA twin with the
sibling-algorithm step compiled in (the production shape), the XLA
fixed-window-only step (multi_algo=False gate), and the Pallas scan in
interpret mode. Streams cover the regimes that stress different parts of
the update: Zipf (a stable hot head accumulating via phase A), uniform
(insert churn spread across sets), and adversarial churn (a rotating cold
wave that maximizes inherit-displacement — the space-saving worst case).

On top of bit-exactness, the oracle's per-lane error ledger (inherited /
acc) is asserted against the true offered stream: count == inherited +
acc between decays, and a resident key's accumulated weight never exceeds
what the stream actually offered it — the two directions of the
space-saving bound.

Campaign sizing follows the SLAB_FUZZ_EXAMPLES contract
(tests/test_slab_fuzz.py): HOTKEY_FUZZ_EXAMPLES scales the same
properties deeper on idle hardware; the tier-1 default stays small.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from api_ratelimit_tpu.ops.sketch import (
    PLANE_COUNT,
    PLANE_FP_HI,
    PLANE_FP_LO,
    _sketch_scan,
    make_sketch,
    pallas_sketch_match,
    sketch_decay,
    sketch_topk,
    sketch_update,
    sketch_ways,
)
from api_ratelimit_tpu.ops.slab import (
    ROW_DIVIDER,
    ROW_FP_HI,
    ROW_FP_LO,
    ROW_HITS,
    ROW_JITTER,
    ROW_LIMIT,
    ROW_SCALARS,
    make_slab,
    slab_step_packed,
    validate_ways,
)
from api_ratelimit_tpu.testing.oracle import SketchOracle

pytestmark = pytest.mark.hotkeys

FUZZ_EXAMPLES = int(os.environ.get("HOTKEY_FUZZ_EXAMPLES", "0") or 0)

# one slab/sketch geometry per campaign keeps it to one compile per arm;
# 8-way slab sets, 32 sketch lanes in 4 sets of 8 — small enough that
# eviction pressure and insert contention are both routine
N_SLOTS, WAYS, PAD_TO, LANES = 512, 8, 128, 32


def _fmix32(x: int) -> int:
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & 0xFFFFFFFF
    return x ^ (x >> 16)


def _fp(key_id: int) -> tuple[int, int]:
    """(fp_lo, fp_hi) per fuzz key — same construction as
    tests/test_slab_fuzz.py: mixed fp_lo (set spread), unique id in
    fp_hi's top 16 bits (distinct keys never share a fingerprint)."""
    return (
        _fmix32(key_id),
        (((key_id + 1) & 0xFFFF) << 16) | (_fmix32(key_id ^ 0xA5A5) & 0xFFFF),
    )


def _pack(items, now: int, pad_to: int) -> np.ndarray:
    packed = np.zeros((7, pad_to), dtype=np.uint32)
    for i, (fp_lo, fp_hi, hits, limit, div, jit) in enumerate(items):
        packed[ROW_FP_LO, i] = fp_lo
        packed[ROW_FP_HI, i] = fp_hi
        packed[ROW_HITS, i] = hits
        packed[ROW_LIMIT, i] = limit
        packed[ROW_DIVIDER, i] = div
        packed[ROW_JITTER, i] = jit
    packed[ROW_SCALARS, 0] = np.uint32(now)
    packed[ROW_SCALARS, 1] = np.float32(0.8).view(np.uint32)
    return packed


class _SketchHarness:
    """Drives slab_step_packed with a live sketch and the SketchOracle in
    lockstep; after every launch and every drain the device planes must
    equal the oracle planes bit-for-bit."""

    def __init__(self, multi_algo: bool = True):
        self.state = make_slab(N_SLOTS)
        self.ways = validate_ways(N_SLOTS, WAYS)
        self.skw = sketch_ways(self.ways, LANES)
        self.sketch = make_sketch(LANES)
        self.oracle = SketchOracle(LANES, self.skw)
        self.multi_algo = multi_algo
        self.offered: dict[tuple[int, int], int] = {}

    def step(self, items, now: int, label=""):
        assert len(items) <= PAD_TO
        packed = _pack(items, now, PAD_TO)
        self.state, _out, _health, self.sketch = slab_step_packed(
            self.state,
            jnp.asarray(packed),
            ways=self.ways,
            multi_algo=self.multi_algo,
            sketch=self.sketch,
            sketch_ways=self.skw,
        )
        # host candidates: one per distinct fingerprint, weighted by the
        # batch's total raw hits for that key — the segment totals the
        # kernel's cumsum produces (every fuzz item carries hits >= 1, so
        # every distinct key's segment end survives the hits>0 gate)
        cands: dict[tuple[int, int], int] = {}
        for fp_lo, fp_hi, hits, _l, _d, _j in items:
            assert hits >= 1
            cands[(fp_lo, fp_hi)] = cands.get((fp_lo, fp_hi), 0) + hits
            self.offered[(fp_lo, fp_hi)] = (
                self.offered.get((fp_lo, fp_hi), 0) + hits
            )
        self.oracle.update([(lo, hi, w) for (lo, hi), w in cands.items()])
        np.testing.assert_array_equal(
            np.asarray(self.sketch), self.oracle.planes, err_msg=str(label)
        )

    def drain(self, k: int = 8, label=""):
        """The engine's stats-cadence drain: pull, report, halve,
        re-upload — topk and the post-decay planes both pinned."""
        dev = np.asarray(self.sketch).copy()
        assert sketch_topk(dev, k) == self.oracle.topk(k), label
        sketch_decay(dev)
        self.oracle.decay()
        np.testing.assert_array_equal(
            dev, self.oracle.planes, err_msg=str(label)
        )
        self.sketch = jnp.asarray(dev)

    def assert_error_bounds(self, label=""):
        """The space-saving statement, per occupied lane: the estimate is
        exactly inherited + accumulated, and a resident key never
        accumulated more weight than the stream offered it (decay only
        shrinks the ledger, so the inequality survives drains)."""
        o = self.oracle
        occ = np.flatnonzero(o.count.view(np.int32) > 0)
        assert (
            o.count[occ].astype(np.uint64)
            == o.inherited[occ] + o.acc[occ]
        ).all(), label
        for lane in occ:
            fp = (int(o.fp_lo[lane]), int(o.fp_hi[lane]))
            offered = self.offered.get(fp)
            assert offered is not None, (label, fp)
            assert int(o.acc[lane]) <= offered, (label, fp)


def _run_stream(draw_key, rng, examples: int, seed_base: int, drain_every=3):
    for ex in range(examples):
        seed = seed_base + ex
        r = np.random.default_rng(seed)
        h = _SketchHarness()
        now = 1_000
        for step in range(8):
            n = int(r.integers(8, PAD_TO + 1))
            items = []
            for _ in range(n):
                key = draw_key(r, step)
                lo, hi = _fp(key)
                items.append(
                    (lo, hi, int(r.integers(1, 6)), 1_000, 1, 0)
                )
            h.step(items, now, label=(seed, step))
            now += int(r.integers(0, 3))
            if (step + 1) % drain_every == 0:
                h.drain(label=(seed, step))
        h.assert_error_bounds(label=seed)


def _zipf_key(r, _step):
    return min(int(r.zipf(1.5)), 200)


def _uniform_key(r, _step):
    return int(r.integers(1, 300))


def _churn_key(r, step):
    # a rotating cold wave: every step brings a fresh key-id band, so
    # nearly every candidate is an unmatched insert displacing a
    # resident — maximum inherit pressure — with a thin persistent
    # head mixed in so phase A and phase B interleave in one launch
    if r.random() < 0.2:
        return int(r.integers(1, 4))  # the persistent head
    return 1_000 + step * 64 + int(r.integers(0, 64))


STREAMS = {"zipf": _zipf_key, "uniform": _uniform_key, "churn": _churn_key}


class TestFuzzStreams:
    def test_zipf_stream(self):
        examples = FUZZ_EXAMPLES or 2
        _run_stream(_zipf_key, None, examples, 0xA15)

    def test_uniform_stream(self):
        examples = FUZZ_EXAMPLES or 2
        _run_stream(_uniform_key, None, examples, 0xB27)

    def test_adversarial_churn_stream(self):
        examples = FUZZ_EXAMPLES or 2
        _run_stream(_churn_key, None, examples, 0xC39)



def _pallas_flag_update_parity(lanes, seed):
    """sketch_update with and without use_pallas (interpret mode) on the
    ways == 128 geometry, state threaded across four launches."""
    examples = FUZZ_EXAMPLES or 2
    for ex in range(examples):
        r = np.random.default_rng(seed + ex)
        skw = 128
        sk_x = make_sketch(lanes)
        sk_p = make_sketch(lanes)
        for step in range(4):
            b = 256
            keys = np.sort(r.integers(1, 64, b))
            lo = np.array([_fp(int(k))[0] for k in keys], dtype=np.uint32)
            hi = np.array([_fp(int(k))[1] for k in keys], dtype=np.uint32)
            # segment ends over the sorted keys; weight = cumulative
            # hits within the segment, exactly the kernel's shape
            hits = r.integers(1, 5, b).astype(np.uint32)
            seg_last = np.r_[keys[1:] != keys[:-1], True]
            incl = np.cumsum(hits, dtype=np.uint32)
            excl = incl - hits
            seg_start = np.r_[True, keys[1:] != keys[:-1]]
            base = np.maximum.accumulate(np.where(seg_start, excl, 0))
            weight = (incl - base).astype(np.uint32)
            args = (
                jnp.asarray(lo),
                jnp.asarray(hi),
                jnp.asarray(weight),
                jnp.asarray(seg_last),
            )
            sk_x = sketch_update(sk_x, *args, ways=skw)
            sk_p = sketch_update(
                sk_p, *args, ways=skw, use_pallas=True, interpret=True
            )
            np.testing.assert_array_equal(
                np.asarray(sk_x), np.asarray(sk_p), err_msg=f"{ex}:{step}"
            )

class TestArms:
    def test_multi_algo_off_arm_matches(self):
        """The fixed-window-only step (multi_algo=False) must produce the
        identical sketch: the gate changes decision arms, never the
        segment weights the sketch consumes."""
        r = np.random.default_rng(7)
        arms = [_SketchHarness(multi_algo=True), _SketchHarness(multi_algo=False)]
        now = 1_000
        for step in range(4):
            items = [
                (*_fp(min(int(r.zipf(1.5)), 99)), int(r.integers(1, 6)), 500, 1, 0)
                for _ in range(48)
            ]
            for h in arms:
                h.step(items, now, label=("arm", step))
            now += 1
        np.testing.assert_array_equal(
            np.asarray(arms[0].sketch), np.asarray(arms[1].sketch)
        )

    def test_pallas_update_parity(self):
        """Whole-update parity: the Pallas arm of sketch_update (one set:
        the sketch_match kernel) == the XLA arm, state threaded across
        several launches."""
        _pallas_flag_update_parity(lanes=128, seed=0xE00)

    def test_several_sets_pallas_update_parity(self):
        """Two 128-way sets (HOTKEY_LANES=256): with the Pallas flag set,
        sketch_update gathers and scans each candidate's set in XLA, as
        the XLA arm does, launch after launch."""
        _pallas_flag_update_parity(lanes=256, seed=0xE80)

    def test_gate_off_shape(self):
        """sketch=None keeps the pre-hotkeys 3-tuple return — the arity
        half of the byte-identity gate (the wire/program half is pinned in
        tests/test_hotkeys.py)."""
        state = make_slab(N_SLOTS)
        packed = _pack([( *_fp(1), 1, 10, 1, 0)], 1_000, PAD_TO)
        out = slab_step_packed(state, jnp.asarray(packed), ways=WAYS)
        assert len(out) == 3


# --- the one-set sketch: the default TPU geometry (HOTKEY_LANES=128,
# SLAB_WAYS=128) is one 128-way set, whose phase A runs as the Mosaic
# kernel pallas_sketch_match (interpret mode here; compiled on the chip by
# tests/test_pallas_tpu.py through run_one_set_case/run_one_set_stream),
# held to the XLA gather-and-scan arm, to that arm's scan plus
# scatter-add, and to the oracle.

ONE_SET = 128
PAD_KEY = 1 << 20  # no stream draws it
_update_xla = jax.jit(functools.partial(sketch_update, ways=ONE_SET))


@functools.cache
def _update_kernel(interpret: bool):
    return jax.jit(
        functools.partial(
            sketch_update, ways=ONE_SET, use_pallas=True, interpret=interpret
        )
    )


def _segments(keys, hits, fp=_fp):
    """A launch as the slab step hands it to the sketch: items sorted by
    key, one candidate per key at its segment's last row (if that row has
    hits), weighted by the segment's total hits. Returns the four device
    vectors and the oracle's candidate list."""
    keys = np.asarray(keys, dtype=np.int64)
    hits = np.asarray(hits, dtype=np.uint32)
    order = np.argsort(keys, kind="stable")
    keys, hits = keys[order], hits[order]
    fps = [fp(int(k)) for k in keys]
    lo = np.array([f[0] for f in fps], dtype=np.uint32)
    hi = np.array([f[1] for f in fps], dtype=np.uint32)
    same = np.r_[False, keys[1:] == keys[:-1]]
    incl = np.cumsum(hits, dtype=np.uint32)
    base = np.maximum.accumulate(np.where(~same, incl - hits, 0)).astype(np.uint32)
    weight = incl - base
    cand = np.r_[~same[1:], True] & (hits > 0)
    cands = [(int(a), int(b), int(w)) for a, b, w in zip(lo[cand], hi[cand], weight[cand])]
    return [jnp.asarray(x) for x in (lo, hi, weight, cand)], cands


def _resident(keys, counts, fp=_fp, stale=False) -> tuple[np.ndarray, SketchOracle]:
    """A sketch holding `keys` in its first ways with `counts` (0 leaves
    a way empty, and `stale` leaves the key's fingerprint in it), and the
    oracle in the same state."""
    planes = np.zeros((3, ONE_SET), dtype=np.uint32)
    for way, (k, c) in enumerate(zip(keys, counts)):
        if c or stale:
            planes[PLANE_FP_LO, way], planes[PLANE_FP_HI, way] = fp(int(k))
            planes[PLANE_COUNT, way] = c
    oracle = SketchOracle(ONE_SET, ONE_SET)
    oracle.fp_lo[:], oracle.fp_hi[:], oracle.count[:] = planes
    oracle.inherited[:] = planes[PLANE_COUNT]
    return planes, oracle


def _phase_a_reference(sketch, lo, hi, cand_weight):
    """The gather path's phase A on one set: every item's copy of the set,
    _sketch_scan's first matching way, then the scatter-add."""
    b = lo.shape[0]
    rows = [jnp.broadcast_to(sketch[p], (b, ONE_SET)) for p in range(3)]
    m_way, m_any, _, _ = _sketch_scan(*rows, lo, hi)
    lane = jnp.where(m_any, m_way, ONE_SET)
    cnt = sketch[PLANE_COUNT].at[lane].add(
        jnp.where(m_any, cand_weight, jnp.uint32(0)), mode="drop"
    )
    return m_any, cnt


def _check_one_launch(sketch, args, cands, oracle, label, interpret=True):
    """Kernel phase A == the reference's; both arms of the whole update
    == the oracle. Returns the updated sketch."""
    lo, hi, weight, cand = args
    cw = jnp.where(cand, weight, jnp.uint32(0))
    m_ref, cnt_ref = _phase_a_reference(sketch, lo, hi, cw)
    m_got, add = pallas_sketch_match(sketch, lo, hi, cw, interpret=interpret)
    np.testing.assert_array_equal(np.asarray(m_got), np.asarray(m_ref), err_msg=label)
    np.testing.assert_array_equal(
        np.asarray(sketch[PLANE_COUNT] + add), np.asarray(cnt_ref), err_msg=label
    )
    new_x = _update_xla(sketch, *args)
    new_p = _update_kernel(interpret)(sketch, *args)
    np.testing.assert_array_equal(np.asarray(new_p), np.asarray(new_x), err_msg=label)
    oracle.update(cands)
    np.testing.assert_array_equal(np.asarray(new_p), oracle.planes, err_msg=label)
    return new_p


def _lo_collides(k: int) -> tuple[int, int]:
    """Keys k and k + 64 share fp_lo; fp_hi tells them apart."""
    return _fp(k % 64)[0], _fp(k)[1]


def _zero_key(k: int) -> tuple[int, int]:
    """Key 7's fingerprint is (0, 0), an empty way's."""
    return (0, 0) if k == 7 else _fp(k)


def _one_set_case(name, r) -> dict:
    """One launch's edge case: the resident keys and counts, the batch's
    keys and hits, and how keys map to fingerprints."""
    full = np.arange(1, ONE_SET + 1)
    counts = r.integers(1, 50, ONE_SET)
    mixed = r.integers(1, 300, 256)
    hits = r.integers(1, 5, 256)
    case = dict(res=full, counts=counts, keys=mixed, hits=hits)
    if name == "empty_sketch":
        case.update(counts=counts * 0)
    elif name == "every_candidate_matched":
        case.update(keys=np.r_[full, r.choice(full, 128)])
    elif name == "no_candidate":
        case.update(hits=hits * 0)
    elif name == "full_sketch_no_match":
        case.update(keys=r.integers(1_000, 2_000, 256))
    elif name == "batch_of_128":
        case.update(keys=r.integers(1, 400, 128), hits=r.integers(0, 5, 128))
    elif name == "batch_of_65536":
        keys = np.minimum(r.zipf(1.2, 65536), 5_000)
        case.update(keys=keys, hits=r.integers(0, 5, 65536))
    elif name == "odd_batch":
        case.update(keys=r.integers(1, 400, 200), hits=r.integers(0, 5, 200))
    elif name == "weights_near_2_31":
        # a resident key and a newcomer, each weighing just under 2^31 (the
        # launch's total hits stay below 2^32), among items with no hits
        keys = np.r_[5, 900, r.integers(1, 400, 126)]
        case.update(keys=keys, hits=np.r_[(1 << 31) - 1, (1 << 31) - 3, np.zeros(126, int)])
    elif name == "fp_lo_collision":
        # residents 0..63; the batch's 64..127 match their fp_lo alone
        case.update(res=np.arange(64), counts=counts[:64], keys=np.r_[np.arange(64, 128),
                    r.integers(0, 128, 192)], fp=_lo_collides)
    elif name == "unoccupied_ways":
        # half the ways empty, some holding a stale fingerprint, one a count
        # past 2^31 (unoccupied in the int32 view), and a key fingerprinted
        # (0, 0): none of these ways matches
        counts = np.where(np.arange(ONE_SET) % 2, counts, 0)
        counts[1] = (1 << 31) + 5
        case.update(counts=counts, keys=np.r_[7, np.arange(1, 128)], hits=hits[:128],
                    fp=_zero_key, stale=True)
    else:
        raise AssertionError(name)
    return case


ONE_SET_CASES = [
    "empty_sketch",
    "every_candidate_matched",
    "no_candidate",
    "full_sketch_no_match",
    "batch_of_128",
    "batch_of_65536",
    "odd_batch",
    "weights_near_2_31",
    "fp_lo_collision",
    "unoccupied_ways",
]


def run_one_set_case(case, interpret=True):
    """One launch of a named edge case, checked as _check_one_launch does."""
    r = np.random.default_rng(0xF00 + ONE_SET_CASES.index(case))
    c = _one_set_case(case, r)
    fp = c.get("fp", _fp)
    planes, oracle = _resident(c["res"], c["counts"], fp, c.get("stale", False))
    args, cands = _segments(c["keys"], c["hits"], fp)
    _check_one_launch(jnp.asarray(planes), args, cands, oracle, case, interpret)


def run_one_set_stream(stream, warm, width=256, launches=12, interpret=True):
    """A fuzz stream through the one-set update in launches of `width`
    items, launch after launch and drain after drain; `warm` starts from a
    sketch holding 128 of the stream's keys, so every launch evicts."""
    draw = STREAMS[stream]
    for ex in range(FUZZ_EXAMPLES or 1):
        r = np.random.default_rng(0xF40 + 2 * ex + warm)
        pool = np.unique([draw(r, 0) for _ in range(1_000)])[:ONE_SET]
        counts = r.integers(1, 40, len(pool)) if warm else np.zeros(len(pool), int)
        planes, oracle = _resident(pool, counts)
        sketch = jnp.asarray(planes)
        for step in range(launches):
            # n items, padded to one launch width: padding has no hits
            n = int(r.integers(8, width + 1))
            keys = [draw(r, step) for _ in range(n)] + [PAD_KEY] * (width - n)
            hits = np.r_[r.integers(1, 6, n), np.zeros(width - n, int)]
            args, cands = _segments(keys, hits)
            label = (stream, warm, width, ex, step)
            sketch = _check_one_launch(sketch, args, cands, oracle, label, interpret)
            if step % 4 == 3:
                dev = np.asarray(sketch).copy()
                assert sketch_topk(dev, 16) == oracle.topk(16), label
                sketch_decay(dev)
                oracle.decay()
                np.testing.assert_array_equal(dev, oracle.planes, err_msg=str(label))
                sketch = jnp.asarray(dev)


class TestOneSetKernel:
    @pytest.mark.parametrize("case", ONE_SET_CASES)
    def test_edge_case(self, case):
        run_one_set_case(case)

    @pytest.mark.parametrize("stream", sorted(STREAMS))
    @pytest.mark.parametrize("warm", [False, True], ids=["empty", "full"])
    def test_stream(self, stream, warm):
        """The fuzz streams through the one-set update (run_one_set_stream)."""
        run_one_set_stream(stream, warm)


class TestDrainHelpers:
    def test_topk_rank_is_total_order(self):
        planes = np.zeros((3, 8), dtype=np.uint32)
        planes[PLANE_FP_LO] = [1, 2, 3, 4, 0, 0, 0, 0]
        planes[PLANE_FP_HI] = [9, 9, 8, 7, 0, 0, 0, 0]
        planes[PLANE_COUNT] = [5, 5, 5, 9, 0, 0, 0, 0]
        got = sketch_topk(planes, 3)
        assert got == [(4, 7, 9), (2, 9, 5), (1, 9, 5)]

    def test_decay_clears_dead_fps(self):
        planes = np.zeros((3, 4), dtype=np.uint32)
        planes[PLANE_FP_LO] = [11, 22, 0, 33]
        planes[PLANE_FP_HI] = [1, 2, 0, 3]
        planes[PLANE_COUNT] = [1, 4, 0, 3]
        sketch_decay(planes)
        assert planes[PLANE_COUNT].tolist() == [0, 2, 0, 1]
        assert planes[PLANE_FP_LO].tolist() == [0, 22, 0, 33]
        assert planes[PLANE_FP_HI].tolist() == [0, 2, 0, 3]
