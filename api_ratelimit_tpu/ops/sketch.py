"""In-kernel heavy-hitter sketch: device-side space-saving top-K beside the slab.

The slab answers "how fast are we deciding"; this answers "what are we
deciding about". A few extra uint32 lanes ride next to the row table and
are updated per launch with the SAME bounded W-wide scan shape the
eviction path already pays for (PAPERS "Limited Associativity Caching in
the Data Plane" — detect the hot head where the traffic flows). Each
stats cadence the engine drains the planes to the host, publishes the
top-K (`ratelimit.hotkeys.*`, GET /debug/hotkeys), and halves the counts
so the head tracks the CURRENT traffic mix instead of all history.

Layout — `uint32[SKETCH_PLANES, lanes]`, three parallel planes viewed as
`[n_sets, ways]` with ways = min(SLAB_WAYS, lanes) (one lane register per
set on TPU, a cache-line-scale set on hosts — the slab's own geometry
argument, ops/slab.py default_ways):

    plane 0: fp_lo   64-bit key fingerprint, low half
    plane 1: fp_hi   high half
    plane 2: count   space-saving estimate (occupied iff > 0)

A key lives only in set `fp_lo mod n_sets`. Per launch the update sees
one CANDIDATE per distinct key in the batch — the sorted segment ends the
slab step already delineates — weighted by the segment's total hits (raw
requested traffic: denied hits still heat a key; heat is what the wire
carries, not what the limiter admits). Two phases, in this order:

  A. matched candidates scatter-add their weight into their lane;
  B. per sketch set, ONE unmatched candidate per launch wins the insert —
     ranked lexicographically by (weight, fp_hi, fp_lo), a content-based
     order the host oracle can mirror without knowing the device sort —
     and replaces the argmin-count way of its set with
     count = victim_count + weight (the space-saving inheritance:
     the estimate OVERCOUNTS by at most the inherited amount, never
     undercounts a resident key's hits since insertion).

Losing unmatched candidates simply retry next launch (their weight is
dropped, so the sketch can UNDERCOUNT the raw stream for keys that keep
losing — the bounded-insert price of a one-scatter update; the
differential fuzz suite tracks both error directions). The winner rank
is unique by construction: candidates are distinct fingerprints, so the
(weight, fp_hi, fp_lo) triple never ties — the winner scatter keeps the
slab's unique_indices discipline.

On the default TPU geometry (128 lanes, ways == 128) the sketch is ONE
set, and the Pallas arm runs phase A as one Mosaic kernel
(pallas_sketch_match) that reads the set's 128 entries as scalars and
streams the candidates lane-dense: no per-candidate copy of the set, no
scatter. With several sets, and off the Pallas arm, each candidate's set
is gathered and scanned in XLA (_sketch_scan) and phase A scatter-adds;
phase B stays XLA everywhere. Counts stay below 2^31 by the
drain-halving cadence, so the int32 views order identically to
uint32 — the same contract the slab kernels document.

Everything here is deterministic and bit-exactly mirrored by the numpy
SketchOracle (testing/oracle.py); tests/test_hotkeys_fuzz.py holds the
XLA twin, the Pallas interpret arm, and the oracle to one state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SKETCH_PLANES = 3
PLANE_FP_LO, PLANE_FP_HI, PLANE_COUNT = range(3)

# lanes default: one full TPU lane register of head keys — enough for a
# top-16 report with 8x slack for churn, and exactly one sketch set on the
# default TPU geometry (ways == 128)
DEFAULT_LANES = 128


def validate_lanes(lanes: int) -> int:
    lanes = int(lanes)
    if lanes <= 0 or lanes & (lanes - 1):
        raise ValueError(
            f"hotkey lanes must be a positive power of two, got {lanes}"
        )
    return lanes


def sketch_ways(slab_ways: int, lanes: int) -> int:
    """Sketch set associativity: the slab's own W where it fits, else the
    whole sketch is one set (tiny-lanes case — fully associative, the
    classic space-saving shape)."""
    return min(int(slab_ways), validate_lanes(lanes))


def make_sketch(lanes: int, device=None) -> jnp.ndarray:
    planes = jnp.zeros((SKETCH_PLANES, validate_lanes(lanes)), dtype=jnp.uint32)
    if device is not None:
        planes = jax.device_put(planes, device)
    return planes


def _sketch_scan(rows_lo, rows_hi, rows_cnt, q_lo, q_hi):
    """The set scan of a sketch with several sets: per candidate, over its
    gathered set planes — (int32[b] match way, bool[b] match any,
    int32[b] victim way = argmin count with first-way tiebreak, uint32[b]
    victim count). int32 count view: the drain-halving cadence keeps
    counts below 2^31 (module docstring), so the orderings agree."""
    cnt = rows_cnt.astype(jnp.int32)
    occupied = cnt > 0
    match = occupied & (rows_lo == q_lo[:, None]) & (rows_hi == q_hi[:, None])
    match_any = match.any(axis=1)
    match_way = jnp.argmax(match, axis=1).astype(jnp.int32)
    vic_way = jnp.argmin(cnt, axis=1).astype(jnp.int32)
    vic_cnt = jnp.take_along_axis(rows_cnt, vic_way[:, None], axis=1)[:, 0]
    return match_way, match_any, vic_way, vic_cnt


def _sketch_match_kernel(sk_ref, q_lo_ref, q_hi_ref, cw_ref, m_ref, add_ref):
    """Mosaic phase A of a one-set sketch: the sketch's entries as SMEM
    scalars, the candidates lane-dense. Per entry j, every item of the
    block compares against it; an item's first matching entry (the lowest
    way, _sketch_scan's argmax rule) sets its matched flag and adds its
    candidate weight into lane j of the increment, which stays resident
    across the sequential grid."""
    ways = add_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        add_ref[...] = jnp.zeros_like(add_ref)

    m_ref[...] = jnp.zeros_like(m_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, add_ref.shape, 1)

    def entry(j, c):
        @pl.when(sk_ref[PLANE_COUNT * ways + j] > 0)  # occupied
        def _():
            eq = (q_lo_ref[...] == sk_ref[PLANE_FP_LO * ways + j]) & (
                q_hi_ref[...] == sk_ref[PLANE_FP_HI * ways + j]
            )
            m = m_ref[...]
            first = jnp.where(eq & (m == 0), cw_ref[...], jnp.int32(0))
            m_ref[...] = m | eq.astype(jnp.int32)
            s = jnp.sum(jnp.sum(first, axis=0, keepdims=True), axis=1, keepdims=True)
            add_ref[...] += jnp.where(lane == j, s, jnp.int32(0))

        return c

    jax.lax.fori_loop(0, ways, entry, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_sketch_match(sketch, q_lo, q_hi, cand_weight, interpret=False):
    """Phase A of a one-set (n_sets == 1, ways == 128) sketch as one
    Mosaic kernel, with no per-item copy of the set: (bool[b] matched,
    uint32[ways] the count plane's increment). cand_weight is the
    candidate's weight, 0 where the item is no candidate. Bit-identical to
    _sketch_scan's match plus phase A's scatter-add (pinned by
    tests/test_hotkeys_fuzz.py in interpret mode)."""
    from .pallas_slab import BLOCK_ROWS, LANES, out_vma

    _, ways = sketch.shape
    if ways != LANES:
        raise ValueError(f"pallas sketch match needs one {LANES}-way set, got {ways}")
    (b,) = q_lo.shape
    # rows of 128 items, padded to whole (8, 128) tiles, and past one
    # block to whole blocks: Mosaic takes no other block shape
    rows = -(-b // LANES)
    tile = 8 if rows <= BLOCK_ROWS else BLOCK_ROWS
    rows = -(-rows // tile) * tile
    block_rows = min(rows, BLOCK_ROWS)

    def as2d(x):  # padding items are no candidate: weight 0
        x = jnp.pad(x.astype(jnp.int32), (0, rows * LANES - b))
        return x.reshape(rows, LANES)

    items = [as2d(q_lo), as2d(q_hi), as2d(cand_weight)]
    sk = sketch.astype(jnp.int32).reshape(-1)
    block = pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_rows,),
        in_specs=[block] * len(items),
        out_specs=[block, pl.BlockSpec((1, LANES), lambda i, *_: (0, 0))],
    )
    vma = out_vma(sk, *items)
    m, add = pl.pallas_call(
        _sketch_match_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32, vma=vma),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="sketch_match",
        interpret=interpret,
    )(sk, *items)
    return m.reshape(-1)[:b] > 0, add[0].astype(jnp.uint32)


def sketch_update(
    sketch: jnp.ndarray,  # uint32[SKETCH_PLANES, lanes]
    fp_lo: jnp.ndarray,  # uint32[b] sorted batch fingerprints
    fp_hi: jnp.ndarray,
    weight: jnp.ndarray,  # uint32[b] segment-total hits (valid at cand rows)
    cand: jnp.ndarray,  # bool[b] one True per distinct key (segment end)
    ways: int,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """One launch's sketch update (module docstring). Traced inside the
    slab step's jit, so the update fuses with the launch program — the
    sketch never costs an extra device round trip.

    On the Pallas arm a one-set sketch (the default TPU geometry) takes
    phase A in pallas_sketch_match, which reads the set where it lies;
    every other shape gathers each candidate's set and scans it."""
    lanes = sketch.shape[1]
    n_sets = lanes // ways
    u0 = jnp.uint32(0)
    set_idx = (fp_lo & jnp.uint32(n_sets - 1)).astype(jnp.int32)
    sets = sketch.reshape(SKETCH_PLANES, n_sets, ways)

    # --- phase A: matched candidates accumulate in place. The lanes are
    # unique by construction: a fingerprint occupies at most one lane of
    # its set (phase B never inserts a fp that matched, and set_idx is a
    # pure function of fp_lo), and candidates are distinct keys — so two
    # candidates can never match the same lane. unique_indices lets XLA
    # compile the add as gather+select instead of a serialized scatter. ---
    if use_pallas and ways == 128 and n_sets == 1:
        m_any, add = pallas_sketch_match(
            sketch, fp_lo, fp_hi, jnp.where(cand, weight, u0),
            interpret=interpret,
        )
        cnt_plane = sketch[PLANE_COUNT] + add
    else:
        rows = [sets[p][set_idx] for p in range(SKETCH_PLANES)]
        m_way, m_any, _, _ = _sketch_scan(*rows, fp_lo, fp_hi)
        matched = m_any & cand
        drop = jnp.int32(lanes)  # out-of-bounds scatter sentinel (mode="drop")
        add_lane = jnp.where(matched, set_idx * jnp.int32(ways) + m_way, drop)
        cnt_plane = sketch[PLANE_COUNT].at[add_lane].add(
            jnp.where(matched, weight, u0), mode="drop", unique_indices=True
        )

    # --- phase B: one winner per set among unmatched candidates, ranked
    # lexicographically by (weight, fp_hi, fp_lo) via three masked
    # segment-max rounds — content-based so the host oracle needs no sort
    # knowledge, and unique because candidate fingerprints are distinct.
    # DENSE (b, n_sets) reductions, not scatter-max: n_sets is tiny
    # (lanes/ways; 1 on the default TPU geometry) and a non-unique
    # scatter-max lowers to a serialized loop over the batch — measured
    # at ~80% of the whole step on the CPU twin before this. ---
    unmatched = cand & ~m_any
    onehot = set_idx[:, None] == jnp.arange(n_sets, dtype=jnp.int32)[None, :]

    def seg_max(mask: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
        # max over {vals[i] : mask[i] and set_idx[i] == s} ∪ {0}, per set —
        # exactly the zeros.at[sel].max(vals, mode="drop") semantics
        return jnp.where(mask[:, None] & onehot, vals[:, None], u0).max(axis=0)

    w_max = seg_max(unmatched, weight)
    w_ok = unmatched & (weight == w_max[set_idx])
    h_max = seg_max(w_ok, fp_hi)
    h_ok = w_ok & (fp_hi == h_max[set_idx])
    l_max = seg_max(h_ok, fp_lo)

    # The write itself is per-SET, not per-candidate: every candidate of a
    # set gathered the same rows, so the victim way (argmin count, lowest
    # way on ties — the scan's v_way for each of them) is a set property
    # computable straight from the planes, and the winner's content IS the
    # segment maxima above. lanes-sized selects replace three scatters.
    # The winner inherits the displaced count — the space-saving bound.
    set_cnt_i32 = sets[PLANE_COUNT].astype(jnp.int32)  # (n_sets, ways)
    vic_way = jnp.argmin(set_cnt_i32, axis=1).astype(jnp.int32)
    vic_cnt = jnp.take_along_axis(
        sets[PLANE_COUNT], vic_way[:, None], axis=1
    )[:, 0]
    win_exists = w_max > u0  # candidate weights are >= 1 (hits > 0)
    way_iota = jnp.arange(ways, dtype=jnp.int32)[None, :]
    win_mask = (
        (way_iota == vic_way[:, None]) & win_exists[:, None]
    ).reshape(lanes)
    lo_plane = jnp.where(
        win_mask, jnp.repeat(l_max, ways), sketch[PLANE_FP_LO]
    )
    hi_plane = jnp.where(
        win_mask, jnp.repeat(h_max, ways), sketch[PLANE_FP_HI]
    )
    cnt_plane = jnp.where(
        win_mask, jnp.repeat(vic_cnt + w_max, ways), cnt_plane
    )
    return jnp.stack([lo_plane, hi_plane, cnt_plane])


# --- host-side drain helpers -------------------------------------------------
#
# The engine pulls the planes on the stats cadence (never per launch), and
# these run on the numpy copy. sketch_decay is the SAME function the
# SketchOracle semantics specify, so kernel-vs-oracle state stays bit-exact
# across drains.


def sketch_topk(planes: np.ndarray, k: int):
    """Top-k occupied entries of a drained plane copy, hottest first:
    [(fp_lo, fp_hi, count)] ordered by (count, fp_hi, fp_lo) descending —
    the same content-based rank the insert path uses, so the report is
    deterministic under equal counts."""
    planes = np.asarray(planes)
    cnt = planes[PLANE_COUNT]
    occ = np.flatnonzero(cnt > 0)
    if occ.size == 0 or k <= 0:
        return []
    order = occ[
        np.lexsort(
            (planes[PLANE_FP_LO][occ], planes[PLANE_FP_HI][occ], cnt[occ])
        )[::-1]
    ][:k]
    return [
        (int(planes[PLANE_FP_LO][i]), int(planes[PLANE_FP_HI][i]), int(cnt[i]))
        for i in order
    ]


def sketch_decay(planes: np.ndarray) -> np.ndarray:
    """Post-drain decay, in place on the host copy: halve every count so
    the head tracks current traffic (two cadences of silence fade any
    entry below a steady key), and clear the fingerprints of entries that
    decayed to zero — an unoccupied lane must not carry a stale tag into
    the next drain's witness resolution."""
    planes = np.asarray(planes)
    cnt = planes[PLANE_COUNT]
    cnt >>= 1
    dead = cnt == 0
    planes[PLANE_FP_LO][dead] = 0
    planes[PLANE_FP_HI][dead] = 0
    return planes


class HostTopK:
    """Space-saving top-K on the HOST — the mesh engine's sketch fallback.

    The device sketch (planes above) rides a single chip's launch; the
    mesh engine's per-shard launches would each see only their shard's
    slice of the stream, and merging K per-shard sketches coherently is
    exactly the associativity fight the planes were built to avoid. So
    ShardedSlabEngine feeds THIS summary from the one place that still
    sees the whole stream — the host routing pass that buckets rows by
    shard — closing PR 15's "mesh engines decline the sketch" gap.

    Same algorithm family as the device planes (space-saving: a full
    summary evicts its min-count entry and the newcomer INHERITS that
    count, so estimates only ever over-count — a true heavy hitter can
    never be displaced by noise), same drain contract (sketch_topk
    ordering: count desc, fp as the deterministic tiebreak) and the same
    halve-on-drain decay. Pure dict + numpy; the cost rides the host
    routing pass, not the device."""

    def __init__(self, lanes: int):
        self.lanes = validate_lanes(lanes)
        self._counts: dict[int, int] = {}

    def update(self, fp_lo, fp_hi, hits) -> None:
        """Fold a batch in: fp halves + per-row hit weights (uint32
        arrays, padding already stripped). Batches pre-aggregate by key
        before touching the dict — hot batches repeat keys heavily."""
        fp_lo = np.asarray(fp_lo, dtype=np.uint64)
        fp_hi = np.asarray(fp_hi, dtype=np.uint64)
        combined = fp_lo | (fp_hi << np.uint64(32))
        keys, inv = np.unique(combined, return_inverse=True)
        sums = np.bincount(
            inv, weights=np.asarray(hits, dtype=np.float64)
        ).astype(np.int64)
        counts = self._counts
        for key, add in zip(keys.tolist(), sums.tolist()):
            cur = counts.get(key)
            if cur is not None:
                counts[key] = cur + add
            elif len(counts) < self.lanes:
                counts[key] = add
            else:
                # space-saving eviction: newcomer inherits the floor
                victim = min(counts, key=counts.get)
                floor = counts.pop(victim)
                counts[key] = floor + add

    def topk(self, k: int) -> list:
        """[(fp_lo, fp_hi, count)] — sketch_topk's exact ordering: count
        desc, then (fp_hi, fp_lo) desc so equal counts stay stable."""
        if k <= 0 or not self._counts:
            return []
        order = sorted(
            self._counts.items(),
            key=lambda kv: (kv[1], kv[0] >> 32, kv[0] & 0xFFFFFFFF),
            reverse=True,
        )[:k]
        return [
            (int(fp & 0xFFFFFFFF), int(fp >> 32), int(cnt))
            for fp, cnt in order
        ]

    def decay(self) -> None:
        """sketch_decay's halve-and-drop, dict-shaped: two cadences of
        silence fade any entry below a steady key."""
        self._counts = {
            fp: cnt >> 1 for fp, cnt in self._counts.items() if cnt >> 1
        }
