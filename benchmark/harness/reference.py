"""The plain reference: a fixed-window counter store, and the comparison
that decides `correct`. Independent of the program: numpy only.

A fixed window counts the hits of a key within one window
(window = floor(unix seconds / unit)); the n-th hit of a key in a window
reads counter n, and is OVER_LIMIT iff n > limit. The program's clock is
read somewhere between the client's send and its receipt of the answer,
so each hit carries the windows [w_lo, w_hi] it may have landed in; a hit
whose interval spans a window edge is ambiguous.

Concurrent clients give no order between hits of one key, so the
comparison is order-free: per (key, window) the multiset of counters (or
of verdicts) is what the reference fixes.

Guarantees the configurations state (README "fail-open"): no counter is
ever above the true count (so no false OVER_LIMIT); a counter falls below
the true count only through the slab's counted lossy events (in-batch
contention drops, live evictions), each of which restarts one key's count
in one window."""

from __future__ import annotations

import numpy as np

# the slab's counted lossy events (health_snapshot keys): the only leave
# the guarantees give to under-count, or to admit past the limit
LOSSY_EVENTS = ("drops", "evictions_live")


def lossy_events(before: dict, after: dict) -> int:
    """Lossy events counted between two health snapshots."""
    return sum(int(after.get(k, 0)) - int(before.get(k, 0)) for k in LOSSY_EVENTS)


def _group(key: np.ndarray, win: np.ndarray):
    """Sort order and group boundaries of (key, window) pairs."""
    order = np.lexsort((win, key))
    k, w = key[order], win[order]
    edge = np.ones(k.shape[0], dtype=bool)
    edge[1:] = (k[1:] != k[:-1]) | (w[1:] != w[:-1])
    starts = np.flatnonzero(edge)
    return order, starts


def _pair_code(key, win) -> np.ndarray:
    return np.asarray(key, dtype=np.int64) * (1 << 32) + np.asarray(win, dtype=np.int64)


def _maybe_counts(key, w_lo, w_hi, at_key, at_win) -> np.ndarray:
    """For each (at_key, at_win): how many ambiguous hits (w_lo != w_hi) of
    that key may lie in that window."""
    amb = np.flatnonzero(w_lo != w_hi)
    if not amb.shape[0]:
        return np.zeros(np.shape(at_key)[0], dtype=np.int64)
    span = w_hi[amb] - w_lo[amb] + 1
    rep = np.repeat(amb, span)
    win = w_lo[rep] + (np.arange(rep.shape[0]) - np.repeat(np.cumsum(span) - span, span))
    codes, counts = np.unique(_pair_code(key[rep], win), return_counts=True)
    want = _pair_code(at_key, at_win)
    pos = np.clip(np.searchsorted(codes, want), 0, codes.shape[0] - 1)
    return np.where(codes[pos] == want, counts[pos], 0)


def compare_counters(key, w_lo, w_hi, after, cap: int) -> dict:
    """Owner cells: post-increment counters (hits=1 each) against the
    reference, per (key, window), every counter saturated at the launch's
    readback cap as the program reads it back.

    The hits known to lie in a window (sure) hold distinct true counters
    among 1..n+m, where n are the sure hits and m the ambiguous ones that
    may lie in it; so the i-th smallest sure counter lies in [i, i+m]
    (exactly i when m = 0). Ambiguous hits are not compared themselves.

    counter_over: counters above i+m (never allowed).
    under_rows / under_pairs: counters below i, and the (key, window)
    pairs that hold them.
    restarts: the fewest restarts of a count that explain the counters.
    Without one, the sure counters of a pair are distinct values from 1
    up; each restart (a lossy event) begins another run from 1, so a value
    held by r + 1 sure rows needs r restarts. Per pair: the largest
    multiplicity of a value below the readback cap, less one (at least 1
    where a counter lies below its rank); summed over the pairs."""
    key = np.asarray(key, dtype=np.int64)
    w_lo = np.asarray(w_lo, dtype=np.int64)
    w_hi = np.asarray(w_hi, dtype=np.int64)
    after = np.asarray(after, dtype=np.int64)
    sure = w_lo == w_hi
    k, w, a = key[sure], w_lo[sure], after[sure]
    out = {"compared_rows": int(k.shape[0]), "ambiguous_rows": int((~sure).sum()),
           "counter_over": 0, "under_rows": 0, "under_pairs": 0, "restarts": 0, "pairs": 0}
    if not k.shape[0]:
        return out
    order, starts = _group(k, w)
    sizes = np.diff(np.append(starts, k.shape[0]))
    grp = np.repeat(np.arange(starts.shape[0]), sizes)
    rank = np.arange(k.shape[0]) - np.repeat(starts, sizes) + 1
    m = np.repeat(_maybe_counts(key, w_lo, w_hi, k[order][starts], w[order][starts]), sizes)
    a = a[order]
    prog = a[np.lexsort((a, grp))]  # program counters sorted within each group
    out["counter_over"] = int(np.sum(prog > np.minimum(rank + m, cap)))
    under = prog < np.minimum(rank, cap)
    under_grps = np.unique(grp[under])
    out["under_rows"] = int(np.sum(under))
    out["under_pairs"] = int(under_grps.shape[0])
    # runs of one value within a group: each repeat of a value below the
    # cap is one more restart that group needs
    new_run = np.ones(prog.shape[0], dtype=bool)
    new_run[1:] = (grp[1:] != grp[:-1]) | (prog[1:] != prog[:-1])
    run_id = np.cumsum(new_run) - 1
    run_len = np.bincount(run_id)
    run_grp = grp[new_run]
    run_val = prog[new_run]
    dup = np.where((run_val >= 1) & (run_val < cap), run_len - 1, 0)
    per_grp = np.zeros(starts.shape[0], dtype=np.int64)
    np.maximum.at(per_grp, run_grp, dup)
    per_grp[under_grps] = np.maximum(per_grp[under_grps], 1)
    out["restarts"] = int(per_grp.sum())
    out["pairs"] = int(starts.shape[0])
    return out


def compare_verdicts(key, limit, w_lo, w_hi, code, answered) -> dict:
    """Edge cells: per (key, window), the codes of hits known to lie in it
    (sure) against the reference's min(n, limit) OK and the rest
    OVER_LIMIT, where the true count n lies between the sure hits and the
    sure plus the ambiguous ones (hits that may lie in it, and requests
    with no answer, which the server may still have counted).

    false_over: sure OVER_LIMIT beyond max(0, n_max - limit).
    excess_ok: sure OK beyond the limit.
    malformed: answered hits whose code is neither OK nor OVER_LIMIT."""
    key = np.asarray(key, dtype=np.int64)
    limit = np.asarray(limit, dtype=np.int64)
    w_lo = np.asarray(w_lo, dtype=np.int64)
    w_hi = np.asarray(w_hi, dtype=np.int64)
    code = np.asarray(code, dtype=np.int64)
    answered = np.asarray(answered, dtype=bool)
    malformed = int(np.sum(answered & (code != 1) & (code != 2)))
    sure = answered & (w_lo == w_hi) & ((code == 1) | (code == 2))
    # every (key, window) a hit may count in: sure hits once, others once
    # per candidate window
    span = (w_hi - w_lo + 1)
    rep = np.repeat(np.arange(key.shape[0]), span)
    win = w_lo[rep] + (np.arange(rep.shape[0]) - np.repeat(np.cumsum(span) - span, span))
    k_all = key[rep]
    is_sure = sure[rep]
    order, starts = _group(k_all, win)
    bounds = np.append(starts, order.shape[0])
    s_cnt = np.add.reduceat(is_sure[order].astype(np.int64), starts)
    all_cnt = np.diff(bounds)
    ok = np.add.reduceat((is_sure & (code[rep] == 1))[order].astype(np.int64), starts)
    over = np.add.reduceat((is_sure & (code[rep] == 2))[order].astype(np.int64), starts)
    lim = limit[rep][order][starts]
    false_over = np.maximum(0, over - np.maximum(0, all_cnt - lim))
    excess_ok = np.maximum(0, ok - lim)
    return {
        "compared_hits": int(sure.sum()),
        "ambiguous_hits": int((~sure).sum()),
        "false_over": int(false_over.sum()),
        "excess_ok": int(excess_ok.sum()),
        "malformed": malformed,
        "pairs": int(starts.shape[0]),
        "pairs_all_sure": int(np.sum(s_cnt == all_cnt)),
    }


# -- controls: the reference with one stated guarantee broken, put in the
# program's place (run only with --control; never in the driver's runs) --


def control_lost_update(key, w_lo, frame, cap: int) -> np.ndarray:
    """Owner control: the INCRBY made non-atomic within a launch. All rows
    of one key in one frame read the same counter and write it plus one,
    so concurrent increments are lost without a counted event."""
    key = np.asarray(key, dtype=np.int64)
    w_lo = np.asarray(w_lo, dtype=np.int64)
    frame = np.asarray(frame, dtype=np.int64)
    order = np.lexsort((frame, w_lo, key))
    k, w, f = key[order], w_lo[order], frame[order]
    new_pair = np.ones(k.shape[0], dtype=bool)
    new_pair[1:] = (k[1:] != k[:-1]) | (w[1:] != w[:-1])
    new_frame = new_pair.copy()
    new_frame[1:] |= f[1:] != f[:-1]
    # the counter after a frame = frames of this key seen so far in the window
    frames_seen = np.cumsum(new_frame)
    pair_base = np.maximum.accumulate(np.where(new_pair, frames_seen - 1, 0))
    out = np.empty(k.shape[0], dtype=np.int64)
    out[order] = np.minimum(frames_seen - pair_base, cap)
    return out


def control_over_admit(key, limit, w_lo, code_order) -> np.ndarray:
    """Edge control: the limit compared off by one (count <= limit + 1
    admitted), so one more request per key and window gets through."""
    key = np.asarray(key, dtype=np.int64)
    out = np.empty(key.shape[0], dtype=np.int64)
    counts: dict = {}
    for i in np.asarray(code_order).tolist():
        kw = (int(key[i]), int(w_lo[i]))
        counts[kw] = counts.get(kw, 0) + 1
        out[i] = 1 if counts[kw] <= int(limit[i]) + 1 else 2
    return out
