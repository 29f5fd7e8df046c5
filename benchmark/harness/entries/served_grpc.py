"""served_grpc: the server's gRPC v3 ShouldRateLimit, as Envoy calls it.

Set-up starts the load-generator processes, boots the real Runner
in-process on the configuration's environment and rule YAML (this process
holds the chip), and runs a warm-up phase of the cell's own traffic. The
window is an open loop (Poisson arrivals at `rate`, each request timed
from when it was due) or a closed loop (`concurrency` requests
outstanding). Every request waits up to ANSWER_WAIT_S for its answer, so
one held up by a stall of the program is late (its latency counts the
wait, and it is not on time), not failed: `failed` counts only requests
with no answer or a malformed one. Every descriptor's code in every
response, warm-up included, is compared with the reference: gRPC decode, matcher, key
composition, dispatch, slab step and response encoding are all under it."""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from .. import reference, traffic
from .. import trace as tr
from ..loadgen import ST_OK, Workers
from ..run_context import Result, RunContext

# how long a request waits for its answer: a minute past Envoy's deadline
ANSWER_WAIT_S = 60.0
# a request with no answer may still have been counted by the server up to
# this long after it was sent (the wait plus the server's own lag)
UNANSWERED_SPAN_S = ANSWER_WAIT_S + 2.0


class Universes:
    """The configuration's descriptor keys: per descriptor, a Zipf over its
    key universe, the strings the client sends, and the limit."""

    def __init__(self, cfg: dict):
        self.descs = cfg["descriptors"]
        self.zipfs = [traffic.Zipf(int(d["keys"]), float(d["zipf"])) for d in self.descs]
        self.limits = np.array([int(d["limit"]) for d in self.descs], dtype=np.int64)
        self.units = np.array([int(d["unit_seconds"]) for d in self.descs], dtype=np.int64)

    def draw(self, seed: int, n: int) -> np.ndarray:
        """int64[n, n_desc] ranks, each descriptor drawn independently."""
        cols = [z.draw(traffic.rng_for(seed, f"desc{i}"), n)
                for i, z in enumerate(self.zipfs)]
        return np.stack(cols, axis=1).astype(np.int64)

    def strings(self, ranks: np.ndarray) -> list:
        cols = []
        for i, d in enumerate(self.descs):
            fmt = traffic.ip_strings if d["values"] == "ip" else traffic.user_strings
            cols.append([(d["key"], v) for v in fmt(ranks[:, i])])
        return [[[col[j]] for col in cols] for j in range(ranks.shape[0])]


def _boot(cfg: dict, tmp: str):
    from api_ratelimit_tpu.runner import Runner
    from api_ratelimit_tpu.settings import new_settings

    config_dir = os.path.join(tmp, "current", "ratelimit", "config")
    os.makedirs(config_dir)
    with open(os.path.join(config_dir, "rules.yaml"), "w") as f:
        f.write(cfg["rules_yaml"])
    settings = new_settings({
        **cfg["env"],
        "RUNTIME_ROOT": os.path.join(tmp, "current"),
        "RUNTIME_SUBDIRECTORY": "ratelimit",
        "RUNTIME_WATCH_ROOT": "false",
        "PORT": "0", "GRPC_PORT": "0", "DEBUG_PORT": "0",
        "USE_STATSD": "false",
    })
    runner = Runner(settings)
    runner.run_background()
    return runner


class Phases:
    """Runs load phases on the workers and keeps every request's record."""

    def __init__(self, workers: Workers, wl: dict, n_desc: int, pool: int):
        self.workers, self.wl, self.n_desc, self.pool = workers, wl, n_desc, pool
        self.records: list = []
        self.deadline_s = float(wl["deadline_ms"]) / 1e3

    def open(self, seed: int, stream: str, rate: float, seconds: float, lead: float = 0.3):
        """Poisson arrivals at `rate` over `seconds`, keys from the pool in a
        seeded order; returns the phase's records and its window."""
        rng = traffic.rng_for(seed, stream)
        offsets = traffic.open_loop_offsets(rng, rate, seconds)
        idx = rng.integers(0, self.pool, offsets.shape[0])
        t0 = time.perf_counter() + lead
        w = self.workers.n
        jobs = [{"kind": "open", "idx": idx[k::w], "offsets": offsets[k::w], "t0": t0,
                 "wait_s": ANSWER_WAIT_S, "n_desc": self.n_desc} for k in range(w)]
        return self._collect(jobs, seconds), (t0, t0 + seconds)

    def closed(self, seed: int, stream: str, concurrency: int, seconds: float, lead: float = 0.3):
        rng = traffic.rng_for(seed, stream)
        idx = rng.permutation(self.pool)
        t0 = time.perf_counter() + lead
        w = self.workers.n
        per = [concurrency // w + (1 if k < concurrency % w else 0) for k in range(w)]
        jobs = [{"kind": "closed", "idx": idx[k::w], "t0": t0, "t_end": t0 + seconds,
                 "concurrency": per[k], "wait_s": ANSWER_WAIT_S,
                 "n_desc": self.n_desc} for k in range(w)]
        return self._collect(jobs, seconds), (t0, t0 + seconds)

    def _collect(self, jobs, seconds):
        parts = self.workers.run(jobs, timeout=seconds + ANSWER_WAIT_S + 60.0)
        rec = {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
        self.records.append(rec)
        return rec


def window_numbers(rec: dict, window, deadline_s: float, n_desc: int) -> dict:
    """End-to-end numbers of one phase: every verdict returned in the window
    per second, and latency from due to done of every request due in it.
    A request answered after the deadline is late: its latency is its
    own, and it is not on time. One with no answer, or a malformed one,
    failed: it counts at the deadline or its time, whichever is larger
    (Envoy has failed open by then)."""
    t0, t1 = window
    answered = (rec["status"] == ST_OK) & ~rec["malformed"]
    done_in = answered & (rec["t_done"] >= t0) & (rec["t_done"] <= t1)
    due_in = (rec["t_due"] >= t0) & (rec["t_due"] <= t1)
    lat = (rec["t_done"] - rec["t_due"])[due_in]
    failed_in = ~answered[due_in]
    lat = np.where(failed_in, np.maximum(np.nan_to_num(lat, nan=deadline_s), deadline_s), lat)
    on_time_in = ~failed_in & (lat <= deadline_s)
    gen_late = (rec["t_sent"] - rec["t_due"])[due_in]
    second = np.floor(rec["t_due"][due_in] - t0).astype(np.int64)
    return {
        "missed_by_s": np.bincount(second[~on_time_in], minlength=int(t1 - t0)).tolist(),
        "decisions": int(done_in.sum()) * n_desc,
        "latency_ms": lat * 1e3,
        "attempted": int(due_in.sum()),
        "failed": int(failed_in.sum()),
        "on_time": int(on_time_in.sum()),
        "late_ms": gen_late * 1e3,
    }


def _percentiles(x_ms) -> dict:
    from ..stats import percentile

    if not len(x_ms):
        return {}
    return {"p50": percentile(x_ms, 50), "p99": percentile(x_ms, 99),
            "max": float(np.max(x_ms)), "n": int(len(x_ms))}


def _compare(records: list, ranks: np.ndarray, uni: Universes, control: str | None) -> dict:
    """Every descriptor of every request sent, against the reference."""
    rec = {k: np.concatenate([r[k] for r in records]) for k in records[0]}
    n, n_desc = rec["idx"].shape[0], len(uni.descs)
    answered = (rec["status"] == ST_OK) & ~rec["malformed"]
    wall_done = np.where(answered, rec["wall_done"], rec["wall_sent"] + UNANSWERED_SPAN_S)
    keys, lim, lo, hi, code, ans, order_key = [], [], [], [], [], [], []
    for i in range(n_desc):
        unit = int(uni.units[i])
        keys.append(ranks[rec["idx"], i] + (i << 40))
        lim.append(np.full(n, uni.limits[i]))
        lo.append(np.floor(rec["wall_sent"] / unit).astype(np.int64))
        hi.append(np.floor(wall_done / unit).astype(np.int64))
        code.append(rec["codes"][:, i].astype(np.int64))
        ans.append(answered)
        order_key.append(rec["wall_done"])
    key, limit, w_lo, w_hi = (np.concatenate(a) for a in (keys, lim, lo, hi))
    code, answered = np.concatenate(code), np.concatenate(ans)
    if control == "over_admit":
        sure = answered & (w_lo == w_hi)
        order = np.flatnonzero(sure)[np.argsort(np.concatenate(order_key)[sure], kind="stable")]
        ctl = reference.control_over_admit(key, limit, w_lo, order)
        code = np.where(sure, ctl, code)
    elif control is not None:
        raise ValueError(f"no control {control!r} for served_grpc")
    return reference.compare_verdicts(key, limit, w_lo, w_hi, code, answered)


def run(rc: RunContext) -> Result:
    from .. import device, stats

    cfg, wl = rc.cfg, rc.wl
    uni = Universes(cfg)
    n_desc = len(uni.descs)
    pool = int(wl["pool_requests"])
    ranks = uni.draw(rc.seed, pool)
    workers = Workers(int(wl["workers"]))
    runner = None
    with tempfile.TemporaryDirectory(prefix="bench_edge_") as tmp:
        try:
            runner = _boot(cfg, tmp)
            rc.mark("boot")
            target = f"localhost:{runner.server.grpc_port}"
            workers.start(target, cfg["domain"])
            w = workers.n
            workers.run([{"kind": "payloads", "descriptors": uni.strings(ranks)}] * w,
                        timeout=300)
            rc.mark("workers")
            # the stats flush's slab health read compiles on first use: warm
            # it, and count lossy events from here on
            health0 = runner.limiter.engine.health_snapshot()
            ph = Phases(workers, wl, n_desc, pool)
            loop = wl["loop"]

            def phase(stream, seconds):
                if loop == "open":
                    return ph.open(rc.seed, stream, float(wl["rate"]), seconds)
                return ph.closed(rc.seed, stream, int(wl["concurrency"]), seconds)

            phase("warmup", float(wl["warmup_seconds"]))
            store = runner.stats_store
            lowered0, compiled0 = rc.compiles.mark()
            hist0 = stats.histogram_totals(store)
            if rc.trace:
                tr.start(rc.trace_dir)
            setup_s = time.perf_counter() - rc.t_process
            t_tr = time.perf_counter()
            rec, window = phase("window", rc.seconds)
            t_traced = time.perf_counter() - t_tr
            if rc.trace:
                tr.stop()
            hist = stats.delta(hist0, stats.histogram_totals(store))
            lowered1, compiled1 = rc.compiles.mark()
            engine = runner.limiter.engine
            health = engine.health_snapshot()
            peak = device.memory_peak_bytes(rc.devices)
        finally:
            workers.close()
            if runner is not None:
                runner.stop()
                if runner.limiter is not None:
                    runner.limiter.close()
    del runner
    nums = window_numbers(rec, window, ph.deadline_s, n_desc)
    lat = _percentiles(nums["latency_ms"])
    rc.info("window", lowered=lowered1 - lowered0, compiled=compiled1 - compiled0,
            attempted=nums["attempted"], failed=nums["failed"], on_time=nums["on_time"],
            missed_by_s=nums["missed_by_s"],
            latency_ms=lat, generator_late_ms=_percentiles(nums["late_ms"]))
    lossy = reference.lossy_events(health0, health)
    rc.info("slab", occupancy=health.get("occupancy"), lossy_events=lossy)

    cmp = _compare(ph.records, ranks, uni, rc.control)
    rc.info("compare", **cmp)
    if cmp["compared_hits"] < int(wl["min_compared_hits"]):
        raise RuntimeError(f"only {cmp['compared_hits']} descriptor verdicts compared")
    # the configuration's guarantee: never a false OVER_LIMIT; an OK past
    # the limit only after a counted lossy event, each of which may admit
    # one key up to its limit again
    checks = {
        "false_over": (cmp["false_over"], 0),
        "excess_ok": (cmp["excess_ok"], int(uni.limits.max()) * lossy),
        "malformed": (cmp["malformed"], 0),
    }
    trace_summary = None
    if rc.trace:
        trace_summary = tr.reduce_xplane(tr.find_xplane(rc.trace_dir), t_traced)
    e2e = {"setup_s": setup_s, "served_decisions_per_s": nums["decisions"] / rc.seconds}
    if lat:
        e2e.update(p50_ms=lat["p50"], on_time_pct=100.0 * nums["on_time"] / nums["attempted"])
    return Result(end_to_end=e2e,
                  layer_ctx={"hist": hist, "window_s": rc.seconds, "latency": lat},
                  checks=checks, attempted=nums["attempted"], failed=nums["failed"],
                  memory_peak_bytes=peak, trace=trace_summary)


def sweep(rc: RunContext, points: list) -> list:
    """One boot, one phase of rc.seconds per point: offered rates (open
    loop) or concurrencies (closed loop). Returns the curve."""
    cfg, wl = rc.cfg, rc.wl
    uni = Universes(cfg)
    n_desc = len(uni.descs)
    pool = int(wl["pool_requests"])
    ranks = uni.draw(rc.seed, pool)
    workers = Workers(int(wl["workers"]))
    runner = None
    curve = []
    with tempfile.TemporaryDirectory(prefix="bench_sweep_") as tmp:
        try:
            runner = _boot(cfg, tmp)
            workers.start(f"localhost:{runner.server.grpc_port}", cfg["domain"])
            workers.run([{"kind": "payloads", "descriptors": uni.strings(ranks)}] * workers.n,
                        timeout=300)
            ph = Phases(workers, wl, n_desc, pool)
            for k, p in enumerate(points):
                if wl["loop"] == "open":
                    rec, window = ph.open(rc.seed, f"sweep{k}", p, rc.seconds)
                else:
                    rec, window = ph.closed(rc.seed, f"sweep{k}", int(p), rc.seconds)
                nums = window_numbers(rec, window, ph.deadline_s, n_desc)
                point = {"point": p, "loop": wl["loop"],
                         "decisions_per_s": nums["decisions"] / rc.seconds,
                         "completed_per_s": nums["decisions"] / n_desc / rc.seconds,
                         "attempted": nums["attempted"], "failed": nums["failed"],
                         "on_time": nums["on_time"], "missed_by_s": nums["missed_by_s"],
                         "latency_ms": _percentiles(nums["latency_ms"]),
                         "late_ms": _percentiles(nums["late_ms"])}
                rc.info("sweep_point", **point)
                curve.append(point)
        finally:
            workers.close()
            if runner is not None:
                runner.stop()
                if runner.limiter is not None:
                    runner.limiter.close()
    return curve
