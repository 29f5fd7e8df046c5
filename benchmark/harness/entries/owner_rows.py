"""owner_rows: the device owner's row entry, SlabDeviceEngine.submit_rows.

Set-up builds the engine as the server does (runner.create_limiter, the
configuration's environment), runs YCSB's load phase (every record
inserted once) and a short warm-up through the same call. The window is a
closed loop: `threads` submitter threads each post `frame_rows`-row
frames from a pool drawn from the seed; the dispatch loop coalesces them
into launches. Every row of a sample of keys (1 in `sample_one_in` by a
seeded hash, plus the `hot_sampled` hottest) is recorded from the load
phase on, and its post-increment counter compared with the reference."""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import reference, traffic
from .. import trace as tr
from ..run_context import Result, RunContext


# the load phase's window length: its window starts (multiples of 86,399 s)
# meet a minute's start once in 60 days, so a loaded row never shares a
# window with the traffic that follows
LOAD_DIVIDER = 86399


def _readback_cap(hits: int, limit: int) -> int:
    """The program reads a launch's counters back saturated at the smallest
    unsigned width above hits + limit (README: saturating readback)."""
    m = hits + limit
    return 0xFF if m < 0xFF else 0xFFFF if m < 0xFFFF else 0xFFFFFFFF


class _Recorder:
    """Sampled rows of every submitted frame: key ids, wall-clock interval,
    counters; one list per thread, joined after the window."""

    def __init__(self):
        self.parts = []
        self.lock = threading.Lock()
        self.instances = 0

    def add(self, keys, afters, wall0, wall1):
        with self.lock:
            inst = self.instances
            self.instances += 1
            self.parts.append((keys, afters, wall0, wall1, inst))

    def arrays(self, unit: int):
        if not self.parts:
            z = np.zeros(0, dtype=np.int64)
            return z, z, z, z, z
        keys = np.concatenate([p[0] for p in self.parts]).astype(np.int64)
        afters = np.concatenate([p[1] for p in self.parts]).astype(np.int64)
        sizes = [p[0].shape[0] for p in self.parts]
        w_lo = np.repeat([int(p[2] // unit) for p in self.parts], sizes)
        w_hi = np.repeat([int(p[3] // unit) for p in self.parts], sizes)
        frame = np.repeat([p[4] for p in self.parts], sizes)
        return keys, w_lo, w_hi, afters, frame


def run(rc: RunContext) -> Result:
    from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
    from api_ratelimit_tpu.runner import create_limiter
    from api_ratelimit_tpu.settings import new_settings
    from api_ratelimit_tpu.stats.sinks import NullSink
    from api_ratelimit_tpu.stats.store import Store
    from api_ratelimit_tpu.utils.timeutil import RealTimeSource

    from .. import device, stats

    cfg, wl = rc.cfg, rc.wl
    records = int(cfg["records"])
    limit, unit = int(cfg["limit"]), int(cfg["unit_seconds"])
    frame_rows, threads = int(wl["frame_rows"]), int(wl["threads"])
    cap = _readback_cap(1, limit)

    # -- traffic, from the seed --
    zipf = traffic.Zipf(records, float(cfg["zipfian_constant"]))
    rng = traffic.rng_for(rc.seed, "owner_pool")
    pool_ids = zipf.draw(rng, int(wl["pool_frames"]) * frame_rows).reshape(-1, frame_rows)
    salt = np.uint32(rc.seed & 0xFFFFFFFF)

    def sampled(ids):
        return ((traffic.fmix32(ids ^ salt) % np.uint32(wl["sample_one_in"])) == 0) | (
            ids < np.uint32(wl["hot_sampled"]))

    def block_of(ids, divider=unit):
        b = np.empty((6, ids.shape[0]), dtype=np.uint32)
        b[0], b[1] = traffic.record_fingerprints(ids)
        b[2], b[3], b[4], b[5] = 1, limit, divider, 0
        return b

    pool = [block_of(ids) for ids in pool_ids]
    pool_pos = [np.flatnonzero(sampled(ids)) for ids in pool_ids]
    pool_keys = [ids[pos] for ids, pos in zip(pool_ids, pool_pos)]
    rec = _Recorder()
    rc.mark("pool")

    store = Store(NullSink())
    cache = create_limiter(new_settings(dict(cfg["env"])),
                           BaseRateLimiter(RealTimeSource()), store)
    engine = cache.engine
    rc.mark("engine")
    try:
        # -- YCSB load phase: every record inserted once, as a row of an
        # earlier window (LOAD_DIVIDER), so the slab holds the whole
        # universe and the measured minute windows start from zero --
        load_rows = int(wl["load_frame_rows"])
        t_load = time.time()
        for start in range(0, records, load_rows):
            ids = np.arange(start, min(records, start + load_rows), dtype=np.uint32)
            engine.submit_rows(block_of(ids, LOAD_DIVIDER))
        load_start = t_load // LOAD_DIVIDER * LOAD_DIVIDER
        if load_start % unit == 0 and t_load - load_start < 900:
            raise RuntimeError("the load window starts on a minute edge")
        health0 = engine.health_snapshot()
        rc.mark("load")

        def submitter(t: int, end: float, counted: list, errors: list):
            i, done_rows = t, 0
            n_pool = len(pool)
            try:
                while time.perf_counter() < end:
                    f = i % n_pool
                    w0 = time.time()
                    with rc.span("bench.submit_rows"):
                        afters = engine.submit_rows(pool[f])
                    sel = afters[pool_pos[f]].copy()
                    w1 = time.time()
                    if time.perf_counter() <= end:
                        done_rows += frame_rows
                    rec.add(pool_keys[f], sel, w0, w1)
                    i += threads
            except Exception as e:  # noqa: BLE001 - re-raised by closed_loop
                errors.append(e)
            counted[t] = done_rows

        def closed_loop(seconds: float) -> int:
            """Rows whose counters returned within `seconds` from now."""
            end = time.perf_counter() + seconds
            counted, errors = [0] * threads, []
            ths = [threading.Thread(target=submitter, args=(t, end, counted, errors),
                                    name=f"bench-submit-{t}") for t in range(threads)]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            if errors:
                raise errors[0]
            return sum(counted)

        closed_loop(float(wl["warmup_seconds"]))
        rc.info("setup", load_rows=records, compiles=rc.compiles.mark())

        # -- the measured window --
        lowered0, compiled0 = rc.compiles.mark()
        hist0 = stats.histogram_totals(store)
        if rc.trace:
            tr.start(rc.trace_dir)
        setup_s = time.perf_counter() - rc.t_process
        t0 = time.perf_counter()
        decisions = closed_loop(rc.seconds)
        t_traced = time.perf_counter() - t0
        if rc.trace:
            tr.stop()
        hist = stats.delta(hist0, stats.histogram_totals(store))
        lowered1, compiled1 = rc.compiles.mark()
        rc.info("window", lowered=lowered1 - lowered0, compiled=compiled1 - compiled0,
                decisions=decisions)
        health1 = engine.health_snapshot()
        peak = device.memory_peak_bytes(rc.devices)
    finally:
        cache.close()
    del engine, cache

    lossy = reference.lossy_events(health0, health1)
    rc.info("slab", occupancy=health1.get("occupancy"), lossy_events=lossy)

    # -- the comparison, once the program's state is freed --
    keys, w_lo, w_hi, afters, frame = rec.arrays(unit)
    if rc.control == "lost_update":
        afters = reference.control_lost_update(keys, w_lo, frame, cap)
    elif rc.control is not None:
        raise ValueError(f"no control {rc.control!r} for owner_rows")
    cmp = reference.compare_counters(keys, w_lo, w_hi, afters, cap)
    rc.info("compare", **cmp)
    if cmp["compared_rows"] < int(wl["min_compared_rows"]):
        raise RuntimeError(f"only {cmp['compared_rows']} rows compared")
    # the configuration's guarantee: no counter above the true count; one
    # below it only where a counted lossy event restarted a key's count
    checks = {
        "counter_over": (cmp["counter_over"], 0),
        "restarts": (cmp["restarts"], lossy),
    }
    trace_summary = None
    if rc.trace:
        trace_summary = tr.reduce_xplane(tr.find_xplane(rc.trace_dir), t_traced)
    return Result(
        end_to_end={"decisions_per_s": decisions / rc.seconds, "setup_s": setup_s},
        layer_ctx={"hist": hist, "window_s": rc.seconds},
        checks=checks,
        attempted=decisions,
        failed=0,
        memory_peak_bytes=peak,
        trace=trace_summary,
    )
