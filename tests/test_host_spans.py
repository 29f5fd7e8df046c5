"""Program spans on the profiler's clock (tracing/host.py) and the three
timings recorded at the same sites: the slab health drain, the launch
path's wait for the state lock, and a caller's submit wait.

The traces here are real jax.profiler captures on the CPU, read back with
ProfileData; no number here is a device metric."""

import gc
import glob
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from api_ratelimit_tpu.backends.dispatch import DispatchLoop
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
from api_ratelimit_tpu.ops.slab import HEALTH_WIDTH, fold_health_vectors
from api_ratelimit_tpu.stats import Store
from api_ratelimit_tpu.tracing import host
from api_ratelimit_tpu.tracing import host_span, install_gc_spans
from api_ratelimit_tpu.utils import FakeTimeSource

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _capture(tmp_path, body):
    """Run body() under a CPU profiler capture (Python tracer off, host
    TraceMe on, as the benchmark and /debug/profile run it); returns
    {span name: [(line index, start_ns, end_ns)]} of the ratelimit.* host
    events."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ratelimit."):
                    events.setdefault(ev.name, []).append((i, ev.start_ns, ev.end_ns))
    return events


class _CountingAnnotation:
    made = 0

    def __init__(self, name):
        type(self).made += 1


class TestHostSpan:
    def test_off_builds_no_traceme(self, monkeypatch):
        """With no capture running every site gets the one shared no-op
        and no TraceMe object is built."""
        import jax  # noqa: F401 - the program imports it before any site runs

        assert host_span("ratelimit.test.off") is host._NOOP  # binds
        monkeypatch.setattr(host, "_annotation", _CountingAnnotation)
        _CountingAnnotation.made = 0
        for _ in range(1000):
            with host_span("ratelimit.test.off"):
                pass
        assert _CountingAnnotation.made == 0

    def test_off_cost_is_small(self):
        """The guard is the whole cost of a site with profiling off: well
        under a microsecond (a loose bound for a shared CPU)."""
        import jax  # noqa: F401

        n = 200_000
        t0 = time.perf_counter()
        for _ in range(n):
            with host_span("ratelimit.test.cost"):
                pass
        assert (time.perf_counter() - t0) / n < 5e-6

    def test_on_records_named_events_per_thread(self, tmp_path):
        """While a capture runs, a span is a named host event on its
        thread's line, readable with ProfileData."""

        def body():
            def worker():
                with host_span("ratelimit.test.worker"):
                    time.sleep(0.01)

            t = threading.Thread(target=worker)
            t.start()
            with host_span("ratelimit.test.main"):
                time.sleep(0.02)
            t.join(timeout=5)
            assert not t.is_alive()

        events = _capture(tmp_path, body)
        (main,) = events["ratelimit.test.main"]
        (worker,) = events["ratelimit.test.worker"]
        assert main[0] != worker[0]  # one host line per thread
        assert main[2] - main[1] >= 15e6 and worker[2] - worker[1] >= 5e6
        # and off again once the capture stops
        assert host_span("ratelimit.test.main") is host._NOOP

    def test_gc_span_only_while_capturing(self, tmp_path):
        install_gc_spans()
        install_gc_spans()  # once per process
        assert sum(isinstance(cb, host._GcSpans) for cb in gc.callbacks) == 1
        gc.collect(1)  # off: nothing is opened
        hook = next(cb for cb in gc.callbacks if isinstance(cb, host._GcSpans))
        assert hook.open is None
        events = _capture(tmp_path, lambda: gc.collect(2))
        assert "ratelimit.gc.gen2" in events
        assert hook.open is None

    def test_no_jax_means_noop_and_no_import(self):
        """A frontend-only process: the helper never imports jax."""
        code = (
            "import sys\n"
            "from api_ratelimit_tpu.tracing.host import host_span, _NOOP\n"
            "assert host_span('ratelimit.x') is _NOOP\n"
            "assert 'jax' not in sys.modules, 'imported jax'\n"
        )
        out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-800:]


# -- the three timings --


def _hist(store, name):
    s = store._histograms[name].snapshot()
    return int(s["count"]), float(s["sum"])


def _engine(store, n_slots=1 << 12):
    return SlabDeviceEngine(
        time_source=FakeTimeSource(1_700_000_000),
        n_slots=n_slots,
        buckets=(128,),
        max_batch=128,
        use_pallas=False,
        block_mode=True,
        scope=store.scope("ratelimit"),
    )


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    b = np.zeros((6, n), dtype=np.uint32)
    b[0] = rng.integers(1, 2**31, n)
    b[1] = rng.integers(1, 2**31, n)
    b[2], b[3], b[4] = 1, 1000, 60
    return b


class TestEngineTimings:
    def test_one_lock_wait_sample_per_launch(self):
        store = Store()
        eng = _engine(store)
        try:
            for i in range(5):
                eng.submit_block(_rows(16, i))
            count, _ = _hist(store, "ratelimit.slab.lock_wait_ms")
            assert count == len(eng.launch_sizes) == 5
        finally:
            eng.close()

    def test_lock_wait_times_a_held_lock(self):
        """A launch that finds the state lock held waits for it, and the
        wait is what lock_wait_ms records."""
        store = Store()
        eng = _engine(store)
        try:
            eng.submit_block(_rows(8))  # compiled, uncontended
            held = threading.Event()

            def holder():
                with eng._state_lock:
                    held.set()
                    time.sleep(0.08)

            t = threading.Thread(target=holder)
            t.start()
            held.wait(5)
            eng.submit_block(_rows(8, 1))
            t.join(timeout=5)
            assert not t.is_alive()
            count, total = _hist(store, "ratelimit.slab.lock_wait_ms")
            assert count == 2 and total >= 50.0
        finally:
            eng.close()

    def test_one_health_drain_sample_per_drain(self):
        store = Store()
        eng = _engine(store)
        try:
            eng.submit_block(_rows(8))
            eng.submit_block(_rows(8, 1))
            eng.health_snapshot()  # drains the 2 parked vectors
            assert _hist(store, "ratelimit.slab.health_drain_ms")[0] == 1
            eng.health_snapshot()  # nothing parked: no drain to time
            assert _hist(store, "ratelimit.slab.health_drain_ms")[0] == 1
            # the inline drain once more than 4,096 vectors are parked
            eng._health.pending.extend(
                [np.zeros(HEALTH_WIDTH, np.uint32)] * 4096)
            eng.submit_block(_rows(8, 2))
            assert _hist(store, "ratelimit.slab.health_drain_ms")[0] == 2
            assert eng._health.pending == []
        finally:
            eng.close()

    def test_health_vectors_counts_each_drain(self):
        """slab.health_vectors takes one sample per drain: the number of
        vectors it folded."""
        store = Store()
        eng = _engine(store)
        try:
            for i in range(3):
                eng.submit_block(_rows(8, i))
            eng.health_snapshot()
            assert _hist(store, "ratelimit.slab.health_vectors") == (1, 3.0)
            eng.health_snapshot()  # nothing parked: no sample
            assert _hist(store, "ratelimit.slab.health_vectors") == (1, 3.0)
            eng._health.pending.extend(
                [np.zeros(HEALTH_WIDTH, np.uint32)] * 4096)
            eng.submit_block(_rows(8, 3))  # the inline drain: 4,096 + 1
            assert _hist(store, "ratelimit.slab.health_vectors") == (2, 4100.0)
        finally:
            eng.close()

    def test_drain_and_lock_wait_spans_name_the_stall(self, tmp_path):
        """The stall is gone: in a capture, a launch made while a health
        drain fetches its vectors waits for the state lock only briefly,
        and its ratelimit.slab.lock_wait span ends before the drain's
        ratelimit.slab.health_drain does."""
        fetching = threading.Event()

        class SlowHealth:
            """A parked health vector whose device read takes 50 ms."""

            def __array__(self, dtype=None, copy=None):
                fetching.set()
                time.sleep(0.05)
                return np.zeros(HEALTH_WIDTH, np.uint32)

        store = Store()
        eng = _engine(store)
        try:
            eng.submit_block(_rows(8))

            def body():
                eng._health.pending.extend(SlowHealth() for _ in range(3))
                t = threading.Thread(target=eng.health_snapshot)
                t.start()
                assert fetching.wait(10)
                eng.submit_block(_rows(8, 3))
                t.join(timeout=10)
                assert not t.is_alive()

            events = _capture(tmp_path, body)
        finally:
            eng.close()
        (wait,) = events["ratelimit.slab.lock_wait"]
        (drain,) = events["ratelimit.slab.health_drain"]
        assert drain[0] != wait[0]
        assert drain[1] < wait[1] and drain[2] - drain[1] >= 100e6
        assert wait[2] - wait[1] <= 10e6 and wait[2] < drain[2]
        assert {"ratelimit.device.pack", "ratelimit.device.readback",
                "ratelimit.slab.live_slots"} <= set(events)


def _mesh_packed(n, seed, now):
    rng = np.random.default_rng(seed)
    p = np.zeros((7, n), dtype=np.uint32)
    p[0] = rng.integers(1, 2**32, n, dtype=np.uint64)
    p[1] = rng.integers(1, 2**32, n, dtype=np.uint64)
    p[2], p[3], p[4] = 1, 1000, 60
    p[6, 0] = now
    p[6, 1] = np.float32(0.8).view(np.uint32)
    p[6, 2] = np.float32(1.0).view(np.uint32)
    return p


class TestHealthDrainCounts:
    @pytest.mark.parametrize("n", [1, 255, 256, 257, 1150])
    def test_fold_matches_the_loop(self, n):
        """fold_health_vectors against a Python loop over each vector:
        device vectors on two devices (a group and a chunk chain each) and
        host arrays, in one call."""
        import jax

        rng = np.random.default_rng(n)
        host = rng.integers(0, 1 << 16, (n, HEALTH_WIDTH)).astype(np.uint32)
        devices = jax.devices()[:2]
        vectors = [jax.device_put(h, devices[i % 2]) if i % 3 else h
                   for i, h in enumerate(host)]
        expected = [0] * HEALTH_WIDTH
        for h in host:
            for k, v in enumerate(h):
                expected[k] += int(v)
        folded = fold_health_vectors(vectors)
        assert folded.dtype == np.uint64 and folded.tolist() == expected

    @pytest.mark.parametrize("engine,drain", [
        ("single", "flush"), ("single", "inline"),
        ("mesh", "flush"), ("mesh", "inline"),
    ])
    def test_every_vector_counted_once(self, monkeypatch, engine, drain):
        """One thread launches while another snapshots over and over; the
        final snapshot's totals are the sum of every parked vector, none
        lost and none counted twice. The inline case parks 4,096 more
        before some launches, so the launching thread drains them; a gate
        keeps the snapshots out of that one step."""
        from api_ratelimit_tpu.parallel import ShardedSlabEngine, make_mesh

        now = 1_700_000_000
        if engine == "single":
            eng = _engine(Store(), n_slots=1 << 8)

            def launch(i):
                eng.submit_block(_rows(128, i))

            def snapshot():
                return eng.health_snapshot()
        else:
            eng = ShardedSlabEngine(mesh=make_mesh(), n_slots_global=8 * 64)

            def launch(i):
                eng.step_after_compact(_mesh_packed(128, i, now), 0xFFFF)

            def snapshot():
                return eng.health_snapshot(now)

        parked = []
        park = eng._health.park

        def recording_park(health):
            parked.append(health)
            park(health)

        monkeypatch.setattr(eng._health, "park", recording_park)
        drained = []
        drain_fn = eng._health.drain

        def recording_drain(state_lock):
            n, totals = drain_fn(state_lock)
            drained.append((threading.current_thread().name, n))
            return n, totals

        monkeypatch.setattr(eng._health, "drain", recording_drain)
        launch(0)  # the step and the drain's fold compile before the race
        snapshot()
        gate = threading.Lock()
        done = threading.Event()
        seen = []
        keys = ("evictions_expired", "evictions_window", "evictions_live",
                "drops", "algo_resets")

        def snapshots():
            while not done.is_set():
                with gate:
                    snap = snapshot()
                seen.append([snap[k] for k in keys])
                time.sleep(0.002)

        t = threading.Thread(target=snapshots, name="snapshots")
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)  # interleave the threads finely
        t.start()
        try:
            for i in range(1, 41):
                if drain == "inline" and i % 10 == 0:
                    with gate:
                        with eng._state_lock:
                            for j in range(4096):
                                eng._health.park(np.full(HEALTH_WIDTH, j % 7 + i,
                                                         np.uint32))
                        launch(i)
                else:
                    launch(i)
                if i % 10 == 5:  # let a snapshot start after this launch
                    n_seen = len(seen)
                    deadline = time.monotonic() + 10
                    while len(seen) < n_seen + 2 and time.monotonic() < deadline:
                        time.sleep(0.001)
        finally:
            done.set()
            t.join(timeout=30)
            sys.setswitchinterval(switch)
        assert not t.is_alive()
        final = snapshot()
        if engine == "single":
            eng.close()
        expected = [0] * HEALTH_WIDTH
        for health in parked:
            for k, v in enumerate(np.asarray(health)):
                expected[k] += int(v)
        assert [final[k] for k in keys] == expected
        assert sum(expected[:4]) > 0  # the launches evicted or dropped
        assert all(a <= b for prev, cur in zip(seen, seen[1:])
                   for a, b in zip(prev, cur))
        assert sum(n for _, n in drained) == len(parked)
        by_snapshots = [n for name, n in drained if name == "snapshots" and n]
        assert len(by_snapshots) >= 4
        if drain == "inline":
            assert len([n for name, n in drained
                        if name != "snapshots" and n > 4096]) == 4


class TestSubmitWait:
    def test_one_sample_per_submit_and_it_covers_the_device(self):
        store = Store()

        def launch(blocks):
            return [np.array(b[2]) for b in blocks]

        def collect(token):
            time.sleep(0.02)  # a slow device
            return np.concatenate(token)

        loop = DispatchLoop(launch, collect, scope=store.scope("ratelimit"))
        try:
            for i in range(4):
                out = loop.submit(_rows(3, i))
                assert out.tolist() == [1, 1, 1]
        finally:
            loop.close()
        count, total = _hist(store, "ratelimit.dispatch.submit_wait_ms")
        assert count == 4 and total / count >= 15.0

    def test_owner_spans_cover_the_loop(self, tmp_path):
        """In a capture the owner thread's time is named by its loop
        spans, on one line, and the caller's wait by submit_wait."""
        store = Store()

        def launch(blocks):
            return [np.array(b[2]) for b in blocks]

        def collect(token):
            time.sleep(0.005)
            return np.concatenate(token)

        loop = DispatchLoop(launch, collect, scope=store.scope("ratelimit"))

        def body():
            time.sleep(0.06)  # the owner parks: wait_work
            for i in range(3):
                loop.submit(_rows(2, i))

        try:
            events = _capture(tmp_path, body)
        finally:
            loop.close()
        owner = {"ratelimit.dispatch." + n
                 for n in ("wait_work", "take", "launch", "redeem")}
        assert owner <= set(events)
        # this loop's owner launches; another test's idle loop in this
        # process may park and take, but never launches
        (line,) = {line for n in ("launch", "redeem")
                   for line, _s, _e in events["ratelimit.dispatch." + n]}
        assert all(any(ln == line for ln, _s, _e in events[n]) for n in owner)
        callers = {ln for ln, _s, _e in events["ratelimit.dispatch.submit_wait"]}
        assert len(callers) == 1 and line not in callers


def test_stats_flush_spans_each_generator(tmp_path):
    class SlabHealthStats:
        def generate_stats(self):
            time.sleep(0.002)

    store = Store()
    store.add_stat_generator(SlabHealthStats())
    events = _capture(tmp_path, store.flush)
    (flush,) = events["ratelimit.stats.flush"]
    (gen,) = events["ratelimit.stats.generate.slab_health"]
    assert flush[0] == gen[0] and flush[1] <= gen[1] <= gen[2] <= flush[2]


_CFG = (
    "domain: d\n"
    "descriptors:\n"
    "  - key: api\n"
    "    rate_limit:\n"
    "      unit: second\n"
    "      requests_per_unit: 4\n"
)


class _StaticRuntime:
    def snapshot(self):
        class Snap:
            def keys(self):
                return ["config.d"]

            def get(self, key):
                return _CFG

        return Snap()

    def add_update_callback(self, cb):
        pass


def test_request_thread_spans(tmp_path):
    """A gRPC request through the real service marks the handler, the
    matcher and the submit wait with the spans named for their
    histograms, all on the handler's thread."""
    from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache
    from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
    from api_ratelimit_tpu.pb import rls_v3
    from api_ratelimit_tpu.server.grpc_service import RateLimitServicerV3
    from api_ratelimit_tpu.service.ratelimit import RateLimitService
    from api_ratelimit_tpu.utils.timeutil import RealTimeSource

    class _Ctx:
        def time_remaining(self):
            return None

    store = Store()
    cache = TpuRateLimitCache(
        BaseRateLimiter(FakeTimeSource(1_000_000), near_limit_ratio=0.8),
        n_slots=1 << 10, buckets=(8,), max_batch=8, use_pallas=False,
        batch_window_seconds=0.0002, stats_scope=store.scope("ratelimit"),
    )
    scope = store.scope("ratelimit").scope("service")
    service = RateLimitService(runtime=_StaticRuntime(), cache=cache,
                               stats_scope=scope, time_source=RealTimeSource())
    servicer = RateLimitServicerV3(service, scope)
    req = rls_v3.RateLimitRequest(domain="d")
    req.descriptors.add().entries.add(key="api", value="u")
    try:
        assert servicer.ShouldRateLimit(req, _Ctx()).overall_code == 1  # compiled
        events = _capture(tmp_path, lambda: servicer.ShouldRateLimit(req, _Ctx()))
    finally:
        cache.close()
    names = ("ratelimit.service.transport.grpc", "ratelimit.service.host.matcher",
             "ratelimit.dispatch.submit_wait")
    (grpc,) = events[names[0]]
    for name in names[1:]:
        (inner,) = events[name]
        assert inner[0] == grpc[0] and grpc[1] <= inner[1] <= inner[2] <= grpc[2]
    assert _hist(store, "ratelimit.dispatch.submit_wait_ms")[0] == 2
