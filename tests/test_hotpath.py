"""Host-path vectorization pins: bucket-ladder precompile (no
first-request JIT compile), the zero-object row pipeline
(do_limit_resolved vs do_limit equivalence), scratch-block reuse in
both batching modes, and the host-stage histograms the bench's
host_split block reads."""

from __future__ import annotations

import threading

import numpy as np
import pytest

from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, TpuRateLimitCache, _Item
from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
from api_ratelimit_tpu.models import Code, Descriptor, RateLimitRequest, Unit
from api_ratelimit_tpu.stats import Store, TestSink
from api_ratelimit_tpu.utils import FakeTimeSource


class TestPrecompile:
    def test_ladder_fully_covered_and_slab_untouched(self):
        ts = FakeTimeSource(1000)
        eng = SlabDeviceEngine(
            time_source=ts,
            n_slots=1 << 10,
            buckets=(8, 16),
            use_pallas=False,
            precompile=True,
        )
        try:
            assert set(eng.precompiled) == {
                (bucket, dtype)
                for bucket in (8, 16)
                for dtype in ("uint8", "uint16", "uint32")
            }
            # the all-padding warmers must leave the slab bit-empty
            assert int(np.asarray(eng._state.count).sum()) == 0
            assert eng.health_snapshot()["live_slots"] == 0
            # and real traffic starts from a clean counter
            assert eng.submit(
                [_Item(fp=42, hits=1, limit=10, divider=60, jitter=0)]
            ) == [1]
        finally:
            eng.close()

    def test_no_first_request_jit_compile(self):
        """The acceptance pin: after precompile, the first real submit
        must be a jit cache HIT for every readback width the ladder can
        produce."""
        from api_ratelimit_tpu.ops import slab

        ts = FakeTimeSource(1000)
        eng = SlabDeviceEngine(
            time_source=ts,
            n_slots=1 << 10,
            buckets=(8,),
            use_pallas=False,
            precompile=True,
        )
        try:
            size_before = slab.slab_step_after._cache_size()
            # u8, u16, u32 readback widths, all inside bucket 8
            eng.submit([_Item(fp=1, hits=1, limit=10, divider=60, jitter=0)])
            eng.submit([_Item(fp=2, hits=1, limit=1000, divider=60, jitter=0)])
            eng.submit([_Item(fp=3, hits=1, limit=100_000, divider=60, jitter=0)])
            assert slab.slab_step_after._cache_size() == size_before
        finally:
            eng.close()

    def test_runner_precompiles_before_ready(self, tmp_path, monkeypatch):
        """TPU_PRECOMPILE=true: the ladder is compiled by the time the
        runner reports ready/healthy — a first request can never ride a
        compile."""
        from api_ratelimit_tpu.runner import Runner
        from api_ratelimit_tpu.settings import Settings

        config_dir = tmp_path / "current" / "ratelimit" / "config"
        config_dir.mkdir(parents=True)
        (config_dir / "basic.yaml").write_text(
            "domain: basic\n"
            "descriptors:\n"
            "  - key: key1\n"
            "    rate_limit: {unit: second, requests_per_unit: 50}\n"
        )
        settings = Settings(
            port=0,
            grpc_port=0,
            debug_port=0,
            use_statsd=False,
            runtime_path=str(tmp_path / "current"),
            runtime_subdirectory="ratelimit",
            backend_type="tpu",
            tpu_slab_slots=1 << 10,
            tpu_precompile=True,
            tpu_buckets="8",
            tpu_use_pallas=False,
            expiration_jitter_max_seconds=0,
            log_level="ERROR",
        )
        runner = Runner(settings, sink=TestSink())
        runner.run_background()
        try:
            assert runner.wait_ready(30.0)
            engine = runner.service._cache.engine
            assert set(engine.precompiled) == {
                (8, "uint8"), (8, "uint16"), (8, "uint32")
            }
        finally:
            runner.stop()


def _make_pair(local_cache_size=0, jitter_max=0, seed=7):
    """Two independent identical stacks: one driven through
    do_limit_resolved, one through legacy do_limit."""
    import random

    from api_ratelimit_tpu.limiter import LocalCache

    stacks = []
    for _ in range(2):
        ts = FakeTimeSource(1_000_000)
        local = LocalCache(local_cache_size, ts) if local_cache_size else None
        base = BaseRateLimiter(
            ts,
            jitter_rand=random.Random(seed),
            expiration_jitter_max_seconds=jitter_max,
            local_cache=local,
            near_limit_ratio=0.8,
        )
        cache = TpuRateLimitCache(
            base,
            n_slots=1 << 12,
            buckets=(8, 128),
            max_batch=1024,
            use_pallas=False,
        )
        stacks.append((ts, cache))
    return stacks


def _load_cfg(yaml_text):
    from api_ratelimit_tpu.config.loader import ConfigFile, load_config
    from api_ratelimit_tpu.stats.sinks import NullSink
    from api_ratelimit_tpu.stats.store import Store as _Store

    return load_config(
        [ConfigFile(name="config.t", contents=yaml_text)],
        _Store(NullSink()).scope("rl"),
    )


_CFG = """\
domain: d
descriptors:
  - key: api
    rate_limit: {unit: minute, requests_per_unit: 4}
  - key: free
  - key: staged
    rate_limit: {unit: hour, requests_per_unit: 2}
    shadow_mode: true
"""


class TestZeroObjectPipeline:
    @pytest.mark.parametrize("local_cache_size", [0, 256])
    def test_resolved_path_matches_legacy_path(self, local_cache_size):
        """Same request stream through do_limit_resolved and do_limit on
        twin stacks (one config each): identical codes, remaining,
        durations, throttle, and per-rule stats."""
        (ts_a, cache_a), (ts_b, cache_b) = _make_pair(local_cache_size)
        cfg_a, cfg_b = _load_cfg(_CFG), _load_cfg(_CFG)
        reqs = []
        for i in range(40):
            descs = (
                Descriptor.of(("api", f"u{i % 3}")),
                Descriptor.of(("free", "x")),
                Descriptor.of(("nomatch", "y")),
                Descriptor.of(("staged", f"u{i % 2}")),
            )
            reqs.append(RateLimitRequest(domain="d", descriptors=descs, hits_addend=1 + i % 2))
        try:
            for step, request in enumerate(reqs):
                resolved = [
                    cfg_a.compiled.resolve(request.domain, d)
                    for d in request.descriptors
                ]
                limits = [
                    cfg_b.get_limit(request.domain, d)
                    for d in request.descriptors
                ]
                ra = cache_a.do_limit_resolved(request, resolved)
                rb = cache_b.do_limit(request, limits)
                assert ra.throttle_millis == rb.throttle_millis, step
                for i, (sa, sb) in enumerate(
                    zip(ra.descriptor_statuses, rb.descriptor_statuses)
                ):
                    assert sa.code == sb.code, (step, i)
                    assert sa.limit_remaining == sb.limit_remaining, (step, i)
                    assert sa.duration_until_reset == sb.duration_until_reset, (step, i)
                if step % 10 == 9:
                    ts_a.advance(30)
                    ts_b.advance(30)
            for key in ("d.api", "d.staged"):
                la = cfg_a.get_limit("d", Descriptor.of((key.split(".")[1], "u0")))
                lb = cfg_b.get_limit("d", Descriptor.of((key.split(".")[1], "u0")))
                assert la.stats.total_hits.value() == lb.stats.total_hits.value()
                assert la.stats.over_limit.value() == lb.stats.over_limit.value()
                assert la.stats.near_limit.value() == lb.stats.near_limit.value()
                assert la.stats.shadow_mode.value() == lb.stats.shadow_mode.value()
        finally:
            cache_a.close()
            cache_b.close()

    def test_jitter_stream_identical(self):
        """The expiry-jitter RNG must be consumed in the same per-
        descriptor order on both paths (seeded streams stay aligned)."""
        (ts_a, cache_a), (ts_b, cache_b) = _make_pair(jitter_max=300, seed=42)
        cfg_a, cfg_b = _load_cfg(_CFG), _load_cfg(_CFG)
        request = RateLimitRequest(
            domain="d",
            descriptors=(
                Descriptor.of(("api", "u")),
                Descriptor.of(("staged", "u")),
            ),
        )
        try:
            for _ in range(5):
                resolved = [
                    cfg_a.compiled.resolve("d", d) for d in request.descriptors
                ]
                limits = [cfg_b.get_limit("d", d) for d in request.descriptors]
                cache_a.do_limit_resolved(request, resolved)
                cache_b.do_limit(request, limits)
            # aligned RNG streams => identical next draw
            assert cache_a._base.jitter_rand.random() == cache_b._base.jitter_rand.random()
        finally:
            cache_a.close()
            cache_b.close()

    def test_service_uses_fast_path_and_flags_work(self):
        """Through RateLimitService: a cache with do_limit_resolved takes
        the resolved path (do_limit untouched); a cache without it takes
        do_limit — and both answer the same."""
        from api_ratelimit_tpu.service.ratelimit import RateLimitService
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        class StaticRuntime:
            def snapshot(self):
                class Snap:
                    def keys(self):
                        return ["config.d"]

                    def get(self, key):
                        return _CFG

                return Snap()

            def add_update_callback(self, cb):
                pass

        class PerObjectOnly:
            """The per-object cache surface alone (as the memory, Redis
            and Memcache backends expose it)."""

            def __init__(self, inner):
                self.do_limit = inner.do_limit
                self.flush = inner.flush
                self.close = inner.close

        answers = {}
        for fast in (True, False):
            ts = FakeTimeSource(1_000_000)
            base = BaseRateLimiter(ts, near_limit_ratio=0.8)
            cache = TpuRateLimitCache(
                base, n_slots=1 << 10, buckets=(8,), max_batch=8, use_pallas=False
            )
            calls = {"resolved": 0, "legacy": 0}
            real_resolved = cache.do_limit_resolved
            real_legacy = cache.do_limit
            cache.do_limit_resolved = lambda *a, **k: (
                calls.__setitem__("resolved", calls["resolved"] + 1),
                real_resolved(*a, **k),
            )[1]
            cache.do_limit = lambda *a, **k: (
                calls.__setitem__("legacy", calls["legacy"] + 1),
                real_legacy(*a, **k),
            )[1]
            store = Store(TestSink())
            service = RateLimitService(
                runtime=StaticRuntime(),
                cache=cache if fast else PerObjectOnly(cache),
                stats_scope=store.scope("ratelimit").scope("service"),
                time_source=RealTimeSource(),
            )
            request = RateLimitRequest(
                domain="d", descriptors=(Descriptor.of(("api", "u")),)
            )
            got = []
            for _ in range(5):
                code, statuses, _ = service.should_rate_limit(request)
                assert statuses[0].current_limit.requests_per_unit == 4
                got.append((code, statuses[0].code, statuses[0].limit_remaining))
            if fast:
                assert calls == {"resolved": 5, "legacy": 0}
            else:
                assert calls == {"resolved": 0, "legacy": 5}
            answers[fast] = got
            cache.close()
        assert answers[True] == answers[False]
        assert answers[True][0][0] == Code.OK
        assert answers[True][-1][0] == Code.OVER_LIMIT

    def test_host_stage_histograms_recorded(self):
        """ratelimit.host.{key_compose_ms,response_ms} and
        ratelimit.service.host.matcher_ms — the sources for the bench's
        host_split block — record once per request."""
        from api_ratelimit_tpu.service.ratelimit import RateLimitService
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        class StaticRuntime:
            def snapshot(self):
                class Snap:
                    def keys(self):
                        return ["config.d"]

                    def get(self, key):
                        return _CFG

                return Snap()

            def add_update_callback(self, cb):
                pass

        store = Store(TestSink())
        ts = FakeTimeSource(1_000_000)
        base = BaseRateLimiter(ts, near_limit_ratio=0.8)
        cache = TpuRateLimitCache(
            base,
            n_slots=1 << 10,
            buckets=(8,),
            max_batch=8,
            use_pallas=False,
            stats_scope=store.scope("ratelimit"),
        )
        service = RateLimitService(
            runtime=StaticRuntime(),
            cache=cache,
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=RealTimeSource(),
        )
        request = RateLimitRequest(
            domain="d", descriptors=(Descriptor.of(("api", "u")),)
        )
        for _ in range(3):
            service.should_rate_limit(request)
        hists = store.metrics_snapshot()["histograms"]
        for name in (
            "ratelimit.host.key_compose_ms",
            "ratelimit.host.response_ms",
            "ratelimit.service.host.matcher_ms",
        ):
            assert hists[name]["count"] == 3, name
        cache.close()


class TestRowRing:
    """Callers hand in a thread-local scratch block they reuse on the next
    request: the dispatch loop copies it into the caller's submit ring,
    and direct mode executes it before submit returns."""

    def test_engine_scratch_reuse_is_safe_under_concurrency(self):
        """do_limit_resolved from many threads over the windowed engine:
        each caller's counts are exact (thread-local scratch + ring copy
        never cross-contaminate)."""
        self._scratch_reuse(batch_window_seconds=0.002)

    def test_engine_scratch_reuse_is_safe_in_direct_mode(self):
        """The same over direct mode (TPU_BATCH_WINDOW=0): callers take
        turns at the direct lock, each executing its own scratch."""
        self._scratch_reuse(batch_window_seconds=0.0)

    @staticmethod
    def _scratch_reuse(batch_window_seconds):
        cfg = _load_cfg(
            "domain: d\n"
            "descriptors:\n"
            "  - key: api\n"
            "    rate_limit: {unit: hour, requests_per_unit: 1000000}\n"
        )
        ts = FakeTimeSource(1_000_000)
        base = BaseRateLimiter(ts, near_limit_ratio=0.8)
        cache = TpuRateLimitCache(
            base,
            n_slots=1 << 12,
            batch_window_seconds=batch_window_seconds,
            buckets=(8, 128),
            max_batch=128,
            use_pallas=False,
        )
        assert (cache.engine.dispatch_loop is None) == (
            batch_window_seconds == 0
        )
        per_thread = 25
        remaining: dict[int, list] = {}

        def worker(tid):
            request = RateLimitRequest(
                domain="d", descriptors=(Descriptor.of(("api", f"u{tid}")),)
            )
            resolved = [cfg.compiled.resolve("d", d) for d in request.descriptors]
            got = []
            for _ in range(per_thread):
                resp = cache.do_limit_resolved(request, resolved)
                got.append(resp.descriptor_statuses[0].limit_remaining)
            remaining[tid] = got

        threads = [
            threading.Thread(target=worker, args=(tid,)) for tid in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20.0)
        cache.close()
        # per-key counters are disjoint: each thread must see exactly
        # 1M-1, 1M-2, ... in order
        for tid, got in remaining.items():
            assert got == [1_000_000 - i for i in range(1, per_thread + 1)], tid
