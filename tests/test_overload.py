"""Overload admission-control suite (the pressure-side twin of test_chaos):
deadline propagation (expired work never reaches a device launch), the
bounded batcher queue, the latency brownout with enter/exit hysteresis,
each shed posture at the service level and over real gRPC, slab-saturation
watermarks with the expired-slot sweep, and drain-under-load shedding the
throttle sleep instead of pinning workers.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from api_ratelimit_tpu.backends.batcher import MicroBatcher
from api_ratelimit_tpu.backends.overload import (
    SHED_MODE_ALLOW,
    SHED_MODE_DENY,
    SHED_MODE_UNAVAILABLE,
    AdmissionController,
    BrownoutError,
    OverloadError,
    QueueFullError,
)
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, _Item
from api_ratelimit_tpu.limiter.cache import DeadlineExceededError
from api_ratelimit_tpu.models import Code, Descriptor, RateLimitRequest
from api_ratelimit_tpu.models.response import DescriptorStatus, DoLimitResponse
from api_ratelimit_tpu.service import RateLimitService
from api_ratelimit_tpu.stats import Store, TestSink
from api_ratelimit_tpu.testing.faults import FaultInjector, parse_fault_spec
from api_ratelimit_tpu.utils import FakeTimeSource
from api_ratelimit_tpu.utils.deadline import deadline_scope, time_remaining


# -- harness (mirrors test_service / test_chaos) -----------------------------


class _FakeRuntime:
    def __init__(self, files):
        self._files = dict(files)

    def snapshot(self):
        files = self._files

        class Snap:
            def keys(self):
                return list(files)

            def get(self, key):
                return files[key]

        return Snap()

    def add_update_callback(self, cb):
        pass


class _FakeCache:
    def __init__(self):
        self.calls = 0
        self.raise_error = None
        self.next_throttle = 0

    def do_limit(self, request, limits):
        self.calls += 1
        if self.raise_error is not None:
            raise self.raise_error
        return DoLimitResponse(
            descriptor_statuses=[
                DescriptorStatus(code=Code.OK) for _ in request.descriptors
            ],
            throttle_millis=self.next_throttle,
        )

    def flush(self):
        pass


OVERLOAD_YAML = """
domain: overload
descriptors:
  - key: k
    value: v
    rate_limit: {unit: minute, requests_per_unit: 10}
"""

SLEEPY_YAML = """
domain: sleepy
descriptors:
  - key: k
    value: v
    rate_limit: {unit: minute, requests_per_unit: 10}
    sleep_on_throttle: true
    report_details: true
"""


def _req(domain="overload"):
    return RateLimitRequest(
        domain=domain,
        descriptors=(Descriptor.of(("k", "v")),),
        hits_addend=1,
    )


def _service(store, overload=None, cache=None, files=None, **kw):
    cache = cache or _FakeCache()
    svc = RateLimitService(
        runtime=_FakeRuntime(
            files if files is not None else {"config.ov": OVERLOAD_YAML}
        ),
        cache=cache,
        stats_scope=store.scope("ratelimit").scope("service"),
        time_source=FakeTimeSource(1_000_000),
        overload=overload,
        **kw,
    )
    return svc, cache


def _controller(store, **kw):
    kw.setdefault("shed_mode", SHED_MODE_UNAVAILABLE)
    return AdmissionController(scope=store.scope("ratelimit"), **kw)


def _brownout(controller):
    """Force the controller into brownout via its own EWMA machinery."""
    for _ in range(8):
        controller.observe_queue_wait(1e6)
    assert controller.brownout


# -- deadline propagation ----------------------------------------------------


class TestDeadlineContext:
    def test_no_scope_means_no_deadline(self):
        assert time_remaining() is None

    def test_scope_sets_and_restores(self):
        with deadline_scope(5.0):
            remaining = time_remaining()
            assert remaining is not None and 4.0 < remaining <= 5.0
            with deadline_scope(0.1):
                assert time_remaining() <= 0.1
            assert time_remaining() > 4.0
        assert time_remaining() is None


def _zeros_exec(blocks):
    """Direct-mode executor stub: one 0 verdict per row."""
    return np.zeros(sum(b.shape[1] for b in blocks), dtype=np.uint32)


def _one_row():
    return np.zeros((6, 1), dtype=np.uint32)


class TestBatcherDeadline:
    def test_direct_mode_expired_sheds_before_execute(self):
        executed = []
        b = MicroBatcher(lambda blocks: executed.append(blocks) or _zeros_exec(blocks))
        with deadline_scope(-0.001):
            with pytest.raises(DeadlineExceededError):
                b.submit(_one_row())
        assert executed == []
        assert b.deadline_drops == 1
        # without a deadline the same submit executes
        assert b.submit(_one_row()).tolist() == [0]

    def test_service_sheds_expired_before_cache(self, test_store):
        store, _ = test_store
        controller = _controller(store)
        svc, cache = _service(store, overload=controller)
        with deadline_scope(-0.001):
            with pytest.raises(DeadlineExceededError):
                svc.should_rate_limit(_req())
        assert cache.calls == 0  # shed before any backend work
        snap = store.debug_snapshot()
        assert snap["ratelimit.overload.deadline_expired"] == 1
        # not a backend failure: redis_error stays untouched
        assert (
            snap["ratelimit.service.call.should_rate_limit.redis_error"] == 0
        )


# -- bounded queue + fault site ----------------------------------------------


class TestQueueBound:
    def test_injected_queue_full_fault(self):
        faults = FaultInjector(parse_fault_spec("batcher.submit:queue_full:1.0"))
        b = MicroBatcher(_zeros_exec, fault_injector=faults)
        with pytest.raises(QueueFullError, match="injected"):
            b.submit(_one_row())
        assert faults.fired() == {"batcher.submit:queue_full": 1}

    def test_injected_delay_stalls_submit(self):
        slept = []
        faults = FaultInjector(
            parse_fault_spec("batcher.submit:delay_ms:250"), sleep=slept.append
        )
        b = MicroBatcher(_zeros_exec, fault_injector=faults)
        assert b.submit(_one_row()).tolist() == [0]
        assert slept == [0.25]


# -- brownout hysteresis -----------------------------------------------------


class TestBrownoutHysteresis:
    def test_enter_and_exit_with_hysteresis(self, test_store):
        store, _ = test_store
        c = _controller(
            store,
            brownout_target_ms=5.0,
            brownout_exit_ms=2.0,
            ewma_alpha=1.0,  # EWMA == last sample: deterministic
        )
        assert not c.brownout
        c.observe_queue_wait(10.0)
        assert c.brownout  # 10 > 5: enter
        c.observe_queue_wait(3.0)
        assert c.brownout  # 3 in (2, 5]: hysteresis holds it in
        c.observe_queue_wait(1.0)
        assert not c.brownout  # 1 < 2: exit
        snap = store.debug_snapshot()
        assert snap["ratelimit.overload.brownout"] == 0
        assert snap["ratelimit.overload.queue_wait_ewma_us"] == 1000

    def test_default_exit_is_half_target(self, test_store):
        store, _ = test_store
        c = _controller(store, brownout_target_ms=10.0, ewma_alpha=1.0)
        c.observe_queue_wait(11.0)
        assert c.brownout
        c.observe_queue_wait(6.0)  # above 10/2: still browned out
        assert c.brownout
        c.observe_queue_wait(4.0)  # below 10/2: out
        assert not c.brownout

    def test_degraded_reason_while_browned_out(self, test_store):
        store, _ = test_store
        c = _controller(store, brownout_target_ms=5.0, ewma_alpha=1.0)
        assert c.degraded_reason() is None
        c.observe_queue_wait(50.0)
        assert "brownout" in c.degraded_reason()

    def test_batcher_sheds_during_brownout(self, test_store):
        store, _ = test_store
        c = _controller(store, brownout_target_ms=1.0, ewma_alpha=1.0)
        _brownout(c)
        b = MicroBatcher(_zeros_exec, overload=c)
        with pytest.raises(BrownoutError):
            b.submit(_one_row())

    def test_validation(self, test_store):
        store, _ = test_store
        with pytest.raises(ValueError, match="hysteresis"):
            _controller(
                store, brownout_target_ms=5.0, brownout_exit_ms=5.0
            )
        with pytest.raises(ValueError, match="alpha"):
            _controller(store, ewma_alpha=0.0)
        with pytest.raises(ValueError, match="shed mode"):
            AdmissionController(shed_mode="nope")


# -- shed postures at the service level --------------------------------------


class TestShedPostures:
    def _browned_service(self, store, mode):
        controller = _controller(
            store, shed_mode=mode, brownout_target_ms=1.0, ewma_alpha=1.0
        )
        _brownout(controller)
        svc, cache = _service(store, overload=controller)
        return svc, cache, controller

    def test_allow_posture_fails_open_with_shed_header(self, test_store):
        store, sink = test_store
        svc, cache, controller = self._browned_service(store, SHED_MODE_ALLOW)
        overall, statuses, headers = svc.should_rate_limit(_req())
        assert overall == Code.OK
        assert statuses[0].code == Code.OK
        assert any(
            h.key == "x-ratelimit-shed" and h.value == "brownout"
            for h in headers
        )
        assert cache.calls == 0  # shed pre-dispatch
        store.flush()
        assert sink.counters["ratelimit.overload.shed"] == 1
        assert sink.counters["ratelimit.overload.brownout_shed"] == 1
        assert sink.gauges["ratelimit.overload.shedding"] == 1
        assert "overload" in controller.degraded_reason()

    def test_deny_posture_answers_over_limit(self, test_store):
        store, sink = test_store
        svc, _, _ = self._browned_service(store, SHED_MODE_DENY)
        overall, statuses, _ = svc.should_rate_limit(_req())
        assert overall == Code.OVER_LIMIT
        assert statuses[0].code == Code.OVER_LIMIT
        store.flush()
        assert sink.counters["ratelimit.overload.shed"] == 1

    def test_unavailable_posture_raises(self, test_store):
        store, sink = test_store
        svc, _, _ = self._browned_service(store, SHED_MODE_UNAVAILABLE)
        with pytest.raises(BrownoutError):
            svc.should_rate_limit(_req())
        store.flush()
        # counted as shed, NOT as a backend failure
        assert sink.counters["ratelimit.overload.shed"] == 1
        assert (
            sink.counters.get(
                "ratelimit.service.call.should_rate_limit.redis_error", 0
            )
            == 0
        )

    def test_backend_overload_error_answers_by_posture(self, test_store):
        """An OverloadError surfacing from the cache layer (rather than
        the batcher's own admission check) is a shed, not a backend
        failure: the posture answers it."""
        store, sink = test_store
        controller = _controller(store, shed_mode=SHED_MODE_ALLOW)
        svc, cache = _service(store, overload=controller)
        cache.raise_error = QueueFullError("ring full")
        overall, _, headers = svc.should_rate_limit(_req())
        assert overall == Code.OK
        assert any(
            h.key == "x-ratelimit-shed" and h.value == "queue_full"
            for h in headers
        )
        store.flush()
        assert sink.counters["ratelimit.overload.queue_full"] == 1

    def test_no_controller_reraises_overload(self, test_store):
        store, _ = test_store
        svc, cache = _service(store, overload=None)
        cache.raise_error = QueueFullError("full")
        with pytest.raises(OverloadError):
            svc.should_rate_limit(_req())

    def test_shed_state_clears_on_next_admitted_request(self, test_store):
        store, sink = test_store
        controller = _controller(store, shed_mode=SHED_MODE_ALLOW)
        svc, cache = _service(store, overload=controller)
        cache.raise_error = QueueFullError("full")
        svc.should_rate_limit(_req())
        assert controller.degraded_reason() is not None
        cache.raise_error = None
        svc.should_rate_limit(_req())
        assert controller.degraded_reason() is None
        store.flush()
        assert sink.gauges["ratelimit.overload.shedding"] == 0

    def test_healthcheck_stacks_overload_and_fallback_probes(self, test_store):
        from api_ratelimit_tpu.server.health import HealthChecker

        store, _ = test_store
        controller = _controller(store, shed_mode=SHED_MODE_ALLOW)
        health = HealthChecker()
        health.add_degraded_probe(controller.degraded_reason)
        assert health.http_response() == (200, "OK")
        controller.note_shed(QueueFullError("full"))
        status, body = health.http_response()
        assert status == 200  # shedding still serves; never drained
        assert body.startswith("OK") and "overload" in body
        controller.note_ok()
        assert health.http_response() == (200, "OK")


# -- throttle-sleep hardening ------------------------------------------------


class TestSleepShed:
    def test_draining_skips_sleep_and_counts(self, test_store):
        store, sink = test_store
        svc, cache = _service(
            store,
            files={"config.sleepy": SLEEPY_YAML},
            max_sleeping_routines=2,
            draining_probe=lambda: True,
        )
        cache.next_throttle = 1500
        _, _, headers = svc.should_rate_limit(_req(domain="sleepy"))
        assert svc._time_source.sleeps == []  # never pinned a worker
        # not slept server-side: the throttle header reaches the client
        assert any(h.key == "x-ratelimit-throttle-ms" for h in headers)
        store.flush()
        assert (
            sink.counters["ratelimit.service.call.should_rate_limit.sleep_shed"]
            == 1
        )

    def test_exhausted_semaphore_counts_sleep_shed(self, test_store):
        store, sink = test_store
        svc, cache = _service(
            store,
            files={"config.sleepy": SLEEPY_YAML},
            max_sleeping_routines=1,
        )
        cache.next_throttle = 1500
        assert svc._sleeper_semaphore.acquire(blocking=False)
        try:
            svc.should_rate_limit(_req(domain="sleepy"))
        finally:
            svc._sleeper_semaphore.release()
        assert svc._time_source.sleeps == []
        store.flush()
        assert (
            sink.counters["ratelimit.service.call.should_rate_limit.sleep_shed"]
            == 1
        )

    def test_not_draining_still_sleeps(self, test_store):
        store, _ = test_store
        svc, cache = _service(
            store,
            files={"config.sleepy": SLEEPY_YAML},
            max_sleeping_routines=2,
            draining_probe=lambda: False,
        )
        cache.next_throttle = 1500
        svc.should_rate_limit(_req(domain="sleepy"))
        assert svc._time_source.sleeps == [1.5]


# -- slab watermarks ---------------------------------------------------------


def _engine(ts, **kw):
    kw.setdefault("n_slots", 1 << 10)
    kw.setdefault("buckets", (128, 1024))
    kw.setdefault("max_batch", 1024)
    kw.setdefault("use_pallas", False)
    return SlabDeviceEngine(time_source=ts, **kw)


def _fill(engine, n, divider=60, jitter=300):
    # structured fingerprints with pairwise-distinct (set, way-preference)
    # under the default geometry (1024 slots / 128 ways = 8 sets): fp_lo
    # walks the sets, fp_hi bits [7, 14) (the rotation source,
    # ops/slab.py _choose_ways) walk the ways within each set — so a
    # ONE-batch fill deterministically creates n live rows instead of
    # dropping a handful to in-batch way contention
    items = [
        _Item(
            fp=((((i + 1) >> 3) << 39) | (i + 1)),
            hits=1,
            limit=1000,
            divider=divider,
            jitter=jitter,
        )
        for i in range(n)
    ]
    engine.submit(items)


class TestSlabWatermarks:
    def test_high_watermark_is_pure_observability(self):
        """The pressure watermark raises the degraded probe and NOTHING
        else: no sweep pass, no admission shed — the set-associative scan
        absorbs pressure by evicting least-valuable ways in-kernel."""
        ts = FakeTimeSource(1_000_000)
        engine = _engine(ts, watermark_high=0.05)
        _fill(engine, 100)  # occupancy ~0.098 >= 0.05
        snap = engine.health_snapshot()
        assert snap["watermark"] == 1
        assert snap["live_slots"] == 100
        assert "sweeps" not in snap  # the stop-the-world sweep is gone
        assert "pressure" in engine.watermark_reason()
        # rows stay TTL-pinned past their window end — nothing reclaims
        # them eagerly; the eviction scan reuses them lazily, per insert
        ts.advance(120)
        snap = engine.health_snapshot()
        assert snap["live_slots"] == 100
        assert snap["watermark"] == 1
        # TTL (window 60s + jitter 300s) passes: occupancy drains itself
        ts.advance(300)
        snap = engine.health_snapshot()
        assert snap["live_slots"] == 0
        assert snap["watermark"] == 0
        assert engine.watermark_reason() is None

    def test_full_occupancy_never_sheds_admission(self):
        """The old critical-watermark cliff is gone: at (and past) 100%
        live occupancy every submit still answers — colliding inserts
        evict the least-valuable way in-kernel, and the eviction mix is
        the only signal pressure emits."""
        ts = FakeTimeSource(1_000_000)
        # 128 slots = exactly one 128-way set: wave A fills it completely
        engine = _engine(ts, n_slots=128, buckets=(128,), max_batch=128)
        for i in range(128):
            assert engine.submit(
                [_Item(fp=i + 1, hits=1, limit=1000, divider=60, jitter=300)]
            ) == [1]
        snap = engine.health_snapshot()
        assert snap["live_slots"] == 128
        assert snap["occupancy"] == 1.0
        # wave B: 64 NEW keys against the full set — each submit answers
        # (count restarts at 1, the fail-open posture) by evicting a live
        # way, and every displacement is counted, never silent
        for i in range(64):
            assert engine.submit(
                [_Item(fp=1000 + i, hits=1, limit=1000, divider=60, jitter=300)]
            ) == [1]
        snap = engine.health_snapshot()
        assert snap["occupancy"] == 1.0  # still full, still serving
        assert snap["evictions_live"] == 64
        assert snap["watermark"] == 0  # no watermark configured: no alarm

    def test_watermarks_off_by_default(self):
        ts = FakeTimeSource(1_000_000)
        engine = _engine(ts)
        _fill(engine, 100)
        snap = engine.health_snapshot()
        assert snap["watermark"] == 0
        assert engine.watermark_reason() is None

    def test_critical_watermark_kwarg_is_gone(self):
        """The shed path is deleted, not deprecated-but-alive: the engine
        no longer even accepts the knob (settings translate a configured
        SLAB_WATERMARK_CRITICAL into a boot-time deprecation warning)."""
        ts = FakeTimeSource(1_000_000)
        with pytest.raises(TypeError):
            _engine(ts, watermark_high=0.9, watermark_critical=0.95)


# -- settings ----------------------------------------------------------------


class TestOverloadSettings:
    def test_env_parsing(self):
        from api_ratelimit_tpu.settings import new_settings

        s = new_settings(
            {
                "OVERLOAD_SHED_MODE": "deny",
                "OVERLOAD_MAX_QUEUE": "8192",
                "OVERLOAD_BROWNOUT_TARGET_MS": "5.5",
                "OVERLOAD_BROWNOUT_EXIT_MS": "2",
                "OVERLOAD_EWMA_ALPHA": "0.5",
                "OVERLOAD_DEADLINE_PROPAGATION": "false",
                "SLAB_WATERMARK_HIGH": "0.85",
            }
        )
        assert s.shed_mode() == "deny"
        assert s.overload_max_queue == 8192
        assert s.overload_brownout_target_ms == 5.5
        assert s.overload_brownout_exit_ms == 2.0
        assert s.overload_ewma_alpha == 0.5
        assert s.overload_deadline_propagation is False
        assert s.slab_watermark() == 0.85

    def test_defaults_are_inert(self):
        from api_ratelimit_tpu.settings import new_settings

        s = new_settings({})
        assert s.shed_mode() == SHED_MODE_UNAVAILABLE
        assert s.overload_max_queue == 0
        assert s.overload_brownout_target_ms == 0.0
        assert s.overload_deadline_propagation is True
        assert s.slab_watermark() == 0.0

    def test_junk_shed_mode_fails_boot(self):
        from api_ratelimit_tpu.settings import new_settings

        s = new_settings({"OVERLOAD_SHED_MODE": "yolo"})
        with pytest.raises(ValueError, match="OVERLOAD_SHED_MODE"):
            s.shed_mode()

    def test_junk_watermarks_fail_boot(self):
        from api_ratelimit_tpu.settings import new_settings

        with pytest.raises(ValueError, match="SLAB_WATERMARK"):
            new_settings({"SLAB_WATERMARK_HIGH": "1.5"}).slab_watermark()

    def test_critical_watermark_deprecated_not_fatal(self, caplog):
        """An old deployment config carrying SLAB_WATERMARK_CRITICAL (even
        one the old validator would have rejected as misordered) keeps
        booting: the knob is accepted-and-ignored with one warning line."""
        import logging

        from api_ratelimit_tpu.settings import new_settings

        s = new_settings(
            {"SLAB_WATERMARK_HIGH": "0.9", "SLAB_WATERMARK_CRITICAL": "0.5"}
        )
        assert s.slab_watermark() == 0.9  # no ordering validation, no raise
        log = logging.getLogger("test.deprecations")
        with caplog.at_level(logging.WARNING):
            s.warn_deprecated_knobs(log)
        assert any(
            "SLAB_WATERMARK_CRITICAL is deprecated" in r.message
            for r in caplog.records
        )
        # unset: silent
        caplog.clear()
        with caplog.at_level(logging.WARNING):
            new_settings({}).warn_deprecated_knobs(log)
        assert not caplog.records

    def test_queue_full_fault_kind_parses(self):
        rules = parse_fault_spec("batcher.submit:queue_full:0.5")
        assert rules[0].kind == "queue_full"
        with pytest.raises(ValueError, match="probability"):
            parse_fault_spec("batcher.submit:queue_full:2.0")


# -- full stack over real gRPC -----------------------------------------------


class TestFullStackOverload:
    """The acceptance scenario: batcher stalled/filled via fault injection,
    requests past the watermark answered within their deadline by the
    configured posture, with overload stats + degraded healthcheck body."""

    def _boot(self, tmp_path, **settings_kw):
        from api_ratelimit_tpu.runner import Runner
        from api_ratelimit_tpu.settings import Settings

        config_dir = tmp_path / "current" / "rl" / "config"
        config_dir.mkdir(parents=True, exist_ok=True)
        (config_dir / "c.yaml").write_text(
            "domain: overload\n"
            "descriptors:\n"
            "  - key: one\n"
            "    rate_limit: {unit: minute, requests_per_unit: 100}\n"
            "  - key: sleepy\n"
            "    rate_limit: {unit: minute, requests_per_unit: 1}\n"
            "    sleep_on_throttle: true\n"
        )
        settings = Settings(
            port=0,
            grpc_port=0,
            debug_port=0,
            use_statsd=False,
            runtime_path=str(tmp_path / "current"),
            runtime_subdirectory="rl",
            backend_type="tpu",
            tpu_slab_slots=1 << 12,
            tpu_use_pallas=False,
            expiration_jitter_max_seconds=0,
            log_level="ERROR",
            **settings_kw,
        )
        runner = Runner(settings, sink=TestSink())
        runner.run_background()
        assert runner.wait_ready(10.0)
        return runner

    def _grpc_request(self, key="one"):
        from api_ratelimit_tpu.pb import rls_v3

        request = rls_v3.RateLimitRequest(domain="overload")
        d = request.descriptors.add()
        d.entries.add(key=key, value="x")
        return request

    def _healthcheck(self, runner):
        import urllib.request

        with urllib.request.urlopen(
            f"http://localhost:{runner.server.http_port}/healthcheck",
            timeout=5,
        ) as resp:
            return resp.status, resp.read().decode()

    def test_queue_full_shed_allow_posture(self, tmp_path):
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc, rls_v3

        runner = self._boot(
            tmp_path,
            overload_shed_mode="allow",
            fault_inject="batcher.submit:queue_full:1.0",
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                t0 = time.monotonic()
                responses = [
                    stub.ShouldRateLimit(self._grpc_request(), timeout=5.0)
                    for _ in range(3)
                ]
                elapsed = time.monotonic() - t0
            # every shed answered OK, within the deadline, carrying the
            # shed header
            assert elapsed < 5.0
            for resp in responses:
                assert resp.overall_code == rls_v3.RateLimitResponse.OK
                assert any(
                    h.key == "x-ratelimit-shed"
                    for h in resp.response_headers_to_add
                )
            snap = runner.stats_store.debug_snapshot()
            assert snap["ratelimit.overload.shed"] == 3
            assert snap["ratelimit.overload.queue_full"] == 3
            assert snap["ratelimit.overload.shedding"] == 1
            status, body = self._healthcheck(runner)
            assert status == 200 and "overload" in body
            # chaos ends: traffic admits normally, shed state clears
            runner.fault_injector.clear()
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                resp = stub.ShouldRateLimit(self._grpc_request(), timeout=5.0)
            assert resp.overall_code == rls_v3.RateLimitResponse.OK
            assert not resp.response_headers_to_add
            status, body = self._healthcheck(runner)
            assert (status, body) == (200, "OK")
        finally:
            runner.stop()

    def test_queue_full_shed_unavailable_posture(self, tmp_path):
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc

        runner = self._boot(
            tmp_path,
            overload_shed_mode="unavailable",
            fault_inject="batcher.submit:queue_full:1.0",
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                with pytest.raises(grpc.RpcError) as err:
                    stub.ShouldRateLimit(self._grpc_request(), timeout=5.0)
            # UNAVAILABLE: the Envoy-retriable shed class
            assert err.value.code() == grpc.StatusCode.UNAVAILABLE
            snap = runner.stats_store.debug_snapshot()
            assert snap["ratelimit.overload.shed"] == 1
        finally:
            runner.stop()

    def test_queue_full_shed_deny_posture(self, tmp_path):
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc, rls_v3

        runner = self._boot(
            tmp_path,
            overload_shed_mode="deny",
            fault_inject="batcher.submit:queue_full:1.0",
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                resp = stub.ShouldRateLimit(self._grpc_request(), timeout=5.0)
            assert resp.overall_code == rls_v3.RateLimitResponse.OVER_LIMIT
        finally:
            runner.stop()

    def test_deadline_exceeded_full_stack(self, tmp_path):
        """A stalled batcher (injected delay) + a short client deadline:
        the request resolves as DEADLINE_EXCEEDED quickly and the drop is
        counted — never a late answer, never an unbounded wait."""
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc

        runner = self._boot(
            tmp_path, fault_inject="batcher.submit:delay_ms:400"
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                t0 = time.monotonic()
                with pytest.raises(grpc.RpcError) as err:
                    stub.ShouldRateLimit(self._grpc_request(), timeout=0.15)
                elapsed = time.monotonic() - t0
            assert err.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
            assert elapsed < 5.0
            # the server-side drop lands slightly after the client timeout
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                snap = runner.stats_store.debug_snapshot()
                if snap.get("ratelimit.overload.deadline_expired", 0) >= 1:
                    break
                time.sleep(0.05)
            assert snap["ratelimit.overload.deadline_expired"] >= 1
        finally:
            runner.stop()

    def test_drain_under_load_sheds_sleep(self, tmp_path):
        """Drain-under-load: once health flips for shutdown, a
        sleep_on_throttle request returns immediately (sleep_shed) instead
        of pinning a worker for the pacing sleep."""
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc

        runner = self._boot(tmp_path, max_sleeping_routines=4)
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                # drain: health goes NOT_SERVING, but in-flight/straggler
                # traffic is still answered — without the pacing sleep
                runner.server.health.fail()
                t0 = time.monotonic()
                stub.ShouldRateLimit(
                    self._grpc_request(key="sleepy"), timeout=10.0
                )
                elapsed = time.monotonic() - t0
            assert elapsed < 5.0  # limit 1/min: an un-shed sleep is >> this
            snap = runner.stats_store.debug_snapshot()
            assert (
                snap["ratelimit.service.call.should_rate_limit.sleep_shed"]
                >= 1
            )
        finally:
            runner.stop()


# -- windowed / direct mode parity -------------------------------------------


class TestDispatchLoopOverloadParity:
    """The dispatch loop (windowed mode, backends/dispatch.py) and direct
    mode (backends/batcher.py) share one admission contract: expired work
    never reaches the device, the shared batcher.submit chaos site sheds
    identically, and every shed posture answers the same wire response in
    both modes. `arm` True is the dispatch loop, False direct mode."""

    @staticmethod
    def _real_cache(store, arm, **kw):
        from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache
        from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter

        base = BaseRateLimiter(FakeTimeSource(1_000_000), near_limit_ratio=0.8)
        return TpuRateLimitCache(
            base,
            n_slots=1 << 12,
            batch_window_seconds=0.002 if arm else 0.0,
            buckets=(8, 128),
            max_batch=128,
            use_pallas=False,
            stats_scope=store.scope("ratelimit"),
            **kw,
        )

    @pytest.mark.parametrize("arm", [True, False])
    def test_expired_dropped_at_take_before_packing(self, arm, test_store):
        store, _ = test_store
        cache = self._real_cache(store, arm)
        engine = cache.engine
        assert (engine._dispatch is not None) == arm
        import numpy as np

        block = np.zeros((6, 1), dtype=np.uint32)
        block[0] = 42
        block[2] = 1
        block[3] = 10
        block[4] = 60
        try:
            with deadline_scope(-0.001):
                with pytest.raises(DeadlineExceededError):
                    engine.submit_rows(np.array(block))
            # dropped BEFORE packing: the device never saw a decision
            assert engine.health_snapshot()["decisions"] == 0
            drops = (
                engine._dispatch.deadline_drops
                if arm
                else engine._batcher.deadline_drops
            )
            assert drops == 1
            # a fresh submit on the same arm still works
            assert engine.submit_rows(np.array(block)).tolist() == [1]
        finally:
            cache.close()

    @pytest.mark.parametrize("arm", [True, False])
    @pytest.mark.parametrize(
        "mode", [SHED_MODE_ALLOW, SHED_MODE_DENY, SHED_MODE_UNAVAILABLE]
    )
    def test_shed_postures_answer_identically(self, arm, mode, test_store):
        """queue_full injected at the SHARED batcher.submit site: the
        service's posture answer must be byte-for-byte the same whichever
        arm is live."""
        store, sink = test_store
        controller = AdmissionController(
            shed_mode=mode, scope=store.scope("ratelimit")
        )
        injector = FaultInjector.from_spec("batcher.submit:queue_full:1")
        cache = self._real_cache(
            store, arm, overload=controller, fault_injector=injector
        )
        svc = RateLimitService(
            runtime=_FakeRuntime({"config.ov": OVERLOAD_YAML}),
            cache=cache,
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=FakeTimeSource(1_000_000),
            overload=controller,
        )
        try:
            if mode == SHED_MODE_UNAVAILABLE:
                with pytest.raises(QueueFullError):
                    svc.should_rate_limit(_req())
            else:
                overall, statuses, headers = svc.should_rate_limit(_req())
                if mode == SHED_MODE_ALLOW:
                    assert overall == Code.OK
                    assert statuses[0].code == Code.OK
                    assert any(
                        h.key == "x-ratelimit-shed" and h.value == "queue_full"
                        for h in headers
                    )
                else:
                    assert overall == Code.OVER_LIMIT
                    assert statuses[0].code == Code.OVER_LIMIT
            store.flush()
            assert sink.counters["ratelimit.overload.shed"] == 1
            assert sink.counters["ratelimit.overload.queue_full"] == 1
        finally:
            cache.close()

    @pytest.mark.parametrize("arm", [True, False])
    def test_brownout_sheds_identically(self, arm, test_store):
        store, _ = test_store
        controller = AdmissionController(
            shed_mode=SHED_MODE_UNAVAILABLE,
            brownout_target_ms=1.0,
            ewma_alpha=1.0,
            scope=store.scope("ratelimit"),
        )
        cache = self._real_cache(store, arm, overload=controller)
        engine = cache.engine
        import numpy as np

        block = np.zeros((6, 1), dtype=np.uint32)
        block[0] = 7
        block[2] = 1
        block[3] = 10
        block[4] = 60
        try:
            assert engine.submit_rows(np.array(block)).tolist() == [1]
            _brownout(controller)
            with pytest.raises(BrownoutError):
                engine.submit_rows(np.array(block))
        finally:
            cache.close()
