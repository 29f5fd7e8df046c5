"""End-to-end journey tracing + the tail-sampled flight recorder.

Covers the PR-7 tentpole: trace context riding the dispatch ring (batch
spans with followsFrom links, per-stage child spans closing the request
span's blind gap), B3 over the sidecar wire (one trace across both
processes, surviving retries/redials and a breaker half-open probe), the
journey recorder's tail sampling, dispatch-arm stage parity, and the
debug-port exports (/debug/journeys, /debug/profile)."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from api_ratelimit_tpu.backends.sidecar import (
    SidecarEngineClient,
    SlabSidecarServer,
)
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
from api_ratelimit_tpu.limiter.cache import CacheError
from api_ratelimit_tpu.tracing import (
    RecordingTracer,
    activate,
    reset_global_tracer,
    set_global_tracer,
)
from api_ratelimit_tpu.tracing import journeys
from api_ratelimit_tpu.tracing.journeys import (
    STAGES,
    JourneyRecorder,
    set_global_recorder,
)
from api_ratelimit_tpu.utils import RealTimeSource


@pytest.fixture(autouse=True)
def _clean_globals():
    reset_global_tracer()
    set_global_recorder(None)
    yield
    reset_global_tracer()
    set_global_recorder(None)


def make_engine(window=0.002, block_mode=False):
    return SlabDeviceEngine(
        time_source=RealTimeSource(),
        n_slots=1 << 12,
        batch_window_seconds=window,
        max_batch=1024,
        buckets=(8, 64),
        use_pallas=False,
        block_mode=block_mode,
    )


def block(n=2, limit=100):
    out = np.zeros((6, n), dtype=np.uint32)
    out[0] = np.arange(1, n + 1)  # fp_lo
    out[2] = 1  # hits
    out[3] = limit
    out[4] = 60  # divider
    return out


class TestJourneyRecorder:
    def test_begin_mark_finish_and_stage_order(self):
        rec = JourneyRecorder(slow_ms=1e9)
        j = rec.begin("request", trace_id=0xAB, span_id=0xCD)
        assert rec.current() is j
        for stage in STAGES:
            j.mark(stage)
        promoted = rec.finish(j, 1.5)
        assert promoted is False  # no flags, not slow
        assert rec.current() is None
        assert set(j.stages) == set(STAGES)
        assert j.duration_ms == 1.5

    @pytest.mark.parametrize(
        "flag", ["shed", "deadline", "fault", "over_limit"]
    )
    def test_outcome_flags_promote(self, flag):
        rec = JourneyRecorder(slow_ms=1e9)
        j = rec.begin("request")
        assert rec.finish(j, 0.1, flags=(flag,)) is True
        (got,) = rec.retained()
        assert flag in got.flags

    def test_slow_threshold_promotes(self):
        rec = JourneyRecorder(slow_ms=10.0)
        fast = rec.begin("request")
        assert rec.finish(fast, 5.0) is False
        slow = rec.begin("request")
        assert rec.finish(slow, 50.0) is True
        (got,) = rec.retained()
        assert "slow" in got.flags

    def test_live_p99_promotion_when_knob_zero(self):
        rec = JourneyRecorder(slow_ms=0.0)
        # build a baseline of fast journeys so the p99 estimate settles
        for _ in range(256):
            rec.finish(rec.begin("request"), 1.0)
        outlier = rec.begin("request")
        assert rec.finish(outlier, 500.0) is True
        assert any("slow" in j.flags for j in rec.retained())

    def test_note_flag_merges_at_finish(self):
        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        j = rec.begin("request")
        journeys.note_flag(journeys.FLAG_SHED)
        rec.finish(j, 0.1)
        (got,) = rec.retained()
        assert "shed" in got.flags

    def test_retained_buffer_bounded(self):
        rec = JourneyRecorder(slow_ms=1e9, retain=4)
        for i in range(10):
            rec.finish(rec.begin("request"), 0.1, flags=("fault",))
        assert len(rec.retained()) == 4

    def test_snapshot_and_json_shape(self):
        rec = JourneyRecorder(slow_ms=1e9)
        j = rec.begin("request", trace_id=7)
        j.mark("publish", 100)
        rec.finish(j, 0.2, flags=("fault",))
        snap = json.loads(rec.dump_json())
        assert snap["enabled"] is True
        (retained,) = snap["retained"]
        assert retained["trace_id"].endswith("7")
        assert retained["stages"]["publish"] == 100
        assert retained["flags"] == ["fault"]
        assert snap["recent"]  # per-thread ring has the journey too

    def test_module_hooks_noop_when_unregistered(self):
        assert journeys.begin_request() is None
        journeys.mark("publish")  # must not raise
        journeys.merge_owner_stages((1, 2, 3, 4, 5))
        journeys.note_flag("fault")
        assert journeys.recording() is False

    def test_junk_config_rejected(self):
        with pytest.raises(ValueError):
            JourneyRecorder(retain=0)
        with pytest.raises(ValueError):
            JourneyRecorder(ring=-1)
        with pytest.raises(ValueError):
            JourneyRecorder(slow_ms=-1.0)


class TestDispatchArmParity:
    """Both batching modes — the dispatch loop (windowed) and direct mode
    (TPU_BATCH_WINDOW=0) — must record the SAME journey stage set."""

    def _journey_stages(self, window: float) -> set:
        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        engine = make_engine(window=window)
        try:
            j = rec.begin("request")
            engine.submit_rows(block())
            rec.finish(j, 1.0)
            return set(j.stages)
        finally:
            engine.close()
            set_global_recorder(None)

    def test_stage_sets_identical_across_arms(self):
        loop_stages = self._journey_stages(window=0.002)
        direct_stages = self._journey_stages(window=0.0)
        assert loop_stages == set(STAGES)
        assert direct_stages == set(STAGES)

    def test_direct_mode_records_full_stage_set(self):
        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        engine = make_engine(window=0.0)
        try:
            j = rec.begin("request")
            engine.submit_rows(block())
            assert set(j.stages) == set(STAGES)
        finally:
            engine.close()


class TestConnectedTrace:
    def test_dispatch_loop_yields_one_connected_trace(self):
        """Request span -> ring/pack/launch/redeem child stages -> a
        dispatch.batch span linking the coalesced request (the tentpole
        acceptance shape, in-process arm)."""
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        engine = make_engine(window=0.002)
        try:
            request_span = tracer.start_span("request")
            with request_span, activate(request_span):
                out = engine.submit_rows(block())
            assert out.shape == (2,)
        finally:
            engine.close()
        spans = {s.operation_name: s for s in tracer.finished_spans()}
        trace_id = request_span.context.trace_id
        for stage in ("ring_wait", "pack", "launch", "redeem"):
            child = spans[f"dispatch.{stage}"]
            assert child.context.trace_id == trace_id
            assert child.parent_id == request_span.context.span_id
        batch = spans["dispatch.batch"]
        assert [c.span_id for c in batch.links] == [
            request_span.context.span_id
        ]
        assert batch.tags["batch_items"] == 2

    def test_batch_span_links_every_coalesced_request(self):
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        engine = make_engine(window=0.01)
        barrier = threading.Barrier(3)
        span_ids = []
        lock = threading.Lock()

        def caller(i):
            span = tracer.start_span(f"request-{i}")
            with lock:
                span_ids.append(span.context.span_id)
            with span, activate(span):
                barrier.wait()
                engine.submit_rows(block(n=1))

        threads = [
            threading.Thread(target=caller, args=(i,)) for i in range(3)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(10)
        finally:
            engine.close()
        batches = [
            s
            for s in tracer.finished_spans()
            if s.operation_name == "dispatch.batch"
        ]
        assert batches
        linked = {c.span_id for b in batches for c in b.links}
        assert linked == set(span_ids)

    def test_untraced_requests_build_no_spans(self):
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        engine = make_engine(window=0.002)
        try:
            engine.submit_rows(block())
        finally:
            engine.close()
        assert tracer.finished_spans() == []


class TestSidecarWireTrace:
    def _stack(self, tmp_path, fault_injector=None, **client_kwargs):
        engine = make_engine(window=0.002, block_mode=True)
        path = str(tmp_path / "sidecar.sock")
        server = SlabSidecarServer(path, engine)
        client = SidecarEngineClient(
            path, fault_injector=fault_injector, **client_kwargs
        )
        return engine, server, client

    def test_same_trace_id_on_both_sides_of_the_wire(self, tmp_path):
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        engine, server, client = self._stack(tmp_path)
        try:
            request_span = tracer.start_span("request")
            with request_span, activate(request_span):
                out = client.submit_rows(block())
            assert out.shape == (2,)
        finally:
            client.close()
            server.close()
        spans = {s.operation_name: s for s in tracer.finished_spans()}
        trace_id = request_span.context.trace_id
        rpc = spans["sidecar.submit"]  # frontend-process client span
        assert rpc.context.trace_id == trace_id
        assert rpc.parent_id == request_span.context.span_id
        srv = spans["sidecar.submit_rows"]  # device-owner-process span
        assert srv.context.trace_id == trace_id
        assert srv.parent_id == rpc.context.span_id
        # the device-owner batch span links the server-side request span
        batch = spans["dispatch.batch"]
        assert any(c.trace_id == trace_id for c in batch.links)

    def test_b3_survives_retry_and_redial_one_trace(self, tmp_path):
        from api_ratelimit_tpu.testing.faults import FaultInjector

        tracer = RecordingTracer()
        set_global_tracer(tracer)
        injector = FaultInjector()
        # backoff sleep "ends the outage": the first post-redial retry
        # succeeds, so the request survives on one trace with the retry
        # story logged on its rpc span
        engine, server, client = self._stack(
            tmp_path,
            fault_injector=injector,
            retries=2,
            sleep=lambda _s: injector.clear(),
        )
        injector.configure("sidecar.submit:error:1.0")
        try:
            request_span = tracer.start_span("request")
            with request_span, activate(request_span):
                out = client.submit_rows(block())
            assert out.shape == (2,)
        finally:
            client.close()
            server.close()
        spans = {s.operation_name: s for s in tracer.finished_spans()}
        rpc = spans["sidecar.submit"]
        events = [f.get("event") for _, f in rpc.logs]
        assert "sidecar.redial" in events  # pooled conn died -> free redial
        assert "sidecar.retry" in events  # then a budgeted retry
        faults = [f for _, f in rpc.logs if f.get("event") == "fault"]
        assert faults and faults[0]["kind"] == "error"
        assert faults[0]["site"] == "sidecar.submit"
        # one trace end to end despite the failed attempts
        assert (
            spans["sidecar.submit_rows"].context.trace_id
            == request_span.context.trace_id
        )

    def test_b3_survives_breaker_half_open_probe(self, tmp_path):
        from api_ratelimit_tpu.testing.faults import FaultInjector

        tracer = RecordingTracer()
        set_global_tracer(tracer)
        injector = FaultInjector()
        engine, server, client = self._stack(
            tmp_path,
            fault_injector=injector,
            retries=0,
            breaker_threshold=1,
            breaker_reset=0.05,
        )
        try:
            injector.configure("sidecar.submit:error:1.0")
            with pytest.raises(CacheError):
                client.submit_rows(block())
            assert not client.breaker.allow()  # open: failing fast
            injector.clear()
            time.sleep(0.1)  # open -> half-open probe window
            probe_span = tracer.start_span("probe-request")
            with probe_span, activate(probe_span):
                out = client.submit_rows(block())
            assert out.shape == (2,)
        finally:
            client.close()
            server.close()
        srv = [
            s
            for s in tracer.finished_spans()
            if s.operation_name == "sidecar.submit_rows"
        ]
        # the half-open probe request still carried its B3 context
        assert srv and srv[-1].context.trace_id == probe_span.context.trace_id

    def test_sidecar_server_records_journeys(self, tmp_path):
        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        engine, server, client = self._stack(tmp_path)
        try:
            client.submit_rows(block())
            deadline = time.monotonic() + 2
            while time.monotonic() < deadline:
                snap = rec.snapshot()
                kinds = [
                    j["kind"]
                    for ring in snap["recent"].values()
                    for j in ring
                ]
                if "sidecar.submit" in kinds:
                    break
                time.sleep(0.01)
            else:
                pytest.fail("sidecar journey never recorded")
        finally:
            client.close()
            server.close()


class TestDispatchTelemetry:
    def test_ring_wait_exemplar_attached_for_traced_slow_frame(self):
        from api_ratelimit_tpu.stats import Store, TestSink

        # one-boundary ladder: every recorded value is "slow" (overflow
        # bucket), so the exemplar path runs deterministically
        store = Store(TestSink(), latency_buckets=(1e-9,))
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        engine = SlabDeviceEngine(
            time_source=RealTimeSource(),
            n_slots=1 << 12,
            batch_window_seconds=0.002,
            buckets=(8, 64),
            use_pallas=False,
            scope=store.scope("ratelimit"),
        )
        try:
            span = tracer.start_span("request")
            with span, activate(span):
                engine.submit_rows(block())
        finally:
            engine.close()
        hists = store.metrics_snapshot()["histograms"]
        want = f"{span.context.trace_id:032x}"
        for name in (
            "ratelimit.dispatch.ring_wait_ms",
            "ratelimit.dispatch.launch_ms",
            "ratelimit.dispatch.redeem_ms",
        ):
            snap = hists[name]
            assert snap["count"] >= 1
            assert snap["exemplar"]["trace_id"] == want, name

    def test_dispatch_launch_fault_logs_kind_on_batch_span(self):
        from api_ratelimit_tpu.testing.faults import FaultInjector

        tracer = RecordingTracer()
        set_global_tracer(tracer)
        injector = FaultInjector()
        engine = SlabDeviceEngine(
            time_source=RealTimeSource(),
            n_slots=1 << 12,
            batch_window_seconds=0.002,
            buckets=(8, 64),
            use_pallas=False,
            fault_injector=injector,
        )
        injector.configure("dispatch.launch:error:1.0")
        try:
            span = tracer.start_span("request")
            with pytest.raises(CacheError):
                with span, activate(span):
                    engine.submit_rows(block())
        finally:
            injector.clear()
            engine.close()
        batches = [
            s
            for s in tracer.finished_spans()
            if s.operation_name == "dispatch.batch"
        ]
        assert batches
        faults = [
            f
            for _, f in batches[0].logs
            if f.get("event") == "fault"
        ]
        assert faults and faults[0]["kind"] == "error"
        assert faults[0]["site"] == "dispatch.launch"
        assert batches[0].tags.get("error") is True


class TestServiceJourneys:
    def _service(self, test_store, cache=None):
        from api_ratelimit_tpu.backends.memory import MemoryRateLimitCache
        from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
        from api_ratelimit_tpu.service.ratelimit import RateLimitService
        from api_ratelimit_tpu.utils.timeutil import FakeTimeSource

        store, _sink = test_store

        class FakeRuntime:
            def snapshot(self):
                class Snap:
                    def keys(self):
                        return ["config.basic"]

                    def get(self, key):
                        return (
                            "domain: basic\n"
                            "descriptors:\n"
                            "  - key: k1\n"
                            "    rate_limit: {unit: minute, requests_per_unit: 2}\n"
                        )

                return Snap()

            def add_update_callback(self, cb):
                pass

        ts = FakeTimeSource(1234)
        base = BaseRateLimiter(time_source=ts, jitter_rand=None)
        return RateLimitService(
            runtime=FakeRuntime(),
            cache=cache or MemoryRateLimitCache(base),
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=ts,
            runtime_watch_root=True,
        )

    def test_over_limit_journey_promoted(self, test_store):
        from api_ratelimit_tpu.models.descriptors import (
            Descriptor,
            RateLimitRequest,
        )

        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        service = self._service(test_store)
        req = RateLimitRequest(
            domain="basic", descriptors=(Descriptor.of(("k1", "v1")),)
        )
        for _ in range(3):
            service.should_rate_limit(req)
        retained = rec.retained()
        assert retained and "over_limit" in retained[-1].flags
        assert retained[-1].kind == "request"

    def test_fault_journey_promoted(self, test_store):
        from api_ratelimit_tpu.models.descriptors import (
            Descriptor,
            RateLimitRequest,
        )

        class BoomCache:
            def do_limit(self, request, limits):
                raise CacheError("backend down")

            def flush(self):
                pass

        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        service = self._service(test_store, cache=BoomCache())
        req = RateLimitRequest(
            domain="basic", descriptors=(Descriptor.of(("k1", "v1")),)
        )
        with pytest.raises(CacheError):
            service.should_rate_limit(req)
        (got,) = rec.retained()
        assert "fault" in got.flags

    def test_journey_carries_trace_id_of_active_span(self, test_store):
        from api_ratelimit_tpu.models.descriptors import (
            Descriptor,
            RateLimitRequest,
        )

        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        tracer = RecordingTracer()
        set_global_tracer(tracer)
        service = self._service(test_store)
        req = RateLimitRequest(
            domain="basic", descriptors=(Descriptor.of(("k1", "v1")),)
        )
        with tracer.start_span("rpc") as span, activate(span):
            service.should_rate_limit(req)
        snap = rec.snapshot()
        recorded = [j for ring in snap["recent"].values() for j in ring]
        assert recorded
        assert recorded[-1]["trace_id"] == f"{span.context.trace_id:032x}"


class TestDebugEndpoints:
    def _get(self, port, path, timeout=5):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as resp:
            return resp.status, resp.read()

    def test_debug_journeys_endpoint(self, test_store):
        from api_ratelimit_tpu.server.http_server import new_debug_server

        store, _ = test_store
        rec = JourneyRecorder(slow_ms=1e9)
        set_global_recorder(rec)
        rec.finish(rec.begin("request", trace_id=9), 0.5, flags=("fault",))
        server = new_debug_server("127.0.0.1", 0, store)
        server.serve_background()
        try:
            status, body = self._get(server.port, "/debug/journeys")
        finally:
            server.shutdown()
        assert status == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["retained"][0]["flags"] == ["fault"]

    def test_debug_journeys_disabled_shape(self, test_store):
        from api_ratelimit_tpu.server.http_server import new_debug_server

        store, _ = test_store
        server = new_debug_server("127.0.0.1", 0, store)
        server.serve_background()
        try:
            status, body = self._get(server.port, "/debug/journeys")
        finally:
            server.shutdown()
        assert status == 200
        assert json.loads(body) == {
            "enabled": False,
            "retained": [],
            "recent": {},
        }

    def test_debug_profile_disabled_without_dir(self, test_store):
        from api_ratelimit_tpu.server.http_server import new_debug_server

        store, _ = test_store
        server = new_debug_server("127.0.0.1", 0, store)
        server.serve_background()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                self._get(server.port, "/debug/profile?ms=1")
        finally:
            server.shutdown()
        assert exc_info.value.code == 404

    def test_debug_profile_captures_jax_trace(self, test_store, tmp_path):
        import os

        from api_ratelimit_tpu.server.http_server import new_debug_server

        store, _ = test_store
        profile_dir = str(tmp_path / "profiles")
        os.makedirs(profile_dir)
        server = new_debug_server(
            "127.0.0.1", 0, store, profile_dir=profile_dir
        )
        server.serve_background()
        try:
            # the first trace initializes the profiler backend; generous
            # timeout so a cold CI box never flakes this
            status, body = self._get(
                server.port, "/debug/profile?ms=20", timeout=60
            )
        finally:
            server.shutdown()
        assert status == 200
        doc = json.loads(body)
        assert doc["profile_dir"] == profile_dir
        produced = [
            os.path.join(r, f)
            for r, _, fs in os.walk(profile_dir)
            for f in fs
        ]
        assert produced, "profiler wrote no trace files"
