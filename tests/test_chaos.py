"""Chaos suite: the resilience ladder under injected faults.

Drives testing/faults.py (the FAULT_INJECT harness) against the sidecar
client/server and the service-level FAILURE_MODE_DENY degradation ladder
(backends/fallback.py): transient-fault retry absorption, free redial
across a sidecar restart (zero failed requests), per-RPC deadline expiry
against a slow engine, the breaker's closed -> open -> half-open -> closed
cycle, and each failure-mode rung. Every scenario is deterministic: faults
fire at probability 1.0 or from a seeded RNG, and backoffs use injected
sleeps where wall time doesn't matter.
"""

from __future__ import annotations

import threading
import time

import pytest

from api_ratelimit_tpu.backends.fallback import (
    FAILURE_MODE_ALLOW,
    FAILURE_MODE_DEGRADED,
    FAILURE_MODE_DENY,
    CircuitBreaker,
    FallbackLimiter,
)
from api_ratelimit_tpu.backends.sidecar import (
    SidecarEngineClient,
    SlabSidecarServer,
)
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, _Item
from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
from api_ratelimit_tpu.limiter.cache import CacheError
from api_ratelimit_tpu.models import Code, Descriptor, RateLimitRequest
from api_ratelimit_tpu.service import RateLimitService
from api_ratelimit_tpu.stats import Store, TestSink
from api_ratelimit_tpu.testing.faults import FaultInjector, parse_fault_spec
from api_ratelimit_tpu.utils import FakeTimeSource


def _make_engine(ts):
    return SlabDeviceEngine(
        time_source=ts,
        n_slots=1 << 12,
        buckets=(128, 1024),
        max_batch=1024,
        use_pallas=False,
        block_mode=True,  # the production sidecar server runs block-native
    )


def _item(fp=7):
    return [_Item(fp=fp, hits=1, limit=1_000_000, divider=60, jitter=0)]


def _client(address, faults=None, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("retry_backoff", 0.001)
    kw.setdefault("retry_backoff_max", 0.005)
    kw.setdefault("breaker_threshold", 0)
    return SidecarEngineClient(address, fault_injector=faults, **kw)


@pytest.fixture
def sidecar_tcp():
    ts = FakeTimeSource(1_000_000)
    server = SlabSidecarServer("tcp://127.0.0.1:0", _make_engine(ts))
    yield server, f"tcp://127.0.0.1:{server.port}"
    server.close()


class TestFaultInjectorUnit:
    def test_deterministic_for_a_seed(self):
        rules = parse_fault_spec("x.y:error:0.5")
        a = FaultInjector(rules, seed=42)
        b = FaultInjector(rules, seed=42)
        seq_a = [a.fire("x.y") for _ in range(50)]
        seq_b = [b.fire("x.y") for _ in range(50)]
        assert seq_a == seq_b
        assert "error" in seq_a and None in seq_a  # 0.5 actually mixes

    def test_delay_rules_sleep_and_sum(self):
        slept = []
        inj = FaultInjector(
            parse_fault_spec("s:delay_ms:200,s:delay_ms:300"),
            sleep=slept.append,
        )
        assert inj.fire("s") is None
        assert slept == [0.5]
        assert inj.fired() == {"s:delay_ms": 1}

    def test_unmatched_site_is_free(self):
        inj = FaultInjector(parse_fault_spec("a.b:error:1.0"))
        assert inj.fire("other.site") is None

    def test_configure_and_clear_at_runtime(self):
        inj = FaultInjector()
        assert not inj.enabled()
        inj.configure("s:error:1.0")
        assert inj.enabled() and inj.fire("s") == "error"
        inj.clear()
        assert not inj.enabled() and inj.fire("s") is None
        assert inj.fired() == {"s:error": 1}  # counts survive clear()


class TestCircuitBreakerUnit:
    def _breaker(self, threshold=3, reset=10.0):
        clock = FakeTimeSource(100)
        transitions = []
        breaker = CircuitBreaker(
            threshold,
            reset,
            clock=lambda: clock.now,
            on_transition=lambda a, b: transitions.append((a, b)),
        )
        return breaker, clock, transitions

    def test_opens_after_consecutive_failures_only(self):
        breaker, _, transitions = self._breaker(threshold=3)
        breaker.record_failure()
        breaker.record_failure()
        breaker.record_success()  # streak broken
        breaker.record_failure()
        breaker.record_failure()
        assert breaker.state == CircuitBreaker.CLOSED
        breaker.record_failure()
        assert breaker.state == CircuitBreaker.OPEN
        assert transitions == [("closed", "open")]

    def test_open_fails_fast_then_half_open_probe_closes(self):
        breaker, clock, _ = self._breaker(threshold=1, reset=10.0)
        breaker.record_failure()
        assert not breaker.allow()  # open: fail fast
        clock.advance(11)
        assert breaker.allow()  # this caller is the half-open probe
        assert breaker.state == CircuitBreaker.HALF_OPEN
        assert not breaker.allow()  # others fail fast while probing
        breaker.record_success()
        assert breaker.state == CircuitBreaker.CLOSED
        assert breaker.allow()

    def test_failed_probe_reopens(self):
        breaker, clock, _ = self._breaker(threshold=1, reset=10.0)
        breaker.record_failure()
        clock.advance(11)
        assert breaker.allow()
        breaker.record_failure()  # probe failed
        assert breaker.state == CircuitBreaker.OPEN
        assert not breaker.allow()
        clock.advance(11)
        assert breaker.allow()  # next probe window

    def test_threshold_zero_disables(self):
        breaker = CircuitBreaker(0, 1.0)
        for _ in range(10):
            breaker.record_failure()
        assert breaker.allow()
        assert breaker.state == CircuitBreaker.CLOSED


class _NShotFaults(FaultInjector):
    """Fires the configured fault only for the first `n` trips — the
    transient-glitch shape (network blip, not an outage)."""

    def __init__(self, spec, n, seed=0):
        super().__init__(parse_fault_spec(spec), seed=seed)
        self._remaining = n

    def fire(self, site):
        if self._remaining <= 0:
            return None
        action = super().fire(site)
        if action is not None:
            self._remaining -= 1
        return action


class TestSidecarRetries:
    def test_transient_fault_absorbed_by_retry(self, sidecar_tcp, test_store):
        """One injected transport glitch must cost zero failed requests."""
        _, address = sidecar_tcp
        store, _ = test_store
        faults = _NShotFaults("sidecar.submit:error:1.0", 1)
        client = _client(address, faults, scope=store.scope("ratelimit"))
        try:
            assert client.submit(_item()) == [1]  # survived the glitch
        finally:
            client.close()
        # the glitch hit the pooled (constructor-ping) conn, so the free
        # redial absorbed it without spending the retry budget
        assert faults.fired() == {"sidecar.submit:error": 1}
        snap = store.debug_snapshot()
        assert snap["ratelimit.sidecar.redial"] == 1
        assert snap["ratelimit.sidecar.retry"] == 0

    def test_persistent_faults_exhaust_bounded_retries(self, sidecar_tcp):
        _, address = sidecar_tcp
        faults = FaultInjector(parse_fault_spec("sidecar.submit:error:1.0"))
        client = _client(address, faults, retries=2)
        try:
            with pytest.raises(CacheError, match="injected fault"):
                client.submit(_item())
        finally:
            client.close()
        # 1 free redial (pooled conn) + initial attempt + 2 retries
        assert faults.fired()["sidecar.submit:error"] == 4

    def test_deadline_expires_on_slow_engine(self, test_store):
        """Per-RPC deadline: a wedged/slow sidecar engine must cost one
        deadline, not an unbounded hang."""
        ts = FakeTimeSource(1_000_000)
        server_faults = FaultInjector(
            parse_fault_spec("sidecar.server.submit:delay_ms:30000")
        )
        server = SlabSidecarServer(
            "tcp://127.0.0.1:0", _make_engine(ts), fault_injector=server_faults
        )
        client = _client(
            f"tcp://127.0.0.1:{server.port}", retries=0, rpc_deadline=0.05
        )
        try:
            t0 = time.monotonic()
            with pytest.raises(CacheError, match="transport failure"):
                client.submit(_item())
            assert time.monotonic() - t0 < 5.0  # deadline, not the delay
        finally:
            client.close()
            server_faults.clear()  # let the server thread's sleep stub go
            server.close()

    def test_server_side_drop_and_partial_write_are_retried(self, test_store):
        """Connection drops and truncated responses from the server are
        transport failures — absorbed by redial/retry."""
        for kind in ("drop", "partial_write"):
            ts = FakeTimeSource(1_000_000)
            faults = _NShotFaults(f"sidecar.server.submit:{kind}:1.0", 1)
            server = SlabSidecarServer(
                "tcp://127.0.0.1:0", _make_engine(ts), fault_injector=faults
            )
            client = _client(f"tcp://127.0.0.1:{server.port}")
            try:
                assert client.submit(_item()) == [1]
            finally:
                client.close()
                server.close()


class TestBreakerCycle:
    def test_open_half_open_close_cycle(self, sidecar_tcp, test_store):
        """The core acceptance cycle: breaker opens after the configured
        threshold, fails fast while open, recovers via the half-open probe
        once faults clear."""
        _, address = sidecar_tcp
        store, _ = test_store
        faults = FaultInjector(parse_fault_spec("sidecar.submit:error:1.0"))
        client = _client(
            address,
            faults,
            retries=0,
            breaker_threshold=2,
            breaker_reset=0.05,
            scope=store.scope("ratelimit"),
        )
        try:
            for _ in range(2):
                with pytest.raises(CacheError, match="injected fault"):
                    client.submit(_item())
            assert client.breaker.state == CircuitBreaker.OPEN
            before = faults.fired()["sidecar.submit:error"]
            with pytest.raises(CacheError, match="circuit open"):
                client.submit(_item())
            # failing fast: no transport attempt was made while open
            assert faults.fired()["sidecar.submit:error"] == before
            snap = store.debug_snapshot()
            assert snap["ratelimit.sidecar.breaker_open"] == 1
            assert snap["ratelimit.sidecar.breaker_state"] == 2  # open

            # faults clear; after the reset window the half-open probe
            # closes the breaker and traffic flows again
            faults.clear()
            time.sleep(0.06)
            assert client.submit(_item()) == [1]
            assert client.breaker.state == CircuitBreaker.CLOSED
            assert client.submit(_item()) == [2]
            snap = store.debug_snapshot()
            assert snap["ratelimit.sidecar.breaker_state"] == 0  # closed
        finally:
            client.close()

    def test_failed_probe_reopens_breaker(self, sidecar_tcp):
        _, address = sidecar_tcp
        faults = FaultInjector(parse_fault_spec("sidecar.submit:error:1.0"))
        client = _client(
            address, faults, retries=0, breaker_threshold=1, breaker_reset=0.05
        )
        try:
            with pytest.raises(CacheError, match="injected fault"):
                client.submit(_item())
            assert client.breaker.state == CircuitBreaker.OPEN
            time.sleep(0.06)
            # the probe goes to the wire (faults still on) and fails
            with pytest.raises(CacheError, match="injected fault"):
                client.submit(_item())
            assert client.breaker.state == CircuitBreaker.OPEN
        finally:
            client.close()


class TestSidecarRestart:
    def test_restart_is_free_without_retry_budget(self, test_store):
        """The one-shot redial alone (retries=0) absorbs a sidecar restart
        detected on a pooled connection."""
        ts = FakeTimeSource(1_000_000)
        engine = _make_engine(ts)
        server = SlabSidecarServer("tcp://127.0.0.1:0", engine)
        port = server.port
        client = _client(f"tcp://127.0.0.1:{port}", retries=0)
        try:
            assert client.submit(_item()) == [1]
            server.close()
            server = SlabSidecarServer(
                f"tcp://127.0.0.1:{port}", _make_engine(ts)
            )
            # the pooled conn is stale -> evict-all + free redial; counters
            # continue on the fresh slab (soft state)
            assert client.submit(_item()) == [1]
        finally:
            client.close()
            server.close()

    def test_restart_under_load_zero_failed_requests(self, test_store):
        """The acceptance bar: a sidecar restart while 4 threads hammer it
        costs ZERO failed requests — stale pooled sockets redial, requests
        in the dial gap ride the retry budget."""
        ts = FakeTimeSource(1_000_000)
        server = SlabSidecarServer("tcp://127.0.0.1:0", _make_engine(ts))
        port = server.port
        client = SidecarEngineClient(
            f"tcp://127.0.0.1:{port}",
            retries=8,
            retry_backoff=0.02,
            retry_backoff_max=0.2,
            breaker_threshold=0,
        )
        errors: list[Exception] = []
        done = [0]
        lock = threading.Lock()

        def worker(k):
            for i in range(30):
                try:
                    client.submit(_item(fp=k * 1000 + i))
                except Exception as e:  # noqa: BLE001 - collected for assert
                    with lock:
                        errors.append(e)
                else:
                    with lock:
                        done[0] += 1

        threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # let load build
        server.close()
        server2 = SlabSidecarServer(f"tcp://127.0.0.1:{port}", _make_engine(ts))
        try:
            for t in threads:
                t.join(30.0)
            assert errors == []
            assert done[0] == 120
        finally:
            client.close()
            server2.close()


# -- the FAILURE_MODE_DENY ladder at the service level --

LADDER_YAML = """
domain: chaos
descriptors:
  - key: k
    value: v
    rate_limit: {unit: minute, requests_per_unit: 2}
"""


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


class _FlakyCache:
    """Raises CacheError while .down is True, else answers OK."""

    def __init__(self):
        self.down = True

    def do_limit(self, request, limits):
        if self.down:
            raise CacheError("backend dark")
        from api_ratelimit_tpu.models.response import (
            DescriptorStatus,
            DoLimitResponse,
        )

        return DoLimitResponse(
            descriptor_statuses=[
                DescriptorStatus(code=Code.OK) for _ in request.descriptors
            ]
        )

    def flush(self):
        pass


def _ladder_service(mode, store):
    ts = FakeTimeSource(1_000_000)
    cache = _FlakyCache()
    fallback = FallbackLimiter(
        mode,
        base_limiter=BaseRateLimiter(ts, near_limit_ratio=0.8),
        scope=store.scope("ratelimit"),
    )
    svc = RateLimitService(
        runtime=_FakeRuntime({"config.chaos": LADDER_YAML}),
        cache=cache,
        stats_scope=store.scope("ratelimit").scope("service"),
        time_source=ts,
        fallback=fallback,
    )
    return svc, cache, fallback


def _req():
    return RateLimitRequest(
        domain="chaos",
        descriptors=(Descriptor.of(("k", "v")),),
        hits_addend=1,
    )


class TestFailureModeLadder:
    def test_fail_open_returns_ok_and_counts_redis_error(self, test_store):
        store, sink = test_store
        svc, cache, fallback = _ladder_service(FAILURE_MODE_ALLOW, store)
        overall, statuses, _ = svc.should_rate_limit(_req())
        assert overall == Code.OK
        assert statuses[0].code == Code.OK
        assert fallback.degraded
        assert "mode=allow" in fallback.degraded_reason()
        store.flush()
        assert (
            sink.counters["ratelimit.service.call.should_rate_limit.redis_error"]
            == 1
        )
        assert sink.counters["ratelimit.fallback.allow"] == 1
        assert sink.gauges["ratelimit.fallback.degraded"] == 1
        # backend heals: degraded state clears on the next success
        cache.down = False
        overall, _, _ = svc.should_rate_limit(_req())
        assert overall == Code.OK
        assert not fallback.degraded
        assert fallback.degraded_reason() is None
        store.flush()
        assert sink.gauges["ratelimit.fallback.degraded"] == 0

    def test_deny_mode_denies_all(self, test_store):
        store, sink = test_store
        svc, _, _ = _ladder_service(FAILURE_MODE_DENY, store)
        overall, statuses, _ = svc.should_rate_limit(_req())
        assert overall == Code.OVER_LIMIT
        assert statuses[0].code == Code.OVER_LIMIT
        assert statuses[0].current_limit.requests_per_unit == 2
        store.flush()
        assert sink.counters["ratelimit.fallback.deny"] == 1

    def test_degraded_mode_keeps_local_enforcement(self, test_store):
        """The degraded rung: during the outage the in-memory fixed-window
        limiter still denies over-limit descriptors (limit 2/min)."""
        store, sink = test_store
        svc, _, fallback = _ladder_service(FAILURE_MODE_DEGRADED, store)
        codes = [svc.should_rate_limit(_req())[0] for _ in range(3)]
        assert codes == [Code.OK, Code.OK, Code.OVER_LIMIT]
        assert fallback.degraded
        store.flush()
        assert sink.counters["ratelimit.fallback.local"] == 3
        assert (
            sink.counters["ratelimit.service.call.should_rate_limit.redis_error"]
            == 3
        )

    def test_healthcheck_reports_degraded_body(self, test_store):
        from api_ratelimit_tpu.server.health import HealthChecker

        store, _ = test_store
        svc, cache, fallback = _ladder_service(FAILURE_MODE_ALLOW, store)
        health = HealthChecker()
        health.set_degraded_probe(fallback.degraded_reason)
        assert health.http_response() == (200, "OK")
        svc.should_rate_limit(_req())
        status, body = health.http_response()
        assert status == 200  # degraded still serves; never drained
        assert body.startswith("OK") and "degraded" in body
        cache.down = False
        svc.should_rate_limit(_req())
        assert health.http_response() == (200, "OK")

    def test_no_fallback_keeps_legacy_raise(self, test_store):
        store, _ = test_store
        ts = FakeTimeSource(1_000_000)
        svc = RateLimitService(
            runtime=_FakeRuntime({"config.chaos": LADDER_YAML}),
            cache=_FlakyCache(),
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=ts,
        )
        with pytest.raises(CacheError):
            svc.should_rate_limit(_req())


class TestClosedBatcherIsCacheError:
    """Satellite: a submit racing shutdown must surface as a counted
    backend failure (CacheError), not an unhandled RuntimeError 500."""

    @staticmethod
    def _closed_engine(window):
        import numpy as np

        eng = SlabDeviceEngine(
            time_source=FakeTimeSource(1_000_000),
            n_slots=1 << 10,
            use_pallas=False,
            batch_window_seconds=window,
            buckets=(8,),
            max_batch=8,
        )
        eng.close()
        block = np.zeros((6, 1), dtype=np.uint32)
        block[2] = 1
        return eng, block

    def test_direct_mode(self):
        import numpy as np

        from api_ratelimit_tpu.backends.batcher import MicroBatcher

        b = MicroBatcher(lambda blocks: np.zeros(1, dtype=np.uint32))
        b.close()
        with pytest.raises(CacheError, match="batcher is closed"):
            b.submit(np.zeros((6, 1), dtype=np.uint32))

    def test_windowed_mode(self):
        """Windowed mode submits ride the dispatch loop: once the engine
        is closed they answer CacheError the same way."""
        eng, block = self._closed_engine(0.001)
        assert eng.dispatch_loop is not None
        with pytest.raises(CacheError, match="dispatch loop is closed"):
            eng.submit_rows(block)

    def test_direct_mode_engine(self):
        eng, block = self._closed_engine(0.0)
        assert eng.dispatch_loop is None
        with pytest.raises(CacheError, match="batcher is closed"):
            eng.submit_rows(block)


class TestFullStackAcceptance:
    """The issue's acceptance scenario end to end: a real runner with
    BACKEND_TYPE=tpu-sidecar, FAULT_INJECT forcing 100% sidecar transport
    errors, driven over real gRPC + HTTP."""

    def _boot(self, tmp_path, sock, **settings_kw):
        from api_ratelimit_tpu.runner import Runner
        from api_ratelimit_tpu.settings import Settings

        config_dir = tmp_path / "current" / "rl" / "config"
        config_dir.mkdir(parents=True, exist_ok=True)
        (config_dir / "c.yaml").write_text(
            "domain: chaos\n"
            "descriptors:\n"
            "  - key: one\n"
            "    rate_limit: {unit: minute, requests_per_unit: 1}\n"
        )
        settings = Settings(
            port=0,
            grpc_port=0,
            debug_port=0,
            use_statsd=False,
            runtime_path=str(tmp_path / "current"),
            runtime_subdirectory="rl",
            backend_type="tpu-sidecar",
            sidecar_socket=sock,
            sidecar_retries=0,
            sidecar_retry_backoff=0.001,
            sidecar_breaker_threshold=0,
            expiration_jitter_max_seconds=0,
            log_level="ERROR",
            **settings_kw,
        )
        runner = Runner(settings, sink=TestSink())
        runner.run_background()
        assert runner.wait_ready(10.0)
        return runner

    def _healthcheck(self, runner):
        import urllib.request

        with urllib.request.urlopen(
            f"http://localhost:{runner.server.http_port}/healthcheck",
            timeout=5,
        ) as resp:
            return resp.status, resp.read().decode()

    def test_fail_open_full_stack(self, tmp_path):
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc, rls_v3
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        engine = SlabDeviceEngine(
            time_source=RealTimeSource(),
            n_slots=1 << 12,
            buckets=(128, 1024),
            max_batch=1024,
            use_pallas=False,
            block_mode=True,
        )
        sock = str(tmp_path / "slab.sock")
        server = SlabSidecarServer(sock, engine)
        runner = self._boot(
            tmp_path,
            sock,
            failure_mode_deny="false",  # upstream fail-open posture
            fault_inject="sidecar.submit:error:1.0",
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                request = rls_v3.RateLimitRequest(domain="chaos")
                d = request.descriptors.add()
                d.entries.add(key="one", value="x")
                # 100% transport errors + fail-open => OK every time
                codes = [
                    stub.ShouldRateLimit(request).overall_code
                    for _ in range(3)
                ]
            assert codes == [rls_v3.RateLimitResponse.OK] * 3
            snap = runner.stats_store.debug_snapshot()
            assert (
                snap["ratelimit.service.call.should_rate_limit.redis_error"]
                == 3
            )
            assert snap["ratelimit.fallback.degraded"] == 1
            status, body = self._healthcheck(runner)
            assert status == 200 and "degraded" in body
        finally:
            runner.stop()
            server.close()

    def test_degraded_local_full_stack(self, tmp_path):
        """Degraded rung over the wire: with the sidecar unreachable, the
        in-memory fallback still denies the over-limit descriptor."""
        import grpc

        from api_ratelimit_tpu.pb import rls_grpc, rls_v3
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        engine = SlabDeviceEngine(
            time_source=RealTimeSource(),
            n_slots=1 << 12,
            buckets=(128, 1024),
            max_batch=1024,
            use_pallas=False,
            block_mode=True,
        )
        sock = str(tmp_path / "slab.sock")
        server = SlabSidecarServer(sock, engine)
        runner = self._boot(
            tmp_path,
            sock,
            failure_mode_deny="degraded",
            fault_inject="sidecar.submit:error:1.0",
        )
        try:
            with grpc.insecure_channel(
                f"localhost:{runner.server.grpc_port}"
            ) as ch:
                stub = rls_grpc.RateLimitServiceV3Stub(ch)
                request = rls_v3.RateLimitRequest(domain="chaos")
                d = request.descriptors.add()
                d.entries.add(key="one", value="x")
                codes = [
                    stub.ShouldRateLimit(request).overall_code
                    for _ in range(3)
                ]
            # limit is 1/minute: the local limiter allows one then denies
            assert codes == [
                rls_v3.RateLimitResponse.OK,
                rls_v3.RateLimitResponse.OVER_LIMIT,
                rls_v3.RateLimitResponse.OVER_LIMIT,
            ]
            status, body = self._healthcheck(runner)
            assert status == 200 and "degraded" in body
        finally:
            runner.stop()
            server.close()


# ---------------------------------------------------------------------------
# Hierarchical quota leasing x the failure ladder (backends/lease.py):
# while the device owner is dark, outstanding leases keep answering with
# REAL granted budget; an expired/exhausted lease falls through to the
# configured FAILURE_MODE_DENY rung, and the sticky lease.degraded probe
# rides /healthcheck until the next device success.
# ---------------------------------------------------------------------------

LEASE_LADDER_YAML = """
domain: chaos
descriptors:
  - key: k
    rate_limit: {unit: minute, requests_per_unit: 50}
"""


class _FlakyEngine:
    """Row-verb engine wrapper: raises CacheError while .down, else
    delegates to a real SlabDeviceEngine (so lease grants execute)."""

    def __init__(self, engine):
        self._engine = engine
        self.down = False

    @property
    def lease_registry(self):
        return self._engine.lease_registry

    def submit_rows(self, block, lease_ops=None):
        if self.down:
            raise CacheError("device owner dark")
        return self._engine.submit_rows(block, lease_ops=lease_ops)

    def flush(self):
        self._engine.flush()

    def close(self):
        self._engine.close()


def _lease_ladder_service(mode, store):
    import random

    from api_ratelimit_tpu.backends.lease import LeaseTable
    from api_ratelimit_tpu.backends.tpu import (
        SlabDeviceEngine,
        TpuRateLimitCache,
    )

    ts = FakeTimeSource(1_000_000)
    base = BaseRateLimiter(
        ts, jitter_rand=random.Random(0), expiration_jitter_max_seconds=0
    )
    table = LeaseTable(
        base,
        min_size=4,
        max_size=16,
        scope=store.scope("ratelimit").scope("lease"),
    )
    engine = _FlakyEngine(
        SlabDeviceEngine(
            time_source=ts, n_slots=1 << 10, use_pallas=False, buckets=(128,)
        )
    )
    fallback = None
    if mode is not None:
        fallback = FallbackLimiter(
            mode,
            base_limiter=base,
            scope=store.scope("ratelimit"),
            lease_table=table,
        )
    cache = TpuRateLimitCache(base, engine=engine, lease_table=table)
    svc = RateLimitService(
        runtime=_FakeRuntime({"config.chaos": LEASE_LADDER_YAML}),
        cache=cache,
        stats_scope=store.scope("ratelimit").scope("service"),
        time_source=ts,
        fallback=fallback,
        lease=table,
    )
    return svc, engine, table, fallback, ts


def _lease_req(value="hot"):
    return RateLimitRequest(
        domain="chaos", descriptors=(Descriptor.of(("k", value)),)
    )


class TestLeaseFailureLadder:
    def test_outstanding_leases_serve_through_outage(self, test_store):
        """Device dies mid-window: every decision covered by the live
        lease budget still answers OK, with no redis_error and no
        fallback consultation — the outage is invisible until the budget
        runs out."""
        store, sink = test_store
        svc, engine, table, _, _ = _lease_ladder_service(
            FAILURE_MODE_DENY, store
        )
        assert svc.should_rate_limit(_lease_req())[0] == Code.OK  # grant 4
        engine.down = True
        for _ in range(4):  # exactly the leased budget
            assert svc.should_rate_limit(_lease_req())[0] == Code.OK
        store.flush()
        assert (
            sink.counters.get(
                "ratelimit.service.call.should_rate_limit.redis_error", 0
            )
            == 0
        )
        assert sink.counters.get("ratelimit.fallback.deny", 0) == 0
        assert not table.degraded

    @pytest.mark.parametrize(
        "mode,expected_code",
        [
            (FAILURE_MODE_DENY, Code.OVER_LIMIT),
            (FAILURE_MODE_ALLOW, Code.OK),
            (FAILURE_MODE_DEGRADED, Code.OK),
        ],
    )
    def test_exhausted_lease_falls_to_rung(self, test_store, mode, expected_code):
        """Budget exhausted while the device is dark: the renewal attempt
        hits CacheError and the request degrades to the configured rung —
        with the sticky lease.degraded probe raised."""
        store, sink = test_store
        svc, engine, table, fallback, _ = _lease_ladder_service(mode, store)
        svc.should_rate_limit(_lease_req())  # grant 4
        engine.down = True
        for _ in range(4):
            svc.should_rate_limit(_lease_req())
        # budget gone: the next request needs the device
        code, statuses, _ = svc.should_rate_limit(_lease_req())
        assert code == expected_code
        assert statuses[0].code == expected_code
        assert table.degraded
        assert "lease.degraded" in table.degraded_reason()
        store.flush()
        assert sink.gauges["ratelimit.lease.degraded"] == 1
        assert (
            sink.counters[
                "ratelimit.service.call.should_rate_limit.redis_error"
            ]
            == 1
        )

    def test_expired_lease_falls_to_rung(self, test_store):
        """TTL expiry behaves exactly like exhaustion: once the lease is
        dead and the device is dark, the rung answers (the fail-open
        composition the ladder documents)."""
        store, _ = test_store
        svc, engine, table, _, ts = _lease_ladder_service(
            FAILURE_MODE_ALLOW, store
        )
        svc.should_rate_limit(_lease_req())  # grant, TTL 15s
        engine.down = True
        assert svc.should_rate_limit(_lease_req())[0] == Code.OK  # leased
        ts.advance(16)  # TTL passes (window still open)
        code, _, _ = svc.should_rate_limit(_lease_req())
        assert code == Code.OK  # the allow rung, not the lease
        assert table.degraded

    def test_healthcheck_carries_sticky_lease_probe(self, test_store):
        from api_ratelimit_tpu.server.health import HealthChecker

        store, sink = test_store
        svc, engine, table, _, _ = _lease_ladder_service(
            FAILURE_MODE_ALLOW, store
        )
        health = HealthChecker()
        health.add_degraded_probe(table.degraded_reason)
        svc.should_rate_limit(_lease_req())
        assert health.http_response() == (200, "OK")
        engine.down = True
        for _ in range(6):  # exhaust the budget, then fail over
            svc.should_rate_limit(_lease_req())
        status, body = health.http_response()
        assert status == 200 and "lease.degraded" in body
        # recovery: the next successful device interaction clears it
        engine.down = False
        svc.should_rate_limit(_lease_req())
        assert health.http_response() == (200, "OK")
        store.flush()
        assert sink.gauges["ratelimit.lease.degraded"] == 0

    def test_fallback_serves_leased_descriptor_mixed_request(self, test_store):
        """A request mixing a leased and an unleased descriptor while the
        device is dark: the leased one answers from its REAL budget (exact
        remaining), the other by the rung."""
        store, _ = test_store
        svc, engine, table, _, _ = _lease_ladder_service(
            FAILURE_MODE_DENY, store
        )
        svc.should_rate_limit(_lease_req("a"))  # grant for "a"
        engine.down = True
        request = RateLimitRequest(
            domain="chaos",
            descriptors=(
                Descriptor.of(("k", "a")),
                Descriptor.of(("k", "never-seen")),
            ),
        )
        code, statuses, _ = svc.should_rate_limit(request)
        assert statuses[0].code == Code.OK  # from the lease
        assert statuses[0].limit_remaining > 0
        assert statuses[1].code == Code.OVER_LIMIT  # the deny rung
        assert code == Code.OVER_LIMIT
        store.flush()
        snap = store.debug_snapshot()
        assert snap["ratelimit.lease.fallback_hits"] == 1


# ---------------------------------------------------------------------------
# Warm-standby replication chaos (persist/replication.py): each injectable
# failure — replication lag, a partitioned standby, a corrupt delta frame —
# exercised through live traffic, then the SIGKILL acceptance scenario.
# ---------------------------------------------------------------------------


class TestReplicationChaos:
    def _cluster(self, tmp_path, interval_ms=20.0, faults_p=None, faults_s=None):
        from api_ratelimit_tpu.persist.replication import (
            ReplicationCoordinator,
        )
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        def make_engine():
            return SlabDeviceEngine(
                time_source=RealTimeSource(),
                n_slots=1 << 10,
                buckets=(128,),
                max_batch=1024,
                use_pallas=False,
                block_mode=True,
            )

        p_sock = str(tmp_path / "p.sock")
        s_sock = str(tmp_path / "s.sock")
        p_engine = make_engine()
        p_coord = ReplicationCoordinator(
            p_engine, "primary", interval_ms=interval_ms, fault_injector=faults_p
        )
        p_server = SlabSidecarServer(p_sock, p_engine, repl=p_coord)
        p_coord.start()
        s_engine = make_engine()
        s_coord = ReplicationCoordinator(
            s_engine,
            "standby",
            peer_address=p_sock,
            interval_ms=interval_ms,
            fault_injector=faults_s,
        )
        s_server = SlabSidecarServer(s_sock, s_engine, repl=s_coord)
        s_coord.start()
        return p_sock, s_sock, p_server, p_coord, s_server, s_coord

    def test_replication_lag_raises_degraded_while_serving(self, tmp_path):
        """repl.ship delay_ms (a slow/partitioned link): the primary's
        repl.degraded probe fires while client traffic keeps flowing
        un-degraded — replication is never on the serving path."""
        from api_ratelimit_tpu.testing.faults import FaultInjector

        faults = FaultInjector(
            parse_fault_spec("repl.ship:delay_ms:500"), seed=1
        )
        p_sock, s_sock, p_srv, p_coord, s_srv, s_coord = self._cluster(
            tmp_path, interval_ms=20.0, faults_p=faults
        )
        client = SidecarEngineClient(
            [p_sock, s_sock], retries=2, breaker_threshold=0
        )
        try:
            for _ in range(10):
                client.submit(_item())  # serving is unaffected
            time.sleep(0.2)
            reason = p_coord.degraded_reason()
            assert reason is not None and "repl.degraded" in reason
        finally:
            faults.clear()
            client.close()
            p_srv.close()
            p_coord.close()
            s_srv.close()
            s_coord.close()

    def test_partitioned_standby_resyncs_when_the_link_heals(self, tmp_path):
        """repl.ship drop (a partition that eats frames): sequence gaps
        force full resyncs, and once the partition heals the standby
        converges on the primary's true counters."""
        from api_ratelimit_tpu.testing.faults import FaultInjector

        faults = FaultInjector(parse_fault_spec("repl.ship:drop:0.4"), seed=5)
        p_sock, s_sock, p_srv, p_coord, s_srv, s_coord = self._cluster(
            tmp_path, interval_ms=15.0, faults_p=faults
        )
        client = SidecarEngineClient(
            [p_sock, s_sock], retries=2, breaker_threshold=0
        )
        try:
            for _ in range(15):
                client.submit(_item(fp=77))
            deadline = time.monotonic() + 10.0
            while s_coord.resyncs_total < 1:
                assert time.monotonic() < deadline, "gap never forced a resync"
                time.sleep(0.01)
            faults.clear()  # partition heals
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                tables, _, _ = s_coord.replica_state()
                if tables is not None:
                    rows = tables[0]
                    hit = rows[rows[:, 0] == 77]
                    if hit.shape[0] and int(hit[0, 2]) == 15:
                        break
                time.sleep(0.02)
            else:
                pytest.fail("standby never converged after the partition")
        finally:
            client.close()
            p_srv.close()
            p_coord.close()
            s_srv.close()
            s_coord.close()

    def test_corrupt_delta_frame_forces_resync_never_divergence(self, tmp_path):
        """repl.apply torn_write (a corrupt frame): the standby must
        refuse to apply it, resync, and land on the true counter — a
        corrupt delta can delay convergence but never skew it."""
        from api_ratelimit_tpu.testing.faults import FaultInjector

        class _OneShot(FaultInjector):
            def __init__(self):
                super().__init__(
                    parse_fault_spec("repl.apply:torn_write:1.0")
                )
                self.shots = 2

            def fire(self, site):
                if self.shots <= 0:
                    return None
                action = super().fire(site)
                if action is not None:
                    self.shots -= 1
                return action

        faults = _OneShot()
        p_sock, s_sock, p_srv, p_coord, s_srv, s_coord = self._cluster(
            tmp_path, interval_ms=15.0, faults_s=faults
        )
        client = SidecarEngineClient(
            [p_sock, s_sock], retries=2, breaker_threshold=0
        )
        try:
            for _ in range(9):
                client.submit(_item(fp=88))
            deadline = time.monotonic() + 10.0
            while s_coord.resyncs_total < 1:
                assert time.monotonic() < deadline, "corruption never resynced"
                time.sleep(0.01)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                tables, _, _ = s_coord.replica_state()
                if tables is not None:
                    rows = tables[0]
                    hit = rows[rows[:, 0] == 88]
                    if hit.shape[0] and int(hit[0, 2]) == 9:
                        break
                time.sleep(0.02)
            else:
                pytest.fail("standby never converged after corruption")
        finally:
            client.close()
            p_srv.close()
            p_coord.close()
            s_srv.close()
            s_coord.close()


_REPL_OWNER_CHILD = """\
import json, os, sys, time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, {repo!r})

from api_ratelimit_tpu.backends.sidecar import SlabSidecarServer
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
from api_ratelimit_tpu.persist.replication import ReplicationCoordinator
from api_ratelimit_tpu.utils.timeutil import RealTimeSource

sock, role, peer, ctl, interval_ms = (
    sys.argv[1], sys.argv[2], sys.argv[3], sys.argv[4], float(sys.argv[5])
)
engine = SlabDeviceEngine(
    RealTimeSource(),
    n_slots=1 << 12,
    use_pallas=False,
    buckets=(128,),
    block_mode=True,
)
coord = ReplicationCoordinator(
    engine,
    role,
    peer_address=(peer if peer != "-" else None),
    interval_ms=interval_ms,
)
server = SlabSidecarServer(sock, engine, repl=coord)
coord.start()
with open(ctl + ".ready", "w") as f:
    f.write("ok")
while True:  # runs until SIGKILLed / SIGTERMed by the parent
    with open(ctl + ".stats.tmp", "w") as f:
        json.dump(
            {{
                "role": coord.role,
                "epoch": coord.epoch,
                "stale_epoch_rejected": coord.stale_epoch_rejected_total,
                "frames_shipped": coord.frames_shipped_total,
                "frames_applied": coord.frames_applied_total,
                "promotions": coord.promotions_total,
            }},
            f,
        )
    os.replace(ctl + ".stats.tmp", ctl + ".stats")
    time.sleep(0.02)
"""


class TestSigkillFailoverAcceptance:
    """The acceptance scenario: SIGKILL the primary device-owner
    SUBPROCESS under closed-loop load with a live standby. Zero failed
    requests (the client rides retries + failover while the standby
    promotes), counter overshoot bounded by one REPL_INTERVAL_MS of
    admitted traffic (differential vs the exact oracle), and a
    resurrected stale primary's write is rejected with a pinned
    stale_epoch_rejected count."""

    INTERVAL_MS = 50.0

    def _spawn(self, sock, role, peer, ctl):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        return subprocess.Popen(
            [
                sys.executable,
                "-c",
                _REPL_OWNER_CHILD.format(repo=repo),
                sock,
                role,
                peer,
                ctl,
                str(self.INTERVAL_MS),
            ],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    @staticmethod
    def _wait_ready(ctl, timeout=60.0):
        import os

        deadline = time.time() + timeout
        while not os.path.exists(ctl + ".ready"):
            assert time.time() < deadline, "device owner never came up"
            time.sleep(0.05)
        os.unlink(ctl + ".ready")

    @staticmethod
    def _child_stats(ctl, timeout=30.0):
        import json as json_mod
        import os

        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                with open(ctl + ".stats") as f:
                    return json_mod.load(f)
            except (OSError, ValueError):
                time.sleep(0.05)
        raise AssertionError("child never published stats")

    def test_kill9_primary_under_closed_loop_load(self, tmp_path):
        import os
        import random
        import signal
        import struct as struct_mod

        import numpy as np

        from api_ratelimit_tpu.backends.sidecar import (
            FLAG_EPOCH,
            MAGIC,
            OP_SUBMIT,
            STATUS_STALE_EPOCH,
            VERSION,
            SidecarEngineClient,
            _HDR,
            _recv_exact,
            encode_items,
        )
        from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache
        from api_ratelimit_tpu.testing.oracle import occurrence_rank
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        p_sock = str(tmp_path / "p.sock")
        s_sock = str(tmp_path / "s.sock")
        p_ctl = str(tmp_path / "p_ctl")
        s_ctl = str(tmp_path / "s_ctl")

        primary = self._spawn(p_sock, "primary", "-", p_ctl)
        standby = None
        try:
            self._wait_ready(p_ctl)
            standby = self._spawn(s_sock, "standby", p_sock, s_ctl)
            self._wait_ready(s_ctl)

            # hour window: no window roll mid-test; limit 50 so the run
            # crosses it and the oracle comparison bites
            yaml_text = (
                "domain: chaos\n"
                "descriptors:\n"
                "  - key: k\n"
                "    rate_limit: {unit: hour, requests_per_unit: 50}\n"
            )
            from api_ratelimit_tpu.stats import Store, TestSink

            store = Store(TestSink())
            base = BaseRateLimiter(
                RealTimeSource(),
                jitter_rand=random.Random(0),
                expiration_jitter_max_seconds=0,
            )
            client = SidecarEngineClient(
                [p_sock, s_sock],
                retries=6,
                retry_backoff=0.02,
                retry_backoff_max=0.2,
                breaker_threshold=3,
                breaker_reset=0.1,
            )
            cache = TpuRateLimitCache(base, engine=client)
            svc = RateLimitService(
                runtime=_FakeRuntime({"config.chaos": yaml_text}),
                cache=cache,
                stats_scope=store.scope("ratelimit").scope("service"),
                time_source=RealTimeSource(),
            )

            errors: list[Exception] = []
            admits: list[float] = []  # monotonic stamp per admitted req
            total = [0]

            def drive(n):
                for _ in range(n):
                    total[0] += 1
                    try:
                        code, _, _ = svc.should_rate_limit(
                            _lease_req("hot")
                        )
                    except Exception as e:  # noqa: BLE001 - the assert
                        errors.append(e)
                    else:
                        if code == Code.OK:
                            admits.append(time.monotonic())
                    time.sleep(0.002)  # ~500/s closed loop

            drive(30)
            # let at least two replication intervals ship
            time.sleep(3.0 * self.INTERVAL_MS / 1e3)
            p_stats = self._child_stats(p_ctl)
            assert p_stats["frames_shipped"] >= 2

            t_kill = time.monotonic()
            os.kill(primary.pid, signal.SIGKILL)
            primary.wait(timeout=10)

            drive(60)  # rides failover + promotion

            # 1) zero failed requests through the crash
            assert errors == [], errors[:3]

            # 2) the standby promoted
            s_stats = self._child_stats(s_ctl)
            assert s_stats["role"] == "primary"
            assert s_stats["promotions"] == 1
            assert s_stats["epoch"] >= 2

            # 3) overshoot vs the exact oracle bounded by one replication
            # interval of admitted traffic (+ scheduling slack; no leases
            # in this run, so the lease term is 0)
            ids = np.zeros(total[0], dtype=np.int64)
            oracle_admitted = int(np.sum(occurrence_rank(ids) + 1 <= 50))
            overshoot = len(admits) - oracle_admitted
            window_s = 3.0 * self.INTERVAL_MS / 1e3  # interval + slack
            lost_window = sum(
                1 for t in admits if t_kill - window_s < t <= t_kill
            )
            assert overshoot <= lost_window + 2, (
                f"overshoot {overshoot} exceeds one replication interval "
                f"of admitted traffic ({lost_window})"
            )

            # 4) the split-brain guard: resurrect the old primary fresh
            # (epoch 1) and fence a write on the promoted epoch
            primary = self._spawn(p_sock, "primary", "-", p_ctl)
            self._wait_ready(p_ctl)
            conn = __import__("socket").socket(
                __import__("socket").AF_UNIX,
                __import__("socket").SOCK_STREAM,
            )
            conn.connect(p_sock)
            from api_ratelimit_tpu.backends.tpu import _Item

            payload = encode_items(
                [_Item(fp=7, hits=1, limit=50, divider=3600, jitter=0)]
            )
            conn.sendall(
                _HDR.pack(MAGIC, VERSION, OP_SUBMIT, FLAG_EPOCH)
                + payload
                + struct_mod.pack("<I", client._epoch_known)
            )
            assert _recv_exact(conn, 1) == bytes([STATUS_STALE_EPOCH])
            conn.close()
            deadline = time.time() + 10
            while time.time() < deadline:
                if self._child_stats(p_ctl)["stale_epoch_rejected"] > 0:
                    break
                time.sleep(0.05)
            assert self._child_stats(p_ctl)["stale_epoch_rejected"] > 0

            client.close()
            cache.close()
        finally:
            for proc in (primary, standby):
                if proc is not None and proc.poll() is None:
                    proc.terminate()
                    try:
                        proc.wait(timeout=10)
                    except Exception:
                        proc.kill()


_LEASE_OWNER_CHILD = """\
import os, sys, time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, {repo!r})

from api_ratelimit_tpu.backends.sidecar import SlabSidecarServer
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter
from api_ratelimit_tpu.utils.timeutil import RealTimeSource

snap_dir, sock, ctl = sys.argv[1], sys.argv[2], sys.argv[3]
engine = SlabDeviceEngine(
    RealTimeSource(),
    n_slots=1 << 12,
    use_pallas=False,
    buckets=(128,),
    block_mode=True,
)
snap = SlabSnapshotter(engine, snap_dir, interval_ms=3_600_000.0)
snap.restore()  # warm boot: slab + lease liabilities (floors applied)
server = SlabSidecarServer(sock, engine)
with open(ctl + ".ready", "w") as f:
    f.write("ok")
while True:  # runs until SIGKILLed / SIGTERMed by the parent
    if os.path.exists(ctl + ".snap_req"):
        os.unlink(ctl + ".snap_req")
        snap.snapshot_once()
        with open(ctl + ".snap_done", "w") as f:
            f.write("ok")
    time.sleep(0.02)
"""


class TestSigkillDeviceOwnerWithLeases:
    """The lease chaos acceptance: SIGKILL the device-owner process under
    lease-held Zipf traffic. While leases live the frontend keeps
    answering with ZERO failed requests; after the owner restarts from
    its snapshot (slab + lease liabilities), total admitted for the hot
    key overshoots the exact oracle by at most the outstanding lease
    budgets at the kill — and with the liability floors restored, by 0."""

    def test_kill9_under_lease_held_traffic(self, tmp_path):
        import os
        import random
        import signal
        import subprocess
        import sys

        import numpy as np

        from api_ratelimit_tpu.backends.lease import LeaseTable
        from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache
        from api_ratelimit_tpu.service.ratelimit import RateLimitService
        from api_ratelimit_tpu.stats import Store, TestSink
        from api_ratelimit_tpu.testing.oracle import occurrence_rank
        from api_ratelimit_tpu.utils.timeutil import RealTimeSource

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        snap_dir = str(tmp_path / "snaps")
        os.makedirs(snap_dir)
        sock = str(tmp_path / "owner.sock")
        ctl = str(tmp_path / "ctl")

        def spawn():
            return subprocess.Popen(
                [
                    sys.executable,
                    "-c",
                    _LEASE_OWNER_CHILD.format(repo=repo),
                    snap_dir,
                    sock,
                    ctl,
                ],
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )

        def wait_ready(timeout=60.0):
            deadline = time.time() + timeout
            while not os.path.exists(ctl + ".ready"):
                assert time.time() < deadline, "device owner never came up"
                time.sleep(0.05)
            os.unlink(ctl + ".ready")

        # hour window: no window roll and no lease TTL expiry mid-test —
        # "while leases live" holds for the whole run by construction
        yaml_text = (
            "domain: chaos\n"
            "descriptors:\n"
            "  - key: k\n"
            "    rate_limit: {unit: hour, requests_per_unit: 50}\n"
        )

        proc = spawn()
        try:
            wait_ready()
            from api_ratelimit_tpu.backends.sidecar import SidecarEngineClient

            store = Store(TestSink())
            base = BaseRateLimiter(
                RealTimeSource(),
                jitter_rand=random.Random(0),
                expiration_jitter_max_seconds=0,
            )
            table = LeaseTable(base, min_size=4, max_size=16)
            client = SidecarEngineClient(
                sock, retries=0, breaker_threshold=0
            )
            cache = TpuRateLimitCache(
                base, engine=client, lease_table=table
            )
            svc = RateLimitService(
                runtime=_FakeRuntime({"config.chaos": yaml_text}),
                cache=cache,
                stats_scope=store.scope("ratelimit").scope("service"),
                time_source=RealTimeSource(),
                lease=table,
            )

            # Zipf-ish lease-held traffic: a hot key plus a tail
            rng = np.random.default_rng(5)
            tail = [f"t{int(i)}" for i in (rng.zipf(1.3, 40) % 8)]
            stream = []
            admitted_hot = 0
            for i in range(30):
                stream.append("hot")
                code, _, _ = svc.should_rate_limit(_lease_req("hot"))
                if code == Code.OK:
                    admitted_hot += 1
                if i < len(tail):
                    svc.should_rate_limit(_lease_req(tail[i]))

            # one deterministic snapshot (slab + lease liabilities)...
            with open(ctl + ".snap_req", "w") as f:
                f.write("go")
            deadline = time.time() + 30
            while not os.path.exists(ctl + ".snap_done"):
                assert time.time() < deadline, "owner never snapshotted"
                time.sleep(0.05)

            held, outstanding = table.outstanding()
            assert held >= 1 and outstanding > 0

            # the hot key's own remaining leased budget (the zero-failure
            # window): read it the way the decide path would
            from api_ratelimit_tpu.ops.hashing import fingerprint64

            fp_hot = fingerprint64(
                "chaos", Descriptor.of(("k", "hot")).entries, 3600
            )
            now = int(time.time())
            window = now - now % 3600
            hot_lease = table._leases.get((fp_hot, window))
            assert hot_lease is not None
            budget = min(hot_lease.granted - hot_lease.consumed, 8)
            assert budget > 0

            # ...then kill -9 the owner mid-stream
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=10)

            # zero failed requests while leases live: the hot key's
            # remaining budget answers locally with the owner DEAD
            for _ in range(budget):
                stream.append("hot")
                code, _, _ = svc.should_rate_limit(_lease_req("hot"))
                assert code == Code.OK
                admitted_hot += 1

            # owner restarts from the snapshot; frontends redial free
            proc = spawn()
            wait_ready()

            # run the hot key well past its limit
            for _ in range(60):
                stream.append("hot")
                code, _, _ = svc.should_rate_limit(_lease_req("hot"))
                if code == Code.OK:
                    admitted_hot += 1

            # exact oracle for the single-key stream: first LIMIT
            # occurrences admitted (testing/oracle.py semantics)
            ids = np.zeros(
                sum(1 for s in stream if s == "hot"), dtype=np.int64
            )
            oracle_admitted = int(np.sum(occurrence_rank(ids) + 1 <= 50))
            overshoot = admitted_hot - oracle_admitted
            # the PINNED bound: overshoot <= Σ outstanding lease budgets
            # at the kill; with the liability floors restored it is 0
            assert overshoot <= outstanding
            assert overshoot <= 0, (
                f"liability floors must prevent double-granting "
                f"(admitted {admitted_hot}, oracle {oracle_admitted})"
            )
            client.close()
            cache.close()
        finally:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


# ---------------------------------------------------------------------------
# Tiered-slab chaos (backends/victim.py): the victim.demote fault site as
# the "what the tier buys" measurement arm, then the SIGKILL acceptance —
# an owner killed under eviction pressure restores the victim tier from
# victim.snap and overshoots the exact oracle by at most one snapshot
# interval of admitted traffic.
# ---------------------------------------------------------------------------


def _vfp(set_idx, uid):
    """Colliding fingerprints for a tiny n_slots=8 / ways=2 slab: set =
    fp_lo & 3, distinct top-16 fp_hi bits per uid (the tests/test_victim.py
    construction)."""
    return (((uid + 1) << 16) << 32) | ((set_idx & 3) | (uid << 2))


class TestVictimTierChaos:
    def _pressure(self, eng):
        """One demotion's worth of set pressure on set 0."""
        for uid in (1, 2):
            for _ in range(3):
                eng._launch(
                    [_Item(fp=_vfp(0, uid), hits=1, limit=100,
                           divider=3600, jitter=0)]
                )
        eng._launch(
            [_Item(fp=_vfp(0, 3), hits=1, limit=100, divider=3600, jitter=0)]
        )

    def test_demote_drop_arm_measures_what_the_tier_buys(self):
        """victim.demote:drop:1.0 IS the pre-tier behavior (rows silently
        vanish); clearing the fault mid-scenario — the outage "ends" —
        restores the hierarchy, so one run measures the tier's value."""
        inj = FaultInjector.from_spec("victim.demote:drop:1.0")
        eng = SlabDeviceEngine(
            FakeTimeSource(1_000_000),
            n_slots=8,
            ways=2,
            buckets=(16,),
            use_pallas=False,
            victim_max_rows=64,
            fault_injector=inj,
        )
        self._pressure(eng)
        assert eng.victim_tier.rows == 0  # the loss arm: nothing absorbed
        assert inj.fired().get("victim.demote:drop", 0) >= 1
        inj.clear()  # the outage ends
        eng._launch(
            [_Item(fp=_vfp(0, 4), hits=1, limit=100, divider=3600, jitter=0)]
        )
        assert eng.victim_tier.rows == 1  # the tier is back in the loop


_VICTIM_OWNER_CHILD = """\
import json, os, sys, time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, {repo!r})

from api_ratelimit_tpu.backends.sidecar import SlabSidecarServer
from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine
from api_ratelimit_tpu.persist.snapshotter import SlabSnapshotter
from api_ratelimit_tpu.utils.timeutil import RealTimeSource

snap_dir, sock, ctl = sys.argv[1], sys.argv[2], sys.argv[3]
# a deliberately TINY slab (8 rows, 2 ways) so a handful of keys is
# already keyspace overload -> live evictions -> victim-tier traffic
engine = SlabDeviceEngine(
    RealTimeSource(),
    n_slots=8,
    ways=2,
    buckets=(16,),
    use_pallas=False,
    block_mode=True,
    victim_max_rows=256,
)
snap = SlabSnapshotter(engine, snap_dir, interval_ms=3_600_000.0)
snap.restore()  # warm boot: slab shards + victim.snap (FLAG_VICTIM)
server = SlabSidecarServer(sock, engine)
with open(ctl + ".ready", "w") as f:
    f.write("ok")
while True:  # runs until SIGKILLed / SIGTERMed by the parent
    if os.path.exists(ctl + ".snap_req"):
        os.unlink(ctl + ".snap_req")
        snap.snapshot_once()
        with open(ctl + ".snap_done", "w") as f:
            f.write("ok")
    with open(ctl + ".stats.tmp", "w") as f:
        json.dump(
            dict(
                restore=snap.restore_stats,
                victim_rows=engine.victim_debug().get("rows", -1),
            ),
            f,
        )
    os.replace(ctl + ".stats.tmp", ctl + ".stats")
    time.sleep(0.02)
"""


class TestSigkillVictimTier:
    """The tiered-slab chaos acceptance: SIGKILL the device-owner process
    UNDER EVICTION PRESSURE — the hot key's live counter is sitting in
    the host victim tier, not on the slab, when the process dies. The
    restarted owner restores the tier from victim.snap and the key
    RESUMES mid-window: total admitted overshoots the exact per-key
    oracle by at most the admits of one snapshot interval (everything
    after the last snapshot_once), never by a whole reset window."""

    LIMIT = 50

    def test_kill9_under_eviction_pressure_restores_victim_snap(
        self, tmp_path
    ):
        import json as json_mod
        import os
        import signal
        import subprocess
        import sys

        from api_ratelimit_tpu.backends.sidecar import SidecarEngineClient

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        snap_dir = str(tmp_path / "snaps")
        os.makedirs(snap_dir)
        sock = str(tmp_path / "owner.sock")
        ctl = str(tmp_path / "ctl")

        def spawn():
            return subprocess.Popen(
                [
                    sys.executable,
                    "-c",
                    _VICTIM_OWNER_CHILD.format(repo=repo),
                    snap_dir,
                    sock,
                    ctl,
                ],
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )

        def wait_ready(timeout=60.0):
            deadline = time.time() + timeout
            while not os.path.exists(ctl + ".ready"):
                assert time.time() < deadline, "device owner never came up"
                time.sleep(0.05)
            os.unlink(ctl + ".ready")

        def child_stats(want=None, timeout=30.0):
            """Latest child stats; with `want` set, polls until the
            predicate holds (the stats file trails the engine by one
            publish tick) or returns the last snapshot at timeout."""
            deadline = time.time() + timeout
            last = None
            while time.time() < deadline:
                try:
                    with open(ctl + ".stats") as f:
                        last = json_mod.load(f)
                except (OSError, ValueError):
                    last = None
                if last is not None and (want is None or want(last)):
                    return last
                time.sleep(0.05)
            if last is not None:
                return last
            raise AssertionError("child never published stats")

        HOT, FILL, EVICTOR = _vfp(0, 2), _vfp(0, 1), _vfp(0, 3)
        proc = spawn()
        try:
            wait_ready()
            client = SidecarEngineClient(
                sock,
                retries=4,
                retry_backoff=0.02,
                retry_backoff_max=0.2,
                breaker_threshold=0,
            )

            admitted = [0]

            def sub(fp, n=1):
                last = 0
                for _ in range(n):
                    last = client.submit(
                        [_Item(fp=fp, hits=1, limit=self.LIMIT,
                               divider=3600, jitter=0)]
                    )[0]
                    if last <= self.LIMIT:
                        admitted[0] += 1
                return last

            # the hot key lives on the slab at count 30...
            assert sub(HOT, 30) == 30
            # ...until keyspace overload: a heavier neighbor fills its
            # set and a new key's insert demotes the LIGHTER live row —
            # the hot counter now exists ONLY in the host victim tier
            for _ in range(40):
                client.submit(
                    [_Item(fp=FILL, hits=1, limit=1_000_000,
                           divider=3600, jitter=0)]
                )
            client.submit(
                [_Item(fp=EVICTOR, hits=1, limit=1_000_000,
                       divider=3600, jitter=0)]
            )
            got = child_stats(want=lambda s: s["victim_rows"] == 1)
            assert got["victim_rows"] == 1

            # one deterministic snapshot: slab shards + victim.snap
            with open(ctl + ".snap_req", "w") as f:
                f.write("go")
            deadline = time.time() + 30
            while not os.path.exists(ctl + ".snap_done"):
                assert time.time() < deadline, "owner never snapshotted"
                time.sleep(0.05)

            # one snapshot interval of post-snapshot traffic: the hot
            # key promotes back out of the tier and RESUMES (31..35) —
            # these 5 admits are exactly what the kill may lose
            before_lost = admitted[0]
            assert sub(HOT, 5) == 35
            lost_window = admitted[0] - before_lost
            assert lost_window == 5

            # kill -9 mid-pressure, restart from the snapshot set
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=10)
            proc = spawn()
            wait_ready()

            # the victim tier came back from victim.snap, not cold
            stats = child_stats(want=lambda s: s["victim_rows"] == 1)
            assert stats["restore"]["restored"]
            assert stats["restore"]["restored_victim_rows"] == 1
            assert stats["victim_rows"] == 1

            # the hot key's FIRST post-restart decision resumes from the
            # tier-restored counter (30 + 1), not from a silent reset
            assert sub(HOT, 1) == 31
            sub(HOT, 59)  # run well past the limit

            # exact single-key oracle: first LIMIT occurrences admitted
            overshoot = admitted[0] - self.LIMIT
            assert overshoot <= lost_window, (
                f"overshoot {overshoot} exceeds one snapshot interval "
                f"of admitted traffic ({lost_window}) — victim.snap "
                f"restore must bound the loss"
            )
            client.close()
        finally:
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
