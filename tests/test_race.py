"""Concurrency races + property-based differential fuzzing.

The reference runs its whole suite under `go test -race` (Makefile:83-89)
and unit-tests its known race windows (memcache add/increment, locked rand,
burst sampler CAS — SURVEY.md §5.2). Python has no race detector, so these
tests attack the same windows directly: many threads hammering the hot path
while config reloads swap state underneath, plus hypothesis-driven random
op streams holding the slab engine to the memory oracle.
"""

from __future__ import annotations

import os
import threading

import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

# Default example counts keep the suite fast; an extended campaign sets
# SLAB_FUZZ_EXAMPLES (e.g. 2000) to mine the same differential properties
# much deeper on idle hardware.
FUZZ_EXAMPLES = int(os.environ.get("SLAB_FUZZ_EXAMPLES", "0") or 0)

from api_ratelimit_tpu.backends.memory import MemoryRateLimitCache
from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
from api_ratelimit_tpu.models.config import RateLimit, new_rate_limit_stats
from api_ratelimit_tpu.models.descriptors import Descriptor, RateLimitRequest
from api_ratelimit_tpu.models.response import RateLimitValue
from api_ratelimit_tpu.models.units import Unit
from api_ratelimit_tpu.service.ratelimit import RateLimitService
from api_ratelimit_tpu.utils.timeutil import FakeTimeSource


class _MutableRuntime:
    """Runtime whose snapshot can be swapped between reloads."""

    def __init__(self, yaml_text: str):
        self.yaml_text = yaml_text
        self._lock = threading.Lock()

    def snapshot(self):
        outer = self

        class Snap:
            def keys(self):
                return ["config.test"]

            def get(self, key):
                with outer._lock:
                    return outer.yaml_text

        return Snap()

    def add_update_callback(self, cb):
        pass

    def set_yaml(self, text: str):
        with self._lock:
            self.yaml_text = text


_YAML_A = """\
domain: racing
descriptors:
  - key: k
    rate_limit: {unit: hour, requests_per_unit: 1000000}
"""

_YAML_B = """\
domain: racing
descriptors:
  - key: k
    rate_limit: {unit: hour, requests_per_unit: 999999}
  - key: other
    rate_limit: {unit: minute, requests_per_unit: 5}
"""


class TestReloadUnderFire:
    def test_hot_path_races_config_reload(self, test_store):
        """Requests must never observe a broken config mid-swap: every call
        either resolves against config A or config B, and reloads never
        raise (ratelimit.go's RWMutex window, :302-306)."""
        store, _ = test_store
        ts = FakeTimeSource(1000)
        base = BaseRateLimiter(time_source=ts, jitter_rand=None)
        runtime = _MutableRuntime(_YAML_A)
        service = RateLimitService(
            runtime=runtime,
            cache=MemoryRateLimitCache(base),
            stats_scope=store.scope("ratelimit").scope("service"),
            time_source=ts,
        )
        errors: list[BaseException] = []
        stop = threading.Event()

        def hammer():
            req = RateLimitRequest(
                domain="racing", descriptors=(Descriptor.of(("k", "v")),)
            )
            while not stop.is_set():
                try:
                    overall, statuses, _ = service.should_rate_limit(req)
                    # limit must come from exactly config A or config B
                    rpu = statuses[0].current_limit.requests_per_unit
                    if rpu not in (1_000_000, 999_999):
                        raise AssertionError(f"torn config: {rpu}")
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
                    return

        def reloader():
            flip = False
            while not stop.is_set():
                runtime.set_yaml(_YAML_B if flip else _YAML_A)
                try:
                    service.reload_config()
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
                    return
                flip = not flip

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        threads.append(threading.Thread(target=reloader))
        for t in threads:
            t.start()
        import time

        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(5.0)
        assert not errors

    def test_memory_backend_concurrent_counts_exact(self, test_store):
        """N threads x M hits on one key must count to exactly N*M — the
        memory backend's lock must serialize increments."""
        store, _ = test_store
        ts = FakeTimeSource(5000)
        base = BaseRateLimiter(time_source=ts, jitter_rand=None)
        cache = MemoryRateLimitCache(base)
        scope = store.scope("t")
        limit = RateLimit(
            full_key="k",
            stats=new_rate_limit_stats(scope, "k"),
            limit=RateLimitValue(requests_per_unit=1_000_000, unit=Unit.HOUR),
        )
        req = RateLimitRequest(
            domain="c", descriptors=(Descriptor.of(("k", "v")),)
        )
        n_threads, per_thread = 8, 200
        results: list[int] = []
        lock = threading.Lock()

        def worker():
            local = []
            for _ in range(per_thread):
                resp = cache.do_limit(req, [limit])
                local.append(resp.descriptor_statuses[0].limit_remaining)
            with lock:
                results.extend(local)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        total = n_threads * per_thread
        # every decision got a distinct remaining value => exact serialization
        assert len(set(results)) == total
        assert min(results) == 1_000_000 - total

    def test_pipelined_batcher_concurrent_counts_exact(self, test_store):
        """Same exactness through the DOUBLE-BUFFERED tpu backend: the
        dispatch loop launches batch k+1 while it redeems batch k's
        readback (backends/dispatch.py), and no result may be lost,
        duplicated, or misrouted across that handoff."""
        self._tpu_counts_exact(test_store, batch_window_seconds=0.0005)

    def test_direct_mode_tpu_concurrent_counts_exact(self, test_store):
        """The same through direct mode (TPU_BATCH_WINDOW=0): callers
        take turns at the direct lock, one launch each."""
        self._tpu_counts_exact(test_store, batch_window_seconds=0.0)

    @staticmethod
    def _tpu_counts_exact(test_store, batch_window_seconds):
        from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache

        store, _ = test_store
        base = BaseRateLimiter(time_source=FakeTimeSource(5000), jitter_rand=None)
        cache = TpuRateLimitCache(
            base,
            n_slots=1 << 12,
            batch_window_seconds=batch_window_seconds,
            max_batch=256,
        )
        scope = store.scope("t")
        limit = RateLimit(
            full_key="k",
            stats=new_rate_limit_stats(scope, "k"),
            limit=RateLimitValue(requests_per_unit=1_000_000, unit=Unit.HOUR),
        )
        req = RateLimitRequest(
            domain="c", descriptors=(Descriptor.of(("k", "v")),)
        )
        n_threads, per_thread = 8, 100
        results: list[int] = []
        lock = threading.Lock()

        def worker():
            local = []
            for _ in range(per_thread):
                resp = cache.do_limit(req, [limit])
                local.append(resp.descriptor_statuses[0].limit_remaining)
            with lock:
                results.extend(local)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        cache.close()
        total = n_threads * per_thread
        assert len(results) == total
        assert len(set(results)) == total
        assert min(results) == 1_000_000 - total


class TestSlabPropertyDifferential:
    """hypothesis-driven random op streams: the slab engine must agree with
    the memory oracle on every decision code (the §4.4 differential oracle,
    fuzzed rather than hand-cased)."""

    @settings(max_examples=FUZZ_EXAMPLES or 20, deadline=None)
    @given(
        ops=st.lists(
            st.tuples(
                st.integers(min_value=0, max_value=7),  # key id
                # mostly small hits; occasionally large enough to push
                # counters across the u8/u16 readback-width boundaries
                st.one_of(
                    st.integers(min_value=1, max_value=3),
                    st.sampled_from([100, 40000]),
                ),
                st.integers(min_value=0, max_value=90),  # seconds to advance
            ),
            min_size=1,
            max_size=60,
        ),
        limit_rpu=st.one_of(
            st.integers(min_value=1, max_value=6),
            st.sampled_from([250, 300, 70000]),
        ),
        unit=st.sampled_from([Unit.SECOND, Unit.MINUTE, Unit.HOUR]),
    )
    def test_engine_matches_oracle(self, ops, limit_rpu, unit):
        from api_ratelimit_tpu.backends.tpu import TpuRateLimitCache
        from api_ratelimit_tpu.stats.sinks import NullSink
        from api_ratelimit_tpu.stats.store import Store

        store = Store(NullSink())
        scope = store.scope("t")

        def fresh(name):
            ts = FakeTimeSource(700_000)
            base = BaseRateLimiter(time_source=ts, jitter_rand=None)
            limit = RateLimit(
                full_key=name,
                stats=new_rate_limit_stats(scope, name),
                limit=RateLimitValue(requests_per_unit=limit_rpu, unit=unit),
            )
            return ts, base, limit

        ts_e, base_e, limit_e = fresh("engine")
        ts_o, base_o, limit_o = fresh("oracle")
        engine = TpuRateLimitCache(base_e, n_slots=256)
        oracle = MemoryRateLimitCache(base_o)

        try:
            for key_id, hits, advance in ops:
                ts_e.advance(advance)
                ts_o.advance(advance)
                req = RateLimitRequest(
                    domain="fuzz",
                    descriptors=(Descriptor.of(("k", f"key{key_id}")),),
                    hits_addend=hits,
                )
                got = engine.do_limit(req, [limit_e]).descriptor_statuses[0]
                want = oracle.do_limit(req, [limit_o]).descriptor_statuses[0]
                assert got.code == want.code, (key_id, hits, advance)
                assert got.limit_remaining == want.limit_remaining
        finally:
            engine.close()


class TestBlockPathPropertyDifferential:
    """The sidecar server's block-native path must be op-for-op identical
    to the per-item engine path under random op streams — duplicates in a
    batch, window rollovers, and counter continuation included."""

    @settings(max_examples=FUZZ_EXAMPLES or 15, deadline=None)
    @given(
        ops=st.lists(
            st.tuples(
                st.integers(min_value=0, max_value=5),  # key id
                st.one_of(  # small hits + width-boundary crossers
                    st.integers(min_value=1, max_value=3),
                    st.sampled_from([100, 40000]),
                ),
                st.integers(min_value=0, max_value=90),  # seconds to advance
                st.integers(min_value=1, max_value=3),  # duplicates in batch
            ),
            min_size=1,
            max_size=30,
        ),
        limit=st.one_of(
            st.integers(min_value=1, max_value=6),
            st.sampled_from([250, 300, 70000]),
        ),
        divider=st.sampled_from([1, 60, 3600]),
    )
    def test_block_matches_item_engine(self, ops, limit, divider):
        import numpy as np

        from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, _Item

        ts_a, ts_b = FakeTimeSource(700_000), FakeTimeSource(700_000)
        item_eng = SlabDeviceEngine(
            time_source=ts_a, n_slots=256, use_pallas=False
        )
        blk_eng = SlabDeviceEngine(
            time_source=ts_b, n_slots=256, use_pallas=False, block_mode=True
        )
        try:
            for key_id, hits, advance, repeat in ops:
                ts_a.advance(advance)
                ts_b.advance(advance)
                fp = (0x9E3779B97F4A7C15 * (key_id + 1)) & ((1 << 64) - 1)
                items = [
                    _Item(fp=fp, hits=hits, limit=limit, divider=divider, jitter=0)
                ] * repeat
                block = np.zeros((6, repeat), dtype=np.uint32)
                block[0] = fp & 0xFFFFFFFF
                block[1] = fp >> 32
                block[2] = hits
                block[3] = limit
                block[4] = divider
                want = item_eng.submit(items)
                got = blk_eng.submit_block(block)
                assert want == got.tolist(), (key_id, hits, advance, repeat)
        finally:
            item_eng.close()
            blk_eng.close()
