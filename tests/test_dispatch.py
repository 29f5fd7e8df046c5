"""Persistent device-owner dispatch loop (backends/dispatch.py): submit-ring
mechanics, double-buffered launch overlap, drain/close with tickets parked
in both in-flight buffers, deadline drops at ring take time, the
dispatch.launch chaos site, and engine-level parity with direct mode
(TPU_BATCH_WINDOW=0).
"""

import threading
import time

import numpy as np
import pytest

from api_ratelimit_tpu.backends.dispatch import (
    FAULT_SITE_LAUNCH,
    DispatchLoop,
    SubmitRing,
    _Ticket,
)
from api_ratelimit_tpu.backends.overload import (
    AdmissionController,
    BrownoutError,
    QueueFullError,
)
from api_ratelimit_tpu.limiter.cache import CacheError, DeadlineExceededError
from api_ratelimit_tpu.utils import FakeTimeSource
from api_ratelimit_tpu.utils.deadline import deadline_scope


def test_native_codec_must_load_when_toolchain_present():
    """Build hygiene gate: on a host WITH a g++ toolchain (every CI/dev
    image — `make tests_unit` builds it first) the native codec MUST be
    available. A silently broken build would put the dispatch loop's
    pack/scatter on the pure-Python fallback with no signal; this test is
    the signal. Hosts without the toolchain legitimately fall back."""
    import shutil

    from api_ratelimit_tpu.ops import native

    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain: the pure-Python fallback is expected")
    info = native.build_info()
    assert info["source_present"], "native/host_codec.cpp missing"
    assert info["available"], (
        f"g++ present but native codec failed to build/load "
        f"(so={info['so_path']})"
    )


def _block(values, rows=6):
    """uint32[6, n] block whose hits row carries `values` (easy to assert
    through fake executors)."""
    n = len(values)
    block = np.zeros((rows, n), dtype=np.uint32)
    block[2] = values
    return block


def _echo_loop(**kwargs):
    """A loop whose fake device echoes each block's hits row back."""

    def launch(blocks):
        return [np.array(b[2]) for b in blocks]

    def collect(token):
        return np.concatenate(token)

    return DispatchLoop(launch, collect, **kwargs)


class TestSubmitRing:
    def test_publish_take_roundtrip_and_wraparound(self):
        """Far more frames than slots and far more rows than the arena:
        every frame read back intact — wraparound can reorder storage but
        never corrupt it."""
        ring = SubmitRing(slots=8, arena_rows=32)
        ticket = _Ticket()
        for i in range(100):
            n = 1 + (i % 5)
            ring.publish(
                _block([i] * n), n, None, time.monotonic(), ticket, False
            )
            # consume like the owner: read slot, free arena after "pack"
            slot = ring.slots[ring.head & ring.mask]
            ring.slots[ring.head & ring.mask] = None
            rows, count, _dl, _enq, _t, arena_used = slot
            assert rows[2].tolist() == [i] * n
            assert count == n
            ring.head += 1
            ring.items_out += count
            ring.rows_out += arena_used
        assert ring.depth == 0

    def test_overflow_raises_queue_full_not_corruption(self):
        """With no consumer, slot exhaustion must raise QueueFullError and
        leave every already-published frame intact."""
        ring = SubmitRing(slots=8, arena_rows=1 << 12)
        ticket = _Ticket()
        for i in range(8):
            ring.publish(_block([i]), 1, None, 0.0, ticket, False)
        with pytest.raises(QueueFullError):
            ring.publish(_block([99]), 1, None, 0.0, ticket, False)
        got = [ring.slots[i & ring.mask][0][2][0] for i in range(8)]
        assert got == list(range(8))

    def test_arena_exhaustion_falls_back_to_owned_copy(self):
        """Rows beyond the arena capacity still publish correctly (the
        overflow path copies instead of failing or aliasing)."""
        ring = SubmitRing(slots=64, arena_rows=4)
        ticket = _Ticket()
        src = _block([7, 8, 9])
        ring.publish(src, 3, None, 0.0, ticket, False)  # arena
        ring.publish(src, 3, None, 0.0, ticket, False)  # would wrap: copy
        src[:] = 0xFFFF  # caller reuses scratch
        first = ring.slots[0][0]
        second = ring.slots[1][0]
        # first frame sits in the arena (copied), second is an owned copy
        assert second.base is None or second.base is not ring.arena
        assert first[2].tolist() == [7, 8, 9]
        assert second[2].tolist() == [7, 8, 9]


class TestDispatchLoop:
    def test_results_and_order(self):
        loop = _echo_loop()
        try:
            outs = {}
            lock = threading.Lock()

            def worker(tid):
                got = loop.submit(_block([tid * 10, tid * 10 + 1]))
                with lock:
                    outs[tid] = got.tolist()

            threads = [
                threading.Thread(target=worker, args=(t,)) for t in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(5.0)
            assert outs == {
                t: [t * 10, t * 10 + 1] for t in range(8)
            }
        finally:
            loop.close()

    def test_launch_overlaps_redeem(self):
        """While batch 1's readback is gated mid-execute, a second known
        producer's frame must LAUNCH — the double-buffer overlap is the
        whole point of the loop (successor to
        test_launch_overlaps_collect). Both producers submit once with the
        gate open first: the loop's producer census only waits for
        arrivals from rings it has seen traffic on."""
        launches = []
        gate = threading.Event()
        gate.set()

        def launch(blocks):
            launches.append([np.array(b[2]) for b in blocks])
            return [np.array(b[2]) for b in blocks]

        def collect(token):
            gate.wait(5.0)
            return np.concatenate(token)

        loop = DispatchLoop(launch, collect, ready=lambda t: gate.is_set())
        try:
            # producer threads that live across both submits so each keeps
            # ONE ring: an ungated census warm-up round, then the gated
            # overlap round on the same threads via queues
            import queue as _q

            jobs1, jobs2 = _q.Queue(), _q.Queue()
            out1, out2 = [], []

            def producer(jobs, out):
                while True:
                    v = jobs.get()
                    if v is None:
                        return
                    out.append(loop.submit(_block([v])).tolist())

            p1 = threading.Thread(target=producer, args=(jobs1, out1))
            p2 = threading.Thread(target=producer, args=(jobs2, out2))
            p1.start()
            p2.start()
            jobs1.put(101)
            jobs2.put(102)
            deadline = time.monotonic() + 2.0
            while (not out1 or not out2) and time.monotonic() < deadline:
                time.sleep(0.002)
            assert out1 and out2  # both rings known to the census

            gate.clear()
            n_before = len(launches)
            jobs1.put(1)  # batch 1: launched, readback gated
            deadline = time.monotonic() + 2.0
            while len(launches) < n_before + 1 and time.monotonic() < deadline:
                time.sleep(0.002)
            jobs2.put(2)  # must launch WHILE batch 1 is still gated
            deadline = time.monotonic() + 2.0
            while len(launches) < n_before + 2 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert len(launches) >= n_before + 2, (
                "launch 2 did not overlap redeem 1"
            )
            gate.set()
            jobs1.put(None)
            jobs2.put(None)
            p1.join(5.0)
            p2.join(5.0)
            assert out1 == [[101], [1]] and out2 == [[102], [2]]
        finally:
            gate.set()
            loop.close()

    def test_drain_resolves_tickets_parked_in_both_inflight_buffers(self):
        """drain() with one batch mid-readback AND a second batch launched
        behind it: both buffers' tickets must resolve, then the owner
        thread exits."""
        import queue as _q

        gate = threading.Event()
        gate.set()
        launched = []

        def launch(blocks):
            launched.append(len(blocks))
            return [np.array(b[2]) for b in blocks]

        def collect(token):
            gate.wait(5.0)
            return np.concatenate(token)

        loop = DispatchLoop(launch, collect, ready=lambda t: gate.is_set())
        jobs1, jobs2 = _q.Queue(), _q.Queue()
        out1, out2 = [], []

        def producer(jobs, out):
            while True:
                v = jobs.get()
                if v is None:
                    return
                out.append(int(loop.submit(_block([v]))[0]))

        p1 = threading.Thread(target=producer, args=(jobs1, out1))
        p2 = threading.Thread(target=producer, args=(jobs2, out2))
        p1.start()
        p2.start()
        # census warm-up round, ungated
        jobs1.put(101)
        jobs2.put(102)
        deadline = time.monotonic() + 2.0
        while (not out1 or not out2) and time.monotonic() < deadline:
            time.sleep(0.002)
        gate.clear()
        n_before = len(launched)
        jobs1.put(1)
        deadline = time.monotonic() + 2.0
        while len(launched) < n_before + 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        jobs2.put(2)
        deadline = time.monotonic() + 2.0
        while len(launched) < n_before + 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        # both in-flight buffers occupied, neither redeemed
        assert len(launched) == n_before + 2
        drainer = threading.Thread(target=loop.drain)
        drainer.start()
        gate.set()
        drainer.join(5.0)
        assert not drainer.is_alive(), "drain() hung"
        jobs1.put(None)
        jobs2.put(None)
        p1.join(5.0)
        p2.join(5.0)
        assert out1 == [101, 1] and out2 == [102, 2]
        # post-drain submits are refused
        with pytest.raises(CacheError):
            loop.submit(_block([3]))
        loop.close()

    def test_close_with_inflight(self):
        gate = threading.Event()
        loop = DispatchLoop(
            lambda blocks: [np.array(b[2]) for b in blocks],
            lambda token: (gate.wait(5.0), np.concatenate(token))[1],
        )
        out = []
        t = threading.Thread(target=lambda: out.append(loop.submit(_block([5]))))
        t.start()
        time.sleep(0.05)
        closer = threading.Thread(target=loop.close)
        closer.start()
        gate.set()
        closer.join(5.0)
        assert not closer.is_alive(), "close() deadlocked"
        t.join(5.0)
        assert out and out[0].tolist() == [5]

    def test_launch_error_fails_only_that_batch(self):
        calls = []

        def launch(blocks):
            calls.append(len(blocks))
            if len(calls) == 1:
                raise CacheError("device on fire")
            return [np.array(b[2]) for b in blocks]

        loop = DispatchLoop(
            launch, lambda token: np.concatenate(token)
        )
        try:
            with pytest.raises(CacheError, match="device on fire"):
                loop.submit(_block([1]))
            assert loop.submit(_block([2])).tolist() == [2]
        finally:
            loop.close()

    def test_redeem_error_propagates(self):
        def collect(token):
            raise RuntimeError("readback failed")

        loop = DispatchLoop(
            lambda blocks: [np.array(b[2]) for b in blocks], collect
        )
        try:
            with pytest.raises(RuntimeError, match="readback failed"):
                loop.submit(_block([1]))
        finally:
            loop.close()

    def test_expired_ticket_dropped_at_take_before_packing(self):
        """A frame whose propagated deadline expired while queued resolves
        as DeadlineExceededError at ring take time and never reaches the
        launch callable (overload parity with the batcher's take-time
        drop)."""
        gate = threading.Event()
        launched_rows = []

        def launch(blocks):
            launched_rows.extend(int(b[2][0]) for b in blocks)
            return [np.array(b[2]) for b in blocks]

        def collect(token):
            gate.wait(5.0)
            return np.concatenate(token)

        loop = DispatchLoop(launch, collect)
        errors = []
        # occupy the owner with a gated readback so the expiring frame
        # sits queued past its deadline
        t1 = threading.Thread(target=lambda: loop.submit(_block([1])))
        t1.start()
        deadline = time.monotonic() + 2.0
        while not launched_rows and time.monotonic() < deadline:
            time.sleep(0.005)

        def expiring():
            with deadline_scope(0.05):
                try:
                    loop.submit(_block([99]))
                except DeadlineExceededError as e:
                    errors.append(e)

        t2 = threading.Thread(target=expiring)
        t2.start()
        time.sleep(0.15)  # let the deadline lapse while parked in the ring
        gate.set()
        t1.join(5.0)
        t2.join(5.0)
        loop.close()
        assert len(errors) == 1
        assert 99 not in launched_rows
        assert loop.deadline_drops == 1

    def test_max_queue_sheds_with_queue_full(self):
        gate = threading.Event()
        loop = DispatchLoop(
            lambda blocks: [np.array(b[2]) for b in blocks],
            lambda token: (gate.wait(5.0), np.concatenate(token))[1],
            max_queue=2,
        )
        t1 = threading.Thread(target=lambda: loop.submit(_block([1])))
        t1.start()
        time.sleep(0.05)  # batch 1 launched, readback gated

        stalled = []
        t2 = threading.Thread(
            target=lambda: stalled.append(loop.submit(_block([2, 3])))
        )
        t2.start()
        deadline = time.monotonic() + 2.0
        while loop.queue_depth < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(QueueFullError):
            loop.submit(_block([4]))
        gate.set()
        t1.join(5.0)
        t2.join(5.0)
        loop.close()
        assert stalled and stalled[0].tolist() == [2, 3]

    def test_brownout_sheds_on_submit(self):
        controller = AdmissionController(
            brownout_target_ms=1.0, ewma_alpha=1.0
        )
        loop = _echo_loop(overload=controller)
        try:
            assert loop.submit(_block([1])).tolist() == [1]
            controller.observe_queue_wait(50.0)  # force the brownout
            assert controller.should_shed()
            with pytest.raises(BrownoutError):
                loop.submit(_block([2]))
        finally:
            loop.close()

    def test_dispatch_launch_fault_site(self):
        from api_ratelimit_tpu.testing.faults import FaultInjector

        injector = FaultInjector.from_spec(f"{FAULT_SITE_LAUNCH}:error:1")
        loop = _echo_loop(fault_injector=injector)
        try:
            with pytest.raises(CacheError, match="dispatch.launch"):
                loop.submit(_block([1]))
            assert injector.fired()[f"{FAULT_SITE_LAUNCH}:error"] >= 1
            injector.clear()
            assert loop.submit(_block([2])).tolist() == [2]
        finally:
            loop.close()

    def test_stalled_owner_grows_queue_wait_signal(self):
        """dispatch.launch:delay_ms models a stalled device owner: the
        ring wait observed by the admission controller grows past the
        brownout target and the loop starts shedding — the chaos-ladder
        behavior the site exists for."""
        from api_ratelimit_tpu.testing.faults import FaultInjector

        controller = AdmissionController(
            brownout_target_ms=5.0, ewma_alpha=1.0
        )
        injector = FaultInjector.from_spec(f"{FAULT_SITE_LAUNCH}:delay_ms:40")
        loop = _echo_loop(overload=controller, fault_injector=injector)

        def submit_quietly():
            try:
                loop.submit(_block([1]))
            except BrownoutError:
                pass

        try:
            # concurrent rounds: frames published while the owner is
            # stalled inside the injected launch delay wait >= that delay
            # in the ring, which is what drives the EWMA past target
            deadline = time.monotonic() + 10.0
            while not controller.brownout and time.monotonic() < deadline:
                threads = [
                    threading.Thread(target=submit_quietly) for _ in range(3)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(5.0)
            assert controller.brownout
        finally:
            loop.close()


def _mode_engine(loop, **kwargs):
    """A small engine in windowed mode (loop=True: the dispatch loop) or
    direct mode (loop=False: TPU_BATCH_WINDOW=0)."""
    from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine

    kwargs.setdefault("time_source", FakeTimeSource(700_000))
    return SlabDeviceEngine(
        n_slots=1 << 12,
        use_pallas=False,
        batch_window_seconds=0.002 if loop else 0.0,
        buckets=(8, 128),
        max_batch=128,
        **kwargs,
    )


def _rows(fps, hits=1, limit=1_000_000, divider=60):
    block = np.zeros((6, len(fps)), dtype=np.uint32)
    block[0] = fps
    block[2] = hits
    block[3] = limit
    block[4] = divider
    return block


class TestEngineParity:
    """Row-block results must be byte-identical between the dispatch loop
    and direct mode, and both must answer saturation identically."""

    @staticmethod
    def _engine(loop, **kwargs):
        return _mode_engine(loop, **kwargs)

    def test_row_block_results_byte_identical_across_arms(self):
        import random

        rng = random.Random(3)
        eng_loop = self._engine(True)
        eng_lead = self._engine(False)
        assert eng_loop._dispatch is not None
        assert eng_lead._dispatch is None
        try:
            for step in range(40):
                n = rng.randrange(1, 9)
                block = np.zeros((6, n), dtype=np.uint32)
                block[0] = [rng.randrange(1, 64) for _ in range(n)]
                block[2] = 1
                block[3] = rng.randrange(2, 30)
                block[4] = 60
                a = eng_loop.submit_rows(np.array(block))
                b = eng_lead.submit_rows(np.array(block))
                assert a.dtype == b.dtype == np.uint32
                assert a.tobytes() == b.tobytes(), step
        finally:
            eng_loop.close()
            eng_lead.close()

    def test_windowed_engine_rides_loop_and_coalesces(self):
        eng = self._engine(True)
        try:
            outs = []
            lock = threading.Lock()

            def worker(tid):
                block = np.zeros((6, 1), dtype=np.uint32)
                block[0] = 4242
                block[2] = 1
                block[3] = 1_000_000
                block[4] = 60
                r = eng.submit_rows(block)
                with lock:
                    outs.append(int(r[0]))

            threads = [
                threading.Thread(target=worker, args=(t,)) for t in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(5.0)
            assert sorted(outs) == [1, 2, 3, 4, 5, 6]
            assert eng.health_snapshot()["decisions"] == 6
        finally:
            eng.close()

    def test_engine_drain_with_loop(self):
        eng = self._engine(True)
        block = np.zeros((6, 1), dtype=np.uint32)
        block[0] = 9
        block[2] = 1
        block[3] = 100
        block[4] = 60
        assert eng.submit_rows(block).tolist() == [1]
        eng.drain()
        with pytest.raises(CacheError):
            eng.submit_rows(np.array(block))
        eng.close()

    def test_full_occupancy_parity(self):
        """There is no saturation shed anymore: past 100% live occupancy
        the dispatch loop and direct mode keep answering (the set scan
        evicts in-kernel), and the answers stay byte-identical."""
        from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine

        outs = {}
        for arm in (True, False):
            eng = SlabDeviceEngine(
                time_source=FakeTimeSource(700_000),
                n_slots=128,
                use_pallas=False,
                batch_window_seconds=0.002 if arm else 0.0,
                buckets=(8,),
                max_batch=8,
            )
            got = []
            try:
                # 160 distinct keys through one 128-way set: the tail 32
                # inserts each evict a live way instead of shedding
                for i in range(160):
                    block = np.zeros((6, 1), dtype=np.uint32)
                    block[0] = i + 1
                    block[2] = 1
                    block[3] = 1000
                    block[4] = 60
                    got.append(eng.submit_rows(block).tobytes())
                snap = eng.health_snapshot()
                assert snap["occupancy"] == 1.0
                assert snap["evictions_live"] == 32
            finally:
                eng.close()
            outs[arm] = got
        assert outs[True] == outs[False]


MODES = pytest.mark.parametrize("loop", [True, False], ids=["loop", "direct"])


class TestEngineModes:
    """Engine-level behaviour both batching modes owe their callers: the
    dispatch loop (windowed) and direct mode (TPU_BATCH_WINDOW=0)."""

    @MODES
    def test_mode_follows_the_window(self, loop):
        eng = _mode_engine(loop)
        try:
            assert (eng.dispatch_loop is not None) == loop
        finally:
            eng.close()

    @MODES
    def test_closed_engine_raises_cache_error(self, loop):
        eng = _mode_engine(loop)
        eng.close()
        with pytest.raises(CacheError, match="closed"):
            eng.submit_rows(_rows([5]))
        assert eng.health_snapshot()["decisions"] == 0

    @MODES
    def test_drain_refuses_then_finishes(self, loop):
        """drain() returns only once every admitted submit has launched:
        the decision count read right after it never moves again, and it
        equals the submits that were answered."""
        eng = _mode_engine(loop)
        answered = []
        lock = threading.Lock()
        started = threading.Barrier(5)

        def worker(tid):
            block = _rows([100 + tid])
            started.wait(5.0)
            n = 0
            while True:
                try:
                    eng.submit_rows(block)
                except CacheError:
                    break
                n += 1
            with lock:
                answered.append(n)

        threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in threads:
            t.start()
        started.wait(5.0)
        time.sleep(0.05)
        eng.drain()
        at_drain = eng.health_snapshot()["decisions"]
        for t in threads:
            t.join(10.0)
        try:
            assert len(answered) == 4
            assert sum(answered) > 0
            assert eng.health_snapshot()["decisions"] == at_drain
            assert at_drain == sum(answered)
            with pytest.raises(CacheError):
                eng.submit_rows(_rows([1]))
        finally:
            eng.close()

    @MODES
    def test_submit_rows_registers_lease_ops(self, loop):
        from api_ratelimit_tpu.backends.lease import LeaseOps

        eng = _mode_engine(loop)
        try:
            block = _rows([11, 12], hits=(1, 9))
            ops = LeaseOps(grants=[(1, 8, 60, 30)], settles=[])
            afters = eng.submit_rows(block, lease_ops=ops)
            assert afters.tolist() == [1, 9]
            # one liability, its 8 tokens unsettled
            assert eng.lease_registry.outstanding() == (1, 8)
        finally:
            eng.close()

    @MODES
    def test_scratch_block_reuse_under_concurrency(self, loop):
        """Each thread rewrites ONE scratch block between submits (keys
        alternate): counters stay exact per key, so no submit ever read a
        block its caller had already rewritten."""
        eng = _mode_engine(loop)
        per_thread = 20
        got: dict = {}

        def worker(tid):
            scratch = _rows([0])
            seen = []
            for i in range(per_thread):
                scratch[0, 0] = 1000 + 2 * tid + (i & 1)
                seen.append(int(eng.submit_rows(scratch)[0]))
            got[tid] = seen

        threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        eng.close()
        want = [i // 2 + 1 for i in range(per_thread)]
        assert got == {tid: want for tid in range(4)}

    @MODES
    def test_batcher_submit_fault_site(self, loop):
        from api_ratelimit_tpu.testing.faults import FaultInjector

        injector = FaultInjector.from_spec("batcher.submit:queue_full:1")
        eng = _mode_engine(loop, fault_injector=injector)
        try:
            with pytest.raises(QueueFullError, match="injected"):
                eng.submit_rows(_rows([3]))
            assert eng.health_snapshot()["decisions"] == 0
            assert injector.fired() == {"batcher.submit:queue_full": 1}
        finally:
            eng.close()

    @MODES
    def test_item_verb_matches_row_verb(self, loop):
        from api_ratelimit_tpu.backends.tpu import _Item

        eng_items = _mode_engine(loop)
        eng_rows = _mode_engine(loop)
        try:
            items = [_Item(fp, 1, 50, 60, 0) for fp in (7, 8, 7, 7)]
            by_items = eng_items.submit(items)
            by_rows = eng_rows.submit_rows(_rows([7, 8, 7, 7], limit=50))
            assert by_items == by_rows.tolist() == [1, 1, 2, 3]
        finally:
            eng_items.close()
            eng_rows.close()

    @MODES
    def test_submit_block_answers_owned_arrays(self, loop):
        """block_mode (the sidecar server): submit_block results belong to
        the caller and survive the same thread's next submit."""
        eng = _mode_engine(loop, block_mode=True)
        try:
            first = eng.submit_block(_rows([21, 22]))
            second = eng.submit_block(_rows([21, 22]))
            assert first.tolist() == [1, 1]
            assert second.tolist() == [2, 2]
            with pytest.raises(RuntimeError, match="block_mode"):
                eng.submit([])
        finally:
            eng.close()

    @MODES
    def test_queue_wait_lands_in_its_mode_histogram(self, loop):
        """Direct mode records ratelimit.batcher.queue_wait_ms, the loop
        ratelimit.dispatch.ring_wait_ms; the batcher gauges are exported
        in both modes."""
        from api_ratelimit_tpu.stats import Store, TestSink

        store = Store(TestSink())
        eng = _mode_engine(loop, scope=store.scope("ratelimit"))
        try:
            for _ in range(3):
                eng.submit_rows(_rows([31]))
        finally:
            eng.close()
        store.flush()
        snap = store.metrics_snapshot()
        hists = snap["histograms"]
        ring = hists.get("ratelimit.dispatch.ring_wait_ms", {}).get("count", 0)
        direct = hists["ratelimit.batcher.queue_wait_ms"]["count"]
        assert (ring, direct) == ((3, 0) if loop else (0, 3))
        assert "ratelimit.batcher.queue_depth" in snap["gauges"]
        assert "ratelimit.batcher.inflight" in snap["gauges"]
