"""Direct-mode batcher: the caller executes its own row block.

With TPU_BATCH_WINDOW=0 (the default) every submit runs its own device
launch on the calling thread, single-flight under the direct lock —
lowest latency, no cross-request amortization (exactly like an unset
pipeline window in the reference, src/redis/driver_impl.go:84-90).
Windowed mode (TPU_BATCH_WINDOW > 0) coalesces on the device-owner
dispatch loop instead (backends/dispatch.py).

Both modes share the admission contract: the 'batcher.submit' chaos
site and the brownout shed run before any lock work, and a propagated
deadline that expires while the caller waits behind another launch
resolves as DeadlineExceededError without reaching the device.
"""

from __future__ import annotations

import threading
import time
from typing import Callable

import numpy as np

from ..limiter.cache import CacheError, DeadlineExceededError
from ..tracing import journeys
from ..utils.deadline import current_deadline
from .overload import BrownoutError, QueueFullError

FAULT_SITE_SUBMIT = "batcher.submit"  # testing/faults.py chaos site


class BatcherStats:
    """StatGenerator exporting the batcher's instantaneous backlog at every
    stats flush / metrics scrape:

        <scope>.queue_depth   items awaiting execution (always 0: the
                              caller executes its own block)
        <scope>.inflight      launches executing right now
    """

    def __init__(self, batcher: "MicroBatcher", scope):
        self._batcher = batcher
        self._queue_depth = scope.gauge("queue_depth")
        self._inflight = scope.gauge("inflight")

    def generate_stats(self) -> None:
        self._queue_depth.set(self._batcher.queue_depth)
        self._inflight.set(self._batcher.inflight)


class MicroBatcher:
    def __init__(
        self,
        execute: Callable[[list], np.ndarray],
        scope=None,
        overload=None,
        fault_injector=None,
    ):
        """execute: runs a list of uint32[6, n] row blocks (the sidecar
        wire layout) through one device launch and returns the uint32
        results in row order. submit() hands it a one-block list.

        scope: optional stats Scope (stats/store.py). When set, the batcher
        records queue_wait_ms (the wait for the direct lock behind another
        caller's launch) and batch_size (rows per launch, pow-2 buckets),
        and registers a StatGenerator exporting queue_depth / inflight
        gauges at every flush/scrape.

        overload: optional AdmissionController (backends/overload.py).
        When set, the batcher feeds it the queue-wait EWMA brownout signal
        (one observation per launch), sheds new submits with BrownoutError
        while the brownout is active, and reports deadline-expired drops.

        fault_injector: optional FaultInjector consulted at site
        'batcher.submit' before each launch — delay_ms stalls the caller,
        queue_full raises QueueFullError — so chaos tests rehearse overload
        deterministically (testing/faults.py)."""
        self._execute = execute
        self._overload = overload
        self._faults = fault_injector
        # deadline-expired submits dropped before a launch (plain int — also
        # mirrored into the overload controller's counter when one is wired)
        self.deadline_drops = 0
        self._direct_lock = threading.Lock()
        self._closed = False
        self._h_wait = self._h_batch = None
        if scope is not None:
            from ..stats.store import DEFAULT_SIZE_BUCKETS

            self._h_wait = scope.histogram("queue_wait_ms")
            self._h_batch = scope.histogram(
                "batch_size", boundaries=DEFAULT_SIZE_BUCKETS
            )
            scope.add_stat_generator(BatcherStats(self, scope))

    @property
    def queue_depth(self) -> int:
        """Items awaiting execution: none, the caller executes its own."""
        return 0

    @property
    def inflight(self) -> int:
        """Launches executing right now (racy read; stats only)."""
        return int(self._direct_lock.locked())

    def _admit(self) -> None:
        """Admission gate: chaos site, then the brownout shed. Runs BEFORE
        any lock work — overload is answered at the cheapest possible
        point."""
        if self._faults is not None:
            action = self._faults.fire(FAULT_SITE_SUBMIT)
            if action == "queue_full":
                raise QueueFullError("injected queue_full fault")
        if self._overload is not None and self._overload.should_shed():
            raise BrownoutError(
                "batcher brownout: queue wait ewma over target"
            )

    def submit(self, block: np.ndarray) -> np.ndarray:
        """Run one uint32[6, n] row block through the executor on this
        thread; returns its uint32[n] results. The block is consumed before
        submit returns, so the caller may reuse a scratch buffer.

        The caller's propagated deadline (utils/deadline.py) is checked
        once the direct lock is held: work that expired waiting behind
        another caller's launch resolves as DeadlineExceededError without
        reaching the device."""
        count = block.shape[1]
        if count == 0:
            return np.empty(0, dtype=np.uint32)
        self._admit()
        deadline = current_deadline()
        # queue_wait is the time spent blocked on the direct lock behind
        # another caller — the signal that a window would start paying off
        t_enq = time.monotonic()
        with self._direct_lock:
            if self._closed:
                # CacheError, not a bare RuntimeError: a submit racing
                # shutdown must surface as a counted backend failure
                # (redis_error + a proper wire error), not an unhandled
                # 500 from the transport
                raise CacheError("batcher is closed")
            if deadline is not None and time.monotonic() >= deadline:
                self._note_expired(1)
                raise DeadlineExceededError(
                    "deadline expired before device dispatch"
                )
            wait_ms = (time.monotonic() - t_enq) * 1e3
            if self._h_wait is not None:
                self._h_wait.record(wait_ms)
                self._h_batch.record(count)
            if self._overload is not None:
                self._overload.observe_queue_wait(wait_ms)
            if not journeys.recording():
                return self._execute([block])
            # the caller IS the owner: launch and readback are fused in
            # one execute, so stamp the dispatch loop's full stage set with
            # the execute call as the launch..scatter interval
            ns0 = time.monotonic_ns()
            for stage in ("publish", "take", "pack"):
                journeys.mark(stage, ns0)
            try:
                return self._execute([block])
            finally:
                ns1 = time.monotonic_ns()
                for stage in ("launch", "redeem", "scatter"):
                    journeys.mark(stage, ns1)

    def _note_expired(self, n: int) -> None:
        self.deadline_drops += n
        if self._overload is not None:
            self._overload.note_deadline_expired(n)

    def flush(self) -> None:
        """Block until the launch executing right now (if any) is done."""
        with self._direct_lock:
            return

    def drain(self) -> None:
        """Graceful-drain quiesce: wait out the launch in progress, then
        refuse new submits. The warm-restart handoff runs this before the
        final slab snapshot (persist/snapshotter.py) so a planned restart
        captures every decision that was admitted."""
        self.close()

    def close(self) -> None:
        with self._direct_lock:
            self._closed = True
