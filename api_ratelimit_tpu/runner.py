"""Composition root — the Python twin of src/service_cmd/runner/runner.go.

Run(): parse settings, configure logging, build the local over-limit cache,
stats store + sink, transport server, the backend selected by BACKEND_TYPE
(runner.go:43-64 — here: tpu | memory), the service with its runtime loader,
register v3 + v2 gRPC services and /json (runner.go:115-121), hang /rlconfig
on the debug port (runner.go:108-113), and serve.

Backend factory differences from the reference: the reference switches
between redis and memcache processes reached over TCP; here the equivalents
are the in-process TPU slab engine (single- or multi-chip) and the pure-host
memory oracle. The redis/memcache parity backends plug into the same switch
when present.
"""

from __future__ import annotations

import json
import logging
import os
import random
import signal as signal_module
import sys
import threading

from .backends.memory import MemoryRateLimitCache
from .limiter.base_limiter import BaseRateLimiter
from .limiter.cache import RateLimitCache
from .limiter.local_cache import LocalCache, LocalCacheStats
from .server.runtime_loader import DirectoryRuntimeLoader
from .server.server import Server, new_server
from .service.ratelimit import RateLimitService
from .settings import Settings, new_settings
from .stats.sinks import NullSink, StatsdSink
from .stats.store import Store
from .tracing import journeys as journeys_mod
from .tracing import set_global_tracer, tracer_from_env
from .utils.timeutil import process_time_source

logger = logging.getLogger("ratelimit.runner")

_LOG_LEVELS = {
    "TRACE": logging.DEBUG,
    "DEBUG": logging.DEBUG,
    "INFO": logging.INFO,
    "WARN": logging.WARNING,
    "WARNING": logging.WARNING,
    "ERROR": logging.ERROR,
    "FATAL": logging.CRITICAL,
}


class _JsonFormatter(logging.Formatter):
    """LOG_FORMAT=json with the reference's field remaps: @timestamp/@message
    (runner.go:75-83) so existing log collectors keep working."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "@timestamp": self.formatTime(record, "%Y-%m-%dT%H:%M:%S%z"),
            "@message": record.getMessage(),
            "level": record.levelname.lower(),
            "logger": record.name,
        }
        if record.exc_info:
            out["exception"] = self.formatException(record.exc_info)
        return json.dumps(out)


def setup_logging(settings: Settings) -> None:
    level = _LOG_LEVELS.get(settings.log_level.upper())
    if level is None:
        raise ValueError(f"invalid log level: {settings.log_level}")
    handler = logging.StreamHandler(sys.stderr)
    if settings.log_format == "json":
        handler.setFormatter(_JsonFormatter())
    elif settings.log_format == "text":
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s: %(message)s")
        )
    else:
        raise ValueError(f"invalid log format: {settings.log_format}")
    root = logging.getLogger()
    root.handlers[:] = [handler]
    root.setLevel(level)


def create_limiter(
    settings: Settings,
    base: BaseRateLimiter,
    stats_store: Store,
    fault_injector=None,
    overload=None,
    lease_table=None,
) -> RateLimitCache:
    """BackendType switch (runner.go:43-64). The TPU backends get the
    `ratelimit` scope so the per-stage pipeline histograms
    (batcher.queue_wait_ms, device.{pack,launch,readback}_ms,
    sidecar.rpc_ms) land in the same store /metrics scrapes.
    fault_injector (FAULT_INJECT) reaches the sidecar client's and the
    batching layer's chaos sites; overload (the AdmissionController) wires
    the bounded-queue/brownout/watermark admission layer into the
    in-process TPU engine."""
    backend = settings.backend_type
    scope = stats_store.scope("ratelimit")
    if backend == "tpu":
        from .backends.tpu import TpuRateLimitCache
        from .utils.jaxsetup import enable_compile_cache, serving_mesh

        logger.info("jax compile cache: %s", enable_compile_cache())
        mesh = serving_mesh(settings.tpu_mesh_devices)
        settings.warn_deprecated_knobs(logger)
        kwargs = {}
        ladder = settings.buckets()
        if ladder is not None:
            kwargs["buckets"] = ladder
        hk_enabled, hk_k, hk_lanes = settings.hotkey_config()
        v_enabled, v_max_rows, v_watermark = settings.victim_config()
        sr_routed, sr_hot, sr_salt = settings.shard_config()
        return TpuRateLimitCache(
            base,
            n_slots=settings.tpu_slab_slots,
            ways=settings.slab_ways_count(),
            batch_window_seconds=settings.tpu_batch_window,
            max_batch=settings.tpu_batch_limit,
            use_pallas=None if settings.tpu_use_pallas else False,
            mesh=mesh,
            stats_scope=scope,
            max_queue=settings.overload_max_queue,
            watermark_high=settings.slab_watermark(),
            overload=overload,
            fault_injector=fault_injector,
            # the bucket ladder compiles BEFORE the server reports
            # healthy: no request ever rides a first-touch XLA compile
            precompile=settings.tpu_precompile,
            lease_table=lease_table,
            gcra_burst_ratio=settings.gcra_burst(),
            hotkey_lanes=hk_lanes if hk_enabled else 0,
            hotkey_k=hk_k,
            victim_max_rows=v_max_rows if v_enabled else 0,
            victim_watermark=v_watermark,
            shard_routed_batching=sr_routed,
            hot_tier_enabled=sr_hot,
            hot_tier_salt_ways=sr_salt,
            **kwargs,
        )
    if backend == "tpu-sidecar":
        k, _groups, _route_sets, _rate = settings.cluster_config()
        if k > 1:
            # PARTITIONS>1: the partition router (cluster/router.py) —
            # one per-partition failover client behind the same engine
            # verbs. PARTITIONS=1 never builds it: the plain client
            # below ships byte-identical pre-cluster frames (the pinned
            # rollback arm).
            from .cluster.router import new_partitioned_cache_from_settings

            return new_partitioned_cache_from_settings(
                settings, base, stats_scope=scope,
                fault_injector=fault_injector, lease_table=lease_table,
            )
        from .backends.sidecar import new_sidecar_cache_from_settings

        return new_sidecar_cache_from_settings(
            settings, base, stats_scope=scope, fault_injector=fault_injector,
            lease_table=lease_table,
        )
    if backend == "memory":
        return MemoryRateLimitCache(base)
    if backend == "redis":
        from .backends.redis import new_redis_cache_from_settings

        return new_redis_cache_from_settings(settings, base, stats_store)
    if backend == "memcache":
        from .backends.memcache import new_memcache_cache_from_settings

        return new_memcache_cache_from_settings(settings, base)
    raise ValueError(f"invalid backend type: {backend!r}")


class Runner:
    def __init__(self, settings: Settings | None = None, sink=None):
        self.settings = settings if settings is not None else new_settings()
        if sink is None:
            sink = (
                StatsdSink(self.settings.statsd_host, self.settings.statsd_port)
                if self.settings.use_statsd
                else NullSink()
            )
        self.stats_store = Store(
            sink, latency_buckets=self.settings.latency_buckets()
        )
        self.scope = self.stats_store.scope("ratelimit")
        self.server: Server | None = None
        self.service: RateLimitService | None = None
        self.runtime: DirectoryRuntimeLoader | None = None
        self.tracer = None
        self.journeys = None
        self.fallback = None
        self.overload = None
        self.fault_injector = None
        self.snapshotter = None
        self.lease_table = None
        self.federation = None
        self.limiter: RateLimitCache | None = None
        self._ready = threading.Event()

    def get_stats_store(self) -> Store:
        return self.stats_store

    def _build(self) -> None:
        settings = self.settings
        setup_logging(settings)

        # One clock authority per process (utils/timeutil.py): every
        # time-semantic component below shares it, so the /debug/clock
        # admin surface (and the chaos clock-skew nemesis behind it) skews
        # the whole process coherently instead of one component at a time.
        self.time_source = process_time_source()

        # Post-mortem muscle: faulthandler dumps every thread's stack on a
        # hard fault, and SIGUSR2 dumps them on demand — plus the journey
        # flight recorder's retained tail (tracing/journeys.py), so "the
        # service stopped answering" yields both where every worker IS and
        # where the slow requests WENT. The signal registration is
        # main-thread-only (background/test boots skip it); enable() is
        # safe anywhere.
        import faulthandler

        faulthandler.enable()

        def on_sigusr2(signum, frame):
            faulthandler.dump_traceback(all_threads=True)
            recorder = journeys_mod.global_recorder()
            if recorder is not None:
                sys.stderr.write(recorder.dump_json())
                sys.stderr.flush()

        try:
            if hasattr(signal_module, "SIGUSR2"):
                signal_module.signal(signal_module.SIGUSR2, on_sigusr2)
        except (ValueError, OSError):
            pass  # not the main thread (run_background from a test)

        # Tracer from K_TRACING_* env, registered globally so the gRPC
        # interceptor and /json middleware pick it up (runner.go:90-95);
        # closed with a bounded flush in _teardown (runner.go:91).
        self.tracer = tracer_from_env()
        set_global_tracer(self.tracer)

        # Journey flight recorder (tracing/journeys.py): every request's
        # stage itinerary, tail-sampled by outcome into /debug/journeys
        # and the SIGUSR2 dump. Registered globally like the tracer so
        # the service boundary and both dispatch arms find it.
        jr_enabled, jr_slow_ms, jr_retain, jr_ring = settings.journey_config()
        self.journeys = None
        if jr_enabled:
            self.journeys = journeys_mod.JourneyRecorder(
                slow_ms=jr_slow_ms,
                retain=jr_retain,
                ring=jr_ring,
                scope=self.scope.scope("journeys"),
            )
        journeys_mod.set_global_recorder(self.journeys)

        # Prewarm the native host codec here, at startup, for EVERY backend:
        # generate_cache_keys lazily triggers its build (a synchronous g++
        # compile, up to ~2min) and the redis/memcache/memory backends would
        # otherwise pay it inside the first large request, blowing upstream
        # gRPC deadlines. The TPU backend prewarms in its own constructor too;
        # available() memoizes so the second call is free. The build result
        # is surfaced loudly (log + ratelimit.native.available gauge) so the
        # pure-Python fallback can never silently eat the dispatch-path win.
        from .ops import native

        info = native.build_info()
        self.scope.scope("native").gauge("available").set(
            1 if info["available"] else 0
        )
        if info["available"]:
            logger.info("native host codec loaded: %s", info["so_path"])
        else:
            logger.warning(
                "native host codec UNAVAILABLE (so=%s, source_present=%s): "
                "fingerprint/pack/scatter run on the pure-Python fallback",
                info["so_path"],
                info["source_present"],
            )

        # build/hardware provenance gauges (ratelimit.build.*) next to
        # native.available: a scraped fleet self-describes the regime it
        # is measured in (utils/provenance.py; merged by MAX fleet-wide).
        # A frontend owns no accelerator — it honestly reports cpu/0; the
        # device owner (cmd/sidecar_cmd.py) reports the real platform.
        from .utils import provenance

        provenance.register_build_gauges(self.scope)

        # bench-driver affinity plan: when the fleet master armed a
        # multi-core run it hands each process its CPU slice via this
        # env knob (tools/bench_driver.py); outside a driven run the
        # knob is unset and this is a no-op
        aff = os.environ.get("BENCH_CPU_AFFINITY", "").strip()
        if aff:
            try:
                os.sched_setaffinity(
                    0, {int(c) for c in aff.split(",") if c.strip()}
                )
                logger.info("pinned to cpus {%s} (BENCH_CPU_AFFINITY)", aff)
            except (AttributeError, ValueError, OSError) as e:
                logger.warning("BENCH_CPU_AFFINITY %r not applied: %s", aff, e)

        local_cache = None
        if settings.local_cache_size_in_bytes > 0:
            # freecache is sized in bytes; entries here are (key -> expiry)
            # pairs of ~100 bytes, so the byte knob maps onto an entry cap.
            local_cache = LocalCache(
                max_entries=max(1, settings.local_cache_size_in_bytes // 100),
                time_source=self.time_source,
            )
            self.stats_store.add_stat_generator(
                LocalCacheStats(local_cache, self.scope.scope("localcache"))
            )

        self.server = new_server(settings, self.stats_store)

        base = BaseRateLimiter(
            time_source=self.time_source,
            jitter_rand=random.Random(),
            expiration_jitter_max_seconds=settings.expiration_jitter_max_seconds,
            local_cache=local_cache,
            near_limit_ratio=settings.near_limit_ratio,
        )

        # Fault injector (FAULT_INJECT) — chaos rehearsal for the
        # resilience ladder; a junk spec fails the boot here, like a junk
        # bucket ladder. Always constructed (empty = a lock-free no-op on
        # the hot path) so the /debug/faults admin surface can arm faults
        # on a LIVE process — chaos campaigns reconfigure at runtime
        # instead of rebooting per scenario.
        from .testing.faults import FaultInjector

        fault_rules = settings.fault_rules()
        self.fault_injector = FaultInjector(
            fault_rules, seed=settings.fault_inject_seed
        )
        if fault_rules:
            logger.warning(
                "FAULT_INJECT active (%d rule(s)) — chaos mode",
                len(fault_rules),
            )
        from .server.http_server import add_chaos_admin

        add_chaos_admin(
            self.server.debug, self.fault_injector, self.time_source
        )

        # Overload admission control (backends/overload.py): always built —
        # the default knobs (no queue bound, no brownout) make it inert on
        # the hot path while keeping the overload.* stats and the shed
        # posture defined for watermark/fault-injected sheds.
        from .backends.overload import AdmissionController

        self.overload = AdmissionController(
            shed_mode=settings.shed_mode(),
            max_queue=settings.overload_max_queue,
            brownout_target_ms=settings.overload_brownout_target_ms,
            brownout_exit_ms=settings.overload_brownout_exit_ms,
            ewma_alpha=settings.overload_ewma_alpha,
            scope=self.scope,
        )
        self.server.health.add_degraded_probe(self.overload.degraded_reason)

        # Hierarchical quota leasing (LEASE_ENABLED; backends/lease.py):
        # the frontend lease table answers hot-key decisions locally from
        # device-granted budget slices. Rides the compiled-matcher
        # pipeline (do_limit_resolved).
        self.lease_table = None
        (
            lease_on,
            lease_min,
            lease_max,
            lease_ttl,
            lease_near,
        ) = settings.lease_config()
        if lease_on and settings.backend_type in ("tpu", "tpu-sidecar"):
            from .backends.lease import LeaseTable

            self.lease_table = LeaseTable(
                base,
                min_size=lease_min,
                max_size=lease_max,
                ttl_fraction=lease_ttl,
                near_limit_ratio=lease_near,
                scope=self.scope.scope("lease"),
            )
            self.server.health.add_degraded_probe(
                self.lease_table.degraded_reason
            )

        # Global quota federation (FED_ENABLED; cluster/federation.py):
        # an in-process device owner (BACKEND_TYPE=tpu) hosts its own
        # FederationCoordinator — the share ledger peers exchange
        # settlement frames against. Sidecar FRONTENDS don't build one
        # (the device-owner process, cmd/sidecar_cmd.py, owns the ledger
        # exactly like it owns the slab). FED_ENABLED=false keeps every
        # layer byte-identical to the pre-federation build (the pinned
        # rollback arm).
        self.federation = None
        (
            fed_on,
            fed_self,
            fed_peers,
            fed_min,
            fed_max,
            fed_interval,
            fed_lag,
            fed_ttl,
        ) = settings.fed_config()
        if fed_on and settings.backend_type == "tpu":
            from .cluster.federation import FederationCoordinator

            self.federation = FederationCoordinator(
                fed_self,
                fed_peers,
                time_source=self.time_source,
                share_min=fed_min,
                share_max=fed_max,
                settle_interval_ms=fed_interval,
                max_lag_ms=fed_lag,
                share_ttl_ms=fed_ttl,
                scope=self.scope,
                fault_injector=self.fault_injector,
            )
            self.federation.bind_base(base)
            self.server.health.add_degraded_probe(
                self.federation.degraded_reason
            )
            self.server.add_debug_endpoint(
                "/debug/federation",
                lambda: json.dumps(self.federation.describe(), indent=2),
            )

        cache = self.limiter = create_limiter(
            settings, base, self.stats_store, self.fault_injector,
            self.overload, self.lease_table,
        )

        # Slab health gauges (ratelimit.slab.*) for engines that expose a
        # snapshot — the in-process single-chip and mesh-sharded engines do;
        # sidecar frontends don't (the device-owner process owns the slab).
        engine = getattr(cache, "engine", None)
        if engine is not None and hasattr(engine, "health_snapshot"):
            from .backends.tpu import SlabHealthStats

            self.stats_store.add_stat_generator(
                SlabHealthStats(engine, self.scope.scope("slab"))
            )
        # Lease liability gauges for device-owning engines: how much
        # un-settled leased budget is outstanding — the Σ budgets term of
        # the crash-overshoot bound (backends/lease.py).
        if (
            self.lease_table is not None
            and engine is not None
            and getattr(engine, "lease_registry", None) is not None
        ):
            from .backends.lease import LeaseRegistryStats

            self.stats_store.add_stat_generator(
                LeaseRegistryStats(
                    engine.lease_registry, self.scope.scope("lease")
                )
            )
        # Heavy-hitter telemetry (HOTKEYS_ENABLED; ops/sketch.py): the
        # HotkeyStats generator IS the sketch drain cadence — each stats
        # flush pulls the planes, publishes ratelimit.hotkeys.* and the
        # ranked top-K behind GET /debug/hotkeys (witness-resolved to
        # descriptor keys by the cache), and halves the counts so the head
        # tracks current traffic.
        if engine is not None and getattr(engine, "hotkeys_enabled", False):
            from .backends.tpu import HotkeyStats

            self.stats_store.add_stat_generator(
                HotkeyStats(engine, self.scope.scope("hotkeys"))
            )
        if hasattr(cache, "hotkeys_debug"):
            self.server.add_debug_endpoint(
                "/debug/hotkeys",
                lambda: json.dumps(cache.hotkeys_debug(), indent=2),
            )
        # Sharded-dispatch telemetry (SHARD_ROUTED_BATCHING /
        # HOT_TIER_ENABLED; parallel/sharded_slab.py): padding waste,
        # per-shard routed rows and hot-tier population under
        # ratelimit.shard.* — the gauges that make the hot-shard
        # pathology (and its cure) visible on a dashboard.
        if engine is not None and hasattr(engine, "shard_routing_snapshot"):
            _snap = engine.shard_routing_snapshot()
            if _snap.get("enabled"):
                from .backends.dispatch import ShardRoutingStats

                self.stats_store.add_stat_generator(
                    ShardRoutingStats(
                        engine.shard_routing_snapshot,
                        self.scope.scope("shard"),
                        int(_snap.get("shards", 0)),
                    )
                )
        # Victim-tier telemetry (VICTIM_TIER_ENABLED; backends/victim.py):
        # the VictimStats generator IS the tier's TTL/window reclamation
        # cadence — each stats flush reclaims dead rows, publishes
        # ratelimit.victim.* and the full occupancy/age document behind
        # GET /debug/victim.
        if engine is not None and getattr(engine, "victim_enabled", False):
            from .backends.tpu import VictimStats

            self.stats_store.add_stat_generator(
                VictimStats(engine, self.scope.scope("victim"))
            )
        if hasattr(cache, "victim_debug"):
            self.server.add_debug_endpoint(
                "/debug/victim",
                lambda: json.dumps(cache.victim_debug(), indent=2),
            )
        # Watermark degraded probe: slab pressure/saturation shows up in
        # the /healthcheck body next to the fallback/overload reasons.
        if engine is not None and hasattr(engine, "watermark_reason"):
            self.server.health.add_degraded_probe(engine.watermark_reason)
        # ... and the victim tier's own occupancy watermark beside it: a
        # tier filling toward value-ranked overflow is pressure building
        # one level down the hierarchy.
        if engine is not None and hasattr(engine, "victim_watermark_reason"):
            self.server.health.add_degraded_probe(
                engine.victim_watermark_reason
            )
        # Device-owner failover probe (SIDECAR_ADDRS; backends/sidecar.py):
        # while this frontend serves from a standby address the cluster is
        # one failure from the degradation ladder — /healthcheck carries
        # it while the instance keeps serving. The partition router
        # (cluster/router.py) exposes the same probe aggregated over its
        # per-partition clients.
        if engine is not None and hasattr(engine, "failover_reason"):
            self.server.health.add_degraded_probe(engine.failover_reason)
        # Partitioned-cluster debug surface (PARTITIONS>1; cluster/): the
        # adopted map epoch, each partition's range, active address, and
        # breaker state — GET /debug/cluster on the frontend debug port
        # (the per-owner view lives on each sidecar's own debug port).
        if engine is not None and hasattr(engine, "cluster_snapshot"):
            self.server.add_debug_endpoint(
                "/debug/cluster",
                lambda: json.dumps(engine.cluster_snapshot(), indent=2),
            )

        # Warm restart (persist/): restore the slab from the last snapshot
        # BEFORE serving, then re-snapshot on a cadence off the hot path;
        # the drain path (teardown) takes a final copy so planned restarts
        # lose ~0 state. Only device-owning engines participate — sidecar
        # FRONTENDS don't hold the slab, their device-owner process
        # (cmd/sidecar_cmd.py) runs its own snapshotter.
        snap_dir, snap_interval_ms, snap_stale_ms = settings.snapshot_config()
        if snap_dir and engine is not None and hasattr(engine, "export_tables"):
            from .persist.snapshotter import SlabSnapshotter

            self.snapshotter = SlabSnapshotter(
                engine,
                snap_dir,
                interval_ms=snap_interval_ms,
                stale_after_ms=snap_stale_ms,
                time_source=self.time_source,
                scope=self.scope,
                fault_injector=self.fault_injector,
                fed=self.federation,
            )
            self.snapshotter.restore()
            self.snapshotter.start()
            # staleness is degraded-only: durability at risk must not
            # drain an instance that is still serving fine from HBM
            self.server.health.add_degraded_probe(self.snapshotter.stale_reason)

        self.runtime = DirectoryRuntimeLoader(
            runtime_path=settings.runtime_path,
            runtime_subdirectory=settings.runtime_subdirectory,
            ignore_dotfiles=settings.runtime_ignoredotfiles,
            poll_interval_seconds=settings.runtime_poll_interval,
            watcher=settings.runtime_watcher,
            safety_rescan_seconds=settings.runtime_safety_rescan,
        )
        # Degradation ladder (FAILURE_MODE_DENY): when configured, backend
        # CacheErrors degrade to a policy decision (deny / fail-open /
        # local in-memory limiting) and /healthcheck reports the degraded
        # state in its body while staying 200 (fallback.py rationale).
        self.fallback = None
        failure_mode = settings.failure_mode()
        if failure_mode is not None:
            from .backends.fallback import FallbackLimiter

            self.fallback = FallbackLimiter(
                failure_mode,
                base_limiter=base,
                scope=self.scope,
                # outstanding leases answer before the rung does: real
                # device-granted budget outlives the device (lease.py);
                # federation shares answer next — global budget this
                # cluster already owns survives a WAN cut (federation.py)
                lease_table=self.lease_table,
                fed_shares=self.federation,
            )
            self.server.health.set_degraded_probe(
                self.fallback.degraded_reason
            )

        # the config loader carries the validated algorithm knobs: the
        # concurrency idle TTL is stamped into rules at load/hot-reload
        from .config.loader import load_config as _load_config

        service_scope = self.scope.scope("service")
        rl_scope = service_scope.scope("rate_limit")
        concurrency_ttl = settings.concurrency_ttl()
        self.service = RateLimitService(
            runtime=self.runtime,
            cache=cache,
            stats_scope=service_scope,
            config_loader=lambda files: _load_config(
                files, rl_scope, concurrency_ttl_s=concurrency_ttl
            ),
            time_source=self.time_source,
            runtime_watch_root=settings.runtime_watch_root,
            max_sleeping_routines=settings.max_sleeping_routines,
            fallback=self.fallback,
            overload=self.overload,
            # drain-aware pacing: once health flips for shutdown, throttle
            # sleeps shed instead of pinning workers through the drain
            draining_probe=lambda: not self.server.health.ok(),
            lease=self.lease_table,
        )

        def dump_config() -> str:
            config = self.service.get_current_config()
            return config.dump() if config is not None else ""

        self.server.add_debug_endpoint("/rlconfig", dump_config)
        self.server.register_service(self.service, self.scope.scope("service"))
        if self.federation is not None:
            self.federation.start()
        self.runtime.start_watching()
        self.stats_store.start_flushing()

    def run(self) -> None:
        """Build and serve; blocks until shutdown (Runner.Run, runner.go:66)."""
        self._build()
        self.server.install_signal_handlers()
        self._ready.set()
        try:
            self.server.start()
        finally:
            self._teardown()

    def run_background(self) -> None:
        """Build and serve on daemon threads (integration-test entry)."""
        self._build()
        self.server.start_background()
        self._ready.set()

    def wait_ready(self, timeout: float = 10.0) -> bool:
        return self._ready.wait(timeout)

    def stop(self) -> None:
        if self.server is not None:
            self.server.stop()
        self._teardown()

    def _teardown(self) -> None:
        if self.runtime is not None:
            self.runtime.stop()
        if self.federation is not None:
            # stop the settle pump BEFORE the final drain snapshot so the
            # fed.snap section captures a quiescent ledger
            federation, self.federation = self.federation, None
            federation.close()
        if self.snapshotter is not None:
            # drain handoff: quiesce the engine and take the final
            # snapshot — the state the next process warm-boots from
            snapshotter, self.snapshotter = self.snapshotter, None
            snapshotter.drain()
        self.stats_store.stop_flushing()
        if self.tracer is not None:
            self.tracer.close()
        if self.journeys is not None:
            # unregister only OUR recorder (in-process test boots share
            # the module global; a later Runner may already own it)
            if journeys_mod.global_recorder() is self.journeys:
                journeys_mod.set_global_recorder(None)
            self.journeys = None
