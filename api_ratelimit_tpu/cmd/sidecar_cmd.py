"""Slab sidecar entry point: the device-owner process.

Run ONE of these per TPU host, then any number of frontend servers with
BACKEND_TYPE=tpu-sidecar sharing the same SIDECAR_SOCKET — they bind the
serving ports together via SO_REUSEPORT and the kernel load-balances
connections across them, while every rate-limit increment serializes
through this process's slab (backends/sidecar.py).

Warm-standby redundancy (--role / REPL_ROLE + SIDECAR_ADDRS;
persist/replication.py): run a SECOND sidecar with --role standby (or
auto) pointed at the same SIDECAR_ADDRS list — it subscribes to the
primary, mirrors the slab through streamed dirty-row deltas, and promotes
itself (epoch bump + boot-style reconcile) the moment a failed-over
frontend writes to it. Frontends list both addresses in SIDECAR_ADDRS and
ride the circuit breaker across the failover with zero failed requests.
`--role auto` is the restart-friendly choice: a crashed-and-restarted old
primary finds the promoted standby serving and rejoins as ITS standby.

Honors the same TPU_* env knobs as the in-process backend: TPU_SLAB_SLOTS,
TPU_BATCH_WINDOW (recommended: 100-500us — the cross-frontend coalescing
window), TPU_BATCH_LIMIT, TPU_MESH_DEVICES, TPU_USE_PALLAS — and the
SLAB_SNAPSHOT_* warm-restart knobs: the sidecar owns the slab, so the
crash-safe snapshot/restore cycle (persist/) runs HERE, never in the
frontends.

Telemetry: the sidecar owns the device, so the device-stage histograms
(batcher queue wait / batch size, pack/launch/readback) and the slab
health gauges live HERE, not in the frontends. It runs its own stats
store (statsd push per USE_STATSD) and its own debug listener with
GET /metrics + /stats on DEBUG_PORT — give the sidecar a distinct
DEBUG_PORT from any same-host frontend, or SO_REUSEPORT will split
scrapes between the two processes.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import threading

from ..backends.sidecar import SlabSidecarServer
from ..backends.tpu import SlabDeviceEngine, SlabHealthStats
from ..runner import setup_logging
from ..server.http_server import (
    add_chaos_admin,
    add_healthcheck,
    new_debug_server,
)
from ..settings import new_settings
from ..stats.sinks import NullSink, StatsdSink
from ..stats.store import Store
from ..tracing import journeys as journeys_mod
from ..tracing import set_global_tracer, tracer_from_env
from ..utils.timeutil import process_time_source

logger = logging.getLogger("ratelimit.sidecar.main")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(
        description="TPU slab device-owner process (sidecar)"
    )
    parser.add_argument(
        "--role",
        choices=("primary", "standby", "auto"),
        default=None,
        help="warm-standby replication role (overrides REPL_ROLE; "
        "requires SIDECAR_ADDRS to name the peer for standby/auto)",
    )
    parser.add_argument(
        "--partition",
        type=int,
        default=None,
        help="which cluster partition this owner serves (PARTITIONS>1; "
        "cluster/). Defaults to the PARTITION_ADDRS group listing this "
        "process's SIDECAR_SOCKET",
    )
    args = parser.parse_args(argv)
    settings = new_settings()
    if args.role is not None:
        settings.repl_role = args.role
    setup_logging(settings)

    # bench-driver CPU slice (tools/bench_driver.py): the fleet master
    # hands each member its cores when a multi-core tier armed; unset
    # outside a driven run. Pin BEFORE jax init so compile threads land
    # on the slice too.
    _aff = os.environ.get("BENCH_CPU_AFFINITY", "").strip()
    if _aff:
        try:
            os.sched_setaffinity(
                0, {int(c) for c in _aff.split(",") if c.strip()}
            )
            logger.info("pinned to cpus {%s} (BENCH_CPU_AFFINITY)", _aff)
        except (AttributeError, ValueError, OSError) as e:
            logger.warning("BENCH_CPU_AFFINITY %r not applied: %s", _aff, e)

    # Partitioned cluster membership (PARTITIONS>1; cluster/): this owner
    # serves ONE keyspace partition of the boot map — map-stamped SUBMIT
    # frames are fenced against it (a stale client map gets
    # STATUS_STALE_MAP + the new map, never a silently misrouted write)
    # and the reshard admin ops are served. PARTITIONS=1 builds none of
    # this: the pre-cluster owner, byte-identical on the wire.
    cluster_k, cluster_groups, cluster_route_sets, _mb = (
        settings.cluster_config()
    )
    partition_index = None
    if cluster_k > 1:
        partition_index = (
            args.partition
            if args.partition is not None
            else settings.cluster_partition_of(settings.sidecar_socket)
        )
        if partition_index is None:
            raise SystemExit(
                f"PARTITIONS={cluster_k} but neither --partition was "
                f"given nor does any PARTITION_ADDRS group list this "
                f"process's SIDECAR_SOCKET ({settings.sidecar_socket!r})"
            )

    sink = (
        StatsdSink(settings.statsd_host, settings.statsd_port)
        if settings.use_statsd
        else NullSink()
    )
    store = Store(sink, latency_buckets=settings.latency_buckets())
    scope = store.scope("ratelimit")

    # Tracer + journey recorder, same posture as the frontend runner: the
    # dispatch loop's batch spans parent into frontend traces arriving
    # over the wire (B3 trailer, backends/sidecar.py), and the device
    # owner keeps its own tail-sampled journey buffer on /debug/journeys.
    tracer = tracer_from_env()
    set_global_tracer(tracer)
    jr_enabled, jr_slow_ms, jr_retain, jr_ring = settings.journey_config()
    if jr_enabled:
        journeys_mod.set_global_recorder(
            journeys_mod.JourneyRecorder(
                slow_ms=jr_slow_ms,
                retain=jr_retain,
                ring=jr_ring,
                scope=scope.scope("journeys"),
            )
        )

    from ..utils.jaxsetup import enable_compile_cache, serving_mesh

    logger.info("jax compile cache: %s", enable_compile_cache())

    # Surface the native codec state before the engine builds (runner.py
    # rationale): the device owner's pack/scatter hot path must not ride
    # the pure-Python fallback silently.
    from ..ops import native

    native_info = native.build_info()
    scope.scope("native").gauge("available").set(
        1 if native_info["available"] else 0
    )
    if native_info["available"]:
        logger.info("native host codec loaded: %s", native_info["so_path"])
    else:
        logger.warning(
            "native host codec UNAVAILABLE (so=%s, source_present=%s): "
            "pack/scatter run on the pure-Python fallback",
            native_info["so_path"],
            native_info["source_present"],
        )

    # build/hardware provenance gauges (ratelimit.build.*) next to
    # native.available (utils/provenance.py): the device owner is the one
    # fleet member whose platform/device_count are real accelerator facts,
    # so stamp them from jax itself — the fleet merge takes the MAX per
    # gauge, so the owner's tpu platform_id wins over frontend cpu rows.
    import jax as _jax

    from ..utils import provenance

    _devices = _jax.devices()
    provenance.register_build_gauges(
        scope,
        platform=_devices[0].platform,
        device_count=len(_devices),
    )

    mesh = serving_mesh(settings.tpu_mesh_devices)

    # FAULT_INJECT chaos hook (sites sidecar.server.submit +
    # batcher.submit): lets staging rehearse slow-engine / error-reply /
    # dropped-connection / queue-full behavior on the device-owner side;
    # junk specs fail the boot here. Always constructed (empty = lock-free
    # no-op) so the OP_FAULTS_SET admin op and POST /debug/faults can arm
    # faults on the LIVE owner — chaos campaigns reconfigure at runtime.
    from ..testing.faults import FaultInjector

    # One clock authority for the whole owner process: engine windows,
    # lease expiry, fed share TTLs, repl lag and snapshot staleness all
    # read it, so OP_CLOCK_SET / POST /debug/clock skew them coherently.
    time_source = process_time_source()
    fault_rules = settings.fault_rules()
    fault_injector = FaultInjector(
        fault_rules, seed=settings.fault_inject_seed
    )
    if fault_rules:
        logger.warning(
            "FAULT_INJECT active (%d rule(s)) — chaos mode", len(fault_rules)
        )

    # Overload admission control for the shared batcher: the sidecar is
    # where every frontend's traffic coalesces, so the bounded queue and
    # brownout live here too. A shed surfaces to frontends as an error
    # reply -> CacheError -> their FAILURE_MODE_DENY posture answers.
    from ..backends.overload import AdmissionController

    overload = AdmissionController(
        shed_mode=settings.shed_mode(),
        max_queue=settings.overload_max_queue,
        brownout_target_ms=settings.overload_brownout_target_ms,
        brownout_exit_ms=settings.overload_brownout_exit_ms,
        ewma_alpha=settings.overload_ewma_alpha,
        scope=scope,
    )
    settings.warn_deprecated_knobs(logger)

    hk_enabled, hk_k, hk_lanes = settings.hotkey_config()
    v_enabled, v_max_rows, v_watermark = settings.victim_config()
    engine = SlabDeviceEngine(
        time_source=time_source,
        near_limit_ratio=settings.near_limit_ratio,
        n_slots=settings.tpu_slab_slots,
        ways=settings.slab_ways_count(),
        batch_window_seconds=settings.tpu_batch_window,
        max_batch=settings.tpu_batch_limit,
        use_pallas=None if settings.tpu_use_pallas else False,
        mesh=mesh,
        # frontends ship packed uint32[6, n] wire blocks; the block-native
        # batcher keeps the aggregation path free of per-item Python
        # objects (decode + repack cost ~2.3us/item otherwise — an ~0.4M
        # items/s server ceiling at batch 8k, measured in PERF.md)
        block_mode=True,
        scope=scope,
        max_queue=settings.overload_max_queue,
        watermark_high=settings.slab_watermark(),
        overload=overload,
        fault_injector=fault_injector,
        # compile the bucket ladder before the first frontend connects —
        # the device owner must never spend a frontend's RPC deadline on
        # a first-touch XLA compile
        precompile=settings.tpu_precompile,
        # partition labeling for the arena-pressure telemetry
        # (DispatchStats): ring pressure on a K-partition host traces to
        # the keyspace slice generating it
        partition=-1 if partition_index is None else partition_index,
        # in-kernel heavy-hitter sketch (ops/sketch.py): the device owner
        # sees the coalesced traffic of every frontend, so the hot-key
        # head measured here is the authoritative one
        hotkey_lanes=hk_lanes if hk_enabled else 0,
        hotkey_k=hk_k,
        # host-RAM victim tier (backends/victim.py): demoted live rows
        # park beside the device owner and resume mid-window on promote
        victim_max_rows=v_max_rows if v_enabled else 0,
        victim_watermark=v_watermark,
        **({"buckets": settings.buckets()} if settings.buckets() else {}),
    )
    cluster_node = None
    if partition_index is not None:
        from ..cluster.node import ClusterNode
        from ..cluster.partition_map import PartitionMap

        cluster_node = ClusterNode(
            partition_index,
            PartitionMap.even_map(
                cluster_groups, route_sets=cluster_route_sets
            ),
            scope=scope,
        )
        logger.warning(
            "cluster partition %d of %d (route sets %d)",
            partition_index,
            cluster_k,
            cluster_route_sets,
        )
    store.add_stat_generator(SlabHealthStats(engine, scope.scope("slab")))
    if engine.hotkeys_enabled:
        from ..backends.tpu import HotkeyStats

        # the stats flush cadence IS the sketch drain cadence (see
        # HotkeyStats): gauges + the ranked head for /debug/hotkeys
        store.add_stat_generator(
            HotkeyStats(engine, scope.scope("hotkeys"))
        )
    if engine.victim_enabled:
        from ..backends.tpu import VictimStats

        # the stats flush cadence IS the tier's reclamation cadence (see
        # VictimStats): gauges + the occupancy document for /debug/victim
        store.add_stat_generator(
            VictimStats(engine, scope.scope("victim"))
        )
    # Lease liability gauges (backends/lease.py): frontends with
    # LEASE_ENABLED ship grant/settle trailers on their SUBMIT frames; the
    # device owner tracks the outstanding budget here — the Σ budgets term
    # of the crash-overshoot bound, and the liability section of the
    # warm-restart snapshot.
    from ..backends.lease import LeaseRegistryStats

    store.add_stat_generator(
        LeaseRegistryStats(engine.lease_registry, scope.scope("lease"))
    )

    # Warm-standby replication (persist/replication.py): build the
    # coordinator BEFORE the snapshotter — a standby defers its restore
    # (the replicated stream supersedes any local snapshot, and
    # periodically snapshotting an un-promoted standby's empty slab would
    # clobber good files) and starts snapshotting only at promotion.
    repl = None
    repl_role, repl_interval_ms, repl_max_lag_ms = settings.repl_config()
    on_promote_hooks: list = []
    if repl_role:
        from ..persist.replication import ReplicationCoordinator

        repl = ReplicationCoordinator(
            engine,
            repl_role,
            peer_address=settings.repl_peer_address(),
            interval_ms=repl_interval_ms,
            max_lag_ms=repl_max_lag_ms,
            scope=scope.scope("repl"),
            fault_injector=fault_injector,
            time_source=time_source,
            on_promote=lambda: [hook() for hook in on_promote_hooks],
        )

    # Global quota federation (FED_ENABLED; cluster/federation.py): the
    # device owner hosts this cluster's share ledger — peers dial our
    # sidecar listener's OP_FED_EXCHANGE verb for grants and settlements,
    # and our pump dials theirs. Built BEFORE the snapshotter so the
    # ledger rides the fed.snap section of the warm-restart set.
    # FED_ENABLED=false builds none of this: the pre-federation owner,
    # byte-identical on the wire (the pinned rollback arm).
    fed = None
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
    if fed_on:
        from ..cluster.federation import FederationCoordinator

        fed = FederationCoordinator(
            fed_self,
            fed_peers,
            time_source=time_source,
            share_min=fed_min,
            share_max=fed_max,
            settle_interval_ms=fed_interval,
            max_lag_ms=fed_lag,
            share_ttl_ms=fed_ttl,
            scope=scope,
            fault_injector=fault_injector,
        )
        logger.warning(
            "federation cluster %r joining %s (settle interval %.0fms, "
            "share ttl %.0fms)",
            fed_self,
            sorted(fed_peers),
            fed_interval,
            fed._ttl_s * 1000.0,
        )

    # Warm restart (persist/): the sidecar IS the device owner, so the
    # snapshot/restore cycle lives here — restore the shared slab before
    # accepting the first frontend connection, snapshot on the
    # SLAB_SNAPSHOT_INTERVAL_MS cadence, final copy on graceful shutdown.
    snapshotter = None
    snap_dir, snap_interval_ms, snap_stale_ms = settings.snapshot_config()
    if snap_dir:
        from ..persist.snapshotter import SlabSnapshotter

        snap_partition = None
        if cluster_node is not None:
            own = cluster_node.pmap.partitions[partition_index]
            snap_partition = (
                partition_index, own.lo, own.hi, cluster_route_sets,
            )
        snapshotter = SlabSnapshotter(
            engine,
            snap_dir,
            interval_ms=snap_interval_ms,
            stale_after_ms=snap_stale_ms,
            time_source=time_source,
            scope=scope,
            fault_injector=fault_injector,
            # stamp this owner's keyspace slice into every shard header
            # so snapshot_inspect can tell which slice a file holds
            partition=snap_partition,
            # the federation share ledger rides the snapshot set
            # (fed.snap, FLAG_FED) so a restart never re-serves budget
            # other clusters already hold
            fed=fed,
        )
        if repl is None or not repl.is_standby:
            # explicit primary (or no replication): the original contract
            # — restore the slab BEFORE the first frontend connection
            snapshotter.restore()
            snapshotter.start()
        # standby/auto: deferred until the role resolves (below) — the
        # replicated stream supersedes any local snapshot, and snapshotting
        # an un-promoted standby's empty slab would clobber good files

    # /healthcheck on the debug port, both roles: degraded reasons stack
    # the same way the frontend's do — replication lag / missing standby
    # (repl.degraded) next to snapshot staleness. Degraded-only: a
    # device owner with at-risk durability must keep serving.
    from ..server.health import HealthChecker

    health = HealthChecker(name="ratelimit-sidecar")
    if repl is not None:
        health.add_degraded_probe(repl.degraded_reason)
    if snapshotter is not None:
        health.add_degraded_probe(snapshotter.stale_reason)
    if fed is not None:
        # WAN settlement lag past FED_MAX_LAG_MS: degraded-only — the
        # cluster keeps serving its granted slice while divergence grows
        health.add_degraded_probe(fed.degraded_reason)
    if engine.victim_enabled:
        # victim-tier occupancy past VICTIM_WATERMARK: degraded-only —
        # the tier overflows by value-ranked drop, never OOM or shed
        health.add_degraded_probe(engine.victim_watermark_reason)

    debug = new_debug_server(
        "",
        settings.debug_port,
        store,
        enable_metrics=settings.debug_metrics_enabled,
        profile_dir=settings.tpu_profile_dir,
    )
    add_healthcheck(debug, health)
    # runtime fault/clock reconfiguration (chaos campaigns): the same
    # verbs the sidecar wire protocol exposes as OP_FAULTS_SET/OP_CLOCK_SET
    add_chaos_admin(debug, fault_injector, time_source)
    if cluster_node is not None:
        import json as _json

        def handle_cluster(h) -> None:
            h._write(
                200,
                _json.dumps(cluster_node.describe(), indent=2).encode(),
                content_type="application/json",
            )

        debug.add_get("/debug/cluster", handle_cluster)
    if engine.hotkeys_enabled:
        import json as _hk_json

        def handle_hotkeys(h) -> None:
            # no compose-time witness in the device owner (keys live in
            # the frontends), so entries carry fingerprints only — the
            # frontend /debug/hotkeys resolves them to descriptor keys
            h._write(
                200,
                _hk_json.dumps(engine.hotkeys_snapshot(), indent=2).encode(),
                content_type="application/json",
            )

        debug.add_get("/debug/hotkeys", handle_hotkeys)
    if engine.victim_enabled:
        import json as _v_json

        def handle_victim(h) -> None:
            # tier occupancy, counters, and the row-age histogram — the
            # operator's view of how much demoted state is parked and
            # how long it waits before promotion or reclamation
            h._write(
                200,
                _v_json.dumps(engine.victim_debug(), indent=2).encode(),
                content_type="application/json",
            )

        debug.add_get("/debug/victim", handle_victim)
    if fed is not None:
        import json as _fed_json

        def handle_federation(h) -> None:
            # the per-cluster ledger view: peer links, outstanding
            # shares, settlement lag, the live overshoot bound
            h._write(
                200,
                _fed_json.dumps(fed.describe(), indent=2).encode(),
                content_type="application/json",
            )

        debug.add_get("/debug/federation", handle_federation)
    debug.serve_background()
    store.start_flushing()
    # shm submit rings (SHM_RINGS; backends/shm_ring.py): same-host
    # frontend processes publish straight into this owner's dispatch
    # loop. Replicated deployments keep the socket path — shm frames
    # bypass the promote-on-write / epoch-fence interception that lives
    # in the wire handler, so the two features are mutually exclusive
    # until the fence moves engine-side.
    shm_control = settings.shm_control_path()
    if shm_control and repl is not None:
        logger.warning(
            "SHM_RINGS disabled: REPL_ROLE is set and shm frames would "
            "bypass the epoch fence (socket RPC only on this owner)"
        )
        shm_control = ""
    if shm_control and cluster_node is not None:
        # same rationale as the epoch fence: shm frames carry no map
        # stamp, so a stale router could write misrouted rows straight
        # into the dispatch loop — the cluster stays on the fenced wire
        logger.warning(
            "SHM_RINGS disabled: PARTITIONS>1 and shm frames would "
            "bypass the partition-map fence (socket RPC only)"
        )
        shm_control = ""
    server = SlabSidecarServer(
        settings.sidecar_socket,
        engine,
        socket_mode=settings.sidecar_socket_mode,
        tls_cert=settings.sidecar_tls_cert,
        tls_key=settings.sidecar_tls_key,
        tls_ca=settings.sidecar_tls_ca,
        fault_injector=fault_injector,
        repl=repl,
        shm_control_path=shm_control,
        cluster=cluster_node,
        fed=fed,
        time_source=time_source,
    )
    if fed is not None:
        # start the settle pump only once our own listener is up (a
        # federation booting together must be able to find each other —
        # same discipline as the replication auto role)
        fed.start()
    if repl is not None:
        # resolve the auto role / start the standby subscription only
        # once our own listener is up (an auto pair booting together must
        # be able to find each other)
        was_standby_at_boot = repl.is_standby
        repl.start()
        logger.warning(
            "replication role %s (epoch %d, interval %.0fms)",
            repl.role,
            repl.epoch,
            repl_interval_ms,
        )
        if snapshotter is not None and was_standby_at_boot:
            if repl.is_standby:
                # promotion turns the standby into the durability owner:
                # the periodic cycle starts then (no restore — the
                # replicated state it just uploaded IS newer than any
                # local snapshot)
                on_promote_hooks.append(snapshotter.start)
            else:
                # auto resolved to primary (peer dark): normal warm boot
                snapshotter.restore()
                snapshotter.start()

    stop = threading.Event()

    def on_signal(signum, frame):
        logger.warning("got signal %s, shutting down sidecar", signum)
        stop.set()

    for sig in (signal.SIGINT, signal.SIGTERM, signal.SIGHUP):
        signal.signal(sig, on_signal)
    stop.wait()
    server.close()
    if fed is not None:
        # stop the settle pump before the final drain snapshot so the
        # fed.snap section captures a quiescent ledger
        fed.close()
    if repl is not None:
        repl.close()
    if snapshotter is not None:
        # frontends are disconnected; quiesce the batcher and hand the
        # next process a slab with every admitted decision in it
        # (a never-promoted standby never started the cycle and must not
        # overwrite the primary's files with its empty slab)
        if repl is None or not repl.is_standby:
            snapshotter.drain()
    store.stop_flushing()
    debug.shutdown()
    tracer.close()


if __name__ == "__main__":
    main()
