"""Host-path profiler: cProfile over the flat_per_second request loop.

Answers "where does the host half of a should_rate_limit go?" with the
exact service stack bench.py's flat_per_second tier builds (same config,
same TPU-slab backend, same batch window), driven from ONE thread under
cProfile and printed as a top-N cumulative table:

    python -m tools.hotpath_profile                 # 2000 requests, top 25
    python -m tools.hotpath_profile -n 500 --top 10 --sort tottime
    make profile

The dispatch loop's owner thread is not profiled here: its
take/launch/redeem cycle is named by ratelimit.dispatch.* spans in any
jax.profiler capture (GET /debug/profile; tracing/host.py).

Single-thread on purpose: cProfile instruments only the calling thread,
so the dispatcher/device threads show up as one honest
`lock.acquire` line (the time THIS thread spends waiting on the launch
round trip) instead of half-attributed noise. Use `--pyinstrument` for a
wall-clock sampling view when that package is installed.

Output contract (pinned by tests/test_tools_platform.py): a
`[hotpath] rate=<N>/s requests=<N>` summary line, then the standard
pstats table whose header row contains `ncalls  tottime`.
"""

from __future__ import annotations

import argparse
import cProfile
import io
import os
import pstats
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("-n", type=int, default=2000, help="requests to drive")
    parser.add_argument("--top", type=int, default=25, help="rows to print")
    parser.add_argument(
        "--sort",
        default="cumulative",
        choices=["cumulative", "tottime", "ncalls"],
    )
    parser.add_argument(
        "--frontend",
        action="store_true",
        help="profile one FRONTEND WORKER's hot loop end to end "
        "(decode -> match -> compose -> publish over shm rings to a "
        "local device owner) and print the native-vs-python split",
    )
    parser.add_argument(
        "--pyinstrument",
        action="store_true",
        help="wall-clock sampling profile instead of cProfile",
    )
    parser.add_argument(
        "--shard-split",
        action="store_true",
        help="print the ROUTED mesh dispatch owner's stage split "
        "(host bucket / pad+H2D / launch ns per mesh launch, "
        "parallel/sharded_slab.py shard_routing_snapshot) on a virtual "
        "CPU mesh, plus the per-shard row mix and padding waste",
    )
    parser.add_argument(
        "--shards",
        type=int,
        default=4,
        help="virtual mesh size for --shard-split (default 4)",
    )
    parser.add_argument(
        "--slab-split",
        action="store_true",
        help="print the slab stage-split baseline (set-gather / scan / "
        "scatter ns per launch, SlabDeviceEngine.profile_slab_split) "
        "instead of a host profile",
    )
    args = parser.parse_args(argv)

    sys.path.insert(0, REPO)
    if args.shard_split:
        # must run before anything imports jax: the forced device split
        # only takes effect at backend init
        return _run_shard_split(args)
    if args.frontend:
        return _run_frontend_profile(args)
    import bench

    service, cache, _store = bench._build_service(
        "flat_per_second",
        bench._FLAT,
        telemetry=True,
    )
    reqs = bench._requests_for("flat_per_second", 2048)
    # warmup: compile/prime outside the profiled region
    for request in reqs[:64]:
        service.should_rate_limit(request)

    if args.slab_split:
        return _run_slab_split(cache, _store)
    try:
        if args.pyinstrument:
            return _run_pyinstrument(service, reqs, args)
        prof = cProfile.Profile()
        t0 = time.perf_counter()
        prof.enable()
        for i in range(args.n):
            service.should_rate_limit(reqs[i % len(reqs)])
        prof.disable()
        elapsed = time.perf_counter() - t0
        print(f"[hotpath] rate={round(args.n / elapsed)}/s requests={args.n}")
        out = io.StringIO()
        stats = pstats.Stats(prof, stream=out)
        stats.sort_stats(args.sort).print_stats(args.top)
        print(out.getvalue())
        return 0
    finally:
        cache.close()


def _run_slab_split(cache, store) -> int:
    """The slab_split stage baseline: gather/scan/scatter per-launch ns
    on this box's geometry, recorded into (and reported from) the same
    ratelimit.slab.split.* runtime histograms bench.py publishes.

    Output contract (pinned by tests/test_tools_platform.py): one
    `[slab_split] batch=<N>` line, then `<stage>_ns p50=<N> p99=<N>`
    per stage."""
    try:
        engine = getattr(cache, "engine", None)
        if engine is None or not hasattr(engine, "profile_slab_split"):
            print("[slab_split] no slab engine in this build", file=sys.stderr)
            return 1
        result = engine.profile_slab_split(
            scope=store.scope("ratelimit").scope("slab"), iters=30
        )
        if not result:
            print("[slab_split] mesh engine: use tools/profile_engine.py",
                  file=sys.stderr)
            return 1
        import bench

        split = bench._slab_split(store)
        print(f"[slab_split] batch={result['batch']}")
        for stage in ("gather_ns", "scan_ns", "scatter_ns"):
            h = split.get(stage, {})
            print(
                f"  {stage:<11} p50={h.get('p50', result[stage])} "
                f"p99={h.get('p99', result[stage])}"
            )
        return 0
    finally:
        cache.close()


def _run_shard_split(args) -> int:
    """The routed dispatch owner's stage split on a virtual CPU mesh
    (SHARD_ROUTED_BATCHING, parallel/sharded_slab.py): host owner-hash +
    argsort (bucket), per-shard block fill + H2D (pad), and device
    dispatch (launch), per mesh launch, driven by a Zipf-skewed stream
    with the hot-key tier armed so the printout shows the shipped
    default's flattened shard mix.

    Output contract (pinned by tests/test_tools_platform.py): one
    `[shard_split] shards=<N> launches=<M>` line, a `<stage>_ns
    p50=<N> p99=<N>` row per stage, the per-shard routed row counts,
    and the cumulative `padding_waste_pct=`."""
    n_shards = max(2, int(args.shards))
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_shards}"
    ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    import bench
    from api_ratelimit_tpu.parallel.sharded_slab import (
        ShardedSlabEngine,
        make_mesh,
    )
    from api_ratelimit_tpu.ops.slab import (
        ROW_DIVIDER,
        ROW_FP_HI,
        ROW_FP_LO,
        ROW_HITS,
        ROW_LIMIT,
        ROW_SCALARS,
    )

    devices = jax.devices()[:n_shards]
    if len(devices) < 2:
        print(
            f"[shard_split] needs >=2 devices, got {len(devices)} "
            "(is another jax backend already initialized?)",
            file=sys.stderr,
        )
        return 1
    engine = ShardedSlabEngine(
        mesh=make_mesh(devices),
        n_slots_global=len(devices) * (1 << 13),
        routed=True,
        hot_tier=True,
        hotkey_lanes=128,
        hotkey_k=16,
        hot_min_count=200,
    )
    batch = 8192
    now = int(time.time())
    ids = bench.zipf_ids(50_000, batch, 6, seed=1)

    def pack(block_ids: np.ndarray) -> np.ndarray:
        p = np.zeros((7, block_ids.size), dtype=np.uint32)
        x = block_ids.astype(np.uint32)
        p[ROW_FP_LO] = bench.fmix32_np(x)
        p[ROW_FP_HI] = bench.fmix32_np(x ^ np.uint32(0xA5A5A5A5))
        p[ROW_HITS] = 1
        p[ROW_LIMIT] = 100
        p[ROW_DIVIDER] = 60
        p[ROW_SCALARS, 0] = np.uint32(now)
        p[ROW_SCALARS, 1] = np.float32(0.8).view(np.uint32)
        return p

    # block 0 warms the compile and feeds the sketch; the drain promotes
    # the Zipf head so the timed launches run the shipped default
    engine.step_after_compact(pack(ids[0]), 0xFFFF)
    engine.drain_hotkeys()
    for i in range(1, 6):
        engine.step_after_compact(pack(ids[i]), 0xFFFF)

    snap = engine.shard_routing_snapshot()
    print(f"[shard_split] shards={snap['shards']} launches={snap['launches']}")
    for stage in ("bucket_ns", "pad_ns", "launch_ns"):
        h = snap["stage_ns"][stage]
        print(f"  {stage:<10} p50={h.get('p50', 0)} p99={h.get('p99', 0)}")
    print(f"  shard_rows {snap['shard_rows']}")
    print(
        f"  padding_waste_pct={snap['padding_waste_pct']} "
        f"hot_keys={snap['hot_tier']['keys']}"
    )
    return 0


def _run_frontend_profile(args) -> int:
    """The FRONTEND_PROCS worker's view: a sidecar-backed service whose
    submits publish over shm rings to a device owner (running here on
    background threads, so the profiled REQUEST thread sees exactly what
    a worker process's handler thread sees: transport decode -> compiled
    matcher -> key compose -> row write -> shm publish -> verdict spin).
    Prints the standard pstats table plus a [native_split] block: which
    hot-loop stages run native and the per-stage ns from the runtime
    histograms.

    Output contract (pinned by tests/test_tools_platform.py): the
    `[hotpath] ... path=frontend-shm` line, a `[native_split]` line, then
    the pstats header row."""
    import tempfile

    import numpy as np  # noqa: F401 - bench pulls it anyway

    import bench
    from api_ratelimit_tpu.backends.sidecar import (
        SidecarEngineClient,
        SlabSidecarServer,
    )
    from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine, TpuRateLimitCache
    from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter
    from api_ratelimit_tpu.ops import native
    from api_ratelimit_tpu.service.ratelimit import RateLimitService
    from api_ratelimit_tpu.stats.sinks import NullSink
    from api_ratelimit_tpu.stats.store import Store
    from api_ratelimit_tpu.utils.timeutil import RealTimeSource

    td = tempfile.mkdtemp()
    sock = os.path.join(td, "owner.sock")
    ctl = sock + ".shmctl"
    engine = SlabDeviceEngine(
        RealTimeSource(),
        n_slots=1 << 16,
        use_pallas=False,
        buckets=(8, 128, 1024),
        batch_window_seconds=0.0005,
        max_batch=8192,
        block_mode=True,
    )
    server = SlabSidecarServer(sock, engine, shm_control_path=ctl)
    store = Store(NullSink())
    scope = store.scope("ratelimit")
    client = SidecarEngineClient(sock, scope=scope, shm_control_path=ctl)
    cache = TpuRateLimitCache(
        BaseRateLimiter(RealTimeSource()), engine=client
    )
    service = RateLimitService(
        runtime=bench._StaticRuntime(bench._FLAT),
        cache=cache,
        stats_scope=scope.scope("service"),
        time_source=RealTimeSource(),
    )
    reqs = bench._requests_for("flat_per_second", 2048)
    for request in reqs[:64]:
        service.should_rate_limit(request)
    try:
        prof = cProfile.Profile()
        t0 = time.perf_counter()
        prof.enable()
        for i in range(args.n):
            service.should_rate_limit(reqs[i % len(reqs)])
        prof.disable()
        elapsed = time.perf_counter() - t0
        print(
            f"[hotpath] rate={round(args.n / elapsed)}/s "
            f"requests={args.n} path=frontend-shm"
        )
        config = service.get_current_config()
        matcher_native = bool(
            config is not None
            and getattr(config.compiled, "native_active", False)
        )
        shm_active = client._shm is not None and not client._shm.dead
        print(
            f"[native_split] codec={'native' if native.available() else 'python'} "
            f"matcher={'native' if matcher_native else 'python'} "
            f"submit={'shm' if shm_active else 'socket'}"
        )
        snap = store.debug_snapshot()
        for label, key in (
            ("matcher_ns", "ratelimit.service.host.matcher_ms"),
            ("key_compose_ns", "ratelimit.host.key_compose_ms"),
            ("pack_ns", "ratelimit.host.pack_ms"),
            ("shm_submit_ns", "ratelimit.sidecar.shm_ms"),
        ):
            p50 = snap.get(f"{key}.p50")
            p99 = snap.get(f"{key}.p99")
            if p50 is None:
                continue
            print(
                f"  {label:<15} p50={round(p50 * 1e6)} p99={round(p99 * 1e6)}"
            )
        out = io.StringIO()
        stats = pstats.Stats(prof, stream=out)
        stats.sort_stats(args.sort).print_stats(args.top)
        print(out.getvalue())
        return 0
    finally:
        cache.close()
        server.close()


def _run_pyinstrument(service, reqs, args) -> int:
    try:
        from pyinstrument import Profiler
    except ImportError:
        print(
            "[hotpath] pyinstrument is not installed in this environment; "
            "re-run without --pyinstrument",
            file=sys.stderr,
        )
        return 2
    profiler = Profiler()
    t0 = time.perf_counter()
    with profiler:
        for i in range(args.n):
            service.should_rate_limit(reqs[i % len(reqs)])
    elapsed = time.perf_counter() - t0
    print(f"[hotpath] rate={round(args.n / elapsed)}/s requests={args.n}")
    print(profiler.output_text(unicode=True, color=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
