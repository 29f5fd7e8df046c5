"""The hash-sharded sidecar owner (configuration incrby_mesh4:
TPU_MESH_DEVICES=4, routed per-shard batching, hot tier off) built as the
server builds it, through create_limiter, on 4 of the suite's 8 virtual
CPU devices at a small slab.

Its counters are held to the benchmark's plain reference
(benchmark/harness/reference.py) and, single-threaded, to the
single-device engine's on the same stream; a control that drops one
increment must fail the same comparison. The host stages the mesh adds
record one histogram sample and one span each per launch. No number here
is a device metric."""

from __future__ import annotations

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from harness import reference, spec, stats, traffic  # noqa: E402

from api_ratelimit_tpu.limiter.base_limiter import BaseRateLimiter  # noqa: E402
from api_ratelimit_tpu.runner import create_limiter  # noqa: E402
from api_ratelimit_tpu.settings import new_settings  # noqa: E402
from api_ratelimit_tpu.stats.sinks import NullSink  # noqa: E402
from api_ratelimit_tpu.stats.store import Store  # noqa: E402
from api_ratelimit_tpu.utils.timeutil import FakeTimeSource  # noqa: E402

NOW = 1_800_000_000  # a fixed clock: every row lands in one minute window
UNIT = 60
LIMIT = 100
CAP = 0xFF  # the readback width for hits=1, limit=100 (README: saturating readback)
KEYS = 3000
FRAME = 256
SHARD = "ratelimit.shard."


def _env(**over) -> dict:
    """incrby_mesh4's environment at a small slab and launch width."""
    env = dict(spec.config("incrby_mesh4")["env"])
    env.update(TPU_SLAB_SLOTS=str(1 << 16), TPU_BUCKETS="1024",
               TPU_BATCH_LIMIT="1024")
    env.update(over)
    return env


def _build(env: dict):
    store = Store(NullSink())
    cache = create_limiter(new_settings(env), BaseRateLimiter(FakeTimeSource(NOW)),
                           store)
    return cache, store


def _block(ids: np.ndarray, divider: int = UNIT) -> np.ndarray:
    b = np.empty((6, ids.shape[0]), dtype=np.uint32)
    b[0], b[1] = traffic.record_fingerprints(ids)
    b[2], b[3], b[4], b[5] = 1, LIMIT, divider, 0
    return b


def _frames(seed: int, n: int) -> list:
    zipf = traffic.Zipf(KEYS, 0.99)
    rng = traffic.rng_for(seed, "mesh_owner")
    return list(zipf.draw(rng, n * FRAME).reshape(n, FRAME))


def _run_threads(engine, frames, threads: int):
    """Each thread submits every threads-th frame; returns (key ids,
    counters) in submission order per thread, joined."""
    parts, errors = [None] * threads, []

    def submitter(t):
        keys, afters = [], []
        try:
            for ids in frames[t::threads]:
                afters.append(np.array(engine.submit_rows(_block(ids))))
                keys.append(ids)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)
        parts[t] = (keys, afters)

    ths = [threading.Thread(target=submitter, args=(t,)) for t in range(threads)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    if errors:
        raise errors[0]
    keys = np.concatenate([k for p in parts for k in p[0]]).astype(np.int64)
    afters = np.concatenate([a for p in parts for a in p[1]]).astype(np.int64)
    return keys, afters


def _compare(keys, afters) -> dict:
    win = np.full(keys.shape[0], NOW // UNIT, dtype=np.int64)
    return reference.compare_counters(keys, win, win, afters, CAP)


@pytest.fixture(scope="module")
def threaded_run():
    """Four submitter threads over one zipf stream; the mesh engine built
    by create_limiter, every key inserted first in an earlier window (a
    warm owner, as the benchmark's load phase), its lossy events counted
    around the stream."""
    cache, _store = _build(_env())
    engine = cache.engine
    try:
        assert engine._engine is not None and engine._engine._routed
        assert not engine._engine.hot_tier_enabled
        for ids in np.array_split(np.arange(KEYS, dtype=np.uint32), 4):
            engine.submit_rows(_block(ids, divider=86399))
        health0 = engine.health_snapshot()
        keys, afters = _run_threads(engine, _frames(2**31 + 7, 48), threads=4)
        lossy = reference.lossy_events(health0, engine.health_snapshot())
    finally:
        cache.close()
    return keys, afters, lossy


def test_mesh_owner_holds_to_the_reference(threaded_run):
    keys, afters, lossy = threaded_run
    cmp = _compare(keys, afters)
    assert cmp["compared_rows"] == 48 * FRAME
    # every key already holds its row: nothing evicted, nothing dropped
    assert lossy == 0
    assert cmp["counter_over"] == 0
    assert cmp["restarts"] <= lossy
    assert cmp["under_rows"] == 0


def test_a_dropped_increment_fails_the_comparison(threaded_run):
    """Control: the hottest key's second increment lost, so every counter
    above its first reads one less and one value repeats — a restart no
    counted lossy event explains."""
    keys, afters, lossy = threaded_run
    afters = afters.copy()
    hot = keys == np.bincount(keys).argmax()
    first = afters[hot].min()
    afters[hot] = np.where(afters[hot] > first, afters[hot] - 1, afters[hot])
    cmp = _compare(keys, afters)
    assert cmp["restarts"] > lossy


def test_mesh_counters_equal_the_single_device_engine():
    """Single-threaded, launch for launch: the routed mesh and the
    single-device engine give the same post-increment counters."""
    frames = _frames(2**32 + 3, 24)
    got = {}
    for name, env in (("mesh", _env()), ("one", _env(TPU_MESH_DEVICES="0"))):
        cache, _store = _build(env)
        try:
            assert (cache.engine._engine is not None) == (name == "mesh")
            got[name] = _run_threads(cache.engine, frames, threads=1)
        finally:
            cache.close()
    np.testing.assert_array_equal(got["mesh"][0], got["one"][0])
    np.testing.assert_array_equal(got["mesh"][1], got["one"][1])
    assert _compare(*got["mesh"])["counter_over"] == 0


# -- the instrumentation of the mesh's host stages --


def test_one_routed_launch_records_each_stage_once():
    cache, store = _build(_env())
    try:
        ids = _frames(5, 1)[0]
        before = stats.histogram_totals(store)
        cache.engine.submit_rows(_block(ids))
        hist = stats.delta(before, stats.histogram_totals(store))
    finally:
        cache.close()
    for stage in ("topk_ms", "route_ms", "collect_ms"):
        count, total = hist[SHARD + stage]
        assert count == 1 and total > 0, stage
    assert hist[SHARD + "routed_rows"] == (1, float(FRAME))
    lanes_count, lanes = hist[SHARD + "padded_lanes"]
    assert lanes_count == 1 and lanes >= FRAME
    assert hist["ratelimit.dispatch.batch_size"] == (1, float(FRAME))


def test_rows_and_padded_lanes_sum_over_launches():
    """Over many launches of a threaded stream: every submitted row routed
    once, and at least as many lanes launched."""
    cache, store = _build(_env())
    try:
        frames = _frames(11, 12)
        _run_threads(cache.engine, frames, threads=3)
        hist = stats.histogram_totals(store)
    finally:
        cache.close()
    launches, rows = hist[SHARD + "routed_rows"]
    assert rows == 12 * FRAME and 1 <= launches <= 12
    assert hist[SHARD + "padded_lanes"][0] == launches
    assert hist[SHARD + "padded_lanes"][1] >= rows
    assert hist[SHARD + "topk_ms"][0] == launches


def test_no_scope_no_histograms():
    """An engine built without a stats scope (direct use) records nothing
    and still serves."""
    import jax

    from api_ratelimit_tpu.parallel.sharded_slab import ShardedSlabEngine, make_mesh

    eng = ShardedSlabEngine(mesh=make_mesh(jax.devices()[:4]), n_slots_global=1 << 14,
                            routed=True, hotkey_lanes=128)
    assert eng._h_topk is None and eng._h_rows is None
    packed = np.zeros((7, 128), dtype=np.uint32)
    packed[:6, :FRAME // 2] = _block(_frames(3, 1)[0][: FRAME // 2])
    packed[6, 0] = NOW
    out = eng.step_after_compact(packed, cap=CAP)
    assert out.shape == (128,) and out[: FRAME // 2].min() >= 1


def test_stage_spans_while_a_profile_runs(tmp_path):
    """Under a capture each launch opens ratelimit.shard.{topk, route,
    launch, collect} once, in that order."""
    import jax
    from jax.profiler import ProfileData

    cache, _store = _build(_env())
    try:
        ids = _frames(6, 1)[0]
        cache.engine.submit_rows(_block(ids))  # compile outside the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            cache.engine.submit_rows(_block(ids))
        finally:
            jax.profiler.stop_trace()
    finally:
        cache.close()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.start_ns, ev.name) for ev in line.events
                          if ev.name.startswith(SHARD)]
    names = [n for _t, n in sorted(spans)]
    assert names == [SHARD + s for s in ("topk", "route", "launch", "collect")]


def test_routed_module_is_named():
    """The routed per-shard program's module reads jit_slab_step_routed,
    so a trace reader finds it by name."""
    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH
    from api_ratelimit_tpu.parallel.sharded_slab import slab_step_routed

    dev = jax.devices()[3]
    table = jax.device_put(jnp.zeros((1 << 12, ROW_WIDTH), jnp.uint32), dev)
    block = jax.device_put(jnp.zeros((7, 128), jnp.uint32), dev)
    text = slab_step_routed.lower(table, block, ways=8, cap=CAP,
                                  use_pallas=False).as_text()
    assert text.splitlines()[0].startswith("module @jit_slab_step_routed ")


# -- the benchmark cell through its entry, at a tiny size --


def test_cell_end_to_end_is_correct(tmp_path):
    """owner_zipf_mesh4 through harness/entries/owner_rows.py on four CPU
    devices: correct, and the new histogram readers read its context."""
    import importlib

    import jax
    from harness import device
    from harness.run_context import RunContext

    wl = spec.workload("owner_zipf_mesh4")
    cfg = spec.config(wl["config"])
    cfg["env"].update(TPU_SLAB_SLOTS=str(1 << 16), TPU_BUCKETS="1024",
                      TPU_BATCH_LIMIT="1024")
    cfg["records"] = 20000
    wl.update(frame_rows=256, pool_frames=16, load_frame_rows=1024, warmup_seconds=0.3,
              sample_one_in=4, min_compared_rows=100)
    rc = RunContext(cell="owner_zipf_mesh4", wl=wl, cfg=cfg, seed=2**31 + 13, seconds=1.5,
                    trace=False, control=None, t_process=time.perf_counter(),
                    devices=jax.devices()[:4], compiles=device.CompileCounter(),
                    trace_dir=str(tmp_path / "trace"), info=lambda *a, **k: None)
    entry = importlib.import_module(f"harness.entries.{wl['entry']}")
    res = entry.run(rc)
    assert all(v <= lim for v, lim in res.checks.values()), res.checks
    assert res.end_to_end["decisions_per_s"] > 0
    bench = spec.benchmark(CHECKOUT)
    for m in spec.per_layer_for(bench, "owner_zipf_mesh4"):
        value = spec.metric_reader(m["name"])(res.layer_ctx)
        if m["source"] == "device_trace":
            assert value is None, m["name"]  # no trace in an untraced run
        else:
            assert value is not None and value > 0, m["name"]
