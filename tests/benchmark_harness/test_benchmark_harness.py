"""Tests of the benchmark's own code (BENCHMARK.json, benchmark/), on the
CPU at tiny sizes; tier-1 runs them with the rest of tests/.

No number here is a device metric: these check the harness's arithmetic,
its discovery by name, its refusal without a chip, that each entry drives
a whole run end to end, that the comparison catches the controls and the
faults a cell can have, and that the real shapes compile for a described
v5e chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
DATA = os.path.join(BENCH_DIR, "testdata")
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import bytemodel, device, reference, spec, stats, trace, traffic, wire  # noqa: E402


# -- arithmetic --


def test_byte_model_is_92_bytes_per_decision():
    assert bytemodel.BYTES_PER_DECISION == 24 + 2 * 32 + 4 == 92
    peak = device.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    # 1M decisions in 1 ms of device time: 92 MB / 819 GB/s = 112.3 us
    assert bytemodel.roofline_percent(10**6, 1e-3, peak) == pytest.approx(11.233, rel=1e-3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v99")


def test_percentiles_from_raw_samples():
    xs = list(range(1, 101))  # 1..100
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.5], 99) == 7.5
    # not bucket edges: a value between ladder steps comes back as itself
    assert stats.percentile([0.31, 0.47, 2.9], 99) == 2.9
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)


def test_union_and_idle_share():
    busy, merged = trace.union_length([(0, 10), (5, 15), (20, 30), (29, 31), (40, 40)])
    assert busy == 15 + 11 and merged[:2] == [[0, 15], [20, 31]]
    summ = trace.TraceSummary(window_s=100e-9, devices=[trace.DeviceTrace("d", busy, merged)],
                              host_spans=[(16, 19, "bench.x")])
    assert summ.idle_share() == pytest.approx(1 - 26 / 100)
    gaps = summ.breakdown()["idle_gaps"]
    assert gaps[0] == ["unattributed", 9e-9] and ["bench.x", 5e-9] in gaps


def test_trace_reduction_on_recorded_trace():
    """A trace recorded on a v5e chip (three 128-wide slab-step launches,
    benchmark/testdata): the reduction finds the device, its busy time,
    the step modules and a breakdown."""
    path = os.path.join(DATA, "tiny.xplane.pb")
    summ = trace.reduce_xplane(path, window_s=1.0)
    assert len(summ.devices) == 1
    assert 0 < summ.busy_s < 1.0
    runs, secs = summ.module_time("jit_slab_step")
    # the only device work is the 3 steps; a module's span also covers the
    # short gaps between its operations, so it is a little above their union
    assert runs == 3 and summ.busy_s <= secs < 1.2 * summ.busy_s
    bd = summ.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]
    assert all(name.startswith("bench.") or name == "unattributed" for name, _ in bd["idle_gaps"])


def test_wire_round_trip():
    from api_ratelimit_tpu.pb import rls_v3

    raw = wire.encode_request("d", [[("remote_address", "10.0.0.1")], [("user_id", "u1")]])
    req = rls_v3.RateLimitRequest.FromString(raw)
    assert req.domain == "d" and [e.value for d in req.descriptors for e in d.entries] == [
        "10.0.0.1", "u1"]
    resp = rls_v3.RateLimitResponse(overall_code=2)
    for code in (1, 2):
        resp.statuses.add(code=code, limit_remaining=3)
    assert wire.decode_status_codes(resp.SerializeToString()) == [1, 2]


def test_open_loop_schedule_is_fixed_work():
    a = traffic.open_loop_offsets(traffic.rng_for(1, "s"), 1000.0, 2.0)
    b = traffic.open_loop_offsets(traffic.rng_for(2**33 + 1, "s"), 1000.0, 2.0)
    assert a.shape == b.shape == (2000,)
    assert 0 <= a.min() and a.max() < 2.0 and np.all(np.diff(a) > 0)
    z = traffic.Zipf(1000, 0.99)
    assert np.array_equal(z.draw(traffic.rng_for(5, "z"), 100), z.draw(traffic.rng_for(5, "z"), 100))


def test_late_answer_is_late_not_failed():
    """An answer past the deadline keeps its own latency and is not on
    time; only a request with no answer, or a malformed one, failed."""
    from harness.entries.served_grpc import window_numbers
    from harness.loadgen import ST_ERROR, ST_NO_ANSWER, ST_OK

    t_due = np.array([0.1, 0.2, 0.3, 0.4, 0.5, 1.5])
    rec = {
        "t_due": t_due, "t_sent": t_due,
        "t_done": t_due + np.array([0.005, 0.6, 0.01, 60.0, 0.004, 0.002]),
        "status": np.array([ST_OK, ST_OK, ST_ERROR, ST_NO_ANSWER, ST_OK, ST_OK], dtype=np.int8),
        "malformed": np.array([False, False, False, False, True, False]),
    }
    nums = window_numbers(rec, (0.0, 1.0), deadline_s=0.25, n_desc=2)
    assert nums["attempted"] == 5 and nums["failed"] == 3 and nums["on_time"] == 1
    # answered in the window: the first two (the late one too), 2 descriptors each
    assert nums["decisions"] == 4
    np.testing.assert_allclose(nums["latency_ms"], [5.0, 600.0, 250.0, 60000.0, 250.0])
    assert nums["missed_by_s"] == [4]


# -- the reference --


def test_counter_comparison():
    key = np.array([1, 1, 1, 2, 2])
    w = np.zeros(5, dtype=np.int64)
    ok = reference.compare_counters(key, w, w, np.array([2, 1, 3, 1, 2]), 255)
    assert (ok["counter_over"], ok["under_pairs"], ok["restarts"]) == (0, 0, 0)
    over = reference.compare_counters(key, w, w, np.array([2, 1, 4, 1, 2]), 255)
    assert over["counter_over"] == 1
    under = reference.compare_counters(key, w, w, np.array([1, 1, 2, 1, 2]), 255)
    assert (under["counter_over"], under["under_pairs"], under["restarts"]) == (0, 1, 1)
    # a value held by three rows needs two restarts; a counter of 0 (a row
    # the step never counted) one
    twice = reference.compare_counters(key, w, w, np.array([1, 1, 1, 1, 0]), 255)
    assert twice["restarts"] == 2 + 1
    sat = reference.compare_counters(np.ones(300, dtype=np.int64), np.zeros(300, dtype=np.int64),
                                     np.zeros(300, dtype=np.int64),
                                     np.minimum(np.arange(1, 301), 255), 255)
    assert (sat["counter_over"], sat["under_rows"], sat["restarts"]) == (0, 0, 0)
    # an ambiguous hit is not compared, and may have come first in either
    # of its windows: key 1's sure counters {1, 2} or {2, 3} both pass
    w_hi = np.array([0, 0, 1, 0, 0])
    for got in ([3, 2, 9, 1, 2], [1, 2, 9, 1, 2]):
        amb = reference.compare_counters(key, w, w_hi, np.array(got), 255)
        assert amb["compared_rows"] == 4 and (amb["counter_over"], amb["under_pairs"]) == (0, 0)
    amb = reference.compare_counters(key, w, w_hi, np.array([4, 2, 9, 1, 2]), 255)
    assert amb["counter_over"] == 1


def test_verdict_comparison():
    key = np.array([7] * 4)
    lim = np.full(4, 2)
    w = np.zeros(4, dtype=np.int64)
    ans = np.ones(4, dtype=bool)
    good = reference.compare_verdicts(key, lim, w, w, np.array([1, 2, 1, 2]), ans)
    assert (good["false_over"], good["excess_ok"], good["malformed"]) == (0, 0, 0)
    bad = reference.compare_verdicts(key, lim, w, w, np.array([1, 1, 1, 2]), ans)
    assert bad["excess_ok"] == 1
    bad = reference.compare_verdicts(key, lim, w, w, np.array([2, 2, 2, 1]), ans)
    assert bad["false_over"] == 1
    # an unanswered request may have been counted: it loosens, never fails
    part = reference.compare_verdicts(key, lim, w, w, np.array([1, 2, 0, 0]),
                                      np.array([True, True, False, False]))
    assert part["false_over"] == 0 and part["compared_hits"] == 2


# -- discovery by name --


def test_discovery_of_added_files(tmp_path):
    """A cell, a configuration and a metric are added as files and entries
    only; the harness finds each by name."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = spec.benchmark(CHECKOUT)
    bench["configs"].append({"name": "cfg_new", "source": "https://example.org/x",
                             "file": "benchmark/configs/cfg_new.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "cell_new", "config": "cfg_new", "traffic": "cell_new",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "metric_new.rate", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "batching",
                               "moves": "decisions_per_s", "workloads": ["cell_new"]})
    bench["end_to_end"][0].setdefault("workloads", []).append("cell_new")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bdir = str(root / "benchmark")
    (root / "benchmark" / "configs" / "cfg_new.json").write_text(json.dumps({"name": "cfg_new"}))
    (root / "benchmark" / "workloads" / "cell_new.json").write_text(
        json.dumps({"config": "cfg_new", "entry": "owner_rows"}))
    (root / "benchmark" / "metrics" / "metric_new.rate.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    got = spec.benchmark(str(root))
    assert spec.cell_entry(got, "cell_new")["config"] == "cfg_new"
    assert spec.workload("cell_new", bdir)["config"] == "cfg_new"
    assert spec.config("cfg_new", bdir)["name"] == "cfg_new"
    names = [m["name"] for m in spec.per_layer_for(got, "cell_new")]
    assert names == ["metric_new.rate"]
    assert spec.metric_reader("metric_new.rate", bdir)({"x": 21}) == 42
    e2e = [m["name"] for m in spec.end_to_end_for(got, "cell_new")]
    assert "setup_s" in e2e and bench["end_to_end"][0]["name"] in e2e


def test_every_listed_file_exists():
    bench = spec.benchmark(CHECKOUT)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(CHECKOUT, c["file"]))
        assert spec.config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        assert spec.workload(w["name"])["config"] == w["config"]
        assert spec.per_layer_for(bench, w["name"])
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


# -- the command without a chip --


def _cmd(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "benchmark/run.py", "--workload", "owner_zipf",
                           "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    out = _cmd(CHECKOUT)
    assert out.returncode != 0
    assert "no TPU visible" in out.stderr
    assert out.stdout.strip() == ""


def test_command_refuses_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cmd(str(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""


# -- each entry end to end at a tiny size on the CPU, with its controls and
# the faults the cell can have --


def _rc(cell: str, tmp_path, control=None, trace=False, **wl_over):
    import run
    from harness.run_context import RunContext

    jax = run.setup_jax()
    wl = spec.workload(cell)
    cfg = spec.config(wl["config"])
    if wl["entry"] == "owner_rows":
        cfg["env"].update(TPU_SLAB_SLOTS=str(1 << 16), TPU_BUCKETS="1024", TPU_BATCH_LIMIT="1024")
        cfg["records"] = 20000
        wl.update(frame_rows=256, pool_frames=16, load_frame_rows=1024, warmup_seconds=0.3,
                  sample_one_in=4, min_compared_rows=100)
    else:
        cfg["env"].update(TPU_SLAB_SLOTS=str(1 << 16), TPU_BUCKETS="128", TPU_BATCH_LIMIT="128")
        wl.update(rate=300, pool_requests=3000, warmup_seconds=0.5, workers=2, concurrency=8,
                  min_compared_hits=200)
    wl.update(wl_over)
    return RunContext(cell=cell, wl=wl, cfg=cfg, seed=2**31 + 11, seconds=1.5, trace=trace,
                      control=control, t_process=time.perf_counter(),
                      devices=jax.devices()[:1], compiles=device.CompileCounter(),
                      trace_dir=str(tmp_path / "trace"), info=lambda *a, **k: None)


def _run(cell, tmp_path, **kw):
    import importlib

    rc = _rc(cell, tmp_path, **kw)
    entry = importlib.import_module(f"harness.entries.{rc.wl['entry']}")
    res = entry.run(rc)
    return res, all(v <= lim for v, lim in res.checks.values())


@pytest.fixture
def frozen_state(monkeypatch):
    """Fault: the slab step returns its state unchanged."""
    import jax
    import jax.numpy as jnp

    from api_ratelimit_tpu.backends import tpu

    real = tpu.slab_step_after

    def step(state, *a, **k):
        kept = jax.tree.map(lambda x: jnp.array(x, copy=True), state)
        outs = real(state, *a, **k)
        return (kept,) + tuple(outs[1:])

    monkeypatch.setattr(tpu, "slab_step_after", step)


CELLS = ["owner_zipf", "edge_steady", "edge_saturate"]
CONTROLS = {"owner_rows": "lost_update", "served_grpc": "over_admit"}


@pytest.mark.parametrize("cell", CELLS)
def test_entry_end_to_end_is_correct(cell, tmp_path):
    res, correct = _run(cell, tmp_path)
    assert correct, res.checks
    assert res.attempted > 0 and res.end_to_end["setup_s"] > 0
    bench = spec.benchmark(CHECKOUT)
    for m in spec.end_to_end_for(bench, cell):
        assert res.end_to_end[m["name"]] > 0, m["name"]
    # the per-layer readers that need no trace read from this run's context
    for m in spec.per_layer_for(bench, cell):
        if m["source"] == "host_clock":
            assert spec.metric_reader(m["name"])(res.layer_ctx) > 0, m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    entry = spec.workload(cell)["entry"]
    _res, correct = _run(cell, tmp_path, control=CONTROLS[entry])
    assert not correct


@pytest.mark.parametrize("cell", ["owner_zipf", "edge_steady"])
def test_fault_state_unchanged_is_not_correct(cell, tmp_path, frozen_state):
    _res, correct = _run(cell, tmp_path)
    assert not correct


def test_fault_counter_altered_is_not_correct(tmp_path, monkeypatch):
    from api_ratelimit_tpu.backends.tpu import SlabDeviceEngine

    real = SlabDeviceEngine.submit_rows

    def altered(self, block, lease_ops=None):
        out = np.array(real(self, block, lease_ops))
        out[0] += 1
        return out

    monkeypatch.setattr(SlabDeviceEngine, "submit_rows", altered)
    res, correct = _run("owner_zipf", tmp_path)
    assert not correct and res.checks["counter_over"][0] > 0


def test_fault_verdict_altered_is_not_correct(tmp_path, monkeypatch):
    from api_ratelimit_tpu.server import grpc_service

    real = grpc_service.proto_adapter.response_to_v3

    def altered(overall, statuses, headers):
        resp = real(overall, statuses, headers)
        if resp.statuses and resp.statuses[0].code == 1:
            resp.statuses[0].code = 2
        return resp

    monkeypatch.setattr(grpc_service.proto_adapter, "response_to_v3", altered)
    res, correct = _run("edge_steady", tmp_path)
    assert not correct and res.checks["false_over"][0] > 0


def test_traced_run_reports_layers(tmp_path):
    """--trace 1 on the CPU: the histogram readers read; the trace has no
    TPU plane, so the reduction refuses rather than inventing a number."""
    with pytest.raises(ValueError, match="no device plane"):
        _run("owner_zipf", tmp_path, trace=True)


# -- compile checks at the real shapes, for a described v5e chip --


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_owner_step_compiles_for_v5e(topo):
    """incrby_owner's step: 2^24 slots, a 65,536-wide launch, Pallas, on one
    described v5e chip. The program's memory estimate is recorded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from api_ratelimit_tpu.ops.sketch import SKETCH_PLANES, sketch_ways
    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, SlabState, default_ways, slab_step_after

    cfg = spec.config("incrby_owner")
    slots = int(cfg["env"]["TPU_SLAB_SLOTS"])
    batch = int(cfg["env"]["TPU_BATCH_LIMIT"])
    ways, lanes = default_ways("tpu"), 128  # the defaults: 128 ways, HOTKEY_LANES=128
    one = SingleDeviceSharding(topo.devices[0])

    def u32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = slab_step_after.lower(
            SlabState(table=u32((slots, ROW_WIDTH))), u32((7, batch)), ways=ways,
            out_dtype=jnp.uint8, use_pallas=True, multi_algo=False,
            sketch=u32((SKETCH_PLANES, lanes)), sketch_ways=sketch_ways(ways, lanes),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    mem = compiled.memory_analysis()
    print("owner step memory_analysis:", mem)
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.argument_size_in_bytes >= slots * ROW_WIDTH * 4


def test_mesh4_routed_step_compiles_for_v5e(topo):
    """The mesh owner's routed step (a later cell, PERF.md Open
    questions): one shard of the 2^26-slot mesh, 2^24 slots, a 65,536-wide
    launch, placed on the last chip of a described v5e 2x2."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from api_ratelimit_tpu.ops.slab import ROW_WIDTH, default_ways
    from api_ratelimit_tpu.parallel.sharded_slab import _routed_body

    last = SingleDeviceSharding(topo.devices[3])
    step = jax.jit(functools.partial(_routed_body, ways=default_ways("tpu"), cap=0xFF,
                                     use_pallas=True), donate_argnums=(0,))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = step.lower(
            jax.ShapeDtypeStruct(((1 << 26) // 4, ROW_WIDTH), jnp.uint32, sharding=last),
            jax.ShapeDtypeStruct((7, 65536), jnp.uint32, sharding=last),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    mem = compiled.memory_analysis()
    print("mesh4 routed shard memory_analysis:", mem)
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.argument_size_in_bytes >= (1 << 24) * ROW_WIDTH * 4
