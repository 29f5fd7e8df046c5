"""Idle gaps named by the program's spans (benchmark/harness/gaps.py), the
recorded trace's numbers pinned, and the readers of the engine's drain,
lock-wait and submit-wait histograms. CPU only; no device metric here."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
TINY = os.path.join(BENCH_DIR, "testdata", "tiny.xplane.pb")
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import gaps, spec, trace  # noqa: E402

OWNER = ("/host:CPU", 3)
FLUSH = ("/host:CPU", 7)
REQUEST = ("/host:CPU", 9)


def _summary(program_spans, bench_spans=()):
    """One chip busy over [0,10], [20,30], [60,70], [120,130]: gaps of 50,
    30 and 10 ns, with midpoints 95, 45 and 15."""
    merged = [[0, 10], [20, 30], [60, 70], [120, 130]]
    dev = trace.DeviceTrace("/device:TPU:0", busy_ns=40, merged=merged)
    base = trace.TraceSummary(window_s=140e-9, devices=[dev], host_spans=list(bench_spans))
    return gaps.NamedTraceSummary.of(base, list(program_spans))


def test_gap_named_by_the_owner_span():
    s = _summary([
        (0, 200, "ratelimit.dispatch.wait_work", OWNER),   # not innermost
        (85, 105, "ratelimit.dispatch.redeem", OWNER),
        (90, 100, "ratelimit.device.readback", OWNER),     # innermost at 95
        (0, 200, "ratelimit.dispatch.submit_wait", REQUEST),  # not the owner
    ])
    assert s.breakdown()["idle_gaps"][0] == ["ratelimit.device.readback", 50e-9]
    assert s.breakdown()["idle_gaps"][1] == ["ratelimit.dispatch.wait_work", 30e-9]


def test_the_owner_is_the_launching_loop():
    """A second, idle loop parks and takes far more often than the owner
    launches; the owner's line is still the one that launches."""
    idle_loop = ("/host:CPU", 11)
    s = _summary([(t, t + 1, "ratelimit.dispatch.wait_work", idle_loop) for t in range(0, 200, 2)]
                 + [(90, 100, "ratelimit.dispatch.launch", OWNER)])
    assert gaps.owner_line(s.program_spans) == OWNER
    assert s.breakdown()["idle_gaps"][0] == ["ratelimit.dispatch.launch", 50e-9]


def test_a_collection_on_any_thread_names_the_gap():
    """A garbage collection holds the interpreter: over most of a gap,
    the owner's own span (here parked for work) is not what kept it from
    launching; a short one inside a long gap does not name it."""
    s = _summary([
        (0, 200, "ratelimit.dispatch.wait_work", OWNER),
        (75, 110, "ratelimit.service.host.matcher", REQUEST),
        (80, 110, "ratelimit.gc.gen2", REQUEST),  # 30 of the 50-ns gap
        (40, 50, "ratelimit.gc.gen0", REQUEST),   # 10 of the 30-ns gap
    ])
    idle = s.breakdown()["idle_gaps"]
    assert idle[0] == ["ratelimit.gc.gen2", 50e-9]
    assert idle[1] == ["ratelimit.dispatch.wait_work", 30e-9]


def test_lock_wait_is_named_with_its_holder():
    s = _summary([
        (40, 50, "ratelimit.dispatch.take", OWNER),        # owner line: most loop spans
        (70, 119, "ratelimit.dispatch.launch", OWNER),
        (72, 118, "ratelimit.slab.lock_wait", OWNER),
        (0, 200, "ratelimit.stats.flush", FLUSH),
        (1, 199, "ratelimit.stats.generate.slab_health", FLUSH),
        (2, 150, "ratelimit.slab.health_drain", FLUSH),
        (0, 200, "ratelimit.service.transport.grpc", REQUEST),  # not a holder
    ])
    name, length = s.breakdown()["idle_gaps"][0]
    assert name == "ratelimit.slab.lock_wait < ratelimit.slab.health_drain"
    assert length == 50e-9


def test_no_program_span_falls_back_to_bench_span():
    s = _summary([(40, 50, "ratelimit.dispatch.take", OWNER)],
                 bench_spans=[(90, 100, "bench.submit_rows")])
    idle = s.breakdown()["idle_gaps"]
    assert idle[0] == ["bench.submit_rows", 50e-9]
    assert idle[1] == ["ratelimit.dispatch.take", 30e-9]


def test_no_span_at_all_is_unattributed():
    s = _summary([], bench_spans=[(90, 100, "bench.submit_rows")])
    idle = s.breakdown()["idle_gaps"]
    assert [n for n, _ in idle] == ["bench.submit_rows", "unattributed", "unattributed"]


def test_names_change_and_numbers_do_not():
    """The named breakdown has trace.py's gaps, lengths, order and device
    operations; only names differ."""
    spans = [(0, 200, "ratelimit.dispatch.wait_work", OWNER)]
    named = _summary(spans)
    plain = trace.TraceSummary(window_s=named.window_s, devices=named.devices,
                               host_spans=named.host_spans)
    a, b = named.breakdown(), plain.breakdown()
    assert a["device_ops"] == b["device_ops"]
    assert [x for _, x in a["idle_gaps"]] == [x for _, x in b["idle_gaps"]]
    assert [n for n, _ in b["idle_gaps"]] == ["unattributed"] * 3
    assert named.busy_s == plain.busy_s and named.idle_share() == plain.idle_share()
    assert named.span_totals() == {"ratelimit.dispatch.wait_work": [1, 200e-9]}
    assert [at for _n, _l, at in named.gaps_at()] == [70e-9, 30e-9, 10e-9]


# -- the recorded v5e trace: the reduction's numbers, pinned --

TINY_DEVICE_OPS = [
    ("%copy.93", 7.4317e-05),
    ("%copy.102", 6.9136e-05),
    ("%copy.91", 1.8441e-05),
    ("%fusion.10", 1.7959e-05),
    ("%reduce-window.1", 1.628e-05),
    ("%fusion", 1.0824e-05),
    ("%sort.11", 7.639e-06),
    ("%concatenate.13", 5.155e-06),
    ("%pallas_slab_apply.1", 4.705e-06),
    ("%fusion.11", 4.55e-06),
]


@pytest.mark.parametrize("reduce", ["plain", "named"])
def test_recorded_trace_numbers_are_pinned(reduce):
    """benchmark/testdata/tiny.xplane.pb (three 128-wide slab steps on a
    v5e) reduces to the same numbers with or without program spans; it
    holds none, so the named gaps keep trace.py's names."""
    base = trace.reduce_xplane(TINY, window_s=1.0)
    summ = base if reduce == "plain" else gaps.NamedTraceSummary.of(
        base, gaps.program_spans(TINY))
    assert summ.busy_s == pytest.approx(0.000301488, rel=1e-12)
    runs, secs = summ.module_time("jit_slab_step")
    assert runs == 3 and secs == pytest.approx(0.000310201, rel=1e-12)
    bd = summ.breakdown()
    got = [(n.split(" = ", 1)[0], v) for n, v in bd["device_ops"]]
    assert [n for n, _ in got] == [n for n, _ in TINY_DEVICE_OPS]
    assert [v for _, v in got] == pytest.approx([v for _, v in TINY_DEVICE_OPS], rel=1e-9)
    assert bd["idle_gaps"] == base.breakdown()["idle_gaps"]
    assert bd["idle_gaps"][0] == ["bench.submit_rows", pytest.approx(0.002293755, rel=1e-9)]


# -- the readers of the program's new timings --

NEW_READERS = ["health_drain_ms.tail", "health_drain_ms.served", "lock_wait_share.tail",
               "lock_wait_share.served", "submit_wait_ms.tail", "submit_wait_ms.served"]
HIST = {
    "health_drain_ms": "ratelimit.slab.health_drain_ms",
    "lock_wait_share": "ratelimit.slab.lock_wait_ms",
    "submit_wait_ms": "ratelimit.dispatch.submit_wait_ms",
}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader(name):
    read = spec.metric_reader(name)
    # a program without the histogram (the parent commit): nothing, no raise
    assert read({"hist": {}, "window_s": 30.0}) is None
    hist = {HIST[name.split(".")[0]]: (6, 3900.0)}
    want = 100.0 * 3900.0 / 30e3 if name.startswith("lock_wait_share") else 650.0
    assert read({"hist": hist, "window_s": 30.0}) == pytest.approx(want)


def test_new_metrics_are_listed_for_the_edge_cells_only():
    bench = spec.benchmark(CHECKOUT)
    for cell, suffix in (("edge_steady", ".tail"), ("edge_saturate", ".served")):
        names = {m["name"] for m in spec.per_layer_for(bench, cell)}
        assert {n for n in NEW_READERS if n.endswith(suffix)} <= names
    owner = {m["name"] for m in spec.per_layer_for(bench, "owner_zipf")}
    assert owner.isdisjoint(NEW_READERS)


def test_program_spans_in_a_traced_owner_run(tmp_path):
    """A whole traced owner run at a tiny size on the CPU: the capture the
    benchmark starts holds the owner loop's spans on one line, and the
    gaps would be named by them. (The CPU trace has no TPU plane, so the
    reduction itself refuses, as in test_traced_run_reports_layers.)"""
    import test_benchmark_harness as tbh

    with pytest.raises(ValueError, match="no device plane"):
        tbh._run("owner_zipf", tmp_path, trace=True)
    spans = gaps.program_spans(trace.find_xplane(str(tmp_path / "trace")))
    owner = gaps.owner_line(spans)
    assert owner is not None
    on_owner = {n for _s, _e, n, line in spans if line == owner}
    assert {"ratelimit.dispatch.take", "ratelimit.dispatch.launch",
            "ratelimit.dispatch.redeem", "ratelimit.slab.lock_wait"} <= on_owner
    assert any(n == "ratelimit.dispatch.submit_wait" and line != owner
               for _s, _e, n, line in spans)
    start, end = max(((s, e) for s, e, n, _line in spans
                      if n == "ratelimit.device.readback"), key=lambda se: se[1] - se[0])
    assert gaps.name_gap(start, end, spans, owner, []) == "ratelimit.device.readback"


def test_named_gaps_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "benchmark/named_gaps.py", "--workload", "edge_steady",
                          "--seed", "3", "--seconds", "1", "--trace", "1"],
                         cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "no TPU visible" in out.stderr
    assert out.stdout.strip() == ""
