"""The reader of sketch_kernel_ms.rate on synthetic trace summaries that hold
each form of the slab step's hot-key sketch: the scan of each candidate's
gathered set (pallas_sketch_scan, beside the phase-A scatter-add fusion)
and the one-set match kernel named sketch_match. CPU only; no device
metric here."""

from __future__ import annotations

import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec, trace  # noqa: E402

RUNS = 1000
# trace names as the profiler gives them: the HLO text of each op
SCAN = ("%pallas_sketch_scan.1 = s32[65536,128]{1,0:T(8,128)S(1)} custom-call(s32[65536,128]"
        "{1,0:T(8,128)S(1)} %broadcast_in_dim.34, s32[65536,128]{1,0:T(8,128)S(1)} "
        "%broadcast_in_dim.38), custom_call_target=\"tpu_custom_call\"")
SCATTER_ADD = ("%fusion.10 = u32[128]{0:T(128)S(1)} fusion(u32[128]{0:T(128)S(1)} %slice.7, "
               "s32[65536]{0:T(1024)S(1)} %select.12), kind=kLoop, calls=%fused_computation.10")
MATCH = ("%sketch_match.1 = (s32[512,128]{1,0:T(8,128)S(1)}, s32[1,128]{1,0:T(1,128)S(1)}) "
         "custom-call(s32[384]{0:T(512)S(6)} %convert_bitcast_fusion.1, s32[512,128]"
         "{1,0:T(8,128)S(1)} %bitcast_convert_fusion.1), custom_call_target=\"tpu_custom_call\"")
WAY_SCAN = ("%pallas_way_scan.1 = s32[65536,128]{1,0:T(8,128)S(1)} custom-call(s32[1]{0:T(128)} "
            "%bitcast.2), custom_call_target=\"tpu_custom_call\"")
SORT_GATHER = ("%fusion.2 = u32[65536]{0:T(1024)S(1)} fusion(u32[65536]{0:T(1024)S(1)} %p.1, "
               "s32[65536]{0:T(1024)S(1)} %sketch_order.3), kind=kLoop, calls=%fused_computation.2")


def _summary(ops: dict, modules=None) -> trace.TraceSummary:
    """One chip; ops maps a trace name to its total ms over RUNS steps."""
    dev = trace.DeviceTrace("/device:TPU:0")
    dev.ops = {name: [RUNS, ms * 1e6] for name, ms in ops.items()}
    dev.modules = {"jit_slab_step_after": [RUNS, 13.0 * RUNS * 1e6]} if modules is None else modules
    return trace.TraceSummary(window_s=30.0, devices=[dev], host_spans=[])


@pytest.fixture(scope="module")
def read():
    return spec.metric_reader("sketch_kernel_ms.rate")


def test_the_scan_form(read):
    """The set scan alone: not its scatter-add fusion, nor the slab's own
    way scan, nor an op that merely takes a sketch-named operand."""
    ops = {SCAN: 1.05 * RUNS, SCATTER_ADD: 0.57 * RUNS, WAY_SCAN: 0.71 * RUNS,
           SORT_GATHER: 0.47 * RUNS}
    assert read({"trace": _summary(ops)}) == pytest.approx(1.05)


def test_the_match_kernel_form(read):
    ops = {MATCH: 0.04 * RUNS, WAY_SCAN: 0.71 * RUNS, SORT_GATHER: 0.47 * RUNS}
    assert read({"trace": _summary(ops)}) == pytest.approx(0.04)


def test_summed_over_chips(read):
    devs = []
    for i in range(2):
        d = trace.DeviceTrace(f"/device:TPU:{i}")
        d.ops = {MATCH: [RUNS, 0.05 * RUNS * 1e6]}
        d.modules = {"jit_slab_step_after": [RUNS, 13.0 * RUNS * 1e6]}
        devs.append(d)
    tr = trace.TraceSummary(window_s=30.0, devices=devs, host_spans=[])
    assert read({"trace": tr}) == pytest.approx(0.05)


def test_nothing_to_read(read):
    assert read({}) is None
    assert read({"trace": _summary({WAY_SCAN: 1.0, SCATTER_ADD: 1.0})}) is None
    assert read({"trace": _summary({MATCH: 1.0}, modules={})}) is None


def test_listed_for_the_owner_cell_only():
    bench = spec.benchmark(CHECKOUT)
    (m,) = [m for m in bench["per_layer"] if m["name"] == "sketch_kernel_ms.rate"]
    assert m["workloads"] == ["owner_zipf"] and m["moves"] == "decisions_per_s"
    assert m["layer"] == "kernels" and m["source"] == "device_trace"
