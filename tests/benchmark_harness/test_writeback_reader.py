"""The reader of writeback_ms.rate on synthetic trace summaries that hold
each form of the slab step's table write-back: the row scatter's fusion
(whose output is the whole table) and the set-tile kernel named
slab_writeback. CPU only; no device metric here."""

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
SCATTER = ("%fusion.10 = u32[16777216,8]{0,1:T(8,128)} fusion(u32[16777216,8]{0,1:T(8,128)} "
           "%state_table.1, s32[65536]{0:T(1024)S(1)} %copy-done.26, "
           "u32[65536,8]{0,1:T(8,128)S(1)} %concatenate.14), kind=kCustom, "
           "calls=%fused_computation.10")
KERNEL = ("%slab_writeback.1 = u32[131072,8,128]{2,1,0:T(8,128)} custom-call(s32[1]{0:T(128)} "
          "%bitcast.12, s32[65536]{0:T(1024)S(1)} %broadcast_select_fusion.1, "
          "u32[512,8,128]{2,1,0:T(8,128)S(1)} %bitcast.115, u32[131072,8,128]{2,1,0:T(8,128)} "
          "%bitcast.30), custom_call_target=\"tpu_custom_call\"")
GATHER = ("%fusion = u32[65536,128,8]{1,2,0:T(8,128)} fusion(u32[131072,128,8]{1,2,0:T(8,128)} "
          "%bitcast.3, s32[65536]{0:T(1024)S(1)} %broadcast_clamp_fusion.1), kind=kCustom, "
          "calls=%fused_computation")
ROWS = ("%fusion.9 = u32[65536,8]{0,1:T(8,128)S(1)} fusion(u32[65536,128,8]{1,2,0:T(8,128)} "
        "%fusion, s32[65536]{0:T(1024)S(1)} %fusion.24), kind=kCustom, calls=%fused_computation.9")


def _summary(ops: dict, modules=None) -> trace.TraceSummary:
    """One chip; ops maps a trace name to its total ms over RUNS steps."""
    dev = trace.DeviceTrace("/device:TPU:0")
    dev.ops = {name: [RUNS, ms * 1e6] for name, ms in ops.items()}
    dev.modules = {"jit_slab_step_after": [RUNS, 17.0 * RUNS * 1e6]} if modules is None else modules
    return trace.TraceSummary(window_s=30.0, devices=[dev], host_spans=[])


@pytest.fixture(scope="module")
def read():
    return spec.metric_reader("writeback_ms.rate")


def test_the_scatter_form(read):
    """The parent's program: the fusion whose output is the whole table,
    and not the gather or the per-lane rows beside it."""
    ops = {SCATTER: 4.95 * RUNS, GATHER: 1.2 * RUNS, ROWS: 0.75 * RUNS}
    assert read({"trace": _summary(ops)}) == pytest.approx(4.95)


def test_the_kernel_form(read):
    ops = {KERNEL: 1.4 * RUNS, GATHER: 1.2 * RUNS, ROWS: 0.75 * RUNS}
    assert read({"trace": _summary(ops)}) == pytest.approx(1.4)


def test_nothing_to_read(read):
    assert read({}) is None
    assert read({"trace": _summary({GATHER: 1.0, ROWS: 1.0})}) is None
    assert read({"trace": _summary({KERNEL: 1.0}, modules={})}) is None


def test_listed_for_the_owner_cell_only():
    bench = spec.benchmark(CHECKOUT)
    (m,) = [m for m in bench["per_layer"] if m["name"] == "writeback_ms.rate"]
    assert m["workloads"] == ["owner_zipf"] and m["moves"] == "decisions_per_s"
    assert m["layer"] == "kernels" and m["source"] == "device_trace"
