"""The readers of the mesh owner's per-layer metrics (cell
owner_zipf_mesh4) on synthetic run contexts: the program's histogram
deltas and trace summaries with four chips. Each reads None where its
input is missing, as on a program that records no ratelimit.shard.*
histogram or launches no jit_slab_step_routed module. CPU only; no
device metric here."""

from __future__ import annotations

import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmark")
for p in (CHECKOUT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import bytemodel, device, spec, trace  # noqa: E402

CELL = "owner_zipf_mesh4"
MESH = ["decisions_per_launch.mesh", "shard_topk_ms.mesh", "shard_route_ms.mesh",
        "shard_collect_ms.mesh", "shard_pad_share.mesh", "step_ms.mesh",
        "step_roofline.mesh", "device_idle_share.mesh"]
LAUNCHES = 100
PEAK = device.PEAKS["TPU v5 lite"]


def _hist(**over) -> dict:
    """A window's histogram deltas, {full name: (count, sum)}: 100 launches
    of 65,536 rows, each shard padded to its own rung."""
    h = {
        "ratelimit.dispatch.batch_size": (LAUNCHES, 65536.0 * LAUNCHES),
        "ratelimit.shard.topk_ms": (LAUNCHES, 165.0 * LAUNCHES),
        "ratelimit.shard.route_ms": (LAUNCHES, 2.5 * LAUNCHES),
        "ratelimit.shard.collect_ms": (LAUNCHES, 4.0 * LAUNCHES),
        "ratelimit.shard.routed_rows": (LAUNCHES, 65536.0 * LAUNCHES),
        "ratelimit.shard.padded_lanes": (LAUNCHES, 98304.0 * LAUNCHES),
    }
    h.update(over)
    return {k: v for k, v in h.items() if v is not None}


def _summary(step_ms: float = 3.0, module: str = "jit_slab_step_routed",
             busy_s=(3.0, 3.3, 2.7, 3.0)) -> trace.TraceSummary:
    """Four chips; each ran the routed step once per launch."""
    devs = []
    for i, busy in enumerate(busy_s):
        d = trace.DeviceTrace(f"/device:TPU:{i}", busy_ns=busy * 1e9)
        d.modules = {module: [LAUNCHES, step_ms * 1e6 * LAUNCHES]}
        devs.append(d)
    return trace.TraceSummary(window_s=30.0, devices=devs, host_spans=[])


@pytest.fixture(scope="module")
def readers():
    return {name: spec.metric_reader(name) for name in MESH}


def test_histogram_means(readers):
    ctx = {"hist": _hist()}
    assert readers["decisions_per_launch.mesh"](ctx) == pytest.approx(65536.0)
    assert readers["shard_topk_ms.mesh"](ctx) == pytest.approx(165.0)
    assert readers["shard_route_ms.mesh"](ctx) == pytest.approx(2.5)
    assert readers["shard_collect_ms.mesh"](ctx) == pytest.approx(4.0)


def test_pad_share(readers):
    """100 x (lanes - rows) / lanes: 98,304 lanes for 65,536 rows is a third
    padding."""
    assert readers["shard_pad_share.mesh"]({"hist": _hist()}) == pytest.approx(100.0 / 3)
    no_pad = _hist(**{"ratelimit.shard.padded_lanes": (LAUNCHES, 65536.0 * LAUNCHES)})
    assert readers["shard_pad_share.mesh"]({"hist": no_pad}) == pytest.approx(0.0)


def test_step_and_roofline_read_the_routed_modules(readers):
    ctx = {"hist": _hist(), "trace": _summary(), "peaks": PEAK}
    assert readers["step_ms.mesh"](ctx) == pytest.approx(3.0)
    # the modules' time summed over the four chips: 4 x 100 x 3 ms
    want = bytemodel.roofline_percent(65536 * LAUNCHES, 4 * LAUNCHES * 3e-3, PEAK)
    assert readers["step_roofline.mesh"](ctx) == pytest.approx(want)
    assert 0 < want < 100


def test_idle_share_is_the_mean_over_the_chips(readers):
    ctx = {"hist": _hist(), "trace": _summary()}
    assert readers["device_idle_share.mesh"](ctx) == pytest.approx(90.0)


def test_nothing_to_read(readers):
    """A program without the mesh's histograms, an untraced run, and a
    trace of a program whose routed module has another name."""
    bare = {"hist": {"ratelimit.dispatch.batch_size": (LAUNCHES, 65536.0 * LAUNCHES)}}
    for name in ("shard_topk_ms.mesh", "shard_route_ms.mesh", "shard_collect_ms.mesh",
                 "shard_pad_share.mesh"):
        assert readers[name](bare) is None, name
    assert readers["decisions_per_launch.mesh"]({"hist": {}}) is None
    for name in ("step_ms.mesh", "step_roofline.mesh", "device_idle_share.mesh"):
        assert readers[name]({"hist": _hist()}) is None, name
    unnamed = {"hist": _hist(), "trace": _summary(module="jit__unknown"), "peaks": PEAK}
    assert readers["step_ms.mesh"](unnamed) is None
    assert readers["step_roofline.mesh"](unnamed) is None
    no_peak = {"hist": _hist(), "trace": _summary(), "peaks": None}
    assert readers["step_roofline.mesh"](no_peak) is None


def test_mesh_metrics_go_to_the_mesh_cell_only():
    bench = spec.benchmark(CHECKOUT)
    got = [m["name"] for m in spec.per_layer_for(bench, CELL)]
    assert got == MESH
    for m in bench["per_layer"]:
        if m["name"] in MESH:
            assert m["workloads"] == [CELL] and m["moves"] == "decisions_per_s"
    for cell in ("edge_steady", "owner_zipf", "edge_saturate"):
        names = [m["name"] for m in spec.per_layer_for(bench, cell)]
        assert names and not any(n.endswith(".mesh") for n in names), cell
    owner = [m["name"] for m in spec.per_layer_for(bench, "owner_zipf")]
    assert owner == ["decisions_per_launch.rate", "ring_wait_ms.rate", "redeem_ms.rate",
                     "step_ms.rate", "step_roofline.rate", "device_idle_share.rate",
                     "writeback_ms.rate", "sketch_kernel_ms.rate"]


def test_the_cell_and_its_configuration():
    bench = spec.benchmark(CHECKOUT)
    cell = spec.cell_entry(bench, CELL)
    assert cell["chips"] == 4 and cell["config"] == "incrby_mesh4"
    assert [m["name"] for m in spec.end_to_end_for(bench, CELL)] == [
        "decisions_per_s", "setup_s"]
    cfg = spec.config("incrby_mesh4")
    env = cfg["env"]
    assert env["TPU_MESH_DEVICES"] == "4" and env["HOT_TIER_ENABLED"] == "false"
    assert env["SHARD_ROUTED_BATCHING"] == "true"
    assert int(env["TPU_SLAB_SLOTS"]) == 1 << 26 and cfg["records"] == 40_000_000
    assert spec.workload(CELL)["entry"] == "owner_rows"
