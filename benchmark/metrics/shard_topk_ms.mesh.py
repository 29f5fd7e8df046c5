"""shard_topk_ms.mesh: the mean time of the host top-K fold (the mesh's
hot-key telemetry, ops/sketch.py HostTopK.update) per launch, in ms
(shard.topk_ms, span ratelimit.shard.topk); None where the program
records no such histogram."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.shard.topk_ms")
