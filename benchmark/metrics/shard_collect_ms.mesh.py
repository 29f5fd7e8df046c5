"""shard_collect_ms.mesh: the mean time per mesh launch of the per-shard
readback and the unscatter into arrival order, in ms, the wait for the
devices included (shard.collect_ms, span ratelimit.shard.collect); None
where the program records no such histogram."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.shard.collect_ms")
