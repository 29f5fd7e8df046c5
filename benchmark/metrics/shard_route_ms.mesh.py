"""shard_route_ms.mesh: the mean host routing time per mesh launch, in ms:
the owner hash, bincount and argsort and the per-shard block fill
(shard.route_ms, span ratelimit.shard.route); None where the program
records no such histogram."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.shard.route_ms")
