"""health_drain_ms.tail: the mean time of one drain of the engine's parked slab health vectors (slab.health_drain_ms: one blocking device read per parked launch, under the state lock), over the window; None where the program records no such histogram."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.slab.health_drain_ms")
