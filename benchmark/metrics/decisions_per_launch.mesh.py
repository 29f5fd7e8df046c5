"""decisions_per_launch.mesh: the mesh owner's mean launch width: decisions
per launch (dispatch.batch_size, a count), over the window; each launch
then splits into one routed program per shard."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.dispatch.batch_size")
