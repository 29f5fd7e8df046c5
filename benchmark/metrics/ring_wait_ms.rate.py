"""ring_wait_ms.rate: the mean wait of a frame in the submit ring before its launch (dispatch.ring_wait_ms), over the window."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.dispatch.ring_wait_ms")
