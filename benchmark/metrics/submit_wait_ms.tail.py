"""submit_wait_ms.tail: the mean wait of a request thread from its publish to the submit ring to its verdict (dispatch.submit_wait_ms), over the window; None where the program records no such histogram."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_hist", os.path.join(os.path.dirname(__file__), "_hist.py"))
_hist = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_hist)


def read(ctx):
    return _hist.mean(ctx, "ratelimit.dispatch.submit_wait_ms")
