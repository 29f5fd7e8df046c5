"""step_roofline.mesh: the routed slab step's share of its roofline on the
mesh, in %. The least time is the byte model's 92 B per launched decision
over one chip's HBM peak (harness/bytemodel.py; bytes bound it), the time
is the device time of the jit_slab_step_routed modules summed over the
chips, so the share is of four chips' time that the bytes need. Decisions
launched are the window's dispatch.batch_size sum (padding lanes do not
count). None where either is missing."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import bytemodel  # noqa: E402

STEP_MODULE_PREFIX = "jit_slab_step_routed"


def read(ctx):
    tr, peak = ctx.get("trace"), ctx.get("peaks")
    _launches, decisions = ctx["hist"].get("ratelimit.dispatch.batch_size", (0, 0.0))
    if tr is None or peak is None or not decisions:
        return None
    runs, secs = tr.module_time(STEP_MODULE_PREFIX)
    if not runs:
        return None
    return bytemodel.roofline_percent(int(decisions), secs, peak)
