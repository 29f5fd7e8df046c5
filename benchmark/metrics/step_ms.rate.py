"""step_ms.rate: device time per launch of the slab step programs
(modules named jit_slab_step*), from the trace; None where the trace has
no such module."""

STEP_MODULE_PREFIX = "jit_slab_step"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs, secs = tr.module_time(STEP_MODULE_PREFIX)
    return 1e3 * secs / runs if runs else None
