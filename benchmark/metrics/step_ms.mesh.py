"""step_ms.mesh: device time per run of the routed per-shard slab step
(modules named jit_slab_step_routed: one run per shard and launch), from
the trace; None where the trace has no such module."""

STEP_MODULE_PREFIX = "jit_slab_step_routed"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs, secs = tr.module_time(STEP_MODULE_PREFIX)
    return 1e3 * secs / runs if runs else None
