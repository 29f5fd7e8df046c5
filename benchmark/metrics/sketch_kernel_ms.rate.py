"""sketch_kernel_ms.rate: device time per launch of the slab step programs
(modules named jit_slab_step*) spent in the hot-key sketch's kernels, from
the trace.

The sketch's kernels are the ops whose name begins with sketch (the
one-set match kernel, sketch_match) or is pallas_sketch_scan (the scan of
each candidate's gathered set). An op's trace name is its HLO text,
'%<name> = <output type> <opcode>(...)'. XLA's own ops around a kernel are
not counted: beside pallas_sketch_scan, the phase-A scatter-add into the
u32[128] count plane (a fusion of its own, fusion.10 in the v5e owner
step) and the broadcasts that feed the scan fall outside this metric, so
between a program with the scan and one with the match kernel,
step_ms.rate carries the whole saving; the name says that scope, the
sketch's kernels and not the whole mechanism. None where the trace has no step
module or no sketch kernel (HOTKEYS_ENABLED=false)."""

import re

STEP_MODULE_PREFIX = "jit_slab_step"
_NAME = re.compile(r"^%([\w.-]+) = ")


def is_sketch_kernel(op_name: str) -> bool:
    m = _NAME.match(op_name)
    if not m:
        return False
    name = m.group(1).split(".")[0]
    return name.startswith("sketch") or name == "pallas_sketch_scan"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs, _ = tr.module_time(STEP_MODULE_PREFIX)
    ns = sum(t for d in tr.devices for name, (_c, t) in d.ops.items()
             if is_sketch_kernel(name))
    return 1e3 * ns / 1e9 / runs if runs and ns else None
