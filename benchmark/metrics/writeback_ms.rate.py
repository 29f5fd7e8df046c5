"""writeback_ms.rate: device time per launch of the slab step programs
(modules named jit_slab_step*) spent writing the launch's rows back into
the table, from the trace.

The write-back is the op named slab_writeback (the set-tile kernel), or,
in a program without it, each op whose output is the whole table: the
row scatter's fusion. An op's trace name is its HLO text,
'%<name> = <output type> <opcode>(<operand type> %<operand>, ...)', so
the table's shape is read off the op that takes the step's state table
as an operand ('... u32[16777216,8]{0,1:T(8,128)} %state_table.1 ...').
None where the trace has no step module or no write-back op."""

import re

STEP_MODULE_PREFIX = "jit_slab_step"
KERNEL = "slab_writeback"
_TABLE_OPERAND = re.compile(r"(\w+\[[\d,]+\])(?:\{[^}]*\})? %state_table\b")
_OUTPUT = re.compile(r"^%([\w.-]+) = (\w+\[[\d,]+\])")


def writeback_seconds(tr):
    """Device seconds of the write-back ops, summed over the chips."""
    ops: dict = {}
    for d in tr.devices:
        for name, (_c, ns) in d.ops.items():
            ops[name] = ops.get(name, 0.0) + ns
    table = next((m.group(1) for name in ops
                  for m in [_TABLE_OPERAND.search(name)] if m), None)
    ns = 0.0
    for name, t in ops.items():
        m = _OUTPUT.match(name)
        if m and (m.group(1).split(".")[0] == KERNEL or m.group(2) == table):
            ns += t
    return ns / 1e9


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    runs, _ = tr.module_time(STEP_MODULE_PREFIX)
    secs = writeback_seconds(tr)
    return 1e3 * secs / runs if runs and secs else None
