"""What the harness hands an entry, and what the entry hands back."""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass


@dataclass
class RunContext:
    cell: str
    wl: dict  # the workload file
    cfg: dict  # the configuration file
    seed: int
    seconds: float
    trace: bool
    control: str | None  # run a control in the program's place (not in checks)
    t_process: float  # perf_counter at process start
    devices: list
    compiles: object  # device.CompileCounter
    trace_dir: str
    info: object  # callable(tag, **fields): an informational stderr line

    def mark(self, what: str, **fields) -> None:
        """A set-up milestone: seconds since the process started."""
        self.info("setup_mark", what=what, at_s=time.perf_counter() - self.t_process, **fields)

    def span(self, name: str):
        """A host span in the trace (benchmark's own calls into the
        program); nothing when not tracing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclass
class Result:
    end_to_end: dict  # metric name -> value (host clock)
    layer_ctx: dict  # what the per-layer readers read
    checks: dict  # name -> (value, limit): correct iff value <= limit
    attempted: int
    failed: int
    memory_peak_bytes: int | None
    trace: object = None  # trace.TraceSummary
