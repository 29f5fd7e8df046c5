"""Reduction of a profiler trace (.xplane.pb) to device numbers.

Read with jax.profiler.ProfileData alone. On a TPU each chip is a plane
named "/device:TPU:<n>"; its "XLA Ops" line holds one event per device
operation and its "XLA Modules" line one event per program run (a jitted
function's module, e.g. "jit_slab_step_after(...)"). Event times are in
nanoseconds on one clock shared with the host planes, so the gaps between
device work can be named by the host spans around them.

busy: the union of the intervals in which an operation ran on a chip.
idle share: 1 - busy / traced window, per chip, then the mean."""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


def module_name(event_name: str) -> str:
    """'jit_slab_step_after(123)' -> 'jit_slab_step_after'."""
    return event_name.split("(", 1)[0]


def union_length(intervals) -> tuple[float, list]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


@dataclass
class DeviceTrace:
    name: str
    busy_ns: float = 0.0
    merged: list = field(default_factory=list)
    ops: dict = field(default_factory=dict)  # op name -> [count, ns]
    modules: dict = field(default_factory=dict)  # module -> [count, ns]


@dataclass
class TraceSummary:
    window_s: float
    devices: list
    host_spans: list  # (start_ns, end_ns, name)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, prefix: str) -> tuple[int, float]:
        """(runs, device seconds) of the modules whose name starts with
        `prefix`, summed over the chips."""
        runs, ns = 0, 0.0
        for d in self.devices:
            for name, (c, t) in d.modules.items():
                if name.startswith(prefix):
                    runs += c
                    ns += t
        return runs, ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds, averaged over
        the chips), and the longest idle gaps, each named by the host span
        of the benchmark around it ("unattributed" where none is)."""
        ops: dict = {}
        for d in self.devices:
            for name, (_c, t) in d.ops.items():
                ops[name] = ops.get(name, 0.0) + t / 1e9 / len(self.devices)
        device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.devices:
            m = d.merged
            for (_s0, e0), (s1, _e1) in zip(m, m[1:]):
                gaps.append((s1 - e0, e0, s1))
        gaps.sort(reverse=True)
        idle = []
        for length, s, e in gaps[:top]:
            mid = (s + e) / 2
            names = [n for hs, he, n in self.host_spans if hs <= mid <= he]
            idle.append([names[-1] if names else "unattributed", length / 1e9])
        return {"device_ops": [[n, v] for n, v in device_ops], "idle_gaps": idle}


def start(trace_dir: str) -> None:
    """Start the profiler with its Python tracer off (it would time every
    Python call of every thread, the server's included, and slow the host
    side it measures); host TraceMe spans (TraceAnnotation) stay on."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: str, window_s: float) -> TraceSummary:
    """Reduce one trace file; window_s is the traced window by the host
    clock (start_trace to stop_trace)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceTrace(plane.name)
            intervals = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        intervals.append((ev.start_ns, ev.end_ns))
                        a = dev.ops.setdefault(ev.name, [0, 0.0])
                        a[0] += 1
                        a[1] += ev.duration_ns
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        a = dev.modules.setdefault(module_name(ev.name), [0, 0.0])
                        a[0] += 1
                        a[1] += ev.duration_ns
            dev.busy_ns, dev.merged = union_length(intervals)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    if not devices:
        raise ValueError(f"{path}: no device plane (want {DEVICE_PLANE.pattern})")
    return TraceSummary(window_s=window_s, devices=devices, host_spans=spans)
