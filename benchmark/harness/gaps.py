"""Idle gaps named by the program's own spans.

The program marks its host work with profiler spans named `ratelimit.*`
(api_ratelimit_tpu/tracing/host.py), on the same clock as the device
trace. This module reads them back, one host line (thread) each, and
names a device idle gap by what the dispatch owner — the only thread that
launches — was doing at the gap's midpoint:

- a garbage collection (ratelimit.gc.gen<N>) on any line that covers the
  midpoint and at least half the gap: it holds the interpreter, so the
  owner cannot run whatever its own span says (a short collection inside
  a long gap is not what made the gap);
- else the innermost program span covering the midpoint on the owner's line
  (the line whose ratelimit.dispatch.* loop spans hold the launches);
- where that span is ratelimit.slab.lock_wait, the name goes on with
  " < " and the innermost ratelimit.slab.* / ratelimit.stats.* span
  covering the midpoint on another line: the lock's holder
  ("ratelimit.slab.lock_wait < ratelimit.slab.health_drain");
- where no program span covers it, the reduction's own rule stands: the
  benchmark's `bench.` span around it, else "unattributed".

Only names change: the gaps, their lengths and their order are
trace.TraceSummary.breakdown's, and no number the reduction computes
(busy_s, idle_share, module_time, device_ops) is touched."""

from __future__ import annotations

from dataclasses import dataclass, field

from . import trace

PROGRAM_PREFIX = "ratelimit."
# the owner loop's spans (backends/dispatch.py DispatchLoop._run); a
# request thread's ratelimit.dispatch.submit_wait is not one of them
OWNER_SPANS = frozenset(
    "ratelimit.dispatch." + n
    for n in ("wait_work", "linger", "take", "launch", "await_ready", "redeem"))
OWNER_LAUNCH = "ratelimit.dispatch.launch"
LOCK_WAIT = "ratelimit.slab.lock_wait"
GC_PREFIX = "ratelimit.gc."
HOLDER_PREFIXES = ("ratelimit.slab.", "ratelimit.stats.")


def program_spans(path: str) -> list:
    """[(start_ns, end_ns, name, line)] of every ratelimit.* host span in
    a trace file; `line` is (plane name, line index): one per thread."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    spans.append((ev.start_ns, ev.end_ns, ev.name, (plane.name, i)))
    return spans


def owner_line(spans: list):
    """The owner loop's line: the one with the most launches, then the
    most loop spans (an idle loop parks and takes but never launches);
    None without any loop span."""
    counts: dict = {}
    for _s, _e, name, line in spans:
        if name in OWNER_SPANS:
            c = counts.setdefault(line, [0, 0])
            c[0] += name == OWNER_LAUNCH
            c[1] += 1
    return max(counts, key=lambda line: counts[line]) if counts else None


def innermost(spans: list, t: float, keep):
    """The innermost span covering t among those `keep` accepts: the one
    that started last (spans of one thread nest), the shortest on a tie."""
    best = None
    for s, e, name, line in spans:
        if s <= t <= e and keep(name, line):
            if best is None or (s, best[1]) > (best[0], e):
                best = (s, e, name, line)
    return best


def name_gap(start: float, end: float, spans: list, owner, bench_spans: list) -> str:
    """The name of the idle gap [start, end] (see the module doc)."""
    t = (start + end) / 2
    gc = innermost(spans, t, lambda n, _line: n.startswith(GC_PREFIX))
    if gc is not None and min(gc[1], end) - max(gc[0], start) >= (end - start) / 2:
        return gc[2]
    if owner is not None:
        span = innermost(spans, t, lambda _n, line: line == owner)
        if span is not None:
            name = span[2]
            if name == LOCK_WAIT:
                holder = innermost(
                    spans, t,
                    lambda n, line: line != owner and n.startswith(HOLDER_PREFIXES))
                if holder is not None:
                    name = f"{name} < {holder[2]}"
            return name
    names = [n for hs, he, n in bench_spans if hs <= t <= he]
    return names[-1] if names else "unattributed"


def longest_gaps(summary, top: int) -> list:
    """(length_ns, start_ns, end_ns) of the longest idle gaps between a
    chip's merged busy intervals, as TraceSummary.breakdown takes them."""
    gaps = []
    for d in summary.devices:
        m = d.merged
        for (_s0, e0), (s1, _e1) in zip(m, m[1:]):
            gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    return gaps[:top]


@dataclass
class NamedTraceSummary(trace.TraceSummary):
    """A TraceSummary whose breakdown names idle gaps by program spans."""

    program_spans: list = field(default_factory=list)

    @classmethod
    def of(cls, base, spans: list) -> "NamedTraceSummary":
        """`base` (a trace.reduce_xplane result) with `spans` kept."""
        return cls(window_s=base.window_s, devices=base.devices,
                   host_spans=base.host_spans, program_spans=spans)

    def breakdown(self, top: int = 10) -> dict:
        out = super().breakdown(top)
        out["idle_gaps"] = [[name, length] for name, length, _at in self.gaps_at(top)]
        return out

    def gaps_at(self, top: int = 10) -> list:
        """[name, length s, start s after the first device operation] of
        the longest idle gaps, as breakdown() names them."""
        owner = owner_line(self.program_spans)
        t0 = min(d.merged[0][0] for d in self.devices if d.merged)
        return [[name_gap(s, e, self.program_spans, owner, self.host_spans),
                 length / 1e9, (s - t0) / 1e9]
                for length, s, e in longest_gaps(self, top)]

    def span_totals(self) -> dict:
        """{span name: [count, seconds]} over the traced window, summed
        over threads."""
        out: dict = {}
        for s, e, name, _line in self.program_spans:
            a = out.setdefault(name, [0, 0.0])
            a[0] += 1
            a[1] += (e - s) / 1e9
        return out
