#!/usr/bin/env python3
"""Run one cell of the benchmark once, as run.py does, with the idle gaps
of a traced run named by the program's own spans.

    python3 benchmark/named_gaps.py --workload <name> --seed <n> --seconds <s> --trace 1

Arguments, result line and checks are run.py's. With --trace 1 the
reduction also keeps the program's ratelimit.* host spans
(harness/gaps.py): `breakdown.idle_gaps` names each gap by what the
dispatch owner was doing in it (e.g. "ratelimit.dispatch.wait_work", or
"ratelimit.slab.lock_wait < ratelimit.slab.health_drain"), a
`bench program_spans` stderr line gives each span's count and seconds in
the traced window, and a `bench idle_gaps_at` line gives each named gap
with its start, in seconds after the trace's first device operation.
Against a program without such spans the names are run.py's own. Every
computed number is run.py's."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402 - first: run.py takes the process start time
from harness import gaps, trace  # noqa: E402

_reduce = trace.reduce_xplane


def reduce_named(path: str, window_s: float) -> gaps.NamedTraceSummary:
    summary = gaps.NamedTraceSummary.of(_reduce(path, window_s), gaps.program_spans(path))
    run.info("program_spans", **summary.span_totals())
    run.info("idle_gaps_at", gaps=summary.gaps_at())
    return summary


if __name__ == "__main__":
    trace.reduce_xplane = reduce_named
    sys.exit(run.main())
