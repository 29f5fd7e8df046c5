"""Host spans on the JAX profiler's clock.

`host_span(name)` marks a block of host work in the profiler's own trace
(a `jax.profiler.TraceAnnotation`, i.e. a TraceMe on the calling thread's
host line), so a trace of the device shows what each host thread was
doing in every device gap: the dispatch owner parked for work, waiting
for the state lock, draining slab health, a request thread waiting for
its verdict.

Spans exist exactly while a profile is being captured (`GET
/debug/profile`, or any `jax.profiler.start_trace`). With no capture
running a site costs one `TraceMe.is_enabled()` call and gets a shared
no-op context manager; there is no setting. This module never imports
jax: it uses `jax.profiler` once the process has imported it, and a
process that never does (a frontend-only process) can hold no capture,
so it always gets the no-op.

Span names are the stats histogram's name without `_ms` where the site
also records one (`ratelimit.dispatch.redeem` beside
`ratelimit.dispatch.redeem_ms`), so a trace and /metrics name a stage
alike.

`install_gc_spans()` adds a `gc.callbacks` hook that wraps each garbage
collection in a `ratelimit.gc.gen<N>` span while a capture runs."""

from __future__ import annotations

import contextlib
import gc
import sys

_NOOP = contextlib.nullcontext()
# bound on first use once jax.profiler is loaded: TraceMe.is_enabled and
# the TraceAnnotation class (None until then)
_is_enabled = None
_annotation = None
_GC_SPAN_NAMES = tuple(f"ratelimit.gc.gen{g}" for g in range(3))


def _bind():
    """Bind the profiler's entry points; None while jax.profiler is not
    loaded (no capture can be running then). Imports nothing, so it is
    safe inside a gc callback that fires while jax itself is importing."""
    global _is_enabled, _annotation
    annotation = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if annotation is None:
        return None
    _annotation = annotation
    _is_enabled = annotation.is_enabled
    return _is_enabled


def host_span(name: str):
    """A context manager marking the block as `name` in the profiler
    trace while a capture runs; a shared no-op otherwise."""
    enabled = _is_enabled or _bind()
    if enabled is None or not enabled():
        return _NOOP
    return _annotation(name)


class _GcSpans:
    """gc.callbacks hook: opens a span at a collection's start and closes
    it at its stop. Collections do not nest, so one slot holds the open
    span."""

    def __init__(self):
        self.open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            enabled = _is_enabled or _bind()
            if enabled is not None and enabled():
                span = _annotation(_GC_SPAN_NAMES[info["generation"]])
                span.__enter__()
                self.open = span
        elif self.open is not None:
            span, self.open = self.open, None
            span.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Add the garbage-collection span hook once per process."""
    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())
