"""Per-request deadline propagation (the Go context.Context deadline twin).

The reference service inherits deadline handling from grpc-go: the context
carries the client deadline and every layer below can ask "how long do I
have left?". The Python gRPC servicer only exposes
``context.time_remaining()`` at the transport edge, so this module carries
that value the rest of the way — a contextvar holding the ABSOLUTE
monotonic deadline, set by the transport for the duration of one request
and readable by any layer on the same thread of execution (the service
brain, the batching layer's submit path).

Why a contextvar and not a parameter: the deadline must cross the
``RateLimitCache.do_limit`` seam without changing every backend's
signature, exactly like ``tracing.active_span()`` crosses it. Backends
that don't care never look; the batching layer reads it at submit time and
drops already-expired work before packing a device launch
(backends/batcher.py, backends/dispatch.py).

Monotonic clock only: deadlines are durations from "now", so they must be
immune to wall-clock steps.
"""

from __future__ import annotations

import contextlib
import contextvars
import time

_DEADLINE: contextvars.ContextVar[float | None] = contextvars.ContextVar(
    "request_deadline", default=None
)


def current_deadline() -> float | None:
    """The absolute ``time.monotonic()`` deadline of the current request,
    or None when the caller set none (no deadline == infinite)."""
    return _DEADLINE.get()


def time_remaining() -> float | None:
    """Seconds until the current deadline (may be negative once expired),
    or None when no deadline is set."""
    deadline = _DEADLINE.get()
    if deadline is None:
        return None
    return deadline - time.monotonic()


def expired() -> bool:
    """True when a deadline is set and has already passed."""
    deadline = _DEADLINE.get()
    return deadline is not None and time.monotonic() >= deadline


@contextlib.contextmanager
def deadline_scope(remaining_seconds: float | None):
    """Bind the current request's deadline for the duration of the block.

    ``remaining_seconds`` is the transport's view of time left (e.g.
    ``grpc_context.time_remaining()`` or Envoy's
    ``x-envoy-expected-rq-timeout-ms`` header). None means no deadline.
    A non-positive value is kept as an already-expired deadline so the
    layers below shed the work instead of answering late.
    """
    if remaining_seconds is None:
        yield
        return
    token = _DEADLINE.set(time.monotonic() + float(remaining_seconds))
    try:
        yield
    finally:
        _DEADLINE.reset(token)
