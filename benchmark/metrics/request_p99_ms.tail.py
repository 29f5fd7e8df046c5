"""request_p99_ms.tail: the 99th percentile of every request's latency over the window, from
when it was due to its answer (raw samples, nearest rank); None where no request was timed."""


def read(ctx):
    lat = ctx.get("latency") or {}
    return lat.get("p99")
