"""Shared arithmetic of the histogram readers: the exact mean of one of the
program's histograms over the window (the delta of its sum over the delta
of its count); None where nothing was recorded."""


def mean(ctx: dict, name: str):
    count, total = ctx["hist"].get(name, (0, 0.0))
    return total / count if count else None
