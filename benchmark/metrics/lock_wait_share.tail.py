"""lock_wait_share.tail: the share of the window the launch path spent waiting for the
engine's state lock (100 x the sum of slab.lock_wait_ms over the window's
length), in %; None where the program records no such histogram."""


def read(ctx):
    count, total_ms = ctx["hist"].get("ratelimit.slab.lock_wait_ms", (0, 0.0))
    return 100.0 * total_ms / (1e3 * ctx["window_s"]) if count else None
