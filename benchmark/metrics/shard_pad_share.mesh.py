"""shard_pad_share.mesh: the share of the lanes the mesh launched that were
padding, in %: 100 x (lanes launched - rows routed) / lanes launched, over
the window (the sums of shard.padded_lanes and shard.routed_rows, one
sample of each per launch). Each shard pads its own rows to a power of
two. None where the program records no such histograms."""

ROWS = "ratelimit.shard.routed_rows"
LANES = "ratelimit.shard.padded_lanes"


def read(ctx):
    _n, rows = ctx["hist"].get(ROWS, (0, 0.0))
    _m, lanes = ctx["hist"].get(LANES, (0, 0.0))
    if not lanes or not rows:
        return None
    return 100.0 * (lanes - rows) / lanes
