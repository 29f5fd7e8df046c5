"""device_idle_share.served: 1 - (union of device-op intervals / traced
window), in %, the mean over the chips; None without a trace."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr.idle_share()
