"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(ctx):
    t = ctx.trace
    return 100.0 * t.idle_share() if t.busy_s > 0 else None
