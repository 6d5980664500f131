"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(ctx):
    window = ctx.trace.window_seconds()
    if window <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_seconds() / window)
