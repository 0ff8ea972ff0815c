"""device_idle_pct (layer: device): the share of the traced window in
which no operation ran on the chip, 1 - (union of device-op intervals /
window), from the profiler trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    window = ctx.trace.window_s()
    if window <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / window)
