"""The share of the traced window in which the chips ran no operation:
1 less the union of their operations' intervals over the window, averaged
over the chips the cell uses."""


def read(ctx):
    if ctx.trace is None:
        return None
    return (1.0 - ctx.trace.mean_busy_s / ctx.trace.window_s) * 100.0
