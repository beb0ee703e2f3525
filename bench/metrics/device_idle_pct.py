"""Share of the traced window in which no operation ran on the device:
1 - (union of the `XLA Ops` intervals) / window, averaged over the chips
the cell uses.  Host work the device waits for (argument building,
dispatch, the Python between steps) shows up here."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
