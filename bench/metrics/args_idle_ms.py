"""Device-idle milliseconds per sweep while the host was inside the program's
span `noc.args`: building the arguments (configurations, demand rows, fault
and placement streams, per-tile slices, initial state). Idle is the
stretches of the traced window in which no `XLA Ops` event runs on a
device, averaged over the chips the cell uses; the span's intervals are
events of the window's host thread on the same clock."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_sweep(ctx, "noc.args")
