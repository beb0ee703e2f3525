"""Device-idle milliseconds per sweep while the host was inside the program's
span `noc.schedules`, nested in `noc.args`: resolving and materialising
every point's per-epoch streams (its scenario's demand rows, its fault
masks and its placement plans).  Idle is the stretches of the traced
window in which no `XLA Ops` event runs on a device, averaged over the
chips the cell uses; the span's intervals are events of the window's host
thread on the same clock.  A program without the span reads nothing."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_sweep(ctx, "noc.schedules")
