"""Device-idle milliseconds per sweep while the host was inside the program's
span `noc.rows`: cutting the answer into per-point rows. Idle is the
stretches of the traced window in which no `XLA Ops` event runs on a
device, averaged over the chips the cell uses; the span's intervals are
events of the window's host thread on the same clock."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_sweep(ctx, "noc.rows")
