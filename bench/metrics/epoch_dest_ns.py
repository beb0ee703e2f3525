"""Device time per simulated point-cycle of the operations labelled
`epoch.dest` in `_simulate_impl`, outside the cycle loop: with a peer table,
each epoch's tile-to-tile destination draw (one uint32 a tile a cycle from
the epoch's own stream) and the weighted pick of the peer, summed over the
chips the cell uses.  What XLA sinks of these into the cycle loop runs
every cycle and is `cycle_scan_ops_ns`'s.  A program without the label
reads nothing."""

from bench import spans


def read(ctx):
    return spans.labelled_ns(ctx, "epoch.dest")
