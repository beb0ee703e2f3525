"""Device time per simulated point-cycle of the operations labelled
`epoch.boundary` in `_simulate_impl`, outside the cycle loop: the rest of
each epoch step outside its RNG streams and its cycle scan (VC masks and
placement rows, the prologue inject, lane packing of the carry and of the
per-cycle inputs, unpacking, the KF, the policy and the per-epoch
readings), summed over the chips the cell uses.  What XLA sinks of these
into the cycle loop runs every cycle and is `cycle_scan_ops_ns`'s."""

from bench import spans


def read(ctx):
    return spans.labelled_ns(ctx, "epoch.boundary")
