"""Device time per simulated point-cycle of the operations labelled
`epoch.placement` in `_simulate_impl`, outside the cycle loop: each
epoch's choice of class plan (`placement_class`) and the rows derived from
it (the virtual node type, the GPU and CPU masks, the request subnets, and
the fused kernel's node-type and policy lane rows), summed over the chips
the cell uses.  What XLA sinks of these into the cycle loop runs every
cycle and is `cycle_scan_ops_ns`'s.  A program without the label reads
nothing."""

from bench import spans


def read(ctx):
    return spans.labelled_ns(ctx, "epoch.placement")
