"""Device time per simulated point-cycle of the operations labelled
`epoch.rng` in `_simulate_impl`, outside the cycle loop: each epoch's key
split and its `u_phase`/`u_gen`/`d_idx` draws for every cycle (and the
split of the epoch keys), summed over the chips the cell uses."""

from bench import spans


def read(ctx):
    return spans.labelled_ns(ctx, "epoch.rng")
