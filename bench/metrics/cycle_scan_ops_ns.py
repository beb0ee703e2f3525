"""Device time per simulated point-cycle inside the cycle `lax.scan`
(the loop labelled `cycle.scan` in `_simulate_impl`) outside the fused
cycle kernel: the loop's control, the slicing of each cycle's inputs, the
copies around every launch and the epoch-step operations XLA sinks into
the loop, summed over the chips the cell uses."""

from bench import spans


def read(ctx):
    return spans.scan_ops_ns(ctx)
