"""Simulated router-cycles per second of wall time over the whole window.

Every router of every point (memory controllers' routers included, padded
tile or shard rows not) for every simulated cycle, summed over the steps
started while the window was open, divided by the time from the window's
start to the last step's `block_until_ready`."""


def read(ctx):
    return sum(s.router_cycles for s in ctx.steps) / ctx.window_s
