"""Seconds of set-up spent building the sweep's arguments: the union of the
program's `/repro/noc/args` spans (`jax.monitoring`, wall clock; the host
span `noc.args` in `sim.sweep`, `sim.simulate_batch` and `sim.simulate`)
that began during set-up.  Configurations, demand rows, fault and
placement streams, the per-tile slices and the initial state, each built
with eager operations that trace, compile or load on first use."""

from bench import spans

EVENT = "/repro/noc/args"


def read(ctx):
    return spans.setup_span_s(ctx, EVENT)
