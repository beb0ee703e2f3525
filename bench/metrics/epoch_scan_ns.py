"""Device busy time outside the fused cycle kernel per simulated
point-cycle: the union of all `XLA Ops` intervals minus the kernel's, on
every chip the cell uses, divided by points x cycles in the traced window.
That is the epoch layer of `_simulate_impl` and the cycle scan around the
kernel: scan control, slicing of the per-cycle inputs, the epoch prologue,
pack/unpack, KF and policy."""

from bench import kernels, trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    busy = sum(trace_reduce.union((s, e) for _, s, e in evs)
               for evs in ctx.trace.ops.values())
    return (busy - kernels.kernel_ns(ctx.trace)) / sum(
        s.point_cycles for s in ctx.steps)
