"""Device time of the fused cycle kernel per simulated point-cycle: the
summed device time of the kernel's `XLA Ops` events on every chip the cell
uses, divided by points x cycles simulated in the traced window.  Counting
per point-cycle keeps the number comparable whatever implements the cycle
(a kernel launch per cycle or per several, any tile size)."""

from bench import kernels


def read(ctx):
    if ctx.trace is None:
        return None
    ns = kernels.kernel_ns(ctx.trace)
    if not ns:
        return None  # the kernel is not on this path
    return ns / sum(s.point_cycles for s in ctx.steps)
