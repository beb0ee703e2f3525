"""Share of device busy time, in %, that neither carries a `noc_layer`
label nor is the fused cycle kernel: the epoch loop's own control and
input slicing, what XLA creates without the label, the program's set-up
and answer outside the epoch loop, and eager operations of the host code.
With the three labelled readers it splits the busy time outside the kernel:
`epoch_rng_ns` + `epoch_boundary_ns` + `cycle_scan_ops_ns` + this share x
busy per point-cycle = `epoch_scan_ns`."""

from bench import spans


def read(ctx):
    return spans.unlabeled_busy_pct(ctx)
