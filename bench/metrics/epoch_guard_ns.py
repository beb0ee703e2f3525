"""Device time per simulated point-cycle of the operations labelled
`epoch.guard`, outside the cycle loop: the KF's innovation gate (the NIS
against its threshold), the divergence watchdog and covariance reset
(`predictor.step_probed`) and the fair-split fallback (`degrade_policy`),
summed over the chips the cell uses.  A program without the label reads
nothing."""

from bench import spans


def read(ctx):
    return spans.labelled_ns(ctx, "epoch.guard")
