"""Which device events are the fused NoC cycle kernel.

The fused engine launches one Mosaic kernel per simulated cycle
(`repro.kernels.noc_cycle.kernel.fused_cycle_kernel`, a `pallas_call` with
no `name=`).  On a TPU v5e trace every `XLA Ops` event is named by its HLO
instruction text, and the kernel's reads
`%closed_call.<n> = (...) custom-call(...), custom_call_target="tpu_custom_call", ...`:
the only Mosaic custom call in the `pallas` engine's program (the KF's LU
custom calls have other targets).
"""
from __future__ import annotations

import re

from bench import trace_reduce

CYCLE_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def kernel_ns(trace, pattern: re.Pattern = CYCLE_KERNEL) -> float:
    """Device time of the matching events, summed over the devices (the
    union per device, so nested or overlapping events count once)."""
    return sum(
        trace_reduce.union((s, e) for n, s, e in evs if pattern.search(n))
        for evs in trace.ops.values())
