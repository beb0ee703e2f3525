"""The program's own spans and labels, as the per-layer readers see them.

Host spans (`repro.obs.profiling.span`, DESIGN.md §18): `noc.sweep` around
`sim.sweep`, and within it `noc.args` (argument building), `noc.dispatch`
(each call of the compiled program) and `noc.rows` (cutting the answer
into per-point rows).  In a trace they are events of the window's host
thread (`trace.host`), on the device planes' clock; on the wall clock they
are `jax.monitoring` spans `/repro/noc/args`, ... (`ctx.monitor`).

Device labels: `_simulate_impl` marks its operations with the XLA frontend
attribute `noc_layer` (`epoch.rng`, `epoch.boundary`, `cycle.scan`).  On a
TPU each `XLA Ops` event is named by its HLO text, which carries
`frontend_attributes={noc_layer="..."}`; XLA keeps the attribute on most
top-level operations but not on every one it creates.  The fused cycle
kernel is found by its target (`bench.kernels`), whatever its labels.

A program without the spans or labels (an older commit) gives nothing to
read: every function here then returns None and raises nothing.
"""
from __future__ import annotations

import re
from typing import NamedTuple

import numpy as np

from bench import kernels, trace_reduce

HOST_STEPS = ("noc.args", "noc.dispatch", "noc.rows")
LABEL = re.compile(r'noc_layer="([^"]+)"')


def covered(starts, ends) -> int:
    """Length of the union of the intervals [starts[i], ends[i]) (ns):
    sorted by start, an interval opens a new stretch where it begins after
    every earlier one has ended."""
    starts = np.asarray(starts, np.int64)
    ends = np.asarray(ends, np.int64)
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    if not len(starts):
        return 0
    order = np.argsort(starts, kind="stable")
    starts, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.flatnonzero(np.r_[True, starts[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(starts) - 1]
    return int((reach[last] - starts[first]).sum())


class DeviceTime(NamedTuple):
    """One device's window, split by what ran (ns; unions, so nested or
    overlapping events count once)."""
    starts: np.ndarray         # every operation's interval
    ends: np.ndarray
    busy: int                  # any operation
    kernel: int                # the fused cycle kernel
    marked: int                # a labelled operation or the kernel
    loop: int                  # the `cycle.scan` loop or the kernel
    by_label: dict             # label -> its operations' time outside
    #                            the cycle loop


def label(name: str) -> str | None:
    """The `noc_layer` label in a device event's name, if any."""
    m = LABEL.search(name)
    return m.group(1) if m else None


def _device_time(evs) -> DeviceTime:
    # classify each distinct event name once: a sweep repeats a few
    # thousand names millions of times
    kinds: dict[str, int] = {}
    names: list[tuple[str | None, bool]] = []
    kind, starts, ends = [], [], []
    for n, s, e in evs:
        k = kinds.get(n)
        if k is None:
            k = kinds[n] = len(names)
            names.append((label(n), bool(kernels.CYCLE_KERNEL.search(n))))
        kind.append(k)
        starts.append(s)
        ends.append(e)
    kind = np.asarray(kind, np.int64)
    starts = np.asarray(starts, np.int64)
    ends = np.asarray(ends, np.int64)

    def of(test):
        m = np.array([test(lab, ker) for lab, ker in names], bool)[kind]
        return starts[m], ends[m]

    loop_s, loop_e = of(lambda lab, ker: ker or lab == "cycle.scan")
    loop = covered(loop_s, loop_e)
    outside = {}
    for layer in {lab for lab, _ in names if lab is not None}:
        s, e = of(lambda lab, ker: lab == layer)
        outside[layer] = covered(np.r_[s, loop_s], np.r_[e, loop_e]) - loop
    return DeviceTime(
        starts=starts, ends=ends, busy=covered(starts, ends),
        kernel=covered(*of(lambda lab, ker: ker)),
        marked=covered(*of(lambda lab, ker: ker or lab is not None)),
        loop=loop, by_label=outside)


def device_time(ctx) -> dict[int, DeviceTime] | None:
    """Per device, the traced window split by what ran; None without a
    trace.  Worked out once per context: a full sweep is millions of
    events and every reader of this module needs the split."""
    if ctx.trace is None:
        return None
    memo = getattr(ctx, "_noc_device_time", None)
    if memo is None or memo[0] is not ctx.trace:
        memo = (ctx.trace, {dev: _device_time(evs)
                            for dev, evs in ctx.trace.ops.items()})
        ctx._noc_device_time = memo
    return memo[1]


# ---- host spans against device idle time


def idle_covered_ns(ctx, names) -> float | None:
    """Device-idle time, averaged over devices, during which the window's
    host thread was inside one of the spans `names`; None without a trace
    or when the trace holds none of those spans.  The spans lie in the
    window, so the idle time under them is what they add to the busy
    time's union."""
    if ctx.trace is None:
        return None
    spans = [(s, e) for n, s, e in ctx.trace.host if n in names]
    if not spans:
        return None
    s, e = np.asarray(spans, np.int64).T
    dt = device_time(ctx)
    return sum(covered(np.r_[d.starts, s], np.r_[d.ends, e]) - d.busy
               for d in dt.values()) / len(dt)


def idle_ms_per_sweep(ctx, name: str) -> float | None:
    """Device-idle ms per sweep under the host span `name`."""
    ns = idle_covered_ns(ctx, (name,))
    return None if ns is None else ns / 1e6 / len(ctx.steps)


def idle_unattributed_pct(ctx) -> float | None:
    """Share of device-idle time under none of the host steps, in %."""
    covered_ns = idle_covered_ns(ctx, HOST_STEPS)
    if covered_ns is None:
        return None
    dt = device_time(ctx)
    lo, hi = ctx.trace.window_ns
    idle = sum(hi - lo - d.busy for d in dt.values()) / len(dt)
    return 100.0 * (idle - covered_ns) / idle if idle else 0.0


def setup_span_s(ctx, event: str) -> float | None:
    """Union of the wall-clock spans `event` that began during set-up."""
    lo, hi = ctx.setup_span
    spans = [(s, t) for _, s, t in ctx.monitor.between(lo, hi, event)]
    return trace_reduce.union(spans) if spans else None


# ---- device labels


def per_point_cycle(ctx, ns: float) -> float:
    return ns / sum(s.point_cycles for s in ctx.steps)


def labelled_ns(ctx, layer: str) -> float | None:
    """Device time of the operations labelled `layer` per point-cycle,
    outside the cycle loop, summed over devices; None where nothing carries
    the label.  XLA sinks some epoch-step operations (broadcasts of the
    per-epoch rows) into the cycle loop, where they run every cycle: that
    time is the loop's (`scan_ops_ns`), so no time counts twice."""
    dt = device_time(ctx)
    if not dt or not any(layer in d.by_label for d in dt.values()):
        return None
    return per_point_cycle(
        ctx, sum(d.by_label.get(layer, 0) for d in dt.values()))


def scan_ops_ns(ctx) -> float | None:
    """Device time in the `cycle.scan` loop outside the kernel per
    point-cycle, summed over devices: whatever runs in the loop, whichever
    label it carries."""
    dt = device_time(ctx)
    if not dt or not any("cycle.scan" in d.by_label for d in dt.values()):
        return None
    return per_point_cycle(ctx, sum(d.loop - d.kernel for d in dt.values()))


def unlabeled_busy_pct(ctx) -> float | None:
    """Share of busy time under no label and outside the kernel, in %."""
    dt = device_time(ctx)
    if not dt or not any(d.by_label for d in dt.values()):
        return None
    busy = sum(d.busy for d in dt.values())
    return 100.0 * sum(d.busy - d.marked for d in dt.values()) / busy
