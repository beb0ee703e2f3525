"""From a JAX profiler trace to the numbers the per-layer readers use.

The trace is the `.xplane.pb` that `jax.profiler.start_trace` writes, read
with `jax.profiler.ProfileData` (nothing but JAX).  Device planes are named
`/device:TPU:<n>`; their `XLA Ops` line holds one event per operation that
ran, on the same clock as the host planes.  The window is the host span the
benchmark opened around its traced steps; every device event is clipped to
it.  Host spans (the benchmark's `jax.profiler.TraceAnnotation`s and JAX's
own host events) label the idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10  # entries in each list of the breakdown


class Reduced(NamedTuple):
    window_ns: tuple[int, int]        # the benchmark's window span
    ops: dict[int, list]              # device id -> [(name, start, end)] ns
    host: list                        # [(name, start, end)] ns, window thread
    busy_s: float                     # union of op intervals, mean over devices
    window_s: float
    breakdown: dict


def union(intervals) -> float:
    """Length covered by the union of (start, end) intervals."""
    total, hi = 0, None
    for s, e in sorted(intervals):
        if hi is None or s > hi:
            total += e - s
            hi = e
        elif e > hi:
            total += e - hi
            hi = e
    return total


def gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def find_window(planes, window: str):
    """The window span and the host line that holds it."""
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == window:
                    return (ev.start_ns, ev.end_ns), line
    raise ValueError(f"no host span {window!r} in the trace")


def reduce_profile(pd, window: str, n_devices: int) -> Reduced:
    planes = list(pd.planes)
    (lo, hi), host_line = find_window(planes, window)
    ops = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops[int(m.group(1))] = clip(
                    ((ev.name, ev.start_ns, ev.end_ns) for ev in line.events),
                    lo, hi)
    if len(ops) != n_devices:
        raise ValueError(f"trace has '{OPS_LINE}' for devices {sorted(ops)}, "
                         f"expected {n_devices}")
    host = clip(((ev.name, ev.start_ns, ev.end_ns)
                 for ev in host_line.events), lo, hi)
    busy = [union((s, e) for _, s, e in evs) for evs in ops.values()]
    return Reduced(
        window_ns=(lo, hi), ops=ops, host=host,
        busy_s=sum(busy) / len(busy) / 1e9, window_s=(hi - lo) / 1e9,
        breakdown=breakdown(ops, host, lo, hi),
    )


def reduce_dir(trace_dir: str, window: str, n_devices: int) -> Reduced:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce_profile(jax.profiler.ProfileData.from_file(paths[0]),
                          window, n_devices)


OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """`%name opcode [target]` of an HLO instruction's text (device events
    on a TPU are named by the whole text, often thousands of characters)."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:120]
    op = OPCODE.search(rest)
    target = TARGET.search(rest)
    return " ".join([head] + ([op.group(1)] if op else [])
                    + ([target.group(1)] if target else []))


def host_label(host, t) -> str:
    """The innermost host span that holds time t, or `host idle`."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "host idle"


def breakdown(ops, host, lo, hi) -> dict:
    """The device operations that took most time (summed over devices), and
    the longest idle gaps, each named by what the host was doing then."""
    per_op: dict[str, int] = {}
    for evs in ops.values():
        for name, s, e in evs:
            per_op[name] = per_op.get(name, 0) + (e - s)
    top_ops = [(short_name(n), d) for n, d in
               sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]]
    idle = []
    for dev, evs in ops.items():
        for s, e in gaps([(s, e) for _, s, e in evs], lo, hi):
            idle.append((e - s, s, dev))
    idle.sort(reverse=True)
    multi = len(ops) > 1
    named = []
    for length, s, dev in idle[:TOP]:
        label = host_label(host, s + length // 2)
        named.append([f"{label} (TPU:{dev})" if multi else label, length / 1e9])
    return {"device_ops": [[n, d / 1e9] for n, d in top_ops],
            "idle_gaps": named}
