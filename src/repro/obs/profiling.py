"""jax.profiler hooks: the program's host spans, and the captures behind
the fig drivers' `--profile DIR` flag.

`span(name)` marks one step of the program's host code on two clocks at
once: a `jax.profiler.TraceAnnotation` on the profiler's host plane (the
clock of the device planes in the same capture), and a
`jax.monitoring` event-time span `/repro/<name with dots as slashes>` on
the wall clock, for any registered span listener.  With no profiler and no
listener it costs two clock reads and the annotation's own check.

`profiled_run(outdir, fn)` runs `fn` twice under two separate profiler
traces: DIR/compile (first call — includes tracing + XLA compilation)
and DIR/steady (second call — jit caches warm, pure device execution).
With outdir falsy it degrades to a single plain call, so drivers can
wrap their `run(...)` unconditionally.

View the captures with `tensorboard --logdir DIR` or Perfetto
(`xprof`); the trace directories are plain TensorBoard event layouts.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Iterator, TypeVar

import jax

T = TypeVar("T")


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Mark the enclosed host code as the program step `name` (DESIGN.md
    §18): a profiler host span named `name`, and on exit the wall-clock
    event-time span `/repro/` + `name` with its dots as slashes.  A span's
    parent is the span that encloses it on the same thread.  Host code
    only: inside a traced function it would time the tracing."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.time()
        try:
            yield
        finally:
            jax.monitoring.record_event_time_span(
                "/repro/" + name.replace(".", "/"), t0, time.time())


@contextlib.contextmanager
def trace(outdir: str | None, label: str) -> Iterator[None]:
    """Profile the enclosed block into outdir/label (no-op when falsy)."""
    if not outdir:
        yield
        return
    path = os.path.join(outdir, label)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        yield


def profiled_run(outdir: str | None, fn: Callable[[], T], label: str = "") -> T:
    """Call fn under compile- and steady-phase profiler traces.

    The doubled call is deliberate: one capture that mixes tracing,
    compilation, and execution is unattributable, which is the problem
    this flag exists to solve. Without `--profile` there is exactly one
    call and zero overhead.
    """
    if not outdir:
        return fn()
    prefix = f"{label}-" if label else ""
    with trace(outdir, f"{prefix}compile"):
        fn()
    with trace(outdir, f"{prefix}steady"):
        return fn()
