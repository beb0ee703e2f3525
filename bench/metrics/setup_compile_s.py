"""Seconds of set-up spent in XLA/Mosaic compilation or in loading an
executable from the persistent cache: the union of JAX's
`backend_compile_duration` spans (`jax.monitoring`; the span wraps the cache
lookup) that began during set-up."""

from bench import trace_reduce

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    lo, hi = ctx.setup_span
    spans = [(s, t) for _, s, t in ctx.monitor.between(lo, hi, EVENT)]
    return trace_reduce.union(spans) if spans else None
