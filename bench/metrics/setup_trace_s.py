"""Seconds of set-up spent tracing jaxprs and lowering them to MLIR: the
union of JAX's `jaxpr_trace_duration` and `jaxpr_to_mlir_module_duration`
spans (`jax.monitoring`) that began during set-up."""

from bench import trace_reduce

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration")


def read(ctx):
    lo, hi = ctx.setup_span
    spans = [(s, t) for e, s, t in ctx.monitor.between(lo, hi) if e in EVENTS]
    return trace_reduce.union(spans) if spans else None
