"""GPipe-style pipeline parallelism over a `stage` mesh axis.

`pipeline_apply` runs S identical-signature stages on S devices with the
classic rotating schedule: at tick t, stage s computes microbatch t-s and
ppermutes its activation to stage s+1, so the pipe drains in
n_micro + S - 1 ticks with every stage busy in the steady state.  The whole
schedule lives inside one shard_map + lax.scan, is differentiable (ppermute
and psum have transposes), and degenerates to a plain per-microbatch apply
at S = 1 — tested against that oracle in tests/test_dist.py and against the
4-stage composition in tests/test_multidevice.py.

Stages must preserve the activation shape (the rotating buffer is a single
(mb, ...) slot); parameters carry a leading stage dim (see `split_stages`).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


def split_stages(params: Any, n_stages: int) -> Any:
    """Fold a leading layer dim into (n_stages, layers_per_stage, ...)."""
    def split(leaf):
        n = leaf.shape[0]
        if n % n_stages:
            raise ValueError(
                f"cannot split {n} layers into {n_stages} stages")
        return leaf.reshape((n_stages, n // n_stages) + leaf.shape[1:])

    return jax.tree.map(split, params)


def pipeline_apply(
    stage_fn: Callable[[Any, Array], Array],
    params: Any,
    microbatches: Array,
    mesh,
    *,
    stage_axis: str = "stage",
) -> Array:
    """Apply S pipeline stages to every microbatch.

    params:       pytree with a leading stage dim of size S on every leaf
                  (device s applies ``stage_fn(params[s], x)``).
    microbatches: (n_micro, mb, ...) activations, replicated.
    Returns       (n_micro, mb, ...) — each microbatch through all S stages.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = sizes[stage_axis]
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(w_stack, mbs):
        s = jax.lax.axis_index(stage_axis)
        w_local = jax.tree.map(lambda l: l[0], w_stack)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 pulls microbatch t from the feed; others read the ring
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(mbs, mb_idx, 0,
                                                keepdims=False)
            y = stage_fn(w_local, jnp.where(s == 0, feed, buf))
            # the last stage finishes microbatch t - (S-1) at tick t
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (s == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx, 0,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, y, cur), out_idx, 0)
            return (jax.lax.ppermute(y, stage_axis, perm), outs), None

        # the ring buffer and the outputs differ per stage from the first
        # tick on, so their initial values are typed as varying over stages
        buf0, outs0 = jax.lax.pcast(
            (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs)), (stage_axis,),
            to="varying")
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_micro + n_stages - 1))
        # only the last stage holds results; sum-select replicates them
        outs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    w_specs = jax.tree.map(lambda _: P(stage_axis), params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(w_specs, P()), out_specs=P(),
        axis_names={stage_axis},
    )(params, microbatches)
