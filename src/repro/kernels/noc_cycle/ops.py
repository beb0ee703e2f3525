"""Dispatch entry for the fused cycle kernel, with interpret mode off-TPU.

`fused_cycle_step` is one `fused_cycle_kernel` launch: one simulated
cycle with the whole scan carry in lane layout (DESIGN.md §13), the step
that `engine.pallas_engine` scans.  Off-TPU it runs in interpret mode (like
`repro.kernels.kf_bank`), so the `pallas` engine runs everywhere the tests
run.
"""
from __future__ import annotations

import jax

from repro.kernels.noc_cycle import fused
from repro.kernels.noc_cycle.kernel import fused_cycle_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_cycle_step(
    dims: fused.LaneDims,
    state: fused.LaneState,
    xi: jax.Array, xf: jax.Array,
    gmask: jax.Array, cmask: jax.Array, prof: jax.Array,
    pol_sr: jax.Array, pol_r: jax.Array,
    ntype: jax.Array, route: jax.Array, exists: jax.Array,
    probe: fused.ProbeLanes | None = None,
):
    """One fused simulated cycle (interpret-mode fallback off-TPU).

    Returns LaneState, or (LaneState, ProbeLanes) when a flight-recorder
    carry is threaded through (DESIGN.md §14)."""
    return fused_cycle_kernel(
        state, xi, xf, gmask, cmask, prof, pol_sr, pol_r,
        ntype, route, exists,
        dims=dims, interpret=_interpret(), probe=probe,
    )
