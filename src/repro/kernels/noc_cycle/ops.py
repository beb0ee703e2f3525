"""Dispatch entry for the fused cycle kernel, with interpret mode off-TPU.

`fused_cycles` is one `fused_cycle_kernel` launch: a chunk of K
simulated cycles with the whole scan carry in lane layout (DESIGN.md
§13), the step that `engine.pallas_engine` scans.  Off-TPU it runs in
interpret mode (like `repro.kernels.kf_bank`), so the `pallas` engine
runs everywhere the tests run.
"""
from __future__ import annotations

import jax

from repro.kernels.noc_cycle import fused
from repro.kernels.noc_cycle.kernel import fused_cycle_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_cycles(
    dims: fused.LaneDims,
    state: fused.LaneState,
    probe: fused.ProbeLanes | None,
    xi: jax.Array, xf: jax.Array,
    gmask: jax.Array, cmask: jax.Array, prof: jax.Array,
    pol_sr: jax.Array, pol_r: jax.Array,
    ntype: jax.Array, route: jax.Array, exists: jax.Array,
):
    """`xi.shape[0]` fused simulated cycles (interpret-mode fallback
    off-TPU).  Returns (LaneState, ProbeLanes or None): the flight-recorder
    carry (DESIGN.md §14) is threaded through when given."""
    return fused_cycle_kernel(
        state, xi, xf, gmask, cmask, prof, pol_sr, pol_r,
        ntype, route, exists,
        dims=dims, interpret=_interpret(), probe=probe,
    )
