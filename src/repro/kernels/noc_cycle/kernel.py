"""Pallas TPU kernel: K simulated NoC cycles over the whole lane state.

`fused_cycle_kernel` runs every stage of a simulated cycle — MC service,
route and switch allocation, the buffer writes, MC enqueue, reply
completion, source generation, the merged inject and the counters — for
EVERY (subnet, router) pair at once, for K consecutive cycles, as ONE
`pallas_call` with the whole scan carry resident in its VMEM refs
(DESIGN.md §13).  The pairs ride the 128-wide TPU lanes, the small
microarchitectural axes (P*V requesters, O output ports, V virtual
channels) ride sublanes with the port/VC loops unrolled at trace time, so
every op in the kernel is a 2D (sublane, lane) VPU op.

The value-level body is `fused.cycle_step_lanes` (its switch allocation is
`fused.lane_arbitrate`); the dense `ref` engine (`repro.core.noc.engine`)
is the oracle it must match BITWISE, and `engine.pallas_engine` launches
it once per `cycles_per_launch` cycles.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels.noc_cycle import fused

# VMEM for one buffer of a launch's per-cycle inputs (the pipeline keeps
# two): well under the 16 MiB default scoped VMEM beside the carry
XS_VMEM_BUDGET = 2 << 20


def _vmem_bytes(rows: int, lanes: int) -> int:
    """VMEM of a (rows, lanes) 32-bit block, in Mosaic's (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-lanes // 128)) * 128 * 4


def cycles_per_launch(dims: fused.LaneDims, epoch_len: int) -> int:
    """K: the largest divisor of `epoch_len` whose block of per-cycle
    inputs (`xi` and `xf` rows, K cycles of each) fits `XS_VMEM_BUDGET`.
    125 at the paper's shapes and 500 cycles; 1 for a prime epoch longer
    than the budget holds."""
    per_cycle = (_vmem_bytes(fused.XI_ROWS, dims.lanes_sr)
                 + _vmem_bytes(fused.XF_ROWS, fused.LANES_R))
    fit = min(epoch_len, max(1, XS_VMEM_BUDGET // per_cycle))
    return max(k for k in range(1, fit + 1) if epoch_len % k == 0)


# ---------------------------------------------------------------------------
# fused multi-cycle kernel: ONE launch per K simulated cycles (DESIGN.md §13)
# ---------------------------------------------------------------------------

def _fused_cycle_kernel(
    xi_ref, xf_ref, gmask_ref, cmask_ref, prof_ref, pol_sr_ref, pol_r_ref,
    ntype_ref, route_ref, exists_ref, *carry_refs,
    dims: fused.LaneDims,
):
    """K cycles on the carry (LaneState, then ProbeLanes when probed):
    the input carry refs are copied to the output refs once, then every
    cycle reads its state from and writes it back to the output refs.
    The copy is needed on a TPU: an output block is a VMEM buffer of its
    own that starts unset, though it aliases the input in HBM (interpret
    mode starts it from the input, so the CPU tests cannot tell)."""
    n = len(carry_refs) // 2
    ins, outs = carry_refs[:n], carry_refs[n:]
    for i, o in zip(ins, outs):
        o[...] = i[...]
    n_state = len(fused.LaneState._fields)
    probed = n > n_state

    def cycle(c, _):
        vals = [o[...] for o in outs]
        new = fused.cycle_step_lanes(
            dims, fused.LaneState(*vals[:n_state]), xi_ref[c], xf_ref[c],
            gmask_ref[...], cmask_ref[...], prof_ref[...],
            pol_sr_ref[...], pol_r_ref[...],
            ntype_ref[...], route_ref[...], exists_ref[...],
            probe=fused.ProbeLanes(*vals[n_state:]) if probed else None,
        )
        if probed:
            new = (*new[0], *new[1])
        for o, v in zip(outs, new):
            o[...] = v

    jax.lax.fori_loop(0, xi_ref.shape[0], cycle, None)


def fused_cycle_kernel(
    state: fused.LaneState,
    xi: jax.Array,       # (K, XI_ROWS, S*64) int32 — K cycles' xs
    xf: jax.Array,       # (K, XF_ROWS, 128) float32
    gmask: jax.Array,    # (V, S*64) int32 0/1 — epoch VC masks
    cmask: jax.Array,    # (V, S*64) int32 0/1
    prof: jax.Array,     # (n_prof, 128) float32 — workload rows
    pol_sr: jax.Array,   # (PS_ROWS, S*64) int32 — subnet structure
    pol_r: jax.Array,    # (PR_ROWS, 128) int32
    ntype: jax.Array,    # (1, 128) int32 — node types (constant)
    route: jax.Array,    # (R, S*64) int32 — route table (constant)
    exists: jax.Array,   # (P, S*64) int32 0/1 — link table (constant)
    *,
    dims: fused.LaneDims,
    interpret: bool = False,
    probe: fused.ProbeLanes | None = None,
):
    """K = `xi.shape[0]` simulated cycles as ONE pallas_call over the
    whole lane state; returns (LaneState, ProbeLanes or None).

    The grid is (1,) and every BlockSpec covers its operand, so each
    operand crosses to VMEM once a launch: the carry (aliased in place
    to the outputs), the K cycles' `xi`/`xf` block and the epoch's
    constant rows, which arrive as input refs because Pallas kernel
    bodies may not capture constant arrays.  With `probe` the
    ProbeLanes carry rides beside the state, one cycle at a time.
    """
    ins = (xi, xf, gmask, cmask, prof, pol_sr, pol_r, ntype, route, exists)
    carry = tuple(state) if probe is None else tuple(state) + tuple(probe)

    def spec(x):
        return pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim)

    outs = pl.pallas_call(
        functools.partial(_fused_cycle_kernel, dims=dims),
        grid=(1,),
        in_specs=[spec(x) for x in ins + carry],
        out_specs=[spec(x) for x in carry],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in carry],
        input_output_aliases={len(ins) + j: j for j in range(len(carry))},
        interpret=interpret,
        name="noc_cycle_fused",
        metadata={"noc_layer": "cycle.kernel",
                  "cycles_per_launch": xi.shape[0]},
    )(*ins, *carry)
    n = len(fused.LaneState._fields)
    return (fused.LaneState(*outs[:n]),
            fused.ProbeLanes(*outs[n:]) if probe is not None else None)
