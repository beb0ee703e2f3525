"""Pallas TPU kernel: one simulated NoC cycle over the whole lane state.

`fused_cycle_kernel` runs every stage of a simulated cycle — MC service,
route and switch allocation, the buffer writes, MC enqueue, reply
completion, source generation, the merged inject and the counters — for
EVERY (subnet, router) pair at once, as ONE `pallas_call` with the whole
scan carry in its refs (DESIGN.md §13).  The pairs ride the 128-wide TPU
lanes, the small microarchitectural axes (P*V requesters, O output ports,
V virtual channels) ride sublanes with the port/VC loops unrolled at trace
time, so every op in the kernel is a 2D (sublane, lane) VPU op.

The value-level body is `fused.cycle_step_lanes` (its switch allocation is
`fused.lane_arbitrate`); the dense `ref` engine (`repro.core.noc.engine`)
is the oracle it must match BITWISE, and `engine.pallas_engine` launches
it once per simulated cycle.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels.noc_cycle import fused


# ---------------------------------------------------------------------------
# fused full-cycle kernel: ONE launch per simulated NoC cycle (DESIGN.md §13)
# ---------------------------------------------------------------------------

def _fused_cycle_kernel(
    xi_ref, xf_ref, gmask_ref, cmask_ref, prof_ref, pol_sr_ref, pol_r_ref,
    ntype_ref, route_ref, exists_ref,
    buf_meta_ref, buf_binj_ref, head_ref, count_ref, rr_ref,
    mcq_ref, mc_ref, node_ref, cnt_ref,
    o_buf_meta, o_buf_binj, o_head, o_count, o_rr,
    o_mcq, o_mc, o_node, o_cnt,
    *,
    dims: fused.LaneDims,
):
    state = fused.LaneState(
        buf_meta=buf_meta_ref[...],
        buf_binj=buf_binj_ref[...],
        head=head_ref[...],
        count=count_ref[...],
        rr=rr_ref[...],
        mcq=mcq_ref[...],
        mc=mc_ref[...],
        node=node_ref[...],
        cnt=cnt_ref[...],
    )
    new = fused.cycle_step_lanes(
        dims, state, xi_ref[...], xf_ref[...],
        gmask_ref[...], cmask_ref[...], prof_ref[...],
        pol_sr_ref[...], pol_r_ref[...],
        ntype_ref[...], route_ref[...], exists_ref[...],
    )
    o_buf_meta[...] = new.buf_meta
    o_buf_binj[...] = new.buf_binj
    o_head[...] = new.head
    o_count[...] = new.count
    o_rr[...] = new.rr
    o_mcq[...] = new.mcq
    o_mc[...] = new.mc
    o_node[...] = new.node
    o_cnt[...] = new.cnt


def _fused_cycle_probed_kernel(
    xi_ref, xf_ref, gmask_ref, cmask_ref, prof_ref, pol_sr_ref, pol_r_ref,
    ntype_ref, route_ref, exists_ref,
    buf_meta_ref, buf_binj_ref, head_ref, count_ref, rr_ref,
    mcq_ref, mc_ref, node_ref, cnt_ref,
    p_occ_ref, p_arb_ref, p_mcq_ref,
    o_buf_meta, o_buf_binj, o_head, o_count, o_rr,
    o_mcq, o_mc, o_node, o_cnt,
    o_p_occ, o_p_arb, o_p_mcq,
    *,
    dims: fused.LaneDims,
):
    """Flight-recorder variant of `_fused_cycle_kernel` (DESIGN.md §14):
    the ProbeLanes carry rides three extra in/out refs.  Separate kernel
    function so the probes-off pallas_call signature is untouched."""
    state = fused.LaneState(
        buf_meta=buf_meta_ref[...],
        buf_binj=buf_binj_ref[...],
        head=head_ref[...],
        count=count_ref[...],
        rr=rr_ref[...],
        mcq=mcq_ref[...],
        mc=mc_ref[...],
        node=node_ref[...],
        cnt=cnt_ref[...],
    )
    probe = fused.ProbeLanes(
        occ=p_occ_ref[...], arb=p_arb_ref[...], mcq=p_mcq_ref[...]
    )
    new, new_probe = fused.cycle_step_lanes(
        dims, state, xi_ref[...], xf_ref[...],
        gmask_ref[...], cmask_ref[...], prof_ref[...],
        pol_sr_ref[...], pol_r_ref[...],
        ntype_ref[...], route_ref[...], exists_ref[...],
        probe=probe,
    )
    o_buf_meta[...] = new.buf_meta
    o_buf_binj[...] = new.buf_binj
    o_head[...] = new.head
    o_count[...] = new.count
    o_rr[...] = new.rr
    o_mcq[...] = new.mcq
    o_mc[...] = new.mc
    o_node[...] = new.node
    o_cnt[...] = new.cnt
    o_p_occ[...] = new_probe.occ
    o_p_arb[...] = new_probe.arb
    o_p_mcq[...] = new_probe.mcq


def fused_cycle_kernel(
    state: fused.LaneState,
    xi: jax.Array,       # (XI_ROWS, S*64) int32 — this cycle's xs
    xf: jax.Array,       # (XF_ROWS, 128) float32
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
    """One simulated cycle as ONE pallas_call over the whole lane state.

    Every operand is small enough (< 100 KiB total at the paper's shapes)
    that the kernel runs as a single full-width block: the grid is (1,) and
    every BlockSpec covers its operand.  Constant tables arrive as input
    refs because Pallas kernel bodies may not capture constant arrays.

    With `probe` the ProbeLanes carry joins the refs and the return value
    is (LaneState, ProbeLanes) — a distinct kernel (so probes-off stays
    byte-identical), still ONE launch per cycle.
    """
    ins = (xi, xf, gmask, cmask, prof, pol_sr, pol_r, ntype, route, exists)
    carry = tuple(state) if probe is None else tuple(state) + tuple(probe)

    def spec(x):
        return pl.BlockSpec(x.shape, lambda i: (0, 0))

    body = _fused_cycle_kernel if probe is None else _fused_cycle_probed_kernel
    kernel = functools.partial(body, dims=dims)
    outs = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[spec(x) for x in ins + carry],
        out_specs=[spec(x) for x in carry],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in carry],
        interpret=interpret,
        name="noc_cycle_fused",
        metadata={"noc_layer": "cycle.kernel"},
    )(*ins, *carry)
    if probe is None:
        return fused.LaneState(*outs)
    n = len(fused.LaneState._fields)
    return fused.LaneState(*outs[:n]), fused.ProbeLanes(*outs[n:])
