"""Pallas TPU kernel: the NoC router-arbitration inner loop over lanes.

One simulated cycle's switch allocation — VC allocation at the downstream
router, per-output round-robin arbitration, and the one-traversal-per-input
grant filter — for EVERY (subnet, router) pair at once.  The pairs ride the
128-wide TPU lanes as a flattened `(S*R)` lane axis (batched sweeps flatten
`(B*S*R)`), and the small microarchitectural axes (P*V requesters, O output
ports, V virtual channels) ride sublanes with the port/VC loops unrolled at
trace time — every op in the kernel is a 2D (sublane, lane) VPU op.

This is the jax_pallas-facing half of the cycle engine (DESIGN.md §11, §13):
the dense-jnp `router.arbitrate` is the oracle, `ops.arbitrate_lanes` is the
`simulate(..., backend="pallas_arb")` entry with interpret-mode fallback
off-TPU, and the two must agree BITWISE — the packed-min trick, the
argmax-of-bool VC pick and the garbage-when-ungranted conventions are all
mirrored exactly.  The value-level arbitration body lives in
`fused.lane_arbitrate` and is shared with `fused_cycle_kernel` — the
full-cycle kernel that `simulate(..., backend="pallas")` launches once per
simulated cycle with the whole scan carry in its refs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.noc_cycle import fused

BIG = fused.BIG


def _noc_cycle_kernel(
    valid_ref, cls_ref, out_port_ref, rr_ref, down_ref, exists_ref,
    gmask_ref, cmask_ref, sa_ref, accept_ref, active_ref,
    grant_ref, winner_ref, down_vc_ref, deq_ref, new_rr_ref,
    any_req_ref, w_cls_ref,
    *,
    depth: int,
):
    arb = fused.lane_arbitrate(
        valid_ref[...] != 0,
        cls_ref[...],
        out_port_ref[...],
        rr_ref[...],
        down_ref[...],
        exists_ref[...] != 0,
        gmask_ref[...] != 0,
        cmask_ref[...] != 0,
        sa_ref[...],
        accept_ref[...] != 0,
        active_ref[...] != 0,
        depth=depth,
    )
    grant_ref[...] = fused.stack_rows(arb.grant)
    winner_ref[...] = jnp.concatenate(arb.winner, axis=0)
    down_vc_ref[...] = jnp.concatenate(arb.down_vc, axis=0)
    deq_ref[...] = arb.deq
    new_rr_ref[...] = jnp.concatenate(arb.new_rr, axis=0)
    any_req_ref[...] = fused.stack_rows(arb.any_req)
    w_cls_ref[...] = jnp.concatenate(arb.w_cls, axis=0)


def noc_cycle_kernel(
    valid: jax.Array,       # (PV, L) int32 0/1
    cls: jax.Array,         # (PV, L) int32
    out_port: jax.Array,    # (PV, L) int32
    rr_ptr: jax.Array,      # (O, L) int32
    down_count: jax.Array,  # (O*V, L) int32
    down_exists: jax.Array,  # (O, L) int32 0/1
    gmask: jax.Array,       # (V, L) int32 0/1
    cmask: jax.Array,       # (V, L) int32 0/1
    sa_pref: jax.Array,     # (1, L) int32
    accept: jax.Array,      # (1, L) int32 0/1
    active: jax.Array,      # (1, L) int32 0/1
    *,
    depth: int,
    n_vcs: int,
    block_l: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, ...]:
    """Lane-blocked dispatch; L must be a multiple of `block_l`."""
    pv, lanes = valid.shape
    o = rr_ptr.shape[0]
    assert lanes % block_l == 0, (lanes, block_l)
    grid = (lanes // block_l,)

    def spec(rows):
        return pl.BlockSpec((rows, block_l), lambda i: (0, i))

    out_rows = [o, o, o, pv, o, o, o]
    kernel = functools.partial(_noc_cycle_kernel, depth=depth)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec(pv), spec(pv), spec(pv), spec(o), spec(o * n_vcs),
            spec(o), spec(n_vcs), spec(n_vcs), spec(1), spec(1), spec(1),
        ],
        out_specs=[spec(r) for r in out_rows],
        out_shape=[
            jax.ShapeDtypeStruct((r, lanes), jnp.int32) for r in out_rows
        ],
        interpret=interpret,
    )(valid, cls, out_port, rr_ptr, down_count, down_exists,
      gmask, cmask, sa_pref, accept, active)


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
    )(*ins, *carry)
    if probe is None:
        return fused.LaneState(*outs)
    n = len(fused.LaneState._fields)
    return fused.LaneState(*outs[:n]), fused.ProbeLanes(*outs[n:])
