"""The fused `pallas` cycle engine (DESIGN.md §13): the TPU path.

Built and called like the dense `ref` engine (see `repro.core.noc.engine`
for the interface).  This module owns the lane layout's side of the seam:
at build it fixes the `fused.LaneDims` and the run's constant lane tables;
each epoch it builds the mask, profile, placement, policy and per-cycle
rows, folds the epoch's link faults into the link-exists rows, packs the
carry into lane layout, scans one `fused_cycle_kernel` launch per cycle
with the whole state in kernel refs, and unpacks at the epoch's end.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.core.noc.engine import (
    BCAP,
    EpochCounters,
    EpochInputs,
    Fabric,
    MCState,
    ProbeAcc,
)
from repro.kernels.noc_cycle import fused as lanes
from repro.kernels.noc_cycle import ops as lane_ops


def pallas_engine(stc, topo, fab: Fabric, binj_dtype):
    """The fused engine for a run (``stc`` is its `SimStatic`)."""
    assert lanes.COUNTER_FIELDS == EpochCounters._fields, (
        "fused kernel counter lanes out of sync with EpochCounters"
    )
    S, R = stc.n_subnets, topo.n_routers
    probe_on = stc.probe.enabled
    # the lane engine carries stamps as int32 and masks the latency
    # subtraction instead, reproducing the uint16 wraparound bitwise
    stamp_mask = 0xFFFF if binj_dtype == jnp.uint16 else 0
    lane_dims = lanes.lane_dims(
        S=S, R=R, V=stc.n_vcs, B=stc.buf_depth, Q=stc.mc_queue_cap,
        width=topo.width, mc_service_period=stc.mc_service_period,
        mshr_limit=stc.mshr_limit, bcap=BCAP, stamp_mask=stamp_mask,
    )
    # the node-type row and the req_match-bearing policy rows are
    # per-epoch data (placement, DESIGN.md §17) — rebuilt in `run`
    route_rows, exists_rows, _ = lanes.run_consts(lane_dims, topo)

    def run(carry, ep: EpochInputs):
        subs, mc, phase, outst, backlog = carry
        flt = ep.flt
        gm_rows, cm_rows = lanes.mask_rows(lane_dims, ep.g_vec, ep.c_vec)
        pr_rows = lanes.prof_rows(ep.prof)
        # placement lane rows (DESIGN.md §17): the node-type row and
        # the req_match-bearing policy rows follow this epoch's plan
        with set_xla_metadata(noc_layer="epoch.placement"):
            ntype_row = lanes.placement_rows(lane_dims, ep.ntype)
            req_match = (
                (fab.sub_ids[:, None] == ep.req_sub[None, :])
                & fab.sub_enabled[:, None]
            )
            pol_sr, pol_r = lanes.policy_rows(
                lane_dims, fab.sub_enabled, fab.sub_is_req, fab.sub_is_rep,
                req_match, fab.four_subnet, fab.n_req_subs,
            )
        xi, xf = lanes.cycle_xs(
            lane_dims, *ep.rows,
            router_ok=flt.router_ok, mc_ok=flt.mc_ok,
        )
        # epoch link-fault mask folded into the link-exists rows: the
        # lane kernel sees a dead link exactly as a non-existent one
        link_rows = jnp.tile(
            jnp.pad(
                flt.link_ok.astype(jnp.int32).T,
                ((0, 0), (0, lanes.R_PAD - R)),
            ),
            (1, S),
        )
        exists_ep = exists_rows * link_rows
        ls0 = lanes.pack_state(lane_dims, subs, mc, outst, backlog, phase)

        def fused_cycle(ls, x):
            ls = lane_ops.fused_cycle_step(
                lane_dims, ls, x[0], x[1], gm_rows, cm_rows, pr_rows,
                pol_sr, pol_r, ntype_row, route_rows, exists_ep,
            )
            return ls, None

        def fused_cycle_probed(carry, x):
            ls, pb = carry
            ls, pb = lane_ops.fused_cycle_step(
                lane_dims, ls, x[0], x[1], gm_rows, cm_rows, pr_rows,
                pol_sr, pol_r, ntype_row, route_rows, exists_ep,
                probe=pb,
            )
            return (ls, pb), None

        prb = None
        if probe_on:
            with set_xla_metadata(noc_layer="cycle.scan"):
                (ls, pb), _ = jax.lax.scan(
                    fused_cycle_probed,
                    (ls0, lanes.zero_probe(lane_dims)),
                    (xi, xf),
                )
            prb = ProbeAcc(*lanes.unpack_probe(lane_dims, pb))
        else:
            with set_xla_metadata(noc_layer="cycle.scan"):
                ls, _ = jax.lax.scan(fused_cycle, ls0, (xi, xf))
        subs, mc, outst, backlog, phase = lanes.unpack_state(
            lane_dims, ls, MCState, binj_dtype
        )
        cnt = EpochCounters(
            *(ls.cnt[0, i] for i in range(lanes.N_COUNTERS))
        )
        return (subs, mc, phase, outst, backlog), cnt, prb

    return run
