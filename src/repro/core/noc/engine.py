"""The cycle-engine interface and the dense `ref` engine (DESIGN.md §11,
§13).

`sim._simulate_impl` is the epoch layer: once a run it builds a cycle
engine, then for each epoch it computes that epoch's inputs and calls the
engine once for the epoch's `epoch_len` cycles.  Two engines share the
interface and agree bitwise (tests/test_cycle_engine.py):

  * `ref`    — `ref_engine` below: one `lax.scan` of the dense jnp
               `ref_cycle` over `(S, R, ...)` state.  The CPU path and the
               reference every other engine is held to.
  * `pallas` — `repro.kernels.noc_cycle.engine.pallas_engine`: the fused
               full-cycle Pallas kernel, one launch per cycle with the
               carry in lane layout.  The TPU path.

An engine is built as ``build(stc, topo, fab, binj_dtype) -> run`` from the
run's `SimStatic`, its `Topology`, its `Fabric` and the carry's stamp
dtype, and is called as ``run(carry, ep) -> (carry, counters, probes)``:
``carry`` is ``(subs, mc, phase, outstanding, backlog)``, ``ep`` the
epoch's `EpochInputs`, ``counters`` its `EpochCounters`, and ``probes`` a
`ProbeAcc` when the run's probes are on (else None).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.core.noc import router as rt
from repro.core.noc.faults import FaultStream
from repro.core.noc.traffic import (
    WorkloadProfile,
    injection_rates,
    step_phase_u,
)

Array = jax.Array

BCAP = 64  # per-node source-queue (shader/LSQ) capacity


class MCState(NamedTuple):
    q_meta: Array     # (R, Q) int8 — pending request src | cls << 6
    head: Array       # (R,)
    count: Array      # (R,)
    timer: Array      # (R,) cycles until current service completes
    stage_valid: Array  # (R,) staged reply waiting to inject
    stage_dst: Array
    stage_cls: Array


class EpochCounters(NamedTuple):
    gpu_push: Array           # GPU request injections accepted
    gpu_stall_icnt: Array     # GPU node-cycles blocked at MSHR/injection
    gpu_stall_dram: Array     # GPU dramfull events
    cpu_push: Array
    gpu_done: Array           # completed GPU transactions
    cpu_done: Array
    gpu_gen: Array            # generated GPU demand
    cpu_gen: Array
    lat_sum: Array            # all ejected packets: sum of network latency
    lat_cnt: Array
    cpu_lat_sum: Array        # per-class NETWORK latency of ejected packets
    cpu_lat_cnt: Array        # (excludes DRAM queue wait: the NoC's own share)
    gpu_lat_sum: Array
    gpu_lat_cnt: Array
    moved: Array


def zero_counters() -> EpochCounters:
    z = jnp.int32(0)
    return EpochCounters(z, z, z, z, z, z, z, z, z, z, z, z, z, z, z)


class ProbeAcc(NamedTuple):
    """Flight-recorder accumulators (repro.obs, DESIGN.md §14): the
    per-cycle carry the probes-on cycle scan threads next to the counters.
    The fused engine's twin is `fused.ProbeLanes`; both sample END-of-cycle
    state, so the two agree bitwise."""

    occ: Array      # (S, R, P, V) int32 — summed VC occupancy
    grant: Array    # (S, R) int32 — switch grants, summed over outputs
    deny: Array     # (S, R) int32 — refused requests, summed over outputs
    mcq_sum: Array  # (R,) int32 — summed MC queue depth
    mcq_max: Array  # (R,) int32 — running max MC queue depth


def zero_probe_acc(S: int, R: int, V: int) -> ProbeAcc:
    return ProbeAcc(
        occ=jnp.zeros((S, R, rt.N_PORTS, V), jnp.int32),
        grant=jnp.zeros((S, R), jnp.int32),
        deny=jnp.zeros((S, R), jnp.int32),
        mcq_sum=jnp.zeros((R,), jnp.int32),
        mcq_max=jnp.zeros((R,), jnp.int32),
    )


class Fabric(NamedTuple):
    """The run's network as the engines see it, fixed for the whole run:
    the topology tables and the traced subnet structure (DESIGN.md §10).
    Padded subnet rows are zero-width: excluded from every inject
    want-matrix and never link-active, so no packet can enter them."""

    route: Array        # (R, R) int32 — topology.route
    neighbor: Array     # (R, P) int32
    opposite: Array     # (P,) int32
    is_mc: Array        # (R,) bool — physical: placement never moves MCs
    nodes: Array        # (R,) router ids
    four_subnet: Array  # () bool — class-segregated routing
    sub_ids: Array      # (S,) int32
    sub_enabled: Array  # (S,) bool — live rows of the padded subnet axis
    sub_is_req: Array   # (S,) bool
    sub_is_rep: Array   # (S,) bool
    n_req_subs: Array   # () int32


class CycleRows(NamedTuple):
    """One epoch's per-cycle rows, a leading (L,) cycle axis on each: the
    epoch-invariant hoisting of DESIGN.md §11, scanned as the cycles' xs."""

    cycle: Array    # (L,) int32 — absolute cycle
    u_phase: Array  # (L,) float32 — burst-phase uniforms
    u_gen: Array    # (L, R) float32 — generation uniforms
    dest: Array     # (L, R) int32 — request destinations (MC ids)
    sa: Array       # (L,) int32 — switch-allocation preference
    active: Array   # (L, S) bool — subnet link activation
    gate: Array     # (L,) bool — merged reply inject allowed (not the last)


class EpochInputs(NamedTuple):
    """What an engine reads for one epoch besides the carry."""

    g_vec: Array      # (V,) bool — VCs GPU packets may occupy this epoch
    c_vec: Array      # (V,) bool — VCs CPU packets may occupy
    gpu_masks: Array  # (S, V) bool — g_vec on every subnet
    cpu_masks: Array  # (S, V) bool
    ntype: Array      # (R,) virtual node type from the placement plan
    is_gpu: Array     # (R,) bool
    is_cpu: Array     # (R,) bool
    node_cls: Array   # (R,) class of a node's traffic
    req_sub: Array    # (R,) request subnet of a node's own traffic
    rows: CycleRows
    prof: WorkloadProfile  # this epoch's scalar-leaf profile
    flt: FaultStream       # this epoch's fault masks: link_ok (R, P), ...


def staged_reply_want(fab: Fabric, mc: MCState) -> Array:
    """Want-matrix for staged MC replies (reply subnet of requester
    class c is 2c+1 under class-segregated routing, subnet 1 otherwise)."""
    rep_target = jnp.where(fab.four_subnet, 2 * mc.stage_cls + 1, 1)
    return (
        (fab.sub_ids[:, None] == rep_target[None, :])
        & (mc.stage_valid & fab.is_mc)[None, :]
        & fab.sub_enabled[:, None]
    )


def ref_cycle(stc, fab: Fabric, ep: EpochInputs, carry, x: CycleRows):
    """One simulated cycle of the dense engine: the `lax.scan` body of
    `ref_engine` (``stc`` is the run's `SimStatic`)."""
    is_mc, sub_ids, sub_enabled = fab.is_mc, fab.sub_ids, fab.sub_enabled
    sub_is_req, sub_is_rep = fab.sub_is_req, fab.sub_is_rep
    is_gpu, is_cpu, flt, prof = ep.is_gpu, ep.is_cpu, ep.flt, ep.prof
    S, R = stc.n_subnets, is_mc.shape[0]
    if stc.probe.enabled:
        subs, mc, phase, outstanding, bl_count, cnt, prb = carry
    else:
        subs, mc, phase, outstanding, bl_count, cnt = carry
    cycle, u_ph, u_gen_c, dests, sa_pref, active, gate = x

    # MC acceptance applies to ejections on *request* subnets at MC
    # nodes, judged on the queue depth BEFORE this cycle's service
    # frees a slot.  With multiple request subnets (4-subnet mode)
    # up to S/2 packets can arrive at one MC per cycle, so reserve
    # that many slots.
    mc_space = mc.count <= stc.mc_queue_cap - fab.n_req_subs
    can_accept = jnp.where(is_mc, mc_space, True)  # (R,)
    accept_s = jnp.where(
        sub_is_req[:, None], can_accept[None, :], True
    )

    # ---- 1. MC service: tick timers, move head request -> staging
    # (a stalled MC — flt.mc_ok False — freezes its timer and
    # staging; the queue keeps filling until it back-pressures)
    can_serve = is_mc & (mc.count > 0) & ~mc.stage_valid & flt.mc_ok
    timer = jnp.where(
        can_serve, jnp.maximum(mc.timer - 1, 0), mc.timer
    )
    done = can_serve & (timer == 0)
    hq = mc.head[:, None]
    q_head = jnp.take_along_axis(
        mc.q_meta, hq, axis=1
    )[:, 0].astype(jnp.int32)
    # MC-queue meta is src | cls << META_SRC_SHIFT (router ids fit
    # the shift width — asserted once in rt.device_tables)
    src_out = q_head & ((1 << rt.META_SRC_SHIFT) - 1)
    cls_out = q_head >> rt.META_SRC_SHIFT
    mc = mc._replace(
        head=jnp.where(
            done, (mc.head + 1) % stc.mc_queue_cap, mc.head
        ),
        count=mc.count - done.astype(jnp.int32),
        timer=jnp.where(done, stc.mc_service_period, timer),
        stage_valid=mc.stage_valid | done,
        stage_dst=jnp.where(done, src_out, mc.stage_dst),
        stage_cls=jnp.where(done, cls_out, mc.stage_cls),
    )

    # ---- 2. route/arbitrate every subnet (per-epoch fault masks:
    # a dead link back-pressures, a browned-out router grants
    # nothing — DESIGN.md §16)
    subs, events = rt.router_cycle(
        subs, fab.route, fab.neighbor, fab.opposite,
        ep.gpu_masks, ep.cpu_masks, sa_pref, accept_s, active,
        link_ok=flt.link_ok, router_ok=flt.router_ok,
    )

    # ---- 3. ejection handling
    # request-subnet ejections at MC nodes -> enqueue into MC
    # queues.  A per-subnet exclusive prefix count serializes
    # same-MC arrivals into consecutive ring slots; the write is a
    # dense masked where over (R, Q) (no scatter).
    req_ej = (
        events.eject_valid & sub_is_req[:, None] & is_mc[None, :]
    )  # (S, R)
    arr_i = req_ej.astype(jnp.int32)
    slot_off = jnp.cumsum(arr_i, axis=0) - arr_i
    slot = (
        mc.head[None, :] + mc.count[None, :] + slot_off
    ) % stc.mc_queue_cap
    qmask = req_ej[..., None] & (
        slot[..., None] == jnp.arange(stc.mc_queue_cap)
    )  # (S, R, Q) — at most one subnet hits each slot
    qhit = jnp.any(qmask, axis=0)
    q_val = events.eject_src + (events.eject_cls << rt.META_SRC_SHIFT)
    qm = jnp.sum(jnp.where(qmask, q_val[..., None], 0), axis=0)
    mc = mc._replace(
        q_meta=jnp.where(qhit, qm.astype(jnp.int8), mc.q_meta),
        count=mc.count + jnp.sum(arr_i, axis=0),
    )
    # reply-subnet ejections at source nodes -> complete
    # transactions (masked to live reply rows under S-padding)
    rep_ej = (
        events.eject_valid & sub_is_rep[:, None] & (~is_mc)[None, :]
    )
    rep_done = jnp.any(rep_ej, axis=0)
    outstanding = outstanding - rep_done.astype(jnp.int32)
    rep_cls = jnp.sum(jnp.where(rep_ej, events.eject_cls, 0), axis=0)

    # Fig. 11 packet latency: network time (injection -> ejection).
    # The subtraction runs in the stamp dtype — wraparound-exact
    # for uint16 stamps because ages are <= total - 1 <= 2^16 - 1
    # by construction (the init_sim_state stamp-dtype gate).
    dt = events.eject_binj.dtype
    age = (cycle.astype(dt) - events.eject_binj).astype(jnp.int32)
    ej_lat = jnp.where(events.eject_valid, age, 0)
    cpu_ej = events.eject_valid & (events.eject_cls == 0)
    gpu_ej = events.eject_valid & (events.eject_cls == 1)

    # ---- 4. source generation -> per-node source-queue depth
    phase = step_phase_u(prof, phase, u_ph)
    rates = injection_rates(prof, ep.ntype, phase)
    gen = (u_gen_c < rates) & ~is_mc  # == bernoulli(k_gen, rates)
    # push into the per-node source queue (drop + stall if full)
    can_push = gen & (bl_count < BCAP)
    bl_count = bl_count + can_push.astype(jnp.int32)

    can_inj = (
        (bl_count > 0) & (outstanding < stc.mshr_limit) & ~is_mc
    )

    # ---- 5. ONE merged inject: this cycle's sources (request
    # rows) + the replies staged this cycle (reply rows — the old
    # engine injected those at the TOP of the next cycle; nothing
    # between the two points touches reply-row state, so fusing
    # them here is value-identical; `gate` defers the epoch's last
    # cycle to the next epoch's prologue).
    want_src = (
        (sub_ids[:, None] == ep.req_sub[None, :])
        & can_inj[None, :]
        & sub_enabled[:, None]
    )
    want_rep = staged_reply_want(fab, mc) & gate
    is_req_row = sub_is_req[:, None]
    subs, ok = rt.inject_all(
        subs, want_src | want_rep,
        jnp.where(is_req_row, dests[None, :], mc.stage_dst[None, :]),
        jnp.broadcast_to(fab.nodes, (S, R)),
        jnp.where(
            is_req_row, ep.node_cls[None, :], mc.stage_cls[None, :]
        ),
        jnp.where(is_req_row, cycle, cycle + 1),
        ep.gpu_masks, ep.cpu_masks,
    )
    inj_ok = jnp.any(ok & is_req_row, axis=0)
    mc = mc._replace(
        stage_valid=mc.stage_valid
        & ~jnp.any(ok & ~is_req_row, axis=0)
    )
    bl_count = bl_count - inj_ok.astype(jnp.int32)
    outstanding = outstanding + inj_ok.astype(jnp.int32)

    # ---- 6. counters
    gpu_blocked = is_gpu & (bl_count > 0)  # shader stuck at ICNT
    cnt = EpochCounters(
        gpu_push=cnt.gpu_push
        + jnp.sum((inj_ok & is_gpu).astype(jnp.int32)),
        gpu_stall_icnt=cnt.gpu_stall_icnt
        + jnp.sum(gpu_blocked.astype(jnp.int32)),
        gpu_stall_dram=cnt.gpu_stall_dram + events.dram_block_gpu,
        cpu_push=cnt.cpu_push
        + jnp.sum((inj_ok & is_cpu).astype(jnp.int32)),
        gpu_done=cnt.gpu_done
        + jnp.sum((rep_done & (rep_cls == 1)).astype(jnp.int32)),
        cpu_done=cnt.cpu_done
        + jnp.sum((rep_done & (rep_cls == 0)).astype(jnp.int32)),
        gpu_gen=cnt.gpu_gen + jnp.sum((gen & is_gpu).astype(jnp.int32)),
        cpu_gen=cnt.cpu_gen + jnp.sum((gen & is_cpu).astype(jnp.int32)),
        lat_sum=cnt.lat_sum + jnp.sum(ej_lat),
        lat_cnt=cnt.lat_cnt
        + jnp.sum(events.eject_valid.astype(jnp.int32)),
        cpu_lat_sum=cnt.cpu_lat_sum
        + jnp.sum(jnp.where(cpu_ej, ej_lat, 0)),
        cpu_lat_cnt=cnt.cpu_lat_cnt
        + jnp.sum(cpu_ej.astype(jnp.int32)),
        gpu_lat_sum=cnt.gpu_lat_sum
        + jnp.sum(jnp.where(gpu_ej, ej_lat, 0)),
        gpu_lat_cnt=cnt.gpu_lat_cnt
        + jnp.sum(gpu_ej.astype(jnp.int32)),
        moved=cnt.moved + events.moved,
    )
    if stc.probe.enabled:
        # ---- 7. flight-recorder accumulation from END-of-cycle
        # state (the fused engine samples at the same point)
        prb = ProbeAcc(
            occ=prb.occ + subs.count.astype(jnp.int32),
            grant=prb.grant + events.grant_cnt,
            deny=prb.deny + events.deny_cnt,
            mcq_sum=prb.mcq_sum + mc.count,
            mcq_max=jnp.maximum(prb.mcq_max, mc.count),
        )
        return (
            subs, mc, phase, outstanding, bl_count, cnt, prb
        ), None
    return (subs, mc, phase, outstanding, bl_count, cnt), None


def ref_engine(stc, topo, fab: Fabric, binj_dtype):
    """The dense engine: an epoch's cycles as one `lax.scan` of
    `ref_cycle` (see the module docstring for the interface)."""
    del binj_dtype  # the dense carry holds its stamps in their own dtype
    S, R, V = stc.n_subnets, topo.n_routers, stc.n_vcs
    probe_on = stc.probe.enabled

    def run(carry, ep: EpochInputs):
        subs, mc, phase, outst, backlog = carry
        inner0 = (subs, mc, phase, outst, backlog, zero_counters())
        if probe_on:
            inner0 = inner0 + (zero_probe_acc(S, R, V),)
        with set_xla_metadata(noc_layer="cycle.scan"):
            inner, _ = jax.lax.scan(
                functools.partial(ref_cycle, stc, fab, ep), inner0, ep.rows
            )
        if probe_on:
            subs, mc, phase, outst, backlog, cnt, prb = inner
        else:
            (subs, mc, phase, outst, backlog, cnt), prb = inner, None
        return (subs, mc, phase, outst, backlog), cnt, prb

    return run
