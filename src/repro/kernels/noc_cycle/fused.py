"""Lane-layout twins of the dense cycle engine + the fused full-cycle step.

This module is the pure-jnp half of the fused Pallas cycle kernel
(DESIGN.md §13): every stage of the dense engine's `engine.ref_cycle` — MC
acceptance/service, route + switch allocation, buffer dequeue/enqueue
writes, MC enqueue, reply completion, source generation, the merged inject,
and the metrics counters — rewritten over the packed lane layout below, as
plain 2D (sublane, lane) ops with NO captured constant arrays.
`cycle_step_lanes` is therefore callable both as a regular jitted function
(the dense twin the micro-congruence tests compare stage by stage) and as
the body of a `pallas_call` that loops it over a chunk of simulated
cycles (`kernel.fused_cycle_kernel`).

Lane layout
-----------
Subnet-resolved state rides an `(S * 64)`-lane axis: lane `l` holds
(subnet `l // 64`, router `l % 64`), with routers padded 36 -> 64 so every
subnet block is XY-shift-closed (a mesh neighbor is always `l +/- 1` or
`l +/- width` *within* the block; shifts that cross a block edge land on
padded or masked lanes only).  Per-node state (MC queues, MSHRs, source
backlogs, burst phase, epoch counters) rides one 128-lane block with
routers in lanes `0..R-1`.  Rows are the microarchitectural axes,
flattened C-style like the dense state, except that the buffer slot
axis comes first so each slot is one contiguous (P*V)-row block:

  buf_meta/buf_binj : (B*P*V, S*64)  row = b*P*V + p*V + v
  head/count        : (P*V,   S*64)  row = p*V + v
  rr                : (P,     S*64)
  mcq               : (Q,     128)
  mc                : (6,     128)   rows MC_HEAD..MC_SCLS
  node              : (3,     128)   rows ND_OUTST/ND_BACKLOG/ND_PHASE
  cnt               : (1,     128)   lane i = EpochCounters field i

Everything is int32 on the lane axis; `pack_state`/`unpack_state` convert
to/from the narrow packed dtypes (int16 meta, uint16/int32 stamps, int8
head/count/rr/q_meta) with value-exact casts (meta < 2^15, q_meta < 2^7,
and a uint16 stamp cast reproduces the dense engine's wraparound stores).

Garbage-value conventions (DESIGN.md §11): padded lanes and cross-block
shift reads hold arbitrary values, but every such site is masked (by
`exists`, a false grant, or a false eject) before it can reach a state
write or a counter — the dense engine and this module agree BITWISE on all
carried state and counters, which tests/test_cycle_engine.py pins per stage
and end to end.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.noc.router import META_CLS_SHIFT, META_SRC_SHIFT, SubnetState
from repro.core.noc.topology import (
    N_PORTS,
    NT_CPU,
    NT_GPU,
    NT_MC,
    OPPOSITE,
    PORT_L,
    Topology,
)
from repro.core.noc.traffic import (
    WorkloadProfile,
    injection_rates,
    step_phase_u,
)

Array = jax.Array

R_PAD = 64     # router lanes per subnet block (shift-closed padding)
LANES_R = 128  # per-node state rides one 128-lane block
BIG = 1 << 20  # grant-rank sentinel — must match router.py

OPP = tuple(int(p) for p in OPPOSITE)

# `mc` row indices (mirror engine.MCState field order)
MC_HEAD, MC_COUNT, MC_TIMER, MC_SVALID, MC_SDST, MC_SCLS = range(6)
MC_ROWS = 6
# `node` row indices
ND_OUTST, ND_BACKLOG, ND_PHASE = range(3)
ND_ROWS = 3
# counter lanes — must equal engine.EpochCounters._fields (asserted at build)
COUNTER_FIELDS = (
    "gpu_push", "gpu_stall_icnt", "gpu_stall_dram", "cpu_push",
    "gpu_done", "cpu_done", "gpu_gen", "cpu_gen",
    "lat_sum", "lat_cnt", "cpu_lat_sum", "cpu_lat_cnt",
    "gpu_lat_sum", "gpu_lat_cnt", "moved",
)
N_COUNTERS = len(COUNTER_FIELDS)

# per-cycle xs rows (int / float blocks); XI_MCOK carries the per-epoch MC
# fault mask (DESIGN.md §16) — R-padded then S-tiled like every lane row, and
# consumed on the first 128 lanes only (garbage tiles masked by ntype)
XI_CYCLE, XI_SA, XI_GATE, XI_ACTIVE, XI_DEST, XI_MCOK = range(6)
XI_ROWS = 6
XF_UPHASE, XF_UGEN = range(2)
XF_ROWS = 2
# per-run policy rows (subnet-resolved / per-node)
PS_ENABLED, PS_IS_REQ, PS_IS_REP, PS_REQ_MATCH = range(4)
PS_ROWS = 4
PR_FS, PR_NREQ = range(2)
PR_ROWS = 2


class LaneDims(NamedTuple):
    """Static shape/parameter bundle threaded through every lane stage.

    Hashable (all ints), so it can close over a Pallas kernel body and key
    jit caches.  `stamp_mask` is 0xFFFF when the dense engine carries
    uint16 injection stamps (total cycles <= 2^16) and 0 for int32 stamps;
    the lane engine carries stamps as int32 and applies the mask to the
    latency subtraction, which reproduces the uint16 wraparound arithmetic
    bit for bit (see `cycle_step_lanes`).
    """

    S: int
    R: int
    V: int
    B: int
    Q: int
    width: int
    mc_service_period: int
    mshr_limit: int
    bcap: int
    stamp_mask: int

    @property
    def PV(self) -> int:
        return N_PORTS * self.V

    @property
    def lanes_sr(self) -> int:
        return self.S * R_PAD

    @property
    def deltas(self) -> tuple[int, int, int, int, int]:
        """Lane offset of the neighbor through each port (N, E, S, W, L)."""
        return (-self.width, 1, self.width, -1, 0)


class LaneState(NamedTuple):
    """The whole cycle-scan carry in lane layout (all int32, lanes last)."""

    buf_meta: Array  # (B*P*V, S*64)
    buf_binj: Array  # (B*P*V, S*64)
    head: Array      # (P*V,   S*64)
    count: Array     # (P*V,   S*64)
    rr: Array        # (P,     S*64)
    mcq: Array       # (Q, 128)
    mc: Array        # (MC_ROWS, 128)
    node: Array      # (ND_ROWS, 128)
    cnt: Array       # (1, 128) — counter lanes


class ProbeLanes(NamedTuple):
    """Flight-recorder counter lanes (DESIGN.md §14) — an OPTIONAL extra
    scan carry next to LaneState, present only when `ProbeConfig.enabled`
    compiled the probed kernel variant.  All int32; accumulated per cycle
    from END-of-cycle state so the lane engine agrees bitwise with the
    dense engine's probe accumulators."""

    occ: Array  # (P*V, S*64) — sum over cycles of per-buffer flit count
    arb: Array  # (2, S*64)   — rows (PB_GRANT, PB_DENY) switch outcomes
    mcq: Array  # (2, 128)    — rows (PB_MCQ_SUM, PB_MCQ_MAX) queue depth


PB_GRANT, PB_DENY = 0, 1
PB_MCQ_SUM, PB_MCQ_MAX = 0, 1


def zero_probe(d: LaneDims) -> ProbeLanes:
    return ProbeLanes(
        occ=jnp.zeros((N_PORTS * d.V, d.lanes_sr), jnp.int32),
        arb=jnp.zeros((2, d.lanes_sr), jnp.int32),
        mcq=jnp.zeros((2, LANES_R), jnp.int32),
    )


class LaneArb(NamedTuple):
    """Per-output-port arbitration results as lists of (rows, L) blocks.

    The list-of-rows form keeps the port loop unrolled at trace time: the
    fused step indexes them per port.
    """

    grant: list      # O x (1, L) bool
    winner: list     # O x (1, L) int32
    down_vc: list    # O x (1, L) int32
    deq: Array       # (PV, L) int32 0/1
    new_rr: list     # O x (1, L) int32
    any_req: list    # O x (1, L) bool
    w_cls: list      # O x (1, L) int32
    sel: list        # O x (PV, L) bool — winner one-hot over requesters


def _bsel(c: Array, a: Array, b: Array) -> Array:
    """`jnp.where(c, a, b)` for bool `a`/`b` as mask logic: Mosaic cannot
    lower a select over i1 vectors (it truncates through i8)."""
    return (c & a) | (~c & b)


def stack_rows(rows: list) -> Array:
    """Concatenate bool rows along sublanes as int32 0/1: Mosaic cannot
    relayout i1 vectors through a concatenate."""
    return jnp.concatenate([r.astype(jnp.int32) for r in rows], axis=0)


def lane_arbitrate(
    valid: Array,    # (PV, L) bool — head packet present
    cls: Array,      # (PV, L) int32
    out_port: Array,  # (PV, L) int32
    rr: Array,       # (O, L) int32
    down: Array,     # (O*V, L) int32 — downstream VC occupancy
    exists: Array,   # (O, L) bool
    gmask: Array,    # (V, L) bool
    cmask: Array,    # (V, L) bool
    sa: Array,       # (1, L) int32
    accept: Array,   # (1, L) bool
    active: Array,   # (1, L) bool
    *,
    depth: int,
) -> LaneArb:
    """Switch allocation over lanes.

    Bitwise-identical to `router.arbitrate` on every output (the packed-min
    winner pick, the min-of-iota first-free-VC pick mirroring argmax-of-bool,
    and the garbage-when-ungranted conventions are all mirrored exactly);
    the switch-allocation stage of `cycle_step_lanes`.
    """
    PV, _ = valid.shape
    O = rr.shape[0]
    V = gmask.shape[0]
    P = PV // V
    local = O - 1  # PORT_L is the last port by convention

    pv_iota = jax.lax.broadcasted_iota(jnp.int32, valid.shape, 0)
    v_iota = jax.lax.broadcasted_iota(jnp.int32, gmask.shape, 0)
    is_pref = (cls == sa) | (sa < 0)
    penalty = jnp.where(is_pref, 0, PV)  # (PV, L)

    grants, winners, down_vcs, new_rrs = [], [], [], []
    any_reqs, w_clss, w_ports, sel_ohs = [], [], [], []
    for o in range(O):
        req_o = valid & (out_port == o)                # (PV, L)
        rr_o = rr[o:o + 1, :]                          # (1, L)
        key = (pv_iota - rr_o) % PV + penalty
        # the empty-column sentinel must be a multiple of PV so the garbage
        # winner (% PV) is 0, exactly like the reference's packed min
        packed = jnp.where(req_o, key * PV + pv_iota, PV * (1 << 14))
        win_o = jnp.min(packed, axis=0, keepdims=True) % PV
        any_o = jnp.any(req_o, axis=0, keepdims=True)
        sel_o = pv_iota == win_o                       # (PV, L) one-hot
        wcls_o = jnp.sum(jnp.where(sel_o, cls, 0), axis=0, keepdims=True)

        allowed = _bsel(wcls_o == 1, gmask, cmask)      # (V, L)
        dc_o = down[o * V:(o + 1) * V, :]               # (V, L)
        has = (dc_o < depth) & allowed
        credit_o = jnp.any(has, axis=0, keepdims=True)
        first_vc = jnp.min(jnp.where(has, v_iota, V), axis=0, keepdims=True)
        down_vc_o = jnp.where(credit_o, first_vc, 0)   # argmax-of-bool conv.

        if o == local:
            grant_o = any_o & accept & active
        else:
            exists_o = exists[o:o + 1, :]
            grant_o = any_o & exists_o & credit_o & active

        grants.append(grant_o)
        winners.append(win_o)
        down_vcs.append(down_vc_o)
        any_reqs.append(any_o)
        w_clss.append(wcls_o)
        w_ports.append(win_o // V)
        sel_ohs.append(sel_o)
        new_rrs.append((win_o + 1) % PV)

    # one traversal per input port: keep the lowest-output grant per port
    ranks = [jnp.where(grants[o], o, BIG) for o in range(O)]
    min_rank = []
    for p in range(P):
        mr = jnp.full_like(ranks[0], BIG)
        for o in range(O):
            mr = jnp.minimum(mr, jnp.where(w_ports[o] == p, ranks[o], BIG))
        min_rank.append(mr)
    deq = jnp.zeros(valid.shape, jnp.int32)
    for o in range(O):
        sel_rank = jnp.zeros_like(ranks[o])
        for p in range(P):
            sel_rank = sel_rank + jnp.where(w_ports[o] == p, min_rank[p], 0)
        grants[o] = grants[o] & (ranks[o] == sel_rank)
        deq = deq | (sel_ohs[o] & grants[o]).astype(jnp.int32)
        new_rrs[o] = jnp.where(grants[o], new_rrs[o], rr[o:o + 1, :])

    return LaneArb(
        grant=grants, winner=winners, down_vc=down_vcs, deq=deq,
        new_rr=new_rrs, any_req=any_reqs, w_cls=w_clss, sel=sel_ohs,
    )


# ---------------------------------------------------------------------------
# lane-axis helpers (pure value-level ops, usable inside a kernel body)
# ---------------------------------------------------------------------------

def _shift(x: Array, delta: int) -> Array:
    """out[:, l] = x[:, l + delta] (lane wrap — wrapped reads are masked)."""
    if delta == 0:
        return x
    if delta > 0:
        return jnp.concatenate([x[:, delta:], x[:, :delta]], axis=1)
    d = -delta
    return jnp.concatenate([x[:, -d:], x[:, :-d]], axis=1)


def _tile_r(x: Array, S: int) -> Array:
    """Broadcast a per-node (k, 128) row onto the (k, S*64) subnet lanes."""
    if x.dtype == jnp.bool_:  # lane relayouts of i1 vectors do not lower
        return _tile_r(x.astype(jnp.int32), S) != 0
    return jnp.concatenate([x[:, :R_PAD]] * S, axis=1)


def _pad_r(x: Array) -> Array:
    """Pad a (k, 64) router block back up to the (k, 128) node lanes."""
    if x.dtype == jnp.bool_:
        return _pad_r(x.astype(jnp.int32)) != 0
    k, w = x.shape
    pad = jnp.zeros((k, LANES_R - w), x.dtype)
    return jnp.concatenate([x, pad], axis=1)


def _s_slices(d: LaneDims, x: Array):
    """The per-subnet (k, 64) blocks of a (k, S*64) row."""
    return [x[:, s * R_PAD:(s + 1) * R_PAD] for s in range(d.S)]


# ---------------------------------------------------------------------------
# stage twins — each mirrors one `engine.ref_cycle` stage over lanes
# ---------------------------------------------------------------------------

def mc_service_lanes(
    d: LaneDims, mc: Array, mcq: Array, ntype: Array,
    mc_ok: Array | None = None,
):
    """MC service tick: timers, head request -> staging (ref_cycle stage 1).

    Returns the six updated `mc` rows; the queue head peek is a Q-step
    one-hot sum (head is always in [0, Q), so it equals the dense
    take_along_axis gather exactly).

    `mc_ok` (1, 128) bool is the MC-stall fault mask (DESIGN.md §16): a
    False lane freezes service (timer, staging and dequeue all hold)
    while the queue keeps filling — the lane twin of the dense engine's
    `can_serve & mc_ok` gate.  None behaves as all-True.
    """
    i32 = jnp.int32
    is_mc = ntype == NT_MC
    head = mc[MC_HEAD:MC_HEAD + 1]
    count = mc[MC_COUNT:MC_COUNT + 1]
    svalid = mc[MC_SVALID:MC_SVALID + 1] != 0

    can_serve = is_mc & (count > 0) & ~svalid
    if mc_ok is not None:
        can_serve = can_serve & mc_ok
    timer = jnp.where(
        can_serve, jnp.maximum(mc[MC_TIMER:MC_TIMER + 1] - 1, 0),
        mc[MC_TIMER:MC_TIMER + 1],
    )
    done = can_serve & (timer == 0)
    q_head = jnp.zeros_like(head)
    for q in range(d.Q):
        q_head = q_head + jnp.where(head == q, mcq[q:q + 1], 0)
    src_out = q_head & ((1 << META_SRC_SHIFT) - 1)
    cls_out = q_head >> META_SRC_SHIFT
    head = jnp.where(done, (head + 1) % d.Q, head)
    count = count - done.astype(i32)
    timer = jnp.where(done, d.mc_service_period, timer)
    sdst = jnp.where(done, src_out, mc[MC_SDST:MC_SDST + 1])
    scls = jnp.where(done, cls_out, mc[MC_SCLS:MC_SCLS + 1])
    svalid = svalid | done
    return head, count, timer, svalid, sdst, scls


def router_stage_lanes(
    d: LaneDims,
    buf_meta: Array, buf_binj: Array, head: Array, count: Array, rr: Array,
    gmask: Array, cmask: Array, sa: Array, accept: Array, active: Array,
    route: Array, exists: Array,
):
    """One full router cycle over lanes (ref_cycle stage 2 / router_cycle).

    Head peeks are B-step one-hot sums over the slot-major buffer blocks,
    the route lookup is an R-step one-hot sum over the route table rows,
    and every neighbor gather (downstream credit, upstream traversal) is
    a static lane shift: input port p of lane l is driven only by lane
    `l + deltas[p]`'s output port `OPP[p]`.  Cross-block and mesh-edge
    shift reads are garbage but always masked by `exists` before use.

    Returns the updated buffer rows plus the per-lane event rows
    (ej, eject_src, eject_cls, eject_binj), the (moved, dram_block_gpu)
    scalars the counter stage consumes, and the per-lane probe rows
    (grant_cnt, deny_cnt) — switch-allocation outcomes summed over output
    ports, the lane twin of CycleEvents.grant_cnt/deny_cnt (DESIGN.md
    §14; dead code when probes are off).
    """
    i32 = jnp.int32
    V, B, P, PV = d.V, d.B, N_PORTS, d.PV

    # --- peek head-of-line packets
    meta_h = jnp.zeros_like(head)
    binj_h = jnp.zeros_like(head)
    for b in range(B):
        at_b = head == b
        meta_h = meta_h + jnp.where(at_b, buf_meta[b * PV:(b + 1) * PV], 0)
        binj_h = binj_h + jnp.where(at_b, buf_binj[b * PV:(b + 1) * PV], 0)
    dest_h = meta_h & ((1 << META_SRC_SHIFT) - 1)
    cls_h = meta_h >> META_CLS_SHIFT
    valid = count > 0

    # --- route: desired output port of each head packet
    out_port = jnp.zeros_like(meta_h)
    for dst in range(d.R):
        out_port = out_port + jnp.where(dest_h == dst, route[dst:dst + 1], 0)

    # --- downstream VC occupancy: neighbor through output o is lane
    # l + deltas[o]; its input port facing us is OPP[o]
    down = jnp.concatenate(
        [
            _shift(count[OPP[o] * V:(OPP[o] + 1) * V], d.deltas[o])
            for o in range(P)
        ],
        axis=0,
    )

    arb = lane_arbitrate(
        valid, cls_h, out_port, rr, down, exists, gmask, cmask,
        sa, accept, active, depth=B,
    )

    # --- dequeue winners, advance RR pointers past them
    deq = arb.deq != 0
    head2 = jnp.where(deq, (head + 1) % B, head)
    count2 = count - arb.deq
    rr2 = jnp.concatenate(arb.new_rr, axis=0)

    # --- winner packet fields per output (one-hot reduction; like the dense
    # gsel, a garbage winner (any_req false -> winner 0) selects row 0's
    # real value, so even the garbage sites agree with the reference)
    w_meta = jnp.concatenate(
        [
            jnp.sum(jnp.where(arb.sel[o], meta_h, 0), axis=0, keepdims=True)
            for o in range(P)
        ],
        axis=0,
    )
    w_binj = jnp.concatenate(
        [
            jnp.sum(jnp.where(arb.sel[o], binj_h, 0), axis=0, keepdims=True)
            for o in range(P)
        ],
        axis=0,
    )
    w_src = (w_meta >> META_SRC_SHIFT) & (
        (1 << (META_CLS_SHIFT - META_SRC_SHIFT)) - 1
    )

    # --- ejections: only the Local output column can eject
    ej = arb.grant[PORT_L]
    eject_src = w_src[PORT_L:PORT_L + 1]
    eject_cls = arb.w_cls[PORT_L]
    eject_binj = w_binj[PORT_L:PORT_L + 1]
    moved = jnp.sum(stack_rows(arb.grant))
    blocked_local = arb.any_req[PORT_L] & ~accept
    dram_block_gpu = jnp.sum(
        (blocked_local & (arb.w_cls[PORT_L] == 1)).astype(i32)
    )

    # --- probe rows: grants and refusals per lane, summed over outputs
    # (padded lanes have no valid heads -> any_req false -> both stay 0)
    grant_cnt = sum(arb.grant[o].astype(i32) for o in range(P))
    deny_cnt = sum(
        (arb.any_req[o] & ~arb.grant[o]).astype(i32) for o in range(P)
    )

    # --- link traversals as dense pulls through static lane shifts
    tail = (head2 + count2) % B
    v_iota = jax.lax.broadcasted_iota(i32, (V, head.shape[1]), 0)
    vmask_p, in_meta_p, in_binj_p = [], [], []
    for p in range(P):
        po = OPP[p]
        dl = d.deltas[p]
        in_ok = _shift(arb.grant[po], dl) & exists[p:p + 1]
        in_vc = _shift(arb.down_vc[po], dl)
        vmask_p.append(in_ok & (in_vc == v_iota))                 # (V, L)
        in_meta_p.append(jnp.broadcast_to(
            _shift(w_meta[po:po + 1], dl), v_iota.shape))
        in_binj_p.append(jnp.broadcast_to(
            _shift(w_binj[po:po + 1], dl), v_iota.shape))
    vmask = stack_rows(vmask_p) != 0                              # (PV, L)
    in_meta = jnp.concatenate(in_meta_p, axis=0)
    in_binj = jnp.concatenate(in_binj_p, axis=0)
    new_meta, new_binj = [], []
    for b in range(B):
        bm = vmask & (tail == b)
        blk = slice(b * PV, (b + 1) * PV)
        new_meta.append(jnp.where(bm, in_meta, buf_meta[blk]))
        new_binj.append(jnp.where(bm, in_binj, buf_binj[blk]))
    buf_meta2 = jnp.concatenate(new_meta, axis=0)
    buf_binj2 = jnp.concatenate(new_binj, axis=0)
    count3 = count2 + vmask.astype(i32)

    return (
        buf_meta2, buf_binj2, head2, count3, rr2,
        ej, eject_src, eject_cls, eject_binj, moved, dram_block_gpu,
        grant_cnt, deny_cnt,
    )


def inject_lanes(
    d: LaneDims,
    buf_meta: Array, buf_binj: Array, head: Array, count: Array,
    want: Array, dest: Array, src: Array, cls: Array, binj: Array,
    gmask: Array, cmask: Array,
):
    """Inject at the Local port of every lane (twin of `router.inject_all`).

    The first-free-VC pick is a min-of-iota mirroring the dense argmax-of-
    bool (VC 0 when no space, gated by `ok`).  Returns the updated buffer
    rows and the per-lane `ok` row.
    """
    i32 = jnp.int32
    V, B = d.V, d.B
    l0 = PORT_L * V

    lcount = count[l0:l0 + V]                         # (V, L)
    allowed = _bsel(cls == 1, gmask, cmask)           # (V, L)
    has = (lcount < B) & allowed
    v_iota = jax.lax.broadcasted_iota(i32, has.shape, 0)
    first = jnp.min(jnp.where(has, v_iota, V), axis=0, keepdims=True)
    any_has = jnp.any(has, axis=0, keepdims=True)
    vc = jnp.where(any_has, first, 0)
    ok = want & any_has

    tail = (head[l0:l0 + V] + lcount) % B
    meta = dest + (src << META_SRC_SHIFT) + (cls << META_CLS_SHIFT)
    vmask = ok & (vc == v_iota)                       # (V, L)
    # the Local port is the last port, so its V rows close every b-block
    new_meta, new_binj = [], []
    for b in range(B):
        bm = vmask & (tail == b)
        lo, hi = b * d.PV + l0, (b + 1) * d.PV
        new_meta += [buf_meta[b * d.PV:lo],
                     jnp.where(bm, meta, buf_meta[lo:hi])]
        new_binj += [buf_binj[b * d.PV:lo],
                     jnp.where(bm, binj, buf_binj[lo:hi])]
    buf_meta2 = jnp.concatenate(new_meta, axis=0)
    buf_binj2 = jnp.concatenate(new_binj, axis=0)
    count2 = jnp.concatenate(
        [count[:l0], lcount + vmask.astype(i32)], axis=0
    )
    return buf_meta2, buf_binj2, count2, ok


def mc_enqueue_lanes(
    d: LaneDims, mcq: Array, head: Array, count: Array,
    req_ej: Array, q_val: Array,
):
    """Enqueue request ejections into MC ring slots (ref_cycle stage 3a).

    The per-subnet exclusive prefix over the S blocks serializes same-MC
    arrivals into consecutive slots, matching the dense cumsum exactly.
    Returns (mcq', count', arrivals) on the 64-lane router block.
    """
    i32 = jnp.int32
    head64 = head[:, :R_PAD]
    cnt64 = count[:, :R_PAD]
    off = jnp.zeros_like(head64)
    arr_s, slot_s, val_s = [], [], []
    for s in range(d.S):
        a = req_ej[:, s * R_PAD:(s + 1) * R_PAD]
        arr_s.append(a)
        slot_s.append((head64 + cnt64 + off) % d.Q)
        val_s.append(q_val[:, s * R_PAD:(s + 1) * R_PAD])
        off = off + a.astype(i32)
    rows = []
    for q in range(d.Q):
        hit = jnp.zeros(head64.shape, jnp.bool_)
        val = jnp.zeros_like(head64)
        for s in range(d.S):
            m = arr_s[s] & (slot_s[s] == q)
            hit = hit | m
            val = val + jnp.where(m, val_s[s], 0)
        old = mcq[q:q + 1]
        new64 = jnp.where(hit, val, old[:, :R_PAD])
        rows.append(jnp.concatenate([new64, old[:, R_PAD:]], axis=1))
    return jnp.concatenate(rows, axis=0), cnt64 + off, off


def counter_row(d: LaneDims, values: dict) -> Array:
    """Scatter the 15 counter increments onto their `cnt` lanes.

    `values` maps every COUNTER_FIELDS name to its scalar increment; the
    row add is a 15-step one-hot sum so the kernel never materializes a
    scatter.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, LANES_R), 1)
    inc = jnp.zeros((1, LANES_R), jnp.int32)
    for i, name in enumerate(COUNTER_FIELDS):
        inc = inc + jnp.where(iota == i, values[name], 0)
    return inc


def cycle_step_lanes(
    d: LaneDims,
    st: LaneState,
    xi: Array,      # (XI_ROWS, S*64) int32 — per-cycle xs
    xf: Array,      # (XF_ROWS, 128) float32 — per-cycle uniforms
    gmask: Array,   # (V, S*64) int32 0/1 — epoch VC masks
    cmask: Array,   # (V, S*64) int32 0/1
    prof: Array,    # (5, 128) float32 — WorkloadProfile rows
    pol_sr: Array,  # (PS_ROWS, S*64) int32 — subnet structure rows
    pol_r: Array,   # (PR_ROWS, 128) int32
    ntype: Array,   # (1, 128) int32 (padded lanes -1)
    route: Array,   # (R, S*64) int32 — route[dst, lane] table
    exists: Array,  # (P, S*64) int32 0/1 — link exists through port p
    probe: ProbeLanes | None = None,
):
    """ONE full simulated NoC cycle over lanes — the fused kernel body.

    Stage order and semantics mirror `engine.ref_cycle` exactly; every
    input/output is a 2D (sublane, lane) int32/float32 block so the same
    function traces as a Pallas kernel body and as a plain jitted twin.

    With `probe` (the flight-recorder carry) the return value is
    (LaneState, ProbeLanes) instead of a bare LaneState; the probed
    variant is its own compiled program, so the probes-off kernel stays
    byte-identical to before.
    """
    i32 = jnp.int32
    S, Q = d.S, d.Q

    cycle = xi[XI_CYCLE:XI_CYCLE + 1]
    sa = xi[XI_SA:XI_SA + 1]
    gate = xi[XI_GATE:XI_GATE + 1] != 0
    active = xi[XI_ACTIVE:XI_ACTIVE + 1] != 0
    dests = xi[XI_DEST:XI_DEST + 1]
    mc_ok = xi[XI_MCOK:XI_MCOK + 1, :LANES_R] != 0
    u_ph = xf[XF_UPHASE:XF_UPHASE + 1]
    u_gen = xf[XF_UGEN:XF_UGEN + 1]

    gmask_b = gmask != 0
    cmask_b = cmask != 0
    sub_en = pol_sr[PS_ENABLED:PS_ENABLED + 1] != 0
    sub_req = pol_sr[PS_IS_REQ:PS_IS_REQ + 1] != 0
    sub_rep = pol_sr[PS_IS_REP:PS_IS_REP + 1] != 0
    req_match = pol_sr[PS_REQ_MATCH:PS_REQ_MATCH + 1] != 0
    fs_sr = _tile_r(pol_r[PR_FS:PR_FS + 1], S) != 0
    n_req = pol_r[PR_NREQ:PR_NREQ + 1]

    is_mc_r = ntype == NT_MC
    is_gpu_r = ntype == NT_GPU
    is_cpu_r = ntype == NT_CPU
    is_mc_sr = _tile_r(is_mc_r, S)
    node_cls_sr = _tile_r(jnp.where(is_gpu_r, 1, 0), S)
    sub_id_sr = jax.lax.broadcasted_iota(i32, cycle.shape, 1) // R_PAD

    # ---- MC acceptance: queue depth BEFORE this cycle's service
    mc_count0 = st.mc[MC_COUNT:MC_COUNT + 1]
    can_accept = ~is_mc_r | (mc_count0 <= Q - n_req)
    accept = ~sub_req | _tile_r(can_accept, S)

    # ---- 1. MC service
    mc_head, mc_count, mc_timer, svalid, sdst, scls = mc_service_lanes(
        d, st.mc, st.mcq, ntype, mc_ok
    )

    # ---- 2. route/arbitrate every subnet
    (buf_meta, buf_binj, head, count, rr,
     ej, eject_src, eject_cls, eject_binj, moved, dram_gpu,
     grant_cnt, deny_cnt,
     ) = router_stage_lanes(
        d, st.buf_meta, st.buf_binj, st.head, st.count, st.rr,
        gmask_b, cmask_b, sa, accept, active, route, exists,
    )

    # ---- 3a. request ejections at MCs -> MC queues
    req_ej = ej & sub_req & is_mc_sr
    q_val = eject_src + (eject_cls << META_SRC_SHIFT)
    mcq, mc_count64, _ = mc_enqueue_lanes(
        d, st.mcq, mc_head, mc_count, req_ej, q_val
    )
    mc_count = jnp.concatenate([mc_count64, mc_count[:, R_PAD:]], axis=1)

    # ---- 3b. reply ejections at sources -> complete transactions
    rep_ej = ej & sub_rep & ~is_mc_sr
    rep_done64 = jnp.zeros((1, R_PAD), jnp.bool_)
    rep_cls64 = jnp.zeros((1, R_PAD), i32)
    for s in range(S):
        r_s = rep_ej[:, s * R_PAD:(s + 1) * R_PAD]
        rep_done64 = rep_done64 | r_s
        rep_cls64 = rep_cls64 + jnp.where(
            r_s, eject_cls[:, s * R_PAD:(s + 1) * R_PAD], 0
        )
    rep_done = _pad_r(rep_done64)
    rep_cls = _pad_r(rep_cls64)
    outstanding = st.node[ND_OUTST:ND_OUTST + 1] - rep_done.astype(i32)

    # ---- 3c. packet latency: the masked subtraction reproduces the dense
    # engine's stamp-dtype arithmetic (uint16 wraparound when stamp_mask
    # is 0xFFFF, plain int32 otherwise)
    age = cycle - eject_binj
    if d.stamp_mask:
        age = age & d.stamp_mask
    ej_lat = jnp.where(ej, age, 0)
    cpu_ej = ej & (eject_cls == 0)
    gpu_ej = ej & (eject_cls == 1)

    # ---- 4. source generation -> per-node source-queue depth
    prof_t = WorkloadProfile(
        *(prof[i:i + 1] for i in range(len(WorkloadProfile._fields)))
    )
    phase = step_phase_u(prof_t, st.node[ND_PHASE:ND_PHASE + 1], u_ph)
    rates = injection_rates(prof_t, ntype, phase)
    gen = (u_gen < rates) & ~is_mc_r
    backlog = st.node[ND_BACKLOG:ND_BACKLOG + 1]
    can_push = gen & (backlog < d.bcap)
    backlog = backlog + can_push.astype(i32)
    can_inj = (backlog > 0) & (outstanding < d.mshr_limit) & ~is_mc_r

    # ---- 5. ONE merged inject: sources (request rows) + staged replies
    want_src = req_match & _tile_r(can_inj, S)
    rep_target = jnp.where(fs_sr, 2 * _tile_r(scls, S) + 1, 1)
    want_rep = (
        (sub_id_sr == rep_target)
        & _tile_r(svalid & is_mc_r, S)
        & sub_en & gate
    )
    dest_i = jnp.where(sub_req, dests, _tile_r(sdst, S))
    src_i = jax.lax.broadcasted_iota(i32, cycle.shape, 1) % R_PAD
    cls_i = jnp.where(sub_req, node_cls_sr, _tile_r(scls, S))
    binj_i = jnp.where(sub_req, cycle, cycle + 1)
    buf_meta, buf_binj, count, ok = inject_lanes(
        d, buf_meta, buf_binj, head, count,
        want_src | want_rep, dest_i, src_i, cls_i, binj_i,
        gmask_b, cmask_b,
    )
    inj_ok64 = jnp.zeros((1, R_PAD), jnp.bool_)
    stage_hit64 = jnp.zeros((1, R_PAD), jnp.bool_)
    for s in range(S):
        ok_s = ok[:, s * R_PAD:(s + 1) * R_PAD]
        req_s = sub_req[:, s * R_PAD:(s + 1) * R_PAD]
        inj_ok64 = inj_ok64 | (ok_s & req_s)
        stage_hit64 = stage_hit64 | (ok_s & ~req_s)
    inj_ok = _pad_r(inj_ok64)
    svalid = svalid & ~_pad_r(stage_hit64)
    backlog = backlog - inj_ok.astype(i32)
    outstanding = outstanding + inj_ok.astype(i32)

    # ---- 6. counters
    gpu_blocked = is_gpu_r & (backlog > 0)
    inc = counter_row(d, {
        "gpu_push": jnp.sum((inj_ok & is_gpu_r).astype(i32)),
        "gpu_stall_icnt": jnp.sum(gpu_blocked.astype(i32)),
        "gpu_stall_dram": dram_gpu,
        "cpu_push": jnp.sum((inj_ok & is_cpu_r).astype(i32)),
        "gpu_done": jnp.sum((rep_done & (rep_cls == 1)).astype(i32)),
        "cpu_done": jnp.sum((rep_done & (rep_cls == 0)).astype(i32)),
        "gpu_gen": jnp.sum((gen & is_gpu_r).astype(i32)),
        "cpu_gen": jnp.sum((gen & is_cpu_r).astype(i32)),
        "lat_sum": jnp.sum(ej_lat),
        "lat_cnt": jnp.sum(ej.astype(i32)),
        "cpu_lat_sum": jnp.sum(jnp.where(cpu_ej, ej_lat, 0)),
        "cpu_lat_cnt": jnp.sum(cpu_ej.astype(i32)),
        "gpu_lat_sum": jnp.sum(jnp.where(gpu_ej, ej_lat, 0)),
        "gpu_lat_cnt": jnp.sum(gpu_ej.astype(i32)),
        "moved": moved,
    })

    mc_rows = jnp.concatenate(
        [mc_head, mc_count, mc_timer, svalid.astype(i32), sdst, scls], axis=0
    )
    node_rows = jnp.concatenate(
        [outstanding, backlog, phase.astype(i32)], axis=0
    )
    st2 = LaneState(
        buf_meta=buf_meta, buf_binj=buf_binj, head=head, count=count, rr=rr,
        mcq=mcq, mc=mc_rows, node=node_rows, cnt=st.cnt + inc,
    )
    if probe is None:
        return st2
    # ---- 7. flight-recorder accumulation from END-of-cycle state — the
    # lane twin of the dense engine's ProbeAcc update (engine.ref_cycle)
    probe2 = ProbeLanes(
        occ=probe.occ + count,
        arb=probe.arb + jnp.concatenate([grant_cnt, deny_cnt], axis=0),
        mcq=jnp.concatenate(
            [
                probe.mcq[PB_MCQ_SUM:PB_MCQ_SUM + 1] + mc_count,
                jnp.maximum(probe.mcq[PB_MCQ_MAX:PB_MCQ_MAX + 1], mc_count),
            ],
            axis=0,
        ),
    )
    return st2, probe2


# ---------------------------------------------------------------------------
# packing: dense sim state <-> lane layout, plus the per-run constant rows
# ---------------------------------------------------------------------------

def lane_dims(
    *, S: int, R: int, V: int, B: int, Q: int, width: int,
    mc_service_period: int, mshr_limit: int, bcap: int, stamp_mask: int,
) -> LaneDims:
    assert R <= R_PAD <= LANES_R, (R, R_PAD, LANES_R)
    assert (S * R_PAD) % LANES_R == 0, (S, R_PAD)
    return LaneDims(
        S=S, R=R, V=V, B=B, Q=Q, width=width,
        mc_service_period=mc_service_period, mshr_limit=mshr_limit,
        bcap=bcap, stamp_mask=stamp_mask,
    )


def run_consts(d: LaneDims, topo: Topology):
    """Constant lane tables (route, link-exists, node-type) as device rows.

    Passed to the kernel as INPUT refs — Pallas kernel bodies may not
    capture non-scalar constant arrays.
    """
    route = np.zeros((d.R, R_PAD), np.int32)
    route[:, :d.R] = topo.route.T        # route[dst, r] = port at r toward dst
    route = np.tile(route, (1, d.S))
    exists = np.zeros((N_PORTS, R_PAD), np.int32)
    exists[:, :d.R] = (topo.neighbor >= 0).T
    exists = np.tile(exists, (1, d.S))
    ntype = np.full((1, LANES_R), -1, np.int32)
    ntype[0, :d.R] = topo.node_type
    return jnp.asarray(route), jnp.asarray(exists), jnp.asarray(ntype)


def placement_rows(d: LaneDims, ntype_e: Array) -> Array:
    """Per-epoch node-type lane row from a traced placement (DESIGN.md §17).

    The lane layout's node-type row was a run constant (`run_consts`);
    with placement the virtual node type is per-epoch DATA, so the epoch
    body rebuilds this (1, 128) row — padded lanes carry -1, exactly the
    constant row's convention, so every `ntype == NT_*` compare in the
    kernel stays false on padding.  Identity placement reproduces the
    `run_consts` row bit-for-bit."""
    pad = jnp.full((LANES_R - d.R,), -1, jnp.int32)
    return jnp.concatenate([ntype_e.astype(jnp.int32), pad])[None, :]


def policy_rows(
    d: LaneDims,
    sub_enabled: Array, sub_is_req: Array, sub_is_rep: Array,  # (S,) bool
    req_match: Array,                                          # (S, R) bool
    fs: Array, n_req_subs: Array,                              # () scalars
):
    """Subnet-structure rows: (PS_ROWS, S*64) + (PR_ROWS, 128)."""
    i32 = jnp.int32

    def sr_of_s(x):
        return jnp.repeat(x.astype(i32), R_PAD)[None, :]

    rm = jnp.pad(req_match.astype(i32), ((0, 0), (0, R_PAD - d.R)))
    pol_sr = jnp.concatenate(
        [sr_of_s(sub_enabled), sr_of_s(sub_is_req), sr_of_s(sub_is_rep),
         rm.reshape(1, d.lanes_sr)],
        axis=0,
    )
    pol_r = jnp.stack(
        [
            jnp.broadcast_to(fs.astype(i32), (LANES_R,)),
            jnp.broadcast_to(n_req_subs.astype(i32), (LANES_R,)),
        ],
        axis=0,
    )
    return pol_sr, pol_r


def mask_rows(d: LaneDims, g_vec: Array, c_vec: Array):
    """Epoch VC-partition masks (V,) -> (V, S*64) int32 rows."""
    i32 = jnp.int32
    gm = jnp.broadcast_to(g_vec.astype(i32)[:, None], (d.V, d.lanes_sr))
    cm = jnp.broadcast_to(c_vec.astype(i32)[:, None], (d.V, d.lanes_sr))
    return gm, cm


def prof_rows(prof: WorkloadProfile) -> Array:
    """This epoch's scalar profile leaves broadcast to (n_fields, 128)."""
    return jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(leaf, jnp.float32), (LANES_R,))
            for leaf in prof
        ],
        axis=0,
    )


def cycle_xs(
    d: LaneDims,
    cycles: Array,      # (E,) int32
    u_phase: Array,     # (E,) float32
    u_gen: Array,       # (E, R) float32
    dests_all: Array,   # (E, R) int32
    sa_all: Array,      # (E,) int32
    active_all: Array,  # (E, S) bool
    rep_gate: Array,    # (E,) bool
    router_ok: Array | None = None,  # (R,) bool — epoch fault mask
    mc_ok: Array | None = None,      # (R,) bool — epoch fault mask
):
    """Per-cycle scan xs in lane layout: (E, XI_ROWS, S*64) + (E, XF_ROWS, 128).

    The epoch-constant fault masks (DESIGN.md §16) ride the xs rows:
    `router_ok` ANDs into the XI_ACTIVE row (a browned-out router grants
    nothing in any subnet; padded lanes carry 0, which is inert — they
    never hold valid heads), `mc_ok` becomes the XI_MCOK row.  None
    behaves as all-True.
    """
    E = cycles.shape[0]
    L = d.lanes_sr
    i32 = jnp.int32

    def b_sr(x):
        return jnp.broadcast_to(x.astype(i32)[:, None], (E, L))

    def r_row(x):  # (R,) -> (L,) lane row: pad to R_PAD, tile over subnets
        return jnp.tile(jnp.pad(x.astype(i32), (0, R_PAD - d.R)), d.S)

    dest_rows = jnp.tile(
        jnp.pad(dests_all.astype(i32), ((0, 0), (0, R_PAD - d.R))), (1, d.S)
    )
    act_rows = jnp.repeat(active_all.astype(i32), R_PAD, axis=1)
    if router_ok is not None:
        act_rows = act_rows * r_row(router_ok)[None, :]
    mcok_src = (
        jnp.ones((d.R,), i32) if mc_ok is None else mc_ok
    )
    mcok_rows = jnp.broadcast_to(r_row(mcok_src)[None, :], (E, L))
    xi = jnp.stack(
        [b_sr(cycles), b_sr(sa_all), b_sr(rep_gate), act_rows, dest_rows,
         mcok_rows],
        axis=1,
    )
    u_ph = jnp.broadcast_to(
        u_phase.astype(jnp.float32)[:, None], (E, LANES_R)
    )
    u_g = jnp.pad(
        u_gen.astype(jnp.float32), ((0, 0), (0, LANES_R - d.R))
    )
    xf = jnp.stack([u_ph, u_g], axis=1)
    return xi, xf


def _to_sr_rows(d: LaneDims, x: Array) -> Array:
    """(S, R, *tail) -> (prod(tail), S*64) int32, tail flattened C-style."""
    tail = x.shape[2:]
    rows = 1
    for t in tail:
        rows *= t
    x = x.astype(jnp.int32).reshape(d.S, d.R, rows)
    x = jnp.moveaxis(x, 2, 0)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, R_PAD - d.R)))
    return x.reshape(rows, d.lanes_sr)


def _from_sr_rows(d: LaneDims, x: Array, tail: tuple, dtype) -> Array:
    rows = x.shape[0]
    x = x.reshape(rows, d.S, R_PAD)[:, :, :d.R]
    return jnp.moveaxis(x, 0, 2).reshape((d.S, d.R) + tail).astype(dtype)


def _to_r_row(d: LaneDims, x: Array) -> Array:
    return jnp.pad(x.astype(jnp.int32), (0, LANES_R - d.R))[None, :]


def pack_state(
    d: LaneDims, subs: SubnetState, mc, outstanding: Array,
    backlog: Array, phase: Array,
) -> LaneState:
    """Dense sim carry -> lane layout (all int32; uint16 stamps widen
    value-exactly, so in-lane stamps stay full-width until unpack)."""
    mcq = jnp.pad(
        mc.q_meta.astype(jnp.int32).T, ((0, 0), (0, LANES_R - d.R))
    )
    mc_rows = jnp.concatenate(
        [
            _to_r_row(d, mc.head), _to_r_row(d, mc.count),
            _to_r_row(d, mc.timer), _to_r_row(d, mc.stage_valid),
            _to_r_row(d, mc.stage_dst), _to_r_row(d, mc.stage_cls),
        ],
        axis=0,
    )
    node_rows = jnp.concatenate(
        [
            _to_r_row(d, outstanding), _to_r_row(d, backlog),
            jnp.broadcast_to(phase.astype(jnp.int32), (1, LANES_R)),
        ],
        axis=0,
    )
    return LaneState(
        buf_meta=_to_sr_rows(d, jnp.moveaxis(subs.buf_meta, -1, 2)),
        buf_binj=_to_sr_rows(d, jnp.moveaxis(subs.buf_binj, -1, 2)),
        head=_to_sr_rows(d, subs.head),
        count=_to_sr_rows(d, subs.count),
        rr=_to_sr_rows(d, subs.rr_ptr),
        mcq=mcq,
        mc=mc_rows,
        node=node_rows,
        cnt=jnp.zeros((1, LANES_R), jnp.int32),
    )


def unpack_state(d: LaneDims, ls: LaneState, mc_cls, binj_dtype):
    """Lane layout -> dense sim carry.  `mc_cls` is the dense MCState class
    (`repro.core.noc.engine.MCState`, passed in by the caller); the int32 ->
    narrow-dtype casts reproduce the dense engine's stored values exactly
    (meta < 2^15, q_meta < 2^7, and the uint16 stamp cast IS the dense
    engine's wraparound store)."""
    P, V, B = N_PORTS, d.V, d.B
    subs = SubnetState(
        buf_meta=jnp.moveaxis(
            _from_sr_rows(d, ls.buf_meta, (B, P, V), jnp.int16), 2, -1),
        buf_binj=jnp.moveaxis(
            _from_sr_rows(d, ls.buf_binj, (B, P, V), binj_dtype), 2, -1),
        head=_from_sr_rows(d, ls.head, (P, V), jnp.int8),
        count=_from_sr_rows(d, ls.count, (P, V), jnp.int8),
        rr_ptr=_from_sr_rows(d, ls.rr, (P,), jnp.int8),
    )
    mc = mc_cls(
        q_meta=ls.mcq[:, :d.R].T.astype(jnp.int8),
        head=ls.mc[MC_HEAD, :d.R],
        count=ls.mc[MC_COUNT, :d.R],
        timer=ls.mc[MC_TIMER, :d.R],
        stage_valid=ls.mc[MC_SVALID, :d.R] != 0,
        stage_dst=ls.mc[MC_SDST, :d.R],
        stage_cls=ls.mc[MC_SCLS, :d.R],
    )
    outstanding = ls.node[ND_OUTST, :d.R]
    backlog = ls.node[ND_BACKLOG, :d.R]
    phase = ls.node[ND_PHASE, 0]
    return subs, mc, outstanding, backlog, phase


def unpack_probe(d: LaneDims, pb: ProbeLanes):
    """Probe lanes -> dense probe accumulators, all int32:
    (occ (S,R,P,V), grant (S,R), deny (S,R), mcq_sum (R,), mcq_max (R,)).

    Padded lanes never accumulate (no heads, no links, no MCs), so the
    [:R] slices are exact — not a masked approximation."""
    occ = _from_sr_rows(d, pb.occ, (N_PORTS, d.V), jnp.int32)
    arb = _from_sr_rows(d, pb.arb, (2,), jnp.int32)
    return (
        occ,
        arb[..., PB_GRANT],
        arb[..., PB_DENY],
        pb.mcq[PB_MCQ_SUM, :d.R],
        pb.mcq[PB_MCQ_MAX, :d.R],
    )
