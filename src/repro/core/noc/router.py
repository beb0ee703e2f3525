"""Flit-level router microarchitecture, fully vectorized over (subnet, router).

Models the paper's network (Fig. 6): per-input-port VC FIFOs with credit flow
control, XY routing, VC allocation at the downstream router constrained by
the class partition (Fig. 7), and switch allocation that is either
round-robin or the KF-triggered 2:1 GPU-priority pattern (Fig. 8).

Packed-lane state layout (DESIGN.md §11) — every subnet's buffers live in one
(S, R, P, V, B) block with narrow dtypes on the scan-bound hot loop:

  buf_meta  : (S, R, P, V, B) int16 — dest | src << 6 | cls << 12
  buf_binj  : (S, R, P, V, B) int32 — injection timestamp (network latency)
  head, count : (S, R, P, V)  int8
  rr_ptr      : (S, R, P)     int8  per-output RR pointer over P*V requesters

Generation timestamps (the old `buf_birth` chain: source queue -> request ->
MC queue -> reply) were carried end-to-end but never consumed by any counter
or metric — every latency figure uses the injection stamp `binj` (network
time, Fig. 11).  The dead chain was eliminated: on the memory-bound cycle
loop it cost a full int32 buffer in every peek/select/write.  Reintroduce a
`buf_birth` alongside a round-trip-latency metric if one is ever needed.

All packets are single-flit (DESIGN.md §8.2); B is the per-VC buffer depth
(paper: 4).  One traversal per output port and at most one per input port per
cycle (a crossbar has one input per port).

The cycle engine is SCATTER-FREE: every buffer write site has a unique,
statically-known source (the link into input port p of router r can only be
driven by `neighbor[r, p]`'s output port `opposite[p]`), so each update is a
dense masked `where` over the full state block instead of an XLA scatter.
XLA:CPU executes scatters as serial per-update loops, which made the old
formulation the dominant cost of the batched sweep; the dense form vectorizes
on CPU and maps directly onto accelerator lanes.

`arbitrate` is the pure switch-allocation inner loop (VC allocation +
per-output RR arbitration + grant filtering), shared by the default jnp path
and the Pallas kernel in `repro.kernels.noc_cycle` (which must agree with it
bitwise — see tests/test_cycle_engine.py).  This whole module is also the
per-stage ORACLE for the fused full-cycle lane kernel
(`repro.kernels.noc_cycle.fused`, DESIGN.md §13): `router_cycle` and
`inject_all` each have a lane twin (`router_stage_lanes`, `inject_lanes`)
that must reproduce them bitwise — including the garbage-value conventions
on ungranted outputs — so any semantic change here must land in the twin in
the same commit.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.noc.topology import N_PORTS, PORT_L, Topology

Array = jax.Array
BIG = jnp.int32(1 << 20)

# meta packing: dest | src << 6 | cls << 12 (needs R <= 64, cls in {0, 1})
META_SRC_SHIFT = 6
META_CLS_SHIFT = 12


def pack_meta(dest: Array, src: Array, cls: Array) -> Array:
    """Pack (dest, src, cls) into one int16 word (values are < 64 / < 64 / 1b)."""
    word = dest + (src << META_SRC_SHIFT) + (cls << META_CLS_SHIFT)
    return word.astype(jnp.int16)


def unpack_meta(meta: Array) -> tuple[Array, Array, Array]:
    """Inverse of `pack_meta`; returns int32 (dest, src, cls)."""
    w = meta.astype(jnp.int32)
    dest = w & ((1 << META_SRC_SHIFT) - 1)
    src = (w >> META_SRC_SHIFT) & ((1 << (META_CLS_SHIFT - META_SRC_SHIFT)) - 1)
    cls = w >> META_CLS_SHIFT
    return dest, src, cls


class SubnetState(NamedTuple):
    buf_meta: Array   # (S, R, P, V, B) int16 — dest | src<<6 | cls<<12
    buf_binj: Array   # (S, R, P, V, B) int32 injection timestamp (Fig. 11)
    head: Array       # (S, R, P, V) int8
    count: Array      # (S, R, P, V) int8
    rr_ptr: Array     # (S, R, P) int8 round-robin pointer over P*V index


class CycleEvents(NamedTuple):
    """Per-cycle outputs consumed by metrics / the MC model."""

    # ejected-at-local packets (<= 1 ejection/router/cycle per subnet)
    eject_valid: Array   # (S, R) bool
    eject_src: Array     # (S, R) int32
    eject_cls: Array     # (S, R) int32
    eject_binj: Array    # (S, R) int32 injection timestamp
    moved: Array         # () int32 — switch traversals this cycle (utilization)
    dram_block_gpu: Array  # () int32 — GPU ejections blocked by a full MC queue
    dram_block_cpu: Array  # () int32 — CPU ejections blocked by a full MC queue
    # flight-recorder probes (repro.obs, DESIGN.md §14): per-router switch
    # allocation outcomes, summed over output ports.  Dead code (free) when
    # probes are off; appended last so positional consumers stay valid.
    grant_cnt: Array     # (S, R) int32 — outputs granted this cycle
    deny_cnt: Array      # (S, R) int32 — requested outputs refused this cycle


class Arbitration(NamedTuple):
    """Outputs of the switch-allocation inner loop (shapes lead with `...`)."""

    grant: Array    # (..., O) bool — output port fires this cycle
    winner: Array   # (..., O) int32 — flat P*V requester index per output
    down_vc: Array  # (..., O) int32 — downstream VC granted to the winner
    deq: Array      # (..., P*V) bool — head packet pops this cycle
    new_rr: Array   # (..., O) int32 — advanced round-robin pointer
    any_req: Array  # (..., O) bool — some head packet wants this output
    w_cls: Array    # (..., O) int32 — class of the winning packet


def arbitrate(
    valid: Array,        # (..., P*V) bool — head packet present
    cls: Array,          # (..., P*V) int32 — head packet class (0/1)
    out_port: Array,     # (..., P*V) int32 — desired output port (XY route)
    rr_ptr: Array,       # (..., O) int32 — per-output RR pointer
    down_count: Array,   # (..., O, V) int32 — VC occupancy at the downstream
    down_exists: Array,  # (..., O) bool — a link exists through this output
    gpu_vc_mask: Array,  # (..., V) bool — VCs GPU packets may occupy
    cpu_vc_mask: Array,  # (..., V) bool
    sa_pref: Array,      # (...,) int32: -1 round-robin, else preferred class
    accept: Array,       # (...,) bool — ejection credit at the local sink
    active: Array,       # (...,) bool — link active (4-subnet: half width)
    *,
    depth: int,
) -> Arbitration:
    """One switch-allocation step: per (…, out_port) pick one (in_port, vc).

    Pure dense math (no gather/scatter): this is the function the Pallas
    `noc_cycle` kernel reimplements over a flattened lane axis, and the two
    must agree bitwise on every output.
    """
    PV = valid.shape[-1]
    oid = jnp.arange(N_PORTS, dtype=jnp.int32)
    pv = jnp.arange(PV, dtype=jnp.int32)
    pv16 = jnp.arange(PV, dtype=jnp.int16)
    big16 = jnp.int16(PV * (2 * PV + 1))  # > any live packed key

    # requester matrix + round-robin key relative to the per-output pointer.
    # The (..., PV, O) intermediates dominate this function's memory traffic,
    # so the key math runs in int16 (max packed value PV*(2PV)+PV-1 << 2^15).
    req = valid[..., :, None] & (out_port[..., :, None] == oid)   # (...,PV,O)
    # KF=1: prefer the pattern class first (paper Fig. 8, 2 GPU : 1 CPU);
    # the penalty is per requester (no O axis needed)
    is_pref = (cls == sa_pref[..., None]) | (sa_pref[..., None] < 0)
    penalty = jnp.where(is_pref, jnp.int16(0), jnp.int16(PV))     # (..., PV)
    key = (pv16[:, None] - rr_ptr.astype(jnp.int16)[..., None, :]) % PV
    key = key + penalty[..., :, None]
    # packed min == argmin (ties break to the lowest pv, like argmin)
    packed = jnp.where(req, key * PV + pv16[:, None], big16)
    m = jnp.min(packed, axis=-2).astype(jnp.int32)                # (..., O)
    winner = m % PV
    any_req = jnp.any(req, axis=-2)                               # (..., O)

    w_onehot = pv == winner[..., None]                            # (...,O,PV)
    w_cls = jnp.sum(jnp.where(w_onehot, cls[..., None, :], 0), axis=-1)

    # --- output-side credit check: first free VC the winner's class may use
    allowed = jnp.where((w_cls == 1)[..., None], gpu_vc_mask[..., None, :],
                        cpu_vc_mask[..., None, :])                # (...,O,V)
    has_space = (down_count < depth) & allowed
    down_vc = jnp.argmax(has_space, axis=-1).astype(jnp.int32)
    credit_ok = jnp.any(has_space, axis=-1)                       # (..., O)

    is_local = oid == PORT_L
    eject_ok = is_local & accept[..., None]
    link_ok = (~is_local) & down_exists & credit_ok
    grant = any_req & (eject_ok | link_ok) & active[..., None]    # (..., O)

    # --- one traversal per input port: keep the lowest-output grant per port
    w_port = winner // (PV // N_PORTS)                            # (..., O)
    rank = jnp.where(grant, oid, BIG)
    pmatch = w_port[..., None, :] == oid[:, None]                 # (...,P,O)
    min_rank = jnp.min(jnp.where(pmatch, rank[..., None, :], BIG), axis=-1)
    sel = jnp.sum(jnp.where(pmatch, min_rank[..., :, None], 0), axis=-2)
    grant = grant & (rank == sel)

    deq = jnp.any(w_onehot & grant[..., None], axis=-2)           # (...,PV)
    new_rr = jnp.where(grant, (winner + 1) % PV, rr_ptr)
    return Arbitration(grant, winner, down_vc, deq, new_rr, any_req, w_cls)


def router_cycle(
    state: SubnetState,
    topo_route: Array,      # (R, R) int32 device copy of topology.route
    topo_neighbor: Array,   # (R, P)
    topo_opposite: Array,   # (P,)
    gpu_vc_mask: Array,     # (S, V) bool — VCs GPU packets may occupy
    cpu_vc_mask: Array,     # (S, V) bool
    sa_pref_class: Array,   # () int32: -1 round-robin, else preferred class
    mc_can_accept: Array,   # (S, R) bool — ejection credit at local sink
    active: Array,          # (S,) bool — link active this cycle
    link_ok: Array | None = None,    # (R, P) bool — fault mask: port usable
    router_ok: Array | None = None,  # (R,) bool — fault mask: router granting
) -> tuple[SubnetState, CycleEvents]:
    """Advance every router of every subnet by one cycle.

    Fault masks (DESIGN.md §16) only ever AND into existing gates: a
    False ``link_ok[r, p]`` makes port p of router r look like a
    non-existent link (its head packets are never granted and
    back-pressure in place), a False ``router_ok[r]`` suppresses every
    grant at router r including local ejection (a brownout).  ``None``
    (or all-True) masks leave the program's values bit-for-bit unchanged.
    """
    S, R, P, V, B = state.buf_meta.shape
    ar = jnp.arange(R)

    # --- peek head-of-line packets -> (S, R, P, V) fields
    hidx = state.head.astype(jnp.int32)[..., None]
    meta = jnp.take_along_axis(state.buf_meta, hidx, axis=4)[..., 0]
    binj = jnp.take_along_axis(state.buf_binj, hidx, axis=4)[..., 0]
    dest, _, cls = unpack_meta(meta)
    valid = state.count > 0

    # --- route computation: desired output port of each head packet
    out_port = topo_route[ar[:, None, None], dest]                # (S,R,P,V)

    # --- downstream VC occupancy through each output (static-index gather)
    nb_safe = jnp.maximum(topo_neighbor, 0)                       # (R, O)
    opp_b = jnp.broadcast_to(topo_opposite[None, :], (R, N_PORTS))
    down_count = state.count[:, nb_safe, opp_b, :].astype(jnp.int32)
    usable = topo_neighbor >= 0
    if link_ok is not None:
        usable = usable & link_ok
    down_exists = jnp.broadcast_to(usable, (S, R, N_PORTS))
    granting = jnp.broadcast_to(active[:, None], (S, R))
    if router_ok is not None:
        granting = granting & router_ok[None, :]

    arb = arbitrate(
        valid.reshape(S, R, P * V),
        cls.reshape(S, R, P * V),
        out_port.reshape(S, R, P * V),
        state.rr_ptr.astype(jnp.int32),
        down_count,
        down_exists,
        gpu_vc_mask[:, None, :],
        cpu_vc_mask[:, None, :],
        jnp.broadcast_to(sa_pref_class, (S, R)),
        mc_can_accept,
        granting,
        depth=B,
    )

    # --- apply: dequeue winners, advance RR pointers past them
    deq = arb.deq.reshape(S, R, P, V)
    head2 = jnp.where(deq, (state.head + 1) % B, state.head)
    count2 = state.count - deq.astype(jnp.int8)
    rr2 = arb.new_rr.astype(state.rr_ptr.dtype)

    # --- gather winner packet fields (S, R, O) — one-hot reduction over the
    # requester axis (vectorizes; dynamic gather at these indices does not)
    w_onehot = jnp.arange(P * V) == arb.winner[..., None]         # (S,R,O,PV)

    def gsel(x):  # x: (S, R, P, V) int — select the winner's field per output
        return jnp.sum(
            jnp.where(w_onehot, x.reshape(S, R, 1, P * V), 0), axis=-1,
            dtype=x.dtype,  # one-hot: a single term survives, no overflow
        )

    w_meta = gsel(meta.astype(jnp.int32))
    w_binj = gsel(binj)
    wd, ws, _ = unpack_meta(w_meta)

    # --- ejections: only the Local output column can eject (<=1 per router)
    ej = arb.grant[..., PORT_L]                                   # (S, R)
    blocked_local = arb.any_req[..., PORT_L] & ~mc_can_accept
    blocked_cls = arb.w_cls[..., PORT_L]
    events = CycleEvents(
        eject_valid=ej,
        eject_src=ws[..., PORT_L],
        eject_cls=arb.w_cls[..., PORT_L],
        eject_binj=w_binj[..., PORT_L],
        moved=jnp.sum(arb.grant.astype(jnp.int32)),
        dram_block_gpu=jnp.sum(
            (blocked_local & (blocked_cls == 1)).astype(jnp.int32)
        ),
        dram_block_cpu=jnp.sum(
            (blocked_local & (blocked_cls == 0)).astype(jnp.int32)
        ),
        grant_cnt=jnp.sum(arb.grant.astype(jnp.int32), axis=-1),
        deny_cnt=jnp.sum(
            (arb.any_req & ~arb.grant).astype(jnp.int32), axis=-1
        ),
    )

    # --- link traversals as a dense pull: input port p of router r can only
    # be driven by neighbor[r, p] through its output port opposite[p], so the
    # old scatter-enqueue is a static-index gather + masked where.
    lk = arb.grant & (jnp.arange(N_PORTS) != PORT_L)              # (S, R, O)

    def up(x):  # value at the (unique) upstream driver of each (r, p) input
        return x[:, nb_safe, opp_b]

    in_ok = up(lk) & (topo_neighbor >= 0)                         # (S, R, P)
    in_meta = up(w_meta)
    in_binj = up(w_binj)
    in_vc = up(arb.down_vc)

    tail = ((head2 + count2) % B).astype(jnp.int32)               # (S,R,P,V)
    vmask = in_ok[..., None] & (in_vc[..., None] == jnp.arange(V))
    bmask = vmask[..., None] & (tail[..., None] == jnp.arange(B))
    state3 = SubnetState(
        buf_meta=jnp.where(
            bmask, in_meta[..., None, None].astype(jnp.int16), state.buf_meta
        ),
        buf_binj=jnp.where(bmask, in_binj[..., None, None], state.buf_binj),
        head=head2,
        count=count2 + vmask.astype(jnp.int8),
        rr_ptr=rr2,
    )
    return state3, events


def inject_all(
    state: SubnetState,
    want: Array,         # (S, R) bool — one injection attempt per (subnet, router)
    dest: Array, src: Array, cls: Array,   # (S, R) int32 packet fields
    binj: Array,                           # (S, R) int32 injection timestamp
    gpu_vc_mask: Array, cpu_vc_mask: Array,  # (S, V) bool class VC partition
) -> tuple[SubnetState, Array]:
    """Inject at the Local input port of every (subnet, router) at once.

    Returns (state, accepted (S, R) bool).  Dense formulation of the old
    per-subnet scatter inject: pick the first free VC the class may use and
    write the tail slot with a masked where.
    """
    S, R, P, V, B = state.buf_meta.shape
    local_count = state.count[:, :, PORT_L]                       # (S, R, V)
    allowed = jnp.where(cls[..., None] == 1, gpu_vc_mask[:, None, :],
                        cpu_vc_mask[:, None, :])
    has_space = (local_count < B) & allowed
    vc = jnp.argmax(has_space, axis=-1).astype(jnp.int32)
    ok = want & jnp.any(has_space, axis=-1)

    head_l = state.head[:, :, PORT_L]
    tail = ((head_l + local_count) % B).astype(jnp.int32)         # (S, R, V)
    vmask = ok[..., None] & (vc[..., None] == jnp.arange(V))      # (S, R, V)
    bmask = vmask[..., None] & (tail[..., None] == jnp.arange(B))
    meta = pack_meta(dest, src, cls)

    def wr(buf, val):
        val = jnp.asarray(val).astype(buf.dtype)
        new_local = jnp.where(bmask, val[..., None, None], buf[:, :, PORT_L])
        return buf.at[:, :, PORT_L].set(new_local)

    state = state._replace(
        buf_meta=wr(state.buf_meta, meta),
        buf_binj=wr(state.buf_binj, binj),
        count=state.count.at[:, :, PORT_L].set(
            local_count + vmask.astype(jnp.int8)
        ),
    )
    return state, ok


def device_tables(topo: Topology):
    """Move topology tables onto device once per simulation.

    Since the placement layer (DESIGN.md §17) the static `node_type`
    table only seeds the physical `is_mc` mask — per-epoch node classes
    come from the traced placement stream, and routing/neighbor tables
    stay position-only (relocation moves a tile's CLASS, not its router).
    """
    assert topo.n_routers <= 64, "meta packing assumes router ids fit 6 bits"
    return (
        jnp.asarray(topo.route),
        jnp.asarray(topo.neighbor),
        jnp.asarray(topo.opposite),
        jnp.asarray(topo.node_type),
        jnp.asarray(topo.mc_ids),
    )
