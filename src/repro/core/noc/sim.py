"""Cycle-stepped heterogeneous-chiplet NoC simulation with the KF in the loop.

Reproduces the paper's evaluation pipeline end to end:

  traffic sources -> routers (VC alloc + switch alloc) -> MCs -> replies
        ^                                                          |
        '------ per-epoch counters -> Kalman Filter -> policy <----'

Four network configurations (paper §4.2):
  * ``baseline``  — 2 subnets (req/reply), VCs fully shared, round-robin SA.
  * ``fair``      — 2 subnets, static 2:2 VC partition between GPU and CPU.
  * ``4subnet``   — physical segregation: {CPU,GPU} x {req,reply}; each
                    subnet gets half link width (modeled as alternating-cycle
                    link activation) and half the VCs.
  * ``kf``        — 2 subnets + Kalman-Filter-driven reconfiguration of the
                    VC partition (2:2 <-> 3:1) and switch arbitration
                    (RR <-> GPU,GPU,CPU pattern), with the paper's
                    warmup / hold / revert hysteresis.
  * ``static``    — fixed [gpu:cpu] VC partition, for the Fig. 2/3 sweep.

The whole run is one jitted ``lax.scan`` over epochs with an inner scan over
cycles; 36 routers x 4 VCs x depth 4 keeps per-cycle tensors tiny.

Batched sweep engine (DESIGN.md §4, §10)
----------------------------------------
``mode``, the static VC ratio, the workload rates, the seed, AND the subnet
structure are all *traced* data (`allocator.ModePolicy` tensors +
`traffic.WorkloadProfile` pytrees): every configuration's subnet axis is
padded to ``S_MAX`` (padded subnets are zero-width — never injected into,
links never active) and the 4-subnet network's 2 VCs/subnet ride a V-padded
axis with the upper VCs masked off, so 2-subnet and 4-subnet configurations
share ONE compiled program.  ``simulate_batch`` vmaps that program over a
leading batch axis (configs x workloads x seeds evaluated in lockstep, with
donated carry buffers) and can shard that axis data-parallel across devices
(``devices=``/``mesh=``, via `jax.shard_map`);
``sweep`` / ``sweep_sharded`` are the drivers the paper-figure benchmarks
run on.

Predictor ablation + scenario schedules (DESIGN.md §12): the epoch-boundary
reconfiguration signal comes from a traced predictor *bank*
(`repro.core.predictor` — KF / EMA / last-value / always-on / always-off,
selected by `ModePolicy.predictor.kind`), and workloads — stationary or
`traffic.ScenarioSchedule` programs — are materialized to per-epoch
parameter rows consumed through the epoch scan's `xs`, so the whole
ablation x scenario grid still costs the ONE compiled program.

Traffic sources (DESIGN.md §15): every entry point accepts any
`traffic.TrafficSource` — a workload name, `WorkloadProfile`,
`ScenarioSchedule`, or a replayed `RecordedTrace` — and lowers it through
the single `traffic.resolve_source` path to the canonical per-epoch
`EpochDemand` rows, so recorded/adapted traces reuse the same compiled
program as synthetic generators.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kalman, predictor
from repro.core.allocator import (
    ModePolicy,
    PolicyConfig,
    apply_policy_gated,
    class_vc_masks,
    epoch_sa_prefs,
    init_policy_state,
    mode_policy,
    placement_class,
)
from repro.core.allocator import degrade_policy
from repro.core.noc import metrics
from repro.core.noc import router as rt
from repro.core.noc.faults import (
    TELEM_DROP,
    TELEM_NAN,
    TELEM_SPIKE,
    FaultSourceLike,
    FaultStream,
    resolve_faults,
)
from repro.core.noc.placement import (
    PlacementSourceLike,
    PlacementStream,
    resolve_placement,
)
from repro.core.noc.topology import make_topology
from repro.obs.probes import ProbeConfig, SimTrace
from repro.core.noc.traffic import (
    TrafficSource,
    TrafficSourceLike,
    WorkloadProfile,
    init_phase,
    injection_rates,
    resolve_source,
    stack_profiles,
    step_phase_u,
)

Array = jax.Array

BCAP = 64  # per-node source-queue (shader/LSQ) capacity

# Padded subnet-axis length shared by every mode's program (DESIGN.md §10):
# large enough for the 4-subnet network; 2-subnet modes leave rows 2..3
# zero-width (never injected into, links never active).
S_MAX = 4


@dataclasses.dataclass(frozen=True)
class SimStatic:
    """The structural (compile-time) part of a simulation config.

    Everything the XLA program *shape* depends on.  Deliberately excludes
    ``mode`` — including its subnet structure, which since the S-padding
    refactor (DESIGN.md §10) is traced `ModePolicy` data over a padded
    (``n_subnets``, ..., ``n_vcs``) state — plus the static VC ratio and the
    seed.  With the default padded spec every configuration shares one
    compiled executable.
    """

    n_subnets: int   # length of the (possibly padded) subnet axis
    n_vcs: int       # per-subnet VC axis length (possibly padded)
    buf_depth: int
    epoch_len: int
    n_epochs: int
    mc_queue_cap: int
    mc_service_period: int
    mshr_limit: int
    policy: PolicyConfig
    z_scales: tuple[float, float, float]
    kf_q: float
    kf_r: float
    # cycle-engine knobs (DESIGN.md §11, §13): scan unroll factor for the
    # inner cycle loop, and which engine to trace ("ref" = dense jnp,
    # "pallas" = the fused full-cycle repro.kernels.noc_cycle lane kernel,
    # "pallas_arb" = dense body with only arbitration on the lane kernel).
    cycle_unroll: int = 1
    backend: str = "ref"
    # injection-stamp dtype: "auto" picks uint16 whenever every age the run
    # can produce is wraparound-exact (see init_sim_state); "int32" forces
    # the wide stamps — a test/debug knob the uint16-boundary regression
    # test uses to pin auto == int32 bitwise at the 2^16-cycle boundary.
    stamp_dtype: str = "auto"
    # flight recorder (repro.obs, DESIGN.md §14): probes off (the default)
    # leaves the traced program — and so the goldens and trace count —
    # bit-for-bit unchanged; probes on is its own single trace returning
    # (SimResult, SimTrace).
    probe: ProbeConfig = ProbeConfig()
    # mesh geometry (DESIGN.md §17): the topology tables are shape-bearing,
    # so grid dimensions are structural.  The paper grid (6x6, 8 MCs) is
    # the default; any grid accepted by `topology.validate_topology_args`
    # builds and runs (capped at 64 routers by the lane-metadata packing).
    width: int = 6
    height: int = 6
    n_mc: int = 8


@dataclasses.dataclass(frozen=True)
class NoCConfig:
    mode: str = "kf"              # baseline | fair | 4subnet | kf | static
    static_gpu_vcs: int = 2       # for mode=static: GPU gets [g : V-g]
    n_vcs: int = 4                # per input port per subnet (2-subnet modes)
    buf_depth: int = 4            # packets per VC (paper: 4)
    epoch_len: int = 500          # cycles per KF epoch
    n_epochs: int = 120
    # DRAM is the scarce shared resource (paper §2.1: "CPU packets pile up at
    # MCs which already have many GPU packets waiting").  Total DRAM service
    # is 8 MCs / 2 cycles = 4 pkt/cycle vs ~7.3 offered during bursts; the
    # NoC's VC partition + switch priority decide *admission* into MC queues,
    # which is exactly the lever the paper's KF reconfigures.
    mc_queue_cap: int = 16
    mc_service_period: int = 2    # cycles per serviced request per MC
    mshr_limit: int = 16          # max outstanding requests per node (MSHRs)
    policy: PolicyConfig = PolicyConfig()
    # normalization scales for KF observations (counters per epoch)
    z_scales: tuple[float, float, float] = (300.0, 160.0, 2500.0)
    kf_q: float = 1e-3
    kf_r: float = 2e-1
    seed: int = 0
    cycle_unroll: int = 1         # inner cycle-scan unroll factor
    backend: str = "ref"          # cycle engine: ref | pallas | pallas_arb
    stamp_dtype: str = "auto"     # injection-stamp dtype: auto | int32
    # predictor-ablation knobs (DESIGN.md §12): which bank member drives the
    # hysteresis machine (only meaningful for mode="kf") and the EMA
    # predictor's smoothing factor.  Traced data — not part of SimStatic.
    predictor: str = "kf"
    ema_alpha: float = 0.5   # the textbook naive-EMA default
    # robustness knobs (DESIGN.md §16) — both traced data, NOT SimStatic:
    # `guard` arms the predictor's self-healing layer (innovation gate +
    # divergence watchdog + covariance reset + fair-split fallback);
    # `faults` is any `faults.FaultSourceLike` (scenario name,
    # FaultSchedule, FaultStream, or None = healthy) injected through the
    # epoch scan's xs — faulty and healthy runs share one compiled program.
    guard: bool = False
    faults: FaultSourceLike = None
    # compute-placement knobs (DESIGN.md §17) — both traced data, NOT
    # SimStatic: `placement` is any `placement.PlacementSourceLike`
    # (scenario name, PlacementSchedule, PlacementStream, or None = the
    # identity/static layout) riding the epoch scan's xs; `control` picks
    # which lever(s) the applied config drives — "bandwidth" (the paper's
    # VC/SA controller), "placement" (relocation only), or "joint".
    placement: PlacementSourceLike = None
    control: str = "bandwidth"
    # flight recorder (repro.obs, DESIGN.md §14) — static, default off
    probe: ProbeConfig = ProbeConfig()
    # mesh geometry (DESIGN.md §17) — structural; see SimStatic
    width: int = 6
    height: int = 6
    n_mc: int = 8

    @property
    def n_subnets(self) -> int:
        return 4 if self.mode == "4subnet" else 2

    @property
    def vcs_per_subnet(self) -> int:
        return self.n_vcs // 2 if self.mode == "4subnet" else self.n_vcs

    def static_spec(self, padded: bool = True) -> SimStatic:
        """Structural spec — padded (default) or the mode's dedicated shape.

        ``padded=True`` pads the subnet axis to ``S_MAX`` and keeps the full
        VC axis, so EVERY mode returns the same spec and shares one compiled
        program.  ``padded=False`` reproduces the pre-§10 dedicated traces
        (2-subnet xV, or 4-subnet x V/2) — kept for the equivalence tests.
        """
        return SimStatic(
            n_subnets=S_MAX if padded else self.n_subnets,
            n_vcs=self.n_vcs if padded else self.vcs_per_subnet,
            buf_depth=self.buf_depth,
            epoch_len=self.epoch_len,
            n_epochs=self.n_epochs,
            mc_queue_cap=self.mc_queue_cap,
            mc_service_period=self.mc_service_period,
            mshr_limit=self.mshr_limit,
            policy=self.policy,
            z_scales=tuple(self.z_scales),
            kf_q=self.kf_q,
            kf_r=self.kf_r,
            cycle_unroll=self.cycle_unroll,
            backend=self.backend,
            stamp_dtype=self.stamp_dtype,
            probe=self.probe,
            width=self.width,
            height=self.height,
            n_mc=self.n_mc,
        )

    def mode_policy(self, padded: bool = True) -> ModePolicy:
        stc = self.static_spec(padded)
        return mode_policy(
            self.mode, stc.n_vcs, self.static_gpu_vcs,
            n_subnets=stc.n_subnets, active_vcs=self.vcs_per_subnet,
            predictor=self.predictor, ema_alpha=self.ema_alpha,
            guard=self.guard, control=self.control,
        )


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


def _zero_counters() -> EpochCounters:
    z = jnp.int32(0)
    return EpochCounters(z, z, z, z, z, z, z, z, z, z, z, z, z, z, z)


class _ProbeAcc(NamedTuple):
    """Dense-engine flight-recorder accumulators (repro.obs, DESIGN.md §14):
    the per-cycle carry the probes-on cycle scan threads next to the
    counters.  The fused engine's twin is `fused.ProbeLanes`; both sample
    END-of-cycle state, so the two agree bitwise."""

    occ: Array      # (S, R, P, V) int32 — summed VC occupancy
    grant: Array    # (S, R) int32 — switch grants, summed over outputs
    deny: Array     # (S, R) int32 — refused requests, summed over outputs
    mcq_sum: Array  # (R,) int32 — summed MC queue depth
    mcq_max: Array  # (R,) int32 — running max MC queue depth


def _zero_probe_acc(S: int, R: int, V: int) -> _ProbeAcc:
    return _ProbeAcc(
        occ=jnp.zeros((S, R, rt.N_PORTS, V), jnp.int32),
        grant=jnp.zeros((S, R), jnp.int32),
        deny=jnp.zeros((S, R), jnp.int32),
        mcq_sum=jnp.zeros((R,), jnp.int32),
        mcq_max=jnp.zeros((R,), jnp.int32),
    )


class SimResult(NamedTuple):
    gpu_ipc: Array        # (E,) per-epoch GPU IPC proxy
    cpu_ipc: Array        # (E,)
    avg_latency: Array    # (E,) mean packet network latency
    kf_signal: Array      # (E,) binarized KF output
    applied_config: Array  # (E,) configuration actually applied
    counters: EpochCounters  # (E,) leaves
    gpu_inj_rate: Array   # (E,) offered GPU load (Fig. 4 trace)
    # VCs the GPU class could occupy during the epoch — pins the hoisted
    # per-epoch masks to the policy state that entered the epoch (the mask
    # flip must trail `applied_config` by exactly one epoch; see
    # tests/test_cycle_engine.py's policy-boundary regression test).
    gpu_vc_quota: Array   # (E,)


def _make_kf(stc: SimStatic):
    return kalman.paper_params(q=stc.kf_q, r=stc.kf_r)


def init_sim_state(stc: SimStatic, batch: int | None = None):
    """Zero-initialized carry buffers (subnets, MC queues, source backlogs).

    Built outside the jitted entry points so the batched path can donate
    them: XLA then reuses the buffers in place instead of holding both the
    init and the first-iteration copy live.
    """
    topo = make_topology(stc.width, stc.height, stc.n_mc)
    R = topo.n_routers
    S, V, B = stc.n_subnets, stc.n_vcs, stc.buf_depth

    def z(shape, dtype=jnp.int32):
        if batch is not None:
            shape = (batch,) + shape
        return jnp.zeros(shape, dtype)

    # Injection stamps ride uint16 when every possible age fits: the latency
    # subtraction is wraparound-exact for ages <= 2^16 - 1.  The max age is
    # `total - 1` (a cycle-0 injection ejected on the last cycle): stamps
    # are injection cycles <= total - 1 — epoch-end replies carry the next
    # epoch's first cycle, but the run's final cycle defers its replies to
    # an epoch prologue that never executes, so no stamp exceeds total - 1
    # either — hence uint16 is exact whenever total <= 2^16.  (The old gate
    # `total + 1 <= 0xFFFF` was conservative by two: totals of exactly
    # 65535/65536 cycles paid int32 stamps for no reason — pinned at the
    # boundary by tests/test_predictor_ablation.py.)
    total_cycles = stc.epoch_len * stc.n_epochs
    if stc.stamp_dtype == "int32":
        binj_dtype = jnp.int32
    elif stc.stamp_dtype == "auto":
        binj_dtype = jnp.uint16 if total_cycles <= 2**16 else jnp.int32
    else:
        raise ValueError(
            f"unknown stamp_dtype {stc.stamp_dtype!r}; expected auto|int32"
        )
    subnets0 = rt.SubnetState(
        buf_meta=z((S, R, rt.N_PORTS, V, B), jnp.int16),
        buf_binj=z((S, R, rt.N_PORTS, V, B), binj_dtype),
        head=z((S, R, rt.N_PORTS, V), jnp.int8),
        count=z((S, R, rt.N_PORTS, V), jnp.int8),
        rr_ptr=z((S, R, rt.N_PORTS), jnp.int8),
    )
    mc0 = MCState(
        q_meta=z((R, stc.mc_queue_cap), jnp.int8),
        head=z((R,)),
        count=z((R,)),
        timer=z((R,)),
        stage_valid=z((R,), bool),
        stage_dst=z((R,)),
        stage_cls=z((R,)),
    )
    outstanding0 = z((R,))
    backlog0 = z((R,))  # per-node source-queue depth (see BCAP)
    return subnets0, mc0, outstanding0, backlog0


# Incremented each time XLA actually (re)traces the simulator — the
# equivalence tests assert the whole paper sweep costs at most two traces.
_trace_counter = [0]


def trace_count() -> int:
    return _trace_counter[0]


def reset_trace_count() -> None:
    _trace_counter[0] = 0


def _simulate_impl(
    stc: SimStatic,
    mp: ModePolicy,
    profile: WorkloadProfile,
    seed: Array,
    state0,
    faults: FaultStream,
    placement: PlacementStream,
) -> SimResult:
    """Core jitted simulation.  ``profile`` arrives MATERIALIZED: every leaf
    is an (n_epochs,) float32 row (``traffic.materialize``), consumed by the
    epoch scan as `xs` — one parameter row per epoch.  Stationary workloads
    broadcast their scalars across the epoch axis, so scenario schedules
    (piecewise switches, ramps, pinned burst phases — DESIGN.md §12) share
    this one trace with them by construction.

    ``faults`` arrives the same way (DESIGN.md §16): per-epoch mask rows
    (`faults.resolve_faults`) rode through the epoch scan's xs, always
    threaded — a healthy run carries the identity stream, so faulty and
    healthy configurations share this ONE trace and the healthy values are
    bit-for-bit the pre-fault program's (every fault gate is an AND or a
    mode-0 `where`).

    ``placement`` too (DESIGN.md §17): per-epoch (R,) node-class plans
    (`placement.resolve_placement`) riding the epoch scan's xs.  Node
    identity — `is_gpu`/`is_cpu`/`node_cls`/`req_sub` and the injection
    gates — is derived per epoch from the traced plan inside `epoch_body`
    instead of from static topology constants, so relocated and static
    runs share this ONE trace; the identity stream carries the topology's
    own layout, making a static run's derived values bit-for-bit the
    pre-placement program's.  MCs are physical and never relocate: `is_mc`
    stays a static table and the virtual node type re-asserts it."""
    _trace_counter[0] += 1  # Python side effect: runs only at trace time

    topo = make_topology(stc.width, stc.height, stc.n_mc)
    route_t, nb_t, opp_t, ntype, mc_ids = rt.device_tables(topo)
    R = topo.n_routers
    S = stc.n_subnets
    V = stc.n_vcs

    is_mc = ntype == 2  # static: MCs are physical, placement never moves them
    ar = jnp.arange(R)

    # Traced subnet structure (DESIGN.md §10): which rows of the padded
    # subnet axis are live, which carry requests, and whether routing is
    # class-segregated.  Padded rows are zero-width: excluded from every
    # inject want-matrix below and link-inactive in cycle_body, so no packet
    # can ever enter them.
    fs = mp.four_subnet                      # () bool
    sub_enabled = mp.sub_enabled             # (S,) bool
    sub_is_req = mp.sub_is_req               # (S,) bool
    sub_is_rep = sub_enabled & ~sub_is_req   # (S,) bool
    n_req_subs = jnp.sum(sub_is_req.astype(jnp.int32))
    sub_ids = jnp.arange(S, dtype=jnp.int32)
    # NB `is_gpu`/`is_cpu`/`node_cls`/`req_sub` are no longer derived here:
    # they are per-epoch quantities computed in `epoch_body` from the traced
    # placement plan (DESIGN.md §17).

    subnets0, mc0, outstanding0, backlog0 = state0

    # flight recorder (DESIGN.md §14): a STATIC switch — probes off traces
    # the exact pre-probe program (the accumulators below are Python-gated,
    # not lax.cond-gated), probes on is its own single trace.
    probe_on = stc.probe.enabled

    kf_params = _make_kf(stc)
    z_scales = jnp.asarray(stc.z_scales, jnp.float32)

    # cycle-engine backend (DESIGN.md §11, §13) — all three agree bitwise
    # (tests/test_cycle_engine.py), so the choice is pure perf:
    #   "ref"        — the dense jnp cycle body below.
    #   "pallas"     — the FUSED full-cycle Pallas kernel: one launch per
    #                  simulated cycle with the whole carry in lane refs
    #                  (repro.kernels.noc_cycle, interpret-mode off-TPU).
    #   "pallas_arb" — dense cycle body with only switch allocation swapped
    #                  for the arbitration lane kernel (the PR-4 path).
    fused_engine = stc.backend == "pallas"
    if stc.backend == "pallas_arb":
        from repro.kernels.noc_cycle.ops import arbitrate_lanes as arb_fn
    elif stc.backend in ("ref", "pallas"):
        arb_fn = rt.arbitrate
    else:
        raise ValueError(f"unknown cycle-engine backend {stc.backend!r}")
    if fused_engine:
        from repro.kernels.noc_cycle import fused as lanes
        from repro.kernels.noc_cycle import ops as lane_ops

        assert lanes.COUNTER_FIELDS == EpochCounters._fields, (
            "fused kernel counter lanes out of sync with EpochCounters"
        )
        # the lane engine carries stamps as int32 and masks the latency
        # subtraction instead, reproducing the uint16 wraparound bitwise
        stamp_mask = 0xFFFF if subnets0.buf_binj.dtype == jnp.uint16 else 0
        lane_dims = lanes.lane_dims(
            S=S, R=R, V=V, B=stc.buf_depth, Q=stc.mc_queue_cap,
            width=topo.width, mc_service_period=stc.mc_service_period,
            mshr_limit=stc.mshr_limit, bcap=BCAP, stamp_mask=stamp_mask,
        )
        # the node-type row and the req_match-bearing policy rows are now
        # per-epoch data (placement, DESIGN.md §17) — rebuilt in epoch_body
        route_rows, exists_rows, _ = lanes.run_consts(lane_dims, topo)

    def make_want_rep(mc):
        """Want-matrix for staged MC replies (reply subnet of requester
        class c is 2c+1 under class-segregated routing, subnet 1 otherwise)."""
        rep_target = jnp.where(fs, 2 * mc.stage_cls + 1, 1)
        return (
            (sub_ids[:, None] == rep_target[None, :])
            & (mc.stage_valid & is_mc)[None, :]
            & sub_enabled[:, None]
        )

    def epoch_body(carry, epoch_xs):
        # prof: this epoch's scalar-leaf profile; flt: this epoch's fault
        # masks — link_ok (R, P), router_ok (R,), mc_ok (R,), telem ()s;
        # plc: this epoch's placement plans — cls0/cls1 (R,)
        epoch_key, prof, flt, plc = epoch_xs
        subs, mc, phase, outst, backlog, policy, pred_state, cycle0 = carry

        # ---- epoch-invariant hoisting (DESIGN.md §11): `policy.config` is
        # frozen until the KF acts at the epoch boundary, so the VC masks,
        # the SA preference stream, the link-activation parity and ALL of
        # the cycle RNG are computed here once and fed to the cycle scan as
        # per-cycle `xs` instead of being recomputed every cycle.
        config_idx = policy.config
        g_vec, c_vec = class_vc_masks(mp, config_idx)          # (V,)
        gpu_masks = jnp.broadcast_to(g_vec, (S, V))
        cpu_masks = jnp.broadcast_to(c_vec, (S, V))

        # ---- traced node identity (DESIGN.md §17): the applied config
        # selects between this epoch's base/boosted placement plans (gated
        # on `place_enable`), and EVERY class-derived quantity follows.
        # MC rows re-assert NT_MC — memory controllers are physical.  With
        # the identity stream all of these select the static topology
        # values bit-for-bit.
        cls_e = placement_class(mp, config_idx, plc.cls0, plc.cls1)
        ntype_e = jnp.where(is_mc, 2, cls_e)               # (R,) virtual type
        is_gpu = ntype_e == 1
        is_cpu = ntype_e == 0
        node_cls = jnp.where(is_gpu, 1, 0)  # class a node's traffic belongs to
        # request subnet of a node's own traffic; the reply subnet
        # additionally depends on the requester's class under
        # class-segregated routing.
        req_sub = jnp.where(fs, 2 * node_cls, 0)

        # Epoch prologue: replies staged on the previous epoch's last cycle
        # inject under THIS epoch's masks.  The in-cycle merged inject is
        # gated off on the epoch's last cycle (`rep_gate`), which preserves
        # the original engine's ordering across a KF reconfiguration: a
        # reply staged at cycle E-1 always entered the network with the
        # *new* epoch's VC partition.
        subs, ok0 = rt.inject_all(
            subs, make_want_rep(mc), mc.stage_dst, ar,
            mc.stage_cls, cycle0, gpu_masks, cpu_masks,
        )
        mc = mc._replace(stage_valid=mc.stage_valid & ~jnp.any(ok0, axis=0))

        # Per-epoch RNG streams: the SAME keys and draws as the old
        # per-cycle `split(cycle_key, 3)` engine, batched with vmap (a
        # value-preserving transform), so every stream is bitwise-identical
        # to drawing inside the loop.
        ep_len = stc.epoch_len
        keys = jax.random.split(epoch_key, ep_len)
        k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
        u_phase = jax.vmap(lambda k: jax.random.uniform(k, ()))(k3[:, 0])
        u_gen = jax.vmap(
            lambda k: jax.random.uniform(k, (R,), jnp.float32)
        )(k3[:, 1])
        d_idx = jax.vmap(
            lambda k: jax.random.randint(k, (R,), 0, mc_ids.shape[0])
        )(k3[:, 2])
        dests_all = jnp.take(mc_ids, d_idx)                     # (L, R)
        cycles = cycle0 + jnp.arange(ep_len, dtype=jnp.int32)
        sa_all = epoch_sa_prefs(mp, config_idx, cycles)         # (L,)
        # subnet link activation: full width (2-subnet) or alternating-cycle
        # half width (4-subnet); padded subnet rows are never active.
        alternating = (cycles[:, None] % 2) == (jnp.arange(S)[None, :] % 2)
        active_all = sub_enabled[None, :] & jnp.where(fs, alternating, True)
        rep_gate = jnp.arange(ep_len) < ep_len - 1
        xs = (cycles, u_phase, u_gen, dests_all, sa_all, active_all, rep_gate)

        def cycle_body(carry, x):
            if probe_on:
                subs, mc, phase, outstanding, bl_count, cnt, prb = carry
            else:
                subs, mc, phase, outstanding, bl_count, cnt = carry
            cycle, u_ph, u_gen_c, dests, sa_pref, active, gate = x

            # MC acceptance applies to ejections on *request* subnets at MC
            # nodes, judged on the queue depth BEFORE this cycle's service
            # frees a slot.  With multiple request subnets (4-subnet mode)
            # up to S/2 packets can arrive at one MC per cycle, so reserve
            # that many slots.
            mc_space = mc.count <= stc.mc_queue_cap - n_req_subs
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
                subs, route_t, nb_t, opp_t,
                gpu_masks, cpu_masks, sa_pref, accept_s, active,
                arbitrate_fn=arb_fn,
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
            rates = injection_rates(prof, ntype_e, phase)
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
                (sub_ids[:, None] == req_sub[None, :])
                & can_inj[None, :]
                & sub_enabled[:, None]
            )
            want_rep = make_want_rep(mc) & gate
            is_req_row = sub_is_req[:, None]
            subs, ok = rt.inject_all(
                subs, want_src | want_rep,
                jnp.where(is_req_row, dests[None, :], mc.stage_dst[None, :]),
                jnp.broadcast_to(ar, (S, R)),
                jnp.where(
                    is_req_row, node_cls[None, :], mc.stage_cls[None, :]
                ),
                jnp.where(is_req_row, cycle, cycle + 1),
                gpu_masks, cpu_masks,
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
            if probe_on:
                # ---- 7. flight-recorder accumulation from END-of-cycle
                # state (the fused engine samples at the same point)
                prb = _ProbeAcc(
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

        if fused_engine:
            # ---- fused path (DESIGN.md §13): pack the carry into lane
            # layout once per epoch, run ONE pallas_call per cycle with the
            # whole state in kernel refs, unpack at the epoch boundary.
            # Everything outside the cycle scan (prologue inject, RNG, KF,
            # policy) is byte-for-byte the dense engine's code above/below.
            gm_rows, cm_rows = lanes.mask_rows(lane_dims, g_vec, c_vec)
            pr_rows = lanes.prof_rows(prof)
            # placement lane rows (DESIGN.md §17): the node-type row and
            # the req_match-bearing policy rows follow this epoch's plan
            ntype_row = lanes.placement_rows(lane_dims, ntype_e)
            req_match = (
                (sub_ids[:, None] == req_sub[None, :]) & sub_enabled[:, None]
            )
            pol_sr, pol_r = lanes.policy_rows(
                lane_dims, sub_enabled, sub_is_req, sub_is_rep, req_match,
                fs, n_req_subs,
            )
            xi, xf = lanes.cycle_xs(
                lane_dims, cycles, u_phase, u_gen, dests_all, sa_all,
                active_all, rep_gate,
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

            if probe_on:
                (ls, pb), _ = jax.lax.scan(
                    fused_cycle_probed, (ls0, lanes.zero_probe(lane_dims)),
                    (xi, xf), unroll=stc.cycle_unroll,
                )
                prb = _ProbeAcc(*lanes.unpack_probe(lane_dims, pb))
            else:
                ls, _ = jax.lax.scan(
                    fused_cycle, ls0, (xi, xf), unroll=stc.cycle_unroll
                )
            subs, mc, outst, backlog, phase = lanes.unpack_state(
                lane_dims, ls, MCState, subnets0.buf_binj.dtype
            )
            cnt = EpochCounters(
                *(ls.cnt[0, i] for i in range(lanes.N_COUNTERS))
            )
        else:
            inner0 = (subs, mc, phase, outst, backlog, _zero_counters())
            if probe_on:
                inner0 = inner0 + (_zero_probe_acc(S, R, V),)
                (subs, mc, phase, outst, backlog, cnt, prb), _ = jax.lax.scan(
                    cycle_body, inner0, xs, unroll=stc.cycle_unroll
                )
            else:
                (subs, mc, phase, outst, backlog, cnt), _ = jax.lax.scan(
                    cycle_body, inner0, xs, unroll=stc.cycle_unroll
                )
        cycle = cycle0 + jnp.int32(stc.epoch_len)

        # ---- KF epoch update (paper §3.2)
        raw = jnp.stack(
            [
                cnt.gpu_stall_dram.astype(jnp.float32),
                cnt.gpu_push.astype(jnp.float32),
                cnt.gpu_stall_icnt.astype(jnp.float32),
            ]
        )
        z = kalman.normalize_observations(raw, jnp.zeros(3), z_scales)
        # telemetry corruption (DESIGN.md §16): applied AFTER normalization
        # so a spike escapes the [-1, 1] clip the way a corrupted counter
        # bus escapes the sensor's calibrated range.  Mode 0 selects the
        # clean vector through every `where`, so a healthy epoch's z is
        # bit-for-bit the pre-fault program's.
        tm = flt.telem_mode
        z = jnp.where(tm == TELEM_DROP, jnp.full_like(z, -1.0), z)
        z = jnp.where(tm == TELEM_SPIKE, z + flt.telem_mag, z)
        z = jnp.where(tm == TELEM_NAN, jnp.full_like(z, jnp.nan), z)
        # predictor bank (DESIGN.md §12): every member advances, the traced
        # `mp.predictor.kind` selects which signal drives the hysteresis
        # machine — the KF lane reproduces the legacy
        # `binarize(kalman.step(...).x[0])` bitwise.
        if probe_on:
            pred_state, signal, kfi = predictor.step_probed(
                mp.predictor, kf_params, pred_state, z
            )
        else:
            pred_state, signal = predictor.step(
                mp.predictor, kf_params, pred_state, z
            )
        policy = apply_policy_gated(stc.policy, mp, policy, signal, cycle)
        # degraded-mode fallback (DESIGN.md §16): while the predictor
        # watchdog reports unhealthy, the applied configuration reverts to
        # the fair static split; `healthy` is constant True whenever the
        # guard is disarmed, so this is an identity on pre-guard programs.
        policy = degrade_policy(policy, pred_state.healthy)

        # ---- IPC proxies (documented in metrics.py)
        gpu_ipc = metrics.gpu_ipc_proxy(
            cnt.gpu_done.astype(jnp.float32), cnt.gpu_gen.astype(jnp.float32)
        )
        cpu_lat = cnt.cpu_lat_sum / jnp.maximum(cnt.cpu_lat_cnt, 1)
        cpu_ipc = metrics.cpu_ipc_proxy(cpu_lat)
        avg_lat = cnt.lat_sum / jnp.maximum(cnt.lat_cnt, 1)
        inj_rate = (cnt.gpu_push.astype(jnp.float32)
                    / (stc.epoch_len * jnp.sum(is_gpu)))

        out = (gpu_ipc, cpu_ipc, avg_lat, signal, policy.config, cnt, inj_rate,
               jnp.sum(g_vec.astype(jnp.int32)))
        if probe_on:
            # fault-event channel: how many fabric elements this epoch's
            # masks suppressed, plus whether telemetry was corrupted
            faults_active = (
                jnp.sum((~flt.link_ok).astype(jnp.int32))
                + jnp.sum((~flt.router_ok).astype(jnp.int32))
                + jnp.sum((~flt.mc_ok).astype(jnp.int32))
                + (tm != 0).astype(jnp.int32)
            )
            # placement channel (DESIGN.md §17): the virtual node class
            # applied this epoch — shared by every backend, so the
            # relocation timeline is cross-engine congruent by construction
            out = (out, (prb, kfi, z, faults_active, cls_e))
        return (subs, mc, phase, outst, backlog, policy, pred_state, cycle), out

    key0 = jax.random.PRNGKey(seed)
    epoch_keys = jax.random.split(key0, stc.n_epochs)
    carry0 = (
        subnets0,
        mc0,
        init_phase(),
        outstanding0,
        backlog0,
        init_policy_state(),
        predictor.init_state(),
        jnp.int32(0),
    )
    _, outs = jax.lax.scan(
        epoch_body, carry0, (epoch_keys, profile, faults, placement)
    )
    if probe_on:
        outs, (prb, kfi, z_obs, faults_active, place_cls) = outs
    gpu_ipc, cpu_ipc, avg_lat, sig, conf, cnt, inj, quota = outs
    result = SimResult(
        gpu_ipc=gpu_ipc,
        cpu_ipc=cpu_ipc,
        avg_latency=avg_lat,
        kf_signal=sig,
        applied_config=conf,
        counters=cnt,
        gpu_inj_rate=inj,
        gpu_vc_quota=quota,
    )
    if not probe_on:
        return result
    trace = SimTrace(
        occ_sum=prb.occ,
        arb_grant=prb.grant,
        arb_deny=prb.deny,
        mcq_sum=prb.mcq_sum,
        mcq_max=prb.mcq_max,
        kf_innovation=kfi.innovation,
        kf_gain=kfi.gain,
        kf_cov_trace=kfi.cov_trace,
        kf_x_pred=kfi.x_pred,
        z_obs=z_obs,
        kf_nis=kfi.nis,
        kf_rejected=kfi.rejected,
        kf_reset=kfi.reset,
        kf_healthy=kfi.healthy,
        faults_active=faults_active,
        place_cls=place_cls,
    )
    return result, trace


_SIM_JIT = jax.jit(_simulate_impl, static_argnums=0)

_BATCH_JIT = None


def _batch_jit():
    """Batched entry: vmap over (policy tensors, profile, seed, carry).

    Carry buffers are donated so XLA reuses the (B, S, R, P, V, B)-sized
    state in place; CPU's runtime has no donation support, so skip it there
    to avoid a warning per call.  Built lazily on first use — deciding at
    import time would initialize the JAX backend before callers can
    configure the platform (e.g. `jax.config.update("jax_platform_name")`).
    """
    global _BATCH_JIT
    if _BATCH_JIT is None:
        donate = () if jax.default_backend() == "cpu" else (4,)
        _BATCH_JIT = jax.jit(
            jax.vmap(_simulate_impl, in_axes=(None, 0, 0, 0, 0, 0, 0)),
            static_argnums=0,
            donate_argnums=donate,
        )
    return _BATCH_JIT


def _run_faults(source: FaultSourceLike, stc: SimStatic) -> FaultStream:
    """Lower a config's fault source against the run topology.

    The neighbor table makes link faults symmetric (a dead link is dead
    both ways — `faults.FaultSchedule.materialize`)."""
    topo = make_topology(stc.width, stc.height, stc.n_mc)
    return resolve_faults(
        source, stc.n_epochs, n_routers=topo.n_routers,
        neighbor=topo.neighbor, opposite=topo.opposite,
    )


def _run_placement(
    source: PlacementSourceLike, stc: SimStatic
) -> PlacementStream:
    """Lower a config's placement source against the run topology."""
    topo = make_topology(stc.width, stc.height, stc.n_mc)
    return resolve_placement(source, stc.n_epochs, topo)


def simulate(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
    backend: str | None = None,
) -> SimResult:
    """Run one configuration (compiles at most once per `SimStatic`).

    ``source`` may be any `traffic.TrafficSource` — a stationary
    `WorkloadProfile`, a `traffic.ScenarioSchedule` (piecewise workload
    program — DESIGN.md §12), a replayed `traffic.RecordedTrace`
    (DESIGN.md §15), or a name resolving to any of them; it is lowered to
    per-epoch rows by `traffic.resolve_source` before dispatch, so every
    source kind reuses the same compiled program as stationary workloads.

    With ``padded=True`` (default) every mode runs the shared S/V-padded
    program; ``padded=False`` compiles the mode's dedicated trace, kept so
    the equivalence tests can pin padded == dedicated bit-for-bit.
    ``backend`` overrides the config's cycle-engine backend ("ref" |
    "pallas" | "pallas_arb", see DESIGN.md §11/§13 — "pallas" is the fused
    full-cycle lane kernel); each backend is its own `SimStatic`, so opting
    into a Pallas path never perturbs the default program's trace count.
    """
    return _SIM_JIT(*sim_args(cfg, source, padded, backend))


def sim_args(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
    backend: str | None = None,
) -> tuple:
    """The `_SIM_JIT` arguments `simulate` dispatches: (SimStatic, policy,
    demand rows, seed, initial state, faults, placement)."""
    stc = cfg.static_spec(padded)
    if backend is not None:
        stc = dataclasses.replace(stc, backend=backend)
    return (
        stc,
        cfg.mode_policy(padded),
        resolve_source(source, stc.n_epochs),
        jnp.int32(cfg.seed),
        init_sim_state(stc),
        _run_faults(cfg.faults, stc),
        _run_placement(cfg.placement, stc),
    )


def simulate_with_trace(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
    backend: str | None = None,
) -> tuple[SimResult, SimTrace]:
    """`simulate` with the flight recorder on: returns (SimResult, SimTrace).

    Forces ``probe.enabled`` — a distinct `SimStatic`, so the probed
    program is its own single trace and the probes-off program (goldens,
    sweeps) is never perturbed.  `SimResult` is bitwise the probes-off
    result; `SimTrace` is bitwise-equal across cycle-engine backends
    (tests/test_obs.py)."""
    if not cfg.probe.enabled:
        cfg = dataclasses.replace(cfg, probe=ProbeConfig(enabled=True))
    return simulate(cfg, source, padded=padded, backend=backend)


def _tree_rows(tree, sl):
    return jax.tree.map(lambda x: x[sl], tree)


def _pad_rows(tree, n_pad: int):
    """Append n_pad copies of row 0 along axis 0 of every leaf (discarded
    after the dispatch)."""
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.repeat(x[:1], n_pad, axis=0)], axis=0
        ),
        tree,
    )


# Sharded dispatch cache: one jitted shard_map program per (SimStatic, Mesh).
# jit itself handles per-batch-shape retraces under each entry.
_SHARD_JIT: dict = {}


def _sharded_jit(stc: SimStatic, mesh):
    """Data-parallel batched entry: the vmapped program under shard_map.

    The batch axis is split across the mesh's `sweep` axis; each device runs
    the SAME per-shard vmapped program with no cross-device communication:
    every mesh axis is manual here and no collective is ever emitted.
    """
    key = (stc, mesh)
    if key not in _SHARD_JIT:
        from jax.sharding import PartitionSpec as P

        batched = jax.vmap(_simulate_impl, in_axes=(None, 0, 0, 0, 0, 0, 0))

        def shard_body(mp, prof, seeds, state0, flt, plc):
            return batched(stc, mp, prof, seeds, state0, flt, plc)

        spec = P(SWEEP_AXIS)
        # check_vma off: the scans in `_simulate_impl` start carries such as
        # the zeroed epoch counters from constants, which are invariant over
        # the `sweep` axis while the scan bodies make them per-shard; jax
        # 0.9.0 refuses such a scan unless every one of those carries is
        # pcast to varying inside the program the unsharded paths share.
        # No collective is emitted, so the check has nothing to verify.
        # Carry donation mirrors _batch_jit (state0 is shard_body arg 3;
        # CPU has no donation support).
        donate = () if jax.default_backend() == "cpu" else (3,)
        _SHARD_JIT[key] = jax.jit(
            jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(spec,) * 6, out_specs=spec,
                axis_names={SWEEP_AXIS}, check_vma=False,
            ),
            donate_argnums=donate,
        )
    return _SHARD_JIT[key]


def batch_args(
    cfgs: Sequence[NoCConfig],
    sources: TrafficSourceLike | Sequence,
    seeds: Sequence[int] | None = None,
) -> tuple:
    """Stack B configs into the batched program's inputs: (SimStatic,
    policy, demand rows, seeds, faults, placement), each leaf with a
    leading (B,) axis (see `simulate_batch` for the arguments)."""
    cfgs = list(cfgs)
    if not cfgs:
        raise ValueError("simulate_batch needs at least one config")
    stc = cfgs[0].static_spec()
    for c in cfgs[1:]:
        if c.static_spec() != stc:
            raise ValueError(
                "all configs in a batch must share the same structural "
                f"config; got {c.static_spec()} != {stc} — group with sweep()"
            )
    B = len(cfgs)
    # NB WorkloadProfile is itself a tuple, so a single source must be
    # detected by type (name or TrafficSource), not by Sequence-ness.
    if isinstance(sources, (str, TrafficSource)):
        sources = [sources] * B
    profiles = [resolve_source(s, stc.n_epochs) for s in sources]
    if len(profiles) != B:
        raise ValueError(f"{len(profiles)} sources for {B} configs")
    if seeds is None:
        seeds = [c.seed for c in cfgs]
    seeds = jnp.asarray(list(seeds), jnp.int32)
    if seeds.shape[0] != B:
        raise ValueError(f"{seeds.shape[0]} seeds for {B} configs")

    mp = jax.tree.map(lambda *xs: jnp.stack(xs), *[c.mode_policy() for c in cfgs])
    prof = stack_profiles(profiles)
    flt = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[_run_faults(c.faults, stc) for c in cfgs]
    )
    plc = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_run_placement(c.placement, stc) for c in cfgs],
    )
    return stc, mp, prof, seeds, flt, plc


def simulate_batch(
    cfgs: Sequence[NoCConfig],
    sources: TrafficSourceLike | Sequence,
    seeds: Sequence[int] | None = None,
    batch_tile: int | None = None,
    devices: int | None = None,
    mesh=None,
) -> SimResult:
    """Evaluate many configurations in lockstep: one compiled program,
    one device dispatch per tile.

    cfgs      — length-B configs; all must share the same `static_spec()`
                (mode/ratio/seed/subnet-structure/predictor are traced).
    sources   — length-B demand sources, or one for all rows; each entry
                may be any `traffic.TrafficSource` (`WorkloadProfile`,
                `ScenarioSchedule`, `RecordedTrace`) or a name resolving
                to one — all rows lower through `traffic.resolve_source`
                to per-epoch rows and share the one compiled program.
    seeds     — optional per-row seeds; defaults to each cfg's own seed.
    batch_tile— if set, the batch is processed in fixed-size tiles (short
                batches and the ragged tail padded up), so EVERY sweep in
                the process reuses the same (tile-shaped) executable
                regardless of its batch size.
    devices / mesh —
                shard the batch axis data-parallel across devices: the flat
                point list is padded to a multiple of the device count and
                dispatched once through the shard_map path (`batch_tile` is
                ignored; per-device row count is the effective tile).
                `devices=N` builds a mesh over the first N local devices;
                pass `mesh` to reuse one (must have a `sweep` axis).

    Returns a `SimResult` whose leaves carry a leading (B,) axis.
    """
    stc, mp, prof, seeds, flt, plc = batch_args(cfgs, sources, seeds)
    B = int(seeds.shape[0])

    if devices is not None or mesh is not None:
        if mesh is None:
            from repro.dist import sharding as dist_sharding

            mesh = dist_sharding.sweep_mesh(devices)
        ndev = int(mesh.devices.size)
        padded_b = -(-B // ndev) * ndev
        mp, prof, seeds, flt, plc = (
            _pad_rows(t, padded_b - B) for t in (mp, prof, seeds, flt, plc)
        )
        out = _sharded_jit(stc, mesh)(
            mp, prof, seeds, init_sim_state(stc, padded_b), flt, plc
        )
        return _tree_rows(out, slice(0, B))

    tile = B if batch_tile is None else batch_tile
    parts = []
    for lo in range(0, B, tile):
        sl = slice(lo, min(lo + tile, B))
        n = sl.stop - sl.start
        mp_t, prof_t, seeds_t, flt_t, plc_t = (
            _tree_rows(t, sl) for t in (mp, prof, seeds, flt, plc)
        )
        if n < tile:  # pad the ragged tail by repeating row 0 (discarded)
            mp_t, prof_t, seeds_t, flt_t, plc_t = (
                _pad_rows(t, tile - n)
                for t in (mp_t, prof_t, seeds_t, flt_t, plc_t)
            )
        out = _batch_jit()(
            stc, mp_t, prof_t, seeds_t, init_sim_state(stc, tile), flt_t,
            plc_t,
        )
        parts.append(_tree_rows(out, slice(0, n)))
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)


class SweepSpec(NamedTuple):
    """One row of a sweep: a network config x workload x seed point.

    ``workload`` names any demand source resolvable by
    `traffic.lookup_workload`: a stationary profile (`traffic.PROFILES`),
    a scenario schedule (`traffic.SCENARIOS`), or a trace/custom source
    added via `traffic.register_workload` / `traffic.register_trace`
    (DESIGN.md §15); ``predictor`` picks the bank member driving the
    hysteresis machine (meaningful for mode="kf" — the predictor-ablation
    axis, DESIGN.md §12).

    ``faults`` names a registered fault scenario (`faults.FAULTS`, None =
    healthy) and ``guard`` arms the predictor's self-healing layer
    (DESIGN.md §16) — both traced data, so the whole fault x guard grid
    rides the same compiled program and batches into one dispatch.  A
    ``faults``/``guard`` key in `sweep`'s overrides (e.g. the shared
    `--faults` CLI flag) takes precedence over the per-spec value.

    ``placement`` names a registered placement scenario
    (`placement.PLACEMENTS`, None = the identity/static layout) and
    ``control`` picks which lever(s) the applied config drives
    ("bandwidth" | "placement" | "joint" — DESIGN.md §17); both traced
    data with the same override-precedence rule as ``faults``/``guard``
    (the shared `--placement` CLI flag)."""

    mode: str
    workload: str
    static_gpu_vcs: int = 2
    seed: int = 0
    predictor: str = "kf"
    faults: str | None = None
    guard: bool = False
    placement: str | None = None
    control: str = "bandwidth"


# Tile size for sweep batches.  The paper sweeps (4 workloads x 3 ratios,
# 6 workloads x 4 modes) are all multiples of 6 once multiplied by any seed
# count, so 6 gives zero padding waste while keeping every sweep on the one
# shared S/V-padded executable.
SWEEP_TILE = 6

# Mesh axis name the sharded sweep path splits the batch axis over.
SWEEP_AXIS = "sweep"


def sweep(
    specs: Sequence[SweepSpec],
    batch_tile: int | None = SWEEP_TILE,
    devices: int | None = None,
    mesh=None,
    **overrides,
) -> list[SimResult]:
    """Run a heterogeneous sweep, batching rows that share an executable.

    Rows are grouped by `static_spec()` — since the S-padding refactor
    (DESIGN.md §10) every mode shares one spec, so the whole sweep is a
    single group and dispatches once — each group runs through
    `simulate_batch`, and results come back as one `SimResult` per spec, in
    input order.  `overrides` are forwarded to every row's `NoCConfig`
    (e.g. n_epochs=30); `devices`/`mesh` select the device-sharded dispatch
    path (see `simulate_batch`).
    """
    specs = list(specs)
    rows: list[SimResult | None] = [None] * len(specs)
    groups: dict[SimStatic, list[int]] = defaultdict(list)
    cfgs = []
    for i, sp in enumerate(specs):
        kw = dict(overrides)
        kw.setdefault("faults", sp.faults)
        kw.setdefault("guard", sp.guard)
        kw.setdefault("placement", sp.placement)
        kw.setdefault("control", sp.control)
        cfg = NoCConfig(
            mode=sp.mode, static_gpu_vcs=sp.static_gpu_vcs, seed=sp.seed,
            predictor=sp.predictor, **kw,
        )
        cfgs.append(cfg)
        groups[cfg.static_spec()].append(i)
    for idxs in groups.values():
        res = simulate_batch(
            [cfgs[i] for i in idxs],
            [specs[i].workload for i in idxs],
            batch_tile=batch_tile,
            devices=devices,
            mesh=mesh,
        )
        for j, i in enumerate(idxs):
            rows[i] = _tree_rows(res, j)
    return rows


def sweep_sharded(
    specs: Sequence[SweepSpec],
    devices: int | None = None,
    mesh=None,
    **overrides,
) -> list[SimResult]:
    """`sweep` with the flat point list data-parallel across devices.

    The point list is padded to a multiple of the device count (pad rows
    repeat row 0 and are discarded), then the whole sweep runs as ONE
    shard_map dispatch of the shared padded program.  Defaults to all local
    devices; results are identical to `sweep` row-for-row.
    """
    if mesh is None and devices is None:
        devices = len(jax.devices())
    return sweep(specs, batch_tile=None, devices=devices, mesh=mesh,
                 **overrides)


def run_workload(mode: str, workload: str, **overrides) -> SimResult:
    cfg = NoCConfig(mode=mode, **overrides)
    return simulate(cfg, workload)


def summarize(res: SimResult, warmup_epochs: int = 10) -> dict:
    # Clamp the warmup slice so short runs (n_epochs <= warmup_epochs, e.g.
    # the fig4/fig12 smoke invocations) summarize their tail epoch instead
    # of taking the mean of an empty slice (NaN).
    n_epochs = int(res.gpu_ipc.shape[-1])
    sl = slice(min(warmup_epochs, max(n_epochs - 1, 0)), None)
    return {
        "gpu_ipc": float(jnp.mean(res.gpu_ipc[sl])),
        "cpu_ipc": float(jnp.mean(res.cpu_ipc[sl])),
        "avg_latency": float(jnp.mean(res.avg_latency[sl])),
        "kf_on_frac": float(jnp.mean(res.applied_config[sl])),
    }


def summarize_seeds(rows: Sequence[SimResult], warmup_epochs: int = 10) -> dict:
    """Aggregate one sweep point over its seed replicas: mean + `<k>_std`."""
    per = [summarize(r, warmup_epochs) for r in rows]
    out = {}
    for k in per[0]:
        vals = np.asarray([p[k] for p in per])
        out[k] = float(vals.mean())
        out[k + "_std"] = float(vals.std())
    return out
