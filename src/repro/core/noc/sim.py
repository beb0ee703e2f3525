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
import functools
from collections import defaultdict
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

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
from repro.core.noc.engine import (
    CycleRows,
    EpochCounters,
    EpochInputs,
    Fabric,
    MCState,
    PeerCounters,
    ref_engine,
    staged_reply_want,
)
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
from repro.core.noc.topology import NT_GPU, Topology, make_topology
from repro.obs.probes import ProbeConfig, SimTrace
from repro.obs.profiling import span
from repro.core.noc.traffic import (
    PeerProfile,
    TrafficSource,
    TrafficSourceLike,
    WorkloadProfile,
    init_phase,
    resolve_source,
    stack_profiles,
)

Array = jax.Array

# Padded subnet-axis length shared by every mode's program (DESIGN.md §10):
# large enough for the 4-subnet network; 2-subnet modes leave rows 2..3
# zero-width (never injected into, links never active).
S_MAX = 4

# Tile-to-tile destinations (DESIGN.md §19).  The peer draw's key is the
# epoch key folded with DEST_STREAM: past any cycle index, so it never
# meets a key of the epoch's per-cycle split and every existing stream
# stays as it was.  A draw is the top PEER_BITS bits of one uint32 a
# (cycle, router); the peer pick scales the share's threshold by the
# source's cumulative peer weights in int32, hence the cap on their sum.
DEST_STREAM = 0xFFFFFFFF
PEER_BITS = 23
PEER_WEIGHT_MAX = (2**31 - 1) // 2**PEER_BITS      # 255


class PeerTable(NamedTuple):
    """A peer table lowered for the destination draw (host arrays): the
    routers that hold experts, and per source router the inclusive
    cumulative weight over them with the source's own left out."""

    ids: np.ndarray    # (H,) int32 — routers with a nonzero weight
    cum: np.ndarray    # (R, H) int32


def peer_dests(peers: PeerTable, epoch_key, share, is_gpu, dests):
    """An epoch's request destinations `dests` (L, R) with peer ones drawn
    in (DESIGN.md §19): one PEER_BITS-bit draw u a (cycle, router) from
    the epoch's own stream; a GPU source with peers sends to a peer iff
    u < t = floor(share * 2^PEER_BITS), and then to the first peer whose
    bound t * cum // total exceeds u, so each peer of a source is drawn in
    proportion to its weight and the source never is."""
    key = jax.random.fold_in(epoch_key, DEST_STREAM)
    u = (jax.random.bits(key, dests.shape, jnp.uint32)
         >> (32 - PEER_BITS)).astype(jnp.int32)
    t = jnp.floor(share * float(2**PEER_BITS)).astype(jnp.int32)
    total = peers.cum[:, -1]
    bound = t * peers.cum // np.maximum(total, 1)[:, None]
    pick = jnp.sum(bound[None] <= u[..., None], axis=-1)
    to_peer = is_gpu & (total > 0) & (u < t)
    return jnp.where(to_peer, jnp.take(peers.ids, pick, mode="clip"), dests)


def peer_table(weights: Sequence[int], topo: Topology) -> PeerTable:
    """Validate `NoCConfig.peer_weights` against the package and lower it.

    One non-negative integer a router, in router order, nonzero only on
    GPU tiles, summing to at most PEER_WEIGHT_MAX."""
    w = np.asarray(weights)
    R = topo.n_routers
    if w.shape != (R,) or not np.issubdtype(w.dtype, np.integer):
        raise ValueError(
            f"peer_weights needs one integer per router ({R}); got {weights}")
    if (w < 0).any() or w.sum() == 0 or w.sum() > PEER_WEIGHT_MAX:
        raise ValueError(
            "peer_weights must be non-negative with a sum in "
            f"[1, {PEER_WEIGHT_MAX}]; got sum {int(w.sum())}")
    if (w[topo.node_type != NT_GPU] != 0).any():
        raise ValueError("peer_weights may be nonzero on GPU tiles only")
    ids = np.flatnonzero(w).astype(np.int32)
    own = np.where(np.arange(R)[:, None] == ids[None, :], 0, w[ids][None, :])
    return PeerTable(ids, np.cumsum(own, axis=1).astype(np.int32))


def platform_engine() -> str:
    """The cycle engine for the default platform: the fused Pallas kernel
    on a TPU, the dense jnp engine elsewhere (DESIGN.md §11, §13)."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _cycle_engine(backend: str):
    """The builder of the named cycle engine (`repro.core.noc.engine`)."""
    if backend == "ref":
        return ref_engine
    if backend == "pallas":
        from repro.kernels.noc_cycle.engine import pallas_engine

        return pallas_engine
    raise ValueError(
        f"unknown cycle-engine backend {backend!r}; expected ref|pallas")


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
    # the cycle engine to trace (DESIGN.md §11, §13): "ref" = the dense jnp
    # engine, "pallas" = the fused full-cycle repro.kernels.noc_cycle lane
    # kernel; `NoCConfig.static_spec` resolves the platform's default.
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
    # tile-to-tile destinations (DESIGN.md §19): the experts each router
    # holds, in router order.  None traces the MC-only program; a table
    # makes its GPU tiles servers and draws peer destinations.
    peer_weights: tuple[int, ...] | None = None


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
    # cycle engine: ref | pallas; None = the platform's (`platform_engine`)
    backend: str | None = None
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
    # tile-to-tile destinations (DESIGN.md §19) — structural; see SimStatic
    peer_weights: tuple[int, ...] | None = None

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
            backend=self.backend or platform_engine(),
            stamp_dtype=self.stamp_dtype,
            probe=self.probe,
            width=self.width,
            height=self.height,
            n_mc=self.n_mc,
            peer_weights=(None if self.peer_weights is None
                          else tuple(int(w) for w in self.peer_weights)),
        )

    def mode_policy(self, padded: bool = True) -> ModePolicy:
        stc = self.static_spec(padded)
        return mode_policy(
            self.mode, stc.n_vcs, self.static_gpu_vcs,
            n_subnets=stc.n_subnets, active_vcs=self.vcs_per_subnet,
            predictor=self.predictor, ema_alpha=self.ema_alpha,
            guard=self.guard, control=self.control,
        )


class SimResult(NamedTuple):
    gpu_ipc: Array        # (E,) per-epoch GPU IPC proxy
    cpu_ipc: Array        # (E,)
    avg_latency: Array    # (E,) mean packet network latency
    kf_signal: Array      # (E,) binarized KF output
    applied_config: Array  # (E,) configuration actually applied
    # (E,) leaves: `EpochCounters`, or `PeerCounters` with a peer table
    counters: EpochCounters | PeerCounters
    gpu_inj_rate: Array   # (E,) offered GPU load (Fig. 4 trace)
    # VCs the GPU class could occupy during the epoch — pins the hoisted
    # per-epoch masks to the policy state that entered the epoch (the mask
    # flip must trail `applied_config` by exactly one epoch; see
    # tests/test_cycle_engine.py's policy-boundary regression test).
    gpu_vc_quota: Array   # (E,)


def _make_kf(stc: SimStatic):
    return kalman.paper_params(q=stc.kf_q, r=stc.kf_r)


def init_sim_state(stc: SimStatic, batch: int | None = None):
    """Zero-initialized carry buffers (subnets, MC queues, source backlogs),
    as host (NumPy) arrays.

    Built outside the jitted entry points so the batched path can donate
    them: XLA then reuses the buffers in place instead of holding both the
    init and the first-iteration copy live.  They cross to the device in
    the same transfer as the rest of a dispatch's arguments, which makes a
    fresh buffer for every dispatch.
    """
    topo = make_topology(stc.width, stc.height, stc.n_mc)
    R = topo.n_routers
    S, V, B = stc.n_subnets, stc.n_vcs, stc.buf_depth

    def z(shape, dtype=np.int32):
        if batch is not None:
            shape = (batch,) + shape
        return np.zeros(shape, dtype)

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
        binj_dtype = np.int32
    elif stc.stamp_dtype == "auto":
        binj_dtype = np.uint16 if total_cycles <= 2**16 else np.int32
    else:
        raise ValueError(
            f"unknown stamp_dtype {stc.stamp_dtype!r}; expected auto|int32"
        )
    subnets0 = rt.SubnetState(
        buf_meta=z((S, R, rt.N_PORTS, V, B), np.int16),
        buf_binj=z((S, R, rt.N_PORTS, V, B), binj_dtype),
        head=z((S, R, rt.N_PORTS, V), np.int8),
        count=z((S, R, rt.N_PORTS, V), np.int8),
        rr_ptr=z((S, R, rt.N_PORTS), np.int8),
    )
    mc0 = MCState(
        q_meta=z((R, stc.mc_queue_cap), np.int8),
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
    stays a static table and the virtual node type re-asserts it.

    With a peer table (``stc.peer_weights``, DESIGN.md §19) the GPU tiles
    holding experts serve requests beside the MCs (`Fabric.is_server`),
    and a GPU source sends a request to a peer with probability
    ``profile.gpu_peer_share`` of a `traffic.PeerProfile` (0 for a plain
    `WorkloadProfile`; label `epoch.dest`)."""
    _trace_counter[0] += 1  # Python side effect: runs only at trace time

    topo = make_topology(stc.width, stc.height, stc.n_mc)
    route_t, nb_t, opp_t, ntype, mc_ids = rt.device_tables(topo)
    R = topo.n_routers
    S = stc.n_subnets
    V = stc.n_vcs

    is_mc = ntype == 2  # static: MCs are physical, placement never moves them
    ar = jnp.arange(R)
    peers = None if stc.peer_weights is None else peer_table(
        stc.peer_weights, topo)
    is_server = is_mc if peers is None else (
        is_mc | jnp.asarray(np.isin(np.arange(R), peers.ids)))

    # Traced subnet structure (DESIGN.md §10): which rows of the padded
    # subnet axis are live, which carry requests, and whether routing is
    # class-segregated.  Padded rows are zero-width: excluded from every
    # inject want-matrix and link-inactive in the cycle engines, so no
    # packet can ever enter them.
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
    # the exact pre-probe program (the accumulators below and in the cycle
    # engines are Python-gated, not lax.cond-gated), probes on is its own
    # single trace.
    probe_on = stc.probe.enabled

    kf_params = _make_kf(stc)
    z_scales = jnp.asarray(stc.z_scales, jnp.float32)

    # the cycle engine (DESIGN.md §11, §13), built once a run and called
    # once an epoch; both engines agree bitwise (tests/test_cycle_engine.py)
    fab = Fabric(
        route=route_t, neighbor=nb_t, opposite=opp_t, is_mc=is_mc,
        is_server=is_server, nodes=ar,
        four_subnet=fs, sub_ids=sub_ids, sub_enabled=sub_enabled,
        sub_is_req=sub_is_req, sub_is_rep=sub_is_rep, n_req_subs=n_req_subs,
    )
    run_cycles = _cycle_engine(stc.backend)(
        stc, topo, fab, subnets0.buf_binj.dtype)

    def epoch_body(carry, epoch_xs):
        # device labels (DESIGN.md §18): the epoch step is `epoch.boundary`
        # except where a nested label names its RNG streams or cycle scan;
        # the epoch loop itself carries none
        with set_xla_metadata(noc_layer="epoch.boundary"):
            return epoch_step(carry, epoch_xs)

    def epoch_step(carry, epoch_xs):
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
        # (device label `epoch.placement`, DESIGN.md §18)
        with set_xla_metadata(noc_layer="epoch.placement"):
            cls_e = placement_class(mp, config_idx, plc.cls0, plc.cls1)
            ntype_e = jnp.where(is_mc, 2, cls_e)           # (R,) virtual type
            is_gpu = ntype_e == 1
            is_cpu = ntype_e == 0
            node_cls = jnp.where(is_gpu, 1, 0)  # class of a node's traffic
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
            subs, staged_reply_want(fab, mc), mc.stage_dst, ar,
            mc.stage_cls, cycle0, gpu_masks, cpu_masks,
        )
        mc = mc._replace(stage_valid=mc.stage_valid & ~jnp.any(ok0, axis=0))

        # Per-epoch RNG streams: the SAME keys and draws as the old
        # per-cycle `split(cycle_key, 3)` engine, batched with vmap (a
        # value-preserving transform), so every stream is bitwise-identical
        # to drawing inside the loop.
        ep_len = stc.epoch_len
        with set_xla_metadata(noc_layer="epoch.rng"):
            keys = jax.random.split(epoch_key, ep_len)
            k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            u_phase = jax.vmap(lambda k: jax.random.uniform(k, ()))(k3[:, 0])
            u_gen = jax.vmap(
                lambda k: jax.random.uniform(k, (R,), jnp.float32)
            )(k3[:, 1])
            d_idx = jax.vmap(
                lambda k: jax.random.randint(k, (R,), 0, mc_ids.shape[0])
            )(k3[:, 2])
            dests_all = jnp.take(mc_ids, d_idx)                 # (L, R)
        if peers is not None:
            with set_xla_metadata(noc_layer="epoch.dest"):
                share = (prof.gpu_peer_share if isinstance(prof, PeerProfile)
                         else jnp.float32(0.0))
                dests_all = peer_dests(peers, epoch_key, share, is_gpu,
                                       dests_all)
        cycles = cycle0 + jnp.arange(ep_len, dtype=jnp.int32)
        sa_all = epoch_sa_prefs(mp, config_idx, cycles)         # (L,)
        # subnet link activation: full width (2-subnet) or alternating-cycle
        # half width (4-subnet); padded subnet rows are never active.
        alternating = (cycles[:, None] % 2) == (jnp.arange(S)[None, :] % 2)
        active_all = sub_enabled[None, :] & jnp.where(fs, alternating, True)
        rep_gate = jnp.arange(ep_len) < ep_len - 1

        # ---- the epoch's cycles: one call of the run's cycle engine
        ep = EpochInputs(
            g_vec=g_vec, c_vec=c_vec, gpu_masks=gpu_masks,
            cpu_masks=cpu_masks, ntype=ntype_e, is_gpu=is_gpu, is_cpu=is_cpu,
            node_cls=node_cls, req_sub=req_sub,
            rows=CycleRows(cycles, u_phase, u_gen, dests_all, sa_all,
                           active_all, rep_gate),
            prof=prof, flt=flt,
        )
        (subs, mc, phase, outst, backlog), cnt, prb = run_cycles(
            (subs, mc, phase, outst, backlog), ep)
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
        with set_xla_metadata(noc_layer="epoch.guard"):
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

    with set_xla_metadata(noc_layer="epoch.rng"):
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
    # the tile-to-tile channels read 0 without a peer table
    peer_push, peer_served = (
        (cnt.gpu_peer_push, cnt.peer_served) if peers is not None
        else (jnp.zeros_like(cnt.moved),) * 2)
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
        gpu_peer_push=peer_push,
        peer_served=peer_served,
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
    """Lower a config's placement source against the run topology.  A
    peer table pins experts to tiles, so it takes the identity plan
    only."""
    topo = make_topology(stc.width, stc.height, stc.n_mc)
    stream = resolve_placement(source, stc.n_epochs, topo)
    if stc.peer_weights is not None:
        peer_table(stc.peer_weights, topo)
        if any((np.asarray(c) != topo.node_type).any() for c in stream):
            raise ValueError(
                "a peer table pins experts to GPU tiles: its runs take the "
                "identity placement only")
    return stream


def simulate(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
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
    The cycle engine is ``cfg.backend``, the platform's when None (see
    `platform_engine`); each engine is its own `SimStatic`.
    """
    with span("noc.args"):
        stc, *args = sim_args(cfg, source, padded)
        args = jax.device_put(args)
    with span("noc.dispatch"):
        return _SIM_JIT(stc, *args)


def sim_args(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
) -> tuple:
    """The `_SIM_JIT` arguments `simulate` dispatches: (SimStatic, policy,
    demand rows, seed, initial state, faults, placement), every array a
    host (NumPy) array; `simulate` puts them on the device in one
    transfer."""
    stc = cfg.static_spec(padded)
    with span("noc.schedules"):
        demand = resolve_source(source, stc.n_epochs)
        flt = _run_faults(cfg.faults, stc)
        plc = _run_placement(cfg.placement, stc)
    return (
        stc,
        cfg.mode_policy(padded),
        demand,
        np.asarray(cfg.seed, np.int32),
        init_sim_state(stc),
        flt,
        plc,
    )


def simulate_with_trace(
    cfg: NoCConfig,
    source: TrafficSourceLike,
    padded: bool = True,
) -> tuple[SimResult, SimTrace]:
    """`simulate` with the flight recorder on: returns (SimResult, SimTrace).

    Forces ``probe.enabled`` — a distinct `SimStatic`, so the probed
    program is its own single trace and the probes-off program (goldens,
    sweeps) is never perturbed.  `SimResult` is bitwise the probes-off
    result; `SimTrace` is bitwise-equal across cycle-engine backends
    (tests/test_obs.py)."""
    if not cfg.probe.enabled:
        cfg = dataclasses.replace(cfg, probe=ProbeConfig(enabled=True))
    return simulate(cfg, source, padded=padded)


def _tree_rows(tree, sl):
    return jax.tree.map(lambda x: x[sl], tree)


def _join(outs, ns, sharding=None):
    """The tiles' answers `outs` joined along the batch axis, the first
    `ns[k]` rows of tile k (its padded tail dropped); laid out on
    `sharding` when one is given."""
    joined = jax.tree.map(
        lambda *xs: jnp.concatenate([x[:n] for x, n in zip(xs, ns)]), *outs)
    if sharding is not None:
        joined = jax.lax.with_sharding_constraint(joined, sharding)
    return joined


def _split(outs, ns, sharding=None):
    """`_join`'s answer cut into one `SimResult` per point, in order."""
    leaves, tree = jax.tree.flatten(_join(outs, ns, sharding))
    return tuple(tree.unflatten(row)
                 for row in zip(*(jnp.unstack(x) for x in leaves)))


# Row-cutting cache: one jitted `_join` or `_split` per (function, mesh);
# jit keys each on the answers' shapes and the static row counts.
_ROWS_JIT: dict = {}


def _rows_jit(fn, mesh=None):
    """`fn` (`_join` or `_split`) as one compiled program, the row counts
    static.  On a mesh the answer is gathered once and the rows come back
    replicated over it, as the eager slices of a sharded answer did, so
    any two rows share a device set and combine with `jnp` (cutting the
    sharded leaves one by one instead compiles ~5x slower on a v5e mesh);
    on one device the rows stay where the answer is."""
    key = (fn, mesh)
    if key not in _ROWS_JIT:
        if mesh is None:
            _ROWS_JIT[key] = jax.jit(fn, static_argnums=1)
        else:
            rep = NamedSharding(mesh, P())
            _ROWS_JIT[key] = jax.jit(functools.partial(fn, sharding=rep),
                                     static_argnums=1, out_shardings=rep)
    return _ROWS_JIT[key]


def _pad_rows(tree, n_pad: int):
    """Append n_pad copies of row 0 along axis 0 of every host leaf
    (discarded after the dispatch)."""
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda x: np.concatenate([x, np.repeat(x[:1], n_pad, axis=0)]),
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
    policy, demand rows, seeds, faults, placement), each leaf a host
    (NumPy) array with a leading (B,) axis (see `simulate_batch` for the
    arguments).  Dispatches no device op."""
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
    sources = list(sources)
    if len(sources) != B:
        raise ValueError(f"{len(sources)} sources for {B} configs")
    if seeds is None:
        seeds = [c.seed for c in cfgs]
    seeds = np.asarray(list(seeds), np.int32)
    if seeds.shape[0] != B:
        raise ValueError(f"{seeds.shape[0]} seeds for {B} configs")

    def stack(trees):
        return jax.tree.map(lambda *xs: np.stack(xs), *trees)

    mp = stack([c.mode_policy() for c in cfgs])
    # every point's demand, fault and placement streams (DESIGN.md §18)
    with span("noc.schedules"):
        prof = stack_profiles(
            [resolve_source(s, stc.n_epochs) for s in sources])
        flt = stack([_run_faults(c.faults, stc) for c in cfgs])
        plc = stack([_run_placement(c.placement, stc) for c in cfgs])
    return stc, mp, prof, seeds, flt, plc


def _run_batch(
    cfgs: Sequence[NoCConfig],
    sources: TrafficSourceLike | Sequence,
    seeds: Sequence[int] | None,
    batch_tile: int | None,
    devices: int | None,
    mesh,
) -> tuple[tuple[SimResult, ...], tuple[int, ...], object]:
    """Build a batch's arguments and call the program once per tile, or
    once over the mesh (see `simulate_batch` for the arguments).

    Returns the program's raw answers, the number of real rows at the head
    of each, and the mesh the answer lives on (None on one device).  Waits
    for nothing: the answers are still being computed."""
    with span("noc.args"):
        stc, mp, prof, seeds, flt, plc = batch_args(cfgs, sources, seeds)
    B = int(seeds.shape[0])

    if devices is not None or mesh is not None:
        with span("noc.args"):
            if mesh is None:
                from repro.dist import sharding as dist_sharding

                mesh = dist_sharding.sweep_mesh(devices)
            ndev = int(mesh.devices.size)
            padded_b = -(-B // ndev) * ndev
            args = _pad_rows((mp, prof, seeds, init_sim_state(stc, B), flt,
                              plc), padded_b - B)
            # each chip receives its own shard of the batch axis
            args = jax.device_put(args, NamedSharding(mesh, P(SWEEP_AXIS)))
        with span("noc.dispatch"):
            out = _sharded_jit(stc, mesh)(*args)
        return (out,), (B,), mesh

    tile = B if batch_tile is None else batch_tile
    outs, ns = [], []
    for lo in range(0, B, tile):
        with span("noc.args"):
            n = min(tile, B - lo)
            sl = slice(lo, lo + n)
            args = tuple(_tree_rows(t, sl) for t in (mp, prof, seeds))
            args += (init_sim_state(stc, n),)
            args += tuple(_tree_rows(t, sl) for t in (flt, plc))
            # pad the ragged tail by repeating row 0 (discarded)
            args = jax.device_put(_pad_rows(args, tile - n))
        with span("noc.dispatch"):
            out = _batch_jit()(stc, *args)
        del args  # the running program holds them; freed when it ends
        outs.append(out)
        ns.append(n)
    return tuple(outs), tuple(ns), None


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

    Returns a `SimResult` whose leaves carry a leading (B,) axis
    (replicated over the mesh on the sharded path).

    Host spans (DESIGN.md §18): `noc.args` around `batch_args` and each
    tile's arguments, `noc.dispatch` around each call of the program,
    `noc.rows` around joining the answers into B rows, one compiled call.
    Arguments are built, sliced and padded on the host; each tile (or the
    sharded batch) crosses to the device in one `jax.device_put`.
    """
    outs, ns, mesh = _run_batch(cfgs, sources, seeds, batch_tile, devices,
                                mesh)
    with span("noc.rows"):
        return _rows_jit(_join, mesh)(outs, ns)


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
    `simulate_batch`'s tiles, and results come back as one `SimResult` per
    spec, in input order, cut from a group's answer by one compiled call
    (`noc.rows`; on a mesh the rows are replicated over it).  Nothing waits
    for the device.  `overrides` are forwarded to every row's `NoCConfig`
    (e.g. n_epochs=30); `devices`/`mesh` select the device-sharded dispatch
    path (see `simulate_batch`).  The whole call is the host span
    `noc.sweep` (DESIGN.md §18).
    """
    with span("noc.sweep"):
        specs = list(specs)
        rows: list[SimResult | None] = [None] * len(specs)
        groups: dict[SimStatic, list[int]] = defaultdict(list)
        cfgs = []
        with span("noc.args"):
            for i, sp in enumerate(specs):
                kw = dict(overrides)
                kw.setdefault("faults", sp.faults)
                kw.setdefault("guard", sp.guard)
                kw.setdefault("placement", sp.placement)
                kw.setdefault("control", sp.control)
                cfg = NoCConfig(
                    mode=sp.mode, static_gpu_vcs=sp.static_gpu_vcs,
                    seed=sp.seed, predictor=sp.predictor, **kw,
                )
                cfgs.append(cfg)
                groups[cfg.static_spec()].append(i)
        for idxs in groups.values():
            outs, ns, on = _run_batch(
                [cfgs[i] for i in idxs],
                [specs[i].workload for i in idxs],
                None, batch_tile, devices, mesh,
            )
            with span("noc.rows"):
                for i, row in zip(idxs, _rows_jit(_split, on)(outs, ns)):
                    rows[i] = row
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
