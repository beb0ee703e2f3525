"""Synthetic CPU/GPU chiplet traffic (paper §4.1 workloads, Fig. 4 dynamics).

The paper drives GPU chiplets with ISPASS2009/Rodinia benchmarks (PATH, LIB,
STO, MUM, BFS, LPS) and CPU chiplets with SPEC 2006 (omnetpp).  Those traces
are a data gate offline, so we model each benchmark as a Markov-modulated
Bernoulli injection process whose parameters are chosen to match the paper's
qualitative description:

  * GPU injection varies strongly over time (bursty phases, Fig. 4);
  * CPU injection is comparatively stable;
  * benchmarks differ in mean demand and burstiness (BFS the burstiest —
    it shows the largest KF gain in Fig. 10).

Each profile defines (rate_lo, rate_hi, p_enter_burst, p_exit_burst) for GPU
nodes, in packets/node/cycle on the request subnet.  Rates are per GPU
*chiplet* (2 SMs per tile, Table 1).

``WorkloadProfile`` is a JAX pytree whose leaves are *rate scalars*, not a
static hashable: the simulator traces over the rates, so every workload
shares one compiled program (DESIGN.md §4).  Profile names live in the
``PROFILES`` dict keys.  ``stack_profiles`` builds the batched (B,)-leaf
profile pytree consumed by ``sim.simulate_batch``.

TrafficSource protocol (DESIGN.md §15)
--------------------------------------
Every demand input implements one protocol: ``epoch_demand(n_epochs)``
lowers the source to the canonical ``EpochDemand`` — a ``WorkloadProfile``
whose leaves are ``(n_epochs,)`` float32 rows of ``(rate_lo, rate_hi,
p_enter, p_exit, cpu_rate)``, exactly the pytree the simulator consumes
through its epoch scan ``xs``.  Three implementations ship here:

  * ``WorkloadProfile``   — stationary rates, broadcast across epochs;
  * ``ScenarioSchedule``  — piecewise synthetic programs (DESIGN.md §12):
    each ``Segment`` is a base profile, optionally ramping into another
    and/or pinning the Markov burst phase;
  * ``RecordedTrace``     — replayed per-epoch demand rows captured from a
    previous run (`repro.obs.recorder.TraceRecorder`), loaded from the
    versioned npz trace schema, or synthesized by the HLO-cost adapter
    (`repro.core.noc.trace_adapters`) — with tile/stretch fit controls so
    the trace length need not match ``n_epochs``.

``resolve_source`` is the one lowering path the simulator entry points
call; because every source lowers to the same per-epoch-xs pytree, all
source kinds share the simulator's ONE compiled program.  Names resolve
through the workload registry: ``PROFILES`` and ``SCENARIOS`` plus
anything added via ``register_workload`` / ``register_trace`` (recorded
trace files become first-class sweep workloads).  ``materialize`` is the
deprecated pre-§15 spelling of ``resolve_source`` and accepts the same
inputs for one more release.
"""
from __future__ import annotations

import dataclasses
import difflib
import json

from typing import Iterable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class WorkloadProfile(NamedTuple):
    """Markov-modulated Bernoulli injection parameters (a JAX pytree).

    Leaves may be Python floats (single run) or (B,) arrays (batched sweep).
    """

    gpu_rate_lo: float | Array
    gpu_rate_hi: float | Array
    p_enter: float | Array      # low -> high phase transition prob per cycle
    p_exit: float | Array       # high -> low
    # omnetpp is memory-heavy: 14 CPU tiles x 0.12 ~= 1.7 pkt/cycle of
    # stable demand — a meaningful share of the ~8 pkt/cycle MC ingress,
    # so CPU and GPU classes genuinely contend during GPU bursts.
    cpu_rate: float | Array = 0.12

    def epoch_demand(self, n_epochs: int) -> "WorkloadProfile":
        """TrafficSource: broadcast stationary rates across the epoch axis.

        Scalar leaves become constant ``(n_epochs,)`` float32 NumPy rows —
        the same float32 values the scalar-leaf trace consumed, so the
        lowering is value-invisible (pinned by
        tests/test_predictor_ablation.py).  Already-per-epoch leaves pass
        through after a length check, so a materialized ``EpochDemand`` is
        itself a valid source.  Every leaf lowers alike, so a
        `PeerProfile` (which shares this method) comes back a
        `PeerProfile` with its ``gpu_peer_share`` row.
        """

        def lower(x):
            x = np.asarray(x, np.float32)
            if x.ndim == 0:
                return np.full((n_epochs,), x, np.float32)
            if x.shape != (n_epochs,):
                raise ValueError(
                    f"per-epoch profile leaf has shape {x.shape}, expected "
                    f"({n_epochs},)"
                )
            return x

        return jax.tree.map(lower, self)


class PeerProfile(NamedTuple):
    """A `WorkloadProfile` whose GPU tiles also send requests to each
    other (expert-parallel dispatch, DESIGN.md §19): its five rates, then
    `gpu_peer_share`, the share of GPU requests addressed to a GPU tile
    that holds experts instead of to an MC.  A run draws such peers only
    where its `NoCConfig.peer_weights` names tiles that hold experts.

    A type of its own rather than a sixth `WorkloadProfile` leaf, so every
    profile, schedule and recorded trace of the five rates keeps its
    five-leaf pytree.  It lowers like a `WorkloadProfile`, one row per
    leaf."""

    gpu_rate_lo: float | Array
    gpu_rate_hi: float | Array
    p_enter: float | Array
    p_exit: float | Array
    cpu_rate: float | Array = 0.12
    gpu_peer_share: float | Array = 0.0

    epoch_demand = WorkloadProfile.epoch_demand


assert PeerProfile._fields[:-1] == WorkloadProfile._fields


# Burstiness/demand ordering mirrors the paper's figures: BFS and MUM show the
# biggest dynamic swings; LIB/PATH are moderate; STO/LPS have high mean load.
# High-phase aggregate offered load (14 GPU tiles x rate_hi) is tuned to
# exceed the network's ejection/link capacity near the MCs so that bursts
# genuinely contend for VCs and switch slots (paper Fig. 4 shows saturating
# spikes), while the low phase is comfortably under capacity.
# Burst dwell times are program phases: thousands of cycles (several KF
# epochs), matching the paper's 5k/10k-cycle hysteresis constants.
# High-phase loads put the network at rho ~ 0.85-0.97 of the 8 pkt/cycle MC
# ingress capacity: the queueing-delay regime where buffer (VC) allocation
# and switch priority actually move throughput (via the MSHR feedback loop),
# rather than a hard-saturated regime where only link capacity matters.
PROFILES: dict[str, WorkloadProfile] = {
    "PATH": WorkloadProfile(0.06, 0.31, 0.00020, 0.00040),
    "LIB": WorkloadProfile(0.08, 0.33, 0.00025, 0.00035),
    "STO": WorkloadProfile(0.12, 0.36, 0.00030, 0.00028),
    "MUM": WorkloadProfile(0.04, 0.38, 0.00025, 0.00020),
    "BFS": WorkloadProfile(0.03, 0.40, 0.00030, 0.00012),
    "LPS": WorkloadProfile(0.10, 0.35, 0.00028, 0.00030),
}


def stack_profiles(profiles: Iterable[WorkloadProfile]) -> WorkloadProfile:
    """Stack profiles into one pytree with (B, ...) float32 NumPy leaves
    (vmap axis 0).  Where any is a `PeerProfile`, the others join it with
    a zero share."""
    rows = list(profiles)
    if any(isinstance(p, PeerProfile) for p in rows):
        rows = [p if isinstance(p, PeerProfile) else PeerProfile(
            *p, gpu_peer_share=np.zeros_like(p.gpu_rate_lo)) for p in rows]
    return jax.tree.map(
        lambda *xs: np.stack(xs).astype(np.float32, copy=False), *rows
    )


def init_phase() -> Array:
    """Global burst phase: 0 = low, 1 = high.

    GPU kernels execute in lock-step program phases across the chiplets, so
    the burst phase is shared by all GPU tiles (Fig. 4 shows coherent,
    workload-wide spikes) — per-tile Bernoulli draws still decorrelate the
    individual packet injections.
    """
    return jnp.int32(0)


def step_phase_u(profile: WorkloadProfile, phase: Array, u: Array) -> Array:
    """Advance the global Markov burst phase given a pre-drawn uniform `u`.

    The cycle engine precomputes its whole epoch's uniforms in one batched
    draw (DESIGN.md §11); `u` here must be `jax.random.uniform(key, ())` for
    the cycle's key so the split is value-identical to drawing in the loop.
    """
    enter = (phase == 0) & (u < profile.p_enter)
    exit_ = (phase == 1) & (u < profile.p_exit)
    return jnp.where(enter, 1, jnp.where(exit_, 0, phase)).astype(jnp.int32)


def step_phase(profile: WorkloadProfile, phase: Array, key: Array) -> Array:
    """Advance the global Markov burst phase by one cycle."""
    return step_phase_u(profile, phase, jax.random.uniform(key, ()))


def injection_rates(
    profile: WorkloadProfile, node_type: Array, phase: Array
) -> Array:
    """Offered load (prob of generating a request this cycle) per node.

    ``node_type`` is TRACED data since the placement layer (DESIGN.md
    §17): the simulator passes the per-epoch virtual class `ntype_e`
    derived from the placement stream, so relocating a tile moves its
    offered load with it; static runs pass rows that equal the topology
    constants bit-for-bit.
    """
    gpu_rate = jnp.where(phase == 1, profile.gpu_rate_hi, profile.gpu_rate_lo)
    rates = jnp.where(node_type == 1, gpu_rate, 0.0)          # GPU tiles
    rates = jnp.where(node_type == 0, profile.cpu_rate, rates)  # CPU tiles
    return rates  # MC tiles inject only replies, handled by the MC model


def pick_mc_dest(key: Array, shape, mc_ids: Array) -> Array:
    """Uniformly choose a destination MC for each generated request."""
    idx = jax.random.randint(key, shape, 0, mc_ids.shape[0])
    return mc_ids[idx]


# ---------------------------------------------------------------------------
# Scenario schedules: piecewise workload programs (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _resolve_profile(p: str | WorkloadProfile) -> WorkloadProfile:
    return PROFILES[p] if isinstance(p, str) else p


class Segment(NamedTuple):
    """One piece of a scenario: governs epochs in [start, next start).

    start      — fraction of the run in [0, 1) where this segment begins
                 (fractional so one schedule serves any ``n_epochs``).
    profile    — base injection parameters (name or WorkloadProfile).
    ramp_to    — if set, rates interpolate linearly from ``profile`` to this
                 across the segment (a rate ramp).
    pin_phase  — None leaves the Markov burst phase free; 0/1 force the
                 phase low/high via (p_enter, p_exit) = (0,1)/(1,0), making
                 burst timing deterministic to within one cycle.
    """

    start: float
    profile: str | WorkloadProfile
    ramp_to: str | WorkloadProfile | None = None
    pin_phase: int | None = None


@dataclasses.dataclass(frozen=True)
class ScenarioSchedule:
    """A piecewise-constant (or ramped) workload program.

    ``materialize(n_epochs)`` lowers the schedule to a ``WorkloadProfile``
    with ``(n_epochs,)`` float32 leaves — one parameter row per epoch —
    which the simulator consumes through its epoch scan ``xs``.  Epoch
    boundaries are exact: epoch ``e`` is governed by the last segment with
    ``round(start * n_epochs) <= e``.
    """

    segments: tuple[Segment, ...]

    def __post_init__(self):
        if not self.segments:
            raise ValueError("ScenarioSchedule needs at least one segment")
        starts = [s.start for s in self.segments]
        if starts != sorted(starts):
            raise ValueError(f"segment starts must be sorted, got {starts}")
        if starts[0] != 0.0:
            raise ValueError(f"first segment must start at 0.0, got {starts[0]}")
        for s in self.segments:
            if not 0.0 <= s.start < 1.0:
                raise ValueError(f"segment start {s.start} outside [0, 1)")
            if s.pin_phase not in (None, 0, 1):
                raise ValueError(f"pin_phase must be None/0/1, got {s.pin_phase}")

    def materialize(self, n_epochs: int) -> WorkloadProfile:
        bounds = [int(round(s.start * n_epochs)) for s in self.segments]
        bounds.append(n_epochs)
        rows = {f: np.empty((n_epochs,), np.float32)
                for f in WorkloadProfile._fields}
        for seg, lo, hi in zip(self.segments, bounds, bounds[1:]):
            if hi <= lo:
                continue  # segment collapsed at this n_epochs resolution
            base = _resolve_profile(seg.profile)
            tgt = _resolve_profile(seg.ramp_to) if seg.ramp_to is not None else None
            # t in [0, 1] across the segment's epochs (0/1 at its endpoints)
            t = (np.arange(hi - lo, dtype=np.float32)
                 / max(hi - lo - 1, 1))
            for f in WorkloadProfile._fields:
                a = np.float32(getattr(base, f))
                row = a + t * (np.float32(getattr(tgt, f)) - a) if tgt is not None \
                    else np.full((hi - lo,), a, np.float32)
                rows[f][lo:hi] = row
            if seg.pin_phase is not None:
                rows["p_enter"][lo:hi] = 1.0 if seg.pin_phase == 1 else 0.0
                rows["p_exit"][lo:hi] = 0.0 if seg.pin_phase == 1 else 1.0
        return WorkloadProfile(**rows)

    def epoch_demand(self, n_epochs: int) -> WorkloadProfile:
        """TrafficSource: lower the schedule to per-epoch demand rows."""
        return self.materialize(n_epochs)


def materialize(
    workload: "TrafficSourceLike", n_epochs: int
) -> WorkloadProfile:
    """Deprecated pre-§15 spelling of :func:`resolve_source`.

    Kept for one release so existing callers (and the old ad-hoc
    ``str | WorkloadProfile | ScenarioSchedule`` union) keep working; new
    code should call ``resolve_source`` directly, which also accepts
    ``RecordedTrace`` and anything else implementing ``TrafficSource``.
    """
    return resolve_source(workload, n_epochs)


def phase_shift(
    a: str | WorkloadProfile = "PATH",
    b: str | WorkloadProfile = "BFS",
    at: float = 0.5,
) -> ScenarioSchedule:
    """Piecewise workload switch: run ``a``, then ``b`` from fraction ``at``
    (the SHIFT-style compute-relocation scenario, e.g. PATH -> BFS mid-run)."""
    return ScenarioSchedule((Segment(0.0, a), Segment(at, b)))


def shift_scenario(
    a: str | WorkloadProfile = "PATH",
    b: str | WorkloadProfile = "BFS",
    dip_scale: float = 0.0,
) -> ScenarioSchedule:
    """The predictor-ablation gate scenario: a program phase shift (``a``
    then ``b`` mid-run) whose programs execute as deterministic kernel-phase
    arcs — calm, a long burst, a short inter-kernel gap ("dip"), and a
    second burst — pinned via the Markov phase so the comparison is
    reproducible across seeds.

    The arc geometry — per 30-epoch arc (canonical 120-epoch run):
    [calm 12][burst 10][dip 2][burst 6] — is sized against the paper's
    hysteresis constants (hold 10 epochs, revert 20) and the simulator's
    observation dynamics (the dip's first epoch reads saturated counters
    while the burst backlog drains; only its second epoch reads low) so
    that *prediction quality*, not hysteresis smoothing, decides the score:

      * the observational dip epoch lands 11 epochs after the burst onset
        — past the hold — so a reactive predictor (last-value, or EMA at
        the textbook α=0.5, since ``dip_scale=0`` drives every observation
        to −1) is FREE to un-boost on it and then pays the hold lockout
        for the entire second burst, while the KF's posterior rides the
        one-epoch gap;
      * the boosted burst span (~18 epochs) stays inside the 20-epoch
        revert budget, so the revert rule and its hold shadow land in the
        calm window (harmless) rather than mid-burst — the paper-tuned
        filter (q=1e-3) takes ~10 calm epochs to release, which the
        12-epoch calm absorbs exactly.
    """
    arcs = []
    for arc, prof in ((0, a), (30, a), (60, b), (90, b)):
        base = _resolve_profile(prof)
        arcs += [
            Segment(arc / 120, base, pin_phase=0),                 # calm 12
            Segment((arc + 12) / 120, base, pin_phase=1),          # burst 10
            Segment((arc + 22) / 120, scale_rates(base, dip_scale),
                    pin_phase=0),                                  # dip 2
            Segment((arc + 24) / 120, base, pin_phase=1),          # burst 6
        ]
    return ScenarioSchedule(tuple(arcs))


def scale_rates(p: str | WorkloadProfile, scale: float) -> WorkloadProfile:
    """Scale a profile's GPU injection rates (phase dynamics untouched)."""
    p = _resolve_profile(p)
    return p._replace(
        gpu_rate_lo=float(p.gpu_rate_lo) * scale,
        gpu_rate_hi=float(p.gpu_rate_hi) * scale,
    )


def rate_ramp(
    base: str | WorkloadProfile = "LIB",
    lo_scale: float = 0.5,
    hi_scale: float = 1.5,
) -> ScenarioSchedule:
    """Linear offered-load ramp from ``lo_scale`` x to ``hi_scale`` x the
    base profile's GPU rates across the whole run."""
    base = _resolve_profile(base)
    return ScenarioSchedule((
        Segment(0.0, scale_rates(base, lo_scale),
                ramp_to=scale_rates(base, hi_scale)),
    ))


def program_mix(
    programs: tuple[str | WorkloadProfile, ...] = ("PATH", "STO", "BFS"),
    repeats: int = 2,
) -> ScenarioSchedule:
    """Time-multiplexed multi-program mix: the programs run back-to-back in
    equal slices, the whole sequence repeated ``repeats`` times."""
    n = len(programs) * repeats
    segs = tuple(
        Segment(i / n, programs[i % len(programs)]) for i in range(n)
    )
    return ScenarioSchedule(segs)


def burst_train(
    base: str | WorkloadProfile = "BFS",
    calm: int = 8,
    burst: int = 10,
    dip: int = 1,
) -> ScenarioSchedule:
    """Deterministic burst train with mid-burst micro-dips, on a 64-slot
    fractional grid: ``calm`` slots pinned low, then a burst of ``burst``
    slots pinned high broken by a ``dip``-slot pinned-low notch, repeating.

    A reporting scenario, NOT the ablation gate: its notches land inside
    the hysteresis hold window, so every predictor rides them and the
    measured predictor spread is within noise (see the committed
    `noc_ablation` rows — last-value even noses ahead).  The gate scenario
    is `shift_scenario`, whose dip geometry is sized against the hold and
    revert constants so prediction quality actually separates.
    """
    base = _resolve_profile(base)
    if calm + burst + dip + burst > 64:
        raise ValueError("one burst unit must fit the 64-slot grid")
    segs, pos = [], 0
    while pos < 64:
        for length, pin in ((calm, 0), (burst, 1), (dip, 0), (burst, 1)):
            if pos >= 64:
                break
            segs.append(Segment(pos / 64, base, pin_phase=pin))
            pos += length
    return ScenarioSchedule(tuple(segs))


# Scenario library (DESIGN.md §12).  Names share the SweepSpec.workload
# namespace with PROFILES and resolve through `lookup_workload`.
SCENARIOS: dict[str, ScenarioSchedule] = {
    # SHIFT-style program relocation (moderate PATH, then bursty BFS) with
    # deterministic kernel-phase arcs — the predictor-ablation gate.
    "SHIFT_PATH_BFS": shift_scenario("PATH", "BFS"),
    # the plain mid-run workload switch, Markov phases left free
    "SHIFT_SMOOTH": phase_shift("PATH", "BFS", at=0.5),
    # offered-load ramp through the contention knee
    "RAMP_LIB": rate_ramp("LIB", 0.5, 1.5),
    # time-multiplexed multi-program mix
    "MIX_PATH_STO_BFS": program_mix(("PATH", "STO", "BFS"), repeats=2),
    # deterministic burst train with micro-dips (ablation stressor)
    "BURSTS_BFS": burst_train("BFS"),
}


# ---------------------------------------------------------------------------
# TrafficSource protocol, recorded traces, and the workload registry
# (DESIGN.md §15)
# ---------------------------------------------------------------------------

@runtime_checkable
class TrafficSource(Protocol):
    """Anything that can lower itself to per-epoch demand rows.

    ``epoch_demand(n_epochs)`` must return an ``EpochDemand``: a
    ``WorkloadProfile`` whose five leaves are ``(n_epochs,)`` float32 rows,
    or a ``PeerProfile`` whose six are.
    ``resolve_source`` validates that contract after the call, so custom
    sources cannot silently feed the simulator a second program shape.
    """

    def epoch_demand(self, n_epochs: int) -> WorkloadProfile:
        ...


# The canonical lowered form: a WorkloadProfile whose leaves are
# (n_epochs,) float32 rows — one parameter row per epoch, consumed by the
# simulator's epoch scan as `xs`.  An alias, not a subclass: EpochDemand
# must remain pytree-identical to WorkloadProfile so every source kind
# shares the simulator's single compiled program.
EpochDemand = WorkloadProfile

# Versioned npz trace schema (DESIGN.md §15).  A trace file is a plain
# npz (no pickling) with:
#   schema          — the literal "noc_demand_trace"
#   schema_version  — int, currently 1
#   name            — short trace name (informational)
#   meta_json       — JSON dict of provenance (recorder config, adapter
#                     parameters, source workload, ...)
#   demand_<field>  — (T,) float32 row per WorkloadProfile field
TRACE_SCHEMA = "noc_demand_trace"
TRACE_SCHEMA_VERSION = 1

_FIT_MODES = ("exact", "tile", "stretch")


@dataclasses.dataclass(frozen=True)
class RecordedTrace:
    """A replayed per-epoch demand trace (TrafficSource implementation).

    ``demand`` holds the recorded rows as a ``WorkloadProfile`` of ``(T,)``
    float32 numpy leaves.  ``fit`` controls how a trace of length ``T`` is
    fitted to a run of ``n_epochs`` epochs:

      * ``"exact"``   — require ``T == n_epochs`` (the bitwise-replay mode);
      * ``"tile"``    — repeat the trace cyclically (epoch ``e`` reads row
                        ``e % T``);
      * ``"stretch"`` — linearly resample the rows onto ``n_epochs`` points
                        (preserves the trace's shape, not its timing).

    When ``T == n_epochs`` every mode passes the rows through untouched,
    so a trace recorded from a run replays bitwise-identical to that run
    regardless of ``fit``.
    """

    demand: WorkloadProfile
    fit: str = "exact"
    name: str = "trace"
    meta: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.fit not in _FIT_MODES:
            raise ValueError(
                f"fit must be one of {_FIT_MODES}, got {self.fit!r}"
            )
        rows = {}
        length = None
        for f in WorkloadProfile._fields:
            row = np.asarray(getattr(self.demand, f), np.float32)
            if row.ndim == 0:
                raise ValueError(
                    f"RecordedTrace leaf {f!r} is a scalar; recorded demand "
                    "must be per-epoch (T,) rows — use WorkloadProfile for "
                    "stationary sources"
                )
            if row.ndim != 1:
                raise ValueError(
                    f"RecordedTrace leaf {f!r} has shape {row.shape}, "
                    "expected (T,)"
                )
            if length is None:
                length = row.shape[0]
            elif row.shape[0] != length:
                raise ValueError(
                    f"RecordedTrace leaves disagree on length: {f!r} has "
                    f"{row.shape[0]}, expected {length}"
                )
            rows[f] = row
        if length == 0:
            raise ValueError("RecordedTrace needs at least one epoch row")
        object.__setattr__(self, "demand", WorkloadProfile(**rows))

    @property
    def n_epochs_recorded(self) -> int:
        return int(np.asarray(self.demand.gpu_rate_lo).shape[0])

    def epoch_demand(self, n_epochs: int) -> WorkloadProfile:
        """TrafficSource: fit the recorded rows to ``n_epochs`` epochs."""
        T = self.n_epochs_recorded
        if T == n_epochs:
            rows = {f: np.asarray(getattr(self.demand, f))
                    for f in WorkloadProfile._fields}
        elif self.fit == "exact":
            raise ValueError(
                f"trace {self.name!r} has {T} recorded epochs but the run "
                f"wants {n_epochs}; use fit='tile' or fit='stretch' to "
                "adapt it"
            )
        elif self.fit == "tile":
            idx = np.arange(n_epochs) % T
            rows = {f: np.asarray(getattr(self.demand, f))[idx]
                    for f in WorkloadProfile._fields}
        else:  # stretch: linear resample onto n_epochs sample points
            src = np.linspace(0.0, 1.0, T, dtype=np.float64)
            dst = np.linspace(0.0, 1.0, n_epochs, dtype=np.float64)
            rows = {
                f: np.interp(
                    dst, src, np.asarray(getattr(self.demand, f), np.float64)
                ).astype(np.float32)
                for f in WorkloadProfile._fields
            }
        return WorkloadProfile(**{
            f: np.asarray(rows[f], np.float32)
            for f in WorkloadProfile._fields
        })

    def with_fit(self, fit: str) -> "RecordedTrace":
        return dataclasses.replace(self, fit=fit)

    def save(self, path) -> None:
        """Write the trace as a versioned npz file (no pickling)."""
        payload = {
            "schema": TRACE_SCHEMA,
            "schema_version": np.int64(TRACE_SCHEMA_VERSION),
            "name": self.name,
            "meta_json": json.dumps(self.meta, sort_keys=True),
        }
        for f in WorkloadProfile._fields:
            payload[f"demand_{f}"] = np.asarray(
                getattr(self.demand, f), np.float32
            )
        np.savez(path, **payload)

    @classmethod
    def load(cls, path, fit: str = "exact") -> "RecordedTrace":
        """Load a trace written by :meth:`save` (schema-validated)."""
        with np.load(path, allow_pickle=False) as data:
            problems = validate_trace_npz(data)
            if problems:
                raise ValueError(
                    f"{path}: not a valid {TRACE_SCHEMA} file: "
                    + "; ".join(problems)
                )
            demand = WorkloadProfile(**{
                f: np.asarray(data[f"demand_{f}"], np.float32)
                for f in WorkloadProfile._fields
            })
            name = str(np.asarray(data["name"]).item())
            meta = json.loads(str(np.asarray(data["meta_json"]).item()))
        return cls(demand=demand, fit=fit, name=name, meta=meta)


def validate_trace_npz(data) -> list[str]:
    """Return schema problems for an opened npz mapping ([] when valid)."""
    problems = []
    keys = set(getattr(data, "files", data.keys()))
    for key in ("schema", "schema_version", "name", "meta_json"):
        if key not in keys:
            problems.append(f"missing key {key!r}")
    if "schema" in keys:
        schema = str(np.asarray(data["schema"]).item())
        if schema != TRACE_SCHEMA:
            problems.append(f"schema is {schema!r}, expected {TRACE_SCHEMA!r}")
    if "schema_version" in keys:
        version = int(np.asarray(data["schema_version"]).item())
        if version > TRACE_SCHEMA_VERSION:
            problems.append(
                f"schema_version {version} is newer than supported "
                f"{TRACE_SCHEMA_VERSION}"
            )
    length = None
    for f in WorkloadProfile._fields:
        key = f"demand_{f}"
        if key not in keys:
            problems.append(f"missing key {key!r}")
            continue
        row = np.asarray(data[key])
        if row.ndim != 1 or row.shape[0] == 0:
            problems.append(f"{key} has shape {row.shape}, expected (T,)")
        elif length is None:
            length = row.shape[0]
        elif row.shape[0] != length:
            problems.append(
                f"{key} has length {row.shape[0]}, expected {length}"
            )
        if row.size and not np.all(np.isfinite(row)):
            problems.append(f"{key} contains non-finite values")
        elif row.size and np.any(row < 0):
            # negative demand rows would silently invert injection gates
            # downstream; reject them at the schema boundary
            problems.append(f"{key} contains negative values")
    if "meta_json" in keys:
        try:
            meta = json.loads(str(np.asarray(data["meta_json"]).item()))
            if not isinstance(meta, dict):
                problems.append("meta_json is not a JSON object")
        except (json.JSONDecodeError, ValueError):
            problems.append("meta_json is not valid JSON")
    return problems


# Workload registry: names registered here share the SweepSpec.workload
# namespace with PROFILES and SCENARIOS and win on collision (so a
# registered trace can shadow a builtin for an experiment).
_REGISTRY: dict[str, "TrafficSource"] = {}


def register_workload(
    name: str, source: "TrafficSource", overwrite: bool = False
) -> None:
    """Register a named workload (any TrafficSource, e.g. a RecordedTrace).

    Refuses to shadow an existing registered/builtin name unless
    ``overwrite=True``.
    """
    if not isinstance(source, TrafficSource):
        raise TypeError(
            f"source for {name!r} does not implement TrafficSource "
            "(needs an epoch_demand(n_epochs) method)"
        )
    if not overwrite and (
        name in _REGISTRY or name in PROFILES or name in SCENARIOS
    ):
        raise ValueError(
            f"workload {name!r} already exists; pass overwrite=True to "
            "replace it"
        )
    _REGISTRY[name] = source


def register_trace(
    name: str, path, fit: str = "exact", overwrite: bool = False
) -> RecordedTrace:
    """Load a trace file and register it as a named workload."""
    trace = RecordedTrace.load(path, fit=fit)
    register_workload(name, trace, overwrite=overwrite)
    return trace


def unregister_workload(name: str) -> None:
    """Remove a registered workload (builtins are untouchable)."""
    _REGISTRY.pop(name, None)


def lookup_workload(name: str) -> "TrafficSource":
    """Resolve a workload name from the registry, PROFILES, or SCENARIOS.

    Unknown names raise ``ValueError`` listing close matches across all
    three namespaces (registered traces included).
    """
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name in PROFILES:
        return PROFILES[name]
    if name in SCENARIOS:
        return SCENARIOS[name]
    known = sorted({*PROFILES, *SCENARIOS, *_REGISTRY})
    near = difflib.get_close_matches(name, known, n=3, cutoff=0.4)
    hint = f"; did you mean {near}?" if near else ""
    raise ValueError(
        f"unknown workload {name!r}{hint} (known workloads: {known})"
    )


def resolve_source(source: "TrafficSourceLike", n_epochs: int) -> EpochDemand:
    """Lower any demand source to the canonical EpochDemand pytree.

    The ONE resolution path used by ``simulate`` / ``simulate_with_trace``
    / ``simulate_batch`` / ``sweep``:

      * ``str``           — resolved via :func:`lookup_workload` (registry,
                            PROFILES, SCENARIOS);
      * ``TrafficSource`` — anything with ``epoch_demand(n_epochs)``:
                            ``WorkloadProfile``, ``ScenarioSchedule``,
                            ``RecordedTrace``, or a custom source;
      * bare 5-tuples     — deprecation shim for the pre-§15 union: coerced
                            to ``WorkloadProfile`` for one release.

    The result is validated to have exactly ``(n_epochs,)`` float32 leaves,
    so every source kind feeds the simulator the same program shape; a
    `PeerProfile` source lowers to a `PeerProfile` of rows, its share in
    [0, 1].  Its
    leaves are host (NumPy) rows whatever the source returned: the
    simulator's argument layer builds on the host and crosses to the
    device once per dispatch (DESIGN.md §18).
    """
    if isinstance(source, str):
        source = lookup_workload(source)
    if not isinstance(source, TrafficSource):
        if isinstance(source, tuple) and len(source) == len(
            WorkloadProfile._fields
        ):
            # pre-§15 callers could pass any profile-shaped tuple
            source = WorkloadProfile(*source)
        else:
            raise TypeError(
                f"cannot resolve demand source of type "
                f"{type(source).__name__}; expected a workload name, "
                "WorkloadProfile, ScenarioSchedule, RecordedTrace, or any "
                "TrafficSource"
            )
    demand = source.epoch_demand(n_epochs)
    kind = PeerProfile if isinstance(demand, PeerProfile) else WorkloadProfile
    rows = {}
    for f in kind._fields:
        leaf = getattr(demand, f)
        if tuple(leaf.shape) != (n_epochs,) or leaf.dtype != np.float32:
            raise ValueError(
                f"source {type(source).__name__} produced leaf {f!r} with "
                f"shape {leaf.shape} dtype {leaf.dtype}; EpochDemand needs "
                f"({n_epochs},) float32"
            )
        # value gate: a NaN/inf or negative demand row fed to the sim
        # would silently poison injection gates and every KF observation
        # downstream — reject it here, at the ONE resolution path
        row = rows[f] = np.array(leaf)  # a copy: callers own their rows
        if not np.all(np.isfinite(row)):
            raise ValueError(
                f"source {type(source).__name__} produced non-finite demand "
                f"in leaf {f!r}"
            )
        if np.any(row < 0):
            raise ValueError(
                f"source {type(source).__name__} produced negative demand "
                f"in leaf {f!r}"
            )
    if kind is PeerProfile and np.any(rows["gpu_peer_share"] > 1):
        raise ValueError(
            f"source {type(source).__name__} produced a gpu_peer_share "
            "above 1"
        )
    return kind(**rows)


# The union accepted by resolve_source (and, transitionally, the old
# entry-point signatures): a workload name or any TrafficSource.
TrafficSourceLike = (str | WorkloadProfile | PeerProfile | ScenarioSchedule
                     | RecordedTrace)
