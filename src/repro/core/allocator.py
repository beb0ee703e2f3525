"""Reconfiguration policy (paper §3.2 deployment rules + §3.3 allocation).

The KF emits a binary signal each epoch.  The policy turns that signal into
an *applied configuration* under three hysteresis rules:

  1. warmup  — KF decisions are ignored for the first `warmup` cycles
               (paper: 10,000 cycles after GPU apps start);
  2. hold    — after any reallocation the configuration is frozen for
               `hold` cycles (paper: 5,000 cycles);
  3. revert  — if the boosted state (config=1) persists beyond `revert`
               cycles, fall back to the equal split (paper: 10,000 cycles).

The same state machine drives (a) the NoC simulator's VC partition + switch
arbitration and (b) the TPU comm scheduler's compiled-variant selection —
only the *meaning* of the configuration index differs.

Implemented as a pure jittable function over `PolicyState` so it can live
inside `lax.scan`.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.predictor import PredictorPolicy, predictor_policy

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    warmup: int = 10_000     # cycles before the KF may act
    hold: int = 5_000        # min cycles between reallocations
    revert: int = 10_000     # max cycles to stay boosted before fallback
    n_configs: int = 2       # paper uses {0: equal, 1: GPU-boosted}


class PolicyState(NamedTuple):
    config: Array          # () int32 — currently applied configuration
    last_change: Array     # () int32 — cycle of the last reallocation
    boosted_since: Array   # () int32 — cycle when config became nonzero (-1 if not)


def init_policy_state() -> PolicyState:
    return PolicyState(
        config=jnp.int32(0),
        last_change=jnp.int32(-(10**9)),
        boosted_since=jnp.int32(-1),
    )


def apply_policy(
    cfg: PolicyConfig, state: PolicyState, kf_signal: Array, cycle: Array
) -> PolicyState:
    """Advance the hysteresis machine by one epoch.

    kf_signal: () int32 in [0, n_configs) — the KF's desired configuration.
    cycle:     () int32 — current cycle count.
    """
    desired = jnp.clip(kf_signal, 0, cfg.n_configs - 1)

    in_warmup = cycle < cfg.warmup
    in_hold = (cycle - state.last_change) < cfg.hold
    # revert rule: boosted for too long -> force equal split
    boosted = state.config > 0
    over_revert = boosted & (state.boosted_since >= 0) & (
        (cycle - state.boosted_since) > cfg.revert
    )

    want = jnp.where(over_revert, jnp.int32(0), desired)
    blocked = in_warmup | (in_hold & ~over_revert)
    new_config = jnp.where(blocked, state.config, want)

    changed = new_config != state.config
    new_last_change = jnp.where(changed, cycle, state.last_change)
    new_boosted_since = jnp.where(
        (new_config > 0) & ~boosted,
        cycle,
        jnp.where(new_config > 0, state.boosted_since, jnp.int32(-1)),
    )
    return PolicyState(
        config=new_config,
        last_change=new_last_change,
        boosted_since=new_boosted_since,
    )


# ---------------------------------------------------------------------------
# Configuration tables (paper §3.3, Figure 7/8)
# ---------------------------------------------------------------------------

class ModePolicy(NamedTuple):
    """Traced policy tensors: everything a network *mode* means to the sim.

    The simulator used to branch at trace time on ``cfg.mode`` — every mode
    (and every static VC ratio) compiled its own XLA program.  A
    ``ModePolicy`` lifts all of that into data so ``baseline``/``fair``/
    ``static``/``kf`` share one compiled 2-subnet trace and can be stacked
    along a batch axis for ``sim.simulate_batch`` (DESIGN.md §4).

    Since the S-padding refactor (DESIGN.md §10) the *subnet structure* is
    traced too: ``sub_enabled``/``sub_is_req`` describe which rows of the
    padded subnet axis are live and which direction they carry, and
    ``four_subnet`` selects the class-segregated routing of Fig. 9.  With
    those in data, 2-subnet and 4-subnet configurations share ONE compiled
    program (padded subnets are zero-width: never injected into, links never
    active).

    Since the predictor-ablation subsystem (DESIGN.md §12) the *predictor*
    driving the hysteresis machine is traced data too: ``predictor`` is a
    `repro.core.predictor.PredictorPolicy` sub-pytree selecting which bank
    member (KF / EMA / last-value / always-on / always-off) emits the
    epoch-boundary signal.

    Since the placement subsystem (DESIGN.md §17) the hysteresis machine
    drives TWO levers, each behind its own traced enable: ``bw_enable``
    lets the applied config reconfigure the VC partition + SA pattern (the
    paper's bandwidth lever) and ``place_enable`` lets it relocate compute
    between the placement stream's base/boosted plans (the SHIFT-style
    lever).  bandwidth-only / placement-only / joint control is therefore
    one compiled program — `mode_policy(..., control=...)` just flips these
    two scalars.

    Leaves may carry a leading batch dimension when stacked.
    """

    gpu_mask0: Array   # (V,) bool — VCs GPU packets may occupy, config = 0
    cpu_mask0: Array   # (V,) bool
    gpu_mask1: Array   # (V,) bool — masks when boosted (config = 1)
    cpu_mask1: Array   # (V,) bool
    sa_enable: Array   # ()  bool — enable the Fig. 8 SA preference pattern
    kf_enable: Array   # ()  bool — let the KF hysteresis machine drive config
    four_subnet: Array  # () bool — class-segregated subnet routing (Fig. 9)
    sub_enabled: Array  # (S,) bool — live rows of the padded subnet axis
    sub_is_req: Array   # (S,) bool — request-direction subnets (rest: reply)
    predictor: PredictorPolicy  # traced predictor-bank selection (§12)
    bw_enable: Array    # () bool — config drives the VC/SA bandwidth lever (§17)
    place_enable: Array  # () bool — config drives the compute-placement lever


# control levers the applied configuration may drive (DESIGN.md §17)
CONTROLS = ("bandwidth", "placement", "joint")


def mode_policy(
    mode: str,
    n_vcs: int = 4,
    static_gpu_vcs: int = 2,
    *,
    n_subnets: int | None = None,
    active_vcs: int | None = None,
    predictor: str = "kf",
    ema_alpha: float = 0.5,
    guard: bool = False,
    control: str = "bandwidth",
) -> ModePolicy:
    """Build the traced policy tensors for one of the paper's modes.

    baseline — VCs fully shared between classes, round-robin SA, no KF.
    fair     — static equal VC partition, no KF.
    static   — fixed [static_gpu_vcs : V - static_gpu_vcs] partition (Fig. 2/3).
    kf       — equal partition when config=0, boosted partition + SA pattern
               when config=1, KF drives config.
    4subnet  — physical segregation: within a subnet every VC its class may
               use is allowed (the subnet index segregates classes).

    ``n_subnets`` is the (possibly padded) length of the subnet axis and
    ``active_vcs`` the number of usable VCs out of ``n_vcs`` — VC indices
    ``>= active_vcs`` are masked off for both classes, which is how the
    4-subnet network (2 VCs/subnet) rides a V-padded shared program.  Both
    default to the mode's dedicated (unpadded) structure.

    ``predictor``/``ema_alpha`` pick the bank member that emits the
    reconfiguration signal (repro.core.predictor; meaningful only when the
    hysteresis machine is enabled, i.e. mode="kf").  ``guard`` arms that
    member's self-healing layer (innovation gate, divergence watchdog,
    covariance reset — DESIGN.md §16); disarmed it is bitwise inert.

    ``control`` selects which lever(s) the applied config drives
    (DESIGN.md §17): "bandwidth" (VC partition + SA pattern — the paper's
    controller, and the bitwise-identity default), "placement" (compute
    relocation between the placement stream's plans only), or "joint"
    (both).  Pure traced data — all three compile to one program.

    The tensors are host (NumPy) arrays, built without a device op: the
    simulator's argument layer crosses to the device once per dispatch
    (DESIGN.md §18).
    """
    if control not in CONTROLS:
        raise ValueError(
            f"unknown control {control!r}; expected one of {CONTROLS}"
        )
    if n_subnets is None:
        n_subnets = 4 if mode == "4subnet" else 2
    if active_vcs is None:
        active_vcs = n_vcs
    if not 0 < active_vcs <= n_vcs:
        raise ValueError(f"active_vcs={active_vcs} outside (0, {n_vcs}]")
    avail = np.arange(n_vcs) < active_vcs
    if mode in ("baseline", "4subnet"):
        g0, c0 = avail, avail
    elif mode == "fair":
        g0, c0 = vc_partition(0, active_vcs)
    elif mode == "static":
        g0 = (np.arange(n_vcs) < static_gpu_vcs) & avail
        c0 = avail & ~g0
    elif mode == "kf":
        g0, c0 = vc_partition(0, active_vcs)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    if mode == "kf":
        g1, c1 = vc_partition(1, active_vcs)
    else:
        g1, c1 = g0, c0  # config never leaves 0 when the KF is disabled

    def pad_v(m: np.ndarray) -> np.ndarray:  # masks are over active_vcs
        return np.concatenate([m, np.zeros((n_vcs - m.shape[0],), bool)])

    sub = np.arange(n_subnets)
    if mode == "4subnet":
        if n_subnets != 4:
            raise ValueError("4subnet mode needs a 4-row subnet axis, got "
                             f"{n_subnets}")
        sub_enabled = np.ones((n_subnets,), bool)
        sub_is_req = sub % 2 == 0          # {CPU,GPU} x {req, reply}
    else:
        if n_subnets < 2:
            raise ValueError(f"2-subnet modes need n_subnets >= 2, got "
                             f"{n_subnets}")
        sub_enabled = sub < 2              # rows 2.. are zero-width padding
        sub_is_req = sub == 0              # subnet 0 req, subnet 1 reply
    is_kf = mode == "kf"
    return ModePolicy(
        gpu_mask0=pad_v(g0), cpu_mask0=pad_v(c0),
        gpu_mask1=pad_v(g1), cpu_mask1=pad_v(c1),
        sa_enable=np.asarray(is_kf), kf_enable=np.asarray(is_kf),
        four_subnet=np.asarray(mode == "4subnet"),
        sub_enabled=sub_enabled,
        sub_is_req=sub_is_req,
        predictor=predictor_policy(predictor, ema_alpha=ema_alpha,
                                   guard=guard),
        bw_enable=np.asarray(control != "placement"),
        place_enable=np.asarray(control != "bandwidth"),
    )


def class_vc_masks(policy: ModePolicy, config: Array) -> tuple[Array, Array]:
    """Select the (V,) GPU/CPU VC masks for the applied configuration.

    Gated on ``bw_enable`` (DESIGN.md §17): under placement-only control
    the VC partition stays at the config-0 split no matter what the
    hysteresis machine applied.  ``bw_enable`` defaults True, so
    pre-placement programs select identical values."""
    boosted = (config > 0) & policy.bw_enable
    gpu = jnp.where(boosted, policy.gpu_mask1, policy.gpu_mask0)
    cpu = jnp.where(boosted, policy.cpu_mask1, policy.cpu_mask0)
    return gpu, cpu


def placement_class(
    policy: ModePolicy, config: Array, cls0: Array, cls1: Array
) -> Array:
    """Select the (R,) node-class plan for the applied configuration.

    The placement twin of `class_vc_masks` (DESIGN.md §17): while the
    hysteresis machine holds a boosted config AND ``place_enable`` is set,
    compute relocates to the placement stream's boosted plan ``cls1``;
    otherwise it sits on the base plan ``cls0``.  The identity stream
    carries ``cls0 == cls1``, so placement-free runs select bit-for-bit
    the static layout either way."""
    boosted = (config > 0) & policy.place_enable
    return jnp.where(boosted, cls1, cls0)


def apply_policy_gated(
    cfg: PolicyConfig,
    policy: ModePolicy,
    state: PolicyState,
    kf_signal: Array,
    cycle: Array,
) -> PolicyState:
    """`apply_policy` under a traced enable flag (no-op unless kf_enable)."""
    new = apply_policy(cfg, state, kf_signal, cycle)
    return jax.tree.map(
        lambda n, o: jnp.where(policy.kf_enable, n, o), new, state
    )


def degrade_policy(state: PolicyState, healthy: Array) -> PolicyState:
    """Traced degraded-mode fallback (DESIGN.md §16).

    While the predictor watchdog reports unhealthy, the applied
    configuration reverts to the fair static split (config 0) and the
    boost timer is cleared, so a poisoned filter can never starve a
    chiplet class worse than the no-predictor baseline.  `last_change`
    is kept, not reset: on recovery the hysteresis hold window is
    whatever it already was, so a healthy signal can re-boost
    immediately instead of serving a fresh hold penalty.

    `healthy` is a () bool (from `PredictorState.healthy`); it is
    constant True whenever the guard is disarmed, making this an
    elementwise identity on every pre-guard program.
    """
    fallback = PolicyState(
        config=jnp.int32(0),
        last_change=state.last_change,
        boosted_since=jnp.int32(-1),
    )
    return jax.tree.map(
        lambda f, o: jnp.where(healthy, o, f), fallback, state
    )


def epoch_sa_prefs(policy: ModePolicy, config: Array, cycles: Array) -> Array:
    """Per-cycle SA preference stream for one epoch (cycle-engine `xs`).

    `config` is frozen between epoch boundaries (`apply_policy_gated` runs
    only after the inner cycle scan), so the whole epoch's switch-arbitration
    preference classes can be precomputed from the cycle numbers instead of
    branching per cycle: returns (len(cycles),) int32, -1 for round-robin.
    The SA pattern is a bandwidth lever, so it rides ``bw_enable`` (§17).
    """
    pattern = sa_priority_pattern(config, cycles)
    return jnp.where(policy.sa_enable & policy.bw_enable, pattern,
                     jnp.int32(-1))


def vc_partition(config: int, n_vcs: int = 4) -> tuple[np.ndarray, np.ndarray]:
    """Return boolean masks (gpu_vcs, cpu_vcs) over VC indices, as host
    (NumPy) arrays.

    config=0: GPU {0,1}, CPU {2,3}     (equal split)
    config=1: GPU {0,1,2}, CPU {3}     (75/25 boost)
    Generalized to n_vcs: equal split at n/2, boost at n-1.
    """
    gpu_hi = n_vcs - 1 if int(config) > 0 else n_vcs // 2  # exclusive bound
    gpu_mask = np.arange(n_vcs) < gpu_hi
    return gpu_mask, ~gpu_mask


def sa_priority_pattern(config: Array, phase: Array) -> Array:
    """Switch-arbitration class preference for this cycle.

    Returns the preferred class (0=CPU, 1=GPU) given the 3-phase pattern.
    config=0: round-robin (no class preference — encoded as -1).
    config=1: GPU, GPU, CPU repeating (paper Fig. 8).
    """
    pattern = jnp.asarray([1, 1, 0], dtype=jnp.int32)[phase % 3]
    return jnp.where(config > 0, pattern, jnp.int32(-1))
