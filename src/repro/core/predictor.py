"""Predictor bank for the reconfiguration controller (DESIGN.md §12).

The paper's central claim is not "a KF can drive reconfiguration" but "a KF
predicts next-epoch demand *better than naive predictors*, so the network
reacts without thrashing".  Reproducing that claim needs the naive
predictors as first-class citizens of the same controller: this module
generalizes the epoch-boundary step

    counters -> normalize -> Kalman step -> binarize -> hysteresis machine

into a *bank* of predictors sharing one traced program.  Which predictor
drives the hysteresis machine is selected by a traced tensor
(`PredictorPolicy.kind`), never a Python branch, so the whole ablation grid
(predictor x scenario x workload x seed) batches into the simulator's ONE
compiled program (`sim.trace_count() == 1`) and the default KF path stays
bitwise-identical to `tests/golden_cycle_engine.json`.

Predictor kinds (paper Fig. 9/10 ablation axis):

  * ``kf``         — the paper's filter: scalar-state KF over the 3
                     normalized NoC observations; the signal binarizes the
                     one-step prediction `A x_k` (== the posterior for the
                     paper's random-walk A = I, bitwise).
  * ``ema``        — exponential moving average of the mean observation
                     with traced smoothing factor α.
  * ``last``       — last-value predictor: next epoch == this epoch's mean
                     observation (the "naive" baseline of the paper's
                     comparison).
  * ``always_on``  — constant boost request (upper envelope of reactive
                     boosting; the hysteresis revert rule still cycles it).
  * ``always_off`` — never request a boost (== the static fair split).

Every predictor's state advances every epoch regardless of `kind` (the
selection applies only to the emitted signal), which is what keeps the
program branch-free; the extra EMA arithmetic is two fused scalar ops per
epoch — noise next to the cycle scan.

Since the placement layer (DESIGN.md §17) the emitted signal drives up to
TWO levers: the VC bandwidth boost (`ModePolicy.bw_enable`) and compute
relocation (`ModePolicy.place_enable` selecting the placement stream's
boosted class plan).  The bank is lever-agnostic — it predicts demand;
which levers the prediction pulls is the allocator's `control` setting.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from repro.core import kalman

Array = jax.Array

# Predictor-kind encoding for the traced selector.  Order is load-bearing:
# `step` stacks the candidate signals in this order and `jnp.take`s by kind.
KF = 0
EMA = 1
LAST = 2
ALWAYS_ON = 3
ALWAYS_OFF = 4

PREDICTORS: dict[str, int] = {
    "kf": KF,
    "ema": EMA,
    "last": LAST,
    "always_on": ALWAYS_ON,
    "always_off": ALWAYS_OFF,
}


class PredictorPolicy(NamedTuple):
    """Traced predictor selection: which bank member drives the hysteresis
    machine, plus the naive predictors' parameters.

    Leaves may carry a leading batch dimension when stacked for
    `sim.simulate_batch` (exactly like `allocator.ModePolicy`, which embeds
    one of these).
    """

    kind: Array            # () int32 in [0, 5) — see PREDICTORS
    ema_alpha: Array       # () float32 — EMA smoothing factor
    threshold: Array       # () float32 — binarization threshold (paper: 0.0)
    guard: Array           # () bool — self-healing gate armed (DESIGN.md §16)
    nis_threshold: Array   # () float32 — innovation-gate NIS reject level
    watchdog_limit: Array  # () int32 — consecutive rejects before unhealthy
    cov_limit: Array       # () float32 — tr(P) divergence-watchdog ceiling


def predictor_policy(
    name: str = "kf",
    ema_alpha: float = 0.5,
    threshold: float = 0.0,
    guard: bool = False,
    nis_threshold: float = 50.0,
    watchdog_limit: int = 3,
    cov_limit: float = 1e4,
) -> PredictorPolicy:
    """Build the traced selector for one predictor by name, as host
    (NumPy) scalars: the simulator's argument layer crosses to the device
    once per dispatch (DESIGN.md §18).

    `guard=True` arms the self-healing layer (innovation gate + divergence
    watchdog + covariance reset); with the default `guard=False` every
    gated `where` selects the unguarded value, so the emitted state and
    signal are bitwise those of the pre-guard implementation.
    """
    if name not in PREDICTORS:
        raise ValueError(
            f"unknown predictor {name!r}; expected one of {sorted(PREDICTORS)}"
        )
    if not 0.0 < ema_alpha <= 1.0:
        raise ValueError(f"ema_alpha={ema_alpha} outside (0, 1]")
    if nis_threshold <= 0.0:
        raise ValueError(f"nis_threshold={nis_threshold} must be positive")
    if watchdog_limit < 1:
        raise ValueError(f"watchdog_limit={watchdog_limit} must be >= 1")
    if cov_limit <= 0.0:
        raise ValueError(f"cov_limit={cov_limit} must be positive")
    return PredictorPolicy(
        kind=np.asarray(PREDICTORS[name], np.int32),
        ema_alpha=np.asarray(ema_alpha, np.float32),
        threshold=np.asarray(threshold, np.float32),
        guard=np.asarray(bool(guard)),
        nis_threshold=np.asarray(nis_threshold, np.float32),
        watchdog_limit=np.asarray(watchdog_limit, np.int32),
        cov_limit=np.asarray(cov_limit, np.float32),
    )


class PredictorState(NamedTuple):
    """Carry for the whole bank: every member's state advances each epoch."""

    kf: kalman.KalmanState  # x (1,), p (1, 1)
    ema: Array              # () float32 — EMA of the mean observation
    reject_run: Array       # () int32 — consecutive innovation-gate rejects
    healthy: Array          # () bool — watchdog verdict after this epoch;
    #                         the allocator's degraded-mode fallback reads it
    #                         (always True when the guard is disarmed)


def init_state(dtype=jnp.float32) -> PredictorState:
    """Zero state — the KF member is exactly `kalman.init_state(1)`."""
    return PredictorState(
        kf=kalman.init_state(1, dtype=dtype),
        ema=jnp.zeros((), dtype),
        reject_run=jnp.int32(0),
        healthy=jnp.asarray(True),
    )


class KFInternals(NamedTuple):
    """Flight-recorder view of one epoch-boundary filter step (obs probes,
    DESIGN.md §14): everything the paper's Fig. 4-style narrative needs to
    explain WHY the signal flipped."""

    innovation: Array  # (m,) z - H x^  — surprise vs the filter's forecast
    gain: Array        # (m,) Kalman gain row K[0] that weighted it
    cov_trace: Array   # () tr(P_k) — posterior uncertainty
    x_pred: Array      # () one-step demand prediction A x_k (the signal's
                       #    pre-binarization value for the KF member)
    nis: Array         # () normalized innovation squared of the epoch
    rejected: Array    # () int32 {0,1} — innovation gate coasted this epoch
    reset: Array       # () int32 {0,1} — covariance reset fired this epoch
    healthy: Array     # () int32 {0,1} — watchdog verdict (1 = healthy)


def step_probed(
    pp: PredictorPolicy,
    kf_params: kalman.KalmanParams,
    state: PredictorState,
    z: Array,
) -> tuple[PredictorState, Array, KFInternals]:
    """`step` plus the KF internals of the epoch (see KFInternals).

    The extra outputs are pure functions of values `step` already
    computes (the gain recomputation CSEs against the measurement
    update), so the (state, signal) pair is bitwise that of `step` —
    which is in fact implemented as this function minus the internals.

    Self-healing layer (DESIGN.md §16), armed by `pp.guard`:

      * innovation gate — an epoch whose observation is non-finite or
        whose NIS exceeds `pp.nis_threshold` is REJECTED: the filter
        coasts on the a-priori state (the time update still ran, so
        uncertainty keeps growing) instead of ingesting the corruption.
      * divergence watchdog — `pp.watchdog_limit` consecutive rejects,
        or a posterior covariance trace that is non-finite or above
        `pp.cov_limit`, marks the filter UNHEALTHY; the allocator reads
        `PredictorState.healthy` and falls back to the fair split.
      * covariance reset — on the epoch the reject run first hits the
        limit (or on a bad covariance), P snaps back to the init prior
        and any non-finite state components are zeroed, so the filter
        re-converges from scratch once observations clean up.

    Every guard effect routes through `jnp.where(pp.guard, ...)`: with
    the guard disarmed the emitted state, signal, and legacy internals
    are bitwise those of the unguarded step.
    """
    kf_post, kf_prior, innovation = kalman.step(kf_params, state.kf, z)
    zbar = jnp.mean(z)
    ema = pp.ema_alpha * zbar + (1.0 - pp.ema_alpha) * state.ema

    # the gate, the watchdog and the reset carry the simulator's device
    # label `epoch.guard` (DESIGN.md §18); a compile-time annotation only
    with set_xla_metadata(noc_layer="epoch.guard"):
        # --- innovation gate -----------------------------------------------
        nis = kalman.innovation_nis(kf_params, kf_prior, z)
        z_finite = jnp.all(jnp.isfinite(z))
        # NaN NIS compares False against the threshold, hence the explicit
        # finiteness term: a NaN observation must always reject.
        reject = pp.guard & (~z_finite | (nis > pp.nis_threshold))
        kf_x = jnp.where(reject, kf_prior.x, kf_post.x)
        kf_p = jnp.where(reject, kf_prior.p, kf_post.p)

        # --- divergence watchdog + covariance reset ------------------------
        reject_run = jnp.where(reject, state.reject_run + 1, jnp.int32(0))
        cov_tr = jnp.trace(kf_p)
        cov_bad = ~jnp.isfinite(cov_tr) | (cov_tr > pp.cov_limit)
        run_bad = reject_run >= pp.watchdog_limit
        do_reset = pp.guard & ((reject_run == pp.watchdog_limit) | cov_bad)
        n = kf_params.state_dim
        kf_x = jnp.where(
            do_reset, jnp.where(jnp.isfinite(kf_x), kf_x, 0.0), kf_x
        )
        kf_p = jnp.where(do_reset, jnp.eye(n, dtype=kf_p.dtype), kf_p)
        healthy = ~pp.guard | ~(run_bad | cov_bad)
    kf_state = kalman.KalmanState(x=kf_x, p=kf_p)

    x_pred = kalman.one_step_prediction(kf_params, kf_state)[0]
    sig_kf = kalman.binarize(x_pred, pp.threshold)
    sig_ema = kalman.binarize(ema, pp.threshold)
    sig_last = kalman.binarize(zbar, pp.threshold)
    candidates = jnp.stack(
        [sig_kf, sig_ema, sig_last, jnp.int32(1), jnp.int32(0)]
    )
    signal = jnp.take(candidates, pp.kind)
    internals = KFInternals(
        innovation=innovation,
        gain=kalman.kalman_gain(kf_params, kf_prior)[0],
        cov_trace=jnp.trace(kf_state.p),
        x_pred=x_pred,
        nis=nis,
        rejected=reject.astype(jnp.int32),
        reset=do_reset.astype(jnp.int32),
        healthy=healthy.astype(jnp.int32),
    )
    new_state = PredictorState(
        kf=kf_state, ema=ema, reject_run=reject_run, healthy=healthy
    )
    return new_state, signal, internals


def step(
    pp: PredictorPolicy,
    kf_params: kalman.KalmanParams,
    state: PredictorState,
    z: Array,
) -> tuple[PredictorState, Array]:
    """Advance the bank one epoch and emit the selected binary signal.

    z: (m,) normalized observations (the same vector the KF consumes).
    Returns (new_state, signal) with signal a () int32 in {0, 1}.

    Bitwise contract: with ``kind == KF`` the emitted signal is exactly the
    legacy `binarize(kalman.step(...).x[0])` — the one-step prediction
    `A x_k` equals the posterior elementwise for the paper's A = I, and the
    `jnp.take` selection is an identity on the chosen lane.
    """
    new_state, signal, _ = step_probed(pp, kf_params, state, z)
    return new_state, signal
