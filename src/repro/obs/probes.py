"""Probe configuration and trace pytree for the NoC simulator.

`ProbeConfig` is a STATIC, hashable knob: it rides on `NoCConfig` /
`sim.SimStatic`, so flipping it produces a different compiled program.
Probes off (the default) leaves the simulator's traced computation — and
therefore the trace count and every golden capture — bit-for-bit
unchanged; probes on is its own single trace that additionally returns a
`SimTrace` alongside `SimResult`.

This module must stay import-light (no sim/router imports): sim.py
imports it at module load.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

from jax import Array


@dataclasses.dataclass(frozen=True)
class ProbeConfig:
    """Static flight-recorder switch.

    enabled=False must be the all-default value: `SimStatic` embeds this
    dataclass, and any non-default field would change the jit cache key of
    every existing caller.
    """

    enabled: bool = False


class SimTrace(NamedTuple):
    """Per-epoch introspection stream (leading axis = n_epochs, E).

    Fabric probes are accumulated per cycle inside the epoch and summed
    (or maxed) over the epoch's `epoch_len` cycles; KF internals are the
    epoch-boundary filter step that produced `SimResult.kf_signal`.

    Shapes use S = padded subnets, R = routers, P = ports, V = VCs per
    subnet, and all fabric probes sample END-of-cycle state so the `ref`
    and fused `pallas` engines agree bitwise.
    """

    # fabric occupancy: sum over cycles of per-buffer flit count
    occ_sum: Array        # (E, S, R, P, V) int32
    # switch allocation: grants and refusals per router, summed over
    # output ports and cycles
    arb_grant: Array      # (E, S, R) int32
    arb_deny: Array       # (E, S, R) int32
    # memory-controller queue depth, summed / maxed over cycles
    mcq_sum: Array        # (E, R) int32
    mcq_max: Array        # (E, R) int32
    # KF internals at the epoch boundary (scalar-state, 3-obs filter)
    kf_innovation: Array  # (E, 3) float32
    kf_gain: Array        # (E, 3) float32
    kf_cov_trace: Array   # (E,)   float32
    kf_x_pred: Array      # (E,)   float32  one-step demand prediction
    # realized (normalized) observation vector the filter consumed —
    # kf_x_pred[e] vs z_obs[e+1] is the prediction-vs-realized pairing
    # (z_obs records the POST-corruption vector under telemetry faults:
    # what the filter actually saw)
    z_obs: Array          # (E, 3) float32
    # fault + self-healing channels (DESIGN.md §16): the fault->reject->
    # reset->recover chain, one sample per epoch
    kf_nis: Array         # (E,)   float32 normalized innovation squared
    kf_rejected: Array    # (E,)   int32 {0,1} innovation gate coasted
    kf_reset: Array       # (E,)   int32 {0,1} covariance reset fired
    kf_healthy: Array     # (E,)   int32 {0,1} watchdog verdict (0 => the
    #                       allocator ran the fair-split fallback)
    faults_active: Array  # (E,)   int32 suppressed fabric elements +
    #                       telemetry-corruption flag this epoch
    # placement channel (DESIGN.md §17): the virtual node class applied
    # each epoch (the placement plan the traced policy selected) — the
    # relocation timeline `noc_trace` renders.  Appended LAST so older
    # positional consumers of the fault channels keep their indices.
    place_cls: Array      # (E, R) int32 node class per router (NT_* values)
    # tile-to-tile channels (DESIGN.md §19): the epoch counters of the same
    # names, 0 without a peer table.  Appended after the placement channel.
    gpu_peer_push: Array  # (E,)   int32 GPU requests injected to a GPU tile
    peer_served: Array    # (E,)   int32 requests served by a non-MC router


def summarize_trace(trace: SimTrace) -> dict:
    """Small JSON-friendly digest of a SimTrace (for ledger rows)."""
    import numpy as np

    occ = np.asarray(trace.occ_sum)
    healthy = np.asarray(trace.kf_healthy)
    return {
        "epochs": int(occ.shape[0]),
        "occ_sum_total": int(occ.sum()),
        "arb_grant_total": int(np.asarray(trace.arb_grant).sum()),
        "arb_deny_total": int(np.asarray(trace.arb_deny).sum()),
        "mcq_max": int(np.asarray(trace.mcq_max).max()),
        "kf_innovation_rms": float(
            np.sqrt(np.mean(np.square(np.asarray(trace.kf_innovation))))
        ),
        "kf_cov_trace_last": float(np.asarray(trace.kf_cov_trace)[-1]),
        "kf_rejected_total": int(np.asarray(trace.kf_rejected).sum()),
        "kf_reset_total": int(np.asarray(trace.kf_reset).sum()),
        "fallback_epochs": int((healthy == 0).sum()),
        "fault_epochs": int((np.asarray(trace.faults_active) > 0).sum()),
        # total router-epochs whose node class differs from the previous
        # epoch's plan: 0 on every identity-placement run
        "place_moves_total": int(
            (np.diff(np.asarray(trace.place_cls), axis=0) != 0).sum()
        ),
    }
