"""Traced fault-injection streams for the NoC simulator (DESIGN.md §16).

The paper's controller must "react in real-time" — which presupposes it
survives runtime disturbances: links flap, routers brown out, memory
controllers stall, and the telemetry the KF ingests can be corrupted.
This module models those disturbances as *data*, never as program
structure: a `FaultSchedule` (the fault-domain sibling of
`traffic.ScenarioSchedule`) materializes to a `FaultStream` — per-epoch
mask rows delivered to `sim._simulate_impl` through the epoch scan `xs`
exactly like the demand rows and RNG streams — so faulty and healthy
configurations share the simulator's ONE compiled program
(`sim.trace_count() == 1` is preserved; a healthy run threads the
identity stream from `healthy_stream`).

Fault semantics (consumed by `router.router_cycle` / the fused lane
kernel / the epoch-boundary KF step):

  * link    — `link_ok[e, r, p]` False suppresses grants through output
              port `p` of router `r`: the masked link is never granted,
              in-flight flits back-pressure in their VCs (they never
              vanish).  With a neighbor table, the reverse direction of
              each masked link is masked too (a dead link is dead both
              ways).
  * router  — `router_ok[e, r]` False suppresses EVERY grant at router
              `r` (a brownout: no traversal, no ejection); upstream
              credit stalls propagate the back-pressure.
  * mc      — `mc_ok[e, r]` False freezes MC service at router `r`:
              timers stop, the queue keeps filling until `mc_queue_cap`
              back-pressures the fabric.
  * telem   — `telem_mode[e]` corrupts the normalized observation vector
              BEFORE the predictor bank sees it: 1 drops it to the
              normalization floor (-1), 2 adds `telem_mag[e]` (a spike),
              3 replaces it with NaN.  Mode 0 selects the clean vector
              bit-for-bit, so a healthy epoch is value-identical to the
              pre-fault program.

Faults only ever SUPPRESS (masks are AND-ed into existing gates), never
enable — padded-lane garbage conventions in the lane engine stay safe by
construction.
"""
from __future__ import annotations

import dataclasses
import difflib
from typing import NamedTuple

import jax
import numpy as np

from repro.core.noc.topology import N_PORTS, PORT_E, PORT_L, PORT_N, PORT_S, PORT_W

Array = jax.Array

# default router count of the paper topology (6x6 mesh); callers with a
# custom topology pass n_routers/neighbor explicitly.
DEFAULT_R = 36

# telemetry-corruption modes (telem_mode values)
TELEM_OK, TELEM_DROP, TELEM_SPIKE, TELEM_NAN = range(4)

_KINDS = ("link", "router", "mc", "telem")
_NONLOCAL_PORTS = (PORT_N, PORT_E, PORT_S, PORT_W)


class FaultStream(NamedTuple):
    """Per-epoch fault masks (a JAX pytree; leading axis = n_epochs, E).

    Consumed by the epoch scan as `xs`: each epoch body receives one
    (R, P) link row, (R,) router/MC rows and the scalar telemetry mode.
    Leaves may carry an extra leading batch dimension when stacked for
    `sim.simulate_batch` (exactly like `traffic.WorkloadProfile`).
    """

    link_ok: Array     # (E, R, P) bool — grant allowed through port p
    router_ok: Array   # (E, R) bool — router grants anything at all
    mc_ok: Array       # (E, R) bool — MC service ticks
    telem_mode: Array  # (E,) int32 — TELEM_* corruption mode
    telem_mag: Array   # (E,) float32 — spike magnitude (mode TELEM_SPIKE)


class FaultEvent(NamedTuple):
    """One fault arc: governs epochs in [start, stop) (run fractions).

    kind     — "link" | "router" | "mc" | "telem".
    routers  — affected router ids (empty = every router) for the
               physical kinds; ignored for "telem".
    ports    — affected output ports for kind="link" (empty = all four
               mesh ports; the Local port is never maskable — ejection
               faults are router brownouts).
    period   — 0 = solid fault; > 0 = transient flapping: the fault is
               active for `period` epochs, then released for `period`,
               repeating across [start, stop).
    mode/mag — telemetry corruption mode and spike magnitude.
    """

    start: float
    stop: float
    kind: str
    routers: tuple[int, ...] = ()
    ports: tuple[int, ...] = ()
    period: int = 0
    mode: int = TELEM_DROP
    mag: float = 0.0


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """A piecewise fault program (sibling of `traffic.ScenarioSchedule`).

    ``materialize(n_epochs)`` lowers the schedule to a `FaultStream` with
    exact epoch boundaries: epoch ``e`` is inside an event iff
    ``round(start * n_epochs) <= e < round(stop * n_epochs)`` (and, for
    flapping events, the epoch falls in an active half-period).
    """

    events: tuple[FaultEvent, ...]

    def __post_init__(self):
        for ev in self.events:
            if ev.kind not in _KINDS:
                raise ValueError(
                    f"unknown fault kind {ev.kind!r}; expected one of {_KINDS}"
                )
            if not 0.0 <= ev.start < ev.stop <= 1.0:
                raise ValueError(
                    f"fault event window [{ev.start}, {ev.stop}) outside [0, 1]"
                )
            if ev.period < 0:
                raise ValueError(f"fault period {ev.period} must be >= 0")
            if ev.kind == "telem":
                if ev.mode not in (TELEM_DROP, TELEM_SPIKE, TELEM_NAN):
                    raise ValueError(
                        f"telem fault mode {ev.mode} not in "
                        f"{{TELEM_DROP, TELEM_SPIKE, TELEM_NAN}}"
                    )
            if ev.kind == "link":
                bad = [p for p in ev.ports if p not in _NONLOCAL_PORTS]
                if bad:
                    raise ValueError(
                        f"link fault ports {bad} invalid: only the four mesh "
                        f"ports {_NONLOCAL_PORTS} can be masked"
                    )

    def materialize(
        self,
        n_epochs: int,
        n_routers: int = DEFAULT_R,
        n_ports: int = N_PORTS,
        neighbor: np.ndarray | None = None,
        opposite: np.ndarray | None = None,
    ) -> FaultStream:
        link_ok = np.ones((n_epochs, n_routers, n_ports), bool)
        router_ok = np.ones((n_epochs, n_routers), bool)
        mc_ok = np.ones((n_epochs, n_routers), bool)
        telem_mode = np.zeros((n_epochs,), np.int32)
        telem_mag = np.zeros((n_epochs,), np.float32)

        for ev in self.events:
            lo = int(round(ev.start * n_epochs))
            hi = int(round(ev.stop * n_epochs))
            epochs = np.arange(lo, hi)
            if ev.period > 0:  # transient flap: period on, period off
                epochs = epochs[((epochs - lo) // ev.period) % 2 == 0]
            if epochs.size == 0:
                continue
            routers = (
                np.arange(n_routers)
                if not ev.routers
                else np.asarray(ev.routers, np.int64)
            )
            if routers.size and (routers.min() < 0 or routers.max() >= n_routers):
                raise ValueError(
                    f"fault routers {tuple(ev.routers)} outside [0, {n_routers})"
                )
            if ev.kind == "telem":
                telem_mode[epochs] = ev.mode
                telem_mag[epochs] = np.float32(ev.mag)
            elif ev.kind == "router":
                router_ok[np.ix_(epochs, routers)] = False
            elif ev.kind == "mc":
                mc_ok[np.ix_(epochs, routers)] = False
            else:  # link
                ports = ev.ports or _NONLOCAL_PORTS
                for p in ports:
                    link_ok[np.ix_(epochs, routers, [p])] = False
                    if neighbor is not None:
                        # a dead link is dead both ways: mask the reverse
                        # direction at each downstream neighbor too
                        opp = (
                            np.asarray(opposite)
                            if opposite is not None
                            else np.asarray([PORT_S, PORT_W, PORT_N, PORT_E,
                                             PORT_L])
                        )
                        for r in routers:
                            nb = int(np.asarray(neighbor)[r, p])
                            if nb >= 0:
                                link_ok[np.ix_(epochs, [nb], [int(opp[p])])] \
                                    = False
        return FaultStream(link_ok, router_ok, mc_ok, telem_mode, telem_mag)


def healthy_stream(
    n_epochs: int, n_routers: int = DEFAULT_R, n_ports: int = N_PORTS
) -> FaultStream:
    """The identity fault stream: every mask passes, telemetry clean.

    This is what every healthy run threads through the epoch scan, which
    is what keeps faulty x healthy configurations on one compiled program
    — and, because every fault gate is an AND / a mode-0 `where`, the
    healthy program's VALUES are bit-for-bit the pre-fault program's.
    """
    return FaultSchedule(()).materialize(n_epochs, n_routers, n_ports)


# ---------------------------------------------------------------------------
# Fault scenario library + registry (the fault-domain SCENARIOS dict).
# Windows are phased against traffic.SCENARIOS["SHIFT_PATH_BFS"]'s four
# 30-epoch kernel arcs (PATH, PATH, BFS, BFS on the canonical 120 epochs).
# ---------------------------------------------------------------------------

FAULTS: dict[str, FaultSchedule] = {
    # transient link flaps on the links feeding top-row MCs 2 and 3
    # (routers 8/9 port N and the reverse direction), flapping in
    # 2-epoch bursts across the BFS half of the run.
    "FLAP_BFS": FaultSchedule((
        FaultEvent(0.55, 0.80, "link", routers=(8, 9), ports=(PORT_N,),
                   period=2),
    )),
    # a center-of-mesh router brownout during the second PATH burst: no
    # grants at routers 14/15/20/21 for ~12 epochs.
    "BROWNOUT": FaultSchedule((
        FaultEvent(0.30, 0.40, "router", routers=(14, 15, 20, 21)),
    )),
    # pure telemetry corruption, network healthy: NaNs across the shift
    # onto BFS, a +8 spike mid-burst, a dropped-to-floor window late.
    "TELEM_GLITCH": FaultSchedule((
        FaultEvent(0.50, 0.60, "telem", mode=TELEM_NAN),
        FaultEvent(0.70, 0.75, "telem", mode=TELEM_SPIKE, mag=8.0),
        FaultEvent(0.85, 0.90, "telem", mode=TELEM_DROP),
    )),
    # the compound case: link flaps spanning the PATH->BFS shift while
    # the telemetry NaNs out right at the shift point.
    "FLAP_DURING_SHIFT": FaultSchedule((
        FaultEvent(0.45, 0.65, "link", routers=(8, 9), ports=(PORT_N,),
                   period=3),
        FaultEvent(0.50, 0.55, "telem", mode=TELEM_NAN),
    )),
}


def register_faults(
    name: str, schedule: FaultSchedule, overwrite: bool = False
) -> None:
    """Register a named fault scenario (shares the `--faults` namespace)."""
    if not isinstance(schedule, FaultSchedule):
        raise TypeError(
            f"fault scenario {name!r} must be a FaultSchedule, got "
            f"{type(schedule).__name__}"
        )
    if not overwrite and name in FAULTS:
        raise ValueError(
            f"fault scenario {name!r} already exists; pass overwrite=True"
        )
    FAULTS[name] = schedule


def lookup_faults(name: str) -> FaultSchedule:
    if name in FAULTS:
        return FAULTS[name]
    near = difflib.get_close_matches(name, sorted(FAULTS), n=3, cutoff=0.4)
    hint = f"; did you mean {near}?" if near else ""
    raise ValueError(
        f"unknown fault scenario {name!r}{hint} "
        f"(known: {sorted(FAULTS)})"
    )


# The union accepted by resolve_faults: a scenario name, a schedule, a
# pre-materialized stream, or None (healthy).
FaultSourceLike = str | FaultSchedule | FaultStream | None


def resolve_faults(
    source: FaultSourceLike,
    n_epochs: int,
    n_routers: int = DEFAULT_R,
    n_ports: int = N_PORTS,
    neighbor: np.ndarray | None = None,
    opposite: np.ndarray | None = None,
) -> FaultStream:
    """Lower any fault source to the canonical per-epoch `FaultStream`.

    The ONE resolution path the simulator entry points call (mirroring
    `traffic.resolve_source`); the result is shape-validated so every
    source kind feeds the simulator the same program shape, and its
    leaves are host (NumPy) arrays whatever the source held.
    """
    if source is None:
        stream = healthy_stream(n_epochs, n_routers, n_ports)
    elif isinstance(source, str):
        stream = lookup_faults(source).materialize(
            n_epochs, n_routers, n_ports, neighbor, opposite
        )
    elif isinstance(source, FaultSchedule):
        stream = source.materialize(
            n_epochs, n_routers, n_ports, neighbor, opposite
        )
    elif isinstance(source, FaultStream):
        stream = FaultStream(*(np.asarray(x) for x in source))
    else:
        raise TypeError(
            f"cannot resolve fault source of type {type(source).__name__}; "
            "expected a scenario name, FaultSchedule, FaultStream, or None"
        )
    expect = {
        "link_ok": (n_epochs, n_routers, n_ports),
        "router_ok": (n_epochs, n_routers),
        "mc_ok": (n_epochs, n_routers),
        "telem_mode": (n_epochs,),
        "telem_mag": (n_epochs,),
    }
    for f, shape in expect.items():
        leaf = getattr(stream, f)
        if tuple(leaf.shape) != shape:
            raise ValueError(
                f"fault stream leaf {f!r} has shape {tuple(leaf.shape)}, "
                f"expected {shape}"
            )
    return stream
