"""Traced compute-placement streams for the NoC simulator (DESIGN.md §17).

The paper's controller only reallocates VCs/bandwidth; SHIFT (PAPERS.md)
relocates *compute* across chiplets when communication dominates.  This
module makes that possible by turning the injection source→node binding
— previously the static `Topology.node_type` numpy constants baked into
the trace — into per-epoch DATA: a `PlacementSchedule` (the
placement-domain sibling of `faults.FaultSchedule`) materializes to a
`PlacementStream` of per-epoch `(E, R)` node-class rows delivered to
`sim._simulate_impl` through the epoch scan `xs` exactly like the fault
masks, so relocated and static configurations share the simulator's ONE
compiled program (`sim.trace_count() == 1`; a static run threads the
identity stream from `static_placement`).

Each stream carries TWO class plans per epoch, mirroring how the VC
allocator carries masks0/masks1:

  * ``cls0`` — the base plan: which node class (NT_CPU / NT_GPU) each
               non-MC tile hosts when the placement controller is idle.
  * ``cls1`` — the boosted plan: the relocated layout the controller
               switches to while the KF-driven hysteresis machine holds
               config 1 (gated by `ModePolicy.place_enable`).

MC tiles are physical — memory controllers never relocate — so MC rows
always carry NT_MC and the simulator re-asserts that with a `where` on
the static `is_mc` mask.  The identity stream sets both plans to the
topology's own `node_type`, which makes every derived quantity
(`is_gpu`, `node_cls`, `req_sub`, injection gates) select bit-for-bit
the pre-refactor constants: static placement is bitwise-unchanged by
construction.
"""
from __future__ import annotations

import dataclasses
import difflib
from typing import Callable, NamedTuple

import jax
import numpy as np

from repro.core.noc.topology import NT_CPU, NT_GPU, NT_MC, Topology, make_topology

Array = jax.Array

_SLOTS = ("base", "boost")


class PlacementStream(NamedTuple):
    """Per-epoch node-class plans (a JAX pytree; leading axis = E).

    Consumed by the epoch scan as `xs`: each epoch body receives one
    (R,) base row and one (R,) boosted row; the traced policy picks
    between them.  Leaves may carry an extra leading batch dimension
    when stacked for `sim.simulate_batch` (like `faults.FaultStream`).
    """

    cls0: Array  # (E, R) int32 — base node class per router (NT_*)
    cls1: Array  # (E, R) int32 — boosted/relocated node class per router


class PlacementEvent(NamedTuple):
    """One relocation arc: governs epochs in [start, stop) (run fractions).

    plan — name of a registered plan builder (`PLAN_BUILDERS`): the
           (R,) layout written over the affected window.
    slot — "boost" writes the layout into ``cls1`` (the controller
           relocates only while the KF holds config 1); "base" writes
           ``cls0`` (a forced, scheduled migration à la SHIFT,
           independent of the controller).
    """

    start: float
    stop: float
    plan: str = "gpu_near_mc"
    slot: str = "boost"


def _plan_identity(topo: Topology) -> np.ndarray:
    return np.asarray(topo.node_type, np.int32).copy()


def _plan_gpu_near_mc(topo: Topology) -> np.ndarray:
    """Relocate the GPU class onto the non-MC tiles nearest the MCs.

    Keeps the GPU/CPU tile counts of the base layout (14 + 14 on the
    6x6) and ranks non-MC tiles by Manhattan distance to the closest
    MC (ties broken by router id, deterministically).  Shorter
    request/reply paths for the memory-bound class is the mechanism
    behind the joint >= bandwidth-only GPU-IPC gate in fig_placement.
    """
    nt = np.asarray(topo.node_type, np.int32)
    n_gpu = int((nt == NT_GPU).sum())
    w = topo.width
    ids = np.arange(topo.n_routers)
    xy = np.stack([ids % w, ids // w], axis=1)
    mc_xy = xy[np.asarray(topo.mc_ids)]
    dist = np.abs(xy[:, None, :] - mc_xy[None, :, :]).sum(-1).min(-1)
    non_mc = ids[nt != NT_MC]
    order = non_mc[np.lexsort((non_mc, dist[non_mc]))]
    plan = nt.copy()
    plan[order[:n_gpu]] = NT_GPU
    plan[order[n_gpu:]] = NT_CPU
    return plan


def _plan_swap_classes(topo: Topology) -> np.ndarray:
    """Swap the GPU and CPU classes on every non-MC tile."""
    nt = np.asarray(topo.node_type, np.int32)
    plan = nt.copy()
    plan[nt == NT_GPU] = NT_CPU
    plan[nt == NT_CPU] = NT_GPU
    return plan


# (R,) layout builders an event's `plan` names.  Builders only ever
# reassign non-MC tiles between NT_CPU/NT_GPU; MC rows stay NT_MC.
PLAN_BUILDERS: dict[str, Callable[[Topology], np.ndarray]] = {
    "identity": _plan_identity,
    "gpu_near_mc": _plan_gpu_near_mc,
    "swap_classes": _plan_swap_classes,
}


@dataclasses.dataclass(frozen=True)
class PlacementSchedule:
    """A piecewise relocation program (sibling of `faults.FaultSchedule`).

    ``materialize(n_epochs, topology)`` lowers the schedule to a
    `PlacementStream` with exact epoch boundaries: epoch ``e`` is inside
    an event iff ``round(start * n_epochs) <= e < round(stop * n_epochs)``.
    Outside every event both plans are the topology's base layout.
    """

    events: tuple[PlacementEvent, ...]

    def __post_init__(self):
        for ev in self.events:
            if ev.plan not in PLAN_BUILDERS:
                raise ValueError(
                    f"unknown placement plan {ev.plan!r}; expected one of "
                    f"{sorted(PLAN_BUILDERS)}"
                )
            if ev.slot not in _SLOTS:
                raise ValueError(
                    f"placement slot {ev.slot!r} must be one of {_SLOTS}"
                )
            if not 0.0 <= ev.start < ev.stop <= 1.0:
                raise ValueError(
                    f"placement event window [{ev.start}, {ev.stop}) "
                    "outside [0, 1]"
                )

    def materialize(
        self, n_epochs: int, topology: Topology | None = None
    ) -> PlacementStream:
        topo = topology if topology is not None else make_topology()
        base = _plan_identity(topo)
        cls0 = np.tile(base, (n_epochs, 1))
        cls1 = np.tile(base, (n_epochs, 1))
        for ev in self.events:
            lo = int(round(ev.start * n_epochs))
            hi = int(round(ev.stop * n_epochs))
            if hi <= lo:
                continue
            plan = PLAN_BUILDERS[ev.plan](topo)
            if plan.shape != base.shape:
                raise ValueError(
                    f"plan {ev.plan!r} built shape {plan.shape} for a "
                    f"{topo.n_routers}-router topology"
                )
            target = cls1 if ev.slot == "boost" else cls0
            target[lo:hi] = plan
        return PlacementStream(cls0=cls0, cls1=cls1)


def static_placement(
    n_epochs: int, topology: Topology | None = None
) -> PlacementStream:
    """The identity placement stream: both plans = the topology layout.

    This is what every placement-free run threads through the epoch
    scan, which is what keeps relocated x static configurations on one
    compiled program — and, because every derived node-class quantity is
    a select against these rows, the static program's VALUES are
    bit-for-bit the pre-placement program's.
    """
    return PlacementSchedule(()).materialize(n_epochs, topology)


# ---------------------------------------------------------------------------
# Placement scenario library + registry (the placement-domain FAULTS dict).
# ---------------------------------------------------------------------------

PLACEMENTS: dict[str, PlacementSchedule] = {
    # the KF-gated relocation of record: while the controller holds the
    # boost config, GPU compute sits on the tiles nearest the MCs.
    "GPU_NEAR_MC": PlacementSchedule((
        PlacementEvent(0.0, 1.0, "gpu_near_mc", "boost"),
    )),
    # forced static relocation: the near-MC layout is the base plan for
    # the whole run, independent of the controller (ablation baseline).
    "GPU_NEAR_MC_ALWAYS": PlacementSchedule((
        PlacementEvent(0.0, 1.0, "gpu_near_mc", "base"),
    )),
    # a scheduled SHIFT-style migration timeline: mid-run the base plan
    # swaps every GPU/CPU tile (exercises the relocation trace channel).
    "SWAP_MID": PlacementSchedule((
        PlacementEvent(0.5, 1.0, "swap_classes", "base"),
    )),
}


def register_placement(
    name: str, schedule: PlacementSchedule, overwrite: bool = False
) -> None:
    """Register a named placement scenario (shares the `--placement` namespace)."""
    if not isinstance(schedule, PlacementSchedule):
        raise TypeError(
            f"placement scenario {name!r} must be a PlacementSchedule, got "
            f"{type(schedule).__name__}"
        )
    if not overwrite and name in PLACEMENTS:
        raise ValueError(
            f"placement scenario {name!r} already exists; pass overwrite=True"
        )
    PLACEMENTS[name] = schedule


def lookup_placement(name: str) -> PlacementSchedule:
    if name in PLACEMENTS:
        return PLACEMENTS[name]
    near = difflib.get_close_matches(name, sorted(PLACEMENTS), n=3, cutoff=0.4)
    hint = f"; did you mean {near}?" if near else ""
    raise ValueError(
        f"unknown placement scenario {name!r}{hint} "
        f"(known: {sorted(PLACEMENTS)})"
    )


# The union accepted by resolve_placement: a scenario name, a schedule, a
# pre-materialized stream, or None (identity/static placement).
PlacementSourceLike = str | PlacementSchedule | PlacementStream | None


def resolve_placement(
    source: PlacementSourceLike,
    n_epochs: int,
    topology: Topology | None = None,
) -> PlacementStream:
    """Lower any placement source to the canonical per-epoch stream.

    The ONE resolution path the simulator entry points call (mirroring
    `faults.resolve_faults`); the result is shape-validated so every
    source kind feeds the simulator the same program shape, and its
    leaves are host (NumPy) arrays whatever the source held.
    """
    topo = topology if topology is not None else make_topology()
    if source is None:
        stream = static_placement(n_epochs, topo)
    elif isinstance(source, str):
        stream = lookup_placement(source).materialize(n_epochs, topo)
    elif isinstance(source, PlacementSchedule):
        stream = source.materialize(n_epochs, topo)
    elif isinstance(source, PlacementStream):
        stream = PlacementStream(*(np.asarray(x) for x in source))
    else:
        raise TypeError(
            f"cannot resolve placement source of type {type(source).__name__}; "
            "expected a scenario name, PlacementSchedule, PlacementStream, "
            "or None"
        )
    expect = {
        "cls0": (n_epochs, topo.n_routers),
        "cls1": (n_epochs, topo.n_routers),
    }
    for f, shape in expect.items():
        leaf = getattr(stream, f)
        if tuple(leaf.shape) != shape:
            raise ValueError(
                f"placement stream leaf {f!r} has shape {tuple(leaf.shape)}, "
                f"expected {shape}"
            )
    return stream
