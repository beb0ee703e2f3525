"""Entry: the relocation deployment through the public NoC sweep.

The same `sim.sweep` (and `sim.sweep_sharded` across several chips) as the
`sweep` entry, over a grid of scenario x control x fault case, every point
in the `kf` mode with the KF predictor, the guard armed and the
configuration's placement plan.  The traffic file writes out each
scenario's segments and each fault case's events; the entry registers them
with the program under names that carry a digest of their content, so one
file sets what both the program and the plain reference
(`bench.reference.noc_relocate`) run.  A step is one sweep of the grid on
one seed drawn from (`--seed`, k), then `jax.block_until_ready` on its
rows.  The check is the `sweep` entry's: a sample of the window's points
drawn from the seed (every scenario, control and fault case), compared
with the reference by the same readings and limits, integers exactly,
floats by their relative gap.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import sys

from repro.core.allocator import PolicyConfig
from repro.core.noc import faults as program_faults
from repro.core.noc import placement as program_placement
from repro.core.noc import sim
from repro.core.noc import traffic as program_traffic

from bench.entries import sweep
# what `bench/control.py` reads from an entry besides its Runner
from bench.entries.sweep import Step, compare, judge, step_seed  # noqa: F401
from bench.reference import noc_relocate as reference


def digest(obj) -> str:
    return hashlib.sha1(json.dumps(obj, sort_keys=True).encode()).hexdigest()[:10]


def grid(traffic: dict) -> list[dict]:
    """The traffic's points: scenario x control x fault case, in that
    order (scenario-major)."""
    return [dict(workload=w, control=c, faults=f) for w, c, f in
            itertools.product(traffic["scenarios"], traffic["controls"],
                              traffic["faults"])]


def schedule(segments: list[dict]) -> program_traffic.ScenarioSchedule:
    profile = program_traffic.WorkloadProfile
    return program_traffic.ScenarioSchedule(tuple(
        program_traffic.Segment(
            s["start"], profile(**s["profile"]),
            ramp_to=profile(**s["ramp_to"]) if s.get("ramp_to") else None,
            pin_phase=s.get("pin_phase"))
        for s in segments))


def fault_schedule(events: list[dict]) -> program_faults.FaultSchedule:
    return program_faults.FaultSchedule(tuple(
        program_faults.FaultEvent(
            e["start"], e["stop"], e["kind"], routers=tuple(e["routers"]),
            ports=tuple(e["ports"]), period=e["period"], mode=e["mode"],
            mag=e["mag"])
        for e in events))


def register(config: dict, traffic: dict) -> dict:
    """Register the traffic's scenarios and fault cases and the
    configuration's placement with the program; returns the name each runs
    under there (None for a case without faults)."""
    names = {"workload": {}, "faults": {}}
    for wl, segments in traffic["scenarios"].items():
        name = f"bench.{wl}.{digest(segments)}"
        program_traffic.register_workload(name, schedule(segments),
                                          overwrite=True)
        names["workload"][wl] = name
    for case, events in traffic["faults"].items():
        name = f"bench.{case}.{digest(events)}" if events else None
        if events:
            program_faults.register_faults(name, fault_schedule(events),
                                           overwrite=True)
        names["faults"][case] = name
    p = config["placement"]
    names["placement"] = f"bench.placement.{digest(p)}"
    program_placement.register_placement(
        names["placement"], program_placement.PlacementSchedule((
            program_placement.PlacementEvent(p["start"], p["stop"], p["plan"],
                                             p["slot"]),)),
        overwrite=True)
    return names


class Runner(sweep.Runner):
    """One cell: its steps (sweeps) and the check of their answers; a step,
    the sample and the check are the `sweep` entry's."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        self.config = config
        self.traffic = traffic
        self.chips = chips
        self.seed = seed
        self.points = grid(traffic)
        self.names = register(config, traffic)
        self.fields = dict(sweep.program_fields(config),
                           policy=PolicyConfig(**config["hysteresis"]))
        noc = config["noc"]
        self.cycles = noc["n_epochs"] * noc["epoch_len"]
        self.routers = noc["width"] * noc["height"]

    def describe(self) -> str:
        return (f"{super().describe()}, guard {self.config['guard']}, "
                f"placement {self.config['placement']['plan']}")

    def specs(self, seed: int) -> list:
        return [sim.SweepSpec(
            mode=self.traffic["mode"], predictor=self.traffic["predictor"],
            workload=self.names["workload"][p["workload"]],
            faults=self.names["faults"][p["faults"]],
            guard=self.config["guard"], placement=self.names["placement"],
            control=p["control"], seed=seed) for p in self.points]

    def reference(self, seeds_points: list[tuple[int, int]], lowp=False):
        """The plain reference over (seed, point index) pairs, on the host
        CPU; one dict of readings per pair.  Logs what the deployment did
        in them."""
        pts = [dict(mode=self.traffic["mode"],
                    control=self.points[i]["control"],
                    segments=self.traffic["scenarios"][
                        self.points[i]["workload"]],
                    faults=self.traffic["faults"][self.points[i]["faults"]],
                    seed=seed)
               for seed, i in seeds_points]
        rows = reference.simulate(self.config, pts, lowp=lowp)
        count = lambda f: sum(int(f(r).sum()) for r in rows)
        print(f"[reference] {len(rows)} points: "
              f"{count(lambda r: r['relocated'])} relocated epochs, "
              f"{count(lambda r: r['kf_rejected'])} guard rejections, "
              f"{count(lambda r: r['links_down'] > 0)} epochs with links "
              "down", file=sys.stderr, flush=True)
        return rows
