"""Entry: tile-to-tile traffic through the public NoC sweep.

The `sweep` entry's grid, steps and sample (`sim.sweep` over every workload
x mode, one seed drawn from (`--seed`, k) a step), on a configuration whose
`noc` block carries a peer table (`peer_weights`): the GPU tiles that hold
a model's routed experts take requests and serve them, and the traffic's
workloads, registered with the program as `traffic.PeerProfile`s, send a
share `gpu_peer_share` of their GPU requests to them (DESIGN.md §19).  The check compares a sample of the window's points with
the plain reference of that traffic (`bench.reference.noc_peer`) by the
`sweep` entry's readings and limits, and the two tile-to-tile counters
(`gpu_peer_push`, `peer_served`) exactly besides.
"""
from __future__ import annotations

import hashlib
import json
import sys

import jax
import numpy as np

from repro.core.noc import traffic as program_traffic

from bench.entries import sweep
# what `bench/control.py` reads from an entry besides its Runner
from bench.entries.sweep import Step, judge, step_seed  # noqa: F401
from bench.reference import noc_peer as reference

PEER_COUNTERS = ("gpu_peer_push", "peer_served")


def compare(got, want) -> tuple[int, float]:
    """`sweep.compare`, plus the tile-to-tile counters' elements that
    differ (all of them where the program lacks one)."""
    bad, gap = sweep.compare(got, want)
    got, want = sweep.readings(got), sweep.readings(want)
    for k in PEER_COUNTERS:
        b = np.asarray(want[k])
        a = np.asarray(got[k]) if k in got else None
        if a is None or a.dtype != b.dtype or a.shape != b.shape:
            bad += b.size
        else:
            bad += int(np.count_nonzero(a != b))
    return bad, gap


def register_workloads(traffic: dict) -> dict[str, str]:
    """`sweep.register_workloads` for rates that carry `gpu_peer_share`:
    each workload is registered as a `PeerProfile`, under a name with a
    digest of its rates."""
    names = {}
    for wl, rates in traffic["workloads"].items():
        digest = hashlib.sha1(json.dumps(rates, sort_keys=True).encode())
        name = f"bench.{wl}.{digest.hexdigest()[:10]}"
        program_traffic.register_workload(
            name, program_traffic.PeerProfile(**rates), overwrite=True)
        names[wl] = name
    return names


class Runner(sweep.Runner):
    """One cell: the `sweep` entry's steps and sample, checked against the
    tile-to-tile reference."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        self.config = config
        self.traffic = traffic
        self.chips = chips
        self.seed = seed
        self.points = sweep.grid(traffic)
        self.names = register_workloads(traffic)
        self.fields = sweep.program_fields(config)
        noc = config["noc"]
        self.cycles = noc["n_epochs"] * noc["epoch_len"]
        self.routers = noc["width"] * noc["height"]

    def describe(self) -> str:
        experts = sum(self.config["noc"]["peer_weights"])
        tiles = sum(w > 0 for w in self.config["noc"]["peer_weights"])
        return f"{super().describe()}, {experts} experts on {tiles} tiles"

    def reference(self, seeds_points: list[tuple[int, int]], lowp=False):
        """The plain reference over (seed, point index) pairs, on the host
        CPU; one dict of readings per pair.  Logs the tile-to-tile traffic
        of each point."""
        pts = [(self.points[i]["mode"],
                self.traffic["workloads"][self.points[i]["workload"]], seed)
               for seed, i in seeds_points]
        rows = reference.simulate(self.config["noc"], pts, lowp=lowp)
        for (seed, i), r in zip(seeds_points, rows):
            p = self.points[i]
            print(f"[reference] {p['workload']}/{p['mode']} seed {seed}: "
                  f"gpu_peer_push {int(r['gpu_peer_push'].sum())} of "
                  f"gpu_push {int(r['gpu_push'].sum())}, peer_served "
                  f"{int(r['peer_served'].sum())}", file=sys.stderr,
                  flush=True)
        return rows

    def check(self, steps: list[Step]) -> dict:
        picks = self.sample(steps)
        got = jax.device_get([steps[s].rows[i] for s, i in picks])
        for s in steps:  # free the window's rows before the reference runs
            s.rows.clear()
        want = self.reference([(steps[s].seed, i) for s, i in picks])
        return judge([compare(a, b) for a, b in zip(got, want)])
