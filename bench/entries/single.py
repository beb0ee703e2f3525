"""Entry: one design point at a time, `sim.simulate` with batch 1.

Architects who try one configuration at a time call the simulator once a
point.  Step k is one `sim.simulate` of the traffic's workload k mod n (in
the file's order) in the traffic's mode, on a seed drawn from (`--seed`,
k), then `jax.block_until_ready` on its answer: the launch cost a sweep
spreads over its points is paid by every point here.  The workloads are
registered with the program as the `sweep` entry registers them.  The
check draws, from the seed, one window step of each workload (as many as
the traffic's `sample`), runs the plain reference (`bench.reference.noc`)
on them on the host CPU and compares them by the `sweep` entry's readings
and limits.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.noc import sim

from bench.entries import sweep
# what `bench/control.py` reads from an entry besides its Runner
from bench.entries.sweep import Step, compare, judge, step_seed  # noqa: F401
from bench.reference import noc as reference


class Runner:
    """One cell: its steps (one simulation each) and their check."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        if chips != 1:
            raise ValueError("the single entry runs on one chip")
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.workloads = list(traffic["workloads"])
        self.names = sweep.register_workloads(traffic)
        self.fields = sweep.program_fields(config)
        noc = config["noc"]
        self.cycles = noc["n_epochs"] * noc["epoch_len"]
        self.routers = noc["width"] * noc["height"]

    def describe(self) -> str:
        noc = self.config["noc"]
        return (f"1 point a step ({self.traffic['mode']}, "
                f"{len(self.workloads)} workloads in turn), "
                f"{noc['width']}x{noc['height']} mesh, {noc['n_mc']} MCs, "
                f"{self.cycles} cycles, engine {self.config['engine']}")

    def workload(self, k: int) -> int:
        return k % len(self.workloads)

    def step(self, k: int) -> Step:
        seed = step_seed(self.seed, k)
        cfg = sim.NoCConfig(mode=self.traffic["mode"], seed=seed,
                            **self.fields)
        with jax.profiler.TraceAnnotation("bench.simulate"):
            row = sim.simulate(
                cfg, self.names[self.workloads[self.workload(k)]])
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(row)
        return Step(k, seed, [row], 1, self.routers * self.cycles,
                    self.cycles)

    # ---- the check

    def sample(self, steps: list[Step]) -> list[tuple[int, int]]:
        """(step position, workload index) pairs to compare, drawn from the
        seed: for each workload in a random order, one of the steps that
        ran it, until the traffic's `sample` is reached."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     (self.seed >> 32) & 0xFFFFFFFF, 0x5146])
        picks = []
        for w in rng.permutation(len(self.workloads)):
            ran = [i for i, s in enumerate(steps) if self.workload(s.k) == w]
            if ran and len(picks) < self.traffic["sample"]:
                picks.append((ran[int(rng.integers(len(ran)))], int(w)))
        return picks

    def reference(self, seeds_points: list[tuple[int, int]], lowp=False):
        """The plain reference over (seed, workload index) pairs, on the
        host CPU; one dict of readings per pair."""
        pts = [(self.traffic["mode"],
                self.traffic["workloads"][self.workloads[w]], seed)
               for seed, w in seeds_points]
        return reference.simulate(self.config["noc"], pts, lowp=lowp)

    def check(self, steps: list[Step]) -> dict:
        picks = self.sample(steps)
        got = jax.device_get([steps[s].rows[0] for s, _ in picks])
        for s in steps:  # free the window's answers before the reference
            s.rows.clear()
        want = self.reference([(steps[s].seed, w) for s, w in picks])
        return judge([compare(a, b) for a, b in zip(got, want)])
