"""Entry: the public NoC sweep, `sim.sweep` on one chip and
`sim.sweep_sharded` across several.

A step is one sweep of the traffic's grid of points, followed by
`jax.block_until_ready` on its rows, as a user summarising them would do.
Step k simulates every point with one seed drawn from (`--seed`, k), so
every step does the same amount of work.  The traffic file's workloads are
registered with the program under names of their own, so one file sets the
rates both sides run.  The check draws a sample of the window's points from
the seed, runs the plain reference (`bench.reference.noc`, which imports
nothing of the program) over them on the host CPU, and compares every
per-epoch reading by name: integers (the counters, the KF signal, the
applied configuration, the GPU's VC quota) exactly, floats (IPC, latency,
injection rate) by their relative gap.
"""
from __future__ import annotations

import hashlib
import itertools
import json
from typing import NamedTuple

import jax
import numpy as np

from repro.core.noc import sim
from repro.core.noc import traffic as program_traffic

from bench.reference import noc as reference

# Integers are exact.  A float may differ from the reference's by rounding
# alone: the program runs on the chip and the reference on the host, and
# each is free to order its float32 arithmetic as it likes.  The limit lies
# between the gaps sound runs read and those of the bfloat16 control
# (PERF.md §2).
INT_LIMIT = 0
FLOAT_GAP_LIMIT = 1e-5
NOT_A_GAP = 1e30   # what a missing, reshaped or non-finite float reads


class Step(NamedTuple):
    k: int
    seed: int
    rows: list
    points: int
    router_cycles: int
    point_cycles: int


def step_seed(seed: int, k: int) -> int:
    """A 31-bit simulation seed for step k of a run with `--seed` seed (the
    program's seeds are int32; `--seed` may need more than 32 bits)."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, k]
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def grid(traffic: dict) -> list[dict]:
    """The traffic's points: every workload x mode, workload-major."""
    return [dict(workload=w, mode=m) for w, m in
            itertools.product(traffic["workloads"], traffic["modes"])]


def sample_points(points: list[dict], n: int, rng) -> list[int]:
    """n distinct point indices drawn from rng.  Workload and mode each walk
    a random permutation of their values, so every value of a field with at
    most n values is drawn."""
    keys = list(points[0])
    index = {tuple(p[k] for k in keys): i for i, p in enumerate(points)}
    walks = []
    for k in keys:
        values = list(dict.fromkeys(p[k] for p in points))
        walks.append([values[j] for j in rng.permutation(len(values))])
    picked = [index[tuple(w[i % len(w)] for w in walks)] for i in range(n)]
    return list(dict.fromkeys(picked))


def register_workloads(traffic: dict) -> dict[str, str]:
    """Register the traffic's workloads with the program; returns the name
    each runs under there.  A name carries a digest of its rates, so two
    files that give one workload different rates never share a name."""
    names = {}
    for wl, rates in traffic["workloads"].items():
        digest = hashlib.sha1(json.dumps(rates, sort_keys=True).encode())
        name = f"bench.{wl}.{digest.hexdigest()[:10]}"
        program_traffic.register_workload(
            name, program_traffic.WorkloadProfile(**rates), overwrite=True)
        names[wl] = name
    return names


def program_fields(config: dict) -> dict:
    """The sweep's `NoCConfig` overrides: the configuration's fields and the
    engine, the latter only while `NoCConfig` has a `backend` field."""
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["noc"].items()}
    if "backend" in sim.NoCConfig.__dataclass_fields__:
        kw["backend"] = config["engine"]
    return kw


def readings(row) -> dict:
    """A `SimResult` (or the reference's dict) as {leaf name: array}."""
    if isinstance(row, dict):
        return row
    out = row._asdict()
    out.update(out.pop("counters")._asdict())
    return out


def compare(got, want) -> tuple[int, float]:
    """(integer elements that differ, widest relative gap of a float)."""
    got, want = readings(got), readings(want)
    bad, gap = 0, 0.0
    for k in reference.INTS:
        b = np.asarray(want[k])
        a = np.asarray(got[k]) if k in got else None
        if a is None or a.dtype != b.dtype or a.shape != b.shape:
            bad += b.size
        else:
            bad += int(np.count_nonzero(a != b))
    for k in reference.FLOATS:
        b = np.asarray(want[k], np.float64)
        a = np.asarray(got[k]) if k in got else None
        if (a is None or a.dtype != np.float32 or a.shape != b.shape
                or not np.isfinite(a).all()):
            return bad, NOT_A_GAP
        d = np.abs(a.astype(np.float64) - b) / np.maximum(np.abs(b), 1e-30)
        gap = max(gap, float(d.max(initial=0.0)))
    return bad, gap


class Runner:
    """One cell: its steps (sweeps) and the check of their answers."""

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        self.config = config
        self.traffic = traffic
        self.chips = chips
        self.seed = seed
        self.points = grid(traffic)
        self.names = register_workloads(traffic)
        self.fields = program_fields(config)
        noc = config["noc"]
        self.cycles = noc["n_epochs"] * noc["epoch_len"]
        self.routers = noc["width"] * noc["height"]

    def describe(self) -> str:
        noc = self.config["noc"]
        return (f"{len(self.points)} points, {noc['width']}x{noc['height']} "
                f"mesh, {noc['n_mc']} MCs, {self.cycles} cycles, engine "
                f"{self.config['engine']}, {self.chips} chip(s)")

    def specs(self, seed: int) -> list:
        return [sim.SweepSpec(mode=p["mode"], workload=self.names[p["workload"]],
                              seed=seed) for p in self.points]

    def step(self, k: int) -> Step:
        seed = step_seed(self.seed, k)
        with jax.profiler.TraceAnnotation("bench.build_specs"):
            specs = self.specs(seed)
        with jax.profiler.TraceAnnotation("bench.sweep_dispatch"):
            if self.chips > 1:
                rows = sim.sweep_sharded(specs, devices=self.chips,
                                         **self.fields)
            else:
                rows = sim.sweep(specs, **self.fields)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(rows)
        n = len(specs)
        return Step(k, seed, rows, n, n * self.routers * self.cycles,
                    n * self.cycles)

    # ---- the check

    def sample(self, steps: list[Step]) -> list[tuple[int, int]]:
        """(step position, point index) pairs to compare, drawn from the
        seed: every mode and workload at least once, as many on each chip."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF,
                                     (self.seed >> 32) & 0xFFFFFFFF, 0xC4EC])
        n = -(-self.traffic["sample"] // self.chips) * self.chips
        idx = sample_points(self.points, n, rng)
        return [(int(rng.integers(len(steps))), i) for i in idx]

    def reference(self, seeds_points: list[tuple[int, int]], lowp=False):
        """The plain reference over (seed, point index) pairs, on the host
        CPU; one dict of readings per pair."""
        pts = [(self.points[i]["mode"],
                self.traffic["workloads"][self.points[i]["workload"]], seed)
               for seed, i in seeds_points]
        return reference.simulate(self.config["noc"], pts, lowp=lowp)

    def check(self, steps: list[Step]) -> dict:
        picks = self.sample(steps)
        got = jax.device_get([steps[s].rows[i] for s, i in picks])
        for s in steps:  # free the window's rows before the reference runs
            s.rows.clear()
        want = self.reference([(steps[s].seed, i) for s, i in picks])
        return judge([compare(a, b) for a, b in zip(got, want)])


def judge(results: list[tuple[int, float]]) -> dict:
    """The check's verdict over per-point (int mismatches, float gap)."""
    bad = sum(b for b, _ in results)
    gap = max(g for _, g in results)
    return {
        "correct": bad <= INT_LIMIT and gap <= FLOAT_GAP_LIMIT,
        "failed": sum(b > INT_LIMIT or g > FLOAT_GAP_LIMIT
                      for b, g in results),
        "points": len(results),
        "compared": {"int_mismatches": (bad, INT_LIMIT),
                     "float_rel_gap": (gap, FLOAT_GAP_LIMIT)},
    }
