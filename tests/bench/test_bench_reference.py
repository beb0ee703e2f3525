"""The plain reference against the program's dense engine on the CPU."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.noc import sim, topology

from bench.entries import sweep as entry
from bench.reference import noc

ROOT = Path(__file__).resolve().parents[2]
CFG = dict(json.loads((ROOT / "bench/configs/noc6x6.json").read_text())["noc"],
           n_epochs=4, epoch_len=40)
WORKLOADS = json.loads((ROOT / "bench/traffic/fig9.json").read_text())[
    "workloads"]
MODES = ("4subnet", "baseline", "fair", "kf")
POINTS = [(m, w, 2**31 - 9 - i) for i, (m, w) in enumerate(
    (m, w) for m in MODES for w in ("BFS", "STO"))]


@pytest.fixture(scope="module")
def program_rows():
    names = entry.register_workloads({"workloads": WORKLOADS})
    fields = entry.program_fields({"noc": CFG, "engine": "ref"})
    specs = [sim.SweepSpec(mode=m, workload=names[w], seed=s)
             for m, w, s in POINTS]
    return jax.device_get(sim.sweep(specs, **fields))


@pytest.mark.parametrize("mode", MODES)
def test_reference_matches_the_program(program_rows, mode):
    idx = [i for i, p in enumerate(POINTS) if p[0] == mode]
    want = noc.simulate(CFG, [(m, WORKLOADS[w], s) for m, w, s in
                              (POINTS[i] for i in idx)])
    for i, row in zip(idx, want):
        bad, gap = entry.compare(program_rows[i], row)
        assert bad == 0 and gap <= entry.FLOAT_GAP_LIMIT, POINTS[i]
        assert row["moved"].min() > 0 and row["gpu_done"].sum() > 0


def test_the_bfloat16_control_differs():
    pts = [(m, WORKLOADS["MUM"], 5) for m in MODES]
    verdict = entry.judge([entry.compare(a, b) for a, b in zip(
        noc.simulate(CFG, pts, lowp=True), noc.simulate(CFG, pts))])
    assert not verdict["correct"]
    assert verdict["compared"]["float_rel_gap"][0] > entry.FLOAT_GAP_LIMIT


@pytest.mark.parametrize("grid", [(6, 6, 8), (4, 4, 4), (8, 8, 16), (5, 3, 3)],
                         ids=lambda g: "x".join(map(str, g)))
def test_package_matches_the_program_topology(grid):
    topo = topology.make_topology(*grid)
    route, neighbour, kind, mcs = noc.package(*grid)
    assert np.array_equal(route, topo.route)
    assert np.array_equal(neighbour, topo.neighbor)
    assert np.array_equal(kind, topo.node_type)
    assert np.array_equal(mcs, topo.mc_ids)
