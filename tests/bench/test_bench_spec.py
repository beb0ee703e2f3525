"""BENCHMARK.json and the files it names: configurations, traffic, readers."""
from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("group,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
])
def test_entries_have_exactly_their_keys(group, keys):
    names = [e["name"] for e in SPEC[group]]
    assert len(names) == len(set(names))
    for e in SPEC[group]:
        assert set(e) == keys, e["name"]
        assert NAME.match(e["name"]) and 1 <= len(e["why"]) <= 200


def test_metrics_are_well_formed():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        # every metric is read by a file of its own
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_cells_name_existing_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        assert w["config"] in configs
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "entries" / f"{traffic['entry']}.py").is_file()
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_builds_a_valid_noc(entry):
    import numpy as np

    from repro.core.noc import sim, topology

    from bench.entries import sweep
    from bench.reference import noc as ref

    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    noc = cfg["noc"]
    topology.validate_topology_args(noc["width"], noc["height"], noc["n_mc"])
    prog = sim.NoCConfig(**sweep.program_fields(cfg))
    assert prog.backend == cfg["engine"]
    for k, v in noc.items():
        assert getattr(prog, k) == (tuple(v) if isinstance(v, list) else v)
    # the reference builds the package the program simulates
    topo = topology.make_topology(noc["width"], noc["height"], noc["n_mc"])
    assert (topo.node_type == 2).sum() == noc["n_mc"]
    route, neighbour, kind, mcs = ref.package(noc["width"], noc["height"],
                                              noc["n_mc"])
    assert np.array_equal(route, topo.route)
    assert np.array_equal(neighbour, topo.neighbor)
    assert np.array_equal(kind, topo.node_type)
    assert np.array_equal(mcs, topo.mc_ids)


def test_fig9_expands_to_the_paper_grid():
    from repro.core.noc import sim, traffic as prog_traffic

    from bench.entries import sweep

    fig9 = json.loads((ROOT / "bench/traffic/fig9.json").read_text())
    points = sweep.grid(fig9)
    assert len(points) == 24
    specs = [sim.SweepSpec(seed=1, **p) for p in points]
    assert [(s.workload, s.mode) for s in specs[:5]] == [
        ("PATH", "4subnet"), ("PATH", "baseline"), ("PATH", "fair"),
        ("PATH", "kf"), ("LIB", "4subnet")]
    assert all(s.faults is None and not s.guard and s.placement is None
               for s in specs)
    # the file pins the rates the program's profiles hold today
    for wl, params in fig9["workloads"].items():
        assert prog_traffic.PROFILES[wl] == prog_traffic.WorkloadProfile(
            **params)


def test_step_seeds_fit_int32_and_differ():
    from bench.entries import sweep

    seeds = [sweep.step_seed(s, k) for s in (0, 2**31 + 7, 2**40)
             for k in range(4)]
    assert all(0 <= s < 2**31 for s in seeds)
    assert len(set(seeds)) == len(seeds)
    assert sweep.step_seed(2**31 + 7, 2) == sweep.step_seed(2**31 + 7, 2)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11, 12345])
def test_sample_covers_every_mode_and_workload(seed):
    import numpy as np

    from bench.entries import sweep

    fig9 = json.loads((ROOT / "bench/traffic/fig9.json").read_text())
    points = sweep.grid(fig9)
    picked = sweep.sample_points(points, 6, np.random.default_rng(seed))
    assert len(set(picked)) == 6
    assert {points[i]["workload"] for i in picked} == set(fig9["workloads"])
    assert {points[i]["mode"] for i in picked} == set(fig9["modes"])
