"""The one-point-a-step cell (`noc6x6.single`) on the CPU: its steps, the
sample its check draws, the harness end to end, and what the check
catches.  The configuration is `noc6x6` cut to 3 epochs of 16 cycles, on
the dense engine."""
from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from repro.core.noc import sim
from repro.core.noc import traffic as prog_traffic

from bench import run
from bench.entries import single as entry
from bench.entries import sweep as sweep_entry

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.single"
CONFIG = json.loads((ROOT / "bench/configs/noc6x6.json").read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/single.json").read_text())
SEED = 2**33 + 5


def tiny_config() -> dict:
    cfg = json.loads(json.dumps(CONFIG))
    cfg["name"] = "tiny"
    cfg["engine"] = "ref"
    cfg["noc"].update(n_epochs=3, epoch_len=16)
    return cfg


def test_the_traffic_is_fig9s_workloads_in_kf():
    fig9 = json.loads((ROOT / "bench/traffic/fig9.json").read_text())
    assert TRAFFIC["entry"] == "single" and TRAFFIC["mode"] == "kf"
    assert TRAFFIC["workloads"] == fig9["workloads"]
    assert TRAFFIC["sample"] == len(fig9["workloads"])


def test_a_step_is_one_point_of_workload_k_mod_6():
    r = entry.Runner(tiny_config(), TRAFFIC, chips=1, seed=SEED)
    steps = [r.step(k) for k in (0, 7)]
    for s in steps:
        assert s.points == 1 and len(s.rows) == 1
        assert s.router_cycles == 36 * 48 and s.point_cycles == 48
        assert s.seed == sweep_entry.step_seed(SEED, s.k)
    assert [r.workload(s.k) for s in steps] == [0, 1]
    # the step ran the workload's rates under the kf mode and its seed
    want = sim.simulate(sim.NoCConfig(
        mode="kf", seed=steps[1].seed, **sweep_entry.program_fields(
            tiny_config())), prog_traffic.WorkloadProfile(
                **TRAFFIC["workloads"]["LIB"]))
    for a, b in zip(jax_leaves(steps[1].rows[0]), jax_leaves(want)):
        assert np.array_equal(a, b)


def jax_leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("n", [1, 5, 40])
def test_the_sample_takes_one_step_of_each_workload(n):
    r = entry.Runner(tiny_config(), TRAFFIC, chips=1, seed=SEED)
    steps = [sweep_entry.Step(k, k, [], 1, 0, 0) for k in range(1, n + 1)]
    picks = r.sample(steps)
    assert len(picks) == min(n, 6)
    assert len({w for _, w in picks}) == len(picks)
    for s, w in picks:
        assert r.workload(steps[s].k) == w
    assert picks == r.sample(steps)        # drawn from the seed


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(tiny_config()))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "single", "chips": 1, "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root):
    return run.run_cell(root, CELL, SEED, 0.5, False, require_tpu=False)


def test_the_cell_is_correct_at_a_tiny_size(root):
    out = run_tiny(root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 6
    assert set(out["metrics"]) == {"router_cycles_per_s", "setup_s"}
    assert out["compared"]["int_mismatches"] == {
        "value": 0, "limit": sweep_entry.INT_LIMIT}


def _wrong_mode(real):
    # the class-segregated network in place of the KF's
    def simulate(cfg, source, **kw):
        return real(dataclasses.replace(cfg, mode="4subnet"), source, **kw)
    return simulate


def _frozen_seed(real):
    # every step on one seed
    def simulate(cfg, source, **kw):
        return real(dataclasses.replace(cfg, seed=1), source, **kw)
    return simulate


@pytest.mark.parametrize("fault", [_wrong_mode, _frozen_seed],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_step_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(sim, "simulate", fault(sim.simulate))
    out = run_tiny(root)
    assert out["correct"] is False
    assert out["compared"]["int_mismatches"]["value"] > 0
