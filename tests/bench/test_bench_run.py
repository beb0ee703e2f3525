"""The benchmark harness end to end on the CPU at a tiny size.

The cell here is the paper grid on a 6x6 package cut to 3 epochs of 16
cycles, on the dense engine (the fused kernel runs in interpret mode off a
TPU, which is slow to trace).  Every run skips the harness's look for a chip
and drives the rest: set-up, window, metrics and the check against the plain
reference.  The faults and the control break the timed path underneath and
must turn `correct` false.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noc import sim

from bench import run
from bench.entries import sweep as entry

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.fig9"
TINY = {"n_epochs": 3, "epoch_len": 16}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def make_root(tmp: Path) -> Path:
    """A copy of the benchmark with one more cell: a tiny configuration."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/noc6x6.json").read_text())
    cfg["name"] = "tiny"
    cfg["noc"].update(TINY)
    cfg["engine"] = "ref"
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "fig9", "chips": 1, "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def run_tiny(root, seed=2**31 + 17, trace=False, cell=CELL):
    return run.run_cell(root, cell, seed, 0.2, trace, require_tpu=False)


def test_result_line_keys_and_metrics(root):
    out = run_tiny(root)
    assert set(out) == KEYS
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] % 24 == 0 and out["attempted"] >= 24
    assert set(out["metrics"]) == {"router_cycles_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert list(out["compared"]) == ["int_mismatches", "float_rel_gap"]
    ints, floats = out["compared"].values()
    assert ints == {"value": 0, "limit": entry.INT_LIMIT}
    assert 0 <= floats["value"] <= floats["limit"] == entry.FLOAT_GAP_LIMIT
    json.dumps(out)  # the line is JSON


def test_same_seed_same_work(root):
    a = run.Bench(root)
    cfg = a.config("tiny")
    traffic = a.traffic("fig9")
    r1 = entry.Runner(cfg, traffic, chips=1, seed=5)
    r2 = entry.Runner(cfg, traffic, chips=1, seed=5)
    s1, s2 = r1.step(3), r2.step(3)
    assert s1.seed == s2.seed and s1.router_cycles == 24 * 36 * 48
    for a_row, b_row in zip(s1.rows, s2.rows):
        assert entry.compare(a_row, b_row) == (0, 0.0)
    assert r1.sample([s1, s1]) == r2.sample([s2, s2])


def test_new_config_traffic_and_metric_are_data(root, tmp_path):
    """A later cell brings only files and BENCHMARK.json entries."""
    new = make_root(tmp_path)
    cfg = json.loads((new / "bench/configs/tiny.json").read_text())
    cfg["noc"].update(width=4, height=4, n_mc=4)
    (new / "bench/configs/tiny4.json").write_text(json.dumps(cfg))
    traffic = json.loads((new / "bench/traffic/fig9.json").read_text())
    traffic["modes"] = ["kf"]
    traffic["workloads"] = {k: traffic["workloads"][k] for k in ("BFS", "MUM")}
    # a workload the program has never heard of: the file sets its rates
    traffic["workloads"]["BURSTY"] = {"gpu_rate_lo": 0.01, "gpu_rate_hi": 0.6,
                                      "p_enter": 0.05, "p_exit": 0.05,
                                      "cpu_rate": 0.2}
    traffic["sample"] = 3
    (new / "bench/traffic/kf2.json").write_text(json.dumps(traffic))
    (new / "bench/metrics/steps_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps))\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny4", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny4.json", "why": "t"})
    spec["workloads"].append({"name": "tiny4.kf2", "config": "tiny4",
                              "traffic": "kf2", "chips": 1, "why": "t"})
    spec["end_to_end"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny4.kf2"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run_tiny(new, cell="tiny4.kf2")
    assert out["correct"] is True
    assert out["attempted"] % 3 == 0
    assert out["metrics"]["steps_in_window"]["value"] >= 1
    assert out["metrics"]["router_cycles_per_s"]["value"] > 0


def _alter_answers(rows):
    # the engine miscounts one event where it produces the epoch counters
    def alter(r):
        c = r.counters
        return r._replace(
            counters=c._replace(gpu_done=c.gpu_done.at[-1].add(1)))
    return [alter(r) for r in rows]


def _drop_half(rows):
    # half of the batch never simulated: its rows repeat the other half's
    half = len(rows) // 2
    return rows[:half] + rows[:len(rows) - half]


def _state_unchanged(rows):
    # a step that returns its state unchanged: nothing ever moves
    return [jax.tree.map(jnp.zeros_like, r) for r in rows]


def _no_exchange(rows):
    # only the first shard's rows come back; the other shards repeat them
    tile = sim.SWEEP_TILE
    return [rows[i % tile] for i in range(len(rows))]


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half,
                                   _state_unchanged, _no_exchange],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    real = sim.sweep

    def broken(specs, **kw):
        return fault(list(real(specs, **kw)))

    monkeypatch.setattr(sim, "sweep", broken)
    out = run_tiny(root)
    assert out["correct"] is False
    assert out["compared"]["int_mismatches"]["value"] > 0
    assert out["failed"] > 0


def test_the_control_is_not_correct(root, monkeypatch):
    """The reference computed in bfloat16, in the program's place."""
    bench = run.Bench(root)
    cfg, traffic = bench.config("tiny"), bench.traffic("fig9")

    def control(specs, **kw):
        runner = entry.Runner(cfg, traffic, chips=1, seed=0)
        index = {(runner.names[p["workload"]], p["mode"]): i
                 for i, p in enumerate(runner.points)}
        return runner.reference(
            [(s.seed, index[(s.workload, s.mode)]) for s in specs], lowp=True)

    monkeypatch.setattr(sim, "sweep", control)
    out = run_tiny(root)
    assert out["correct"] is False
    floats = out["compared"]["float_rel_gap"]
    assert floats["value"] > floats["limit"]


def test_mismatches_counts_elements_dtype_and_shape():
    from bench.reference import noc

    want = {k: np.arange(4, dtype=np.int32) for k in noc.INTS}
    want.update({k: np.float32([0.5, 2.0, 0.0, 1.0]) for k in noc.FLOATS})
    assert entry.compare(want, want) == (0, 0.0)
    got = dict(want, moved=np.int32([0, 1, 2, 4]))
    assert entry.compare(got, want) == (1, 0.0)
    got = dict(want, lat_sum=np.arange(4, dtype=np.int64))
    assert entry.compare(got, want)[0] == 4
    got = {k: v for k, v in want.items() if k != "kf_signal"}
    assert entry.compare(got, want)[0] == 4
    got = dict(want, cpu_ipc=np.float32([0.5, 2.0 * (1 + 2e-5), 0.0, 1.0]))
    assert 1.9e-5 < entry.compare(got, want)[1] < 2.1e-5
    got = dict(want, avg_latency=np.float32([0.5, 2.0, 1e-6, 1.0]))
    assert entry.compare(got, want)[1] > 1e20
    for bad in (np.float32([0.5, np.nan, 0.0, 1.0]), np.float64([0.5, 2, 0, 1]),
                np.float32([0.5, 2.0])):
        assert entry.compare(dict(want, gpu_ipc=bad), want)[1] == entry.NOT_A_GAP
    # a program row reads by the same names
    r = sim.SimResult(*(np.zeros(3, np.float32) for _ in range(5)),
                      counters=sim.EpochCounters(*(np.zeros(3, np.int32)
                                                   for _ in noc.COUNTERS)),
                      gpu_inj_rate=np.zeros(3, np.float32),
                      gpu_vc_quota=np.zeros(3, np.int32))
    assert set(entry.readings(r)) == set(noc.INTS + noc.FLOATS)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "noc6x6.fig9", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero_without_a_result(tmp_path):
    """A checkout of only BENCHMARK.json and its paths has no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "noc6x6.fig9",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
