"""The relocation deployment's cell on the CPU: its plain reference against
the program, the traffic against the program's schedules, the harness end
to end, and what the cell's check catches.

The run here is the `noc6x6-relocate` configuration at 40 epochs of 40
cycles on the dense engine, with the hysteresis (paper §3.2) and the KF's
observation scales cut by the same 40/500, so that the KF acts, boosts and
relocates within the run and every fault and scenario window covers an
epoch.  The schedules themselves are also checked at the cell's 120 epochs.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

from repro.core.noc import faults as prog_faults
from repro.core.noc import placement as prog_placement
from repro.core.noc import sim, topology
from repro.core.noc import traffic as prog_traffic

from bench import run, trace_reduce
from bench.entries import relocate as entry
from bench.entries import sweep as sweep_entry
from bench.reference import noc, noc_relocate

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.shift-faults"
CONFIG = json.loads((ROOT / "bench/configs/noc6x6-relocate.json").read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/shift-faults.json").read_text())
SEED = 2**31 + 29


def tiny_config(n_epochs=40, epoch_len=40) -> dict:
    cut = epoch_len / CONFIG["noc"]["epoch_len"]
    cfg = json.loads(json.dumps(CONFIG))
    cfg["name"] = "tiny"
    cfg["engine"] = "ref"
    cfg["noc"].update(n_epochs=n_epochs, epoch_len=epoch_len, z_scales=[
        s * cut for s in CONFIG["noc"]["z_scales"]])
    cfg["hysteresis"] = {k: int(v * cut)
                         for k, v in CONFIG["hysteresis"].items()}
    return cfg


@pytest.fixture(scope="module")
def runner():
    return entry.Runner(tiny_config(), TRAFFIC, chips=1, seed=SEED)


@pytest.fixture(scope="module")
def rows(runner):
    """The program's and the reference's answers for all 24 points."""
    step = runner.step(1)
    got = jax.device_get(step.rows)
    want = runner.reference([(step.seed, i) for i in range(len(got))])
    return got, want


def point_id(p):
    return f"{p['workload']}-{p['control']}-{p['faults']}"


POINTS = entry.grid(TRAFFIC)


def test_grid_is_every_scenario_control_and_fault_case():
    assert len(POINTS) == 24 and sim.SWEEP_TILE * 4 == 24
    assert {point_id(p) for p in POINTS} == {
        f"{w}-{c}-{f}" for w in ("SHIFT_PATH_BFS", "MIX_PATH_STO_BFS")
        for c in ("bandwidth", "placement", "joint")
        for f in ("healthy", "FLAP_DURING_SHIFT", "BROWNOUT", "TELEM_GLITCH")}
    assert sum(bool(TRAFFIC["faults"][p["faults"]]) for p in POINTS) == 18
    assert sum(p["control"] != "bandwidth" for p in POINTS) == 16


@pytest.mark.parametrize("i", range(len(POINTS)),
                         ids=[point_id(p) for p in POINTS])
def test_reference_matches_the_program(rows, i):
    got, want = rows
    bad, gap = sweep_entry.compare(got[i], want[i])
    assert bad == 0 and gap <= sweep_entry.FLOAT_GAP_LIMIT, POINTS[i]
    assert want[i]["moved"].min() > 0 and want[i]["gpu_done"].sum() > 0


def test_the_traffic_exercises_the_mechanisms(rows):
    """Over the 24 points: relocation only under a lever that relocates,
    guard rejections under NaN telemetry, links down under the flaps."""
    _, want = rows
    seen = {}
    for p, w in zip(POINTS, want):
        for k in ("relocated", "kf_rejected"):
            seen.setdefault((k, p["control"]), 0)
            seen[(k, p["control"])] += int(w[k].sum())
            seen.setdefault((k, p["faults"]), 0)
            seen[(k, p["faults"])] += int(w[k].sum())
        seen.setdefault(("links", p["faults"]), 0)
        seen[("links", p["faults"])] += int((w["links_down"] > 0).sum())
    assert seen[("relocated", "placement")] > 0
    assert seen[("relocated", "joint")] > 0
    assert seen[("relocated", "bandwidth")] == 0
    assert seen[("kf_rejected", "TELEM_GLITCH")] > 0
    assert seen[("kf_rejected", "FLAP_DURING_SHIFT")] > 0
    assert seen[("links", "FLAP_DURING_SHIFT")] > 0
    assert seen[("links", "healthy")] == seen[("links", "BROWNOUT")] == 0
    # the levers act: the VC quota moves only where bandwidth is pulled
    quota = {c: max(int(w["gpu_vc_quota"].max()) for p, w in
                    zip(POINTS, want) if p["control"] == c)
             for c in ("bandwidth", "placement", "joint")}
    assert quota == {"bandwidth": 3, "placement": 2, "joint": 3}


@pytest.mark.parametrize("n_epochs", [40, 120])
def test_the_reference_builds_the_program_schedules(n_epochs):
    """The traffic file holds the program's scenarios and fault cases at
    the `fig9` rates, and the reference lowers them, and the placement, to
    the program's per-epoch rows (at the test's and the cell's length)."""
    topo = topology.make_topology(6, 6, 8)
    for wl, segments in TRAFFIC["scenarios"].items():
        want = prog_traffic.SCENARIOS[wl].materialize(n_epochs)
        got = entry.schedule(segments).materialize(n_epochs)
        assert all(np.array_equal(a, b) for a, b in zip(got, want)), wl
        rows = noc_relocate.demand_rows(segments, n_epochs)
        assert np.array_equal(rows, np.stack(want, axis=1))
    for case, events in TRAFFIC["faults"].items():
        want = prog_faults.resolve_faults(
            prog_faults.FAULTS.get(case), n_epochs, neighbor=topo.neighbor,
            opposite=topo.opposite)
        got = noc_relocate.fault_rows(events, n_epochs, topo.neighbor)
        assert want.mc_ok.all()     # no case stalls an MC
        for a, b in zip(got, (want.link_ok, want.router_ok, want.telem_mode,
                              want.telem_mag)):
            assert np.array_equal(a, b), case
        # every window of the case covers an epoch
        for ev in events:
            assert len(noc_relocate.event_epochs(ev, n_epochs)) > 0
        link_ok, router_ok, telem, _ = got
        down = (~link_ok).any(axis=(1, 2)).sum()
        assert (down > 0) == (case == "FLAP_DURING_SHIFT"), case
        assert (~router_ok).any() == (case == "BROWNOUT"), case
        nan = (telem == noc_relocate.TELEM_NAN).sum()
        assert (nan > 0) == (case in ("FLAP_DURING_SHIFT", "TELEM_GLITCH"))
    _, _, kind, mcs = noc.package(6, 6, 8)
    base, boost = noc_relocate.placement_rows(CONFIG["placement"], n_epochs,
                                              6, kind, mcs)
    want = prog_placement.PLACEMENTS["GPU_NEAR_MC"].materialize(n_epochs,
                                                                topo)
    assert np.array_equal(base, want.cls0)
    assert np.array_equal(boost, want.cls1)
    # the plan moves GPU compute and keeps class counts and the MCs
    assert (boost != base).any()
    for c in (noc.CPU, noc.GPU, noc.MC):
        assert (boost[0] == c).sum() == (kind == c).sum()
    assert np.array_equal(boost[0] == noc.MC, kind == noc.MC)


def test_demand_rows_ramp_as_the_program_does():
    """A ramped segment (the program's `RAMP_LIB`), lowered by both."""
    seg = prog_traffic.SCENARIOS["RAMP_LIB"].segments[0]
    segments = [{"start": seg.start, "profile": seg.profile._asdict(),
                 "ramp_to": seg.ramp_to._asdict(), "pin_phase": None}]
    for n_epochs in (40, 120):
        want = prog_traffic.SCENARIOS["RAMP_LIB"].materialize(n_epochs)
        assert np.array_equal(noc_relocate.demand_rows(segments, n_epochs),
                              np.stack(want, axis=1))


def test_the_bfloat16_control_differs(runner):
    pairs = [(7, i) for i in (3, 9, 17)]
    verdict = sweep_entry.judge([sweep_entry.compare(a, b) for a, b in zip(
        runner.reference(pairs, lowp=True), runner.reference(pairs))])
    assert not verdict["correct"]
    assert verdict["compared"]["float_rel_gap"][0] > \
        sweep_entry.FLOAT_GAP_LIMIT


# ---- the harness end to end, and what the check catches


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A copy of the benchmark with one more cell: the tiny configuration
    under the cell's traffic."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench/configs/tiny.json").write_text(json.dumps(tiny_config()))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "shift-faults", "chips": 1,
                              "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root):
    return run.run_cell(root, CELL, SEED, 0.2, False, require_tpu=False)


def test_the_cell_is_correct_at_a_tiny_size(root):
    out = run_tiny(root)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] % 24 == 0 and out["attempted"] >= 24
    assert set(out["metrics"]) == {"router_cycles_per_s", "setup_s"}
    assert out["compared"]["int_mismatches"] == {
        "value": 0, "limit": sweep_entry.INT_LIMIT}


def _without_links(spec):
    # the program ignores the link mask: the flaps' events are dropped
    if spec.faults is None:
        return spec
    events = tuple(e for e in prog_faults.FAULTS[spec.faults].events
                   if e.kind != "link")
    prog_faults.register_faults(spec.faults + ".nolink",
                                prog_faults.FaultSchedule(events),
                                overwrite=True)
    return spec._replace(faults=spec.faults + ".nolink")


def _guard_disarmed(spec):
    return spec._replace(guard=False)


def _placement_identity(spec):
    return spec._replace(placement=None)


@pytest.mark.parametrize("fault", [_without_links, _guard_disarmed,
                                   _placement_identity],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_deployment_is_not_correct(root, monkeypatch, fault):
    real = sim.sweep

    def broken(specs, **kw):
        return real([fault(s) for s in specs], **kw)

    monkeypatch.setattr(sim, "sweep", broken)
    out = run_tiny(root)
    assert out["correct"] is False
    assert out["compared"]["int_mismatches"]["value"] > 0
    assert out["failed"] > 0


# ---- the new per-layer readers


def _op(name, label):
    return (f"%{name} = s32[6] fusion(s32[6] %p), "
            f'frontend_attributes={{noc_layer="{label}"}}')


def _profile(device_ops, host):
    ev = lambda n, s, e: NS(name=n, start_ns=s, end_ns=e)
    planes = [NS(name=f"/device:TPU:{d}", lines=[
        NS(name="XLA Ops", events=[ev(*o) for o in ops])])
        for d, ops in device_ops.items()]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*h) for h in host])]))
    return NS(planes=planes)


READERS = ("schedules_idle_ms", "epoch_placement_ns", "epoch_guard_ns")


def read_new(ctx):
    return {m: run.load_module(ROOT / "bench/metrics" / f"{m}.py",
                               f"test_relocate_{m}").read(ctx)
            for m in READERS}


def test_the_new_readers_on_a_synthetic_trace():
    """The schedules span nested in the arguments span, and the placement
    and guard labels beside the boundary's, on two devices."""
    ops = {0: [(_op("f.1", "epoch.boundary"), 1400, 1500),
               (_op("f.2", "epoch.placement"), 1500, 1530),
               (_op("f.3", "epoch.guard"), 1600, 1610),
               (_op("f.4", "cycle.scan"), 1700, 1900),
               (_op("f.5", "epoch.guard"), 1800, 1850)],
           1: [(_op("f.6", "epoch.placement"), 1450, 1470)]}
    host = [("bench.window", 1000, 3000), ("noc.sweep", 1100, 2000),
            ("noc.args", 1100, 1450), ("noc.schedules", 1200, 1420)]
    red = trace_reduce.reduce_profile(_profile(ops, host), "bench.window",
                                      n_devices=2)
    ctx = run.Context(trace=red, steps=[NS(point_cycles=10)],
                      monitor=run.Monitor(), setup_span=(0.0, 1.0))
    got = read_new(ctx)
    # device 0 is busy from 1400 in the schedules span (idle 200 of 220),
    # device 1 idle throughout it
    assert got["schedules_idle_ms"] == pytest.approx((200 + 220) / 2 * 1e-6)
    # labels outside the cycle loop, summed over devices, per point-cycle
    assert got["epoch_placement_ns"] == pytest.approx((30 + 20) / 10)
    assert got["epoch_guard_ns"] == pytest.approx(10 / 10)


def test_the_new_readers_read_nothing_without_spans_or_labels():
    """A trace of a program without them (a v5e trace of the parent's
    labels and spans) gives no value and raises nothing."""
    import gzip

    pd = jax.profiler.ProfileData.from_serialized_xspace(gzip.decompress(
        (ROOT / "bench/testdata/labelled_v5e.xplane.pb.gz").read_bytes()))
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=1)
    ctx = run.Context(trace=red, steps=[NS(point_cycles=24 * 8)],
                      monitor=run.Monitor(), setup_span=(0.0, 1.0))
    assert all(v is None for v in read_new(ctx).values())
    ctx.trace = None
    assert all(v is None for v in read_new(ctx).values())


def test_fig9x4_is_the_fig9_grid_across_four_chips():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}["noc6x6.fig9x4"]
    assert (cell["config"], cell["chips"]) == ("noc6x6", 4)
    fig9 = json.loads((ROOT / "bench/traffic/fig9.json").read_text())
    fig9x4 = json.loads((ROOT / "bench/traffic/fig9x4.json").read_text())
    assert fig9x4["entry"] == "sweep" and fig9x4["sample"] == 8
    assert sweep_entry.grid(fig9x4) == sweep_entry.grid(fig9)
    assert fig9x4["workloads"] == fig9["workloads"]
    r = sweep_entry.Runner(json.loads(
        (ROOT / "bench/configs/noc6x6.json").read_text()), fig9x4, chips=4,
        seed=SEED)
    picks = r.sample([None])
    assert len(picks) == 8 and len({i for _, i in picks}) == 8
