"""Tile-to-tile destinations (DESIGN.md §19) on the CPU at a small size.

A 4x4 package with 4 MCs (6 GPU and 6 CPU tiles), 40 epochs of 40 cycles,
seeded: the GPU tiles hold experts (`NoCConfig.peer_weights`) and a share
of GPU requests goes to them.  The two cycle engines agree bitwise with a
peer table; a zero share with a table reads as no table; the destination
draw keeps to its sources and targets; the counters add up; and what a
table may not be is refused.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noc import placement, sim, topology, traffic

ROOT = Path(__file__).resolve().parents[1]
GRID = dict(width=4, height=4, n_mc=4, n_epochs=40, epoch_len=40)
TOPO = topology.make_topology(4, 4, 4)
GPUS = np.flatnonzero(TOPO.node_type == topology.NT_GPU)
# two experts on the first two GPU tiles, one on the next three, none on
# the last: weighted peers, and a GPU tile that only sends
WEIGHTS = tuple(int(w) for w in np.isin(np.arange(16), GPUS[:5])
                + np.isin(np.arange(16), GPUS[:2]))
BURSTY = traffic.PeerProfile(0.1, 0.45, 0.003, 0.002, 0.12, 0.4)
MODES = ("4subnet", "baseline", "fair", "kf")


def leaves(res) -> dict:
    out = res._asdict()
    out.update(out.pop("counters")._asdict())
    return {k: np.asarray(v) for k, v in out.items()}


def run(backend, profile=BURSTY, peer_weights=WEIGHTS, **kw):
    specs = [sim.SweepSpec(mode=m, workload="peer-test", seed=11 + i)
             for i, m in enumerate(MODES)]
    traffic.register_workload("peer-test", profile, overwrite=True)
    rows = sim.sweep(specs, backend=backend, peer_weights=peer_weights,
                     **dict(GRID, **kw))
    return [leaves(r) for r in jax.device_get(rows)]


@pytest.fixture(scope="module")
def engines():
    return {b: run(b) for b in ("ref", "pallas")}


@pytest.mark.parametrize("i", range(len(MODES)), ids=MODES)
def test_ref_and_pallas_agree_with_a_peer_table(engines, i):
    ref, pal = engines["ref"][i], engines["pallas"][i]
    assert ref.keys() == pal.keys()
    for k in ref:
        assert np.array_equal(ref[k], pal[k]), k
    # the table was used: dispatches flowed and were served
    assert ref["gpu_peer_push"].sum() > 0 and ref["peer_served"].sum() > 0


@pytest.mark.parametrize("i", range(len(MODES)), ids=MODES)
def test_peer_counters_add_up(engines, i):
    r = engines["ref"][i]
    # a served dispatch was pushed first; about 0.4 of GPU pushes go to
    # peers (this tile only sends, so its peers take a little more)
    assert r["peer_served"].sum() <= r["gpu_peer_push"].sum()
    share = r["gpu_peer_push"].sum() / r["gpu_push"].sum()
    assert 0.3 < share < 0.5


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_a_zero_share_reads_as_no_table(backend):
    quiet = BURSTY._replace(gpu_peer_share=0.0)
    with_table = run(backend, quiet, n_epochs=12)
    without = run(backend, traffic.WorkloadProfile(*BURSTY[:5]),
                  peer_weights=None, n_epochs=12)
    for a, b in zip(with_table, without):
        assert a["gpu_peer_push"].sum() == a["peer_served"].sum() == 0
        assert set(a) - set(b) == {"gpu_peer_push", "peer_served"}
        for k in b:
            assert np.array_equal(a[k], b[k]), k


def test_generation_does_not_see_the_table(engines):
    """Tiles generate as without a table (MCs never do): the expert tiles'
    server role changes where requests go, not what is generated."""
    without = run("ref", peer_weights=None)
    for a, b in zip(engines["ref"], without):
        assert np.array_equal(a["gpu_gen"], b["gpu_gen"])
        assert np.array_equal(a["cpu_gen"], b["cpu_gen"])
        assert not np.array_equal(a["lat_sum"], b["lat_sum"])


@pytest.mark.parametrize("share", [0.0, 0.4, 1.0])
def test_the_draw_keeps_to_its_sources_and_targets(share):
    peers = sim.peer_table(WEIGHTS, TOPO)
    mcs = np.asarray(TOPO.mc_ids)
    L, R = 4000, 16
    base = jnp.asarray(np.resize(mcs, (L, R)), jnp.int32)
    is_gpu = jnp.asarray(TOPO.node_type == topology.NT_GPU)
    dests = np.asarray(sim.peer_dests(peers, jax.random.PRNGKey(5),
                                      jnp.float32(share), is_gpu, base))
    to_peer = dests != np.asarray(base)
    # CPU tiles and MCs never draw a peer; nobody draws itself
    assert not to_peer[:, TOPO.node_type != topology.NT_GPU].any()
    src = np.broadcast_to(np.arange(R), (L, R))
    assert not (dests[to_peer] == src[to_peer]).any()
    # a peer is a tile that holds experts
    assert np.isin(dests[to_peer], np.flatnonzero(WEIGHTS)).all()
    frac = to_peer[:, GPUS].mean()
    assert abs(frac - share) < 0.02
    if share == 1.0:
        # each source draws its peers in proportion to their experts
        w = np.asarray(WEIGHTS, float)
        for s in GPUS:
            own = w.copy()
            own[s] = 0
            seen = np.bincount(dests[:, s], minlength=R) / L
            assert np.abs(seen - own / own.sum()).max() < 0.03, s


def test_the_destination_stream_is_its_own():
    """The MC draw is the stream an MC-only run draws: a zero share leaves
    every destination as the MC draw gave it."""
    peers = sim.peer_table(WEIGHTS, TOPO)
    base = jnp.arange(64, dtype=jnp.int32).reshape(4, 16) % 4
    got = sim.peer_dests(peers, jax.random.PRNGKey(1), jnp.float32(0.0),
                         jnp.ones(16, bool), base)
    assert np.array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("weights,why", [
    (WEIGHTS[:-1], "one integer per router"),
    ((1,) + WEIGHTS[1:], "GPU tiles only"),        # router 0 is an MC
    (tuple(0 for _ in WEIGHTS), "sum in"),
    (tuple(200 if r in GPUS[:2] else 0 for r in range(16)), "sum in"),
    (tuple(-1 if r == GPUS[0] else w for r, w in enumerate(WEIGHTS)),
     "non-negative"),
])
def test_a_bad_peer_table_is_refused(weights, why):
    with pytest.raises(ValueError, match=why):
        sim.sim_args(sim.NoCConfig(peer_weights=weights, **GRID), BURSTY)


@pytest.mark.parametrize("plan", ["GPU_NEAR_MC", "GPU_NEAR_MC_ALWAYS",
                                  "SWAP_MID"])
def test_a_peer_table_with_relocation_raises(plan):
    cfg = sim.NoCConfig(peer_weights=WEIGHTS, placement=plan, **GRID)
    with pytest.raises(ValueError, match="identity placement"):
        sim.simulate(cfg, BURSTY)
    # the identity plan, named or not, is accepted
    ident = placement.static_placement(GRID["n_epochs"], TOPO)
    sim.sim_args(dataclasses.replace(cfg, placement=ident), BURSTY)
    sim.sim_args(dataclasses.replace(cfg, placement=None), BURSTY)


# ---- the share's profile type and the demand files


@pytest.mark.parametrize("name", ["fig9", "fig9x4", "shift-faults"])
def test_mc_only_traffic_files_load_as_before(name):
    """The five-rate files build the five-leaf `WorkloadProfile`s the
    program's own profiles are."""
    tr = json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())
    rates = tr.get("workloads") or {
        f"{wl}.{i}": seg["profile"] for wl, segs in tr["scenarios"].items()
        for i, seg in enumerate(segs)}
    for wl, r in rates.items():
        p = traffic.WorkloadProfile(**r)
        rows = traffic.resolve_source(p, 7)
        assert type(rows) is traffic.WorkloadProfile and len(rows) == 5
        assert not hasattr(rows, "gpu_peer_share")
        if name != "shift-faults":
            assert p == traffic.PROFILES[wl]


def test_the_dispatch_traffic_lowers_to_peer_rows():
    tr = json.loads((ROOT / "bench/traffic/prefill4k.json").read_text())
    for wl, r in tr["workloads"].items():
        rows = traffic.resolve_source(traffic.PeerProfile(**r), 7)
        assert type(rows) is traffic.PeerProfile
        assert rows.gpu_peer_share.tolist() == [np.float32(0.39)] * 7
        five = {k: v for k, v in r.items() if k != "gpu_peer_share"}
        plain = traffic.resolve_source(traffic.WorkloadProfile(**five), 7)
        for a, b in zip(rows, plain):
            assert np.array_equal(a, b), wl


def test_mixed_profiles_stack_with_a_zero_share():
    peer = traffic.resolve_source(BURSTY, 3)
    plain = traffic.resolve_source("BFS", 3)
    stacked = traffic.stack_profiles([plain, peer])
    assert type(stacked) is traffic.PeerProfile
    assert stacked.gpu_peer_share.tolist() == [[0.0] * 3,
                                               [np.float32(0.4)] * 3]
    assert type(traffic.stack_profiles([plain, plain])) is \
        traffic.WorkloadProfile


def test_a_sweep_mixing_profiles_is_one_program():
    """A plain workload in a sweep with dispatching ones runs as a zero
    share on the same compiled program."""
    traffic.register_workload("peer-test", BURSTY, overwrite=True)
    specs = [sim.SweepSpec(mode="kf", workload=w, seed=3)
             for w in ("peer-test", "BFS", "peer-test")]
    kw = dict(GRID, n_epochs=6, backend="ref", peer_weights=WEIGHTS)
    sim.reset_trace_count()
    rows = jax.device_get(sim.sweep(specs, **kw))
    assert sim.trace_count() == 1
    assert rows[1].counters.gpu_peer_push.sum() == 0
    assert rows[0].counters.gpu_peer_push.sum() > 0


def test_a_share_above_one_is_refused():
    with pytest.raises(ValueError, match="above 1"):
        traffic.resolve_source(BURSTY._replace(gpu_peer_share=1.5), 4)
