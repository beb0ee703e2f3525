"""Traced-placement + arbitrary-topology suite (DESIGN.md §17).

Pins the placement refactor from four sides:

  1. topology generalization — `validate_topology_args` rejects grids
     that cannot host the MC rows or the CPU/GPU tiling (the old code
     silently backfilled colliding MC columns), and non-paper grids
     build exact layouts;
  2. placement model — `PlacementSchedule` validation, plan builders
     (class counts preserved, MC tiles never reassigned), registry
     lookup/registration errors, `resolve_placement` shape checks;
  3. zero-cost identity path — the refactor guard: placement=None runs
     replay the committed PR-4 goldens bitwise on ALL three backends,
     an explicit identity stream is bitwise placement-free, a
     bandwidth-control row CARRYING a relocation stream is bitwise a
     row with no stream at all (a disarmed lever is free), and the
     control x placement grid compiles exactly ONE simulate trace;
  4. relocation semantics — a scheduled SWAP_MID migration moves every
     non-MC tile at the midpoint epoch (visible in `SimTrace.place_cls`
     and `place_moves_total`), and an active-relocation run is bitwise
     congruent across ref / pallas / pallas_arb, on 6x6 and on a
     non-paper 4x4 grid.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noc import sim
from repro.core.noc.placement import (
    PLACEMENTS,
    PlacementEvent,
    PlacementSchedule,
    PlacementStream,
    lookup_placement,
    register_placement,
    resolve_placement,
    static_placement,
)
from repro.core.noc.sim import NoCConfig, SweepSpec
from repro.core.noc.topology import (
    MAX_ROUTERS,
    NT_CPU,
    NT_GPU,
    NT_MC,
    make_topology,
    validate_topology_args,
)

TINY = dict(n_epochs=8, epoch_len=80)
FAST = dict(n_epochs=8, epoch_len=100)  # the golden capture's dims
BACKENDS = ("ref", "pallas", "pallas_arb")
GOLDEN_PATH = os.path.join(
    os.path.dirname(__file__), "golden_cycle_engine.json"
)


def _bitwise_equal(a, b, label):
    for (path, x), (_, y) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{label}: leaf {jax.tree_util.keystr(path)}",
        )


# ---------------------------------------------------------------------------
# 1. topology generalization: validation + non-paper grids
# ---------------------------------------------------------------------------

class TestTopologyValidation:
    def test_rejects_non_int_dims(self):
        with pytest.raises(ValueError, match="width must be an int"):
            validate_topology_args(6.0, 6, 8)
        with pytest.raises(ValueError, match="height must be an int"):
            validate_topology_args(6, True, 8)
        with pytest.raises(ValueError, match="n_mc must be an int"):
            validate_topology_args(6, 6, "8")

    def test_rejects_degenerate_mesh(self):
        with pytest.raises(ValueError, match="width >= 2 and height >= 2"):
            validate_topology_args(1, 6, 2)
        with pytest.raises(ValueError, match="n_mc must be >= 1"):
            validate_topology_args(6, 6, 0)

    def test_rejects_mc_row_overflow(self):
        # 9 MCs on a width-4 mesh: bottom row needs ceil(9/2)=5 > 4 slots
        with pytest.raises(ValueError, match="does not fit on the top"):
            validate_topology_args(4, 4, 9)

    def test_rejects_all_mc_mesh(self):
        with pytest.raises(ValueError, match="non-MC tile"):
            validate_topology_args(2, 2, 4)

    def test_rejects_over_64_routers(self):
        with pytest.raises(ValueError, match="packed\n?.*lane layout caps"):
            validate_topology_args(9, 8, 8)
        assert 8 * 8 == MAX_ROUTERS
        validate_topology_args(8, 8, 8)  # exactly at the cap is fine

    def test_make_topology_rejects_via_validate(self):
        with pytest.raises(ValueError, match="does not fit"):
            make_topology(2, 8, 5)

    def test_default_grid_unchanged(self):
        """The paper layout is pinned: any drift breaks every golden."""
        topo = make_topology()
        assert topo.mc_ids.tolist() == [0, 2, 3, 5, 30, 32, 33, 35]
        nt = topo.node_type
        assert int((nt == NT_GPU).sum()) == 14
        assert int((nt == NT_CPU).sum()) == 14
        assert int((nt == NT_MC).sum()) == 8

    def test_non_paper_grid_builds_exactly(self):
        topo = make_topology(4, 5, 6)
        nt = topo.node_type
        assert topo.n_routers == 20
        assert int((nt == NT_MC).sum()) == 6
        # MCs only on top and bottom rows, all distinct
        rows = set(int(r) // 4 for r in topo.mc_ids)
        assert rows <= {0, 4}
        assert len(set(topo.mc_ids.tolist())) == 6
        # remaining tiles alternate GPU/CPU
        assert int((nt == NT_GPU).sum()) == 7
        assert int((nt == NT_CPU).sum()) == 7


# ---------------------------------------------------------------------------
# 2. placement model: schedules, plans, registry, resolution
# ---------------------------------------------------------------------------

class TestPlacementModel:
    def test_rejects_bad_events(self):
        with pytest.raises(ValueError, match="unknown placement plan"):
            PlacementSchedule((PlacementEvent(0.0, 1.0, "teleport"),))
        with pytest.raises(ValueError, match="slot"):
            PlacementSchedule((
                PlacementEvent(0.0, 1.0, "gpu_near_mc", "turbo"),
            ))
        with pytest.raises(ValueError, match="outside"):
            PlacementSchedule((
                PlacementEvent(0.7, 0.3, "gpu_near_mc"),
            ))

    def test_plans_preserve_counts_and_mc_tiles(self):
        topo = make_topology()
        nt = np.asarray(topo.node_type)
        for name in ("GPU_NEAR_MC", "GPU_NEAR_MC_ALWAYS", "SWAP_MID"):
            stream = lookup_placement(name).materialize(8, topo)
            for plan in (np.asarray(stream.cls0), np.asarray(stream.cls1)):
                # MC rows are physical: never reassigned, in any epoch
                assert (plan[:, nt == NT_MC] == NT_MC).all(), name
                # relocation conserves compute: class counts fixed
                assert ((plan == NT_GPU).sum(axis=1) == 14).all(), name
                assert ((plan == NT_CPU).sum(axis=1) == 14).all(), name

    def test_gpu_near_mc_moves_gpu_toward_mcs(self):
        topo = make_topology()
        base = np.asarray(topo.node_type)
        plan = np.asarray(
            lookup_placement("GPU_NEAR_MC").materialize(4, topo).cls1[0]
        )
        ids = np.arange(topo.n_routers)
        xy = np.stack([ids % 6, ids // 6], axis=1)
        mc_xy = xy[np.asarray(topo.mc_ids)]
        dist = np.abs(xy[:, None, :] - mc_xy[None, :, :]).sum(-1).min(-1)
        assert dist[plan == NT_GPU].mean() < dist[base == NT_GPU].mean()

    def test_registry_errors(self):
        with pytest.raises(ValueError, match="did you mean"):
            lookup_placement("GPU_NEAR_MCC")
        with pytest.raises(TypeError, match="must be a PlacementSchedule"):
            register_placement("BAD", object())
        with pytest.raises(ValueError, match="already exists"):
            register_placement("GPU_NEAR_MC", PLACEMENTS["GPU_NEAR_MC"])

    def test_resolve_shapes_and_types(self):
        topo = make_topology()
        for src in (None, "SWAP_MID", PLACEMENTS["GPU_NEAR_MC"],
                    static_placement(8, topo)):
            stream = resolve_placement(src, 8, topo)
            assert stream.cls0.shape == (8, 36)
            assert stream.cls1.shape == (8, 36)
        with pytest.raises(TypeError, match="cannot resolve placement"):
            resolve_placement(42, 8, topo)
        with pytest.raises(ValueError, match="has shape"):
            resolve_placement(static_placement(4, topo), 8, topo)

    def test_identity_stream_is_the_topology_layout(self):
        topo = make_topology()
        stream = static_placement(3, topo)
        want = np.tile(np.asarray(topo.node_type), (3, 1))
        np.testing.assert_array_equal(np.asarray(stream.cls0), want)
        np.testing.assert_array_equal(np.asarray(stream.cls1), want)


# ---------------------------------------------------------------------------
# 3. the refactor guard: identity placement is bitwise-free
# ---------------------------------------------------------------------------

class TestIdentityPlacement:
    def test_goldens_replay_on_all_backends(self):
        """Committed PR-4 goldens replay bitwise with the placement layer
        in the loop, on every backend — the tentpole's no-regression pin."""
        with open(GOLDEN_PATH) as f:
            golden = json.load(f)["cases"]
        for backend in BACKENDS:
            for key, g in golden.items():
                mode, wl, gs, ss = key.split("/")
                cfg = NoCConfig(mode=mode, static_gpu_vcs=int(gs[1:]),
                                seed=int(ss[1:]), placement=None, **FAST)
                res = sim.simulate(cfg, wl, backend=backend)
                sums = {n: int(np.sum(np.asarray(leaf)))
                        for n, leaf in zip(res.counters._fields,
                                           res.counters)}
                assert sums == g["counter_sums"], \
                    f"{backend}/{key}: counter drift"
                assert (np.asarray(res.applied_config).tolist()
                        == g["applied_config"]), f"{backend}/{key}"

    def test_explicit_identity_stream_is_bitwise_free(self):
        cfg = NoCConfig(mode="kf", **TINY)
        a = sim.simulate(cfg, "SHIFT_PATH_BFS")
        b = sim.simulate(
            dataclasses_replace(cfg, placement=static_placement(
                TINY["n_epochs"], make_topology())),
            "SHIFT_PATH_BFS",
        )
        _bitwise_equal(a, b, "explicit identity stream")

    def test_armed_but_idle_lever_is_bitwise_free(self):
        """Bandwidth control carrying the GPU_NEAR_MC stream == no stream:
        `place_enable` False must make the relocation rows unreachable."""
        cfg = NoCConfig(mode="kf", control="bandwidth", **TINY)
        a = sim.simulate(cfg, "SHIFT_PATH_BFS")
        b = sim.simulate(
            dataclasses_replace(cfg, placement="GPU_NEAR_MC"),
            "SHIFT_PATH_BFS",
        )
        _bitwise_equal(a, b, "armed-but-idle placement lever")

    def test_control_x_placement_grid_is_one_trace(self):
        specs = [
            SweepSpec("kf", "SHIFT_PATH_BFS", seed=s, placement=plc,
                      control=ctl)
            for s in (0, 1)
            for plc in (None, "GPU_NEAR_MC", "SWAP_MID")
            for ctl in ("bandwidth", "placement", "joint")
        ]
        sim.reset_trace_count()
        # epoch_len unique to this test: other suites compile (8, 80)
        # batched programs, and a jit-cache hit would count 0 traces
        rows = sim.sweep(specs, n_epochs=8, epoch_len=96)
        assert len(rows) == len(specs)
        assert sim.trace_count() == 1, (
            f"control x placement grid traced {sim.trace_count()}x"
        )


# ---------------------------------------------------------------------------
# 4. relocation semantics + backend congruence
# ---------------------------------------------------------------------------

class TestRelocation:
    def test_swap_mid_migrates_at_midpoint(self):
        cfg = NoCConfig(mode="kf", placement="SWAP_MID", control="joint",
                        **TINY)
        _, trace = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
        cls = np.asarray(trace.place_cls)
        moves = (np.diff(cls, axis=0) != 0).sum(axis=1)
        # exactly one migration epoch: the midpoint swap of all 28 non-MC
        # tiles; the boost slot never engages in this warmup-short run
        assert moves.tolist() == [0, 0, 0, 28, 0, 0, 0]
        from repro.obs.probes import summarize_trace

        assert summarize_trace(trace)["place_moves_total"] == 28

    def test_identity_run_has_zero_moves(self):
        cfg = NoCConfig(mode="kf", **TINY)
        _, trace = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
        from repro.obs.probes import summarize_trace

        assert summarize_trace(trace)["place_moves_total"] == 0

    @pytest.mark.parametrize("backend", BACKENDS[1:])
    def test_active_relocation_congruent_across_backends(self, backend):
        cfg = NoCConfig(mode="kf", placement="SWAP_MID", control="joint",
                        faults="FLAP_BFS", guard=True, **TINY)
        ref = sim.simulate(cfg, "SHIFT_PATH_BFS", backend="ref")
        other = sim.simulate(cfg, "SHIFT_PATH_BFS", backend=backend)
        _bitwise_equal(ref, other, f"relocation+faults ref vs {backend}")

    @pytest.mark.parametrize("backend", BACKENDS[1:])
    def test_non_paper_grid_congruent_across_backends(self, backend):
        cfg = NoCConfig(mode="kf", width=4, height=4, placement="SWAP_MID",
                        control="joint", **TINY)
        ref = sim.simulate(cfg, "SHIFT_PATH_BFS", backend="ref")
        other = sim.simulate(cfg, "SHIFT_PATH_BFS", backend=backend)
        _bitwise_equal(ref, other, f"4x4 ref vs {backend}")

    def test_non_paper_grid_runs_and_differs(self):
        base = sim.simulate(NoCConfig(mode="kf", **TINY), "SHIFT_PATH_BFS")
        small = sim.simulate(
            NoCConfig(mode="kf", width=4, height=4, **TINY),
            "SHIFT_PATH_BFS",
        )
        assert np.isfinite(np.asarray(small.gpu_ipc)).all()
        # a 4x4/8-MC grid is a different machine: outputs must move
        assert not np.array_equal(np.asarray(base.counters.gpu_gen),
                                  np.asarray(small.counters.gpu_gen))

    def test_bench_sweep_seed_style_tracks_impl_signature(self):
        # bench_sweep's serial baseline jits sim._simulate_impl directly
        # (by design: it times fresh-trace recompiles, so it can't go
        # through the public cached wrappers) — a new positional arg on
        # the impl, like this PR's placement stream, breaks it without
        # any public-API test noticing.  One tiny point keeps it in sync.
        from benchmarks import bench_sweep

        cfgs, profs = bench_sweep._grid(
            ["PATH"], [2], [0], n_epochs=2, epoch_len=8
        )
        assert bench_sweep.time_serial_seed_style(cfgs, profs) > 0.0


def dataclasses_replace(cfg, **kw):
    import dataclasses

    return dataclasses.replace(cfg, **kw)
