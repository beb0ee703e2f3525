"""Batched sweep engine: equivalence with per-config runs + compile budget.

The contract of `sim.simulate_batch` / `sim.sweep` (DESIGN.md §4, §10):

  1. a batch row is bit-for-bit the same simulation as a standalone
     `simulate()` with the same config/workload/seed;
  2. the S/V-padded shared program is bit-for-bit the mode's dedicated
     (unpadded) trace — padding must be invisible in every counter;
  3. the whole paper evaluation (Fig 2/3 + Fig 9/10/11 + Fig 12) costs
     exactly ONE trace of the simulator — 4-subnet included;
  4. `sweep_sharded` returns `sweep`'s rows exactly, including on a ragged
     (non-divisible) point count;
  5. the sweep cuts its answer into rows with one compiled call, on one
     device or over a mesh, and no row crosses to the host.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.noc import sim
from repro.core.noc.sim import NoCConfig, SweepSpec
from repro.core.noc.traffic import PROFILES, RecordedTrace

FAST = dict(n_epochs=8, epoch_len=100)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _assert_rows_equal(row, ref, label):
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(row),
        jax.tree_util.tree_leaves_with_path(ref),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6,
            err_msg=f"{label}: leaf {jax.tree_util.keystr(path)}",
        )


def _on_four_devices(body):
    """Run `body` in a subprocess on 4 virtual CPU devices; its stdout.

    A subprocess because the XLA device count is locked at first jax init
    (same pattern as tests/test_multidevice.py)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


SPECS = [
    SweepSpec(mode, wl, seed=seed)
    for mode in ("baseline", "fair", "kf", "4subnet")
    for wl in ("PATH", "BFS")
    for seed in (0, 3)
] + [
    SweepSpec("static", wl, static_gpu_vcs=g, seed=1)
    for wl in ("PATH", "BFS")
    for g in (1, 2, 3)
]


def test_sweep_rows_match_per_config_simulate():
    """Every mode/workload/ratio/seed: batch row == standalone simulate."""
    rows = sim.sweep(SPECS, batch_tile=4, **FAST)
    for sp, row in zip(SPECS, rows):
        cfg = NoCConfig(mode=sp.mode, static_gpu_vcs=sp.static_gpu_vcs,
                        seed=sp.seed, **FAST)
        ref = sim.simulate(cfg, PROFILES[sp.workload])
        _assert_rows_equal(row, ref, f"{sp.mode}/{sp.workload}/g{sp.static_gpu_vcs}/s{sp.seed}")


def test_paper_sweeps_compile_exactly_once():
    """Fig 2/3 + Fig 9/10/11 + Fig 12 together: ONE trace (DESIGN.md §10).

    Since the subnet axis is S-padded and the structure traced, the
    4-subnet network no longer compiles its own program — the entire paper
    evaluation is one executable.  (Tightened from <= 2 when S-padding
    landed.)
    """
    from benchmarks import fig2_3_vc_sweep, fig9_10_11_configs, fig12_dynamic_kf

    mini = dict(n_epochs=3, epoch_len=150, seeds=(0,))
    sim.reset_trace_count()
    fig2_3_vc_sweep.run(**mini)
    fig9_10_11_configs.run(**mini)
    fig12_dynamic_kf.run(**mini)
    assert sim.trace_count() == 1, (
        f"paper sweeps traced simulate {sim.trace_count()} times; all modes "
        "(4subnet included) must share the one S/V-padded program"
    )


def test_padded_program_matches_dedicated_trace():
    """S/V-padding equivalence: the shared padded program reproduces the
    mode's dedicated trace bit-for-bit — per-seed counters included.

    4subnet is the load-bearing case (padded V with masked upper VCs AND
    a re-indexed switch-allocation requester space); one 2-subnet mode
    guards the padded-subnet direction.
    """
    for mode, wl in (("4subnet", "STO"), ("kf", "PATH")):
        for seed in (0, 1):
            cfg = NoCConfig(mode=mode, seed=seed, **FAST)
            pad = sim.simulate(cfg, PROFILES[wl])
            ded = sim.simulate(cfg, PROFILES[wl], padded=False)
            label = f"{mode}/{wl}/s{seed}"
            _assert_rows_equal(pad, ded, f"padded vs dedicated {label}")
            for name, a, b in zip(
                pad.counters._fields, pad.counters, ded.counters
            ):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{label}: counter {name} not bitwise equal",
                )


def test_sweep_sharded_matches_sweep_on_ragged_batch():
    """`sweep_sharded` == `sweep` on a point count that does NOT divide the
    device count (5 points, 4 devices -> one pad row per the padding rule).
    """
    body = """
        import jax, numpy as np
        from repro.core.noc import sim
        from repro.core.noc.sim import SweepSpec
        FAST = dict(n_epochs=2, epoch_len=50)
        specs = [
            SweepSpec("baseline", "PATH"),
            SweepSpec("4subnet", "LIB", seed=1),
            SweepSpec("kf", "STO", seed=2),
            SweepSpec("static", "PATH", static_gpu_vcs=3, seed=3),
            SweepSpec("fair", "BFS", seed=4),
        ]
        assert len(jax.devices()) == 4
        rows = sim.sweep(specs, **FAST)
        rows_sh = sim.sweep_sharded(specs, devices=4, **FAST)
        for i, (a, b) in enumerate(zip(rows, rows_sh)):
            for (p, x), (_, y) in zip(
                jax.tree_util.tree_leaves_with_path(a),
                jax.tree_util.tree_leaves_with_path(b),
            ):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"row {i} {jax.tree_util.keystr(p)}")
        print("SHARDED_RAGGED_OK")
    """
    assert "SHARDED_RAGGED_OK" in _on_four_devices(body)


def test_batch_profile_broadcast_and_seed_override():
    cfgs = [NoCConfig(mode="fair", seed=9, **FAST)] * 2
    res = sim.simulate_batch(cfgs, PROFILES["LIB"], seeds=(9, 9))
    _assert_rows_equal(
        jax.tree.map(lambda x: x[0], res),
        jax.tree.map(lambda x: x[1], res),
        "identical rows",
    )
    ref = sim.simulate(cfgs[0], PROFILES["LIB"])
    _assert_rows_equal(jax.tree.map(lambda x: x[0], res), ref, "vs single")


def test_batch_rejects_mixed_structures():
    """Genuinely structural differences still refuse to batch — but mode is
    no longer one of them: 2-subnet and 4-subnet rows share the padded
    program (DESIGN.md §10) and batch together."""
    cfgs = [NoCConfig(mode="baseline", **FAST),
            NoCConfig(mode="baseline", n_epochs=4, epoch_len=100)]
    with pytest.raises(ValueError, match="structural"):
        sim.simulate_batch(cfgs, PROFILES["PATH"])

    mixed = [NoCConfig(mode="baseline", **FAST),
             NoCConfig(mode="4subnet", **FAST)]
    res = sim.simulate_batch(mixed, PROFILES["PATH"])
    assert res.gpu_ipc.shape[0] == 2


def test_summarize_seeds_reports_mean_and_std():
    specs = [SweepSpec("fair", "PATH", seed=s) for s in (0, 1)]
    rows = sim.sweep(specs, **FAST)
    agg = sim.summarize_seeds(rows, warmup_epochs=2)
    per = [sim.summarize(r, warmup_epochs=2) for r in rows]
    assert agg["gpu_ipc"] == pytest.approx(
        (per[0]["gpu_ipc"] + per[1]["gpu_ipc"]) / 2
    )
    assert agg["gpu_ipc_std"] >= 0.0
    assert "avg_latency_std" in agg


# ---------------------------------------------------------------------------
# Host-built arguments (DESIGN.md §18): the argument layer dispatches no
# device op and crosses to the device once per tile, or once per sharded
# batch, each chip receiving its own shard.
# ---------------------------------------------------------------------------

def _trace_of(workload):
    return RecordedTrace(demand=PROFILES[workload].epoch_demand(
        FAST["n_epochs"]), name=f"{workload}-trace")


HOST_CASES = {
    "profile": (dict(mode="kf"), PROFILES["BFS"]),
    "scenario": (dict(mode="kf"), "SHIFT_PATH_BFS"),
    "recorded_trace": (dict(mode="fair"), _trace_of("STO")),
    "fault_scenario": (dict(mode="kf", faults="FLAP_BFS", guard=True), "LIB"),
    "placement_scenario": (dict(mode="kf", placement="GPU_NEAR_MC",
                                control="joint"), "MUM"),
}


@pytest.mark.parametrize("case", sorted(HOST_CASES))
def test_arguments_are_host_arrays(case):
    """`batch_args` and `sim_args` return NumPy leaves only, and build them
    with no transfer either way."""
    kw, source = HOST_CASES[case]
    cfgs = [NoCConfig(seed=s, **kw, **FAST) for s in (0, 1, 2)]
    with jax.transfer_guard("disallow"):
        _, *batch = sim.batch_args(cfgs, source)
        _, *single = sim.sim_args(cfgs[0], source)
    for leaf in jax.tree.leaves((batch, single)):
        assert type(leaf) is np.ndarray, type(leaf)
    # the host arguments are the ones the simulation consumes
    _assert_rows_equal(sim.simulate_batch(cfgs[:1], source),
                       jax.tree.map(lambda x: x[None],
                                    sim.simulate(cfgs[0], source)),
                       case)


@pytest.mark.parametrize("n_points,tile,transfers", [
    (12, 6, 2), (10, 6, 2), (5, None, 1),
])
def test_simulate_batch_crosses_to_the_device_once_per_tile(
        monkeypatch, n_points, tile, transfers):
    """One `jax.device_put` per tile (the ragged tail padded on the host):
    every argument reaches the program on the device already, and the
    call never reads the device back."""
    cfgs = [NoCConfig(mode=("kf", "fair", "4subnet")[i % 3], seed=i, **FAST)
            for i in range(n_points)]
    sources = [("PATH", "BFS")[i % 2] for i in range(n_points)]
    puts, dispatched = [], []
    real_put, real_jit = jax.device_put, sim._batch_jit

    def counting_put(*args, **kwargs):
        puts.append(args[0])
        return real_put(*args, **kwargs)

    def spy_jit():
        def run(stc, *args):
            dispatched.append(args)
            return real_jit()(stc, *args)
        return run

    sim.simulate_batch(cfgs[:1], sources[:1], batch_tile=tile)  # warm
    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(sim, "_batch_jit", spy_jit)
    with jax.transfer_guard_device_to_host("disallow"):
        res = sim.simulate_batch(cfgs, sources, batch_tile=tile)
        jax.block_until_ready(res)
    assert len(puts) == len(dispatched) == transfers
    for tree in puts:  # the whole tile's arguments, initial state included
        assert len(tree) == 6
        assert all(type(x) is np.ndarray for x in jax.tree.leaves(tree))
    for args in dispatched:
        assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(args))
    assert res.gpu_ipc.shape[0] == n_points
    ref = sim.simulate(cfgs[-1], sources[-1])
    _assert_rows_equal(jax.tree.map(lambda x: x[-1], res), ref, "last row")


def test_simulate_crosses_to_the_device_once(monkeypatch):
    cfg = NoCConfig(mode="kf", seed=4, **FAST)
    sim.simulate(cfg, "BFS")  # warm
    calls = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: calls.append(a) or real_put(*a, **k))
    with jax.transfer_guard("disallow"):
        jax.block_until_ready(sim.simulate(cfg, "BFS"))
    assert len(calls) == 1


def test_sharded_batch_puts_each_shard_on_its_own_device():
    """On 4 virtual CPU devices a 5-point batch is padded to 8 on the host
    and crosses in one `device_put`: every argument reaches the program
    split along the batch axis, two rows on each device."""
    body = """
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.noc import sim
        from repro.core.noc.sim import NoCConfig
        assert len(jax.devices()) == 4
        cfgs = [NoCConfig(mode=m, seed=i, n_epochs=2, epoch_len=50)
                for i, m in enumerate(("kf", "fair", "4subnet", "kf", "baseline"))]
        seen, puts = [], []
        real_jit, real_put = sim._sharded_jit, jax.device_put
        def spy_jit(stc, mesh):
            fn = real_jit(stc, mesh)
            def run(*args):
                seen.append((mesh, args))
                return fn(*args)
            return run
        def spy_put(*a, **k):
            puts.append(a)
            return real_put(*a, **k)
        sim._sharded_jit = spy_jit
        jax.device_put = spy_put
        with jax.transfer_guard_device_to_host("disallow"):
            res = sim.simulate_batch(cfgs, "PATH", devices=4)
            jax.block_until_ready(res)
        assert len(puts) == 1, len(puts)
        (mesh, args), = seen
        want = NamedSharding(mesh, P(sim.SWEEP_AXIS))
        for x in jax.tree.leaves(args):
            assert isinstance(x, jax.Array) and x.shape[0] == 8, x.shape
            assert x.sharding.is_equivalent_to(want, x.ndim), x.sharding
            shards = x.addressable_shards
            assert len({s.device for s in shards}) == 4
            assert all(s.data.shape[0] == 2 for s in shards)
        assert res.gpu_ipc.shape[0] == 5
        print("SHARDS_OK")
    """
    assert "SHARDS_OK" in _on_four_devices(body)


# ---------------------------------------------------------------------------
# Rows (DESIGN.md §18): a sweep's answer is cut into per-point rows by one
# compiled call per sweep, on one device or over a mesh, and the rows stay
# on the device.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_points,tile", [(12, 6), (5, 6)])
def test_sweep_cuts_its_rows_in_one_call(monkeypatch, n_points, tile):
    """One call of the compiled split a sweep, traced once for a grid, with
    no row crossing to the host; a ragged tile's pad rows never come
    back, and every row is bitwise the joined answer's row."""
    specs = [SweepSpec(("kf", "fair", "4subnet", "baseline")[i % 4],
                       ("PATH", "BFS")[i % 2], seed=i)
             for i in range(n_points)]
    real = sim._rows_jit
    split = real(sim._split)
    calls = []

    def spy(fn, mesh=None):
        jitted = real(fn, mesh)

        def run(*args):
            calls.append((fn, mesh))
            return jitted(*args)
        return run

    monkeypatch.setattr(sim, "_rows_jit", spy)
    traced = []
    for _ in range(2):
        calls.clear()
        with jax.transfer_guard_device_to_host("disallow"):
            rows = sim.sweep(specs, batch_tile=tile, **FAST)
            jax.block_until_ready(rows)
        assert calls == [(sim._split, None)]
        traced.append(split._cache_size())
    assert traced[0] == traced[1]
    assert len(rows) == n_points
    cfgs = [NoCConfig(mode=sp.mode, seed=sp.seed, **FAST) for sp in specs]
    joined = sim.simulate_batch(cfgs, [sp.workload for sp in specs],
                                batch_tile=tile)
    assert joined.gpu_ipc.shape[0] == n_points
    for j, row in enumerate(rows):
        assert row.gpu_ipc.shape == (FAST["n_epochs"],)
        for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(row),
                             jax.tree.leaves(joined)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)[j],
                err_msg=f"row {j} {jax.tree_util.keystr(p)}")
    _assert_rows_equal(rows[-1], sim.simulate(cfgs[-1], specs[-1].workload),
                       "last row")


def test_sweep_sharded_cuts_its_rows_over_the_mesh():
    """`sweep_sharded(devices=4)` on 5 points (padded to 8): at most one
    split call a chip, no row crossing to the host, rows bitwise `sweep`'s,
    and every row on the whole mesh, so two rows combine with `jnp`."""
    body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.noc import sim
        from repro.core.noc.sim import SweepSpec
        F = dict(n_epochs=2, epoch_len=50)
        specs = [SweepSpec(m, w, seed=i) for i, (m, w) in enumerate(
            [("kf", "PATH"), ("fair", "LIB"), ("4subnet", "STO"),
             ("baseline", "BFS"), ("kf", "MUM")])]
        assert len(jax.devices()) == 4
        rows = sim.sweep(specs, **F)
        calls, real = [], sim._rows_jit
        def spy(fn, mesh=None):
            jitted = real(fn, mesh)
            def run(*args):
                calls.append(fn)
                return jitted(*args)
            return run
        sim._rows_jit = spy
        with jax.transfer_guard_device_to_host("disallow"):
            rows_sh = sim.sweep_sharded(specs, devices=4, **F)
            jax.block_until_ready(rows_sh)
            both = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                                rows_sh[0], rows_sh[4])
        assert 1 <= len(calls) <= 4 and set(calls) == {sim._split}, calls
        assert len(rows_sh) == 5
        mesh = frozenset(jax.devices())
        for r in rows_sh:
            for x in jax.tree.leaves(r):
                assert x.sharding.device_set == mesh, x.sharding
        for i, (a, b) in enumerate(zip(rows, rows_sh)):
            for (p, x), (_, y) in zip(
                jax.tree_util.tree_leaves_with_path(a),
                jax.tree_util.tree_leaves_with_path(b),
            ):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"row {i} {jax.tree_util.keystr(p)}")
        for x, a, b in zip(jax.tree.leaves(both), jax.tree.leaves(rows[0]),
                           jax.tree.leaves(rows[4])):
            np.testing.assert_array_equal(np.asarray(x), np.stack([a, b]))
        print("SHARDED_ROWS_OK")
    """
    assert "SHARDED_ROWS_OK" in _on_four_devices(body)
