"""Compile rehearsal for TPU v5e with no chip attached.

The TPU compiler compiles for a described `v5e:2x2` topology, so every
Pallas kernel of the main path is lowered by Mosaic here at a real width:
what interpret mode accepts and Mosaic refuses (an unaligned or strided
slice, an i1 relayout, an unimplemented primitive, too much VMEM) fails
this file and not a chip run.  Each case asserts that the compiled
program holds the kernel (`tpu_custom_call`).

The topology is described inside a module fixture and never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.core.noc import sim
from repro.kernels.flash_attn import ops as fa_ops
from repro.kernels.kf_bank import ops as kf_ops
from repro.kernels.mamba_scan import fused as ms_fused
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.noc_cycle import ops as noc_ops

SHORT = dict(n_epochs=4, epoch_len=100)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Lower the Pallas kernels for Mosaic rather than the interpreter.

    `_interpret()` is read while tracing, so jit's trace caches are
    cleared on both sides: no CPU trace is reused for the TPU compile, and
    no TPU trace is left for later CPU tests.  The persistent compilation
    cache is off: an entry written for a described chip cannot be read
    back without one.
    """
    for mod in (noc_ops, kf_ops, fa_ops, ms_ops, ms_fused):
        monkeypatch.setattr(mod, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)


def _abstract(tree, sharding):
    """Each host argument's exact shape and dtype, on `sharding`."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend,side", [
    ("pallas", 6),
    ("pallas", 8),  # 64 routers: the largest grid the lane layout holds
])
def test_sim_program_compiles(one_chip, tpu_lowering, backend, side):
    cfg = sim.NoCConfig(backend=backend, width=side, height=side, **SHORT)
    stc, *args = sim.sim_args(cfg, "BFS")
    _assert_kernel(
        sim._SIM_JIT.lower(stc, *_abstract(args, one_chip)).compile())


# the routed experts of the expert-parallel configuration on the 6x6
# package's GPU tiles (bench/configs/noc6x6-moe-ep.json)
PEER_WEIGHTS = tuple(
    {4: 5, 7: 5, 9: 5, 11: 5, 13: 5, 15: 5, 17: 5, 19: 5, 21: 4, 23: 4,
     25: 4, 27: 4, 29: 4, 34: 4}.get(r, 0) for r in range(36))


def _sweep_tile_args(peer_weights=None):
    # the paper's 500-cycle epochs: 4 launches of 125 cycles an epoch
    cfgs = [sim.NoCConfig(mode=m, backend="pallas", n_epochs=4,
                          epoch_len=500, peer_weights=peer_weights)
            for m in ("4subnet", "baseline", "fair", "kf", "kf", "fair")]
    assert len(cfgs) == sim.SWEEP_TILE
    workload = "BFS" if peer_weights is None else \
        sim.PeerProfile(0.03, 0.4, 0.0003, 0.00012, 0.12, 0.39)
    stc, mp, prof, seeds, flt, plc = sim.batch_args(cfgs, workload)
    state0 = sim.init_sim_state(stc, len(cfgs))
    return stc, (mp, prof, seeds, state0, flt, plc)


def _sweep_tile(one_chip, peer_weights=None):
    stc, args = _sweep_tile_args(peer_weights)
    return sim._batch_jit().lower(stc, *_abstract(args, one_chip)).compile()


def _signature(tree):
    return [(x.shape, np.dtype(x.dtype)) for x in jax.tree.leaves(tree)]


def test_sweep_tile_from_host_arguments_is_the_same_program(
        one_chip, tpu_lowering):
    """The host (NumPy) arguments lower to the program that the same
    arguments as device arrays (`jnp.asarray`, which drops a 64-bit host
    leaf to 32 bits) lower to: same shapes and dtypes, same v5e tile."""
    stc, host = _sweep_tile_args()
    assert all(type(x) is np.ndarray for x in jax.tree.leaves(host))
    device = jax.tree.map(jnp.asarray, host)
    assert _signature(host) == _signature(device)
    texts = [
        sim._batch_jit().lower(stc, *_abstract(args, one_chip))
        .compile().as_text()
        for args in (host, device)
    ]
    assert texts[0] == texts[1]


def test_pallas_sweep_tile_compiles(one_chip, tpu_lowering):
    _assert_kernel(_sweep_tile(one_chip))


def test_peer_sweep_tile_compiles_with_its_label(one_chip, tpu_lowering):
    """The expert-parallel tile (DESIGN.md §19): the kernel with its GPU
    servers and peer counters, and the destination draw under its own
    `epoch.dest` label."""
    text = _sweep_tile(one_chip, PEER_WEIGHTS).as_text()
    assert "tpu_custom_call" in text
    assert re.search(r'noc_layer="epoch\.dest"', text)


def test_sweep_tile_keeps_its_device_labels(one_chip, tpu_lowering):
    """The `noc_layer` labels of `_simulate_impl` (DESIGN.md §18) survive
    the TPU compiler on the operations a device trace names: top-level
    fusions (the placement and guard labels among them), the cycle loop
    and the kernel, whose own metadata (its layer and the cycles it runs
    a launch) the scan's label sits beside without replacing it."""
    text = _sweep_tile(one_chip).as_text()
    assert re.search(r' fusion\(.*noc_layer="epoch\.rng"', text)
    assert re.search(r' while\(.*noc_layer="cycle\.scan"', text)
    assert re.search(r' fusion\(.*noc_layer="epoch\.boundary"', text)
    # the placement rows and the KF guard, nested in the boundary
    assert re.search(r' fusion\(.*noc_layer="epoch\.placement"', text)
    assert re.search(r' fusion\(.*noc_layer="epoch\.guard"', text)
    kernel = re.search(
        r'custom_call_target="tpu_custom_call".*?'
        r'frontend_attributes=\{kernel_metadata=\{\s*'
        r'"cycles_per_launch":125,\s*'
        r'"noc_layer":"cycle\.kernel"\s*\},noc_layer="cycle\.scan"\}',
        text, re.S)
    assert kernel


@pytest.mark.parametrize("chips", [1, 4])
def test_row_split_compiles(topo, one_chip, tpu_lowering, chips):
    """`sim.sweep`'s row split for the paper grid's 24 points: four tiles
    on one chip, or one answer sharded over the 2x2 mesh, cut into one
    row per point by one program; over the mesh the answer is gathered
    once and every row comes back replicated on all four chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    stc, args = _sweep_tile_args()
    tile = jax.eval_shape(lambda *a: sim._batch_jit()(stc, *a), *args)
    n = sim.SWEEP_TILE

    def answer(rows, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            (rows,) + x.shape[1:], x.dtype, sharding=sharding), tile)

    if chips == 1:
        mesh, answers, ns = None, (answer(n, one_chip),) * 4, (n,) * 4
    else:
        mesh = Mesh(np.array(topo.devices).reshape(chips), (sim.SWEEP_AXIS,))
        answers = (answer(4 * n, NamedSharding(mesh, P(sim.SWEEP_AXIS))),)
        ns = (4 * n,)
    compiled = sim._rows_jit(sim._split, mesh).lower(answers, ns).compile()
    rows = compiled.output_shardings
    assert len(rows) == 4 * n
    leaves = jax.tree.leaves(rows)
    assert len(leaves) == 4 * n * len(jax.tree.leaves(tile))
    if mesh is not None:
        assert "all-gather" in compiled.as_text()
        assert all(s.is_fully_replicated and len(s.device_set) == chips
                   for s in leaves)


def test_kf_bank_compiles(one_chip, tpu_lowering):
    n, m = 131072, 3
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32, sharding=one_chip)
            for s in ((n,), (n,), (n, m), (m,), (m,))]
    _assert_kernel(kf_ops.kf_bank_step.lower(*args).compile())


def test_flash_attention_compiles(one_chip, tpu_lowering):
    cfg = configs.get("stablelm-1.6b")
    shape = (1, 2048, cfg.n_heads, cfg.head_dim)
    q, k, v = (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
               for _ in range(3))
    _assert_kernel(fa_ops.flash_attention.lower(q, k, v).compile())


def test_mamba_scan_compiles(one_chip, tpu_lowering):
    b, l, d, s = 2, 256, 64, 16
    f32 = jnp.float32
    ab = jax.ShapeDtypeStruct((b, l, d, s), f32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((b, d, s), f32, sharding=one_chip)
    _assert_kernel(ms_ops.mamba_chunk_scan.lower(
        ab, ab, h0, chunk=128, block_d=64).compile())
    row = jax.ShapeDtypeStruct((b, l, d), f32, sharding=one_chip)
    bc = jax.ShapeDtypeStruct((b, l, s), f32, sharding=one_chip)
    a_mat = jax.ShapeDtypeStruct((d, s), f32, sharding=one_chip)
    _assert_kernel(ms_fused.fused_mamba_scan.lower(
        row, row, bc, bc, a_mat, chunk=128, block_d=64).compile())
