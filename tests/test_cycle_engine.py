"""Cycle-engine equivalence suite (DESIGN.md §11).

The packed-lane engine rewrote the hot loop under a bitwise contract: every
rewrite (epoch-hoisted masks, precomputed RNG streams, the merged inject,
packed narrow-dtype state, the scatter-free dense writes, the Pallas
arbitration kernel) must leave all observable outputs exactly as the PR-3
engine produced them.  Three layers of pinning:

  1. golden outputs — `tests/golden_cycle_engine.json` was captured from the
     PR-3 padded program; the new engine must reproduce it bit-for-bit;
  2. rewrite micro-tests — each equivalence-preserving rewrite is checked
     directly against the formulation it replaced;
  3. ref <-> Pallas congruence — the fused `pallas` engine
     (`kernels.noc_cycle`, interpret mode off TPU) must agree with the
     dense `ref` engine exactly, from a single arbitration step through
     one epoch called at the engine interface up to a whole run.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocator import PolicyConfig
from repro.core.noc import router as rt
from repro.core.noc import sim
from repro.core.noc.sim import NoCConfig
from repro.core.noc.topology import N_PORTS, make_topology
from repro.core.noc.traffic import PROFILES
from repro.obs.probes import ProbeConfig

FAST = dict(n_epochs=8, epoch_len=100)
GOLDEN_PATH = os.path.join(
    os.path.dirname(__file__), "golden_cycle_engine.json"
)


# ---------------------------------------------------------------------------
# 1. golden pinning vs the PR-3 engine
# ---------------------------------------------------------------------------

def test_outputs_match_pr3_golden_capture():
    """Counters/config/latency match the pre-rewrite padded program exactly.

    The golden file was first captured from the PR-3 engine (per-cycle RNG
    splits, separate injects, int32 scatter state) before this refactor
    landed; equality here proves the whole rewrite chain is value-preserving,
    not just self-consistent.  Its provenance records the one recapture,
    for jax's new default PRNG stream.
    """
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)["cases"]
    for key, g in golden.items():
        mode, wl, gs, ss = key.split("/")
        cfg = NoCConfig(mode=mode, static_gpu_vcs=int(gs[1:]),
                        seed=int(ss[1:]), backend="ref", **FAST)
        res = sim.simulate(cfg, PROFILES[wl])
        sums = {n: int(np.sum(np.asarray(leaf)))
                for n, leaf in zip(res.counters._fields, res.counters)}
        assert sums == g["counter_sums"], f"{key}: counter drift"
        assert np.asarray(res.applied_config).tolist() == g["applied_config"]
        assert np.asarray(res.kf_signal).tolist() == g["kf_signal"]
        np.testing.assert_allclose(
            float(np.asarray(res.avg_latency)[-1]), g["avg_latency_last"],
            rtol=0, atol=1e-6, err_msg=key,
        )


# ---------------------------------------------------------------------------
# 2. rewrite micro-tests
# ---------------------------------------------------------------------------

def test_batched_rng_streams_match_per_cycle_splits():
    """The per-epoch vmapped RNG precompute == the old per-cycle splits."""
    epoch_key = jax.random.PRNGKey(42)
    ep_len, R, n_mc = 37, 36, 8
    keys = jax.random.split(epoch_key, ep_len)

    # old engine: draw inside the loop, one cycle at a time
    u_ph_ref, u_gen_ref, d_ref = [], [], []
    for i in range(ep_len):
        k_phase, k_gen, k_dest = jax.random.split(keys[i], 3)
        u_ph_ref.append(jax.random.uniform(k_phase, ()))
        u_gen_ref.append(jax.random.uniform(k_gen, (R,), jnp.float32))
        d_ref.append(jax.random.randint(k_dest, (R,), 0, n_mc))

    # new engine: one batched draw per epoch (sim.epoch_body's precompute)
    k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    u_phase = jax.vmap(lambda k: jax.random.uniform(k, ()))(k3[:, 0])
    u_gen = jax.vmap(
        lambda k: jax.random.uniform(k, (R,), jnp.float32)
    )(k3[:, 1])
    d_idx = jax.vmap(
        lambda k: jax.random.randint(k, (R,), 0, n_mc)
    )(k3[:, 2])

    np.testing.assert_array_equal(np.asarray(u_phase), np.stack(u_ph_ref))
    np.testing.assert_array_equal(np.asarray(u_gen), np.stack(u_gen_ref))
    np.testing.assert_array_equal(np.asarray(d_idx), np.stack(d_ref))


def _random_subnet_state(rng, S=4, R=36, P=N_PORTS, V=4, B=4):
    dest = rng.integers(0, R, (S, R, P, V, B))
    src = rng.integers(0, R, (S, R, P, V, B))
    cls = rng.integers(0, 2, (S, R, P, V, B))
    return rt.SubnetState(
        buf_meta=jnp.asarray(
            dest + (src << rt.META_SRC_SHIFT) + (cls << rt.META_CLS_SHIFT),
            jnp.int16,
        ),
        buf_binj=jnp.asarray(
            rng.integers(0, 5000, (S, R, P, V, B)), jnp.uint16
        ),
        head=jnp.asarray(rng.integers(0, B, (S, R, P, V)), jnp.int8),
        count=jnp.asarray(rng.integers(0, B + 1, (S, R, P, V)), jnp.int8),
        rr_ptr=jnp.asarray(rng.integers(0, P * V, (S, R, P)), jnp.int8),
    )


def _states_equal(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"state leaf {name}"
        )


def test_merged_inject_equals_separate_injects():
    """One inject over the union want-matrix == two per-kind injects.

    The cycle engine fuses the MC-reply and source injections into one
    `inject_all` pass; they target disjoint subnet rows, so the merged call
    must be exactly the composition of the separate ones.
    """
    rng = np.random.default_rng(7)
    S, R, V = 4, 36, 4
    state = _random_subnet_state(rng)
    sub_is_req = jnp.asarray([True, False, True, False])

    want_src = jnp.asarray(rng.random((S, R)) < 0.5) & sub_is_req[:, None]
    want_rep = jnp.asarray(rng.random((S, R)) < 0.5) & ~sub_is_req[:, None]
    dest = jnp.asarray(rng.integers(0, R, (S, R)), jnp.int32)
    src = jnp.asarray(rng.integers(0, R, (S, R)), jnp.int32)
    cls = jnp.asarray(rng.integers(0, 2, (S, R)), jnp.int32)
    binj = jnp.asarray(rng.integers(0, 5000, (S, R)), jnp.int32)
    gmask = jnp.asarray(rng.random((S, V)) < 0.7)
    cmask = jnp.asarray(rng.random((S, V)) < 0.7)

    merged, ok_m = rt.inject_all(
        state, want_src | want_rep, dest, src, cls, binj, gmask, cmask
    )
    step1, ok_rep = rt.inject_all(
        state, want_rep, dest, src, cls, binj, gmask, cmask
    )
    sep, ok_src = rt.inject_all(
        step1, want_src, dest, src, cls, binj, gmask, cmask
    )
    _states_equal(merged, sep)
    np.testing.assert_array_equal(np.asarray(ok_m), np.asarray(ok_rep | ok_src))


def test_packed_state_roundtrips_and_wrap_exact_latency():
    """Packed vs int32 state: every field a packet can carry survives the
    int16 meta pack exactly, and the uint16 injection stamps give the same
    latency as int32 arithmetic for every age the engine can produce."""
    R = make_topology().n_routers
    dest, src, cls = np.meshgrid(
        np.arange(R), np.arange(R), np.arange(2), indexing="ij"
    )
    d, s, c = (jnp.asarray(x.ravel(), jnp.int32) for x in (dest, src, cls))
    meta = rt.pack_meta(d, s, c)
    assert meta.dtype == jnp.int16
    d2, s2, c2 = rt.unpack_meta(meta)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(c))

    # wraparound-exact uint16 age: (cycle - binj) mod 2^16 == true age
    total = 60_001  # default paper run: 120 epochs x 500 cycles (+1 stamp)
    binj = jnp.asarray([0, 1, 40_000, 60_000, 65_000], jnp.uint16)
    cycle = jnp.int32(total - 1)
    age16 = (cycle.astype(jnp.uint16) - binj).astype(jnp.int32)
    true_age = cycle - jnp.asarray([0, 1, 40_000, 60_000, 65_000], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(age16)[true_age >= 0], np.asarray(true_age)[true_age >= 0]
    )


def test_policy_boundary_masks_flip_exactly_one_epoch_after_config():
    """Guard the epoch-level mask hoisting against an off-by-one epoch.

    `apply_policy_gated` runs at the END of epoch e, so the masks applied
    DURING epoch e must reflect `applied_config[e-1]` — never `[e]` (that
    would mean the hoist reads the config too early) and never `[e-2]`
    (stale by one).  `gpu_vc_quota` reports the hoisted mask the epoch
    actually used; with warmup/hold disabled the KF toggles mid-run.
    """
    # seed 2 toggles mid-run under jax's default (partitionable) PRNG stream
    cfg = NoCConfig(mode="kf", n_epochs=15, epoch_len=300, seed=2,
                    policy=PolicyConfig(warmup=0, hold=0, revert=10**9))
    res = sim.simulate(cfg, PROFILES["BFS"])
    conf = np.asarray(res.applied_config)
    quota = np.asarray(res.gpu_vc_quota)
    assert (np.diff(conf) != 0).any(), "scenario no longer toggles the KF"
    # kf-mode partitions: config 0 -> GPU {0,1} (2 VCs), config 1 -> 3 VCs
    used_config = np.concatenate([[0], conf[:-1]])
    np.testing.assert_array_equal(quota, np.where(used_config > 0, 3, 2))


# ---------------------------------------------------------------------------
# 3. ref <-> Pallas congruence (interpret mode off-TPU)
# ---------------------------------------------------------------------------

def _random_arbitrate_inputs(rng, lead, P=N_PORTS, V=4, B=4):
    PV = P * V
    gm = jnp.asarray(rng.random(lead[:-1] + (1, V)) < 0.7)
    cm = jnp.asarray(rng.random(lead[:-1] + (1, V)) < 0.7)
    return dict(
        valid=jnp.asarray(rng.random(lead + (PV,)) < 0.5),
        cls=jnp.asarray(rng.integers(0, 2, lead + (PV,)), jnp.int32),
        out_port=jnp.asarray(rng.integers(0, P, lead + (PV,)), jnp.int32),
        rr_ptr=jnp.asarray(rng.integers(0, PV, lead + (P,)), jnp.int32),
        down_count=jnp.asarray(
            rng.integers(0, B + 1, lead + (P, V)), jnp.int32
        ),
        down_exists=jnp.asarray(rng.random(lead + (P,)) < 0.8),
        gpu_vc_mask=jnp.broadcast_to(gm, lead + (V,)),
        cpu_vc_mask=jnp.broadcast_to(cm, lead + (V,)),
        sa_pref=jnp.asarray(rng.integers(-1, 2, lead), jnp.int32),
        accept=jnp.asarray(rng.random(lead) < 0.7),
        active=jnp.asarray(rng.random(lead) < 0.9),
    )


def _arbitrate_on_lanes(inp, depth):
    """`fused.lane_arbitrate` on `router.arbitrate`'s inputs: every leading
    dimension flattened onto the lane axis and padded to whole 128-lane
    blocks, as the fused kernel lays out `(S, R)`, and the answer cut back
    to `Arbitration`'s shapes."""
    from repro.kernels.noc_cycle import fused

    lead = inp["valid"].shape[:-1]
    lanes = int(np.prod(lead))
    pad = (-lanes) % 128
    pv = inp["valid"].shape[-1]
    o = inp["rr_ptr"].shape[-1]
    v = inp["down_count"].shape[-1]

    def to_lanes(x, tail=()):
        rows = int(np.prod(tail, dtype=int))
        x = jnp.broadcast_to(x, lead + tail).reshape(lanes, rows)
        return jnp.pad(x.astype(jnp.int32), ((0, pad), (0, 0))).T

    def back(x, tail, as_bool=False):
        x = x.T[:lanes].reshape(lead + tail)
        return x != 0 if as_bool else x

    arb = fused.lane_arbitrate(
        to_lanes(inp["valid"], (pv,)) != 0,
        to_lanes(inp["cls"], (pv,)),
        to_lanes(inp["out_port"], (pv,)),
        to_lanes(inp["rr_ptr"], (o,)),
        to_lanes(inp["down_count"], (o, v)),
        to_lanes(inp["down_exists"], (o,)) != 0,
        to_lanes(inp["gpu_vc_mask"], (v,)) != 0,
        to_lanes(inp["cpu_vc_mask"], (v,)) != 0,
        to_lanes(inp["sa_pref"]),
        to_lanes(inp["accept"]) != 0,
        to_lanes(inp["active"]) != 0,
        depth=depth,
    )
    return rt.Arbitration(
        grant=back(fused.stack_rows(arb.grant), (o,), as_bool=True),
        winner=back(jnp.concatenate(arb.winner, axis=0), (o,)),
        down_vc=back(jnp.concatenate(arb.down_vc, axis=0), (o,)),
        deq=back(arb.deq, (pv,), as_bool=True),
        new_rr=back(jnp.concatenate(arb.new_rr, axis=0), (o,)),
        any_req=back(fused.stack_rows(arb.any_req), (o,), as_bool=True),
        w_cls=back(jnp.concatenate(arb.w_cls, axis=0), (o,)),
    )


def test_lane_arbitrate_matches_ref_on_random_states():
    """`fused.lane_arbitrate`, the fused kernel's switch allocation, agrees
    with `router.arbitrate` on every `Arbitration` output — including the
    ragged lane tail (S*R = 144 pads up to the 256-lane grid)."""
    rng = np.random.default_rng(3)
    for lead in [(4, 36), (2, 36), (1, 7)]:
        inp = _random_arbitrate_inputs(rng, lead)
        ref = rt.arbitrate(**inp, depth=4)
        lane = _arbitrate_on_lanes(inp, depth=4)
        for name, a, b in zip(ref._fields, ref, lane):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"lead={lead}: arbitration output {name}",
            )


def test_simulate_pallas_backend_runs_fig2_3_smoke():
    """A `NoCConfig(backend="pallas")` run of a Fig. 2/3 grid point runs end
    to end and reproduces the `ref` engine bit-for-bit (the backend is its
    own `SimStatic`, so this never disturbs the paper sweep's single
    compiled program)."""
    tiny = dict(n_epochs=2, epoch_len=40)
    cfg = NoCConfig(mode="static", static_gpu_vcs=3, **tiny)
    ref = sim.simulate(cfg, PROFILES["PATH"])
    pal = sim.simulate(dataclasses.replace(cfg, backend="pallas"),
                       PROFILES["PATH"])
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref),
        jax.tree_util.tree_leaves_with_path(pal),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"leaf {jax.tree_util.keystr(path)}",
        )


@pytest.mark.parametrize("backend", ["cuda", "pallas_arb"])
def test_unknown_backend_rejected(backend):
    cfg = NoCConfig(mode="baseline", n_epochs=1, epoch_len=10,
                    backend=backend)
    with pytest.raises(ValueError, match=f"backend {backend!r}"):
        sim.simulate(cfg, PROFILES["PATH"])


@pytest.mark.parametrize(
    "platform,engine", [("cpu", "ref"), ("tpu", "pallas")])
def test_platform_picks_the_engine(monkeypatch, platform, engine):
    """With no engine named, the platform picks it: the fused kernel on a
    TPU, the dense engine elsewhere; a named engine is kept."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert NoCConfig().static_spec().backend == engine
    for named in ("ref", "pallas"):
        assert NoCConfig(backend=named).static_spec().backend == named


SEAM_CASES = {
    "4subnet": dict(mode="4subnet"),
    "kf-flap-guard": dict(mode="kf", faults="FLAP_DURING_SHIFT", guard=True),
    "probes": dict(mode="kf", probe=ProbeConfig(enabled=True)),
}


@pytest.mark.parametrize("case", list(SEAM_CASES))
def test_engines_agree_from_a_mid_run_carry(monkeypatch, case):
    """Both engines, called directly through the engine interface from one
    mid-run carry over one epoch's inputs, return the same carry, counters
    and probe accumulators bitwise.  The carry and the inputs are those a
    real `ref` run hands its engine, recorded on the host as the run goes:
    of the epoch with the most dead links, the latest if none has any."""
    cfg = NoCConfig(n_epochs=20, epoch_len=20, seed=1, backend="ref",
                    **SEAM_CASES[case])
    build = {name: sim._cycle_engine(name) for name in ("ref", "pallas")}
    seen = []

    def record(cycle0, *args):
        seen.append((int(cycle0), args))

    def spy_build(backend):
        def build_spy(stc, topo, fab, binj_dtype):
            run = build[backend](stc, topo, fab, binj_dtype)

            def run_spy(carry, ep):
                jax.debug.callback(record, ep.rows.cycle[0], fab, carry, ep)
                return run(carry, ep)
            return run_spy
        return build_spy

    monkeypatch.setattr(sim, "_cycle_engine", spy_build)
    stc, *args = sim.sim_args(cfg, "SHIFT_PATH_BFS")
    jax.block_until_ready(
        jax.jit(lambda *a: sim._simulate_impl(stc, *a))(*args))
    assert len(seen) == cfg.n_epochs
    cycle0, (fab, carry, ep) = max(
        seen, key=lambda rec: (np.sum(~rec[1][2].flt.link_ok), rec[0]))
    assert cycle0 > 0
    assert np.any(~ep.flt.link_ok) == (cfg.faults is not None)
    subs, mc, _, outstanding, _ = carry
    assert np.sum(subs.count) > 0 and np.sum(outstanding) > 0, "not mid-run"

    topo = make_topology(stc.width, stc.height, stc.n_mc)
    outs = {
        name: jax.jit(b(stc, topo, fab, subs.buf_binj.dtype))(carry, ep)
        for name, b in build.items()
    }
    ref, pal = outs["ref"], outs["pallas"]
    assert (ref[2] is None) == (not cfg.probe.enabled)
    assert jax.tree.structure(ref) == jax.tree.structure(pal)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref),
        jax.tree_util.tree_leaves_with_path(pal),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{case}: leaf {jax.tree_util.keystr(path)}",
        )


def test_cycles_per_launch_divides_the_epoch_and_fits_vmem():
    """K is the largest divisor of the epoch whose per-cycle input block
    fits the VMEM budget: 125 of the paper's 500 cycles, 1 for a prime
    epoch longer than the budget holds, the whole epoch when it fits."""
    from repro.kernels.noc_cycle import fused, kernel

    for S in (2, 4):
        d = _lane_dims(S=S)
        per_cycle = (kernel._vmem_bytes(fused.XI_ROWS, d.lanes_sr)
                     + kernel._vmem_bytes(fused.XF_ROWS, fused.LANES_R))
        for epoch_len in range(1, 1201):
            k = kernel.cycles_per_launch(d, epoch_len)
            assert epoch_len % k == 0
            assert k == 1 or k * per_cycle <= kernel.XS_VMEM_BUDGET
            assert not any(
                epoch_len % j == 0 and j * per_cycle <= kernel.XS_VMEM_BUDGET
                for j in range(k + 1, epoch_len + 1)), (S, epoch_len, k)
    d = _lane_dims()
    assert kernel.cycles_per_launch(d, 500) == 125
    assert kernel.cycles_per_launch(d, 173) == 1
    assert kernel.cycles_per_launch(d, 40) == 40


@pytest.mark.parametrize("probes", [False, True], ids=["probes-off",
                                                         "probes-on"])
@pytest.mark.parametrize("epoch_len,k", [
    (40, 40),   # one launch an epoch
    (180, 90),  # two launches an epoch: the carry crosses between them
    (173, 1),   # a prime epoch past the budget: one launch a cycle
], ids=["one-chunk", "two-chunks", "prime"])
def test_pallas_engine_chunks_match_ref(epoch_len, k, probes):
    """The `pallas` engine, K cycles a launch, reproduces the `ref`
    engine bitwise for every chunking of the epoch, probes off and on."""
    from repro.kernels.noc_cycle import kernel

    assert kernel.cycles_per_launch(_lane_dims(), epoch_len) == k
    cfg = NoCConfig(mode="kf", n_epochs=2, epoch_len=epoch_len, seed=3,
                    probe=ProbeConfig(enabled=probes))
    ref, pal = (
        sim.simulate(dataclasses.replace(cfg, backend=b), PROFILES["PATH"])
        for b in ("ref", "pallas"))
    assert jax.tree.structure(ref) == jax.tree.structure(pal)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(ref),
        jax.tree_util.tree_leaves_with_path(pal),
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"K={k}: leaf {jax.tree_util.keystr(path)}",
        )


def test_pallas_sweep_chunks_match_ref():
    """Under the sweep's `vmap` (a batch grid axis on every launch, the
    carry aliased in place per point): two launches an epoch, `pallas`
    against `ref` bitwise, point for point."""
    specs = [sim.SweepSpec(m, "BFS", seed=s)
             for m, s in (("kf", 0), ("fair", 1), ("4subnet", 2))]
    ref, pal = (sim.sweep(specs, n_epochs=2, epoch_len=180, backend=b)
                for b in ("ref", "pallas"))
    for i, (a, b) in enumerate(zip(ref, pal, strict=True)):
        for (path, x), (_, y) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b),
        ):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"point {i}: leaf {jax.tree_util.keystr(path)}",
            )


# ---------------------------------------------------------------------------
# 4. fused full-cycle kernel (DESIGN.md §13): golden pinning + stage twins
# ---------------------------------------------------------------------------

def _lane_dims(S=4, V=4, B=4):
    from repro.kernels.noc_cycle import fused

    return fused.lane_dims(
        S=S, R=36, V=V, B=B, Q=16, width=6, mc_service_period=2,
        mshr_limit=16, bcap=64, stamp_mask=0xFFFF,
    )


def _sv_mask_rows(x):
    """Per-subnet (S, V) bool masks -> (V, S*64) int32 lane rows (more
    general than the engine's own subnet-uniform masks — the stage twins
    must honor per-lane variation)."""
    from repro.kernels.noc_cycle import fused

    S, V = x.shape
    return jnp.concatenate(
        [
            jnp.broadcast_to(x[s].astype(jnp.int32)[:, None], (V, fused.R_PAD))
            for s in range(S)
        ],
        axis=1,
    )


def _sr_row(x, R=36):
    """(S, R) -> (1, S*64) int32 lane row."""
    from repro.kernels.noc_cycle import fused

    x = jnp.pad(x.astype(jnp.int32), ((0, 0), (0, fused.R_PAD - R)))
    return x.reshape(1, -1)


def test_fused_backend_matches_golden_capture():
    """The fused kernel runs the golden grid (static/baseline/4subnet/kf x
    workloads) bitwise-identical to the PR-3 capture — the engine-level
    acceptance gate for `backend="pallas"`."""
    with open(GOLDEN_PATH) as f:
        golden = json.load(f)["cases"]
    for key, g in golden.items():
        mode, wl, gs, ss = key.split("/")
        cfg = NoCConfig(mode=mode, static_gpu_vcs=int(gs[1:]),
                        seed=int(ss[1:]), **FAST)
        res = sim.simulate(dataclasses.replace(cfg, backend="pallas"),
                           PROFILES[wl])
        sums = {n: int(np.sum(np.asarray(leaf)))
                for n, leaf in zip(res.counters._fields, res.counters)}
        assert sums == g["counter_sums"], f"{key}: fused counter drift"
        assert np.asarray(res.applied_config).tolist() == g["applied_config"]
        assert np.asarray(res.kf_signal).tolist() == g["kf_signal"]
        np.testing.assert_allclose(
            float(np.asarray(res.avg_latency)[-1]), g["avg_latency_last"],
            rtol=0, atol=1e-6, err_msg=key,
        )


def test_fused_pack_unpack_roundtrip():
    """Lane pack -> unpack is the identity on every carry leaf (localizes
    layout/transpose bugs away from the stage math)."""
    from repro.kernels.noc_cycle import fused

    rng = np.random.default_rng(5)
    d = _lane_dims()
    R, Q = 36, 16
    subs = _random_subnet_state(rng)
    mc = sim.MCState(
        q_meta=jnp.asarray(rng.integers(0, 100, (R, Q)), jnp.int8),
        head=jnp.asarray(rng.integers(0, Q, (R,)), jnp.int32),
        count=jnp.asarray(rng.integers(0, Q + 1, (R,)), jnp.int32),
        timer=jnp.asarray(rng.integers(0, 3, (R,)), jnp.int32),
        stage_valid=jnp.asarray(rng.random((R,)) < 0.5),
        stage_dst=jnp.asarray(rng.integers(0, R, (R,)), jnp.int32),
        stage_cls=jnp.asarray(rng.integers(0, 2, (R,)), jnp.int32),
    )
    outst = jnp.asarray(rng.integers(0, 16, (R,)), jnp.int32)
    backlog = jnp.asarray(rng.integers(0, 64, (R,)), jnp.int32)
    phase = jnp.int32(1)

    ls = fused.pack_state(d, subs, mc, outst, backlog, phase)
    subs2, mc2, outst2, backlog2, phase2 = fused.unpack_state(
        d, ls, sim.MCState, subs.buf_binj.dtype
    )
    _states_equal(subs, subs2)
    _states_equal(mc, mc2)
    np.testing.assert_array_equal(np.asarray(outst), np.asarray(outst2))
    np.testing.assert_array_equal(np.asarray(backlog), np.asarray(backlog2))
    assert int(phase2) == int(phase)


def test_fused_inject_stage_matches_inject_all():
    """`fused.inject_lanes` == `router.inject_all` on random states with
    per-subnet VC masks: buffer writes, counts, and the ok row."""
    from repro.kernels.noc_cycle import fused

    rng = np.random.default_rng(13)
    d = _lane_dims()
    S, R, V = 4, 36, 4
    subs = _random_subnet_state(rng)
    want = jnp.asarray(rng.random((S, R)) < 0.6)
    dest = jnp.asarray(rng.integers(0, R, (S, R)), jnp.int32)
    src = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32), (S, R))
    cls = jnp.asarray(rng.integers(0, 2, (S, R)), jnp.int32)
    binj = jnp.asarray(rng.integers(0, 5000, (S, R)), jnp.int32)
    gmask = jnp.asarray(rng.random((S, V)) < 0.7)
    cmask = jnp.asarray(rng.random((S, V)) < 0.7)

    ref_state, ref_ok = rt.inject_all(
        subs, want, dest, src, cls, binj, gmask, cmask
    )

    ls = fused.pack_state(
        d, subs,
        sim.MCState(*[jnp.zeros((R, 16), jnp.int8)]
                    + [jnp.zeros((R,), jnp.int32)] * 3
                    + [jnp.zeros((R,), bool)]
                    + [jnp.zeros((R,), jnp.int32)] * 2),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32), jnp.int32(0),
    )
    src_lane = jax.lax.broadcasted_iota(jnp.int32, (1, d.lanes_sr), 1) % 64
    bm, bb, ct, ok = fused.inject_lanes(
        d, ls.buf_meta, ls.buf_binj, ls.head, ls.count,
        _sr_row(want) != 0, _sr_row(dest), src_lane, _sr_row(cls),
        _sr_row(binj), _sv_mask_rows(gmask) != 0, _sv_mask_rows(cmask) != 0,
    )
    lane_state, *_ = fused.unpack_state(
        d, ls._replace(buf_meta=bm, buf_binj=bb, count=ct),
        sim.MCState, subs.buf_binj.dtype,
    )
    _states_equal(ref_state, lane_state)
    ok_sr = np.asarray(ok).reshape(S, 64)[:, :R]
    np.testing.assert_array_equal(ok_sr, np.asarray(ref_ok))


def test_fused_mc_service_stage_matches_dense():
    """`fused.mc_service_lanes` == the dense cycle_body MC-service stage
    (timers, queue-head unpack, ring advance, staging)."""
    from repro.kernels.noc_cycle import fused

    rng = np.random.default_rng(17)
    d = _lane_dims()
    topo = make_topology()
    R, Q, period = topo.n_routers, 16, 2
    ntype = jnp.asarray(topo.node_type)
    is_mc = ntype == 2
    mc = sim.MCState(
        q_meta=jnp.asarray(rng.integers(0, 100, (R, Q)), jnp.int8),
        head=jnp.asarray(rng.integers(0, Q, (R,)), jnp.int32),
        count=jnp.asarray(rng.integers(0, Q + 1, (R,)), jnp.int32),
        timer=jnp.asarray(rng.integers(0, 3, (R,)), jnp.int32),
        stage_valid=jnp.asarray(rng.random((R,)) < 0.3),
        stage_dst=jnp.asarray(rng.integers(0, R, (R,)), jnp.int32),
        stage_cls=jnp.asarray(rng.integers(0, 2, (R,)), jnp.int32),
    )

    # dense twin: cycle_body stage 1 verbatim
    can_serve = is_mc & (mc.count > 0) & ~mc.stage_valid
    timer = jnp.where(can_serve, jnp.maximum(mc.timer - 1, 0), mc.timer)
    done = can_serve & (timer == 0)
    q_head = jnp.take_along_axis(
        mc.q_meta, mc.head[:, None], axis=1
    )[:, 0].astype(jnp.int32)
    src_out = q_head & ((1 << rt.META_SRC_SHIFT) - 1)
    cls_out = q_head >> rt.META_SRC_SHIFT
    ref = sim.MCState(
        q_meta=mc.q_meta,
        head=jnp.where(done, (mc.head + 1) % Q, mc.head),
        count=mc.count - done.astype(jnp.int32),
        timer=jnp.where(done, period, timer),
        stage_valid=mc.stage_valid | done,
        stage_dst=jnp.where(done, src_out, mc.stage_dst),
        stage_cls=jnp.where(done, cls_out, mc.stage_cls),
    )

    ls = fused.pack_state(
        d, _random_subnet_state(rng), mc,
        jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32), jnp.int32(0),
    )
    ntype_row = jnp.pad(ntype, (0, 128 - R), constant_values=-1)[None, :]
    head, count, timer_l, svalid, sdst, scls = fused.mc_service_lanes(
        d, ls.mc, ls.mcq, ntype_row
    )
    for name, ref_v, lane_row in [
        ("head", ref.head, head), ("count", ref.count, count),
        ("timer", ref.timer, timer_l),
        ("stage_valid", ref.stage_valid, svalid),
        ("stage_dst", ref.stage_dst, sdst),
        ("stage_cls", ref.stage_cls, scls),
    ]:
        np.testing.assert_array_equal(
            np.asarray(ref_v).astype(np.int32),
            np.asarray(lane_row)[0, :R].astype(np.int32),
            err_msg=f"mc service field {name}",
        )


def test_fused_router_stage_matches_router_cycle():
    """`fused.router_stage_lanes` == `router.router_cycle` on random states:
    buffer dequeue/enqueue writes, RR pointers, and every event field
    (including the garbage-site convention on eject_src/cls/binj)."""
    from repro.kernels.noc_cycle import fused

    rng = np.random.default_rng(23)
    d = _lane_dims()
    topo = make_topology()
    R = topo.n_routers
    route_t, nb_t, opp_t, _, _ = rt.device_tables(topo)
    S, V = 4, 4
    subs = _random_subnet_state(rng)
    gmask = jnp.asarray(rng.random((S, V)) < 0.7)
    cmask = jnp.asarray(rng.random((S, V)) < 0.7)
    sa = jnp.int32(1)
    accept = jnp.asarray(rng.random((S, R)) < 0.8)
    active = jnp.asarray([True, True, False, True])

    ref_state, ref_ev = rt.router_cycle(
        subs, route_t, nb_t, opp_t, gmask, cmask, sa, accept, active
    )

    ls = fused.pack_state(
        d, subs,
        sim.MCState(*[jnp.zeros((R, 16), jnp.int8)]
                    + [jnp.zeros((R,), jnp.int32)] * 3
                    + [jnp.zeros((R,), bool)]
                    + [jnp.zeros((R,), jnp.int32)] * 2),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32), jnp.int32(0),
    )
    route_rows, exists_rows, _ = fused.run_consts(d, topo)
    active_rows = jnp.repeat(active.astype(jnp.int32), fused.R_PAD)[None, :]
    sa_row = jnp.full((1, d.lanes_sr), sa, jnp.int32)
    (bm, bb, hd, ct, rr2, ej, e_src, e_cls, e_binj, moved, dram_gpu,
     grant_cnt, deny_cnt,
     ) = fused.router_stage_lanes(
        d, ls.buf_meta, ls.buf_binj, ls.head, ls.count, ls.rr,
        _sv_mask_rows(gmask) != 0, _sv_mask_rows(cmask) != 0,
        sa_row, _sr_row(accept) != 0, active_rows != 0,
        route_rows, exists_rows != 0,
    )
    lane_state, *_ = fused.unpack_state(
        d, ls._replace(buf_meta=bm, buf_binj=bb, head=hd, count=ct, rr=rr2),
        sim.MCState, subs.buf_binj.dtype,
    )
    _states_equal(ref_state, lane_state)

    def sr(row):
        return np.asarray(row).reshape(S, 64)[:, :R]

    np.testing.assert_array_equal(sr(ej), np.asarray(ref_ev.eject_valid))
    np.testing.assert_array_equal(sr(e_src), np.asarray(ref_ev.eject_src))
    np.testing.assert_array_equal(sr(e_cls), np.asarray(ref_ev.eject_cls))
    np.testing.assert_array_equal(
        sr(e_binj), np.asarray(ref_ev.eject_binj).astype(np.int32)
    )
    assert int(moved) == int(ref_ev.moved)
    assert int(dram_gpu) == int(ref_ev.dram_block_gpu)
    # probe rows (DESIGN.md §14): the lane twin of CycleEvents.grant_cnt
    # and deny_cnt must agree even when probes are off (they feed the
    # flight recorder only when ProbeConfig.enabled compiles them in)
    np.testing.assert_array_equal(sr(grant_cnt), np.asarray(ref_ev.grant_cnt))
    np.testing.assert_array_equal(sr(deny_cnt), np.asarray(ref_ev.deny_cnt))


def test_fused_single_cycle_counters_match_ref():
    """One-cycle runs pin the counter-update stage: every EpochCounters
    lane agrees with the dense engine after exactly one simulated cycle
    (and after three, covering the carry add)."""
    for mode, ep_len in [("kf", 1), ("4subnet", 1), ("kf", 3)]:
        cfg = NoCConfig(mode=mode, n_epochs=1, epoch_len=ep_len, seed=3)
        ref = sim.simulate(cfg, PROFILES["BFS"])
        pal = sim.simulate(dataclasses.replace(cfg, backend="pallas"),
                           PROFILES["BFS"])
        for name, a, b in zip(
            ref.counters._fields, ref.counters, pal.counters
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{mode}/L{ep_len}: counter {name}",
            )
