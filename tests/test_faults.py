"""Fault-injection + self-healing KF suite (DESIGN.md §16).

Pins the robustness layer from four sides:

  1. fault model — `FaultSchedule` validation, flap periodicity, and the
     symmetric (both-directions) link masking;
  2. zero-cost healthy path — faults=None and an armed-but-idle guard are
     bitwise the pre-fault program, and the healthy x faulty x guarded
     grid still compiles exactly ONE simulate trace;
  3. backend congruence — every registered fault scenario produces a
     bitwise-identical SimResult AND SimTrace on ref and pallas (fault
     masks ride the same lane contract as the architectural state);
  4. self-healing semantics — the innovation gate rejects corrupted
     telemetry, the watchdog resets a poisoned filter, the allocator
     falls back to the fair split while unhealthy and recovers after.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.noc import sim
from repro.core.noc.faults import (
    FAULTS,
    TELEM_NAN,
    TELEM_SPIKE,
    FaultEvent,
    FaultSchedule,
    healthy_stream,
    lookup_faults,
    resolve_faults,
)
from repro.core.noc.sim import NoCConfig, SweepSpec
from repro.core.noc.topology import (
    PORT_L,
    PORT_N,
    PORT_S,
    make_topology,
)

TINY = dict(n_epochs=8, epoch_len=80)
BACKENDS = ("ref", "pallas")


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
# 1. fault model: schedule validation + materialization
# ---------------------------------------------------------------------------

class TestFaultSchedule:
    def test_rejects_bad_events(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultSchedule((FaultEvent(0.0, 0.5, "gamma_ray"),))
        with pytest.raises(ValueError, match="outside"):
            FaultSchedule((FaultEvent(0.5, 0.4, "link"),))
        with pytest.raises(ValueError, match="outside"):
            FaultSchedule((FaultEvent(-0.1, 0.5, "mc"),))
        with pytest.raises(ValueError, match="period"):
            FaultSchedule((FaultEvent(0.0, 0.5, "link", period=-1),))
        with pytest.raises(ValueError, match="telem fault mode"):
            FaultSchedule((FaultEvent(0.0, 0.5, "telem", mode=9),))
        with pytest.raises(ValueError, match="only the four mesh ports"):
            FaultSchedule((
                FaultEvent(0.0, 0.5, "link", ports=(PORT_L,)),
            ))

    def test_rejects_out_of_range_routers_at_materialize(self):
        sched = FaultSchedule((FaultEvent(0.0, 0.5, "router",
                                          routers=(99,)),))
        with pytest.raises(ValueError, match="outside"):
            sched.materialize(8)

    def test_flap_period_alternates(self):
        """period=2 in [0, 1): 2 epochs down, 2 up, repeating."""
        sched = FaultSchedule((
            FaultEvent(0.0, 1.0, "router", routers=(5,), period=2),
        ))
        stream = sched.materialize(8)
        down = ~np.asarray(stream.router_ok)[:, 5]
        assert down.tolist() == [True, True, False, False,
                                 True, True, False, False]

    def test_link_fault_masks_both_directions(self):
        """With a neighbor table, router 8's dead N link also masks the
        reverse (S) direction at its northern neighbor."""
        topo = make_topology()
        sched = FaultSchedule((
            FaultEvent(0.0, 1.0, "link", routers=(8,), ports=(PORT_N,)),
        ))
        stream = sched.materialize(
            4, neighbor=np.asarray(topo.neighbor),
            opposite=np.asarray(topo.opposite))
        link_ok = np.asarray(stream.link_ok)
        nb = int(np.asarray(topo.neighbor)[8, PORT_N])
        assert nb >= 0
        assert not link_ok[:, 8, PORT_N].any()
        assert not link_ok[:, nb, PORT_S].any()
        # nothing else is masked
        assert link_ok.sum() == link_ok.size - 2 * 4

    def test_lookup_suggests_near_miss(self):
        with pytest.raises(ValueError, match="FLAP_BFS"):
            lookup_faults("FLAP_BFSS")

    def test_resolve_rejects_wrong_shape_stream(self):
        stream = healthy_stream(6)
        with pytest.raises(ValueError, match="link_ok"):
            resolve_faults(stream, 8)

    def test_healthy_stream_is_identity(self):
        stream = healthy_stream(5)
        assert np.asarray(stream.link_ok).all()
        assert np.asarray(stream.router_ok).all()
        assert np.asarray(stream.mc_ok).all()
        assert not np.asarray(stream.telem_mode).any()


# ---------------------------------------------------------------------------
# 2. zero-cost healthy path
# ---------------------------------------------------------------------------

def test_fault_none_bitwise_equals_explicit_healthy_stream():
    cfg = NoCConfig(mode="kf", seed=3, **TINY)
    res_none = sim.simulate(cfg, "SHIFT_PATH_BFS")
    explicit = dataclasses.replace(
        cfg, faults=healthy_stream(TINY["n_epochs"]))
    res_stream = sim.simulate(explicit, "SHIFT_PATH_BFS")
    _bitwise_equal(res_none, res_stream, "faults=None vs healthy_stream")


def test_fault_guard_armed_but_idle_is_bitwise_free():
    """Clean telemetry: the armed guard's innovation gate never fires, so
    guard=True is bit-for-bit guard=False."""
    cfg = NoCConfig(mode="kf", seed=3, **TINY)
    res_off = sim.simulate(cfg, "SHIFT_PATH_BFS")
    res_on = sim.simulate(dataclasses.replace(cfg, guard=True),
                          "SHIFT_PATH_BFS")
    _bitwise_equal(res_on, res_off, "guard on vs off (healthy)")


def test_fault_grid_shares_one_simulate_trace():
    """Healthy + every fault scenario x guard settings: one compiled
    program (fault masks are scan xs, guard knobs are traced policy)."""
    specs = [SweepSpec("kf", "SHIFT_PATH_BFS", seed=0, faults=f, guard=g)
             for f in (None, *FAULTS) for g in (False, True)]
    sim.reset_trace_count()
    rows = sim.sweep(specs, **TINY)
    assert sim.trace_count() == 1
    assert len(rows) == len(specs)


# ---------------------------------------------------------------------------
# 3. backend congruence under faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=sorted(FAULTS))
def fault_runs(request):
    """One probed guarded run per backend for a given fault scenario."""
    cfg = NoCConfig(mode="kf", seed=1, guard=True,
                    faults=request.param, **TINY)
    return request.param, {
        be: sim.simulate_with_trace(dataclasses.replace(cfg, backend=be),
                                    "SHIFT_PATH_BFS")
        for be in BACKENDS
    }


def test_fault_scenarios_backend_congruent(fault_runs):
    """SimResult AND SimTrace bitwise across ref/pallas for every
    registered fault scenario."""
    name, runs = fault_runs
    res_ref, tr_ref = runs["ref"]
    for be in BACKENDS[1:]:
        res_be, tr_be = runs[be]
        _bitwise_equal(res_ref, res_be, f"{name}: SimResult ref vs {be}")
        _bitwise_equal(tr_ref, tr_be, f"{name}: SimTrace ref vs {be}")


def test_fault_scenarios_perturb_the_run(fault_runs):
    """Every scenario actually does something: fault epochs are recorded,
    and either the result differs from the healthy run (physical faults)
    or the guard visibly handled telemetry corruption (a successfully
    absorbed telem-only glitch may leave the RESULT bitwise-healthy —
    that is the guard working, so the trace must show the rejections)."""
    name, runs = fault_runs
    res, tr = runs["ref"]
    assert int(np.asarray(tr.faults_active).sum()) > 0
    healthy = sim.simulate(
        NoCConfig(mode="kf", seed=1, guard=True, **TINY), "SHIFT_PATH_BFS")
    diff = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(res), jax.tree.leaves(healthy))
    )
    handled = int(np.asarray(tr.kf_rejected).sum()) > 0
    assert diff or handled, (
        f"{name}: faulty run bitwise-equal to healthy with no guard "
        "activity")


# ---------------------------------------------------------------------------
# 4. fault semantics in the fabric
# ---------------------------------------------------------------------------

def test_fault_brownout_routers_grant_nothing():
    """During a brownout window the affected routers issue zero grants
    (no traversal, no ejection) and recover afterwards."""
    routers = (14, 15)
    sched = FaultSchedule((
        FaultEvent(0.25, 0.75, "router", routers=routers),
    ))
    cfg = NoCConfig(mode="baseline", seed=0, faults=sched, **TINY)
    _, tr = sim.simulate_with_trace(cfg, "PATH")
    grants = np.asarray(tr.arb_grant)  # (E, S, R)
    lo, hi = 2, 6  # round(0.25 * 8), round(0.75 * 8)
    assert grants[lo:hi, :, routers].sum() == 0
    assert grants[hi:, :, routers].sum() > 0  # traffic resumes


def test_fault_masked_flits_backpressure_not_vanish():
    """Flit conservation under link faults: completions never exceed
    injections, and the blocked traffic WAITS (latency rises vs healthy)
    rather than vanishing."""
    sched = FaultSchedule((
        FaultEvent(0.25, 0.5, "link", routers=(8, 9)),
    ))
    cfg = NoCConfig(mode="baseline", seed=0, faults=sched, **TINY)
    res = sim.simulate(cfg, "PATH")
    healthy = sim.simulate(dataclasses.replace(cfg, faults=None), "PATH")
    c = res.counters
    injected = int(np.asarray(c.gpu_push).sum() +
                   np.asarray(c.cpu_push).sum())
    completed = int(np.asarray(c.gpu_done).sum() +
                    np.asarray(c.cpu_done).sum())
    assert 0 < completed <= injected
    assert (float(np.asarray(res.avg_latency)[-1])
            > float(np.asarray(healthy.avg_latency)[-1]))


def test_fault_mc_stall_freezes_service():
    """An all-MC stall for the whole run: memory service is frozen, so
    transaction completions collapse vs the healthy run (queues
    back-pressure instead of dropping)."""
    stall = FaultSchedule((FaultEvent(0.0, 1.0, "mc"),))
    cfg = NoCConfig(mode="baseline", seed=0, faults=stall, **TINY)
    res = sim.simulate(cfg, "PATH")
    healthy = sim.simulate(dataclasses.replace(cfg, faults=None), "PATH")
    assert (int(np.asarray(res.counters.gpu_done).sum())
            < int(np.asarray(healthy.counters.gpu_done).sum()) // 2)


# ---------------------------------------------------------------------------
# 5. self-healing KF semantics
# ---------------------------------------------------------------------------

def _nan_window(start=0.25, stop=0.75):
    return FaultSchedule((FaultEvent(start, stop, "telem",
                                     mode=TELEM_NAN),))


def test_fault_telem_nan_unguarded_poisons_filter():
    cfg = NoCConfig(mode="kf", seed=0, faults=_nan_window(), **TINY)
    _, tr = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
    assert not np.isfinite(np.asarray(tr.kf_x_pred)).all()
    # NaN NIS compares False against the threshold: the unguarded filter
    # never rejects and never resets
    assert int(np.asarray(tr.kf_rejected).sum()) == 0
    assert int(np.asarray(tr.kf_reset).sum()) == 0


def test_fault_telem_nan_guarded_stays_finite_and_recovers():
    cfg = NoCConfig(mode="kf", seed=0, guard=True,
                    faults=_nan_window(), **TINY)
    _, tr = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
    assert np.isfinite(np.asarray(tr.kf_x_pred)).all()
    assert np.isfinite(np.asarray(tr.kf_cov_trace)).all()
    rejected = np.asarray(tr.kf_rejected)
    healthy = np.asarray(tr.kf_healthy)
    lo, hi = 2, 6
    assert rejected[lo:hi].sum() == hi - lo  # every NaN epoch gated
    # watchdog declares unhealthy after watchdog_limit consecutive
    # rejections -> fair-split fallback epochs are recorded ...
    assert (healthy == 0).sum() > 0
    assert int(np.asarray(tr.kf_reset).sum()) >= 1
    # ... and health returns once telemetry is clean again
    assert healthy[-1] == 1


def test_fault_telem_spike_rejected_by_innovation_gate():
    """A +8 spike on normalized-to-[-1, 1] observations is far past the
    NIS threshold: the guarded filter coasts through it and its posterior
    keeps tracking the clean prediction."""
    spike = FaultSchedule((
        FaultEvent(0.5, 0.625, "telem", mode=TELEM_SPIKE, mag=8.0),
    ))
    cfg = NoCConfig(mode="kf", seed=0, guard=True, faults=spike, **TINY)
    _, tr = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
    assert int(np.asarray(tr.kf_rejected)[4:5].sum()) == 1
    # the spiked epoch's NIS is enormous; clean epochs stay modest
    nis = np.asarray(tr.kf_nis)
    assert nis[4] > 50.0


def test_fault_fallback_is_fair_split():
    """While unhealthy, the allocator pins the fair static split: the
    applied config is 0 in every fallback epoch."""
    cfg = NoCConfig(mode="kf", seed=0, guard=True,
                    faults=_nan_window(), **TINY)
    res, tr = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
    healthy = np.asarray(tr.kf_healthy)
    applied = np.asarray(res.applied_config)
    # applied_config[e] records epoch e's post-degrade decision (the VC
    # masks flip one epoch later): every unhealthy epoch decides config 0
    assert (healthy == 0).any()
    assert (applied[healthy == 0] == 0).all()


def test_fault_summarize_trace_counts():
    cfg = NoCConfig(mode="kf", seed=0, guard=True,
                    faults="TELEM_GLITCH", **TINY)
    _, tr = sim.simulate_with_trace(cfg, "SHIFT_PATH_BFS")
    from repro.obs.probes import summarize_trace

    s = summarize_trace(tr)
    assert s["fault_epochs"] == int((np.asarray(tr.faults_active) > 0).sum())
    assert s["kf_rejected_total"] == int(np.asarray(tr.kf_rejected).sum())
    assert s["kf_reset_total"] == int(np.asarray(tr.kf_reset).sum())
    assert s["fallback_epochs"] == int((np.asarray(tr.kf_healthy) == 0).sum())
