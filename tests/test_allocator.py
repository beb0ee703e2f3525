"""Tests for the reconfiguration policy (paper §3.2 rules, §3.3 tables)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests are optional; unit tests run without hypothesis
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    hypothesis = None

from repro.core.allocator import (
    PolicyConfig,
    apply_policy,
    apply_policy_gated,
    class_vc_masks,
    init_policy_state,
    mode_policy,
    sa_priority_pattern,
    vc_partition,
)

CFG = PolicyConfig(warmup=10_000, hold=5_000, revert=10_000)


def run_policy(signals_cycles, cfg=CFG):
    """Apply the policy at (signal, cycle) pairs, returning applied configs."""
    st_ = init_policy_state()
    out = []
    for sig, cyc in signals_cycles:
        st_ = apply_policy(cfg, st_, jnp.int32(sig), jnp.int32(cyc))
        out.append(int(st_.config))
    return out


def test_warmup_blocks_reconfiguration():
    # paper: KF not activated until 10,000 cycles after start
    configs = run_policy([(1, 1_000), (1, 5_000), (1, 9_999)])
    assert configs == [0, 0, 0]
    configs = run_policy([(1, 10_000)])
    assert configs == [1]


def test_hold_prevents_flapping():
    # after a change, configuration is frozen for >= 5,000 cycles
    configs = run_policy([(1, 10_000), (0, 12_000), (0, 14_999), (0, 15_000)])
    assert configs == [1, 1, 1, 0]


def test_revert_rule():
    # staying boosted for > 10,000 cycles forces a fallback to equal share
    configs = run_policy([(1, 10_000), (1, 15_000), (1, 20_001)])
    assert configs == [1, 1, 0]


def test_vc_partition_tables():
    g0, c0 = vc_partition(jnp.int32(0), 4)
    np.testing.assert_array_equal(g0, [True, True, False, False])
    np.testing.assert_array_equal(c0, [False, False, True, True])
    g1, c1 = vc_partition(jnp.int32(1), 4)
    np.testing.assert_array_equal(g1, [True, True, True, False])
    np.testing.assert_array_equal(c1, [False, False, False, True])


@pytest.mark.parametrize("n_vcs", [1, 2, 3, 4, 8])
def test_vc_partition_splits_any_vc_count(n_vcs):
    """Host masks for any VC count: the GPU class holds the low n/2 VCs at
    config 0 and the low n-1 at config 1, the CPU class the rest."""
    for config, n_gpu in ((0, n_vcs // 2), (1, n_vcs - 1)):
        g, c = vc_partition(config, n_vcs)
        for m in (g, c):
            assert type(m) is np.ndarray and m.dtype == np.bool_
        np.testing.assert_array_equal(g, np.arange(n_vcs) < n_gpu)
        np.testing.assert_array_equal(c, ~g)


def test_mode_policy_tables():
    """The traced policy tensors reproduce each mode's trace-time branches."""
    mp = mode_policy("baseline", 4)
    np.testing.assert_array_equal(mp.gpu_mask0, [True] * 4)  # fully shared
    np.testing.assert_array_equal(mp.cpu_mask0, [True] * 4)
    assert not bool(mp.kf_enable) and not bool(mp.sa_enable)

    mp = mode_policy("fair", 4)
    np.testing.assert_array_equal(mp.gpu_mask0, [True, True, False, False])

    mp = mode_policy("static", 4, static_gpu_vcs=3)
    np.testing.assert_array_equal(mp.gpu_mask0, [True, True, True, False])
    np.testing.assert_array_equal(mp.cpu_mask0, [False, False, False, True])

    mp = mode_policy("kf", 4)
    assert bool(mp.kf_enable) and bool(mp.sa_enable)
    g0, c0 = class_vc_masks(mp, jnp.int32(0))
    g1, c1 = class_vc_masks(mp, jnp.int32(1))
    np.testing.assert_array_equal(g0, [True, True, False, False])
    np.testing.assert_array_equal(g1, [True, True, True, False])
    assert bool(jnp.all(g0 ^ c0)) and bool(jnp.all(g1 ^ c1))

    with pytest.raises(ValueError):
        mode_policy("bogus", 4)


def test_apply_policy_gated_is_noop_when_disabled():
    mp_off = mode_policy("fair", 4)
    mp_on = mode_policy("kf", 4)
    st0 = init_policy_state()
    sig, cyc = jnp.int32(1), jnp.int32(20_000)
    off = apply_policy_gated(CFG, mp_off, st0, sig, cyc)
    on = apply_policy_gated(CFG, mp_on, st0, sig, cyc)
    assert int(off.config) == 0
    assert int(off.last_change) == int(st0.last_change)
    assert int(off.boosted_since) == int(st0.boosted_since)
    assert int(on.config) == 1


def test_sa_pattern():
    # config 0: round robin (-1); config 1: GPU,GPU,CPU repeating
    assert int(sa_priority_pattern(jnp.int32(0), jnp.int32(0))) == -1
    pat = [int(sa_priority_pattern(jnp.int32(1), jnp.int32(c))) for c in range(6)]
    assert pat == [1, 1, 0, 1, 1, 0]


if hypothesis is not None:

    @hypothesis.given(
        sigs=st.lists(st.integers(0, 1), min_size=1, max_size=60),
        step=st.integers(100, 3_000),
    )
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_property_partition_disjoint_and_complete(sigs, step):
        """At every reachable policy state the VC masks partition the VC set,
        so no VC is ever unowned (deadlock) or double-owned (class mixing)."""
        st_ = init_policy_state()
        for i, sig in enumerate(sigs):
            st_ = apply_policy(CFG, st_, jnp.int32(sig), jnp.int32(i * step))
            g, c = vc_partition(st_.config, 4)
            assert bool(jnp.all(g ^ c))  # disjoint and covering

    @hypothesis.given(
        sigs=st.lists(st.integers(0, 1), min_size=2, max_size=80),
    )
    @hypothesis.settings(max_examples=30, deadline=None)
    def test_property_no_change_within_hold(sigs):
        """Reallocation intervals respect the paper's 5,000-cycle minimum,
        except the revert rule which may only move config back to 0."""
        st_ = init_policy_state()
        prev_cfg, prev_change_cycle = 0, None
        for i, sig in enumerate(sigs):
            cycle = 10_000 + i * 1_000
            st_ = apply_policy(CFG, st_, jnp.int32(sig), jnp.int32(cycle))
            cfg_now = int(st_.config)
            if cfg_now != prev_cfg:
                if prev_change_cycle is not None:
                    gap = cycle - prev_change_cycle
                    assert gap >= CFG.hold or cfg_now == 0  # revert is exempt
                prev_change_cycle = cycle
            prev_cfg = cfg_now

else:

    def test_property_suite_needs_hypothesis():
        pytest.skip("hypothesis not installed (pip install -e .[test])")


def test_starvation_freedom_of_sa_pattern():
    """Even in boosted mode the CPU gets a guaranteed arbitration phase."""
    prefs = [int(sa_priority_pattern(jnp.int32(1), jnp.int32(c))) for c in range(30)]
    assert prefs.count(0) == 10  # one CPU phase per 3 cycles
