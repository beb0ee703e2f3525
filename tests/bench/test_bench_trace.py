"""The reduction from a profiler trace to the per-layer metrics."""
from __future__ import annotations

import gzip
import random
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import kernels, run, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
RECORDED = ROOT / "bench" / "testdata" / "tiny_v5e.xplane.pb.gz"
KERNEL = ('%closed_call.51 = (s32[6,80,256]{2,1,0:T(8,128)S(1)}) custom-call('
          's32[6,6,256]{2,1,0} %x), custom_call_target="tpu_custom_call"')


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def profile(device_ops, host):
    """A stand-in for `jax.profiler.ProfileData`: device planes with an
    `XLA Ops` line each, and one host thread."""
    planes = [NS(name=f"/device:TPU:{d}", lines=[
        NS(name="XLA Modules", events=[ev("jit_x", 0, 10**9)]),
        NS(name="XLA Ops", events=[ev(*o) for o in ops])])
        for d, ops in device_ops.items()]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*h) for h in host])]))
    return NS(planes=planes)


def naive_union(intervals):
    """Covered length by counting boundaries (another algorithm)."""
    edges = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in
                                                       intervals])
    depth, last, total = 0, None, 0
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.mark.parametrize("seed", range(5))
def test_union_and_gaps_agree_with_a_naive_count(seed):
    rng = random.Random(seed)
    ivs = [(s, s + rng.randint(1, 50)) for s in
           (rng.randint(0, 1000) for _ in range(60))]
    assert trace_reduce.union(ivs) == naive_union(ivs)
    idle = trace_reduce.gaps(ivs, 0, 1100)
    # every interval ends by 1050, inside [0, 1100)
    assert trace_reduce.union(ivs) + sum(e - s for s, e in idle) == 1100
    for s, e in idle:
        assert not any(a < e and b > s for a, b in ivs)


def test_reduction_of_a_synthetic_trace():
    # window 1000..2000 ns; device 0 busy 1000..1400 (kernel nested inside
    # a while loop) and 1600..1700; device 1 busy 1500..2000
    pd = profile(
        {0: [("%while.1 = () while(%a)", 900, 1400), (KERNEL, 1000, 1300),
             (KERNEL, 1310, 1390), ("%copy.2 = s32[] copy(%b)", 1600, 1700)],
         1: [(KERNEL, 1500, 2100)]},
        [("bench.window", 1000, 2000), ("bench.block_until_ready", 1400, 2000),
         ("PjitFunction(step)", 1450, 1550)])
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=2)
    assert red.window_s == pytest.approx(1e-6)
    # busy: device 0 400 + 100 = 500 ns, device 1 500 ns (clipped)
    assert red.busy_s == pytest.approx(500e-9)
    assert kernels.kernel_ns(red) == 300 + 80 + 500
    gaps = red.breakdown["idle_gaps"]
    # each gap is named by the innermost host span at its middle
    assert gaps == [["bench.window (TPU:1)", 500e-9],
                    ["bench.block_until_ready (TPU:0)", 300e-9],
                    ["PjitFunction(step) (TPU:0)", 200e-9]]
    ops = dict(red.breakdown["device_ops"])
    assert ops["%closed_call.51 custom-call tpu_custom_call"] == \
        pytest.approx(880e-9)
    assert ops["%while.1 while"] == pytest.approx(400e-9)

    steps = [NS(point_cycles=10, router_cycles=360)]
    ctx = run.Context(trace=red, steps=steps)
    read = {m: run.load_module(ROOT / "bench/metrics" / f"{m}.py", m).read
            for m in ("device_idle_pct", "cycle_kernel_ns", "epoch_scan_ns")}
    assert read["device_idle_pct"](ctx) == pytest.approx(50.0)
    # per point-cycle, summed over devices: kernel 880 ns, the rest of the
    # busy time 1000 - 880 = 120 ns
    assert read["cycle_kernel_ns"](ctx) == pytest.approx(88.0)
    assert read["epoch_scan_ns"](ctx) == pytest.approx(12.0)
    # no trace: no device metric
    assert all(r(run.Context(trace=None, steps=steps)) is None
               for r in read.values())


def test_missing_window_or_device_is_an_error():
    pd = profile({0: [(KERNEL, 0, 5)]}, [("bench.window", 0, 10)])
    with pytest.raises(ValueError, match="devices"):
        trace_reduce.reduce_profile(pd, "bench.window", n_devices=2)
    with pytest.raises(ValueError, match="no host span"):
        trace_reduce.reduce_profile(pd, "other", n_devices=1)


def test_short_names():
    assert trace_reduce.short_name(KERNEL) == \
        "%closed_call.51 custom-call tpu_custom_call"
    assert trace_reduce.short_name(
        "%copy.1 = pred[1,4]{1,0:T(4,128)(4,1)} copy(pred[1,4] %b)") == \
        "%copy.1 copy"


def test_recorded_v5e_trace():
    """A sweep of the paper grid at 1 epoch x 8 cycles, traced on a v5e."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=1)
    ops = red.ops[0]
    kernel = [o for o in ops if kernels.CYCLE_KERNEL.search(o[0])]
    # one kernel launch per simulated cycle of each 6-point tile
    assert len(kernel) == 4 * 8
    assert 0 < red.busy_s < red.window_s
    assert trace_reduce.union((s, e) for _, s, e in ops) == \
        naive_union([(s, e) for _, s, e in ops])
    assert 0 < kernels.kernel_ns(red) < red.busy_s * 1e9
    assert red.breakdown["device_ops"] and red.breakdown["idle_gaps"]
