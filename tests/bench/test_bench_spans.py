"""The readers of the program's own spans and labels (`bench/spans.py`).

A synthetic trace with the sweep's host spans nested as the program nests
them and device operations carrying `noc_layer` labels checks the
arithmetic against a count of covered nanoseconds, one by one, and the two
identities the readers promise: the host steps and the unattributed share
split `device_idle_pct`, and the labels and the unlabeled share split
`epoch_scan_ns`.  A trace recorded on a v5e checks that the labels reach
the chip's trace.
"""
from __future__ import annotations

import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run, spans, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = ROOT / "bench" / "testdata"
UNLABELLED = TESTDATA / "tiny_v5e.xplane.pb.gz"
LABELLED = TESTDATA / "labelled_v5e.xplane.pb.gz"
NEW = ("setup_args_s", "args_idle_ms", "dispatch_idle_ms", "rows_idle_ms",
       "idle_unattributed_pct", "epoch_rng_ns", "epoch_boundary_ns",
       "cycle_scan_ops_ns", "unlabeled_busy_pct")
OLD = ("device_idle_pct", "cycle_kernel_ns", "epoch_scan_ns")
DEVICE = ("epoch_rng_ns", "epoch_boundary_ns", "cycle_scan_ops_ns",
          "unlabeled_busy_pct")
IDLE = ("args_idle_ms", "dispatch_idle_ms", "rows_idle_ms",
        "idle_unattributed_pct")


def readers(names=NEW + OLD):
    return {m: run.load_module(ROOT / "bench/metrics" / f"{m}.py",
                               f"test_{m}").read for m in names}


def read_all(ctx, names=NEW + OLD):
    return {m: r(ctx) for m, r in readers(names).items()}


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def profile(device_ops, host):
    planes = [NS(name=f"/device:TPU:{d}", lines=[
        NS(name="XLA Ops", events=[ev(*o) for o in ops])])
        for d, ops in device_ops.items()]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*h) for h in host])]))
    return NS(planes=planes)


def op(name, label=None):
    attrs = f', frontend_attributes={{noc_layer="{label}"}}' if label else ""
    return f"%{name} = s32[6] fusion(s32[6] %p){attrs}"


# the fused kernel as a v5e trace names it: the pallas_call's metadata is a
# JSON string inside `kernel_metadata`, the scan's label beside it
KERNEL = ('%noc_cycle_fused.6 = (s32[6,80,256]{2,1,0}) custom-call('
          's32[6,6,256]{2,1,0} %x), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={\n"noc_layer":"cycle.kernel"'
          '\n},noc_layer="cycle.scan"}')
EPOCH_LOOP = "%while.166 = (s32[6]) while(%t), body=%b"
CYCLE_LOOP = ('%while.167 = (s32[6]) while(%t), body=%c, '
              'frontend_attributes={noc_layer="cycle.scan"}')
# window 1000..3000 ns.  Device 0: an eager op while the arguments are
# built, the epoch loop 1400..2500 with RNG, boundary, the cycle loop (two
# kernel launches and, between them, a boundary op XLA sank into the loop)
# and a stretch nothing labelled covers, then eager row slices.  Device 1:
# one boundary op and one unlabelled op.
OPS = {
    0: [(op("copy.1"), 1200, 1210),
        (EPOCH_LOOP, 1400, 2500),
        (op("fusion.1", "epoch.rng"), 1400, 1480),
        (op("fusion.2", "epoch.boundary"), 1480, 1600),
        (CYCLE_LOOP, 1600, 2400),
        (KERNEL, 1610, 1900), (op("broadcast.1", "epoch.boundary"), 1900,
                                1950), (KERNEL, 1950, 2300),
        (op("fusion.3", "epoch.boundary"), 2400, 2440),
        (op("slice.1"), 2650, 2660), (op("slice.2"), 2900, 3100)],
    1: [(op("fusion.4", "epoch.boundary"), 1500, 1700),
        (op("copy.2"), 2000, 2100)],
}
HOST = [("bench.window", 1000, 3000), ("bench.build_specs", 1000, 1100),
        ("noc.sweep", 1100, 2850),
        ("noc.args", 1100, 1300), ("noc.args", 1150, 1250),
        ("PjitFunction(broadcast_in_dim)", 1200, 1230),
        ("noc.dispatch", 1300, 1350), ("noc.args", 1350, 1500),
        ("noc.dispatch", 1500, 1560), ("noc.rows", 2600, 2800),
        ("bench.block_until_ready", 2850, 3000)]
STEPS = [NS(point_cycles=10, router_cycles=360)]


def ns_set(intervals, lo=1000, hi=3000):
    """The integer nanoseconds the intervals cover inside the window."""
    return {t for s, e in intervals for t in range(max(s, lo), min(e, hi))}


@pytest.fixture(scope="module")
def synthetic():
    pd = profile(OPS, HOST)
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=2)
    return run.Context(trace=red, steps=STEPS, monitor=run.Monitor(),
                       setup_span=(0.0, 1.0))


def test_idle_attribution_counts_every_idle_nanosecond(synthetic):
    got = read_all(synthetic)
    window = set(range(1000, 3000))
    idle = [window - ns_set((s, e) for _, s, e in OPS[d]) for d in OPS]

    def under(name):
        host = ns_set((s, e) for n, s, e in HOST if n == name)
        return sum(len(i & host) for i in idle) / len(idle)

    for metric, name in (("args_idle_ms", "noc.args"),
                         ("dispatch_idle_ms", "noc.dispatch"),
                         ("rows_idle_ms", "noc.rows")):
        assert got[metric] == pytest.approx(under(name) / 1e6)
    # by hand: the arguments span 1100..1300 and 1350..1500; device 0 is
    # busy in them with the copy (10 ns) and the epoch loop from 1400, so
    # idle 240 ns, device 1 idle throughout (350); both are idle in the
    # first dispatch and busy in the second; the rows span 2600..2800
    # holds device 0's 10 ns slice
    assert got["args_idle_ms"] == pytest.approx((240 + 350) / 2 * 1e-6)
    assert got["dispatch_idle_ms"] == pytest.approx(50e-6)
    assert got["rows_idle_ms"] == pytest.approx((190 + 200) / 2 * 1e-6)
    steps_ns = (under("noc.args") + under("noc.dispatch")
                + under("noc.rows"))
    total = sum(len(i) for i in idle) / len(idle)
    assert got["idle_unattributed_pct"] == pytest.approx(
        100 * (total - steps_ns) / total)
    # the identity: the host steps and the unattributed share make up the
    # idle share
    idle_pct = got["device_idle_pct"]
    parts = (100 * 1e6 * len(STEPS) * sum(got[m] for m in IDLE[:3])
             / (synthetic.trace.window_s * 1e9))
    assert parts + got["idle_unattributed_pct"] / 100 * idle_pct == \
        pytest.approx(idle_pct)


def test_labels_split_the_busy_time_outside_the_kernel(synthetic):
    got = read_all(synthetic)
    pc = sum(s.point_cycles for s in STEPS)

    def labelled(layer, d):
        return ns_set((s, e) for n, s, e in OPS[d]
                      if spans.label(n) == layer)

    def outside_loop(layer):
        return sum(len(labelled(layer, d) - labelled("cycle.scan", d))
                   for d in OPS)

    assert got["epoch_rng_ns"] == pytest.approx(outside_loop("epoch.rng")
                                                / pc)
    assert got["epoch_boundary_ns"] == pytest.approx(
        outside_loop("epoch.boundary") / pc)
    assert got["epoch_rng_ns"] == pytest.approx(80 / pc)
    # the boundary op inside the cycle loop is the loop's time
    assert got["epoch_boundary_ns"] == pytest.approx((120 + 40 + 200) / pc)
    # the cycle loop 1600..2400 less the launches 1610..1900, 1950..2300
    assert got["cycle_scan_ops_ns"] == pytest.approx((800 - 640) / pc)
    # unlabelled: the eager copy (10), the epoch loop outside any label
    # (2440..2500: 60), the row slices (10 + 100, clipped to the window)
    # and device 1's copy (100), of 1220 + 300 busy
    assert got["unlabeled_busy_pct"] == pytest.approx(
        100 * (10 + 60 + 110 + 100) / (1220 + 300))
    # the identity: labels, loop and the unlabeled share make up the busy
    # time outside the kernel
    busy_pc = got["cycle_kernel_ns"] + got["epoch_scan_ns"]
    parts = (got["epoch_rng_ns"] + got["epoch_boundary_ns"]
             + got["cycle_scan_ops_ns"]
             + got["unlabeled_busy_pct"] / 100 * busy_pc)
    assert parts == pytest.approx(got["epoch_scan_ns"])


def test_setup_args_is_the_union_of_set_up_spans():
    monitor = run.Monitor()
    monitor.spans += [("/repro/noc/args", 10.0, 10.5),
                      ("/repro/noc/args", 10.2, 10.4),
                      ("/repro/noc/args", 12.0, 12.25),
                      ("/repro/noc/dispatch", 10.5, 11.0),
                      ("/repro/noc/args", 30.0, 31.0)]
    ctx = run.Context(monitor=monitor, setup_span=(5.0, 20.0), trace=None,
                      steps=STEPS)
    assert readers(("setup_args_s",))["setup_args_s"](ctx) == \
        pytest.approx(0.75)
    ctx.setup_span = (0.0, 1.0)
    assert readers(("setup_args_s",))["setup_args_s"](ctx) is None


def test_no_trace_and_no_spans_read_nothing():
    """A run without a trace, and a program without spans or labels (a
    trace of the commit before them), give no value and raise nothing."""
    import jax

    ctx = run.Context(trace=None, steps=STEPS, monitor=run.Monitor(),
                      setup_span=(0.0, 1.0))
    assert all(v is None for v in read_all(ctx, NEW).values())
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(UNLABELLED.read_bytes()))
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=1)
    ctx.trace = red
    assert all(v is None for v in read_all(ctx, NEW).values())
    assert all(v is not None for v in read_all(ctx, OLD).values())


def test_labelled_v5e_trace():
    """A sweep of the paper grid at 1 epoch x 8 cycles, with the program's
    spans and labels, traced on a v5e (`bench/record_trace.py`, seed 0)."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(LABELLED.read_bytes()))
    red = trace_reduce.reduce_profile(pd, "bench.window", n_devices=1)
    steps = [NS(point_cycles=24 * 8, router_cycles=24 * 8 * 36)]
    ctx = run.Context(trace=red, steps=steps)
    got = read_all(ctx, DEVICE + IDLE + OLD)
    host = [n for n, _, _ in red.host if n.startswith("noc.")]
    # four tiles: the configurations, `batch_args`, each tile's arguments
    # and dispatch, rows cut by the batch and by the sweep
    assert host == ["noc.sweep", "noc.args", "noc.args"] + [
        "noc.args", "noc.dispatch"] * 4 + ["noc.rows", "noc.rows"]
    kernel = [n for n, _, _ in red.ops[0] if "tpu_custom_call" in n]
    assert len(kernel) == 4 * 8
    assert all('"noc_layer":"cycle.kernel"' in n
               and spans.label(n) == "cycle.scan" for n in kernel)
    # the readers on this trace, pinned: at 8 cycles an epoch most busy time
    # is eager operations of the host steps and epoch ops XLA leaves
    # unlabelled (the KF's LU solve, copies), hence 59% unlabelled busy
    # time here against 1.8% in the cell's 120 x 500 run (PERF.md §5)
    assert got == pytest.approx({
        "device_idle_pct": 99.9189627338719,
        "cycle_kernel_ns": 1532.3229166666667,
        "epoch_scan_ns": 3992.6614583333335,
        "args_idle_ms": 756.279228,
        "dispatch_idle_ms": 6.049912,
        "rows_idle_ms": 541.373629,
        "idle_unattributed_pct": 0.32570572669899145,
        "epoch_rng_ns": 311.7395833333333,
        "epoch_boundary_ns": 307.609375,
        "cycle_scan_ops_ns": 115.13020833333333,
        "unlabeled_busy_pct": 58.971791963966716}, rel=1e-9)
    busy_pc = got["cycle_kernel_ns"] + got["epoch_scan_ns"]
    parts = (got["epoch_rng_ns"] + got["epoch_boundary_ns"]
             + got["cycle_scan_ops_ns"]
             + got["unlabeled_busy_pct"] / 100 * busy_pc)
    assert parts == pytest.approx(got["epoch_scan_ns"], rel=0.01)
    idle_pct = got["device_idle_pct"]
    steps_pct = (100 * 1e6 * sum(got[m] for m in IDLE[:3])
                 / (red.window_s * 1e9))
    assert steps_pct + got["idle_unattributed_pct"] / 100 * idle_pct == \
        pytest.approx(idle_pct, abs=1.0)
