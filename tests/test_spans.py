"""Host spans of the sweep path (DESIGN.md §18).

`repro.obs.profiling.span` reports each step of `sim.sweep`,
`sim.simulate_batch` and `sim.simulate` to `jax.monitoring` span listeners
as `/repro/noc/<step>`: `noc.sweep` around a sweep, `noc.args` around
argument building, `noc.schedules` inside it around the points' demand,
fault and placement streams, `noc.dispatch` around each call of the
compiled program, `noc.rows` around cutting the answer into rows.  These
tests pin which spans a call emits, in which order, and that they nest.
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core.noc import sim
from repro.core.noc.sim import NoCConfig, SweepSpec
from repro.obs import profiling

TINY = dict(n_epochs=2, epoch_len=8)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SWEEP, ARGS, SCHEDULES, DISPATCH, ROWS = (
    f"/repro/noc/{s}" for s in
    ("sweep", "args", "schedules", "dispatch", "rows"))
# 12 points: two tiles of the sweep's 6
SPECS = [SweepSpec(mode, wl, seed=3)
         for wl in ("PATH", "BFS", "LIB")
         for mode in ("4subnet", "baseline", "fair", "kf")]


class Recorder:
    """The program's `/repro/` spans, in the order they closed."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **_):
        if event.startswith("/repro/"):
            self.spans.append((event, start, end))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self)

    def names(self):
        return [e for e, _, _ in self.spans]


def test_span_reports_dotted_name_as_path_and_nests():
    with Recorder() as rec:
        with profiling.span("noc.outer"):
            with profiling.span("noc.inner"):
                pass
    (inner, s_in, e_in), (outer, s_out, e_out) = rec.spans
    assert (inner, outer) == ("/repro/noc/inner", "/repro/noc/outer")
    assert s_out <= s_in <= e_in <= e_out


def test_span_closes_when_the_body_raises():
    with Recorder() as rec, pytest.raises(ValueError):
        with profiling.span("noc.fails"):
            raise ValueError("boom")
    assert rec.names() == ["/repro/noc/fails"]


def _tiled_sweep_spans():
    with Recorder() as rec:
        rows = sim.sweep(SPECS, batch_tile=6, **TINY)
    jax.block_until_ready(rows)
    assert len(rows) == len(SPECS)
    return rec


def test_sweep_spans_once_per_tile_inside_the_sweep():
    first = _tiled_sweep_spans()
    # the configurations, `batch_args` (its streams in `noc.schedules`),
    # then each tile's arguments and dispatch; rows are cut once per sweep
    assert first.names() == [ARGS, SCHEDULES, ARGS, ARGS, DISPATCH, ARGS,
                             DISPATCH, ROWS, SWEEP]
    (_, lo, hi), = [sp for sp in first.spans if sp[0] == SWEEP]
    for _, s, e in first.spans:
        assert lo <= s <= e <= hi
    # the schedules lie inside `batch_args`' argument span
    (_, s_lo, s_hi), = [sp for sp in first.spans if sp[0] == SCHEDULES]
    (_, a_lo, a_hi) = first.spans[2]
    assert a_lo <= s_lo <= s_hi <= a_hi
    # the steps never overlap, so no idle time is counted under two
    steps = sorted((s, e) for n, s, e in first.spans
                   if n not in (SWEEP, SCHEDULES))
    for (_, e0), (s1, _) in zip(steps, steps[1:]):
        assert s1 >= e0
    # a second, warm call emits the same spans in the same order
    assert _tiled_sweep_spans().names() == first.names()


def test_simulate_spans_args_then_dispatch():
    cfg = NoCConfig(mode="kf", seed=1, **TINY)
    for _ in range(2):
        with Recorder() as rec:
            jax.block_until_ready(sim.simulate(cfg, "BFS"))
        assert rec.names() == [SCHEDULES, ARGS, DISPATCH]
        (_, s_lo, s_hi), (_, a_lo, args_end), (_, dispatch_start, _) = \
            rec.spans
        assert a_lo <= s_lo <= s_hi <= args_end <= dispatch_start


def test_sharded_sweep_spans():
    """`sweep_sharded` on 4 virtual CPU devices: one argument span for the
    configurations, one for `batch_args` (with the schedules inside it),
    one for the shard padding, one dispatch, and rows cut once.

    Runs in a subprocess: the device count is fixed when JAX starts."""
    body = """
        import jax
        from repro.core.noc import sim
        from repro.core.noc.sim import SweepSpec
        assert len(jax.devices()) == 4
        spans = []
        def listen(event, start, end, **_):
            if event.startswith("/repro/"):
                spans.append((event.rsplit("/", 1)[1], start, end))
        jax.monitoring.register_event_time_span_listener(listen)
        specs = [SweepSpec(m, w, seed=2) for w in ("PATH", "BFS")
                 for m in ("4subnet", "baseline", "fair")]
        seen = []
        for _ in range(2):
            spans.clear()
            jax.block_until_ready(sim.sweep_sharded(
                specs, devices=4, n_epochs=2, epoch_len=8))
            seen.append([n for n, _, _ in spans])
            (_, lo, hi), = [s for s in spans if s[0] == "sweep"]
            assert all(lo <= s <= e <= hi for _, s, e in spans), spans
        assert seen[0] == seen[1], seen
        print("SPANS", ",".join(seen[0]))
    """
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert ("SPANS args,schedules,args,args,dispatch,rows,sweep"
            in out.stdout)
