"""Flight-recorder observability layer (DESIGN.md §14, §18).

Four modules; the probes and the profiler captures are opt-in and cost
nothing when off, the host spans cost two clock reads each:

  * probes.py    — `ProbeConfig` / `SimTrace`: per-epoch introspection
                   emitted by the traced simulator (occupancy, arbitration
                   grant/deny, MC queue depth, KF internals), bitwise-equal
                   across the `ref` and fused `pallas` cycle engines.
  * ledger.py    — structured run records: the single append path for
                   BENCH_noc.json plus a JSONL mirror, with the schema
                   validator that benchmarks/check_bench.py enforces.
  * profiling.py — `span(name)`: the program's host spans, each a
                   `jax.profiler.TraceAnnotation` and a `jax.monitoring`
                   span `/repro/<name, dots as slashes>`; and the
                   jax.profiler trace contexts behind the fig drivers'
                   `--profile DIR` flag.
  * recorder.py  — `TraceRecorder`: captures the per-epoch demand rows of
                   any run as a replayable `traffic.RecordedTrace`
                   (DESIGN.md §15), optionally stamped with the observed
                   §14 telemetry digest.

Names the program uses (DESIGN.md §18).  Host spans in `sim.sweep`,
`sim.simulate_batch` and `sim.simulate`: `noc.sweep` (a whole sweep),
`noc.args` (argument building), `noc.dispatch` (each call of the compiled
program), `noc.rows` (cutting the answer into rows).  Device labels, the
XLA frontend attribute `noc_layer` on the compiled operations of
`_simulate_impl` and the cycle engines: `epoch.rng`, `epoch.boundary`,
`cycle.scan`; the fused `pallas_call` carries `cycle.kernel` in its
kernel metadata.
"""

from repro.obs.probes import ProbeConfig, SimTrace
from repro.obs import ledger, profiling, recorder
from repro.obs.recorder import TraceRecorder
