"""Demand-trace recorder: capture per-epoch demand rows from any run
(DESIGN.md §15).

`TraceRecorder` turns a (config, source) pair into a `RecordedTrace` — the
replayable demand artifact of the TrafficSource redesign.  The capture is
the *input* side of a run: the exact per-epoch parameter rows
`traffic.resolve_source` lowered for the simulator's epoch scan.  Because
the simulator consumes nothing about demand but those rows (plus the seed,
which lives in the config), replaying the capture through the SAME config
is bitwise-identical to the originating run — the property
tests/test_traffic_source.py and the CI trace-replay smoke both pin.

With ``run=True`` the recorder also rides the §14 flight-recorder path
(`sim.simulate_with_trace`) and attaches the run's *observed* telemetry
digest (occupancy / arbitration / MC-queue / KF-innovation summaries) to
the trace's meta — provenance that says what the fabric actually did under
this demand, without changing the replayable rows.

Import note: this module must import `sim` lazily (inside functions) —
`sim.py` imports `repro.obs.probes` at module load, which loads this
package's ``__init__``; a top-level sim import here would cycle.
"""

from __future__ import annotations

import dataclasses

from repro.core.noc.traffic import RecordedTrace, WorkloadProfile

import numpy as np


def _source_descriptor(source) -> str:
    """A short human-readable provenance tag for a demand source."""
    if isinstance(source, str):
        return source
    name = getattr(source, "name", None)
    if isinstance(name, str) and name:
        return f"{type(source).__name__}:{name}"
    return type(source).__name__


@dataclasses.dataclass
class TraceRecorder:
    """Captures replayable demand traces from simulation runs.

    name     — base name stamped on captured traces.
    observe  — when True (default), `record` runs the simulation with the
               §14 flight recorder on and stores the observed telemetry
               digest + result summary in the trace meta; when False the
               capture is input-only (no simulation dispatched), which is
               what the cheap CI smoke uses.
    """

    name: str = "capture"
    observe: bool = True

    def record(self, cfg, source) -> RecordedTrace:
        """Capture the per-epoch demand rows a (cfg, source) run consumes.

        Returns a `RecordedTrace` whose rows replay bitwise-identical to
        running ``source`` directly under the same ``cfg`` (fit="exact",
        length pinned to ``cfg.n_epochs``).
        """
        from repro.core.noc import sim
        from repro.core.noc.traffic import resolve_source

        demand = resolve_source(source, cfg.n_epochs)
        rows = WorkloadProfile(**{
            f: np.asarray(getattr(demand, f), np.float32)
            for f in WorkloadProfile._fields
        })
        meta = {
            "source": _source_descriptor(source),
            "mode": cfg.mode,
            "n_epochs": int(cfg.n_epochs),
            "epoch_len": int(cfg.epoch_len),
            "seed": int(cfg.seed),
            "backend": cfg.static_spec().backend,
            "recorder": "TraceRecorder",
        }
        if self.observe:
            from repro.obs.probes import summarize_trace

            res, trace = sim.simulate_with_trace(cfg, demand)
            meta["observed"] = summarize_trace(trace)
            meta["result"] = sim.summarize(res)
        return RecordedTrace(demand=rows, fit="exact", name=self.name,
                             meta=meta)

    def record_to(self, path, cfg, source) -> RecordedTrace:
        """`record` and save the capture as a versioned npz trace file."""
        trace = self.record(cfg, source)
        trace.save(path)
        return trace


def capture_demand(cfg, source, path=None, name: str = "capture",
                   observe: bool = False) -> RecordedTrace:
    """One-shot convenience: capture (and optionally save) a demand trace."""
    rec = TraceRecorder(name=name, observe=observe)
    if path is not None:
        return rec.record_to(path, cfg, source)
    return rec.record(cfg, source)
