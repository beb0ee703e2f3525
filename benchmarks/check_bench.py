"""Perf-regression gate over the committed BENCH_noc.json trajectory.

Re-runs the sweep grid (`bench_sweep.run`) and fails if the engine
regressed versus the last committed `noc_sweep_serial_vs_batched` row on a
guarded axis:

  * trace count — the batched arm must not trace the simulator more often
    than the committed row did (1 since the S-padding refactor; the whole
    point of the engine is that the sweep is ONE compiled program);
  * end-to-end speedup — the serial-vs-batched speedup must clear an
    absolute floor AND a fraction of the committed row's speedup.  The
    fraction is deliberately loose — this is a cliff detector (e.g. the
    jit-cache identity gotcha quietly rebatching the serial arm, or a
    retrace per point sneaking back in), not a 5%-noise tripwire;
  * steady-state speedup (full grid only) — the packed-lane cycle engine
    (DESIGN.md §11) recovered `speedup_steady` to ~1x from the 0.39 the
    padded program paid before it, and this gate keeps it recovered: a
    fresh full-grid row must clear an absolute floor and a fraction of the
    committed row's steady speedup.

`--grid smoke` keeps the old fast mode: trace + end-to-end gates on the
tiny CI grid, with the steady gate skipped — a smoke steady pass is
milliseconds of scan against fixed per-op dispatch overhead (observed
0.2-1x run to run), so gating it would only add flakes.  The default full
grid takes a few minutes (24 fresh serial compiles) but measures a steady
state worth gating.

Pre-PR-3 BENCH rows lack some of the guarded fields (`batched_traces`,
`speedup_steady`); a missing baseline field downgrades that gate to its
absolute floor instead of raising KeyError.

The `noc_ablation` record (benchmarks/fig_ablation.py, DESIGN.md §12) is
guarded the same tolerate-then-gate way: while no committed row exists the
gate is skipped with a note, and once one lands it must say the KF beat
every naive predictor on the phase-shift scenario from a single-trace grid
— a committed ablation row that stopped clearing the paper's ordering is a
regression even though this script never re-runs the (expensive) grid.

Fused-engine rows (`bench_sweep --backend pallas`, DESIGN.md §13) are
guarded the same way via `check_pallas_row`: they never become the ref
baseline, and once one is committed it must show a single-trace batched
arm with a recorded steady speedup.

Since the run-ledger PR (DESIGN.md §14) two more gates run over the
committed rows: every row is validated against the ledger schema
(`check_ledger_schema` — hard for `ledger_version`-stamped rows, tolerant
for pre-ledger history), and the `noc_obs` flight-recorder row, once
committed, must keep its probe-overhead measurement and one-trace-per-
probe-setting contract (`check_obs_row`).

The `noc_faults` row (benchmarks/fig_faults.py, DESIGN.md §16) follows
the same tolerate-then-gate pattern via `check_faults_row`: once
committed it must keep showing the guarded KF >= unguarded KF and >=
always_off under every fault scenario, a bitwise-free healthy guard, and
a single-trace fault x guard grid.

So does the `noc_placement` row (benchmarks/fig_placement.py,
DESIGN.md §17) via `check_placement_row`: once committed it must keep
showing joint (bandwidth + relocation) control >= bandwidth-only mean
GPU IPC on its gate scenario, a bitwise-free disarmed placement lever,
and a single-trace control x placement grid.

    PYTHONPATH=src python -m benchmarks.check_bench [--grid smoke|full]

Exit code 0 = within tolerance, 1 = regression (message says which gate).
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmarks import bench_sweep

DEFAULT_MIN_SPEEDUP = 1.5  # absolute end-to-end floor
DEFAULT_FRAC = 0.25  # of the last committed row's end-to-end speedup
DEFAULT_MIN_STEADY = 0.4  # absolute steady floor (full grid; pre-§11 was 0.39)
DEFAULT_STEADY_FRAC = 0.5  # of the last committed row's steady speedup


def load_records(path: str) -> list:
    with open(path) as f:
        return json.load(f)


def last_committed_row(records: list, bench: str = "noc_sweep_serial_vs_batched"):
    """Last committed row of the REF-engine trajectory.

    Rows produced with `bench_sweep --backend pallas` carry a
    `sim_backend` marker and are excluded here: they time a different
    engine (interpret-mode Pallas on CPU), so letting one become the
    baseline would silently relax — or falsely trip — every relative gate.
    Pre-PR-4 rows lack the field and are ref by construction.
    """
    rows = [
        r for r in records
        if r.get("bench") == bench and r.get("sim_backend", "ref") == "ref"
    ]
    if not rows:
        msg = f"no committed ref-engine {bench!r} row in the bench json"
        raise SystemExit(msg + "; run benchmarks.bench_sweep (non-smoke) first")
    return rows[-1]


def check_pallas_row(records: list) -> list:
    """Tolerate-then-gate the committed fused-engine sweep row.

    Same onboarding pattern as `check_ablation`: while no
    `sim_backend == "pallas"` row exists the gate is skipped with a note;
    once one lands it must document the fused engine's contract — a
    single-trace batched arm and a recorded `speedup_steady` (the honest
    serial-ref-vs-batched-pallas number; interpret mode on CPU, so only
    its presence and the trace count are gated, not its magnitude).
    """
    rows = [
        r for r in records
        if r.get("bench") == "noc_sweep_serial_vs_batched"
        and r.get("sim_backend") == "pallas"
    ]
    if not rows:
        print("pallas sweep: no committed sim_backend=pallas row yet — "
              "tolerated (run benchmarks.bench_sweep --backend pallas "
              "non-smoke to add one)")
        return []
    row = rows[-1]
    failures = []
    if row.get("batched_traces") != 1:
        failures.append(
            "pallas regression: committed fused-engine row traced simulate "
            f"{row.get('batched_traces')}x (contract: the one shared "
            "program per backend)"
        )
    if "speedup_steady" not in row:
        failures.append(
            "pallas regression: committed fused-engine row lacks "
            "speedup_steady (bench must record the honest steady number)"
        )
    return failures


def check_ledger_schema(records: list) -> list:
    """Validate every committed BENCH row against the ledger schema.

    Tolerate-then-gate along the ROW axis: rows written before the ledger
    (no `ledger_version`) only get the core check (bench/timestamp/backend
    present and typed) and a failing legacy row is tolerated with a note —
    rewriting history to satisfy a new schema is not this gate's job.
    Rows stamped by `repro.obs.ledger.append` are hard-gated on the full
    schema: a malformed stamped row means the single-append-path contract
    broke.
    """
    from repro.obs import ledger

    failures, legacy_bad = [], 0
    for i, row in enumerate(records):
        stamped = isinstance(row, dict) and "ledger_version" in row
        problems = ledger.validate_row(row)
        if not problems:
            continue
        if stamped:
            failures += [
                f"ledger schema: row {i} "
                f"({row.get('bench', '?')}): {p}" for p in problems
            ]
        else:
            legacy_bad += 1
    if legacy_bad:
        print(f"ledger schema: {legacy_bad} pre-ledger row(s) with core-"
              "schema gaps — tolerated (no ledger_version stamp)")
    return failures


def check_obs_row(records: list) -> list:
    """Tolerate-then-gate the committed `noc_obs` flight-recorder row.

    Same onboarding pattern as `check_ablation`: absent -> tolerated with
    a note; present -> it must document the probe contract — recorded
    probe overhead (steady-time ratio probes-on/off) and a single trace
    for each of the probes-off and probes-on programs.
    """
    rows = [r for r in records if r.get("bench") == "noc_obs"]
    if not rows:
        print("noc_obs: no committed flight-recorder row yet — tolerated "
              "(run benchmarks.noc_trace --record to add one)")
        return []
    row = rows[-1]
    failures = []
    overhead = row.get("probe_overhead_steady")
    if not isinstance(overhead, (int, float)) or overhead <= 0:
        failures.append(
            "obs regression: committed noc_obs row lacks a positive "
            f"probe_overhead_steady (got {overhead!r})"
        )
    for field in ("traces_off", "traces_on"):
        if row.get(field) != 1:
            failures.append(
                f"obs regression: committed noc_obs row has {field}="
                f"{row.get(field)!r} (contract: one compiled program per "
                "probe setting)"
            )
    return failures


def check_ablation(records: list) -> list:
    """Tolerate-then-gate the committed `noc_ablation` record.

    Mirrors the pre-PR-3 missing-field path: absent record -> tolerated
    (the ablation bench has simply never been run on this checkout);
    present record -> it must document the paper's predictor ordering
    (kf_beats_all) and the single-trace contract.
    """
    rows = [r for r in records if r.get("bench") == "noc_ablation"]
    if not rows:
        print("noc_ablation: no committed record yet — tolerated "
              "(run benchmarks.fig_ablation non-smoke to add one)")
        return []
    row = rows[-1]
    failures = []
    if row.get("traces", 1) != 1:
        failures.append(
            f"ablation regression: committed noc_ablation row traced "
            f"simulate {row.get('traces')}x (contract: 1)"
        )
    if row.get("kf_beats_all") is not True:
        failures.append(
            "ablation regression: committed noc_ablation row no longer "
            f"shows KF >= every naive predictor on {row.get('scenario')!r} "
            f"(margins: {row.get('margins')})"
        )
    return failures


def check_trace_replay_row(records: list) -> list:
    """Tolerate-then-gate the committed `noc_trace_replay` record.

    Absent record -> tolerated (the trace-replay bench has never been run
    on this checkout); present record -> it must document KF >= every
    naive predictor on the replayed trace, the single-trace contract, and
    a bitwise-green record->replay round trip.
    """
    rows = [r for r in records if r.get("bench") == "noc_trace_replay"]
    if not rows:
        print("noc_trace_replay: no committed record yet — tolerated "
              "(run benchmarks.fig_trace_replay non-smoke to add one)")
        return []
    row = rows[-1]
    failures = []
    if row.get("traces", 1) != 1:
        failures.append(
            f"trace-replay regression: committed noc_trace_replay row "
            f"traced simulate {row.get('traces')}x (contract: 1)"
        )
    if row.get("kf_beats_all") is not True:
        failures.append(
            "trace-replay regression: committed noc_trace_replay row no "
            "longer shows KF >= every naive predictor on the replayed "
            f"trace {row.get('source')!r} (margins: {row.get('margins')})"
        )
    if row.get("replay_bitwise") is not True:
        failures.append(
            "trace-replay regression: committed noc_trace_replay row's "
            "record->replay round trip was not bitwise-identical"
        )
    return failures


def check_faults_row(records: list) -> list:
    """Tolerate-then-gate the committed `noc_faults` record.

    Absent record -> tolerated (the fault-injection bench has never been
    run on this checkout); present record -> it must document the
    robustness contract (DESIGN.md §16): guarded KF >= unguarded KF and
    >= always_off under every fault scenario, the healthy guard-on/off
    pair bitwise-identical, and the fault x guard grid single-trace.
    """
    rows = [r for r in records if r.get("bench") == "noc_faults"]
    if not rows:
        print("noc_faults: no committed record yet — tolerated "
              "(run benchmarks.fig_faults non-smoke to add one)")
        return []
    row = rows[-1]
    failures = []
    if row.get("traces", 1) != 1:
        failures.append(
            f"faults regression: committed noc_faults row traced simulate "
            f"{row.get('traces')}x (contract: 1)"
        )
    if row.get("guard_beats_all") is not True:
        failures.append(
            "faults regression: committed noc_faults row no longer shows "
            "guarded KF >= unguarded KF and >= always_off under every "
            f"fault scenario (margins: {row.get('margins')})"
        )
    if row.get("healthy_bitwise") is not True:
        failures.append(
            "faults regression: committed noc_faults row's healthy "
            "guard-on run was not bitwise-equal to guard-off (arming the "
            "guard must be free on clean telemetry)"
        )
    return failures


def check_placement_row(records: list) -> list:
    """Tolerate-then-gate the committed `noc_placement` record.

    Absent record -> tolerated (the placement-control bench has never
    been run on this checkout); present record -> it must document the
    placement-layer contract (DESIGN.md §17): joint control >=
    bandwidth-only mean GPU IPC on the gate scenario, the identity pair
    (bandwidth control with vs without a carried placement stream)
    bitwise-identical, and the control x placement grid single-trace.
    """
    rows = [r for r in records if r.get("bench") == "noc_placement"]
    if not rows:
        print("noc_placement: no committed record yet — tolerated "
              "(run benchmarks.fig_placement non-smoke to add one)")
        return []
    row = rows[-1]
    failures = []
    if row.get("traces", 1) != 1:
        failures.append(
            f"placement regression: committed noc_placement row traced "
            f"simulate {row.get('traces')}x (contract: 1)"
        )
    if row.get("joint_beats_bandwidth") is not True:
        failures.append(
            "placement regression: committed noc_placement row no longer "
            "shows joint control >= bandwidth-only mean GPU IPC on "
            f"{row.get('gate_scenario')!r} (margins: {row.get('margins')})"
        )
    if row.get("identity_bitwise") is not True:
        failures.append(
            "placement regression: committed noc_placement row's "
            "bandwidth-control run carrying a placement stream was not "
            "bitwise-equal to the no-stream run (a disarmed lever must "
            "be free)"
        )
    return failures


def check(rec: dict, baseline: dict, min_speedup: float, frac: float,
          min_steady: float = DEFAULT_MIN_STEADY,
          steady_frac: float = DEFAULT_STEADY_FRAC,
          gate_steady: bool = True) -> list:
    """Return the list of violated gates (empty = pass).

    Baseline fields may be absent (pre-PR-3 rows): a missing field drops
    the relative term of its gate, leaving the absolute floor.
    """
    failures = []
    allowed = baseline.get("batched_traces", 1)
    got = rec["batched_traces"]
    if got > allowed:
        failures.append(
            f"trace regression: batched arm traced simulate {got}x "
            f"(committed row: {allowed}x)"
        )

    base_e2e = baseline.get("speedup_end_to_end")
    floor = (
        max(min_speedup, frac * base_e2e)
        if base_e2e is not None
        else min_speedup
    )
    speedup = rec["speedup_end_to_end"]
    if speedup < floor:
        failures.append(
            f"speedup regression: end-to-end {speedup}x < floor {floor:.2f}x "
            f"(committed row: {base_e2e}x, frac {frac}, abs min {min_speedup})"
        )

    if gate_steady:
        base_steady = baseline.get("speedup_steady")
        steady_floor = (
            max(min_steady, steady_frac * base_steady)
            if base_steady is not None
            else min_steady
        )
        committed = (
            f"committed row: {base_steady}x, frac {steady_frac}, "
            if base_steady is not None
            else "committed row predates speedup_steady, "
        )
        steady = rec["speedup_steady"]
        if steady < steady_floor:
            failures.append(
                f"steady-state regression: {steady}x < floor "
                f"{steady_floor:.2f}x ({committed}abs min {min_steady}) — "
                "the packed-lane cycle engine (DESIGN.md §11) is supposed "
                "to keep the padded program at parity with the dedicated "
                "traces"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", choices=("full", "smoke"), default="full",
                    help="full: default bench grid, all gates incl. steady; "
                         "smoke: tiny grid, steady gate skipped (noise)")
    ap.add_argument("--min-speedup", type=float, default=DEFAULT_MIN_SPEEDUP)
    ap.add_argument("--frac", type=float, default=DEFAULT_FRAC)
    ap.add_argument("--min-steady", type=float, default=DEFAULT_MIN_STEADY)
    ap.add_argument("--steady-frac", type=float, default=DEFAULT_STEADY_FRAC)
    ap.add_argument("--bench-json", default=bench_sweep.BENCH_PATH)
    args = ap.parse_args(argv)

    records = load_records(args.bench_json)
    baseline = last_committed_row(records)
    rec = bench_sweep.run(smoke=args.grid == "smoke")
    print(json.dumps(rec, indent=2))

    failures = check(
        rec, baseline, args.min_speedup, args.frac,
        min_steady=args.min_steady, steady_frac=args.steady_frac,
        gate_steady=args.grid == "full",
    )
    failures += check_ablation(records)
    failures += check_trace_replay_row(records)
    failures += check_faults_row(records)
    failures += check_placement_row(records)
    failures += check_pallas_row(records)
    failures += check_ledger_schema(records)
    failures += check_obs_row(records)
    if failures:
        for failure in failures:
            print(f"BENCH REGRESSION: {failure}", file=sys.stderr)
        return 1
    steady_note = (
        f", steady {rec['speedup_steady']}x" if args.grid == "full"
        else " (steady not gated on smoke)"
    )
    print(
        f"bench gate OK: {rec['batched_traces']} trace(s), "
        f"{rec['speedup_end_to_end']}x end-to-end{steady_note} (committed: "
        f"{baseline.get('speedup_end_to_end')}x on "
        f"{baseline['grid']['n_points']} points)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
