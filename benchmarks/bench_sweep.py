"""Perf-trajectory harness: serial vs batched vs device-sharded NoC sweeps.

Times the Fig. 2/3-style grid (workloads x static VC ratios x seeds) and
appends records to BENCH_noc.json so the speedup trajectory is tracked
across PRs:

  * serial  — the seed-repo execution model: one jit cache per (config,
              workload) tuple, i.e. XLA retraces and recompiles `simulate`
              for every grid point, then runs them one dispatch at a time.
  * batched — `sim.simulate_batch`: every point (2-subnet AND 4-subnet,
              since the S-padding refactor) shares ONE compiled program and
              executes as lockstep batch dispatches.
  * sharded — `--devices N`: the same batch split data-parallel over N
              devices through the shard_map path; results are asserted
              equal to the batched arm before timing is reported.

Compile and steady-state wall-clock are reported separately: steady-state
is a second timed pass over already-compiled programs, and compile time is
the first-pass excess over it.

    PYTHONPATH=src python -m benchmarks.bench_sweep \
        [--smoke] [--seeds N] [--devices N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.core.noc import sim
from repro.core.noc.traffic import PROFILES, resolve_source

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_noc.json")


def _grid(workloads, ratios, seeds, **overrides):
    cfgs, profs = [], []
    for wl in workloads:
        for g in ratios:
            for s in seeds:
                cfgs.append(sim.NoCConfig(
                    mode="static", static_gpu_vcs=g, seed=s, **overrides))
                profs.append(PROFILES[wl])
    return cfgs, profs


def _block(res):
    jax.block_until_ready(res)
    return res


def _fresh_jit(fn):
    """Wrap `fn` in jit under a NEW function identity.

    jax.jit's cache is keyed on the *underlying function object*, so
    re-wrapping the same function merely returns the cached executable; only
    a fresh `def` per call site forces the recompile that the seed repo paid
    per grid point.  Keep the serial baseline on this helper — timing the
    shared-cache path instead silently reads ~1x and buries the regression
    this harness exists to track.
    """
    def point(stc, mp, profile, seed, state0, faults, placement):
        return fn(stc, mp, profile, seed, state0, faults, placement)

    return jax.jit(point, static_argnums=0)


def time_serial_seed_style(cfgs, profs) -> float:
    """Seed-repo model: `simulate` was jitted with the WHOLE config and the
    workload profile as static arguments, so XLA retraced and recompiled for
    every (config, workload) grid point (see `_fresh_jit`).

    Runs the mode's DEDICATED (padded=False) trace: the seed repo predates
    S/V padding, so timing the padded program here would overstate the
    baseline's cost ~2x and break row-to-row trajectory comparability in
    BENCH_noc.json."""
    t0 = time.perf_counter()
    for cfg, prof in zip(cfgs, profs):
        fresh = _fresh_jit(sim._simulate_impl)
        stc = cfg.static_spec(padded=False)
        _block(fresh(stc, cfg.mode_policy(padded=False),
                     resolve_source(prof, stc.n_epochs), cfg.seed,
                     sim.init_sim_state(stc), sim._run_faults(None, stc),
                     sim._run_placement(None, stc)))
    return time.perf_counter() - t0


def time_serial_steady(cfgs, profs) -> float:
    """Serial dispatches through the shared (pre-warmed) dedicated
    executable (padded=False, matching the seed-style arm)."""
    _block(sim.simulate(cfgs[0], profs[0], padded=False))  # warm the cache
    t0 = time.perf_counter()
    for cfg, prof in zip(cfgs, profs):
        _block(sim.simulate(cfg, prof, padded=False))
    return time.perf_counter() - t0


def _assert_batches_equal(a, b, label: str) -> None:
    for (path, x), (_, y) in zip(
        jax.tree_util.tree_leaves_with_path(a),
        jax.tree_util.tree_leaves_with_path(b),
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), atol=1e-6, rtol=1e-6,
            err_msg=f"{label}: leaf {jax.tree_util.keystr(path)}",
        )


def state_bytes(stc) -> int:
    """Per-row carry footprint of the packed cycle-engine state (bytes)."""
    leaves = jax.tree.leaves(sim.init_sim_state(stc))
    return int(sum(x.size * x.dtype.itemsize for x in leaves))


def run(n_epochs: int = 8, epoch_len: int = 100,
        seeds=(0, 1), smoke: bool = False, devices: int | None = None,
        sim_backend: str = "ref") -> dict:
    """Default grid: 24 points x 800 cycles — the smoke/--fast sweep regime
    where the seed's per-point recompile dominated wall-clock.

    Reading the record: on CPU the end-to-end win is compile amortization
    (N dedicated compiles -> 1).  Steady-state was the weak axis of the
    S/V-padded single-trace program until the packed-lane cycle engine
    (DESIGN.md §11) — the padded program's per-dispatch cost now tracks the
    dedicated traces (full-grid `speedup_steady` ~1x, up from 0.39), so a
    full-grid row regressing on it is a real engine cliff and
    `benchmarks/check_bench.py` gates it.  SMOKE rows are different: their
    steady pass is milliseconds of scan against fixed per-op dispatch
    overhead, swinging 0.2-1x run to run — meaningless for trend-reading,
    which is why only full rows land in BENCH_noc.json.

    `sim_backend` switches the BATCHED arm's cycle engine ("ref" |
    "pallas" fused full-cycle kernel); the serial arms
    always run the dense ref engine so every row's serial baseline stays
    comparable across the committed trajectory, and the resulting
    `speedup_*` is the honest serial-ref-vs-batched-<backend> number
    (interpret-mode Pallas on CPU — see `check_bench.check_pallas_row`)."""
    workloads = ("PATH", "LIB") if smoke else ("PATH", "LIB", "STO", "MUM")
    ratios = (1, 3) if smoke else (1, 2, 3)
    if smoke:
        n_epochs, epoch_len, seeds = 4, 50, (0,)
    ov = dict(n_epochs=n_epochs, epoch_len=epoch_len, backend=sim_backend)
    cfgs, profs = _grid(workloads, ratios, seeds, **ov)
    ref_cfgs = (
        cfgs if sim_backend == "ref"
        else [dataclasses.replace(c, backend="ref") for c in cfgs]
    )

    serial_total = time_serial_seed_style(ref_cfgs, profs)

    sim.reset_trace_count()
    t0 = time.perf_counter()
    batched_res = _block(sim.simulate_batch(cfgs, profs))
    batched_first = time.perf_counter() - t0
    batched_traces = sim.trace_count()
    t0 = time.perf_counter()
    _block(sim.simulate_batch(cfgs, profs))
    batched_steady = time.perf_counter() - t0

    serial_steady = time_serial_steady(ref_cfgs, profs)

    stc = cfgs[0].static_spec()
    rec = {
        "bench": "noc_sweep_serial_vs_batched",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "sim_backend": stc.backend,
        "state_bytes": state_bytes(stc),
        "smoke": smoke,
        "grid": {"workloads": list(workloads), "ratios": list(ratios),
                 "seeds": list(seeds), "n_epochs": n_epochs,
                 "epoch_len": epoch_len, "n_points": len(cfgs)},
        "serial_total_s": round(serial_total, 3),
        "serial_steady_s": round(serial_steady, 3),
        "serial_compile_s": round(max(serial_total - serial_steady, 0.0), 3),
        "batched_total_s": round(batched_first, 3),
        "batched_steady_s": round(batched_steady, 3),
        "batched_compile_s": round(max(batched_first - batched_steady, 0.0), 3),
        "batched_traces": batched_traces,
        "speedup_end_to_end": round(serial_total / max(batched_first, 1e-9), 2),
        "speedup_steady": round(serial_steady / max(batched_steady, 1e-9), 2),
    }
    if devices is not None:
        rec["sharded"] = run_sharded(cfgs, profs, devices, batched_res,
                                     batched_steady)
    return rec


def run_sharded(cfgs, profs, devices: int, batched_res,
                batched_steady: float) -> dict:
    """Time the device-sharded dispatch and pin it equal to the batched arm.

    The equivalence assert runs before any timing is reported: a sharded
    path that drifts numerically must fail the bench (and the CI job built
    on it), not report a speedup.
    """
    n_dev = len(jax.devices())
    if devices > n_dev:
        raise SystemExit(
            f"--devices {devices} but only {n_dev} available; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count on CPU")
    t0 = time.perf_counter()
    sharded_res = _block(sim.simulate_batch(cfgs, profs, devices=devices))
    sharded_first = time.perf_counter() - t0
    _assert_batches_equal(sharded_res, batched_res, "sharded vs batched")
    t0 = time.perf_counter()
    _block(sim.simulate_batch(cfgs, profs, devices=devices))
    sharded_steady = time.perf_counter() - t0
    return {
        "bench": "noc_sweep_sharded",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "devices": devices,
        "n_points": len(cfgs),
        "sharded_total_s": round(sharded_first, 3),
        "sharded_steady_s": round(sharded_steady, 3),
        "sharded_compile_s": round(
            max(sharded_first - sharded_steady, 0.0), 3),
        "steady_speedup_vs_batched": round(
            batched_steady / max(sharded_steady, 1e-9), 2),
        "equivalent_to_batched": True,  # asserted above
    }


def append_record(rec: dict, path: str = BENCH_PATH) -> None:
    """Append a bench row via the run ledger (repro.obs.ledger).

    Every driver in benchmarks/ funnels through here, so the ledger is the
    single append path: rows get stamped with provenance (git sha, device
    kind, ledger_version), schema-validated before the write, and mirrored
    to the gitignored LEDGER_noc.jsonl next to BENCH_noc.json.
    """
    from repro.obs import ledger

    ledger.append(rec, path=path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (no BENCH_noc.json append)")
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--epoch-len", type=int, default=100)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--devices", type=int, default=None,
                    help="also time the device-sharded dispatch over N "
                         "devices (asserts equality with the batched arm)")
    ap.add_argument("--backend", choices=("ref", "pallas"),
                    default="ref",
                    help="cycle engine for the batched arm (serial arms "
                         "always time the dense ref engine)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture one jax.profiler trace of the whole run "
                         "into DIR (the harness already separates compile "
                         "vs steady phases internally)")
    args = ap.parse_args(argv)
    from repro.obs import profiling

    with profiling.trace(args.profile, "bench_sweep"):
        rec = run(n_epochs=args.epochs, epoch_len=args.epoch_len,
                  seeds=tuple(range(args.seeds)), smoke=args.smoke,
                  devices=args.devices, sim_backend=args.backend)
    sharded = rec.pop("sharded", None)
    print(json.dumps(rec, indent=2))
    if sharded is not None:
        print(json.dumps(sharded, indent=2))
    if not args.smoke:
        append_record(rec)
        if sharded is not None:
            append_record(sharded)
        print(f"appended to {os.path.normpath(BENCH_PATH)}")
    ratio = rec["speedup_end_to_end"]
    print(f"end-to-end speedup over serial seed path: {ratio:.1f}x "
          f"(steady-state {rec['speedup_steady']:.1f}x, "
          f"{rec['batched_traces']} trace(s))")
    if sharded is not None:
        print(f"sharded over {sharded['devices']} devices: steady "
              f"{sharded['steady_speedup_vs_batched']:.2f}x vs batched, "
              f"results equivalent")
    return rec


if __name__ == "__main__":
    main()
