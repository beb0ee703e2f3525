"""Benchmark aggregator: one section per paper table/figure + the TPU
adaptation A/B + kernel micro-benches.

    PYTHONPATH=src python -m benchmarks.run [--fast]

The NoC figures reproduce the paper's evaluation qualitatively (synthetic
workload profiles — DESIGN.md §2) and all run on the batched sweep engine
(DESIGN.md §4): one compiled program per network structure, every
(mode, workload, ratio, seed) point dispatched in lockstep batches.  The
roofline table comes from the dry-run artifacts in results/dryrun (run
repro.launch.dryrun first for the full 40-cell table).

Observability (DESIGN.md §14): each fig driver's own `main` takes
`--profile DIR` to capture jax.profiler traces of its compile and steady
phases, and `benchmarks/noc_trace.py` replays probes-on flight-recorder
captures (per-epoch occupancy / arbitration / MC-queue / KF-internals
timelines) for any workload or scenario.
"""
from __future__ import annotations

import argparse
import time

from repro.launch import compile_cache


def _section(title):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}", flush=True)


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer epochs / one seed for the NoC sims")
    args = ap.parse_args(argv)
    epochs = 30 if args.fast else 60
    seeds = (0,) if args.fast else (0, 1, 2)

    t0 = time.time()

    _section("Fig 2/3 — IPC vs static VC allocation ratio")
    from benchmarks import fig2_3_vc_sweep
    res = fig2_3_vc_sweep.run(n_epochs=epochs, seeds=seeds)
    for wl, row in res.items():
        line = "  ".join(
            f"{r}: gpu={s['gpu_ipc']:.3f}±{s['gpu_ipc_std']:.3f} "
            f"cpu={s['cpu_ipc']:.3f}"
            for r, s in row.items())
        print(f"{wl:6s} {line}")

    _section("Fig 4 — dynamic traffic pattern (bursty GPU, stable CPU)")
    from benchmarks import fig4_traffic
    tr = fig4_traffic.run(n_epochs=epochs)
    gpu_cov = tr["gpu_inj_rate"].std() / max(tr["gpu_inj_rate"].mean(), 1e-9)
    cpu_cov = tr["cpu_push"].std() / max(tr["cpu_push"].mean(), 1e-9)
    print(f"gpu_inj CoV={gpu_cov:.3f}  cpu_push CoV={cpu_cov:.3f}  "
          f"bursty-vs-stable: {gpu_cov > 2 * cpu_cov}")

    _section("Figs 9/10/11 — four configurations")
    from benchmarks import fig9_10_11_configs
    res = fig9_10_11_configs.run(n_epochs=epochs, seeds=seeds)
    wls = list(res)
    for wl in wls:
        row = res[wl]
        print(f"{wl:5s} " + "  ".join(
            f"{m}: gpu={s['gpu_ipc']:.3f}±{s['gpu_ipc_std']:.3f} "
            f"lat={s['avg_latency']:.1f}"
            for m, s in row.items()))
    lat_wins = sum(res[w]["kf"]["avg_latency"]
                   <= res[w]["baseline"]["avg_latency"] for w in wls)
    gains = [res[w]["kf"]["gpu_ipc"] / max(res[w]["baseline"]["gpu_ipc"], 1e-9)
             - 1 for w in wls]
    print(f"KF latency wins: {lat_wins}/{len(wls)}; GPU IPC gain "
          f"mean {sum(gains)/len(gains):+.1%} max {max(gains):+.1%} "
          f"(paper: +7% mean, +19% max)")

    _section("Fig 12 — dynamic GPU IPC, fair vs KF")
    from benchmarks import fig12_dynamic_kf
    tr = fig12_dynamic_kf.run(n_epochs=max(epochs, 100), seeds=seeds)
    sl = slice(10, None)
    print(f"mean GPU IPC: fair {tr['fair_ipc'][sl].mean():.4f} "
          f"kf {tr['kf_ipc'][sl].mean():.4f}; "
          f"KF engaged {tr['kf_config'][sl].mean():.0%} of epochs")

    _section("Sweep engine — serial vs batched wall-clock")
    from benchmarks import bench_sweep
    rec = bench_sweep.run(smoke=args.fast)
    if not args.fast:
        bench_sweep.append_record(rec)
    print(f"serial {rec['serial_total_s']:.1f}s "
          f"(compile {rec['serial_compile_s']:.1f}s) vs batched "
          f"{rec['batched_total_s']:.1f}s "
          f"(compile {rec['batched_compile_s']:.1f}s): "
          f"{rec['speedup_end_to_end']:.1f}x end-to-end, "
          f"{rec['speedup_steady']:.1f}x steady-state")

    _section("Predictor ablation — KF vs naive predictors (DESIGN.md §12)")
    from benchmarks import fig_ablation
    ab = fig_ablation.run(**(fig_ablation.SMOKE if args.fast else {}))
    for sc, cells in ab["table"].items():
        print(f"{sc}: " + "  ".join(
            f"{p}={s['gpu_ipc']:.3f}" for p, s in cells.items()))
    verdict = fig_ablation.kf_verdict(ab["table"])
    print(f"kf_beats_all={verdict['kf_beats_all']} on "
          f"{verdict['scenario']} ({ab['traces']} trace)")

    _section("TPU adaptation — KF-arbitrated serving engine A/B")
    from benchmarks import kf_scheduler_ab
    res = kf_scheduler_ab.run()
    for mode, s in res.items():
        print(f"{mode:7s} ttft={s['mean_ttft']:.4f} "
              f"p90={s['p90_ttft']:.4f} lat={s['mean_latency']:.4f} "
              f"thr={s['throughput_tok_s']:.1f} "
              f"kf_on={s['kf_on_frac']:.2f}")

    _section("Fleet-KF bank — per-epoch filter-bank timings")
    from benchmarks import bench_fleet_kf
    for r in bench_fleet_kf.run():
        print(f"n={r['n_filters']:5d} epoch={r['epoch_us']:.1f}us "
              f"({r['ns_per_filter']:.0f}ns/filter)")

    _section("Kernel micro-benches (interpret mode)")
    from benchmarks import kernels_bench
    kernels_bench.main([])  # no --record: aggregator runs never append

    _section("Roofline table (from dry-run artifacts)")
    from benchmarks import roofline_table
    roofline_table.main()

    print(f"\n[benchmarks.run] done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
