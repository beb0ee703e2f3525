"""Flight-recorder replay: probe captures -> per-epoch ASCII/CSV timelines.

Runs any workload or scenario from the traffic library with the probes on
(`sim.simulate_with_trace`, DESIGN.md §14) and renders the capture as a
per-epoch timeline: occupancy heat per subnet, arbitration grant/deny,
MC queue depth, and the KF's decision annotations (observation, innovation,
gain, one-step prediction, emitted signal, applied config) — the "why did
the KF flip the VC allocation at epoch e" view the paper's Fig. 4/12
narrative is built on.

    PYTHONPATH=src python -m benchmarks.noc_trace [--workload SHIFT_PATH_BFS]
        [--mode kf] [--epochs 24] [--epoch-len 200] [--seed 0]
        [--backend ref|pallas] [--csv] [--save F.npz] [--load F.npz]

Special modes:

  --check    CI self-validation: tiny probes-on capture, invariant checks,
             save/load round-trip, both renderers.  Exit 0 = OK.
  --record   Measure the probe overhead (steady-state wall-clock ratio
             probes-on / probes-off) and append a `noc_obs` ledger row to
             BENCH_noc.json (gated by benchmarks/check_bench.py).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core.noc import sim
from repro.obs import ledger, probes

HEAT = " .:-=+*#%@"

# capture metadata keys stored alongside the SimTrace arrays in the npz
META_KEYS = ("workload", "mode", "n_epochs", "epoch_len", "seed", "backend")


def capture(workload: str = "SHIFT_PATH_BFS", mode: str = "kf",
            n_epochs: int = 24, epoch_len: int = 200, seed: int = 0,
            backend: str = "ref", faults: str | None = None,
            guard: bool = False, placement: str | None = None,
            control: str = "bandwidth") -> dict:
    """Probes-on run -> flat dict of numpy arrays + run metadata."""
    cfg = sim.NoCConfig(mode=mode, n_epochs=n_epochs, epoch_len=epoch_len,
                        seed=seed, faults=faults, guard=guard,
                        placement=placement, control=control,
                        backend=backend)
    res, trace = sim.simulate_with_trace(cfg, workload)
    cap = {f: np.asarray(v) for f, v in zip(sim.SimTrace._fields, trace)}
    cap["kf_signal"] = np.asarray(res.kf_signal)
    cap["applied_config"] = np.asarray(res.applied_config)
    cap["gpu_ipc"] = np.asarray(res.gpu_ipc)
    cap["avg_latency"] = np.asarray(res.avg_latency)
    cap.update(workload=workload, mode=mode, n_epochs=n_epochs,
               epoch_len=epoch_len, seed=seed, backend=backend)
    return cap


def save(cap: dict, path: str) -> None:
    np.savez(path, **cap)


def load(path: str) -> dict:
    with np.load(path, allow_pickle=False) as f:
        cap = {k: f[k] for k in f.files}
    for k in META_KEYS:  # 0-d string/int arrays back to scalars
        if k in cap:
            cap[k] = cap[k].item() if cap[k].ndim == 0 else cap[k]
    return cap


def _occ_frac(cap: dict) -> np.ndarray:
    """(E, S) mean buffer occupancy as a fraction of capacity."""
    occ = cap["occ_sum"]                      # (E, S, R, P, V)
    _, S, R, P, V = occ.shape
    # sum over cycles of count / (cycles * buffers * depth); depth B is not
    # in the capture, so normalize by the observed per-buffer ceiling
    per_buf = occ.sum(axis=(2, 3, 4)) / (cap["epoch_len"] * R * P * V)
    return per_buf  # mean flits per buffer per cycle (0..B)


def render_ascii(cap: dict) -> list:
    """One line per epoch: subnet occupancy heat + KF decision annotations."""
    frac = _occ_frac(cap)
    depth_est = max(float(frac.max()), 1e-9)
    E, S = frac.shape
    has_faults = "faults_active" in cap  # pre-§16 captures lack the channels
    has_place = "place_cls" in cap       # pre-§17 captures lack the channel
    lines = [
        f"# workload={cap['workload']} mode={cap['mode']} "
        f"epochs={cap['n_epochs']} epoch_len={cap['epoch_len']} "
        f"seed={cap['seed']} backend={cap['backend']}",
        "#  ep |occ/subnet| grant  deny mcqMax | z(dram,push,icnt) "
        "innov0   gain0  x_pred sig cfg"
        + (" | flt rej rst ok     nis" if has_faults else "")
        + (" |  mv gpu" if has_place else ""),
    ]
    for e in range(E):
        heat = "".join(
            HEAT[min(int(frac[e, s] / depth_est * (len(HEAT) - 1)),
                     len(HEAT) - 1)]
            for s in range(S)
        )
        z = cap["z_obs"][e]
        fault_cols = ""
        if has_faults:
            # the fault -> reject -> reset -> recover story, one glyph each
            fault_cols = (
                f" | {'F' if cap['faults_active'][e] else '.':>3s}"
                f" {'R' if cap['kf_rejected'][e] else '.':>3s}"
                f" {'*' if cap['kf_reset'][e] else '.':>3s}"
                f" {'y' if cap['kf_healthy'][e] else 'n':>2s}"
                f" {float(cap['kf_nis'][e]):7.2f}"
            )
        place_cols = ""
        if has_place:
            # relocation timeline (DESIGN.md §17): tiles whose class moved
            # vs the previous epoch's plan, and the GPU tile count ('M'
            # marks a migration epoch)
            moves = (
                0 if e == 0
                else int((cap["place_cls"][e] != cap["place_cls"][e - 1]).sum())
            )
            n_gpu = int((cap["place_cls"][e] == 1).sum())
            place_cols = (
                f" | {('M' + str(moves)) if moves else '.':>3s} {n_gpu:3d}"
            )
        lines.append(
            f"{e:5d} |{heat:^10s}| {int(cap['arb_grant'][e].sum()):6d}"
            f" {int(cap['arb_deny'][e].sum()):5d}"
            f" {int(cap['mcq_max'][e].max()):6d} |"
            f" ({z[0]:+.2f},{z[1]:+.2f},{z[2]:+.2f})"
            f" {cap['kf_innovation'][e][0]:+.3f}"
            f" {cap['kf_gain'][e][0]:7.3f}"
            f" {cap['kf_x_pred'][e]:+.3f}"
            f" {int(cap['kf_signal'][e]):3d}"
            f" {int(cap['applied_config'][e]):3d}"
            + fault_cols
            + place_cols
        )
    return lines


def render_csv(cap: dict) -> list:
    """Machine-readable per-epoch rows (same quantities as the ASCII view)."""
    has_faults = "faults_active" in cap  # pre-§16 captures lack the channels
    has_place = "place_cls" in cap       # pre-§17 captures lack the channel
    cols = (
        ["epoch", "occ_sum", "arb_grant", "arb_deny", "mcq_sum", "mcq_max"]
        + [f"z_{i}" for i in range(3)]
        + [f"innovation_{i}" for i in range(3)]
        + [f"gain_{i}" for i in range(3)]
        + ["cov_trace", "x_pred", "kf_signal", "applied_config",
           "gpu_ipc", "avg_latency"]
        + (["faults_active", "kf_nis", "kf_rejected", "kf_reset",
            "kf_healthy"] if has_faults else [])
        + (["place_moves", "place_gpu_tiles"] if has_place else [])
    )
    lines = [",".join(cols)]
    for e in range(int(cap["n_epochs"])):
        row = (
            [e, int(cap["occ_sum"][e].sum()), int(cap["arb_grant"][e].sum()),
             int(cap["arb_deny"][e].sum()), int(cap["mcq_sum"][e].sum()),
             int(cap["mcq_max"][e].max())]
            + [float(v) for v in cap["z_obs"][e]]
            + [float(v) for v in cap["kf_innovation"][e]]
            + [float(v) for v in cap["kf_gain"][e]]
            + [float(cap["kf_cov_trace"][e]), float(cap["kf_x_pred"][e]),
               int(cap["kf_signal"][e]), int(cap["applied_config"][e]),
               float(cap["gpu_ipc"][e]), float(cap["avg_latency"][e])]
            + ([int(cap["faults_active"][e]), float(cap["kf_nis"][e]),
                int(cap["kf_rejected"][e]), int(cap["kf_reset"][e]),
                int(cap["kf_healthy"][e])] if has_faults else [])
            + ([0 if e == 0 else
                int((cap["place_cls"][e] != cap["place_cls"][e - 1]).sum()),
                int((cap["place_cls"][e] == 1).sum())] if has_place else [])
        )
        lines.append(",".join(str(v) for v in row))
    return lines


def check(save_path: str | None = None) -> int:
    """CI self-validation: capture, invariants, round-trip, renderers."""
    sim.reset_trace_count()
    cap = capture(workload="PATH", n_epochs=4, epoch_len=60)
    assert sim.trace_count() == 1, (
        f"probes-on capture traced {sim.trace_count()}x (contract: 1)"
    )
    E, L = int(cap["n_epochs"]), int(cap["epoch_len"])
    occ = cap["occ_sum"]
    assert occ.min() >= 0 and occ.max() <= L * 64, "occupancy out of bounds"
    assert cap["mcq_max"].min() >= 0, "negative MC queue depth"
    assert (cap["arb_grant"] >= 0).all() and (cap["arb_deny"] >= 0).all()
    assert np.isfinite(cap["kf_gain"]).all(), "non-finite Kalman gain"
    # the KF member's signal is the binarized one-step prediction
    assert (
        (cap["kf_x_pred"] > 0.0).astype(np.int32) == cap["kf_signal"]
    ).all(), "kf_signal inconsistent with one-step prediction"

    path = save_path or "probe_capture.npz"
    save(cap, path)
    cap2 = load(path)
    for k, v in cap.items():
        np.testing.assert_array_equal(np.asarray(cap2[k]), np.asarray(v),
                                      err_msg=f"round-trip mismatch: {k}")
    a_lines, c_lines = render_ascii(cap2), render_csv(cap2)
    assert len(a_lines) == E + 2 and len(c_lines) == E + 1
    print("\n".join(a_lines))
    print(f"noc_trace check OK ({path}, {E} epochs)")
    return 0


def record(backend: str = "ref") -> dict:
    """Measure probe overhead and append the `noc_obs` ledger row."""
    from benchmarks.bench_sweep import append_record

    cfg = sim.NoCConfig(mode="kf", n_epochs=8, epoch_len=100,
                        backend=backend)
    wl = "SHIFT_PATH_BFS"

    def steady(fn):
        import jax

        jax.block_until_ready(fn())  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    sim.reset_trace_count()
    t_off = steady(lambda: sim.simulate(cfg, wl))
    traces_off = sim.trace_count()
    sim.reset_trace_count()
    res_trace = []
    t_on = steady(
        lambda: res_trace.append(
            sim.simulate_with_trace(cfg, wl)
        ) or res_trace[-1]
    )
    traces_on = sim.trace_count()
    _, trace = res_trace[-1]

    import jax

    rec = {
        "bench": "noc_obs",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "sim_backend": backend,
        "workload": wl,
        "n_epochs": cfg.n_epochs,
        "epoch_len": cfg.epoch_len,
        "config_hash": ledger.config_hash(cfg),
        "steady_off_s": round(t_off, 4),
        "steady_on_s": round(t_on, 4),
        "probe_overhead_steady": round(t_on / max(t_off, 1e-9), 3),
        "traces_off": traces_off,
        "traces_on": traces_on,
        "probe_summary": probes.summarize_trace(trace),
    }
    append_record(rec)
    print(f"noc_obs row appended: overhead {rec['probe_overhead_steady']}x "
          f"(off {t_off:.3f}s, on {t_on:.3f}s), "
          f"traces off/on {traces_off}/{traces_on}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Replay NoC/KF flight-recorder captures (DESIGN.md §14)"
    )
    ap.add_argument("--workload", default="SHIFT_PATH_BFS",
                    help="any PROFILES or SCENARIOS name")
    ap.add_argument("--mode", default="kf")
    ap.add_argument("--epochs", type=int, default=24)
    ap.add_argument("--epoch-len", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="ref",
                    choices=("ref", "pallas"),
                    help="cycle engine; both bitwise-identical, incl. probes")
    ap.add_argument("--faults", metavar="NAME", default=None,
                    help="inject a registered fault scenario (DESIGN.md §16)"
                         " and render the fault/reject/reset/recover columns")
    ap.add_argument("--guard", action="store_true",
                    help="arm the self-healing KF guard (innovation gate +"
                         " watchdog + fair-split fallback)")
    ap.add_argument("--placement", metavar="NAME", default=None,
                    help="apply a registered placement scenario "
                         "(DESIGN.md §17) and render the relocation-timeline"
                         " columns")
    ap.add_argument("--control", default="bandwidth",
                    choices=("bandwidth", "placement", "joint"),
                    help="which levers the KF signal may pull: VC bandwidth"
                         " boosts, placement relocation, or both")
    ap.add_argument("--csv", action="store_true",
                    help="emit CSV rows instead of the ASCII timeline")
    ap.add_argument("--save", metavar="F.npz", help="save the capture")
    ap.add_argument("--load", metavar="F.npz",
                    help="render a saved capture instead of simulating")
    ap.add_argument("--check", action="store_true",
                    help="CI self-validation (tiny capture + invariants)")
    ap.add_argument("--record", action="store_true",
                    help="append the noc_obs probe-overhead ledger row")
    args = ap.parse_args(argv)

    if args.check:
        return check(save_path=args.save)
    if args.record:
        record(backend=args.backend)
        return 0

    if args.load:
        cap = load(args.load)
    else:
        cap = capture(workload=args.workload, mode=args.mode,
                      n_epochs=args.epochs, epoch_len=args.epoch_len,
                      seed=args.seed, backend=args.backend,
                      faults=args.faults, guard=args.guard,
                      placement=args.placement, control=args.control)
    if args.save:
        save(cap, args.save)
    lines = render_csv(cap) if args.csv else render_ascii(cap)
    print("\n".join(lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
