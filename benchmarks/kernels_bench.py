"""Kernel micro-benches: wall time of the Pallas kernels (interpret mode on
CPU — correctness-shaped timings, not TPU perf) vs their jnp oracles, plus
the kf_bank fleet-scale batch sweep and the noc_cycle engines (the fused
full-cycle kernel vs the dense ref engine).

`--record` appends a `noc_cycle_engines` row to BENCH_noc.json so the
kernel-vs-ref trajectory is tracked alongside the sweep records.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.core.noc import sim as noc_sim
from repro.core.noc.traffic import PROFILES
from repro.kernels.flash_attn import ops as fa_ops
from repro.kernels.flash_attn import ref as fa_ref
from repro.kernels.kf_bank import ops as kf_ops
from repro.kernels.mamba_scan import ops as ms_ops
from repro.kernels.mamba_scan import ref as ms_ref


def _time(f, *args, reps=3):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def noc_cycle_entries() -> dict:
    """Time the noc_cycle engines at the paper's shapes (S=4, R=36):
    `simulate` steady-state per engine — the dense ref engine vs one
    `fused_cycle_kernel` launch per chunk of cycles (interpret mode
    off-TPU)."""
    mode = "compiled" if jax.default_backend() == "tpu" else "interp"
    cfg = noc_sim.NoCConfig(mode="static", static_gpu_vcs=3,
                            n_epochs=4, epoch_len=100)
    n_cycles = cfg.n_epochs * cfg.epoch_len
    prof = PROFILES["PATH"]
    t_sim = {
        be: _time(lambda be=be: noc_sim.simulate(
            dataclasses.replace(cfg, backend=be), prof))
        for be in ("ref", "pallas")
    }
    return {
        "mode": mode,
        "sim_cycles": n_cycles,
        "sim_ref_us": round(t_sim["ref"], 1),
        "sim_fused_us": round(t_sim["pallas"], 1),
        "fused_us_per_cycle": round(t_sim["pallas"] / n_cycles, 2),
        "fused_vs_ref": round(t_sim["ref"] / max(t_sim["pallas"], 1e-9), 3),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help="append a noc_cycle_engines row to BENCH_noc.json")
    args = ap.parse_args(argv)

    print("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)

    # flash attention
    q = jax.random.normal(key, (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(key, (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, 512, 2, 64), jnp.float32)
    t_kern = _time(lambda: fa_ops.flash_attention(
        q, k, v, block_q=128, block_k=128))
    t_ref = _time(lambda: fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)))
    print(f"flash_attn_512_interp,{t_kern:.0f},ref={t_ref:.0f}us")

    # mamba scan
    a = jax.random.uniform(key, (2, 256, 64, 16), jnp.float32, 0.9, 0.999)
    b = jax.random.normal(key, (2, 256, 64, 16), jnp.float32)
    h0 = jnp.zeros((2, 64, 16))
    t_kern = _time(lambda: ms_ops.mamba_chunk_scan(a, b, h0, chunk=64,
                                                   block_d=64))
    t_ref = _time(lambda: ms_ref.scan_ref(a, b, h0))
    print(f"mamba_scan_256_interp,{t_kern:.0f},ref={t_ref:.0f}us")

    # kf bank: fleet sizes (one filter per link x class x pod)
    for n in (1024, 16384, 131072):
        x = jnp.zeros((n,))
        p = jnp.ones((n,))
        z = jax.random.normal(key, (n, 3))
        h = jnp.ones((3,))
        r = jnp.full((3,), 0.2)
        t = _time(lambda: kf_ops.kf_bank_step(x, p, z, h, r))
        print(f"kf_bank_{n},{t:.0f},filters_per_s={n / t * 1e6:.2e}")

    # noc_cycle: the fused full-cycle engine
    noc = noc_cycle_entries()
    print(f"noc_cycle_fused_{noc['mode']},{noc['sim_fused_us']:.0f},"
          f"ref={noc['sim_ref_us']:.0f}us per {noc['sim_cycles']}-cycle sim "
          f"({noc['fused_us_per_cycle']:.1f}us/cycle, "
          f"{noc['fused_vs_ref']:.2f}x vs ref)")

    if args.record:
        from benchmarks.bench_sweep import BENCH_PATH, append_record

        rec = {
            "bench": "noc_cycle_engines",
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "backend": jax.default_backend(),
            **noc,
        }
        append_record(rec)
        print(json.dumps(rec, indent=2))
        print(f"appended noc_cycle_engines record to {BENCH_PATH}")


if __name__ == "__main__":
    main()
