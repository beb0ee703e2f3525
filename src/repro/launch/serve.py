"""Serving driver: continuous batching with KF-arbitrated scheduling.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --mode kf --requests 48

Runs the reduced config (`--size smoke`, the default) or the published
one (`--size full`) with the bursty synthetic workload and prints the
latency/throughput summary for the chosen arbitration mode
(rr | static | kf) — the serving-side A/B of the paper's technique
(benchmarks/kf_scheduler_ab.py sweeps all three).
"""
from __future__ import annotations

import argparse
import json

import jax

import repro.configs as configs
from repro.launch import compile_cache
from repro.models import lm
from repro.serve import batching
from repro.serve.engine import Engine, EngineConfig


def build(arch: str, mode: str, n_requests: int = 48, seed: int = 0,
          max_slots: int = 8, max_len: int = 128, budget: int = 128,
          size: str = "smoke"):
    """Returns (engine, requests): the model from `seed`, the engine in
    `mode`, and the bursty synthetic workload."""
    cfg = configs.smoke(arch) if size == "smoke" else configs.get(arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("serve driver targets decoder LMs; "
                         "seamless decode is covered by the dry-run")
    params, _ = lm.make_lm(jax.random.PRNGKey(seed), cfg)
    wl = batching.WorkloadConfig(n_requests=n_requests, mean_prompt=48,
                                 mean_gen=12, seed=seed)
    ecfg = EngineConfig(mode=mode, max_slots=max_slots, max_len=max_len,
                        budget_tokens=budget)
    return Engine(params, cfg, ecfg, seed=seed), batching.generate(wl)


def run(arch: str, mode: str, n_requests: int = 48, seed: int = 0,
        max_slots: int = 8, max_len: int = 128, budget: int = 128,
        size: str = "smoke"):
    engine, requests = build(arch, mode, n_requests, seed, max_slots,
                             max_len, budget, size)
    return engine.run(requests).summary()


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--mode", default="kf", choices=["rr", "static", "kf"])
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    summary = run(args.arch, args.mode, args.requests, args.seed,
                  size=args.size)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
