"""End-to-end training driver with the KF scheduler in the loop.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --size smoke --steps 200 --kf --ckpt-dir /tmp/ckpt

`--size smoke` trains the reduced config on the host mesh (CPU-runnable,
used by examples/); `--size full` targets the production mesh.  Both
compile the two step variants (balanced / comm-priority) up front and let
the KF scheduler dispatch between them — the paper's pre-defined
configuration model.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

import repro.configs as configs
from repro.data import synthetic
from repro.dist import sharding
from repro.dist.kf_scheduler import KFScheduler, SchedulerConfig
from repro.dist.telemetry import StaticCosts, Telemetry
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.train import loop as loop_lib
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib


def build(arch: str, size: str, seq_len: int, global_batch: int,
          mesh=None, lr: float = 3e-4, total_steps: int = 1000,
          seed: int = 0, use_kf: bool = True):
    """Returns (state, step_fns, make_batch, scheduler, mesh)."""
    cfg = configs.smoke(arch) if size == "smoke" else configs.get(arch)
    mesh = mesh if mesh is not None else make_host_mesh()
    opt_cfg = opt_lib.OptimizerConfig(
        lr=lr, total_steps=total_steps,
        moment_dtype=cfg.optimizer_dtype)

    with sharding.activate(mesh):
        state, specs_tree = step_lib.init_train_state(
            jax.random.PRNGKey(seed), cfg, opt_cfg)
        ds = synthetic.make_dataset(cfg, seq_len, global_batch, seed=seed)
        batch0 = ds.batch(0)
        step_fns = {}
        for variant in (step_lib.BALANCED, step_lib.COMM_PRIORITY):
            fn = step_lib.make_train_step(
                cfg, opt_cfg, mesh=mesh, variant=variant)
            step_fns[variant] = step_lib.jit_step(
                fn, mesh, state, specs_tree, batch0)

    scheduler = None
    if use_kf:
        telemetry = Telemetry(costs_by_variant={
            0: StaticCosts(flops=0, hbm_bytes=0, collective_bytes=1e9),
            1: StaticCosts(flops=0, hbm_bytes=0, collective_bytes=2.5e8),
        })
        scheduler = KFScheduler(SchedulerConfig(
            epoch_steps=10, warmup_steps=30, hold_steps=20,
            revert_steps=60), telemetry)

    return state, step_fns, ds.batch, scheduler, mesh, cfg


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--size", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--kf", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure (fault-tolerance demo)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    mesh = (make_production_mesh() if args.size == "full"
            else make_host_mesh())
    state, step_fns, make_batch, scheduler, mesh, cfg = build(
        args.arch, args.size, args.seq_len, args.global_batch,
        mesh=mesh, lr=args.lr, total_steps=args.steps, seed=args.seed,
        use_kf=args.kf)

    loop_cfg = loop_lib.LoopConfig(
        total_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
    )
    with sharding.activate(mesh):
        result = loop_lib.run(loop_cfg, state, step_fns, make_batch,
                              scheduler, fail_at=args.fail_at)
    losses = result.losses
    print(f"[train] {args.arch} ({args.size}) {len(losses)} steps: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(min {np.min(losses):.4f}); "
          f"stragglers={result.straggler_events}; "
          f"variants used={sorted(set(result.variants))}")
    return result


if __name__ == "__main__":
    main()
