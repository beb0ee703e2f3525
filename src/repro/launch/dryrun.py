import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract memory / cost / collective
analyses for EXPERIMENTS.md §Dry-run and §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod

Results land in results/dryrun/<mesh>_<arch>_<shape>.json.  The 512
placeholder host devices exist ONLY in this process (the env flag above is
set before jax initializes); smoke tests and benches see the host's real
single device.
"""
import argparse
import json
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.dist import sharding
from repro.launch import compile_cache
from repro.launch import hlo_cost, roofline, specs
from repro.launch.mesh import make_production_mesh
from repro.train import step as step_lib

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _sharding_tree(spec_tree, abstract_tree, mesh):
    return jax.tree.map(
        lambda s, a: NamedSharding(
            mesh, sharding.logical_to_mesh(s, getattr(a, "shape", None), mesh)
        ),
        spec_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _batch_shardings(batch_abs, mesh):
    return jax.tree.map(
        lambda v: NamedSharding(
            mesh,
            sharding.logical_to_mesh(
                P("batch", *([None] * (len(v.shape) - 1))), v.shape, mesh),
        ),
        batch_abs,
    )


def lower_cell(arch: str, shape: str, mesh, *, variant: int = 0,
               remat: str = None, moe_group: int = 0):
    """Returns (lowered, n_chips). Raises on inapplicable cells."""
    import dataclasses as _dc

    cfg = configs.get(arch)
    if remat:
        cfg = _dc.replace(cfg, remat=remat)
    if moe_group:
        cfg = _dc.replace(cfg, moe_groups=moe_group)
    cell = specs.SHAPES[shape]
    if not specs.applicable(arch, shape):
        raise ValueError(f"{arch} x {shape}: skipped (DESIGN.md §5)")

    with sharding.activate(mesh):
        if cell.kind == "train":
            opt_cfg = specs.default_opt_cfg(cfg)
            mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            state_abs, state_specs = specs.abstract_train_state(
                cfg, opt_cfg,
                with_residuals=(variant == step_lib.COMM_PRIORITY
                                and "pod" in mesh.axis_names),
                data_size=mesh_sizes.get("data", 1),
                pod_size=mesh_sizes.get("pod", 1))
            batch_abs = specs.batch_struct(cfg, cell)
            step = step_lib.make_train_step(
                cfg, opt_cfg, mesh=mesh, variant=variant)
            state_sh = _sharding_tree(state_specs, state_abs, mesh)
            batch_sh = _batch_shardings(batch_abs, mesh)
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, batch_abs)
        elif cell.kind == "prefill":
            params_abs = specs.abstract_params(cfg)
            pspecs = specs.param_specs(cfg)
            batch_abs = specs.batch_struct(cfg, cell)
            step = specs.make_prefill_step(cfg)
            params_sh = _sharding_tree(pspecs, params_abs, mesh)
            batch_sh = _batch_shardings(batch_abs, mesh)
            logits_sh = NamedSharding(
                mesh, sharding.logical_to_mesh(
                    P("batch", None, "vocab"),
                    (cell.batch, cell.seq, cfg.vocab_size), mesh))
            jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                             out_shardings=logits_sh)
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            params_abs = specs.abstract_params(cfg)
            pspecs = specs.param_specs(cfg)
            token_abs, state_abs = specs.abstract_decode_inputs(cfg, cell)
            dspecs = specs.decode_specs(cfg)
            step = specs.make_serve_step(cfg)
            params_sh = _sharding_tree(pspecs, params_abs, mesh)
            state_sh = _sharding_tree(dspecs, state_abs, mesh)
            token_sh = NamedSharding(
                mesh, sharding.logical_to_mesh(
                    P("batch", None), (cell.batch, 1), mesh))
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, token_sh, state_sh),
                out_shardings=(None, state_sh),
                donate_argnums=(2,))
            lowered = jitted.lower(params_abs, token_abs, state_abs)
    return lowered, mesh.size


def run_cell(arch: str, shape: str, mesh_kind: str, *, variant: int = 0,
             out_dir: str = RESULTS_DIR, flash: bool = False,
             seq_parallel: bool = False, dp_only: bool = False,
             remat: str = None, moe_group: int = 0,
             fused_scan: bool = False, tag: str = "") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    record: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "mesh_shape": list(mesh.devices.shape),
        "variant": variant,
        "options": {"flash": flash, "seq_parallel": seq_parallel,
                    "dp_only": dp_only, "remat": remat,
                    "moe_group": moe_group, "fused_scan": fused_scan},
    }
    sharding.set_option("seq_parallel", seq_parallel)
    sharding.set_option("dp_only", dp_only)
    try:
        lowered, n_chips = lower_cell(arch, shape, mesh, variant=variant,
                                      remat=remat, moe_group=moe_group)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        xla_cost = hlo_cost.xla_cost_analysis(compiled)
        mem = compiled.memory_analysis()
        hlo_text = compiled.as_text()
        # scan-aware analysis (XLA's cost_analysis counts while bodies once
        # — see launch/hlo_cost.py); the compiled module is per-device.
        _cfg0 = configs.get(arch)
        cost_c = hlo_cost.analyze_hlo(
            hlo_text,
            seq=specs.SHAPES[shape].seq if flash else None,
            assume_flash=flash,
            ssm_state=_cfg0.ssm_state if fused_scan else None,
            assume_fused_scan=fused_scan,
            pod_size=256 if mesh_kind == "multipod" else None)
        rl = roofline.analyze(
            {"flops": cost_c.flops, "bytes accessed": cost_c.bytes},
            roofline.CollectiveStats(
                wire_bytes=cost_c.wire_bytes, by_kind=cost_c.wire_by_kind,
                count=int(cost_c.coll_count)),
            n_chips)
        cfg = configs.get(arch)
        cell = specs.SHAPES[shape]
        mf = roofline.model_flops(cfg, cell)

        record.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "cost_xla_unscaled": {
                k: float(v) for k, v in xla_cost.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed")},
            "memory": _mem_dict(mem),
            "collectives": {
                "wire_bytes_per_chip": cost_c.wire_bytes,
                "wire_cross_pod_per_chip": cost_c.wire_cross_pod,
                "count": cost_c.coll_count,
                "by_kind": cost_c.wire_by_kind,
            },
            "roofline": rl.to_dict(),
            "model_flops_global": mf,
            "model_flops_per_chip": mf / n_chips,
            "useful_flops_ratio": (
                (mf / n_chips) / rl.flops_per_chip
                if rl.flops_per_chip else None),
        })
    except ValueError as e:
        if "skipped" in str(e):
            record.update({"status": "skip", "reason": str(e)})
        else:
            record.update({"status": "error", "error": str(e),
                           "traceback": traceback.format_exc()})
    except Exception as e:  # noqa: BLE001 — record, don't crash the sweep
        record.update({"status": "error", "error": str(e)[:2000],
                       "traceback": traceback.format_exc()[-4000:]})

    os.makedirs(out_dir, exist_ok=True)
    fname = f"{mesh_kind}_{arch}_{shape}" + \
        (f"_v{variant}" if variant else "") + \
        (f"_{tag}" if tag else "") + ".json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=1, default=float)
    sharding.set_option("seq_parallel", False)
    sharding.set_option("dp_only", False)
    return record


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if out:
        out["total_hbm_bytes"] = (
            out.get("temp_size_in_bytes", 0)
            + out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--variant", type=int, default=0)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    # §Perf hillclimb knobs
    ap.add_argument("--flash", action="store_true",
                    help="analyze with the Pallas flash-attention traffic model")
    ap.add_argument("--fused-scan", action="store_true",
                    help="analyze with the fused mamba-scan kernel traffic model")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--remat", default=None, choices=[None, "full", "dots",
                                                      "none"])
    ap.add_argument("--moe-group", type=int, default=0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = (specs.all_cells() if args.all
             else [(args.arch, args.shape)])
    n_errors = 0
    for arch, shape in cells:
        if arch is None or shape is None:
            ap.error("--arch/--shape required unless --all")
        rec = run_cell(arch, shape, args.mesh, variant=args.variant,
                       out_dir=args.out, flash=args.flash,
                       seq_parallel=args.seq_parallel, dp_only=args.dp_only,
                       remat=args.remat, moe_group=args.moe_group,
                       fused_scan=args.fused_scan, tag=args.tag)
        status = rec["status"]
        n_errors += status == "error"
        extra = ""
        if status == "ok":
            rl = rec["roofline"]
            extra = (f" dom={rl['dominant']} t={rl['step_time_s']:.4f}s "
                     f"compile={rec['compile_s']:.0f}s")
        print(f"[dryrun] {args.mesh} {arch} {shape}: {status}{extra}",
              flush=True)
        if status == "ok":
            print(f"  memory_analysis: {rec['memory']}")
            print(f"  cost_analysis (scan-corrected): "
                  f"flops/chip={rec['roofline']['flops_per_chip']:.3e} "
                  f"bytes/chip={rec['roofline']['hbm_bytes_per_chip']:.3e} "
                  f"wire/chip={rec['roofline']['wire_bytes_per_chip']:.3e}",
                  flush=True)
        elif status == "error":
            print(f"  error: {rec['error']}", file=sys.stderr, flush=True)
    if n_errors:
        raise SystemExit(f"[dryrun] {n_errors} cell(s) failed")


if __name__ == "__main__":
    main()
