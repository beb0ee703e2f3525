"""Smoke check of the simulator's main path on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

Phase A runs the Fig. 9/10/11 NoC sweep (6 workloads x 4 modes, seed 0)
at the paper's package and length -- the default `NoCConfig`: 6x6 mesh,
8 MCs, 4 VCs, buffer depth 4, 120 epochs x 500 cycles -- through
`sim.sweep` once per cycle engine (`ref`, `pallas`, `pallas_arb`).  Every
`SimResult` leaf must be bitwise equal across the engines, every summary
value finite, and both Pallas programs must hold a compiled Mosaic kernel
(`tpu_custom_call`).

Phase B serves 8 requests of the bursty workload with the KF-arbitrated
engine (`repro.launch.serve.build`) on stablelm-1.6b at its published
widths, with random weights from seed 0.  Every request must finish with
token ids inside the vocabulary, and one decode step through the serving
cache must agree with `lm.forward` computed in float32.

`--chips 4` runs only the sharded sweep: the same grid on the `pallas`
engine through `sim.sweep_sharded` over 4 devices and through `sim.sweep`
on one, which must agree bitwise, with the sharded output on 4 devices.

Earlier lines report what ran and how long it took; the last line of
stdout is one JSON object naming the device.  Any failed check raises, so
the exit code is non-zero and that line is never printed.  Without a TPU
the script fails at once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ENGINES = ("ref", "pallas", "pallas_arb")
SERVE_ARCH = "stablelm-1.6b"
N_REQUESTS = 8
# Max |serve - reference| over one decode step's logits, as a share of the
# reference's largest |logit|.  The served path stores weights and carries
# activations in bfloat16 (8-bit mantissa, rounding error 2^-9 per op)
# through 24 residual layers; the float32 reference does neither.
LOGIT_RTOL = 3e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def leaves_bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np

    la = [np.asarray(x) for x in jax.tree.leaves(a)]
    lb = [np.asarray(x) for x in jax.tree.leaves(b)]
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
        for x, y in zip(la, lb))


def grid_specs():
    from benchmarks.fig9_10_11_configs import MODES, WORKLOADS
    from repro.core.noc import sim

    return [sim.SweepSpec(m, wl, seed=0) for wl in WORKLOADS for m in MODES]


def compile_tile(specs, backend: str, **overrides):
    """AOT-compile the sweep-tile program that `sim.sweep` dispatches; the
    later `sweep` call reuses this executable."""
    from repro.core.noc import sim

    tile = specs[:sim.SWEEP_TILE]
    cfgs = [sim.NoCConfig(mode=s.mode, seed=s.seed, backend=backend,
                          **overrides) for s in tile]
    stc, mp, prof, seeds, flt, plc = sim.batch_args(
        cfgs, [s.workload for s in tile])
    state0 = sim.init_sim_state(stc, len(tile))
    return sim._batch_jit().lower(
        stc, mp, prof, seeds, state0, flt, plc).compile()


def run_sweep(specs, **kw):
    import jax
    from repro.core.noc import sim

    t0 = time.perf_counter()
    rows = sim.sweep(specs, **kw)
    jax.block_until_ready(rows)
    return rows, time.perf_counter() - t0


def phase_sweep(**overrides) -> None:
    """Phase A: the paper grid on every cycle engine, bitwise equal."""
    import numpy as np
    from repro.core.noc import sim

    specs = grid_specs()
    cfg = sim.NoCConfig(**overrides)
    cycles = cfg.n_epochs * cfg.epoch_len
    log(f"[sweep] {len(specs)} points, {cfg.width}x{cfg.height} mesh, "
        f"{cfg.n_mc} MCs, {cfg.n_vcs} VCs, depth {cfg.buf_depth}, "
        f"{cfg.n_epochs} epochs x {cfg.epoch_len} cycles = {cycles} "
        f"cycles per point")
    results = {}
    for be in ENGINES:
        t0 = time.perf_counter()
        compiled = compile_tile(specs, be, **overrides)
        compile_s = time.perf_counter() - t0
        kernel = "tpu_custom_call" in compiled.as_text()
        if be != "ref" and not kernel:
            raise AssertionError(f"{be}: no tpu_custom_call in the program")
        rows, sweep_s = run_sweep(specs, backend=be, **overrides)
        results[be] = rows
        log(f"[sweep] engine={be} compile_s={compile_s:.3f} "
            f"sweep_s={sweep_s:.3f} tpu_custom_call={kernel}")
    for be in ENGINES[1:]:
        for sp, a, b in zip(specs, results["ref"], results[be]):
            if not leaves_bitwise_equal(a, b):
                raise AssertionError(f"{be} != ref at {sp.mode}/{sp.workload}")
    summaries = [sim.summarize(row) for row in results["ref"]]
    for sp, s in zip(specs, summaries):
        if not all(np.isfinite(v) for v in s.values()):
            raise AssertionError(f"non-finite summary at {sp}: {s}")
    log(f"[sweep] {', '.join(ENGINES[1:])} bitwise equal to ref on all "
        f"{len(specs)} points; all summaries finite")
    for wl in dict.fromkeys(sp.workload for sp in specs):
        cells = "  ".join(
            f"{sp.mode}: gpu={s['gpu_ipc']:.4f} cpu={s['cpu_ipc']:.4f} "
            f"lat={s['avg_latency']:.2f}"
            for sp, s in zip(specs, summaries) if sp.workload == wl)
        log(f"[sweep] {wl:5s} {cells}")


def reference_logits(params, tokens, cfg):
    """`lm.forward` in float32: float32 weights and activations at the
    highest matmul precision (a plain float32 matmul runs at lower
    precision on a TPU).  The model's activation dtype is a module
    constant, so it is swapped for this trace only."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    act = lm.ACT_DTYPE
    lm.ACT_DTYPE = jnp.float32
    try:
        with jax.default_matmul_precision("highest"):
            logits = jax.jit(
                lambda p, t: lm.forward(p, t, cfg).logits)(p32, tokens)
            return jax.block_until_ready(logits)
    finally:
        lm.ACT_DTYPE = act


def check_decode_against_reference(engine, seed: int, n_prompt: int = 32):
    """Prefill a prompt into one slot of the serving cache, decode one
    token with the engine's compiled decode step, and compare the logits
    with the float32 forward pass over the same tokens."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    from repro.serve import cache as cache_lib

    cfg, ecfg = engine.cfg, engine.ecfg
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, n_prompt + 1)), jnp.int32)
    slot = ecfg.max_slots // 2
    state = lm.init_decode_state(ecfg.max_slots, ecfg.max_len, cfg)
    state = cache_lib.insert_request(
        state,
        lm.prefill_caches(engine.params, toks[:, :n_prompt], cfg,
                          ecfg.max_len),
        slot)
    step = jnp.zeros((ecfg.max_slots, 1), jnp.int32)
    step = step.at[slot, 0].set(toks[0, n_prompt])
    logits, _ = engine._decode_fn(engine.params, step, state)
    got = np.asarray(logits, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite decode logits")
    want = np.asarray(reference_logits(engine.params, toks, cfg),
                      np.float32)[0, -1]
    err = float(np.max(np.abs(got[slot, 0] - want)))
    scale = float(np.max(np.abs(want)))
    log(f"[serve] decode vs float32 forward: max_abs_err={err:.6f} "
        f"max_abs_ref={scale:.6f} rel={err / scale:.6f} "
        f"(tolerance rel<={LOGIT_RTOL})")
    if not err <= LOGIT_RTOL * scale:
        raise AssertionError(f"decode logits off by {err} (ref {scale})")


def phase_serve(arch: str = SERVE_ARCH, size: str = "full",
                n_requests: int = N_REQUESTS, seed: int = 0) -> None:
    """Phase B: the KF-arbitrated server answers requests at full width."""
    import jax
    from repro.launch import serve

    t0 = time.perf_counter()
    engine, requests = serve.build(arch, "kf", n_requests=n_requests,
                                   seed=seed, size=size)
    jax.block_until_ready(engine.params)
    cfg = engine.cfg
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab_size}, {n_params} parameters, "
        f"built in {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    stats = engine.run(requests)
    serve_s = time.perf_counter() - t0
    done = {r.rid for r in stats.finished}
    if done != {r.rid for r in requests}:
        raise AssertionError(f"finished {sorted(done)} of {n_requests}")
    ids = [i for r in stats.finished for i in r.out_ids]
    if not ids or not all(0 <= i < cfg.vocab_size for i in ids):
        raise AssertionError("decoded token ids outside the vocabulary")
    s = stats.summary()
    log(f"[serve] mode=kf answered {len(done)}/{n_requests} requests, "
        f"{len(ids)} decoded tokens in range, {stats.iters} engine "
        f"iterations, wall_s={serve_s:.3f}, kf_on_frac={s['kf_on_frac']:.3f}")
    check_decode_against_reference(engine, seed)


def phase_sharded(n_devices: int, **overrides) -> None:
    """The sweep grid on the pallas engine, sharded vs one device."""
    import jax
    from repro.core.noc import sim

    specs = grid_specs()
    rows_1, t_1 = run_sweep(specs, backend="pallas", **overrides)
    log(f"[sharded] sweep on 1 device: {t_1:.3f} s (compile included)")
    t0 = time.perf_counter()
    rows_n = sim.sweep_sharded(specs, devices=n_devices, backend="pallas",
                               **overrides)
    jax.block_until_ready(rows_n)
    t_n = time.perf_counter() - t0
    log(f"[sharded] sweep_sharded on {n_devices} devices: {t_n:.3f} s "
        f"(compile included)")
    spans = {len(leaf.sharding.device_set)
             for row in rows_n for leaf in jax.tree.leaves(row)}
    if spans != {n_devices}:
        raise AssertionError(f"sharded output spans {spans} devices")
    for sp, a, b in zip(specs, rows_1, rows_n):
        if not leaves_bitwise_equal(a, b):
            raise AssertionError(f"sharded != sweep at {sp.mode}/{sp.workload}")
    log(f"[sharded] sweep_sharded bitwise equal to sweep on all "
        f"{len(specs)} points; output on {n_devices} devices")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}, but {len(devices)} devices")
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    if args.chips == 4:
        phase_sharded(args.chips)
    else:
        phase_sweep()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
