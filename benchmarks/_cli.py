"""Shared CLI surface for the fig drivers (DESIGN.md §15).

Every fig driver used to copy-paste the same argparse block
(``--devices``, ``--backend``, ``--profile``, plus ``--smoke``/``--gate``
where gated); new cross-cutting flags then had to land six times.  This
helper is the one place that surface lives now:

    ap = _cli.build_parser(__doc__, smoke_help=..., gate_help=...)
    args = ap.parse_args(argv)
    wl = _cli.registered_trace(args)        # --trace F.npz -> workload name

``--trace PATH`` (and its ``--trace-fit`` companion) registers a recorded
demand trace (`traffic.RecordedTrace` npz schema) as a sweep workload so
any figure can be driven by replayed/adapted demand instead of its
builtin synthetic workloads.  The default fit is "stretch": drivers run
at many ``n_epochs``, and a linear resample keeps any trace usable
everywhere (pass ``--trace-fit exact`` to insist on bitwise replay).

``--faults NAME`` injects a registered fault scenario (`faults.FAULTS`,
DESIGN.md §16) into every row a driver sweeps: drivers splat
`fault_overrides(args)` into their `run(**overrides)` call, and since
`faults` is an `NoCConfig` field carried as traced data, the faulty grid
still shares the healthy grid's one compiled program.

``--placement NAME`` (placement scenarios, `placement.PLACEMENTS`) and
``--topology WxH`` (non-paper mesh grids) follow the same pattern
(DESIGN.md §17): `placement_overrides(args)` / `topology_overrides(args)`
splat into `run(**overrides)` with the same precedence rule — the CLI
value overrides any per-spec value.  Placement is traced data (shared
program); topology is structural (its own compile, like ``--backend``).
"""
from __future__ import annotations

import argparse

BACKENDS = ("ref", "pallas")

# The registry name `--trace` files land under: drivers substitute it for
# their builtin workload/scenario set when the flag is present.
TRACE_WORKLOAD = "TRACE"


def build_parser(
    description: str | None = None,
    *,
    smoke_help: str | None = None,
    gate_help: str | None = None,
    trace: bool = True,
) -> argparse.ArgumentParser:
    """The fig drivers' common parser; driver-specific flags add on top."""
    ap = argparse.ArgumentParser(
        description=description,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the sweep batch axis across N devices")
    ap.add_argument("--backend", choices=BACKENDS, default=None,
                    help="cycle engine: dense jnp (ref) or the fused "
                         "full-cycle lane kernel (pallas), bitwise-"
                         "identical; default: the platform's (pallas on "
                         "a TPU, ref elsewhere)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture jax.profiler traces (compile + steady "
                         "phases) into DIR")
    if smoke_help is not None:
        ap.add_argument("--smoke", action="store_true", help=smoke_help)
    if gate_help is not None:
        ap.add_argument("--gate", action="store_true", help=gate_help)
    ap.add_argument("--faults", metavar="NAME", default=None,
                    help="inject a registered fault scenario "
                         "(repro.core.noc.faults.FAULTS, e.g. FLAP_BFS) "
                         "into every swept row; default: healthy fabric")
    ap.add_argument("--placement", metavar="NAME", default=None,
                    help="apply a registered placement scenario "
                         "(repro.core.noc.placement.PLACEMENTS, e.g. "
                         "GPU_NEAR_MC) to every swept row; default: the "
                         "static paper layout")
    ap.add_argument("--topology", metavar="WxH", default=None,
                    help="run on a WxH mesh instead of the paper's 6x6 "
                         "(e.g. 4x4, 8x8; validated against the MC rows, "
                         "capped at 64 routers)")
    if trace:
        ap.add_argument("--trace", metavar="F.npz", default=None,
                        help="drive the figure with a recorded demand trace "
                             "(DESIGN.md §15 npz schema) instead of its "
                             "builtin workloads")
        ap.add_argument("--trace-fit", choices=("exact", "tile", "stretch"),
                        default="stretch",
                        help="how a trace of T epochs fits a run of "
                             "n_epochs: exact requires T == n_epochs, tile "
                             "repeats cyclically, stretch resamples "
                             "linearly (default)")
    return ap


def fault_overrides(args) -> dict:
    """Config overrides for ``--faults`` ({} when the flag is absent).

    Drivers splat the result into their `run(**overrides)` call; `sweep`
    forwards overrides to every row's `NoCConfig`, where an explicit
    `faults` key takes precedence over any per-spec value.  The name is
    validated eagerly so a typo fails at the CLI (with the registry's
    close-match suggestions) instead of deep inside the dispatch.
    """
    name = getattr(args, "faults", None)
    if not name:
        return {}
    from repro.core.noc.faults import lookup_faults

    lookup_faults(name)
    print(f"# --faults: injecting fault scenario {name!r} into every row")
    return {"faults": name}


def placement_overrides(args) -> dict:
    """Config overrides for ``--placement`` ({} when the flag is absent).

    Mirrors `fault_overrides` precedence exactly: `sweep` forwards the
    override to every row's `NoCConfig`, beating any per-spec value; the
    name is validated eagerly (with close-match suggestions)."""
    name = getattr(args, "placement", None)
    if not name:
        return {}
    from repro.core.noc.placement import lookup_placement

    lookup_placement(name)
    print(f"# --placement: applying placement scenario {name!r} to every row")
    return {"placement": name}


def topology_overrides(args) -> dict:
    """Config overrides for ``--topology WxH`` ({} when absent).

    Parses "WxH" into `NoCConfig(width=..., height=...)` and validates the
    grid eagerly (`topology.validate_topology_args`, against the default
    MC count) so an impossible mesh fails at the CLI."""
    spec = getattr(args, "topology", None)
    if not spec:
        return {}
    try:
        w_s, h_s = spec.lower().split("x")
        width, height = int(w_s), int(h_s)
    except ValueError:
        raise SystemExit(
            f"--topology expects WxH (e.g. 6x6, 4x8), got {spec!r}"
        ) from None
    from repro.core.noc.sim import NoCConfig
    from repro.core.noc.topology import validate_topology_args

    validate_topology_args(width, height, NoCConfig().n_mc)
    print(f"# --topology: running every row on a {width}x{height} mesh")
    return {"width": width, "height": height}


def shared_overrides(args) -> dict:
    """Every cross-cutting override in one splat: --faults, --placement,
    --topology.  The keys are disjoint by construction."""
    return {
        **fault_overrides(args),
        **placement_overrides(args),
        **topology_overrides(args),
    }


def registered_trace(args) -> str | None:
    """Register ``--trace`` (if given) as a workload; return its name.

    Returns None when the flag is absent so drivers can fall back to
    their builtin workload sets.
    """
    path = getattr(args, "trace", None)
    if not path:
        return None
    from repro.core.noc.traffic import register_trace

    trace = register_trace(TRACE_WORKLOAD, path,
                           fit=getattr(args, "trace_fit", "stretch"),
                           overwrite=True)
    print(f"# --trace: registered {path} as workload {TRACE_WORKLOAD!r} "
          f"({trace.n_epochs_recorded} epochs, fit={trace.fit})")
    return TRACE_WORKLOAD
