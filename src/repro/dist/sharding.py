"""Logical-axis sharding: resolution of model-level axis names onto mesh axes.

Model code annotates parameters and activations with LOGICAL names
("batch", "mlp", "kv", ...; see models/layers.py).  This module owns the
single source of truth for how those names land on the physical mesh:

  * divisibility — an axis is only sharded if the dim size divides the mesh
    axis size; otherwise it degrades to replication (never an error, which
    is what makes elastic remesh (ckpt/elastic.py) a pure re-resolution);
  * conflict fallback — within one PartitionSpec each mesh axis is claimed
    at most once.  Claims resolve in priority order (primary tensor-parallel
    users first), and losers fall through their candidate list: e.g. in a
    MoE weight (expert, embed, mlp) `expert` takes `model` and `mlp` falls
    back to `data` (FSDP);
  * multi-pod batch — "batch" claims every data-parallel mesh axis it can
    (("pod", "data") jointly when the pod axis exists and divides).

Options (process-global, see `set_option`):
  seq_parallel — resolve the activation sequence axis onto `model`
                 (Megatron-style sequence parallelism for norm/residual);
  dp_only      — drop every tensor-parallel rule (pure data parallel), used
                 by the dry-run hillclimb as an ablation.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# Ordered mesh-axis candidates per logical name.  First candidate that is
# (a) present in the mesh, (b) unclaimed within the spec and (c) divides the
# dim wins; an empty tuple means "always replicate".
AXIS_RULES: dict[str, tuple[str, ...]] = {
    "batch":      ("pod", "data"),     # joint claim (multi-pod data parallel)
    "grad_shard": ("data",),           # EF residual shards (train/step.py)
    "vocab":      ("model",),
    "heads":      ("model",),
    "kv":         ("model",),
    "expert":     ("model",),          # expert parallelism
    "mlp":        ("model", "data"),   # FSDP fallback on conflict
    "kv_seq":     ("model",),          # seq-sharded KV cache when kv loses
    "embed":      (),
    "seq":        (),                  # ("model",) under seq_parallel
}

# Logical names that claim ALL their candidates jointly (one tuple entry)
# rather than first-fit.
_JOINT = frozenset({"batch"})

# Conflict priority: lower resolves first.  Primary tensor-parallel users
# (heads/kv/expert/vocab) outrank the FSDP fallback (mlp), which outranks
# the opportunistic KV-sequence shard.
_PRIORITY = {"mlp": 1, "kv_seq": 2}

_OPTIONS = {"seq_parallel": False, "dp_only": False}
_ACTIVE_MESH: Optional[Any] = None


# --------------------------------------------------------------------------
# Options + active-mesh context
# --------------------------------------------------------------------------

def set_option(name: str, value: bool) -> None:
    if name not in _OPTIONS:
        raise KeyError(f"unknown sharding option {name!r} "
                       f"(have {sorted(_OPTIONS)})")
    _OPTIONS[name] = bool(value)


def get_option(name: str) -> bool:
    return _OPTIONS[name]


def seq_axis() -> str:
    """Logical name of the activation sequence axis (resolution is governed
    by the seq_parallel option, so call sites never branch)."""
    return "seq"


@contextlib.contextmanager
def activate(mesh):
    """Make `mesh` the resolution target for `constrain` within the block."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


def active_mesh():
    return _ACTIVE_MESH


# --------------------------------------------------------------------------
# Resolution
# --------------------------------------------------------------------------

def _mesh_sizes(mesh) -> dict[str, int]:
    # works for jax.sharding.Mesh and duck-typed meshes (tests use a
    # FakeMesh with .axis_names / .devices only)
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _candidates(name: str, sizes: dict[str, int]) -> tuple[str, ...]:
    if _OPTIONS["dp_only"] and name not in ("batch", "grad_shard"):
        return ()
    if name == "seq":
        return ("model",) if _OPTIONS["seq_parallel"] else ()
    if name in AXIS_RULES:
        return AXIS_RULES[name]
    if name in sizes:          # already a mesh-axis name: pass through
        return (name,)
    return ()                  # unknown logical name: replicate


def _prio(entry) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    return min((_PRIORITY.get(n, 0) for n in names if n is not None),
               default=0)


def _divides(dim: Optional[int], n: int) -> bool:
    return dim is None or (n > 0 and dim % n == 0)


def logical_to_mesh(spec: P, shape: Optional[Sequence[int]], mesh) -> P:
    """Resolve a logical PartitionSpec against `mesh` for a tensor `shape`.

    Returns a spec whose entries are tuples of mesh-axis names (or None) —
    ready for `NamedSharding`.  `shape` may be None to skip divisibility
    checks, or shorter/longer than the spec (extra dims replicate).
    """
    sizes = _mesh_sizes(mesh)
    entries = list(spec)
    dims: list[Optional[int]] = [None] * len(entries)
    if shape is not None:
        for i in range(min(len(entries), len(shape))):
            dims[i] = int(shape[i])

    resolved: list[Optional[tuple[str, ...]]] = [None] * len(entries)
    used: set[str] = set()
    order = sorted(range(len(entries)), key=lambda i: (_prio(entries[i]), i))
    for i in order:
        entry = entries[i]
        if entry is None:
            continue
        claimed: list[str] = []
        for name in (entry if isinstance(entry, tuple) else (entry,)):
            if name is None:
                continue
            cands = tuple(a for a in _candidates(name, sizes)
                          if a in sizes and a not in used
                          and a not in claimed)
            if name in _JOINT:
                # longest suffix of the candidate list whose product divides
                # (prefer ("pod","data") jointly, then ("data",), ...)
                for k in range(len(cands)):
                    sub = cands[k:]
                    prod = 1
                    for a in sub:
                        prod *= sizes[a]
                    if _divides(dims[i], prod):
                        claimed.extend(sub)
                        break
            else:
                for a in cands:
                    if _divides(dims[i], sizes[a]):
                        claimed.append(a)
                        break
        if claimed:
            used.update(claimed)
            resolved[i] = tuple(claimed)
    return P(*resolved)


def shard_specs(spec_tree, template, mesh):
    """Resolve a logical spec tree into a NamedSharding tree.

    `template` supplies shapes (arrays or ShapeDtypeStructs) and must be
    congruent with `spec_tree` (tested by tests/test_spec_congruence.py).
    """
    return jax.tree.map(
        lambda s, t: NamedSharding(
            mesh, logical_to_mesh(s, getattr(t, "shape", None), mesh)),
        spec_tree, template,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------------------------
# Activation constraints
# --------------------------------------------------------------------------

def _in_manual_region() -> bool:
    """True while tracing inside shard_map (named mesh axes in scope).
    with_sharding_constraint over manual axes is invalid there — the body is
    already per-device — so `constrain` becomes the identity."""
    return bool(jax.core.nonempty_axis_env_DO_NOT_USE())


def constrain(x, *logical_axes):
    """`with_sharding_constraint` by logical names against the active mesh.

    No-op when no mesh is active (single-host eager paths) or inside a
    shard_map body (the manual region owns its own layout)."""
    mesh = _ACTIVE_MESH
    if mesh is None or _in_manual_region():
        return x
    spec = logical_to_mesh(P(*logical_axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Host-local sweep mesh
# --------------------------------------------------------------------------

def sweep_mesh(n_devices: Optional[int] = None, axis: str = "sweep"):
    """A 1-D mesh over host-local devices for data-parallel sweep dispatch.

    The NoC sweep engine (core/noc/sim.py) splits its flat batch axis over
    this mesh's single `sweep` axis — pure data parallelism, no
    collectives.  `n_devices=None` takes every local device.
    """
    import numpy as np

    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 0 < n <= len(devs):
        raise ValueError(
            f"sweep_mesh over {n} devices, but {len(devs)} are available"
        )
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))

