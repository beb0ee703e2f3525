"""Production meshes.  Functions, not module constants — importing this
module never touches jax device state (dry-run sets device-count flags
before first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with every axis `Auto`: the model code steers GSPMD
    with `with_sharding_constraint` over logical specs, which `Explicit`
    axes (jax.make_mesh's default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (1 CPU device in the container) — used by
    smoke tests and the CPU examples."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
