"""Fused selective-scan kernel v2: builds the recurrence inputs in VMEM.

The v1 kernel (kernel.py) consumes precomputed a = exp(dt*A) and b = dt*x*B
of shape (B, L, D, S) — an O(L*D*S) HBM round-trip that dominates the
falcon-mamba roofline (S=16 => 16x the O(L*D) activation traffic).  v2
fuses the construction AND the C-projection:

    HBM in : dt, xc (B, L, D) + b, c (B, L, S) + A (D, S)
    VMEM   : a = exp(dt x A), bx = (dt*xc) x b, doubling scan, y = <h, c>
    HBM out: y (B, L, D) + h_last (B, D, S)

traffic O(L*D + L*S) — the 2(S)x win the §Perf hillclimb claims, backed by
this kernel validating against the same oracle as v1.

Grid (B, nD, nL), sequence chunks innermost (sequential) with the carry in
VMEM scratch, exactly like v1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(
    dt_ref, xc_ref, b_ref, c_ref, a_mat_ref,   # (1,TC,TD) x2, (1,TC,S) x2, (TD,S)
    y_ref, hlast_ref,                          # (1,TC,TD), (1,TD,S)
    h_scr,                                     # VMEM (1, TD, S)
    *,
    chunk: int,
    n_chunks: int,
):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    dt = dt_ref[0].astype(jnp.float32)          # (TC, TD)
    xc = xc_ref[0].astype(jnp.float32)
    bt = b_ref[0].astype(jnp.float32)           # (TC, S)
    ct = c_ref[0].astype(jnp.float32)
    a_mat = a_mat_ref[...]                      # (TD, S) negative decay

    # build recurrence inputs in VMEM (never hit HBM)
    a = jnp.exp(dt[:, :, None] * a_mat[None])              # (TC, TD, S)
    bx = (dt * xc)[:, :, None] * bt[:, None, :]            # (TC, TD, S)

    # Hillis–Steele doubling over time (sublane axis)
    shift = 1
    while shift < chunk:
        a_prev = jnp.roll(a, shift, axis=0)
        b_prev = jnp.roll(bx, shift, axis=0)
        t = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        live = t >= shift
        a, bx = (jnp.where(live, a * a_prev, a),
                 jnp.where(live, a * b_prev + bx, bx))
        shift *= 2

    hs = a * h_scr[...] + bx                               # (TC, TD, S)
    y_ref[0] = jnp.sum(hs * ct[:, None, :], axis=-1).astype(y_ref.dtype)
    # static slice: a value index (`hs[-1]`) lowers to a dynamic_slice,
    # which Mosaic does not implement
    h_scr[...] = hs[chunk - 1:chunk]

    @pl.when(il == n_chunks - 1)
    def _final():
        hlast_ref[...] = h_scr[...]


def fused_mamba_scan_kernel(
    dt: jax.Array,     # (B, L, D) fp32
    xc: jax.Array,     # (B, L, D)
    b: jax.Array,      # (B, L, S)
    c: jax.Array,      # (B, L, S)
    a_mat: jax.Array,  # (D, S) negative decay matrix
    *,
    chunk: int = 256,
    block_d: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bsz, L, d = dt.shape
    s = a_mat.shape[1]
    chunk = min(chunk, L)
    block_d = min(block_d, d)
    assert L % chunk == 0 and d % block_d == 0, (L, chunk, d, block_d)
    n_chunks, n_d = L // chunk, d // block_d

    grid = (bsz, n_d, n_chunks)
    kernel = functools.partial(_fused_kernel, chunk=chunk, n_chunks=n_chunks)
    y, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, id_, il: (b_, il, id_)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, id_, il: (b_, il, id_)),
            pl.BlockSpec((1, chunk, s), lambda b_, id_, il: (b_, il, 0)),
            pl.BlockSpec((1, chunk, s), lambda b_, id_, il: (b_, il, 0)),
            pl.BlockSpec((block_d, s), lambda b_, id_, il: (id_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, id_, il: (b_, il, id_)),
            pl.BlockSpec((1, block_d, s), lambda b_, id_, il: (b_, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, L, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d, s), jnp.float32)],
        interpret=interpret,
    )(dt, xc, b, c, a_mat)
    return y, hlast


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def fused_mamba_scan(dt, xc, b, c, a_mat, *, chunk: int = 256,
                     block_d: int = 256):
    return fused_mamba_scan_kernel(
        dt, xc, b, c, a_mat, chunk=chunk, block_d=block_d,
        interpret=_interpret())
