"""Pallas TPU selective-scan kernel (Mamba1 diagonal recurrence).

    h_t = a_t * h_{t-1} + b_t        a, b: (B, L, D, S) fp32

Grid (B, nD, nL): the LAST axis walks chunks of the sequence sequentially,
carrying the (TD, S) boundary state in VMEM scratch — the Pallas mirror of
`repro.models.mamba.chunked_scan`.  Inside a chunk the recurrence runs as a
log2(TC)-step Hillis–Steele doubling scan over the time axis: each step is
one full-tile multiply-add on the VPU (time on sublanes, channels on lanes),
instead of TC serial scalar steps.

TPU adaptation note (DESIGN.md §3): the CUDA Mamba kernel fuses conv1d +
scan per thread-block with warp shuffles; TPU has no warp-level exchange, so
the doubling scan over a (TC, TD*S) VMEM tile is the natural lowering — the
shifted operand is a sublane roll, compute stays dense elementwise.

VMEM per step (TC=256, TD=256, S=16): a/b/hs tiles 3 x 4 MiB fp32 + carry
16 KiB ≈ 12 MiB — sized to the v5e budget; shrink TD for larger S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    a_ref, b_ref, h0_ref,     # (1, TC, TD, S), (1, TC, TD, S), (1, TD, S)
    hs_ref, hlast_ref,        # (1, TC, TD, S), (1, TD, S)
    h_scr,                    # VMEM (1, TD, S) fp32 carry across chunks
    *,
    chunk: int,
    n_chunks: int,
):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_scr[...] = h0_ref[...]

    a = a_ref[0]              # (TC, TD, S)
    b = b_ref[0]

    # Hillis–Steele doubling: after step k, (a, b)[t] composes the recurrence
    # over the last 2^k elements.  Shift via jnp.roll + mask (sublane roll).
    shift = 1
    while shift < chunk:
        a_prev = jnp.roll(a, shift, axis=0)
        b_prev = jnp.roll(b, shift, axis=0)
        t = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        live = t >= shift
        a_new = jnp.where(live, a * a_prev, a)
        b_new = jnp.where(live, a * b_prev + b, b)
        a, b = a_new, b_new
        shift *= 2

    # prefix over the chunk composed with the incoming carry
    h0 = h_scr[...]
    hs = a * h0 + b           # (TC, TD, S)
    hs_ref[0] = hs
    # the last row is read back through the ref: a value index (`hs[-1]`)
    # lowers to a dynamic_slice, which Mosaic does not implement
    h_scr[...] = hs_ref[0, pl.ds(chunk - 1, 1)]

    @pl.when(il == n_chunks - 1)
    def _final():
        hlast_ref[...] = h_scr[...]


def mamba_scan_kernel(
    a: jax.Array,   # (B, L, D, S) fp32
    b: jax.Array,
    h0: jax.Array,  # (B, D, S) fp32
    *,
    chunk: int = 256,
    block_d: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bsz, L, d, s = a.shape
    chunk = min(chunk, L)
    block_d = min(block_d, d)
    assert L % chunk == 0 and d % block_d == 0, (L, chunk, d, block_d)
    n_chunks, n_d = L // chunk, d // block_d

    grid = (bsz, n_d, n_chunks)
    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    hs, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, s), lambda b_, id_, il: (b_, il, id_, 0)),
            pl.BlockSpec((1, chunk, block_d, s), lambda b_, id_, il: (b_, il, id_, 0)),
            pl.BlockSpec((1, block_d, s), lambda b_, id_, il: (b_, id_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d, s), lambda b_, id_, il: (b_, il, id_, 0)),
            pl.BlockSpec((1, block_d, s), lambda b_, id_, il: (b_, id_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, L, d, s), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d, s), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    return hs, hlast
