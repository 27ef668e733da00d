"""Pallas TPU kernel: chunked SSD (Mamba2) scan forward.

TPU adaptation of the SSD insight (Dao & Gu 2024): the inter-chunk
recurrence carries an (head_dim × d_state) state matrix in VMEM scratch
across a *sequential* chunk grid axis; intra-chunk work is three MXU
matmuls on (L × L)/(L × N)/(L × P) tiles.  Where the GPU kernel spreads
chunks over thread blocks and synchronizes states through global memory,
the TPU version makes the chunk axis the innermost sequential grid
dimension — states never leave VMEM.

Grid: (B, H, n_chunks) — last axis "arbitrary"; scratch S (P, N) f32.
Per (b, h, c) block:

    cum   = cumsum(dA)                              (L,)  [masked sums]
    y_diag = ((C·Bᵀ) ∘ exp(segsum(dA)) ∘ tril) · x  (L, P)
    y_off  = exp(cum) ∘ (C · Sᵀ)                    (L, P)
    S     ← exp(cum_L) S + xᵀ · (exp(cum_L − cum) ∘ B)

Inputs are pre-arranged (B, H, C, L, ·) by ops.py (dt folded into x and
dA = dt·A_h, GQA-style group broadcast already applied).  Block shapes:
L multiple of 8; P/N are lane-padded to 128 by ops.py for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(xdt_ref, da_ref, da_row_ref, b_ref, c_ref, y_ref, s_out_ref,
                s_ref, *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)       # (L, P)
    da = da_ref[0, 0, 0].astype(jnp.float32)         # (L, 1)
    da_row = da_row_ref[0, 0, 0].astype(jnp.float32)  # (1, L)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)          # (L, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)          # (L, N)
    L = xdt.shape[0]

    # prefix sums of dA as masked (L, L) reductions — Mosaic has no cumsum
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = col <= row
    cum = jnp.sum(jnp.where(tri, jnp.broadcast_to(da_row, (L, L)), 0.0),
                  axis=1, keepdims=True)             # (L, 1): cum_z
    cum_row = jnp.sum(jnp.where(row <= col, jnp.broadcast_to(da, (L, L)),
                                0.0), axis=0, keepdims=True)  # (1, L): cum_s
    total = jnp.sum(da_row, axis=1, keepdims=True)   # (1, 1): cum_L
    seg = cum - cum_row                              # (L, L): cum_z − cum_s
    lmat = jnp.where(tri, jnp.exp(seg), 0.0)

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y = jax.lax.dot_general(cb * lmat, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)

    s_prev = s_ref[...]                              # (P, N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cm, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (L,N)·(P,N)ᵀ → (L,P)

    decay_end = jnp.exp(total - cum)                 # (L, 1)
    s_ref[...] = (jnp.exp(total) * s_prev
                  + jax.lax.dot_general(
                      xdt, decay_end * Bm,
                      (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_scan_bhclp(xdt: jax.Array, da: jax.Array, b: jax.Array,
                   c: jax.Array, *, interpret: bool = False):
    """xdt (B,H,C,L,P); da (B,H,C,L,1); b, c (B,H,C,L,N).
    Returns (y (B,H,C,L,P), state (B,H,P,N) f32)."""
    da_row = jnp.swapaxes(da, -1, -2)                # (B,H,C,1,L)
    B, H, C, L, P = xdt.shape
    N = b.shape[-1]
    grid = (B, H, C)
    kernel = functools.partial(_ssd_kernel, n_chunks=C)
    return pl.pallas_call(
        kernel,
        grid=grid,
        name="ssd_scan",
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda i, j, k: (i, j, k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, C, L, P), xdt.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, da, da_row, b, c)
