"""Pallas TPU kernels: wire compression on the flat (M, P) layout.

The compression stage (core/compress.py, DESIGN.md §14) turns every
transmitted quantity — client deltas, ν updates, the server broadcast —
into a quantized/sparsified wire payload.  On the lane-padded flat layout
that is pure streaming elementwise arithmetic over ``(rows, 128·k)``
matrices with one scalar (the scale / the top-k threshold) per row:

* ``quantize_2d``   — int codes  q = clip(round(x / s), −qmax, qmax)
  (qmax = 127 for int8, 7 for int4; the int4 codes ship in an int8
  container on device — the *wire* accounting charges 4 bits/element,
  see ``compress.payload_bytes``);
* ``dequantize_2d`` — x̂ = q · s, the server-side reconstruction;
* ``topk_mask_2d``  — x̂ = x · 1[|x| ≥ tᵣ], the row-threshold form of
  top-k sparsification (the k-th magnitude per row is computed outside
  the kernel — a ``lax.top_k`` reduction, not a streaming op).

Same conventions as calibrated_update/kernel.py: one ``tile_2d`` VMEM tile
per grid step, per-row scalars ride along as a (rows, 1) f32 operand
blocked to (block rows, 1), compile-time-constant qmax in SMEM so
int8/int4 share one kernel.  Scale selection (padding-masked amax) is the
caller's job: these kernels transform exactly what they are given, so the
padding tail stays zero iff the input tail is zero — which the compressor
stage guarantees by masking (core/compress.py pins it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.calibrated_update.kernel import (BLOCK_ROWS, LANES,
                                                    tile_2d)


def _quantize_kernel(scal_ref, x_ref, s_ref, o_ref):
    qmax = scal_ref[0]
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)              # (br, 1) broadcasts
    o_ref[...] = jnp.clip(jnp.round(x / s), -qmax, qmax).astype(jnp.int8)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s).astype(o_ref.dtype)


def _topk_mask_kernel(x_ref, t_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)              # (br, 1) broadcasts
    o_ref[...] = jnp.where(jnp.abs(x) >= t, x, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("qmax", "block_rows",
                                             "interpret"))
def quantize_2d(x: jax.Array, scale: jax.Array, *, qmax: int = 127,
                block_rows: int = BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    """x: (rows, 128·k); scale: (rows, 1) f32 > 0.  Returns int8 codes in
    [−qmax, qmax] (int4 uses qmax = 7 in the same container)."""
    rows, cols = x.shape
    assert cols % LANES == 0, cols
    br, bc = tile_2d(rows, cols, block_rows)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    scal = jnp.asarray([float(qmax)], jnp.float32)
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    sspec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        name="quantize",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec, sspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int8),
        interpret=interpret,
    )(scal, x, scale)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_rows",
                                             "interpret"))
def dequantize_2d(q: jax.Array, scale: jax.Array, *,
                  out_dtype=jnp.float32, block_rows: int = BLOCK_ROWS,
                  interpret: bool = False) -> jax.Array:
    """q: (rows, 128·k) int8 codes; scale: (rows, 1) f32.  x̂ = q·s."""
    rows, cols = q.shape
    assert cols % LANES == 0, cols
    br, bc = tile_2d(rows, cols, block_rows)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    sspec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        name="dequantize",
        in_specs=[spec, sspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.dtype(out_dtype)),
        interpret=interpret,
    )(q, scale)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def topk_mask_2d(x: jax.Array, thresh: jax.Array, *,
                 block_rows: int = BLOCK_ROWS,
                 interpret: bool = False) -> jax.Array:
    """x: (rows, 128·k); thresh: (rows, 1) f32 ≥ 0 — the k-th |x| per row.
    Zeroes every element strictly below its row threshold (ties survive,
    so ≥ k elements may pass; the wire model charges exactly k)."""
    rows, cols = x.shape
    assert cols % LANES == 0, cols
    br, bc = tile_2d(rows, cols, block_rows)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    sspec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        _topk_mask_kernel,
        grid=grid,
        name="topk_mask",
        in_specs=[spec, sspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, thresh)
