"""Pallas TPU kernel: fused calibrated local update.

The hot loop of FedaGrac's local step is parameter-vector AXPY arithmetic:
``x ← x − η (g + λ c)``.  Unfused, XLA issues three HBM-bound elementwise
ops (add, mul, sub) ⇒ up to 3 reads + intermediate writes of a full
parameter-sized tensor per local step.  The fused kernel streams x, g, c
through VMEM once: 3 reads + 1 write, the bandwidth floor.

The output takes x's buffer (``input_output_aliases``): a caller whose x
is dead after the step — the flat client loop's carry — updates it in
place, and XLA copies x first only where the caller still reads it.

TPU adaptation: the parameter pytree is flattened and lane-padded to
(rows, 128·k); each grid step processes one VMEM tile of at most
BLOCK_ROWS × 128 elements (``tile_2d``) — the last-dim multiple-of-128
requirement of the VPU.  Wide matrices tile their columns too: the flat
round's (M, P) client rows hold P ≈ 10⁸ columns, far more than VMEM.  η and
λ are scalar operands in SMEM so schedules (λ increasing over rounds)
don't recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512            # (512, 128) fp32 tile = 256 KiB/operand in VMEM


def tile_2d(rows: int, cols: int, block_rows: int = BLOCK_ROWS
            ) -> tuple[int, int]:
    """(block rows, block cols) for a (rows, cols) streaming kernel: up to
    ``block_rows`` rows, and as many 128-lane columns as keep the tile at
    ``block_rows × 128`` elements.  A block dim either equals the array's
    or is a multiple of the (8, 128) tile; the grid covers ragged edges."""
    br = min(block_rows, rows)
    bc = min(cols, max(LANES, block_rows * LANES // br // LANES * LANES))
    return br, bc


def _kernel(scal_ref, x_ref, g_ref, c_ref, o_ref):
    eta = scal_ref[0]
    lam = scal_ref[1]
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    o_ref[...] = (x - eta * (g + lam * c)).astype(o_ref.dtype)


def _kernel_prox(scal_ref, x_ref, g_ref, c_ref, x0_ref, o_ref):
    eta = scal_ref[0]
    lam = scal_ref[1]
    mu = scal_ref[2]
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    x0 = x0_ref[...].astype(jnp.float32)
    o_ref[...] = (x - eta * (g + lam * c + mu * (x - x0))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def calibrated_update_2d(x: jax.Array, g: jax.Array, c: jax.Array,
                         eta: jax.Array, lam: jax.Array, *,
                         block_rows: int = BLOCK_ROWS,
                         interpret: bool = False) -> jax.Array:
    """x, g, c: (rows, 128·k).  eta/lam: f32 scalars."""
    rows, cols = x.shape
    assert cols % LANES == 0, cols
    br, bc = tile_2d(rows, cols, block_rows)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    scal = jnp.stack([jnp.asarray(eta, jnp.float32),
                      jnp.asarray(lam, jnp.float32)])
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        name="calibrated_update",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal, x, g, c)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def calibrated_update_prox_2d(x, g, c, x0, eta, lam, mu, *,
                              block_rows: int = BLOCK_ROWS,
                              interpret: bool = False) -> jax.Array:
    rows, cols = x.shape
    assert cols % LANES == 0, cols
    br, bc = tile_2d(rows, cols, block_rows)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    scal = jnp.stack([jnp.asarray(eta, jnp.float32),
                      jnp.asarray(lam, jnp.float32),
                      jnp.asarray(mu, jnp.float32)])
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel_prox,
        grid=grid,
        name="calibrated_update_prox",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(scal, x, g, c, x0)
