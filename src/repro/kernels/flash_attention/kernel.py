"""Pallas TPU flash attention (forward) — blocked online softmax.

TPU adaptation of the GPU flash-attention insight: instead of shared-memory
tiles + warp shuffles, VMEM-resident (block_q × head_dim) accumulators carried
across a *sequential* kv grid axis; the MXU consumes (block_q × block_k)
score tiles.  Causal + sliding-window blocks outside the band are skipped
with ``pl.when`` (zero MXU work), giving the 2× causal and O(S·W) window
savings structurally.

Grid: (B, H, Sq/bq, Skv/bk) — last axis "arbitrary" (sequential), carrying
(m, l, acc) scratch.  GQA maps q head h → kv head h // (H/Hkv) in the
index_map, so no repeated-KV materialization.

Block shapes: bq, bk multiples of the (8,128) fp32 VMEM tile; head_dim is
lane-padded to 128 by the ops wrapper when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                 acc_ref, *,
                 scale: float, block_q: int, block_k: int, n_kv: int,
                 causal: bool, window: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = qi * block_q
    k_off = ki * block_k

    # band check: does this kv block intersect [q_pos-window+1, q_pos]?
    in_band = True
    if causal:
        in_band = jnp.logical_and(in_band, k_off <= q_off + block_q - 1)
    if window:
        in_band = jnp.logical_and(
            in_band, k_off + block_k - 1 > q_off - window)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kv_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kv_pos <= q_pos)
        if window:
            mask = jnp.logical_and(mask, kv_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (alpha * acc_ref[...]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_fwd_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True, window: int = 0,
                             block_q: int = 256, block_k: int = 256,
                             interpret: bool = False):
    """q (B, H, Sq, D); k, v (B, Hkv, Skv, D).
    Returns (o (B, H, Sq, D), lse (B, H, Sq, 1)) — lse feeds backward."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    n_kv = Skv // bk
    grid = (B, H, Sq // bq, n_kv)
    scale = D ** -0.5

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=bq, block_k=bk, n_kv=n_kv,
        causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=grid,
        name="flash_fwd",
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g_=g: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g_=g: (b, h // g_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom l
            pltpu.VMEM((bq, D), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, block_q=256,
                         block_k=256, interpret=False) -> jax.Array:
    o, _ = flash_attention_fwd_bhsd(q, k, v, causal=causal, window=window,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)
    return o
