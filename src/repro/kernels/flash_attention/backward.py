"""Pallas TPU flash-attention backward kernels.

Standard flash backward (Dao 2022), adapted to the TPU grid model:
residuals are (q, k, v, o, lse); ``delta = rowsum(do ∘ o)`` is precomputed
in jnp (cheap elementwise pass).  Two kernels:

* ``dq``  — grid (B, H, Sq/bq, Skv/bk), kv sequential, accumulating dq in
            VMEM scratch;
* ``dkv`` — grid (B, Hkv, Skv/bk, Sq/bq), q sequential, accumulating
            dk/dv in VMEM scratch summed over the GQA group.

Scores are recomputed from (q, k, lse) inside VMEM — they never touch HBM,
which is the whole point: training-time attention HBM traffic drops from
O(S²) to O(S·D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _band(q_off, k_off, bq, bk, causal, window):
    in_band = True
    if causal:
        in_band = jnp.logical_and(in_band, k_off <= q_off + bq - 1)
    if window:
        in_band = jnp.logical_and(in_band, k_off + bk - 1 > q_off - window)
    return in_band


def _mask(s, q_off, k_off, causal, window):
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kv_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, kv_pos <= q_pos)
    if window:
        mask = jnp.logical_and(mask, kv_pos > q_pos - window)
    return mask


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, block_q, block_k, n_kv, causal, window):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = qi * block_q
    k_off = ki * block_k

    @pl.when(_band(q_off, k_off, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)                # (bq, 1)
        delta = delta_ref[0, 0].astype(jnp.float32)            # (bq, 1)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _mask(s, q_off, k_off, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[...] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, block_q, block_k, n_q, n_g, causal, window):
    ki = pl.program_id(2)
    step = pl.program_id(3)            # enumerates (g, qi) pairs
    qi = step % n_q

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_off = qi * block_q
    k_off = ki * block_k

    @pl.when(_band(q_off, k_off, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)
        delta = delta_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = _mask(s, q_off, k_off, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == n_g * n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_bwd_bhsd(q, k, v, o, lse, do, *, causal=True, window=0,
                             block_q=256, block_k=256, interpret=False):
    """q/do/o (B,H,Sq,D); k,v (B,Hkv,Skv,D); lse (B,H,Sq).
    Returns (dq, dk, dv) in the input layouts."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    n_q, n_kv = Sq // bq, Skv // bk
    scale = D ** -0.5

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                   # (B,H,Sq)
    lse4 = lse[..., None]                                      # (B,H,Sq,1)
    delta4 = delta[..., None]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                          n_kv=n_kv, causal=causal, window=window),
        grid=(B, H, n_q, n_kv),
        name="flash_bwd_dq",
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g_=g: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g_=g: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse4, delta4)

    # dk/dv: one kv-head per grid row; the sequential axis enumerates the
    # g query-heads of the GQA group × the q blocks
    def hq(b, hkv, j, step, g_=g, n_q_=n_q):
        return (b, hkv * g_ + step // n_q_, step % n_q_, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=bq, block_k=bk,
                          n_q=n_q, n_g=g, causal=causal, window=window),
        grid=(B, Hkv, n_kv, g * n_q),
        name="flash_bwd_dkv",
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), hq),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, s: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, s: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq, D), hq),
            pl.BlockSpec((1, 1, bq, 1), hq),
            pl.BlockSpec((1, 1, bq, 1), hq),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, s: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, s: (b, h, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, Skv, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse4, delta4)
    return dq, dk, dv
