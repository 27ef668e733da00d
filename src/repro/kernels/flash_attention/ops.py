"""Public ops: flash attention over the model-layer (B, S, H, D) layout.

* ``flash_attention``      — forward-only (serving paths / benchmarks).
* ``flash_attention_diff`` — custom_vjp op whose forward AND backward run
  the Pallas kernels (backward.py): softmax scores never touch HBM in
  either pass, so training-time attention HBM traffic is O(S·D) instead
  of O(S²).

Both handle layout transposes, head-dim lane padding to 128, and backend
dispatch (interpret mode off-TPU).  ``use_pallas=False`` falls back to the
jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.flash_attention import ref
from repro.kernels.flash_attention.backward import flash_attention_bwd_bhsd
from repro.kernels.flash_attention.kernel import (flash_attention_bhsd,
                                                  flash_attention_fwd_bhsd)

LANES = 128


def _pad_scale(q, k, v):
    """Lane-pad head dims to a common multiple of 128 (q/k share D_qk;
    v may differ — MLA); rescale q so the kernel's 1/√D' matches 1/√D_qk."""
    D = q.shape[-1]
    Dv = v.shape[-1]
    Dt = max(-(-D // LANES), -(-Dv // LANES)) * LANES
    if D == Dt and Dv == Dt:
        return q, k, v, 0
    scale_fix = (D ** -0.5) / (Dt ** -0.5)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, Dt - D))) * scale_fix
    k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, Dt - D)))
    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, Dt - Dv)))
    return q, k, v, Dt - D


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 256,
                    use_pallas: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """Forward-only.  q (B, Sq, H, D); k, v (B, Skv, Hkv, D)."""
    if not use_pallas:
        return ref.attention(q, k, v, causal=causal, window=window)
    if interpret is None:
        interpret = not backend.on_tpu()
    dv = v.shape[-1]                 # output head dim (MLA: D_v ≠ D_qk)
    q, k, v, pad = _pad_scale(q, k, v)
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    out = out.transpose(0, 2, 1, 3)
    return out[..., :dv] if out.shape[-1] != dv else out


# ---------------------------------------------------------------------------
# differentiable op (training path)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff_bhsd(q, k, v, causal, window, block_q, block_k, interpret):
    o, _ = flash_attention_fwd_bhsd(q, k, v, causal=causal, window=window,
                                    block_q=block_q, block_k=block_k,
                                    interpret=interpret)
    return o


def _flash_diff_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = flash_attention_fwd_bhsd(q, k, v, causal=causal, window=window,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret)
    return o, (q, k, v, o, lse[..., 0])


def _flash_diff_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd_bhsd(
        q, k, v, o, lse, do, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash_diff_bhsd.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention_diff(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         block_q: int = 256, block_k: int = 256,
                         interpret: bool | None = None) -> jax.Array:
    """Differentiable flash attention.  q (B, Sq, H, D); k, v
    (B, Skv, Hkv, D) -> (B, Sq, H, D)."""
    if interpret is None:
        interpret = not backend.on_tpu()
    dv = v.shape[-1]                 # output head dim (MLA: D_v ≠ D_qk)
    q, k, v, pad = _pad_scale(q, k, v)
    out = _flash_diff_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, window, block_q, block_k,
        interpret)
    out = out.transpose(0, 2, 1, 3)
    return out[..., :dv] if out.shape[-1] != dv else out
