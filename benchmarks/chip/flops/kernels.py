"""Operations and bytes that a kernel call requires, from its shapes.

Each function takes the call's first output shape as the trace reports it
(``trace.out_shape``) and, where the shape hides a size (the flash kernels
pad the head dimension to 128 lanes), the model's own sizes.  Counts are of
the work the algorithm needs: every matmul once, a causal mask halving the
score work, no recomputation.
"""
from __future__ import annotations

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "s8": 1, "u8": 1}


def calibrated_update(dtype: str, shape: tuple[int, ...]) -> tuple[float, float]:
    """``x − η(g + λc)`` on (rows, cols): 4 operations per element; x, g, c
    read and the result written once."""
    n = 1
    for d in shape:
        n *= d
    return 4.0 * n, 4.0 * n * ITEMSIZE[dtype]


def _flash_dims(shape, n_heads: int, n_kv_heads: int, head_dim: int):
    """(..., heads, seq, padded head) -> batch, seq and the model's sizes;
    every leading axis (a vmapped client axis too) counts as batch."""
    b = 1
    for d in shape[:-3]:
        b *= d
    return b, shape[-2], n_heads, n_kv_heads, head_dim


def flash_fwd(dtype, shape, *, n_heads, n_kv_heads, head_dim):
    """Causal forward: QKᵀ and PV over the lower triangle; reads q, k, v,
    writes o and the f32 log-sum-exp row."""
    b, s, h, hkv, d = _flash_dims(shape, n_heads, n_kv_heads, head_dim)
    flops = 2.0 * b * h * d * s * s                  # 2 matmuls × 2 × s²/2
    it = ITEMSIZE[dtype]
    byts = it * b * s * d * (2 * h + 2 * hkv) + 4.0 * b * h * s
    return flops, byts


def flash_bwd_dq(dtype, shape, *, n_heads, n_kv_heads, head_dim):
    """dP = dO Vᵀ and dQ = dS K (the recomputed QKᵀ is not counted); reads
    q, k, v, dO, lse and δ, writes dq."""
    b, s, h, hkv, d = _flash_dims(shape, n_heads, n_kv_heads, head_dim)
    flops = 2.0 * b * h * d * s * s
    it = ITEMSIZE[dtype]
    byts = it * b * s * d * (3 * h + 2 * hkv) + 8.0 * b * h * s
    return flops, byts


def flash_bwd_dkv(dtype, shape, *, n_heads, n_kv_heads, head_dim):
    """dV = Pᵀ dO and dK = dSᵀ Q (recomputation not counted); reads q, k,
    v, dO, lse and δ, writes dk and dv."""
    b, s, h, hkv, d = _flash_dims(shape, n_heads, n_kv_heads, head_dim)
    flops = 2.0 * b * h * d * s * s
    it = ITEMSIZE[dtype]
    byts = it * b * s * d * (2 * h + 4 * hkv) + 8.0 * b * h * s
    return flops, byts


FLASH = {"flash_fwd": flash_fwd, "flash_bwd_dq": flash_bwd_dq,
         "flash_bwd_dkv": flash_bwd_dkv}
