"""Dispatch wrappers for the quantize kernels (kernel on TPU, oracle
elsewhere — the ``ops.calibrated_update_tree`` convention) plus the
scalar-selection helpers the kernels deliberately exclude:

* ``masked_abs_rowmax`` — per-row max |x| over the TRUE elements only:
  the lane-padding tail ``[n, p)`` is masked OUT of the reduction, so a
  (hypothetically) poisoned pad can never inflate a quantization scale.
  This is the structural fix the compression stage builds every scale on.
* ``row_scales`` — the int8/int4 scale s = max(amax/qmax, eps).
* ``topk_thresholds`` — the k-th |x| per row (pad masked to −1 so it can
  never enter the top-k), consumed by ``topk_mask_2d``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.quantize import kernel, ref


def _resolve(use_pallas: Optional[bool],
             interpret: Optional[bool]) -> tuple[bool, bool]:
    use_pallas = backend.on_tpu() if use_pallas is None else use_pallas
    interpret = (not backend.on_tpu()) if interpret is None else interpret
    return use_pallas, interpret


def quantize_2d(x: jax.Array, scale: jax.Array, *, qmax: int = 127,
                use_pallas: Optional[bool] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    use_pallas, interpret = _resolve(use_pallas, interpret)
    if use_pallas:
        return kernel.quantize_2d(x, scale, qmax=qmax, interpret=interpret)
    return ref.quantize_2d(x, scale, qmax=qmax)


def dequantize_2d(q: jax.Array, scale: jax.Array, *, out_dtype=jnp.float32,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    use_pallas, interpret = _resolve(use_pallas, interpret)
    if use_pallas:
        return kernel.dequantize_2d(q, scale, out_dtype=out_dtype,
                                    interpret=interpret)
    return ref.dequantize_2d(q, scale, out_dtype=out_dtype)


def topk_mask_2d(x: jax.Array, thresh: jax.Array, *,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    use_pallas, interpret = _resolve(use_pallas, interpret)
    if use_pallas:
        return kernel.topk_mask_2d(x, thresh, interpret=interpret)
    return ref.topk_mask_2d(x, thresh)


# -- scalar selection (outside the streaming kernels) ------------------------

def masked_abs_rowmax(x: jax.Array, n: int) -> jax.Array:
    """(rows, P) → (rows, 1) f32: max |x| over columns [0, n) ONLY — the
    lane-padding tail [n, P) is excluded from the reduction by
    construction, not by assuming it holds zeros."""
    p = x.shape[-1]
    mask = jnp.arange(p) < n                         # static n: folded
    a = jnp.where(mask, jnp.abs(x.astype(jnp.float32)), 0.0)
    return jnp.max(a, axis=-1, keepdims=True)


def row_scales(x: jax.Array, n: int, qmax: int,
               eps: float = 1e-12) -> jax.Array:
    """Per-row symmetric quantization scale s = max(amax/qmax, eps)."""
    return jnp.maximum(masked_abs_rowmax(x, n) / float(qmax), eps)


def topk_thresholds(x: jax.Array, n: int, k: int) -> jax.Array:
    """(rows, P) → (rows, 1) f32: the k-th largest |x| per row over the
    true columns (pad magnitudes forced to −1, below any real |x|, so
    padding can never occupy a top-k slot).  Requires k ≤ n."""
    p = x.shape[-1]
    mask = jnp.arange(p) < n
    mag = jnp.where(mask, jnp.abs(x.astype(jnp.float32)), -1.0)
    top = jax.lax.top_k(mag, k)[0]
    return top[..., k - 1:k]
