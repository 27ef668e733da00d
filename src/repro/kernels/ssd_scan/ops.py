"""Public op: Mamba2 SSD scan with the model-layer calling convention.

Matches ``repro.models.mamba2.ssd_chunked(x, dt, A, B, C, chunk)``:
x (b, l, h, p); dt (b, l, h) post-softplus; A (h,) negative;
B, C (b, l, g, n) with g groups broadcast over heads.  Returns
(y (b, l, h, p), final state (b, h, p, n)).

The wrapper folds dt into x, expands groups to heads, reshapes to the
kernel's (B, H, C, L, ·) layout, and lane-pads P/N to 128 for MXU
alignment (zero-padding is exact: padded state rows/cols stay zero).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.ssd_scan.kernel import ssd_scan_bhclp

LANES = 128


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, chunk: int, *, use_pallas: bool = True,
             interpret: bool | None = None):
    if not use_pallas:
        from repro.kernels.ssd_scan.ref import ssd_chunked
        return ssd_chunked(x, dt, A, B, C, chunk)
    if interpret is None:
        interpret = not backend.on_tpu()
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    L = min(chunk, l)
    assert l % L == 0, (l, L)
    nc = l // L
    rep = h // g

    xdt = (x * dt[..., None]).astype(jnp.float32)
    da = dt.astype(jnp.float32) * A[None, None, :]
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)

    def to_bhcl(t, feat):
        # (b, l, h, f) -> (b, h, nc, L, f)
        return t.transpose(0, 2, 1, 3).reshape(b, h, nc, L, feat)

    xdt_k = to_bhcl(xdt, p)
    da_k = da.transpose(0, 2, 1).reshape(b, h, nc, L, 1)
    B_k = to_bhcl(Bh, n)
    C_k = to_bhcl(Ch, n)

    pad_p = (-p) % LANES if not interpret else 0
    pad_n = (-n) % LANES if not interpret else 0
    if pad_p:
        xdt_k = jnp.pad(xdt_k, ((0, 0),) * 4 + ((0, pad_p),))
    if pad_n:
        B_k = jnp.pad(B_k, ((0, 0),) * 4 + ((0, pad_n),))
        C_k = jnp.pad(C_k, ((0, 0),) * 4 + ((0, pad_n),))

    y, state = ssd_scan_bhclp(xdt_k, da_k, B_k, C_k, interpret=interpret)
    y = y[..., :p].reshape(b, h, l, p).transpose(0, 2, 1, 3)
    state = state[:, :, :p, :n]
    return y.astype(jnp.float32), state
