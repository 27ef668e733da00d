"""Plain reference of FedaGrac rounds (Algorithm 1, paper §4), with the
model's loss supplied by ``reference/<config>.py``.

Round t, with server model x, global orientation ν and client references
ν⁽ⁱ⁾ (all zero at the start), K̄ = Σ_i w_i K_i:

    client i:  c_i = ν − ν⁽ⁱ⁾;  y = x;  for k < K_i:
                   g_k = ∇f_i(y; batch_{i,k});  y ← y − η (g_k + λ c_i)
               ḡ_i = (1/K_i) Σ_k g_k
               sends y, and g_0 if K_i > K̄ else ḡ_i
    server:    x ← Σ_i w_i y_i;  ν ← Σ_i w_i (sent gradient);  ν⁽ⁱ⁾ ← ḡ_i

The round's loss is Σ_i w_i f_i(x; batch_{i,0}).  Each client runs exactly
its K_i steps (no masking); every step is one float32 value-and-gradient
call on one client's batch, so the reference holds one batch of
activations at a time.

``MATMUL`` holds the matmul the model is computed with: ``plain`` (float32
at the highest precision) for the reference, ``fp8`` (both operands
rounded to float8 e4m3 with a per-tensor scale, gradients passed straight
through) for the control, and ``bf16`` (operands rounded to bfloat16,
float32 accumulation: the precision the configurations state) as a
witness of how far rounding alone carries the rounds apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@jax.custom_vjp
def _to_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_to_fp8.defvjp(lambda x: (_to_fp8(x), None), lambda _, g: (g,))


def _fp8(spec, a, b):
    return jnp.einsum(spec, _to_fp8(a), _to_fp8(b), precision=HIGHEST)


def _bf16(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMUL = {"plain": _plain, "bf16": _bf16, "fp8": _fp8}


def rounds(model, cfg: dict, params, batches, ks, *, lr: float, lam: float,
           matmul: str = "plain", batch_rows: slice | None = None):
    """Run ``len(ks)`` rounds from ``params`` (a float32 tree).
    ``batches[t]`` is {"tokens", "labels"} of (M, k_max, B, S); ``ks`` is
    (rounds, M).  ``batch_rows`` keeps only those rows of every batch (a
    planted fault).  Returns the per-round losses, the final params, ν and
    the clients' ν⁽ⁱ⁾."""
    mm = MATMUL[matmul]

    def vg(p, b):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda q: model.loss(q, b, cfg, mm))(p)

    vg = jax.jit(vg)
    # y + a·v, leaf by leaf; the trees are freed as soon as they are
    # consumed, so a round holds about ten model-sized trees at once
    axpy = jax.jit(lambda a, y, v: jax.tree.map(lambda p, q: p + a * q, y, v))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m = ks.shape[1]
    w = 1.0 / m
    x, nu = params, zeros(params)
    nu_i = [zeros(params) for _ in range(m)]
    losses = []
    for t in range(ks.shape[0]):
        kbar = sum(w * float(k) for k in ks[t])
        x_new, nu_new, first_losses = zeros(x), zeros(x), []
        for i in range(m):
            k_i = int(ks[t, i])
            fast = k_i > kbar + 1e-4 * max(kbar, 1.0)
            c_i = axpy(-1.0, nu, nu_i[i])
            nu_i[i] = None
            y, g_sum, g0 = x, zeros(x), None
            for k in range(k_i):
                b = {key: jnp.asarray(v[i, k]) for key, v in
                     batches[t].items()}
                if batch_rows is not None:
                    b = {key: v[batch_rows] for key, v in b.items()}
                loss, g = vg(y, b)
                if k == 0:
                    first_losses.append(float(loss))
                    g0 = g if fast else None
                g_sum = axpy(1.0, g_sum, g)
                y = axpy(-lr, y, axpy(lam, g, c_i))
                del g
            del c_i
            g_mean = jax.tree.map(lambda v: v / k_i, g_sum)
            del g_sum
            x_new = axpy(w, x_new, y)
            nu_new = axpy(w, nu_new, g0 if fast else g_mean)
            nu_i[i] = g_mean
            del y, g0
        x, nu = x_new, nu_new
        losses.append(sum(w * li for li in first_losses))
    return {"losses": losses, "params": x, "nu": nu, "nu_i": nu_i}
