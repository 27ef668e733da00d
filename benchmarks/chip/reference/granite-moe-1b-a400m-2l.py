"""Plain reference of granite-3.0-1b-a400m as the program lays out its
weights: float32 jax.numpy, no kernels, no cache, no batching tricks.

Per layer: x += Wo · attn(RoPE(Wq h), RoPE(Wk h), Wv h) with h = RMSNorm(x)
(scale stored as 1 + s), grouped-query causal softmax attention
(query head j reads key/value head j // (H / H_kv)), scale head_dim^-0.5;
then x += Σ_k p_k · E_k(RMSNorm(x)) over the top-k of a softmax router,
p renormalised over the k chosen, E_e(h) = W_out,e (silu(W_gate,e h) ⊙
W_in,e h).  Every expert is computed for every token and weighted by a
routing matrix that is zero off the top-k, so no token is ever dropped.
The switch load-balance loss E · Σ_e mean(prob_e) · share(first choice = e)
times ``router_aux_loss_coef`` is added per layer.  Final RMSNorm, tied
head (logits = h Eᵀ), mean token cross-entropy.

Departures from the published model are those of the program, listed in
the configuration file (no muP multipliers).  ``mm`` computes every matmul:
``plain`` in float32, or a lower-precision stand-in (``fp8``) for the
control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv               # (S, d/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, c, mm):
    b, s, d = x.shape
    h_q, h_kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    eps = c["rms_norm_eps"]
    pos = jnp.arange(s)
    h = _rms(x, p["norm1"]["scale"], eps)
    q = mm("bsd,de->bse", h, p["attn"]["wq"]).reshape(b, s, h_q, hd)
    k = mm("bsd,de->bse", h, p["attn"]["wk"]).reshape(b, s, h_kv, hd)
    v = mm("bsd,de->bse", h, p["attn"]["wv"]).reshape(b, s, h_kv, hd)
    q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, h_q // h_kv, axis=2)
    v = jnp.repeat(v, h_q // h_kv, axis=2)
    sc = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = pos[None, :] <= pos[:, None]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    a = mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
    x = x + mm("bse,ed->bsd", a.reshape(b, s, h_q * hd), p["attn"]["wo"])

    e, top = c["num_local_experts"], c["num_experts_per_tok"]
    h = _rms(x, p["norm2"]["scale"], eps).reshape(b * s, d)
    probs = jax.nn.softmax(mm("td,de->te", h, p["moe"]["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    route = jnp.zeros_like(probs).at[
        jnp.arange(b * s)[:, None], top_i].set(top_p)           # (T, E)
    gate = mm("td,edf->tef", h, p["moe"]["w_gate"])
    up = mm("td,edf->tef", h, p["moe"]["w_in"])
    y = mm("tef,efd->td", jax.nn.silu(gate) * up * route[..., None],
           p["moe"]["w_out"])
    first = jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(probs, 0) * jnp.mean(first, 0))
    return x + y.reshape(b, s, d), aux


def forward(params, tokens, c, mm):
    """tokens (B, S) -> (logits (B, S, V) float32, summed aux loss)."""
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    seg = params["segments"][0]
    groups, count = jax.tree.leaves(seg)[0].shape[:2]
    for g in range(groups):
        for j in range(count):
            x, a = _layer(jax.tree.map(lambda t: t[g, j], seg), x, c, mm)
            aux = aux + a
    h = _rms(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return mm("bsd,vd->bsv", h, params["embed"]), aux


def loss(params, batch, c, mm):
    logits, aux = forward(params, batch["tokens"], c, mm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - picked) + c["router_aux_loss_coef"] * aux
