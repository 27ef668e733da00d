"""Mixture-of-Experts with top-k routing.

Dispatch is *sort-based with a capacity limit* (honest active-FLOPs: no dense
one-hot matmuls): token→expert assignments are argsorted by expert id, each
expert processes a fixed-capacity (E, C, d) buffer, and outputs are combined
by gather + weighted sum.  Expert weights carry an expert axis sharded over
``mp`` — the (E, C, d) buffers are sharding-constrained on that axis so the
SPMD partitioner inserts the all-to-alls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain
from repro.models.layers import activation, dense_init
from repro.models.mlp import init_mlp, mlp


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    assert cfg.moe is not None
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff, m.n_experts
    keys = jax.random.split(key, 6)
    scale = (1.0 / d) ** 0.5
    p = {
        "router": dense_init(keys[0], d, E, jnp.float32),
        "w_in": (jax.random.normal(keys[1], (E, d, f), jnp.float32) * scale).astype(dtype),
        "w_out": (jax.random.normal(keys[2], (E, f, d), jnp.float32) * (1.0 / f) ** 0.5).astype(dtype),
    }
    if cfg.glu:
        p["w_gate"] = (jax.random.normal(keys[3], (E, d, f), jnp.float32) * scale).astype(dtype)
    if m.n_shared_experts:
        p["shared"] = init_mlp(keys[4], cfg, dtype, d_ff=m.n_shared_experts * f)
    return p


@jax.named_scope("moe.route")
def route(router_w: jax.Array, x: jax.Array, top_k: int):
    """x (T, d) -> (weights (T,k), ids (T,k), aux_loss, router_probs)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # switch-style load-balance aux loss
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)                              # mean prob / expert
    one_hot = jax.nn.one_hot(top_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)                            # token fraction / expert
    aux = E * jnp.sum(me * ce)
    return top_p, top_ids, aux


def moe(params: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (y, aux_loss)."""
    assert cfg.moe is not None
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    k = m.top_k
    E = m.n_experts
    xt = x.reshape(T, d)

    weights, ids, aux = route(params["router"], xt, k)        # (T,k)

    capacity = int(max(1, round(T * k / E * m.capacity_factor)))
    with jax.named_scope("moe.dispatch"):
        flat_ids = ids.reshape(-1)                            # (T*k,)
        order = jnp.argsort(flat_ids)                         # stable
        sorted_ids = flat_ids[order]
        # position of each assignment within its expert's queue
        pos_in_expert = jnp.arange(T * k) - jnp.searchsorted(
            sorted_ids, sorted_ids, side="left")
        keep = pos_in_expert < capacity

        token_of = order // k                                 # source token
        dst = jnp.where(keep, sorted_ids * capacity + pos_in_expert,
                        E * capacity)

        # scatter tokens into (E*C, d) buffers (row E*C is a dropped-token
        # sink)
        buf = jnp.zeros((E * capacity + 1, d), x.dtype)
        buf = buf.at[dst].set(xt[token_of], mode="drop")
        buf = buf[: E * capacity].reshape(E, capacity, d)
        buf = constrain(buf, "mp", None, None)                # all-to-all here

    with jax.named_scope("moe.experts"):
        h = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
        if cfg.glu:
            g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
            h = activation(g, cfg.act) * h
        else:
            h = activation(h, cfg.act)
        out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])  # (E,C,d)
        out = constrain(out, "mp", None, None)

    with jax.named_scope("moe.combine"):
        out_flat = jnp.concatenate(
            [out.reshape(E * capacity, d), jnp.zeros((1, d), out.dtype)],
            axis=0)
        # gather back: assignment j of token t reads row dst[inv_order[t*k+j]]
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
        rows = out_flat[dst[inv]].reshape(T, k, d)
        y = jnp.einsum("tkd,tk->td", rows.astype(jnp.float32),
                       weights.astype(jnp.float32)).astype(x.dtype)

    if m.n_shared_experts:
        y = y + mlp(params["shared"], x, cfg).reshape(T, d)
    return y.reshape(B, S, d), aux * m.aux_loss_coef
