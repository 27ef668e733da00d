"""Mixture-of-Experts with top-k routing.

Dispatch is *sort-based with a capacity limit* (honest active-FLOPs: no dense
one-hot matmuls): each expert processes a fixed-capacity (E, C, d) buffer
that holds the first C of its assignments in flat order (token-major), and
outputs are combined by a weighted sum.  Every index map is dense
arithmetic (``Routing``): ranks from a cumulative sum of the one-hot expert
ids, expert order from one stable argsort.  Rows move by gathers only: into
the buffers, one gather of the T*k assignments in expert order cut into
contiguous per-expert windows; back, one gather of each assignment's row.
Each is the other's transpose (``_dispatch``, ``_combine``), so no pass,
forward or backward, scatters.  Expert weights carry an expert axis sharded
over ``mp`` — the (E, C, d) buffers are sharding-constrained on that axis
so the SPMD partitioner inserts the all-to-alls.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(probs: jax.Array, k: int):
    """``lax.top_k``, whose values transpose by a one-hot product rather
    than by a scatter-add."""
    return jax.lax.top_k(probs, k)


def _top_k_fwd(probs, k):
    out = jax.lax.top_k(probs, k)
    chosen = out[1][..., None] == jnp.arange(probs.shape[-1])  # (..., k, E)
    return out, chosen


def _top_k_bwd(k, chosen, cts):
    d_top_p, _ = cts
    return (jnp.sum(jnp.where(chosen, d_top_p[..., None], 0), axis=-2),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


@jax.named_scope("moe.route")
def route(router_w: jax.Array, x: jax.Array, top_k: int):
    """x (T, d) -> (weights (T,k), ids (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = _top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # switch-style load-balance aux loss
    E = router_w.shape[-1]
    me = jnp.mean(probs, axis=0)                              # mean prob / expert
    one_hot = jax.nn.one_hot(top_ids[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)                            # token fraction / expert
    aux = E * jnp.sum(me * ce)
    return top_p, top_ids, aux


class Routing(NamedTuple):
    """Where each assignment (token t's j-th choice, flat index t*k + j)
    goes in the (E, C, d) buffers, and where each buffer row comes from."""
    ids: jax.Array     # (T, k) expert of each assignment
    pos: jax.Array     # (T, k) its row in the expert's buffer; C if dropped
    starts: jax.Array  # (E,) each expert's first position in expert order
    counts: jax.Array  # (E,) assignments per expert
    order: jax.Array   # (T*k + C,) assignments in expert order (stable),
    #                    then C entries past the end


def _to_experts(x: jax.Array, src: jax.Array, r: Routing) -> jax.Array:
    """(E, C, d) buffers: the rows ``x[src]`` in expert order, cut into one
    window of C rows per expert from its start, rows past the expert's
    count zeroed.  One row gather, then contiguous windows.  The C entries
    of ``src`` past the end only ever land past some expert's count, so
    they may read any row (clipped)."""
    capacity = r.order.shape[0] - r.ids.size
    rows = jnp.take(x, src, axis=0, mode="clip")
    win = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
        rows, s, capacity))(r.starts)
    live = jnp.arange(capacity) < r.counts[:, None]
    return jnp.where(live[..., None], win, jnp.zeros((), x.dtype))


def _from_experts(buf: jax.Array, r: Routing) -> jax.Array:
    """(T, k, d): each assignment's row of ``buf``; zeros where dropped."""
    return buf.at[r.ids, r.pos].get(mode="fill", fill_value=0)


def _int_cotangent(r: Routing) -> Routing:
    return Routing(*(np.zeros(a.shape, jax.dtypes.float0) for a in r))


@jax.custom_vjp
def _dispatch(xt: jax.Array, r: Routing) -> jax.Array:
    """(T, d) tokens -> (E, C, d) buffers; the transpose gathers too."""
    return _to_experts(xt, r.order // r.ids.shape[1], r)


def _dispatch_fwd(xt, r):
    return _dispatch(xt, r), r


def _dispatch_bwd(r, dbuf):
    with jax.named_scope("moe.dispatch"):
        d_rows = _from_experts(dbuf, r).astype(jnp.float32)
        dxt = jnp.sum(d_rows, axis=1).astype(dbuf.dtype)
    return dxt, _int_cotangent(r)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out: jax.Array, r: Routing) -> jax.Array:
    """(E, C, d) expert outputs -> (T, k, d) rows; the transpose gathers
    too."""
    return _from_experts(out, r)


def _combine_fwd(out, r):
    return _combine(out, r), r


def _combine_bwd(r, d_rows):
    with jax.named_scope("moe.combine"):
        d_out = _to_experts(d_rows.reshape(-1, d_rows.shape[-1]), r.order, r)
    return d_out, _int_cotangent(r)


_combine.defvjp(_combine_fwd, _combine_bwd)


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
        # rank of each assignment within its expert, in flat order
        onehot = (ids[..., None] == jnp.arange(E)).astype(jnp.int32)
        onehot = onehot.reshape(T * k, E)
        before = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(before * onehot, axis=1).reshape(T, k)
        counts = jnp.sum(onehot, axis=0)
        order = jnp.concatenate([jnp.argsort(ids.reshape(-1)),
                                 jnp.full((capacity,), T * k, jnp.int32)])
        r = Routing(ids, jnp.minimum(pos, capacity),
                    jnp.cumsum(counts) - counts, counts, order)
        buf = constrain(_dispatch(xt, r), "mp", None, None)  # all-to-all here

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
        rows = _combine(out, r)                               # (T,k,d)
        y = jnp.einsum("tkd,tk->td", rows.astype(jnp.float32),
                       weights.astype(jnp.float32)).astype(x.dtype)

    if m.n_shared_experts:
        y = y + mlp(params["shared"], x, cfg).reshape(T, d)
    return y.reshape(B, S, d), aux * m.aux_loss_coef
