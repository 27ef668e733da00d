"""Composable LM assembly: embeddings → scanned block segments → head.

Layers are grouped into *segments* of identical block kinds so every
architecture — dense, MoE, SSM, hybrid (weight-shared attention), xLSTM,
local/global sliding window — lowers as lax.scan over stacked params:

    uniform  : [(kind, 1, shared=False)] × n_layers
    zamba2   : [("mamba2", E, False), ("attn", 1, shared=True)] × (L / E)
    xlstm    : [("mlstm", E-1, False), ("slstm", 1, False)] × (L / E)
    gemma3   : [("attn_local", E-1, False), ("attn_global", 1, False)] × (L / E)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import constrain
from repro.models.blocks import apply_block, init_block, init_block_cache
from repro.models.layers import (apply_norm, embed_init, init_norm,
                                 mrope_angles, rope_angles)

Params = dict[str, Any]


def group_spec(cfg: ModelConfig) -> tuple[list[tuple[str, int, bool]], int]:
    if cfg.hybrid_attn_every:
        e = cfg.hybrid_attn_every
        assert cfg.n_layers % e == 0, (cfg.n_layers, e)
        return [("mamba2", e, False), ("attn", 1, cfg.hybrid_shared_attn)], cfg.n_layers // e
    if cfg.xlstm is not None:
        e = cfg.xlstm.slstm_every
        assert cfg.n_layers % e == 0
        return [("mlstm", e - 1, False), ("slstm", 1, False)], cfg.n_layers // e
    if cfg.sliding_window and cfg.global_every:
        e = cfg.global_every
        assert cfg.n_layers % e == 0
        return [("attn_local", e - 1, False), ("attn_global", 1, False)], cfg.n_layers // e
    kind = "mamba2" if (cfg.family == "ssm" and cfg.xlstm is None) else "attn"
    return [(kind, 1, False)], cfg.n_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=None) -> Params:
    dtype = jnp.dtype(dtype or cfg.dtype)
    segments, n_groups = group_spec(cfg)
    keys = jax.random.split(key, len(segments) + 4)

    params: Params = {"segments": []}
    for si, (kind, count, shared) in enumerate(segments):
        if shared:
            params["segments"].append({})
            params["shared_attn"] = init_block(keys[si], kind, cfg, dtype)
            continue
        n = n_groups * count
        ks = jax.random.split(keys[si], n)
        stacked = jax.vmap(lambda kk: init_block(kk, kind, cfg, dtype))(ks)
        stacked = jax.tree.map(
            lambda a: a.reshape((n_groups, count) + a.shape[1:]), stacked)
        params["segments"].append(stacked)

    ek = keys[len(segments)]
    if cfg.frontend == "audio":
        params["embed"] = jnp.stack(
            [embed_init(k, cfg.vocab, cfg.d_model, dtype)
             for k in jax.random.split(ek, cfg.n_codebooks)])
        params["heads"] = jnp.stack(
            [embed_init(k, cfg.d_model, cfg.vocab, dtype).reshape(cfg.d_model, cfg.vocab)
             for k in jax.random.split(keys[len(segments) + 1], cfg.n_codebooks)])
    else:
        params["embed"] = embed_init(ek, cfg.vocab, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            params["head"] = embed_init(keys[len(segments) + 1], cfg.d_model,
                                        cfg.vocab, dtype).reshape(cfg.d_model, cfg.vocab)
    params["final_norm"] = init_norm(keys[-1], cfg.d_model, cfg.norm, dtype)
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> list:
    segments, n_groups = group_spec(cfg)
    caches = []
    for kind, count, _shared in segments:
        proto = init_block_cache(kind, cfg, batch, max_len, dtype)
        caches.append(jax.tree.map(
            lambda a: jnp.tile(a[None, None],
                               (n_groups, count) + (1,) * a.ndim), proto))
    return caches


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _row_positions(B: int, S: int, pos_offset) -> jax.Array:
    """(B, S) absolute positions from a scalar or per-row (B,) offset —
    per-row offsets let a continuous-batching engine hold requests at
    different phases in one cache pool (serving/engine.py)."""
    off = jnp.asarray(pos_offset, jnp.int32)
    if off.ndim == 0:
        off = jnp.broadcast_to(off[None], (B,))
    return off[:, None] + jnp.arange(S, dtype=jnp.int32)[None]


@jax.named_scope("model.embed")
def _embed(params: Params, batch: dict, cfg: ModelConfig, pos_offset):
    if cfg.frontend == "vision":
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        B, S = h.shape[0], h.shape[1]
        positions = batch["positions"]                       # (B, 3, S)
        angles = mrope_angles(positions.transpose(1, 0, 2),
                              cfg.resolved_head_dim, cfg.rope_theta,
                              cfg.mrope_sections)
        return h, _row_positions(B, S, pos_offset), angles
    if cfg.frontend == "audio":
        codes = batch["codes"]                               # (B, K, S)
        B, S = codes.shape[0], codes.shape[-1]
        h = sum(params["embed"][k][codes[:, k]]
                for k in range(cfg.n_codebooks))
        q_pos = _row_positions(B, S, pos_offset)
        angles = rope_angles(q_pos, cfg.resolved_head_dim, cfg.rope_theta)
        return h, q_pos, angles
    tokens = batch["tokens"]
    B, S = tokens.shape[0], tokens.shape[-1]
    h = params["embed"][tokens]
    q_pos = _row_positions(B, S, pos_offset)
    angles = rope_angles(q_pos, cfg.resolved_head_dim, cfg.rope_theta)
    return h, q_pos, angles


def forward(params: Params, batch: dict, cfg: ModelConfig, *,
            caches: Optional[list] = None, pos_offset=0,
            seq_shard: bool = False, last_only: bool = False
            ) -> tuple[jax.Array, Optional[list], jax.Array]:
    """Returns (logits, new_caches, aux_loss).  ``last_only`` computes the
    LM head only for the final position (serving prefill)."""
    segments, n_groups = group_spec(cfg)
    h, q_pos, angles = _embed(params, batch, cfg, pos_offset)
    h = constrain(h, "dp", None, None)
    aux0 = jnp.zeros((), jnp.float32)

    def group_fn(carry, xs):
        hh, aux = carry
        seg_p, seg_c = xs
        new_cs = []
        for si, (kind, count, shared) in enumerate(segments):
            if shared:
                c = seg_c[si]
                c1 = jax.tree.map(lambda a: a[0], c) if jax.tree.leaves(c) else None
                hh, nc, a = apply_block(params["shared_attn"], kind, hh, cfg,
                                        angles=angles, q_pos=q_pos, cache=c1,
                                        seq_shard=seq_shard)
                new_cs.append(jax.tree.map(lambda x: x[None], nc) if nc is not None else {})
                aux = aux + a
            else:
                def layer_fn(inner, xs2):
                    h2, a2 = inner
                    p2, c2 = xs2
                    c2 = c2 if jax.tree.leaves(c2) else None
                    h2, nc2, al = apply_block(p2, kind, h2, cfg, angles=angles,
                                              q_pos=q_pos, cache=c2,
                                              seq_shard=seq_shard)
                    return (h2, a2 + al), (nc2 if nc2 is not None else {})
                (hh, aux), ncs = jax.lax.scan(layer_fn, (hh, aux),
                                              (seg_p[si], seg_c[si]))
                new_cs.append(ncs)
        return (hh, aux), new_cs

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn)

    seg_caches = caches if caches is not None else [{} for _ in segments]
    (h, aux), new_caches = jax.lax.scan(group_fn, (h, aux0),
                                        (params["segments"], seg_caches))

    with jax.named_scope("model.head"):
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        if last_only:
            h = h[:, -1:]
        if cfg.frontend == "audio":
            logits = jnp.einsum("bsd,kdv->bskv", h, params["heads"])
        elif cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
        logits = constrain(logits, "dp", None, "mp")
    return logits, (new_caches if caches is not None else None), aux


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------

@jax.named_scope("model.loss")
def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits (..., V) fp-any; labels (...) int32.

    The label pick is a masked reduction, NOT ``take_along_axis``: XLA:CPU
    lowers the 1-element gather to a SERIAL while loop over every (row,
    label) pair — ~2.3 ms per round on the benchmark tasks, longer than
    the entire k-step scan it feeds (found profiling the flat-layout
    round, DESIGN.md §11).  The select+sum picks the identical value
    (adding exact zeros), vectorized."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    v = logits.shape[-1]
    mask = labels[..., None] == jnp.arange(v, dtype=labels.dtype)
    ll = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
    return jnp.mean(lse - ll)


def lm_loss(params: Params, batch: dict, cfg: ModelConfig) -> jax.Array:
    logits, _, aux = forward(params, batch, cfg)
    if cfg.frontend == "audio":
        labels = batch["labels"]                            # (B, K, S)
        loss = cross_entropy(logits, labels.transpose(0, 2, 1))
    else:
        loss = cross_entropy(logits, batch["labels"])
    return loss + aux


def serve_prefill(params: Params, batch: dict, cfg: ModelConfig,
                  caches: Optional[list] = None):
    """Fill the KV caches for the prompt, return last-position logits."""
    logits, new_caches, _ = forward(params, batch, cfg, caches=caches,
                                    last_only=True)
    return logits, new_caches


def serve_decode(params: Params, batch: dict, caches: list, pos_offset,
                 cfg: ModelConfig, seq_shard: bool = False):
    logits, new_caches, _ = forward(params, batch, cfg, caches=caches,
                                    pos_offset=pos_offset, seq_shard=seq_shard)
    return logits, new_caches
