"""Residual blocks over the mixer zoo, with a uniform (params, cache) calling
convention so model.py can lax.scan stacked layers."""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import apply_norm, init_norm
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe

Params = dict[str, Any]

ATTN_KINDS = ("attn", "attn_local", "attn_global")


def init_block(key, kind: str, cfg: ModelConfig, dtype) -> Params:
    keys = jax.random.split(key, 4)
    if kind in ATTN_KINDS:
        p = {
            "norm1": init_norm(keys[0], cfg.d_model, cfg.norm, dtype),
            "attn": attn_mod.init_attention(keys[1], cfg, dtype),
            "norm2": init_norm(keys[2], cfg.d_model, cfg.norm, dtype),
        }
        if cfg.moe is not None:
            p["moe"] = init_moe(keys[3], cfg, dtype)
        elif cfg.d_ff:
            p["mlp"] = init_mlp(keys[3], cfg, dtype)
        return p
    if kind == "mamba2":
        return {"norm": init_norm(keys[0], cfg.d_model, cfg.norm, dtype),
                "mamba": mamba_mod.init_mamba(keys[1], cfg, dtype)}
    if kind == "mlstm":
        return {"norm": init_norm(keys[0], cfg.d_model, cfg.norm, dtype),
                "mlstm": xlstm_mod.init_mlstm(keys[1], cfg, dtype)}
    if kind == "slstm":
        return {"norm": init_norm(keys[0], cfg.d_model, cfg.norm, dtype),
                "slstm": xlstm_mod.init_slstm(keys[1], cfg, dtype)}
    raise ValueError(kind)


def init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype) -> Params:
    if kind in ATTN_KINDS:
        return attn_mod.init_cache(cfg, batch, max_len, dtype,
                                   window_only=(kind == "attn_local"))
    if kind == "mamba2":
        return mamba_mod.init_mamba_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return xlstm_mod.init_slstm_cache(cfg, batch, dtype)
    raise ValueError(kind)


def apply_block(params: Params, kind: str, x: jax.Array, cfg: ModelConfig, *,
                angles, q_pos, cache: Optional[Params], seq_shard: bool
                ) -> tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        h = apply_norm(params["norm1"], x, cfg.norm, cfg.norm_eps)
        is_global = kind != "attn_local" if cfg.sliding_window else True
        with jax.named_scope("model.attention"):
            a, new_cache = attn_mod.attention(
                params["attn"], h, cfg, angles=angles, q_pos=q_pos,
                is_global=is_global, cache=cache, seq_shard=seq_shard)
        x = x + a
        h = apply_norm(params["norm2"], x, cfg.norm, cfg.norm_eps)
        if cfg.moe is not None:
            m, aux = moe(params["moe"], h, cfg)
        elif cfg.d_ff:
            m = mlp(params["mlp"], h, cfg)
        else:
            m = jnp.zeros_like(x)
        return x + m, new_cache, aux
    h = apply_norm(params["norm"], x, cfg.norm, cfg.norm_eps)
    if kind == "mamba2":
        y, new_cache = mamba_mod.mamba(params["mamba"], h, cfg, cache)
    elif kind == "mlstm":
        y, new_cache = xlstm_mod.mlstm(params["mlstm"], h, cfg, cache)
    elif kind == "slstm":
        y, new_cache = xlstm_mod.slstm(params["slstm"], h, cfg, cache)
    else:
        raise ValueError(kind)
    return x + y, new_cache, aux
