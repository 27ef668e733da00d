"""Attention: MHA / GQA / MQA, sliding-window (Gemma3), MLA (DeepSeek-V2),
M-RoPE (Qwen2-VL).  Logical sharding constraints throughout; training /
prefill runs the Pallas flash kernel on TPU (scores stay in VMEM — the
§Perf structural fix for the memory-bound trainers) with a q-block-scan
jnp fallback elsewhere; decode attends a positional KV cache (optionally
sequence-sharded for long contexts).

``REPRO_FLASH_ATTENTION``: ``auto`` (default — kernel on TPU, except in a
program traced under a multi-device mesh, ``dist.partitioned``, since XLA
cannot partition a Mosaic call), ``interpret`` (force the kernel in
interpret mode; tests), ``off``.
"""
from __future__ import annotations

import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist import axis_size, constrain, partitioned
from repro.kernels import backend
from repro.models.layers import apply_rope, dense_init, rms_norm, softcap


def _flash_mode() -> str:
    return os.environ.get("REPRO_FLASH_ATTENTION", "auto")


def _flash_ok(S: int, logit_cap: float, q_pos) -> bool:
    """Kernel path applies to full in-flight attention (training/prefill):
    contiguous positions, no soft-capping, tile-aligned sequence."""
    mode = _flash_mode()
    if mode == "off":
        return False
    if mode == "auto" and (not backend.on_tpu() or partitioned()):
        return False
    return logit_cap == 0.0 and S % 128 == 0

Params = dict[str, Any]
NEG_INF = -2.0 ** 30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        k1, k2, k3, k4 = jax.random.split(key, 4)
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wq": dense_init(k1, d, H * qk_dim, dtype),
            "w_kv_down": dense_init(k2, d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
            "w_kv_up": dense_init(k3, m.kv_lora_rank,
                                  H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
            "wo": dense_init(k4, H * m.v_head_dim, d, dtype),
            "ckv_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        }
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(k1, d, H * hd, dtype),
        "wk": dense_init(k2, d, Hkv * hd, dtype),
        "wv": dense_init(k3, d, Hkv * hd, dtype),
        "wo": dense_init(k4, H * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype,
               window_only: bool = False) -> Params:
    """Positional KV cache.  ``pos[b, s]`` holds the absolute position
    written to slot ``s`` of row ``b`` (-1 = empty) — PER ROW, so a
    continuous-batching engine can hold requests at different phases in
    one pool; local layers use a rolling buffer of size
    ``sliding_window``."""
    size = min(max_len, cfg.sliding_window) if window_only and cfg.sliding_window else max_len
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, size, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, size, m.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, size), -1, jnp.int32),
            "idx": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),
        "idx": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# masked softmax attention cores
# ---------------------------------------------------------------------------

def _mask(q_pos, kv_pos, window, is_global):
    """Causal + optional sliding-window mask.  q_pos (Q,), kv_pos (K,)."""
    causal = kv_pos[None, :] <= q_pos[:, None]
    valid = kv_pos[None, :] >= 0
    if window:
        local = kv_pos[None, :] > q_pos[:, None] - window
        win = jnp.logical_and(causal, local)
        sel = jnp.where(is_global, causal, win)
    else:
        sel = causal
    return jnp.logical_and(sel, valid)


def _mask_rows(q_pos, kv_pos, window, is_global):
    """Per-row decode mask.  q_pos (B,), kv_pos (B, S) -> (B, S)."""
    causal = kv_pos <= q_pos[:, None]
    valid = kv_pos >= 0
    if window:
        local = kv_pos > q_pos[:, None] - window
        win = jnp.logical_and(causal, local)
        sel = jnp.where(is_global, causal, win)
    else:
        sel = causal
    return jnp.logical_and(sel, valid)


def blocked_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                      is_global=True, logit_cap: float = 0.0,
                      block_q: int = 512) -> jax.Array:
    """Causal attention, scanned over query blocks (bounded score memory).

    q (B, Sq, H, D); k, v (B, Skv, Hkv, D); GQA broadcast via head groups.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    scale = D ** -0.5
    bq = min(block_q, Sq)
    n_blk = -(-Sq // bq)
    pad = n_blk * bq - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=-1)
    qb = q.reshape(B, n_blk, bq, Hkv, g, D).transpose(1, 0, 2, 3, 4, 5)
    pb = q_pos.reshape(n_blk, bq)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # sliding-window layers only touch a (window + bq)-wide kv band per q
    # block — computing full S-wide scores and masking wasted 62% of the
    # local layers' score traffic on gemma3-12b/train_4k (§Perf #7)
    Skv = k.shape[1]
    band = (min(Skv, window + bq)
            if (window and is_global is False and Skv == q.shape[1]) else 0)
    starts = (jnp.clip(jnp.arange(n_blk) * bq + bq - band, 0, Skv - band)
              if band else jnp.zeros((n_blk,), jnp.int32))

    def body(_, inp):
        qi, pi, start = inp
        if band:
            kk = jax.lax.dynamic_slice_in_dim(kf, start, band, axis=1)
            vv = jax.lax.dynamic_slice_in_dim(vf, start, band, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kv_pos, start, band, axis=0)
        else:
            kk, vv, kp = kf, vf, kv_pos
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi.astype(jnp.float32) * scale,
                       kk)
        s = softcap(s, logit_cap)
        m = _mask(pi, kp, window, is_global)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vv)
        return None, o.astype(q.dtype)

    # remat the per-block body: without it the backward pass stores the f32
    # softmax probabilities of EVERY block — S²-sized residuals that made
    # the memory roofline term 51 s/round on llama3-8b/train_4k
    # (EXPERIMENTS.md §Perf #2); recomputing them costs ~⅓ extra attention
    # FLOPs on a compute term 10× smaller than the memory term.
    _, out = jax.lax.scan(jax.checkpoint(body), None, (qb, pb, starts))
    Dv = v.shape[-1]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n_blk * bq, H, Dv)
    return out[:, :Sq]


def full_attention(q, k, v, q_pos, *, window: int = 0, is_global=True,
                   logit_cap: float = 0.0) -> jax.Array:
    """In-flight (q_pos == kv_pos, contiguous) attention: Pallas flash
    kernel when eligible, q-block scan otherwise.  Causal/window masks
    depend only on relative position, so any contiguous offset is exact."""
    S = q.shape[1]
    if q_pos.ndim == 2:        # (B, S) row positions: masks are relative,
        q_pos = q_pos[0]       # so any row's positions give the same mask
    # is_global is a static Python bool at every call site
    win = 0 if (is_global is True or not window) else window
    if isinstance(is_global, bool) and _flash_ok(S, logit_cap, q_pos):
        from repro.kernels.flash_attention.ops import flash_attention_diff
        interpret = None if _flash_mode() == "auto" else True
        return flash_attention_diff(q, k, v, causal=True, window=win,
                                    interpret=interpret)
    return blocked_attention(q, k, v, q_pos, q_pos, window=window,
                             is_global=is_global, logit_cap=logit_cap)


def decode_attention(q, k, v, q_pos, kv_pos, *, window: int = 0,
                     is_global=True, logit_cap: float = 0.0) -> jax.Array:
    """Single-position attention against a (possibly sequence-sharded) cache.

    q (B, 1, H, D); k, v (B, S, Hkv, D).  Softmax over S: when the cache is
    sharded over ``sp`` XLA inserts the max/sum all-reduces (flash-decode
    combine) automatically.
    """
    B, _, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    scale = D ** -0.5
    qr = q.reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    s = softcap(s, logit_cap)
    m = _mask_rows(q_pos, kv_pos, window, is_global)        # (B, S)
    s = jnp.where(m[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, 1, H, v.shape[-1]).astype(q.dtype)


def _cache_insert(buf: jax.Array, new: jax.Array, start) -> jax.Array:
    """Write ``new`` (B, S, …) at per-row ring slots
    ``(start[b] + arange(S)) % size``.  ``start`` (B,) int32.

    Writes covering the whole ring (prefill) become a size-bounded gather
    instead of an S-sized batched scatter — the scatter partitions badly
    under SPMD (gemma3 prefill collective term 5.2 → 21.8 s; §Perf #9)."""
    B, size = buf.shape[0], buf.shape[1]
    S = new.shape[1]
    if S >= size:
        # ring slot j of row b ends up holding in-flight index
        # (j − start_b − S) mod size of the last `size` tokens
        tail = new[:, -size:]
        idx = (jnp.arange(size)[None] - start[:, None] - S) % size
        return tail[jnp.arange(B)[:, None], idx].astype(buf.dtype)
    slots = (start[:, None] + jnp.arange(S)) % size              # (B, S)
    rows = jnp.arange(B)[:, None]
    return buf.at[rows, slots].set(new.astype(buf.dtype))


def _pos_insert(pos: jax.Array, q_pos: jax.Array, start) -> jax.Array:
    """pos (B, size); q_pos (B, S) absolute positions; start (B,)."""
    B, size = pos.shape
    S = q_pos.shape[1]
    if S >= size:
        tail = q_pos[:, -size:]
        idx = (jnp.arange(size)[None] - start[:, None] - S) % size
        return tail[jnp.arange(B)[:, None], idx].astype(jnp.int32)
    slots = (start[:, None] + jnp.arange(S)) % size
    rows = jnp.arange(B)[:, None]
    return pos.at[rows, slots].set(q_pos.astype(jnp.int32))


# ---------------------------------------------------------------------------
# full attention layer (standard / GQA path)
# ---------------------------------------------------------------------------

def attention(params: Params, x: jax.Array, cfg: ModelConfig, *,
              angles: jax.Array, q_pos: jax.Array, is_global=True,
              cache: Optional[Params] = None,
              seq_shard: bool = False) -> tuple[jax.Array, Optional[Params]]:
    if cfg.mla is not None:
        return mla_attention(params, x, cfg, angles=angles, q_pos=q_pos,
                             cache=cache, seq_shard=seq_shard)
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    q = apply_rope(q, angles)
    k = apply_rope(k, angles)
    # dist.constrain drops any axis that does not divide (see sharding.py);
    # kv heads stay replicated on meshes wider than Hkv.
    q = constrain(q, "dp", None, "mp", None)
    k = constrain(k, "dp", None, "mp", None)

    window = cfg.sliding_window
    if cache is None:
        out = full_attention(q, k, v, q_pos, window=window,
                             is_global=is_global,
                             logit_cap=cfg.attn_logit_softcap)
        new_cache = None
    else:
        slot = cache["idx"]                          # (B,)
        q_pos_rows = (q_pos if q_pos.ndim == 2
                      else jnp.broadcast_to(q_pos[None], (B, S)))
        cache = dict(cache)
        cache["k"] = _cache_insert(cache["k"], k, slot)
        cache["v"] = _cache_insert(cache["v"], v, slot)
        cache["pos"] = _pos_insert(cache["pos"], q_pos_rows, slot)
        cache["idx"] = cache["idx"] + S
        new_cache = cache
        if S > 1:
            # prefill-into-cache: cache was empty, so attending over the
            # in-flight sequence is exact
            out = full_attention(q, k, v, q_pos, window=window,
                                 is_global=is_global,
                                 logit_cap=cfg.attn_logit_softcap)
        else:
            kc, vc = cache["k"], cache["v"]
            if seq_shard:
                kc = constrain(kc, "dp", "sp", None, None)
                vc = constrain(vc, "dp", "sp", None, None)
            out = decode_attention(q, kc, vc, q_pos_rows[:, 0],
                                   cache["pos"], window=window,
                                   is_global=is_global,
                                   logit_cap=cfg.attn_logit_softcap)
    out = constrain(out, "dp", None, "mp", None)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, H * hd), params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_decode_absorbed(params, cfg: ModelConfig, q_nope, q_rope, cache,
                        q_pos, *, seq_shard: bool) -> jax.Array:
    """Weight-absorbed MLA decode (§Perf #5).

    Scores and outputs are computed in the r-dimensional latent space:
        q̃ = q_nope · W_uk            (B, H, r)
        s  = q̃ · ckvᵀ + q_rope · k_ropeᵀ        (B, H, S)
        õ  = softmax(s) · ckv         (B, H, r)
        o  = õ · W_uv                 (B, H, dv)
    vs the naive path's per-token up-projection of the WHOLE cache
    (O(S·H·(dn+dv)·r) → O(S·H·r)): ~(dn+dv)=256× less decode compute.
    Exactly equivalent in exact arithmetic — W_uk/W_uv are linear.
    """
    m = cfg.mla
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    B = q_nope.shape[0]
    w_up = params["w_kv_up"].reshape(m.kv_lora_rank, H, dn + dv)
    w_uk = w_up[..., :dn]                                    # (r, H, dn)
    w_uv = w_up[..., dn:]                                    # (r, H, dv)

    ckv = cache["ckv"]                                       # (B, S, r)
    krope = cache["krope"]                                   # (B, S, dr)
    if seq_shard:
        ckv = constrain(ckv, "dp", "sp", None)
        krope = constrain(krope, "dp", "sp", None)

    scale = (dn + dr) ** -0.5
    # keep the big cache operands in their storage dtype and accumulate in
    # f32 (native MXU behaviour) — an explicit astype(f32) would double the
    # cache-read bytes, the dominant roofline term of MLA decode (§Perf #6)
    f32 = jnp.float32
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk,
                       preferred_element_type=f32)           # (B, H, r)
    s = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(ckv.dtype), ckv,
                    preferred_element_type=f32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, krope,
                      preferred_element_type=f32)) * scale
    s = softcap(s, cfg.attn_logit_softcap)
    mask = _mask_rows(q_pos, cache["pos"], 0, True)          # (B, S)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p.astype(ckv.dtype), ckv,
                       preferred_element_type=f32)
    o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(ckv.dtype), w_uv,
                   preferred_element_type=f32)
    return o.reshape(B, 1, H, dv).astype(ckv.dtype)

def mla_attention(params: Params, x: jax.Array, cfg: ModelConfig, *,
                  angles: jax.Array, q_pos: jax.Array,
                  cache: Optional[Params] = None,
                  seq_shard: bool = False) -> tuple[jax.Array, Optional[Params]]:
    m = cfg.mla
    assert m is not None
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ang_r = angles[..., : dr // 2]
    q_rope = apply_rope(q_rope, ang_r)

    kv = jnp.einsum("bsd,de->bse", x, params["w_kv_down"])
    ckv, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    ckv = rms_norm(ckv, params["ckv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], ang_r)        # (B,S,1,dr)

    def expand(ckv_seq):
        up = jnp.einsum("bsl,le->bse", ckv_seq, params["w_kv_up"])
        up = up.reshape(B, -1, H, dn + dv)
        return up[..., :dn], up[..., dn:]

    if cache is None:
        k_nope, v = expand(ckv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))],
                            axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        qq = constrain(qq, "dp", None, "mp", None)
        out = full_attention(qq, k, v, q_pos,
                             logit_cap=cfg.attn_logit_softcap)
        new_cache = None
    else:
        size = cache["ckv"].shape[1]
        slot = cache["idx"]                          # (B,)
        q_pos_rows = (q_pos if q_pos.ndim == 2
                      else jnp.broadcast_to(q_pos[None], (B, S)))
        cache = dict(cache)
        cache["ckv"] = _cache_insert(cache["ckv"], ckv, slot)
        cache["krope"] = _cache_insert(cache["krope"], k_rope[:, :, 0, :], slot)
        cache["pos"] = _pos_insert(cache["pos"], q_pos_rows, slot)
        cache["idx"] = cache["idx"] + S
        new_cache = cache
        if S > 1:
            k_nope, v = expand(ckv)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
            qq = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = full_attention(qq, k, v, q_pos,
                                 logit_cap=cfg.attn_logit_softcap)
        elif not m.absorb:
            ckv_c = cache["ckv"]
            if seq_shard:
                ckv_c = constrain(ckv_c, "dp", "sp", None)
            # Naive MLA decode: up-project the whole cache per token —
            # O(S·H·(dn+dv)·r) FLOPs; kept as the §Perf #5 A/B baseline
            # (useful_ratio 0.001 on deepseek-v2-lite/decode_32k).
            k_nope, v = expand(ckv_c)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(cache["krope"][:, :, None, :],
                                          (B, size, H, dr))], axis=-1)
            qq = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = decode_attention(qq, k, v, q_pos_rows[:, 0], cache["pos"],
                                   logit_cap=cfg.attn_logit_softcap)
        else:
            out = mla_decode_absorbed(params, cfg, q_nope[:, 0], q_rope[:, 0],
                                      cache, q_pos_rows[:, 0],
                                      seq_shard=seq_shard)
    out = constrain(out, "dp", None, "mp", None)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, H * dv), params["wo"])
    return y, new_cache
