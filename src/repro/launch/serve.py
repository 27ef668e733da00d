"""Pod-scale serving steps: prefill (prompt → KV caches + last logits) and
decode (one token against a seq_len cache, optionally sequence-sharded for
long contexts)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist import traced_under
from repro.launch import specs as specs_lib
from repro.launch.mesh import mesh_rules
from repro.models.model import serve_decode, serve_prefill


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh):
    bundle = specs_lib.serve_specs(cfg, shape, mesh, kind="prefill")
    sh = lambda t: specs_lib.to_shardings(t, mesh)

    def step(params, batch, caches):
        return serve_prefill(params, batch, cfg, caches=caches)

    jitted = jax.jit(
        traced_under(mesh, mesh_rules(mesh, kind="prefill"), step),
        in_shardings=(sh(bundle["param_ps"]), sh(bundle["batch_ps"]),
                      sh(bundle["cache_ps"])),
        out_shardings=(None, sh(bundle["cache_ps"])),
    )
    return jitted, bundle


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                 kind: str = "decode"):
    """kind "decode" (batch over data) or "long" (cache seq over data)."""
    bundle = specs_lib.serve_specs(cfg, shape, mesh, kind=kind)
    sh = lambda t: specs_lib.to_shardings(t, mesh)
    seq_shard = kind == "long"

    def step(params, batch, caches, pos_offset):
        return serve_decode(params, batch, caches, pos_offset, cfg,
                            seq_shard=seq_shard)

    jitted = jax.jit(
        traced_under(mesh, mesh_rules(mesh, kind=kind), step),
        in_shardings=(sh(bundle["param_ps"]), sh(bundle["batch_ps"]),
                      sh(bundle["cache_ps"]), None),
        out_shardings=(None, sh(bundle["cache_ps"])),
    )
    return jitted, bundle


def lower_serve(cfg: ModelConfig, shape: ShapeConfig, mesh, *, kind: str):
    with jax.set_mesh(mesh):
        if kind == "prefill":
            jitted, bundle = build_prefill(cfg, shape, mesh)
            lowered = jitted.lower(bundle["params"], bundle["batch"],
                                   bundle["caches"])
        else:
            jitted, bundle = build_decode(cfg, shape, mesh, kind=kind)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jitted.lower(bundle["params"], bundle["batch"],
                                   bundle["caches"], pos)
    return lowered, bundle


def build_personalized_decode(cfg: ModelConfig, shape: ShapeConfig, mesh,
                              spec):
    """Personalized decode tick at pod scale (serving/personalized.py):
    the `(P,)` flat base shards over the model axes with the SAME
    ``flat_param_pspec`` rule the flat training state uses, the per-slot
    `(B, P)` delta rows additionally shard their batch dim over the data
    axes, and the per-slot rows (base + delta) feed the vmapped view-table
    decode — one program serves every client's personalized view."""
    from repro.serving.personalized import personalized_decode

    bundle = specs_lib.serve_specs(cfg, shape, mesh, kind="decode")
    sh = lambda t: specs_lib.to_shardings(t, mesh)
    b = shape.global_batch
    bundle["base"] = jax.ShapeDtypeStruct((spec.p,), spec.dtype)
    bundle["base_ps"] = specs_lib.flat_param_pspec(mesh, spec.p)
    bundle["deltas"] = jax.ShapeDtypeStruct((b, spec.p), spec.dtype)
    bundle["delta_ps"] = specs_lib.flat_param_pspec(mesh, spec.p,
                                                    client_dims=1)

    def step(base, deltas, batch, caches, pos_offset):
        rows = base[None] + deltas
        return personalized_decode(spec, cfg, rows, batch["tokens"],
                                   caches, pos_offset)

    jitted = jax.jit(
        traced_under(mesh, mesh_rules(mesh, kind="decode"), step),
        in_shardings=(sh(bundle["base_ps"]), sh(bundle["delta_ps"]),
                      sh(bundle["batch_ps"]), sh(bundle["cache_ps"]), None),
        out_shardings=(None, sh(bundle["cache_ps"])),
    )
    return jitted, bundle


def lower_personalized_serve(cfg: ModelConfig, shape: ShapeConfig, mesh,
                             spec):
    with jax.set_mesh(mesh):
        jitted, bundle = build_personalized_decode(cfg, shape, mesh, spec)
        pos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        lowered = jitted.lower(bundle["base"], bundle["deltas"],
                               bundle["batch"], bundle["caches"], pos)
    return lowered, bundle
