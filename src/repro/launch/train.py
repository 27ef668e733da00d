"""Pod-scale FedaGrac training: the LM round step on the production mesh.

``build_train_round`` returns (round_fn, specs) where round_fn is the jit'd
SPMD FedaGrac round: client axis = mesh data axes (one client per data
slice), tensor parallelism over ``model``.  With ``chunk_rounds > 1`` the
returned function is instead the device-resident chunk (core/engine.py,
DESIGN.md §9): R rounds fused into one ``lax.scan`` dispatch over stacked
per-round inputs, shardings pinned by the in-scan ``param_constraint``
rather than explicit jit shardings.  ``main`` runs a small number of real
rounds on however many devices exist (the end-to-end example path).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import FedConfig, ModelConfig, ShapeConfig
from repro.core import engine, rounds, stages
from repro.core.fedopt import get_algorithm
from repro.dist import traced_under
from repro.launch import specs as specs_lib
from repro.launch.mesh import data_axes, mesh_rules, model_axes
from repro.models.model import lm_loss

PyTree = Any


def _model_size(mesh) -> int:
    out = 1
    for a in model_axes(mesh):
        out *= mesh.shape[a]
    return out


def make_param_constraint(mesh):
    msize = _model_size(mesh)
    cl = data_axes(mesh)

    def constraint(tree: PyTree, client_dims: int) -> PyTree:
        ps = specs_lib.tree_pspecs(tree, msize,
                                   client_axes=cl if client_dims else ())
        return jax.tree.map(
            lambda x, p: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, p)),
            tree, ps, is_leaf=lambda x: isinstance(x, P))

    return constraint


def make_flat_param_constraint(mesh, p: int):
    """Flat twin of ``make_param_constraint``: ONE sharding rule for every
    ``(…, P)`` buffer (specs_lib.flat_param_pspec) instead of the per-leaf
    name-aware table."""
    def constraint(arr, client_dims: int):
        ps = specs_lib.flat_param_pspec(mesh, p, client_dims)
        return jax.lax.with_sharding_constraint(arr,
                                                NamedSharding(mesh, ps))
    return constraint


def build_train_round(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      fed: FedConfig, *, k_max: int = 4,
                      chunk_rounds: int = 1):
    """Returns (jitted_round_fn, spec_bundle).  Call under ``with mesh:``.

    ``chunk_rounds > 1`` returns the scanned R-round chunk instead —
    ``chunk(state, batches, k_steps, weights, lam)`` with every input
    stacked per round (leading ``(R,)``), one dispatch and one host sync
    per chunk (DESIGN.md §9).

    ``fed.param_layout="flat"`` builds the single-buffer round
    (core/flat.py): state is (P,)/(M, P) flat buffers (the bundle carries
    ``flat_spec``), the model consumes view-table slices of the buffer
    (DESIGN.md §13), and ``fed.master_dtype`` keeps an f32 master over
    bf16 compute."""
    algo = get_algorithm(fed.algorithm, fed)
    loss_fn = functools.partial(lm_loss, cfg=cfg)
    if fed.param_layout == "flat":
        from repro.core import flat as flat_lib
        bundle = specs_lib.flat_train_specs(
            cfg, shape, mesh, algo, k_max=k_max,
            master_dtype=fed.master_dtype or None)
        fspec = bundle["flat_spec"]
        round_fn = flat_lib.make_flat_round(
            fspec, lambda p, b: loss_fn(p, b), algo, lr=fed.lr,
            k_max=k_max,
            # a Mosaic call cannot be partitioned: over several devices
            # the local step takes its XLA path
            use_pallas=False if mesh.size > 1 else None,
            param_constraint=make_flat_param_constraint(mesh, fspec.p))
    else:
        round_fn = rounds.make_round(
            lambda p, b: loss_fn(p, b), algo, lr=fed.lr, k_max=k_max,
            spmd_axis_name=data_axes(mesh) or None,
            param_constraint=make_param_constraint(mesh))
        bundle = specs_lib.train_specs(cfg, shape, mesh, algo, k_max=k_max)
    round_fn = traced_under(mesh, mesh_rules(mesh, kind="train"), round_fn)
    if chunk_rounds > 1:
        # sharding layouts are pinned by the in-scan param_constraint;
        # stacked inputs keep their per-round specs on the trailing axes.
        # Length-polymorphic: the final (shorter) tail chunk re-specializes
        return engine.make_round_chunk(round_fn, None), bundle
    sh = lambda tree: specs_lib.to_shardings(tree, mesh)
    ps = bundle["pspecs"]
    jitted = jax.jit(
        round_fn,
        in_shardings=(sh(ps["state"]), sh(ps["batches"]),
                      sh(ps["k_steps"]), sh(ps["weights"])),
        out_shardings=(sh(ps["state"]), None),
    )
    return jitted, bundle


def lower_train(cfg: ModelConfig, shape: ShapeConfig, mesh, fed: FedConfig,
                *, k_max: int = 4):
    """.lower() the round on ShapeDtypeStructs (no allocation)."""
    with jax.set_mesh(mesh):
        jitted, bundle = build_train_round(cfg, shape, mesh, fed, k_max=k_max)
        s = bundle["specs"]
        lowered = jitted.lower(s["state"], s["batches"], s["k_steps"],
                               s["weights"])
    return lowered, bundle


def build_population_round(cfg: ModelConfig, shape: ShapeConfig, mesh,
                           fed: FedConfig, *, m_population: int,
                           k_max: int = 4):
    """The SPMD cohort round at population scale (DESIGN.md §10).

    The mesh's data slots host a cohort of C = n_clients(mesh) sampled
    clients; the calibration state ``nu_i`` keeps ``m_population`` rows,
    row-sharded over the data axes.  The per-round cohort gather / scatter
    of those rows lowers to collectives between the cohort layout and the
    population row sharding.  Returns ``(jitted_round_fn, spec_bundle)``
    with ``round_fn(state, batches, cohort, k_steps, cweights)`` — λ is
    baked in as ``algo.lam`` (the in_shardings cover exactly these five
    arguments).  Call under ``with mesh:``.
    """
    algo = get_algorithm(fed.algorithm, fed)
    loss_fn = functools.partial(lm_loss, cfg=cfg)
    round_fn = stages.make_cohort_round(
        lambda p, b: loss_fn(p, b), algo, lr=fed.lr, k_max=k_max,
        nu_decay=fed.cohort_nu_decay,
        spmd_axis_name=data_axes(mesh) or None,
        param_constraint=make_param_constraint(mesh))
    round_fn = traced_under(mesh, mesh_rules(mesh, kind="train"), round_fn)
    bundle = specs_lib.population_train_specs(cfg, shape, mesh, algo,
                                              m_population, k_max=k_max)
    sh = lambda tree: specs_lib.to_shardings(tree, mesh)
    ps = bundle["pspecs"]
    jitted = jax.jit(
        round_fn,
        in_shardings=(sh(ps["state"]), sh(ps["batches"]), sh(ps["cohort"]),
                      sh(ps["k_steps"]), sh(ps["cweights"])),
        out_shardings=(sh(ps["state"]), None),
    )
    return jitted, bundle


def lower_population(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     fed: FedConfig, *, m_population: int, k_max: int = 4):
    """.lower() the population cohort round on ShapeDtypeStructs."""
    with jax.set_mesh(mesh):
        jitted, bundle = build_population_round(
            cfg, shape, mesh, fed, m_population=m_population, k_max=k_max)
        s = bundle["specs"]
        lowered = jitted.lower(s["state"], s["batches"], s["cohort"],
                               s["k_steps"], s["cweights"])
    return lowered, bundle


# ---------------------------------------------------------------------------
# real-execution driver (multi-host entry: scripts/launch_pod.sh train)
# ---------------------------------------------------------------------------

def _fit_mesh():
    """Production mesh when 256/512 devices exist; else the largest
    (data, model) grid over whatever this run has (CPU dev: 1×1)."""
    import numpy as np
    from repro.launch.mesh import make_mesh, make_production_mesh
    n = len(jax.devices())
    if n >= 512:
        return make_production_mesh(multi_pod=True)
    if n >= 256:
        return make_production_mesh()
    data = 1
    while data * 2 <= n and data < 16:
        data *= 2
    model = max(n // data, 1)
    return make_mesh((data, model), ("data", "model"))


def main() -> None:
    import argparse
    import dataclasses

    from repro.configs.base import reduced
    from repro.configs.registry import ARCHS, get_arch
    from repro.configs.shapes import SHAPES
    from repro.data.synthetic import lm_sequences
    from repro.launch import specs as specs_lib
    from repro.launch.cache import setup_compile_cache
    from repro.launch.distributed import bootstrap, is_coordinator
    from repro.launch.mesh import n_clients

    ap = argparse.ArgumentParser(description="FedaGrac pod training")
    ap.add_argument("--arch", choices=sorted(ARCHS), default="llama3-8b")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="train_4k")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--k-max", type=int, default=4)
    ap.add_argument("--chunk-rounds", type=int, default=1,
                    help="rounds fused into one lax.scan dispatch "
                         "(core/engine.py; host syncs per chunk)")
    ap.add_argument("--algo", default="fedagrac")
    ap.add_argument("--param-layout", choices=("tree", "flat"),
                    default="tree",
                    help="flat = single-buffer rounds with the view-table "
                         "loss boundary (core/flat.py, DESIGN.md §13)")
    ap.add_argument("--master-dtype", choices=("", "float32"), default="",
                    help="flat-only: master-buffer dtype override "
                         "(f32 master over bf16 compute)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced model + tiny shape (CPU/dev runs)")
    args = ap.parse_args()
    setup_compile_cache()

    bootstrap()
    mesh = _fit_mesh()
    cfg = get_arch(args.arch)
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = reduced(cfg)
        shape = dataclasses.replace(shape, seq_len=128,
                                    global_batch=2 * n_clients(mesh))
    cfg = specs_lib.bf16_config(cfg) if not args.reduced else cfg
    fed = FedConfig(algorithm=args.algo, lr=0.3 if args.reduced else 3e-2,
                    param_layout=args.param_layout,
                    master_dtype=args.master_dtype)

    with jax.set_mesh(mesh):
        chunk = max(args.chunk_rounds, 1)
        jitted, bundle = build_train_round(cfg, shape, mesh, fed,
                                           k_max=args.k_max,
                                           chunk_rounds=chunk)
        m, b_local = bundle["m"], bundle["b_local"]
        from repro.core import rounds as rounds_lib
        from repro.models.model import init_params
        params = init_params(jax.random.PRNGKey(0), cfg)
        algo = get_algorithm(fed.algorithm, fed)
        if args.param_layout == "flat":
            from repro.core import flat as flat_lib
            params = flat_lib.ravel(bundle["flat_spec"], params)
        state = rounds_lib.init_state(params, m, algo)
        sh = lambda t: specs_lib.to_shardings(t, mesh)
        ps = bundle["pspecs"]
        state = jax.device_put(state, sh(ps["state"]))
        weights = jax.device_put(jnp.full((m,), 1.0 / m, jnp.float32),
                                 sh(ps["weights"]))
        key = jax.random.PRNGKey(1)

        def round_inputs(t):
            data = lm_sequences(jax.random.fold_in(key, t),
                                m * args.k_max * b_local, shape.seq_len,
                                cfg.vocab)
            batches = jax.tree.map(
                lambda a: jnp.reshape(a, (m, args.k_max, b_local, -1)), data)
            ks = jnp.clip(jax.random.poisson(jax.random.fold_in(key, 1000 + t),
                                             3, (m,)) + 1, 1, args.k_max
                          ).astype(jnp.int32)
            return batches, ks

        for t0 in range(0, args.rounds, chunk):
            r = min(chunk, args.rounds - t0)      # tail chunk may be short
            if chunk == 1:
                batches, ks = round_inputs(t0)
                state, metrics = jitted(
                    state, jax.device_put(batches, sh(ps["batches"])),
                    jax.device_put(ks, sh(ps["k_steps"])), weights)
                losses = [float(metrics["loss"])]
                kbars = [float(metrics["kbar"])]
            else:
                per_round = [round_inputs(t0 + j) for j in range(r)]
                batches = jax.tree.map(lambda *xs: jnp.stack(xs),
                                       *(b for b, _ in per_round))
                ks = jnp.stack([k for _, k in per_round])
                state, metrics = jitted(
                    state, batches, ks,
                    jnp.broadcast_to(weights, (r, m)),
                    jnp.full((r,), algo.lam, jnp.float32))
                losses = [float(x) for x in metrics["loss"]]
                kbars = [float(x) for x in metrics["kbar"]]
            if is_coordinator():
                for j, (lo, kb) in enumerate(zip(losses, kbars)):
                    print(f"round {t0 + j + 1}/{args.rounds}  "
                          f"loss {lo:.4f}  kbar {kb:.2f}", flush=True)


if __name__ == "__main__":
    main()
