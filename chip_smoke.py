#!/usr/bin/env python3
"""Bring-up smoke: the FedaGrac LM round and the serving path on a TPU.

    python3 chip_smoke.py             # one chip: kernel, train, serve phases
    python3 chip_smoke.py --chips 4   # only the sharded round, on four chips

Configuration (``smoke_config``): granite-3.0-1b-a400m
[hf:ibm-granite/granite-3.0-1b-a400m-base] at its published widths —
d_model 1024, 16 query and 8 KV heads of 64, 32 experts top-8 with expert
FFN 512, vocab 49155, tied embeddings — cut in depth only, to 2 of its 24
layers (the layer pattern is uniform, so one layer is a whole period).
bf16 compute under a float32 flat master (``param_layout="flat"``,
``master_dtype="float32"``).  M = 2 clients with step asynchronism (K_i
drawn from N(4, 4) per client and round), sequence 1024 (a multiple of
128, so attention takes the flash kernel), per-client batch 4,
``DeviceLMBatcher`` over seeded topic-skewed ``lm_sequences``.  Weights
are random, made from ``--seed``.

Cut: M = 4 clients do not fit one v5e.  Compiled for the chip, the 3-round
chunk at M = 4 needs 15.84 GB of its 15.75 GB of HBM (each (M, P) float32
client-row temporary is 2.34 GB); at M = 2 it needs 11.98 GB.  No width
was cut.

Kernel phase: the train chunk's Pallas kernels on a small input against
their jnp references — the calibrated update on client rows whose column
tile is ragged, flash attention forward and backward at granite's heads.

Train phase: ``FederatedSimulation`` (fedagrac) runs one scanned chunk of
3 rounds twice (two ``run`` calls, so the second repeats the first's K_i
and batches from the trained state).  It prints the chunk's compile
seconds, each chunk's wall time on the host clock (the second one is
steady state), the per-round losses and the device's peak bytes.  It fails unless every loss is finite, the last round's loss
is below the first's, and the compiled chunk holds the Pallas kernels
(``tpu_custom_call``) of the calibrated update and of flash attention
forward and backward.

Serve phase: ``PersonalizedServeEngine`` over ``sim.publish_snapshot()``
serves 4 greedy requests (128-token prompts, 16 new tokens each).  It
fails unless all 4 complete and each request's first token is the argmax
of one uncached forward pass over its prompt.

``--chips 4`` runs only ``launch.train.build_train_round`` on a
(data=2, model=2) mesh — M = 2 clients, 2 rounds — and compares it with
the same flat round run unsharded on device 0, within the tolerances of
tests/test_dist_spmd.py.  Both take the XLA paths (no Pallas kernel can be
partitioned automatically), and this phase computes in float32 at the
same widths, with float32 matmuls: those tolerances are float32
tolerances, which bf16 rounding (2⁻⁸ relative) would swamp.  Both the
parameters and ν are compared, and the leaves holding violations are
listed by name.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
a failed phase exits non-zero before it is printed, and so does a run that
finds no TPU.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.base import FedConfig, ShapeConfig  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core import flat, rounds  # noqa: E402
from repro.core.fedopt import get_algorithm  # noqa: E402
from repro.data import (DeviceLMBatcher, gaussian_k_schedule,  # noqa: E402
                        lm_sequences)
from repro.fed import FederatedSimulation  # noqa: E402
from repro.kernels import backend  # noqa: E402
from repro.kernels.calibrated_update import ref as cu_ref  # noqa: E402
from repro.kernels.calibrated_update.kernel import (  # noqa: E402
    LANES, calibrated_update_2d)
from repro.kernels.flash_attention import ref as fa_ref  # noqa: E402
from repro.kernels.flash_attention.ops import (  # noqa: E402
    flash_attention_diff)
from repro.launch.cache import setup_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serving import PersonalizedServeEngine, Request  # noqa: E402

SEQ = 1024
BATCH = 4                  # sequences per client per local step
M_CLIENTS = 2              # M = 4 does not fit: see the header
CHUNK = 3                  # rounds per scanned chunk; the run takes two
N_SEQ = 64                 # sequences in each client's token stream
LR = 0.015           # stable at d_model 1024 (0.1 diverges there)
TRAIN_KERNELS = ("calibrated_update", "flash_fwd", "flash_bwd_dq",
                 "flash_bwd_dkv")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# tests/test_dist_spmd.py: sharded round == unsharded round
RTOL, ATOL, LOSS_TOL = 2e-4, 2e-5, 1e-3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(chips: int) -> None:
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{info['platform']!r}); this script runs only on "
                         f"the chip")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: {chips} chips needed, "
                         f"{info['count']} found")


def smoke_config(dtype: str = "bfloat16"):
    return dataclasses.replace(get_arch("granite-moe-1b-a400m"), n_layers=2,
                               dtype=dtype)


def smoke_fed(m: int) -> FedConfig:
    return FedConfig(algorithm="fedagrac", n_clients=m, k_mean=4, k_var=4.0,
                     k_mode="random", lr=LR, calibration_rate=0.5,
                     param_layout="flat", master_dtype="float32")


def make_batcher(cfg, m: int, seq: int, batch: int, seed: int,
                 n_seq: int = N_SEQ) -> DeviceLMBatcher:
    key = jax.random.PRNGKey(seed)
    streams = [lm_sequences(jax.random.fold_in(key, i), n_seq, seq,
                            cfg.vocab, skew_topic=i) for i in range(m)]
    return DeviceLMBatcher(streams, batch_size=batch, seed=seed)


def kernels_in(hlo_text: str, names) -> set:
    """Which of the named Pallas kernels a compiled program holds as
    ``tpu_custom_call`` ops (pallas_call ``name=`` lands in the op name)."""
    calls = [line.split("backend_config=")[0]
             for line in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {n for n in names
            if any(re.search(rf"\b{n}\b", c) for c in calls)}


def collectives_in(hlo_text: str) -> dict:
    return {c: len(re.findall(rf"= [^=]*\b{c}(-start)?\(", hlo_text))
            for c in COLLECTIVES}


def memory_stats() -> dict:
    return jax.devices()[0].memory_stats() or {}


def kernel_phase(cfg, *, seed: int, seq: int = 256) -> None:
    """The train chunk's kernels on a small input against their jnp
    references: the calibrated update on client rows whose column tile is
    ragged, flash attention forward and backward at the config's heads."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    rows, cols = M_CLIENTS, 600 * LANES          # 2.34 column tiles
    x, g, c = (jax.random.normal(k, (rows, cols), jnp.float32)
               for k in ks[:3])
    got = calibrated_update_2d(x, g, c, LR, 0.5,
                               interpret=not backend.on_tpu())
    err = float(jnp.max(jnp.abs(got - cu_ref.calibrated_update(x, g, c, LR,
                                                               0.5))))
    print(f"kernels: calibrated_update ({rows}, {cols}) max_abs_diff "
          f"{err:.3e}", flush=True)
    if not err <= 1e-5:
        fail(f"calibrated_update differs from its reference by {err}")

    hd = cfg.resolved_head_dim
    q = jax.random.normal(ks[3], (1, seq, cfg.n_heads, hd), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, seq, cfg.n_kv_heads, hd), jnp.bfloat16)
            for kk in ks[4:6])
    do = jax.random.normal(ks[6], q.shape, jnp.float32)

    def vjp(attn):
        def f(q, k, v):
            out, pull = jax.vjp(attn, q, k, v)
            return (out,) + pull(do.astype(out.dtype))
        return jax.jit(f)

    got = vjp(lambda q, k, v: flash_attention_diff(q, k, v, causal=True))(
        q, k, v)
    with jax.default_matmul_precision("float32"):
        want = vjp(lambda q, k, v: fa_ref.attention(q, k, v, causal=True))(
            q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        print(f"kernels: flash {name} {a.shape} max_diff/max_ref {rel:.3e}",
              flush=True)
        # bf16 operands: a few bf16 ulps (2⁻⁸) of the largest entry
        if not rel <= 2e-2:
            fail(f"flash attention {name} differs from its reference by "
                 f"{rel} of its largest entry")


def train_phase(cfg, fed: FedConfig, batcher, *, seed: int,
                chunk: int = CHUNK, kernels=TRAIN_KERNELS):
    """One scanned chunk of ``chunk`` rounds, run twice; returns the
    simulation."""
    loss_fn = functools.partial(model_lib.lm_loss, cfg=cfg)
    sim = FederatedSimulation(lambda p, b: loss_fn(p, b),
                              model_lib.init_params(jax.random.PRNGKey(seed),
                                                    cfg),
                              fed, batcher, t_max=2 * chunk)
    print(f"train: {cfg.name} {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} master={fed.master_dtype} "
          f"P={sim.flat_spec.p} M={fed.n_clients} k_max={sim.k_max} "
          f"K_i per round={sim.k_schedule[:chunk].tolist()}", flush=True)
    tic = time.perf_counter()
    compiled = sim.lower_chunk(chunk).compile()
    print(f"train: compile_s {time.perf_counter() - tic:.3f} "
          f"({chunk}-round chunk)", flush=True)
    print(f"train: {compiled.memory_analysis()}", flush=True)
    found = kernels_in(compiled.as_text(), kernels)
    print(f"train: pallas kernels in the chunk: {sorted(found)}", flush=True)
    walls, losses = [], []
    for _ in range(2):
        tic = time.perf_counter()
        losses += sim.run(chunk, eval_every=chunk).loss
        walls.append(time.perf_counter() - tic)
    print(f"train: chunk_wall_s first {walls[0]:.4f} steady {walls[1]:.4f}",
          flush=True)
    print(f"train: losses {[round(x, 5) for x in losses]}", flush=True)
    stats = memory_stats()
    print(f"train: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    print(f"train: memory_stats {stats}", flush=True)
    if not all(np.isfinite(losses)):
        fail(f"non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        fail(f"loss did not fall: first {losses[0]}, last {losses[-1]}")
    if set(kernels) - found:
        fail(f"kernels missing from the compiled chunk: "
             f"{sorted(set(kernels) - found)}")
    return sim


def serve_phase(cfg, sim, *, seed: int, n_req: int = 4,
                prompt_len: int = 128, new_tokens: int = 16,
                max_len: int = 256) -> None:
    """Greedy requests through the personalized engine; each first token
    must be the argmax of an uncached forward pass over its prompt."""
    eng = PersonalizedServeEngine(cfg, sim.flat_spec, sim.publish_snapshot(),
                                  personalizer="none", slots=n_req,
                                  max_len=max_len)
    if prompt_len not in eng.buckets:
        fail(f"prefill buckets {eng.buckets} lack {prompt_len}")
    prompts = np.random.default_rng(seed).integers(
        1, cfg.vocab, (n_req, prompt_len)).astype(np.int32)
    for i in range(n_req):
        eng.submit(Request(uid=i, prompt=prompts[i],
                           max_new_tokens=new_tokens, client_id=i))
    tic = time.perf_counter()
    done = {c.uid: c for c in eng.run()}
    print(f"serve: {len(done)}/{n_req} requests in "
          f"{time.perf_counter() - tic:.3f} s, {eng.ticks} decode ticks",
          flush=True)
    if sorted(done) != list(range(n_req)):
        fail(f"completed {sorted(done)} of {n_req} requests")
    # one uncached pass per prompt: batch 1, as the engine prefills it (a
    # larger batch would change the MoE expert capacity)
    last_logits = jax.jit(lambda p, t: model_lib.forward(
        p, {"tokens": t}, cfg)[0][0, -1])
    for i in range(n_req):
        toks = done[i].tokens
        if len(toks) != new_tokens:
            fail(f"request {i} emitted {len(toks)} of {new_tokens} tokens")
        ref = np.asarray(last_logits(eng.params, jnp.asarray(prompts[i])[None]),
                         np.float32)
        first, best = toks[0], int(np.argmax(ref))
        print(f"serve: request {i} first token {first} uncached argmax "
              f"{best}", flush=True)
        # ties: any token holding the maximum logit is an argmax
        if ref[first] != ref[best]:
            fail(f"request {i}: first token {first} (logit {ref[first]}) "
                 f"is not the argmax {best} (logit {ref[best]})")


def sharded_phase(cfg, *, seed: int, seq: int = SEQ, batch: int = BATCH,
                  n_rounds: int = 2, n_seq: int = N_SEQ) -> None:
    """build_train_round on a (data=2, model=2) mesh vs the same flat round
    unsharded on device 0."""
    from repro.launch import specs as specs_lib
    from repro.launch import train as train_lib
    from repro.launch.mesh import make_mesh

    m = 2
    fed = smoke_fed(m)
    algo = get_algorithm(fed.algorithm, fed)
    ks = gaussian_k_schedule(m, fed.k_mean, fed.k_var, n_rounds,
                             mode=fed.k_mode, seed=fed.seed)
    k_max = int(ks.max())
    batcher = make_batcher(cfg, m, seq, batch, seed, n_seq=n_seq)
    waves = [batcher.sample(jnp.int32(t), k_max) for t in range(n_rounds)]
    params = model_lib.init_params(jax.random.PRNGKey(seed), cfg)
    shape = ShapeConfig("smoke", seq_len=seq, global_batch=m * batch,
                        kind="train")
    mesh = make_mesh((2, 2), ("data", "model"))
    print(f"sharded: {cfg.name} {cfg.n_layers}L dtype={cfg.dtype} "
          f"mesh {dict(mesh.shape)} M={m} K={ks.tolist()}", flush=True)

    with jax.set_mesh(mesh):
        jitted, bundle = train_lib.build_train_round(cfg, shape, mesh, fed,
                                                     k_max=k_max)
        spec = bundle["flat_spec"]
        sh = lambda t: specs_lib.to_shardings(t, mesh)
        ps = bundle["pspecs"]
        state = jax.device_put(
            rounds.init_state(flat.ravel(spec, params), m, algo),
            sh(ps["state"]))
        weights = jax.device_put(jnp.full((m,), 1.0 / m, jnp.float32),
                                 sh(ps["weights"]))
        inputs = [(jax.device_put(w, sh(ps["batches"])),
                   jax.device_put(jnp.asarray(k), sh(ps["k_steps"])))
                  for w, k in zip(waves, ks)]
        tic = time.perf_counter()
        compiled = jitted.lower(state, *inputs[0], weights).compile()
        print(f"sharded: compile_s {time.perf_counter() - tic:.3f}",
              flush=True)
        print(f"sharded: collectives {collectives_in(compiled.as_text())}",
              flush=True)
        losses = []
        for b, k in inputs:
            state, met = jitted(state, b, k, weights)
            losses.append(float(met["loss"]))
        got = {key: np.asarray(state[key], np.float32)
               for key in ("params", "nu")}
    del state, inputs

    # the reference: the same flat round on device 0, on the same XLA paths
    # as the partitioned program (oracle update, blocked attention)
    dev0 = jax.devices()[0]
    loss_fn = functools.partial(model_lib.lm_loss, cfg=cfg)
    ref_round = jax.jit(flat.make_flat_round(
        spec, lambda p, b: loss_fn(p, b), algo, lr=fed.lr, k_max=k_max,
        use_pallas=False), donate_argnums=(0,))
    saved = os.environ.get("REPRO_FLASH_ATTENTION")
    os.environ["REPRO_FLASH_ATTENTION"] = "off"
    try:
        ref = jax.device_put(
            rounds.init_state(flat.ravel(spec, params), m, algo), dev0)
        w0 = jax.device_put(jnp.full((m,), 1.0 / m, jnp.float32), dev0)
        ref_losses = []
        for w, k in zip(waves, ks):
            ref, met = ref_round(ref, jax.device_put(w, dev0),
                                 jax.device_put(jnp.asarray(k), dev0), w0)
            ref_losses.append(float(met["loss"]))
    finally:
        if saved is None:
            os.environ.pop("REPRO_FLASH_ATTENTION")
        else:
            os.environ["REPRO_FLASH_ATTENTION"] = saved
    print(f"sharded: losses {losses} unsharded {ref_losses}", flush=True)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    outside = {}
    for key in ("params", "nu"):
        want = np.asarray(ref[key], np.float32)
        err = np.abs(got[key] - want)
        bad = err > ATOL + RTOL * np.abs(want)
        outside[key] = int(np.sum(bad))
        print(f"sharded: {key} max_abs_diff {float(err.max()):.3e}, "
              f"{outside[key]} of {want.size} outside rtol {RTOL} atol "
              f"{ATOL}", flush=True)
        # where the violations sit: spread over every leaf (arithmetic) or
        # held in a few expert / router leaves (a routing decision)
        for name, off, size in zip(names, spec.offsets, spec.sizes):
            n_bad = int(np.sum(bad[off:off + size]))
            if n_bad:
                print(f"sharded: {key} {name} {n_bad} of {size} outside, "
                      f"max_abs_diff {float(err[off:off + size].max()):.3e}",
                      flush=True)
    if any(outside.values()):
        fail(f"sharded round != unsharded round: {outside}")
    for a, b in zip(losses, ref_losses):
        if not abs(a - b) < LOSS_TOL:
            fail(f"sharded loss {a} != unsharded {b}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded round on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    require_tpu(args.chips)
    print(f"device: {device_info()}  compile cache: {setup_compile_cache()}",
          flush=True)
    if args.chips == 4:
        # float32 tolerances need float32 matmuls.  By default a TPU
        # rounds f32 operands to bf16; the two partitionings sum in
        # different orders, and an operand one f32 ulp apart can then round
        # to bf16 values 2⁻⁸ apart
        with jax.default_matmul_precision("highest"):
            sharded_phase(smoke_config("float32"), seed=args.seed)
    else:
        cfg = smoke_config()
        kernel_phase(cfg, seed=args.seed)
        sim = train_phase(cfg, smoke_fed(M_CLIENTS),
                          make_batcher(cfg, M_CLIENTS, SEQ, BATCH, args.seed),
                          seed=args.seed)
        serve_phase(cfg, sim, seed=args.seed)
    print(json.dumps({"ok": True, "device": device_info()}))


if __name__ == "__main__":
    main()
