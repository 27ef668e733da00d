"""The plain references against the program's models on seeded weights,
at a small width on the CPU, in float32: losses, logits and every
gradient leaf agree to float32 rounding."""
import dataclasses

import chipbench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

CASES = [("granite-moe-1b-a400m-2l", chipbench_tiny.GRANITE)]


def _setup(name, small):
    from repro.models import model as model_lib
    cfg = harness.load_json("configs", name)
    cfg.update(small)
    cfg["model"] = dict(small["model"], dtype="float32")
    if "moe" in cfg["model"]:
        # every expert holds every token: the program drops none either
        cfg["model"]["moe"] = dict(cfg["model"]["moe"], capacity_factor=2.0)
    mc = harness.model_config(cfg)
    params = harness.make_weights(mc, 2**40 + 3, cfg.get("init"))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0,
                              cfg["vocab_size"])
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    ref = harness.load_module("reference", name)
    mm = harness.load_module("reference", "fedagrac").MATMUL["plain"]
    return model_lib, mc, cfg, params, batch, ref, mm


@pytest.mark.parametrize("name,small", CASES, ids=[c[0] for c in CASES])
def test_reference_matches_program(name, small):
    model_lib, mc, cfg, params, batch, ref, mm = _setup(name, small)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: model_lib.lm_loss(p, batch, mc))(params)
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(p, batch, cfg, mm))(params)
        logits_p = model_lib.forward(params, batch, mc)[0]
        logits_r = ref.forward(params, batch["tokens"], cfg, mm)[0]
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_r),
                               atol=5e-5 * float(jnp.max(jnp.abs(logits_r))))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale


def test_granite_reference_drops_no_token():
    """At capacity factor 1 the program drops tokens and parts from the
    dropless reference; at n_experts / top_k it agrees."""
    model_lib, mc, cfg, params, batch, ref, mm = _setup(*CASES[0])
    tight = dataclasses.replace(mc, moe=dataclasses.replace(
        mc.moe, capacity_factor=0.5))
    with jax.default_matmul_precision("highest"):
        lr = float(ref.loss(params, batch, cfg, mm))
        l_tight = float(model_lib.lm_loss(params, batch, tight))
        l_full = float(model_lib.lm_loss(params, batch, mc))
    assert l_full == pytest.approx(lr, rel=1e-5)
    assert abs(l_tight - lr) > 1e-3


def test_control_precision_is_coarser():
    """The control's fp8 matmul rounds operands to 3 mantissa bits."""
    mm = harness.load_module("reference", "fedagrac").MATMUL
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    exact = mm["plain"]("ij,jk->ik", a, b)
    rough = mm["fp8"]("ij,jk->ik", a, b)
    rel = float(jnp.max(jnp.abs(rough - exact)) / jnp.max(jnp.abs(exact)))
    assert 1e-3 < rel < 0.2
