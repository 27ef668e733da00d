"""MoE dispatch correctness: the sort-based capacity dispatch must equal a
dense (every-expert-on-every-token) reference when capacity is unlimited,
and degrade only by dropping when capacity binds.  Its gathers must give
exactly what the scatter formulation they replaced gave, with the same
gradients, and the differentiated layer must lower without a scatter."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.mlp import mlp
from repro.models.moe import init_moe, moe, route


def _cfg(E=4, top_k=2, cap=None) -> ModelConfig:
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=16, vocab=64,
        moe=MoEConfig(n_experts=E, top_k=top_k, d_ff=16,
                      capacity_factor=cap if cap is not None else float(E),
                      aux_loss_coef=0.0))


def dense_moe_ref(params, x, cfg):
    """Every token through every expert, combined by router weights."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    w, ids, _ = route(params["router"], xt, cfg.moe.top_k)
    h = jnp.einsum("td,edf->etf", xt, params["w_in"])
    g = jnp.einsum("td,edf->etf", xt, params["w_gate"])
    out_all = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h,
                         params["w_out"])                      # (E,T,d)
    y = jnp.zeros_like(xt)
    for j in range(cfg.moe.top_k):
        y = y + w[:, j, None] * jnp.take_along_axis(
            out_all, ids[None, :, j, None], axis=0)[0]
    return y.reshape(B, S, d)


def test_moe_matches_dense_reference_no_drop():
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    params = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    got, aux = moe(params, x, cfg)
    want = dense_moe_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) == 0.0                                   # coef 0


def test_route_weights_normalized():
    cfg = _cfg(E=8, top_k=3)
    key = jax.random.PRNGKey(2)
    router = jax.random.normal(key, (32, 8))
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    w, ids, aux = route(router, x, 3)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert int(ids.max()) < 8 and int(ids.min()) >= 0
    # top-k ids distinct per token
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == 3
    assert float(aux) >= 1.0 - 1e-3    # switch aux loss lower bound is 1


def test_capacity_drops_are_bounded():
    """With tight capacity the output differs from dense only on dropped
    tokens, and the shared expert still covers every token."""
    cfg = _cfg(E=4, top_k=2, cap=0.5)
    key = jax.random.PRNGKey(4)
    params = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 16, 32))
    got, _ = moe(params, x, cfg)
    assert np.all(np.isfinite(np.asarray(got)))
    # dropped-token rows are exactly zero (no shared expert here)
    dense = dense_moe_ref(params, x, cfg)
    diff = np.abs(np.asarray(got - dense)).max(axis=-1)[0]
    kept = diff < 1e-4
    assert kept.sum() >= 4          # capacity 0.5 keeps ≥ E*C/k tokens


def test_moe_gradients_flow_to_all_parts():
    cfg = _cfg()
    key = jax.random.PRNGKey(6)
    params = init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32))

    def loss(p):
        y, aux = moe(p, x, cfg)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(params)
    for name in ("router", "w_in", "w_gate", "w_out"):
        assert float(jnp.max(jnp.abs(g[name]))) > 0, name


def scatter_moe_ref(params, x, cfg):
    """The scatter formulation that the gathers replaced: argsort,
    searchsorted ranks, a scatter into an (E*C + 1)-row buffer whose last
    row sinks dropped assignments, and a scattered inverse permutation
    that gathers the rows back."""
    m = cfg.moe
    B, S, d = x.shape
    T, k, E = B * S, m.top_k, m.n_experts
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, ids = jax.lax.top_k(probs, k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    capacity = int(max(1, round(T * k / E * m.capacity_factor)))
    flat_ids = ids.reshape(-1)
    order = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[order]
    pos = jnp.arange(T * k) - jnp.searchsorted(sorted_ids, sorted_ids,
                                               side="left")
    dst = jnp.where(pos < capacity, sorted_ids * capacity + pos,
                    E * capacity)
    buf = jnp.zeros((E * capacity + 1, d), x.dtype)
    buf = buf.at[dst].set(xt[order // k], mode="drop")
    buf = buf[: E * capacity].reshape(E, capacity, d)
    h = jnp.einsum("ecd,edf->ecf", buf, params["w_in"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, params["w_out"])
    out_flat = jnp.concatenate(
        [out.reshape(E * capacity, d), jnp.zeros((1, d), out.dtype)], axis=0)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    rows = out_flat[dst[inv]].reshape(T, k, d)
    y = jnp.einsum("tkd,tk->td", rows.astype(jnp.float32),
                   weights.astype(jnp.float32)).astype(x.dtype)
    if m.n_shared_experts:
        y = y + mlp(params["shared"], x, cfg).reshape(T, d)
    return y.reshape(B, S, d)


# (experts, top-k, capacity factor, shared experts)
SHAPES = {
    "dropless": (4, 2, 4.0, 0),
    "binding-0.5": (8, 2, 0.5, 0),
    "binding-1.25": (8, 3, 1.25, 0),
    "granite": (32, 8, 4.0, 0),               # 32 experts top-8, dropless
    "deepseek-like": (16, 6, 1.25, 2),        # shared experts, binding
}


def _case(name, seed=0, clients=None):
    E, k, cap, shared = SHAPES[name]
    cfg = _cfg(E=E, top_k=k, cap=cap)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_shared_experts=shared))
    key = jax.random.PRNGKey(seed)
    if clients is None:
        params, lead = init_moe(key, cfg, jnp.float32), ()
    else:                       # each client its own parameters, as in a round
        params = jax.vmap(lambda k: init_moe(k, cfg, jnp.float32))(
            jax.random.split(key, clients))
        lead = (clients,)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), lead + (2, 16, 32))
    return cfg, params, x


def _gather_y(params, x, cfg):
    return moe(params, x, cfg)[0]


def _loss(fn, cfg):
    return lambda params, x: jnp.sum(jnp.sin(fn(params, x, cfg)))


def _assert_grads_close(got, want):
    """float32 sums in another order: 1e-5 relative, and 1e-6 of the
    leaf's largest entry for entries that nearly cancel."""
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5,
                                   atol=1e-6 * np.abs(w).max(),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", SHAPES)
def test_moe_forward_bit_identical_to_scatter(name):
    cfg, params, x = _case(name)
    got = jax.jit(_gather_y, static_argnums=2)(params, x, cfg)
    want = jax.jit(scatter_moe_ref, static_argnums=2)(params, x, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", SHAPES)
def test_moe_gradients_match_scatter(name):
    """Gradients with respect to x, the router and the expert weights."""
    cfg, params, x = _case(name, seed=2)
    grad = lambda fn: jax.jit(jax.grad(_loss(fn, cfg), argnums=(0, 1)))
    _assert_grads_close(grad(_gather_y)(params, x),
                        grad(scatter_moe_ref)(params, x))


@pytest.mark.parametrize("name", SHAPES)
def test_moe_matches_scatter_under_client_vmap_and_remat(name):
    """As the flat round runs it: a leading client axis, rematerialised."""
    cfg, params, x = _case(name, seed=4, clients=2)

    def per_client(fn):
        return jax.vmap(jax.checkpoint(_loss(fn, cfg)))

    def value_and_grad(fn):
        total = lambda p, xs: jnp.sum(per_client(fn)(p, xs))
        return jax.jit(jax.value_and_grad(total, argnums=(0, 1)))

    got, got_g = value_and_grad(_gather_y)(params, x)
    want, want_g = value_and_grad(scatter_moe_ref)(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _assert_grads_close(got_g, want_g)


def _vmapped_grad_hlo(fn, cfg, params, x):
    """Compiled HLO of the client-vmapped layer's gradient, without the
    metadata (whose op names may quote any word)."""
    def total(p, xs):
        return jnp.sum(jax.vmap(_loss(fn, cfg))(p, xs))

    text = jax.jit(jax.grad(total, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    ops = set(re.findall(r"=\s.*?\s([a-z][a-z0-9-]*)\(", text))
    dims = {int(n) for s in re.findall(r"\[([0-9,]+)\]", text)
            for n in s.split(",")}
    return ops, dims


def test_moe_grad_lowers_without_scatter_while_or_sink_row():
    """The differentiated, client-vmapped layer is gathers both ways: no
    scatter, no while loop (searchsorted's), no (E*C + 1)-row buffer."""
    cfg, params, x = _case("granite", clients=2)
    m, T = cfg.moe, x.shape[1] * x.shape[2]
    capacity = int(round(T * m.top_k / m.n_experts * m.capacity_factor))
    sink_rows = m.n_experts * capacity + 1
    ops, dims = _vmapped_grad_hlo(_gather_y, cfg, params, x)
    assert {"gather", "sort", "dot"} <= ops
    assert not {"scatter", "while"} & ops
    assert sink_rows not in dims
    # the scatter formulation trips each check
    ops, dims = _vmapped_grad_hlo(scatter_moe_ref, cfg, params, x)
    assert {"scatter", "while"} <= ops and sink_rows in dims
