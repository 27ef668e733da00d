"""Golden layout equivalence: ``param_layout="flat"`` (core/flat.py) must
reproduce the tree-layout round for every algorithm on both engines.

Tolerance, not bit-equality, and deliberately so: the flat round performs
the same elementwise arithmetic in the same order, but XLA:CPU contracts
``x − η·g`` into an FMA (one rounding) in one program layout and not the
other — an LLVM fusion-context decision (verified: the tree path matches
the fused-multiply-add reference exactly, the flat path the two-rounding
reference; the same asymmetry test_calibrated_update_2d documents).  f32
trajectories therefore agree to ~1 ulp per local step; tests pin a few
chained rounds at rtol 1e-6.  bf16 additionally rounds once per fused
kernel instead of once per op — pinned at bf16-ulp scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.core import flat, rounds, stages
from repro.core.fedopt import ALGORITHMS, get_algorithm
from repro.data import DeviceBatcher, FederatedBatcher, fedprox_synthetic
from repro.fed import BufferedAsyncSimulation, FederatedSimulation
from repro.models.simple import lr_accuracy, lr_loss, quad_loss

M, D, K_MAX = 4, 6, 8
W = jnp.array([0.1, 0.2, 0.3, 0.4], jnp.float32)
KS = jnp.array([1, 3, 5, 8], jnp.int32)
PARAMS = {"x": jnp.zeros((D,), jnp.float32)}
SPEC = flat.make_flat_spec(PARAMS)
RTOL, ATOL = 1e-6, 1e-7


def _batches(m=M, key=0):
    rng = np.random.default_rng(key)
    return {
        "A": jnp.asarray(rng.normal(size=(m, K_MAX, D, D)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(m, K_MAX, D)).astype(np.float32)),
        "c0": jnp.zeros((m, K_MAX)),
    }


def _algo(name):
    fed = FedConfig(algorithm=name, n_clients=M, lr=0.01,
                    calibration_rate=0.5)
    return get_algorithm(name, fed)


def _assert_close(tree_out, flat_out, rtol=RTOL, atol=ATOL):
    (state_t, metrics_t), (state_f, metrics_f) = tree_out, flat_out
    assert set(state_t) == set(state_f)
    for (path, lt), lf in zip(
            jax.tree_util.tree_leaves_with_path(state_t),
            jax.tree.leaves(state_f)):
        np.testing.assert_allclose(
            np.asarray(lt, np.float32), np.asarray(lf, np.float32),
            rtol=rtol, atol=atol,
            err_msg=f"state leaf {jax.tree_util.keystr(path)} diverged")
    for k in metrics_t:
        np.testing.assert_allclose(
            np.asarray(metrics_t[k]), np.asarray(metrics_f[k]),
            rtol=rtol, atol=atol, err_msg=f"metric {k!r} diverged")


def _run_pair(algo, n_rounds=3, use_pallas=None, **make_kw):
    state_t = rounds.init_state(dict(PARAMS), M, algo)
    state_f = flat.flatten_state(SPEC, state_t)
    fn_t = jax.jit(rounds.make_round(quad_loss, algo, lr=0.01, k_max=K_MAX,
                                     **make_kw))
    fn_f = jax.jit(flat.make_flat_round(SPEC, quad_loss, algo, lr=0.01,
                                        k_max=K_MAX, use_pallas=use_pallas,
                                        **make_kw))
    b = _batches()
    for _ in range(n_rounds):
        state_t, metrics_t = fn_t(state_t, b, KS, W)
        state_f, metrics_f = fn_f(state_f, b, KS, W)
    return ((state_t, metrics_t),
            (flat.unflatten_state(SPEC, state_f), metrics_f))


# ---------------------------------------------------------------------------
# spec / ravel plumbing
# ---------------------------------------------------------------------------

def test_ravel_roundtrip_and_lane_padding():
    tree = {"a": jnp.arange(7, dtype=jnp.float32).reshape(1, 7),
            "b": {"c": jnp.ones((3, 2), jnp.float32)}}
    spec = flat.make_flat_spec(tree)
    assert spec.n == 13 and spec.p == 128 and spec.dtype == jnp.float32
    buf = flat.ravel(spec, tree)
    assert buf.shape == (128,)
    np.testing.assert_array_equal(np.asarray(buf[13:]), 0.0)
    back = flat.unravel(spec, buf)
    for (pa, la), lb in zip(jax.tree_util.tree_leaves_with_path(tree),
                            jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_ravel_client_stacked_rows():
    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 2, 2),
            "b": jnp.ones((3, 5), jnp.float32)}
    spec = flat.make_flat_spec({"w": jnp.zeros((2, 2)),
                                "b": jnp.zeros((5,))})
    mat = flat.ravel(spec, tree, client_dims=1)
    assert mat.shape == (3, spec.p)
    back = flat.unravel(spec, mat, client_dims=1)
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(back["b"]),
                                  np.asarray(tree["b"]))


def test_mixed_dtype_tree_flattens_to_f32():
    spec = flat.make_flat_spec({"a": jnp.zeros((4,), jnp.bfloat16),
                                "b": jnp.zeros((4,), jnp.float32)})
    assert spec.dtype == jnp.float32


def test_flat_round_keeps_padding_zero():
    """Every stage is padding-preserving: after several chained rounds the
    lane-padding tail of every flat state buffer is exactly zero (the
    invariant that makes the flat ↔ tree bijection stable)."""
    algo = _algo("fedagrac")
    state = flat.flatten_state(SPEC, rounds.init_state(dict(PARAMS), M,
                                                       algo))
    fn = jax.jit(flat.make_flat_round(SPEC, quad_loss, algo, lr=0.01,
                                      k_max=K_MAX))
    b = _batches()
    for _ in range(3):
        state, _ = fn(state, b, KS, W)
    for k in ("params", "nu"):
        np.testing.assert_array_equal(np.asarray(state[k][SPEC.n:]), 0.0)
    np.testing.assert_array_equal(np.asarray(state["nu_i"][:, SPEC.n:]),
                                  0.0)


# ---------------------------------------------------------------------------
# round-level golden equivalence (synchronous engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALGORITHMS)
def test_flat_round_matches_tree_all_algorithms(name):
    """All 9 algorithms: 3 chained rounds, every state leaf + metric within
    ulp-scale tolerance of the tree round."""
    _assert_close(*_run_pair(_algo(name)))


@pytest.mark.parametrize("server_opt,server_lr", [("momentum", 0.7),
                                                  ("adam", 0.1)])
def test_flat_round_matches_tree_server_optimizers(server_opt, server_lr):
    import dataclasses
    algo = dataclasses.replace(_algo("fedagrac"), server_opt=server_opt,
                               server_lr=server_lr)
    _assert_close(*_run_pair(algo))


def test_flat_round_matches_tree_explicit_nu():
    _assert_close(*_run_pair(_algo("fedagrac"), track_nu="explicit"))


@pytest.mark.parametrize("name", ["fedagrac", "fedprox", "fedavg"])
def test_flat_round_pallas_kernel_path(name):
    """The TPU client path — per-step ``calibrated_update_2d`` /
    ``_prox_2d`` launches (interpret mode here) — pinned against the tree
    round like the oracle path."""
    _assert_close(*_run_pair(_algo(name), use_pallas=True))


@pytest.mark.parametrize("use_pallas", [None, True])
def test_flat_round_prox_with_orientation(use_pallas):
    """prox + an orientation selector (no registered algorithm combines
    them, but the Algorithm dataclass permits it): the tree path adds the
    prox term into g BEFORE the g₀ select and ν recovery, so the flat
    path must augment g the same way instead of fusing prox into the
    update only."""
    import dataclasses
    algo = dataclasses.replace(_algo("fedagrac"), prox_mu=0.1)
    _assert_close(*_run_pair(algo, use_pallas=use_pallas))
    algo_first = dataclasses.replace(_algo("fedlin"), prox_mu=0.1)
    _assert_close(*_run_pair(algo_first, use_pallas=use_pallas))


def test_flat_round_matches_tree_quantized_transmit():
    """int8 fake-quantization keeps its per-client-per-LEAF scale semantics
    in flat mode (round-trips through the tree at the transmit)."""
    _assert_close(*_run_pair(_algo("fedagrac"), quantize_transmit=True))


def test_flat_round_bf16_ulp():
    """bf16 state: the fused kernel accumulates in f32 and rounds once
    where the tree path rounds per op — agreement to a few bf16 ulp."""
    algo = _algo("fedagrac")
    params = {"x": jnp.zeros((D,), jnp.bfloat16)}
    spec = flat.make_flat_spec(params)
    assert spec.dtype == jnp.bfloat16
    b = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _batches())
    state_t = rounds.init_state(params, M, algo)
    state_f = flat.flatten_state(spec, state_t)
    fn_t = jax.jit(rounds.make_round(quad_loss, algo, lr=0.01, k_max=K_MAX))
    fn_f = jax.jit(flat.make_flat_round(spec, quad_loss, algo, lr=0.01,
                                        k_max=K_MAX))
    state_t, _ = fn_t(state_t, b, KS, W, jnp.float32(0.5))
    state_f, _ = fn_f(state_f, b, KS, W, jnp.float32(0.5))
    back = flat.unflatten_state(spec, state_f)
    assert back["params"]["x"].dtype == jnp.bfloat16
    for (path, lt), lf in zip(
            jax.tree_util.tree_leaves_with_path(state_t),
            jax.tree.leaves(back)):
        np.testing.assert_allclose(
            np.asarray(lt, np.float32), np.asarray(lf, np.float32),
            rtol=2 ** -6, atol=2 ** -6,
            err_msg=f"bf16 leaf {jax.tree_util.keystr(path)} diverged")


# ---------------------------------------------------------------------------
# cohort round (partial participation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALGORITHMS)
def test_flat_cohort_round_matches_tree(name):
    """The flat cohort round (row gather/scatter on the (M, P) ν⁽ⁱ⁾ store)
    against stages.make_cohort_round, Σw̃ ≠ 1 and ν-decay included."""
    algo = _algo(name)
    c = 3
    cohort = jnp.array([3, 0, 2], jnp.int32)
    ks = jnp.array([2, 5, 8], jnp.int32)
    cw = jnp.array([0.5, 0.7, 0.3], jnp.float32)
    b = _batches(m=c, key=1)
    state_t = rounds.init_state(dict(PARAMS), M, algo)
    state_f = flat.flatten_state(SPEC, state_t)
    fn_t = jax.jit(stages.make_cohort_round(quad_loss, algo, lr=0.01,
                                            k_max=K_MAX, nu_decay=0.1))
    fn_f = jax.jit(flat.make_flat_cohort_round(SPEC, quad_loss, algo,
                                               lr=0.01, k_max=K_MAX,
                                               nu_decay=0.1))
    for _ in range(3):
        state_t, metrics_t = fn_t(state_t, b, cohort, ks, cw)
        state_f, metrics_f = fn_f(state_f, b, cohort, ks, cw)
    _assert_close((state_t, metrics_t),
                  (flat.unflatten_state(SPEC, state_f), metrics_f))


# ---------------------------------------------------------------------------
# engine-level equivalence (the wired simulations)
# ---------------------------------------------------------------------------

def _lr_task():
    key = jax.random.PRNGKey(0)
    data, parts = fedprox_synthetic(key, M, alpha=1.0, beta=1.0)
    params = {"w": jnp.zeros((60, 10)), "b": jnp.zeros((10,))}
    ev = lambda p: float(lr_accuracy(p, {"x": data.x, "y": data.y}))
    return data, parts, params, ev


@pytest.mark.parametrize("sampler", ["host", "device"])
def test_flat_simulation_matches_tree(sampler):
    """FederatedSimulation with param_layout="flat": same losses, metrics
    and final params as the tree layout, chunked AND per-round, λ-schedule
    included."""
    data, parts, params, ev = _lr_task()
    ks = np.full((20, M), 3, np.int32)
    B = {"host": FederatedBatcher, "device": DeviceBatcher}[sampler]

    def run(layout, chunk):
        fed = FedConfig(algorithm="fedagrac", n_clients=M, lr=0.05,
                        calibration_rate=0.5, weights="data",
                        param_layout=layout)
        sim = FederatedSimulation(lr_loss, params, fed, B(data, parts, 10),
                                  eval_fn=ev, k_schedule=ks,
                                  lam_schedule=lambda t: 0.25 * (t + 1))
        hist = sim.run(8, eval_every=4, chunk_rounds=chunk)
        return sim, hist

    for chunk in (1, None):
        sim_t, h_t = run("tree", chunk)
        sim_f, h_f = run("flat", chunk)
        np.testing.assert_allclose(h_t.loss, h_f.loss, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(h_t.metric, h_f.metric, rtol=RTOL,
                                   atol=ATOL)
        for (path, lt), lf in zip(
                jax.tree_util.tree_leaves_with_path(sim_t.params),
                jax.tree.leaves(sim_f.params)):
            np.testing.assert_allclose(
                np.asarray(lt), np.asarray(lf), rtol=RTOL, atol=ATOL,
                err_msg=f"params leaf {jax.tree_util.keystr(path)}")


def test_flat_simulation_cohort_sampler_matches_tree():
    """Partial participation through the simulation: the flat cohort round
    under the uniform sampler reproduces the tree trajectories."""
    data, parts, params, ev = _lr_task()
    ks = np.full((20, M), 3, np.int32)

    def run(layout):
        fed = FedConfig(algorithm="fedagrac", n_clients=M, lr=0.05,
                        calibration_rate=0.5, weights="data", cohort_size=2,
                        cohort_sampler="uniform", cohort_nu_decay=0.1,
                        param_layout=layout)
        sim = FederatedSimulation(lr_loss, params, fed,
                                  FederatedBatcher(data, parts, 10),
                                  eval_fn=ev, k_schedule=ks)
        hist = sim.run(8, eval_every=4)
        return sim, hist

    sim_t, h_t = run("tree")
    sim_f, h_f = run("flat")
    np.testing.assert_allclose(h_t.loss, h_f.loss, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(h_t.mass, h_f.mass, rtol=RTOL, atol=ATOL)
    for lt, lf in zip(jax.tree.leaves(sim_t.params),
                      jax.tree.leaves(sim_f.params)):
        np.testing.assert_allclose(np.asarray(lt), np.asarray(lf),
                                   rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("name", ALGORITHMS)
def test_flat_async_engine_matches_tree(name):
    """BufferedAsyncSimulation: flat (M+1, P) anchor matrices + the
    per-client-anchor flat client scan reproduce the tree engine for all
    9 algorithms (stale anchors, duplicate reporters, staleness discounts
    all exercised by the lognormal clock)."""
    data, parts, params, ev = _lr_task()
    ks = np.full((8, M), 3, np.int32)

    def run(layout):
        fed = FedConfig(algorithm=name, n_clients=M, lr=0.05,
                        calibration_rate=0.5, weights="data", buffer_size=2,
                        staleness="hinge", speed_dist="lognormal",
                        speed_sigma=0.7, param_layout=layout)
        sim = BufferedAsyncSimulation(lr_loss, params, fed,
                                      FederatedBatcher(data, parts, 10),
                                      eval_fn=ev, k_schedule=ks)
        hist = sim.run(6, eval_every=3)
        return sim, hist

    sim_t, h_t = run("tree")
    sim_f, h_f = run("flat")
    np.testing.assert_allclose(h_t.loss, h_f.loss, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(h_t.metric, h_f.metric, rtol=RTOL, atol=ATOL)
    assert h_t.sim_time == h_f.sim_time          # timeline is layout-free
    for (path, lt), lf in zip(
            jax.tree_util.tree_leaves_with_path(sim_t.params),
            jax.tree.leaves(sim_f.params)):
        np.testing.assert_allclose(
            np.asarray(lt), np.asarray(lf), rtol=RTOL, atol=ATOL,
            err_msg=f"params leaf {jax.tree_util.keystr(path)}")


def test_unknown_layout_raises():
    data, parts, params, _ = _lr_task()
    # validation happens at config construction (FedConfig.__post_init__)
    with pytest.raises(ValueError, match="param_layout"):
        FedConfig(algorithm="fedavg", n_clients=M, param_layout="ring")
    # the engine guards are defense-in-depth for a layout smuggled past
    # the frozen dataclass
    fed = FedConfig(algorithm="fedavg", n_clients=M)
    object.__setattr__(fed, "param_layout", "ring")
    with pytest.raises(ValueError, match="param_layout"):
        FederatedSimulation(lr_loss, params, fed,
                            FederatedBatcher(data, parts, 10))
    with pytest.raises(ValueError, match="param_layout"):
        BufferedAsyncSimulation(lr_loss, params, fed,
                                FederatedBatcher(data, parts, 10))


# ---------------------------------------------------------------------------
# the client update: clients in turn, each its own K_i steps
# ---------------------------------------------------------------------------

def _masked_scan(spec, loss_fn, algo, *, lr, k_max, track_nu, anchor, c_all,
                 batches, k_steps, lam, per_client_anchor):
    """The client update as it was before clients ran in turn: a k_max-step
    scan vmapped over the clients, each row past its K_i masked by a zero
    step size (jnp arithmetic)."""
    needs_first = algo.selector in ("fedagrac", "first", "reverse")
    explicit = track_nu == "explicit" and algo.uses_nu
    fuse_prox = bool(algo.prox_mu) and not (needs_first or explicit)
    m = k_steps.shape[0]
    anchors = (anchor if per_client_anchor
               else jnp.broadcast_to(anchor[None], (m, spec.p)))
    lam = lam if algo.uses_nu else 0.0
    grad = jax.vmap(flat.flat_value_and_grad(spec, loss_fn))

    def step(carry, xs):
        k, batch = xs
        x, g0, acc = carry
        loss, g = grad(x, batch)
        if algo.prox_mu and not fuse_prox:
            g = g + algo.prox_mu * (x - anchors)
        eta = jnp.where(k < k_steps, jnp.float32(lr), 0.0)[:, None]
        t = g + lam * c_all if algo.uses_nu else g
        if fuse_prox:
            t = t + algo.prox_mu * (x - anchors)
        x = x - eta * t
        g0 = jnp.where(k == 0, g, g0)
        w = jnp.where(k < k_steps, 1.0 / k_steps.astype(jnp.float32), 0.0)
        return (x, g0, acc + w[:, None] * g), loss

    zeros = jnp.zeros((m, spec.p), spec.dtype)
    (x, g0, acc), losses = jax.lax.scan(
        step, (anchors, zeros, zeros),
        (jnp.arange(k_max), jax.tree.map(lambda b: jnp.swapaxes(b, 0, 1),
                                         batches)))
    return (x, g0 if needs_first else None, acc if explicit else None,
            losses[0])


def _prox_fedagrac():
    import dataclasses
    return dataclasses.replace(_algo("fedagrac"), prox_mu=0.1)


SERIAL_CASES = {
    # name: (algorithm, update kwargs, k_steps, k_max, per_client_anchor)
    "fedagrac_delta": (lambda: _algo("fedagrac"), {}, KS, K_MAX, False),
    "fedagrac_explicit_nu": (lambda: _algo("fedagrac"),
                             {"track_nu": "explicit"}, KS, K_MAX, False),
    "prox_fused": (lambda: _algo("fedprox"), {}, KS, K_MAX, False),
    "prox_unfused": (_prox_fedagrac, {}, KS, K_MAX, False),
    "per_client_anchor": (lambda: _algo("fedagrac"), {}, KS, K_MAX, True),
    # partial work: each client aborts after k′ < K_i of its steps
    "partial_work": (lambda: _algo("fedagrac"), {},
                     jnp.array([1, 2, 3, 5], jnp.int32), K_MAX, False),
    "k_max_1": (lambda: _algo("fedagrac"), {}, jnp.ones((M,), jnp.int32), 1,
                False),
    "pallas_kernel": (lambda: _algo("fedagrac"),
                      {"use_pallas": True, "interpret": True}, KS, K_MAX,
                      False),
}


def _client_inputs(per_client_anchor, key=3):
    rng = np.random.default_rng(key)
    shape = (M, SPEC.p) if per_client_anchor else (SPEC.p,)
    pad = lambda a: a.at[..., SPEC.n:].set(0.0)
    anchor = pad(jnp.asarray(rng.normal(size=shape).astype(np.float32)))
    c_all = pad(jnp.asarray(
        0.1 * rng.normal(size=(M, SPEC.p)).astype(np.float32)))
    return anchor, c_all


@pytest.mark.parametrize("case", SERIAL_CASES)
def test_client_serial_update_matches_masked_scan(case):
    """Clients in turn, each a loop of its own K_i steps, against the
    vmapped k_max-step scan with rows past K_i masked: every output to
    float32 tolerance, and Σ K_i client-steps in place of M · k_max."""
    make_algo, kw, ks, k_max, per_client = SERIAL_CASES[case]
    algo = make_algo()
    track_nu = kw.get("track_nu", "delta")
    anchor, c_all = _client_inputs(per_client)
    b = jax.tree.map(lambda a: a[:, :k_max], _batches())
    cu = jax.jit(flat.make_flat_client_update(
        SPEC, quad_loss, algo, lr=0.01, k_max=k_max,
        per_client_anchor=per_client, **kw))
    x, g0, acc, loss0, steps = cu(anchor, c_all, b, ks, jnp.float32(0.5))
    want = jax.jit(lambda a, c, bb, k: _masked_scan(
        SPEC, quad_loss, algo, lr=0.01, k_max=k_max, track_nu=track_nu,
        anchor=a, c_all=c, batches=bb, k_steps=k, lam=jnp.float32(0.5),
        per_client_anchor=per_client))(anchor, c_all, b, ks)
    for got, ref in zip((x, g0, acc, loss0), want):
        if ref is not None:
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=RTOL, atol=ATOL)
    assert int(steps) == int(np.minimum(np.asarray(ks), k_max).sum())


def test_client_past_its_k_keeps_its_last_active_row():
    """A client whose K_i is below k_max ends on the row of its K_i-th
    step, bit for bit: the same client run with k_max = K_i."""
    algo = _algo("fedagrac")
    anchor, c_all = _client_inputs(False)
    b = _batches()
    run = lambda k_max, ks: flat.make_flat_client_update(
        SPEC, quad_loss, algo, lr=0.01, k_max=k_max)(
            anchor, c_all, jax.tree.map(lambda a: a[:, :k_max], b),
            jnp.asarray(ks, jnp.int32), jnp.float32(0.5))
    x_short = run(3, [3, 3, 3, 3])[0]
    x_long = run(K_MAX, [3, 5, 8, 3])[0]
    for i in (0, 3):
        np.testing.assert_array_equal(np.asarray(x_long[i]),
                                      np.asarray(x_short[i]))


def test_round_evaluates_the_loss_sum_k_times():
    """A jitted round with K = [1, 3] and k_max 3 runs the loss 4 times,
    not M · k_max = 6: counted by the round's ``client_steps`` metric and
    by a callback inside the loss."""
    calls = []

    def counted(params, batch):
        jax.debug.callback(lambda: calls.append(1))
        return quad_loss(params, batch)

    algo = _algo("fedagrac")
    m, k_max = 2, 3
    state = flat.flatten_state(SPEC, rounds.init_state(dict(PARAMS), m,
                                                       algo))
    fn = jax.jit(flat.make_flat_round(SPEC, counted, algo, lr=0.01,
                                      k_max=k_max))
    b = jax.tree.map(lambda a: a[:m, :k_max], _batches())
    _, metrics = fn(state, b, jnp.array([1, 3], jnp.int32),
                    jnp.array([0.5, 0.5], jnp.float32))
    jax.effects_barrier()
    assert int(metrics["client_steps"]) == 4
    assert len(calls) == 4


@pytest.mark.parametrize("cell,schedule,per_chunk", [
    ("granite-fedagrac-kasync", [[3, 4], [7, 5], [1, 4], [3, 4]], 31),
    ("granite-fedagrac-ksync", [[4, 4]] * 4, 32),
])
def test_history_counts_the_client_steps_of_each_round(cell, schedule,
                                                       per_chunk):
    """The flat simulation records each round's client-steps in History:
    under the chip cells' K schedules (M = 2, 4-round chunks) a chunk
    computes 31 and 32 of them."""
    data, parts, params, _ = _lr_task()
    fed = FedConfig(algorithm="fedagrac", n_clients=2, lr=0.05,
                    calibration_rate=0.5, param_layout="flat")
    sim = FederatedSimulation(lr_loss, params, fed,
                              DeviceBatcher(data, parts[:2], 10),
                              k_schedule=np.array(schedule, np.int32))
    hist = sim.run(8, eval_every=4)
    assert hist.client_steps == [float(sum(r)) for r in schedule] * 2
    assert sum(hist.client_steps[:4]) == per_chunk


def _run_on_host_devices(code: str, devices: int = 4) -> str:
    """Run ``code`` in a fresh interpreter with ``devices`` CPU devices
    (XLA fixes the count at its first initialisation)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.parametrize("mesh_shape,steps", [
    (None, 13),          # no mesh: Σ K_i = 1 + 3 + 5 + 4
    ((4, 1), 4 * 5),     # client rows over 4 devices: M · k_max
    ((1, 4), 13),        # rows on every device, P split: clients in turn
])
def test_client_steps_follow_where_the_client_rows_live(mesh_shape, steps):
    """The flat round runs Σ K_i client-steps where each client's row sits
    whole on a device and the masked M · k_max scan where the mesh spreads
    the rows over its data axes (launch/train.py's round); both reach the
    single-device round's state."""
    code = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import dist
from repro.configs.base import FedConfig
from repro.core import flat, rounds
from repro.core.fedopt import get_algorithm
from repro.launch import train as train_lib
from repro.launch.mesh import make_local_mesh, mesh_rules
from repro.models.simple import quad_loss

MESH = %r
m, d, k_max = 4, 6, 5
algo = get_algorithm("fedagrac", FedConfig(algorithm="fedagrac", n_clients=m,
                                           lr=0.01, calibration_rate=0.5))
params = {"x": jnp.zeros((d,), jnp.float32)}
spec = flat.make_flat_spec(params)
rng = np.random.default_rng(0)
b = {"A": jnp.asarray(rng.normal(size=(m, k_max, d, d)), jnp.float32),
     "b": jnp.asarray(rng.normal(size=(m, k_max, d)), jnp.float32),
     "c0": jnp.zeros((m, k_max))}
ks = jnp.array([1, 3, 5, 4], jnp.int32)
w = jnp.full((m,), 0.25, jnp.float32)
state = flat.flatten_state(spec, rounds.init_state(params, m, algo))
one = jax.jit(flat.make_flat_round(spec, quad_loss, algo, lr=0.01,
                                   k_max=k_max))
ref_state, ref_metrics = one(state, b, ks, w)
ref_state, ref_metrics = one(ref_state, b, ks, w)
if MESH is None:
    got_state, metrics = ref_state, ref_metrics
else:
    mesh = make_local_mesh(*MESH)
    fn = dist.traced_under(mesh, mesh_rules(mesh, kind="train"),
                           flat.make_flat_round(
        spec, quad_loss, algo, lr=0.01, k_max=k_max,
        param_constraint=train_lib.make_flat_param_constraint(mesh, spec.p)))
    with jax.set_mesh(mesh):
        fn = jax.jit(fn)
        got_state, metrics = fn(state, b, ks, w)
        got_state, metrics = fn(got_state, b, ks, w)
    for k in ref_state:
        np.testing.assert_allclose(np.asarray(got_state[k], np.float32),
                                   np.asarray(ref_state[k], np.float32),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
print(int(metrics["client_steps"]))
""" % (mesh_shape,)
    assert int(_run_on_host_devices(code).split()[-1]) == steps
