"""Layered round engine: the four composable stages (DESIGN.md §2).

A federated round factors into four pure stages, each independently
pluggable and each reused verbatim by BOTH execution modes (synchronous
rounds in ``fed/simulation.py`` + ``launch/train.py``, and buffered
semi-asynchronous serving in ``fed/async_engine.py``):

1. **client update** (``make_client_update``) — the masked-K_i scan with
   λ-calibration; vmap over the client axis (optionally SPMD-mapped).  The
   anchor x̃ may be shared (synchronous: every client starts the round at
   the same global model) or per-client (asynchronous: each client starts
   from the — possibly stale — model version it was dispatched with).
2. **aggregation** (``AGGREGATORS`` / ``BUFFERED_AGGREGATORS``) — weighted
   average or FedNova-normalized; the buffered variants operate on
   pseudo-deltas δᵢ = xᵢ − anchorᵢ so stale anchors aggregate correctly.
3. **orientation** (``orientation_transmit`` + ``SELECTORS``) — recover the
   averaged local gradient from the parameter delta (paper §4.2) and select
   what each client transmits toward the next global ν (avg / first /
   fedagrac / reverse), with optional int8 fake-quantization.
4. **server optimizer** (``SERVER_OPTIMIZERS``) — FedOpt step on the round
   pseudo-gradient (sgd / momentum / adam; Reddi et al. 2021).

``Algorithm`` (core/fedopt.py) names a composition — ``algo.aggregator``,
``algo.selector``, ``algo.server_opt`` index these registries; there are no
per-algorithm branches below, only per-stage ones.  λ is an ARGUMENT of the
built round function (traced), so λ-schedules never retrace.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import compress
from repro.core import robust as robust_mod
from repro.core.fedopt import Algorithm
from repro.core.tree_util import expand, tree_wsum, tree_zeros

PyTree = Any


def _flat_bridge(spec):
    """Tree-layout access to the flat view table (for the compression
    stage, DESIGN.md §14): ravel/unravel closures over ``spec``.  Imported
    at build time — core.flat imports this module, so the dependency must
    stay function-level."""
    from repro.core import flat as _flat
    return (lambda t: _flat.ravel(spec, t),
            lambda t: _flat.ravel(spec, t, client_dims=1),
            lambda a: _flat.unravel(spec, a),
            lambda a: _flat.unravel(spec, a, client_dims=1))


def _typed_scale(lam, c: jax.Array) -> jax.Array:
    """λ·c in c's dtype.  A traced λ arrives as a STRONG f32 scalar and
    would otherwise promote the whole scan carry (bf16 round state) to f32;
    a baked python-float λ is weak-typed and multiplies in c's dtype, which
    this reproduces exactly (f32 leaves: bit-identical either way)."""
    if isinstance(lam, jax.Array) and lam.dtype != c.dtype:
        return lam.astype(c.dtype) * c
    return lam * c


# ---------------------------------------------------------------------------
# stage 1: client update
# ---------------------------------------------------------------------------

def make_client_update(loss_fn: Callable[[PyTree, PyTree], jax.Array],
                       algo: Algorithm, *, lr: float, k_max: int,
                       track_nu: str = "delta",
                       spmd_axis_name=None,
                       per_client_anchor: bool = False):
    """Build the vmapped per-client local-SGD stage.

    Returns ``f(anchor, c_all, batches, k_steps, lam) ->
    (x_i, g0_i, acc_i, loss0)`` where ``anchor`` is the start model — shared
    (synchronous) or stacked per client (``per_client_anchor=True``, the
    buffered-async path where client *i* starts from its dispatch-time model
    version).  Step asynchronism is masking: the scan runs ``k_max`` steps
    and client *i* applies updates only for ``k < K_i`` (DESIGN.md §3);
    ``K_i`` and ``lam`` are traced, so heterogeneity and λ-schedules change
    per round without recompiles.

    The same mask is the **effective-steps mask** of partial-work recovery
    (fed/scenarios.py, DESIGN.md §12): a mid-round dropout passes its
    effective k′ < K_i as ``k_steps`` and this stage computes exactly the
    k′-step prefix of the client's trajectory — no separate abort path.
    ``K_i ≥ 1`` is a contract: downstream FedNova normalization and the
    ν̄⁽ⁱ⁾ recovery (``recover_avg_grad``) divide by K_i.
    """
    needs_first = algo.selector in ("fedagrac", "first", "reverse")
    grad_fn = jax.value_and_grad(loss_fn)

    @jax.named_scope("fed.client_update")
    def client_run(anchor, c_i, batch_i, K_i, lam):
        lam_c = (jax.tree.map(lambda c: _typed_scale(lam, c), c_i)
                 if algo.uses_nu else None)

        def step(carry, xs):
            k, batch_k = xs
            x, g0, nu_acc = carry
            loss, g = grad_fn(x, batch_k)
            if algo.prox_mu:
                g = jax.tree.map(lambda gg, xx, x0: gg + algo.prox_mu * (xx - x0),
                                 g, x, anchor)
            active = k < K_i
            with jax.named_scope("fed.local_step"):
                if algo.uses_nu:
                    upd = jax.tree.map(
                        lambda xx, gg, cc: xx - lr * (gg + cc), x, g, lam_c)
                else:
                    upd = jax.tree.map(lambda xx, gg: xx - lr * gg, x, g)
                x = jax.tree.map(
                    lambda old, new: jnp.where(active, new, old), x, upd)
            if needs_first:
                g0 = jax.tree.map(lambda a, gg: jnp.where(k == 0, gg, a),
                                  g0, g)
            if track_nu == "explicit" and algo.uses_nu:
                w = jnp.where(active, 1.0 / K_i.astype(jnp.float32), 0.0)
                nu_acc = jax.tree.map(lambda a, gg: a + w * gg, nu_acc, g)
            return (x, g0, nu_acc), loss

        g0_0 = tree_zeros(anchor) if needs_first else jnp.zeros(())
        acc_0 = (tree_zeros(anchor)
                 if (track_nu == "explicit" and algo.uses_nu)
                 else jnp.zeros(()))
        (x, g0, nu_acc), losses = jax.lax.scan(
            step, (anchor, g0_0, acc_0),
            (jnp.arange(k_max), batch_i))
        return x, g0, nu_acc, losses[0]

    anchor_axis = 0 if per_client_anchor else None
    return jax.vmap(client_run, in_axes=(anchor_axis, 0, 0, 0, None),
                    spmd_axis_name=spmd_axis_name)


def zero_corrections(params: PyTree, m: int) -> PyTree:
    """Zero-size per-client correction placeholder for algorithms without ν
    — keeps the client-update vmap signature uniform."""
    return jax.tree.map(
        lambda a: jnp.zeros((m,) + (0,) * a.ndim, a.dtype), params)


# ---------------------------------------------------------------------------
# stage 2: aggregation
# ---------------------------------------------------------------------------

@jax.named_scope("fed.aggregate")
def aggregate_mean(params0: PyTree, x_i: PyTree, kf: jax.Array,
                   weights: jax.Array, kbar: jax.Array) -> PyTree:
    """Plain weighted average  Σ ω_i x⁽ⁱ⁾."""
    return tree_wsum(weights, x_i)


@jax.named_scope("fed.aggregate")
def aggregate_fednova(params0: PyTree, x_i: PyTree, kf: jax.Array,
                      weights: jax.Array, kbar: jax.Array) -> PyTree:
    """FedNova:  x̃ + K̄ Σ ω_i (x⁽ⁱ⁾ − x̃)/K_i  (Wang et al. 2020)."""
    deltas = jax.tree.map(
        lambda xi, p0: (xi.astype(jnp.float32) - p0[None])
        / expand(kf, xi), x_i, params0)
    return jax.tree.map(
        lambda p0, d: (p0 + kbar * jnp.einsum("m,m...->...", weights,
                                              d)).astype(p0.dtype),
        params0, deltas)


AGGREGATORS: dict[str, Callable] = {
    "mean": aggregate_mean,
    "fednova": aggregate_fednova,
}


@jax.named_scope("fed.aggregate")
def buffered_mean(params: PyTree, anchor_i: PyTree, x_i: PyTree,
                  kf: jax.Array, sweights: jax.Array,
                  kbar: jax.Array) -> PyTree:
    """Buffered pseudo-delta average:  x + Σ_{i∈B} w̃_i (x⁽ⁱ⁾ − anchorᵢ).

    ``sweights`` = ω_i·s(τ_i) are the staleness-discounted client weights
    (NOT renormalized): with buffer = M and zero staleness Σ w̃ = 1 and this
    reduces exactly to the synchronous weighted average."""
    deltas = jax.tree.map(
        lambda xi, ai: xi.astype(jnp.float32) - ai.astype(jnp.float32),
        x_i, anchor_i)
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32)
                      + jnp.einsum("m,m...->...", sweights, d)
                      ).astype(p.dtype), params, deltas)


@jax.named_scope("fed.aggregate")
def buffered_fednova(params: PyTree, anchor_i: PyTree, x_i: PyTree,
                     kf: jax.Array, sweights: jax.Array,
                     kbar: jax.Array) -> PyTree:
    """Buffered FedNova:  x + K̄_B Σ_{i∈B} w̃_i (x⁽ⁱ⁾ − anchorᵢ)/K_i with
    K̄_B the discount-weighted mean steps over the buffer."""
    deltas = jax.tree.map(
        lambda xi, ai: (xi.astype(jnp.float32) - ai.astype(jnp.float32))
        / expand(kf, xi), x_i, anchor_i)
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32)
                      + kbar * jnp.einsum("m,m...->...", sweights, d)
                      ).astype(p.dtype), params, deltas)


BUFFERED_AGGREGATORS: dict[str, Callable] = {
    "mean": buffered_mean,
    "fednova": buffered_fednova,
}


def delivered_weights(weights: jax.Array, k_eff: jax.Array,
                      k_sched: jax.Array) -> jax.Array:
    """Partial-work recovery weight rule (fed/scenarios.py, DESIGN.md §12):
    a mid-round dropout delivering k′ < K completed steps keeps its
    (FedNova-normalized) per-step direction but carries only the fraction
    of mass it earned, w̃ ← w̃ · k′/K — deliberately NOT renormalized, so
    lost work is lost mass: the pseudo-delta step shrinks and the ν
    mass-mix keeps (1 − Σw̃) of the previous calibration direction.  Shared
    by the in-scan cohort hook (core/engine.py), its host mirror
    (fed/simulation.py) and the async engine's report weighting."""
    frac = (k_eff.astype(jnp.float32)
            / jnp.maximum(k_sched.astype(jnp.float32), 1.0))
    return weights * frac


@jax.named_scope("fed.orientation")
def nu_mass_mix(nu: PyTree, contrib: PyTree, mass: jax.Array) -> PyTree:
    """ν ← (1 − ρ) ν + (ρ/Σw̃)·Σ w̃ transmitᵢ with ρ = min(Σw̃, 1): keep ρ
    of the new signal, renormalized — convex even when duplicate reporters
    (or Horvitz–Thompson weights) push Σw̃ past 1; for Σw̃ ≤ 1 this is
    exactly (1 − Σw̃)·ν + Σ w̃ transmitᵢ, so the synchronous reduction
    (Σw̃ = 1) is untouched.  Shared by the buffered-async engine and the
    cohort round (DESIGN.md §5, §10)."""
    rho = jnp.minimum(mass, 1.0)
    return jax.tree.map(
        lambda n, c: ((1.0 - rho) * n.astype(jnp.float32)
                      + (rho / mass) * c.astype(jnp.float32)
                      ).astype(n.dtype), nu, contrib)


@jax.named_scope("fed.orientation")
def scatter_nu_rows(nu_i: PyTree, new_nu: PyTree, avg_g: PyTree,
                    ids: jax.Array, nu_decay: float = 0.0) -> PyTree:
    """Write the participants' fresh ν̄⁽ⁱ⁾ rows into the population-sized
    state; non-participants' stale rows decay toward the new global ν at
    ``nu_decay`` per update — their correction c⁽ⁱ⁾ = ν − ν⁽ⁱ⁾ → 0, so cold
    clients degrade gracefully to plain local SGD (0 = frozen rows).  Decay
    first, scatter second: the overwrite keeps participants exact.  Shared
    by the cohort round and the buffered-async engine (DESIGN.md §10)."""
    def one(nui, nu, g):
        if nu_decay:
            nui = (nui.astype(jnp.float32)
                   + nu_decay * (nu[None].astype(jnp.float32)
                                 - nui.astype(jnp.float32)))
        return nui.at[ids].set(g.astype(nui.dtype)).astype(g.dtype)
    return jax.tree.map(one, nu_i, new_nu, avg_g)


# ---------------------------------------------------------------------------
# stage 3: orientation (transmit selection)
# ---------------------------------------------------------------------------

def quantize_int8(tree: PyTree) -> PyTree:
    """Per-client-per-leaf symmetric int8 fake-quantization of the
    transmitted orientation (beyond-paper comms ablation): scale =
    amax/127 over each client's tensor, round-to-nearest.  Halves the ν
    upload vs bf16; EXPERIMENTS.md reports the accuracy cost."""
    def q(a):
        red = tuple(range(1, a.ndim))
        scale = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=red,
                        keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-12)
        return (jnp.round(a.astype(jnp.float32) / scale) * scale
                ).astype(a.dtype)
    return jax.tree.map(q, tree)


def _select_avg(g0_i, avg_g, fast):
    return avg_g


def _select_first(g0_i, avg_g, fast):
    return g0_i


def _select_fedagrac(g0_i, avg_g, fast):
    """Fast clients (K_i > K̄) send the first stochastic gradient, slow
    clients the averaged gradient (paper §4.2)."""
    return jax.tree.map(
        lambda f, a: jnp.where(expand(fast, a), f, a), g0_i, avg_g)


def _select_reverse(g0_i, avg_g, fast):
    return jax.tree.map(
        lambda f, a: jnp.where(expand(fast, a), a, f), g0_i, avg_g)


SELECTORS: dict[str, Callable] = {
    "avg": _select_avg,
    "first": _select_first,
    "fedagrac": _select_fedagrac,
    "reverse": _select_reverse,
}


@jax.named_scope("fed.orientation")
def corrections(nu: PyTree, nu_i: PyTree,
                ids: Optional[jax.Array] = None) -> PyTree:
    """The clients' calibration c⁽ⁱ⁾ = ν − ν⁽ⁱ⁾, one row per client;
    ``ids`` picks the cohort's rows of a population-sized ν⁽ⁱ⁾."""
    def one(n, ni):
        if not ni.ndim:
            return n - ni
        return n[None] - (ni if ids is None else ni[ids])
    return jax.tree.map(one, nu, nu_i)


@jax.named_scope("fed.orientation")
def transmit_mix(weights: jax.Array, transmit: PyTree) -> PyTree:
    """Σ_i w_i · transmit⁽ⁱ⁾: the new global ν of a synchronous round, or
    what a cohort or a buffer contributes to it."""
    return tree_wsum(weights, transmit)


def fast_mask(kf: jax.Array, kbar: jax.Array) -> jax.Array:
    """K_i > K̄ with a tie tolerance: K_i are integers (spacing 1) but K̄ is
    an f32 dot whose summation ORDER can leave it 1 ulp under an exact tie —
    without the epsilon, a client-permutation flips every tied client from
    "slow" (send averaged) to "fast" (send first), found by the
    permutation-invariance property test."""
    return kf > kbar + 1e-4 * jnp.maximum(kbar, 1.0)            # (M,)


def recover_avg_grad(params0: PyTree, x_i: PyTree, c_all: PyTree,
                     kf: jax.Array, lr: float, lam,
                     anchor_i: Optional[PyTree] = None) -> PyTree:
    """Delta recovery of the averaged local gradient (paper §4.2):
    ν̄⁽ⁱ⁾ = (x̃ − x⁽ⁱ⁾_{K_i}) / (η K_i) − λ c⁽ⁱ⁾ — the single-buffer trick
    that keeps big-model round state ≤ 3×params.  ``anchor_i`` (stacked)
    replaces the shared x̃ on the buffered-async path."""
    if anchor_i is None:
        return jax.tree.map(
            lambda x0, xi, ci: ((x0[None].astype(jnp.float32)
                                 - xi.astype(jnp.float32))
                                / (lr * expand(kf, xi))
                                - lam * ci.astype(jnp.float32)
                                ).astype(x0.dtype),
            params0, x_i, c_all)
    return jax.tree.map(
        lambda a0, xi, ci: ((a0.astype(jnp.float32)
                             - xi.astype(jnp.float32))
                            / (lr * expand(kf, xi))
                            - lam * ci.astype(jnp.float32)
                            ).astype(a0.dtype),
        anchor_i, x_i, c_all)


@jax.named_scope("fed.orientation")
def orientation_transmit(algo: Algorithm, params0: PyTree, x_i: PyTree,
                         g0_i: PyTree, acc_i: PyTree, c_all: PyTree,
                         kf: jax.Array, kbar: jax.Array, lr: float, lam, *,
                         track_nu: str = "delta",
                         quantize_transmit: bool = False,
                         anchor_i: Optional[PyTree] = None):
    """Per-client (transmit, avg_g): what flows into the next global ν, and
    the local reference ν⁽ⁱ⁾ (Alg. 1 line 11 — always the averaged grad)."""
    if track_nu == "explicit":
        avg_g = acc_i
    else:
        avg_g = recover_avg_grad(params0, x_i, c_all, kf, lr, lam,
                                 anchor_i=anchor_i)
    transmit = SELECTORS[algo.selector](g0_i, avg_g, fast_mask(kf, kbar))
    if quantize_transmit:
        transmit = quantize_int8(transmit)
    return transmit, avg_g


# ---------------------------------------------------------------------------
# stage 4: server optimizer (FedOpt, Reddi et al. 2021)
# ---------------------------------------------------------------------------

def _server_sgd(algo, state, params0, agg, delta, new_state):
    """server_opt="sgd", server_lr=1 reproduces plain averaging exactly."""
    lr = algo.server_lr
    if lr == 1.0:
        return agg
    return jax.tree.map(
        lambda p, d: (p.astype(jnp.float32) + lr * d).astype(p.dtype),
        params0, delta)


def _server_momentum(algo, state, params0, agg, delta, new_state):
    """FedAvgM."""
    lr, b1 = algo.server_lr, algo.server_beta1
    m = jax.tree.map(lambda mm, d: b1 * mm.astype(jnp.float32) + d,
                     state["server_m"], delta)
    new_state["server_m"] = jax.tree.map(
        lambda mm, p: mm.astype(p.dtype), m, params0)
    return jax.tree.map(
        lambda p, mm: (p.astype(jnp.float32) + lr * mm).astype(p.dtype),
        params0, m)


def _server_adam(algo, state, params0, agg, delta, new_state):
    """FedAdam."""
    lr, b1 = algo.server_lr, algo.server_beta1
    b2, eps = 0.999, 1e-8
    t = state["round"].astype(jnp.float32) + 1.0
    m = jax.tree.map(
        lambda mm, d: b1 * mm.astype(jnp.float32) + (1 - b1) * d,
        state["server_m"], delta)
    v = jax.tree.map(
        lambda vv, d: b2 * vv.astype(jnp.float32) + (1 - b2) * d * d,
        state["server_v"], delta)
    new_state["server_m"] = jax.tree.map(
        lambda mm, p: mm.astype(p.dtype), m, params0)
    new_state["server_v"] = jax.tree.map(
        lambda vv, p: vv.astype(p.dtype), v, params0)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    return jax.tree.map(
        lambda p, mm, vv: (p.astype(jnp.float32)
                           + lr * (mm / bc1)
                           / (jnp.sqrt(vv / bc2) + eps)).astype(p.dtype),
        params0, m, v)


SERVER_OPTIMIZERS: dict[str, Callable] = {
    "sgd": _server_sgd,
    "momentum": _server_momentum,
    "adam": _server_adam,
}


@jax.named_scope("fed.aggregate")
def server_update(algo: Algorithm, state: dict, params0: PyTree,
                  agg: PyTree, new_state: dict) -> PyTree:
    """FedOpt server step on the round pseudo-gradient Δ = agg − x̃_t."""
    if algo.server_opt not in SERVER_OPTIMIZERS:
        raise ValueError(algo.server_opt)
    delta = jax.tree.map(
        lambda a, p: a.astype(jnp.float32) - p.astype(jnp.float32),
        agg, params0)
    return SERVER_OPTIMIZERS[algo.server_opt](algo, state, params0, agg,
                                              delta, new_state)


# ---------------------------------------------------------------------------
# composition: the synchronous round
# ---------------------------------------------------------------------------

def make_layered_round(loss_fn: Callable[[PyTree, PyTree], jax.Array],
                       algo: Algorithm, *, lr: float, k_max: int,
                       track_nu: str = "delta",
                       spmd_axis_name=None,
                       quantize_transmit: bool = False,
                       compression=None, spec=None, robust=None,
                       attack=None,
                       param_constraint: Optional[Callable[[PyTree, int],
                                                           PyTree]] = None):
    """Compose the four stages into the synchronous round function.

    ``round_fn(state, batches, k_steps, weights, lam=None) ->
    (state, metrics)``.  ``lam`` may be a traced scalar (λ-schedules reuse
    one compiled round — see fed/simulation.py); ``None`` bakes ``algo.lam``
    in as a compile-time constant.

    ``compression`` (core/compress.py, DESIGN.md §14) inserts the wire
    stage at trace time: the server→client broadcast is compressed before
    dispatch (clients anchor on — and the server aggregates against —
    what they actually received), the client→server delta and ν transmit
    are compressed with per-client error feedback, all through the flat
    view table of ``spec``.  None (or an all-"none" config) bakes the
    literally unchanged round — the golden bit-identity contract.

    ``attack`` (a payload-corrupting Scenario, fed/scenarios.py) and
    ``robust`` (a RobustConfig, core/robust.py, DESIGN.md §16) bracket the
    same wire boundary: corruption applies to what each client puts on the
    wire (delta + ν transmit, before uplink compression), the defense to
    what the server takes off it (after decompression, before the
    aggregators and the ν mix).  Both are trace-time gated like
    compression: None bakes the identical round.
    """
    client_update = make_client_update(
        loss_fn, algo, lr=lr, k_max=k_max, track_nu=track_nu,
        spmd_axis_name=spmd_axis_name)
    aggregate = AGGREGATORS[algo.aggregator]
    cs = compress.build_stages(compression, spec, algo.uses_nu)
    rb = robust_mod.build_round_robust(robust, spec, algo.uses_nu)
    atk = attack if (attack is not None
                     and attack.corrupts_payload) else None
    if atk is not None and spec is None:
        raise ValueError("payload-corruption scenarios require a FlatSpec "
                         "— the engines build one on both param layouts")
    wire = cs is not None or rb is not None or atk is not None
    if wire:
        _rv, _rvr, _ur, _urr = _flat_bridge(spec)
        n_true = spec.n
    down_on = cs is not None and cs.down is not None
    up_on = cs is not None and cs.up is not None

    def constrain(tree, client_dims):
        if param_constraint is None:
            return tree
        return param_constraint(tree, client_dims)

    def round_fn(state: dict, batches: PyTree, k_steps: jax.Array,
                 weights: jax.Array, lam=None):
        if lam is None:
            lam = algo.lam
        params0 = state["params"]
        m = k_steps.shape[0]
        kbar = jnp.dot(weights, k_steps.astype(jnp.float32))
        new_state = dict(state)

        # -- downlink: clients start from the compressed broadcast --------
        if down_on:
            bc_flat = cs.down(_rv(params0), state, new_state)
            anchor = _ur(bc_flat)
            nu_bc = (_ur(cs.down_nu(_rv(state["nu"]), state, new_state))
                     if algo.uses_nu else None)
        else:
            anchor = params0
            nu_bc = state["nu"] if algo.uses_nu else None

        if algo.uses_nu:
            c_all = corrections(nu_bc, state["nu_i"])
        else:
            c_all = zero_corrections(params0, m)

        x_i, g0_i, acc_i, loss0 = client_update(anchor, c_all, batches,
                                                k_steps, lam)
        x_i = constrain(x_i, 1)
        kf = k_steps.astype(jnp.float32)

        # -- uplink: the server sees x̂ᵢ = anchor + C(Δᵢ + eᵢ) -------------
        w_agg = weights
        if wire:
            a_flat = bc_flat if down_on else _rv(params0)
            d = _rvr(x_i) - a_flat[None]
            if atk is not None:
                d = atk.corrupt_delta(state["round"], d, n_true,
                                      ids=jnp.arange(m, dtype=jnp.int32))
            if up_on:
                d = cs.up(d, state, new_state)
            if rb is not None:
                d, w_agg, qcount = rb.model(d, weights, state, new_state,
                                            state["round"],
                                            jnp.arange(m, dtype=jnp.int32))
            x_srv = _urr(a_flat[None] + d)
        else:
            x_srv = x_i

        agg = aggregate(anchor, x_srv, kf, w_agg, kbar)
        if down_on:
            # re-base onto the true master: the round pseudo-gradient is
            # measured against the broadcast the clients actually anchored
            # on, then applied to the uncompressed server model
            agg = jax.tree.map(
                lambda p0, a, an: (p0.astype(jnp.float32)
                                   + a.astype(jnp.float32)
                                   - an.astype(jnp.float32)
                                   ).astype(p0.dtype), params0, agg, anchor)
        new_params = server_update(algo, state, params0, agg, new_state)
        new_params = constrain(new_params, 0)
        new_state["params"] = new_params
        new_state["round"] = state["round"] + 1

        if algo.uses_nu:
            # avg_g (the client-local reference ν⁽ⁱ⁾) uses the TRUE local
            # iterate — it never crosses the wire; the transmit does, so
            # it alone is compressed (with its own error accumulator)
            transmit, avg_g = orientation_transmit(
                algo, anchor, x_i, g0_i, acc_i, c_all, kf, kbar, lr, lam,
                track_nu=track_nu, quantize_transmit=quantize_transmit)
            w_nu = weights
            if wire and (up_on or atk is not None or rb is not None):
                t_rows = _rvr(transmit)
                if atk is not None:
                    t_rows = atk.corrupt_nu(state["round"], t_rows, n_true,
                                            ids=jnp.arange(m,
                                                           dtype=jnp.int32))
                if up_on:
                    t_rows = cs.up_nu(t_rows, state, new_state)
                if rb is not None:
                    t_rows, w_nu = rb.nu(t_rows, weights, state,
                                         state["round"],
                                         jnp.arange(m, dtype=jnp.int32))
                transmit = _urr(t_rows)
            new_state["nu"] = constrain(transmit_mix(w_nu, transmit), 0)
            # Line 11: the *local* reference ν⁽ⁱ⁾ is always the averaged grad
            new_state["nu_i"] = constrain(avg_g, 1)

        if rb is not None:
            # final non-finite guard: a defended run never writes NaN into
            # the master (or the ν state calibration broadcasts from)
            new_state["params"] = rb.guard(new_state["params"], params0)
            if algo.uses_nu:
                new_state["nu"] = rb.guard(new_state["nu"], state["nu"])
                new_state["nu_i"] = rb.guard(new_state["nu_i"],
                                             state["nu_i"])

        metrics = {"loss": jnp.dot(weights, loss0), "kbar": kbar}
        if rb is not None:
            metrics["quarantined"] = qcount
        return new_state, metrics

    return round_fn


# ---------------------------------------------------------------------------
# composition: the cohort round (partial participation, DESIGN.md §10)
# ---------------------------------------------------------------------------

def make_cohort_round(loss_fn: Callable[[PyTree, PyTree], jax.Array],
                      algo: Algorithm, *, lr: float, k_max: int,
                      nu_decay: float = 0.0,
                      track_nu: str = "delta",
                      spmd_axis_name=None,
                      quantize_transmit: bool = False,
                      compression=None, spec=None, robust=None,
                      attack=None,
                      param_constraint: Optional[Callable[[PyTree, int],
                                                          PyTree]] = None):
    """The synchronous round over a sampled cohort of C ≤ M clients.

    ``round_fn(state, batches, cohort, k_steps, cweights, lam=None)`` —
    ``cohort`` is the (C,) int32 client-id draw (fed/population.py),
    ``batches``/``k_steps`` are cohort-indexed (leading C), ``cweights`` the
    renormalized w̃ (``ClientPopulation.cohort_weights``).  The server state
    stays population-sized: the cohort's ν⁽ⁱ⁾ rows are gathered on device,
    the k-step scan runs over the C axis, and updated rows scatter back.

    Aggregation is the pseudo-delta (Horvitz–Thompson) form
    ``x ← serveropt(x, Σ w̃_i (x⁽ⁱ⁾ − x))`` so Σ w̃ ≠ 1 stays unbiased, and
    ν mass-mixes exactly like the buffered-async engine:
    ``ν ← (1 − ρ) ν + (ρ/Σw̃) Σ w̃ transmitᵢ`` with ρ = min(Σw̃, 1) — at
    Σw̃ = 1 this is the synchronous update.  Non-participants' stale ν⁽ⁱ⁾
    rows decay toward the new global ν at rate ``nu_decay`` per round (their
    correction c⁽ⁱ⁾ = ν − ν⁽ⁱ⁾ → 0, i.e. cold clients degrade gracefully to
    plain local SGD); ``nu_decay=0`` keeps stale rows frozen.
    """
    client_update = make_client_update(
        loss_fn, algo, lr=lr, k_max=k_max, track_nu=track_nu,
        spmd_axis_name=spmd_axis_name)
    aggregate = BUFFERED_AGGREGATORS[algo.aggregator]
    cs = compress.build_stages(compression, spec, algo.uses_nu)
    rb = robust_mod.build_round_robust(robust, spec, algo.uses_nu)
    atk = attack if (attack is not None
                     and attack.corrupts_payload) else None
    if atk is not None and spec is None:
        raise ValueError("payload-corruption scenarios require a FlatSpec "
                         "— the engines build one on both param layouts")
    wire = cs is not None or rb is not None or atk is not None
    if wire:
        _rv, _rvr, _ur, _urr = _flat_bridge(spec)
        n_true = spec.n
    down_on = cs is not None and cs.down is not None
    up_on = cs is not None and cs.up is not None

    def constrain(tree, client_dims):
        if param_constraint is None:
            return tree
        return param_constraint(tree, client_dims)

    def round_fn(state: dict, batches: PyTree, cohort: jax.Array,
                 k_steps: jax.Array, cweights: jax.Array, lam=None):
        if lam is None:
            lam = algo.lam
        params0 = state["params"]
        c = cohort.shape[0]
        kf = k_steps.astype(jnp.float32)
        mass = jnp.sum(cweights)
        kbar = jnp.dot(cweights, kf) / mass          # cohort-weighted K̄
        new_state = dict(state)

        if down_on:
            bc_flat = cs.down(_rv(params0), state, new_state)
            anchor = _ur(bc_flat)
            nu_bc = (_ur(cs.down_nu(_rv(state["nu"]), state, new_state))
                     if algo.uses_nu else None)
        else:
            anchor = params0
            nu_bc = state["nu"] if algo.uses_nu else None

        if algo.uses_nu:
            # gather only the cohort's correction rows: compute is O(C)
            c_all = corrections(nu_bc, state["nu_i"], cohort)
        else:
            c_all = zero_corrections(params0, c)

        x_i, g0_i, acc_i, loss0 = client_update(anchor, c_all, batches,
                                                k_steps, lam)
        x_i = constrain(x_i, 1)

        # uplink compression: error-feedback rows gathered/scattered at
        # the cohort ids only — absentees' accumulators stay untouched
        w_agg = cweights
        if wire:
            a_flat = bc_flat if down_on else _rv(params0)
            d = _rvr(x_i) - a_flat[None]
            if atk is not None:
                d = atk.corrupt_delta(state["round"], d, n_true, ids=cohort)
            if up_on:
                d = cs.up(d, state, new_state, ids=cohort)
            if rb is not None:
                d, w_agg, qcount = rb.model(d, cweights, state, new_state,
                                            state["round"], cohort)
            x_srv = _urr(a_flat[None] + d)
        else:
            x_srv = x_i

        # pseudo-delta aggregation (unbiased under Σ w̃ ≠ 1): the buffered
        # aggregators with the shared broadcast as every client's anchor —
        # base = the TRUE master, deltas measured vs what clients received
        anchor1 = jax.tree.map(lambda p: p[None], anchor)
        agg = aggregate(params0, anchor1, x_srv, kf, w_agg, kbar)

        new_params = server_update(algo, state, params0, agg, new_state)
        new_params = constrain(new_params, 0)
        new_state["params"] = new_params
        new_state["round"] = state["round"] + 1

        if algo.uses_nu:
            transmit, avg_g = orientation_transmit(
                algo, anchor, x_i, g0_i, acc_i, c_all, kf, kbar, lr, lam,
                track_nu=track_nu, quantize_transmit=quantize_transmit)
            w_nu = cweights
            if wire and (up_on or atk is not None or rb is not None):
                t_rows = _rvr(transmit)
                if atk is not None:
                    t_rows = atk.corrupt_nu(state["round"], t_rows, n_true,
                                            ids=cohort)
                if up_on:
                    t_rows = cs.up_nu(t_rows, state, new_state, ids=cohort)
                if rb is not None:
                    # ν renorm preserves Σw̃ so ρ = min(mass, 1) below keeps
                    # its planned value; if the whole cohort is dropped,
                    # contrib = 0 and ν decays by (1 − ρ) toward zero — a
                    # safe calibration fade, never a poisoned mix
                    t_rows, w_nu = rb.nu(t_rows, cweights, state,
                                         state["round"], cohort)
                transmit = _urr(t_rows)
            contrib = transmit_mix(w_nu, transmit)
            new_nu = nu_mass_mix(state["nu"], contrib, mass)
            new_state["nu"] = constrain(new_nu, 0)
            new_state["nu_i"] = constrain(
                scatter_nu_rows(state["nu_i"], new_nu, avg_g, cohort,
                                nu_decay), 1)

        if rb is not None:
            new_state["params"] = rb.guard(new_state["params"], params0)
            if algo.uses_nu:
                new_state["nu"] = rb.guard(new_state["nu"], state["nu"])
                new_state["nu_i"] = rb.guard(new_state["nu_i"],
                                             state["nu_i"])

        metrics = {"loss": jnp.dot(cweights, loss0) / mass, "kbar": kbar,
                   "mass": mass}
        if rb is not None:
            metrics["quarantined"] = qcount
        return new_state, metrics

    return round_fn
