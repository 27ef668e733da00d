"""Personalized serving: golden pin vs the plain engine, per-client view
resolution, hot-swap invariants, load generation, launch lowering."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, reduced
from repro.configs.registry import get_arch
from repro.core import flat
from repro.models import model as M
from repro.serving import (LoadGen, PersonalizedServeEngine, Request,
                           ServeEngine, lowrank_factors, make_personalizer,
                           make_snapshot, replay)
from tests.test_serving_engine import reference_generate


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("llama3-8b"), n_layers=2, d_model=128)
    cfg = dataclasses.replace(cfg, vocab=256)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    spec = flat.make_flat_spec(params)
    base = flat.ravel(spec, params)
    return cfg, params, spec, base


def _requests(vocab, shapes, seed=0, clients=None):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(1, vocab, size=n).astype(np.int32),
                    max_new_tokens=m,
                    client_id=clients[i] if clients else i % 3)
            for i, (n, m) in enumerate(shapes)]


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(dataclasses.replace(r))
    return {c.uid: c for c in eng.run()}


SHAPES = [(5, 6), (16, 4), (9, 8), (12, 3)]


def _nu_snapshot(spec, base, m=3, version=0, seed=1):
    nu = 1e-3 * jax.random.normal(jax.random.PRNGKey(seed), (spec.p,))
    nu_i = nu[None] + 1e-2 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (m, spec.p))
    return make_snapshot(version, base, nu=nu, nu_i=nu_i)


# -- golden pin ---------------------------------------------------------------


def test_none_matches_plain_engine_greedy(setup):
    """personalizer="none" serves bit-identical completions to ServeEngine
    on the same stream — the shared path runs the identical jaxpr on the
    materialized flat view (acceptance criterion)."""
    cfg, params, spec, base = setup
    reqs = _requests(cfg.vocab, SHAPES)
    done0 = _serve(ServeEngine(cfg, params, slots=2, max_len=128,
                               prefill_buckets=(8, 16)), reqs)
    eng = PersonalizedServeEngine(cfg, spec, make_snapshot(0, base),
                                  personalizer="none", slots=2,
                                  max_len=128, prefill_buckets=(8, 16))
    done1 = _serve(eng, reqs)
    assert {u: c.tokens for u, c in done0.items()} \
        == {u: c.tokens for u, c in done1.items()}


def test_none_matches_plain_engine_sampled(setup):
    """Same pin under a key-USING sampler: the per-(uid, step) keys flow
    identically through both engines."""
    cfg, params, spec, base = setup
    sampler = lambda logits, key: jax.random.categorical(key, logits)
    reqs = _requests(cfg.vocab, SHAPES, seed=3)
    done0 = _serve(ServeEngine(cfg, params, slots=2, max_len=128,
                               prefill_buckets=(8, 16), sampler=sampler),
                   reqs)
    done1 = _serve(PersonalizedServeEngine(
        cfg, spec, make_snapshot(0, base), personalizer="none", slots=2,
        max_len=128, prefill_buckets=(8, 16), sampler=sampler), reqs)
    assert {u: c.tokens for u, c in done0.items()} \
        == {u: c.tokens for u, c in done1.items()}


# -- view resolution ----------------------------------------------------------


def test_nu_rows_match_shifted_params_reference(setup):
    """Every completion under the "nu" personalizer equals per-request
    greedy decoding under params = unravel(base + scale·(ν⁽ⁱ⁾ − ν))."""
    cfg, params, spec, base = setup
    snap = _nu_snapshot(spec, base)
    reqs = _requests(cfg.vocab, SHAPES)
    done = _serve(PersonalizedServeEngine(
        cfg, spec, snap, personalizer="nu", scale=0.7, slots=2,
        max_len=128, prefill_buckets=(8, 16)), reqs)
    for r in reqs:
        shift = 0.7 * (snap["nu_i"][r.client_id] - snap["nu"])
        want = reference_generate(cfg, flat.unravel(spec, base + shift),
                                  r.prompt, r.max_new_tokens)
        assert done[r.uid].tokens == want, r.uid


def test_lowrank_exact_at_full_rank(setup):
    """lowrank_factors at r ≥ rank reconstructs the ν deltas exactly, so
    the lowrank engine serves the same tokens as the nu engine."""
    cfg, params, spec, base = setup
    snap = _nu_snapshot(spec, base)
    coeff, basis = lowrank_factors(snap["nu_i"], snap["nu"], r=3)
    assert coeff.shape == (3, 3) and basis.shape == (3, spec.p)
    np.testing.assert_allclose(
        np.asarray(coeff @ basis),
        np.asarray(snap["nu_i"] - snap["nu"][None]), atol=1e-4)
    lr = make_snapshot(0, base, coeff=coeff, basis=basis)
    reqs = _requests(cfg.vocab, SHAPES)
    done_nu = _serve(PersonalizedServeEngine(
        cfg, spec, snap, personalizer="nu", slots=2, max_len=128,
        prefill_buckets=(8, 16)), reqs)
    done_lr = _serve(PersonalizedServeEngine(
        cfg, spec, lr, personalizer="lowrank", slots=2, max_len=128,
        prefill_buckets=(8, 16)), reqs)
    assert {u: c.tokens for u, c in done_nu.items()} \
        == {u: c.tokens for u, c in done_lr.items()}


def test_cold_start_client_serves_base(setup):
    """A client_id outside the stored population resolves to the shared
    base — identical tokens to the plain engine."""
    cfg, params, spec, base = setup
    snap = _nu_snapshot(spec, base, m=3)
    req = _requests(cfg.vocab, [(7, 5)], clients=[999])[0]
    eng = PersonalizedServeEngine(cfg, spec, snap, personalizer="nu",
                                  slots=2, max_len=128,
                                  prefill_buckets=(8, 16))
    assert eng.resolve(999) is None
    done = _serve(eng, [req])
    want = reference_generate(cfg, params, req.prompt, req.max_new_tokens)
    assert done[req.uid].tokens == want


def test_mixed_clients_batch_together(setup):
    """Personalized and cold-start requests share the pool: each still
    matches its own single-request reference (row independence)."""
    cfg, params, spec, base = setup
    snap = _nu_snapshot(spec, base, m=2)
    reqs = _requests(cfg.vocab, SHAPES, clients=[0, 999, 1, 999])
    done = _serve(PersonalizedServeEngine(
        cfg, spec, snap, personalizer="nu", slots=4, max_len=128,
        prefill_buckets=(8, 16)), reqs)
    for r in reqs:
        if r.client_id < 2:
            shift = snap["nu_i"][r.client_id] - snap["nu"]
            p = flat.unravel(spec, base + shift)
        else:
            p = params
        assert done[r.uid].tokens == reference_generate(
            cfg, p, r.prompt, r.max_new_tokens), r.uid


# -- hot-swap -----------------------------------------------------------------


@pytest.mark.parametrize("kind", ["none", "nu"])
def test_hot_swap_preserves_in_flight(setup, kind):
    """A swap between ticks never changes tokens of requests admitted
    before it (acceptance criterion), on both the shared and row decode
    paths; completions record the version they were admitted under."""
    cfg, params, spec, base = setup
    base2 = base + 1e-2 * jax.random.normal(jax.random.PRNGKey(9),
                                            (spec.p,))
    mk = (lambda v, b: make_snapshot(v, b)) if kind == "none" \
        else (lambda v, b: _nu_snapshot(spec, b, version=v))
    pre = _requests(cfg.vocab, [(6, 12)], seed=1)[0]
    post = dataclasses.replace(_requests(cfg.vocab, [(6, 6)], seed=2)[0],
                               uid=1)

    def serve(swap):
        eng = PersonalizedServeEngine(cfg, spec, mk(3, base),
                                      personalizer=kind, slots=2,
                                      max_len=128, prefill_buckets=(8,))
        eng.submit(dataclasses.replace(pre))
        for _ in range(4):
            eng.step()
        if swap:
            eng.swap(mk(7, base2))
        eng.submit(dataclasses.replace(post))
        return {c.uid: c for c in eng.run()}

    plain, swapped = serve(False), serve(True)
    assert swapped[0].tokens == plain[0].tokens        # pre-swap invariant
    assert swapped[0].version == 3 and swapped[1].version == 7
    assert plain[1].version == 3
    # the post-swap request really sees the new base
    eng2 = PersonalizedServeEngine(cfg, spec, mk(7, base2),
                                   personalizer=kind, slots=2,
                                   max_len=128, prefill_buckets=(8,))
    eng2.submit(dataclasses.replace(post))
    assert swapped[1].tokens == eng2.run()[0].tokens


def test_swap_gc_drops_dead_versions(setup):
    cfg, params, spec, base = setup
    eng = PersonalizedServeEngine(cfg, spec, make_snapshot(1, base),
                                  personalizer="none", slots=2,
                                  max_len=128, prefill_buckets=(8,))
    done = _serve(eng, _requests(cfg.vocab, [(5, 3)]))
    assert done[0].version == 1
    eng.swap(make_snapshot(2, base))
    eng.swap(make_snapshot(5, base))
    assert sorted(eng._versions) == [5]


def test_registry_rejects_unknown_kind(setup):
    cfg, params, spec, base = setup
    with pytest.raises(ValueError, match="lowrank"):
        make_personalizer("bogus", make_snapshot(0, base))
    with pytest.raises(ValueError, match="nu_i"):
        make_personalizer("nu", make_snapshot(0, base))
    with pytest.raises(ValueError, match="coeff"):
        make_personalizer("lowrank", make_snapshot(0, base))


def test_lowrank_resolution_flat_in_population(setup):
    """The 100k-client representation: O(M·r + r·P) storage, O(r·P)
    resolve — structurally independent of M."""
    cfg, params, spec, base = setup
    m = 100_000
    coeff = 1e-3 * jax.random.normal(jax.random.PRNGKey(0), (m, 4))
    basis = jax.random.normal(jax.random.PRNGKey(1), (4, spec.p))
    fn = make_personalizer("lowrank",
                           make_snapshot(0, base, coeff=coeff, basis=basis))
    d = fn(m - 1)
    assert d.shape == (spec.p,)
    np.testing.assert_allclose(np.asarray(d),
                               np.asarray(coeff[m - 1] @ basis), rtol=1e-6)
    assert fn(m) is None and fn(-1) is None


# -- load generation ----------------------------------------------------------


def test_loadgen_deterministic_and_bounded():
    gen = LoadGen(population=50, rate=0.8, prompt_len=(3, 8),
                  max_new=(2, 6), vocab=99, seed=4, skew=2.0)
    a, b = gen.generate(40), gen.generate(40)
    assert len(a) == 40
    assert [t for t, _ in a] == [t for t, _ in b]
    assert all(t1 <= t2 for (t1, _), (t2, _) in zip(a, a[1:]))
    for (ta, ra), (tb, rb) in zip(a, b):
        assert ra.uid == rb.uid and ra.client_id == rb.client_id
        assert np.array_equal(ra.prompt, rb.prompt)
        assert 0 <= ra.client_id < 50
        assert 3 <= len(ra.prompt) <= 8 and 2 <= ra.max_new_tokens <= 6
        assert ra.prompt.min() >= 1 and ra.prompt.max() < 99
    # a different seed reshuffles the stream
    c = LoadGen(population=50, rate=0.8, prompt_len=(3, 8), max_new=(2, 6),
                vocab=99, seed=5, skew=2.0).generate(40)
    assert any(not np.array_equal(ra.prompt, rc.prompt)
               for (_, ra), (_, rc) in zip(a, c))


def test_replay_drains_trace_and_reports(setup):
    cfg, params, spec, base = setup
    eng = PersonalizedServeEngine(cfg, spec, make_snapshot(0, base),
                                  personalizer="none", slots=2,
                                  max_len=128, prefill_buckets=(8, 16))
    trace = LoadGen(population=8, rate=0.7, prompt_len=(3, 8),
                    max_new=(2, 5), vocab=cfg.vocab, seed=0).generate(10)
    stats = replay(eng, trace)
    assert stats["n_requests"] == 10
    assert len(stats["tick_wall"]) == len(stats["utilization"])
    assert stats["ticks"] > 0 and stats["requests_per_s"] > 0
    assert {c.uid for c in stats["completions"]} == set(range(10))


def test_replay_swaps_mid_stream(setup):
    cfg, params, spec, base = setup
    eng = PersonalizedServeEngine(cfg, spec, make_snapshot(0, base),
                                  personalizer="none", slots=2,
                                  max_len=128, prefill_buckets=(8, 16))
    trace = LoadGen(population=8, rate=0.5, prompt_len=(3, 8),
                    max_new=(4, 8), vocab=cfg.vocab, seed=2).generate(12)
    stats = replay(eng, trace, swap_at=4, snapshot=make_snapshot(1, base))
    vs = {c.version for c in stats["completions"]}
    assert vs == {0, 1}, vs


# -- launch specs -------------------------------------------------------------


def test_personalized_lowering_single_device(setup):
    """The sharded decode path lowers on a 1×1 local mesh and its bundle
    carries the flat base/delta shapes."""
    from repro import dist
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import lower_personalized_serve
    cfg, params, spec, base = setup
    mesh = make_local_mesh(1, 1)
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="decode")
    lowered, bundle = lower_personalized_serve(cfg, shape, mesh, spec)
    # the mesh rules lived only while the step was traced: later programs
    # in this process run unsharded
    assert dist.axis_size("mp") == 1 and not dist.partitioned()
    assert bundle["base"].shape == (spec.p,)
    assert bundle["deltas"].shape == (4, spec.p)
    assert lowered.compile() is not None


def test_personalized_decode_matches_engine_rows(setup):
    """The launch step (base + deltas → rows) computes the same logits the
    engine's row path does for one decode tick."""
    from repro.serving.personalized import personalized_decode
    cfg, params, spec, base = setup
    b = 2
    caches = M.init_caches(cfg, b, 64, jnp.dtype(cfg.dtype))
    toks = jnp.asarray([[5], [9]], jnp.int32)
    offs = jnp.zeros((b,), jnp.int32)
    deltas = 1e-3 * jax.random.normal(jax.random.PRNGKey(3), (b, spec.p))
    rows = base[None] + deltas
    logits, _ = personalized_decode(spec, cfg, rows, toks, caches, offs)
    assert logits.shape == (b, cfg.vocab)
    for i in range(b):
        ref, _ = M.serve_decode(
            flat.unravel(spec, rows[i]), {"tokens": toks[i][None]},
            M.init_caches(cfg, 1, 64, jnp.dtype(cfg.dtype)), 0, cfg)
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(ref[0, 0]), atol=1e-5)
