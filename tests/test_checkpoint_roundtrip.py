"""Checkpoint round-trips for the serving hot-swap feed: full engine state
(flat master + ν rows + error-feedback residuals) bit-exactly through
checkpoint/serialize.py, snapshot publication from a live simulation, and
a mid-run swap-from-file while requests are in flight."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import serialize
from repro.configs.base import FedConfig, reduced
from repro.configs.registry import get_arch
from repro.core import flat
from repro.data import DeviceBatcher, fedprox_synthetic
from repro.fed import FederatedSimulation
from repro.fed.scenarios import _corrupt_set
from repro.models import model as M_model
from repro.models.simple import lr_loss
from repro.serving import (PersonalizedServeEngine, Request, load_snapshot,
                           make_snapshot, save_snapshot)

M = 8


@pytest.fixture(scope="module")
def task():
    key = jax.random.PRNGKey(0)
    data, parts = fedprox_synthetic(key, M, alpha=1.0, beta=1.0)
    return DeviceBatcher(data, parts, batch_size=8, seed=0)


def _fed(**kw):
    kw.setdefault("algorithm", "fedagrac")
    kw.setdefault("k_mean", 5)
    kw.setdefault("k_var", 2.0)
    kw.setdefault("k_mode", "random")
    return FedConfig(n_clients=M, lr=0.05, calibration_rate=0.5, **kw)


# with jax_threefry_partitionable (jax's default since 0.5) the seed-0
# corrupt set at rate 0.25 is empty for M = 8; seed 2 corrupts two
# clients, so the nan_inject tests exercise a real attack
NAN_SEED = 2


def _nan_fed(**kw):
    """A nan_inject config whose persistent corrupt set is non-empty."""
    fed = _fed(scenario="nan_inject", scenario_rate=0.25, seed=NAN_SEED,
               **kw)
    assert np.asarray(_corrupt_set(M, fed.seed, fed.scenario_rate)).any()
    return fed


def _params():
    return {"w": jnp.zeros((60, 10)), "b": jnp.zeros((10,))}


def _leaves_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- full engine state (the hot-swap source) ---------------------------------


@pytest.mark.parametrize("layout", ["tree", "flat"])
def test_full_state_roundtrips_bit_exact(task, tmp_path, layout):
    """Everything hot-swap consumes — params/master, ν, ν⁽ⁱ⁾ rows — plus
    the PR-8 error-feedback residuals survives save/load bit-for-bit."""
    sim = FederatedSimulation(lr_loss, _params(),
                              _fed(param_layout=layout, compressor="int8"),
                              task)
    sim.run(3, eval_every=3)
    path = str(tmp_path / "state.msgpack")
    serialize.save(path, sim.state)
    restored = serialize.load(path, sim.state)
    for key in ("params", "nu", "nu_i", "ef_up", "ef_nu"):
        assert key in restored
    _leaves_equal(sim.state, restored)


def test_load_raw_matches_structured_load(task, tmp_path):
    """``load_raw`` recovers the identical bytes with no ``like`` tree —
    the schema-free path serving snapshots restore through."""
    sim = FederatedSimulation(lr_loss, _params(), _fed(param_layout="flat"),
                              task)
    sim.run(2, eval_every=2)
    path = str(tmp_path / "state.msgpack")
    serialize.save(path, sim.state)
    raw = serialize.load_raw(path)
    structured = serialize.load(path, sim.state)
    assert sorted(raw) == sorted(structured)
    for k in raw:
        np.testing.assert_array_equal(raw[k], np.asarray(structured[k]))
        assert raw[k].dtype == np.asarray(structured[k]).dtype


# -- snapshot publication -----------------------------------------------------


@pytest.mark.parametrize("layout", ["tree", "flat"])
def test_publish_snapshot_carries_training_state(task, layout):
    sim = FederatedSimulation(lr_loss, _params(),
                              _fed(param_layout=layout), task)
    sim.run(2, eval_every=2)
    snap = sim.publish_snapshot()
    spec = sim.flat_spec
    assert int(snap["version"]) == 2
    assert snap["flat_master"].shape == (spec.p,)
    assert snap["nu"].shape == (spec.p,)
    assert snap["nu_i"].shape == (M, spec.p)
    # the master IS the current model, whatever the layout
    _leaves_equal(flat.unravel(spec, snap["flat_master"]), sim.params)


def test_snapshot_file_roundtrip(task, tmp_path):
    sim = FederatedSimulation(lr_loss, _params(), _fed(param_layout="flat"),
                              task)
    sim.run(2, eval_every=2)
    path = str(tmp_path / "snap.msgpack")
    saved = sim.save_snapshot(path)
    loaded = load_snapshot(path)
    assert sorted(loaded) == sorted(saved)
    assert int(loaded["version"]) == int(saved["version"])
    _leaves_equal({k: v for k, v in saved.items() if k != "version"},
                  {k: v for k, v in loaded.items() if k != "version"})


def test_publish_hook_fires_on_round_boundaries(task):
    seen = []
    sim = FederatedSimulation(lr_loss, _params(), _fed(param_layout="flat"),
                              task)
    sim.run(6, eval_every=6, publish_fn=lambda s: seen.append(s),
            publish_every=2)
    assert [int(s["version"]) for s in seen] == [2, 4, 6]
    # each publication is the exact state at its round, so consecutive
    # masters differ (training moved) but shapes/schema are stable
    assert all(s["flat_master"].shape == seen[0]["flat_master"].shape
               for s in seen)
    assert not np.array_equal(np.asarray(seen[0]["flat_master"]),
                              np.asarray(seen[-1]["flat_master"]))


# -- health/quarantine state (core/robust.py, DESIGN.md §16) ------------------


_HEALTH_KEYS = ("hz_nonfinite", "hz_mean", "hz_var", "hz_count", "hz_until")


@pytest.mark.parametrize("layout", ["tree", "flat"])
def test_health_state_roundtrips_bit_exact(task, tmp_path, layout):
    """The per-client health vectors ride the same checkpoint as the
    model/ν/EF state, bit-for-bit, on both layouts."""
    fed = _nan_fed(param_layout=layout, defense="trimmed_mean",
                   quarantine_window=3)
    sim = FederatedSimulation(lr_loss, _params(), fed, task)
    sim.run(3, eval_every=3)
    assert np.asarray(sim.state["hz_nonfinite"]).sum() > 0
    path = str(tmp_path / "robust.msgpack")
    serialize.save(path, sim.state)
    restored = serialize.load(path, sim.state)
    assert sorted(restored) == sorted(sim.state)
    for key in _HEALTH_KEYS:
        assert key in restored
    _leaves_equal(sim.state, restored)


def test_cohort_absentee_health_rows_untouched(task):
    """A client outside the sampled cohort reports nothing: its health
    rows must stay bit-identical (no decay, no accidental scatter)."""
    fed = _nan_fed(cohort_size=3, defense="median", quarantine_window=4)
    sim = FederatedSimulation(lr_loss, _params(), fed, task)
    before = {k: np.asarray(sim.state[k]).copy() for k in _HEALTH_KEYS}
    sim.run(1)
    ids = set(int(i) for i in sim.population.host_cohort(0)[0])
    after = {k: np.asarray(sim.state[k]) for k in _HEALTH_KEYS}
    for i in range(M):
        if i not in ids:
            for k in _HEALTH_KEYS:
                assert before[k][i] == after[k][i], (k, i)


def test_quarantine_survives_resume(task, tmp_path):
    """A quarantine window in force at save time is still in force after
    load: the restored engine keeps excluding the flagged clients."""
    fed = _nan_fed(defense="trimmed_mean", quarantine_window=8)
    sim = FederatedSimulation(lr_loss, _params(), fed, task)
    sim.run(2, eval_every=2)
    assert np.asarray(sim.state["hz_until"]).max() > 0
    path = str(tmp_path / "quar.msgpack")
    serialize.save(path, sim.state)
    sim2 = FederatedSimulation(lr_loss, _params(), fed, task)
    sim2.state = serialize.load(path, sim2.state)
    _leaves_equal(sim.state, sim2.state)
    hist = sim2.run(1, eval_every=1)
    assert hist.quarantined and hist.quarantined[0] > 0


# -- mid-run swap from file with requests in flight ---------------------------


def test_lm_train_publish_swap_while_in_flight(tmp_path):
    """The full loop: train a tiny LM federated sim, publish to disk,
    serve; train more rounds, publish again, hot-swap FROM FILE while a
    request is mid-decode — the in-flight request's tokens are unchanged
    and versions are recorded per completion."""
    from repro.data import LMFederatedBatcher, lm_sequences

    cfg = reduced(get_arch("gemma-2b"), n_layers=1, d_model=32)
    cfg = dataclasses.replace(cfg, vocab=128)
    params = M_model.init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    streams = [lm_sequences(jax.random.fold_in(key, i), 64, 16, cfg.vocab,
                            skew_topic=i) for i in range(4)]
    batcher = LMFederatedBatcher(streams, batch_size=4)
    fed = FedConfig(algorithm="fedagrac", n_clients=4, k_mean=2,
                    k_var=0.0, lr=0.1, calibration_rate=0.5,
                    param_layout="flat")
    sim = FederatedSimulation(
        functools.partial(M_model.lm_loss, cfg=cfg), params, fed, batcher)
    sim.run(2, eval_every=2)
    p1 = str(tmp_path / "v2.msgpack")
    sim.save_snapshot(p1)
    sim.run(2, eval_every=2)
    p2 = str(tmp_path / "v4.msgpack")
    sim.save_snapshot(p2)

    spec = sim.flat_spec
    rng = np.random.default_rng(0)
    pre = Request(uid=0, prompt=rng.integers(1, cfg.vocab, 5).astype(
        np.int32), max_new_tokens=10, client_id=1)
    post = Request(uid=1, prompt=rng.integers(1, cfg.vocab, 5).astype(
        np.int32), max_new_tokens=4, client_id=2)

    def serve(swap):
        eng = PersonalizedServeEngine(cfg, spec, load_snapshot(p1),
                                      personalizer="nu", slots=2,
                                      max_len=64, prefill_buckets=(8,))
        eng.submit(dataclasses.replace(pre))
        for _ in range(3):
            eng.step()                 # pre is mid-decode
        if swap:
            eng.swap(load_snapshot(p2))
        eng.submit(dataclasses.replace(post))
        return {c.uid: c for c in eng.run()}

    plain, swapped = serve(False), serve(True)
    assert swapped[0].tokens == plain[0].tokens
    assert swapped[0].version == 2 and swapped[1].version == 4
    assert plain[1].version == 2
    # post-swap admission equals serving v4 outright
    eng4 = PersonalizedServeEngine(cfg, spec, load_snapshot(p2),
                                   personalizer="nu", slots=2,
                                   max_len=64, prefill_buckets=(8,))
    eng4.submit(dataclasses.replace(post))
    assert swapped[1].tokens == eng4.run()[0].tokens
