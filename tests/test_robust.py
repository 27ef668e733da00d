"""Byzantine-robust aggregation (core/robust.py, DESIGN.md §16): defense
transforms on padded row blocks, weight-mass preservation, the
defense="none" bit-identity matrix over algorithms × engines × layouts,
payload-corruption purity across chunk splits and resumes, quarantine
semantics, and defended-vs-undefended survival."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import serialize
from repro.configs.base import FedConfig
from repro.core import flat as flat_mod
from repro.core.fedopt import ALGORITHMS
from repro.core.robust import (DEFENSES, HEALTH_WARMUP, ROBUST_STATE_KEYS,
                               RobustConfig, build_round_robust)
from repro.data import DeviceBatcher, fedprox_synthetic
from repro.fed import (BufferedAsyncSimulation, FederatedSimulation,
                       SCENARIOS, garbage_scenario, make_scenario,
                       nan_inject_scenario, scale_attack_scenario,
                       sign_flip_scenario)
from repro.fed.scenarios import _corrupt_set
from repro.models.simple import lr_loss

M = 8
ATTACKS = ["nan_inject", "inf_inject", "scale_attack", "sign_flip",
           "garbage"]


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


def _eval(params):
    return float(jnp.sum(jnp.abs(params["w"])) + jnp.sum(params["b"]))


# ---------------------------------------------------------------------------
# config validation (satellite: fail at construction, not in jit)
# ---------------------------------------------------------------------------

def test_unknown_defense_lists_valid_options():
    with pytest.raises(ValueError) as e:
        FedConfig(defense="majority")
    msg = str(e.value)
    assert "defense" in msg and "'majority'" in msg and "krum" in msg


@pytest.mark.parametrize("kw", [
    {"trim_frac": -0.1}, {"trim_frac": 0.5}, {"trim_frac": 1.0},
    {"defense_clip": -1.0}, {"krum_f": -1},
    {"quarantine_window": -1}, {"quarantine_nonfinite": 0},
    {"quarantine_z": 0.0}, {"quarantine_z": -2.0},
])
def test_robust_field_validation(kw):
    with pytest.raises(ValueError):
        FedConfig(**kw)


def test_robust_fields_construct():
    FedConfig(defense="trimmed_mean", trim_frac=0.25, defense_clip=2.0,
              krum_f=2, quarantine_window=5, quarantine_z=3.0,
              quarantine_nonfinite=2, nu_defense=False)


def test_from_fed_gates_on_none():
    assert RobustConfig.from_fed(FedConfig()) is None
    assert RobustConfig.from_fed(FedConfig(defense="none")) is None
    # quarantine alone activates the robust layer (defense stays identity)
    cfg = RobustConfig.from_fed(FedConfig(quarantine_window=3))
    assert cfg is not None and not cfg.defends and cfg.quarantines
    cfg = RobustConfig.from_fed(FedConfig(defense="median"))
    assert cfg is not None and cfg.defends and not cfg.quarantines


# ---------------------------------------------------------------------------
# defense transforms: unit behavior on (B, P) row blocks
# ---------------------------------------------------------------------------

def _rows(b=6, p=32, seed=0, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, p),
                             jnp.float32) * scale


def test_clip_bounds_survivor_norms():
    cfg = RobustConfig(defense="clip", clip_norm=2.0)
    fn = DEFENSES["clip"](cfg, 32)
    rows = _rows().at[0].mul(100.0)
    out, mask = fn(rows, jnp.ones(6, bool))
    norms = np.sqrt((np.asarray(out) ** 2).sum(-1))
    assert norms.max() <= 2.0 + 1e-5
    assert bool(mask.all())                  # clip never excludes


def test_adaptive_clip_uses_median_of_survivors():
    cfg = RobustConfig(defense="clip", clip_norm=0.0)
    fn = DEFENSES["clip"](cfg, 32)
    rows = _rows().at[0].mul(1e6)
    mask = jnp.ones(6, bool).at[1].set(False)
    out, _ = fn(rows, mask)
    norms_in = np.sqrt((np.asarray(rows) ** 2).sum(-1))
    tau = np.median(np.delete(norms_in, 1))   # dead row excluded
    norms = np.sqrt((np.asarray(out) ** 2).sum(-1))
    assert norms[0] <= tau * (1 + 1e-5)       # outlier pulled to the median


def test_median_broadcasts_columnwise_median_of_survivors():
    cfg = RobustConfig(defense="median")
    fn = DEFENSES["median"](cfg, 32)
    rows = _rows(b=5)
    mask = jnp.ones(5, bool).at[4].set(False)
    out, _ = fn(rows, mask)
    want = np.median(np.asarray(rows)[:4], axis=0)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(out)[i], want, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[4], 0.0)   # dead stays 0


def test_trimmed_mean_resists_one_outlier():
    cfg = RobustConfig(defense="trimmed_mean", trim_frac=0.2)
    fn = DEFENSES["trimmed_mean"](cfg, 32)
    rows = _rows(b=6)
    honest_mean = np.asarray(rows).mean(0)
    poisoned = rows.at[3].set(1e6)
    out, _ = fn(poisoned, jnp.ones(6, bool))
    # every surviving row carries the trimmed center; the outlier's mass
    # cannot shift it by more than the trim band
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(out)[1])
    assert np.abs(np.asarray(out)[0] - honest_mean).max() < 1.0


def test_krum_excludes_planted_outlier():
    cfg = RobustConfig(defense="krum", krum_f=1)
    fn = DEFENSES["krum"](cfg, 32)
    rows = _rows(b=6, scale=0.1).at[2].add(50.0)
    out, mask = fn(rows, jnp.ones(6, bool))
    assert not bool(mask[2])                  # the far row is deselected
    np.testing.assert_array_equal(np.asarray(out)[2], 0.0)
    assert int(np.asarray(mask).sum()) == 5   # keeps B - f rows


def test_defense_factories_cover_registry():
    assert set(DEFENSES) == {"none", "clip", "median", "trimmed_mean",
                             "krum"}


# ---------------------------------------------------------------------------
# attack scenarios: pure in (seed, round, client), persistent corrupt set
# ---------------------------------------------------------------------------

def test_attack_registry_and_corrupts_payload_flag():
    assert set(ATTACKS) <= set(SCENARIOS)
    for name in ATTACKS:
        sc = make_scenario(_fed(scenario=name, scenario_rate=0.3))
        assert sc is not None and sc.corrupts_payload
        assert not sc.perturbs_k      # payload-only: timelines untouched
    assert not make_scenario(_fed(scenario="dropout")).corrupts_payload


def test_corrupt_set_persistent_and_rate_bounded():
    sc = scale_attack_scenario(M, rate=0.5, magnitude=4.0, seed=3)
    rows = jnp.ones((M, 16))
    a = np.asarray(sc.corrupt_delta(0, rows, 16))
    for t in range(1, 6):
        b = np.asarray(sc.corrupt_delta(t, rows, 16))
        np.testing.assert_array_equal((a == 4.0), (b == 4.0))  # same set
    frac = float((a[:, 0] == 4.0).mean())
    assert 0.0 < frac < 1.0


def test_corrupt_rows_pure_across_rebuilds_and_id_subsets():
    a = garbage_scenario(M, rate=0.5, magnitude=3.0, seed=5)
    b = garbage_scenario(M, rate=0.5, magnitude=3.0, seed=5)
    rows = _rows(b=M, p=16, seed=9)
    np.testing.assert_array_equal(np.asarray(a.corrupt_delta(4, rows, 16)),
                                  np.asarray(b.corrupt_delta(4, rows, 16)))
    # a cohort subset sees exactly its rows of the full draw
    ids = jnp.asarray([1, 4, 6], jnp.int32)
    full = np.asarray(a.corrupt_delta(4, rows, 16))
    sub = np.asarray(a.corrupt_delta(4, rows[ids], 16, ids=ids))
    np.testing.assert_array_equal(sub, full[np.asarray(ids)])


def test_corruption_masks_padding_columns():
    sc = nan_inject_scenario(M, rate=1.0, seed=0)
    rows = jnp.zeros((M, 32))
    out = np.asarray(sc.corrupt_delta(0, rows, 20))
    assert np.isnan(out[:, :20]).all()
    np.testing.assert_array_equal(out[:, 20:], 0.0)   # pads stay clean


def test_delta_and_nu_streams_differ():
    sc = garbage_scenario(M, rate=1.0, magnitude=2.0, seed=0)
    rows = _rows(b=M, p=16, seed=2)
    d = np.asarray(sc.corrupt_delta(3, rows, 16))
    n = np.asarray(sc.corrupt_nu(3, rows, 16))
    assert not np.array_equal(d, n)


def test_attack_rate_validation():
    with pytest.raises(ValueError):
        nan_inject_scenario(M, rate=1.5)
    with pytest.raises(ValueError):
        scale_attack_scenario(M, magnitude=0.0)


# ---------------------------------------------------------------------------
# golden pins: defense="none" is trace-time gated to the identical round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", ["tree", "flat"])
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_none_bit_identical_sync(task, algorithm, layout):
    fed_kw = {"algorithm": algorithm, "param_layout": layout}
    ref = FederatedSimulation(lr_loss, _params(), _fed(**fed_kw), task)
    ref.run(2, eval_every=2)
    none = FederatedSimulation(lr_loss, _params(),
                               _fed(**fed_kw, defense="none"), task)
    none.run(2, eval_every=2)
    _leaves_equal(ref.state, none.state)


@pytest.mark.parametrize("layout", ["tree", "flat"])
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_none_bit_identical_cohort(task, algorithm, layout):
    fed_kw = {"algorithm": algorithm, "param_layout": layout,
              "cohort_size": 4}
    ref = FederatedSimulation(lr_loss, _params(), _fed(**fed_kw), task)
    ref.run(2, eval_every=2)
    none = FederatedSimulation(lr_loss, _params(),
                               _fed(**fed_kw, defense="none"), task)
    none.run(2, eval_every=2)
    _leaves_equal(ref.state, none.state)


@pytest.mark.parametrize("layout", ["tree", "flat"])
@pytest.mark.parametrize("algorithm", ALGORITHMS)
def test_none_bit_identical_async(task, algorithm, layout):
    fed_kw = {"algorithm": algorithm, "param_layout": layout,
              "buffer_size": 4, "staleness": "poly"}
    ref = BufferedAsyncSimulation(lr_loss, _params(), _fed(**fed_kw), task)
    ref.run(3)
    none = BufferedAsyncSimulation(lr_loss, _params(),
                                   _fed(**fed_kw, defense="none"), task)
    none.run(3)
    _leaves_equal(ref.state, none.state)


# ---------------------------------------------------------------------------
# corruption determinism: chunk splits, resumes, tree-vs-flat
# ---------------------------------------------------------------------------

def _attacked(**kw):
    kw.setdefault("scenario", "scale_attack")
    kw.setdefault("scenario_rate", 0.3)
    kw.setdefault("scenario_magnitude", 5.0)
    kw.setdefault("defense", "median")
    kw.setdefault("quarantine_window", 2)
    return _fed(**kw)


def test_attacked_run_bit_identical_across_chunk_splits(task):
    a = FederatedSimulation(lr_loss, _params(), _attacked(), task)
    a.run(6, eval_every=6)
    b = FederatedSimulation(lr_loss, _params(), _attacked(), task)
    b.run(6, eval_every=2)
    c = FederatedSimulation(lr_loss, _params(), _attacked(), task)
    c.run(6, eval_every=1)
    _leaves_equal(a.state, b.state)
    _leaves_equal(a.state, c.state)


def test_attacked_state_resumes_bit_exact_from_checkpoint(task, tmp_path):
    """Corruption is keyed off the round counter IN STATE, so a
    save/load/resume replays the identical injections: restoring mid-run
    state into a fresh engine leaves the next round bit-identical."""
    a = FederatedSimulation(lr_loss, _params(), _attacked(), task)
    a.run(2, eval_every=2)
    path = str(tmp_path / "mid.msgpack")
    serialize.save(path, a.state)
    b = FederatedSimulation(lr_loss, _params(), _attacked(), task)
    b.state = serialize.load(path, b.state)
    _leaves_equal(a.state, b.state)
    # one more identical-data round on both engines stays bit-equal
    ha = a.run(1, eval_every=1)
    hb = b.run(1, eval_every=1)
    _leaves_equal(a.state, b.state)
    assert ha.quarantined == hb.quarantined


@pytest.mark.parametrize("defense", ["clip", "median", "trimmed_mean",
                                     "krum"])
def test_tree_and_flat_agree_under_attack(task, defense):
    out = {}
    for layout in ("tree", "flat"):
        sim = FederatedSimulation(
            lr_loss, _params(),
            _attacked(defense=defense, param_layout=layout), task)
        sim.run(3, eval_every=3)
        out[layout] = jax.tree.leaves(sim.params)
    for lt, lf in zip(out["tree"], out["flat"]):
        np.testing.assert_allclose(np.asarray(lt), np.asarray(lf),
                                   rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# defense efficacy + the final non-finite guard
# ---------------------------------------------------------------------------

def test_undefended_nan_inject_raises_at_eval(task):
    sim = FederatedSimulation(lr_loss, _params(),
                              _nan_fed(), task,
                              eval_fn=_eval)
    with pytest.raises(FloatingPointError, match="non-finite"):
        sim.run(4, eval_every=1)


@pytest.mark.parametrize("defense", ["median", "trimmed_mean", "krum"])
def test_defended_nan_inject_stays_finite(task, defense):
    sim = FederatedSimulation(lr_loss, _params(),
                              _nan_fed(defense=defense,
                                       quarantine_window=3), task,
                              eval_fn=_eval)
    hist = sim.run(4, eval_every=1)
    assert all(np.isfinite(hist.metric))
    for leaf in jax.tree.leaves(sim.state):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_defended_async_nan_inject_stays_finite(task):
    sim = BufferedAsyncSimulation(
        lr_loss, _params(),
        _nan_fed(defense="trimmed_mean", quarantine_window=3,
                 buffer_size=4), task, eval_fn=_eval)
    hist = sim.run(6, eval_every=1)
    assert all(np.isfinite(hist.metric))
    for leaf in jax.tree.leaves(sim.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


def test_guard_without_quarantine_keeps_nu_finite(task):
    """defense alone (no quarantine) must still never write NaN into the
    master or ν — the final guard, not the health layer, provides this."""
    sim = FederatedSimulation(lr_loss, _params(),
                              _nan_fed(defense="median"), task)
    sim.run(3, eval_every=3)
    for key in ("params", "nu", "nu_i"):
        for leaf in jax.tree.leaves(sim.state[key]):
            assert bool(jnp.all(jnp.isfinite(leaf))), key


# ---------------------------------------------------------------------------
# quarantine: health state, exclusion, History plumbing
# ---------------------------------------------------------------------------

def test_nonfinite_reporters_get_quarantined(task):
    fed = _nan_fed(defense="trimmed_mean", quarantine_window=4)
    sim = FederatedSimulation(lr_loss, _params(), fed, task)
    hist = sim.run(4, eval_every=1)
    hit = np.asarray(sim.state["hz_nonfinite"]) > 0
    assert hit.any()
    until = np.asarray(sim.state["hz_until"])
    np.testing.assert_array_equal(until > 0, hit)   # flagged ⇔ windowed
    # rounds after the first carry active exclusions
    assert len(hist.quarantined) == 4
    assert sum(hist.quarantined[1:]) > 0
    assert hist.quarantined[0] == 0.0      # nobody pre-flagged at round 0


def test_quarantine_state_keys_allocated_only_when_active(task):
    on = FederatedSimulation(lr_loss, _params(),
                             _fed(quarantine_window=2), task)
    for key in ROBUST_STATE_KEYS:
        assert key in on.state and on.state[key].shape == (M,)
    off = FederatedSimulation(lr_loss, _params(),
                              _fed(defense="median"), task)
    for key in ROBUST_STATE_KEYS:
        assert key not in off.state


def test_flatten_state_passes_health_keys_through(task):
    sim = FederatedSimulation(lr_loss, _params(),
                              _nan_fed(defense="median",
                                       quarantine_window=2), task)
    sim.run(1)
    spec = sim._spec
    flat_state = flat_mod.flatten_state(spec, sim.state)
    for key in ROBUST_STATE_KEYS:
        assert key in flat_state
        np.testing.assert_array_equal(np.asarray(flat_state[key]),
                                      np.asarray(sim.state[key]))
    round_trip = flat_mod.unflatten_state(spec, flat_state)
    for key in ROBUST_STATE_KEYS:
        np.testing.assert_array_equal(np.asarray(round_trip[key]),
                                      np.asarray(sim.state[key]))


def test_build_round_robust_requires_spec():
    cfg = RobustConfig(defense="median")
    with pytest.raises(ValueError, match="FlatSpec"):
        build_round_robust(cfg, None, True)
    assert build_round_robust(None, None, True) is None


# ---------------------------------------------------------------------------
# ν defense ablation: the knob actually changes the calibration stream
# ---------------------------------------------------------------------------

def test_nu_defense_knob_changes_nu_not_gated_runs(task):
    kw = dict(scenario="sign_flip", scenario_rate=0.3, defense="median")
    a = FederatedSimulation(lr_loss, _params(), _fed(**kw), task)
    a.run(3, eval_every=3)
    b = FederatedSimulation(lr_loss, _params(),
                            _fed(**kw, nu_defense=False), task)
    b.run(3, eval_every=3)
    na = np.concatenate([np.ravel(l) for l in jax.tree.leaves(
        a.state["nu"])])
    nb = np.concatenate([np.ravel(l) for l in jax.tree.leaves(
        b.state["nu"])])
    assert not np.array_equal(na, nb)     # ablation is live
    # with no defense at all the knob is inert (trace-time gated away)
    c = FederatedSimulation(lr_loss, _params(),
                            _fed(nu_defense=False), task)
    c.run(2, eval_every=2)
    d = FederatedSimulation(lr_loss, _params(), _fed(), task)
    d.run(2, eval_every=2)
    _leaves_equal(c.state, d.state)
