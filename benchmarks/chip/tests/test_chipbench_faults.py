"""The check catches what it is there to catch.  A run of the granite
training cell is driven on the CPU at a small size, past the look for a
chip, with the timed path sound and then broken underneath; the control
(the reference computed with float8 matmuls in the program's place) is
read at the same size.  Each broken run and the control come out not
correct under the limits for this size."""
import time

import chipbench_tiny
import pytest

import harness

CELL = "granite-fedagrac-kasync"


def _run(seed=3_000_000_019):
    return harness.run(CELL, seed, 0.5, False, time.perf_counter(),
                       chip_check=False, overrides=chipbench_tiny.TINY[CELL])


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_state_returned_unchanged(monkeypatch):
    """Every round hands back the state it was given."""
    from repro.fed.simulation import FederatedSimulation
    build = FederatedSimulation._build_round

    def frozen(self):
        real = build(self)

        def round_fn(state, *args):
            _, metrics = real(state, *args)
            return state, metrics
        return round_fn

    monkeypatch.setattr(FederatedSimulation, "_build_round", frozen)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["update_diff_median"]["value"] > 0.5


def test_half_the_batch_left_out(monkeypatch):
    """The loss is the mean over the first half of each batch only."""
    from repro.models import model as model_lib
    real = model_lib.lm_loss

    def half(params, batch, cfg):
        n = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:n] for k, v in batch.items()}, cfg)

    monkeypatch.setattr(model_lib, "lm_loss", half)
    out = _run()
    assert not out["correct"], out["checks"]


def test_control_fails():
    ctx = harness.context(CELL, 3_000_000_019, chip_check=False,
                          overrides=chipbench_tiny.TINY[CELL])
    control = harness.load_module(".", "control")
    got = control.readings(ctx, ["fp8", "unchanged"])
    for variant in got:
        assert any(got[variant][k] > lim for k, lim in ctx.limits.items()), \
            got[variant]
    # the unchanged stand-in reads as the frozen program does
    assert got["unchanged"]["update_diff"] == pytest.approx(1.0)

