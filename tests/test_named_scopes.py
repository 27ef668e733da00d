"""The program names its work for the profiler: the FedaGrac round's
stages and the model's blocks carry ``jax.named_scope`` names into the
compiled program's ``op_name`` metadata, in the forward pass and in its
transpose, and each scanned chunk runs under ``fed.*`` host spans."""
import dataclasses
import functools
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs.base import FedConfig, reduced
from repro.configs.registry import get_arch
from repro.data import DeviceLMBatcher, lm_sequences
from repro.fed import FederatedSimulation
from repro.fed.simulation import History
from repro.models import model as model_lib

ROUND_SCOPES = ("fed.client_update", "fed.local_step", "fed.flat_boundary",
                "fed.aggregate", "fed.orientation")
# differentiated: each appears in the forward pass and in its transpose
MODEL_SCOPES = ("model.embed", "model.attention", "moe.route",
                "moe.dispatch", "moe.experts", "moe.combine", "model.head",
                "model.loss")
CHUNK_SPANS = ("fed.inputs", "fed.dispatch", "fed.wait", "fed.history")


@pytest.fixture(scope="module")
def sim():
    """The flat FedaGrac round of a tiny MoE LM: 2 clients, k_max 2,
    4 experts top-2, bf16 under a float32 master."""
    cfg = reduced(get_arch("granite-moe-1b-a400m"), n_layers=1, d_model=64,
                  vocab=256)
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    m = 2
    fed = FedConfig(algorithm="fedagrac", n_clients=m, lr=0.01,
                    calibration_rate=0.5, param_layout="flat",
                    master_dtype="float32")
    key = jax.random.PRNGKey(0)
    streams = [lm_sequences(jax.random.fold_in(key, i), 8, 32, cfg.vocab,
                            skew_topic=i) for i in range(m)]
    return FederatedSimulation(
        functools.partial(model_lib.lm_loss, cfg=cfg),
        model_lib.init_params(key, cfg), fed,
        DeviceLMBatcher(streams, batch_size=2, seed=0),
        k_schedule=np.array([[1, 2], [2, 1]]))


@pytest.fixture(scope="module")
def op_names(sim):
    assert sim.k_max == 2
    text = sim.lower_chunk(2).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("scope", ROUND_SCOPES)
def test_round_stage_is_named(op_names, scope):
    assert any(f"{scope})" in n or f"{scope}/" in n for n in op_names)


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_model_block_is_named_forward_and_backward(op_names, scope):
    hits = [n for n in op_names if re.search(rf"\b{re.escape(scope)}\b", n)]
    assert any("transpose(" not in n for n in hits), "no forward op"
    assert any("transpose(" in n for n in hits), "no backward op"
    # every block runs inside the client update
    assert all("fed.client_update" in n for n in hits
               if n.startswith("jit("))


def test_chunk_runs_under_host_spans(sim, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        sim.run(2, eval_every=2)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith("fed.")]
    chunks = [s for s in spans if s[0] == "fed.chunk"]
    assert len(chunks) == 1
    _, c0, c1 = chunks[0]
    inner = {name: (a, b) for name, a, b in spans if name != "fed.chunk"}
    assert set(inner) == set(CHUNK_SPANS)
    # in order, each inside the chunk
    ends = [c0] + [t for name in CHUNK_SPANS for t in inner[name]] + [c1]
    assert ends == sorted(ends)


def test_history_has_no_wall_clock():
    assert "wall" not in {f.name for f in dataclasses.fields(History)}
