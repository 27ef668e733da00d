"""SPMD integration tests — each spawns a subprocess with its own host
device count (XLA locks the count at first init; the main pytest process
must stay single-device for the smoke tests)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_round_matches_single_device():
    """The FedaGrac LM round on a (4,2) mesh == the unsharded round."""
    run_py(r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import FedConfig, reduced
from repro.configs.registry import get_arch
from repro.configs.base import ShapeConfig
from repro.core import rounds
from repro.core.fedopt import get_algorithm
from repro import dist
from repro.launch.mesh import make_local_mesh
from repro.launch import train as train_lib, specs as specs_lib
from repro.models import model as M

cfg = reduced(get_arch("llama3-8b"), n_layers=2, d_model=128)
fed = FedConfig(algorithm="fedagrac", lr=0.05, calibration_rate=0.5)
algo = get_algorithm("fedagrac", fed)
k_max, m, b, s = 2, 4, 2, 16

key = jax.random.PRNGKey(0)
params = M.init_params(key, cfg)
toks = jax.random.randint(key, (m, k_max, b, s), 0, cfg.vocab)
batches = {"tokens": toks, "labels": toks}
ks = jnp.array([1, 2, 2, 1], jnp.int32)
w = jnp.full((m,), 0.25, jnp.float32)
loss = lambda p, bt: M.lm_loss(p, bt, cfg)

# --- single device ---------------------------------------------------------
state0 = rounds.init_state(params, m, algo)
fn = jax.jit(rounds.make_round(loss, algo, lr=fed.lr, k_max=k_max))
ref_state, ref_metrics = fn(state0, batches, ks, w)

# --- (data=4, model=2) mesh --------------------------------------------------
mesh = make_local_mesh(4, 2)
shape = ShapeConfig("t", seq_len=s, global_batch=m * b, kind="train")
with jax.set_mesh(mesh):
    jitted, bundle = train_lib.build_train_round(cfg, shape, mesh, fed,
                                                 k_max=k_max)
    state0b = rounds.init_state(params, m, algo)
    sh = lambda t: specs_lib.to_shardings(t, mesh)
    ps = bundle["pspecs"]
    state0b = jax.device_put(state0b, sh(ps["state"]))
    batches_s = jax.device_put(batches, sh(ps["batches"]))
    spmd_state, spmd_metrics = jitted(state0b, batches_s,
                                      jax.device_put(ks, sh(ps["k_steps"])),
                                      jax.device_put(w, sh(ps["weights"])))
# the mesh rules lived only while the round was traced
assert not dist.partitioned() and dist.axis_size("mp") == 1

for pref, pspmd in zip(jax.tree.leaves(ref_state["params"]),
                       jax.tree.leaves(spmd_state["params"])):
    np.testing.assert_allclose(np.asarray(pref, np.float32),
                               np.asarray(pspmd, np.float32),
                               rtol=2e-4, atol=2e-5)
for nref, nspmd in zip(jax.tree.leaves(ref_state["nu"]),
                       jax.tree.leaves(spmd_state["nu"])):
    np.testing.assert_allclose(np.asarray(nref, np.float32),
                               np.asarray(nspmd, np.float32),
                               rtol=2e-4, atol=2e-5)
assert abs(float(ref_metrics["loss"]) - float(spmd_metrics["loss"])) < 1e-3
print("SPMD==single OK", float(ref_metrics["loss"]))
""")


def test_sharded_decode_matches_single_device():
    run_py(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ShapeConfig, reduced
from repro.configs.registry import get_arch
from repro.launch.mesh import make_local_mesh
from repro.launch import serve as serve_lib
from repro.models import model as M

cfg = reduced(get_arch("llama3-8b"), n_layers=2, d_model=128)
B, S = 8, 32
key = jax.random.PRNGKey(0)
params = M.init_params(key, cfg)
toks = jax.random.randint(key, (B, 1), 0, cfg.vocab)
caches = M.init_caches(cfg, B, max_len=S, dtype=jnp.float32)

ref_logits, _ = M.serve_decode(params, {"tokens": toks}, caches, 0, cfg)

mesh = make_local_mesh(4, 2)
shape = ShapeConfig("d", seq_len=S, global_batch=B, kind="decode")
with jax.set_mesh(mesh):
    jitted, bundle = serve_lib.build_decode(cfg, shape, mesh, kind="decode")
    spmd_logits, _ = jitted(params, {"tokens": toks}, caches,
                            jnp.zeros((), jnp.int32))
np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(spmd_logits),
                           rtol=2e-4, atol=2e-4)
print("decode SPMD OK")
""")


def test_dryrun_cli_small_mesh():
    """The dryrun module itself must import cleanly and its helpers work on
    a real (tiny) mesh inside a 512-device subprocess is too slow here; we
    check skip logic + one reduced lower/compile on 8 devices instead."""
    run_py(r"""
import jax, jax.numpy as jnp
from repro.configs.base import FedConfig, ShapeConfig, reduced
from repro.configs.registry import get_arch
from repro.launch.mesh import make_local_mesh
from repro.launch import train as train_lib
from repro.roofline import analysis as roofline

cfg = reduced(get_arch("granite-moe-1b-a400m"), n_layers=2, d_model=128)
mesh = make_local_mesh(4, 2)
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
lowered, bundle = train_lib.lower_train(cfg, shape, mesh,
                                        FedConfig(algorithm="fedagrac"),
                                        k_max=2)
compiled = lowered.compile()
rl = roofline.from_compiled(compiled, 8,
                            roofline.train_model_flops(cfg, 8 * 32 * 2))
d = rl.as_dict()
assert d["flops_per_chip"] > 0
assert d["t_memory_s"] > 0
print("dryrun-small OK", d["dominant"])
""")


def test_dryrun_skip_logic():
    """long_500k is skipped for pure full-attention archs and run for
    sub-quadratic ones (importing dryrun mutates XLA_FLAGS ⇒ subprocess)."""
    out = run_py(r"""
from repro.launch.dryrun import skip_reason
assert skip_reason("llama3-8b", "long_500k") is not None
assert skip_reason("qwen1.5-32b", "long_500k") is not None
assert skip_reason("zamba2-2.7b", "long_500k") is None
assert skip_reason("xlstm-125m", "long_500k") is None
assert skip_reason("gemma3-12b", "long_500k") is None
assert skip_reason("llama3-8b", "train_4k") is None
print("skip logic OK")
""", devices=1, timeout=300)
    assert "skip logic OK" in out


def test_host_client_slice_local_mesh():
    """Single-host: every client's slice is local ⇒ [0, n_clients)."""
    out = run_py(r"""
import jax
from repro.launch.mesh import make_local_mesh
from repro.launch.distributed import host_client_slice, bootstrap
bootstrap()                      # no-op without cluster env
mesh = make_local_mesh(4, 2)
lo, hi = host_client_slice(mesh)
assert (lo, hi) == (0, 4), (lo, hi)
print("host slice OK")
""", devices=8, timeout=600)
    assert "host slice OK" in out
