"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases run end to end at a tiny size on the CPU (kernels in their CPU
paths), the sharded phase on four virtual devices."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.configs.base import reduced
from repro.configs.registry import get_arch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(dtype):
    cfg = reduced(get_arch("granite-moe-1b-a400m"), n_layers=2, d_model=64,
                  vocab=256)
    return dataclasses.replace(cfg, dtype=dtype)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok": true' not in out.stdout


def test_train_and_serve_phases_tiny(smoke, capsys):
    cfg = _tiny("bfloat16")
    m = 2
    smoke.kernel_phase(cfg, seed=0, seq=128)
    sim = smoke.train_phase(cfg, smoke.smoke_fed(m),
                            smoke.make_batcher(cfg, m, 32, 2, 0, n_seq=8),
                            seed=0, chunk=2, kernels=())
    assert len(sim.state["nu_i"]) == m
    smoke.serve_phase(cfg, sim, seed=0, n_req=2, prompt_len=32,
                      new_tokens=4, max_len=64)
    out = capsys.readouterr().out
    assert "kernels: flash dv" in out
    assert "train: losses" in out and "serve: 2/2 requests" in out


def test_sharded_phase_on_four_cpu_devices():
    code = (f"import sys; sys.path.insert(0, {REPO!r})\n"
            "import dataclasses, chip_smoke as cs\n"
            "from repro.configs.base import reduced\n"
            "from repro.configs.registry import get_arch\n"
            "cfg = reduced(get_arch('granite-moe-1b-a400m'), n_layers=2, "
            "d_model=64, vocab=256)\n"
            "cs.sharded_phase(cfg, seed=0, seq=32, batch=2, n_seq=8)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "sharded: params max_abs_diff" in out.stdout
