"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test compiles a kernel for a described (not attached) v5e chip —
what the chip's compiler would refuse (unaligned tiles, VMEM overuse)
fails here without a chip — and checks that the program holds the kernel
as a ``tpu_custom_call``.  Nothing runs.  The topology is described inside
a fixture, never at import: only the worker that runs these tests loads
the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels.calibrated_update.kernel import (LANES,
                                                    calibrated_update_2d,
                                                    calibrated_update_prox_2d)
from repro.kernels.flash_attention.ops import flash_attention_diff
from repro.kernels.quantize import kernel as qkernel
from repro.kernels.ssd_scan.ops import ssd_scan

# the chip smoke's flat round (chip_smoke.py): granite-3.0-1b-a400m at
# published widths, 2 layers, M = 2 client rows of P lane-padded elements
P_SMOKE = 157_360_128
M_SMOKE = 2
ROWS_SMOKE = M_SMOKE * P_SMOKE // LANES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernels(fn, *args) -> str:
    """The compiled program's tpu_custom_call ops (pallas_call names)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line.split("backend_config=")[0] for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, "no tpu_custom_call in the compiled program"
    return "\n".join(calls)


def _has(calls: str, name: str) -> bool:
    return re.search(rf"\b{name}\b", calls) is not None


# -- calibrated update: the flat round's local step ---------------------------

UPDATE_CASES = {
    # (x, g, c) dtypes: the f32 master round, and bf16 gradients applied
    # to f32 master rows
    "f32": ("float32", "float32", "float32"),
    "bf16_over_f32": ("float32", "bfloat16", "float32"),
}


@pytest.mark.parametrize("shape", [(ROWS_SMOKE, LANES), (M_SMOKE, P_SMOKE)],
                         ids=["rows_x_128", "client_rows"])
@pytest.mark.parametrize("dtypes", sorted(UPDATE_CASES))
@pytest.mark.parametrize("prox", [False, True], ids=["plain", "prox"])
def test_calibrated_update_compiles(one_chip, shape, dtypes, prox):
    x, g, c = (_sds(one_chip, shape, d) for d in UPDATE_CASES[dtypes])
    if prox:
        calls = _kernels(
            lambda x, g, c, a: calibrated_update_prox_2d(x, g, c, a, 0.1,
                                                         0.5, 0.01),
            x, g, c, x)
        assert _has(calls, "calibrated_update_prox")
    else:
        calls = _kernels(
            lambda x, g, c: calibrated_update_2d(x, g, c, 0.1, 0.5),
            x, g, c)
        assert _has(calls, "calibrated_update")


# -- flash attention forward + backward (training path) -----------------------

@pytest.mark.parametrize("arch,batch", [("granite-moe-1b-a400m", 4),
                                        ("gemma-2b", 2)])
def test_flash_attention_fwd_bwd_compiles(one_chip, arch, batch):
    cfg = get_arch(arch)
    hd, s = cfg.resolved_head_dim, 1024
    q = _sds(one_chip, (batch, s, cfg.n_heads, hd), "bfloat16")
    kv = _sds(one_chip, (batch, s, cfg.n_kv_heads, hd), "bfloat16")

    def loss(q, k, v):
        out = flash_attention_diff(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    calls = _kernels(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert _has(calls, name), name


# -- wire compression kernels on the flat client rows -------------------------

def test_quantize_kernels_compile(one_chip):
    x = _sds(one_chip, (M_SMOKE, P_SMOKE), "float32")
    scale = _sds(one_chip, (M_SMOKE, 1), "float32")

    def codec(x, scale):
        q = qkernel.quantize_2d(x, scale, qmax=127)
        return qkernel.dequantize_2d(q, scale), qkernel.topk_mask_2d(x, scale)

    calls = _kernels(codec, x, scale)
    for name in ("quantize", "dequantize", "topk_mask"):
        assert _has(calls, name), name


# -- Mamba2 SSD scan at zamba2-2.7b's SSM widths ------------------------------

def test_ssd_scan_compiles(one_chip):
    cfg = get_arch("zamba2-2.7b")
    ssm = cfg.ssm
    heads = ssm.expand * cfg.d_model // ssm.head_dim
    b, l = 1, 4096
    x = _sds(one_chip, (b, l, heads, ssm.head_dim), "float32")
    dt = _sds(one_chip, (b, l, heads), "float32")
    a = _sds(one_chip, (heads,), "float32")
    bc = _sds(one_chip, (b, l, ssm.n_groups, ssm.d_state), "float32")
    calls = _kernels(
        lambda x, dt, a, B, C: ssd_scan(x, dt, a, B, C, ssm.chunk,
                                        interpret=False),
        x, dt, a, bc, bc)
    assert _has(calls, "ssd_scan")
