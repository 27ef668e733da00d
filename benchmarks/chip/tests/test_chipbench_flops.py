"""The operation and byte counts against hand counts at the
configuration's published widths (sequence 1024)."""
import chipbench_tiny  # noqa: F401  (paths)
import pytest

import harness


def _cfg(name):
    return harness.load_json("configs", name)


def test_granite_train_flops_per_token():
    f = harness.load_module("flops", "granite-moe-1b-a400m-2l")
    # per layer: q,k,v,o 2·1024·(1024+512+512) + 2·1024·1024 = 6,291,456;
    # causal attention 4·16·64·1025/2 = 2,099,200; router 2·1024·32 =
    # 65,536; 8 experts × 3 × 2·1024·512 = 25,165,824
    layer = 6_291_456 + 2_099_200 + 65_536 + 25_165_824
    head = 2 * 1024 * 49155
    assert f.forward_per_token(_cfg("granite-moe-1b-a400m-2l"), 1024) == \
        2 * layer + head == 167_913_472
    assert f.train_per_token(_cfg("granite-moe-1b-a400m-2l"), 1024) == \
        pytest.approx(503_740_416)


def test_kernel_counts():
    k = harness.load_module(".", "flops/kernels")
    assert k.calibrated_update("f32", (2, 16_777_216)) == (
        134_217_728.0, 536_870_912.0)
    att = {"n_heads": 16, "n_kv_heads": 8, "head_dim": 64}
    flops, byts = k.flash_fwd("bf16", (4, 16, 1024, 128), **att)
    assert flops == 2 * 4 * 16 * 64 * 1024 ** 2
    assert byts == 2 * 4 * 1024 * 64 * (2 * 16 + 2 * 8) + 4 * 4 * 16 * 1024
    # the backward kernels each need two of the four backward matmuls
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert k.FLASH[name]("bf16", (4, 16, 1024, 128), **att)[0] == flops
    # a client axis vmapped in front is more batch
    assert k.flash_fwd("bf16", (2, 4, 16, 1024, 128), **att)[0] == 2 * flops
