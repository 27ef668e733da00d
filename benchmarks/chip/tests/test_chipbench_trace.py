"""The trace reduction on a small trace recorded on one TPU v5e: three
rounds of the calibrated-update kernel on (2, 16M) float32 rows and of
flash attention forward and backward at granite's heads (batch 4, seq
1024), each round dispatched under ``bench.dispatch`` and waited for under
``bench.wait``."""
import os

import chipbench_tiny  # noqa: F401  (paths)
import pytest

import harness

TRACE = os.path.join(chipbench_tiny.HERE, "data", "kernels.xplane.pb")
KERNELS = ("calibrated_update", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


@pytest.fixture(scope="module")
def red():
    return harness.load_module(".", "trace").reduce(TRACE)


def test_kernels_found_by_name_with_their_shapes(red):
    k = red["kernels"]
    for name in KERNELS:
        assert k[name]["calls"] == 3, name
    assert k["calibrated_update"]["shapes"] == {"f32[2,16777216]": 3.0}
    assert k["flash_fwd"]["shapes"] == {"bf16[4,16,1024,128]": 3.0}
    assert k["flash_bwd_dkv"]["shapes"] == {"bf16[4,8,1024,128]": 3.0}
    # the device times the trace holds for these events, summed
    assert k["calibrated_update"]["seconds"] == pytest.approx(
        2.364781e-3, rel=1e-6)
    assert k["flash_fwd"]["seconds"] == pytest.approx(2.065037e-3, rel=1e-6)


def test_busy_union_and_idle(red):
    assert 0 < red["busy_s"] < red["window_s"]
    # the kernels are most of what ran
    kernel_s = sum(red["kernels"][n]["seconds"] for n in KERNELS)
    assert kernel_s <= red["busy_s"] <= kernel_s * 1.1
    assert red["chips"] == 1


def test_breakdown_lists(red):
    ops = red["device_ops"]
    assert len(ops) == 10
    assert [n for n, _ in ops[:4]] == ["flash_bwd_dkv.1",
                                       "calibrated_update.1", "flash_fwd.1",
                                       "flash_bwd_dq.1"]
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = red["idle_gaps"]
    assert len(gaps) == 10
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    # the two longest gaps are the host's waits between rounds
    labels = {g[0] for g in gaps[:2]}
    assert labels <= {"bench.wait", "no host span"}
    assert sorted(red["spans"]) == ["bench.dispatch", "bench.wait"]
    assert len(red["spans"]["bench.dispatch"]) == 3


def test_window_clips_events(red):
    tr = harness.load_module(".", "trace")
    full = tr.reduce(TRACE)
    first_op = min(full["spans"]["bench.dispatch"])  # any positive window
    assert first_op > 0
    none = tr.reduce(TRACE, window=(0, 1))
    assert none["busy_s"] == 0 and none["kernels"] == {}


def test_shape_parsing():
    tr = harness.load_module(".", "trace")
    assert tr.parse_shape("f32[2,128]") == ("f32", (2, 128))
    assert tr.out_shape("%flash_fwd.1 = (bf16[4,16,1024,128]{3,2,1,0}, "
                        "f32[4,16,1024]) custom-call()") == (
        "bf16", (4, 16, 1024, 128))
    assert tr.kernel_of("flash_bwd_dq.12") == "flash_bwd_dq"
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(...)") == "fusion.3"
