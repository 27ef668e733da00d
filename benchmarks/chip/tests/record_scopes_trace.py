#!/usr/bin/env python3
"""Records ``data/scopes.xplane.pb``, the small trace the scope reduction
is tested on.  Run on one TPU from the root of a checkout:

    python3 benchmarks/chip/tests/record_scopes_trace.py <out.xplane.pb>

The flat FedaGrac round (M = 2, K_i [[1, 2], [2, 1]], so k_max 2, bf16
under a float32 master) of granite-moe cut to d_model 256, 1 layer, 8
experts top-2, vocab 4096, seq 128, batch 2: one 2-round chunk compiles,
the next is traced inside ``bench.window`` with the Python tracer off.
The file keeps the device planes' ``XLA Ops`` lines and the host's
``bench.*`` and ``fed.*`` spans, with the metadata they refer to.
"""
import dataclasses
import functools
import glob
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(HERE))), "src"))

import scopes  # noqa: E402

KEEP_HOST = ("bench.", "fed.")


def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _field(num: int, val) -> bytes:
    """One field, re-encoded: bytes as length-delimited, an int as a
    varint (the only two wire types of XPlane and XLine)."""
    if isinstance(val, memoryview):
        return _varint(num << 3 | 2) + _varint(len(val)) + bytes(val)
    return _varint(num << 3) + _varint(val)


def _msg(pairs) -> memoryview:
    return memoryview(b"".join(_field(n, v) for n, v in pairs))


def trim(space: bytes) -> bytes:
    """The XSpace with only what the reductions read: a device's ``XLA
    Ops`` line, a host line's events named ``KEEP_HOST``, the metadata
    those events refer to, and of a metadata entry's stats only
    ``tf_op``."""
    planes = []
    for num, plane in scopes.fields(space):
        if num != 1:
            continue
        name, _, ev_meta, st_meta = scopes.plane_parts(plane)
        is_device = name.startswith("/device:TPU:")
        if not (is_device or name.startswith("/host:")):
            continue
        metas = scopes.entries(ev_meta)
        tf_op = {k for k, v in scopes.entries(st_meta).items()
                 if scopes.text(dict(v).get(2, b"")) == "tf_op"}
        lines, used = [], set()
        for n, val in scopes.fields(plane):
            if n != 3:
                continue
            lf = scopes.fields(val)
            lname = next((scopes.text(v) for k, v in lf if k == 2), "")
            if is_device and lname != "XLA Ops":
                continue
            kept = []
            for k, v in lf:
                if k == 4:
                    mid = dict(scopes.fields(v)).get(1, 0)
                    if not is_device and not scopes.text(dict(
                            metas.get(mid, [])).get(2, b"")).startswith(
                                KEEP_HOST):
                        continue
                    used.add(mid)
                kept.append((k, v))
            lines.append((3, _msg(kept)))
        body = []
        for n, val in scopes.fields(plane):
            if n == 3:
                continue
            if n == 4:
                key = dict(scopes.fields(val)).get(1, 0)
                if key not in used:
                    continue
                meta = [(k, v) for k, v in metas[key]
                        if k != 5 or dict(scopes.fields(v)).get(1) in tf_op]
                val = _msg([(1, key), (2, _msg(meta))])
            body.append((n, val))
        planes.append((1, _msg(body + lines)))
    return bytes(_msg(planes))


def main(out_path: str) -> None:
    import jax
    import numpy as np

    from repro.configs.base import FedConfig, reduced
    from repro.configs.registry import get_arch
    from repro.data import DeviceLMBatcher, lm_sequences
    from repro.fed import FederatedSimulation
    from repro.models import model as model_lib

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scopes_trace: no TPU found")
    cfg = reduced(get_arch("granite-moe-1b-a400m"), n_layers=1,
                  d_model=256, max_experts=8, vocab=4096)
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    fed = FedConfig(algorithm="fedagrac", n_clients=2, lr=0.005,
                    calibration_rate=0.5, param_layout="flat",
                    master_dtype="float32")
    key = jax.random.PRNGKey(0)
    streams = [lm_sequences(jax.random.fold_in(key, i), 16, 128, cfg.vocab,
                            skew_topic=i) for i in range(2)]
    sim = FederatedSimulation(
        functools.partial(model_lib.lm_loss, cfg=cfg),
        model_lib.init_params(key, cfg), fed,
        DeviceLMBatcher(streams, batch_size=2, seed=0),
        k_schedule=np.array([[1, 2], [2, 1]]))
    sim.run(2, eval_every=2)
    logdir = tempfile.mkdtemp(prefix="scopes-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            sim.run(2, eval_every=2)
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    with open(out_path, "wb") as f:
        f.write(trim(raw))
    print(f"recorded {out_path}: {os.path.getsize(out_path)} bytes "
          f"(untrimmed {len(raw)})", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
