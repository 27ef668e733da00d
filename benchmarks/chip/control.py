#!/usr/bin/env python3
"""The check's control and its planted faults, read at a cell's own size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --variants fp8,half_batch,bf16

A stand-in takes the program's place and is compared with the plain
reference by the very numbers a run compares; one JSON line per seed and
stand-in.  The reference runs once per seed.

* ``fp8``: the reference computed with every matmul's operands rounded to
  float8 e4m3 (per-tensor scale), the precision step below the bfloat16
  the configurations state.  It has to fail the check.
* ``half_batch``: the reference with half of every batch left out, the
  mean taken over the rest.  A planted fault; it has to fail too.
* ``bf16``: the reference with bfloat16 matmul operands, the precision
  the configurations state: a witness of how far rounding alone carries
  the rounds apart, beside the program's own readings.

* ``unchanged``: a step that returns its state unchanged, the state the
  program starts from with its round losses all the first round's; it
  needs no run of its own.

The benchmark's own runs never run this; it sets the limits' upper
readings (PERF.md).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402

VARIANTS = {"fp8": {"matmul": "fp8"},
            "bf16": {"matmul": "bf16"},
            "half_batch": {"half": True},
            "unchanged": {"unchanged": True}}


def readings(ctx, variants: list[str]) -> dict:
    """{variant: the stand-in's readings against the reference}."""
    import jax
    train = ctx.module("drivers", ctx.traffic["driver"])
    run = train.build(ctx)
    host0 = jax.device_get(run.pop("params"))
    args = (ctx, host0, run["feed"], run["ks"], run["r"])
    ref = train.reference_run(*args)
    out = {}
    for variant in variants:
        kw = dict(VARIANTS[variant])
        if kw.pop("unchanged", False):
            zeros = jax.tree.map(np.zeros_like, host0)
            out[variant] = train.compare(host0, {
                "losses": [ref["losses"][0]] * len(ref["losses"]),
                "params": host0, "nu": zeros,
                "nu_i": [zeros] * ctx.traffic["clients"]}, ref)
            continue
        if kw.pop("half", False):
            kw["batch_rows"] = slice(0, ctx.traffic["batch"] // 2)
        out[variant] = train.compare(host0, train.reference_run(*args, **kw),
                                     ref)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", required=True,
                    help=f"comma-separated, of {sorted(VARIANTS)}")
    args = ap.parse_args(argv)
    variants = args.variants.split(",")
    for v in variants:
        if v not in VARIANTS:
            ap.error(f"unknown variant {v!r}")
    for seed in (int(s) for s in args.seeds.split(",")):
        tic = time.perf_counter()
        ctx = harness.context(args.workload, seed, 0.0, False, tic)
        for variant, got in readings(ctx, variants).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": variant, "readings": got,
                              "seconds": time.perf_counter() - tic}),
                  flush=True)


if __name__ == "__main__":
    main()
