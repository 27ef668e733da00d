"""Benchmark aggregator: one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME[,NAME…]]

Each module prints CSV rows; headers carry the claim being validated in
the module docstring.
"""
from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (ablation_int8_nu, compression_bench, engine_bench,
                        fairness, fig2_lambda, fig3_orientation, fig4_grid,
                        fig5_curves, kernel_bench, lm_bench,
                        population_bench, robust_bench, roofline_table,
                        scenario_bench, server_opt, serving_bench,
                        table1_deterioration, table2_utilization,
                        table6_rounds, table_async, thm1_quadratic)
from repro.launch.cache import setup_compile_cache

MODULES = {
    "thm1": thm1_quadratic,
    "table1": table1_deterioration,
    "table2": table2_utilization,
    "fig2": fig2_lambda,
    "fig3": fig3_orientation,
    "fig4": fig4_grid,
    "table6": table6_rounds,
    "table_async": table_async,
    "fig5": fig5_curves,
    "kernel": kernel_bench,
    "int8_nu": ablation_int8_nu,
    "compression": compression_bench,
    "fairness": fairness,
    "server_opt": server_opt,
    "roofline": roofline_table,
    "engine": engine_bench,
    "lm": lm_bench,
    "population": population_bench,
    "scenarios": scenario_bench,
    "robust": robust_bench,
    "serving": serving_bench,
}


def parse_only(only: str | None) -> list[str]:
    """Validate ``--only``: whitespace-tolerant, order-preserving dedup, and
    a fail-fast error naming every valid module for any unknown (or empty)
    selection — never a silent no-op run."""
    if only is None:
        return list(MODULES)
    names = [n.strip() for n in only.split(",") if n.strip()]
    names = list(dict.fromkeys(names))
    unknown = [n for n in names if n not in MODULES]
    if unknown or not names:
        what = (f"unknown module(s) {unknown}" if unknown
                else f"--only {only!r} selects nothing")
        raise SystemExit(f"error: {what}; choose from {sorted(MODULES)}")
    return names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds/grids (CI budget)")
    ap.add_argument("--only", default=None, metavar="NAME[,NAME…]",
                    help=f"comma-separated subset of {sorted(MODULES)}")
    args = ap.parse_args()
    setup_compile_cache()

    names = parse_only(args.only)
    failures = []
    for name in names:
        mod = MODULES[name]
        print(f"\n# ===== {name}: {mod.__doc__.strip().splitlines()[0]}")
        t0 = time.time()
        try:
            mod.main(quick=args.quick)
            print(f"# {name} done in {time.time() - t0:.1f}s")
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
