"""Benchmark driver: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--smoke]
                                          [--check-parity]

``--smoke`` runs CI-sized sanity passes (the layout-engine benchmark at
quick sizes plus the plan-patch and serving cells, one repetition, written
to BENCH_layout.smoke.json) so the harness can be exercised cheaply without
touching the committed numbers; it exits nonzero if the engine paths
disagree on any final cost, if a patched ShardPlan diverges from a fresh
compile, if the 8-device retrace counts are off, or if the serving cell's
oracle parity / traffic-aware ordering gates fail.

``--check-parity`` re-runs the quick grids and exits nonzero if any cell's
final cost diverges from the committed BENCH_layout.json beyond 1e-12
relative, or the plan-patch cell's traffic accounting drifts — the CI gate
against silent cost regressions.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro import compile_cache
from benchmarks import (adaptability, convergence, cost_comparison,
                        cost_factors, kernel_density, layout_engine,
                        overhead, plan_patch, roofline_table, sensitivity,
                        serving)

SECTIONS = [
    ("cost_comparison  (Fig. 8/9)", cost_comparison.run),
    ("cost_factors     (Fig. 10-13)", cost_factors.run),
    ("convergence      (Fig. 14/15)", convergence.run),
    ("adaptability     (Fig. 16)", adaptability.run),
    ("overhead         (Fig. 17/18)", overhead.run),
    ("sensitivity      (Fig. 19/20)", sensitivity.run),
    ("kernel_density   (ablation: layout -> MXU)", kernel_density.run),
    ("roofline_table   (deliverable g)", roofline_table.run),
    ("layout_engine    (engine vs seed, round solvers)", layout_engine.run),
    ("plan_patch       (incremental ShardPlan pipeline)", plan_patch.run),
    ("serving          (request-driven ego inference)", serving.run),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale graphs (slow)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized sanity pass (layout_engine quick, 1 rep, "
                         "separate output file; fails on cost mismatch)")
    ap.add_argument("--check-parity", action="store_true",
                    help="re-run the quick grid and fail if any final cost "
                         "diverges from the committed BENCH_layout.json")
    args = ap.parse_args()
    if args.check_parity:
        rc = layout_engine.check_parity()
        rc = plan_patch.check_parity() or rc
        rc = serving.check_parity() or rc
        sys.exit(rc)
    if args.smoke:
        print("\n===== smoke: layout_engine (quick, 1 rep) =====")
        t0 = time.perf_counter()
        rc = layout_engine.run(smoke=True)
        print(f"# smoke wall time: {time.perf_counter() - t0:.1f}s")
        print("\n===== smoke: plan_patch (quick, 1 rep) =====")
        t0 = time.perf_counter()
        rc = plan_patch.run(smoke=True) or rc
        print(f"# smoke wall time: {time.perf_counter() - t0:.1f}s")
        print("\n===== smoke: serving (quick) =====")
        t0 = time.perf_counter()
        rc = serving.run(smoke=True) or rc
        print(f"# smoke wall time: {time.perf_counter() - t0:.1f}s")
        sys.exit(rc or 0)
    for name, fn in SECTIONS:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        fn(full=args.full)
        print(f"# section wall time: {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    compile_cache.enable()
    main()
