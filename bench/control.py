#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed wholly in bfloat16, the precision below the
float32 the configurations state (their matmul precision kept).  It must
read above the limit of one of the cell's numbers.

  python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it builds the cell's inputs as a run does, computes the
reference at the configuration's precision and the bfloat16 control,
and compares the control's rows that a run compares (the window's request
targets, or every vertex for a refresh) by the run's own number.  One JSON
line per seed.  Benchmark runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import check, graphs, inputs, reference, registry  # noqa: E402


def control_readings(cell: dict, seed: int, seconds: float,
                     device=None) -> dict:
    """The cell's compared numbers, read off the control."""
    cfg, prm = cell["config"], cell["params"]
    n, edges, _ = graphs.build(cfg["graph"])
    model = cfg["model"]
    feats, weights = inputs.make(model, n, seed, device)
    ref_mode, low_mode = reference.modes(model)
    ref = reference.forward(model, weights, feats, edges, ref_mode)
    low = reference.forward(model, weights, feats, edges, low_mode)
    rows = registry.driver(prm["driver"]).compared_rows(prm, n, seconds,
                                                        seed)
    got = check.readings(low[rows], ref[rows], check.scale_of(ref))
    return {k: got[k] for k in cell["limits"]}


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    seconds = args.seconds or registry.benchmark()["run_seconds"]
    import jax

    run._configure_jax(jax)
    try:
        devices = run.require_chips(1)
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        got = control_readings(cell, seed, seconds, devices[0])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": got, "limits": cell["limits"],
                          "device": devices[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
