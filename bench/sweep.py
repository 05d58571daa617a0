#!/usr/bin/env python3
"""Find an ego cell's capacity once, by a sweep of offered rates on the chip.

  python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 --seconds 10

For each rate it builds the cell afresh at that rate and runs one window,
then prints one JSON line: the latency percentiles of the requests
answered, ticks and requests per tick, and whether the backlog grew (the
95th percentile of the window's last fifth of requests against its first
fifth, and the requests left unanswered at the close).  The highest rate
with no growing backlog is the cell's capacity; an ``ego-zipf`` cell offers
1.5 times that.  Benchmark runs do not run it.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from harness import registry  # noqa: E402


def main(argv=None) -> int:
    import run

    ego = registry.driver("ego")

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = registry.cell(args.workload)
    import jax

    run._configure_jax(jax)
    try:
        devices = run.require_chips(base["chips"])
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell["params"]["rate_rps"] = rate
        d = ego.Driver(cell, args.seed, args.seconds, devices)
        d.window(args.seconds, None)
        lat = (d.done - d.due) * 1e3
        fifth = max(len(lat) // 5, 1)
        c = d.counters()
        print(json.dumps({
            "workload": args.workload, "rate_rps": rate,
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p95_ms": float(np.nanpercentile(lat, 95)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "p95_first_fifth_ms": float(np.nanpercentile(lat[:fifth], 95)),
            "p95_last_fifth_ms": float(np.nanpercentile(lat[-fifth:], 95)),
            "unanswered": int(np.isnan(lat).sum()),
            "tick_ms": c["tick_wall_s"] * 1e3 / max(c["ticks"], 1),
            "batch_fill": c["requests"] / max(c["ticks"], 1)}), flush=True)
        d.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
