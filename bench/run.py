#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files (``bench/workloads/<cell>.json`` and the configuration and
traffic mix it names) say what to build and drive; ``BENCHMARK.json`` says
which metrics the cell reports.  Set-up builds the graph, the inputs from
``--seed``, the program's layout and plan, and warms every shape the window
uses.  The window then measures for ``--seconds``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the same window.  After the
window the outputs are compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each compared number with its limit.
The run refuses to start without a TPU or with fewer chips than the cell
asks for, and then prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import json
import math
import sys
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from harness import check, registry  # noqa: E402

CACHE_DIR = ROOT / ".bench_cache" / "jax"


class NoChip(RuntimeError):
    pass


def require_chips(count: int) -> list:
    """The first ``count`` TPU devices; raises ``NoChip`` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < count:
        raise NoChip(f"needs {count} chips, JAX found {len(devices)}")
    return devices[:count]


def _configure_jax(jax) -> None:
    # A fixed directory inside the checkout: only a cell's first run there
    # compiles.  It overrides JAX_COMPILATION_CACHE_DIR, which a machine may
    # share between checkouts.
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No size bound: a bounded cache keeps access-time files beside its
    # entries and fails on a directory that another setting wrote.
    jax.config.update("jax_compilation_cache_max_size", -1)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.cell(args.workload)
    e2e, layer = registry.cell_metrics(registry.benchmark(), args.workload)

    import jax

    from harness import trace as tr
    from harness.clock import CompileClock

    _configure_jax(jax)
    try:
        devices = require_chips(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    clock = CompileClock()
    module = registry.driver(cell["params"]["driver"])
    driver = module.Driver(cell, args.seed, args.seconds, devices)
    mark = jax.jit(tr.bench_window_mark)
    mark_arg = jax.device_put(np.zeros(8, np.float32), devices[0])
    jax.block_until_ready(mark(mark_arg))
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s:.3f} s, {clock.compiles} backend compiles "
          f"({clock.seconds:.3f} s), {clock.cache_hits} compile-cache hits",
          file=sys.stderr)

    # Set-up's objects are long-lived: keep the collector from walking
    # them again during the window.
    gc.collect()
    gc.freeze()
    before = clock.compiles
    spans = [] if args.trace else None
    if args.trace:
        session = tr.start_profiler()
        mark_ns = time.perf_counter_ns()
        jax.block_until_ready(mark(mark_arg))
    values = driver.window(args.seconds, spans)
    if args.trace:
        jax.block_until_ready(mark(mark_arg))
        t_stop = time.perf_counter()
        profile = session.stop_and_get_profile_data()
        t_stop = time.perf_counter() - t_stop
    print(f"compiles in window: {clock.compiles - before}", file=sys.stderr)
    print(f"window: {values} {driver.counters()}", file=sys.stderr)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    values["setup_s"] = setup_s
    result_extra = {}
    if args.trace:
        t_reduce = time.perf_counter()
        trace = tr.load(profile, len(devices), spans, mark_ns)
        profile = None
        t_load = time.perf_counter() - t_reduce
        run = types.SimpleNamespace(
            config=cell["config"], device_kind=devices[0].device_kind,
            chips=len(devices), trace=trace, counters=driver.counters(),
            n=int(cell["config"]["graph"]["n"]),
            arcs=2 * int(cell["config"]["graph"]["links"]))
        metrics = {}
        for m in layer:
            v = registry.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = trace.window_s
        result_extra["breakdown"] = {"device_ops": tr.top_ops(trace),
                                     "idle_gaps": tr.idle_gaps(trace)}
        print(f"trace: {sum(len(e) for e in trace.ops + trace.async_ops)} "
              f"operations, profiler stopped in {t_stop:.3f} s, loaded in "
              f"{t_load:.3f} s, {time.perf_counter() - t_reduce:.3f} s "
              f"with every reader", file=sys.stderr)
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}

    driver.release()
    correct, checks = check.judge(driver.readings(), cell["limits"])
    correct = correct and driver.failed == 0
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    for c in checks.values():
        c["value"] = _finite(c["value"])
    print(json.dumps({"correct": bool(correct),
                      "attempted": int(driver.attempted),
                      "failed": int(driver.failed), "metrics": metrics,
                      "device": device, **result_extra, "checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
