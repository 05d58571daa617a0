"""Find the benchmark's parts by name: one file per configuration, traffic
mix, cell and per-layer metric.

  bench/configs/<config>.json    graph, model, fleet, source, reduced, assumed
  bench/traffic/<mix>.json       the mix's parameters; ``driver`` names the loop
  bench/workloads/<cell>.json    config, traffic, chips and the cell's own
                                 parameters (rate, limits)
  bench/metrics/<metric>.py      ``read(run) -> float | None``

A later PR adds a cell, a mix, a configuration or a metric by adding files;
nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(name: str) -> dict:
    """The cell's file with its configuration and mix resolved.  The mix's
    parameters are the defaults; the cell's ``params`` override them."""
    spec = load_json("workloads", name)
    mix = load_json("traffic", spec["traffic"])
    params = {**mix, **spec.get("params", {})}
    return {"name": name, "chips": int(spec["chips"]),
            "config": load_json("configs", spec["config"]),
            "config_name": spec["config"], "traffic": spec["traffic"],
            "params": params, "limits": spec["limits"]}


def cell_metrics(bench: dict, cell_name: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that this cell reports."""
    def mine(m):
        return "workloads" not in m or cell_name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in names]
    return e2e, layer


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
