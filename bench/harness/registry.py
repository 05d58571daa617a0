"""Find the benchmark's parts by name: one file per configuration, traffic
mix, cell, per-layer metric, model kind, driver and graph generator.

  bench/configs/<config>.json    graph, model, fleet, source, reduced, assumed
  bench/traffic/<mix>.json       the mix's parameters; ``driver`` names the loop
  bench/workloads/<cell>.json    config, traffic, chips and the cell's own
                                 parameters (rate, limits)
  bench/metrics/<metric>.py      ``read(run) -> float | None``
  bench/kinds/<kind>.py          a configuration's ``model.kind``: its weight
                                 layout, reference layer, operation and byte
                                 counts and the program's ``GNNConfig``
                                 fields
  bench/drivers/<driver>.py      a mix's ``driver``: ``Driver(cell, seed,
                                 seconds, devices)`` that builds and drives
                                 the system under test, and
                                 ``compared_rows(params, n, seconds, seed)``
  bench/generators/<gen>.py      a configuration's ``graph.generator``:
                                 ``generate(n, links, seed, area)``

A later PR adds a cell, a mix, a configuration, a metric, a model kind, a
driver or a graph generator by adding files; nothing here names one.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
METRICS = BENCH / "metrics"
KINDS = BENCH / "kinds"
DRIVERS = BENCH / "drivers"
GENERATORS = BENCH / "generators"


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


@functools.cache
def _module(path: Path):
    """The Python file at ``path``, run once per process."""
    if not path.is_file():
        raise ValueError(f"no {path.parent.name} file named {path.stem!r}")
    stem = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module(METRICS / f"{name}.py").read


def kind(name: str):
    """The model kind's module: ``weights``, ``layer``, ``flops``,
    ``bytes``, ``config_fields``."""
    return _module(KINDS / f"{name}.py")


def driver(name: str):
    """The driver's module: ``Driver``, ``compared_rows``."""
    return _module(DRIVERS / f"{name}.py")


def generator(name: str):
    """``generate(n, links, seed, area) -> (edges, coords)``."""
    return _module(GENERATORS / f"{name}.py").generate
