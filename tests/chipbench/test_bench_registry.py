"""Every part of the benchmark is a file that the harness finds by name
(cells, mixes, configurations, metrics, model kinds, drivers, graph
generators), and ``BENCHMARK.json`` agrees with those files."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import registry  # noqa: E402

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _stems(kind, suffix):
    return sorted(p.name[: -len(suffix)]
                  for p in (ROOT / "bench" / kind).glob("*" + suffix))


def test_every_file_is_named_in_benchmark_json_and_back():
    assert _stems("workloads", ".json") == sorted(CELLS)
    assert _stems("metrics", ".py") == sorted(m["name"]
                                              for m in BENCH["per_layer"])
    assert _stems("configs", ".json") == sorted(c["name"]
                                                for c in BENCH["configs"])
    assert set(_stems("traffic", ".json")) == {w["traffic"]
                                              for w in BENCH["workloads"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    cell = registry.cell(name)
    assert cell["chips"] == entry["chips"]
    assert cell["config_name"] == entry["config"]
    assert cell["traffic"] == entry["traffic"]
    driver = registry.driver(cell["params"]["driver"])
    assert callable(driver.Driver) and callable(driver.compared_rows)
    assert set(cell["limits"]) <= {"max_err", "mean_err"} and cell["limits"]
    e2e, layer = registry.cell_metrics(BENCH, name)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layer


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads_by_name(name):
    assert callable(registry.metric_reader(name))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_kind_and_generator_load_by_name(name):
    cfg = registry.load_json("configs", name)
    kind = registry.kind(cfg["model"]["kind"])
    for part in ("weights", "layer", "flops", "bytes", "config_fields"):
        assert callable(getattr(kind, part))
    assert callable(registry.generator(cfg["graph"]["generator"]))


@pytest.mark.parametrize("loader", [registry.kind, registry.driver,
                                    registry.generator,
                                    registry.metric_reader],
                         ids=lambda f: f.__name__)
def test_an_unknown_name_is_refused(loader):
    with pytest.raises(ValueError, match="no_such_part"):
        loader("no_such_part")


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_matches_its_entry(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(ROOT / entry["file"]) as f:
        cfg = json.load(f)
    assert cfg["name"] == name and cfg["reduced"] == entry["reduced"]
    assert cfg["model"]["layer_dims"][0] == cfg["graph"]["feat_dim"]


def test_names_units_and_keys_keep_to_the_contract():
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert all(c in CELLS for c in m.get("workloads", CELLS))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(BENCH["workloads"])


def test_stall_share_is_the_stalled_ticks_share_of_the_window():
    import types

    read = registry.metric_reader("tick_stall_share")
    run = types.SimpleNamespace(counters={"ticks": 400, "stall_s": 0.25,
                                          "wall_s": 40.0})
    assert read(run) == 100.0 * 0.25 / 40.0
    run.counters["ticks"] = 0
    assert read(run) is None
