"""The whole harness, run on XLA CPU at a tiny graph size.

One child process (``cpu_drive.py``, four virtual CPU devices) runs every
cell once as it is, and once with each fault the cell can have planted in
its timed path; each must then read ``correct: false``.  The look for a
chip is steered there, never through an option of the program.
"""
import copy
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import registry  # noqa: E402

BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
N = 300
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _faults(cell):
    c = registry.cell(cell)
    faults = ["answer", "half", "unchanged"]
    if c["params"]["driver"] == "refresh" and c["params"]["partitions"] > 1:
        faults.append("exchange")
    return faults


JOBS = ([{"cell": c, "seed": 2 ** 31 + 17, "seconds": 1, "n": N,
          "fault": None} for c in CELLS]
        + [{"cell": c, "seed": 23, "seconds": 1, "n": N, "fault": f}
           for c in CELLS for f in _faults(c)])


def _job_id(job):
    return f"{job['cell']}-{job['fault'] or 'sound'}"


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "chipbench" / "cpu_drive.py"),
         json.dumps(JOBS)], env=env, capture_output=True, text=True,
        timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = {}
    for line in proc.stdout.splitlines():
        r = json.loads(line)
        out[_job_id(r["job"])] = r
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_prints_the_contract_line(results, cell):
    r = results[_job_id({"cell": cell, "fault": None})]
    assert r["rc"] == 0
    line = r["result"]
    assert list(line)[: len(KEYS)] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    e2e, _ = registry.cell_metrics(BENCH, cell)
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    units = {m["name"]: m["unit"] for m in e2e}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    dev = line["device"]
    assert dev["count"] == registry.cell(cell)["chips"]
    assert {"platform", "kind", "memory_peak_bytes"} <= set(dev)
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("job", JOBS[len(CELLS):], ids=_job_id)
def test_planted_fault_reads_incorrect(results, job):
    r = results[_job_id(job)]
    assert r["rc"] == 0 and r["result"]["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit_at_a_small_size(cell):
    import control

    c = copy.deepcopy(registry.cell(cell))
    g = c["config"]["graph"]
    g["links"] = int(round(g["links"] * N / g["n"]))
    g["n"] = N
    got = control.control_readings(c, 5, 1.0)
    assert any(got[k] > limit for k, limit in c["limits"].items())


def test_no_chip_no_result(capsys, monkeypatch):
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    # keep this worker's JAX settings for the tests that follow
    monkeypatch.setattr(run, "_configure_jax", lambda jax: None)
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""
