"""The readers of the BSP forward's program time, its HBM roofline share
and the exposed halo exchange, on constructed runs."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from harness import registry, trace  # noqa: E402

MS = 10 ** 6


def _run(chips, refreshes, modules):
    tr = trace.Trace(ops=[[]] * chips, modules=modules,
                     async_ops=[[]] * chips, host=[], window=(0, 10 ** 9))
    return types.SimpleNamespace(trace=tr, chips=chips,
                                 counters={"refreshes": refreshes})


def test_bsp_fwd_device_ms_is_the_program_time_per_refresh_and_chip():
    read = registry.metric_reader("bsp_fwd_device_ms")
    mods = [[("jit_bsp_forward(3)", 0, MS), ("jit_inner(3)", 0, 10 * MS),
             ("jit_bsp_forward(3)", 2 * MS, 3 * MS)],
            [("jit_bsp_forward(3)", 0, 3 * MS)]]
    # 5 ms over 2 chips and 2 refreshes
    assert read(_run(2, 2, mods)) == pytest.approx(1.25)
    assert read(_run(2, 0, mods)) is None
    # a program under another name (an older build) reads nothing
    assert read(_run(2, 2, [mods[0][1:2]] * 2)) is None


def test_refresh_hbm_share_is_the_least_bytes_over_program_time_at_peak():
    read = registry.metric_reader("refresh_hbm_share")
    mods = [[("jit_bsp_forward(3)", 0, MS), ("jit_inner(3)", 0, 10 * MS),
             ("jit_bsp_forward(3)", 2 * MS, 3 * MS)],
            [("jit_bsp_forward(3)", 0, 3 * MS)]]
    run = _run(2, 2, mods)
    run.config = {"model": {"kind": "gcn", "layer_dims": [3, 2, 1],
                            "dtype": "float32"}}
    run.n, run.arcs, run.device_kind = 4, 8, "TPU v5 lite"
    # 320 bytes a forward (test_bench_work's hand count), 2 refreshes,
    # 5 ms of the program summed over both chips, at 819 GB/s
    assert read(run) == pytest.approx(100.0 * 320 * 2 / (5e-3 * 819e9))
    run.counters["refreshes"] = 0
    assert read(run) is None
    # no program time traced: nothing, not 0
    run.counters["refreshes"] = 2
    run.trace = _run(2, 2, [mods[0][1:2]] * 2).trace
    assert read(run) is None


def test_exchange_exposed_ms_is_the_bare_exchange_per_refresh_and_chip():
    read = registry.metric_reader("exchange_exposed_ms")
    # device 0: a permute [0, 4] ms under a fusion [0, 1] ms: 3 ms bare;
    # device 1: an asynchronous permute [2, 6] ms, a fusion [5, 8] ms: 3 ms
    ops = [[("collective-permute.1", 0, 4 * MS), ("fusion.2", 0, MS)],
           [("fusion.3", 5 * MS, 8 * MS)]]
    aops = [[], [("collective-permute-start.4", 2 * MS, 6 * MS)]]
    tr = trace.Trace(ops=ops, modules=[[]] * 2, async_ops=aops, host=[],
                     window=(0, 10 ** 9))
    run = types.SimpleNamespace(trace=tr, chips=2,
                                counters={"refreshes": 3})
    # (3 + 3) / 2 chips = 3 ms over 3 refreshes
    assert read(run) == pytest.approx(1.0)
    run.counters["refreshes"] = 0
    assert read(run) is None
    # a trace with no exchange (one chip) reads nothing, not 0
    one = trace.Trace(ops=[ops[0][1:]], modules=[[]], async_ops=[[]],
                      host=[], window=(0, 10 ** 9))
    assert read(types.SimpleNamespace(trace=one, chips=1,
                                      counters={"refreshes": 3})) is None
