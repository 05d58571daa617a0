"""The reader of the BSP forward's program time, on constructed runs."""
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
