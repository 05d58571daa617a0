"""The benchmark's trace reduction on a constructed two-device trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from harness import trace  # noqa: E402

NS = 1e-9


@pytest.fixture
def tr():
    dev0 = [("fusion.1", 0, 10), ("fusion.2", 5, 20),
            ("collective-permute-start.1", 20, 22),
            ("collective-permute-done.1", 22, 30), ("_kernel", 40, 50),
            ("late", 95, 130)]
    dev1 = [("convolution.3", 0, 50), ("collective-permute-done.2", 10, 20)]
    host = [("tick", 0, 32), ("wait", 32, 100)]
    mods = [[("jit__fwd(7)", 0, 30)], [("jit__fwd(7)", 0, 50)]]
    # an asynchronous exchange on device 1 from 45 to 60: exposed 50 to 60
    aops = [[], [("collective-permute-start.3", 45, 60)]]
    return trace.Trace(ops=[dev0, dev1], modules=mods, async_ops=aops,
                       host=host, window=(0, 100))


def test_union_merges_touching_and_overlapping():
    assert trace.union([(5, 20), (0, 10), (20, 30), (40, 50)]) == \
        [(0, 30), (40, 50)]
    assert trace.length([(0, 10), (5, 20), (40, 50)]) == 30


def test_busy_is_the_union_clipped_to_the_window(tr):
    # device 0: [0, 30] + [40, 50] + [95, 100] = 45 ns; device 1: 50 ns
    assert trace.busy_s(tr) == pytest.approx((45 + 50) / 2 * NS)
    assert trace.idle_share(tr) == pytest.approx(1 - 47.5 / 100)
    assert tr.window_s == pytest.approx(100 * NS)


def test_time_by_name_sums_matching_events_over_devices(tr):
    assert trace.time_by_name(tr, r"^collective-permute") == \
        pytest.approx((2 + 8 + 10) * NS)
    assert trace.time_by_name(tr, r"_kernel") == pytest.approx(10 * NS)
    assert trace.time_by_name(tr, r"^jit__fwd\b", programs=True) == \
        pytest.approx(80 * NS)


def test_exposed_collective_excludes_time_under_compute(tr):
    # device 0's exchange [20, 30] runs alone; device 1's [10, 20] sits
    # under compute and its asynchronous one [45, 60] is bare from 50
    assert trace.exposed_s(tr, r"^collective-permute") == \
        pytest.approx((10 + 10) / 2 * NS)


def test_op_names_are_the_hlo_instructions():
    text = "%spmm.2 = f32[8,52]{1,0} custom-call(s32[1,2] %b), x=\"a = b\""
    assert trace.op_name(text) == "spmm.2"
    assert trace.op_name("jit__fwd(123)") == "jit__fwd(123)"


def test_top_ops_group_names_without_their_suffix(tr):
    top = dict(trace.top_ops(tr))
    assert top["fusion"] == pytest.approx(25 * NS)      # 10 + 15
    assert top["convolution"] == pytest.approx(50 * NS)
    assert top["late"] == pytest.approx(5 * NS)          # clipped at 100


def test_idle_gaps_go_to_the_host_span_covering_most_of_them(tr):
    # device 0 idles in [30, 40] (tick 2 ns, wait 8 ns) and [50, 95] (wait)
    assert trace.idle_gaps(tr) == [["wait", pytest.approx(55 * NS)]]


def test_align_bounds_the_window_by_markers_and_moves_host_spans():
    modules0 = [("jit_bench_window_mark(5)", 1000, 1010),
                ("jit__fwd(7)", 1020, 1030),
                ("jit_bench_window_mark(5)", 1900, 1905)]
    # the first marker was dispatched at host time 400: offset 600
    window, host = trace.align(modules0, [("wait", 450, 500),
                                          ("tick", 410, 440)], 400)
    assert window == (1010, 1900)
    assert host == [("tick", 1010, 1040), ("wait", 1050, 1100)]
    with pytest.raises(RuntimeError):
        trace.align(modules0[:2], [], 0)


def test_host_span_records_only_when_tracing():
    spans = []
    with trace.host_span(spans, "tick"):
        pass
    with trace.host_span(None, "tick"):
        pass
    assert len(spans) == 1 and spans[0][0] == "tick" and spans[0][2] >= spans[0][1]
