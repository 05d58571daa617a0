"""The benchmark's trace reduction on a constructed two-device trace, and
its array reducers against the list-based oracle kept below."""
import bisect
import re
import sys
import types
from pathlib import Path

import numpy as np
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


# ----------------------------------------------------------------- oracle
# The reduction as it was first written, event by event over lists of
# (name, start_ns, end_ns): the array reducers must give the same numbers.
def _o_clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _o_union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _o_length(intervals):
    return sum(e - s for s, e in _o_union(intervals))


def _o_busy_s(ops, window):
    lo, hi = window
    per = [_o_length((s, e) for _, s, e in _o_clip(o, lo, hi)) for o in ops]
    return sum(per) / len(per) * 1e-9


def _o_time_by_name(devs, pattern, window):
    rx = re.compile(pattern)
    lo, hi = window
    return sum(e - s for dev in devs for n, s, e in _o_clip(dev, lo, hi)
               if rx.search(n)) * 1e-9


def _o_overlap(a, b):
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _o_exposed_s(ops_all, aops_all, pattern, window):
    rx = re.compile(pattern)
    lo, hi = window
    total = 0
    for ops, aops in zip(ops_all, aops_all):
        ops, aops = _o_clip(ops, lo, hi), _o_clip(aops, lo, hi)
        coll = _o_union((s, e) for n, s, e in ops + aops if rx.search(n))
        other = _o_union((s, e) for n, s, e in ops if not rx.search(n))
        total += _o_length(coll) - _o_overlap(coll, other)
    return total / len(ops_all) * 1e-9


def _o_base(name):
    return re.sub(r"[.:]\d+$", "", name)


def _o_top_ops(ops_all, window, k=10):
    lo, hi = window
    tot = {}
    for ops in ops_all:
        for n, s, e in _o_clip(ops, lo, hi):
            tot[_o_base(n)] = tot.get(_o_base(n), 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def _o_idle_gaps(ops0, spans, window, k=10):
    lo, hi = window
    busy = _o_union((s, e) for _, s, e in _o_clip(ops0, lo, hi))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    starts = [h[1] for h in spans]
    tot = {}
    for gs, ge in gaps:
        best, label = 0, "none"
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(spans) and spans[i][1] < ge:
            n, s, e = spans[i]
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, n
            i += 1
        tot[label] = tot.get(label, 0) + (ge - gs)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


NAMES = ["fusion.1", "fusion.12", "fusion", "copy.3", "copy-start:2",
         "collective-permute-start.1", "collective-permute-done.7",
         "convolution.4", "_kernel", "late"]


def _random_trace(seed):
    """Lists of events on 1 to 4 devices: overlapping, nested, touching,
    zero-length, and reaching past the window on both sides; host spans
    sorted by start, some overlapping, some of equal length."""
    rng = np.random.default_rng(seed)
    devices = int(rng.integers(1, 5))

    def events(count, names):
        s = rng.integers(-80, 1080, size=count)
        d = rng.choice([0, 1, 5, 20, 60, 300], size=count)
        return [(str(rng.choice(names)), int(a), int(a + b))
                for a, b in zip(s, d)]

    ops = [events(int(rng.integers(0, 60)), NAMES) for _ in range(devices)]
    aops = [events(int(rng.integers(0, 8)), NAMES[4:7])
            for _ in range(devices)]
    mods = [events(int(rng.integers(0, 10)), ["jit__fwd(7)", "jit_x(1)"])
            for _ in range(devices)]
    host = sorted(events(int(rng.integers(0, 40)), ["tick", "wait", "x"]),
                  key=lambda h: h[1])
    return ops, aops, mods, host


def _cases():
    dev0 = [("fusion.1", 0, 10), ("fusion.2", 5, 20),
            ("collective-permute-start.1", 20, 22),
            ("collective-permute-done.1", 22, 30), ("_kernel", 40, 50),
            ("late", 95, 130)]
    dev1 = [("convolution.3", 0, 50), ("collective-permute-done.2", 10, 20)]
    hand = ([dev0, dev1], [[], [("collective-permute-start.3", 45, 60)]],
            [[("jit__fwd(7)", 0, 30)], [("jit__fwd(7)", 0, 50)]],
            [("tick", 0, 32), ("wait", 32, 100)])
    return [("hand", hand)] + [(f"random{s}", _random_trace(s))
                               for s in range(16)]


@pytest.mark.parametrize("case", _cases(), ids=lambda c: c[0])
def test_array_reducers_equal_the_list_oracle(case):
    ops, aops, mods, host = case[1]
    window = (0, 1000) if case[0] != "hand" else (0, 100)
    tr = trace.Trace(ops=ops, modules=mods, async_ops=aops, host=host,
                     window=window)
    assert trace.busy_s(tr) == _o_busy_s(ops, window)
    for pattern in (r"^collective-permute", r"fusion", r"^late$", r"zzz"):
        assert trace.time_by_name(tr, pattern) == \
            _o_time_by_name(ops, pattern, window)
        assert trace.exposed_s(tr, pattern) == \
            _o_exposed_s(ops, aops, pattern, window)
    assert trace.time_by_name(tr, r"^jit__fwd\b", programs=True) == \
        _o_time_by_name(mods, r"^jit__fwd\b", window)
    assert trace.top_ops(tr) == _o_top_ops(ops, window)
    assert trace.top_ops(tr, 3) == _o_top_ops(ops, window, 3)
    assert trace.idle_gaps(tr) == _o_idle_gaps(ops[0], host, window)
    for o in ops:
        assert trace.union((s, e) for _, s, e in o) == \
            _o_union((s, e) for _, s, e in o)


def _profile(planes):
    """A stand-in for the profiler's ``ProfileData``: planes of lines of
    events with a name, a start and a duration in (fractional) ns."""
    def ev(name, start, duration):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=duration)

    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=ln, events=[ev(*e) for e in events])
            for ln, events in lines.items()])
        for name, lines in planes.items()])


def test_load_reads_the_device_planes_of_a_stopped_session():
    mark = "jit_bench_window_mark(3)"
    hlo = "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    prof = _profile({
        "/host:CPU": {"python": [("x", 0.0, 5.0)]},
        "/device:TPU:1": {"XLA Ops": [(hlo, 30.4, 10.9)],
                          "XLA Modules": []},
        "/device:TPU:0": {
            "XLA Ops": [(hlo, 20.7, 4.6), ("copy.1", 40.0, 0.0),
                        (hlo, 50.2, 2.2)],
            "Async XLA Ops": [("collective-permute-start.2", 22.0, 9.0)],
            "XLA Modules": [(mark, 10.0, 2.5), ("jit_f(1)", 20.0, 40.0),
                            (mark, 90.5, 1.0)]},
    })
    tr = trace.load(prof, 2, [("refresh", 1005, 1070)], 1000)
    # the window: the first marker's end to the last one's start; host
    # spans move by the first marker's device start less its dispatch
    assert tr.window == (12, 90) and tr.host == [("refresh", 15, 80)]
    assert tr.ops[0].names == ["fusion.4", "copy.1"]
    assert tr.ops[0].code.tolist() == [0, 1, 0]
    # int(start) and int(start + duration), as the events were read before
    assert tr.ops[0].start.tolist() == [20, 40, 50]
    assert tr.ops[0].end.tolist() == [25, 40, 52]
    assert tr.ops[1].start.tolist() == [30] and tr.ops[1].end.tolist() == [41]
    assert len(tr.async_ops[1]) == 0 and len(tr.async_ops[0]) == 1
    with pytest.raises(RuntimeError):
        trace.load(prof, 3, [], 0)


def test_start_profiler_hands_its_events_over_in_memory():
    import jax.numpy as jnp

    session = trace.start_profiler()
    jnp.arange(4).sum().block_until_ready()
    profile = session.stop_and_get_profile_data()
    assert any(p.name.startswith("/host") for p in profile.planes)
    with pytest.raises(RuntimeError, match="devices"):
        trace.load(profile, 1, [], 0)     # no TPU plane on a CPU
