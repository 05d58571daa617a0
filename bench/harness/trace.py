"""The reduction from a profiler trace to device metrics.

``start_profiler`` starts a profiler session; ``load`` reads the events
it hands over when stopped into a ``Trace``: per device, the operations
the TensorCore ran (line "XLA Ops"), the asynchronous ones that overlap
them (line "Async XLA Ops") and the programs (line "XLA Modules"), each
line's events as ``Events``: arrays of start and end in ns and a code into
the line's distinct names.  The host tracer is off (``start_profiler``),
so the window is bounded on the device by two runs of a marker program
(``jit_bench_window_mark``), and the benchmark's own host spans, taken with
``perf_counter_ns``, move onto the trace's clock by the offset between the
first marker's dispatch and its start on the device.  An operation's name
is its HLO instruction's (the trace prints the whole instruction;
``%spmm.2 = ...`` becomes ``spmm.2``).  Everything else works on that plain
structure, so the tests can build one by hand from ``(name, start_ns,
end_ns)`` tuples, which ``Trace`` turns into ``Events``.

The reducers work on whole arrays, and match a name pattern once per
distinct name, never once per event: a four-chip window of 51 s holds
millions of events.  Every sum is over whole nanoseconds, so each gives
the same number as a loop over the events would.

Names are matched as the trace prints them on a TPU v5e today: the ego
forward's program is ``jit__fwd(<hash>)``, the BSR kernel's operation is
``spmm.<n>`` (the jitted wrapper of the Pallas call, not the kernel's own
``_kernel``), and the halo exchange's operations start with
``collective-permute``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import time

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench_window_mark"


@dataclasses.dataclass(frozen=True)
class Events:
    """One trace line's events: event ``i`` is ``names[code[i]]``, running
    from ``start[i]`` to ``end[i]`` (int64 ns), in the line's order."""
    names: list
    code: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, events) -> "Events":
        """From ``(name, start_ns, end_ns)`` tuples; ``Events`` as they are."""
        if isinstance(events, Events):
            return events
        index = {}
        code = [index.setdefault(n, len(index)) for n, _, _ in events]
        return cls(list(index), np.array(code, np.int64),
                   np.array([e[1] for e in events], np.int64),
                   np.array([e[2] for e in events], np.int64))

    def __len__(self) -> int:
        return len(self.code)

    def clip(self, lo: int, hi: int) -> "Events":
        """The events that overlap ``(lo, hi)``, cut to it."""
        keep = (self.end > lo) & (self.start < hi)
        return Events(self.names, self.code[keep],
                      np.maximum(self.start[keep], lo),
                      np.minimum(self.end[keep], hi))

    def matches(self, rx: re.Pattern) -> np.ndarray:
        """Per event, whether its name matches ``rx`` (searched)."""
        hit = np.array([rx.search(n) is not None for n in self.names], bool)
        return hit[self.code] if len(hit) else np.zeros(len(self), bool)


@dataclasses.dataclass
class Trace:
    ops: list            # per device: Events on the TensorCore
    modules: list        # per device: Events of whole programs
    async_ops: list      # per device: Events overlapping ``ops``
    host: list           # [(name, start_ns, end_ns)] of the benchmark's spans
    window: tuple        # (start_ns, end_ns) between the two markers

    def __post_init__(self):
        for field in ("ops", "modules", "async_ops"):
            setattr(self, field, [Events.of(d) for d in getattr(self, field)])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def bench_window_mark(x):
    """The marker program run just before and just after a traced window;
    jitted, its program is named ``jit_bench_window_mark``."""
    return x + 1


def host_span(spans, name: str):
    """Record ``(name, start_ns, end_ns)`` on ``perf_counter_ns`` into
    ``spans``; nothing where ``spans`` is None (an untraced run)."""
    return contextlib.nullcontext() if spans is None else _Span(spans, name)


class _Span:
    def __init__(self, spans, name):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.start = time.perf_counter_ns()

    def __exit__(self, *exc):
        self.spans.append((self.name, self.start, time.perf_counter_ns()))


def start_profiler():
    """A profiler session that traces the device's operations alone:
    Python function tracing slows the host loop several fold, and even the
    host tracer's lowest level doubles the ego cell's time per tick.  Stop
    it with ``stop_and_get_profile_data``, which hands the events over in
    memory; ``jax.profiler.stop_trace`` first writes them to a file, which
    on one chip took 20.7 s against 8.9 s for the same 51 s window."""
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    return _profiler.ProfilerSession(opts)


def load(profile, devices: int, spans: list, mark_ns: int) -> Trace:
    """Read a stopped session's ``ProfileData``; keeps the first
    ``devices`` TPU planes.  ``spans`` are the host spans of the window and
    ``mark_ns`` the ``perf_counter_ns`` at which the first marker was
    dispatched."""
    ops, modules, async_ops = {}, {}, {}
    by_line = {OPS_LINE: ops, MODULES_LINE: modules, ASYNC_LINE: async_ops}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in by_line:
                by_line[line.name][int(m.group(1))] = _events(line)
    ids = sorted(ops)[:devices]
    if len(ids) < devices:
        raise RuntimeError(f"trace has devices {sorted(ops)}")
    mods = [modules.get(d, []) for d in ids]
    window, host = align(mods[0], spans, mark_ns)
    return Trace(ops=[ops[d] for d in ids], modules=mods,
                 async_ops=[async_ops.get(d, []) for d in ids],
                 host=host, window=window)


def align(modules0, spans: list, mark_ns: int) -> tuple:
    """(window, host spans on the trace's clock) from the first device's
    program events: the window runs from the end of the first marker
    program to the start of the last."""
    mods = Events.of(modules0)
    mark = [i for i, n in enumerate(mods.names) if n.startswith("jit_" + MARK)]
    at = np.flatnonzero(np.isin(mods.code, mark))
    marks = sorted((mods.names[mods.code[i]], int(mods.start[i]),
                    int(mods.end[i])) for i in at)
    if len(marks) < 2:
        raise RuntimeError(f"{len(marks)} window markers in the trace")
    offset = marks[0][1] - mark_ns
    host = sorted(((n, s + offset, e + offset) for n, s, e in spans),
                  key=lambda e: e[1])
    return (marks[0][2], marks[-1][1]), host


def op_name(text: str) -> str:
    """``%spmm.2 = f32[...] custom-call(...)`` -> ``spmm.2``."""
    return text.split(" = ", 1)[0].lstrip("%") if text.startswith("%") \
        else text


def _events(line) -> Events:
    """A profiler line's events; each distinct text is named once."""
    index, code, start, dur = {}, [], [], []
    for ev in line.events:
        code.append(index.setdefault(ev.name, len(index)))
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
    s = np.array(start, np.float64)
    return Events([op_name(t) for t in index], np.array(code, np.int64),
                  s.astype(np.int64), (s + np.array(dur, np.float64))
                  .astype(np.int64))


# ------------------------------------------------------------ interval sums
def _union(start: np.ndarray, end: np.ndarray) -> tuple:
    """(starts, ends) of the disjoint, sorted intervals that cover the
    given ones; touching intervals merge."""
    if not len(start):
        return start, end
    order = np.lexsort((end, start))
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.flatnonzero(np.r_[True, start[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(start) - 1]
    return start[first], reach[last]


def union(intervals) -> list:
    """Disjoint, sorted (start, end) covering the given (start, end)s."""
    iv = np.array(list(intervals), np.int64).reshape(-1, 2)
    s, e = _union(iv[:, 0], iv[:, 1])
    return [(int(a), int(b)) for a, b in zip(s, e)]


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def _length(start: np.ndarray, end: np.ndarray) -> int:
    s, e = _union(start, end)
    return int((e - s).sum())


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, per device, averaged over the
    devices, inside the window."""
    lo, hi = tr.window
    per = [_length(c.start, c.end) for c in (o.clip(lo, hi) for o in tr.ops)]
    return sum(per) / len(per) * 1e-9


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def time_by_name(tr: Trace, pattern: str, programs: bool = False) -> float:
    """Seconds of events whose name matches ``pattern`` (a regular
    expression, searched), summed over all devices, inside the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    total = 0
    for dev in (tr.modules if programs else tr.ops):
        c = dev.clip(lo, hi)
        hit = c.matches(rx)
        total += int((c.end[hit] - c.start[hit]).sum())
    return total * 1e-9


def any_named(tr: Trace, pattern: str) -> bool:
    """Whether an operation matching ``pattern``, on the TensorCore or
    asynchronous, ran on any device inside the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    return any(ev.clip(lo, hi).matches(rx).any()
               for ev in tr.ops + tr.async_ops)


def exposed_s(tr: Trace, pattern: str) -> float:
    """Seconds in which an operation matching ``pattern`` ran, on the
    TensorCore or asynchronously, and no other TensorCore operation did,
    per device, averaged over the devices, in the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    total = 0
    for ops, aops in zip(tr.ops, tr.async_ops):
        ops, aops = ops.clip(lo, hi), aops.clip(lo, hi)
        hit, ahit = ops.matches(rx), aops.matches(rx)
        cs, ce = _union(np.r_[ops.start[hit], aops.start[ahit]],
                        np.r_[ops.end[hit], aops.end[ahit]])
        os_, oe = _union(ops.start[~hit], ops.end[~hit])
        # |coll - other| = |coll u other| - |other|
        total += _length(np.r_[cs, os_], np.r_[ce, oe]) - int((oe - os_).sum())
    return total / len(tr.ops) * 1e-9


# ---------------------------------------------------------------- breakdown
def _base(name: str) -> str:
    return re.sub(r"[.:]\d+$", "", name)


def _first_order_totals(labels: list, codes: np.ndarray,
                        weights: np.ndarray, tot: dict) -> None:
    """Add ``weights`` into ``tot[labels[code]]``, new labels in the order
    in which their codes first occur."""
    uniq, first = np.unique(codes, return_index=True)
    sums = np.zeros(len(labels), np.int64)
    np.add.at(sums, codes, weights)
    for c in uniq[np.argsort(first, kind="stable")]:
        tot[labels[c]] = tot.get(labels[c], 0) + int(sums[c])


def top_ops(tr: Trace, k: int = 10) -> list:
    """[[name, seconds]]: the operations that took most device time, names
    without their numeric suffix, summed over devices, in the window."""
    lo, hi = tr.window
    tot = {}
    for ops in tr.ops:
        c = ops.clip(lo, hi)
        bases = list(dict.fromkeys(_base(n) for n in ops.names))
        to_base = np.array([bases.index(_base(n)) for n in ops.names],
                           np.int64)
        _first_order_totals(bases, to_base[c.code], c.end - c.start, tot)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """[[host span, seconds]]: device 0's idle time in the window, summed
    by the benchmark's host span that covers most of each gap (``none``
    where no span does; of spans that cover a gap alike, the earliest)."""
    lo, hi = tr.window
    c = tr.ops[0].clip(lo, hi)
    bs, be = _union(c.start, c.end)
    gs, ge = np.r_[lo, be], np.r_[bs, hi]
    open_ = ge > gs
    gs, ge = gs[open_], ge[open_]
    names = list(dict.fromkeys(n for n, _, _ in tr.host)) + ["none"]
    hc = np.array([names.index(n) for n, _, _ in tr.host], np.int64)
    hs = np.array([h[1] for h in tr.host], np.int64)
    he = np.array([h[2] for h in tr.host], np.int64)
    # each gap's candidates: from the last span starting at or before it
    # to the last starting before its end
    i0 = np.maximum(np.searchsorted(hs, gs, side="right") - 1, 0)
    i1 = np.searchsorted(hs, ge, side="left")
    cnt = np.maximum(i1 - i0, 0)
    gap = np.repeat(np.arange(len(gs)), cnt)
    pos = np.arange(len(gap)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    span = i0[gap] + pos
    ov = np.minimum(he[span], ge[gap]) - np.maximum(hs[span], gs[gap])
    label = np.full(len(gs), len(names) - 1, np.int64)
    if len(ov):
        best = np.zeros(len(gs), np.int64)
        np.maximum.at(best, gap, ov)
        win = (ov == best[gap]) & (ov > 0)
        g_win, first = np.unique(gap[win], return_index=True)
        label[g_win] = hc[span[win][first]]
    tot = {}
    _first_order_totals(names, label, ge - gs, tot)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]
