"""The reduction from a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a ``Trace``:
per device, the operations the TensorCore ran (line "XLA Ops"), the
asynchronous ones that overlap them (line "Async XLA Ops") and the programs
(line "XLA Modules") as ``(name, start_ns, end_ns)``.  The host tracer is
off: at its lowest level it doubles the ego cell's host time per tick.  So
the window is bounded on the device by two runs of a marker program
(``jit_bench_window_mark``), and the benchmark's own host spans, taken with
``perf_counter_ns``, move onto the trace's clock by the offset between the
first marker's dispatch and its start on the device.  An operation's name
is its HLO instruction's (the trace prints the whole instruction;
``%spmm.2 = ...`` becomes ``spmm.2``).  Everything else works on that plain
structure, so the tests can build one by hand.

Names are matched as the trace prints them on a TPU v5e today: the ego
forward's program is ``jit__fwd(<hash>)``, the BSR kernel's operation is
``spmm.<n>`` (the jitted wrapper of the Pallas call, not the kernel's own
``_kernel``), and the halo exchange's operations start with
``collective-permute``.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench_window_mark"


@dataclasses.dataclass
class Trace:
    ops: list            # per device: [Event] on the TensorCore
    modules: list        # per device: [Event] of whole programs
    async_ops: list      # per device: [Event] overlapping ``ops``
    host: list           # [Event] of the benchmark's own spans
    window: tuple        # (start_ns, end_ns) between the two markers

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


def load(trace_dir: str, devices: int, spans: list, mark_ns: int) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``; keeps the first
    ``devices`` TPU planes.  ``spans`` are the host spans of the window and
    ``mark_ns`` the ``perf_counter_ns`` at which the first marker was
    dispatched."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    pd = ProfileData.from_file(paths[0])
    ops, modules, async_ops = {}, {}, {}
    by_line = {OPS_LINE: ops, MODULES_LINE: modules, ASYNC_LINE: async_ops}
    for plane in pd.planes:
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


def align(modules0: list, spans: list, mark_ns: int) -> tuple:
    """(window, host spans on the trace's clock) from the first device's
    program events: the window runs from the end of the first marker
    program to the start of the last."""
    marks = sorted(e for e in modules0 if e[0].startswith("jit_" + MARK))
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


def _events(line) -> list:
    return [(op_name(ev.name), int(ev.start_ns),
             int(ev.start_ns + ev.duration_ns)) for ev in line.events]


# ------------------------------------------------------------ interval sums
def clip(events, lo: int, hi: int) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals) -> list:
    """Disjoint, sorted (start, end) covering the given (start, end)s."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, per device, averaged over the
    devices, inside the window."""
    lo, hi = tr.window
    per = [length((s, e) for _, s, e in clip(ops, lo, hi)) for ops in tr.ops]
    return sum(per) / len(per) * 1e-9


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def time_by_name(tr: Trace, pattern: str, programs: bool = False) -> float:
    """Seconds of events whose name matches ``pattern`` (a regular
    expression, searched), summed over all devices, inside the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    events = tr.modules if programs else tr.ops
    return sum(e - s for dev in events for n, s, e in clip(dev, lo, hi)
               if rx.search(n)) * 1e-9


def exposed_s(tr: Trace, pattern: str) -> float:
    """Seconds in which an operation matching ``pattern`` ran, on the
    TensorCore or asynchronously, and no other TensorCore operation did,
    per device, averaged over the devices, in the window."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    total = 0
    for ops, aops in zip(tr.ops, tr.async_ops):
        ops, aops = clip(ops, lo, hi), clip(aops, lo, hi)
        coll = union((s, e) for n, s, e in ops + aops if rx.search(n))
        other = union((s, e) for n, s, e in ops if not rx.search(n))
        total += length(coll) - _overlap(coll, other)
    return total / len(tr.ops) * 1e-9


def _overlap(a, b) -> int:
    """Length of the intersection of two disjoint sorted interval lists."""
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


# ---------------------------------------------------------------- breakdown
def _base(name: str) -> str:
    return re.sub(r"[.:]\d+$", "", name)


def top_ops(tr: Trace, k: int = 10) -> list:
    """[[name, seconds]]: the operations that took most device time, names
    without their numeric suffix, summed over devices, in the window."""
    lo, hi = tr.window
    tot = {}
    for ops in tr.ops:
        for n, s, e in clip(ops, lo, hi):
            tot[_base(n)] = tot.get(_base(n), 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t * 1e-9] for n, t in best]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """[[host span, seconds]]: device 0's idle time in the window, summed
    by the benchmark's host span that covers most of each gap (``none``
    where no span does)."""
    lo, hi = tr.window
    busy = union((s, e) for _, s, e in clip(tr.ops[0], lo, hi))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    spans = tr.host
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
