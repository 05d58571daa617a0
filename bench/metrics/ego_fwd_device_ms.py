"""Ego forward (``make_ego_forward``, XLA): device milliseconds of the
jitted ego-forward program per tick, from the trace's program events
(``jit__fwd``).  Moves ``ego_served_rps``."""
from harness import trace

PROGRAM = r"^jit__fwd\b"


def read(run):
    t = trace.time_by_name(run.trace, PROGRAM, programs=True)
    ticks = run.counters["ticks"]
    return t * 1e3 / ticks if t > 0 and ticks else None
