"""BSP forward (``make_bsp_forward``): device milliseconds of the program
``jit_bsp_forward`` per refresh, averaged over the chips, from the trace's
program events.  ``refresh_ms`` less this is the host's share between
programs.  Moves ``refresh_ms``."""
from harness import trace

PROGRAM = r"^jit_bsp_forward\b"


def read(run):
    t = trace.time_by_name(run.trace, PROGRAM, programs=True) / run.chips
    refreshes = run.counters["refreshes"]
    return t * 1e3 / refreshes if t > 0 and refreshes else None
