"""BSP forward (``make_bsp_forward``): the share of the chips' HBM bandwidth
that the refreshes' least traffic would use over the device time of the
program ``jit_bsp_forward``, from the trace's program events:

  100 * bytes * refreshes / (sum over chips of its seconds * HBM peak)

``bytes`` is the model kind's count of what one whole-graph forward must
move (``harness.work.model_bytes``, from the graph's sizes, never from the
plan's padding).  Nothing where no refresh or no program time was traced.
Moves ``refresh_ms``."""
from harness import peaks, trace, work

PROGRAM = r"^jit_bsp_forward\b"


def read(run):
    t = trace.time_by_name(run.trace, PROGRAM, programs=True)
    refreshes = run.counters["refreshes"]
    if t <= 0 or not refreshes:
        return None
    nbytes = work.model_bytes(run.config["model"], run.n, run.arcs)
    peak = peaks.of(run.device_kind)["hbm_bytes_s"]
    return 100.0 * nbytes * refreshes / (t * peak)
