"""Device: the share of the window in which no operation ran on the
device, averaged over the chips, from the trace.  Moves
``ego_served_rps``."""
from harness import trace


def read(run):
    return 100.0 * trace.idle_share(run.trace)
