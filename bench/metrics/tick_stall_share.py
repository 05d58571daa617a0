"""Request loop: the share of the window spent in stalled ticks, those
that took over ten times the window's median tick, timed by the
benchmark's host clock around ``GNNServeEngine.tick``.  Above capacity a
stall costs the window's throughput its own length.  Moves
``ego_served_rps``."""


def read(run):
    c = run.counters
    return 100.0 * c["stall_s"] / c["wall_s"] if c["ticks"] else None
