"""Request loop (``GNNServeEngine.tick``): milliseconds of host time per
tick over the window, from the engine's own ``stats.wall_time_s`` and
``stats.batches``.  Moves ``ego_served_rps``."""


def read(run):
    c = run.counters
    return c["tick_wall_s"] * 1e3 / c["ticks"] if c["ticks"] else None
