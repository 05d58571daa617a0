"""Request loop: requests per tick over the window, from the engine's
``stats.requests`` and ``stats.batches``.  Moves ``ego_served_rps``."""


def read(run):
    c = run.counters
    return c["requests"] / c["ticks"] if c["ticks"] else None
