"""Halo exchange (``_exchange_ppermute``): device milliseconds per refresh
in which a ``collective-permute`` operation ran, on the TensorCore or
asynchronously, and no other TensorCore operation did, averaged over the
chips, from the trace.  Nothing where no exchange ran.  Moves
``refresh_ms``."""
from harness import trace

PATTERN = r"^collective-permute"


def read(run):
    refreshes = run.counters["refreshes"]
    if not refreshes or not trace.any_named(run.trace, PATTERN):
        return None
    return trace.exposed_s(run.trace, PATTERN) * 1e3 / refreshes
