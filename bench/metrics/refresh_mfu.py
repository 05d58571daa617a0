"""BSP forward (``make_bsp_forward``): model operations per refresh
(``harness.work.model_flops``: the model kind's count, from the graph's
sizes) times refreshes per second of the window, over the chips' bfloat16
peak.  Moves ``refresh_ms``."""
from harness import peaks, work


def read(run):
    c = run.counters
    if not c["refreshes"]:
        return None
    flops = work.model_flops(run.config["model"], run.n, run.arcs)
    rate = flops * c["refreshes"] / c["wall_s"]
    return 100.0 * rate / (run.chips * peaks.of(run.device_kind)["flops"])
