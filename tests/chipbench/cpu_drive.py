"""Drive ``bench/run.py`` on XLA CPU at a tiny graph size, for the tests.

  python tests/bench/cpu_drive.py '<json list of jobs>'

Each job is ``{"cell", "seed", "seconds", "trace", "n", "fault"}``.  The
look for a chip is steered here (``run.require_chips`` returns CPU
devices), the cell's graph is cut to ``n`` vertices at its link density,
and ``fault`` plants one break in the timed path:

  answer     one output value altered where the forward produces it
  half       half of each batch (or of each device's rows) left as zeros
  unchanged  the forward returns its input rows, not its output
  exchange   the halo exchange between chips returns zeros

One JSON line per job: the job, the exit code and the run's last line.
Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so that
four-chip cells find their devices.
"""
from __future__ import annotations

import contextlib
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import run  # noqa: E402


def _shrink(load, n):
    def cell(name):
        c = load(name)
        g = c["config"]["graph"]
        g["links"] = int(round(g["links"] * n / g["n"]))
        g["n"] = n
        return c
    return cell


def _wrap_output(fwd, alter):
    def wrapped(*args, **kw):
        return alter(fwd(*args, **kw), args)
    wrapped.stats = getattr(fwd, "stats", None)
    wrapped.lower = getattr(fwd, "lower", None)
    return wrapped


def _alteration(fault: str, d_out: int):
    if fault == "answer":
        return lambda out, args: out.at[(0,) * out.ndim].add(1.0)
    if fault == "half":
        def half(out, args):
            axis = out.ndim - 2
            keep = out.shape[axis] // 2
            idx = (slice(None),) * axis + (slice(keep, None),)
            return out.at[idx].set(0.0)
        return half
    if fault == "unchanged":
        def unchanged(out, args):
            if out.ndim == 3:                       # BSP: (P, cap, d) blocks
                return args[1][..., :d_out]
            feats, _, _, tgt_rows = args            # ego: table rows
            return feats[tgt_rows][:, :d_out]
        return unchanged
    raise ValueError(fault)


@contextlib.contextmanager
def _planted(fault, d_out):
    import jax.numpy as jnp
    import repro.gnn as gnn
    import repro.gnn.distributed as dist
    import repro.gnn.serving as serving

    saved = (serving.make_ego_forward, gnn.make_bsp_forward,
             dist._exchange_ppermute)
    try:
        if fault == "exchange":
            def no_exchange(h_local, rounds, halo_cap, axis_name, init=None):
                return jnp.zeros((halo_cap, h_local.shape[-1]), h_local.dtype)
            dist._exchange_ppermute = no_exchange
        elif fault:
            alter = _alteration(fault, d_out)
            serving.make_ego_forward = (
                lambda *a, **k: _wrap_output(saved[0](*a, **k), alter))
            gnn.make_bsp_forward = (
                lambda *a, **k: _wrap_output(saved[1](*a, **k), alter))
        yield
    finally:
        (serving.make_ego_forward, gnn.make_bsp_forward,
         dist._exchange_ppermute) = saved


def drive(job: dict) -> dict:
    import jax

    load = run.registry.cell
    run.registry.cell = _shrink(load, int(job["n"]))
    run.require_chips = lambda count: jax.devices()[:count]
    d_out = load(job["cell"])["config"]["model"]["layer_dims"][-1]
    buf = io.StringIO()
    run.T_START = time.perf_counter()       # each job is a run of its own
    try:
        with _planted(job.get("fault"), d_out), contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", job["cell"], "--seed",
                           str(job["seed"]), "--seconds", str(job["seconds"]),
                           "--trace", str(job.get("trace", 0))])
    finally:
        run.registry.cell = load
    lines = buf.getvalue().strip().splitlines()
    return {"job": job, "rc": rc,
            "result": json.loads(lines[-1]) if lines else None}


if __name__ == "__main__":
    for job in json.loads(sys.argv[1]):
        print(json.dumps(drive(job)), flush=True)
