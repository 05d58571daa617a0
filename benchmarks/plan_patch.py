"""Plan-patch benchmark: incremental ShardPlan patching vs full recompile.

Section ``plan_patch_cells`` — the serving-side replan loop the incremental
plan pipeline closes: a GLAD-shaped layout over ``m`` servers absorbs a
sequence of small relayouts; each step measures

  * ``patch``   — :func:`repro.gnn.distributed.patch_plan` on the live plan
                  (dirty partitions only; measured on a throwaway deepcopy
                  so best-of-reps sees identical state),
  * ``compile`` — a from-scratch :func:`compile_plan` of the same new
                  assignment (what the pre-pipeline execution layer did
                  after every relayout),

interleaved in the same process/window (the only defensible protocol on a
±30%-noise box; see ROADMAP methodology notes).  Each cell also records
exact-parity counters — every patched plan is compared array-for-array
against a pinned fresh compile (``recompile_like``) — and the final
``halo_bytes_ppermute`` (integer, machine-independent), which the CI
parity gate pins: if the patch path ever drifts from the compile path,
the build fails.

A separate 8-host-device subprocess cell replays a move sequence through a
jitted ``make_bsp_forward`` and records the trace counts: value-only
patches must compile exactly once overall (zero retraces), the forced
capacity-growth step exactly once more.  The child runs on the host
platform (``JAX_PLATFORMS=cpu``) so it never competes for an accelerator,
and a failed child fails the run.

Usage: PYTHONPATH=src python benchmarks/plan_patch.py [--quick] [--smoke]
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core.partition import partition_from_assign
from repro.gnn.distributed import (build_plan_bsr, compile_plan, patch_plan,
                                   plans_equal, recompile_like)
from repro.graphs.datagraph import synthetic_yelp


def _layout(n: int, parts: int, seed: int):
    """A clustered serving workload with a balanced locality layout.

    Yelp-shaped graph (the paper's second dataset: community cliques over
    contiguous ids) under contiguous balanced blocks — low cut, movers'
    neighborhoods span few servers, i.e. the regime a converged GLAD
    layout puts the serving path in.  (SIoT's preferential-attachment
    graph is an expander: NO layout has locality there, every mover's
    neighborhood spans all servers and the dirty set is the whole fleet —
    the ``scatter`` pattern below records that worst case honestly.)"""
    g = synthetic_yelp(n=n, target_links=int(1.2 * n), seed=seed + 1)
    assign = (np.arange(n, dtype=np.int64) * parts) // n
    return g, assign


def _move_sets(g, assign, parts, rng, steps, k, pattern):
    """Per-step mover sets.  ``local``: a BFS ball sheds to one target
    server (fault migration / GLAD-E slot shape — the serving regime);
    ``scatter``: k uniform vertices to uniform servers (worst case: the
    dirty set spans every partition)."""
    out = []
    cur = assign.copy()
    for _ in range(steps):
        new = cur.copy()
        if pattern == "scatter":
            movers = rng.choice(g.n, size=k, replace=False)
            new[movers] = rng.integers(0, parts, size=k)
        else:
            seed_v = int(rng.integers(0, g.n))
            ball, frontier = {seed_v}, [seed_v]
            while len(ball) < k and frontier:
                nxt = [u for v in frontier
                       for u in g.neighbors(v).tolist() if u not in ball]
                ball.update(nxt)
                frontier = nxt
            movers = np.array(sorted(ball))[:k]
            # Shed to the adjacent server — edge rebalancing moves load to
            # a NEARBY server (tau is distance-shaped), which also keeps
            # the ppermute schedule stable (no new shifts, no retrace).
            new[movers] = (int(cur[seed_v]) + 1) % parts
        out.append(new)
        cur = new
    return out


def run_patch_cell(n: int, parts: int, seed: int = 0, reps: int = 3,
                   steps: int = 8, movers: int = 8, pattern: str = "local",
                   bsr: bool = False) -> dict:
    g, assign = _layout(n, parts, seed)
    part = partition_from_assign(g, assign, parts, {})
    t0 = time.perf_counter()
    plan = compile_plan(g, part, slack=0.5)
    first_compile_s = time.perf_counter() - t0
    if bsr:
        build_plan_bsr(plan)

    rng = np.random.default_rng(seed + 1)
    assigns = _move_sets(g, assign, parts, rng, steps, movers, pattern)
    patch_ms, compile_ms, dirty_parts = [], [], []
    mismatches = grew_steps = 0
    for new in assigns:
        best_p = best_c = float("inf")
        for _r in range(reps):
            trial = copy.deepcopy(plan)          # identical state per rep
            t0 = time.perf_counter()
            patch_plan(trial, g, new)
            best_p = min(best_p, time.perf_counter() - t0)
            # The from-scratch path is what every caller ran before the
            # incremental pipeline: DevicePartition + plan (+ BSR retile).
            t0 = time.perf_counter()
            fresh = compile_plan(
                g, partition_from_assign(g, new, parts, {}))
            if bsr:
                build_plan_bsr(fresh)
            best_c = min(best_c, time.perf_counter() - t0)
        delta = patch_plan(plan, g, new)         # commit
        grew_steps += not delta.patched
        dirty_parts.append(len(delta.dirty_parts))
        if plans_equal(plan, recompile_like(plan, g, new)):
            mismatches += 1
        patch_ms.append(best_p * 1e3)
        compile_ms.append(best_c * 1e3)

    med_p = float(np.median(patch_ms))
    med_c = float(np.median(compile_ms))
    return {
        "n": n, "m": parts, "steps": steps, "moved_per_step": movers,
        "pattern": pattern, "bsr": bsr, "reps": reps,
        "first_compile_ms": round(first_compile_s * 1e3, 2),
        "patch_ms": round(med_p, 3), "compile_ms": round(med_c, 3),
        "patch_speedup": round(med_c / max(med_p, 1e-9), 2),
        "median_dirty_parts": float(np.median(dirty_parts)),
        "patch_parity_mismatches": mismatches,
        "grew_steps": grew_steps,
        "final_halo_rows": int(plan.halo_bytes_ppermute),
        "plan_version": int(plan.version),
    }


_RETRACE_SUBPROCESS = textwrap.dedent("""
    import os, json
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import synthetic_siot
    from repro.gnn import (GNNConfig, init_params, compile_plan, patch_plan,
                           make_bsp_forward, scatter_features)
    from repro.core.partition import partition_from_assign
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    g = synthetic_siot(n=240, target_links=700)
    assign = rng.integers(0, 8, size=g.n)
    plan = compile_plan(g, partition_from_assign(g, assign, 8, {}),
                        slack=0.5)
    mesh = make_mesh((8,), ('data',))
    cfg = GNNConfig('gcn', (52, 16, 2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    fwd = make_bsp_forward(cfg, plan, mesh)
    blocks = jnp.asarray(scatter_features(plan, g.features))
    fwd(params, blocks)
    cur, steps = assign, 6
    for _ in range(steps):
        movers = rng.choice(g.n, size=5, replace=False)
        new = cur.copy(); new[movers] = rng.integers(0, 8, size=5)
        patch_plan(plan, g, new)
        fwd(params, blocks)
        cur = new
    patch_traces = fwd.stats['traces']
    new = cur.copy(); new[: g.n // 2] = 0        # force capacity growth
    patch_plan(plan, g, new)
    fwd(params, jnp.asarray(scatter_features(plan, g.features)))
    print(json.dumps({"steps": steps,
                      "traces_after_patches": patch_traces,
                      "traces_after_growth": fwd.stats['traces']}))
""")


def run_retrace_cell() -> dict:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
    env = dict(os.environ, PYTHONPATH=os.path.normpath(src),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _RETRACE_SUBPROCESS], env=env,
                       capture_output=True, text=True, timeout=900)
    if r.returncode != 0:
        raise RuntimeError("retrace cell failed:\n"
                           + (r.stdout + r.stderr)[-2000:])
    cell = json.loads(r.stdout.strip().splitlines()[-1])
    cell["zero_retrace_on_patch"] = cell["traces_after_patches"] == 1
    cell["single_retrace_on_growth"] = cell["traces_after_growth"] == 2
    return cell


def _merge(out_path: str, cells: list, retrace: dict) -> None:
    doc = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    doc["plan_patch_cells"] = cells
    doc["plan_patch_retrace"] = retrace
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"merged plan_patch_cells into {out_path}")


def _verify(cells: list, retrace: dict) -> list:
    bad = []
    for c in cells:
        if c.get("patch_parity_mismatches", 1) != 0:
            bad.append(f"n={c['n']} m={c['m']}: patched plan diverged from "
                       f"fresh compile on {c['patch_parity_mismatches']} "
                       f"steps")
    if not (retrace.get("zero_retrace_on_patch")
              and retrace.get("single_retrace_on_growth")):
        bad.append(f"retrace counts off: {retrace}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small cell only (n=2k)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_layout.json")
    ap.add_argument("--fail-on-mismatch", action="store_true",
                    help="exit nonzero on patch/compile divergence or "
                         "unexpected retraces (the CI smoke gate)")
    args = ap.parse_args(argv)

    grid = [(2000, 8, "local", True)]
    if not args.quick:
        grid += [(20000, 32, "local", False), (20000, 32, "scatter", False),
                 (20000, 16, "local", False)]
    cells = []
    for n, m, pattern, bsr in grid:
        cell = run_patch_cell(n, m, reps=args.reps, pattern=pattern, bsr=bsr)
        cells.append(cell)
        print(f"n={n:>6} m={m:>2} {pattern:7s} bsr={int(bsr)}: patch "
              f"{cell['patch_ms']}ms vs compile {cell['compile_ms']}ms "
              f"-> {cell['patch_speedup']}x  (dirty "
              f"{cell['median_dirty_parts']:.0f}/{m}, parity mismatches "
              f"{cell['patch_parity_mismatches']}, grew "
              f"{cell['grew_steps']}/{cell['steps']})")
    retrace = run_retrace_cell()
    print(f"retrace cell: {retrace}")
    _merge(args.out, cells, retrace)

    if args.fail_on_mismatch:
        bad = _verify(cells, retrace)
        if bad:
            print("PLAN-PATCH GATE FAILURES:")
            for b in bad:
                print("  " + b)
            return 1
        print("plan-patch gate: parity exact, retrace counts as designed")
    return 0


def check_parity(ref_path: str = "BENCH_layout.json") -> int:
    """Re-run the quick cell and fail on drift vs the committed numbers.

    Gated quantities are integers and machine-independent: exact parity
    mismatch counts (must be 0) and the final ppermute traffic of the
    patched plan (pins the patch path's arithmetic, not wall time)."""
    with open(ref_path) as f:
        ref = json.load(f)
    ref_cells = {(c["n"], c["m"], c.get("pattern", "local")): c
                 for c in ref.get("plan_patch_cells", [])}
    if not ref_cells:
        print(f"no plan_patch_cells committed in {ref_path}; failing")
        return 1
    got = run_patch_cell(2000, 8, reps=1, pattern="local", bsr=True)
    bad = _verify([got], {"zero_retrace_on_patch": True,
                          "single_retrace_on_growth": True})
    r = ref_cells.get((2000, 8, "local"))
    if r is None:
        bad.append("committed file lacks the (n=2000, m=8) cell")
    elif got["final_halo_rows"] != r["final_halo_rows"]:
        bad.append(f"final_halo_rows {got['final_halo_rows']} != committed "
                   f"{r['final_halo_rows']} (patch-path drift)")
    if bad:
        print(f"PLAN-PATCH PARITY CHECK FAILED against {ref_path}")
        for b in bad:
            print("  " + b)
        return 1
    print(f"plan-patch parity OK vs {ref_path}")
    return 0


def run(full: bool = False, smoke: bool = False) -> int:
    argv = []
    if smoke or not full:
        argv.append("--quick")
    if smoke:
        argv += ["--reps", "1", "--out", "BENCH_layout.smoke.json",
                 "--fail-on-mismatch"]
    elif not full:
        argv += ["--out", "BENCH_layout.quick.json"]
    return main(argv)


if __name__ == "__main__":
    sys.exit(main())
