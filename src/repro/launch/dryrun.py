import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): .lower().compile() every
(architecture x input shape x mesh) cell, dump memory/cost/roofline
artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k [--multi-pod] [--out benchmarks/artifacts]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Each cell writes <out>/<mesh>/<arch>__<shape>.json with:
  memory_analysis (bytes/device), cost_analysis (FLOPs, bytes), the
  collective schedule (per-kind wire bytes), and the three roofline terms.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models as zoo
from repro.configs import (ARCHS, get_config, input_specs, skip_reason)
from repro.launch.hlo import model_flops_for, roofline
from repro.launch.mesh import batch_axes_of, make_production_mesh
from repro.launch.sharding import (batch_dim_spec, cache_specs,
                                   input_sharding_specs)
from repro.models.common import SHAPES
from repro.models.transformer import Dist
from repro.train import optim
from repro.train.step import make_train_step


# The production meshes are v5e pods (16x16 = 256 chips); the dry run
# compiles on host devices, so the roofline names its target chip.
TARGET_DEVICE_KIND = "TPU v5 lite"


def build_dist(mesh, cfg, shape) -> Dist:
    axes = batch_axes_of(mesh)
    fsdp = (("data", "pod") if (cfg.fsdp_over_pod and "pod" in mesh.axis_names)
            else ())
    probe = Dist(mesh, batch_axes=axes, fsdp_axes=fsdp)
    if batch_dim_spec(shape.global_batch, probe) is None:
        return Dist(mesh, batch_axes=(), seq_shard=True, fsdp_axes=fsdp)
    return probe


def lower_cell(arch: str, shape_name: str, multi_pod: bool, cfg=None,
               microbatches=None):
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dist = build_dist(mesh, cfg, shape)
    ns = lambda s: NamedSharding(mesh, s)

    params_abs = jax.eval_shape(
        lambda: zoo.init_params(cfg, jax.random.PRNGKey(0)))
    if shape.kind != "train":
        # Serving runs on bf16 weights (fp32 masters are a training concern).
        params_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
            params_abs)
    pspecs = zoo.param_specs(cfg, dist)
    tp_weight_bytes = cfg.params_count() * 2 / mesh.shape[dist.model_axis]
    if shape.kind != "train" and tp_weight_bytes <= 8 * 2**30:
        # Serving keeps weights TP-sharded but NOT FSDP-sharded: a decode
        # step re-gathers every FSDP shard for one token of compute (the
        # all-gathers dominated the xlstm decode baseline).  bf16 weights
        # replicated across 'data' fit serving HBM comfortably — EXCEPT at
        # 1T params (kimi), where expert shards must stay FSDP-sharded.
        def strip_fsdp(spec):
            from jax.sharding import PartitionSpec
            clean = []
            for entry in spec:
                if entry in ("data", "pod"):
                    clean.append(None)
                elif isinstance(entry, tuple):
                    kept = tuple(a for a in entry if a not in ("data", "pod"))
                    clean.append(kept if kept else None)
                else:
                    clean.append(entry)
            return PartitionSpec(*clean)
        pspecs = jax.tree.map(strip_fsdp, pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    p_shard = jax.tree.map(ns, pspecs)
    batch_abs = input_specs(cfg, shape)
    in_sh = input_sharding_specs(cfg, shape, dist)

    if shape.kind == "train":
        opt_cfg = optim.for_model(cfg)
        opt_abs = jax.eval_shape(
            lambda p: optim.init_opt_state(opt_cfg, p), params_abs)
        o_shard = jax.tree.map(ns, optim.opt_state_specs(opt_cfg, pspecs))
        b_shard = {k: ns(v) for k, v in in_sh.items()}
        mb = (microbatches if microbatches is not None
              else (cfg.train_microbatches or shape.microbatches))
        step = make_train_step(cfg, dist, opt_cfg, microbatches=mb)
        fn = jax.jit(step,
                     in_shardings=(p_shard, o_shard, None, b_shard),
                     donate_argnums=(0, 1))
        lowered = fn.lower(params_abs, opt_abs, None, batch_abs)
    elif shape.kind == "prefill":
        b_shard = {k: ns(v) for k, v in in_sh.items()}
        csp = cache_specs(cfg, shape, dist)
        cache_abs = jax.eval_shape(
            lambda: zoo.init_cache(cfg, shape.global_batch, shape.seq_len))
        c_shard = {k: ns(csp[k]) for k in cache_abs}
        logits_shard = ns(P(dist.batch, None, dist.model_axis))
        fn = jax.jit(
            lambda p, b: zoo.prefill(cfg, p, b, shape.seq_len, dist),
            in_shardings=(p_shard, b_shard),
            out_shardings=(logits_shard, c_shard))
        lowered = fn.lower(params_abs, batch_abs)
    else:                                  # decode
        cache_abs = batch_abs["cache"]
        csp = cache_specs(cfg, shape, dist)
        c_shard = {k: ns(csp[k]) for k in cache_abs}
        t_shard = ns(in_sh["tokens"])
        logits_shard = ns(P(dist.batch, None, dist.model_axis))
        fn = jax.jit(
            lambda p, t, c: zoo.decode_step(cfg, p, t, c, dist),
            in_shardings=(p_shard, t_shard, c_shard),
            out_shardings=(logits_shard, c_shard),
            donate_argnums=(2,))
        lowered = fn.lower(params_abs, batch_abs["tokens"], cache_abs)
    return cfg, shape, mesh, lowered


def measure_probe(cfg, arch, shape_name, multi_pod):
    """Probe lowering -> per-device {flops, bytes, collectives-per-kind}."""
    from repro.launch.hlo import parse_collectives
    # microbatches=1: grad accumulation repartitions the same total compute,
    # and the mb loop is a scan (counted once) — probes must bypass it.
    _, _, mesh, lowered = lower_cell(arch, shape_name, multi_pod, cfg=cfg,
                                     microbatches=1)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text(), default_group=mesh.size)
    per_kind = {}
    for c in colls:
        per_kind[c.kind] = per_kind.get(c.kind, 0.0) + c.wire_bytes
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": per_kind}


def corrected_cost(arch, shape_name, multi_pod, devices):
    """Structural extrapolation (launch/analysis.py) over probe lowerings."""
    from repro.launch.analysis import combine, probe_plan, slstm_time_flops
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    results = []
    for pcfg, coef in probe_plan(cfg, shape):
        results.append((measure_probe(pcfg, arch, shape_name, multi_pod),
                        coef))
    out = combine(results)
    out["flops"] += slstm_time_flops(cfg, shape, devices)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             keep_hlo: bool = False, exact: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    skip = skip_reason(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if skip:
        rec.update({"status": "skipped", "reason": skip})
        return rec
    t0 = time.time()
    try:
        cfg, shape, mesh, lowered = lower_cell(arch, shape_name, multi_pod)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        rf = roofline(compiled, mesh.size, TARGET_DEVICE_KIND,
                      model_flops_for(cfg, shape), cost, hlo)
        if exact:
            # Correct the scan-body-counted-once undercount (analysis.py).
            cc = corrected_cost(arch, shape_name, multi_pod, mesh.size)
            from repro.launch import hlo as H
            cbytes = sum(cc["collectives"].values())
            terms = H.roofline_terms(cc["flops"], cc["bytes"], cbytes,
                                     TARGET_DEVICE_KIND)
            mf = model_flops_for(cfg, shape)
            rf = H.Roofline(
                flops_per_device=cc["flops"],
                hbm_bytes_per_device=cc["bytes"],
                collective_bytes_per_device=cbytes,
                compute_s=terms["compute"], memory_s=terms["memory"],
                collective_s=terms["collective"],
                bottleneck=max(terms, key=terms.get),
                model_flops=mf,
                useful_ratio=(mf / (cc["flops"] * mesh.size)
                              if cc["flops"] else 0.0),
                collectives=cc["collectives"],
            )
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "devices": mesh.size,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_bytes": (mem.argument_size_in_bytes
                                        + mem.output_size_in_bytes
                                        + mem.temp_size_in_bytes
                                        - mem.alias_size_in_bytes),
            },
            "cost": {k: cost[k] for k in ("flops", "bytes accessed")
                     if k in cost},
            "roofline": rf.as_dict(),
        })
        if keep_hlo:
            hpath = os.path.join(outdir, mesh_name,
                                 f"{arch}__{shape_name}.hlo.txt")
            with open(hpath, "w") as f:
                f.write(hlo)
    except Exception as e:                                 # noqa: BLE001
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--no-exact", action="store_true",
                    help="skip probe lowerings (compile-proof only; the "
                         "roofline table is single-pod per the spec)")
    args = ap.parse_args()

    mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
    os.makedirs(os.path.join(args.out, mesh_name), exist_ok=True)

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        archs = [args.arch] if args.arch else list(ARCHS)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for a in archs:
            for s in shapes:
                cells.append((a, s))

    ok = skipped = failed = 0
    for arch, shape in cells:
        rec = run_cell(arch, shape, args.multi_pod, args.out, args.keep_hlo,
                       exact=not args.no_exact)
        path = os.path.join(args.out, mesh_name, f"{arch}__{shape}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        st = rec["status"]
        ok += st == "ok"
        skipped += st == "skipped"
        failed += st == "error"
        extra = ""
        if st == "ok":
            pk = rec["memory"]["peak_estimate_bytes"] / 2**30
            extra = (f" peak={pk:.2f}GiB/dev "
                     f"bottleneck={rec['roofline']['bottleneck']}")
        if st == "error":
            extra = " " + rec["error"][:160]
        print(f"[{st:7s}] {arch:22s} {shape:12s} {mesh_name}{extra}",
              flush=True)
    print(f"\ndry-run {mesh_name}: {ok} ok, {skipped} skipped, "
          f"{failed} failed")
    return 0 if failed == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
