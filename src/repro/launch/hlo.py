"""HLO post-mortem: collective-bytes scrape + three-term roofline.

cost_analysis() reports per-device FLOPs and bytes AFTER SPMD partitioning
(verified against hand-computed shards), but has no collective entry — so we
parse the optimized HLO text and sum the bytes every collective moves.

Per-device wire-bytes model (ring algorithms, group size N):
  all-reduce        2 (N-1)/N x buffer
  all-gather        (N-1)/N x output
  reduce-scatter    (N-1)/N x input  ~= (N-1) x output
  all-to-all        (N-1)/N x buffer
  collective-permute  1 x buffer

Hardware peaks come from :data:`PEAKS`, keyed by the ``device_kind`` JAX
reports; a kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float                 # bf16 FLOP/s per chip
    hbm_bw: float                # HBM bytes/s per chip
    ici_bw: float                # interconnect bytes/s per link


# Source: Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over
# four links (~50 GB/s per link).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Published peaks of one chip of ``device_kind`` (``jax.Device.device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"repro.launch.hlo.PEAKS with its source") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^\s]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


@dataclasses.dataclass
class Collective:
    kind: str
    bytes_buffer: int            # per-device buffer size in the HLO
    group_size: int
    wire_bytes: float            # per-device bytes on the wire (ring model)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _wire_bytes(kind: str, buf: int, n: int) -> float:
    if kind == "collective-permute":
        return float(buf)        # point-to-point: group size is irrelevant
    if n <= 1:
        return 0.0
    f = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * f * buf
    if kind == "all-gather":
        return f * buf                     # buf = gathered output
    if kind == "reduce-scatter":
        return (n - 1) * buf               # buf = scattered output
    if kind == "all-to-all":
        return f * buf
    if kind == "collective-permute":
        return float(buf)
    return float(buf)


def parse_collectives(hlo_text: str, default_group: int = 1) -> List[Collective]:
    out = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        buf = _shape_bytes(shape_str)
        g = _GROUPS_RE.search(line)
        if g:
            n = g.group(1).count(",") + 1
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            n = int(gi.group(2)) if gi else default_group
        out.append(Collective(kind, buf, n, _wire_bytes(kind, buf, n)))
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float = 0.0
    useful_ratio: float = 0.0
    collectives: Optional[Dict[str, float]] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   device_kind: str) -> Dict[str, float]:
    """Seconds each roofline term takes at ``device_kind``'s peaks."""
    pk = peaks_for(device_kind)
    return {"compute": flops / pk.flops, "memory": hbm_bytes / pk.hbm_bw,
            "collective": collective_bytes / pk.ici_bw}


def roofline(compiled, mesh_devices: int, device_kind: str,
             model_flops: float = 0.0, cost: Optional[dict] = None,
             hlo: Optional[str] = None) -> Roofline:
    ca = cost or compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    text = hlo if hlo is not None else compiled.as_text()
    colls = parse_collectives(text, default_group=mesh_devices)
    cbytes = sum(c.wire_bytes for c in colls)
    per_kind: Dict[str, float] = {}
    for c in colls:
        per_kind[c.kind] = per_kind.get(c.kind, 0.0) + c.wire_bytes
    terms = roofline_terms(flops, hbm, cbytes, device_kind)
    bott = max(terms, key=terms.get)
    useful = (model_flops / (flops * mesh_devices)
              if flops > 0 and model_flops else 0.0)
    return Roofline(
        flops_per_device=flops, hbm_bytes_per_device=hbm,
        collective_bytes_per_device=cbytes,
        compute_s=terms["compute"], memory_s=terms["memory"],
        collective_s=terms["collective"], bottleneck=bott,
        model_flops=model_flops, useful_ratio=useful,
        collectives=per_kind,
    )


def model_flops_for(cfg, shape) -> float:
    """6*N*D accounting (N = params, active params for MoE; D = tokens)."""
    n = cfg.params_count()
    if cfg.n_experts:
        per_exp = 3 * cfg.d_model * cfg.expert_d_ff
        moe_layers = cfg.n_layers - cfg.first_dense_layers
        routed_total = moe_layers * cfg.n_experts * per_exp
        routed_active = moe_layers * cfg.top_k * per_exp
        n = n - routed_total + routed_active
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch                    # decode: one token each
    return 2.0 * n * tokens
