"""GNN models exactly per the paper's Sec. II-A execution semantics.

  GCN  (Eq. 1):  a_v = sum_{u in N_v} h_u
                 h_v' = sigma(W . (a_v + h_v) / (|N_v| + 1))
  GAT  (Eq. 2):  a_v = sum_{u in N_v u {v}} eta_vu . W h_u,  h_v' = sigma(a_v)
  SAGE (Eq. 3):  a_v = mean_{u in N_v} h_u
                 h_v' = sigma(W . concat(a_v, h_v))

All models are pure functions over a params pytree and an edge list; the
neighbor aggregation runs through a pluggable ``segment_sum`` so the Pallas
kernel (kernels/gnn_aggregate) and the distributed BSP engine can reuse the
same layer semantics.  Graphs are encoded as a directed src->dst edge array
(each undirected link appears twice) — the canonical message-passing layout.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Aggregate = Callable[[jnp.ndarray, jnp.ndarray, int], jnp.ndarray]
# (messages (E, d), dst_ids (E,), num_nodes) -> (n, d) summed per dst.


def segment_sum(messages: jnp.ndarray, dst: jnp.ndarray, n: int) -> jnp.ndarray:
    """Default jnp aggregation (the ref path; kernels/ops.py overrides)."""
    return jax.ops.segment_sum(messages, dst, num_segments=n)


def directed_edges(edges: np.ndarray) -> np.ndarray:
    """Undirected (E,2) u<v edge list -> directed (2E,2) src->dst pairs."""
    if len(edges) == 0:
        return np.zeros((0, 2), dtype=np.int32)
    fwd = edges
    bwd = edges[:, ::-1]
    return np.concatenate([fwd, bwd], axis=0).astype(np.int32)


def degrees_from_directed(src_dst: jnp.ndarray, n: int) -> jnp.ndarray:
    ones = jnp.ones((src_dst.shape[0],), jnp.float32)
    return jax.ops.segment_sum(ones, src_dst[:, 1], num_segments=n)


# ---------------------------------------------------------------- parameters
def _glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, dtype, -lim, lim)


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str                      # 'gcn' | 'gat' | 'sage'
    layer_dims: Sequence[int]       # [s_0, ..., s_K]
    dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        # Tuple-ize so the config is hashable (jit static argument).
        object.__setattr__(self, "layer_dims", tuple(self.layer_dims))

    @property
    def num_layers(self) -> int:
        return len(self.layer_dims) - 1


def init_params(key: jax.Array, cfg: GNNConfig):
    params = []
    for k in range(cfg.num_layers):
        key, k1, k2, k3 = jax.random.split(key, 4)
        d_in, d_out = cfg.layer_dims[k], cfg.layer_dims[k + 1]
        if cfg.model == "gcn":
            layer = {"w": _glorot(k1, (d_in, d_out), cfg.dtype)}
        elif cfg.model == "gat":
            layer = {
                "w": _glorot(k1, (d_in, d_out), cfg.dtype),
                "att_src": _glorot(k2, (d_out, 1), cfg.dtype)[:, 0],
                "att_dst": _glorot(k3, (d_out, 1), cfg.dtype)[:, 0],
            }
        elif cfg.model == "sage":
            layer = {"w": _glorot(k1, (2 * d_in, d_out), cfg.dtype)}
        else:
            raise ValueError(cfg.model)
        params.append(layer)
    return params


# -------------------------------------------------------------------- layers
def _activation(x: jnp.ndarray, last: bool) -> jnp.ndarray:
    return x if last else jax.nn.relu(x)


def gcn_layer(p, h, src_dst, deg, n, last, aggregate: Aggregate):
    msgs = h[src_dst[:, 0]]
    agg = aggregate(msgs, src_dst[:, 1], n)                       # sum_{N_v} h_u
    out = (agg + h) / (deg[:, None] + 1.0)                        # / (|N_v|+1)
    return _activation(out @ p["w"], last)


def gat_layer(p, h, src_dst, deg, n, last, aggregate: Aggregate):
    wh = h @ p["w"]                                               # W h_u
    # Attention logits per link (GATv1): LeakyReLU(a_s . Wh_dst + a_d . Wh_src)
    alpha_dst = wh @ p["att_src"]                                 # (n,)
    alpha_src = wh @ p["att_dst"]                                 # (n,)
    # Self loops: every vertex attends to itself too (Eq. 2: N_v u {v}).
    self_ids = jnp.arange(n, dtype=src_dst.dtype)
    src = jnp.concatenate([src_dst[:, 0], self_ids])
    dst = jnp.concatenate([src_dst[:, 1], self_ids])
    logits = jax.nn.leaky_relu(alpha_dst[dst] + alpha_src[src], 0.2)
    # Softmax over each dst's incoming links (numerically stable via segment max).
    seg_max = jax.ops.segment_max(logits, dst, num_segments=n)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    ex = jnp.exp(logits - seg_max[dst])
    denom = aggregate(ex[:, None], dst, n)[:, 0]                  # sum exp per dst
    eta = ex / jnp.maximum(denom[dst], 1e-16)                     # eta_vu
    agg = aggregate(eta[:, None] * wh[src], dst, n)               # sum eta W h_u
    return _activation(agg, last)


def sage_layer(p, h, src_dst, deg, n, last, aggregate: Aggregate):
    msgs = h[src_dst[:, 0]]
    agg = aggregate(msgs, src_dst[:, 1], n) / jnp.maximum(deg, 1.0)[:, None]
    cat = jnp.concatenate([agg, h], axis=-1)                      # (a_v, h_v)
    return _activation(cat @ p["w"], last)


_LAYERS = {"gcn": gcn_layer, "gat": gat_layer, "sage": sage_layer}


def forward(
    cfg: GNNConfig,
    params,
    features: jnp.ndarray,
    src_dst: jnp.ndarray,
    n: Optional[int] = None,
    aggregate: Aggregate = segment_sum,
) -> jnp.ndarray:
    """Full-graph inference: features (n, s_0) -> embeddings (n, s_K)."""
    n = n if n is not None else features.shape[0]
    deg = degrees_from_directed(src_dst, n)
    layer_fn = _LAYERS[cfg.model]
    h = features.astype(cfg.dtype)
    for k, p in enumerate(params):
        h = layer_fn(p, h, src_dst, deg, n, k == cfg.num_layers - 1, aggregate)
    return h


def reference_forward(cfg: GNNConfig, params, features: np.ndarray,
                      edges: np.ndarray) -> np.ndarray:
    """The plain reference: :func:`forward` over the whole graph (undirected
    ``edges``) with every matmul at ``highest`` precision.  Served and
    sharded outputs are compared against it within a tolerance that states
    its reason; on TPU the default precision rounds matmul operands to
    bf16, which this reference does not."""
    with jax.default_matmul_precision("highest"):
        out = forward(cfg, params, jnp.asarray(features),
                      jnp.asarray(directed_edges(edges)))
    return np.asarray(out)


def loss_fn(cfg: GNNConfig, params, features, src_dst, labels, mask=None,
            aggregate: Aggregate = segment_sum):
    """Node-classification cross entropy (the paper's SIoT/Yelp tasks)."""
    logits = forward(cfg, params, features, src_dst, aggregate=aggregate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


@functools.partial(jax.jit, static_argnums=(0,))
def predict(cfg: GNNConfig, params, features, src_dst):
    return jnp.argmax(forward(cfg, params, features, src_dst), axis=-1)
