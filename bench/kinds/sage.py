"""GraphSAGE with the mean aggregator (arXiv 2210.17281, Eq. 3), the model
kind ``sage``:

  h_v' = sigma(W . concat(mean_{u in N_v} h_u, h_v))

sigma is ReLU on hidden layers and the identity on the last; a vertex with
no neighbours takes a mean of zeros.  One weight ``w`` of ``(2 d_in,
d_out)`` a layer, as the program's ``init_params`` lays it out.
"""
import jax
import jax.numpy as jnp

from harness.work import INDEX


def weights(model: dict) -> list:
    """Per layer, ``{name: (shape, fan_in, fan_out)}`` in drawing order."""
    dims = model["layer_dims"]
    return [{"w": ((2 * d_in, d_out), 2 * d_in, d_out)}
            for d_in, d_out in zip(dims[:-1], dims[1:])]


def layer(model: dict, k: int, p: dict, h, g, dt, prec):
    """Layer ``k`` of the reference over the whole graph ``g`` (``src``,
    ``dst``, in-degree ``deg``, ``n``), every array in ``dt``, the product
    with the weight at ``prec``."""
    agg = jax.ops.segment_sum(h[g.src], g.dst, num_segments=g.n)
    z = jnp.concatenate([agg / jnp.maximum(g.deg, 1)[:, None], h], -1)
    out = jnp.dot(z, p["w"].astype(dt), precision=prec,
                  preferred_element_type=dt)
    return out if k == len(model["layer_dims"]) - 2 else jnp.maximum(out, 0)


def flops(model: dict, n: int, arcs: int) -> int:
    """One whole-graph forward: per layer the neighbour adds, the mean's
    divide and the product with the ``2 d_in``-wide ``w``."""
    dims = model["layer_dims"]
    return sum(arcs * d_in + n * d_in + 2 * n * (2 * d_in) * d_out
               for d_in, d_out in zip(dims[:-1], dims[1:]))


def bytes(model: dict, n: int, arcs: int) -> int:
    """The least one whole-graph forward moves: per layer every input row,
    output row and the ``2 d_in``-wide weight once, the in-degree, and each
    arc's two int32 indices."""
    dims, f = model["layer_dims"], jnp.dtype(model["dtype"]).itemsize
    return sum(f * (n * d_in + n * d_out + 2 * d_in * d_out + n)
               + 2 * arcs * INDEX
               for d_in, d_out in zip(dims[:-1], dims[1:]))


def config_fields(model: dict) -> dict:
    """Keyword fields of the program's ``GNNConfig`` beyond kind and widths."""
    return {}
