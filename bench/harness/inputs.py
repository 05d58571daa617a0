"""Features from a run's ``--seed`` and weights from the configuration's
``weights_seed``, made on the device in one jitted call, in the type they
are served in (float32).

The weights are the deployed model's, fixed per configuration like its
graph: the program's ego forward bakes its weights into every compiled
program as constants, so weights drawn per run would make every run
compile each of its ~50 ego-forward programs again.  The features, the
data the requests read, change with every seed.

The weights take the program's parameter layout (a list of ``{"w": ...}``,
SAGE's ``w`` being ``(2 * d_in, d_out)``) so they can be handed to it; their
values come from here, never from the program's ``init_params``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also one past 32 bits."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def weight_shapes(model: dict) -> list:
    dims = model["layer_dims"]
    wide = 2 if model["kind"] == "sage" else 1
    return [(wide * dims[k], dims[k + 1]) for k in range(len(dims) - 1)]


def make(model: dict, n: int, seed: int, device=None):
    """(features (n, d_0) ~ N(0, 1) from ``seed``, weights: Glorot-uniform
    list from ``model["weights_seed"]``) on ``device``."""
    shapes = weight_shapes(model)
    d0 = model["layer_dims"][0]

    def draw(key, wkey):
        feats = jax.random.normal(key, (n, d0), jnp.float32)
        weights = []
        for k, (fi, fo) in zip(jax.random.split(wkey, len(shapes)), shapes):
            lim = (6.0 / (fi + fo)) ** 0.5
            weights.append({"w": jax.random.uniform(k, (fi, fo), jnp.float32,
                                                    -lim, lim)})
        return feats, weights

    keys = (key_of(seed), key_of(model["weights_seed"]))
    if device is not None:
        keys = jax.device_put(keys, device)
    return jax.jit(draw)(*keys)
