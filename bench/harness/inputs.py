"""Features from a run's ``--seed`` and weights from the configuration's
``weights_seed``, made on the device in one jitted call, in the type they
are served in (float32).

The weights are the deployed model's, fixed per configuration like its
graph: the program's ego forward bakes its weights into every compiled
program as constants, so weights drawn per run would make every run
compile each of its ~50 ego-forward programs again.  The features, the
data the requests read, change with every seed.

The weights take the program's parameter layout, which the model kind's
file gives (``bench/kinds/<kind>.py``: per layer, each leaf's name, shape
and fans), so they can be handed to it; their values come from here, never
from the program's ``init_params``.  Each layer has a key of its own,
split from ``weights_seed``; its first leaf draws from that key and a
further leaf ``i`` from the key folded with ``i``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import registry


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also one past 32 bits."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make(model: dict, n: int, seed: int, device=None):
    """(features (n, d_0) ~ N(0, 1) from ``seed``, weights: Glorot-uniform
    per leaf from ``model["weights_seed"]``) on ``device``."""
    layout = registry.kind(model["kind"]).weights(model)
    d0 = model["layer_dims"][0]

    def draw(key, wkey):
        feats = jax.random.normal(key, (n, d0), jnp.float32)
        weights = []
        for k, leaves in zip(jax.random.split(wkey, len(layout)), layout):
            layer = {}
            for i, (name, (shape, fi, fo)) in enumerate(leaves.items()):
                lim = (6.0 / (fi + fo)) ** 0.5
                lk = jax.random.fold_in(k, i) if i else k
                layer[name] = jax.random.uniform(lk, shape, jnp.float32,
                                                 -lim, lim)
            weights.append(layer)
        return feats, weights

    keys = (key_of(seed), key_of(model["weights_seed"]))
    if device is not None:
        keys = jax.device_put(keys, device)
    return jax.jit(draw)(*keys)
