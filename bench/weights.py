"""Model weights made by the benchmark from ``--seed``, on the device in one
jitted call, in float32 as the models train and serve them.  Glorot-uniform
weights and zero biases, laid out as the program's parameter list: one
dict per layer."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, stream: int = 2):
    """A PRNG key for any non-negative seed, however large."""
    word = np.random.SeedSequence([seed, stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def dims(in_dim: int, hidden: int, n_layers: int, n_classes: int) -> tuple:
    return tuple([in_dim] + [hidden] * (n_layers - 1) + [n_classes])


@functools.partial(jax.jit, static_argnames=("model", "dims"))
def init(k, model: str, dims: tuple):
    def glorot(k, fi, fo):
        lim = math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(k, (fi, fo), jnp.float32, -lim, lim)

    layers = []
    for kk, fi, fo in zip(jax.random.split(k, len(dims) - 1),
                          dims[:-1], dims[1:]):
        k1, k2 = jax.random.split(kk)
        b = jnp.zeros((fo,), jnp.float32)
        if model == "gcn":
            layers.append(dict(w=glorot(k1, fi, fo), b=b))
        elif model == "sage":
            layers.append(dict(w_self=glorot(k1, fi, fo),
                               w_neigh=glorot(k2, fi, fo), b=b))
        else:
            raise ValueError(model)
    return layers
