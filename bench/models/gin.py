"""GIN-epsilon for the benchmark: its weights, its plain reference, and the
work a training step requires.  Found by a configuration's ``model``.

The layer (Xu et al., arXiv:1810.00826, eq. 4.1), with a two-layer MLP:

    z  = (1 + eps) h + sum over in-neighbours u of h_u
    h' = relu(z W1 + b1) W2 + b2

ReLU between layers, none after the last.  The sum runs over the listed
edges as they are: unit values, no self-loops added, no normalisation.

The reference is written in the published aggregate-first form over the
edge list, in float32, and imports nothing of the program.  Its products
are ``bench/reference.py``'s, so ``"highest"``, ``"bf16"`` and ``"fp8"``
mean there what they mean for GCN.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import reference, weights

STRUCTURES = ("transform_first", "aggregate_first")


@functools.partial(jax.jit, static_argnames=("dims", "hidden"))
def _init(k, dims: tuple, hidden: int):
    def linear(k, fi, fo):
        kw, kb = jax.random.split(k)
        lim = 1.0 / math.sqrt(fi)
        return (jax.random.uniform(kw, (fi, fo), jnp.float32, -lim, lim),
                jax.random.uniform(kb, (fo,), jnp.float32, -lim, lim))

    layers = []
    for kk, fi, fo in zip(jax.random.split(k, len(dims) - 1),
                          dims[:-1], dims[1:]):
        k1, k2 = jax.random.split(kk)
        w1, b1 = linear(k1, fi, hidden)
        w2, b2 = linear(k2, hidden, fo)
        layers.append(dict(eps=jnp.zeros((), jnp.float32),
                           w1=w1, b1=b1, w2=w2, b2=b2))
    return layers


def init(seed: int, in_dim: int, hidden: int, n_layers: int,
         n_classes: int) -> list:
    """Weights from ``seed`` in the program's layout, on the device: per
    layer ``eps`` 0 and each linear map's weight and bias uniform in
    +-1/sqrt(fan_in), the default of ``torch.nn.Linear`` that Xu et al.'s
    published code keeps.  Glorot weights would not do without the
    BatchNorm that the configuration drops: each layer's sum over about
    12 neighbours would grow the activations several-fold, the first
    loss would be in the hundreds, and rounding would flip the saturated
    softmax's argmax on enough nodes to blur the first gradient."""
    return _init(weights.key(seed), weights.dims(in_dim, hidden, n_layers,
                                                 n_classes), hidden)


def forward(params, x, src, dst, n, precision):
    ones = jnp.ones(src.shape, x.dtype)
    h = x
    for i, layer in enumerate(params):
        z = ((1.0 + layer["eps"]) * h
             + reference._agg(h, src, dst, ones, n, precision))
        h1 = jax.nn.relu(reference._mm(z, layer["w1"], precision)
                         + layer["b1"])
        h = reference._mm(h1, layer["w2"], precision) + layer["b2"]
        if i != len(params) - 1:
            h = jax.nn.relu(h)
    return h


@functools.partial(jax.jit, static_argnames=("n", "precision"))
def loss_grad(params, x, src, dst, labels, mask, n, precision):
    def loss(p):
        return reference.nll(forward(p, x, src, dst, n, precision), labels,
                             mask)
    return jax.value_and_grad(loss)(params)


# --- work counts ----------------------------------------------------------
#
# As bench/flops.py counts them: real nodes and edges only, a matmul of
# (m, k) by (k, n) is 2mkn operations, one aggregation pass at width d over
# E edges 2Ed, only what the gradient needs, elementwise work left out.

def layer_work(n: int, e: int, fin: int, hidden: int, fout: int,
               first: bool, structure: str) -> tuple[int, int, list]:
    """(dense, aggregation, pass widths) of one layer's training step.

    transform-first: S = X W1 is aggregated at the hidden width, forward
    and back (the backward pass feeds dW1); dX only after layer 0.
    aggregate-first: X is aggregated at its own width; the backward pass
    A^T dz exists only for an input gradient, but dz itself is needed
    by eps's gradient, so z W1 costs three matmuls in every layer."""
    w2 = 3 * 2 * n * hidden * fout            # h1 W2, dW2, dh1
    if structure == "transform_first":
        w1 = 2 * n * fin * hidden * (2 if first else 3)
        passes = [hidden, hidden]
    elif structure == "aggregate_first":
        w1 = 3 * 2 * n * fin * hidden
        passes = [fin] if first else [fin, fin]
    else:
        raise ValueError(structure)
    return w1 + w2, sum(2 * e * d for d in passes), passes


def step_work(n: int, e: int, in_dim: int, hidden: int, n_layers: int,
              n_classes: int, structures) -> dict:
    """A training step's work.  ``flops`` takes each layer at the smaller
    of its two structures' operations, so it does not move with the plan;
    ``agg_passes`` lists the passes the committed ``structures`` run."""
    d = weights.dims(in_dim, hidden, n_layers, n_classes)
    dense = agg = 0
    passes = []
    for i, (fin, fout, structure) in enumerate(zip(d[:-1], d[1:],
                                                   structures)):
        both = [layer_work(n, e, fin, hidden, fout, i == 0, s)
                for s in STRUCTURES]
        least = min(both, key=lambda w: w[0] + w[1])
        dense += least[0]
        agg += least[1]
        passes += both[STRUCTURES.index(structure)][2]
    return dict(flops=dense + agg, dense_flops=dense, agg_flops=agg,
                agg_passes=passes)
