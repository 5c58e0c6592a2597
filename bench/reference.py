"""Plain references for the benchmark's models, independent of the program.

Each model is written out in ``jax.numpy`` over an edge list: no tiers, no
kernels, no plan, no padding beyond masks, all arrays in float32.

``precision`` names how the two products of a layer, the matmul and the
aggregation's gather and segment sum, are computed:

- ``"highest"``: every matmul at ``Precision.HIGHEST`` and the aggregation
  in float32.  This is the reference.
- ``"bf16"``: the configurations' stated precision.  Both inputs of every
  matmul and the aggregated features are rounded to bfloat16, forward and
  backward (the cotangent entering each product too), and every product
  accumulates in float32.  A program computed so must pass the comparison.
- ``"fp8"``: the control, the precision below the stated one.  As
  ``"bf16"``, but rounded to float8 (e4m3) under a per-tensor scale (the
  tensor's largest magnitude maps to e4m3's largest, 448).  The comparison
  must refuse it.

Adam follows the program's published form (b1 0.9, b2 0.999, eps 1e-8,
bias-corrected); its state stays float32 in every precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


E4M3_MAX = 448.0
PRECISIONS = ("highest", "bf16", "fp8")


def _hi(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _round(t, fmt: str):
    if fmt == "bf16":
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.max(jnp.abs(t)) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm_low(a, b, fmt):
    return _hi(_round(a, fmt), _round(b, fmt))


def _mm_low_fwd(a, b, fmt):
    a, b = _round(a, fmt), _round(b, fmt)
    return _hi(a, b), (a, b)


def _mm_low_bwd(fmt, res, g):
    a, b = res
    g = _round(g, fmt)
    return _hi(g, b.T), _hi(a.T, g)


_mm_low.defvjp(_mm_low_fwd, _mm_low_bwd)


def _mm(a, b, precision: str):
    if precision == "highest":
        return _hi(a, b)
    if precision not in PRECISIONS:
        raise ValueError(precision)
    return _mm_low(a, b, precision)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _agg_low(h, src, dst, scale, n, fmt):
    # h has n rows in both models, so the transpose also sums into n rows
    return jax.ops.segment_sum(_round(h, fmt)[src] * scale[:, None], dst,
                               num_segments=n)


def _agg_low_fwd(h, src, dst, scale, n, fmt):
    return _agg_low(h, src, dst, scale, n, fmt), (src, dst, scale)


def _agg_low_bwd(n, fmt, res, g):
    src, dst, scale = res
    dh = jax.ops.segment_sum(_round(g, fmt)[dst] * scale[:, None], src,
                             num_segments=n)
    return dh, None, None, None


_agg_low.defvjp(_agg_low_fwd, _agg_low_bwd)


def _agg(h, src, dst, scale, n, precision: str):
    """sum over edges (s -> d) of ``h[s] * scale[e]`` into row ``d``."""
    if precision == "highest":
        return jax.ops.segment_sum(h[src] * scale[:, None], dst,
                                   num_segments=n)
    if precision not in PRECISIONS:
        raise ValueError(precision)
    return _agg_low(h, src, dst, scale, n, precision)


def nll(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    per = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    per = jnp.where(mask, per, 0)
    return per.sum() / jnp.maximum(mask.sum(), 1)


# --- GCN (Kipf & Welling): Y = D_in^-1/2 (A + I) D_out^-1/2 (X W) + b -----

def gcn_edges(n: int, senders: np.ndarray, receivers: np.ndarray):
    """Self-loops added, symmetric normalization per edge (host arrays)."""
    loop = np.arange(n, dtype=np.int32)
    src = np.concatenate([senders, loop]).astype(np.int32)
    dst = np.concatenate([receivers, loop]).astype(np.int32)
    d_in = np.maximum(np.bincount(dst, minlength=n), 1).astype(np.float64)
    d_out = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float64)
    norm = (d_in[dst] ** -0.5 * d_out[src] ** -0.5).astype(np.float32)
    return src, dst, norm


def gcn_forward(params, x, src, dst, norm, n, precision):
    h = x
    for i, layer in enumerate(params):
        hw = _mm(h, layer["w"], precision)
        h = _agg(hw, src, dst, norm, n, precision) + layer["b"]
        if i != len(params) - 1:
            h = jax.nn.relu(h)
    return h


@functools.partial(jax.jit, static_argnames=("n", "precision"))
def gcn_loss_grad(params, x, src, dst, norm, labels, mask, n, precision):
    def loss(p):
        return nll(gcn_forward(p, x, src, dst, norm, n, precision), labels,
                   mask)
    return jax.value_and_grad(loss)(params)


# --- GraphSAGE-mean: Y = X W_self + mean_{in-nbrs}(X) W_neigh + b --------

def sage_forward(params, x, src, dst, emask, n, precision):
    w = emask.astype(x.dtype)
    deg = jax.ops.segment_sum(w, dst, num_segments=n)
    inv = jnp.where(deg > 0, 1 / jnp.maximum(deg, 1), 0).astype(x.dtype)
    h = x
    for i, layer in enumerate(params):
        agg = _agg(h, src, dst, w, n, precision) * inv[:, None]
        h = (_mm(h, layer["w_self"], precision)
             + _mm(agg, layer["w_neigh"], precision) + layer["b"])
        if i != len(params) - 1:
            h = jax.nn.relu(h)
    return h


@functools.partial(jax.jit, static_argnames=("precision",))
def sage_loss_grad(params, x, src, dst, emask, labels, tmask, precision):
    def loss(p):
        return nll(sage_forward(p, x, src, dst, emask, x.shape[0], precision),
                   labels, tmask)
    return jax.value_and_grad(loss)(params)


# --- Adam -----------------------------------------------------------------

def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return dict(m=z, v=jax.tree.map(jnp.zeros_like, params), t=0)


@functools.partial(jax.jit, static_argnames=("lr",))
def _adam(params, grads, m, v, t, lr):
    m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m, grads)
    v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v, grads)
    mh = jax.tree.map(lambda m: m / (1 - B1 ** t), m)
    vh = jax.tree.map(lambda v: v / (1 - B2 ** t), v)
    new = jax.tree.map(lambda p, a, b: p - lr * a / (jnp.sqrt(b) + EPS),
                       params, mh, vh)
    return new, m, v


def adam_step(params, grads, opt, lr):
    t = opt["t"] + 1
    new, m, v = _adam(params, grads, opt["m"], opt["v"],
                      jnp.float32(t), lr=lr)
    return new, dict(m=m, v=v, t=t)


def train_steps(loss_grad, params, feeds, lr):
    """Run one Adam step per feed from ``params``.  Returns the losses, the
    first step's gradient and the parameters after the last step."""
    opt = adam_init(params)
    losses, first_grad = [], None
    for feed in feeds:
        loss, grads = loss_grad(params, *feed)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = grads
        params, opt = adam_step(params, grads, opt, lr)
    return losses, first_grad, params
