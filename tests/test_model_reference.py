"""GCN, GraphSAGE-mean and GIN through the program's step builders against
a plain float32 reference.

The references are the published layers written out in ``jax.numpy`` over a
dense adjacency ``A[dst, src]`` built from the graph's (or the sampled
batch's) edge list, at ``Precision.HIGHEST``, ReLU between layers:

- GCN (Kipf & Welling), transform-first: ``h' = Â (h W) + b`` with
  ``Â = D_r^-1/2 (A + I) D_c^-1/2`` (row and column sums of ``A + I``);
- GraphSAGE-mean (Hamilton et al.): ``h' = h W_self + D^-1 A h W_neigh + b``;
- GIN-eps (Xu et al., eq. 4.1): ``h' = relu(((1 + eps) h + A h) W1 + b1)
  W2 + b2``.

The program runs the same weights, biases and ``eps`` not 0, through the
builders the system trains and serves with: ``gnn.make_train_step`` over
``gnn.prepare``'s decomposition, ``gnn_steps.make_sampled_step`` over a
``prepare_skeleton`` batch padded by ``fix_shapes``, and
``gnn_steps.make_infer_step`` over the same batch.  A train step returns the
loss and, in its Adam state, the gradient: from a zero state the first
moment is exactly ``(1 - b1) g``.  Logits, loss and every parameter's
gradient are compared (logits only for the infer step), each as its
largest error over the reference's largest magnitude.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptgear, gnn
from repro.core.plan import KernelPlan
from repro.graphs import graph as G
from repro.sampling.plan_cache import fix_shapes, plan_payload_keys
from repro.train import gnn_steps

IN_DIM, HIDDEN, CLASSES, COMM = 24, 32, 5, 16
LAYERS = {"gcn": 3, "sage": 2, "gin": 2}
PLANS = {"diag+csr": ("block_diag", "csr"),
         "fused": ("block_diag_fused", "csr_fused"),
         "coo": ("coo", "coo")}
# Both sides compute in float32, in different orders (tiers, kernels, the
# transform-first reassociation): errors stay a few ulps of each array's
# largest entry.  1e-4 of that entry is far below what a dropped tier, self
# loop, self term or normalisation moves.
RTOL = 1e-4
ADAM_B1 = 0.9       # gnn._adam_update's first-moment decay


@functools.lru_cache(maxsize=None)
def _graph() -> G.Graph:
    n = 192
    src, dst = G.community_graph(n, 900, comm_size=COMM, intra_frac=0.7,
                                 seed=5)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((n, IN_DIM)).astype(np.float32)
    labels = rng.integers(0, CLASSES, n).astype(np.int32)
    return G.Graph(n, src, dst, x, labels, CLASSES)


def _cfg(model: str, k: int, **kw) -> gnn.GNNConfig:
    return gnn.GNNConfig(model=model, hidden=HIDDEN, n_layers=LAYERS[model],
                         comm_size=COMM, inter_buckets=k, selector="fixed",
                         seed=1, **kw)


def _params(cfg: gnn.GNNConfig):
    """Seeded weights with biases and ``eps`` away from 0, so a dropped
    bias or self term shows."""
    params = gnn.init_model(jax.random.PRNGKey(7), cfg, IN_DIM, CLASSES)
    rng = np.random.default_rng(11)
    out = []
    for p in params:
        q = dict(p)
        for name in p:
            if name.startswith("b") or name == "eps":
                q[name] = jnp.asarray(
                    0.3 * rng.standard_normal(np.shape(p[name])),
                    jnp.float32)
        out.append(q)
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def dense_adjacency(n, senders, receivers, self_loops=None) -> np.ndarray:
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (receivers, senders), 1.0)
    if self_loops is not None:
        a[self_loops, self_loops] += 1.0
    return a


def reference_logits(model, params, a, x):
    a = jnp.asarray(a)
    if model == "gcn":
        r = jnp.maximum(a.sum(1), 1.0) ** -0.5
        c = jnp.maximum(a.sum(0), 1.0) ** -0.5
        a = a * r[:, None] * c[None, :]
    elif model == "sage":
        a = a / jnp.maximum(a.sum(1), 1.0)[:, None]
    with jax.default_matmul_precision("highest"):
        h = x
        for i, p in enumerate(params):
            if model == "gcn":
                h = a @ (h @ p["w"]) + p["b"]
            elif model == "sage":
                h = h @ p["w_self"] + (a @ h) @ p["w_neigh"] + p["b"]
            else:
                z = (1.0 + p["eps"]) * h + a @ h
                h = jax.nn.relu(z @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
            if i != len(params) - 1:
                h = jax.nn.relu(h)
        return h


def reference_loss(model, params, a, x, labels, mask):
    logits = reference_logits(model, params, a, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(mask, nll, 0.0)) / jnp.maximum(mask.sum(), 1)


def _rel(u, v) -> float:
    u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
    return float(np.max(np.abs(u - v)) / max(np.max(np.abs(v)), 1e-30))


def _compare(model, params, a, x, labels, mask, logits, loss=None,
             opt=None) -> dict:
    """Each compared quantity's error relative to the reference's largest
    magnitude; the gradient is read from the step's Adam first moment."""
    out = dict(logits=_rel(logits, reference_logits(model, params, a, x)))
    if loss is None:
        return out
    lr, gr = jax.value_and_grad(reference_loss, argnums=1)(
        model, params, a, x, labels, mask)
    out["loss"] = _rel(loss, lr)
    for i, (m, r) in enumerate(zip(opt["m"], gr)):
        for name in r:
            out[f"d{name}[{i}]"] = _rel(m[name] / (1 - ADAM_B1), r[name])
    return out


# ---------------------------------------------------------------------------
# the three builders
# ---------------------------------------------------------------------------

def run_full(model, kernels, k):
    """``gnn.make_train_step`` over ``gnn.prepare``'s decomposition, with
    the arguments ``gnn.train`` gives it."""
    g, cfg = _graph(), _cfg(model, k)
    dec = gnn.prepare(g, cfg)
    plan = KernelPlan.make(dec, kernels, n_layers=cfg.n_layers)
    params = _params(cfg)
    perm = np.asarray(dec.perm)
    x = adaptgear.to_reordered(dec, jnp.asarray(g.features))
    labels = np.zeros((dec.n_pad,), np.int32)
    labels[perm] = g.labels
    mask = np.zeros((dec.n_pad,), bool)
    mask[perm] = True
    inv_deg = jnp.zeros((dec.n_pad,), jnp.float32)
    _, opt, loss = gnn.make_train_step(cfg, plan)(
        params, gnn._adam_init(params), dec, x, jnp.asarray(labels),
        jnp.asarray(mask), inv_deg)
    logits = adaptgear.from_reordered(
        dec, jax.jit(lambda p, d, x: gnn.forward(p, cfg, d, x, plan))(
            params, dec, x))
    loops = np.arange(g.n) if model == "gcn" else None
    a = dense_adjacency(g.n, g.senders, g.receivers, loops)
    return _compare(model, params, a, jnp.asarray(g.features),
                    jnp.asarray(g.labels), jnp.ones((g.n,), bool), logits,
                    loss, opt)


def _sampled(model, kernels, k):
    """One sampled batch, prepared as ``train_minibatch`` prepares it:
    ``prepare_skeleton``, the plan's payloads, ``fix_shapes`` to the
    sampler's budget."""
    sampler = "cluster" if model == "gcn" else "neighbor"
    cfg = _cfg(model, k, sampler=sampler, clusters_per_batch=6,
               batch_nodes=16, fanouts=(3, 2))
    smp = gnn_steps.make_sampler(_graph(), cfg)
    batch = smp.sample()
    skel, inv_deg = gnn_steps.prepare_skeleton(batch, cfg)
    dec = skel.materialize()
    plan = KernelPlan.make(dec, kernels, n_layers=cfg.n_layers)
    keep = plan_payload_keys(plan)
    pad = smp.edge_budget + (smp.node_budget if model == "gcn" else 0)
    fixed = fix_shapes(skel.materialize(keep), pad, keep=keep)
    s, r = batch.real_edges()
    loops = batch.node_mask.nonzero()[0] if model == "gcn" else None
    a = dense_adjacency(batch.n, s, r, loops)
    return cfg, batch, plan, fixed, inv_deg, a


def run_sampled(model, kernels, k):
    cfg, batch, plan, dec, inv_deg, a = _sampled(model, kernels, k)
    params = _params(cfg)
    _, opt, loss, finite = gnn_steps.make_sampled_step(
        cfg, plan, {"traces": 0})(
            params, gnn._adam_init(params), dec, batch.features,
            batch.labels, batch.target_mask, inv_deg)
    assert bool(finite)
    logits = gnn_steps.make_infer_step(cfg, plan, {"traces": 0})(
        params, dec, batch.features, inv_deg)
    return _compare(model, params, a, jnp.asarray(batch.features),
                    jnp.asarray(batch.labels),
                    jnp.asarray(batch.target_mask), logits, loss, opt)


def run_infer(model, kernels, k):
    cfg, batch, plan, dec, inv_deg, a = _sampled(model, kernels, k)
    params = _params(cfg)
    logits = gnn_steps.make_infer_step(cfg, plan, {"traces": 0})(
        params, dec, batch.features, inv_deg)
    return _compare(model, params, a, jnp.asarray(batch.features), None,
                    None, logits)


BUILDERS = {"train": run_full, "sampled": run_sampled, "infer": run_infer}


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("builder", list(BUILDERS))
@pytest.mark.parametrize("plan", list(PLANS))
@pytest.mark.parametrize("model", ["gcn", "sage"])
def test_gcn_sage_match_reference(model, plan, builder, k):
    gap = BUILDERS[builder](model, PLANS[plan], k)
    bad = {q: v for q, v in gap.items() if not v <= RTOL}
    assert not bad, bad
    if builder != "infer":
        assert len(gap) == 2 + sum(len(p) for p in _params(
            _cfg(model, k)))


@pytest.mark.parametrize("builder", ["sampled", "infer"])
@pytest.mark.parametrize("plan", list(PLANS))
def test_gin_batch_matches_reference(plan, builder):
    gap = BUILDERS[builder]("gin", PLANS[plan], 4)
    bad = {q: v for q, v in gap.items() if not v <= RTOL}
    assert not bad, bad
