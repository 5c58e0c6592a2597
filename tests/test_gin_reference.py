"""GIN-epsilon through the program against a plain float32 reference.

The reference is the published layer (Xu et al., arXiv:1810.00826, eq.
4.1) written out in ``jax.numpy`` over a dense adjacency ``A[dst, src]``:
``h' = relu(((1 + eps) h + A h) W1 + b1) W2 + b2``, ReLU between layers,
at ``Precision.HIGHEST``.  The program runs the same weights, ``eps`` not
0, through ``gnn.forward`` over its decomposition, at 5 layers of 64,
under each structure pinned through the plan's ``EpilogueSpec`` and under
the structure ``gnn.layer_plan_inputs`` prices, with fused and unfused
kernels.  Logits, loss and every parameter's gradient are compared.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptgear, epilogue as ep_mod, gnn
from repro.core.plan import KernelPlan
from repro.graphs import graph as G

HIDDEN, LAYERS, CLASSES = 64, 5, 7
UNFUSED = ("block_diag", "csr")
FUSED = ("block_diag_fused", "csr_fused")
# Both sides compute in float32, in different orders (tiers, kernels,
# transform-first reassociation), and GIN's unnormalised sums grow the
# activations about (1 + degree)-fold a layer: errors stay a few float32
# ulps of each array's largest entry.  1e-4 of that entry leaves ample room
# and is far below what a dropped eps or self term moves (mutants below).
RTOL = 1e-4


@functools.lru_cache(maxsize=None)
def _setup(in_dim: int):
    n = 160
    src, dst = G.community_graph(n, 640, comm_size=16, intra_frac=0.7,
                                 seed=3)
    rng = np.random.default_rng(in_dim)
    x = (rng.standard_normal((n, in_dim)) * 0.1).astype(np.float32)
    labels = rng.integers(0, CLASSES, n).astype(np.int32)
    g = G.Graph(n, src, dst, x, labels, CLASSES)
    cfg = gnn.GNNConfig(model="gin", hidden=HIDDEN, n_layers=LAYERS,
                        comm_size=16, selector="cost_model")
    dec = gnn.prepare(g, cfg)
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (dst, src), 1.0)
    params = gnn.init_model(jax.random.PRNGKey(in_dim), cfg, in_dim,
                            CLASSES)
    params = [dict(p, eps=jnp.float32(0.1 * (i + 1) - 0.25))
              for i, p in enumerate(params)]
    return g, cfg, dec, jnp.asarray(a), params


def reference_logits(params, a, x):
    with jax.default_matmul_precision("highest"):
        h = x
        for i, p in enumerate(params):
            z = (1.0 + p["eps"]) * h + a @ h
            h = jax.nn.relu(z @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
            if i != len(params) - 1:
                h = jax.nn.relu(h)
        return h


def nll(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def program_logits(params, cfg, dec, x, plan):
    xr = adaptgear.to_reordered(dec, x)
    return adaptgear.from_reordered(dec, gnn.forward(params, cfg, dec, xr,
                                                     plan))


def pinned(dec, in_dim, structure, kernels):
    """A plan whose every layer runs ``structure`` with ``kernels``."""
    dims = [in_dim] + [HIDDEN] * (LAYERS - 1) + [CLASSES]
    eps = tuple(ep_mod.gin_layer_spec(dims[i], HIDDEN, dims[i + 1],
                                      structure) for i in range(LAYERS))
    return KernelPlan.make(dec, kernels, n_layers=LAYERS, epilogues=eps)


def priced(cfg, dec, in_dim):
    pairs, eps = gnn.layer_plan_inputs(cfg, in_dim, CLASSES, dec=dec)
    return gnn.select_plan(dec, cfg, pairs, epilogues=eps)[0]


def gaps(params, plan, in_dim) -> dict:
    """Largest error of the program's logits, loss and each gradient, run
    from ``params``, over the largest magnitude of the reference's, run
    from the seeded weights."""
    g, cfg, dec, a, ref_params = _setup(in_dim)
    x, labels = jnp.asarray(g.features), jnp.asarray(g.labels)

    def prog_loss(p):
        logits = program_logits(p, cfg, dec, x, plan)
        return nll(logits, labels), logits

    def ref_loss(p):
        logits = reference_logits(p, a, x)
        return nll(logits, labels), logits

    (lp, yp), gp = jax.value_and_grad(prog_loss, has_aux=True)(params)
    (lr, yr), gr = jax.value_and_grad(ref_loss, has_aux=True)(ref_params)

    def rel(u, v):
        return float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))

    out = dict(logits=rel(yp, yr), loss=rel(lp, lr))
    for i, (p, r) in enumerate(zip(gp, gr)):
        for k in r:
            out[f"d{k}[{i}]"] = rel(p[k], r[k])
    return out


CASES = {
    "transform_first-unfused": (50, "transform_first", UNFUSED),
    "transform_first-fused": (50, "transform_first", FUSED),
    "aggregate_first-unfused": (50, "aggregate_first", UNFUSED),
    "priced-50": (50, "priced", None),
    "priced-128": (128, "priced", None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_program_matches_reference(case):
    in_dim, structure, kernels = CASES[case]
    g, cfg, dec, a, params = _setup(in_dim)
    plan = (priced(cfg, dec, in_dim) if structure == "priced"
            else pinned(dec, in_dim, structure, kernels))
    if case == "priced-50":
        assert plan.epilogues[0].structure == "aggregate_first"
    if case == "priced-128":
        assert {e.structure for e in plan.epilogues} == {"transform_first"}
    bad = {k: v for k, v in gaps(params, plan, in_dim).items() if v > RTOL}
    assert not bad, (plan.layers, bad)


@pytest.mark.parametrize("mutant", ["gin0", "no_self_term"])
def test_tolerances_catch_a_wrong_layer(mutant):
    """GIN-0 (eps dropped: eps held at 0) and a layer without its self
    term (1 + eps = 0) fail the comparison, under either structure."""
    for structure in ("transform_first", "aggregate_first"):
        g, cfg, dec, a, params = _setup(50)
        eps = 0.0 if mutant == "gin0" else -1.0
        broken = [dict(p, eps=jnp.float32(eps)) for p in params]
        plan = pinned(dec, 50, structure, UNFUSED)
        gap = gaps(broken, plan, 50)
        assert gap["logits"] > 100 * RTOL and gap["loss"] > RTOL, gap


@pytest.mark.parametrize("model,want", [("gin", jnp.float32),
                                        ("gcn", jnp.bfloat16)])
def test_bf16_gin_is_priced_at_float32(monkeypatch, model, want):
    """``gin_conv`` computes up to its ReLU in float32, so ``gnn.train``
    prices and probes a bfloat16 GIN's kernels at float32; other models
    keep the features' dtype."""
    g, cfg, _, _, _ = _setup(50)
    g = G.Graph(g.n, g.senders, g.receivers,
                np.asarray(g.features, jnp.bfloat16), g.labels, CLASSES)
    seen = {}

    class Priced(Exception):
        pass

    def select_plan(dec, cfg, pairs, dtype, epilogues):
        seen["dtype"] = dtype
        raise Priced

    monkeypatch.setattr(gnn, "select_plan", select_plan)
    with pytest.raises(Priced):
        gnn.train(g, dataclasses.replace(cfg, model=model), steps=1)
    assert jnp.dtype(seen["dtype"]) == jnp.dtype(want)
