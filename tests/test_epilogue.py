"""Generalized epilogue fusion (GIN's MLP, SAGE's dual weights) + the
spill/probe feedback satellites.

Property tests (hypothesis, f32/bf16): the epilogue-fused GIN/SAGE layers
— weight pushed through the aggregation, self terms seeding the threaded
accumulator, dual stripes in the Pallas kernel — must match the legacy
unfused dense reference for forward AND grads over k in {1, 2, 4} bucket
counts and over budget-capped blocked-ELL payloads with real spill.  Plus:
the no-retrace contract for fused-epilogue mini-batch plans, free-transform
selection honesty, budget-K autotuning from observed spill, adaptive probe
widening, and the cluster-tuple skeleton cache.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import example, given, settings, st
from _tiers import SpiedRegistry, tier_sum

from repro.core import adaptgear, decompose, epilogue as ep_mod, gnn
from repro.core import selector as sel_mod
from repro.graphs import graph as G
from repro.kernels.registry import REGISTRY
from repro.sampling.plan_cache import MB_KERNELS, PlanCache
from repro.train import gnn_steps


def make_graph(n=180, e=1400, nf=5, nc=3, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    key = src.astype(np.int64) * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    feats = rng.standard_normal((n, nf)).astype(np.float32)
    labels = rng.integers(0, nc, n).astype(np.int32)
    return G.Graph(n, src, dst, feats, labels, nc)


@functools.lru_cache(maxsize=None)
def cached(model, k):
    g = make_graph()
    cfg = gnn.GNNConfig(model=model, comm_size=8, reorder="bfs",
                        inter_buckets=k, hidden=8)
    dec = gnn.prepare(g, cfg)        # bakes SAGE's mean norm into the vals
    a = np.zeros((g.n, g.n), np.float32)
    a[g.receivers, g.senders] = 1.0
    if model == "sage":
        deg = np.bincount(g.receivers, minlength=g.n).astype(np.float32)
        a = a / np.maximum(deg, 1.0)[:, None]
    return g, a, dec, cfg


def dense_layer(model, layer, a, x):
    """Legacy unfused reference for one conv layer (float64-free f32)."""
    if model == "gin":
        h = (1.0 + np.asarray(layer["eps"])) * x + a @ x
        h = np.maximum(h @ np.asarray(layer["w1"]) + np.asarray(layer["b1"]),
                       0.0)
        return h @ np.asarray(layer["w2"]) + np.asarray(layer["b2"])
    agg = a @ x                        # a already carries the mean norm
    return (x @ np.asarray(layer["w_self"])
            + agg @ np.asarray(layer["w_neigh"]) + np.asarray(layer["b"]))


def tol(dt):
    return dict(atol=1e-4, rtol=1e-4) if dt == jnp.float32 else \
        dict(atol=2e-1, rtol=3e-1)


PLANS = [("block_diag_fused", "bell_fused"),
         ("block_diag_fused", "csr_fused"),
         ("block_diag", "bell_fused")]
MODELS = ["gin", "sage"]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), mi=st.integers(0, 1),
       ki=st.integers(0, 2), pi=st.integers(0, len(PLANS) - 1),
       bf16=st.booleans())
# a bfloat16 GIN pre-activation within rounding of zero: the ReLU mask
# flipped when tier partials were summed in bfloat16
@example(seed=27, mi=0, ki=0, pi=0, bf16=True)
def test_fused_epilogue_matches_dense_fwd_and_grad(seed, mi, ki, pi, bf16):
    """Fused GIN/SAGE forward + grads (wrt inputs AND every epilogue
    parameter) == the unfused dense reference, f32 and bf16, any bucket
    count, any fused plan shape."""
    dt = jnp.bfloat16 if bf16 else jnp.float32
    model, k = MODELS[mi], [1, 2, 4][ki]
    g, a, dec, cfg = cached(model, k)
    rng = np.random.default_rng(seed)
    params = [jax.tree.map(lambda v: jnp.asarray(
        rng.standard_normal(v.shape) * 0.5, dt), layer)
        for layer in gnn.init_model(jax.random.PRNGKey(0), cfg,
                                    5, g.n_classes)][:1]
    layer = params[0]
    x = jnp.asarray(rng.standard_normal((g.n, 5)), dt)
    cot = rng.standard_normal((g.n, a.shape[0]))  # unused cols sliced below
    conv = adaptgear.gin_conv if model == "gin" else adaptgear.sage_conv

    def fused(layer, x):
        xr = adaptgear.to_reordered(dec, x)
        return adaptgear.from_reordered(dec, conv(layer, dec, xr, PLANS[pi]))

    y = np.asarray(fused(layer, x), np.float32)
    xf = np.asarray(x, np.float32)
    layer_f = jax.tree.map(lambda v: np.asarray(v, np.float32), layer)
    y_ref = dense_layer(model, layer_f, a, xf)
    np.testing.assert_allclose(y, y_ref, **tol(dt),
                               err_msg=f"{model} k={k} plan={PLANS[pi]} fwd")

    cot = jnp.asarray(cot[:, : y.shape[-1]], jnp.float32)
    grads = jax.grad(lambda l, x: jnp.sum(
        fused(l, x).astype(jnp.float32) * cot), argnums=(0, 1))(layer, x)

    def ref_loss(layer, x):
        xr = adaptgear.to_reordered(dec, x)
        names = ("block_diag", "bell")        # unfused registry reference
        return jnp.sum(adaptgear.from_reordered(
            dec, conv(layer, dec, xr, names)).astype(jnp.float32) * cot)

    grads_ref = jax.grad(ref_loss, argnums=(0, 1))(layer, x)
    for (ga, gb) in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(ga, np.float32),
                                   np.asarray(gb, np.float32), **tol(dt),
                                   err_msg=f"{model} k={k} plan={PLANS[pi]}")


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("ik,ek", PLANS)
@pytest.mark.parametrize("k", [1, 2, 4])
def test_fused_epilogue_matches_dense_deterministic(model, ik, ek, k, rng):
    """Non-hypothesis twin of the property test (runs on machines without
    hypothesis): forward + full grads, f32."""
    g, a, dec, cfg = cached(model, k)
    layer = gnn.init_model(jax.random.PRNGKey(1), cfg, 5, g.n_classes)[0]
    x = jnp.asarray(rng.standard_normal((g.n, 5)), jnp.float32)
    conv = adaptgear.gin_conv if model == "gin" else adaptgear.sage_conv

    def fused(layer, x):
        xr = adaptgear.to_reordered(dec, x)
        return adaptgear.from_reordered(dec, conv(layer, dec, xr, (ik, ek)))

    y = np.asarray(fused(layer, x), np.float32)
    y_ref = dense_layer(model, jax.tree.map(np.asarray, layer), a,
                        np.asarray(x))
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)

    cot = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    loss = lambda f: lambda l, x: jnp.sum(  # noqa: E731
        f(l, x).astype(jnp.float32) * cot)

    def unfused(layer, x):
        xr = adaptgear.to_reordered(dec, x)
        return adaptgear.from_reordered(
            dec, conv(layer, dec, xr, ("block_diag", "bell")))

    grads = jax.grad(loss(fused), argnums=(0, 1))(layer, x)
    grads_ref = jax.grad(loss(unfused), argnums=(0, 1))(layer, x)
    for ga, gb in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   atol=1e-3, rtol=1e-3)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), mi=st.integers(0, 1),
       bf16=st.booleans())
def test_fused_epilogue_over_capped_bell_with_spill(seed, mi, bf16):
    """The mini-batch payload shape: budget-capped blocked-ELL whose cap
    actually spills edges to the in-payload COO.  Fused GIN/SAGE forward +
    grads must stay exact — pad + spill decompose the same matrix."""
    dt = jnp.bfloat16 if bf16 else jnp.float32
    model = MODELS[mi]
    rng = np.random.default_rng(seed)
    n, B = 128, 8
    # hub-heavy: one dense destination block-row spanning many far blocks
    hub_dst = rng.integers(0, B, 300)
    hub_src = rng.integers(0, n, 300)
    base_src = rng.integers(0, n, 200)
    base_dst = rng.integers(0, n, 200)
    src = np.concatenate([hub_src, base_src]).astype(np.int32)
    dst = np.concatenate([hub_dst, base_dst]).astype(np.int32)
    key = src.astype(np.int64) * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    feats = rng.standard_normal((n, 6)).astype(np.float32)
    g = G.Graph(n, src, dst, feats, rng.integers(0, 3, n).astype(np.int32), 3)
    vals = (G.mean_norm_values(n, src, dst) if model == "sage" else None)
    dec = decompose.decompose(
        g, comm_size=B, method="bfs", edge_vals=vals, inter_buckets=2,
        keep_empty_buckets=True, edge_budget=len(src),
        kernels=MB_KERNELS)
    spills = [s.formats["bell"][2].nnz for s in dec.inters
              if "bell" in s.formats]
    assert any(sp > 0 for sp in spills), "profile must exercise the spill"

    a = np.zeros((n, n), np.float32)
    a[dst, src] = 1.0
    if model == "sage":
        deg = np.bincount(dst, minlength=n).astype(np.float32)
        a = a / np.maximum(deg, 1.0)[:, None]
    cfg = gnn.GNNConfig(model=model, comm_size=B, hidden=8)
    layer = jax.tree.map(
        lambda v: jnp.asarray(np.asarray(v, np.float32), dt),
        gnn.init_model(jax.random.PRNGKey(0), cfg, 6, 3)[0])
    x = jnp.asarray(rng.standard_normal((n, 6)), dt)
    conv = adaptgear.gin_conv if model == "gin" else adaptgear.sage_conv
    names = ("block_diag_fused", "bell_fused", "bell_fused")

    def fused(layer, x):
        xr = adaptgear.to_reordered(dec, x)
        return adaptgear.from_reordered(dec, conv(layer, dec, xr, names))

    y = np.asarray(fused(layer, x), np.float32)
    y_ref = dense_layer(model, jax.tree.map(
        lambda v: np.asarray(v, np.float32), layer), a, np.asarray(x, np.float32))
    np.testing.assert_allclose(y, y_ref, **tol(dt), err_msg=f"{model} spill")

    g_x = jax.grad(lambda x: jnp.sum(fused(layer, x).astype(jnp.float32)))(x)
    assert np.isfinite(np.asarray(g_x, np.float32)).all()


def test_minibatch_sage_cost_model_commits_fused_at_one_trace():
    """Acceptance bar: on the dense-inter profile the cost model commits
    the fused dual-weight plan for mini-batch SAGE, the jitted step
    compiles exactly once, and training is finite."""
    from test_sampling import dense_community_graph
    g = dense_community_graph()
    cfg = gnn.GNNConfig(model="sage", sampler="cluster", comm_size=64,
                        clusters_per_batch=2, reorder="bfs",
                        inter_buckets=2)
    res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1)
    used = {k for plan in res.plans for layer in plan for k in layer}
    assert "bell_fused" in used or "block_diag_fused" in used, res.plans
    assert res.n_traces == 1
    assert np.isfinite(res.losses).all()


def test_minibatch_gin_fused_plan_at_one_trace():
    """Mini-batch GIN dispatching a fully fused epilogue plan (fixed
    selector pins it) compiles once and trains finitely — the dispatch
    path is plan-agnostic even where the cost model prefers unfused."""
    g = make_graph(n=128, e=1200)
    cfg = gnn.GNNConfig(model="gin", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, reorder="bfs",
                        inter_buckets=2, selector="fixed",
                        fixed_kernels=("block_diag_fused", "bell_fused"))
    res = gnn_steps.train_minibatch(g, cfg, steps=5, eval_batches=1)
    assert res.n_traces == 1
    expect = ("block_diag_fused",) + ("bell_fused",) * 2
    assert res.plans == [(expect,) * cfg.n_layers]
    assert np.isfinite(res.losses).all()


def test_dual_weight_kernel_hook_equivalence(rng, monkeypatch):
    """The dual-stripe Pallas kernel (both weight stripes in VMEM,
    ``fused_dual_matvec``, or ``_acc`` when the bias seeds it) runs on the
    diagonal tier and matches the layer written out tier by tier, with
    each tier's plain fused hook and the self term and bias added
    explicitly: forward and grads, with and without the bias."""
    g, a, dec, cfg = cached("sage", 2)
    xr = adaptgear.to_reordered(dec, jnp.asarray(
        rng.standard_normal((g.n, 5)), jnp.float32))
    wn = jnp.asarray(rng.standard_normal((5, 7)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((5, 7)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(7), jnp.float32)
    names = ("block_diag_fused", "bell_fused", "bell_fused")
    assert REGISTRY.get(names[0]).fused_dual_matvec is not None

    for bias, attr in ((b, "fused_dual_matvec_acc"),
                       (None, "fused_dual_matvec")):
        hook = lambda xr, wn, ws: adaptgear.aggregate_transform_dual(  # noqa
            dec, xr, wn, ws, names, bias=bias)
        summed = lambda xr, wn, ws: tier_sum(  # noqa: E731
            dec, names, xr, wn, ws, bias=bias)
        spied = SpiedRegistry(names[0], attr)
        with monkeypatch.context() as mp:
            mp.setattr(adaptgear, "REGISTRY", spied)
            y = hook(xr, wn, ws)
        assert spied.calls == 1, attr
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(summed(xr, wn, ws)),
                                   atol=1e-5, rtol=1e-5)
        g_h = jax.grad(lambda *a: jnp.sum(hook(*a) ** 2),
                       (0, 1, 2))(xr, wn, ws)
        g_s = jax.grad(lambda *a: jnp.sum(summed(*a) ** 2),
                       (0, 1, 2))(xr, wn, ws)
        for p, q in zip(g_h, g_s):
            np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                       atol=1e-3, rtol=1e-3)
    # bias grad through the threaded broadcast
    db = jax.grad(lambda b: jnp.sum(adaptgear.aggregate_transform_dual(
        dec, xr, wn, ws, names, bias=b)))(b)
    np.testing.assert_allclose(np.asarray(db),
                               np.full((7,), dec.n_pad, np.float32),
                               atol=1e-3, rtol=1e-4)


def test_gin_free_transform_zeroes_unfused_surcharge():
    """The MLP epilogue's shared transform is free to unfused candidates
    (the self term computes S = X W1 regardless): with it, an unfused
    candidate's whole-layer cost must equal its bare kernel cost, while
    the linear (GCN) epilogue surcharges the transform share."""
    g, _, dec, _ = cached("gin", 2)
    hw = sel_mod.default_hw()
    mlp = ep_mod.EpilogueSpec(kind="mlp", activation="relu", out_dim=3)
    sub = dec.inters[0]
    share_lin = sel_mod._transform_share(dec, 8, np.float32, hw, 16)
    share_mlp = sel_mod._transform_share(dec, 8, np.float32, hw, 16, mlp)
    assert share_lin > 0.0 and share_mlp == 0.0
    bare = sel_mod.candidate_cost(sub, "bell", 8, hw=hw)
    assert sel_mod.candidate_cost(sub, "bell", 8, hw=hw, in_dim=16,
                                  transform_share=share_mlp) == bare
    # under TPU constants (memory-bound) a narrow-input wide-hidden GIN
    # layer still picks fused kernels on MXU-scale dense blocks, even
    # with the unfused side uncharged — fusion wins on bandwidth alone
    src, dst = G.aligned_community_graph(2048, 30000, block=128,
                                         intra_frac=0.9, seed=0)
    gb = G.Graph(2048, src, dst, np.zeros((2048, 4), np.float32),
                 np.zeros(2048, np.int32), 2)
    decb = decompose.decompose(gb, comm_size=128, method="bfs",
                               reorder=False, inter_buckets=1)
    choice = sel_mod.select_by_cost_model(decb, 512, hw=sel_mod.HwModel(),
                                          in_dim=64, epilogue=mlp)
    assert any(REGISTRY.get(k).fused for k in choice), choice


def test_plan_layer_cost_includes_epilogue_terms():
    """Dense epilogue terms (dual self matmul, MLP second layer) enter the
    whole-layer totals the bucket autotuner compares."""
    g, _, dec, _ = cached("sage", 1)
    hw = sel_mod.default_hw()
    base = sel_mod.plan_layer_cost(dec, 8, hw=hw, in_dim=16)
    dual = sel_mod.plan_layer_cost(dec, 8, hw=hw, in_dim=16,
                                   epilogue=ep_mod.EpilogueSpec(kind="dual"))
    mlp = sel_mod.plan_layer_cost(
        dec, 8, hw=hw, in_dim=16,
        epilogue=ep_mod.EpilogueSpec(kind="mlp", out_dim=3))
    assert dual > base
    assert mlp > base
    assert ep_mod.epilogue_cost(None, dec.n_pad, 16, 8, hw=hw) == 0.0


def test_plan_carries_epilogues():
    """EpilogueSpecs thread from gnn through select_plan into the
    KernelPlan (both selector modes and the mini-batch PlanCache)."""
    g, _, dec, cfg = cached("sage", 2)
    pairs = gnn.agg_width_pairs(cfg, 5, g.n_classes)
    eps = gnn.layer_epilogues(cfg, 5, g.n_classes)
    assert all(e.kind == "dual" and e.mean_norm for e in eps)
    assert all(fin is not None for fin, _ in pairs)
    plan, _ = gnn.select_plan(dec, cfg, pairs, epilogues=eps)
    assert plan.epilogues == tuple(eps)
    assert plan.epilogue_for_layer(0).kind == "dual"
    cache = PlanCache(pairs, epilogues=eps)
    skel_plan = cache.select(dec)
    assert skel_plan.epilogues == tuple(eps)
    # gin structure rule: the narrow input layer aggregates raw features
    # (aggregate-first, pair (None, fin)); hidden-width layers keep the
    # transform-first rewrite and aggregate at the MLP hidden width
    cfg_gin = gnn.GNNConfig(model="gin", hidden=8)
    gpairs = gnn.agg_width_pairs(cfg_gin, 5, 3)
    assert gpairs == [(None, 5), (8, 8)]
    geps = gnn.layer_epilogues(cfg_gin, 5, 3)
    assert [e.out_dim for e in geps] == [8, 3]
    assert [e.structure for e in geps] == ["aggregate_first",
                                           "transform_first"]
    assert geps[0].hidden == 8 and not geps[0].free_transform
    assert geps[1].free_transform
    # wide input (hidden <= in_dim): transform-first everywhere, as before
    wpairs = gnn.agg_width_pairs(cfg_gin, 16, 3)
    assert wpairs == [(16, 8), (8, 8)]
    assert all(e.free_transform
               for e in gnn.layer_epilogues(cfg_gin, 16, 3))


def test_budget_k_adapts_from_observed_spill():
    """PlanCache budget-K autotuning: committed capped-bell payloads that
    spill beyond the target step the slack up the ladder, the adapted
    slack keys the signature, and rebuilding with it shrinks the spill."""
    rng = np.random.default_rng(0)
    n, B = 256, 8
    # hub row-block fanning out to many distinct far blocks: the budget
    # cap is too tight at the default slack
    hub_dst = rng.integers(0, B, 400)
    hub_src = rng.integers(0, n, 400)
    src = np.concatenate([hub_src, rng.integers(0, n, 100)]).astype(np.int32)
    dst = np.concatenate([hub_dst, rng.integers(0, n, 100)]).astype(np.int32)
    key = src.astype(np.int64) * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    g = G.Graph(n, src, dst, np.zeros((n, 4), np.float32),
                np.zeros(n, np.int32), 2)
    budget = len(src)

    def build(slack):
        skel = decompose.decompose_skeleton(
            g, comm_size=B, reorder=False, inter_buckets=1,
            keep_empty_buckets=True, edge_budget=budget, bell_slack=slack)
        return skel.materialize(("bell",))

    cache = PlanCache([(4, 8)], adapt_budget_k=True, bell_slack=1.0,
                      spill_min_obs=2)
    sig0 = cache.signature(build(cache.bell_slack))
    assert ("bell_slack", 1.0) in sig0
    spill0 = None
    for _ in range(4):
        dec = build(cache.bell_slack)
        sp = sum(s.formats["bell"][2].nnz for s in dec.subgraphs
                 if "bell" in s.formats)
        spill0 = sp if spill0 is None else spill0
        cache.observe_bell(dec)
    assert spill0 > 0, "profile must spill at the initial slack"
    assert cache.bell_slack > 1.0
    assert cache.stats["slack_changes"] >= 1
    assert cache.stats["spill_nnz"] > 0
    # adapted slack -> larger K -> less spill, and a fresh signature
    dec2 = build(cache.bell_slack)
    spill2 = sum(s.formats["bell"][2].nnz for s in dec2.subgraphs
                 if "bell" in s.formats)
    assert spill2 < spill0
    assert cache.signature(dec2) != sig0

    # near-hit aliasing must not bridge a slack step: a statistically
    # identical batch decomposed under the NEW slack misses (forcing
    # re-selection under the new K) instead of reusing the plan priced
    # for the old cap.  The signature reads the slack baked into the
    # decomposition's own tier stats — not the cache's current slack —
    # so a batch built BEFORE the step (old-slack payload shapes, e.g.
    # one in flight on a pipeline worker) still hits the entry that
    # matches its shapes rather than shearing to the new key
    cache2 = PlanCache([(4, 8)], adapt_budget_k=True, bell_slack=1.0,
                       spill_min_obs=2)
    dec_old = decompose.decompose(
        g, comm_size=B, reorder=False, inter_buckets=1,
        keep_empty_buckets=True, edge_budget=budget,
        bell_slack=cache2.bell_slack, kernels=MB_KERNELS)
    _, hit = cache2.plan_for(dec_old)
    assert not hit
    assert cache2.lookup(dec_old) is not None    # resident at old slack
    cache2._bell_slack = 2.0                     # a slack step
    dec_new = decompose.decompose(
        g, comm_size=B, reorder=False, inter_buckets=1,
        keep_empty_buckets=True, edge_budget=budget,
        bell_slack=cache2.bell_slack, kernels=MB_KERNELS)
    assert cache2.lookup(dec_new) is None        # no cross-slack aliasing
    # in-flight old-slack batch: keyed by its own baked slack, still hits
    assert cache2.lookup(dec_old) is not None


def test_adaptive_probe_topk_widens_within_margin():
    """probe_topk widens past top-2 when the modeled margin sits inside
    the error band, and a zero wall-time budget degrades gracefully to
    the modeled choice."""
    g = make_graph(n=96, e=700)
    cfg = gnn.GNNConfig(model="gin", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, reorder="bfs")
    sampler = gnn_steps.make_sampler(g, cfg)
    dec, _ = gnn_steps.prepare_batch(sampler.sample(), cfg)
    pairs = [tuple(p) for p in
             gnn.agg_width_pairs(cfg, g.features.shape[-1], g.n_classes)]

    errs_narrow, errs_wide = [], []
    sel_mod.probe_topk(dec, pairs[:1], k=2, iters=1, errs=errs_narrow)
    sel_mod.probe_topk(dec, pairs[:1], k=2, k_max=5, margin=100.0, iters=1,
                       errs=errs_wide)
    assert len(errs_wide) > len(errs_narrow)   # the frontier widened

    # exhausted budget: nothing timed, modeled ranking decides
    layers = sel_mod.probe_topk(dec, pairs[:1], k=2, iters=1,
                                time_budget_s=0.0)
    modeled = sel_mod.select_by_cost_model(dec, pairs[0][1],
                                           in_dim=pairs[0][0],
                                           hw=sel_mod.default_hw())
    assert layers[0] == modeled

    # the cache's error band starts unknown and is measured from probes
    cache = PlanCache(pairs, probe_every=1, probe_iters=1)
    assert cache.probe_margin() is None
    cache.plan_for(dec)
    assert cache.stats["probes"] == 1
    assert len(cache._probe_errs) >= 2
    if cache.probe_margin() is not None:
        assert 0.05 <= cache.probe_margin() <= 1.0


def test_skeleton_cache_reuses_cluster_tuples():
    """Repeated cluster tuples skip decompose_skeleton entirely; the
    cached-skeleton run matches the uncached run exactly (same batches,
    same plans, same losses)."""
    g = make_graph(n=64, e=500)
    # 8 clusters, 8 per batch: every epoch redraws the same (full) tuple,
    # so every step past the first must hit the skeleton cache
    base = dict(model="gin", sampler="cluster", comm_size=8,
                clusters_per_batch=8, reorder="bfs", inter_buckets=2)
    res = gnn_steps.train_minibatch(
        g, gnn.GNNConfig(**base, skeleton_cache_entries=64),
        steps=8, eval_batches=1)
    assert res.skeleton_misses == 1
    assert res.skeleton_hits >= 7
    res_off = gnn_steps.train_minibatch(
        g, gnn.GNNConfig(**base, skeleton_cache_entries=0),
        steps=8, eval_batches=1)
    assert res_off.skeleton_hits == 0
    np.testing.assert_allclose(res.losses, res_off.losses, rtol=1e-6)
    assert res.plans == res_off.plans


def test_skeleton_cache_key_rules():
    """The cache key is the drawn cluster tuple (+ the adapted bell
    slack); truncated batches (random edge subset) and non-cluster
    batches never cache."""
    from repro.sampling.sampler import SampledBatch
    mk = lambda meta: SampledBatch(  # noqa: E731
        n=4, nodes=np.zeros(4, np.int32), node_mask=np.ones(4, bool),
        senders=np.zeros(2, np.int32), receivers=np.zeros(2, np.int32),
        edge_mask=np.ones(2, bool), features=np.zeros((4, 2), np.float32),
        labels=np.zeros(4, np.int32), target_mask=np.ones(4, bool),
        meta=meta)
    Key = gnn_steps.SkeletonCache.key
    assert Key(mk(dict(clusters=[1, 3], dropped_edges=0)), None) == \
        ((1, 3), None)
    assert Key(mk(dict(clusters=[1, 3], dropped_edges=0)), 2.0) != \
        Key(mk(dict(clusters=[1, 3], dropped_edges=0)), 1.5)
    assert Key(mk(dict(clusters=[1, 3], dropped_edges=5)), None) is None
    assert Key(mk(dict(seeds=4)), None) is None     # neighbor sampler
    # LRU bound
    cache = gnn_steps.SkeletonCache(max_entries=2)
    for i in range(3):
        cache.put(((i,), None), (i, i))
    assert len(cache._entries) == 2
    assert cache.get(((0,), None)) is None      # evicted
    assert cache.get(((2,), None)) == (2, 2)


def test_gin_structure_equivalence(rng):
    """Aggregate-first and transform-first GIN layers are the same
    function (linearity of aggregation): forward and grads match on real
    decomposed kernels, and both match the dense reference."""
    g, a, dec, _ = cached("gin", 2)
    x = rng.standard_normal((g.n, 5)).astype(np.float32)
    xr = adaptgear.to_reordered(dec, jnp.asarray(x))
    layer = adaptgear.init_gin_conv(jax.random.PRNGKey(3), 5, 8, 7)
    names = ("block_diag", "bell", "bell")

    def run(structure):
        return adaptgear.gin_conv(layer, dec, xr, names,
                                  structure=structure)

    y_tf, y_af = run("transform_first"), run("aggregate_first")
    np.testing.assert_allclose(np.asarray(y_af), np.asarray(y_tf),
                               atol=1e-4, rtol=1e-4)
    ref = dense_layer("gin", layer, a, x)
    back = np.asarray(y_af)[np.asarray(dec.perm)]
    np.testing.assert_allclose(back, ref, atol=1e-4, rtol=1e-4)
    g_tf = jax.grad(lambda p: jnp.sum(adaptgear.gin_conv(
        p, dec, xr, names, structure="transform_first") ** 2))(layer)
    g_af = jax.grad(lambda p: jnp.sum(adaptgear.gin_conv(
        p, dec, xr, names, structure="aggregate_first") ** 2))(layer)
    for k in g_tf:
        np.testing.assert_allclose(np.asarray(g_af[k]), np.asarray(g_tf[k]),
                                   atol=2e-3, rtol=2e-3)


def test_gin_aggregate_first_fused_names_fall_back(rng):
    """A plan that pinned fused kernel names implies transform-first —
    the aggregate-first spec defers to it instead of crashing (fused
    kernels have no raw-aggregation matvec)."""
    g, _, dec, _ = cached("gin", 2)
    xr = adaptgear.to_reordered(
        dec, jnp.asarray(rng.standard_normal((g.n, 5)), jnp.float32))
    layer = adaptgear.init_gin_conv(jax.random.PRNGKey(3), 5, 8, 7)
    fused = ("block_diag_fused", "bell_fused", "bell_fused")
    y = adaptgear.gin_conv(layer, dec, xr, fused,
                           structure="aggregate_first")
    y_tf = adaptgear.gin_conv(layer, dec, xr, fused,
                              structure="transform_first")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_tf))


def test_gin_structure_priced_selection():
    """layer_plan_inputs prices aggregate-first vs transform-first with
    the decomposition in hand: the narrow-input layer flips to
    aggregate-first (pair (None, fin), hidden on the spec), hidden-width
    layers stay transform-first, and the dec-free rule agrees here."""
    g, _, dec, cfg = cached("gin", 2)
    pairs, eps = gnn.layer_plan_inputs(cfg, 5, g.n_classes, dec=dec)
    assert pairs[0] == (None, 5)
    assert eps[0].structure == "aggregate_first" and eps[0].hidden == 8
    assert pairs[1:] == [(8, 8)]
    assert all(e.structure == "transform_first" for e in eps[1:])
    # priced totals really differ: the af layer aggregates at width 5
    hw = sel_mod.default_hw()
    tf_cost = sel_mod.plan_layer_cost(
        dec, 8, hw=hw, in_dim=5,
        epilogue=ep_mod.gin_layer_spec(5, 8, 8, "transform_first"))
    af_cost = sel_mod.plan_layer_cost(
        dec, 5, hw=hw, in_dim=None,
        epilogue=ep_mod.gin_layer_spec(5, 8, 8, "aggregate_first"))
    assert af_cost < tf_cost
    # dec-free path (mini-batch): same structures without pricing
    fpairs, feps = gnn.layer_plan_inputs(cfg, 5, g.n_classes)
    assert fpairs == pairs
    assert [e.structure for e in feps] == [e.structure for e in eps]


def test_epilogue_cost_aggregate_first_prices_whole_mlp():
    """The aggregate-first mlp spec bypasses the fin-None guard: the whole
    MLP (first matmul at the raw width, second at hidden) is priced, with
    the same dense flops as the transform-first split, so plan_layer_cost
    comparisons are carried by the sparse pass alone."""
    hw = sel_mod.HwModel()
    n, fin, hid, out = 4096, 16, 64, 8
    af = ep_mod.gin_layer_spec(fin, hid, out, "aggregate_first")
    tf = ep_mod.gin_layer_spec(fin, hid, out, "transform_first")
    c_af = ep_mod.epilogue_cost(af, n, None, fin, hw=hw)
    c_tf = ep_mod.epilogue_cost(tf, n, fin, hid, hw=hw)
    assert c_af > 0.0 and c_tf > 0.0
    # flops identical (2 n fin hid + 2 n hid out) -> compute-bound costs
    # agree; bandwidth terms differ only in elementwise traffic
    assert abs(c_af - c_tf) < max(c_af, c_tf) * 0.5
    # legacy guard intact for non-mlp specs with no input width
    assert ep_mod.epilogue_cost(
        ep_mod.EpilogueSpec(kind="dual"), n, None, fin, hw=hw) == 0.0


def test_gin_minibatch_aggregate_first_trains():
    """End-to-end mini-batch GIN with a narrow input (in_dim < hidden):
    the first layer runs aggregate-first via the PlanCache-carried
    epilogues, trains finitely, and still compiles once."""
    g = make_graph(n=128, e=1200, nf=4)
    cfg = gnn.GNNConfig(model="gin", sampler="cluster", comm_size=8,
                        clusters_per_batch=4, reorder="bfs", hidden=16,
                        inter_buckets=2, selector="cost_model")
    pairs = gnn.agg_width_pairs(cfg, 4, g.n_classes)
    assert pairs[0] == (None, 4)
    res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1)
    assert res.n_traces == 1
    assert np.isfinite(res.losses).all()
