"""Fused transform+aggregate subsystem.

Covers: fused kernels vs the unfused dense reference (forward AND grads wrt
inputs and params, per-dtype tolerances) for GCN over every bucket count;
every accumulate hook against the explicit per-tier sum; per-bucket
blocked-ELL tiling; the _f_tile divisor fix; selector integration (fused
candidates competing in both modes); and bucket-count autotuning."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _tiers import SpiedRegistry, tier_sum

from repro.core import adaptgear, decompose, formats, gnn, selector
from repro.core import plan as plan_mod
from repro.core.plan import KernelPlan
from repro.graphs import graph as G
from repro.kernels import ops
from repro.kernels.registry import REGISTRY


def make_graph(n=180, e=1400, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    key = src.astype(np.int64) * n + dst
    _, keep = np.unique(key, return_index=True)
    src, dst = src[keep], dst[keep]
    vals = rng.standard_normal(len(src)).astype(np.float32)
    g = G.Graph(n, src, dst, np.zeros((n, 3), np.float32),
                np.zeros(n, np.int32), 2)
    return g, vals


@functools.lru_cache(maxsize=None)
def cached(k):
    g, vals = make_graph()
    a = np.zeros((g.n, g.n), np.float32)
    a[g.receivers, g.senders] = vals
    dec = decompose.decompose(g, comm_size=8, method="bfs", edge_vals=vals,
                              inter_buckets=k)
    return g, a, dec


def tol(dt):
    # bf16 has ~3 significant digits; grads through two chained bf16
    # matmuls legitimately wobble at the 1e-1 scale on O(10) values
    return dict(atol=1e-4, rtol=1e-4) if dt == jnp.float32 else \
        dict(atol=2e-1, rtol=3e-1)


PLANS = [("block_diag_fused", "bell_fused"),   # fully fused
         ("block_diag_fused", "bell"),         # mixed: H materialized
         ("block_diag", "bell_fused")]


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("ik,ek", PLANS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_fused_gcn_matches_dense_fwd_and_grad(ik, ek, k, dt, rng):
    """A (X W) + b through fused/mixed plans == the dense reference, for
    outputs and for grads wrt x, w, and b."""
    g, a, dec = cached(k)
    x = jnp.asarray(rng.standard_normal((g.n, 5)), dt)
    w = jnp.asarray(rng.standard_normal((5, 7)), dt)
    b = jnp.asarray(rng.standard_normal(7), dt)
    cot = jnp.asarray(rng.standard_normal((g.n, 7)), jnp.float32)

    def fused(x, w, b):
        xr = adaptgear.to_reordered(dec, x)
        y = adaptgear.aggregate_transform(dec, xr, w, (ik, ek), bias=b)
        return adaptgear.from_reordered(dec, y)

    def ref(x, w, b):
        af = jnp.asarray(a).astype(jnp.float32)
        return (af @ (x.astype(jnp.float32) @ w.astype(jnp.float32))
                + b.astype(jnp.float32))

    y = np.asarray(fused(x, w, b), np.float32)
    y_ref = np.asarray(ref(x, w, b))
    np.testing.assert_allclose(y, y_ref, **tol(dt),
                               err_msg=f"{ik}/{ek} k={k} fwd")

    loss = lambda f: lambda x, w, b: jnp.sum(  # noqa: E731
        f(x, w, b).astype(jnp.float32) * cot)
    grads = jax.grad(loss(fused), argnums=(0, 1, 2))(x, w, b)
    grads_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(x, w, b)
    for gv, gr, name in zip(grads, grads_ref, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(gv, np.float32),
                                   np.asarray(gr, np.float32), **tol(dt),
                                   err_msg=f"{ik}/{ek} k={k} {name}")


# hook -> (layer, plan).  The plan's later tiers thread the accumulator
# through the hook; where the hook sits on the first (diagonal) tier, the
# bias seeds the accumulator it threads.
ACC_HOOKS = {
    "block_diag.matvec_acc": ("transform", ("block_diag", "bell")),
    "bell.matvec_acc": ("aggregate", ("block_diag", "bell")),
    "tcgnn_tile.matvec_acc": ("aggregate", ("block_diag", "tcgnn_tile")),
    "block_diag_fused.fused_matvec_acc":
        ("transform", ("block_diag_fused", "bell")),
    "bell_fused.fused_matvec_acc":
        ("transform", ("block_diag_fused", "bell_fused")),
    "tcgnn_tile_fused.fused_matvec_acc":
        ("transform", ("block_diag_fused", "tcgnn_tile_fused")),
    "block_diag_fused.fused_dual_matvec_acc":
        ("dual", ("block_diag_fused", "bell_fused")),
}


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("hook", list(ACC_HOOKS))
def test_accumulation_mode_equivalence(hook, k, rng, monkeypatch):
    """Each accumulate hook, the one dispatch path, runs on every tier it
    is planned for and matches the explicit per-tier sum, forward and
    grads through the threaded buffer."""
    layer, plan = ACC_HOOKS[hook]
    kernel, attr = hook.split(".")
    g, a, dec = cached(k)
    names = plan_mod.normalize_layer(dec, plan)
    xr = adaptgear.to_reordered(dec, jnp.asarray(
        rng.standard_normal((g.n, 6)), jnp.float32))
    w = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(4), jnp.float32)
    if layer == "aggregate":
        args = (xr,)
        run = lambda xr: adaptgear.aggregate(dec, xr, names)  # noqa: E731
        ref = lambda xr: tier_sum(dec, names, xr)  # noqa: E731
    elif layer == "transform":
        args = (xr, w, b)
        run = lambda xr, w, b: adaptgear.aggregate_transform(  # noqa: E731
            dec, xr, w, names, bias=b)
        ref = lambda xr, w, b: tier_sum(dec, names, xr, w,  # noqa: E731
                                        bias=b)
    else:
        args = (xr, w, ws, b)
        run = lambda xr, w, ws, b: (  # noqa: E731
            adaptgear.aggregate_transform_dual(dec, xr, w, ws, names,
                                               bias=b))
        ref = lambda xr, w, ws, b: tier_sum(dec, names, xr, w, ws,  # noqa
                                            bias=b)
    spied = SpiedRegistry(kernel, attr)
    monkeypatch.setattr(adaptgear, "REGISTRY", spied)
    y = run(*args)
    assert spied.calls == names.count(kernel), (hook, names)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(*args)),
                               atol=1e-5, rtol=1e-5, err_msg=hook)
    cot = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    argnums = tuple(range(len(args)))
    g_run = jax.grad(lambda *a: jnp.sum(run(*a) * cot), argnums)(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) * cot), argnums)(*args)
    for p, q in zip(g_run, g_ref):
        np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                   atol=1e-3, rtol=1e-3, err_msg=hook)


def test_fused_plan_through_training(rng):
    """End-to-end GCN training with a fixed fully-fused plan converges to
    the same curve as the unfused plan (the fused path is a pure speed
    change, never a math change)."""
    g = G.synth_dataset("cora", scale=0.1, seed=0)
    curves = {}
    for pair in (("block_diag", "bell"), ("block_diag_fused", "bell_fused")):
        cfg = gnn.GNNConfig(model="gcn", selector="fixed",
                            fixed_kernels=pair, hidden=8)
        curves[pair] = gnn.train(g, cfg, steps=5).losses
    np.testing.assert_allclose(curves[("block_diag_fused", "bell_fused")],
                               curves[("block_diag", "bell")],
                               atol=5e-3, rtol=1e-2)


def test_fused_selectable_by_both_selector_modes():
    """Fused kernels must be reachable through the KernelPlan machinery in
    both selector modes: the cost model (TPU constants, where the saved HBM
    round-trip dominates) and the committed feedback argmin."""
    # MXU-scale aligned communities: the regime fusion targets (B=128
    # diagonal blocks, expanding layer width)
    src, dst = G.aligned_community_graph(2048, 30000, block=128,
                                         intra_frac=0.9, seed=0)
    gb = G.Graph(2048, src, dst, np.zeros((2048, 4), np.float32),
                 np.zeros(2048, np.int32), 2)
    decb = decompose.decompose(gb, comm_size=128, method="bfs",
                               reorder=False, inter_buckets=1)
    choice = selector.select_by_cost_model(decb, 512, hw=selector.HwModel(),
                                           in_dim=64)
    plan = KernelPlan.make(decb, choice, n_layers=1)  # validates dispatch
    assert any(REGISTRY.get(k).fused for k in plan.for_layer(0)), choice
    g, _, dec = cached(1)
    # feedback: synthetic observations make the fused kernels fastest
    sel = selector.AdaptiveSelector(dec, warmup_iters=1, include_fused=True)
    for sub in dec.subgraphs:
        for spec in REGISTRY.candidates_for(sub, include_fused=True):
            t = 1e-6 if spec.fused else 1e-3
            sel.observe(sub.name, spec.name, t, width=8)
    committed = sel.choice(8)
    assert all(REGISTRY.get(k).fused for k in committed), committed
    KernelPlan.make(dec, committed, n_layers=2)


def test_feedback_choices_keyed_by_width_pair():
    """Two layers sharing an output width but differing in input width sit
    on opposite sides of the fused recompute crossover — their observations
    and committed choices must not pool."""
    g, _, dec = cached(1)
    sel = selector.AdaptiveSelector(dec, warmup_iters=1, include_fused=True)
    for sub in dec.subgraphs:
        for spec in REGISTRY.candidates_for(sub, include_fused=True):
            # narrow input: fused fastest; wide input: ell fastest
            sel.observe(sub.name, spec.name,
                        1e-6 if spec.fused else 1e-3, width=(4, 8))
            sel.observe(sub.name, spec.name,
                        1e-6 if spec.name == "ell" else 1e-3, width=(64, 8))
    narrow = sel.choice((4, 8))
    wide = sel.choice((64, 8))
    assert all(REGISTRY.get(k).fused for k in narrow), narrow
    assert all(k == "ell" for k in wide), wide
    # committed choices stay sticky per pair
    sel.observe("intra", "coo", 1e-9, width=(4, 8))
    assert sel.choice((4, 8)) == narrow


def test_cost_model_without_in_dim_excludes_fused():
    g, _, dec = cached(2)
    choice = selector.select_by_cost_model(dec, 64)
    assert not any(REGISTRY.get(k).fused for k in choice)
    with pytest.raises(ValueError):
        selector.candidate_cost(dec.intra, "block_diag_fused", 64)


def test_f_tile_picks_largest_divisor():
    """_f_tile must return the largest lane-multiple divisor of the padded
    width <= cap — and never hang or degrade on non-lane-multiple caps."""
    assert ops._f_tile(512) == 512
    assert ops._f_tile(512, cap=256) == 256
    assert ops._f_tile(768) == 384          # 512 does not divide 768
    assert ops._f_tile(1280) == 256         # old walk-down also found this
    assert ops._f_tile(640) == 128          # only 128 divides 640 under 512
    assert ops._f_tile(100) == 128          # pads to one lane tile
    # non-lane-multiple caps (per-bucket tiling): pick the divisor below
    assert ops._f_tile(256, cap=200) == 128
    assert ops._f_tile(1024, cap=1000) == 512
    assert ops._f_tile(512, cap=1) == 128


def test_bell_per_bucket_tiling():
    """Buckets whose stored blocks collapse under merging get a fatter tile;
    scattered buckets keep the community-size block."""
    n = 64
    # aligned cluster: every block-row's edges hit 8-blocks {4, 5}, which
    # form one aligned 16-block -> K halves when Bb doubles
    rows = np.repeat(np.arange(0, n, 8, dtype=np.int32), 2)
    cols = np.tile(np.asarray([32, 40], np.int32), n // 8)
    coo = formats.coo_from_edges(n, n, rows, cols)
    from repro.kernels.registry import _bell_pick_block
    assert _bell_pick_block(coo, 8) > 8
    # scattered: one edge per block-row to a far column -> K stays 1 and
    # merging only grows padding
    rows = np.arange(0, n, 8, dtype=np.int32)
    cols = (rows * 3 + 17) % n
    coo = formats.coo_from_edges(n, n, rows, cols)
    assert _bell_pick_block(coo, 8) == 8
    # payloads carry their own block size and stay numerically exact
    g, vals = make_graph(n=240, e=3000, seed=5)
    dec = decompose.decompose(g, comm_size=8, method="bfs", edge_vals=vals,
                              inter_buckets=2)
    for sub in dec.inters:
        bl = sub.formats["bell"][0]
        assert bl.block_size % 8 == 0 and dec.n_pad % bl.block_size == 0
        assert bl.f_tile_cap >= 128


def test_bucket_count_autotune():
    """inter_buckets=0 decomposes at k in {1,2,4}, totals the cost model
    over the model's layers, and commits the cheapest."""
    g = G.synth_dataset("cora", scale=0.08, seed=0)
    cfg = gnn.GNNConfig(model="gcn", selector="cost_model", inter_buckets=0)
    dec = gnn.prepare(g, cfg)
    tuned = dec.stats["bucket_autotune"]
    assert set(tuned) == {1, 2, 4}
    best_k = min(tuned, key=tuned.get)
    assert dec.stats["inter_buckets"] <= best_k
    # the committed decomposition trains
    res = gnn.train(g, cfg, steps=3)
    assert np.isfinite(res.losses).all()


def test_csr_one_file_kernel_matches_dense(rng):
    """The one-file CSR registration: registered for both kinds, exact
    against the dense reference, natively differentiable."""
    spec = REGISTRY.get("csr")
    assert spec.applies_to("diag") and spec.applies_to("offdiag")
    g, a, dec = cached(2)
    x = jnp.asarray(rng.standard_normal((g.n, 5)), jnp.float32)

    def agg(x):
        xr = adaptgear.to_reordered(dec, x)
        return adaptgear.from_reordered(
            dec, adaptgear.aggregate(dec, xr, ("csr", "csr")))

    np.testing.assert_allclose(np.asarray(agg(x)), a @ np.asarray(x),
                               atol=1e-4, rtol=1e-4)
    w = rng.standard_normal((g.n, 5)).astype(np.float32)
    grad = jax.grad(lambda x: jnp.sum(agg(x) * w))(x)
    np.testing.assert_allclose(np.asarray(grad), a.T @ w, atol=1e-4,
                               rtol=1e-4)


def test_fused_payload_aliasing_saves_memory():
    """Fused specs alias their unfused counterpart's payload: nothing extra
    is materialized, and the plan validator accepts the alias."""
    g, _, dec = cached(1)
    for sub in dec.subgraphs:
        assert "block_diag_fused" not in sub.formats
        assert "bell_fused" not in sub.formats
    KernelPlan.make(dec, ("block_diag_fused", "bell_fused"), n_layers=1)
    # restricting materialization to a fused kernel builds its base payload
    g2, vals = make_graph(seed=7)
    dec2 = decompose.decompose(g2, comm_size=8, method="bfs", edge_vals=vals,
                               kernels=("block_diag_fused", "bell_fused"))
    assert set(dec2.intra.formats) == {"block_diag"}
    assert set(dec2.inters[0].formats) == {"bell"}
    KernelPlan.make(dec2, ("block_diag_fused", "bell_fused"), n_layers=1)
