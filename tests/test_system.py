"""End-to-end behaviour tests for the paper's system (AdaptGear)."""
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import adaptgear, decompose, gnn
from repro.graphs import graph as G
from repro.kernels.registry import REGISTRY


@pytest.fixture(scope="module")
def citeseer():
    return G.synth_dataset("citeseer", scale=0.15, seed=0)


@pytest.mark.parametrize("model", ["gcn", "gin", "gat", "sage"])
def test_training_learns(citeseer, model):
    cfg = gnn.GNNConfig(model=model, selector="fixed",
                        fixed_kernels=("block_diag", "ell"), hidden=16)
    res = gnn.train(citeseer, cfg, steps=25)
    assert res.losses[-1] < res.losses[0] * 0.9, res.losses
    assert np.isfinite(res.losses).all()
    assert res.accuracy > 1.5 / citeseer.n_classes  # beats chance


def test_all_kernel_pairs_same_loss_curve(citeseer):
    """AdaptGear invariant: the kernel choice changes *speed*, never the
    math — every (intra, inter) pair must produce the same training curve."""
    curves = {}
    for ik in REGISTRY.candidates("diag"):
        for ek in REGISTRY.candidates("offdiag"):
            cfg = gnn.GNNConfig(model="gcn", selector="fixed",
                                fixed_kernels=(ik.name, ek.name), hidden=8)
            res = gnn.train(citeseer, cfg, steps=5)
            curves[(ik.name, ek.name)] = res.losses
    base = curves[("block_diag", "bell")]
    for k, c in curves.items():
        # different kernels sum edges in different orders; the fp drift is
        # amplified by Adam across steps — exactness holds per-aggregation
        # (test_decompose), curves agree to ~1%
        np.testing.assert_allclose(c, base, atol=5e-3, rtol=1e-2,
                                   err_msg=str(k))


def test_feedback_selector_runs(citeseer):
    cfg = gnn.GNNConfig(model="gcn", selector="feedback", warmup_iters=1)
    res = gnn.train(citeseer, cfg, steps=5)
    assert len(res.kernels) == cfg.n_layers   # per-layer selection
    dec = gnn.prepare(citeseer, cfg)
    n_cand = 0
    for i, sub in enumerate(dec.subgraphs):
        # GCN is transform-first, so fused candidates compete in the probe
        cands = [s.name for s in REGISTRY.candidates_for(sub,
                                                         include_fused=True)]
        n_cand += len(cands)
        for layer in res.kernels:
            assert layer[i] in cands
    assert len(res.probe_times) >= n_cand


def test_cost_model_selector_runs(citeseer):
    cfg = gnn.GNNConfig(model="gcn", selector="cost_model")
    res = gnn.train(citeseer, cfg, steps=5)
    assert np.isfinite(res.losses).all()


def test_preprocessing_overhead_small(citeseer):
    """Paper §6.3: preprocessing is a one-off, small vs training."""
    cfg = gnn.GNNConfig(model="gcn", selector="fixed")
    res = gnn.train(citeseer, cfg, steps=10)
    assert res.preprocess_seconds < 30.0


def test_memory_overhead_topology(citeseer):
    """Paper Fig. 12: subgraph topology storage is small vs features."""
    from repro.kernels.registry import payload_nbytes
    dec = decompose.decompose(citeseer, comm_size=16, method="bfs")
    topo_bytes = sum(payload_nbytes(payload)
                     for sub in dec.subgraphs
                     for payload in sub.formats.values())
    feat_bytes = citeseer.features.size * 4
    # all candidate formats together stay bounded; the *selected* pair alone
    # is what the paper's 4.47% number refers to (see benchmarks)
    assert topo_bytes < 50 * feat_bytes


def test_lm_moe_adaptgear_hook():
    """The MoE dispatch selector must route big-E configs to the sparse
    path (DESIGN.md §4)."""
    from repro import configs
    from repro.models import blocks as B
    moe16 = configs.get_config("deepseek_moe_16b").moe_cfg()
    assert B.choose_moe_path(moe16, n_tokens=1 << 20) == "sparse"
    v3 = configs.get_config("deepseek_v3_671b").moe_cfg()
    assert B.choose_moe_path(v3, n_tokens=1 << 20) == "sparse"


def test_train_step_takes_graph_as_argument(citeseer):
    """The full-batch step lowers with the decomposition as arguments, not
    as constants: no array constant beyond a few scalars' worth, so the
    graph stays out of the executable and its compile-cache key."""
    import re
    import jax
    cfg = gnn.GNNConfig(model="gcn", selector="fixed", hidden=16)
    dec = gnn.prepare(citeseer, cfg)
    plan = gnn.KernelPlan.make(dec, ("block_diag", "bell"), n_layers=2)
    params = gnn.init_model(jax.random.PRNGKey(0), cfg,
                            citeseer.features.shape[1], citeseer.n_classes)
    n = dec.n_pad
    lowered = gnn.make_train_step(cfg, plan).lower(
        params, gnn._adam_init(params), dec,
        jnp.zeros((n, citeseer.features.shape[1])),
        jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool), jnp.ones((n,)))
    consts = re.findall(r"stablehlo\.constant dense<.*?>\s*:\s*tensor<([^>]*)>",
                        lowered.as_text())
    sizes = [int(np.prod([int(d) for d in c.split("x")[:-1]] or [1]))
             for c in consts]
    assert max(sizes, default=0) <= 128, sorted(sizes)[-5:]
    # every payload leaf crosses the jit boundary
    n_args = len(jax.tree.leaves(lowered.args_info))
    assert n_args >= len(jax.tree.leaves(dec)) + len(jax.tree.leaves(params))


def test_compile_cache_dir_placed_from_outside(tmp_path):
    from repro import compile_cache
    env = {compile_cache.ENV: str(tmp_path)}
    assert compile_cache.cache_dir(env) == str(tmp_path)
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir({}) == str(repo / ".jax_cache")
