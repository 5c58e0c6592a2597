"""Adaptive selector: cost-model ranking sanity, feedback commit protocol,
calibration loop — candidates enumerated from the kernel registry."""
import numpy as np
import pytest

from repro.core import decompose, selector
from repro.core.selector import HwModel
from repro.graphs import graph as G
from repro.kernels.registry import REGISTRY


def kernel_names(kind):
    return [s.name for s in REGISTRY.candidates(kind)]


def make_dec(intra_frac, n=512, e=4096, seed=0, inter_buckets=1):
    src, dst = G.community_graph(n, e, comm_size=16, intra_frac=intra_frac,
                                 seed=seed)
    g = G.Graph(n, src, dst, np.zeros((n, 4), np.float32),
                np.zeros(n, np.int32), 2)
    return decompose.decompose(g, comm_size=16, method="louvain",
                               inter_buckets=inter_buckets)


def test_cost_model_returns_valid_kernels():
    dec = make_dec(0.6)
    choice = selector.select_by_cost_model(dec, feat_dim=64)
    assert len(choice) == len(dec.subgraphs)
    for sub, k in zip(dec.subgraphs, choice):
        assert k in [s.name for s in REGISTRY.candidates_for(sub)]


def test_cost_model_per_bucket_choices():
    dec = make_dec(0.6, inter_buckets=3)
    choice = selector.select_by_cost_model(dec, feat_dim=64)
    assert len(choice) == len(dec.subgraphs)
    # the argmin per subgraph matches candidate_cost directly
    for sub, k in zip(dec.subgraphs, choice):
        costs = {s.name: selector.candidate_cost(sub, s.name, 64)
                 for s in REGISTRY.candidates_for(sub)}
        assert costs[k] == min(costs.values())


def test_cost_model_dense_wins_at_high_density():
    """On the TPU model, a near-full diagonal block favors the MXU dense
    kernel over gather/scatter paths."""
    dec = make_dec(0.95, n=256, e=12000)
    hw = HwModel()
    costs = {k: selector.candidate_cost(dec.intra, k, 256, hw=hw)
             for k in kernel_names("diag")}
    assert costs["block_diag"] == min(costs.values()), costs


def test_cost_model_coo_wins_at_extreme_sparsity():
    dec = make_dec(0.05, n=2048, e=2100)
    hw = HwModel()
    inter = dec.inters[0]
    costs = {k: selector.candidate_cost(inter, k, 64, hw=hw)
             for k in kernel_names("offdiag")}
    # edge-parallel COO beats padded formats when rows are nearly empty
    assert costs["coo"] <= costs["bell"], costs


def test_feedback_commit_protocol():
    dec = make_dec(0.5)
    sel = selector.AdaptiveSelector(dec, warmup_iters=2)
    # feed synthetic timings for every registry candidate: make 'ell'
    # fastest intra, 'coo' fastest inter
    fastest = {"intra": "ell", "inter": "coo"}
    for sub in dec.subgraphs:
        for spec in REGISTRY.candidates_for(sub):
            t = 1e-4 if spec.name == fastest[sub.name] else 3e-3
            for _ in range(2):
                sel.observe(sub.name, spec.name, t)
    assert sel.ready()
    assert sel.choice() == ("ell", "coo")
    # committed choice is sticky
    sel.observe("intra", "coo", 1e-9)
    assert sel.choice() == ("ell", "coo")


def test_feedback_probe_end_to_end(rng):
    dec = make_dec(0.5, n=128, e=512)
    sel = selector.AdaptiveSelector(dec, warmup_iters=1)
    import jax.numpy as jnp
    x = jnp.asarray(rng.standard_normal((dec.n_pad, 16)), jnp.float32)
    res = sel.probe(x, iters=1)
    n_cand = 0
    for sub, k in zip(dec.subgraphs, res.choice):
        cands = [s.name for s in REGISTRY.candidates_for(sub)]
        assert k in cands
        n_cand += len(cands)
    assert len(res.times) == n_cand


def test_feedback_probe_multi_bucket(rng):
    dec = make_dec(0.5, n=256, e=2048, inter_buckets=2)
    assert len(dec.subgraphs) == 3
    sel = selector.AdaptiveSelector(dec, warmup_iters=1)
    import jax.numpy as jnp
    x = jnp.asarray(rng.standard_normal((dec.n_pad, 8)), jnp.float32)
    res = sel.probe(x, iters=1)
    assert len(res.choice) == 3
    assert {s for (s, _) in res.times} == {"intra", "inter0", "inter1"}


def test_calibration_scales_model(rng):
    dec = make_dec(0.5, n=128, e=512)
    sel = selector.AdaptiveSelector(dec, warmup_iters=1)
    import jax.numpy as jnp
    x = jnp.asarray(rng.standard_normal((dec.n_pad, 16)), jnp.float32)
    sel.probe(x, iters=1)
    hw = sel.calibrate_cost_model(feat_dim=16)
    # calibrated model should predict the probed medians within ~100x
    # (CPU interpret-mode variance is huge; we check order of magnitude)
    t_est = selector.candidate_cost(dec.inters[0], "coo", 16, hw=hw)
    t_obs = np.median(sel._times[("inter", "coo", (0, 16))])
    assert t_est > 0 and 1e-3 < t_obs / t_est < 1e3


def test_default_hw_keyed_by_device_kind():
    assert selector.default_hw("cpu") is selector.CPU_HW
    assert selector.default_hw("TPU v5 lite").peak_flops == 197e12
    assert selector.default_hw() is selector.CPU_HW   # tests run on CPU
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        selector.default_hw("TPU v9 imaginary")
