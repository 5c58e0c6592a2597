"""Fused transform+aggregate vs the unfused two-pass layer, per model.

GCN rows measure one layer Y = A (X W) + b on the block-diagonal-dominant
synthetic graph (aligned MXU-scale communities, ring-structured inter
edges): the fully-fused plan against the unfused Pallas pair with the
standalone XLA transform, plus per-tier kernel rows isolating where the
saved H round-trip lands.  The expanding layer width (fin < fout) is the
regime fusion targets — the unfused path materializes the *wide* H.

GIN/SAGE rows measure the epilogue-fused layers (core.epilogue) against
the *legacy* unfused layers that aggregate raw features and apply the
dense epilogue after.  Their winning regime is the contracting width
(fin > fout): pushing W through the aggregation shrinks the aggregated
width from fin to fout/hidden and kills the (n, fin) intermediate, so the
model rows run the GCN widths in the opposite direction.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit, emit
from repro.core import adaptgear, decompose
from repro.graphs import graph as G

FUSED_PLAN = ("block_diag_fused", "bell_fused")
UNFUSED_PLAN = ("block_diag", "bell")


def run(n: int = 2048, e: int = 30000, fin: int = 64, fout: int = 512,
        verbose: bool = True) -> list[dict]:
    src, dst = G.aligned_community_graph(n, e, block=128, intra_frac=0.9,
                                         seed=0)
    g = G.Graph(n, src, dst, np.zeros((n, 4), np.float32),
                np.zeros(n, np.int32), 2)
    dec = decompose.decompose(g, comm_size=128, method="bfs", reorder=False,
                              inter_buckets=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((dec.n_pad, fin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((fin, fout)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(fout), jnp.float32)

    layer = {
        "unfused": jax.jit(lambda x, w, b: adaptgear.aggregate_transform(
            dec, x, w, UNFUSED_PLAN, bias=b)),
        "fused": jax.jit(lambda x, w, b: adaptgear.aggregate_transform(
            dec, x, w, FUSED_PLAN, bias=b)),
    }
    times = {k: timeit(fn, x, w, b, iters=3) for k, fn in layer.items()}
    speedup = times["unfused"] / max(times["fused"], 1e-12)

    # per-tier isolation: the unfused side is charged the transform it needs
    tier = {
        "intra_unfused": jax.jit(lambda x, w: adaptgear.aggregate_sub(
            dec.intra, x @ w, "block_diag")),
        "intra_fused": jax.jit(lambda x, w: adaptgear.aggregate_sub_fused(
            dec.intra, x, w, "block_diag_fused")),
        "inter_unfused": jax.jit(lambda x, w: adaptgear.aggregate_sub(
            dec.inters[0], x @ w, "bell")),
        "inter_fused": jax.jit(lambda x, w: adaptgear.aggregate_sub_fused(
            dec.inters[0], x, w, "bell_fused")),
    }
    tier_times = {k: timeit(fn, x, w, iters=3) for k, fn in tier.items()}

    rows = []
    if verbose:
        emit("fused_gcn_layer_unfused", times["unfused"] * 1e6,
             f"n={n};fin={fin};fout={fout}")
        emit("fused_gcn_layer_fused", times["fused"] * 1e6,
             f"speedup_vs_unfused={speedup:.2f}x")
        for k, t in tier_times.items():
            emit(f"fused_{k}", t * 1e6, "")
    rows.append(dict(n=n, fin=fin, fout=fout, speedup=speedup,
                     **{k: v * 1e6 for k, v in times.items()},
                     **{k: v * 1e6 for k, v in tier_times.items()}))
    # epilogue-fused GIN/SAGE on the contracting profile (widths reversed)
    rows += run_models(n=n, e=e, fin=fout, fout=fin, verbose=verbose)
    # column-condensed MXU tiles vs blocked-ELL vs dense across occupancy
    rows += run_tcgnn(verbose=verbose)
    return rows


def _paired_ratio(fn_a, fn_b, x, reps: int = 5):
    """Interleaved min-times + median paired ratio t_a/t_b (machine-load
    noise is common-mode within a pair — same estimator as run_models)."""
    jax.block_until_ready(fn_a(x))
    jax.block_until_ready(fn_b(x))
    ta_s, tb_s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_a(x))
        ta_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fn_b(x))
        tb_s.append(time.perf_counter() - t0)
    ratio = float(np.median(np.asarray(ta_s) / np.asarray(tb_s)))
    return min(ta_s), min(tb_s), ratio


def _occupancy_tier(n, B, cols_per_brow, edges_per_col, seed=0):
    """One inter tier with ~cols_per_brow distinct columns per block row,
    each ~edges_per_col/B occupied — the knob that sweeps the
    blocked-ELL padding-waste vs condensation-occupancy crossover."""
    from repro.core import decompose as dm
    rng = np.random.default_rng(seed)
    nbr = n // B
    rows_, cols_ = [], []
    for i in range(nbr):
        cs = rng.choice(n, size=cols_per_brow, replace=False)
        for c in cs:
            rr = rng.choice(B, size=edges_per_col, replace=False) + i * B
            rows_.extend(rr)
            cols_.extend([c] * edges_per_col)
    rows_ = np.asarray(rows_, np.int64)
    cols_ = np.asarray(cols_, np.int64)
    return dm.build_subgraph("inter0", "offdiag", n, B, rows_, cols_,
                             np.ones(len(rows_), np.float32))


def run_tcgnn(n: int = 512, B: int = 32, F: int = 16,
              verbose: bool = True) -> list[dict]:
    """tcgnn_tile vs bell vs dense across column occupancy: the crossover
    the cost model prices.  Sparse tiers (few distinct columns) belong to
    blocked-ELL, mid-density tiers (many half-occupied columns) to the
    condensed tiles, near-dense block rows to a plain MXU matmul.  Rows
    are interpret-mode paired ratios — relative kernel work, not TPU
    wall time."""
    from repro.core import selector as sel_mod
    from repro.kernels.registry import REGISTRY
    hw = sel_mod.HwModel()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((n, F)), jnp.float32)
    profiles = {          # cols_per_brow, edges_per_col
        "sparse": (8, 4),
        "mid": (100, 16),
        "dense": (n // 2, B),
    }
    rows = []
    for name, (cpb, epc) in profiles.items():
        sub = _occupancy_tier(n, B, cpb, epc)
        p_tc = sub.formats["tcgnn_tile"]
        p_bell = sub.formats["bell"]
        a_dense = np.zeros((n, n), np.float32)
        co = sub.formats["coo"]
        a_dense[np.asarray(co.rows), np.asarray(co.cols)] = \
            np.asarray(co.vals)
        a_dense = jnp.asarray(a_dense)
        tc = jax.jit(lambda xx: REGISTRY.get("tcgnn_tile").matvec(p_tc, xx))
        bell = jax.jit(lambda xx: REGISTRY.get("bell").matvec(p_bell, xx))
        dense = jax.jit(lambda xx: a_dense @ xx)
        t_bell, t_tc, r_bell = _paired_ratio(bell, tc, x)
        t_dense, _, r_dense = _paired_ratio(dense, tc, x)
        pick = sel_mod.select_for_subgraph(sub, F, hw=hw)
        if verbose:
            emit(f"tcgnn_crossover_{name}", t_tc * 1e6,
                 f"paired bell/tcgnn={r_bell:.2f}x dense/tcgnn="
                 f"{r_dense:.2f}x nnz={sub.stats['nnz']} "
                 f"col_occ={sub.stats['col_occupancy']:.2f} "
                 f"cost_model_pick={pick}")
        rows.append(dict(profile=name, tcgnn_us=t_tc * 1e6,
                         bell_us=t_bell * 1e6, dense_us=t_dense * 1e6,
                         bell_over_tcgnn=r_bell, dense_over_tcgnn=r_dense,
                         pick=pick))
        if name == "mid":
            # fused A @ (X W) on the condensed tiles vs fused blocked-ELL —
            # the layer-shaped row (W folded in, (n, F) intermediate dead)
            w = jnp.asarray(rng.standard_normal((F, F)), jnp.float32)
            tcf = jax.jit(lambda xx: REGISTRY.get(
                "tcgnn_tile_fused").fused_matvec(p_tc, xx, w))
            bellf = jax.jit(lambda xx: REGISTRY.get(
                "bell_fused").fused_matvec(p_bell, xx, w))
            t_bf, t_tf, r_f = _paired_ratio(bellf, tcf, x)
            if verbose:
                emit("tcgnn_fused_mid", t_tf * 1e6,
                     f"paired bell_fused/tcgnn_fused={r_f:.2f}x")
            rows.append(dict(profile="mid_fused", tcgnn_us=t_tf * 1e6,
                             bell_us=t_bf * 1e6, bell_over_tcgnn=r_f))
    return rows


def run_models(n: int = 2048, e: int = 30000, fin: int = 512, fout: int = 64,
               verbose: bool = True) -> list[dict]:
    """Epilogue-fused GIN/SAGE layers vs their legacy unfused forms.

    The unfused baselines aggregate raw features at width ``fin`` and
    apply the dense epilogue after (the pre-epilogue-fusion dispatch).
    The epilogue layers push the weight through the aggregation (width
    ``fout``) and dispatch the plan the cost model commits *under the
    layer's epilogue on this machine's hw model* — exactly what training
    does: fused Pallas kernels where they are modeled to win (SAGE's dual
    epilogue pays the shared-transform surcharge unfused), unfused
    candidates where the epilogue makes the transform free and the
    pushdown alone carries the win (GIN on compute-bound backends)."""
    from repro.core import epilogue as ep_mod, selector as sel_mod
    src, dst = G.aligned_community_graph(n, e, block=128, intra_frac=0.9,
                                         seed=0)
    g = G.Graph(n, src, dst, np.zeros((n, 4), np.float32),
                np.zeros(n, np.int32), 2)
    # SAGE's mean norm baked into the edge values (core.gnn.prepare does
    # this); GIN sums, so the same unit-valued decomposition serves both
    dec_mean = decompose.decompose(
        g, comm_size=128, method="bfs", reorder=False, inter_buckets=1,
        edge_vals=G.mean_norm_values(n, src, dst))
    dec_sum = decompose.decompose(g, comm_size=128, method="bfs",
                                  reorder=False, inter_buckets=1)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((dec_sum.n_pad, fin)), jnp.float32)
    w_n = jnp.asarray(rng.standard_normal((fin, fout)), jnp.float32)
    w_s = jnp.asarray(rng.standard_normal((fin, fout)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(fout), jnp.float32)
    gin_p = dict(eps=jnp.zeros(()), w1=w_n, b1=b,
                 w2=jnp.asarray(rng.standard_normal((fout, 16)), jnp.float32),
                 b2=jnp.asarray(rng.standard_normal(16), jnp.float32))
    hw = sel_mod.default_hw()
    plans = {
        "sage": sel_mod.select_by_cost_model(
            dec_mean, fout, hw=hw, in_dim=fin,
            epilogue=ep_mod.EpilogueSpec(kind="dual", mean_norm=True)),
        "gin": sel_mod.select_by_cost_model(
            dec_sum, fout, hw=hw, in_dim=fin,
            epilogue=ep_mod.EpilogueSpec(kind="mlp", activation="relu",
                                         out_dim=16)),
    }

    def sage_unfused(x):
        agg = adaptgear.aggregate(dec_mean, x, UNFUSED_PLAN)
        return x @ w_s + agg @ w_n + b

    def gin_unfused(x):
        agg = adaptgear.aggregate(dec_sum, x, UNFUSED_PLAN)
        h = (1.0 + gin_p["eps"]) * x + agg
        return jax.nn.relu(h @ w_n + b) @ gin_p["w2"] + gin_p["b2"]

    layers = {
        "sage": (jax.jit(sage_unfused),
                 jax.jit(lambda x: adaptgear.sage_conv(
                     dict(w_self=w_s, w_neigh=w_n, b=b), dec_mean, x,
                     plans["sage"]))),
        "gin": (jax.jit(gin_unfused),
                jax.jit(lambda x: adaptgear.gin_conv(
                    gin_p, dec_sum, x, plans["gin"]))),
    }
    rows = []
    for model, (unf, fus) in layers.items():
        # interleave the pair so machine-load noise hits both alike (the
        # paired-ratio estimator minibatch.py uses for host prepare): an
        # unpaired A-then-B measurement inverts the ratio under load spikes
        jax.block_until_ready(unf(x))
        jax.block_until_ready(fus(x))
        tu_s, tf_s = [], []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(unf(x))
            tu_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fus(x))
            tf_s.append(time.perf_counter() - t0)
        tu, tf = min(tu_s), min(tf_s)
        speedup = float(np.median(np.asarray(tu_s) / np.asarray(tf_s)))
        if verbose:
            emit(f"fused_{model}_layer_unfused", tu * 1e6,
                 f"n={n};fin={fin};fout={fout} (legacy aggregate-at-fin)")
            emit(f"fused_{model}_layer_fused", tf * 1e6,
                 f"paired_speedup_vs_unfused={speedup:.2f}x "
                 f"plan={','.join(plans[model])}")
        rows.append(dict(model=model, n=n, fin=fin, fout=fout,
                         unfused_us=tu * 1e6, fused_us=tf * 1e6,
                         speedup=speedup, plan=plans[model]))
    return rows


if __name__ == "__main__":
    run()
