"""End-to-end GNN models + training on top of AdaptGear aggregation.

Models follow the paper's benchmarks (§5): GCN (Kipf&Welling default: 2
layers, 16 hidden) and GIN (Xu et al. default: 5 layers, MLP per layer),
plus GAT and GraphSAGE as extensions.  Training = full-graph node
classification with Adam, the standard setting for the paper's datasets.

The training loop integrates the paper's feedback-driven selector: the first
``warmup_iters`` iterations time every registry kernel candidate per
subgraph on the real graph, then the loop commits to the fastest jitted step
function.  The committed choices form a KernelPlan (per-layer x
per-subgraph) that forward/train_step are keyed by.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adaptgear, decompose as dec_mod, selector as sel_mod
from repro.core import epilogue as ep_mod
from repro.core.plan import KernelPlan
from repro.graphs import graph as graph_mod

Params = Any


@dataclass
class GNNConfig:
    model: str = "gcn"            # gcn | gin | gat | sage
    hidden: int = 16
    n_layers: int = 2
    lr: float = 1e-2
    dropout: float = 0.0          # kept 0 for determinism in tests
    comm_size: int = 16
    reorder: str = "bfs"          # bfs | louvain
    inter_buckets: int = 1        # density tiers; 0 = autotune over {1,2,4}
    selector: str = "feedback"    # feedback | cost_model | fixed
    fixed_kernels: tuple = ("block_diag", "bell")
    warmup_iters: int = 2
    seed: int = 0
    # --- mini-batch sampling (train/gnn_steps.py; "full" = whole graph) ---
    sampler: str = "full"         # full | cluster | neighbor
    clusters_per_batch: int = 8   # cluster: batch = q community blocks
    batch_nodes: int = 128        # neighbor: loss-carrying seeds per batch
    fanouts: tuple = (8, 4)       # neighbor: per-layer in-neighbor caps
    edge_budget: int = 0          # cluster: padded edge slots (0 = auto)
    cache_entries: int = 128      # PlanCache LRU bound
    # probe-on-Nth-miss: every Nth PlanCache miss wall-clocks the top-2
    # cost-model candidates and pins the winner (0 = cost model only)
    probe_every: int = 0
    # adaptive probe widening: when the cost model's margin between
    # candidates is inside its observed error band, the probe widens from
    # top-2 up to probe_k_max candidates; probe_budget_s caps one miss's
    # probe wall time (compiles included)
    probe_k_max: int = 4
    probe_budget_s: float = 2.0
    # budget-K autotuning: feed observed capped-bell spill back into the
    # blocked-ELL budget cap's slack factor (padding waste vs spill volume
    # per workload).  Off by default: a slack change alters payload shapes
    # and costs one recompile of the affected step functions.
    adapt_budget_k: bool = False
    # skeleton cache: cluster-sampler batches revisit cluster tuples every
    # epoch; a small LRU keyed by the drawn tuple skips even the single
    # decompose_skeleton pass for repeated batches (0 disables)
    skeleton_cache_entries: int = 64
    # async sampler->trainer pipeline (train/pipeline.py): prefetch_depth
    # background-prepared batches staged ahead of the jitted step, so a
    # steady-state iteration pays max(compute, prepare) instead of their
    # sum.  0 = synchronous (prepare inline with the step, the pre-PR-6
    # behavior); pipeline_workers threads share the prepare work.  The
    # async batch stream is bit-identical to the sync one under the same
    # seed (samplers draw from per-index deterministic seed streams).
    prefetch_depth: int = 0
    pipeline_workers: int = 2
    # adaptive-K recompile budget: each bell-slack ladder step re-shapes
    # the capped-bell payloads and costs one recompile per affected step
    # function (pre-compiled in a pipeline worker when prefetching); the
    # cap bounds total slack steps per run
    max_ladder_recompiles: int = 4
    # --- fault tolerance (train/gnn_steps.py + distributed/) --------------
    # crash-safe checkpointing: every checkpoint_every consumed batches the
    # loop snapshots params, opt state, the batch cursor, the sampler draw
    # count, and the full PlanCache state (entries, counters, slack-ladder
    # position, quarantine) through distributed.checkpoint.CheckpointManager
    # (atomic tmp+rename, crc manifest, async writer).  resume_from names a
    # checkpoint directory to restore before training: the resumed run's
    # loss curve, committed plans, and cache hit history are bit-identical
    # to the uninterrupted run's.
    checkpoint_dir: str = ""        # "" = checkpointing off
    checkpoint_every: int = 0       # save every N consumed batches (0 = off)
    checkpoint_keep: int = 3        # CheckpointManager GC horizon
    resume_from: str = ""           # checkpoint dir to restore from ("" = no)
    # transient-failure retry for the racing pipeline stages (batch build /
    # device staging): bounded exponential backoff, interruptible by
    # close(); fatal (non-transient) failures still fail fast
    retry_max: int = 0              # 0 = no retries
    retry_base_delay_s: float = 0.05
    # non-finite guard: a NaN/Inf loss or gradient skips that batch's
    # update inside the jitted step (params and Adam state carried
    # unchanged, the skip counted) instead of silently corrupting params
    nonfinite_guard: bool = True
    # --- observability (repro.obs; train/gnn_steps.py) --------------------
    # telemetry=True enables the span tracer + selector audit for the run
    # (the metrics registry is always live); trace_out / telemetry_out
    # write the Chrome trace-event JSON and the JSONL audit export when
    # training finishes, and either being set implies telemetry on.
    # Telemetry is append-only: losses, plans, hit history, and n_traces
    # are bit-identical with it on or off.
    telemetry: bool = False
    trace_out: str = ""             # Chrome trace path ("" = no export)
    telemetry_out: str = ""         # audit JSONL path ("" = no export)


def prepare(graph: graph_mod.Graph, cfg: GNNConfig) -> dec_mod.Decomposed:
    """Preprocessing stage (paper §3.3/§4.2): self-loops + per-model edge
    normalization + reorder + decomposition, one pass.  GCN bakes the
    symmetric norm into the edge values; SAGE bakes the mean-aggregator's
    ``1/deg(dst)`` the same way, which is what lets its dual-weight
    epilogue push W_neigh through the aggregation (core.epilogue).
    ``cfg.inter_buckets == 0`` autotunes the bucket count: decompose at
    each k in {1, 2, 4}, total the cost-model estimate over the model's
    layers, commit the cheapest."""
    g = graph_mod.add_self_loops(graph) if cfg.model in ("gcn",) else graph
    vals = None
    if cfg.model == "gcn":
        vals = graph_mod.gcn_norm_values(g.n, g.senders, g.receivers)
    elif cfg.model == "sage":
        vals = graph_mod.mean_norm_values(g.n, g.senders, g.receivers)
    if cfg.inter_buckets == 0:
        return autotune_decomposition(
            g, cfg, vals, in_dim=graph.features.shape[-1],
            n_classes=graph.n_classes)
    return dec_mod.decompose(g, comm_size=cfg.comm_size, method=cfg.reorder,
                             edge_vals=vals,
                             inter_buckets=cfg.inter_buckets)


def autotune_decomposition(g: graph_mod.Graph, cfg: GNNConfig,
                           edge_vals, in_dim: int, n_classes: int,
                           ks: tuple = (1, 2, 4)) -> dec_mod.Decomposed:
    """Bucket-count autotuning: compare whole-model cost-model totals across
    candidate inter-bucket counts and commit the cheapest decomposition.
    The per-k totals land in ``dec.stats['bucket_autotune']``."""
    hw = sel_mod.default_hw()
    best, best_total, totals = None, None, {}
    for k in ks:
        dec = dec_mod.decompose(g, comm_size=cfg.comm_size,
                                method=cfg.reorder, edge_vals=edge_vals,
                                inter_buckets=k)
        # priced per k: GIN layers may flip structure with the bucket
        # count (the sparse-pass width tradeoff depends on the tiers)
        pairs, eps = layer_plan_inputs(cfg, in_dim, n_classes, dec=dec,
                                       hw=hw)
        total = sum(sel_mod.plan_layer_cost(dec, fout, hw=hw, in_dim=fin,
                                            epilogue=ep)
                    for (fin, fout), ep in zip(pairs, eps))
        totals[k] = float(total)
        if best_total is None or total < best_total:
            best, best_total = dec, total
    best.stats["bucket_autotune"] = totals
    return best


def init_model(key, cfg: GNNConfig, in_dim: int, n_classes: int) -> Params:
    keys = jax.random.split(key, cfg.n_layers)
    dims = [in_dim] + [cfg.hidden] * (cfg.n_layers - 1) + [n_classes]
    layers = []
    for i in range(cfg.n_layers):
        if cfg.model == "gcn":
            layers.append(adaptgear.init_gcn_conv(keys[i], dims[i], dims[i + 1]))
        elif cfg.model == "gin":
            layers.append(adaptgear.init_gin_conv(keys[i], dims[i], cfg.hidden,
                                                  dims[i + 1]))
        elif cfg.model == "gat":
            layers.append(adaptgear.init_gat_conv(keys[i], dims[i], dims[i + 1]))
        elif cfg.model == "sage":
            layers.append(adaptgear.init_sage_conv(keys[i], dims[i], dims[i + 1]))
        else:
            raise ValueError(cfg.model)
    return layers


def agg_widths(cfg: GNNConfig, in_dim: int, n_classes: int) -> list[int]:
    """Feature width each layer's aggregation runs at (kernel choice is
    width-dependent — per-layer selection, a beyond-paper refinement)."""
    return [fout for _, fout in agg_width_pairs(cfg, in_dim, n_classes)]


def agg_width_pairs(cfg: GNNConfig, in_dim: int,
                    n_classes: int) -> list[tuple]:
    """Per-layer ``(in_dim, agg_dim)`` width pairs.

    ``in_dim`` is non-None for transform-first layers: it is the width the
    fused transform+aggregate kernels consume, and what the selectors need
    to price fused candidates against unfused + shared transform.  GCN is
    transform-first natively; GIN and SAGE become transform-first through
    their epilogue rewrite (core.epilogue) — GIN aggregates at the MLP
    hidden width (W1 pushed through), SAGE at the layer output width
    (W_neigh pushed through).  Models that aggregate raw inputs (GAT) get
    ``(None, width)`` — fused kernels never compete there."""
    dims = [in_dim] + [cfg.hidden] * (cfg.n_layers - 1) + [n_classes]
    if cfg.model in ("gcn", "sage"):
        return list(zip(dims[:-1], dims[1:]))   # transform-first
    if cfg.model == "gin":
        # dec-free structure rule (mirrors epilogue.layer_epilogues):
        # aggregate raw features when they are narrower than the MLP
        # hidden width, else push W1 through and aggregate at hidden
        return [(None, d) if d < cfg.hidden else (d, cfg.hidden)
                for d in dims[:-1]]
    return [(None, w) for w in dims[:-1]]       # gat aggregates raw inputs


def layer_epilogues(cfg: GNNConfig, in_dim: int, n_classes: int) -> tuple:
    """Per-layer EpilogueSpecs aligned with :func:`agg_width_pairs`."""
    dims = [in_dim] + [cfg.hidden] * (cfg.n_layers - 1) + [n_classes]
    return ep_mod.layer_epilogues(cfg.model, dims, cfg.hidden)


def layer_plan_inputs(cfg: GNNConfig, in_dim: int, n_classes: int,
                      dec: dec_mod.Decomposed | None = None,
                      dtype=jnp.float32, hw=None) -> tuple[list, tuple]:
    """``(pairs, epilogues)`` for selection — the priced front door.

    Without ``dec`` this is just ``(agg_width_pairs, layer_epilogues)``:
    GIN layers use the dec-free width rule (aggregate-first iff the raw
    input is narrower than the MLP hidden width) — the mini-batch path
    lives here, since structure must be fixed before any batch exists.

    With ``dec`` (full-batch: the decomposition exists before selection)
    GIN layers where ``hidden > in_dim`` are *priced*: both structure
    candidates run through ``selector.plan_layer_cost`` — sparse pass at
    its structure's width, fused candidates competing only under
    transform-first, the dense MLP terms folded in via ``epilogue_cost``
    — and the cheaper one is committed on the layer's EpilogueSpec, so
    ``tcgnn_tile`` and friends compete under both structures."""
    pairs = agg_width_pairs(cfg, in_dim, n_classes)
    eps = layer_epilogues(cfg, in_dim, n_classes)
    if dec is None or cfg.model != "gin":
        return pairs, eps
    hw = hw or sel_mod.default_hw()
    dims = [in_dim] + [cfg.hidden] * (cfg.n_layers - 1) + [n_classes]
    pairs, eps = list(pairs), list(eps)
    for i in range(cfg.n_layers):
        fin = dims[i]
        if cfg.hidden <= fin:
            continue        # transform-first narrows the pass: keep it
        (tf_pair, tf_spec), (af_pair, af_spec) = \
            ep_mod.gin_structure_candidates(fin, cfg.hidden, dims[i + 1])
        tf_cost = sel_mod.plan_layer_cost(dec, tf_pair[1], dtype, hw=hw,
                                          in_dim=tf_pair[0],
                                          epilogue=tf_spec)
        af_cost = sel_mod.plan_layer_cost(dec, af_pair[1], dtype, hw=hw,
                                          in_dim=af_pair[0],
                                          epilogue=af_spec)
        pairs[i], eps[i] = ((af_pair, af_spec) if af_cost < tf_cost
                            else (tf_pair, tf_spec))
    return pairs, tuple(eps)


def _as_plan(dec: dec_mod.Decomposed, kernels, n_layers: int) -> KernelPlan:
    if isinstance(kernels, KernelPlan):
        if kernels.n_layers != n_layers:
            raise ValueError(f"plan has {kernels.n_layers} layers, "
                             f"model has {n_layers}")
        return kernels
    return KernelPlan.make(dec, kernels, n_layers=n_layers)


def forward(params: Params, cfg: GNNConfig, dec: dec_mod.Decomposed,
            x: jax.Array, kernels,
            inv_deg: jax.Array | None = None) -> jax.Array:
    """Model forward over a decomposition produced by :func:`prepare` (or
    the mini-batch ``prepare_skeleton``) — both bake per-model edge
    normalization, so SAGE dispatches the fused dual-weight epilogue and
    never consumes ``inv_deg`` here (the argument stays for callers whose
    own layers need it, e.g. ``aggregate_mean``)."""
    plan = _as_plan(dec, kernels, len(params))
    h = x
    for i, layer in enumerate(params):
        names = plan.for_layer(i)
        if cfg.model == "gcn":
            h = adaptgear.gcn_conv(layer, dec, h, names)
        elif cfg.model == "gin":
            # structure rides the plan's EpilogueSpec (selection priced
            # it); plans without epilogues keep the transform-first default
            ep = plan.epilogue_for_layer(i)
            h = adaptgear.gin_conv(layer, dec, h, names,
                                   structure=(ep.structure if ep is not None
                                              else "transform_first"))
        elif cfg.model == "gat":
            h = adaptgear.gat_conv(layer, dec, h)
        elif cfg.model == "sage":
            # mean norm is baked into dec's edge values (prepare): the
            # dual-weight epilogue path, fused when the plan picked it
            h = adaptgear.sage_conv(layer, dec, h, names)
        if i != len(params) - 1:
            h = jax.nn.relu(h)
    return h


def _loss(params, cfg, dec, x, labels, node_mask, plan, inv_deg):
    logits = forward(params, cfg, dec, x, plan, inv_deg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    nll = jnp.where(node_mask, nll, 0.0)
    return nll.sum() / jnp.maximum(node_mask.sum(), 1)


def make_train_step(cfg: GNNConfig, plan: KernelPlan):
    """jit step(params, opt, dec, x, labels, node_mask, inv_deg) ->
    (params, opt, loss): SGD-with-Adam over the full graph, one per
    KernelPlan.

    The graph is an argument, not a constant: a closed-over ``dec`` would
    be baked into the executable (gigabytes of HLO constants at Table-1
    sizes) and into its compile-cache key."""
    def step(params, opt, dec, x, labels, node_mask, inv_deg):
        loss, grads = jax.value_and_grad(_loss)(
            params, cfg, dec, x, labels, node_mask, plan, inv_deg)
        new_params, new_opt = _adam_update(params, grads, opt, cfg.lr)
        return new_params, new_opt, loss

    return jax.jit(step)


def _adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return dict(m=zeros, v=jax.tree.map(jnp.zeros_like, params),
                t=jnp.zeros((), jnp.int32))


def _adam_update(params, grads, opt, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    tf = t.astype(jnp.float32)
    mh = jax.tree.map(lambda m: m / (1 - b1 ** tf), m)
    vh = jax.tree.map(lambda v: v / (1 - b2 ** tf), v)
    new = jax.tree.map(lambda p, mh, vh: p - lr * mh / (jnp.sqrt(vh) + eps),
                       params, mh, vh)
    return new, dict(m=m, v=v, t=t)


@dataclass
class TrainResult:
    losses: list
    accuracy: float
    kernels: list          # per-layer tuples (KernelPlan rows)
    probe_times: dict
    step_seconds: float
    preprocess_seconds: float
    plan: Any = None       # the full KernelPlan
    select_seconds: float = 0.0    # kernel selection (feedback probes)
    compile_seconds: float = 0.0   # AOT compile of the train step
    step: Any = None       # the compiled train step (jax.stages.Compiled)
    params: Any = None     # trained model params
    dec: Any = None        # the decomposition the step ran over


def select_plan(dec: dec_mod.Decomposed, cfg: GNNConfig,
                widths: list, dtype=jnp.float32,
                epilogues: tuple | None = None
                ) -> tuple[KernelPlan, dict]:
    """Commit a KernelPlan with the configured selector mode.  ``dtype``
    is the aggregation dtype — feedback probes must time the kernels that
    will actually run.

    ``widths`` entries are either aggregated widths (ints) or
    ``(in_dim, agg_dim)`` pairs from :func:`agg_width_pairs`; a non-None
    in_dim lets fused transform+aggregate candidates compete in both
    selector modes.  ``epilogues`` (from :func:`layer_epilogues`, aligned
    with ``widths``) adjusts the honest comparison per layer: an MLP
    epilogue's shared transform is free to unfused candidates, a dual
    epilogue's self matmul is flat across them."""
    pairs = [(None, w) if isinstance(w, int) else tuple(w) for w in widths]
    eps = tuple(epilogues) if epilogues is not None else (None,) * len(pairs)
    probe_times: dict = {}
    if cfg.selector == "fixed":
        plan = KernelPlan.make(dec, tuple(cfg.fixed_kernels),
                               n_layers=len(pairs), epilogues=eps)
    elif cfg.selector == "cost_model":
        hw = sel_mod.default_hw()
        plan = KernelPlan.make(
            dec, [sel_mod.select_by_cost_model(dec, fout, dtype, hw=hw,
                                               in_dim=fin, epilogue=ep)
                  for (fin, fout), ep in zip(pairs, eps)],
            epilogues=eps)
    elif cfg.selector == "feedback":
        # paper default: probe every registry candidate during warmup
        fused_ok = any(fin is not None for fin, _ in pairs)
        sel = sel_mod.AdaptiveSelector(dec, warmup_iters=cfg.warmup_iters,
                                       include_fused=fused_ok)
        ep_of = {p: e for p, e in zip(pairs, eps)}
        for fin, fout in sorted(set(pairs), key=lambda p: (p[1], p[0] or 0)):
            probe_x = jnp.ones((dec.n_pad, fout), dtype)
            transform = (None if fin is None else
                         (jnp.ones((dec.n_pad, fin), dtype),
                          jnp.ones((fin, fout), dtype)))
            ep = ep_of[(fin, fout)]
            res = sel.probe(probe_x, iters=cfg.warmup_iters,
                            transform=transform,
                            free_transform=bool(ep and ep.free_transform))
            probe_times.update({k + (fout,): v for k, v in res.times.items()})
        # choices are keyed by the full (in_dim, agg_dim) pair: layers that
        # share an output width but differ in input width sit on opposite
        # sides of the fused recompute crossover
        plan = KernelPlan.make(
            dec, [sel.choice(fout if fin is None else (fin, fout))
                  for fin, fout in pairs], epilogues=eps)
    else:
        raise ValueError(f"unknown selector {cfg.selector!r}")
    return plan, probe_times


def train(graph: graph_mod.Graph, cfg: GNNConfig, steps: int = 50,
          verbose: bool = False):
    """Full training driver with the paper's feedback selection protocol.

    ``cfg.sampler != "full"`` switches to mini-batch sampled-subgraph
    training (train/gnn_steps.py: Graph -> Sampler -> SampledBatch ->
    decompose -> PlanCache -> jitted step) and returns its
    MinibatchResult instead of a TrainResult.  There ``fixed`` selection
    is honored per batch, while ``feedback`` and ``cost_model`` both
    resolve to cached cost-model selection (per-batch wall-clock probing
    cannot amortize over fresh subgraphs — see train_minibatch)."""
    if cfg.sampler != "full":
        from repro.train import gnn_steps   # lazy: avoids an import cycle
        return gnn_steps.train_minibatch(graph, cfg, steps=steps,
                                         verbose=verbose)
    t0 = time.perf_counter()
    dec = prepare(graph, cfg)
    t_pre = time.perf_counter() - t0

    x = adaptgear.to_reordered(dec, jnp.asarray(graph.features))
    labels_r = np.zeros((dec.n_pad,), np.int32)
    labels_r[np.asarray(dec.perm)] = graph.labels
    labels_r = jnp.asarray(labels_r)
    node_mask = np.zeros((dec.n_pad,), bool)
    node_mask[np.asarray(dec.perm)] = True
    node_mask = jnp.asarray(node_mask)
    deg = np.bincount(graph.receivers, minlength=graph.n).astype(np.float32)
    inv_deg_r = np.zeros((dec.n_pad,), np.float32)
    inv_deg_r[np.asarray(dec.perm)] = 1.0 / np.maximum(deg, 1.0)
    inv_deg = jnp.asarray(inv_deg_r)

    key = jax.random.PRNGKey(cfg.seed)
    params = init_model(key, cfg, x.shape[-1], graph.n_classes)
    opt = _adam_init(params)

    # --- kernel selection (per layer: aggregation width differs by layer;
    # transform-first layers carry their input width so fused candidates
    # compete — GCN natively, GIN/SAGE through the epilogue rewrite)
    t0 = time.perf_counter()
    pairs, eps = layer_plan_inputs(cfg, x.shape[-1], graph.n_classes,
                                   dec=dec, dtype=x.dtype)
    plan, probe_times = select_plan(dec, cfg, pairs, dtype=x.dtype,
                                    epilogues=eps)
    t_sel = time.perf_counter() - t0

    args = (dec, x, labels_r, node_mask, inv_deg)
    t0 = time.perf_counter()
    step_fn = make_train_step(cfg, plan).lower(params, opt, *args).compile()
    t_compile = time.perf_counter() - t0

    losses = []
    t_step0 = None
    for i in range(steps):
        if i == 1:
            t_step0 = time.perf_counter()
        params, opt, loss = step_fn(params, opt, *args)
        losses.append(float(loss))
        if verbose and i % 10 == 0:
            print(f"step {i:4d} loss {float(loss):.4f} plan={plan.layers}")
    jax.block_until_ready(params)
    step_s = (time.perf_counter() - t_step0) / max(steps - 1, 1) if t_step0 else 0.0

    logits = forward(params, cfg, dec, x, plan, inv_deg)
    pred = jnp.argmax(logits, -1)
    acc = float(jnp.where(node_mask, pred == labels_r, False).sum()
                / node_mask.sum())
    return TrainResult(losses=losses, accuracy=acc,
                       kernels=[tuple(k) for k in plan.layers],
                       probe_times=probe_times, step_seconds=step_s,
                       preprocess_seconds=t_pre, plan=plan,
                       select_seconds=t_sel, compile_seconds=t_compile,
                       step=step_fn, params=params, dec=dec)
