"""Mini-batch GNN training: sampled subgraphs through the AdaptGear stack.

Per step (host side): sample a fixed-shape :class:`SampledBatch`, run the
paper's decomposition on the sampled subgraph, look its quantized density
signature up in the :class:`PlanCache` (cost-model selection on miss), pad
the payloads to the budgets, and feed the jitted step.  The step function
is keyed by the committed :class:`KernelPlan` (kernel choices are static
dispatch); batches sharing a plan share one compiled step, and because
every batch presents identical ShapeDtypeStructs the step never retraces
after its first compile.

The loop mirrors :func:`repro.core.gnn.train` (same models, same Adam, same
masked cross-entropy — here masked to the batch's target nodes) but over
``steps`` sampled batches instead of one full graph.

Fault tolerance (distributed/checkpoint.py + distributed/fault_tolerance.py
revived for the GNN path) — four mechanisms, all driven by GNNConfig knobs
and testable through the deterministic :class:`~repro.distributed.
fault_tolerance.FaultPlan` harness:

* **crash-safe checkpoint/resume**: every ``cfg.checkpoint_every`` consumed
  batches the loop snapshots params + opt state (npz, crc-manifested,
  atomic tmp+rename, async writer) plus an aux payload — the batch cursor,
  the sampler draw count, the full PlanCache state (entries, counters,
  slack-ladder position, quarantine), the per-plan canonical signatures in
  step-function order, and the loss/hit history so far.  Because batch i
  is a pure function of (seed, i) and every shared-cache decision is made
  in batch-index order (the PR-6 determinism contract), restoring that
  snapshot and replaying from the cursor is *bit-identical* to never
  having crashed: same loss curve, same committed plans, same hit history.
  The cache/plan snapshot is captured inside the index-ordered resolve
  stage (not at consume time): with prefetching, the PlanCache at
  consume-time of batch i already holds decisions for batches i+1..i+depth,
  which must not leak into batch i's checkpoint.
* **transient-failure retry**: the pipeline's racing stages retry with
  bounded exponential backoff (``cfg.retry_max``), interruptible on
  close(); non-transient failures fail fast.
* **kernel quarantine**: a Pallas compile or execution failure quarantines
  the implicated (kernel, signature) pairs in the PlanCache, re-selects
  next-best, rebuilds the batch's payloads, and keeps training — a broken
  kernel costs performance, never the run (the XLA coo floor always runs).
* **non-finite guard**: the jitted step carries params/opt through
  unchanged when the loss or any gradient is non-finite
  (``cfg.nonfinite_guard``), and the skip is counted instead of silently
  corrupting the model.
"""
from __future__ import annotations

import logging
import threading
import time
import warnings
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decompose as dec_mod, gnn, selector as sel_mod
from repro.core.plan import KernelPlan
from repro.distributed import checkpoint as ckpt_mod
from repro.distributed import fault_tolerance as ft
from repro.graphs import graph as graph_mod
from repro.kernels.registry import REGISTRY
from repro.obs import Telemetry, enable_verbose, get_logger
from repro.sampling.plan_cache import (MB_KERNELS, PlanCache, fix_shapes,
                                       plan_payload_keys)
from repro.sampling.sampler import (ClusterSampler, NeighborSampler,
                                    SampledBatch)
from repro.train.pipeline import BatchPipeline

_log = get_logger("repro.train")


def make_sampler(graph: graph_mod.Graph, cfg: gnn.GNNConfig):
    """Sampler from the GNNConfig knobs (cfg.sampler: cluster | neighbor).
    Cluster blocks are the decomposition's community size, so per-batch
    ``decompose(reorder=False)`` sees cluster-aligned diagonal blocks."""
    if cfg.sampler == "cluster":
        return ClusterSampler(
            graph, block=cfg.comm_size,
            clusters_per_batch=cfg.clusters_per_batch, method=cfg.reorder,
            edge_budget=cfg.edge_budget or None, seed=cfg.seed)
    if cfg.sampler == "neighbor":
        return NeighborSampler(
            graph, batch_nodes=cfg.batch_nodes, fanouts=cfg.fanouts,
            method=cfg.reorder, block=cfg.comm_size, seed=cfg.seed)
    raise ValueError(f"unknown sampler {cfg.sampler!r} "
                     "(expected 'cluster' or 'neighbor')")


def batch_edge_budget(batch: SampledBatch, cfg: gnn.GNNConfig) -> int:
    """Padded edge-slot count the fixed-shape payloads are built to: the
    sampler's edge budget plus one self-loop slot per (padded) node for
    GCN.  Derived from the batch arrays alone, so it equals
    ``sampler.edge_budget (+ sampler.node_budget)`` for every batch."""
    return len(batch.senders) + (batch.n if cfg.model == "gcn" else 0)


def prepare_skeleton(batch: SampledBatch, cfg: gnn.GNNConfig,
                     bell_slack: float | None = None
                     ) -> tuple[dec_mod.DecomposeSkeleton, np.ndarray]:
    """Single-pass per-batch preprocessing: per-model edge normalization
    over the *sampled* subgraph (GCN: self-loops + symmetric norm; SAGE:
    the mean-aggregator's 1/deg baked into the edge values, which is what
    lets the dual-weight epilogue fuse — core.epilogue) then ONE
    partition+stats pass producing a :class:`DecomposeSkeleton` with a
    pinned bucket count and the edge budget threaded through
    (budget-paddable builders key off it).  ``bell_slack`` is the adapted
    blocked-ELL budget slack from the PlanCache's budget-K autotuner.
    Also returns the batch's inverse in-degree (kept for API stability;
    the baked SAGE path no longer consumes it).

    The hot loop runs the PlanCache lookup against ``skel.stats_only()``
    and materializes payloads from the same skeleton — the edges are never
    re-partitioned, halving host-side prep vs the old two-pass flow."""
    s, r = batch.real_edges()
    vals = None
    if cfg.model == "gcn":
        loops = batch.node_mask.nonzero()[0].astype(np.int32)
        s = np.concatenate([s, loops])
        r = np.concatenate([r, loops])
        vals = graph_mod.gcn_norm_values(batch.n, s, r)
    elif cfg.model == "sage":
        vals = graph_mod.mean_norm_values(batch.n, s, r)
    g = graph_mod.Graph(batch.n, s, r, batch.features, batch.labels,
                        n_classes=1, name="batch")
    skel = dec_mod.decompose_skeleton(
        g, comm_size=cfg.comm_size, reorder=False,
        inter_buckets=max(cfg.inter_buckets, 1), edge_vals=vals,
        keep_empty_buckets=True, edge_budget=batch_edge_budget(batch, cfg),
        bell_slack=bell_slack)
    deg = np.bincount(r, minlength=batch.n).astype(np.float32)
    inv_deg = np.where(batch.node_mask, 1.0 / np.maximum(deg, 1.0), 0.0)
    return skel, inv_deg.astype(np.float32)


def prepare_batch(batch: SampledBatch, cfg: gnn.GNNConfig,
                  kernels: tuple = MB_KERNELS
                  ) -> tuple[dec_mod.Decomposed, np.ndarray]:
    """One-shot prepare: skeleton + materialize in a single call.  Returns
    the decomposition (real, un-padded stats — what selection and the
    signature read) and the inverse in-degree.

    ``kernels=()`` gives a stats-only decomposition (no format payloads).
    Callers that need both a lookup view and payloads should hold the
    :func:`prepare_skeleton` result and materialize from it instead of
    calling this twice — that is the single-pass hot path."""
    skel, inv_deg = prepare_skeleton(batch, cfg)
    return skel.materialize(kernels), inv_deg


def make_sampled_step(cfg: gnn.GNNConfig, plan, counters: dict):
    """jit step(params, opt, dec, x, labels, target_mask, inv_deg)
    -> (params, opt, loss, finite).

    ``dec`` is a *traced argument*, as in the full-batch step: its payload
    arrays change every batch while its structure — after
    :func:`fix_shapes` — does not.
    ``counters['traces']`` increments once per retrace, making the
    no-retrace contract observable by tests and benchmarks.

    With ``cfg.nonfinite_guard`` the update is gated on the loss and every
    gradient being finite: a NaN/Inf batch carries params and the full
    Adam state (including the step count ``t``) through unchanged, and the
    returned ``finite`` flag lets the loop count the skip.  The guard is a
    few elementwise reductions over arrays the step already touched —
    noise next to the aggregation matmuls — so it defaults on."""
    guard = cfg.nonfinite_guard

    def step(params, opt, dec, x, labels, target_mask, inv_deg):
        counters["traces"] += 1
        loss, grads = jax.value_and_grad(gnn._loss)(
            params, cfg, dec, x, labels, target_mask, plan, inv_deg)
        new_params, new_opt = gnn._adam_update(params, grads, opt, cfg.lr)
        if not guard:
            return new_params, new_opt, loss, jnp.bool_(True)
        finite = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            finite = finite & jnp.all(jnp.isfinite(g))
        new_params = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new_params, params)
        new_opt = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new_opt, opt)
        return new_params, new_opt, loss, finite

    return jax.jit(step)


def make_infer_step(cfg: gnn.GNNConfig, plan, counters: dict):
    """jit infer(params, dec, x, inv_deg) -> logits — the serving read
    path (src/repro/serve/): the same forward pass the train step
    differentiates, without loss/grad/Adam.

    The contract mirrors :func:`make_sampled_step`: the step is keyed by
    the committed plan (static kernel dispatch), ``dec`` is a traced
    argument whose :func:`fix_shapes`-padded structure never varies, and
    ``counters['traces']`` increments per retrace — which is how the
    server's warm-start acceptance (zero compiles after warmup) is
    observable.  Returns the full (node_budget, n_classes) logits; the
    caller gathers its seeds' rows host-side, so one compiled executable
    serves every micro-batch composition."""
    def infer(params, dec, x, inv_deg):
        counters["traces"] += 1
        return gnn.forward(params, cfg, dec, x, plan, inv_deg)

    return jax.jit(infer)


@dataclass
class MinibatchResult:
    losses: list
    accuracy: float
    cache: dict                  # PlanCache.stats snapshot
    hit_history: list            # per-step cache hit booleans
    plans: list                  # distinct plan layer tuples, first-seen order
    n_traces: int                # total jit traces across all step fns
    step_seconds: float          # median jitted-step wall time (post-compile)
    sample_seconds: float        # median sampler time per batch
    prepare_seconds: float       # median decompose+select+pad time per batch
    dropped_edges: int           # edges truncated by the budget, total
    plan_cache: Any = None
    skeleton_hits: int = 0       # batches whose cluster tuple reused a
    skeleton_misses: int = 0     # cached DecomposeSkeleton (ClusterSampler)
    iter_seconds: float = 0.0    # median wall time of one full training
    #                              iteration (dequeue/prepare + step); the
    #                              overlap metric: async ~= max(compute,
    #                              prepare), sync ~= their sum
    pipeline: dict | None = None  # BatchPipeline.stats + efficiency_pct /
    #                               loop_seconds (None on the sync path)
    faults: dict | None = None   # fault-tolerance counters: retries,
    #                              quarantined, recoveries, nonfinite_skips,
    #                              checkpoints, resumed_at (-1 = fresh run);
    #                              on a resumed run losses/hit_history hold
    #                              the full curve (restored prefix + new)
    telemetry: dict | None = None  # Telemetry.summary(): span/audit volume,
    #                                the selector calibration report, and
    #                                the full metrics snapshot (the cache/
    #                                pipeline/faults views above are
    #                                assembled from the same registry)
    params: Any = None           # trained model params — what the serving
    #                              tier (repro.serve) loads a server from

    def hit_rate(self, warmup: int = 0) -> float:
        h = self.hit_history[warmup:]
        return sum(h) / max(len(h), 1)


class SkeletonCache:
    """Cluster-tuple -> (skeleton, inv_deg) memo (ROADMAP skeleton reuse).

    ClusterSampler draws cluster combinations without replacement per
    epoch, so tuples recur across epochs; a batch drawn for a tuple is
    fully determined by it (induced edges + features) *unless* the edge
    budget truncated a random subset — such batches are never cached.
    The adapted bell slack is part of the key: a slack step changes the
    capped-bell K baked into the skeleton's tier stats.

    Thread-safe: get/put hold a lock so pipeline workers share the memo
    (two workers racing one tuple at worst both build — counted as two
    misses — and the later put wins; entries are deterministic per key,
    so which one lands is immaterial)."""

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(batch: SampledBatch, bell_slack) -> tuple | None:
        clusters = batch.meta.get("clusters")
        if clusters is None or batch.meta.get("dropped_edges", 0):
            return None
        return (tuple(clusters), bell_slack)

    def get(self, key: tuple):
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self.hits += 1
                self._entries.move_to_end(key)
            return hit

    def put(self, key: tuple, value: tuple) -> None:
        with self._lock:
            self.misses += 1
            self._entries[key] = value
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)


class _CompileFailed:
    """Sentinel the finish stage hands the consumer when AOT lowering of a
    (plan, shapes) key raised: the consumer routes it into the kernel
    quarantine instead of dispatching.  The failure is memoized per shape
    key so in-flight batches sharing the broken plan reuse the verdict
    rather than re-tracing — the one failed trace already counted, and
    ``traces == len(plans)`` must survive a quarantine."""

    def __init__(self, exc: BaseException):
        self.exc = exc


@dataclass
class _Prepared:
    """One fully host-prepared batch: what crosses the producer/consumer
    boundary.  ``args`` is the step's argument tail
    ``(dec, x, labels, target_mask, inv_deg)`` — staged on device by the
    pipeline workers, host numpy on the sync path (jit transfers it).
    ``step`` is the callable to dispatch: the shared jitted step on the
    sync path, the AOT-compiled executable a worker prepared on the async
    path (invoking the executable directly is what keeps the consumer from
    ever tracing — the jit cache and the AOT cache are separate)."""
    batch: SampledBatch
    plan: KernelPlan
    args: tuple
    hit: bool
    sample_s: float
    prepare_s: float
    step: Any


@dataclass
class _InFlight:
    """Mutable carry between the pipeline's stages for one batch: built
    racing (``skel``/speculative payloads), resolved in index order
    (``plan``/``hit``/``sig`` — every shared-cache decision), finished
    racing (payload padding + device staging -> :class:`_Prepared`)."""
    batch: SampledBatch
    skel: dec_mod.DecomposeSkeleton
    inv_deg: np.ndarray
    slack: float | None          # bell slack the skeleton was built with
    sample_s: float
    prepare_s: float
    index: int | None = None     # the sampler's batch index (None: eval)
    dec: dec_mod.Decomposed | None = None
    plan: KernelPlan | None = None
    sig: tuple | None = None
    hit: bool = False


def train_minibatch(graph: graph_mod.Graph, cfg: gnn.GNNConfig,
                    steps: int = 50, verbose: bool = False,
                    eval_batches: int = 4,
                    plan_cache: PlanCache | None = None,
                    fault_plan: "ft.FaultPlan | None" = None,
                    telemetry: Telemetry | None = None
                    ) -> MinibatchResult:
    """Mini-batch driver: Graph -> Sampler -> SampledBatch -> decompose ->
    PlanCache -> jitted step, with per-phase timing and cache accounting.

    Selector modes: ``fixed`` is honored (the configured kernels dispatch
    every batch, no cache needed — they must be budget-paddable, e.g.
    ``("block_diag", "bell")``); ``feedback`` and ``cost_model`` both
    select analytically through the PlanCache — per-batch wall-clock
    probing cannot amortize over a stream of fresh subgraphs, but
    ``cfg.probe_every`` re-adds feedback amortized over the cache's
    lifetime: every Nth miss times the top-2 cost-model candidates and
    pins the winner in the cached entry.

    ``cfg.prefetch_depth > 0`` switches the loop to the async pipeline
    (train/pipeline.py): ``cfg.pipeline_workers`` background threads draw
    batches, run the skeleton/plan/pad prepare, stage device transfers,
    and pre-compile any novel payload shape up to ``prefetch_depth``
    batches ahead; this loop becomes a pure consumer dequeuing ready
    batches in order, so one iteration pays max(compute, prepare) instead
    of their sum.  The batch stream, committed plans, cache counters, and
    loss curve are bit-identical to the sync path under the same seed:
    samplers draw from per-index deterministic seed streams, and every
    shared-cache decision (PlanCache lookup/selection, spill feedback,
    signature seeding) runs through the pipeline's index-ordered resolve
    stage — only the sampler build, skeleton partition, payload padding,
    device staging, and AOT pre-compiles race across workers.  With
    ``cfg.adapt_budget_k`` the committed payloads also materialize in the
    ordered stage (the spill feedback that steps the slack ladder must
    observe batches in order), trading some overlap for determinism.

    Fault tolerance (see the module docstring for the contract):
    ``cfg.checkpoint_dir`` + ``cfg.checkpoint_every`` enable periodic
    crash-safe snapshots, ``cfg.resume_from`` restarts mid-epoch
    bit-identically to the uninterrupted run, ``cfg.retry_max`` retries
    transient build/stage failures with backoff, a Pallas compile/execute
    failure quarantines the (kernel, signature) in the PlanCache and
    degrades to the next-best plan, and ``cfg.nonfinite_guard`` skips (and
    counts) NaN/Inf updates.  ``fault_plan`` injects deterministic faults
    for tests/benchmarks; kernel faults additionally need the registry
    patched via ``with fault_plan.activate(): ...`` around this call.

    Observability (repro.obs): ``telemetry`` (or ``cfg.telemetry`` /
    ``cfg.trace_out`` / ``cfg.telemetry_out``) turns on the span tracer
    and the selector audit for the run; the metrics registry is always
    live (the ``cache``/``pipeline``/``faults`` result views are
    assembled from it).  ``MinibatchResult.telemetry`` carries the
    summary — including the cost-model calibration report — and
    ``cfg.trace_out`` / ``cfg.telemetry_out`` write the Chrome trace and
    the JSONL audit export when the run finishes.  Telemetry is
    append-only: it never feeds back into cache decisions or batch
    order, so enabling it leaves losses, plans, hit history, and
    n_traces bit-identical."""
    if cfg.model not in ("gcn", "gin", "sage"):
        raise ValueError(f"mini-batch training supports gcn/gin/sage, "
                         f"not {cfg.model!r}")
    if verbose:
        enable_verbose()
    tele = (telemetry if telemetry is not None
            else Telemetry(enabled=bool(cfg.telemetry or cfg.trace_out
                                        or cfg.telemetry_out)))
    tracer = tele.tracer
    fixed_names = (tuple(cfg.fixed_kernels) if cfg.selector == "fixed"
                   else None)
    audited_fixed_sigs: set = set()   # one plan receipt per pinned signature
    sampler = make_sampler(graph, cfg)
    in_dim = graph.features.shape[-1]
    pairs = gnn.agg_width_pairs(cfg, in_dim, graph.n_classes)
    epilogues = gnn.layer_epilogues(cfg, in_dim, graph.n_classes)
    # total budget the padded payloads see: sampled edges + GCN self-loops
    pad_budget = sampler.edge_budget + (sampler.node_budget
                                        if cfg.model == "gcn" else 0)
    if plan_cache is not None:
        # a pre-built cache re-homes its instruments into this run's
        # telemetry so the result views and exports see one registry
        plan_cache.attach_telemetry(tele)
    cache = plan_cache or PlanCache(pairs, dtype=np.float32,
                                    hw=sel_mod.default_hw(),
                                    max_entries=cfg.cache_entries,
                                    probe_every=cfg.probe_every,
                                    edge_budget=pad_budget,
                                    epilogues=epilogues,
                                    probe_k_max=cfg.probe_k_max,
                                    probe_budget_s=cfg.probe_budget_s,
                                    adapt_budget_k=cfg.adapt_budget_k,
                                    max_slack_changes=(
                                        cfg.max_ladder_recompiles),
                                    telemetry=tele)
    skel_cache = (SkeletonCache(cfg.skeleton_cache_entries)
                  if cfg.skeleton_cache_entries > 0 else None)

    key = jax.random.PRNGKey(cfg.seed)
    params = gnn.init_model(key, cfg, in_dim, graph.n_classes)
    opt = gnn._adam_init(params)

    ckpt = (ckpt_mod.CheckpointManager(cfg.checkpoint_dir,
                                       keep=cfg.checkpoint_keep,
                                       telemetry=tele)
            if cfg.checkpoint_dir and cfg.checkpoint_every > 0 else None)
    retry_policy = (ft.RetryPolicy(max_retries=cfg.retry_max,
                                   base_delay_s=cfg.retry_base_delay_s,
                                   tracer=tracer if tele.enabled else None)
                    if cfg.retry_max > 0 else None)
    # fault-tolerance counters live in the run's metrics registry; the
    # MinibatchResult.faults view is assembled from them at the end
    fault = {k: tele.metrics.counter(f"faults.{k}")
             for k in ("retries", "quarantined", "recoveries",
                       "nonfinite_skips", "checkpoints")}
    f_resumed = tele.metrics.gauge("faults.resumed_at")
    f_resumed.set(-1)

    def fault_view() -> dict:
        out = {k: c.value for k, c in fault.items()}
        out["resumed_at"] = f_resumed.value
        return out

    # canonical preserved signature per step-fn key (= plan.layers): the
    # bins fix_shapes stamps on the traced Decomposed are static jit
    # metadata, so every batch sharing a step function must carry the SAME
    # value — first signature seen (in batch-index order) for a layer
    # tuple wins
    sig_of_layers: dict[tuple, tuple] = {}

    counters = dict(traces=0)
    # plan.layers -> jitted step, in first-use batch order (sync dispatch
    # and the reported plans list); seeded from the ordered resolve stage
    # so async insertion order matches the sync loop's
    step_fns: dict[tuple, Any] = {}
    # (plan.layers, treedef, leaf shapes) -> AOT executable: what the
    # async consumer dispatches (the jit cache and the AOT cache are
    # separate, so a worker-compiled shape is only a consumer cache hit
    # if the consumer invokes the executable itself)
    compiled_steps: dict[tuple, Any] = {}
    compile_lock = threading.Lock()
    # plan.layers -> full KernelPlan at first use: checkpoints persist the
    # plans in step-fn order so a resumed run can reseed step_fns (and the
    # reported plans list) in the identical order
    first_plan: dict[tuple, KernelPlan] = {}
    # quarantine memos — a plan that failed once is never re-dispatched or
    # re-traced (consume short-circuits straight into recovery)
    failed_steps: dict[tuple, BaseException] = {}
    failed_compiles: dict[tuple, _CompileFailed] = {}
    # abstract (params, opt) twins: pipeline workers AOT-lower the step
    # against these ShapeDtypeStructs for each novel payload shape, so
    # the compile happens off the consumer path without *executing* a
    # throwaway step — an executed warmup would contend with the
    # consumer's real step on the device and skew t_step/efficiency
    aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    warm_params = jax.tree.map(aval, params)
    warm_opt = jax.tree.map(aval, opt)

    def get_step_fn(plan):
        fn = step_fns.get(plan.layers)        # lock-free steady state
        if fn is None:
            with compile_lock:
                fn = step_fns.get(plan.layers)
                if fn is None:
                    first_plan[plan.layers] = plan
                    fn = step_fns[plan.layers] = make_sampled_step(
                        cfg, plan, counters)
        return fn

    def warm_compile(fn, plan, args):
        """AOT-compile (plan, payload shapes) off the consumer path and
        return the executable the consumer dispatches.  Compiles — and
        the trace counter the lowering bumps — serialize behind the lock;
        they are rare: one per plan plus one per adaptive-K ladder step,
        the latter capped by cfg.max_ladder_recompiles through the
        PlanCache."""
        leaves, treedef = jax.tree_util.tree_flatten(args)
        skey = (plan.layers, treedef,
                tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        with compile_lock:
            failed = failed_compiles.get(skey)
            if failed is not None:
                return failed
            comp = compiled_steps.get(skey)
            if comp is None:
                try:
                    comp = compiled_steps[skey] = fn.lower(
                        warm_params, warm_opt, *args).compile()
                except Exception as exc:
                    # broken-kernel lowering: memoize so same-plan batches
                    # already in flight don't re-trace, and let the
                    # consumer quarantine + degrade in index order
                    failed = failed_compiles[skey] = _CompileFailed(exc)
                    return failed
            return comp

    def skeleton_for(batch, slack):
        """Skeleton + inverse in-degree, through the SkeletonCache (one
        partition pass, skipped entirely on a cluster-tuple memo hit)."""
        skey = (SkeletonCache.key(batch, slack) if skel_cache is not None
                else None)
        cached = skel_cache.get(skey) if skey is not None else None
        if cached is not None:
            return cached
        skel, inv_deg = prepare_skeleton(batch, cfg, bell_slack=slack)
        if skey is not None:
            skel_cache.put(skey, (skel, inv_deg))
        return skel, inv_deg

    def build_batch(batch, sample_s, index=None) -> _InFlight:
        """Racing stage: the partition pass into a skeleton — reading a
        *speculative* bell slack when the budget-K autotuner is live (the
        ordered resolve stage rebuilds on the rare mid-flight ladder
        step) — plus the fixed selector's payloads, which involve no
        shared-state decision."""
        t0 = time.perf_counter()
        with tracer.span("build", cat="host", index=index):
            slack = cache.bell_slack if cfg.adapt_budget_k else None
            skel, inv_deg = skeleton_for(batch, slack)
            c = _InFlight(batch=batch, skel=skel, inv_deg=inv_deg,
                          slack=slack, sample_s=sample_s, prepare_s=0.0,
                          index=index)
            if fixed_names is not None and not cfg.adapt_budget_k:
                c.dec = skel.materialize(fixed_names)
                c.plan = KernelPlan.make(c.dec, fixed_names,
                                         n_layers=cfg.n_layers,
                                         epilogues=epilogues)
        c.prepare_s += time.perf_counter() - t0
        return c

    def resolve_batch(c: _InFlight, gi: int | None = None) -> _InFlight:
        """Ordered stage: every shared-cache decision, made in batch-index
        order — the pipeline runs this through its turnstile; the sync
        path is trivially in order.  plan_for's atomicity alone is not
        enough for the determinism contract: a later-index batch racing
        ahead could run its lookup before an earlier-index batch commits
        the entry it would have hit, turning a hit (or near-hit) into a
        genuine miss and diverging hit_history, the LRU order, and the
        near-hit anchor scan from the sync loop.  Selection on a miss
        runs here too — the sync loop pays it at the same point, and
        steady-state misses are rare."""
        t0 = time.perf_counter()
        with tracer.span("resolve", cat="host", index=c.index):
            if cfg.adapt_budget_k:
                slack = cache.bell_slack
                if slack != c.slack:   # ladder stepped while c was in flight
                    c.slack = slack
                    c.skel, c.inv_deg = skeleton_for(c.batch, slack)
                    c.dec = c.plan = None
            if fixed_names is not None:
                if c.dec is None:      # adapt_budget_k defers the build here
                    c.dec = c.skel.materialize(fixed_names)
                    c.plan = KernelPlan.make(c.dec, fixed_names,
                                             n_layers=cfg.n_layers,
                                             epilogues=epilogues)
                c.hit = True
                if tele.audit.enabled:
                    # pinned plans leave the same priced receipt as
                    # cost-model mints (source="fixed"), once per distinct
                    # signature — the calibration report covers every
                    # kernel that actually ran, pinned or selected
                    sig = cache.signature(c.dec)
                    if sig not in audited_fixed_sigs:
                        audited_fixed_sigs.add(sig)
                        modeled = sel_mod.plan_modeled_costs(
                            c.dec, c.plan.layers, cache.pairs, cache.dtype,
                            hw=cache.hw, epilogues=cache.epilogues)
                        tele.audit.plan(
                            sig=sig, layers=c.plan.layers,
                            tiers=[s.name for s in c.dec.subgraphs],
                            modeled_s=modeled, source="fixed")
            else:
                # signature/anchor read tier stats only, so the skeleton is
                # consumed directly — no payload-free Decomposed on the hot
                # path
                c.plan = cache.lookup(c.skel)
                c.hit = c.plan is not None
                if not c.hit:
                    c.dec = c.skel.materialize(MB_KERNELS)
                    c.plan, _ = cache.plan_for(c.dec)
                elif cfg.adapt_budget_k:
                    # the spill-feedback stream steps the slack ladder, so
                    # it must observe batches in order too: the committed
                    # payloads materialize here while the autotuner is live
                    # (with it off — the default — a hit's payloads race in
                    # the finish stage)
                    c.dec = c.skel.materialize(plan_payload_keys(c.plan))
            if c.dec is not None:
                # committed capped-bell payloads feed the budget-K autotuner
                cache.observe_bell(c.dec)
            c.sig = sig_of_layers.setdefault(c.plan.layers,
                                             cache.signature(c.skel))
            get_step_fn(c.plan)  # step-fn (and reported-plan) order pinned
            if (ckpt is not None and gi is not None
                    and (gi + 1) % cfg.checkpoint_every == 0):
                # capture the cache/plan snapshot HERE, inside the
                # index-ordered stage: at consume-time of batch gi the
                # prefetching pipeline has already resolved batches
                # gi+1..gi+depth, whose cache decisions must not leak into
                # batch gi's checkpoint.  The consumer pairs this snapshot
                # with its own params/opt/losses when it commits batch gi.
                with compile_lock:
                    plans = [first_plan[k] for k in step_fns]
                    sigs = [sig_of_layers[k] for k in step_fns]
                with snap_lock:
                    pending_snaps[gi] = dict(cache=cache.state_dict(),
                                             plans=plans, sigs=sigs)
        c.prepare_s += time.perf_counter() - t0
        return c

    def finish_batch(c: _InFlight, stage: bool) -> _Prepared:
        """Racing stage: pad the committed plan's payloads to the budget
        and (async) stage device transfers + AOT-compile, so the
        consumer's dispatch never pays a host->device copy or a compile."""
        t0 = time.perf_counter()
        with tracer.span("finish", cat="host", index=c.index):
            if c.dec is None:
                # tier i materializes only the payloads the plan dispatches
                # on tier i (per-subgraph keep sets)
                c.dec = c.skel.materialize(plan_payload_keys(c.plan))
            # only the payloads this plan dispatches cross the jit
            # boundary; the keep sets are a function of the plan, so
            # batches sharing a step function share one treedef
            fixed = fix_shapes(c.dec, pad_budget,
                               keep=plan_payload_keys(c.plan), stats=c.sig)
            args = (fixed, c.batch.features, c.batch.labels,
                    c.batch.target_mask, c.inv_deg)
            fn = get_step_fn(c.plan)
            if stage:
                args = jax.device_put(args)
                fn = warm_compile(fn, c.plan, args)
        c.prepare_s += time.perf_counter() - t0
        return _Prepared(c.batch, c.plan, args, c.hit,
                         c.sample_s, c.prepare_s, fn)

    def prepare_sync(batch, sample_s=0.0, gi=None) -> _Prepared:
        """The three stages composed inline — the sync training path and
        the eval loop (index order holds trivially; ``gi=None`` — the eval
        loop — never snapshots)."""
        return finish_batch(resolve_batch(build_batch(batch, sample_s), gi),
                            stage=False)

    # resolve-time checkpoint snapshots keyed by global batch index,
    # awaiting their consume-time params/opt
    pending_snaps: dict[int, dict] = {}
    snap_lock = threading.Lock()

    losses, hit_history = [], []
    start_i = 0
    if cfg.resume_from:
        mgr = (ckpt if ckpt is not None
               and cfg.resume_from == cfg.checkpoint_dir
               else ckpt_mod.CheckpointManager(cfg.resume_from,
                                               keep=cfg.checkpoint_keep))
        step_no = mgr.latest_valid_step()
        if step_no is None:
            # crashed before the first checkpoint landed: a fresh run IS
            # the correct resume
            warnings.warn(f"resume_from={cfg.resume_from!r} has no valid "
                          f"checkpoint; starting fresh", stacklevel=2)
        else:
            state, _ = mgr.restore(dict(params=params, opt=opt),
                                   step=step_no)
            params, opt = state["params"], state["opt"]
            aux = mgr.load_aux(step_no)
            start_i = aux["cursor"]
            # batch i is a pure function of (seed, i): replaying the draw
            # count re-aligns the sampler streams exactly
            sampler.fast_forward(start_i)
            cache.load_state_dict(aux["cache"])
            losses = list(aux["losses"])
            hit_history = list(aux["hit_history"])
            # reseed step fns in the checkpointed first-use order so the
            # reported plans list matches the uninterrupted run (restored
            # plans re-trace lazily on first post-resume dispatch, so
            # n_traces is NOT comparable across a resume)
            for plan, sig in zip(aux["plans"], aux["sigs"]):
                sig_of_layers[plan.layers] = sig
                get_step_fn(plan)
            f_resumed.set(start_i)
            _log.info("resumed from %s at batch %d",
                      cfg.resume_from, start_i)
    n_new = max(steps - start_i, 0)
    t_sample, t_prepare, t_step, t_iter = [], [], [], []
    dropped = 0

    def recover_step(item: _Prepared, exc: BaseException):
        """Kernel quarantine with graceful degradation, on the consumer
        thread.  Attribute the failure to kernels (the harness's marker if
        present, else every Pallas-backed kernel the plan dispatches),
        quarantine them for this batch's signature in the PlanCache,
        re-select among the survivors, rebuild the batch's payloads, and
        run the degraded step — escalating if that fails too.  The all-XLA
        ``coo`` floor is never quarantined, so escalation terminates on a
        plan that runs; failures that implicate no kernel (or a fixed
        selector, which has no re-selection freedom) re-raise unchanged —
        real bugs must fail fast, not degrade."""
        nonlocal params, opt
        if fixed_names is not None:
            raise exc
        plan, batch = item.plan, item.batch
        for _ in range(len(MB_KERNELS)):
            ft.drain_effect_tokens()  # the aborted dispatch's poisoned
            failed_steps.setdefault(plan.layers, exc)  # token re-raises
            # at interpreter exit otherwise
            used = {k for layer in plan.layers for k in layer}
            named = ft.fault_kernel_from(exc)
            bad = ({named} if named is not None and named in used
                   else {k for k in used if REGISTRY.get(k).pallas})
            bad.discard("coo")
            if not bad:
                raise exc
            slack = cache.bell_slack if cfg.adapt_budget_k else None
            skel, inv_deg = skeleton_for(batch, slack)
            sig = cache.signature(skel)
            fault["quarantined"].inc(len(cache.quarantine(sig, bad)))
            dec = skel.materialize(MB_KERNELS)
            new_plan, _ = cache.plan_for(dec)
            if new_plan.layers == plan.layers:
                raise exc       # quarantine changed nothing: not a kernel
            csig = sig_of_layers.setdefault(new_plan.layers, sig)
            fixed = fix_shapes(dec, pad_budget,
                               keep=plan_payload_keys(new_plan), stats=csig)
            args = (fixed, batch.features, batch.labels,
                    batch.target_mask, inv_deg)
            fn = get_step_fn(new_plan)
            if cfg.prefetch_depth > 0:
                # dispatch the fallback the same way the consumer normally
                # would (AOT executable): later batches re-selected onto
                # this plan warm-compile in the workers, and the jit cache
                # and AOT cache are separate — mixing them here would
                # double-trace the fallback plan
                args = jax.device_put(args)
                fn = warm_compile(fn, new_plan, args)
                if isinstance(fn, _CompileFailed):
                    plan, exc = new_plan, fn.exc
                    continue
            try:
                out = fn(params, opt, *args)
                out[2].block_until_ready()
                fault["recoveries"].inc()
                tele.audit.degrade(from_layers=item.plan.layers,
                                   to_layers=new_plan.layers,
                                   error=str(exc))
                return new_plan, out
            except Exception as deeper:     # another broken kernel in the
                plan, exc = new_plan, deeper  # fallback plan: escalate
        raise exc

    def consume(i, item: _Prepared):
        nonlocal params, opt, dropped
        gi = start_i + i
        dropped += item.batch.meta.get("dropped_edges", 0)
        hit_history.append(item.hit)
        t_sample.append(item.sample_s)
        t_prepare.append(item.prepare_s)
        t0 = time.perf_counter()
        plan = item.plan
        with tracer.span("device_step", cat="device", index=gi,
                         hit=item.hit):
            if isinstance(item.step, _CompileFailed):
                plan, out = recover_step(item, item.step.exc)
            elif plan.layers in failed_steps:
                plan, out = recover_step(item, failed_steps[plan.layers])
            else:
                try:
                    with tracer.span("step.dispatch", cat="device",
                                     index=gi):
                        out = item.step(params, opt, *item.args)
                    with tracer.span("step.sync", cat="device", index=gi):
                        out[2].block_until_ready()
                except Exception as exc:
                    plan, out = recover_step(item, exc)
            params, opt, loss, finite = out
            with tracer.span("step.sync", cat="device", index=gi):
                loss_v, finite_v = float(loss), bool(finite)
        dt = time.perf_counter() - t0
        t_step.append(dt)
        # the measured side of the per-plan calibration report
        tele.audit.observe_step(plan.layers, dt)
        if not finite_v:
            fault["nonfinite_skips"].inc()
        losses.append(loss_v)
        if ckpt is not None:
            with snap_lock:
                snap = pending_snaps.pop(gi, None)
            if snap is not None:
                # consumer-time params/opt + the resolve-time cache/plan
                # snapshot = exactly the state a fresh run would hold after
                # batch gi with nothing in flight
                aux = dict(cursor=gi + 1, losses=list(losses),
                           hit_history=list(hit_history), **snap)
                ckpt.save(gi + 1, dict(params=params, opt=opt), aux=aux)
                fault["checkpoints"].inc()
        if fault_plan is not None:
            fault_plan.on_committed(gi)
        if i % 10 == 0 and _log.isEnabledFor(logging.INFO):
            cs = cache.stats
            sk = (f" skel[h={skel_cache.hits} m={skel_cache.misses}]"
                  if skel_cache is not None else "")
            bk = (f" bellK[slack={cs['bell_slack']:.2f} "
                  f"spill={cs['spill_frac']:.3f}]"
                  if "bell_slack" in cs else "")
            _log.info(f"batch {i:4d} loss {loss_v:.4f} "
                      f"cache_hit={item.hit} plan={plan.layers[0]} "
                      f"cache[h={cs['hits']} nh={cs['near_hits']} "
                      f"m={cs['misses']} ev={cs['evictions']} "
                      f"pr={cs['probes']} rate={cs['hit_rate']:.2f}]"
                      f"{sk}{bk}")

    def build_with_faults(ticket):
        """Sampler build + the harness's per-batch hooks — the unit the
        retry policy re-runs on a transient failure (injection precedes
        the skeleton build, so a retried item never double-counts the
        skeleton/plan caches)."""
        t0 = time.perf_counter()
        with tracer.span("sample", cat="host", index=ticket.index):
            batch = sampler.build(ticket)
            if fault_plan is not None:
                batch = fault_plan.on_built(ticket.index, batch)
        return build_batch(batch, time.perf_counter() - t0, ticket.index)

    pipe_stats = None
    t_loop0 = time.perf_counter()
    try:
        if cfg.prefetch_depth > 0:
            pipe = BatchPipeline(
                sampler.draw, lambda idx, ticket: build_with_faults(ticket),
                n_items=n_new,
                resolve_fn=lambda idx, c: resolve_batch(c, start_i + idx),
                finish_fn=lambda idx, c: finish_batch(c, stage=True),
                prefetch_depth=cfg.prefetch_depth,
                workers=cfg.pipeline_workers,
                name=f"{cfg.sampler}-{cfg.model}",
                retry=retry_policy, retryable=ft.default_transient,
                telemetry=tele)
            try:
                for i in range(n_new):
                    it0 = time.perf_counter()
                    consume(i, pipe.get())
                    t_iter.append(time.perf_counter() - it0)
            finally:
                pipe_stats = pipe.stats
                pipe.close()
            fault["retries"].inc(pipe_stats["retries"])
        else:
            def on_retry(attempt):
                fault["retries"].inc()

            for i in range(n_new):
                it0 = time.perf_counter()
                ticket = sampler.draw()
                if retry_policy is None:
                    c = build_with_faults(ticket)
                else:
                    c = retry_policy.run(build_with_faults, ticket,
                                         on_retry=on_retry,
                                         retryable=ft.default_transient)
                consume(i, finish_batch(resolve_batch(c, start_i + i),
                                        stage=False))
                t_iter.append(time.perf_counter() - it0)
    finally:
        if ckpt is not None:
            ckpt.wait()     # a crash-in-flight still lands the last save
    loop_s = time.perf_counter() - t_loop0
    if pipe_stats is not None:
        # device-busy share of the steady-state consumer loop: 100% = the
        # device never waited on the host (prepare fully hidden).  The
        # first iteration is excluded — it pays the initial jit compile
        # (in a worker, but the consumer has nothing to overlap it with)
        busy = float(np.sum(t_step[1:]))
        steady = float(np.sum(t_iter[1:]))
        pipe_stats.update(
            loop_seconds=loop_s,
            efficiency_pct=100.0 * busy / max(steady, 1e-12),
            # robustness counters ride the pipeline stats into bench JSON
            retries=fault["retries"].value,
            quarantined=fault["quarantined"].value,
            nonfinite_skips=fault["nonfinite_skips"].value)
        _log.info("pipeline: depth=%d workers=%d ready_mean=%.1f "
                  "wait_full=%.1fms wait_empty=%.1fms efficiency=%.0f%%",
                  pipe_stats["depth"], pipe_stats["workers"],
                  pipe_stats["ready_mean"],
                  pipe_stats["wait_full_s"] * 1e3,
                  pipe_stats["wait_empty_s"] * 1e3,
                  pipe_stats["efficiency_pct"])

    # snapshot before the eval loop below adds its own (mostly-hit)
    # lookups and step-fn seeds: the reported rate and plans list are the
    # *training* steady state
    cache_stats = dict(cache.stats)
    plans_trained = list(step_fns)

    # masked accuracy over a few fresh batches (cluster sampling cycles
    # clusters, so enough eval batches approach full-graph accuracy)
    correct = total = 0
    for _ in range(eval_batches):
        batch = sampler.sample()
        p = prepare_sync(batch)
        logits = gnn.forward(params, cfg, p.args[0],
                             jnp.asarray(batch.features), p.plan,
                             jnp.asarray(p.args[4]))
        pred = np.asarray(jnp.argmax(logits, -1))
        tm = batch.target_mask
        correct += int((pred[tm] == batch.labels[tm]).sum())
        total += int(tm.sum())

    if tele.enabled and (cfg.trace_out or cfg.telemetry_out):
        tele.export(trace_out=cfg.trace_out or None,
                    jsonl_out=cfg.telemetry_out or None)
        if cfg.trace_out:
            _log.info("wrote Chrome trace to %s", cfg.trace_out)
        if cfg.telemetry_out:
            _log.info("wrote telemetry JSONL to %s", cfg.telemetry_out)

    med = lambda ts, skip=0: float(np.median(ts[skip:])) if ts[skip:] else 0.0
    return MinibatchResult(
        losses=losses, accuracy=correct / max(total, 1),
        cache=cache_stats, hit_history=hit_history,
        plans=plans_trained,
        n_traces=counters["traces"],
        step_seconds=med(t_step, skip=min(len(t_step) - 1, 1)),
        sample_seconds=med(t_sample), prepare_seconds=med(t_prepare),
        iter_seconds=med(t_iter, skip=min(len(t_iter) - 1, 1)),
        pipeline=pipe_stats,
        dropped_edges=dropped, plan_cache=cache,
        skeleton_hits=skel_cache.hits if skel_cache else 0,
        skeleton_misses=skel_cache.misses if skel_cache else 0,
        faults=fault_view(),
        telemetry=tele.summary(), params=params)
