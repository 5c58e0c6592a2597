"""AdaptGear core: adaptive subgraph-level GNN aggregation.

Architecture (data flow, one arrow per module boundary):

  graphs.Graph
      |  core.decompose.decompose(..., inter_buckets=k)   [k=0: autotuned]
      v
  Decomposed -- an ordered list of Subgraph density tiers: the intra
      |         (block-diagonal) tier plus k inter-community buckets split
      |         by block-row occupancy.  Each Subgraph eagerly materializes
      |         one format payload per applicable kernel, built by the
      |         kernel registry (kernels.registry.REGISTRY); builders see
      |         the tier's density stats and pick per-bucket tiling (the
      |         blocked-ELL block size / feature-tile cap).  Fused kernels
      |         alias their unfused counterpart's payload — zero extra
      |         device memory.
      |  core.selector (feedback probe | analytic cost model), candidates
      |  enumerated from the registry per subgraph; on transform-first
      |  layers fused transform+aggregate kernels compete: the cost
      |  model surcharges unfused candidates their share of the shared
      |  H = X W pass, the feedback probe times it.  Every model's dense
      |  epilogue is described by a core.epilogue.EpilogueSpec (linear =
      |  GCN bias, dual = SAGE's W_self x + W_neigh agg with the mean
      |  norm baked into the edge values, mlp = GIN's 2-layer MLP whose
      |  W1 pushes through the aggregation by linearity): the spec makes
      |  GIN/SAGE transform-first too, zeroes the unfused surcharge where
      |  the epilogue's self term computes H anyway (mlp free_transform),
      |  and adds the flat dense epilogue terms to whole-layer totals
      v
  core.plan.KernelPlan -- per-layer x per-subgraph kernel names (+ the
      |  per-layer EpilogueSpecs the plan was selected under)
      |  core.adaptgear.aggregate / aggregate_transform(_dual) /
      |  core.gnn.forward
      v
  Y = sum_s A_s @ X   (or A_s @ (X W) + seed fused — the seed carries the
  epilogue self terms: GCN's bias, SAGE's X W_self, GIN's (1+eps) X W1),
  each subgraph dispatched through its registered kernel:
    * unfused matvec      -- Pallas MXU block kernels, XLA gather/segment
    * matvec_acc          -- one output buffer threads through the
                             subgraph list, Pallas kernels seed their VMEM
                             scratch from it (no per-bucket partial
                             tensors); a kernel without the hook adds its
                             partial explicitly
    * fused_matvec(_acc)  -- A_s @ (X W) in one pass: the weight stripe
                             lives in VMEM and the transform product is
                             consumed immediately; the custom VJP runs the
                             same fused form over the materialized transpose
                             payload for dX and a blocked dW reduction —
                             no (n, F) intermediate in forward or backward.
                             CSR/sell-C-sigma get per-edge gathered-
                             transform fused paths (csr_fused, sell_fused)
    * fused_dual_matvec   -- the dual-weight epilogue on the diagonal tier:
                             X W_self + A (X W_neigh) with BOTH stripes in
                             VMEM (the row block is its own source block)

Adding a kernel = one KernelSpec registration (name, kinds, format builder,
matvec / fused_matvec, cost fn) in one file — kernels/csr.py is the
template (kernels/sell_cs.py, the degree-sorted sell-C-sigma format, is a
second instance; kernels/tcgnn_tile.py, the column-condensed dense-tile
format that routes mid-density tiers through the MXU, a third);
decomposition, both selectors, dispatch, and the benchmarks pick it up
with no further edits.

Mini-batch mode (graphs too large for full-batch; repro.sampling +
train/gnn_steps.py) prepends a sampling stage and amortizes selection with
a SINGLE-PASS skeleton prepare:

  graphs.Graph
      |  sampling.sampler: ClusterSampler (community blocks = the
      |  decomposition's diagonal blocks, reusing the same orderings) or
      |  NeighborSampler (layer-wise fanouts, loss on seeds only)
      v
  SampledBatch -- fixed node/edge budgets, masked loss: every batch is one
      |           pytree shape, so the jitted step compiles once
      |  core.decompose.decompose_skeleton(reorder=False,
      |  keep_empty_buckets=True, edge_budget=...)   [ONE partition+stats
      |  pass per batch; tiers row-sorted once, payloads NOT built yet]
      v
  DecomposeSkeleton -- per-tier edge arrays + density stats (repeated
      |  cluster tuples skip even this: a small LRU keyed by the drawn
      |  tuple memoizes the skeleton, cfg.skeleton_cache_entries)
      |  sampling.plan_cache.PlanCache.lookup(skel): quantized density
      |  signature (per-tier log2-nnz + block-row occupancy) -> memoized
      |  KernelPlan, read straight off the skeleton's tier stats;
      |  cost-model selection on a miss only (materializing the full
      |  MB_KERNELS candidate set from the same skeleton); probe-on-Nth-
      |  miss (cfg.probe_every) wall-clocks the modeled frontier — top-2,
      |  widened up to cfg.probe_k_max when the modeled margin sits
      |  inside the model's own observed error band, capped at
      |  cfg.probe_budget_s wall seconds — and pins the measured winner
      |  in the cached entry.  With cfg.adapt_budget_k the cache also
      |  feeds committed capped-bell spill back into the blocked-ELL
      |  budget cap's slack factor (padding waste vs spill volume per
      |  workload; the adapted slack keys the signature)
      v
  skel.materialize(plan_payload_keys(plan)) -- tier i builds only the
      |  payloads the committed plan dispatches on tier i; the edges are
      |  never re-partitioned (the old two-pass prepare decomposed twice)
      v
  train.gnn_steps.make_sampled_step -- jit step(params, opt, dec, batch);
  fix_shapes pads COO/CSR payloads to the edge budget, scrubs per-batch
  stats, and stamps the plan's quantized signature bins (one canonical
  value per step function) so the traced Decomposed never changes
  structure (no retrace) yet stays debuggable

With cfg.prefetch_depth > 0 the whole host-side column above runs on
background threads (train.pipeline.BatchPipeline) in three stages:
cfg.pipeline_workers producers draw deterministic per-index sampler
tickets (sampler.draw / sampler.build — batch i is a pure function of
(seed, i), so the async stream is bit-identical to the sync one) and
race the heavy order-independent work (build + skeleton partition,
then fix_shapes padding + device staging + AOT pre-compile of novel
payload shapes), while every shared-cache decision in between —
PlanCache lookup/selection, spill feedback, signature seeding — runs
through an index-ordered resolve turnstile, up to prefetch_depth
batches ahead behind a bounded semaphore; the training loop is a pure
consumer dequeuing ready batches in index order, so one iteration pays
max(compute, prepare) instead of their sum.  PlanCache/SkeletonCache
are lock-protected (atomic plan_for: racing workers on one fresh
signature pay exactly one miss), and the ordered resolve stage is what
makes the cache counters, LRU/aliasing order, and hit history — not
just the batch stream — bit-identical to sync; backpressure counters
(queue-full / queue-empty waits, mean ready depth, starvation
warn-once) surface through MinibatchResult.pipeline.

Fault tolerance (repro.distributed.checkpoint + fault_tolerance, wired
into train/gnn_steps.py) layers four mechanisms over that loop without
touching the determinism contract:

  * crash-safe checkpoint/resume -- with cfg.checkpoint_dir set,
    CheckpointManager snapshots params + opt state (npz, crc32 manifest,
    atomic tmp-dir+rename, async writer) every cfg.checkpoint_every
    batches together with an aux payload: the batch cursor, loss/hit
    history, the PlanCache state_dict, and the committed plans +
    canonical signatures in step-fn order.  The cache/plan snapshot is
    captured inside the index-ordered resolve turnstile (consume-time
    cache state already holds future prefetched batches' decisions) and
    committed when the cursor batch retires, so cfg.resume_from
    fast-forwards the sampler draw stream and restarts mid-epoch
    bit-identical to the uninterrupted run -- losses, hit history,
    committed plans, cache counters.  (n_traces is the one field not
    comparable across a resume: restored plans re-trace lazily.)
  * transient-failure retry -- cfg.retry_max wraps batch build and the
    racing pipeline stages in ft.RetryPolicy: bounded exponential
    backoff, interruptible (close() cancels a sleeping retry), with
    fatal-vs-transient classification (ft.default_transient) so real
    bugs still fail fast.
  * kernel quarantine -- a Pallas compile/execute failure quarantines
    the (kernel, signature) pair in the PlanCache, purges the poisoned
    entry, and re-selects the next-best plan from the surviving
    candidate set; the XLA coo floor is never quarantined, so
    degradation always terminates.  Failed lowerings and failed plans
    are memoized, preserving traces == len(plans).
  * non-finite guard -- cfg.nonfinite_guard checks loss and grads
    inside the jitted step and no-ops the param/opt update on a
    non-finite result (the loss is still recorded; the skip is counted).

All four surface counters through MinibatchResult.faults (retries,
quarantined, recoveries, nonfinite_skips, checkpoints, resumed_at), and
ft.FaultPlan is a deterministic fault-injection harness (worker
exceptions, compile/execute kernel faults, non-finite losses, simulated
crashes at chosen batch indices) driving the fault-tolerance tests and
benchmarks/robustness.py.

Observability (repro.obs, wired through the whole column above) is one
Telemetry facade with three instruments and a hard contract:

  * span tracer -- every pipeline stage (draw -> build -> resolve ->
    finish -> device step, with the consumer's pipeline.get,
    step.dispatch and step.sync), checkpoint write, probe, and retry
    backoff opens a thread-attributed span; export is Chrome trace-event
    JSON (cfg.trace_out), one swim lane per worker thread, so the overlap
    the pipeline claims is inspectable per run.  Each span is also a
    jax.profiler annotation (repro.<name>), on the device's timeline.
  * metrics registry -- thread-safe counters/gauges/bounded histograms
    (p50/p99) that PlanCache, BatchPipeline, CheckpointManager, and the
    fault-tolerance loop publish into; the legacy dict views
    (PlanCache.stats, BatchPipeline.stats, MinibatchResult.cache /
    pipeline / faults) are assembled FROM the registry with unchanged
    keys, and the registry is always live (counters are the system of
    record even with telemetry off).
  * selector audit -- every minted plan recorded with its per-(layer,
    tier) kernel choice and modeled seconds, every probe as a
    (kernel, modeled, measured) pair, quarantine/degrade events, and
    observed per-plan step times; SelectorAudit.calibration() derives
    the per-kernel predicted-vs-measured error report surfaced through
    MinibatchResult.telemetry and exported as JSONL (cfg.telemetry_out).

Contract: telemetry is append-only and never read by selection, the
cache, or the pipeline -- enabling it leaves losses, committed plans,
hit history, and trace counts bit-identical (tests/test_obs.py); with
telemetry off (the default) call sites pay only null-object hooks,
measured by benchmarks/minibatch.py (telemetry_overhead_pct) and gated
below 2% of the per-batch prepare cost in CI.

MB_KERNELS membership rule: a kernel is admissible iff its payload has a
fixed pytree shape *at the edge budget* — every array dim a function of
(edge budget, node budget, block size), nothing data-dependent.  BlockDiag
is shape-fixed by (n_pad, B); COO/CSR pad to the budget; blocked-ELL
qualifies via its budget-padded variant: K capped at
formats.bell_budget_k(budget, n_pad, B), block payloads padded to the cap
with masked zero-blocks, overflow edges spilled to an in-payload COO tier
(aggregated by segment-sum unfused, by per-edge gathered transform fused).
tcgnn_tile qualifies the same way: its condensed-column count C — normally
the data-dependent max distinct columns per block row — is capped at
tcgnn_budget_c(budget, n_pad, B) (lane-aligned, slack-scaled mean columns
per block row under the budget), tiles and gather index padded to the cap
with masked zero slots, and edges beyond a block row's cap spilled to the
same in-payload COO tier; the budgeted triple replaces the uncapped
payload pair, whose C would retrace on every batch.  ELL stays
full-batch-only (max-degree width is data-dependent).

Online inference serving (repro.serve, driven by repro.launch.serve and
benchmarks/serving.py) is the read path over a trained model — the same
sampled column as mini-batch training, forward-only, under deadlines:

  submit(node, deadline) -> serve.admission.AdmissionController
      |  bounded FIFO, shed at submit time when the queue is full OR the
      |  EWMA-predicted wait already blows the request's deadline (a shed
      |  future resolves immediately; serving it late helps nobody)
      v
  collect() -- deadline-aware micro-batch: block for the first request,
      |  coalesce arrivals until the size target (max_batch) or until
      |  waiting longer would eat the earliest deadline's service slack,
      |  whichever first (max_wait_s caps a lone request's wait);
      |  requests whose slack no longer covers one service time expire
      |  as ``timeout`` here — *before* dispatch, never after
      v
  serve.ego.EgoNetSampler.build -- NeighborSampler.ego_ticket: the
      |  caller's deduped seed set through the sampler's pure fixed-
      |  budget build (bit-identical to training batches for the same
      |  seeds+index; a retried build reproduces its batch exactly);
      |  transient failures absorbed by ft.RetryPolicy with decorrelated
      |  jitter (seeded: deterministic per run index, decorrelated
      |  across concurrent retries)
      v
  prepare_skeleton -> PlanCache lookup/plan_for -> fix_shapes at the
      |  rung's pad budget -> AOT executable keyed (plan, shapes) —
      |  compiled at warmup, which preloads a PlanCache.save/load disk
      |  snapshot (crc-checked atomic write; corruption falls back to
      |  cold start) and AOT-warms the full (plan x rung) cross product,
      |  so a warm-started server records ZERO new traces in steady
      |  state (n_traces is the observable, gated by serve_warm_traces
      |  in CI)
      v
  logits -> per-request futures (status ok/shed/timeout/error)

Resilience invariants (tests/test_serving.py + the CI serving-smoke
job): an ADMITTED request that reaches dispatch is never dropped — a
kernel fault on its batch quarantines the implicated kernels in the
shared PlanCache and re-serves the same batch on the re-selected plan
(the coo floor terminates escalation); overload is answered by shedding
and by serve.degrade.DegradationLadder stepping the fanout rungs down
to a cheaper pre-compiled shape — hysteretic (down_after <
up_after, post-transition cooldown), so an alternating load signal
never moves the rung; load generation in benchmarks/serving.py is
open-loop (arrivals do not slow when the server does), with rates
derived from the server's own measured capacity so the overload window
overloads any machine.
"""
