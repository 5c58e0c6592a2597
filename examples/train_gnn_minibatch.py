"""Mini-batch sampled-subgraph training driver: the sampling subsystem
end-to-end (Graph -> Sampler -> SampledBatch -> per-batch decompose ->
PlanCache -> jitted step), with the plan-cache and no-retrace accounting
printed next to a full-batch reference run.

By default the async sampler->trainer pipeline is on (--prefetch 4
--workers 2): background threads sample, decompose, resolve the PlanCache,
pad, and stage batches ahead of the jitted step, so one iteration pays
~max(compute, prepare) instead of their sum; --prefetch 0 runs the
synchronous loop.

Fault tolerance: --checkpoint-dir + --checkpoint-every snapshot params,
optimizer state, the batch cursor, and the PlanCache periodically (atomic
+ crc-verified, async writer); --resume restarts from the latest valid
checkpoint bit-identically to the uninterrupted run; --retry-max absorbs
transient sampler/stage failures with backoff.  Kill the process mid-run
and rerun with --resume to see the recovery contract in action.

Telemetry (repro.obs): --trace-out writes a Chrome trace-event JSON of the
run's spans (load it in chrome://tracing or https://ui.perfetto.dev — each
pipeline worker gets its own swim lane); --telemetry-out writes the
selector audit as JSONL (per-plan kernel choices with modeled costs, probe
measurements, the cost-model calibration report, the final metrics
snapshot).  Either flag enables telemetry for the run.

  PYTHONPATH=src python examples/train_gnn_minibatch.py [--steps 100]
  PYTHONPATH=src python examples/train_gnn_minibatch.py --sampler neighbor
  PYTHONPATH=src python examples/train_gnn_minibatch.py --prefetch 0
  PYTHONPATH=src python examples/train_gnn_minibatch.py \\
      --trace-out /tmp/gnn_trace.json --telemetry-out /tmp/gnn_audit.jsonl
  PYTHONPATH=src python examples/train_gnn_minibatch.py \\
      --checkpoint-dir /tmp/gnn_ckpt --checkpoint-every 20   # then ^C ...
  PYTHONPATH=src python examples/train_gnn_minibatch.py \\
      --checkpoint-dir /tmp/gnn_ckpt --checkpoint-every 20 --resume
"""
import argparse

from repro import compile_cache
from repro.core import gnn
from repro.graphs import graph as G
from repro.obs import enable_verbose

# the driver's output goes through the namespaced repro.train logger with a
# plain stdout handler — same stream the old prints used, so piping the
# example (CI greps "loss " / "resumed at batch") keeps working
log = enable_verbose("repro.train")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dataset", default="pubmed")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--model", default="gcn", choices=["gcn", "gin", "sage"])
    ap.add_argument("--sampler", default="cluster",
                    choices=["cluster", "neighbor"])
    ap.add_argument("--clusters-per-batch", type=int, default=16)
    ap.add_argument("--batch-nodes", type=int, default=128)
    ap.add_argument("--inter-buckets", type=int, default=2)
    ap.add_argument("--probe-every", type=int, default=0,
                    help="wall-clock the top-2 cost-model candidates on "
                         "every Nth PlanCache miss and pin the winner "
                         "(0 = cost model only)")
    ap.add_argument("--prefetch", type=int, default=4,
                    help="async pipeline prefetch depth: background "
                         "workers sample/decompose/stage this many batches "
                         "ahead of the training step (0 = synchronous)")
    ap.add_argument("--workers", type=int, default=2,
                    help="background sampler/prepare threads for the "
                         "async pipeline")
    ap.add_argument("--full-batch", action="store_true",
                    help="also train full-batch for a step-time reference")
    ap.add_argument("--checkpoint-dir", default="",
                    help="directory for periodic crash-safe checkpoints "
                         "(params + opt + cursor + PlanCache state)")
    ap.add_argument("--checkpoint-every", type=int, default=25,
                    help="checkpoint every N batches (with "
                         "--checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest valid checkpoint in "
                         "--checkpoint-dir (bit-identical to the "
                         "uninterrupted run)")
    ap.add_argument("--retry-max", type=int, default=0,
                    help="retry transient batch-build/stage failures up "
                         "to N times with exponential backoff")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON of the run's "
                         "spans here (implies telemetry on)")
    ap.add_argument("--telemetry-out", default="",
                    help="write the selector-audit JSONL export here "
                         "(implies telemetry on)")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")

    graph = G.synth_dataset(args.dataset, scale=args.scale, seed=0)
    log.info("%s: %d vertices, %d edges, sampler=%s",
             args.dataset, graph.n, graph.n_edges, args.sampler)

    cfg = gnn.GNNConfig(
        model=args.model, sampler=args.sampler, reorder="louvain",
        clusters_per_batch=args.clusters_per_batch,
        batch_nodes=args.batch_nodes, inter_buckets=args.inter_buckets,
        probe_every=args.probe_every, prefetch_depth=args.prefetch,
        pipeline_workers=args.workers,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every if args.checkpoint_dir else 0,
        resume_from=args.checkpoint_dir if args.resume else "",
        retry_max=args.retry_max,
        trace_out=args.trace_out, telemetry_out=args.telemetry_out)
    res = gnn.train(graph, cfg, steps=args.steps)
    warm = min(args.steps // 4, 10)
    log.info("%s/%s: %.2f ms/step (+%.2f sample, "
             "+%.2f decompose+select+pad)",
             args.model, args.sampler, res.step_seconds * 1e3,
             res.sample_seconds * 1e3, res.prepare_seconds * 1e3)
    if res.pipeline is not None:
        p = res.pipeline
        log.info("  pipeline: %.2f ms/iter, %.0f%% device-busy "
                 "(depth=%d workers=%d ready=%.1f wait_full=%.0fms "
                 "wait_empty=%.0fms%s)",
                 res.iter_seconds * 1e3, p["efficiency_pct"], p["depth"],
                 p["workers"], p["ready_mean"], p["wait_full_s"] * 1e3,
                 p["wait_empty_s"] * 1e3, " STARVED" if p["starved"] else "")
    else:
        log.info("  sync loop: %.2f ms/iter (sample + prepare + step, "
                 "serial; --prefetch N enables the async pipeline)",
                 res.iter_seconds * 1e3)
    log.info("  plan cache: %s post-warmup hit rate %.0f%%",
             res.cache, 100 * res.hit_rate(warm))
    log.info("  jit traces: %d across %d batches (%d distinct plan(s): %s)",
             res.n_traces, args.steps, len(res.plans), res.plans)
    log.info("  loss %.4f -> %.4f, eval acc %.3f, dropped edges %d",
             res.losses[0], res.losses[-1], res.accuracy, res.dropped_edges)
    if res.faults is not None:
        f = res.faults
        resumed = (f"resumed at batch {f['resumed_at']}"
                   if f["resumed_at"] >= 0 else "fresh run")
        log.info("  fault tolerance: %s, checkpoints=%d retries=%d "
                 "quarantined=%d nonfinite_skips=%d",
                 resumed, f["checkpoints"], f["retries"],
                 f["quarantined"], f["nonfinite_skips"])
    if res.telemetry is not None and res.telemetry["enabled"]:
        t = res.telemetry
        cal = t["calibration"]
        log.info("  telemetry: %d span events, %d audit events, "
                 "%d calibrated kernel(s), %d plan(s) observed",
                 t["n_span_events"], t["n_audit_events"],
                 len(cal["kernels"]), len(cal["plans"]))
        for name, k in cal["kernels"].items():
            log.info("    %s: modeled %.3g s vs measured %.3g s "
                     "(rel err %.0f%%, n=%d)",
                     name, k["modeled_s"], k["measured_s"],
                     100 * k["rel_err"], k["n"])

    if args.full_batch:
        full = gnn.train(graph, gnn.GNNConfig(
            model=args.model, selector="cost_model", reorder="louvain",
            inter_buckets=args.inter_buckets),
            steps=max(args.steps // 4, 10))
        log.info("full-batch reference: %.2f ms/step (plan %s), acc %.3f",
                 full.step_seconds * 1e3, full.kernels[0], full.accuracy)


if __name__ == "__main__":
    main()
