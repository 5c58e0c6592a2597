#!/usr/bin/env python3
"""Chip smoke: the GNN trainer and server on one TPU, checked end to end.

One process, one chip, three phases in order, each through the entry
point a user calls:

1. full batch — ``gnn.train`` with the paper's ``feedback`` selector on
   pubmed at its Table-1 size (19,717 nodes, 500 features, 3 classes),
   GCN at the published baseline width (hidden 256, 3 layers).  The loss
   must fall, the committed plan must dispatch a Pallas kernel, the
   compiled step must hold one (``tpu_custom_call``), and the logits must
   match a plain ``jax.numpy`` segment-sum GCN on the un-reordered graph;
2. mini batch — ``gnn.train`` with the ``cluster`` sampler and the async
   pipeline (``prefetch_depth=2``), whose AOT compiles run in worker
   threads;
3. serving — ``repro.launch.serve.build_server``, ``warmup()``, then a few
   dozen requests.

Phases 2 and 3 fail on any kernel quarantine, recovery or degrade: those
keep a deployment running, but here they would time the wrong kernel.
A failed phase makes the exit code non-zero.  The last line of stdout is
one JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``.

  python3 chip_smoke.py                          # on a host with one TPU
  JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny # small CPU rehearsal

Without a TPU the script exits non-zero and prints no result; ``--tiny``
runs small sizes anywhere and always reports ``"ok": false``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# relative max error of the logits against the HIGHEST-precision reference.
# On the MXU an f32 matmul outside "highest" runs as one bf16 pass (8-bit
# mantissa, unit roundoff 2^-9): per layer each product carries ~2^-8
# relative error, random-signed over the contraction, so three layers land
# near 5e-3 of the largest logit; 2e-2 leaves 4x margin while a wrong tier,
# transpose or normalization errs at O(1).  Interpret mode on the CPU runs
# f32 throughout.
TOL_TPU = 2e-2
TOL_CPU = 1e-4


class PhaseFailed(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def check_pallas(plans, on_tpu: bool) -> None:
    """Every committed plan dispatches a Pallas kernel.  Off the chip the
    selectors rightly prefer XLA kernels to the Pallas interpreter, so
    there the finding is printed, not required."""
    from repro.kernels.registry import REGISTRY
    for layers in plans:
        hit = any(REGISTRY.get(k).pallas for layer in layers for k in layer)
        if not on_tpu:
            print(f"  plan dispatches Pallas: {hit} (required on the chip)")
        check(hit or not on_tpu, f"plan {layers} dispatches no Pallas kernel")


def reference_gcn(graph, params):
    """Plain GCN over the original node order: self-loops, symmetric
    normalization, ``segment_sum`` aggregation — no registry, no kernels."""
    import jax
    import jax.numpy as jnp
    n = graph.n
    loop = np.arange(n)
    src = np.concatenate([graph.senders, loop])
    dst = np.concatenate([graph.receivers, loop])
    d_in = np.maximum(np.bincount(dst, minlength=n), 1).astype(np.float32)
    d_out = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float32)
    norm = jnp.asarray(d_in[dst] ** -0.5 * d_out[src] ** -0.5)
    src, dst = jnp.asarray(src), jnp.asarray(dst)
    h = jnp.asarray(graph.features)
    with jax.default_matmul_precision("highest"):
        for i, layer in enumerate(params):
            hw = h @ layer["w"]
            h = jax.ops.segment_sum(hw[src] * norm[:, None], dst,
                                    num_segments=n) + layer["b"]
            if i != len(params) - 1:
                h = jax.nn.relu(h)
    return np.asarray(h)


def phase_full_batch(graph, size, on_tpu):
    import jax
    from repro.core import adaptgear, gnn
    cfg = gnn.GNNConfig(model="gcn", hidden=size["hidden"], n_layers=3,
                        selector="feedback", seed=0)
    res = gnn.train(graph, cfg, steps=size["full_steps"])
    print(f"  plan {res.kernels}")
    print(f"  preprocess {res.preprocess_seconds:.2f}s  select "
          f"{res.select_seconds:.2f}s  compile {res.compile_seconds:.2f}s  "
          f"step {res.step_seconds * 1e3:.3f} ms")
    print(f"  losses {[round(v, 5) for v in res.losses]}")
    check(all(np.isfinite(res.losses)), "non-finite loss")
    check(res.losses[-1] < res.losses[0], "loss did not fall")
    check_pallas([res.plan.layers], on_tpu)
    if on_tpu:
        check("tpu_custom_call" in res.step.as_text(),
              "compiled step holds no Pallas kernel (tpu_custom_call)")
    dec = res.dec
    fwd = jax.jit(lambda p, d, x: gnn.forward(p, cfg, d, x, res.plan))
    x = adaptgear.to_reordered(dec, jax.numpy.asarray(graph.features))
    logits = np.asarray(adaptgear.from_reordered(dec, fwd(res.params, dec, x)))
    ref = reference_gcn(graph, res.params)
    err = float(np.max(np.abs(logits - ref)))
    scale = float(np.max(np.abs(ref)))
    tol = TOL_TPU if on_tpu else TOL_CPU
    print(f"  logits vs reference: max abs err {err:.3e}, max |ref| "
          f"{scale:.3e}, relative {err / scale:.3e} (tol {tol:.0e})")
    check(err <= tol * scale, "logits disagree with the reference")


def phase_minibatch(graph, size, on_tpu):
    from repro.core import gnn
    cfg = gnn.GNNConfig(model="gcn", hidden=size["hidden"], n_layers=3,
                        sampler="cluster", prefetch_depth=2, seed=0)
    res = gnn.train(graph, cfg, steps=size["mb_steps"])
    cs = res.cache
    print(f"  plans {res.plans}")
    print(f"  cache hits {cs['hits']} near_hits {cs['near_hits']} misses "
          f"{cs['misses']}  n_traces {res.n_traces}  step "
          f"{res.step_seconds * 1e3:.3f} ms  faults {res.faults}")
    check(all(np.isfinite(res.losses)), "non-finite loss")
    check(res.faults["quarantined"] == 0, "a kernel was quarantined")
    check(res.faults["recoveries"] == 0, "a step was recovered")
    check_pallas(res.plans, on_tpu)


def phase_serving(size, on_tpu):
    from repro.launch.serve import build_server
    from repro.serve import ServeConfig
    from repro.serve.admission import OK
    server = build_server("pubmed", scale=size["scale"], train_steps=5,
                          model="gcn", hidden=size["hidden"],
                          serve_cfg=ServeConfig(deadline_s=30.0))
    warm = server.warmup()
    traces0 = server.n_traces
    print(f"  warmup {warm}")
    rng = np.random.default_rng(0)
    futs = []
    for _ in range(size["waves"]):
        # waves no larger than one micro-batch: a deeper queue would read
        # as overload and step the fanout ladder down
        wave = [server.submit(int(v))
                for v in rng.integers(0, server.ego.graph.n, 12)]
        while not all(f.done() for f in wave):
            server.step()
        futs += wave
    st = server.stats()
    plans = {p.layers for _, p, _ in server.cache.state_dict()["entries"]}
    answered = sum(f.result(0)[0] == OK for f in futs)
    print(f"  answered {answered}/{len(futs)}  plans {sorted(plans)}")
    print(f"  p50 {st['latency']['p50'] * 1e3:.3f} ms  p99 "
          f"{st['latency']['p99'] * 1e3:.3f} ms  new traces "
          f"{server.n_traces - traces0}  degrades {st['degrades']}  "
          f"quarantined {st['quarantined']}  recoveries {st['recoveries']}")
    check(answered == len(futs), "a request went unanswered")
    check(server.n_traces == traces0, "compiled after warmup")
    check(st["degrades"] == 0, "the fanout ladder degraded")
    check(st["quarantined"] == 0, "a kernel was quarantined")
    check(st["recoveries"] == 0, "a batch was recovered")
    check_pallas(sorted(plans), on_tpu)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small sizes for a CPU rehearsal (reports ok: false)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (found {dev.platform}); pass --tiny to "
              f"rehearse on the CPU", file=sys.stderr)
        return 2
    from repro import compile_cache
    from repro.graphs import graph as graph_mod
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {compile_cache.enable()}")

    size = (dict(scale=0.03, hidden=32, full_steps=3, mb_steps=4, waves=2)
            if args.tiny else
            dict(scale=1.0, hidden=256, full_steps=20, mb_steps=10, waves=4))
    t0 = time.perf_counter()
    graph = graph_mod.synth_dataset("pubmed", scale=size["scale"], seed=0)
    print(f"pubmed x{size['scale']}: {graph.n} nodes, {graph.n_edges} edges, "
          f"{graph.features.shape[1]} features, {graph.n_classes} classes "
          f"({time.perf_counter() - t0:.2f}s)")

    phases = (("full_batch", lambda: phase_full_batch(graph, size, on_tpu)),
              ("minibatch", lambda: phase_minibatch(graph, size, on_tpu)),
              ("serving", lambda: phase_serving(size, on_tpu)))
    failed = []
    for name, run in phases:
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        mem = dev.memory_stats() or {}
        peak = mem.get("peak_bytes_in_use")
        print(f"[{name}] {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.2f}s"
              + (f", peak device memory {peak / 2**30:.3f} GiB"
                 if peak else ""), flush=True)

    ok = on_tpu and not args.tiny and not failed
    print(json.dumps(dict(ok=ok, device=dict(platform=dev.platform,
                                             kind=dev.device_kind,
                                             count=len(devices)))))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
