"""Full-batch training through ``gnn.train``.

Set-up is ``gnn.train(graph, cfg, steps=2)``: prepare (self-loops, GCN
normalization, reorder, decomposition), the configured selector, and the
AOT compile of the train step.  The benchmark then drives that compiled
step over the same decomposition, from its own weights and a fresh Adam
state, fetching the loss every step as ``gnn.train`` does.  Its first three
steps are checked against the reference; the window continues from the
fourth and runs for ``--seconds``.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, compare, flops, graphgen, reference, weights

CHECKED_STEPS = 3


def program_feed(graph, dec):
    """The step's argument tail, built as ``gnn.train`` builds it."""
    from repro.core import adaptgear
    perm = np.asarray(dec.perm)
    x = adaptgear.to_reordered(dec, jnp.asarray(graph.features))
    labels = np.zeros((dec.n_pad,), np.int32)
    labels[perm] = graph.labels
    mask = np.zeros((dec.n_pad,), bool)
    mask[perm] = True
    deg = np.bincount(graph.receivers, minlength=graph.n).astype(np.float32)
    inv_deg = np.zeros((dec.n_pad,), np.float32)
    inv_deg[perm] = 1.0 / np.maximum(deg, 1.0)
    return (dec, x, jnp.asarray(labels), jnp.asarray(mask),
            jnp.asarray(inv_deg))


def run(ctx) -> dict:
    from repro.core import gnn
    c, g = ctx.config, ctx.traffic["graph"]
    graph = graphgen.make(g, ctx.seed)
    common.note(f"graph {g['row']} x{g['scale']}: {graph.n} nodes, "
                f"{graph.n_edges} edges, {graph.features.shape[1]} features")
    cfg = gnn.GNNConfig(model=c["model"], hidden=c["hidden_channels"],
                        n_layers=c["num_layers"], lr=c["lr"],
                        dropout=c["dropout"],
                        selector=ctx.traffic["selector"],
                        seed=ctx.seed % (2 ** 31))
    res = gnn.train(graph, cfg, steps=2)
    common.note(f"plan {res.kernels}")
    common.note(f"prepare {res.preprocess_seconds:.3f}s select "
                f"{res.select_seconds:.3f}s compile "
                f"{res.compile_seconds:.3f}s")
    args = program_feed(graph, res.dec)
    step = res.step
    dims = weights.dims(graph.features.shape[1], cfg.hidden, cfg.n_layers,
                        graph.n_classes)
    params0 = weights.init(weights.key(ctx.seed), cfg.model, dims)
    params, opt = params0, gnn._adam_init(params0)

    def one(params, opt):
        with ctx.annotate("bench.step"):
            params, opt, loss = step(params, opt, *args)
        with ctx.annotate("bench.fetch_loss"):
            return params, opt, float(loss)

    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        params, opt, loss = one(params, opt)
        losses.append(loss)
        if i == 0:
            grad = jax.tree.map(lambda m: np.asarray(m) / (1 - reference.B1),
                                opt["m"])
    after = jax.tree.map(np.asarray, params)

    counter = common.CompileCounter()
    n = failed = 0
    with ctx.window():
        counter.on = True
        t0 = time.perf_counter()
        while True:
            params, opt, loss = one(params, opt)
            n += 1
            failed += not np.isfinite(loss)
            elapsed = time.perf_counter() - t0
            if elapsed >= ctx.seconds:
                break
        counter.on = False
    setup_s = t0 - ctx.t_start
    step_s = elapsed / n
    common.note(f"window {n} steps in {elapsed:.6f}s; compile requests in "
                f"the window {counter.requests} (cache hits "
                f"{counter.cache_hits}); setup {setup_s:.3f}s")
    mem = common.memory_peak_bytes()
    common.note(f"peak device memory {mem} bytes")
    work = flops.gcn_step(graph.n, graph.n_edges + graph.n,
                          graph.features.shape[1], cfg.hidden, cfg.n_layers,
                          graph.n_classes)
    info = dict(select_s=res.select_seconds, compile_s=res.compile_seconds,
                steps=n, step_s=step_s, work=work,
                window_flops=work["flops"] * n,
                edge_counts=[int(t.stats["nnz"]) for t in res.dec.subgraphs],
                n_nodes=graph.n, n_edges=graph.n_edges + graph.n)
    del res, step, args, params, opt
    gc.collect()

    prog = dict(losses=losses, grad=grad, delta=compare.delta(after, params0))
    t_ref = time.perf_counter()
    ref = reference_run(graph, params0, cfg.lr, "highest")
    checks = compare.training_checks(prog, ref)
    common.note(f"losses {losses} reference {ref['losses']}; reference "
                f"and comparison {time.perf_counter() - t_ref:.3f}s")
    return dict(e2e=dict(full_step_ms=step_s * 1e3, setup_s=setup_s),
                attempted=n, failed=failed, checks=checks,
                memory_peak_bytes=mem, info=info)


def reference_run(graph, params0, lr, precision, mask=None) -> dict:
    """The reference's first steps from ``params0`` at ``precision``
    (bench/reference.py), the loss over ``mask`` (every node by default)."""
    src, dst, norm = reference.gcn_edges(graph.n, graph.senders,
                                         graph.receivers)
    mask = np.ones((graph.n,), bool) if mask is None else mask
    feed = (jnp.asarray(graph.features), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(norm), jnp.asarray(graph.labels), jnp.asarray(mask))

    def loss_grad(p, *f):
        return reference.gcn_loss_grad(p, *f, n=graph.n,
                                         precision=precision)

    losses, grad, after = reference.train_steps(
        loss_grad, params0, [feed] * CHECKED_STEPS, lr)
    return dict(losses=losses, grad=grad,
                delta=compare.delta(after, params0))


def control(ctx) -> dict:
    """Readings that must fail: the control in the program's place, and
    the reference with half of the nodes left out of the loss; and the
    reading of the stated precision, which must pass."""
    c, g = ctx.config, ctx.traffic["graph"]
    graph = graphgen.make(g, ctx.seed)
    dims = weights.dims(graph.features.shape[1], c["hidden_channels"],
                        c["num_layers"], graph.n_classes)
    params0 = weights.init(weights.key(ctx.seed), c["model"], dims)
    ref = reference_run(graph, params0, c["lr"], "highest")
    low = reference_run(graph, params0, c["lr"], "fp8")
    half = reference_run(graph, params0, c["lr"], "highest",
                         mask=np.arange(graph.n) % 2 == 0)
    stated = reference_run(graph, params0, c["lr"], "bf16")
    return dict(control=compare.training_checks(low, ref),
                half_batch=compare.training_checks(half, ref),
                stated_precision=compare.training_checks(stated, ref))
