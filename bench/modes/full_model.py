"""Full-batch training through ``gnn.train``, for a model whose weights,
plain reference and work counts live in ``bench/models/<model>.py``.

It runs as ``bench/modes/full.py`` does: set-up is ``gnn.train(graph, cfg,
steps=2)``; its compiled step then runs from the model file's weights and
a fresh Adam state; the first three steps are checked against the
reference and the window runs from the fourth.  The window fetches each
step's loss while the next step runs, as a trainer that logs every loss
without waiting on it does; the time ends when the last loss is on the
host.  The graph comes from ``bench/graphrows.py``.  ``info`` also carries
the compiled step's named scopes (``repro.obs.op_scopes``), which the
scope-reading metrics use, and the structure committed per layer.

Besides the checked steps, the same compiled step runs once from the
first weights with its loss mask narrowed to a probe of ``PROBE_NODES``
nodes drawn from the seed, and its gradient is compared with the
reference's over those nodes (``probe_grad_diff``).  Over every node, a
loss that leaves some of them out differs from the full loss only by
sampling noise, which at Table-1 sizes falls under the rounding of the
stated precision; over a few hundred nodes it does not.
"""
from __future__ import annotations

import gc
import importlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, compare, graphrows, reference
from bench.modes.full import CHECKED_STEPS, program_feed

AGG = re.compile(r"(^|[/(])agg/")
PROBE_NODES = 256


def model_file(config: dict):
    return importlib.import_module(f"bench.models.{config['model']}")


def scoped_seconds(ctx, out, keep):
    """Device seconds of the window's operations whose scope name (the
    op_name ``repro.obs.op_scopes`` gives their HLO instruction) ``keep``
    accepts; None where the trace or the scopes hold none."""
    rt, scopes = ctx.reduced_trace, out["info"].get("op_scopes")
    if rt is None or not scopes:
        return None
    s = sum(v for hlo, v in rt["op_s"].items()
            if keep(scopes.get(hlo.partition(" = ")[0].lstrip("%"), "")))
    return s or None


def is_agg(scope: str) -> bool:
    return AGG.search(scope) is not None


def probe_nodes(n: int, seed: int) -> np.ndarray:
    """The probe step's loss mask: ``PROBE_NODES`` of the ``n`` nodes,
    drawn from ``seed``."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    mask = np.zeros((n,), bool)
    mask[rng.choice(n, min(PROBE_NODES, n), replace=False)] = True
    return mask


def run(ctx) -> dict:
    from repro import obs
    from repro.core import gnn
    c, g = ctx.config, ctx.traffic["graph"]
    model = model_file(c)
    graph = graphrows.make(g, ctx.seed)
    common.note(f"graph {g['row']} x{g['scale']}: {graph.n} nodes, "
                f"{graph.n_edges} edges, {graph.features.shape[1]} features")
    cfg = gnn.GNNConfig(model=c["model"], hidden=c["hidden_channels"],
                        n_layers=c["num_layers"], lr=c["lr"],
                        dropout=c["dropout"],
                        selector=ctx.traffic["selector"],
                        seed=ctx.seed % (2 ** 31))
    res = gnn.train(graph, cfg, steps=2)
    structures = [ep.structure for ep in res.plan.epilogues]
    common.note(f"plan {res.kernels}")
    common.note(f"structures {structures}")
    common.note(f"prepare {res.preprocess_seconds:.3f}s select "
                f"{res.select_seconds:.3f}s compile "
                f"{res.compile_seconds:.3f}s")
    args = program_feed(graph, res.dec)
    step = res.step
    in_dim = graph.features.shape[1]
    params0 = model.init(ctx.seed, in_dim, cfg.hidden, cfg.n_layers,
                         graph.n_classes)
    probe = probe_nodes(graph.n, ctx.seed)
    probe_r = np.zeros((res.dec.n_pad,), bool)
    probe_r[np.asarray(res.dec.perm)[probe]] = True
    _, popt, _ = step(params0, gnn._adam_init(params0), *args[:3],
                      jnp.asarray(probe_r), *args[4:])
    probe_grad = first_grad(popt)
    params, opt = params0, gnn._adam_init(params0)

    def dispatch(params, opt):
        with ctx.annotate("bench.step"):
            return step(params, opt, *args)

    def fetch(loss):
        with ctx.annotate("bench.fetch_loss"):
            return float(loss)

    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        params, opt, loss = dispatch(params, opt)
        losses.append(fetch(loss))
        if i == 0:
            grad = first_grad(opt)
    after = jax.tree.map(np.asarray, params)

    counter = common.CompileCounter()
    n = failed = 0
    with ctx.window():
        counter.on = True
        t0 = time.perf_counter()
        pending = None
        while True:
            params, opt, loss = dispatch(params, opt)
            if pending is not None:
                failed += not np.isfinite(fetch(pending))
            pending = loss
            n += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        failed += not np.isfinite(fetch(pending))
        elapsed = time.perf_counter() - t0
        counter.on = False
    setup_s = t0 - ctx.t_start
    step_s = elapsed / n
    common.note(f"window {n} steps in {elapsed:.6f}s; compile requests in "
                f"the window {counter.requests} (cache hits "
                f"{counter.cache_hits}); setup {setup_s:.3f}s")
    mem = common.memory_peak_bytes()
    common.note(f"peak device memory {mem} bytes")
    work = model.step_work(graph.n, graph.n_edges, in_dim, cfg.hidden,
                           cfg.n_layers, graph.n_classes, structures)
    info = dict(select_s=res.select_seconds, compile_s=res.compile_seconds,
                steps=n, step_s=step_s, work=work,
                window_flops=work["flops"] * n,
                edge_counts=[int(t.stats["nnz"]) for t in res.dec.subgraphs],
                n_nodes=graph.n, n_edges=graph.n_edges,
                op_scopes=obs.op_scopes(step), structures=structures)
    del res, step, args, params, opt
    gc.collect()

    prog = dict(losses=losses, grad=grad, delta=compare.delta(after, params0),
                probe_grad=probe_grad)
    t_ref = time.perf_counter()
    ref = reference_run(model, graph, params0, cfg.lr, "highest",
                        probe=probe)
    checks = training_checks(prog, ref)
    common.note(f"losses {losses} reference {ref['losses']}; reference "
                f"and comparison {time.perf_counter() - t_ref:.3f}s")
    return dict(e2e=dict(full_step_ms=step_s * 1e3, setup_s=setup_s),
                attempted=n, failed=failed, checks=checks,
                memory_peak_bytes=mem, info=info)


def first_grad(opt) -> list:
    """The first step's gradient as Adam's first moment holds it."""
    return jax.tree.map(lambda m: np.asarray(m) / (1 - reference.B1),
                        opt["m"])


def training_checks(prog: dict, ref: dict) -> dict:
    """``bench/compare.py``'s numbers, and the probe step's gradient
    against the reference's over the same nodes."""
    return dict(compare.training_checks(prog, ref),
                probe_grad_diff=compare.diff_gap(prog["probe_grad"],
                                                 ref["probe_grad"]))


def reference_run(model, graph, params0, lr, precision, probe, keep=None,
                  frozen=()) -> dict:
    """The model file's reference at ``precision`` from ``params0``: the
    first steps with the loss over the nodes ``keep`` (every node by
    default), and the first gradient with the loss over the ``probe``
    nodes among them.  Leaves named in ``frozen`` get no gradient (a
    planted fault)."""
    keep = np.ones((graph.n,), bool) if keep is None else keep

    def feed(mask):
        return (jnp.asarray(graph.features), jnp.asarray(graph.senders),
                jnp.asarray(graph.receivers), jnp.asarray(graph.labels),
                jnp.asarray(mask))

    def loss_grad(p, *f):
        loss, grad = model.loss_grad(p, *f, n=graph.n, precision=precision)
        return loss, jax.tree_util.tree_map_with_path(
            lambda path, g: (jnp.zeros_like(g)
                             if getattr(path[-1], "key", None) in frozen
                             else g), grad)

    losses, grad, after = reference.train_steps(
        loss_grad, params0, [feed(keep)] * CHECKED_STEPS, lr)
    probe_grad = reference.train_steps(
        loss_grad, params0, [feed(keep & probe)], lr)[1]
    return dict(losses=losses, grad=grad, probe_grad=probe_grad,
                delta=compare.delta(after, params0))


def control(ctx) -> dict:
    """Readings that must fail: the control (float8 products) in the
    program's place, the reference with half of the nodes left out of the
    loss, and the reference with every layer's eps frozen; and the reading
    of the stated precision, which must pass."""
    c, g = ctx.config, ctx.traffic["graph"]
    model = model_file(c)
    graph = graphrows.make(g, ctx.seed)
    params0 = model.init(ctx.seed, graph.features.shape[1],
                         c["hidden_channels"], c["num_layers"],
                         graph.n_classes)
    probe = probe_nodes(graph.n, ctx.seed)

    def variant(precision, **fault):
        return reference_run(model, graph, params0, c["lr"], precision,
                             probe, **fault)

    ref = variant("highest")
    return dict(
        control=training_checks(variant("fp8"), ref),
        half_batch=training_checks(
            variant("highest", keep=np.arange(graph.n) % 2 == 0), ref),
        eps_frozen=training_checks(variant("highest", frozen=("eps",)), ref),
        stated_precision=training_checks(variant("bf16"), ref))
