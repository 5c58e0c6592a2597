"""Mini-batch neighbour-sampled training through ``train_minibatch``.

One call of ``train_minibatch`` with the async pipeline is the object under
test: its sampler, PlanCache, compiled steps and state.  The benchmark gives
it its own weights (``gnn.init_model`` answers with them during the call)
and taps two of its seams without changing what they do: the sampler's
``build`` (to keep each batch's real sizes, and the first batches for the
reference) and the compiled step (to keep the first step's optimizer state
and the parameters after the checked steps, and to read the clock).  The
first three steps are set-up and are checked against the reference; the
window runs from the end of the third step to the end of the last, for
``round(--seconds / nominal_step_s)`` steps.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, compare, flops, graphgen, reference, weights

CHECKED_STEPS = 3


class SamplerTap:
    """The program's sampler, with ``build`` recording what it built."""

    def __init__(self, inner, ctx, keep: int):
        self._inner, self._ctx, self._keep = inner, ctx, keep
        self.sizes: dict[int, tuple] = {}
        self.kept: dict[int, object] = {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def build(self, ticket):
        with self._ctx.annotate("bench.sample"):
            batch = self._inner.build(ticket)
        self.sizes[ticket.index] = (batch.n_real_nodes, batch.n_real_edges)
        if ticket.index < self._keep:
            self.kept[ticket.index] = batch
        return batch

    def sample(self):
        return self.build(self.draw())


class StepTap:
    """Every compiled step the call dispatches goes through :meth:`call`."""

    def __init__(self, ctx, n_steps: int, on_window_start, on_window_end):
        self.ctx, self.n_steps = ctx, n_steps
        self.on_start, self.on_end = on_window_start, on_window_end
        self.calls = 0
        self.grad = self.after = None
        self.t0 = self.t1 = None

    def call(self, fn, args):
        i = self.calls
        self.calls += 1
        with self.ctx.annotate("bench.device_step"):
            out = fn(*args)
        if i == 0:
            self.grad = jax.tree.map(
                lambda m: np.asarray(m) / (1 - reference.B1), out[1]["m"])
        if i == CHECKED_STEPS - 1 or i == self.n_steps - 1:
            jax.block_until_ready(out[2])
            now = time.perf_counter()
            if i == CHECKED_STEPS - 1:
                self.after = jax.tree.map(np.asarray, out[0])
                self.t0 = now
                self.on_start()
            else:
                self.t1 = now
                self.on_end()
        return out

    def wrap(self, jitted):
        tap = self

        class Exe:
            def __init__(self, exe):
                self.exe = exe

            def __call__(self, *args):
                return tap.call(self.exe, args)

        class Lowered:
            def __init__(self, low):
                self.low = low

            def compile(self):
                return Exe(self.low.compile())

        class Jitted:
            def lower(self, *a, **k):
                return Lowered(jitted.lower(*a, **k))

            def __call__(self, *args):
                return tap.call(jitted, args)

        return Jitted()


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def make_cfg(ctx):
    from repro.core import gnn
    c, t = ctx.config, ctx.traffic
    return gnn.GNNConfig(
        model=c["model"], hidden=c["hidden"], n_layers=c["num_layers"],
        lr=c["lr"], dropout=c["dropout"], sampler=t["sampler"],
        fanouts=tuple(c["fanouts"]),
        batch_nodes=t.get("batch_nodes", c["batch_size"]),
        prefetch_depth=t.get("prefetch_depth", 0),
        pipeline_workers=t.get("pipeline_workers", 2),
        seed=ctx.seed % (2 ** 31))


def make_graph(ctx):
    g = ctx.traffic["graph"]
    graph = graphgen.make(g, ctx.seed)
    common.note(f"graph {g['row']} x{g['scale']}: {graph.n} nodes, "
                f"{graph.n_edges} edges, {graph.features.shape[1]} features")
    return graph


def bench_params(ctx, graph, cfg):
    dims = weights.dims(graph.features.shape[1], cfg.hidden, cfg.n_layers,
                        graph.n_classes)
    return weights.init(weights.key(ctx.seed), cfg.model, dims)


def run(ctx) -> dict:
    from repro.core import gnn
    from repro.obs import Telemetry
    from repro.train import gnn_steps
    graph = make_graph(ctx)
    cfg = make_cfg(ctx)
    params0 = bench_params(ctx, graph, cfg)
    window_steps = max(8, round(ctx.seconds / ctx.traffic["nominal_step_s"]))
    n_steps = CHECKED_STEPS + window_steps
    tele = Telemetry(enabled=ctx.trace)
    wait = tele.metrics.counter("pipeline.wait_empty_s")
    counter = common.CompileCounter()
    marks = {}
    window = ctx.window()

    def start():
        marks["wait0"] = wait.value
        counter.on = True
        window.__enter__()

    def end():
        window.__exit__(None, None, None)
        counter.on = False
        marks["wait1"] = wait.value

    taps = {}

    def make_sampler(*a, **k):
        taps["sampler"] = SamplerTap(make_sampler.inner(*a, **k), ctx,
                                     CHECKED_STEPS)
        return taps["sampler"]

    make_sampler.inner = gnn_steps.make_sampler
    step_tap = StepTap(ctx, n_steps, start, end)
    make_step = gnn_steps.make_sampled_step
    with patched(gnn, "init_model", lambda *a, **k: params0), \
            patched(gnn_steps, "make_sampler", make_sampler), \
            patched(gnn_steps, "make_sampled_step",
                    lambda *a, **k: step_tap.wrap(make_step(*a, **k))):
        res = gnn_steps.train_minibatch(graph, cfg, steps=n_steps,
                                        eval_batches=0, telemetry=tele)
    sampler = taps["sampler"]
    elapsed = step_tap.t1 - step_tap.t0
    setup_s = step_tap.t0 - ctx.t_start
    step_s = elapsed / window_steps
    faults = res.faults
    failed = (faults["nonfinite_skips"] + faults["quarantined"]
              + faults["recoveries"])
    common.note(f"plans {res.plans}")
    common.note(f"cache {res.cache} n_traces {res.n_traces}")
    common.note(f"window {window_steps} steps in {elapsed:.6f}s; compile "
                f"requests in the window {counter.requests} (cache hits "
                f"{counter.cache_hits}); setup {setup_s:.3f}s; faults "
                f"{faults}")
    mem = common.memory_peak_bytes()
    common.note(f"peak device memory {mem} bytes")
    in_dim = graph.features.shape[1]
    window_idx = range(CHECKED_STEPS, n_steps)
    window_flops = sum(
        flops.sage_step(*sampler.sizes[i], in_dim, cfg.hidden, cfg.n_layers,
                        graph.n_classes)["flops"] for i in window_idx)
    spans = [e for e in tele.tracer.events()
             if e[0] in ("sample", "build", "resolve", "finish")
             and e[5] is not None and step_tap.t0 <= e[4] <= step_tap.t1]
    info = dict(steps=window_steps, step_s=step_s,
                window_flops=window_flops,
                host_prepare_s=sum(e[5] - e[4] for e in spans),
                wait_empty_s=marks["wait1"] - marks["wait0"])
    prog = dict(losses=res.losses[:CHECKED_STEPS], grad=step_tap.grad,
                delta=compare.delta(step_tap.after, params0))
    kept = [sampler.kept[i] for i in range(CHECKED_STEPS)]
    del res, step_tap, taps
    gc.collect()

    t_ref = time.perf_counter()
    checks = dict(sample_faults=float(sample_faults(
        graph, kept, cfg.fanouts, cfg.batch_nodes)))
    ref = reference_run(graph, kept, params0, cfg.lr, "highest")
    checks.update(compare.training_checks(prog, ref))
    common.note(f"losses {prog['losses']} reference {ref['losses']}; "
                f"reference and comparison "
                f"{time.perf_counter() - t_ref:.3f}s")
    return dict(e2e=dict(mb_step_ms=step_s * 1e3, setup_s=setup_s),
                attempted=window_steps, failed=failed, checks=checks,
                memory_peak_bytes=mem, info=info)


def edge_keys(graph) -> np.ndarray:
    return np.sort(graph.senders.astype(np.int64) * graph.n + graph.receivers)


def sample_faults(graph, batches, fanouts, batch_size) -> int:
    """What a batch breaks of the sampling the configuration states, as a
    count that must be 0: each batch holds ``batch_size`` distinct loss
    rows on real nodes; its real nodes are distinct and are the seeds and
    the nodes its hops reached; each edge is a distinct edge of the graph;
    and hop ``k`` gives each node it expands (the seeds, then the nodes
    the hop before reached first) ``min(in-degree, fanouts[k])`` in-edges,
    and no other node has any."""
    keys = edge_keys(graph)
    indeg = np.bincount(graph.receivers, minlength=graph.n)
    bad = 0
    for b in batches:
        real = b.nodes[b.node_mask].astype(np.int64)
        bad += len(real) - len(np.unique(real)) + int(np.sum(real < 0))
        bad += int(np.sum(b.target_mask & ~b.node_mask))
        seeds = np.unique(b.nodes[b.target_mask & b.node_mask])
        bad += abs(len(seeds) - batch_size)
        m = b.edge_mask
        s = b.nodes[b.senders[m]].astype(np.int64)
        d = b.nodes[b.receivers[m]].astype(np.int64)
        ok = (s >= 0) & (d >= 0)
        bad += int(np.sum(~ok))
        s, d = s[ok], d[ok]
        k = s * graph.n + d
        pos = np.clip(np.searchsorted(keys, k), 0, len(keys) - 1)
        bad += int(np.sum(keys[pos] != k)) + len(k) - len(np.unique(k))
        want = np.zeros(graph.n, np.int64)
        seen = np.zeros(graph.n, bool)
        seen[seeds] = True
        frontier = seeds
        for f in fanouts:
            want[frontier] = np.minimum(indeg[frontier], f)
            reached = np.unique(s[np.isin(d, frontier)])
            frontier = reached[~seen[reached]]
            seen[frontier] = True
        bad += int(np.sum(np.bincount(d, minlength=graph.n) != want))
        present = np.zeros(graph.n, bool)
        present[real[real >= 0]] = True
        bad += int(np.sum(present != seen))
    return bad


def reference_feed(graph, b, mask=None):
    """A sampled batch as the reference reads it: its node ids and edges,
    with features and labels taken from the benchmark's own graph."""
    real = b.nodes >= 0
    idx = np.where(real, b.nodes, 0)
    x = np.where(real[:, None], graph.features[idx], 0).astype(np.float32)
    labels = np.where(real, graph.labels[idx], 0).astype(np.int32)
    tmask = b.target_mask if mask is None else b.target_mask & mask
    return (jnp.asarray(x), jnp.asarray(b.senders), jnp.asarray(b.receivers),
            jnp.asarray(b.edge_mask), jnp.asarray(labels), jnp.asarray(tmask))


def reference_run(graph, batches, params0, lr, precision, mask=None) -> dict:
    feeds = [reference_feed(graph, b, None if mask is None else mask(b))
             for b in batches]

    def loss_grad(p, *f):
        return reference.sage_loss_grad(p, *f, precision=precision)

    losses, grad, after = reference.train_steps(loss_grad, params0, feeds, lr)
    return dict(losses=losses, grad=grad,
                delta=compare.delta(after, params0))


def control(ctx) -> dict:
    """Readings that must fail, on the first batches the program's sampler
    draws for this seed: the control in the program's place, and the
    reference with half of each batch's seeds left out of the loss; and the
    reading of the stated precision, which must pass."""
    from repro.train import gnn_steps
    graph = make_graph(ctx)
    cfg = make_cfg(ctx)
    params0 = bench_params(ctx, graph, cfg)
    sampler = gnn_steps.make_sampler(graph, cfg)
    batches = [sampler.sample() for _ in range(CHECKED_STEPS)]
    ref = reference_run(graph, batches, params0, cfg.lr, "highest")
    low = reference_run(graph, batches, params0, cfg.lr, "fp8")
    half = reference_run(graph, batches, params0, cfg.lr, "highest",
                         mask=lambda b: np.arange(b.n) % 2 == 0)
    stated = reference_run(graph, batches, params0, cfg.lr, "bf16")
    return dict(control=compare.training_checks(low, ref),
                half_batch=compare.training_checks(half, ref),
                stated_precision=compare.training_checks(stated, ref))
