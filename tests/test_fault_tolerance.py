"""Fault-tolerant GNN training (distributed/{checkpoint,fault_tolerance}
wired into train_minibatch): crash-safe checkpoint/resume bit-identical to
the uninterrupted run, transient-failure retry in the pipeline, kernel
quarantine with graceful degradation to the XLA floor, the non-finite
loss/grad guard, and the deterministic FaultPlan injection harness that
drives all of it."""
import dataclasses
import pickle
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core import gnn
from repro.distributed import fault_tolerance as ft
from repro.graphs import graph as G  # noqa: F401  (re-exported helpers)
from repro.train import gnn_steps
from repro.train.pipeline import BatchPipeline

from test_pipeline import small_graph, pipeline_threads
from test_sampling import dense_community_graph


def base_cfg(**kw):
    d = dict(model="gcn", n_layers=2, hidden=8, comm_size=8,
             sampler="cluster", clusters_per_batch=2,
             selector="cost_model", seed=7)
    d.update(kw)
    return gnn.GNNConfig(**d)


def bell_cfg(**kw):
    """Dense-community config whose cost model commits the Pallas bell
    kernel — the quarantine target."""
    d = dict(model="gin", sampler="cluster", comm_size=64,
             clusters_per_batch=2, reorder="bfs", inter_buckets=2)
    d.update(kw)
    return gnn.GNNConfig(**d)


def run_result_equal(a, b):
    assert a.losses == b.losses
    assert a.hit_history == b.hit_history
    assert a.plans == b.plans


# -- crash-safe checkpoint / resume ------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 3], ids=["sync", "async"])
def test_crash_resume_bit_identical(prefetch):
    """Inject a crash mid-epoch, resume from the checkpoint directory, and
    demand the full loss curve, hit history, committed plans, and cache
    counters match the uninterrupted run exactly — the ISSUE 7 acceptance
    bar.  (n_traces is NOT compared: restored plans re-trace lazily on the
    resumed side.)"""
    g = small_graph(n=160, e=1400)
    cfg = base_cfg(prefetch_depth=prefetch,
                   pipeline_workers=2 if prefetch else 0)
    ref = gnn_steps.train_minibatch(g, cfg, steps=10, eval_batches=2)
    with tempfile.TemporaryDirectory() as d:
        ck = dataclasses.replace(cfg, checkpoint_dir=d, checkpoint_every=3)
        fp = ft.FaultPlan(crash_at=7)
        with pytest.raises(ft.SimulatedCrash):
            gnn_steps.train_minibatch(g, ck, steps=10, eval_batches=0,
                                      fault_plan=fp)
        assert not pipeline_threads()   # the crash didn't leak workers
        res = gnn_steps.train_minibatch(
            g, dataclasses.replace(ck, resume_from=d), steps=10,
            eval_batches=2)
    # crash at batch 7 -> last snapshot is the one after batch 6 % 3 == 0
    assert res.faults["resumed_at"] == 6
    run_result_equal(res, ref)
    assert res.cache["hits"] == ref.cache["hits"]
    assert res.cache["misses"] == ref.cache["misses"]
    assert res.cache["near_hits"] == ref.cache["near_hits"]
    assert res.accuracy == ref.accuracy


def test_resume_at_checkpoint_free_index_replays_everything():
    # crash before the first checkpoint lands: resume warns and replays
    # from scratch — which IS the bit-identical resume for that cursor
    g = small_graph()
    cfg = base_cfg()
    ref = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1)
    with tempfile.TemporaryDirectory() as d:
        ck = dataclasses.replace(cfg, checkpoint_dir=d, checkpoint_every=4,
                                 resume_from=d)
        fp = ft.FaultPlan(crash_at=2)
        with pytest.raises(ft.SimulatedCrash):
            gnn_steps.train_minibatch(g, dataclasses.replace(
                ck, resume_from=""), steps=6, eval_batches=0, fault_plan=fp)
        with pytest.warns(UserWarning, match="no valid checkpoint"):
            res = gnn_steps.train_minibatch(g, ck, steps=6, eval_batches=1)
    assert res.faults["resumed_at"] == -1
    run_result_equal(res, ref)


def test_checkpoint_counters_and_cursor():
    g = small_graph()
    with tempfile.TemporaryDirectory() as d:
        cfg = base_cfg(checkpoint_dir=d, checkpoint_every=2)
        res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=0)
        assert res.faults["checkpoints"] == 3    # after batches 1, 3, 5
        from repro.distributed import checkpoint as ckpt_mod
        mgr = ckpt_mod.CheckpointManager(d)
        assert mgr.latest_valid_step() == 6
        aux = mgr.load_aux()
        assert aux["cursor"] == 6
        assert aux["losses"] == res.losses
        assert aux["hit_history"] == res.hit_history
        assert [p.layers for p in aux["plans"]] == res.plans


# -- transient retry ----------------------------------------------------------

def test_transient_worker_faults_retried_bit_identically():
    """Two injected transient faults on one batch: the pipeline absorbs
    them with backoff and the training outcome is indistinguishable from
    the fault-free run (injection precedes the skeleton build, so caches
    never see the aborted attempts)."""
    g = small_graph(n=160, e=1400)
    cfg = base_cfg(prefetch_depth=3, pipeline_workers=2)
    ref = gnn_steps.train_minibatch(g, cfg, steps=8, eval_batches=1)
    fcfg = dataclasses.replace(cfg, retry_max=3, retry_base_delay_s=0.0)
    fp = ft.FaultPlan(worker_faults={2: 2})
    res = gnn_steps.train_minibatch(g, fcfg, steps=8, eval_batches=1,
                                    fault_plan=fp)
    assert res.faults["retries"] == 2
    assert fp.injected_worker == 2
    assert res.pipeline["retries"] == 2      # surfaced for bench JSON
    run_result_equal(res, ref)


def test_retries_exhausted_propagates_the_fault():
    g = small_graph()
    cfg = base_cfg(prefetch_depth=2, pipeline_workers=2, retry_max=2,
                   retry_base_delay_s=0.0)
    fp = ft.FaultPlan(worker_faults={1: 5})  # more faults than retries
    with pytest.raises(ft.InjectedWorkerFault):
        gnn_steps.train_minibatch(g, cfg, steps=4, eval_batches=0,
                                  fault_plan=fp)
    assert not pipeline_threads()


def test_fatal_fault_fails_fast_despite_retry_budget():
    g = small_graph()
    cfg = base_cfg(prefetch_depth=2, pipeline_workers=2, retry_max=5,
                   retry_base_delay_s=10.0)   # a retry would hang the test
    fp = ft.FaultPlan(fatal_at={1})
    t0 = time.perf_counter()
    with pytest.raises(ValueError, match="fatal"):
        gnn_steps.train_minibatch(g, cfg, steps=4, eval_batches=0,
                                  fault_plan=fp)
    assert time.perf_counter() - t0 < 5.0    # no backoff ladder was paid
    assert fp.injected_fatal == 1
    assert not pipeline_threads()


def test_sync_path_retries_too():
    g = small_graph()
    cfg = base_cfg(retry_max=3, retry_base_delay_s=0.0)
    ref = gnn_steps.train_minibatch(g, base_cfg(), steps=6, eval_batches=1)
    fp = ft.FaultPlan(worker_faults={0: 1, 3: 1})
    res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1,
                                    fault_plan=fp)
    assert res.faults["retries"] == 2
    run_result_equal(res, ref)


def test_shutdown_under_retry_joins_promptly():
    """close() mid-backoff must interrupt the retry ladder, not sleep it
    out: the cancel event doubles as the backoff timer."""
    def work(idx, ticket):
        raise ft.TransientError(f"flaky {idx}")

    counter = iter(range(100))
    pipe = BatchPipeline(lambda: next(counter), work, n_items=8,
                         prefetch_depth=2, workers=2,
                         retry=ft.RetryPolicy(max_retries=50,
                                              base_delay_s=30.0),
                         retryable=ft.default_transient)
    time.sleep(0.1)          # let workers enter their first backoff
    t0 = time.perf_counter()
    pipe.close()
    assert time.perf_counter() - t0 < 5.0
    assert not pipeline_threads()


# -- kernel quarantine --------------------------------------------------------

@pytest.mark.parametrize("mode", ["compile", "execute"])
def test_kernel_fault_quarantines_and_degrades(mode):
    """A Pallas kernel that fails to compile (or execute) is quarantined
    for its signature and the cache re-selects next-best; training
    completes, every loss is finite, and the no-retrace contract holds —
    the failed plan's single trace is memoized, never repeated."""
    g = dense_community_graph()
    cfg = bell_cfg()
    ref = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=0)
    used = {k for plan in ref.plans for layer in plan for k in layer}
    assert "bell" in used                    # the fault target is selected
    fp = ft.FaultPlan(kernel_faults={"bell": mode})
    with fp.activate():
        res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1,
                                        fault_plan=fp)
    assert fp.kernel_trips >= 1
    assert res.faults["quarantined"] >= 1
    assert res.faults["recoveries"] >= 1
    assert res.cache["quarantined"] >= 1
    assert len(res.losses) == 6 and np.isfinite(res.losses).all()
    assert res.n_traces == len(res.plans)
    # post-recovery batches never dispatch the broken kernel again
    later = {k for plan in res.plans[1:] for layer in plan for k in layer}
    assert "bell" not in later


def test_kernel_fault_async_pipeline_degrades():
    g = dense_community_graph()
    cfg = bell_cfg(prefetch_depth=3, pipeline_workers=2)
    fp = ft.FaultPlan(kernel_faults={"bell": "compile"})
    with fp.activate():
        res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=1,
                                        fault_plan=fp)
    assert res.faults["recoveries"] >= 1
    assert len(res.losses) == 6 and np.isfinite(res.losses).all()
    assert res.n_traces == len(res.plans)
    assert res.pipeline["quarantined"] == res.faults["quarantined"]
    assert not pipeline_threads()


def test_unattributable_failure_reraises():
    """Failures that implicate no Pallas kernel must NOT degrade — real
    bugs fail fast.  A fault injected into ``bell``, which the config's
    plans (csr_fused on the sparse small graph) never dispatch, never
    trips, and a synthetic non-kernel error in the step propagates."""
    g = small_graph()
    cfg = base_cfg()
    ref = gnn_steps.train_minibatch(g, cfg, steps=4, eval_batches=0)
    used = {k for plan in ref.plans for layer in plan for k in layer}
    assert all(not k.startswith(("block_diag", "bell")) for k in used)
    fp = ft.FaultPlan(kernel_faults={"bell": "compile"})
    with fp.activate():   # patched but never dispatched -> no-op
        res = gnn_steps.train_minibatch(g, cfg, steps=4, eval_batches=0,
                                        fault_plan=fp)
    assert res.faults["quarantined"] == 0
    assert res.losses == ref.losses


# -- PlanCache quarantine bookkeeping ----------------------------------------

def test_plan_cache_quarantine_purges_and_excludes():
    g = dense_community_graph()
    res = gnn_steps.train_minibatch(g, bell_cfg(), steps=6, eval_batches=0)
    cache = res.plan_cache
    sd = cache.state_dict()
    assert sd["entries"], "training should have cached at least one plan"
    sig, plan, _anchor = sd["entries"][0]
    used = {k for layer in plan.layers for k in layer}
    assert "bell" in used
    n_before = len(sd["entries"])
    fresh = cache.quarantine(sig, {"bell", "coo"})
    assert fresh == {"bell"}                 # the XLA floor is untouchable
    assert cache.quarantined_for(sig) == {"bell"}
    assert len(cache.state_dict()["entries"]) == n_before - 1  # purged
    assert cache.quarantine(sig, {"bell"}) == set()   # idempotent
    assert cache.stats["quarantined"] == 1


def test_plan_cache_state_dict_roundtrip_is_stable():
    g = small_graph(n=160, e=1400)
    res = gnn_steps.train_minibatch(g, base_cfg(), steps=8, eval_batches=0)
    cache = res.plan_cache
    sd1 = cache.state_dict()
    blob = pickle.dumps(sd1)                 # must survive the aux pickle
    cache.load_state_dict(pickle.loads(blob))
    sd2 = cache.state_dict()
    assert sd1 == sd2
    assert cache.stats["hits"] == res.cache["hits"]


# -- non-finite guard ---------------------------------------------------------

def test_nonfinite_guard_skips_and_counts():
    """A NaN batch contributes a NaN loss sample but no parameter update:
    training after the poisoned batch continues from the pre-batch params
    and every later loss is finite."""
    g = small_graph()
    cfg = base_cfg()
    ref = gnn_steps.train_minibatch(g, cfg, steps=8, eval_batches=1)
    fp = ft.FaultPlan(nonfinite_at=[3])
    res = gnn_steps.train_minibatch(g, cfg, steps=8, eval_batches=1,
                                    fault_plan=fp)
    assert fp.injected_nonfinite == 1
    assert res.faults["nonfinite_skips"] == 1
    assert res.losses[:3] == ref.losses[:3]
    assert not np.isfinite(res.losses[3])
    assert np.isfinite(res.losses[4:]).all()


def test_nonfinite_without_guard_poisons_params():
    g = small_graph()
    cfg = base_cfg(nonfinite_guard=False)
    fp = ft.FaultPlan(nonfinite_at=[2])
    res = gnn_steps.train_minibatch(g, cfg, steps=6, eval_batches=0,
                                    fault_plan=fp)
    assert res.faults["nonfinite_skips"] == 0
    # NaN grads flowed into Adam: everything after the hit is NaN
    assert not np.isfinite(res.losses[2:]).any()


# -- FaultPlan harness --------------------------------------------------------

def test_fault_plan_is_reusable_state_machine():
    fp = ft.FaultPlan(worker_faults={4: 2}, nonfinite_at=[1])
    batch = None
    with pytest.raises(ft.InjectedWorkerFault):
        fp.on_built(4, batch)
    with pytest.raises(ft.InjectedWorkerFault):
        fp.on_built(4, batch)
    assert fp.on_built(4, batch) is batch    # budget spent -> clean
    assert fp.injected_worker == 2
    fp.on_committed(3)                       # no crash configured
    assert fp.injected_fatal == 0


def test_fault_kernel_attribution_walks_cause_chain():
    inner = ft.KernelFault("__fault_kernel__:bell injected")
    try:
        try:
            raise inner
        except ft.KernelFault as k:
            raise RuntimeError("jit wrapped") from k
    except RuntimeError as outer:
        assert ft.fault_kernel_from(outer) == "bell"
    assert ft.fault_kernel_from(RuntimeError("unrelated")) is None
