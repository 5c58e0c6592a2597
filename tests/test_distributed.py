"""Checkpointing and fault tolerance: the crash-safe resume and quarantine
support under the mini-batch loop and the server."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.distributed import checkpoint as ckpt_mod
from repro.distributed import fault_tolerance as ft


# -- checkpointing -------------------------------------------------------------

def tree():
    return dict(a=jnp.arange(12.0).reshape(3, 4),
                nested=dict(b=jnp.ones((5,), jnp.int32)))


def test_checkpoint_roundtrip(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    t = tree()
    mgr.save(3, t, blocking=True)
    restored, step = mgr.restore(t)
    assert step == 3
    np.testing.assert_array_equal(restored["a"], t["a"])
    np.testing.assert_array_equal(restored["nested"]["b"], t["nested"]["b"])


def test_checkpoint_corruption_falls_back(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    t = tree()
    mgr.save(1, t, blocking=True)
    mgr.save(2, jax.tree.map(lambda x: x + 1, t), blocking=True)
    # corrupt step 2
    with open(os.path.join(str(tmp_path), "step_000000000002",
                           "arrays.npz"), "ab") as f:
        f.write(b"garbage")
    assert mgr.latest_valid_step() == 1
    restored, step = mgr.restore(t)
    assert step == 1
    np.testing.assert_array_equal(restored["a"], t["a"])


def test_checkpoint_gc_keeps_k(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in range(5):
        mgr.save(s, tree(), blocking=True)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(7, tree())
    mgr.wait()
    assert mgr.latest_valid_step() == 7


# -- fault tolerance ------------------------------------------------------------

def test_heartbeat_dead_host():
    hb = ft.HeartbeatMonitor(timeout_s=10)
    hb.beat(0, now=0.0)
    hb.beat(1, now=0.0)
    hb.beat(0, now=8.0)
    assert hb.dead_hosts(now=15.0) == [1]
    assert hb.alive_hosts(now=15.0) == [0]


def test_straggler_detection():
    det = ft.StragglerDetector(threshold=1.5, min_samples=3)
    for _ in range(5):
        for h in range(4):
            det.observe(h, 1.0 if h != 2 else 3.0)
    assert det.stragglers() == [2]


def test_reassign_deterministic_and_complete():
    m1 = ft.reassign_shards(16, [0, 1, 3])
    m2 = ft.reassign_shards(16, [3, 0, 1])   # order must not matter
    assert m1 == m2
    covered = sorted(s for ss in m1.values() for s in ss)
    assert covered == list(range(16))


def test_retry_policy():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    pol = ft.RetryPolicy(max_retries=5, base_delay_s=0)
    assert pol.run(flaky, _sleep=lambda s: None) == "ok"
    assert len(calls) == 3


# -- fault-tolerance additions (crash-safe resume + quarantine support) ----------

def test_heartbeat_prune_after_report():
    """A long-dead host must not be re-reported on every poll: prune=True
    gives report-once semantics, and forget() drops a handled host."""
    hb = ft.HeartbeatMonitor(timeout_s=10)
    hb.beat(0, now=0.0)
    hb.beat(1, now=0.0)
    hb.beat(2, now=0.0)
    assert hb.dead_hosts(now=20.0, prune=True) == [0, 1, 2]
    assert hb.dead_hosts(now=25.0) == []         # pruned, not re-reported
    hb.beat(1, now=26.0)                         # re-registers fresh
    assert hb.alive_hosts(now=27.0) == [1]
    hb.forget(1)
    assert hb.dead_hosts(now=100.0) == []
    assert hb.alive_hosts(now=27.0) == []


def test_checkpoint_stale_tmp_ignored_and_gced(tmp_path):
    """A crash mid-write leaves step_<N>.tmp/ behind: it must never be a
    restore candidate, and a fresh manager GCs it on startup."""
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, tree(), blocking=True)
    stale = os.path.join(str(tmp_path), "step_000000000009.tmp")
    os.makedirs(stale)
    with open(os.path.join(stale, "arrays.npz"), "wb") as f:
        f.write(b"partial write")
    assert mgr.all_steps() == [1]                # tmp is not a step
    assert mgr.latest_valid_step() == 1
    mgr2 = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    assert not os.path.exists(stale)             # GC'd on init
    assert mgr2.latest_valid_step() == 1         # real steps untouched


def test_checkpoint_gc_keep_holds_with_aux(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), keep=2, async_write=False)
    for s in range(5):
        mgr.save(s, tree(), aux=dict(cursor=s), blocking=True)
    assert mgr.all_steps() == [3, 4]
    assert mgr.load_aux() == dict(cursor=4)
    assert mgr.load_aux(step=3) == dict(cursor=3)


def test_checkpoint_aux_roundtrip_and_none(tmp_path):
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    aux = dict(cursor=7, losses=[1.0, 0.5], state=(1, 2, ("x",)))
    mgr.save(7, tree(), aux=aux, blocking=True)
    assert mgr.load_aux() == aux
    mgr.save(8, tree(), blocking=True)           # no aux on this one
    assert mgr.load_aux(step=8) is None


def test_checkpoint_aux_corruption_falls_back(tmp_path):
    """A corrupted aux payload invalidates the whole step (params without
    the cursor/cache state cannot resume bit-identically), falling back to
    the previous step."""
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), async_write=False)
    mgr.save(1, tree(), aux=dict(cursor=1), blocking=True)
    mgr.save(2, tree(), aux=dict(cursor=2), blocking=True)
    with open(os.path.join(str(tmp_path), "step_000000000002",
                           "aux.pkl"), "ab") as f:
        f.write(b"garbage")
    assert mgr.latest_valid_step() == 1
    assert mgr.load_aux() == dict(cursor=1)
    restored, step = mgr.restore(tree())
    assert step == 1


def test_retry_policy_fatal_fails_fast():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("deterministic bug")

    pol = ft.RetryPolicy(max_retries=5, base_delay_s=0)
    with pytest.raises(ValueError):
        pol.run(broken, _sleep=lambda s: None,
                retryable=ft.default_transient)
    assert len(calls) == 1                       # no retry burned


def test_retry_policy_cancel_interrupts_backoff():
    """close() during a backoff must not sleep out the delay ladder: the
    cancel event doubles as the timer and re-raises promptly."""
    import threading as th
    import time as _t
    cancel = th.Event()

    def flaky():
        cancel.set()                             # "close() arrives" mid-run
        raise ft.TransientError("flaky")

    pol = ft.RetryPolicy(max_retries=10, base_delay_s=30.0)
    t0 = _t.perf_counter()
    with pytest.raises(ft.TransientError):
        pol.run(flaky, cancel=cancel, retryable=ft.default_transient)
    assert _t.perf_counter() - t0 < 5.0
