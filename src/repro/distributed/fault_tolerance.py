"""Fault tolerance + straggler mitigation for the training loops.

On a real 1000-node cluster these hooks connect to the coordination service;
here every mechanism is implemented and unit-tested against simulated
heartbeats / step-time streams, and the mini-batch training loop
(train/gnn_steps.py) and the server (serve/server.py) drive them for real
on the host.

Components:
  HeartbeatMonitor  -- per-host liveness with timeout -> dead-host set;
                       reported dead hosts can be pruned so a long-dead
                       host is not re-reported forever
  StragglerDetector -- per-host step-time EWMA; z-score over the fleet
                       median flags stragglers (mitigation: demote the host's
                       data shard)
  reassign_shards   -- deterministic data-shard reassignment when hosts die:
                       surviving hosts take over orphaned shards round-robin
                       (restart-stable: pure function of (n_shards, alive))
  RetryPolicy       -- exponential-backoff retry for transient failures,
                       with an interruptible backoff (``cancel`` event) and
                       a fatal-vs-transient classifier (``retryable``) so
                       real bugs fail fast instead of burning retries
  TransientError /
  default_transient -- the marker + default classifier the mini-batch
                       pipeline uses for per-item worker retries
  FaultPlan         -- deterministic fault-injection harness: worker
                       exceptions, Pallas kernel compile/execute failures,
                       non-finite losses, and simulated crashes at chosen
                       batch indices, driving the robustness tests and bench
"""
from __future__ import annotations

import dataclasses
import re
import threading
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class HeartbeatMonitor:
    timeout_s: float = 60.0
    _last: dict = field(default_factory=dict)

    def beat(self, host: int, now: float | None = None) -> None:
        self._last[host] = time.monotonic() if now is None else now

    def forget(self, host: int) -> None:
        """Drop a host from liveness tracking (it was replaced, drained, or
        its death has been handled) so :meth:`dead_hosts` stops reporting
        it.  A later :meth:`beat` re-registers it fresh."""
        self._last.pop(host, None)

    def dead_hosts(self, now: float | None = None,
                   prune: bool = False) -> list[int]:
        """Hosts whose last beat is older than ``timeout_s``.

        With ``prune=True`` the reported hosts are forgotten in the same
        call (report-once semantics): without pruning, a host that died an
        hour ago is re-reported on every poll and the caller re-triggers
        shard reassignment forever."""
        now = time.monotonic() if now is None else now
        dead = sorted(h for h, t in self._last.items()
                      if now - t > self.timeout_s)
        if prune:
            for h in dead:
                self.forget(h)
        return dead

    def alive_hosts(self, now: float | None = None) -> list[int]:
        now = time.monotonic() if now is None else now
        return sorted(h for h, t in self._last.items()
                      if now - t <= self.timeout_s)


@dataclass
class StragglerDetector:
    """EWMA step-time per host; a host is a straggler when its smoothed step
    time exceeds ``threshold`` x the fleet median."""
    alpha: float = 0.2
    threshold: float = 1.5
    min_samples: int = 3
    _ewma: dict = field(default_factory=dict)
    _count: dict = field(default_factory=dict)

    def observe(self, host: int, step_seconds: float) -> None:
        prev = self._ewma.get(host)
        self._ewma[host] = (step_seconds if prev is None
                            else self.alpha * step_seconds + (1 - self.alpha) * prev)
        self._count[host] = self._count.get(host, 0) + 1

    def stragglers(self) -> list[int]:
        ready = {h: t for h, t in self._ewma.items()
                 if self._count[h] >= self.min_samples}
        if len(ready) < 2:
            return []
        med = sorted(ready.values())[len(ready) // 2]
        return sorted(h for h, t in ready.items() if t > self.threshold * med)


def reassign_shards(n_shards: int, alive_hosts: list[int]) -> dict[int, list[int]]:
    """Deterministic shard->host map: shard i goes to alive_hosts[i % n].
    Any two hosts computing this agree without communication."""
    assert alive_hosts, "no hosts alive"
    hosts = sorted(alive_hosts)
    out: dict[int, list[int]] = {h: [] for h in hosts}
    for s in range(n_shards):
        out[hosts[s % len(hosts)]].append(s)
    return out


# ---------------------------------------------------------------------------
# Transient-vs-fatal classification
# ---------------------------------------------------------------------------

class TransientError(RuntimeError):
    """Marker for failures worth retrying (flaky I/O, injected worker
    faults).  Anything not classified transient is a real bug and must
    fail fast — retrying a deterministic exception just repeats it
    ``max_retries`` times and then hides the first stack trace."""


def default_transient(exc: BaseException) -> bool:
    """The mini-batch pipeline's retry classifier: explicit markers plus
    the OS-level failure classes that are genuinely environmental."""
    return isinstance(exc, (TransientError, OSError, TimeoutError,
                            ConnectionError))


@dataclass
class RetryPolicy:
    max_retries: int = 3
    base_delay_s: float = 1.0
    backoff: float = 2.0
    # decorrelated jitter (Brooker, "Exponential Backoff and Jitter"):
    # each wait draws uniform(base, 3 * previous_wait) capped at
    # max_delay_s, so concurrent callers that failed together (a burst of
    # serving requests hitting one flaky build) spread their retries out
    # instead of re-arriving in lockstep as a retry storm.  Off by default
    # (plain exponential ladder, bit-reproducible timing); with it on,
    # determinism comes from ``seed``: the Nth ``run()`` call on this
    # policy draws from stream (seed, N), a pure function of call order —
    # tests replay exact delay sequences, while concurrent calls still
    # decorrelate because each holds its own stream.
    jitter: bool = False
    max_delay_s: float | None = None
    seed: int | None = None
    # optional obs.Tracer: each backoff wait records a "retry.backoff" span
    # on the waiting thread (attempt + delay visible in the trace)
    tracer: object = None
    _run_count: int = field(default=0, init=False, repr=False,
                            compare=False)
    _count_lock: threading.Lock = field(default_factory=threading.Lock,
                                        init=False, repr=False,
                                        compare=False)

    def _wait(self, delay, attempt, _sleep, cancel):
        if _sleep is not None:
            _sleep(delay)
        elif cancel is not None:
            if cancel.wait(delay):   # interruptible backoff
                raise                # noqa: PLE0704 — re-raise active exc
        else:
            time.sleep(delay)

    def _jitter_rng(self) -> np.random.Generator:
        """One rng stream per run() call: deterministic under ``seed``
        (stream i belongs to the i-th call, whatever thread makes it),
        OS-entropy fresh when seed is None."""
        with self._count_lock:
            i = self._run_count
            self._run_count += 1
        if self.seed is None:
            return np.random.default_rng()
        return np.random.default_rng(np.random.SeedSequence((self.seed, i)))

    def delays(self, rng: np.random.Generator | None = None) -> list[float]:
        """The full backoff-delay ladder one ``run()`` would use: plain
        exponential without jitter, decorrelated-jitter draws with it
        (pass the rng to inspect a specific stream; tests)."""
        cap = (self.max_delay_s if self.max_delay_s is not None
               else self.base_delay_s * self.backoff ** self.max_retries)
        if self.jitter and rng is None:
            rng = self._jitter_rng()
        out, delay = [], self.base_delay_s
        for _ in range(self.max_retries):
            if self.jitter:
                delay = min(cap, float(rng.uniform(self.base_delay_s,
                                                   3.0 * delay)))
                out.append(delay)
            else:
                out.append(min(delay, cap))
                delay *= self.backoff
        return out

    def run(self, fn, *args, on_retry=None, _sleep=None, cancel=None,
            retryable=None, **kwargs):
        """Call ``fn`` with bounded backoff retries (exponential, or
        decorrelated-jitter when ``jitter=True``).

        ``retryable(exc) -> bool`` classifies failures; a non-retryable
        exception re-raises immediately (fatal-fails-fast).  ``cancel`` is
        a ``threading.Event``: the backoff waits on it instead of sleeping,
        so a shutdown mid-backoff re-raises promptly rather than pinning a
        worker thread for the rest of the delay ladder.  ``_sleep``
        overrides the wait entirely (tests)."""
        ladder = iter(self.delays())
        for attempt in range(self.max_retries + 1):
            try:
                return fn(*args, **kwargs)
            except Exception as exc:
                if attempt == self.max_retries:
                    raise
                if retryable is not None and not retryable(exc):
                    raise
                if cancel is not None and cancel.is_set():
                    raise
                if on_retry is not None:
                    on_retry(attempt)
                delay = next(ladder)
                if self.tracer is not None:
                    with self.tracer.span("retry.backoff", cat="fault",
                                          attempt=attempt, delay_s=delay):
                        self._wait(delay, attempt, _sleep, cancel)
                else:
                    self._wait(delay, attempt, _sleep, cancel)


# ---------------------------------------------------------------------------
# Deterministic fault injection (tests + robustness bench)
# ---------------------------------------------------------------------------

class SimulatedCrash(RuntimeError):
    """Raised by :class:`FaultPlan` after the chosen batch commits — the
    process 'dies' with the checkpoint on disk, and the resume path must
    reproduce the uninterrupted run bit-identically."""


class InjectedWorkerFault(TransientError):
    """Transient worker failure injected into the batch-build stage."""


# marker embedded in injected kernel failures so the quarantine path can
# attribute the failure to one kernel even through jax's exception wrapping
_KERNEL_FAULT_MARK = "__fault_kernel__"
_KERNEL_FAULT_RE = re.compile(_KERNEL_FAULT_MARK + r":(\w+)")


def fault_kernel_from(exc: BaseException) -> str | None:
    """Kernel name attributed by an injected-fault marker anywhere in the
    exception chain (jax wraps both trace-time and runtime errors)."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        m = _KERNEL_FAULT_RE.search(str(exc))
        if m:
            return m.group(1)
        exc = exc.__cause__ or exc.__context__
    return None


def drain_effect_tokens() -> None:
    """Block on pending jax effect tokens, swallowing errors from aborted
    dispatches.  A computation that failed mid-flight leaves a poisoned
    runtime token behind; jax's ``wait_for_tokens`` atexit hook would
    re-raise its error at interpreter exit, and ``RuntimeTokenSet.
    block_until_ready`` has no try/finally around its ``clear()``, so
    once poisoned the set can never drain itself — fall back to clearing
    the (thread-local) token set directly."""
    import jax
    try:
        jax.effects_barrier()
    except Exception:
        try:
            from jax._src.dispatch import runtime_tokens
            runtime_tokens.clear()
        except Exception:
            pass


class KernelFault(RuntimeError):
    """Injected Pallas kernel failure (compile- or execute-time)."""


def _raise_kernel_fault(name: str, mode: str):
    raise KernelFault(
        f"{_KERNEL_FAULT_MARK}:{name} injected {mode} failure")


@dataclass
class FaultPlan:
    """Deterministic fault schedule for one training run.

    Every injection is keyed by the *global* batch index (or kernel name),
    so a plan replays identically under any pipeline depth / worker count /
    retry schedule — which is what lets the tests assert bit-identical
    recovery instead of 'it eventually finished'.

      worker_faults   -- batch index -> how many times that batch's build
                         raises :class:`InjectedWorkerFault` (transient:
                         the retry path absorbs them)
      fatal_at        -- batch indices whose build raises ValueError once
                         (non-transient: must fail fast through any retry)
      kernel_faults   -- kernel name -> "compile" | "execute".  Activated
                         by :meth:`activate` (patches the kernel registry):
                         "compile" raises at trace/lower time, "execute"
                         compiles fine and fails at run time via
                         ``jax.pure_callback`` — the two failure surfaces
                         the quarantine path must cover
      nonfinite_at    -- batch indices whose features are corrupted to NaN
                         (flows through the jitted step without a retrace;
                         the non-finite guard must skip the update)
      crash_at        -- batch index after whose commit the loop raises
                         :class:`SimulatedCrash` (None = never)
    """
    worker_faults: dict = field(default_factory=dict)
    fatal_at: frozenset | set = field(default_factory=set)
    kernel_faults: dict = field(default_factory=dict)
    nonfinite_at: frozenset | set = field(default_factory=set)
    crash_at: int | None = None
    # counters (observable by tests/bench)
    injected_worker: int = 0
    injected_fatal: int = 0
    injected_nonfinite: int = 0
    kernel_trips: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)
    _pending: dict = field(default_factory=dict, repr=False)
    _saved_specs: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        self._pending = dict(self.worker_faults)
        self._fatal_pending = set(self.fatal_at)

    # -- build-stage hooks (driven by train_minibatch) ----------------------

    def on_built(self, index: int, batch):
        """Called after batch ``index``'s build on whatever thread built it.
        May raise (worker fault) or return a corrupted batch (non-finite
        injection); retries re-enter here, so injected failure counts are
        consumed under the lock."""
        with self._lock:
            if index in self._fatal_pending:
                self._fatal_pending.discard(index)
                self.injected_fatal += 1
                raise ValueError(
                    f"injected fatal (non-transient) fault at batch {index}")
            left = self._pending.get(index, 0)
            if left > 0:
                self._pending[index] = left - 1
                self.injected_worker += 1
                raise InjectedWorkerFault(
                    f"injected transient worker fault at batch {index} "
                    f"({left - 1} left)")
            if index in self.nonfinite_at:
                self.injected_nonfinite += 1
                batch = dataclasses.replace(
                    batch, features=np.full_like(batch.features, np.nan))
        return batch

    def on_committed(self, index: int) -> None:
        """Called after batch ``index``'s update committed (and any due
        checkpoint was scheduled) — the simulated kill point."""
        if self.crash_at is not None and index == self.crash_at:
            raise SimulatedCrash(f"injected crash after batch {index}")

    # -- kernel fault patching ---------------------------------------------

    def _wrap_device_fn(self, name: str, mode: str, fn):
        if fn is None:
            return None
        if mode == "compile":
            def broken(*args, **kwargs):
                with self._lock:
                    self.kernel_trips += 1
                _raise_kernel_fault(name, "compile")
            return broken

        def exec_broken(*args, **kwargs):
            import jax
            out = fn(*args, **kwargs)

            def die(*_):
                with self._lock:
                    self.kernel_trips += 1
                _raise_kernel_fault(name, "execute")

            # compile succeeds; the callback detonates at execution time
            # (out may be any pytree — matvec_acc variants return tuples).
            # The detonator needs a JVP rule: the training step
            # differentiates through the kernel, and a bare pure_callback
            # would raise "no JVP" at *trace* time — the wrong failure
            # surface.  Tangents pass through untouched; their values never
            # matter because the primal always raises at run time.
            shapes = jax.tree.map(
                lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype), out)

            @jax.custom_jvp
            def bomb(o):
                return jax.pure_callback(die, shapes, o)

            @bomb.defjvp
            def bomb_jvp(primals, tangents):
                return (jax.pure_callback(die, shapes, primals[0]),
                        tangents[0])

            return bomb(out)
        return exec_broken

    def activate(self):
        """Context manager patching the kernel registry so the named
        kernels fail.  Use around the training call:

            with plan.activate():
                train_minibatch(..., fault_plan=plan)
        """
        return _PatchedKernels(self)


class _PatchedKernels:
    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self._saved: dict = {}

    def __enter__(self):
        from repro.kernels.registry import REGISTRY
        for name, mode in self.plan.kernel_faults.items():
            spec = REGISTRY.get(name)
            self._saved[name] = spec
            wrap = lambda fn, n=name, m=mode: (
                self.plan._wrap_device_fn(n, m, fn))
            REGISTRY._specs[name] = dataclasses.replace(
                spec,
                matvec=wrap(spec.matvec),
                matvec_acc=wrap(spec.matvec_acc),
                fused_matvec=wrap(spec.fused_matvec),
                fused_matvec_acc=wrap(spec.fused_matvec_acc),
                fused_dual_matvec=wrap(spec.fused_dual_matvec),
                fused_dual_matvec_acc=wrap(spec.fused_dual_matvec_acc))
        return self.plan

    def __exit__(self, *exc):
        from repro.kernels.registry import REGISTRY
        for name, spec in self._saved.items():
            REGISTRY._specs[name] = spec
        self._saved.clear()
