"""Thread-aware span tracer with Chrome trace-event JSON export.

One :class:`Tracer` instance per training run records *spans* (named,
timed intervals) and *instants* attributed to the thread that emitted
them.  The pipeline stages (draw -> build -> resolve -> finish -> device
step), the consumer's wait for a batch (``pipeline.get``) and its
dispatch and sync of the compiled step (``step.dispatch``,
``step.sync``), checkpoint writes, and retry backoffs each open a span,
so the async overlap the pipeline claims becomes directly visible: load
the exported file into ``chrome://tracing`` or https://ui.perfetto.dev
and every worker thread gets its own swim lane.

While enabled, every span is also a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` whose metadata carries the thread's tracer id
(``thread``) and the span's ``index`` argument, so a profile taken with
``jax.profiler`` places the program's own spans on the timeline of the
device's operations.  Outside a profile the annotation is inert.

Each span records its parent (the enclosing span on its thread) and the
thread's CPU time (``time.thread_time``) at entry and exit beside its
wall time, so a span's CPU seconds can be set against its wall seconds:
a worker that waits on the GIL spends wall time without CPU time.

Disabled-path contract: call sites always go through a tracer object, and
the :data:`NULL_TRACER` singleton makes that path near-free — ``span()``
returns one shared no-op context manager (no allocation, no clock read,
no lock, no annotation).  A mini-batch training step opens about a
dozen spans across its threads; with tracing off each is one method call
returning the singleton.  The enabled cost, measured on one TPU v5e at
the mini-batch benchmark cell's size, is in PERF.md.

Recording a span when *enabled* is two ``perf_counter`` and two
``thread_time`` reads, one profiler annotation, and one locked list
append; events are kept as tuples and only formatted into Chrome trace
dicts at :meth:`Tracer.export` time.  Each thread gets a small sequential
id on its first span (never the OS thread ident, which the OS reuses once
a thread exits), with ``thread_name`` metadata events carrying the Python
thread names (``pipeline-<sampler>-<i>``, ``ckpt-writer``,
``MainThread``).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time

__all__ = ["Tracer", "NullTracer", "NULL_TRACER"]


class _Span:
    """Context manager for one timed interval (allocated per span only
    when tracing is enabled)."""
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_c0", "_ann",
                 "_thread", "_id", "_parent")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "_Span":
        tr = self._tracer
        th = self._thread = tr._thread()
        self._parent = th.stack[-1] if th.stack else None
        self._id = next(tr._ids)
        th.stack.append(self._id)
        self._ann = tr._annotation(self.name, th.tid, self.args)
        self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        c1 = time.thread_time()
        self._ann.__exit__(*exc)
        th = self._thread
        th.stack.pop()
        self._tracer._append(
            (self.name, self.cat, th.tid, th.name, self._t0, t1, self.args,
             self._id, self._parent, self._c0, c1))
        return False


class _NullSpan:
    """Shared no-op span: the entire disabled-path cost of ``with
    tracer.span(...)`` is one method call returning this singleton."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Collects spans/instants; exports Chrome trace-event JSON.

    Each event is a tuple ``(name, cat, tid, thread_name, t0, t1, args,
    span_id, parent_id, cpu0, cpu1)``: ``tid`` the thread's sequential
    tracer id, ``t0``/``t1`` ``perf_counter`` seconds (``t1`` None marks
    an instant), ``parent_id`` the ``span_id`` of the enclosing span on
    the same thread (None at the top), ``cpu0``/``cpu1`` the thread's
    ``thread_time`` at entry and exit (None for an instant)."""

    enabled = True

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self._trace_annotation = TraceAnnotation
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._events: list[tuple] = []
        self._local = threading.local()
        self._tids = itertools.count()
        self._ids = itertools.count()

    # -- recording ----------------------------------------------------------

    def span(self, name: str, cat: str = "host", **args) -> _Span:
        """Context manager timing one interval on the calling thread."""
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        """Zero-duration marker (quarantine events, slack steps, ...)."""
        th = self._thread()
        with self._annotation(name, th.tid, args):
            t = time.perf_counter()
        self._append((name, cat, th.tid, th.name, t, None, args,
                      next(self._ids), th.stack[-1] if th.stack else None,
                      None, None))

    def _thread(self):
        """The calling thread's record: its sequential ``tid``, its name,
        and the stack of its open span ids."""
        th = self._local
        if not hasattr(th, "tid"):
            th.tid = next(self._tids)
            th.name = threading.current_thread().name
            th.stack = []
        return th

    def _annotation(self, name: str, tid: int, args: dict):
        index = args.get("index")
        if index is None:
            return self._trace_annotation("repro." + name, thread=tid)
        return self._trace_annotation("repro." + name, thread=tid,
                                      index=index)

    def _append(self, event: tuple) -> None:
        with self._lock:
            self._events.append(event)

    # -- export -------------------------------------------------------------

    def events(self) -> list[tuple]:
        """Raw event tuples recorded so far (copy)."""
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event document: complete (``ph: X``) events
        with microsecond ``ts``/``dur`` relative to tracer creation,
        instant (``ph: i``) markers, and one ``thread_name`` metadata
        (``ph: M``) event per thread seen."""
        events = self.events()
        pid = os.getpid()
        tid_names: dict[int, str] = {}
        out = []
        for name, cat, tid, tname, t0, t1, args, *_ in events:
            tid_names[tid] = tname
            if t1 is None:
                ev = dict(name=name, cat=cat, ph="i", s="t",
                          ts=(t0 - self._epoch) * 1e6, pid=pid, tid=tid)
            else:
                ev = dict(name=name, cat=cat, ph="X",
                          ts=(t0 - self._epoch) * 1e6,
                          dur=(t1 - t0) * 1e6, pid=pid, tid=tid)
            if args:
                ev["args"] = args
            out.append(ev)
        meta = [dict(name="thread_name", ph="M", pid=pid, tid=tid,
                     args=dict(name=tname))
                for tid, tname in sorted(tid_names.items())]
        return dict(traceEvents=meta + out, displayTimeUnit="ms")

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON; returns the path."""
        doc = self.chrome_trace()
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        return path


class NullTracer:
    """Disabled tracer: every operation is a no-op, ``span`` returns one
    shared context manager.  All call sites stay unconditional."""

    enabled = False

    def span(self, name: str, cat: str = "host", **args) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "host", **args) -> None:
        return None

    def events(self) -> list:
        return []

    def chrome_trace(self) -> dict:
        return dict(traceEvents=[], displayTimeUnit="ms")

    def export(self, path: str) -> str:
        raise RuntimeError("cannot export a disabled (null) tracer; "
                           "enable telemetry to record spans")


NULL_TRACER = NullTracer()
