"""Unified telemetry for the sampler -> pipeline -> kernel path.

Three instruments, one facade:

* :mod:`repro.obs.trace` — a thread-aware span tracer over the pipeline
  stages (draw -> build -> resolve -> finish -> device step, plus
  checkpoint writes and retry backoffs), exported as Chrome trace-event
  JSON (``chrome://tracing`` / Perfetto): the async overlap the pipeline
  claims becomes visible per thread.  While enabled, each span is also a
  ``repro.<name>`` annotation on ``jax.profiler``'s timeline, beside the
  device's operations.
* :mod:`repro.obs.metrics` — a thread-safe registry of counters, gauges,
  and bounded histograms (p50/p99).  PlanCache, BatchPipeline,
  CheckpointManager, and the fault-tolerance loop publish their counters
  into it; the legacy dict views (``PlanCache.stats``,
  ``BatchPipeline.stats``, ``MinibatchResult.cache/pipeline/faults``)
  are assembled *from* the registry, unchanged in keys and semantics.
* :mod:`repro.obs.audit` — the selector audit log: every committed plan
  with per-(layer, tier) kernel choices and modeled costs, probe
  measurements, quarantine/degradation events, observed step times, and
  a cost-model calibration report (per-kernel predicted-vs-measured
  error) surfaced through ``MinibatchResult.telemetry``.

:func:`op_scopes` maps a compiled step's HLO instructions to the named
scopes they came from (``agg/<tier>/<kernel>`` in the aggregation
dispatch), so a profile's device operations can be put down to a tier.

The :class:`Telemetry` facade bundles the three.  Overhead contract:
``Telemetry(enabled=False)`` — the default everywhere — carries the real
metrics registry (counters are the system of record for the stats views)
but the null tracer and null audit, whose methods are no-ops returning
shared singletons.  Call sites are unconditional.  The enabled tracer's
cost was measured on one TPU v5e at the mini-batch benchmark cell's size,
telemetry on against off with the profiler off (PERF.md).  Telemetry
never feeds back into decisions: tracing and auditing are append-only,
so enabling them leaves losses, plans, hit history, and trace counts
bit-identical (tests/test_obs.py locks this in).

Logging: :func:`get_logger` / :func:`enable_verbose` give the training
stack a namespaced ``repro.train`` logger; ``verbose=True`` on the
drivers installs a plain stdout stream handler (idempotent) instead of
scattering ``print`` calls.
"""
from __future__ import annotations

import logging
import sys

from repro.obs.audit import NULL_AUDIT, NullAudit, SelectorAudit
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry)
from repro.obs.scopes import op_scopes  # noqa: F401
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer  # noqa: F401

__all__ = ["Telemetry", "Tracer", "NullTracer", "NULL_TRACER",
           "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "SelectorAudit", "NullAudit", "NULL_AUDIT", "op_scopes",
           "get_logger", "enable_verbose"]


class Telemetry:
    """One run's telemetry bundle: ``tracer`` + ``metrics`` + ``audit``.

    ``enabled=False`` (default) keeps the metrics registry live but
    swaps the tracer and audit for their null singletons; ``metrics``
    may be shared across components by passing one registry in.
    """

    def __init__(self, enabled: bool = False,
                 metrics: MetricsRegistry | None = None):
        self.enabled = bool(enabled)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = Tracer() if self.enabled else NULL_TRACER
        self.audit = SelectorAudit() if self.enabled else NULL_AUDIT

    def summary(self) -> dict:
        """The ``MinibatchResult.telemetry`` view: calibration report plus
        span/audit volume and the full metrics snapshot."""
        return dict(enabled=self.enabled,
                    n_span_events=len(self.tracer.events()),
                    n_audit_events=len(self.audit.events()),
                    calibration=self.audit.calibration(),
                    metrics=self.metrics.snapshot())

    def export(self, trace_out: str | None = None,
               jsonl_out: str | None = None) -> None:
        """Write the Chrome trace and/or the JSONL event export (audit
        events + calibration + final metrics snapshot)."""
        if trace_out:
            self.tracer.export(trace_out)
        if jsonl_out:
            self.audit.export_jsonl(
                jsonl_out,
                extra=[dict(event="metrics", **self.metrics.snapshot())])


# ---------------------------------------------------------------------------
# Namespaced logging (replaces print-based verbose output)
# ---------------------------------------------------------------------------

_VERBOSE_MARK = "_repro_verbose_handler"


def get_logger(name: str = "repro.train") -> logging.Logger:
    return logging.getLogger(name)


def enable_verbose(name: str = "repro.train",
                   level: int = logging.INFO) -> logging.Logger:
    """Install a plain message-only stdout handler on ``name`` once
    (idempotent) — the ``verbose=True`` convenience.  stdout, not stderr,
    so driver output stays pipeable the way the old prints were."""
    logger = logging.getLogger(name)
    logger.setLevel(level)
    if not any(getattr(h, _VERBOSE_MARK, False) for h in logger.handlers):
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(logging.Formatter("%(message)s"))
        setattr(handler, _VERBOSE_MARK, True)
        logger.addHandler(handler)
    return logger
