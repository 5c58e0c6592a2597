"""Arithmetic shared by the per-layer metric readers in bench/metrics/."""
from __future__ import annotations

import re


def idle_pct(ctx):
    rt = ctx.reduced_trace
    if rt is None or rt["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rt["busy_s"] / rt["window_s"])


def step_mfu(ctx, out):
    rt, info = ctx.reduced_trace, out["info"]
    if rt is None or ctx.peaks is None or not info.get("window_flops"):
        return None
    return (100.0 * info["window_flops"]
            / (rt["window_s"] * ctx.peaks["flops_per_s"]))


def is_aggregation(hlo: str, edge_counts) -> bool:
    """An aggregation operation is a Pallas kernel (every GNN Pallas kernel
    aggregates) or an XLA operation over an array as long as a tier's edge
    list (its nnz, or that padded by up to 1% or 1024)."""
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return True
    dims = [int(d) for d in re.findall(r"[\[,](\d+)", hlo)]
    return any(e <= d <= e + max(1024, e // 100)
               for d in dims for e in edge_counts)


def agg_seconds(ctx, out):
    """Device seconds of the window's aggregation operations, or None where
    the trace holds none."""
    rt = ctx.reduced_trace
    if rt is None:
        return None
    edges = out["info"]["edge_counts"]
    s = sum(v for k, v in rt["op_s"].items() if is_aggregation(k, edges))
    return s or None
