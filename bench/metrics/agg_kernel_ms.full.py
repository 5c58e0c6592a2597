"""Device milliseconds per step of the aggregation operations in the
traced window (bench/metrics_lib.is_aggregation): Pallas kernels and the
XLA operations over a tier's edge list."""
from bench.metrics_lib import agg_seconds


def read(ctx, out):
    s = agg_seconds(ctx, out)
    return None if s is None else s / out["info"]["steps"] * 1e3
