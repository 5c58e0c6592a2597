"""The aggregation passes' least time on the chip (bench/flops.py, each pass
bound by its operations or its bytes; bench/peaks.json) over their device
time in the trace, in percent."""
from bench import flops
from bench.metrics_lib import agg_seconds


def read(ctx, out):
    s = agg_seconds(ctx, out)
    if not s or ctx.peaks is None:
        return None
    info = out["info"]
    least = flops.agg_least_seconds(info["n_nodes"], info["n_edges"],
                                    info["work"]["agg_passes"], ctx.peaks)
    return 100.0 * least * info["steps"] / s
