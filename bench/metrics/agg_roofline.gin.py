"""The committed aggregation passes' least time on the chip (their widths
from bench/models/gin.py, each pass bound by its operations or its bytes,
bench/flops.py; bench/peaks.json) over the device time of the operations
under an ``agg/`` scope, in percent."""
from bench import flops
from bench.modes.full_model import is_agg, scoped_seconds


def read(ctx, out):
    s = scoped_seconds(ctx, out, is_agg)
    if s is None or ctx.peaks is None:
        return None
    info = out["info"]
    least = flops.agg_least_seconds(info["n_nodes"], info["n_edges"],
                                    info["work"]["agg_passes"], ctx.peaks)
    return 100.0 * least * info["steps"] / s
