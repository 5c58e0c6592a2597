"""Device milliseconds per step of the window's operations under an
``agg/<tier>/<kernel>`` scope (the compiled step's op_names, via
``repro.obs.op_scopes``), forward and backward."""
from bench.modes.full_model import is_agg, scoped_seconds


def read(ctx, out):
    s = scoped_seconds(ctx, out, is_agg)
    return None if s is None else s / out["info"]["steps"] * 1e3
