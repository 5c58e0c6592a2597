"""Share of the traced window in which no operation ran on the device."""
from bench.metrics_lib import idle_pct


def read(ctx, out):
    return idle_pct(ctx)
