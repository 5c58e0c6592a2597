"""The whole step's share of the chip's peak: the operations a training
step requires (bench/flops.py, real nodes and edges) times the steps of the
traced window, over the window's length times the peak."""
from bench.metrics_lib import step_mfu


def read(ctx, out):
    return step_mfu(ctx, out)
