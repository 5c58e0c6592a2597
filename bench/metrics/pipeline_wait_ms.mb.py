"""Milliseconds per window step that the training loop waited for a
prepared batch (the pipeline's ``wait_empty_s`` counter, read at the
window's two ends)."""


def read(ctx, out):
    info = out["info"]
    return info["wait_empty_s"] / info["steps"] * 1e3
