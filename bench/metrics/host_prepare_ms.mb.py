"""Host milliseconds per window step in the pipeline's stages, from the
program's own spans (``sample``, ``build``, ``resolve``, ``finish``)
started inside the window.  Worker threads overlap, so this can exceed the
step time; it is the host work a step costs."""


def read(ctx, out):
    info = out["info"]
    return info["host_prepare_s"] / info["steps"] * 1e3
