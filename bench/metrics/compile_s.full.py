"""Seconds of the train step's AOT compile (``TrainResult.compile_seconds``),
a load from the persistent cache when the plan repeats."""


def read(ctx, out):
    return out["info"].get("compile_s")
