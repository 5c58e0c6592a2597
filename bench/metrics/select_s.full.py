"""Seconds the configured selector took to commit the plan
(``TrainResult.select_seconds``): the feedback selector compiles and
times every candidate kernel per (layer, tier)."""


def read(ctx, out):
    return out["info"].get("select_s")
