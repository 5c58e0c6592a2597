"""Device milliseconds per step of the window's operations under GIN's
``gin/self`` or ``gin/mlp`` scope and under no ``agg/`` scope: the self
term and the MLP around the aggregation, forward and backward.  None on a
program without those scopes."""
import re

from bench.modes.full_model import is_agg, scoped_seconds

GIN = re.compile(r"(^|[/(])gin/(self|mlp)($|[/)])")


def _epilogue(scope: str) -> bool:
    return GIN.search(scope) is not None and not is_agg(scope)


def read(ctx, out):
    s = scoped_seconds(ctx, out, _epilogue)
    return None if s is None else s / out["info"]["steps"] * 1e3
