"""Shared by the accumulate-path tests: a layer written out tier by tier,
and a registry view that counts one kernel hook's calls."""
import dataclasses

from repro.kernels.registry import REGISTRY


def tier_sum(dec, names, x, w=None, w_self=None, bias=None):
    """The layer written out tier by tier with each kernel's plain hook
    (``matvec`` / ``fused_matvec``), partials added explicitly: no
    accumulator is threaded through any kernel."""
    y = 0.0
    for sub, k in zip(dec.subgraphs, names):
        spec = REGISTRY.get(k)
        p = sub.formats[spec.payload_key]
        if spec.fused:
            y = y + spec.fused_matvec(p, x, w)
        else:
            y = y + spec.matvec(p, x if w is None else x @ w)
    if w_self is not None:
        y = y + x @ w_self
    return y if bias is None else y + bias


class SpiedRegistry:
    """The registry as ``adaptgear`` sees it, with one kernel's hook
    wrapped to count its calls."""

    def __init__(self, kernel, hook):
        self.kernel, self.hook, self.calls = kernel, hook, 0

    def get(self, name):
        spec = REGISTRY.get(name)
        if name != self.kernel:
            return spec
        real = getattr(spec, self.hook)

        def spy(*args):
            self.calls += 1
            return real(*args)
        return dataclasses.replace(spec, **{self.hook: spy})
