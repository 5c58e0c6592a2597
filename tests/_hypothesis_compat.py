"""Import hypothesis when available; otherwise provide stand-ins so modules
still collect and their non-property tests run.  A ``@given``-decorated test
becomes a skip instead of an import-time crash on machines without the
dependency."""

import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # clean machine: property tests skip
    HAVE_HYPOTHESIS = False

    class _StrategyStub:
        """st.<anything>(...) placeholder; never drawn from."""

        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

    def settings(*args, **kwargs):
        def deco(fn):
            return fn
        return deco

    def example(*args, **kwargs):
        def deco(fn):
            return fn
        return deco

    def given(*args, **kwargs):
        def deco(fn):
            # zero-arg replacement: the strategy kwargs must not be
            # mistaken for pytest fixtures
            def skipper():
                pytest.skip("hypothesis not installed")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper
        return deco
