"""Fallback shims so test modules import cleanly without ``hypothesis``.

Usage in a test module::

    try:
        from hypothesis import example, given, settings, strategies as st
    except ModuleNotFoundError:
        from _hypothesis_stub import example, given, settings, st

When hypothesis is absent, ``@given`` replaces the property test with a
zero-arg skipped stand-in (so the rest of the module still collects and
runs); ``@settings`` and ``@example`` are no-ops and ``st.*`` returns
inert placeholders.
Install the real thing with ``pip install -r requirements-dev.txt``.
"""
import pytest

_SKIP_REASON = "hypothesis not installed (pip install -r requirements-dev.txt)"


class _AnyStrategy:
    """Stands in for ``hypothesis.strategies``: every attribute is a callable
    returning an inert placeholder (never executed)."""

    def __getattr__(self, name):
        return lambda *args, **kwargs: None


st = _AnyStrategy()


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
        @pytest.mark.skip(reason=_SKIP_REASON)
        def _skipped():
            pass

        _skipped.__name__ = fn.__name__
        _skipped.__doc__ = fn.__doc__
        return _skipped
    return deco


def assume(condition):
    """Inert stand-in: only ever reachable from a ``@given`` body, which
    the stub never executes."""
    return bool(condition)
