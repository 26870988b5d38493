"""Introspection helpers for the Pallas API (the dispatch spy)."""
from __future__ import annotations

from jax.experimental import pallas as _pl


class PallasCallCounter:
    """Counts ``pl.pallas_call`` invocations while a program traces.

    Each invocation is one kernel launch of the compiled program, so the
    count is the dispatch count of whatever traces inside the ``with``
    block (clear the jit cache of the function under test first, or an
    earlier trace hides its calls).  Used by the single-dispatch
    assertions in tests/test_phase_fused.py and the ``apps_fused``
    benchmark rows.
    """

    def __enter__(self):
        self._real = _pl.pallas_call
        self.count = 0

        def spy(*args, **kwargs):
            self.count += 1
            return self._real(*args, **kwargs)

        _pl.pallas_call = spy
        return self

    def __exit__(self, *exc):
        _pl.pallas_call = self._real
        return False


__all__ = ["PallasCallCounter"]
