"""Deterministic fallback for the optional ``hypothesis`` dev dependency.

Tier-1 must collect and run without optional packages.  When hypothesis is
installed we re-export it untouched; otherwise a minimal deterministic
stand-in runs each ``@given`` test over a fixed set of seeded examples
(seeds are constants, so failures reproduce exactly).

Only the API surface this test-suite uses is implemented:
``st.integers`` / ``st.sampled_from`` / ``st.composite``,
``hypothesis.given`` / ``hypothesis.example`` / ``hypothesis.settings`` /
``hypothesis.HealthCheck``.
Unknown ``settings`` kwargs (deadline, derandomize, suppress_health_check,
...) are accepted and ignored.
"""
from __future__ import annotations

try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    import functools
    import inspect
    import types

    import numpy as np

    class _Strategy:
        """A strategy is just a sampler: rng -> value."""

        def __init__(self, sample):
            self.sample = sample

    def _integers(lo, hi):
        return _Strategy(lambda rng: int(rng.integers(lo, hi + 1)))

    def _sampled_from(options):
        options = list(options)
        return _Strategy(lambda rng: options[int(rng.integers(len(options)))])

    def _composite(fn):
        def build(*args, **kw):
            def sample(rng):
                return fn(lambda s: s.sample(rng), *args, **kw)
            return _Strategy(sample)
        return build

    st = types.SimpleNamespace(
        integers=_integers, sampled_from=_sampled_from, composite=_composite)

    def _settings(max_examples=10, **_ignored):
        def deco(fn):
            fn._compat_max_examples = max_examples
            return fn
        return deco

    def _example(*vals):
        """Pin an explicit example; place it below ``@given``."""
        def deco(fn):
            fn._compat_examples = [vals, *getattr(fn, "_compat_examples", [])]
            return fn
        return deco

    def _given(*strategies):
        def deco(fn):
            n = getattr(fn, "_compat_max_examples", 10)
            pinned = getattr(fn, "_compat_examples", [])

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                for vals in pinned:
                    fn(*args, *vals, **kwargs)
                for i in range(n):
                    rng = np.random.default_rng(0xC0FFEE + 7919 * i)
                    vals = [s.sample(rng) for s in strategies]
                    fn(*args, *vals, **kwargs)

            # Hide the strategy-supplied trailing params from pytest's
            # fixture resolution (real hypothesis does the same).
            sig = inspect.signature(fn)
            params = list(sig.parameters.values())[:-len(strategies)]
            wrapper.__signature__ = sig.replace(parameters=params)
            del wrapper.__wrapped__
            return wrapper
        return deco

    class _HealthCheck:
        too_slow = "too_slow"

    hypothesis = types.SimpleNamespace(
        given=_given, example=_example, settings=_settings,
        HealthCheck=_HealthCheck)
