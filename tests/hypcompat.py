"""hypothesis with a deterministic fallback.

The property tests use a small slice of the hypothesis API (``@given`` with
``st.integers`` / ``st.floats`` / ``st.booleans`` / ``st.sampled_from``,
pinned ``@example(...)`` cases and
``@settings(max_examples=..., deadline=...)``).  Some deploy environments
(including the CI container) don't ship hypothesis; rather than skipping the
property tests entirely there, this shim replays each property on a fixed
number of deterministically seeded draws.  Shrinking, example databases and
the rest of hypothesis are intentionally out of scope — with hypothesis
installed the real library is used unchanged.

Usage in test modules::

    from hypcompat import example, given, settings, st
"""
from __future__ import annotations

__all__ = ["example", "given", "settings", "st", "HAVE_HYPOTHESIS"]

import functools
import os
import zlib

try:  # pragma: no cover - exercised when hypothesis is installed
    from hypothesis import example, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    import random as _random

    # Draw count for the fallback runner (the real library defaults to 100;
    # property bodies here run whole simulations, so keep this small).
    FALLBACK_EXAMPLES = int(os.environ.get("HYPCOMPAT_EXAMPLES", "3"))

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng):
            return self._draw(rng)

    class _Strategies:
        @staticmethod
        def integers(min_value, max_value):
            # random.Random.randint handles arbitrary precision (the DDC
            # tests draw full u64 ranges, which overflow numpy's int64).
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def floats(min_value, max_value, **_kw):
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: rng.random() < 0.5)

        @staticmethod
        def sampled_from(elements):
            elements = list(elements)
            return _Strategy(lambda rng: rng.choice(elements))

    st = _Strategies()

    def example(**pinned):
        """Pin one case; the fallback runner replays it before its draws."""
        def decorate(test):
            test._hypcompat_examples = ([pinned]
                                        + getattr(test, "_hypcompat_examples",
                                                  []))
            return test

        return decorate

    def given(**strategies):
        def decorate(test):
            @functools.wraps(test)
            def wrapper(*args, **kwargs):
                limit = getattr(wrapper, "_hypcompat_max_examples", FALLBACK_EXAMPLES)
                n = min(limit, FALLBACK_EXAMPLES)
                # Seed from the test name so every run replays the same draws.
                rng = _random.Random(zlib.crc32(test.__qualname__.encode()))
                for pinned in getattr(test, "_hypcompat_examples", []):
                    test(*args, **kwargs, **pinned)
                for _ in range(max(n, 1)):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    test(*args, **kwargs, **drawn)

            # pytest resolves fixtures from inspect.signature, which follows
            # __wrapped__ back to the original property arguments — drop it
            # so the drawn parameters aren't mistaken for fixtures.
            del wrapper.__wrapped__
            wrapper.hypothesis_fallback = True
            return wrapper

        return decorate

    def settings(max_examples=None, deadline=None, **_kw):
        del deadline

        def decorate(test):
            if max_examples is not None:
                test._hypcompat_max_examples = max_examples
            return test

        return decorate
