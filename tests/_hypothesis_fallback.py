"""Minimal stand-in for ``hypothesis`` when the real package is absent.

The test suite uses a small slice of the hypothesis API (``given`` /
``example`` / ``settings`` / a handful of strategies). Some execution environments for
this repo cannot install third-party packages, so ``tests/conftest.py``
installs this deterministic fallback into ``sys.modules`` *only when the
real library is missing*. With hypothesis installed (as in CI, see
``pyproject.toml``), the real shrinking/coverage engine is used and this
file is inert.

The fallback draws ``max_examples`` pseudo-random examples from a
per-test seeded RNG — no shrinking, but the same property assertions run
over the same kinds of inputs, so a regression still fails the suite.
"""

from __future__ import annotations

import functools
import sys
import types
import zlib

import numpy as np


class _Strategy:
    def __init__(self, draw):
        self._draw = draw

    def example(self, rng: np.random.Generator):
        return self._draw(rng)


def integers(min_value: int, max_value: int) -> _Strategy:
    return _Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))


def booleans() -> _Strategy:
    return _Strategy(lambda rng: bool(rng.integers(0, 2)))


def floats(min_value=-1e6, max_value=1e6, **_ignored) -> _Strategy:
    return _Strategy(
        lambda rng: float(rng.uniform(min_value, max_value)))


def sampled_from(seq) -> _Strategy:
    seq = list(seq)
    return _Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])


def tuples(*elems: _Strategy) -> _Strategy:
    return _Strategy(lambda rng: tuple(e.example(rng) for e in elems))


def lists(elem: _Strategy, *, min_size: int = 0,
          max_size: int = 20) -> _Strategy:
    def draw(rng):
        n = int(rng.integers(min_size, max_size + 1))
        return [elem.example(rng) for _ in range(n)]
    return _Strategy(draw)


def settings(max_examples: int = 10, deadline=None, **_ignored):
    def deco(fn):
        fn._fallback_max_examples = max_examples
        return fn
    return deco


def example(*drawn):
    """Pin one explicit example; ``given`` runs it before the draws."""
    def deco(fn):
        fn.__dict__.setdefault("_fallback_examples", []).append(drawn)
        return fn
    return deco


def given(*strategies: _Strategy):
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            for drawn in getattr(wrapper, "_fallback_examples", ()):
                fn(*args, *drawn, **kwargs)
            n = getattr(wrapper, "_fallback_max_examples", 10)
            # Deterministic per-test stream: same examples every run.
            seed = zlib.crc32(fn.__qualname__.encode())
            rng = np.random.default_rng(seed)
            for _ in range(n):
                drawn = tuple(s.example(rng) for s in strategies)
                fn(*args, *drawn, **kwargs)
        # pytest resolves fixtures from the *unwrapped* signature; hide it
        # so drawn parameters are not mistaken for fixtures.
        del wrapper.__wrapped__
        return wrapper
    return deco


def install() -> None:
    """Register the fallback as ``hypothesis`` / ``hypothesis.strategies``."""
    mod = types.ModuleType("hypothesis")
    mod.example = example
    mod.given = given
    mod.settings = settings
    strategies = types.ModuleType("hypothesis.strategies")
    for name in ("integers", "booleans", "floats", "sampled_from", "tuples",
                 "lists"):
        setattr(strategies, name, globals()[name])
    mod.strategies = strategies
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strategies
