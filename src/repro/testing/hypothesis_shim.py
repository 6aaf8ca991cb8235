"""Minimal stand-in for the ``hypothesis`` package.

The test suite uses a small, stable slice of hypothesis — ``@given`` /
``@settings`` / ``@example`` with ``integers`` / ``floats`` / ``lists`` /
``sampled_from`` strategies — but the runtime container does not ship the
real package and the repo rule is "no new installs".  This shim implements
exactly that slice with deterministic pseudo-random example generation so
the property tests still execute (explicit examples, boundary values, then
seeded uniform draws).

It is only registered when the real package is absent (see tests/conftest.py),
so CI with ``requirements-dev.txt`` installed runs genuine hypothesis and
gains shrinking/fuzzing; this shim keeps the same tests *collectable and
meaningful* in the hermetic container.

No shrinking, no database, no ``assume``-style filtering beyond re-drawing.
"""

from __future__ import annotations

import functools
import inspect
import math
import random
import sys
import types
import zlib
from typing import Any, Callable, Sequence

__all__ = ["install_hypothesis_shim"]

_DEFAULT_MAX_EXAMPLES = 100


class _Strategy:
    """A draw rule: boundary examples first, seeded-random afterwards."""

    def __init__(self, boundaries: Sequence[Any], draw: Callable[[random.Random], Any]):
        self._boundaries = list(boundaries)
        self._draw = draw

    def example(self, rng: random.Random, i: int) -> Any:
        if i < len(self._boundaries):
            return self._boundaries[i]
        return self._draw(rng)


def integers(min_value: int = -(2 ** 31), max_value: int = 2 ** 31 - 1) -> _Strategy:
    bounds = [v for v in dict.fromkeys((min_value, max_value, 0, 1, -1))
              if min_value <= v <= max_value]
    return _Strategy(bounds, lambda rng: rng.randint(min_value, max_value))


def floats(min_value: float = -1e9, max_value: float = 1e9, *,
           allow_nan: bool = False, allow_infinity: bool = False,
           width: int = 64) -> _Strategy:
    bounds = [v for v in dict.fromkeys((min_value, max_value, 0.0))
              if min_value <= v <= max_value and math.isfinite(v)]
    return _Strategy(bounds, lambda rng: rng.uniform(min_value, max_value))


def booleans() -> _Strategy:
    return _Strategy([False, True], lambda rng: rng.random() < 0.5)


def just(value: Any) -> _Strategy:
    return _Strategy([value], lambda rng: value)


def sampled_from(elements: Sequence[Any]) -> _Strategy:
    elements = list(elements)
    return _Strategy(elements[:2], lambda rng: rng.choice(elements))


def lists(elements: _Strategy, *, min_size: int = 0,
          max_size: int = 10, unique: bool = False) -> _Strategy:
    def draw(rng: random.Random):
        n = rng.randint(min_size, max_size)
        out = [elements.example(rng, len(elements._boundaries) + k)
               for k in range(n)]
        if unique:
            seen, uniq = set(), []
            for v in out:
                if v not in seen:
                    seen.add(v)
                    uniq.append(v)
            out = uniq
        return out

    first = [elements.example(random.Random(0), i) for i in range(min_size)]
    return _Strategy([first], draw)


def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, deadline=None, **_ignored):
    """Decorator recording run parameters for :func:`given` (order-agnostic)."""

    def deco(fn):
        fn._shim_settings = {"max_examples": max_examples}
        return fn

    return deco


def example(*args: Any, **kwargs: Any):
    """Decorator adding an explicit example, run before the generated ones
    (order-agnostic, like :func:`settings`)."""

    def deco(fn):
        fn._shim_examples = [(args, kwargs)] + getattr(fn, "_shim_examples",
                                                       [])
        return fn

    return deco


def given(*strategies: _Strategy, **kw_strategies: _Strategy):
    """Run the test once per explicit example, then once per generated
    example (boundaries, then random).

    The RNG seed is derived from the test's qualified name, so failures are
    reproducible run-to-run without a shared example database.
    """

    def deco(fn):
        conf = getattr(fn, "_shim_settings", None)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            cfg = getattr(wrapper, "_shim_settings", None) or conf or {}
            n = cfg.get("max_examples", _DEFAULT_MAX_EXAMPLES)
            # wraps() copied fn's examples; later ones land on the wrapper
            for ex_args, ex_kw in getattr(wrapper, "_shim_examples", []):
                fn(*args, *ex_args, **{**kwargs, **ex_kw})
            rng = random.Random(zlib.crc32(fn.__qualname__.encode()))
            for i in range(n):
                vals = [s.example(rng, i) for s in strategies]
                kvals = {k: s.example(rng, i) for k, s in kw_strategies.items()}
                try:
                    fn(*args, *vals, **{**kwargs, **kvals})
                except _SkipExample:
                    continue
                except Exception as e:  # pragma: no cover - reporting aid
                    raise AssertionError(
                        f"falsifying example (shim, example {i}): "
                        f"args={vals} kwargs={kvals}") from e

        # pytest introspects the signature (via __wrapped__) to resolve
        # fixtures — hide the strategy-filled parameters or they would be
        # looked up as fixtures named "x", "shift", …
        if hasattr(wrapper, "__wrapped__"):
            del wrapper.__wrapped__
        sig = inspect.signature(fn)
        params = [p for p in sig.parameters.values()
                  if p.name not in kw_strategies]
        if strategies:
            params = params[: len(params) - len(strategies)]
        wrapper.__signature__ = sig.replace(parameters=params)
        wrapper.hypothesis = types.SimpleNamespace(inner_test=fn)
        return wrapper

    return deco


def assume(condition: bool) -> bool:
    """Best-effort ``assume``: abandon the example by raising SkipExample."""
    if not condition:
        raise _SkipExample
    return True


class _SkipExample(Exception):
    pass


def install_hypothesis_shim() -> None:
    """Register this module as ``hypothesis`` (+``hypothesis.strategies``)
    in ``sys.modules`` if the real package is not importable."""
    try:
        import hypothesis  # noqa: F401  (real package wins)
        return
    except ImportError:
        pass
    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.example = example
    mod.assume = assume
    mod.HealthCheck = types.SimpleNamespace(too_slow=None, data_too_large=None)
    strat = types.ModuleType("hypothesis.strategies")
    for name in ("integers", "floats", "booleans", "lists", "sampled_from",
                 "just"):
        setattr(strat, name, globals()[name])
    mod.strategies = strat
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strat
