"""Test bootstrap: provide a minimal `hypothesis` fallback when the real
package is absent (hermetic CI containers). The stub draws deterministic
pseudo-random examples from the declared strategies — no shrinking, no
database — which keeps the property tests meaningful (N seeded examples,
with the bound edges always included) without the dependency.
"""

import sys

try:  # real hypothesis wins whenever it is installed
    import hypothesis  # noqa: F401
except ImportError:
    import functools
    import hashlib
    import inspect
    import types

    import numpy as _np

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example_for(self, rng, index):
            return self._draw(rng, index)

    def _integers(min_value, max_value):
        def draw(rng, index):
            if index == 0:
                return int(min_value)
            if index == 1:
                return int(max_value)
            return int(rng.integers(min_value, max_value + 1))

        return _Strategy(draw)

    def _floats(min_value, max_value, **_kw):
        def draw(rng, index):
            if index == 0:
                return float(min_value)
            if index == 1:
                return float(max_value)
            return float(rng.uniform(min_value, max_value))

        return _Strategy(draw)

    def _booleans():
        return _Strategy(lambda rng, index: bool(rng.integers(0, 2)))

    def _sampled_from(options):
        opts = list(options)
        return _Strategy(lambda rng, index: opts[int(rng.integers(0, len(opts)))])

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.floats = _floats
    _st.booleans = _booleans
    _st.sampled_from = _sampled_from

    _DEFAULT_MAX_EXAMPLES = 20

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                for drawn in getattr(wrapper, "_stub_examples", ()):
                    try:
                        fn(*args, **drawn, **kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"falsifying example (stub, pinned): {drawn}"
                        ) from e
                n = getattr(wrapper, "_stub_max_examples", _DEFAULT_MAX_EXAMPLES)
                seed = int.from_bytes(
                    hashlib.sha256(fn.__qualname__.encode()).digest()[:4], "big"
                )
                for i in range(n):
                    rng = _np.random.default_rng(seed + i)
                    drawn = {
                        name: s.example_for(rng, i) for name, s in strategies.items()
                    }
                    try:
                        fn(*args, **drawn, **kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"falsifying example (stub, #{i}): {drawn}"
                        ) from e

            wrapper._stub_given = True
            # hide the strategy params from pytest's fixture resolution
            params = [
                p
                for p in inspect.signature(fn).parameters.values()
                if p.name not in strategies
            ]
            wrapper.__signature__ = inspect.Signature(params)
            del wrapper.__wrapped__
            return wrapper

        return deco

    def settings(max_examples=_DEFAULT_MAX_EXAMPLES, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn

        return deco

    def example(**drawn):
        def deco(fn):
            fn._stub_examples = getattr(fn, "_stub_examples", ()) + (drawn,)
            return fn

        return deco

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = given
    _hyp.example = example
    _hyp.settings = settings
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
