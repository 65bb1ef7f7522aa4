"""Make `repro` (src layout) and `benchmarks` importable under bare `pytest`,
and provide a minimal `hypothesis` fallback when the real package is absent
(the container does not ship it; tests only use `given` + `settings` +
`st.floats`/`st.integers`). The fallback runs each property test over a
deterministic sample grid — the real hypothesis, when installed, wins.

Also installs a ``threading.excepthook`` so an uncaught exception in a
helper thread FAILS the test that spawned it (the default behavior prints
to stderr and lets join() succeed — a silently half-dead run looks green).
Tests that deliberately crash a bare thread opt out with
``@pytest.mark.allow_thread_exceptions``."""
import os
import random
import sys
import threading
import traceback

import pytest

# (thread name, "Type: msg", formatted traceback) per uncaught exception —
# drained by the autouse fixture below, attributed to the running test.
_THREAD_EXCEPTIONS = []
_ORIG_THREAD_EXCEPTHOOK = threading.excepthook


def _record_thread_exception(args):
    name = args.thread.name if args.thread is not None else "<unknown>"
    _THREAD_EXCEPTIONS.append((
        name,
        f"{args.exc_type.__name__}: {args.exc_value}",
        "".join(traceback.format_exception(
            args.exc_type, args.exc_value, args.exc_traceback)),
    ))
    _ORIG_THREAD_EXCEPTHOOK(args)  # keep the stderr trace for live debugging


threading.excepthook = _record_thread_exception


@pytest.fixture(autouse=True)
def fail_on_thread_exceptions(request):
    """Any exception that escapes a helper thread during a test fails THAT
    test. Attribution is by time window (threads report to the test that was
    running when they died), which is exact for the join-before-assert style
    every threaded suite here uses."""
    start = len(_THREAD_EXCEPTIONS)
    yield
    leaked = _THREAD_EXCEPTIONS[start:]
    del _THREAD_EXCEPTIONS[start:]
    if not leaked:
        return
    if request.node.get_closest_marker("allow_thread_exceptions"):
        return
    detail = "\n".join(
        f"--- thread {name!r}: {head}\n{tb}" for name, head, tb in leaked)
    pytest.fail(
        f"{len(leaked)} uncaught exception(s) in helper threads:\n{detail}",
        pytrace=False,
    )


def pytest_configure(config):
    # The threaded suites pin per-test wall ceilings with
    # ``pytest.mark.timeout`` so a deadlocked barrier/join fails fast
    # instead of eating the whole CI job timeout. pytest-timeout (pinned in
    # requirements-ci.txt) enforces them; when it is absent the marker must
    # still be registered or ``--strict-markers``/warnings choke on it.
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test wall-clock ceiling "
        "(enforced by pytest-timeout when installed)",
    )
    config.addinivalue_line(
        "markers",
        "allow_thread_exceptions: this test deliberately crashes a helper "
        "thread; the thread-excepthook guard must not fail it",
    )
    config.addinivalue_line(
        "markers",
        "slow: compiles a multi-device program in a subprocess (tens of seconds)",
    )

_ROOT = os.path.dirname(os.path.abspath(__file__))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import inspect
    import itertools
    import types

    class _Floats:
        def __init__(self, min_value, max_value):
            self.lo, self.hi = float(min_value), float(max_value)

        def sample(self, rng, k):
            edge = [self.lo, self.hi]
            return edge + [rng.uniform(self.lo, self.hi) for _ in range(max(k - 2, 0))]

    class _Integers:
        def __init__(self, min_value, max_value):
            self.lo, self.hi = int(min_value), int(max_value)

        def sample(self, rng, k):
            edge = [self.lo, self.hi]
            return edge + [rng.randint(self.lo, self.hi) for _ in range(max(k - 2, 0))]

    def _given(**strategies):
        def deco(fn):
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_stub_max_examples", 10)
                n = min(n, 10)  # keep the fallback grid cheap
                rng = random.Random(fn.__qualname__)
                names = sorted(strategies)
                columns = [strategies[name].sample(rng, n) for name in names]
                for row in itertools.islice(zip(*columns), n):
                    fn(*args, **dict(zip(names, row)), **kwargs)

            wrapper.__name__ = fn.__name__
            wrapper.__qualname__ = fn.__qualname__
            wrapper.__doc__ = fn.__doc__
            # hide the strategy params so pytest doesn't look for fixtures
            sig = inspect.signature(fn)
            wrapper.__signature__ = sig.replace(parameters=[
                p for name, p in sig.parameters.items() if name not in strategies
            ])
            return wrapper
        return deco

    def _settings(**kw):
        def deco(fn):
            if "max_examples" in kw:
                fn._stub_max_examples = kw["max_examples"]
            return fn
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.floats = _Floats
    _st.integers = _Integers
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
