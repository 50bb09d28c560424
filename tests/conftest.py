"""Shared test configuration.

Hypothesis: an explicit profile with ``deadline=None`` is registered and
loaded for EVERY suite.  CI boxes (and the emulated-8-device jobs) run the
jit-heavy property tests orders of magnitude slower on their first example
than on later ones, which trips Hypothesis's per-example deadline during
shrinking and produces intermittent ``DeadlineExceeded``/``too_slow`` flakes
— wall clock is bounded by ``max_examples`` at each ``@settings`` site
instead.
"""

import pytest

try:  # hypothesis is an optional test dependency (importorskip elsewhere)
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "repro-ci",
        deadline=None,
        derandomize=True,
        suppress_health_check=[HealthCheck.too_slow],
    )
    settings.load_profile("repro-ci")
except ImportError:  # pragma: no cover
    pass


@pytest.fixture(scope="session", autouse=True)
def _child_compile_cache(tmp_path_factory):
    """Entry points started as subprocesses (``cqp_serve``, the serving
    CLI) cache compiles in the checkout unless ``JAX_COMPILATION_CACHE_DIR``
    names a directory; point them at a per-session temporary one.  This
    process imports JAX first, so its own compiles stay uncached."""
    import jax  # noqa: F401  (JAX reads its environment once, at import)

    mp = pytest.MonkeyPatch()
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path_factory.mktemp("jax_cache")))
    yield
    mp.undo()
