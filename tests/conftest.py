"""Shared fixtures. NOTE: no XLA_FLAGS here — unit/smoke tests see the real
single CPU device; multi-device tests run in subprocesses (tests/md/)."""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="session")
def _isolated_persistent_cache(tmp_path_factory):
    """Point the persistent compiled-program cache (JAX_COMPILATION_CACHE_DIR,
    repro.core.compilecache) at a session tmpdir BEFORE any test touches it:
    the suite must never read from — or, worse, clear — a developer's real
    warm cache, and its own writes vanish with the tmpdir.  Subprocess tests
    inherit the override through the environment."""
    import os

    from repro.core import compilecache

    cache_dir = str(tmp_path_factory.mktemp("repro-cache"))
    prev = os.environ.get(compilecache.ENV_VAR)
    os.environ[compilecache.ENV_VAR] = cache_dir
    compilecache.configure()
    yield cache_dir
    if prev is None:
        os.environ.pop(compilecache.ENV_VAR, None)
    else:
        os.environ[compilecache.ENV_VAR] = prev


@pytest.fixture(autouse=True, scope="module")
def _fresh_compile_caches(_isolated_persistent_cache):
    """Compile/build-cache hygiene between test modules: every module starts
    with ZEROED engine and bundle cache counters, so compile-count and
    build-count assertions (test_churn, test_sweep_batched, the benchmark
    smoke tests) measure their OWN cells rather than leftovers from whatever
    module ran before them.  Lazy imports keep collection cheap; modules that
    never touch a cache pay one no-op clear.

    The churn/rejoin resync programs have no cache of their own — the
    structural ``rejoin_policy`` is part of both cache keys (engine
    ``shape_class_key``, trainer ``bundle_spec``), so clearing these two
    covers every compiled resync graph.  The scenario problem cache is
    cleared too: it keys on workload values only, but zeroing it keeps
    per-module memory flat and rules out cross-module aliasing.

    Only IN-MEMORY caches and counters are touched: the persistent on-disk
    cache (isolated to a session tmpdir above) keeps its files — clearing it
    would throw away exactly the cross-process reuse it exists to provide —
    and only its hit/miss counters are zeroed per module."""
    from repro.core import compilecache
    from repro.core.simulate import engine_cache_clear, engine_cache_stats
    from repro.experiments import runner as _runner
    from repro.train.steps import bundle_cache_clear, bundle_cache_stats

    engine_cache_clear()
    bundle_cache_clear()
    _runner._PROBLEM_CACHE.clear()
    compilecache.reset_stats()
    e, b = engine_cache_stats(), bundle_cache_stats()
    assert (e.compiles, e.hits) == (0, 0), f"engine cache not cleared: {e}"
    assert (b.builds, b.hits) == (0, 0), f"bundle cache not cleared: {b}"
    yield
