"""Persistent on-disk compiled-program cache under both compilation layers.

Two cooperating pieces:

* **jax's persistent compilation cache** — ``configure()`` points jax's cache
  at :func:`cache_dir` and caches every program, so every XLA executable
  compiled by this process (engine class programs from
  ``simulate_training_classbatch``, trainer bundles from ``build_bundle``)
  is serialized under ``path`` and deserialized by later processes instead
  of re-compiled.  jax keys those entries by a hash of the optimized HLO +
  compile options, which is opaque to the repo's taxonomy.

* **a repro-level manifest** next to it (``<path>/repro-manifest/``) keyed by
  the repo's own shape-class signatures — the engine cache key built on
  ``shape_class_key`` and the trainer ``bundle_cache_key`` — plus the
  jax/jaxlib version and device fingerprint (a cache produced by a different
  jax or device kind would never hit at the XLA layer, so the manifest must
  not claim it would) plus a hash of the ``repro`` package's own sources
  (the shape-class key names WHICH step program a cell needs, not what the
  program computes — without the source hash, editing compressor math or
  gradient logic would leave the key unchanged and a warm cache dir would
  silently deserialize the OLD executable and its stale wire artifact).
  ``record_compile`` is called exactly when an
  in-memory registry MISSES and builds fresh; if the manifest already holds
  the signature, some previous process compiled this shape class and the
  build is a persistent **hit** (trace + deserialize, no XLA compile),
  otherwise a persistent **miss**.  That makes cache effectiveness
  observable at shape-class granularity in ``engine_cache_stats()`` /
  ``bundle_cache_stats()`` and every benchmark lane's ``--emit-json``
  record, instead of only as wall-clock.

* **serialized AOT executables** (``<path>/repro-exec/<digest>/``, one file
  per step program) — jax's cache still pays tracing + lowering on every
  process, which bounds the warm speedup at ~2x for the trainer bundles.
  ``repro.train.steps`` additionally AOT-compiles each bundle step from its
  build-time avals and serializes the whole executable
  (``jax.experimental.serialize_executable``), so a warm process
  deserializes and runs with NO tracing at all; the build-time wire
  artifact rides along (``wire.json``) so warm builds skip the abstract
  wire traces too.  Digests share ``stable_digest`` with the manifest, so
  the fingerprint (jax version, device kind/count) gates portability.

Nothing here imports jax at module load — configuration happens lazily so
the ``experiments/run.py`` set-XLA_FLAGS-before-jax contract is preserved.
There is one cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when it is set
(jax reads the same variable), otherwise the fixed ``.jax_cache`` directory
of the checkout.  Entry points call ``configure()`` before their first
compile; the manifest and executable paths configure lazily.
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the cache directory when ``ENV_VAR`` is unset: ``<checkout>/.jax_cache``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")
MANIFEST_DIRNAME = "repro-manifest"
EXEC_DIRNAME = "repro-exec"

_CONFIGURED: str | None = None  # the directory jax's cache points at


@dataclass
class PersistentCacheStats:
    """Per-layer (``engine`` / ``bundle``) persistent-cache counters.

    ``hits``/``misses`` count fresh in-memory-registry builds whose shape
    signature was / was not already in the on-disk manifest; in-memory
    registry hits never consult the disk and are counted by the existing
    ``EngineStats``/``BundleCacheStats`` counters instead.
    """

    hits: int = 0
    misses: int = 0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses, "dir": cache_dir()}


_STATS: dict[str, PersistentCacheStats] = {}


def stats(kind: str) -> PersistentCacheStats:
    return _STATS.setdefault(kind, PersistentCacheStats())


def reset_stats() -> None:
    _STATS.clear()


def cache_fingerprint() -> tuple:
    """jax/jaxlib versions + device platform/kind: entries are only portable
    within one fingerprint (a different jax or backend re-compiles anyway).
    Deliberately environment-only — the source hash lives in
    :func:`source_fingerprint` instead, so calibration profiles (machine
    constants, source-independent) can pin this without churning on every
    code edit."""
    import jax

    try:
        import jaxlib

        jaxlib_v = jaxlib.__version__
    except ImportError:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_v = "?"
    dev = jax.devices()[0]
    return (jax.__version__, jaxlib_v, dev.platform, dev.device_kind, jax.device_count())


_SOURCE_HASH: str | None = None


def source_fingerprint() -> str:
    """sha256 over the ``repro`` package's own ``.py`` sources (sorted
    relative path + contents), cached per process.  Part of every manifest /
    executable digest: the shape-class keys name WHICH program a cell needs,
    this pins WHAT the program computes, so editing step semantics (compressor
    math, gradient logic, wire accounting) invalidates serialized executables
    instead of silently replaying stale ones from a warm cache dir."""
    global _SOURCE_HASH
    if _SOURCE_HASH is None:
        import repro

        # namespace package (no __init__.py): the source roots live in
        # __path__, not __file__
        pkg_dirs = sorted(os.path.abspath(p) for p in repro.__path__)
        h = hashlib.sha256()
        for pkg_dir in pkg_dirs:
            for root, dirs, files in os.walk(pkg_dir):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                for fn in sorted(files):
                    if not fn.endswith(".py"):
                        continue
                    path = os.path.join(root, fn)
                    h.update(os.path.relpath(path, pkg_dir).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
        _SOURCE_HASH = h.hexdigest()[:16]
    return _SOURCE_HASH


def stable_repr(key) -> str:
    """The serialization contract for manifest keys: ``repr`` of the cache-key
    tuple.  Every component of both layers' keys is primitives / primitive
    dataclasses / tuples (guarded by tests/test_persistent_cache.py golden
    files), so the repr is identical across processes."""
    return repr(key)


def stable_digest(kind: str, key) -> str:
    payload = repr((kind, cache_fingerprint(), source_fingerprint(),
                    stable_repr(key)))
    return hashlib.sha256(payload.encode()).hexdigest()


def cache_dir() -> str:
    """The one cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else :data:`DEFAULT_DIR`."""
    return os.path.abspath(os.environ.get(ENV_VAR) or DEFAULT_DIR)


def configure() -> str:
    """Point jax's persistent compilation cache at :func:`cache_dir` and make
    it keep every program (the repo's programs are small but re-compiled by
    every process, which the default 1 s / size thresholds would skip).

    Imports jax — call only after any XLA_FLAGS setup.  Re-resolves the
    variable on every call, so a caller that moves it calls this again.
    Returns the directory."""
    global _CONFIGURED
    path = cache_dir()
    if path != _CONFIGURED:
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        os.makedirs(os.path.join(path, MANIFEST_DIRNAME), exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()  # re-open at the (new) directory
        _CONFIGURED = path
    return path


def exec_dir(kind: str, key) -> str | None:
    """Directory for one shape class's serialized AOT executables
    (``<cache_dir>/repro-exec/<digest>/``) — jax's own cache skips only the
    XLA backend compile; the executables serialized here
    (``jax.experimental.serialize_executable``) also skip tracing/lowering
    on warm processes."""
    return os.path.join(configure(), EXEC_DIRNAME, stable_digest(kind, key))


def record_compile(kind: str, key) -> bool:
    """Called on a fresh in-memory-registry build.  Returns True iff the
    signature was already in the manifest (persistent hit)."""
    d = configure()
    st = stats(kind)
    path = os.path.join(d, MANIFEST_DIRNAME, stable_digest(kind, key) + ".json")
    if os.path.exists(path):
        st.hits += 1
        return True
    st.misses += 1
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"kind": kind, "key": stable_repr(key),
                   "fingerprint": list(cache_fingerprint()),
                   "source": source_fingerprint()}, f)
    os.replace(tmp, path)  # atomic: concurrent processes race benignly
    return False


def record(kind: str) -> dict:
    """The ``persistent_cache`` block for --emit-json records."""
    return stats(kind).as_dict()
