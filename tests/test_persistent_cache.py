"""Persistent on-disk compilation cache + calibration (core.compilecache /
core.calibrate).

The disk cache is only sound if the shape-class signatures serialize
IDENTICALLY across processes — a repr that drifts (dict ordering, object
identity leaking into a key component, a dataclass growing an unstable
field) would silently turn every cross-process lookup into a miss.  The
golden-file test pins the current serializations
(``tests/golden/persistent_cache_keys.json``) and the subprocess test
round-trips them through a fresh interpreter.  Regenerate the golden file
after an INTENTIONAL key change with::

    PYTHONPATH=src python tests/test_persistent_cache.py --write-golden
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys

import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "persistent_cache_keys.json")


# ---------------------------------------------------------------------------
# key construction (shared by the in-process tests, the subprocess child,
# and the golden-file writer)
# ---------------------------------------------------------------------------


def engine_key_repr() -> str:
    """stable_repr of ``shape_class_key`` for a fixed engine cell."""
    from repro.core import compilecache
    from repro.core.simulate import shape_class_key
    from repro.experiments.runner import to_sim_cfg
    from repro.experiments.scenario import Scenario

    s = Scenario(sync="bsp", n_workers=4, steps=8, compressor="qsgd",
                 compressor_kwargs={"levels": 4}, error_feedback=True)
    return compilecache.stable_repr(shape_class_key(to_sim_cfg(s)))


def bundle_key_repr() -> str:
    """stable_repr of ``bundle_cache_key`` for a fixed trainer cell —
    built exactly the way ``build_bundle`` derives it, WITHOUT compiling."""
    from repro.core import aggregate, compilecache
    from repro.core.types import bundle_spec
    from repro.experiments.scenario import Scenario
    from repro.experiments.trainer_substrate import (
        make_tiny_workload, to_comm_config)
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as T
    from repro.optim.optimizers import momentum_sgd
    from repro.train.steps import bundle_cache_key, local_abstract

    s = Scenario(sync="bsp", n_workers=2, steps=8, compressor="qsgd",
                 compressor_kwargs={"levels": 4}, error_feedback=True)
    comm = to_comm_config(s)
    cfg, shape, _ = make_tiny_workload()
    mesh = make_test_mesh(data=1, model=1)
    spec = bundle_spec(comm)
    param_abs, param_specs, _ = T.abstract_params(cfg, mesh.shape["model"])
    plan = aggregate.make_bucket_plan(comm, local_abstract(param_abs, param_specs, mesh))
    key = bundle_cache_key(cfg, mesh, spec, plan, momentum_sgd(0.0), shape)
    return compilecache.stable_repr(key)


def compute_key_reprs() -> dict:
    from repro.core import compilecache

    e, b = engine_key_repr(), bundle_key_repr()
    return {
        "engine_key": e,
        "bundle_key": b,
        "engine_digest": compilecache.stable_digest("engine", e),
        "bundle_digest": compilecache.stable_digest("bundle", b),
    }


@contextlib.contextmanager
def isolated_cache(path):
    """Point the persistent cache at ``path`` (through its one variable) for
    the duration; restore the session-level dir (conftest's tmpdir) and
    zeroed counters after."""
    from repro.core import compilecache

    prev = os.environ[compilecache.ENV_VAR]
    os.environ[compilecache.ENV_VAR] = str(path)
    compilecache.configure()
    compilecache.reset_stats()
    try:
        yield compilecache
    finally:
        os.environ[compilecache.ENV_VAR] = prev
        compilecache.configure()
        compilecache.reset_stats()


# ---------------------------------------------------------------------------
# manifest mechanics
# ---------------------------------------------------------------------------


def test_record_compile_miss_then_hit(tmp_path):
    with isolated_cache(tmp_path) as cc:
        key = ("bsp", 4, 8, True, "qsgd", False, "reset")
        assert cc.record_compile("engine", key) is False  # first build: miss
        assert cc.record_compile("engine", key) is True  # later process: hit
        assert cc.record_compile("bundle", key) is False  # kinds are disjoint
        st = cc.stats("engine")
        assert (st.hits, st.misses) == (1, 1)
        assert st.as_dict() == {"hits": 1, "misses": 1, "dir": str(tmp_path)}
        manifest = os.path.join(str(tmp_path), cc.MANIFEST_DIRNAME)
        assert len(os.listdir(manifest)) == 2  # one entry per (kind, key)


def test_cache_dir_follows_the_variable_else_the_checkout(monkeypatch, tmp_path):
    """One variable places the cache; without it the cache sits at the
    checkout's fixed .jax_cache, and jax's own cache follows either way."""
    import jax

    from repro.core import compilecache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv(compilecache.ENV_VAR)
    assert compilecache.cache_dir() == os.path.join(repo, ".jax_cache")
    monkeypatch.setenv(compilecache.ENV_VAR, str(tmp_path))
    assert compilecache.cache_dir() == str(tmp_path)
    with isolated_cache(tmp_path / "moved") as cc:
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "moved")
        assert cc.exec_dir("bundle", ("k",)).startswith(
            os.path.join(str(tmp_path / "moved"), cc.EXEC_DIRNAME))


def test_stats_surfaced_on_both_cache_stat_objects(tmp_path):
    from repro.core.simulate import engine_cache_stats
    from repro.train.steps import bundle_cache_stats

    with isolated_cache(tmp_path) as cc:
        cc.record_compile("engine", ("e",))
        cc.record_compile("bundle", ("b",))
        cc.record_compile("bundle", ("b",))
        e = engine_cache_stats().persistent_cache
        b = bundle_cache_stats().persistent_cache
        assert e == {"hits": 0, "misses": 1, "dir": str(tmp_path)}
        assert b == {"hits": 1, "misses": 1, "dir": str(tmp_path)}


def test_digest_pins_source_fingerprint(monkeypatch):
    """Editing the repro package's sources must change every manifest /
    executable digest: the shape-class key names WHICH program a cell needs,
    the source hash pins WHAT it computes — without it a warm cache dir
    would silently replay pre-edit executables."""
    from repro.core import compilecache as cc

    key = ("k",)
    real = cc.source_fingerprint()
    assert real and real != "0" * 16
    before = cc.stable_digest("engine", key)
    monkeypatch.setattr(cc, "_SOURCE_HASH", "0" * 16)
    after = cc.stable_digest("engine", key)
    assert before != after


def test_cache_false_build_never_manifested(tmp_path):
    """cache=False is the per-cell rebuild baseline: it gets exec_dir=None,
    so no executable blobs land on disk — it must not seed the manifest
    either, or a later process would claim a persistent hit
    (trace+deserialize, no compile) it cannot actually serve."""
    from repro.experiments.scenario import Scenario
    from repro.experiments.trainer_substrate import (
        make_tiny_workload, to_comm_config)
    from repro.launch.mesh import make_test_mesh
    from repro.optim.optimizers import momentum_sgd
    from repro.train.steps import build_bundle, bundle_cache_clear

    s = Scenario(sync="bsp", n_workers=2, steps=8, compressor="qsgd",
                 compressor_kwargs={"levels": 4}, error_feedback=True)
    comm = to_comm_config(s)
    cfg, shape, _ = make_tiny_workload()
    mesh = make_test_mesh(data=1, model=1)
    opt = momentum_sgd(0.0)

    with isolated_cache(tmp_path) as cc:
        bundle_cache_clear()
        try:
            build_bundle(cfg, mesh, comm, opt, shape, cache=False)
            st = cc.stats("bundle")
            assert (st.hits, st.misses) == (0, 0)
            manifest = os.path.join(str(tmp_path), cc.MANIFEST_DIRNAME)
            assert os.listdir(manifest) == []
            build_bundle(cfg, mesh, comm, opt, shape, cache=True)
            st = cc.stats("bundle")
            assert (st.hits, st.misses) == (0, 1)
            assert len(os.listdir(manifest)) == 1
        finally:
            bundle_cache_clear()


# ---------------------------------------------------------------------------
# key-serialization stability
# ---------------------------------------------------------------------------


def test_key_serializations_match_golden():
    """The checked-in golden reprs ARE the cross-process cache contract: a
    diff here means every existing persistent cache silently stops hitting
    (or, worse, a knob that should split classes stopped doing so).  If the
    change is intentional, regenerate (see module docstring)."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert engine_key_repr() == golden["engine_key"]
    assert bundle_key_repr() == golden["bundle_key"]


def test_key_digests_stable_across_processes(tmp_path):
    """Subprocess round-trip: a fresh interpreter derives byte-identical key
    serializations and manifest digests (digests also pin the jax/jaxlib +
    device fingerprint, equal between parent and child on one machine)."""
    here = compute_key_reprs()
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]); "
        "import test_persistent_cache as m; "
        "print(json.dumps(m.compute_key_reprs()))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code, os.path.dirname(__file__)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, check=True, timeout=240)
    there = json.loads(out.stdout.strip().splitlines()[-1])
    assert there == here


def test_traced_sibling_hits_structural_sibling_misses(tmp_path):
    """The disk cache must key at shape-class granularity: after the
    in-memory registry is dropped, a TRACED-knob sibling (same class,
    different qsgd levels + lr) re-derives the same manifest entry — a
    persistent hit — while a STRUCTURAL sibling (different sync scheme)
    misses and compiles fresh."""
    from repro.experiments.runner import (
        _run_training_scenarios, training_shape_key)
    from repro.experiments.scenario import Scenario, expand
    from repro.core.simulate import engine_cache_clear

    def cell(**kw):
        base = dict(sync="bsp", n_workers=4, steps=3, compressor="qsgd",
                    compressor_kwargs={"levels": 4}, error_feedback=True,
                    lr=0.05)
        return expand([Scenario(**{**base, **kw})], substrate="training")[0]

    a = cell()
    traced_sib = cell(compressor_kwargs={"levels": 16}, lr=0.1)
    structural_sib = cell(sync="local")
    assert training_shape_key(a) == training_shape_key(traced_sib)
    assert training_shape_key(a) != training_shape_key(structural_sib)

    with isolated_cache(tmp_path) as cc:
        engine_cache_clear()
        _run_training_scenarios([a], replicas=1)
        st = cc.stats("engine")
        assert (st.hits, st.misses) == (0, 1)

        engine_cache_clear()  # force a fresh build: next trace asks the disk
        _run_training_scenarios([traced_sib], replicas=1)
        st = cc.stats("engine")
        assert (st.hits, st.misses) == (1, 1), "traced sibling must hit"

        engine_cache_clear()
        _run_training_scenarios([structural_sib], replicas=1)
        st = cc.stats("engine")
        assert (st.hits, st.misses) == (1, 2), "structural sibling must miss"
        engine_cache_clear()


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


def test_fit_alpha_beta_recovers_exact_line():
    from repro.core.calibrate import fit_alpha_beta

    alpha, beta = 3e-4, 2e-9
    xs = [1e3, 1e4, 1e5, 1e6]
    ys = [alpha + beta * x for x in xs]
    a, b = fit_alpha_beta(xs, ys)
    assert a == pytest.approx(alpha, rel=1e-6)
    assert b == pytest.approx(beta, rel=1e-6)
    with pytest.raises(ValueError):
        fit_alpha_beta([1.0], [1.0])


def test_fit_alpha_beta_clamps_nonnegative():
    from repro.core.calibrate import fit_alpha_beta

    # decreasing times vs bytes: noise, not negative bandwidth
    a, b = fit_alpha_beta([1e3, 1e6], [2e-3, 1e-3])
    assert a >= 0 and b > 0


def test_profile_save_load_and_active_registry(tmp_path):
    from repro.core.calibrate import (
        CalibrationProfile, active_launch, active_link, get_active, set_active)
    from repro.core.costmodel import Link

    p = CalibrationProfile(alpha=1e-4, beta=2e-10, t_launch=5e-5,
                           t_step_dense=0.01, meta={"note": "test"})
    path = p.save(str(tmp_path / "calibration.json"))
    q = CalibrationProfile.load(path)
    assert q.as_dict() == p.as_dict()
    assert q.link() == Link(alpha=1e-4, beta=2e-10)

    default = Link()
    assert set_active(q) is None
    try:
        assert get_active() is q
        assert active_link(default) == q.link()
        assert active_launch() == pytest.approx(5e-5)
    finally:
        set_active(None)
    assert active_link(default) is default
    assert active_launch() == 0.0


def test_profile_persists_next_to_cache_dir(tmp_path):
    from repro.core import calibrate

    with isolated_cache(tmp_path):
        path = calibrate.default_path()
        assert path == str(tmp_path / "calibration.json")
        assert calibrate.load_default() is None
        calibrate.CalibrationProfile(
            alpha=1e-4, beta=1e-10, t_launch=1e-5, t_step_dense=None).save(path)
        got = calibrate.load_default()
        assert got is not None and got.t_step_dense is None


def test_load_default_skips_foreign_fingerprint(tmp_path):
    """run.py auto-adopts <cache_dir>/calibration.json — a profile fitted
    under a different fingerprint (other platform / device count, e.g. a
    shared cache dir) must be skipped, not silently miscalibrate every
    predicted column.  A profile without stored fingerprint (explicitly
    constructed, pre-upgrade file) is still adopted."""
    from repro.core import calibrate, compilecache

    with isolated_cache(tmp_path):
        path = calibrate.default_path()
        fp = list(compilecache.cache_fingerprint())
        foreign = fp[:-1] + [fp[-1] + 1]  # same machine, other device count
        calibrate.CalibrationProfile(
            alpha=1e-4, beta=1e-10, t_launch=1e-5, t_step_dense=None,
            meta={"fingerprint": foreign}).save(path)
        assert calibrate.load_default() is None
        calibrate.CalibrationProfile(
            alpha=1e-4, beta=1e-10, t_launch=1e-5, t_step_dense=None,
            meta={"fingerprint": fp}).save(path)
        got = calibrate.load_default()
        assert got is not None and got.meta["fingerprint"] == fp


def test_predict_trainer_step_uses_calibrated_constants():
    """Uncalibrated: the datasheet Scenario constants (compute_time=1.0 s).
    Calibrated: the profile's fitted compute/link/launch terms — for a real
    machine (ms-scale steps) the two predictions differ by orders of
    magnitude, which is exactly the rel-err gap BENCH_coldstart records."""
    from repro.core.calibrate import CalibrationProfile, set_active
    from repro.experiments.scenario import Scenario
    from repro.experiments.trainer_substrate import predict_trainer_step

    s = Scenario(sync="bsp", n_workers=4, steps=8, compressor="qsgd",
                 compressor_kwargs={"levels": 4}, error_feedback=True)
    kw = dict(data_par=4, payload_round=1e6, n_buckets=2)
    before = predict_trainer_step(s, **kw)
    assert before["calibrated"] == 0.0
    assert before["step_time_s"] >= s.compute_time  # datasheet compute term

    prof = CalibrationProfile(alpha=1e-5, beta=1e-10, t_launch=2e-4,
                              t_step_dense=0.004)
    after = predict_trainer_step(s, **kw, profile=prof)
    assert after["calibrated"] == 1.0
    # compute term now the measured dense step; comm includes launch * msgs
    expected_comm = (2 * 3 * 1e-5 + 2 * 3 / 4 * 1e-10 * 1e6) + 2e-4 * 2
    assert after["comm_time_s"] == pytest.approx(expected_comm, rel=1e-9)
    assert after["step_time_s"] == pytest.approx(0.004 + expected_comm, rel=1e-9)

    set_active(prof)
    try:
        active = predict_trainer_step(s, **kw)
    finally:
        set_active(None)
    assert active == after  # active profile == explicit profile


def test_simulate_schedule_launch_term():
    from repro.core.schedule import LayerSpec, simulate_schedule

    layers = [LayerSpec("l0", grad_bytes=1e6, backward_time=0.01),
              LayerSpec("l1", grad_bytes=1e6, backward_time=0.01)]
    base = simulate_schedule(layers, n_workers=4, mode="sequential")
    lifted = simulate_schedule(layers, n_workers=4, mode="sequential",
                               launch=1e-3)
    # default launch=0.0 is bit-identical to the pre-calibration model;
    # a positive launch charges exactly once per message
    assert lifted["total_comm_time"] == pytest.approx(
        base["total_comm_time"] + 1e-3 * base["n_messages"])


def _main(argv: list[str]) -> int:
    if "--write-golden" in argv:
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        reprs = compute_key_reprs()
        with open(GOLDEN, "w") as f:
            json.dump({"engine_key": reprs["engine_key"],
                       "bundle_key": reprs["bundle_key"]}, f, indent=1)
        print(f"wrote {GOLDEN}")
        return 0
    print(json.dumps(compute_key_reprs(), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main(sys.argv[1:]))
