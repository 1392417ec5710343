"""Shape-class batched sweep engine: grouping, compile counting, batched vs
per-cell equivalence across every shape class of the 45-cell perf matrix,
measured wire bits for data-dependent compressors, structural-envelope
batching (powersgd rank), and the trainer CLI lane's device selection."""

import numpy as np
import pytest

from repro.core.compression import get_compressor
from repro.core.compression.base import (
    batch_param_values,
    merge_representative,
    shape_fingerprint,
)
from repro.core.simulate import (
    SimCfg,
    engine_cache_clear,
    engine_cache_stats,
    quadratic_problem,
    shape_class_key,
    simulate_training_batch,
    simulate_training_classbatch,
    simulate_training_reference,
)
from repro.experiments import Scenario
from repro.experiments.runner import (
    measure_sweep_speedup,
    run_scenario,
    run_scenarios,
    sweep_matrix_45,
    training_shape_key,
)


# ---------------------------------------------------------------------------
# Shape-class grouping.
# ---------------------------------------------------------------------------


def test_45_cell_matrix_spans_5_shape_classes():
    """The perf matrix varies only traced values inside each scheme: 45
    cells collapse to one shape class per sync/topology scheme."""
    matrix = sweep_matrix_45()
    assert len(matrix) == 45
    assert len({training_shape_key(s) for s in matrix}) == 5


def test_value_knobs_stay_out_of_the_shape_key():
    base = Scenario(sync="ssp", arch="ps", compressor="qsgd",
                    compressor_kwargs={"levels": 16}, error_feedback=True)
    same = [base.replace(lr=0.1),
            base.replace(staleness=2),
            base.replace(compressor_kwargs={"levels": 4}),
            base.replace(grad_noise=0.3),
            # problem data (A/b, x*) is traced through the Problem protocol,
            # so cells differing only in problem seed share the compile
            base.replace(seed=1)]
    assert {training_shape_key(s) for s in same} == {training_shape_key(base)}
    # structure changers split the class
    assert training_shape_key(base.replace(sync="bsp")) != training_shape_key(base)
    assert training_shape_key(
        base.replace(compressor="terngrad", compressor_kwargs=())
    ) != training_shape_key(base)
    assert training_shape_key(base.replace(error_feedback=False)) != training_shape_key(base)
    assert training_shape_key(base.replace(objective="logistic")) != training_shape_key(base)


def test_qsgd_kernel_levels_traced_like_jnp_qsgd():
    """The Pallas qsgd kernel takes ``levels`` as a traced (1,1) scalar
    block (mask-style), not a specialization constant: knob-varied cells
    share the fingerprint at both layers, like the jnp ``qsgd``."""
    from repro.core.compression.base import runtime_fingerprint

    assert shape_fingerprint(get_compressor("qsgd_kernel", levels=4)) == \
        shape_fingerprint(get_compressor("qsgd_kernel", levels=16))
    assert runtime_fingerprint(get_compressor("qsgd_kernel", levels=4)) == \
        runtime_fingerprint(get_compressor("qsgd_kernel", levels=16))
    assert shape_fingerprint(get_compressor("qsgd", levels=4)) == \
        shape_fingerprint(get_compressor("qsgd", levels=16))
    with pytest.raises(ValueError, match="int8"):
        batch_param_values(get_compressor("qsgd_kernel", levels=200), 64)


def test_qsgd_kernel_cells_share_one_engine_compile():
    """ROADMAP follow-up: qsgd_kernel cells stop compiling per level — one
    class program serves every levels value, with per-cell results matching
    solo runs (and levels genuinely biting)."""
    problem = quadratic_problem(n_workers=4, seed=0)
    cfgs = [SimCfg(n_workers=4, sync="bsp", steps=8, lr=0.05, seed=2,
                   compressor=get_compressor("qsgd_kernel", levels=lv),
                   error_feedback=True)
            for lv in (2, 16)]
    assert shape_class_key(cfgs[0]) == shape_class_key(cfgs[1])
    engine_cache_clear()
    outs = simulate_training_classbatch(cfgs, problem)
    assert engine_cache_stats().compiles == 1
    for cfg, out in zip(cfgs, outs):
        single = simulate_training_batch(cfg, problem)[0]
        np.testing.assert_allclose(out[0]["loss"], single["loss"],
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(out[0]["bits"], single["bits"], rtol=1e-6)
    # coarser quantization transmits fewer bits and converges differently
    assert outs[0][0]["bits"][-1] < outs[1][0]["bits"][-1]
    assert np.abs(outs[0][0]["loss"] - outs[1][0]["loss"]).max() > 1e-6


# ---------------------------------------------------------------------------
# Compile counting: one trace per shape class.
# ---------------------------------------------------------------------------


def test_sweep_compiles_once_per_shape_class():
    matrix = sweep_matrix_45(steps=4, n_workers=4)
    engine_cache_clear()
    run_scenarios(matrix, "training")
    st = engine_cache_stats()
    assert st.compiles == 5  # == number of shape classes
    # a repeat sweep is all cache hits, zero new traces
    run_scenarios(matrix, "training")
    st = engine_cache_stats()
    assert st.compiles == 5 and st.hits == 5


def test_problem_seeds_share_one_compile():
    """Problem data (quadratic A/b, x*) is traced through the Problem
    protocol: cells differing ONLY in problem seed run in one compiled
    program, and their results still match per-cell runs."""
    matrix = sweep_matrix_45(steps=4, n_workers=4, problem_seeds=(0, 1, 2))
    assert len(matrix) == 135
    assert len({training_shape_key(s) for s in matrix}) == 5
    engine_cache_clear()
    batched = run_scenarios(matrix, "training")
    assert engine_cache_stats().compiles == 5  # not 15
    # a seed-1 cell pulled out of the batch equals its solo run
    idx = next(i for i, s in enumerate(matrix) if s.seed == 1)
    single = run_scenario(matrix[idx], "training")
    np.testing.assert_allclose(batched[idx].series["loss"], single.series["loss"],
                               rtol=2e-4, atol=1e-6)
    # different problem seeds genuinely differ
    other = next(i for i, s in enumerate(matrix)
                 if s.seed == 2 and training_shape_key(s) == training_shape_key(matrix[idx])
                 and s.lr == matrix[idx].lr
                 and s.compressor_kwargs == matrix[idx].compressor_kwargs)
    assert np.abs(batched[idx].series["loss"] - batched[other].series["loss"]).max() > 1e-6


def test_classbatch_rejects_mixed_shape_classes():
    cfgs = [SimCfg(sync="bsp", n_workers=4, steps=4),
            SimCfg(sync="local", n_workers=4, steps=4)]
    with pytest.raises(ValueError, match="shape class"):
        simulate_training_classbatch(cfgs, quadratic_problem(n_workers=4))


# ---------------------------------------------------------------------------
# Batched vs per-cell equivalence (every shape class of the 45-cell matrix).
# ---------------------------------------------------------------------------


def test_batched_matches_percell_across_every_shape_class():
    """One full batched sweep of the 45-cell matrix vs a per-cell run of one
    representative per shape class: same losses / consensus / bits."""
    matrix = sweep_matrix_45(steps=6, n_workers=4)
    batched = run_scenarios(matrix, "training", replicas=2)
    seen = set()
    for s, b in zip(matrix, batched):
        key = training_shape_key(s)
        if key in seen:
            continue
        seen.add(key)
        single = run_scenario(s, "training", replicas=2)
        for k in ("loss", "consensus", "bits"):
            np.testing.assert_allclose(b.series[k], single.series[k],
                                       rtol=2e-4, atol=1e-6, err_msg=f"{s.tag()}/{k}")
    assert len(seen) == 5


def test_batched_cells_match_reference_loop():
    """A mid-matrix cell (non-default lr/levels) pulled out of the batched
    sweep equals the per-step Python-loop reference."""
    from repro.core.simulate import PROBLEMS
    from repro.experiments.runner import to_sim_cfg

    matrix = sweep_matrix_45(steps=8, n_workers=4)
    s = matrix[16]  # local_H8, levels=8, lr=0.05
    res = run_scenarios(matrix, "training")[16]
    problem = PROBLEMS[s.objective](n_workers=s.n_workers, noise=s.grad_noise,
                                    seed=s.seed)
    ref = simulate_training_reference(to_sim_cfg(s), problem=problem)
    np.testing.assert_allclose(res.series["loss"][0], ref["loss"], rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(res.series["bits"][0], ref["bits"], rtol=1e-6)


def test_measure_sweep_speedup_smoke():
    """The BENCH_sweep measurement (tiny extent): compile accounting plus
    batched-vs-percell deviation bounds, without timing assertions."""
    rec = measure_sweep_speedup(sweep_matrix_45(steps=3, n_workers=4))
    assert rec["n_cells"] == 45 and rec["n_shape_classes"] == 5
    assert rec["compiles_batched"] == 5
    assert rec["compiles_percell"] == 45
    assert rec["max_rel_dev_loss"] < 2e-4
    assert rec["max_rel_dev_bits"] < 1e-6


# ---------------------------------------------------------------------------
# Measured wire bits for data-dependent compressors.
# ---------------------------------------------------------------------------


def test_threshold_bits_measured_not_zero():
    """Threshold sparsifiers used to charge 0 bits in-engine (analytic NaN);
    now both engine and reference charge the realized 64 bits/coordinate."""
    cfg = SimCfg(n_workers=4, sync="bsp", steps=10, lr=0.03,
                 compressor=get_compressor("threshold", tau=1e-3), seed=3)
    problem = quadratic_problem(n_workers=4, seed=0)
    eng = simulate_training_batch(cfg, problem)[0]
    ref = simulate_training_reference(cfg, problem=problem)
    assert eng["bits"][-1] > 0
    np.testing.assert_allclose(eng["bits"], ref["bits"], rtol=1e-6)
    # a looser threshold transmits more coordinates -> more bits
    loose = simulate_training_batch(
        SimCfg(n_workers=4, sync="bsp", steps=10, lr=0.03,
               compressor=get_compressor("threshold", tau=0.5), seed=3), problem)[0]
    assert eng["bits"][-1] > loose["bits"][-1] > 0


def test_variance_sparse_bits_measured_in_local_sync_rounds():
    """Local SGD charges the realized round bits at sync steps only."""
    cfg = SimCfg(n_workers=4, sync="local", local_steps=4, steps=8, lr=0.03,
                 compressor=get_compressor("variance_sparse"), seed=1)
    problem = quadratic_problem(n_workers=4, seed=0)
    eng = simulate_training_batch(cfg, problem)[0]
    ref = simulate_training_reference(cfg, problem=problem)
    np.testing.assert_allclose(eng["bits"], ref["bits"], rtol=1e-6)
    assert eng["bits"][-1] > 0
    # bits move only at the two sync steps
    assert np.count_nonzero(np.diff(np.concatenate([[0.0], eng["bits"]]))) == 2


# ---------------------------------------------------------------------------
# Structural envelopes: powersgd rank batches via column masking.
# ---------------------------------------------------------------------------


def test_powersgd_ranks_share_one_class_batch():
    problem = quadratic_problem(n_workers=4, seed=0)
    cfgs = [SimCfg(n_workers=4, sync="bsp", steps=8, lr=0.03, seed=2,
                   compressor=get_compressor("powersgd", rank=r))
            for r in (2, 4)]
    assert shape_class_key(cfgs[0]) == shape_class_key(cfgs[1])
    rep = merge_representative([c.compressor for c in cfgs])
    assert rep.rank == 4
    outs = simulate_training_classbatch(cfgs, problem)
    for cfg, out in zip(cfgs, outs):
        single = simulate_training_batch(cfg, problem)[0]
        np.testing.assert_allclose(out[0]["loss"], single["loss"],
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(out[0]["bits"], single["bits"], rtol=1e-6)


def test_batch_param_values_derive_topk_count():
    assert batch_param_values(get_compressor("topk", ratio=0.1), 64) == {"k": 6.0}
    assert batch_param_values(get_compressor("topk", k=3), 64) == {"k": 3.0}
    assert batch_param_values(None, 64) == {}
    # the int8 wire format bounds traced qsgd levels — fail loudly, not wrap
    with pytest.raises(ValueError, match="int8"):
        batch_param_values(get_compressor("qsgd", levels=200), 64)


# ---------------------------------------------------------------------------
# Trainer CLI lane: automated device-count selection.
# ---------------------------------------------------------------------------


def test_select_trainer_device_count():
    from repro.experiments.trainer_substrate import select_trainer_device_count

    s = Scenario(sync="bsp", n_workers=8)
    assert select_trainer_device_count(s, 8) == (8, "")
    assert select_trainer_device_count(s, 4) == (4, "")
    # largest mesh <= available that divides the global batch (64)
    assert select_trainer_device_count(s, 5) == (4, "")
    dp, why = select_trainer_device_count(s, 1)
    assert dp is None and "device" in why
    # invalid trainer cells carry their violation as the reason
    dp, why = select_trainer_device_count(Scenario(sync="ssp", arch="ps"), 8)
    assert dp is None and "simulate-only" in why


def test_cli_trainer_lane_skips_with_reason_when_underprovisioned(capsys):
    """In-process jax already initialized with 1 device: every cell must be
    skipped with a reason, and the sweep still exits cleanly."""
    from repro.experiments.run import main as cli_main

    rc = cli_main(["--substrate", "trainer", "--grid", "sync=bsp",
                   "--steps", "2", "--workers", "2"])
    assert rc == 0
    err = capsys.readouterr().err
    assert "# skip bsp/ring/none/wfbp" in err


@pytest.mark.slow
def test_cli_trainer_lane_runs_on_forced_devices(tmp_path):
    """Subprocess lane: the CLI forces host devices before jax initializes
    and runs the cells on the real mesh runtime."""
    import json
    import os
    import subprocess
    import sys

    out = tmp_path / "trainer.json"
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.experiments.run", "--substrate", "trainer",
         "--grid", "sync=bsp compressor=none,qsgd:levels=16",
         "--steps", "3", "--workers", "2", "--emit-json", str(out)],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr
    assert "data_par=2" in proc.stderr
    rec = json.loads(out.read_text())
    assert rec["n_cells"] == 2
    # the compressed cell moves less wire than the dense one
    dense, comp = rec["cells"]
    assert comp["measured"]["wire_kb_per_step"] < dense["measured"]["wire_kb_per_step"]
