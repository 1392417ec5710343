"""Chaos lane for the elastic-worker churn axis.

Rejoin protocol (ISSUE 8): both rejoin policies reproduce the churn-free
trajectory at dropout 0; ``pull_avg`` pulls a rejoiner to the live-set
average (charged as a dense download) where ``reset`` lets the scheme's own
mixing absorb it; stateful compressors (powersgd factors, choco mirrors,
EF residuals) resynchronize rather than poison the run; and the previously
rejected trainer combos (parameter-averaging sync, powersgd, choco under
churn) now run end-to-end.

Properties, per ISSUE 6:

* the convergence engine always carries the participation mask, so a cell
  whose churn window never opens reproduces the plain cell bitwise, every
  scheme — one program, an all-ones mask;
* a single surviving worker degenerates to solo SGD on that worker's
  objective (hand-rolled reference loop);
* masked mixing renormalizes over the live set: rows keep summing to 1,
  dead rows freeze to identity, an all-ones mask is a bitwise no-op;
* EF residuals of masked-out workers freeze (trainer substrate);
* a worker that rejoins after a churn window is pulled back to consensus
  and the run keeps converging;
* engine and trainer agree on the churn cell contract: dropout-0 churn
  matches the plain cell, 30% dropout stays finite, and dropout VALUES
  never split a compile/build class (the engine has one class for all
  three; the trainer keeps its masked class apart).
"""

import numpy as np
import pytest

from repro.core.gossip import masked_mixing_matrix, ring_mixing_matrix
from repro.core.simulate import (
    SimCfg,
    engine_cache_stats,
    quadratic_problem,
    simulate_training_batch,
    simulate_training_classbatch,
)

SCHEMES = ("bsp", "local", "ssp", "asp", "gossip")
#: schemes whose guarded (integrity) program at corruption rate 0 reproduces
#: the unguarded one bitwise; the parameter-averaging / mixing schemes fuse
#: their guarded reductions differently and match to rtol
BITWISE = ("bsp", "ssp", "asp")


def _qsgd16():
    from repro.core.compression.base import get_compressor

    return get_compressor("qsgd", levels=16)


def _cell(sync, **kw):
    base = dict(sync=sync, n_workers=4, steps=12, lr=0.03, local_steps=4,
                staleness=2, compressor=_qsgd16(), error_feedback=True, seed=7)
    base.update(kw)
    return SimCfg(**base)


# ---------------------------------------------------------------------------
# all-alive mask == today's path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync", SCHEMES)
def test_all_alive_mask_matches_churn_free(sync):
    """A 90% dropout cell whose churn window starts at the run's length
    (``churn_start == steps``) never opens, so its mask is all ones: the
    window gating on the one masked program makes it bitwise the plain
    cell, every scheme."""
    problem = quadratic_problem(dim=24, n_workers=4, noise=0.1, seed=3)
    plain = simulate_training_batch(_cell(sync), problem)[0]
    closed = simulate_training_batch(
        _cell(sync, dropout_rate=0.9, churn_start=12), problem)[0]
    for k in ("loss", "consensus", "bits"):
        np.testing.assert_array_equal(closed[k], plain[k], err_msg=k)


def test_single_alive_worker_matches_solo_sgd():
    """worker_dropout (0,1,1,1): workers 1-3 never participate, so the
    masked mean (denominator renormalized to 1) IS worker 0's gradient and
    the trajectory is plain GD on worker 0's objective."""
    dim, steps, lr = 16, 25, 0.05
    problem = quadratic_problem(dim=dim, n_workers=4, noise=0.0, seed=1)
    cfg = SimCfg(sync="bsp", n_workers=4, steps=steps, lr=lr,
                 worker_dropout=(0.0, 1.0, 1.0, 1.0), seed=0)
    r = simulate_training_batch(cfg, problem)[0]

    A, b = np.asarray(problem.data["A"]), np.asarray(problem.data["b"])
    x = np.zeros(dim, np.float32)
    ref = []
    for _ in range(steps):
        x = x - lr * (A @ (x - b[0]))
        ref.append(float(problem[1](x)))
    np.testing.assert_allclose(r["loss"], ref, rtol=1e-5, atol=1e-6)
    # the global model updates every row, so consensus is exactly zero and
    # only the one live worker is charged wire bits (dense: 32 bits/coord)
    assert float(np.max(r["consensus"])) == 0.0
    assert float(r["bits"][-1]) == 32.0 * dim * steps


# ---------------------------------------------------------------------------
# renormalization: masked mixing matrices
# ---------------------------------------------------------------------------


def test_masked_mixing_matrix_properties(rng):
    W = ring_mixing_matrix(6, 1.0 / 3.0).astype(np.float32)
    for trial in range(20):
        m = (rng.random(6) > 0.4).astype(np.float32)
        Wm = np.asarray(masked_mixing_matrix(W, m))
        # every row still sums to 1 (redistribute-to-self, not row division)
        np.testing.assert_allclose(Wm.sum(axis=1), 1.0, atol=1e-6)
        assert (Wm >= -1e-7).all()
        for i in range(6):
            if m[i] == 0.0:  # dead row: parameters freeze
                np.testing.assert_array_equal(Wm[i], np.eye(6, dtype=np.float32)[i])
        # live-live off-diagonal weights are untouched, so the live-live
        # block of a symmetric W stays symmetric (mass conserved pairwise)
        live = np.nonzero(m)[0]
        for i in live:
            for j in live:
                if i != j:
                    assert Wm[i, j] == W[i, j]
                    assert Wm[i, j] == Wm[j, i]


def test_masked_mixing_matrix_edge_masks():
    W = ring_mixing_matrix(5, 0.25).astype(np.float32)
    # all-ones mask reproduces W bitwise (the churn-free program's matrix)
    np.testing.assert_array_equal(
        np.asarray(masked_mixing_matrix(W, np.ones(5, np.float32))), W)
    # all-dead round: nobody mixes, everyone freezes
    np.testing.assert_array_equal(
        np.asarray(masked_mixing_matrix(W, np.zeros(5, np.float32))),
        np.eye(5, dtype=np.float32))


# ---------------------------------------------------------------------------
# dropout VALUES are traced: one compile per churn class
# ---------------------------------------------------------------------------


def test_dropout_values_share_one_engine_compile():
    problem = quadratic_problem(dim=16, n_workers=4, noise=0.05, seed=2)
    cells = [SimCfg(sync="bsp", n_workers=4, steps=20, lr=0.05,
                    compressor=_qsgd16(), error_feedback=True,
                    dropout_rate=r, seed=5)
             for r in (0.0, 0.1, 0.3)]
    st = engine_cache_stats()
    c0 = st.compiles
    out = simulate_training_classbatch(cells, problem)
    assert engine_cache_stats().compiles - c0 == 1, "dropout rate split a class"
    for cell_res in out:
        assert np.isfinite(cell_res[0]["loss"]).all()
    # the batched dropout-0 member matches a churn-free standalone run
    plain = simulate_training_batch(
        SimCfg(sync="bsp", n_workers=4, steps=20, lr=0.05,
               compressor=_qsgd16(), error_feedback=True, seed=5),
        problem)[0]
    np.testing.assert_allclose(out[0][0]["loss"], plain["loss"], rtol=1e-5)


# ---------------------------------------------------------------------------
# rejoin: a churn window ends and the stragglers are pulled back in
# ---------------------------------------------------------------------------


def test_rejoin_converges_after_churn_window():
    """Workers 2/3 are dead for steps [0, 30) under local SGD, frozen at x0
    while the live pair advances; once the window closes they rejoin at the
    next sync round — consensus collapses and the loss keeps improving."""
    problem = quadratic_problem(dim=32, n_workers=4, noise=0.0, seed=0)
    cfg = SimCfg(sync="local", n_workers=4, steps=90, lr=0.05, local_steps=5,
                 worker_dropout=(0.0, 0.0, 1.0, 1.0),
                 churn_start=0, churn_end=30, seed=0)
    r = simulate_training_batch(cfg, problem)[0]
    assert np.isfinite(r["loss"]).all()
    # inside the window the frozen pair keeps consensus elevated
    assert r["consensus"][29] > 1e-3
    # final sync after rejoin restores exact consensus and a better loss
    assert r["consensus"][-1] < 1e-5
    assert r["loss"][-1] < r["loss"][29]
    assert r["loss"][-1] < r["loss"][0]


# ---------------------------------------------------------------------------
# trainer substrate: EF freeze + engine/trainer agreement on a churn cell
# ---------------------------------------------------------------------------


def _build_trainer(dropout_rate: float):
    from repro.core.types import CommConfig
    from repro.experiments.trainer_substrate import make_tiny_workload
    from repro.launch.mesh import make_test_mesh
    from repro.optim.optimizers import momentum_sgd
    from repro.optim.schedules import constant
    from repro.train.steps import build_bundle
    from repro.train.trainer import Trainer

    cfg, shape, data = make_tiny_workload()
    comm = CommConfig(compressor="qsgd", compressor_kwargs={"levels": 4},
                      error_feedback=True, churn=True,
                      dropout_rate=dropout_rate)
    bundle = build_bundle(cfg, make_test_mesh(data=1, model=1), comm,
                          momentum_sgd(0.0), shape, seed=0, microbatch=1)
    return Trainer(bundle, data, constant(0.1), log_every=1)


def _ef_norm(state) -> float:
    return float(sum(np.abs(np.asarray(e)).max() for e in state["comm"]["ef"]))


def test_ef_freezes_while_masked_out():
    """A worker that is (almost surely) always masked out neither sends nor
    accumulates: its EF residual stays exactly zero, while the same cell
    with dropout 0 accumulates a nonzero qsgd residual — and the two cells
    share ONE compiled bundle (dropout is a traced value)."""
    from repro.train.steps import bundle_cache_stats

    b0, h0 = bundle_cache_stats().builds, bundle_cache_stats().hits
    alive_tr = _build_trainer(0.0)
    dead_tr = _build_trainer(0.999999)
    st = bundle_cache_stats()
    assert st.builds - b0 == 1, "dropout value split the bundle class"
    assert st.hits - h0 == 1

    state_alive = alive_tr.fit(alive_tr.init(), 4)
    state_dead = dead_tr.fit(dead_tr.init(), 4)
    assert _ef_norm(state_alive) > 0.0
    assert _ef_norm(state_dead) == 0.0
    assert all(np.isfinite(h["loss"]) for h in dead_tr.history)


def test_engine_and_trainer_agree_on_churn_cell():
    """The shared churn-cell contract, checked on BOTH substrates: a
    dropout-0 churn cell reproduces the plain cell, 30% dropout stays
    finite, and dropout values never split a class: the engine runs all
    three cells in one class (it always carries the mask), the trainer in
    at most two (plain vs churn)."""
    from repro.experiments import Scenario
    from repro.experiments.runner import run_scenarios, training_shape_key
    from repro.experiments.trainer_substrate import run_trainer_scenario
    from repro.train.steps import bundle_cache_stats

    def cell(**kw):
        base = dict(sync="bsp", n_workers=4, steps=8, lr=0.05,
                    compressor="qsgd", compressor_kwargs={"levels": 16},
                    error_feedback=True, seed=0)
        base.update(kw)
        return Scenario(**base)

    cells = [cell(),
             cell(churn=True, dropout_rate=0.0),
             cell(churn=True, dropout_rate=0.3)]
    assert len({training_shape_key(s) for s in cells}) == 1

    c0 = engine_cache_stats().compiles
    plain, churn0, churn30 = run_scenarios(cells, "training")
    assert engine_cache_stats().compiles - c0 <= 1
    np.testing.assert_array_equal(churn0.series["loss"], plain.series["loss"])
    assert np.isfinite(churn30.series["loss"]).all()

    b0 = bundle_cache_stats().builds
    t_plain, t_churn0, t_churn30 = (
        run_trainer_scenario(s, data_par=1) for s in cells)
    assert bundle_cache_stats().builds - b0 <= 2
    np.testing.assert_allclose(t_churn0.series["loss_full"],
                               t_plain.series["loss_full"], rtol=1e-6)
    assert np.isfinite(t_churn30.series["loss_full"]).all()


# ---------------------------------------------------------------------------
# rejoin protocol (ISSUE 8): dropout-0 no-op, pull_avg vs reset, resync cost
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ("reset", "pull_avg"))
@pytest.mark.parametrize("sync", ("local", "gossip"))
def test_rejoin_policy_dropout0_matches_churn_free(sync, policy):
    """Either rejoin policy at dropout 0 reproduces the churn-free cell —
    the rejoin graph is jnp.where-selected on a ``rejoined`` bit that is
    identically zero when nobody ever drops."""
    problem = quadratic_problem(dim=24, n_workers=4, noise=0.1, seed=3)
    plain = simulate_training_batch(_cell(sync), problem)[0]
    churn0 = simulate_training_batch(
        _cell(sync, dropout_rate=0.0, rejoin_policy=policy),
        problem)[0]
    for k in ("loss", "consensus", "bits"):
        np.testing.assert_allclose(churn0[k], plain[k], rtol=1e-5, atol=1e-6,
                                   err_msg=f"{k} ({policy})")


def test_pull_avg_rejoin_collapses_consensus_and_charges_download():
    """Local SGD, workers 2/3 dead for steps [0, 20): under ``pull_avg`` the
    rejoiners adopt the live pair's average at their first sync round after
    the window — consensus collapses immediately instead of decaying over
    later rounds — and the run is charged exactly one dense model download
    per rejoiner (32 bits x dim x 2 workers) on top of the reset cell."""
    dim = 24
    problem = quadratic_problem(dim=dim, n_workers=4, noise=0.0, seed=0)
    base = dict(sync="local", n_workers=4, steps=40, lr=0.05, local_steps=5,
                worker_dropout=(0.0, 0.0, 1.0, 1.0),
                churn_start=0, churn_end=20, seed=0)
    reset = simulate_training_batch(
        SimCfg(**base, rejoin_policy="reset"), problem)[0]
    pull = simulate_training_batch(
        SimCfg(**base, rejoin_policy="pull_avg"), problem)[0]
    assert np.isfinite(pull["loss"]).all()
    # the rejoin step (20) is NOT a sync round: reset leaves the rejoiners
    # parked at x0 until step 24's average, pull_avg snaps them to the live
    # pair's average immediately
    assert pull["consensus"][20] < 0.5 * reset["consensus"][20]
    extra_bits = float(pull["bits"][-1] - reset["bits"][-1])
    assert extra_bits == 2 * 32.0 * dim, extra_bits


def test_rejoin_policy_is_structural_dropout_is_traced():
    """One engine compile per rejoin_policy class: dropout values never
    split a class, the two policies never share one."""
    problem = quadratic_problem(dim=16, n_workers=4, noise=0.05, seed=2)

    def cells(pol):
        return [SimCfg(sync="local", n_workers=4, steps=15, lr=0.05,
                       local_steps=5, dropout_rate=r,
                       rejoin_policy=pol, seed=5)
                for r in (0.1, 0.3)]

    c0 = engine_cache_stats().compiles
    for pol in ("reset", "pull_avg"):
        out = simulate_training_classbatch(cells(pol), problem)
        for cell_res in out:
            assert np.isfinite(cell_res[0]["loss"]).all()
    assert engine_cache_stats().compiles - c0 == 2, \
        "expected one compile per rejoin policy"


# ---------------------------------------------------------------------------
# timeline substrate: churn as an event stream with priced resync
# ---------------------------------------------------------------------------


def test_timeline_churn_event_stream():
    """Dropout on the timeline substrate: rejoin events are drawn, priced
    per the policy (pull_avg pays a dense download, reset only the alpha
    handshake), masked rounds move no payload, and the analytic prediction
    tracks the measured event count."""
    from repro.experiments import Scenario
    from repro.experiments.runner import predict, run_scenario

    base = dict(sync="bsp", n_workers=4, steps=60, compute_time=0.01,
                churn=True, dropout_rate=0.2, churn_start=10, churn_end=40,
                seed=0)
    pull = run_scenario(Scenario(**base, rejoin_policy="pull_avg"), "timeline")
    reset = run_scenario(Scenario(**base, rejoin_policy="reset"), "timeline")
    free = run_scenario(Scenario(sync="bsp", n_workers=4, steps=60,
                                 compute_time=0.01, seed=0), "timeline")

    assert pull.measured["resync_events"] > 0
    assert pull.measured["resync_events"] == reset.measured["resync_events"]
    assert pull.measured["resync_bytes"] > 0
    assert reset.measured["resync_bytes"] == 0.0
    assert 0 < reset.measured["resync_seconds"] < pull.measured["resync_seconds"]
    assert free.measured["resync_events"] == 0
    # masked iterations move no payload: the churn cell's per-worker bytes
    # (net of the resync downloads) stay below the churn-free cell's
    assert (pull.measured["bytes_per_worker"] - pull.measured["resync_bytes"] / 4
            < free.measured["bytes_per_worker"])
    # analytic event-count prediction within 2x of one sampled stream
    p = predict(Scenario(**base, rejoin_policy="pull_avg"), "timeline")
    assert 0.5 < p["resync_events"] / pull.measured["resync_events"] < 2.0
    assert p["resync_bytes"] > 0


def test_timeline_churn_free_row_has_no_resync_keys():
    from repro.experiments import Scenario
    from repro.experiments.runner import predict

    p = predict(Scenario(sync="bsp", n_workers=4, steps=20), "timeline")
    assert "resync_events" not in p


# ---------------------------------------------------------------------------
# trainer substrate: the three previously-rejected combos run end-to-end
# ---------------------------------------------------------------------------


def _run_trainer_cell(s, **kw):
    from repro.experiments.trainer_substrate import run_trainer_scenario

    return run_trainer_scenario(s, data_par=1, **kw)


def test_trainer_powersgd_under_churn():
    """PowerSGD under churn: the factor psums mask dead contributions, so
    the cell builds and trains — dropout 0 reproduces the plain cell and a
    high rate stays finite, sharing one build (dropout traced)."""
    from repro.experiments import Scenario
    from repro.train.steps import bundle_cache_stats

    def cell(**kw):
        base = dict(sync="bsp", n_workers=4, steps=6, lr=0.05,
                    compressor="powersgd", compressor_kwargs={"rank": 2},
                    error_feedback=True, seed=0)
        base.update(kw)
        return Scenario(**base)

    plain = _run_trainer_cell(cell())
    b0 = bundle_cache_stats().builds
    churn0 = _run_trainer_cell(cell(churn=True, dropout_rate=0.0))
    churn5 = _run_trainer_cell(cell(churn=True, dropout_rate=0.5))
    assert bundle_cache_stats().builds - b0 == 1
    np.testing.assert_allclose(churn0.series["loss_full"],
                               plain.series["loss_full"], rtol=1e-6)
    assert np.isfinite(churn5.series["loss_full"]).all()


@pytest.mark.parametrize("policy", ("reset", "pull_avg"))
def test_trainer_choco_under_churn(policy):
    """CHOCO under churn: the mirror-resync channel keeps the x-hat
    invariant, so the previously-rejected combo runs — dropout 0 matches
    the plain cell, 50% dropout stays finite under both rejoin policies."""
    from repro.experiments import Scenario

    def cell(**kw):
        base = dict(arch="gossip", gossip_compress="choco", n_workers=4,
                    steps=6, lr=0.05, compressor="qsgd",
                    compressor_kwargs={"levels": 16}, seed=0)
        base.update(kw)
        return Scenario(**base)

    plain = _run_trainer_cell(cell())
    churn0 = _run_trainer_cell(cell(churn=True, dropout_rate=0.0,
                                    rejoin_policy=policy))
    churn5 = _run_trainer_cell(cell(churn=True, dropout_rate=0.5,
                                    rejoin_policy=policy))
    np.testing.assert_allclose(churn0.series["loss_full"],
                               plain.series["loss_full"], rtol=1e-6)
    assert np.isfinite(churn5.series["loss_full"]).all()
    # the dense resync channel is reported separately from the payload
    # figure (a 1-device ring moves 0 wire bytes either way — the 4-device
    # e2e below checks the nonzero resync figure); payload matches the
    # plain cell up to the scalar liveness exchange
    assert "wire_resync_kb_per_step" in churn5.measured
    assert "wire_resync_kb_per_step" not in plain.measured
    np.testing.assert_allclose(churn5.measured["wire_kb_per_step"],
                               plain.measured["wire_kb_per_step"],
                               atol=0.1)


@pytest.mark.parametrize("policy", ("reset", "pull_avg"))
def test_trainer_param_avg_sync_under_churn(policy):
    """Masked runtime parameter averaging: the local-SGD sync round — the
    third previously-rejected combo — runs under churn with both rejoin
    policies; dropout 0 reproduces the plain cell."""
    from repro.experiments import Scenario

    def cell(**kw):
        base = dict(sync="local", local_steps=2, n_workers=4, steps=8,
                    lr=0.05, compressor="qsgd",
                    compressor_kwargs={"levels": 16}, error_feedback=True,
                    seed=0)
        base.update(kw)
        return Scenario(**base)

    plain = _run_trainer_cell(cell())
    churn0 = _run_trainer_cell(cell(churn=True, dropout_rate=0.0,
                                    rejoin_policy=policy))
    churn4 = _run_trainer_cell(cell(churn=True, dropout_rate=0.4,
                                    rejoin_policy=policy, churn_start=1,
                                    churn_end=5))
    np.testing.assert_allclose(churn0.series["loss_full"],
                               plain.series["loss_full"], rtol=1e-6)
    assert np.isfinite(churn4.series["loss_full"]).all()


def test_trainer_churn_wire_accounting():
    """Satellite 2: a masked worker's round books no payload — churn cells
    carry the alive-weighted expected wire figure next to the structural
    one, scaled by the closed-form live fraction."""
    from repro.experiments import Scenario
    from repro.experiments.trainer_substrate import expected_live_fraction

    s = Scenario(sync="bsp", n_workers=4, steps=10, lr=0.05,
                 compressor="qsgd", compressor_kwargs={"levels": 16},
                 error_feedback=True, churn=True, dropout_rate=0.3,
                 churn_start=0, churn_end=5, seed=0)
    frac = expected_live_fraction(s)
    # 30% dropout over half the run: 1 - 0.3 * 5/10
    assert abs(frac - 0.85) < 1e-9
    r = _run_trainer_cell(s)
    assert r.measured["live_fraction"] == frac
    np.testing.assert_allclose(r.measured["wire_kb_per_step_alive"],
                               r.measured["wire_kb_per_step"] * frac)
    plain = _run_trainer_cell(s.replace(churn=False, dropout_rate=0.0))
    assert "wire_kb_per_step_alive" not in plain.measured


# ---------------------------------------------------------------------------
# drop-and-rejoin end-to-end on a real 4-device mesh (subprocess)
# ---------------------------------------------------------------------------

REJOIN_E2E = r"""
import numpy as np
from repro.core.types import CommConfig
from repro.experiments.trainer_substrate import make_tiny_workload
from repro.launch.mesh import make_test_mesh
from repro.optim.optimizers import momentum_sgd
from repro.optim.schedules import constant
from repro.train.steps import build_bundle, bundle_cache_stats
from repro.train.trainer import Trainer

def run(comm, steps=16, seed=0):
    cfg, shape, data = make_tiny_workload()
    bundle = build_bundle(cfg, make_test_mesh(data=4, model=1), comm,
                          momentum_sgd(0.0), shape, seed=0, microbatch=1)
    tr = Trainer(bundle, data, constant(0.1), log_every=1)
    tr.fit(tr.init(seed), steps)
    return np.array([h["loss"] for h in tr.history])

window = dict(churn=True, dropout_rate=0.5, churn_start=2, churn_end=8)

# (1) masked parameter averaging + pull_avg rejoin converges with the
#     never-dropped run
base = dict(sync="local", local_steps=2, compressor="qsgd",
            compressor_kwargs={"levels": 16}, error_feedback=True)
never = run(CommConfig(**base))
churn = run(CommConfig(**base, **window, rejoin_policy="pull_avg"))
assert np.isfinite(churn).all()
assert abs(churn[-1] - never[-1]) < 0.25 * abs(never[-1]), (churn[-1], never[-1])

# (2) powersgd under churn: factors re-warm from the live set
base = dict(compressor="powersgd", compressor_kwargs={"rank": 2},
            error_feedback=True)
never = run(CommConfig(**base))
churn = run(CommConfig(**base, **window))
assert np.isfinite(churn).all()
assert abs(churn[-1] - never[-1]) < 0.25 * abs(never[-1]), (churn[-1], never[-1])

# (3) choco under churn, both policies: mirrors resync, run converges, and
#     the dense resync channel is traced into the wire artifact separately
#     from the compressed payload
base = dict(aggregator="gossip", gossip_compress="choco", compressor="qsgd",
            compressor_kwargs={"levels": 16})
never = run(CommConfig(**base))
for pol in ("reset", "pull_avg"):
    churn = run(CommConfig(**base, **window, rejoin_policy=pol))
    assert np.isfinite(churn).all(), pol
    # one-sided: choco's gossip consensus is still transient at this
    # horizon and the rejoiner's exact mirror-snap broadcast can
    # legitimately SPEED consensus up, so the churn run only has to avoid
    # ending much worse than the never-dropped reference
    assert churn[-1] < 1.25 * never[-1], (pol, churn[-1], never[-1])

cfg, shape, data = make_tiny_workload()
bw = build_bundle(cfg, make_test_mesh(data=4, model=1),
                  CommConfig(**base, **window), momentum_sgd(0.0), shape,
                  seed=0, microbatch=1).wire
assert bw["gossip"].get("churn_resync", 0.0) > 0, bw["gossip"]
assert bw["gossip"].get("gossip_mix", 0.0) > 0, bw["gossip"]

print("REJOIN-E2E OK")
"""


@pytest.mark.slow
def test_rejoin_e2e_trainer_4dev():
    from tests.helpers import run_subprocess_devices

    out = run_subprocess_devices(REJOIN_E2E, n_devices=4, timeout=1800)
    assert "REJOIN-E2E OK" in out


# ---------------------------------------------------------------------------
# gradient-integrity guards (ISSUE 10): corruption -> detect -> quarantine ->
# recover, on both substrates
# ---------------------------------------------------------------------------

KINDS = ("nan", "inf", "spike", "bitflip")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("sync", ("bsp", "local"))
def test_engine_corruption_finite_within_2x_of_clean_drop(sync, kind):
    """10% corruption of each kind: the guarded cell stays finite and lands
    within 2x of the equivalent clean-drop churn cell — a quarantined round
    behaves like a one-round dropout, not a poisoned model."""
    problem = quadratic_problem(dim=24, n_workers=4, noise=0.1, seed=3)
    hot = simulate_training_batch(
        _cell(sync, steps=24, corruption_rate=0.1, corruption_kind=kind),
        problem)[0]
    drop = simulate_training_batch(
        _cell(sync, steps=24, dropout_rate=0.1), problem)[0]
    assert np.isfinite(hot["loss"]).all(), kind
    assert np.isfinite(drop["loss"]).all()
    assert hot["loss"][-1] <= 2.0 * drop["loss"][-1] + 1e-6, \
        (kind, hot["loss"][-1], drop["loss"][-1])
    # the guarded program books its integrity tallies
    for k in ("quarantined_bits", "quarantine_rounds", "escalations"):
        assert k in hot, k


@pytest.mark.parametrize("sync", SCHEMES)
def test_engine_corruption0_matches_churn_free(sync):
    """A corruption-0 cell (a named kind at rate 0 — the guarded program) is
    bitwise identical to the plain cell for the shared-denominator
    schemes: every integrity select rides the post-compression jnp.where
    and is the identity when the corruption flag never fires."""
    problem = quadratic_problem(dim=24, n_workers=4, noise=0.1, seed=3)
    plain = simulate_training_batch(_cell(sync), problem)[0]
    hot0 = simulate_training_batch(
        _cell(sync, dropout_rate=0.0, corruption_rate=0.0,
              corruption_kind="bitflip"), problem)[0]
    for k in ("loss", "consensus", "bits"):
        if sync in BITWISE:
            np.testing.assert_array_equal(hot0[k], plain[k], err_msg=k)
        else:
            np.testing.assert_allclose(hot0[k], plain[k], rtol=1e-5,
                                       atol=1e-6, err_msg=k)
    assert float(hot0["quarantine_rounds"][-1]) == 0.0
    assert float(hot0["escalations"][-1]) == 0.0


def test_corruption_rate_traced_kind_structural():
    """Corruption RATES share one engine compile (traced); the KIND splits
    the class (the guarded program differs per kind)."""
    problem = quadratic_problem(dim=16, n_workers=4, noise=0.05, seed=2)
    rates = [SimCfg(sync="bsp", n_workers=4, steps=15, lr=0.05,
                    compressor=_qsgd16(), error_feedback=True,
                    corruption_rate=r, corruption_kind="nan", seed=5)
             for r in (0.05, 0.1, 0.3)]
    c0 = engine_cache_stats().compiles
    out = simulate_training_classbatch(rates, problem)
    assert engine_cache_stats().compiles - c0 == 1, \
        "corruption rate split a compile class"
    for cell_res in out:
        assert np.isfinite(cell_res[0]["loss"]).all()
    import dataclasses

    simulate_training_batch(
        dataclasses.replace(rates[0], corruption_kind="bitflip"), problem)
    assert engine_cache_stats().compiles - c0 == 2, \
        "corruption kind must be structural"


def test_engine_quarantine_detects_and_escalates():
    """Hot corruption (50% nan) on bsp+qsgd: detection fires (quarantined
    rounds and booked-undelivered bits both positive), the bounded counter
    escalates to the rejoin protocol, and the run still trains finitely."""
    problem = quadratic_problem(dim=24, n_workers=4, noise=0.1, seed=3)
    r = simulate_training_batch(
        _cell("bsp", steps=24, corruption_rate=0.5, corruption_kind="nan",
              quarantine_limit=2), problem)[0]
    assert np.isfinite(r["loss"]).all()
    assert float(r["quarantine_rounds"][-1]) > 0
    assert float(r["quarantined_bits"][-1]) > 0
    assert float(r["escalations"][-1]) > 0
    # quarantined bits are booked SEPARATELY from the delivered-bits figure
    assert float(r["quarantined_bits"][-1]) < float(r["bits"][-1])


def test_timeline_corruption_books_quarantined_wire():
    """Timeline substrate: corrupted wire rounds are quarantined (bytes
    moved, booked undelivered), escalations charge the rejoin cost, and the
    closed-form prediction tracks the sampled stream within 2x."""
    from repro.experiments import Scenario
    from repro.experiments.runner import predict, run_scenario

    s = Scenario(sync="bsp", n_workers=4, steps=60, compute_time=0.01,
                 corruption_rate=0.1, corruption_kind="bitflip",
                 quarantine_limit=2, seed=0)
    r = run_scenario(s, "timeline")
    assert r.measured["quarantine_events"] > 0
    assert r.measured["quarantined_bytes"] > 0
    p = predict(s, "timeline")
    assert 0.5 < p["quarantine_events"] / r.measured["quarantine_events"] < 2.0
    clean = run_scenario(Scenario(sync="bsp", n_workers=4, steps=60,
                                  compute_time=0.01, seed=0), "timeline")
    assert clean.measured["quarantine_events"] == 0


@pytest.mark.parametrize("cellkw", [
    dict(sync="bsp", compressor="qsgd", compressor_kwargs={"levels": 16}),
    dict(sync="local", local_steps=2, compressor="qsgd",
         compressor_kwargs={"levels": 16}),
    dict(sync="bsp", compressor="signsgd_packed", wire_format="compressed"),
    dict(sync="local", local_steps=2, compressor="signsgd_packed",
         wire_format="compressed"),
], ids=["bsp-qsgd", "local-qsgd", "bsp-sign-cwire", "local-sign-cwire"])
def test_trainer_corruption_acceptance_cells(cellkw):
    """The acceptance grid on the trainer: 10% bitflip on
    {bsp,local} x {qsgd, signsgd_packed+cwire} trains finitely within 2x of
    the equivalent clean-drop churn cell, and the measured row carries the
    quarantine accounting keys."""
    from repro.experiments import Scenario

    def cell(**kw):
        base = dict(n_workers=4, steps=8, lr=0.05, error_feedback=True,
                    seed=0, **cellkw)
        base.update(kw)
        return Scenario(**base)

    hot = _run_trainer_cell(cell(corruption_rate=0.1,
                                 corruption_kind="bitflip"))
    drop = _run_trainer_cell(cell(churn=True, dropout_rate=0.1))
    assert np.isfinite(hot.series["loss_full"]).all()
    assert (hot.measured["final_loss"]
            <= 2.0 * abs(drop.measured["final_loss"]) + 1e-6)
    for k in ("quarantine_rounds", "escalations", "quarantine_fraction",
              "wire_kb_per_step_quarantined"):
        assert k in hot.measured, k
    assert "quarantine_fraction" in hot.predicted


def test_trainer_corruption_kinds_detected():
    """Each corruption kind at a hot rate on bsp+qsgd: finite loss, and the
    detectable kinds actually quarantine rounds (the 1-bit sign wire is the
    documented undetectable case and is not in this cell)."""
    from repro.experiments import Scenario

    for kind in KINDS:
        s = Scenario(sync="bsp", n_workers=4, steps=10, lr=0.05,
                     compressor="qsgd", compressor_kwargs={"levels": 16},
                     error_feedback=True, seed=0,
                     corruption_rate=0.6, corruption_kind=kind,
                     quarantine_limit=2)
        r = _run_trainer_cell(s)
        assert np.isfinite(r.series["loss_full"]).all(), kind
        assert r.measured["quarantine_rounds"] > 0, kind
        assert r.measured["escalations"] > 0, kind


def test_trainer_corruption0_bitwise_incl_pipelined_staleness1():
    """Corruption-0 cells (explicit kind, rate 0) are BITWISE identical to
    the churn-free cell on the trainer — including the pipelined
    staleness-1 double buffer, whose stale-slot gating must also ride
    identity selects — and the guarded cells share builds with the plain
    churn class, never one per corruption rate."""
    from repro.experiments import Scenario
    from repro.train.steps import bundle_cache_stats

    def cell(**kw):
        base = dict(sync="bsp", n_workers=4, steps=6, lr=0.05,
                    compressor="qsgd", compressor_kwargs={"levels": 16},
                    error_feedback=True, seed=0)
        base.update(kw)
        return Scenario(**base)

    plain = _run_trainer_cell(cell())
    hot0 = _run_trainer_cell(cell(churn=True, dropout_rate=0.0,
                                  corruption_kind="bitflip"))
    np.testing.assert_array_equal(hot0.series["loss_full"],
                                  plain.series["loss_full"])

    pipe = dict(overlap="pipelined", overlap_staleness=1, microbatch=2)
    plain_p = _run_trainer_cell(cell(**pipe))
    churn0_p = _run_trainer_cell(cell(**pipe, churn=True, dropout_rate=0.0))
    hot0_p = _run_trainer_cell(cell(**pipe, churn=True, dropout_rate=0.0,
                                    corruption_kind="bitflip"))
    np.testing.assert_array_equal(churn0_p.series["loss_full"],
                                  plain_p.series["loss_full"])
    np.testing.assert_array_equal(hot0_p.series["loss_full"],
                                  plain_p.series["loss_full"])

    # corruption RATES share one build (traced) within the guarded class:
    # hot0 above (rate 0.0) already built the non-pipelined bitflip class,
    # so two more rates must be pure cache hits
    b0 = bundle_cache_stats().builds
    for rate in (0.1, 0.3):
        r = _run_trainer_cell(cell(corruption_rate=rate,
                                   corruption_kind="bitflip"))
        assert np.isfinite(r.series["loss_full"]).all()
    assert bundle_cache_stats().builds - b0 == 0, \
        "corruption rate split a bundle class"


# ---------------------------------------------------------------------------
# integrity + churn frontiers e2e on a real 4-device mesh (subprocess)
# ---------------------------------------------------------------------------

INTEGRITY_E2E = r"""
import numpy as np, jax
from repro.core.types import CommConfig
from repro.experiments.trainer_substrate import make_tiny_workload
from repro.launch.mesh import make_test_mesh
from repro.optim.optimizers import momentum_sgd
from repro.optim.schedules import constant
from repro.train.steps import build_bundle, bundle_cache_stats
from repro.train.trainer import Trainer

cfg, shape, data = make_tiny_workload()

def run(comm, steps=12, mesh=None, microbatch=1):
    bundle = build_bundle(cfg, mesh or make_test_mesh(data=4, model=1), comm,
                          momentum_sgd(0.0), shape, seed=0,
                          microbatch=microbatch)
    tr = Trainer(bundle, data, constant(0.1), log_every=1)
    state = tr.fit(tr.init(0), steps)
    return np.array([h["loss"] for h in tr.history]), state

# (1) pipelined staleness-1 + churn + rejoin: the dead/rejoined worker's
#     pending stale bucket is masked, dropout 0 is bitwise churn-free
pipe = dict(compressor="qsgd", compressor_kwargs={"levels": 16},
            error_feedback=True, overlap="pipelined", overlap_staleness=1)
plain, _ = run(CommConfig(**pipe), microbatch=2)
churn0, _ = run(CommConfig(**pipe, churn=True, dropout_rate=0.0,
                           rejoin_policy="pull_avg"), microbatch=2)
np.testing.assert_array_equal(churn0, plain)
hot, _ = run(CommConfig(**pipe, churn=True, dropout_rate=0.4,
                        churn_start=2, churn_end=8,
                        rejoin_policy="pull_avg"), microbatch=2)
assert np.isfinite(hot).all()

# (2) per-worker dropout VECTORS on the trainer: worker 1 almost surely
#     dead, the rest clean — finite, and the vector cell shares the scalar
#     cell's bundle (dropout normalizes to a per-shard vector either way)
b0 = bundle_cache_stats().builds
base = dict(compressor="qsgd", compressor_kwargs={"levels": 16},
            error_feedback=True, churn=True)
vec, _ = run(CommConfig(**base, worker_dropout=(0.0, 0.999999, 0.0, 0.3)))
scl, _ = run(CommConfig(**base, dropout_rate=0.3))
assert np.isfinite(vec).all() and np.isfinite(scl).all()
assert bundle_cache_stats().builds - b0 == 1, "dropout vector split the class"

# (3) pod_local + churn + corruption: per-shard masks inside the pod, the
#     pod-sync liveness bit DERIVED from the shard masks, in-pod payload
#     corruption quarantined
pmesh = make_test_mesh(data=2, model=1, pod=2)
pl = dict(pod_local=True, local_steps=2, compressor="qsgd",
          compressor_kwargs={"levels": 16}, error_feedback=True)
plain, _ = run(CommConfig(**pl), mesh=pmesh)
churn0, _ = run(CommConfig(**pl, churn=True, dropout_rate=0.0), mesh=pmesh)
np.testing.assert_array_equal(churn0, plain)
hot, st = run(CommConfig(**pl, churn=True, dropout_rate=0.3,
                         churn_start=1, churn_end=8,
                         corruption_rate=0.5, corruption_kind="nan"),
              mesh=pmesh)
assert np.isfinite(hot).all()
qt = float(np.sum(np.asarray(jax.device_get(st["comm"]["quarantine_total"]))))
assert qt > 0, "pod_local corruption never quarantined"

print("INTEGRITY-E2E OK")
"""


@pytest.mark.slow
def test_integrity_e2e_trainer_4dev():
    from tests.helpers import run_subprocess_devices

    out = run_subprocess_devices(INTEGRITY_E2E, n_devices=4, timeout=1800)
    assert "INTEGRITY-E2E OK" in out
