"""Run a Scenario on the REAL mesh runtime (repro.train) — the setup that
``examples/local_sgd_vs_bsp.py``, ``examples/compression_comparison.py`` and
``examples/gossip_decentralized.py`` used to hand-wire per cell.

Import note: callers must set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
*before* jax initializes (the examples do this at the top of the file);
this module assumes the devices already exist.
"""

from __future__ import annotations

from typing import Any

from repro.experiments.runner import ScenarioResult
from repro.experiments.scenario import Scenario


def to_comm_config(s: Scenario):
    """Scenario -> the runtime CommConfig knobs (repro.core.types)."""
    from repro.core.types import CommConfig

    bad = s.violations("trainer")
    if bad:
        raise ValueError(f"scenario {s.tag()} cannot run on the mesh: {'; '.join(bad)}")
    return CommConfig(
        compressor=s.compressor or "none",
        compressor_kwargs=s.kwargs_dict,
        error_feedback=s.error_feedback,
        sync=s.sync,
        # pod_local keeps H under sync="bsp" too: the pod axis is averaged
        # every local_steps (the §III-D boundary), not every step
        local_steps=(s.local_steps
                     if s.sync in ("local", "post_local") or s.pod_local
                     else 1),
        post_local_switch=s.post_local_switch,
        pod_local=s.pod_local,
        aggregator="gossip" if s.arch == "gossip" else "allreduce",
        gossip_compress=s.gossip_compress,
        bucket_mb=s.bucket_bytes / 1e6,
        overlap=s.overlap,
        overlap_staleness=s.overlap_staleness,
        stale_scale=s.stale_scale,
        wire_format=s.wire_format,
        churn=s.churn,
        dropout_rate=s.dropout_rate,
        worker_dropout=s.worker_dropout,
        churn_start=s.churn_start,
        churn_end=s.churn_end,
        rejoin_policy=s.rejoin_policy,
        corruption_rate=s.corruption_rate,
        corruption_kind=s.corruption_kind,
        quarantine_limit=s.quarantine_limit,
    )


def select_trainer_device_count(
    s: Scenario, n_devices: int, *, global_batch: int = 64
) -> tuple[int | None, str]:
    """Automated device-count selection for the ``--substrate trainer`` CLI
    lane: the largest data-parallel mesh that (a) fits the available
    devices, (b) does not exceed the scenario's worker count, and (c)
    divides the tiny workload's global batch.  Returns ``(data_par, "")``
    or ``(None, reason)`` when the cell must be skipped."""
    bad = s.violations("trainer")
    if bad:
        return None, "; ".join(bad)
    mb = max(1, s.microbatch)
    for dp in range(min(s.n_workers, n_devices), 1, -1):
        if s.worker_dropout and dp != s.n_workers:
            # the per-worker rate vector is indexed by shard: the mesh must
            # realize exactly the scenario's worker count
            continue
        if global_batch % dp == 0 and (global_batch // dp) % mb == 0:
            return dp, ""
    return None, (f"needs a >=2-device mesh dividing batch {global_batch} "
                  f"into {mb} microbatches (have {n_devices} device(s)"
                  + (f"; worker_dropout pins data_par={s.n_workers}"
                     if s.worker_dropout else "") + ")")


def _phase_sync_steps(s: Scenario, steps: int) -> int:
    """Sync steps the runtime actually fires in [post_local_switch, steps):
    ``repro.core.sync`` tests the ABSOLUTE step phase ((t+1) % H == 0), so a
    switch point that is not a multiple of H still syncs on the global
    grid."""
    H = s.local_steps
    return sum(1 for t in range(s.post_local_switch, steps) if (t + 1) % H == 0)


def sync_rounds(s: Scenario, steps: int) -> int:
    """Parameter/gradient synchronization rounds a Scenario performs."""
    if s.sync == "local":
        return steps // s.local_steps
    if s.sync == "post_local":
        return s.post_local_switch + _phase_sync_steps(s, steps)
    return steps


def make_tiny_workload(vocab: int = 128, batch: int = 64, seq: int = 16):
    """The shared micro-model + bigram data source of the comparison
    examples: small enough for host-device smoke runs, real enough that
    compression/sync choices separate the loss curves."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.data.pipeline import BigramSource

    cfg = get_config("qwen3-0.6b").reduced().with_updates(
        vocab=vocab, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256)
    shape = InputShape("train", seq, batch, "train")
    src = BigramSource(cfg.vocab, seed=0)

    class Data:
        def batch(self, step):
            return src.batch(step, shape.global_batch, shape.seq_len)

    return cfg, shape, Data()


def trainer_shape_key(s: Scenario, *, data_par: int | None = None,
                      model_par: int = 1) -> tuple:
    """Hashable trainer shape-class identity of a Scenario: the static
    :func:`repro.core.types.bundle_spec` of its CommConfig plus the mesh
    extents and the microbatch count (a scan-length build flag).  Cells with
    equal keys share ONE compiled bundle
    (``train_step``/``sync_step``/``gossip_step``) through the bundle
    registry in :mod:`repro.train.steps`; everything else — lr, Local-H,
    post-local switch, compressor value knobs, gossip weights, the pipelined
    stale-gradient scale — is either traced or a Python-level trainer
    decision and deliberately absent."""
    from repro.core.types import bundle_spec

    return (bundle_spec(to_comm_config(s)), data_par or s.n_workers, model_par,
            max(1, s.microbatch))


def expected_live_fraction(s: Scenario) -> float:
    """Expected fraction of worker-communication-rounds that actually put
    payload on the wire under the cell's churn window: a masked worker's
    round moves no compressed payload, so the wire artifact's structural
    per-round bytes overcount a churn cell by exactly the expected dead
    fraction.  1.0 for churn-free cells; per-worker rates average."""
    if not s.churn or s.steps <= 0:
        return 1.0
    start = min(max(s.churn_start, 0), s.steps)
    end = s.steps if s.churn_end == -1 else min(s.churn_end, s.steps)
    w = max(0, end - start)
    rates = (list(s.worker_dropout) if s.worker_dropout
             else [s.dropout_rate] * max(1, s.n_workers))
    p_mean = sum(rates) / len(rates)
    return 1.0 - p_mean * w / s.steps


def expected_quarantine_fraction(s: Scenario) -> float:
    """Closed-form expected fraction of worker-wire-rounds quarantined: a
    round is quarantined when the worker is alive (1 - p_drop), in the churn
    window, its payload is corrupted (corruption_rate) AND the wire format
    detects it.  The detection term is 1.0 for every validated format; the
    1-bit packed sign wire has no redundancy (nothing is ever quarantined),
    which the caller accounts for by this returning the *upper* bound —
    measured-vs-predicted on sign cells shows the undetectable gap."""
    rate = s.corruption_rate
    if not s._corruption_active or rate <= 0 or s.steps <= 0:
        return 0.0
    start = min(max(s.churn_start, 0), s.steps)
    end = s.steps if s.churn_end == -1 else min(s.churn_end, s.steps)
    w = max(0, end - start)
    rates = (list(s.worker_dropout) if s.worker_dropout
             else [s.dropout_rate] * max(1, s.n_workers))
    p_mean = sum(rates) / len(rates)
    return rate * (1.0 - p_mean) * w / s.steps


def trainer_wire_resync_per_step(s: Scenario,
                                 wire: dict[str, dict[str, float]]) -> float:
    """Per-step bytes of the dense ``churn_resync`` channel (the CHOCO
    rejoin exact-delta broadcast + mirror rebuild).  Kept OUT of the main
    payload figure: it is a separate dense channel that exists only on
    churn cells, and it is reported per step of the program that carries
    it (the mixing round)."""
    if s.arch == "gossip":
        return wire.get("gossip", {}).get("churn_resync", 0.0)
    rs = wire.get("sync", {}).get("churn_resync", 0.0)
    return rs / s.local_steps if s.sync in ("local", "post_local") else rs


def trainer_wire_per_step(s: Scenario, wire: dict[str, dict[str, float]]) -> float:
    """Per-step wire bytes of one cell from the bundle's build-time wire
    artifact.  ``post_local`` blends the two phases: the BSP phase pays the
    per-step gradient aggregation for ``post_local_switch`` steps, then each
    H-round pays one aggregation + one parameter average (the old accounting
    reported only ``local_sgd_sync / H`` and silently dropped the BSP-phase
    ``grad_agg`` bytes)."""
    ga = wire.get("train", {}).get("grad_agg", 0.0)
    ls = wire.get("sync", {}).get("local_sgd_sync", 0.0)
    if s.arch == "gossip":
        return wire.get("gossip", {}).get("gossip_mix", 0.0)
    if s.pod_local:  # in-pod aggregation every step + pod average every H
        return ga + ls / s.local_steps
    if s.sync == "local":
        return ls / s.local_steps
    if s.sync == "post_local":
        rounds = _phase_sync_steps(s, s.steps)
        return (s.post_local_switch * ga + rounds * (ga + ls)) / s.steps
    return ga


def trainer_wire_formats(s: Scenario, wire: dict) -> dict[str, float]:
    """Per-encoding wire bytes of the cell's aggregation/mixing program (one
    program invocation), from the bundle artifact's ``*_formats`` breakdown —
    shows WHAT the wire carried (f32 vs bf16 vs int8 vs packed1/packed2)."""
    key = "gossip_formats" if s.arch == "gossip" else "train_formats"
    return dict(wire.get(key, {}))


def plan_payload_bytes(plan) -> float:
    """Analytic per-worker payload bytes ONE aggregation round of a
    BucketPlan moves: the compressor's ``wire_bits`` per bucket (dense 32
    bits/element without one; data-dependent NaN sizes — threshold-style —
    fall back to the dense charge).  This is the payload quantity the
    alpha-beta schedule model consumes — deliberately NOT derived from the
    build-time wire artifact, whose per-device byte counts depend on the
    collective algorithm each bucket used (psum vs all_gather)."""
    total = 0.0
    for b in plan.buckets:
        comp = plan.compressor(b)
        wb = comp.wire_bits(b.size) if comp is not None else b.size * 32.0
        if wb != wb:  # NaN
            wb = b.size * 32.0
        total += wb / 8.0
    return total


def predict_overlap_saving(
    s: Scenario,
    *,
    compute_s: float,
    payload_round: float,
    n_buckets: int,
    data_par: int,
    link=None,
    launch: float | None = None,
) -> dict[str, float]:
    """§VII prediction for one trainer cell: feed the cell's OWN message
    structure (microbatch aggregation rounds x bucket-plan messages,
    ``payload_round`` analytic payload bytes per round from
    :func:`plan_payload_bytes`, compute time from the measured step) into
    :func:`repro.core.schedule.simulate_schedule` and return the predicted
    per-step times and overlap saving vs the sequential schedule of the same
    cell.  The alpha-beta link and per-message launch overhead come from the
    active :mod:`repro.core.calibrate` profile when one is installed
    (machine-fitted constants, the Shi et al. methodology) and fall back to
    the Scenario's datasheet constants otherwise — on a forced-host mesh the
    measured saving reflects scheduler/XLA effects, and the two are recorded
    side by side (predicted-vs-measured)."""
    from repro.core import calibrate
    from repro.core.costmodel import Link
    from repro.core.schedule import LayerSpec, simulate_schedule

    n = max(2, data_par)
    M = max(1, s.microbatch)
    rounds = M if s.overlap == "pipelined" else 1
    nb = max(1, n_buckets)
    if link is None:
        link = calibrate.active_link(Link(alpha=s.alpha, beta=s.beta))
    if launch is None:
        launch = calibrate.active_launch(0.0)

    def simulate(n_rounds: int, mode: str) -> dict:
        layers = [
            LayerSpec(f"r{k}b{j}", grad_bytes=payload_round / nb,
                      backward_time=compute_s / (n_rounds * nb))
            for k in range(n_rounds) for j in range(nb)
        ]
        return simulate_schedule(layers, n_workers=n, link=link,
                                 alg=s.allreduce_alg, mode=mode,
                                 staleness=s.overlap_staleness,
                                 launch=launch)

    seq = simulate(1, "sequential")
    pipe = simulate(rounds, "pipelined")
    own = pipe if s.overlap == "pipelined" else seq
    return {
        "iter_time": own["iter_time"],
        "overlap_saving_s": seq["iter_time"] - pipe["iter_time"],
        "comm_time": own["total_comm_time"],
    }


def predict_trainer_step(
    s: Scenario,
    *,
    data_par: int,
    payload_round: float,
    n_buckets: int,
    profile=None,
) -> dict[str, float]:
    """Analytic per-step wall-clock prediction for ANY trainer cell: compute
    term + (amortized sync rounds) x (collective cost of the cell's analytic
    payload + per-message launch overhead).  With a
    :class:`repro.core.calibrate.CalibrationProfile` (argument, else the
    active one) all three constant families are machine-fitted — link
    alpha/beta from timed psum rounds, launch from timed dispatches, compute
    from the measured dense step; without one the datasheet Scenario
    constants apply (``compute_time=1.0`` s et al.), which is the
    uncalibrated "before" column of BENCH_coldstart."""
    from repro.core import calibrate
    from repro.core.costmodel import Link, allreduce_cost, gossip_cost

    if profile is None:
        profile = calibrate.get_active()
    if profile is not None:
        link, launch = profile.link(), profile.t_launch
        compute = (profile.t_step_dense if profile.t_step_dense is not None
                   else s.compute_time)
    else:
        link, launch = Link(alpha=s.alpha, beta=s.beta), 0.0
        compute = s.compute_time
    n = max(2, data_par)
    nb = max(1, n_buckets)
    msgs = nb * (max(1, s.microbatch) if s.overlap == "pipelined" else 1)
    if s.arch == "gossip":
        wire = gossip_cost(payload_round, link=link)
    else:
        wire = allreduce_cost(s.allreduce_alg, n, payload_round, link)
    rounds_per_step = sync_rounds(s, s.steps) / max(1, s.steps)
    comm = rounds_per_step * (wire + launch * msgs)
    return {
        "step_time_s": compute + comm,
        "comm_time_s": comm,
        "calibrated": float(profile is not None),
    }


def run_trainer_scenario(
    s: Scenario,
    *,
    data_par: int | None = None,
    model_par: int = 1,
    momentum: float = 0.0,
    log_every: int | None = None,
    bundle_cache: bool = True,
) -> ScenarioResult:
    """Train the tiny workload under the scenario's CommConfig; measures
    final loss, per-step wall-clock (compile excluded), wire bytes per step
    (from the bundle's build-time wire artifact, so cache-reused bundles
    keep exact accounting) and the number of synchronization rounds.  Every
    cell carries the :func:`predict_trainer_step` step-time prediction
    (calibrated when a :mod:`repro.core.calibrate` profile is active); cells
    on the overlap axis additionally carry the ``simulate_schedule``
    prediction of their per-step time and overlap saving.
    ``bundle_cache=False`` forces a fresh ``build_bundle`` — the per-cell
    baseline the sweep benchmark times."""
    import numpy as np

    from repro.launch.mesh import make_test_mesh
    from repro.optim.optimizers import momentum_sgd
    from repro.optim.schedules import constant
    from repro.train.steps import build_bundle
    from repro.train.trainer import Trainer

    comm = to_comm_config(s)
    cfg, shape, data = make_tiny_workload()
    dp = data_par or s.n_workers
    mb = max(1, s.microbatch)
    if (shape.global_batch // dp) % mb != 0:
        raise ValueError(
            f"{s.tag()}: local batch {shape.global_batch // dp} does not "
            f"split into {mb} microbatches")
    mesh = make_test_mesh(data=dp, model=model_par)

    bundle = build_bundle(cfg, mesh, comm, momentum_sgd(momentum), shape,
                          seed=s.seed, microbatch=mb, cache=bundle_cache)
    trainer = Trainer(bundle, data, constant(s.lr), log_every=1)
    state = trainer.fit(trainer.init(), s.steps)

    # per-step wall-clock with the compile excluded: first logged step pays
    # the jit, the rest amortize
    walls = [h["wall"] for h in trainer.history]
    step_s = ((walls[-1] - walls[0]) / (len(walls) - 1)) if len(walls) > 1 else walls[0]

    measured: dict[str, Any] = {
        "final_loss": float(trainer.history[-1]["loss"]),
        "step_time_s": float(step_s),
        "wire_kb_per_step": trainer_wire_per_step(s, bundle.wire or {}) / 1e3,
        "sync_rounds": float(sync_rounds(s, s.steps)),
        "wire_format_kb": {
            fmt: b / 1e3
            for fmt, b in trainer_wire_formats(s, bundle.wire or {}).items()
        },
    }
    if s.churn:
        # a masked worker's round books no payload: the alive-weighted
        # figure is the expected on-the-wire traffic; the resync channel
        # (dense, rejoin-only semantics) is reported separately
        frac = expected_live_fraction(s)
        measured["live_fraction"] = float(frac)
        measured["wire_kb_per_step_alive"] = measured["wire_kb_per_step"] * frac
        measured["wire_format_kb"] = {
            fmt: kb * frac for fmt, kb in measured["wire_format_kb"].items()}
        measured["wire_resync_kb_per_step"] = (
            trainer_wire_resync_per_step(s, bundle.wire or {}) / 1e3)
    if s._corruption_active:
        # measured quarantine tallies live in the final comm state (per
        # shard, replicated over the model axis); the wire-rounds
        # denominator is sync_rounds x microbatch-rounds for pipelined
        # cells.  Quarantined bytes are BOOKED (excluded from delivery):
        # the predicted figure is the closed form, the measured one scales
        # the same per-step payload by the observed quarantine fraction.
        import jax as _jax
        cst = state["comm"]
        qt = np.asarray(_jax.device_get(cst["quarantine_total"]), dtype=np.float64)
        et = np.asarray(_jax.device_get(cst["escalation_total"]), dtype=np.float64)
        q_rounds = float(np.sum(qt)) / max(1, model_par)
        esc = float(np.sum(et)) / max(1, model_par)
        rounds = sync_rounds(s, s.steps) * (mb if s.overlap == "pipelined" else 1)
        units = dp  # mask units = data shards (per-shard even under pod_local)
        measured["quarantine_rounds"] = q_rounds
        measured["escalations"] = esc
        qfrac_meas = q_rounds / max(1.0, float(rounds * units))
        measured["quarantine_fraction"] = qfrac_meas
        measured["wire_kb_per_step_quarantined"] = (
            measured["wire_kb_per_step"] * qfrac_meas)
    # every cell carries the analytic step-time prediction (calibrated when a
    # profile is active, datasheet constants otherwise) so predicted-vs-
    # measured rel-err is a first-class sweep column, not an overlap-only one
    predicted: dict[str, Any] = predict_trainer_step(
        s, data_par=dp,
        payload_round=plan_payload_bytes(bundle.bucket_plan),
        n_buckets=len(bundle.bucket_plan.buckets))
    if s._corruption_active:
        qfrac = expected_quarantine_fraction(s)
        predicted["quarantine_fraction"] = qfrac
        predicted["wire_kb_per_step_quarantined"] = (
            measured["wire_kb_per_step"] * qfrac)
    if s.overlap == "pipelined":
        predicted.update(predict_overlap_saving(
            s, compute_s=float(step_s),
            payload_round=plan_payload_bytes(bundle.bucket_plan),
            n_buckets=len(bundle.bucket_plan.buckets), data_par=dp))
    every = log_every or max(1, s.steps - 1)
    series = {"loss": np.asarray(
        [h["loss"] for h in trainer.history
         if h["step"] % every == 0 or h["step"] == s.steps - 1])}
    series["loss_full"] = np.asarray([h["loss"] for h in trainer.history])
    return ScenarioResult(s, "trainer", measured, predicted=predicted,
                          replicas=1, series=series)


# ---------------------------------------------------------------------------
# Shape-class batched sweep over the real mesh runtime.
# ---------------------------------------------------------------------------


def run_trainer_sweep(
    scenarios: list[Scenario],
    *,
    n_devices: int | None = None,
    data_par: int | None = None,
    model_par: int = 1,
    momentum: float = 0.0,
    log_every: int | None = None,
    bundle_cache: bool = True,
    verbose: bool = False,
) -> tuple[list[ScenarioResult | None], list[tuple[Scenario, str]]]:
    """Run a Scenario slice on the mesh runtime, grouped by trainer shape
    class (the trainer-lane counterpart of the simulator's
    ``simulate_training_classbatch``).  The build sharing itself comes from
    the bundle registry in :mod:`repro.train.steps` — every cell of a class
    resolves to the same cache key and reuses the compiled
    ``train_step``/``sync_step``/``gossip_step`` with its own traced knob
    values; the grouping here keeps each class's cells contiguous, so a
    class builds once up front and cannot be evicted mid-class by an
    interleaved sweep larger than the registry cap.

    Device counts come from ``data_par`` (fixed) or per cell from
    :func:`select_trainer_device_count` when ``n_devices`` is given.
    Returns ``(results, skipped)``: results in input order (``None`` for
    skipped cells), and the skip reasons.
    """
    import sys

    if data_par is None and n_devices is None:
        # bound per-cell mesh selection by the devices that actually exist
        import jax

        n_devices = len(jax.devices())

    plan: list[tuple[int, Scenario, int]] = []
    skipped: list[tuple[Scenario, str]] = []
    for i, s in enumerate(scenarios):
        if data_par is not None:
            plan.append((i, s, data_par))
            continue
        dp, why = select_trainer_device_count(s, n_devices)
        if dp is None:
            skipped.append((s, why))
        else:
            plan.append((i, s, dp))

    groups: dict[tuple, list[tuple[int, Scenario, int]]] = {}
    for item in plan:
        key = trainer_shape_key(item[1], data_par=item[2], model_par=model_par)
        groups.setdefault(key, []).append(item)

    results: list[ScenarioResult | None] = [None] * len(scenarios)
    for key, items in groups.items():
        for i, s, dp in items:
            if verbose:
                print(f"# trainer cell {s.tag()}: data_par={dp}", file=sys.stderr)
            results[i] = run_trainer_scenario(
                s, data_par=dp, model_par=model_par, momentum=momentum,
                log_every=log_every, bundle_cache=bundle_cache)
    _attach_measured_overlap_saving(results)
    return results, skipped


def _overlap_twin(s: Scenario) -> Scenario:
    """The canonical sequential form of a cell: the overlap mode reset and
    its now-inert knobs normalized.  Applied to BOTH sides of the pairing,
    so a pipelined cell finds its sequential twin regardless of the twin's
    own (inert) staleness / stale-scale values."""
    return s.replace(overlap="sequential", overlap_staleness=1, stale_scale=1.0)


def _attach_measured_overlap_saving(results: list) -> None:
    """Measured counterpart of :func:`predict_overlap_saving`: when a sweep
    contains BOTH a pipelined cell and its sequential twin, the pipelined
    cell's measured overlap saving is the twin's per-step wall-clock minus
    its own — the quantity the BENCH_overlap record tracks against the
    ``simulate_schedule`` prediction."""
    seq_step: dict[Scenario, float] = {
        _overlap_twin(r.scenario): r.measured["step_time_s"]
        for r in results
        if r is not None and r.scenario.overlap == "sequential"
    }
    for r in results:
        if r is None or r.scenario.overlap != "pipelined":
            continue
        twin = seq_step.get(_overlap_twin(r.scenario))
        if twin is not None:
            r.measured["overlap_saving_s"] = twin - r.measured["step_time_s"]


def trainer_matrix_8(*, steps: int = 24, n_workers: int = 4, seed: int = 0) -> list[Scenario]:
    """The original trainer-lane acceptance sweep: 2 sync schemes (bsp,
    local) x 2 compressor families (qsgd, terngrad) x 2 knob values = 8
    cells spanning exactly 4 shape classes.  Kept as the small fixture;
    :func:`trainer_matrix_16` is the BENCH_trainer acceptance matrix."""
    return _trainer_matrix(steps=steps, n_workers=n_workers, seed=seed,
                           knobs_per_family=2)


def trainer_matrix_16(*, steps: int = 24, n_workers: int = 4, seed: int = 0) -> list[Scenario]:
    """The scaled trainer-lane acceptance sweep (the build cost amortizes
    over more knob-traced cells per class): 2 sync schemes x 2 compressor
    families x 4 knob values = 16 cells, still exactly 4 shape classes —
    the sweep builds 4 bundles, not 16."""
    return _trainer_matrix(steps=steps, n_workers=n_workers, seed=seed,
                           knobs_per_family=4)


def _trainer_matrix(*, steps: int, n_workers: int, seed: int,
                    knobs_per_family: int) -> list[Scenario]:
    families = (
        ("qsgd", ({"levels": 4}, {"levels": 16}, {"levels": 8}, {"levels": 32})),
        ("terngrad", ({"clip_sigma": 0.0}, {"clip_sigma": 2.5},
                      {"clip_sigma": 1.5}, {"clip_sigma": 3.5})),
    )
    cells = []
    for sync in ("bsp", "local"):
        for comp, kwargs in families:
            for kw in kwargs[:knobs_per_family]:
                cells.append(Scenario(
                    sync=sync, local_steps=4, n_workers=n_workers, steps=steps,
                    lr=0.1, compressor=comp, compressor_kwargs=kw,
                    error_feedback=True, seed=seed))
    return cells


def measure_trainer_sweep(
    scenarios: list[Scenario] | None = None,
    *,
    data_par: int | None = None,
    model_par: int = 1,
) -> dict[str, Any]:
    """Wall-clock + bundle-build count of the shape-class-shared trainer
    sweep vs the per-cell rebuild path (a fresh ``build_bundle`` per cell),
    plus the max deviation between the two result sets — the acceptance
    record behind ``BENCH_trainer.json``."""
    import time

    import numpy as np

    from repro.train.steps import bundle_cache_clear, bundle_cache_stats

    scenarios = trainer_matrix_16() if scenarios is None else list(scenarios)
    classes = {trainer_shape_key(s, data_par=data_par, model_par=model_par)
               for s in scenarios if not s.violations("trainer")}

    bundle_cache_clear()
    t0 = time.perf_counter()
    shared, skipped = run_trainer_sweep(scenarios, data_par=data_par,
                                        model_par=model_par)
    shared_s = time.perf_counter() - t0
    st = bundle_cache_stats()
    builds_shared, hits_shared = st.builds, st.hits

    bundle_cache_clear()
    t0 = time.perf_counter()
    percell, _ = run_trainer_sweep(scenarios, data_par=data_par,
                                   model_par=model_par, bundle_cache=False)
    percell_s = time.perf_counter() - t0
    builds_percell = bundle_cache_stats().builds

    ran = [(a, b) for a, b in zip(shared, percell) if a is not None and b is not None]
    dev_loss = max(
        (float(np.max(np.abs(a.series["loss"] - b.series["loss"])
                      / np.maximum(np.abs(b.series["loss"]), 1e-6)))
         for a, b in ran),
        default=float("nan"),
    )
    return {
        "n_cells": len(scenarios),
        "n_skipped": len(skipped),
        "n_shape_classes": len(classes),
        "steps": scenarios[0].steps,
        "builds_shared": builds_shared,
        "cache_hits": hits_shared,
        "builds_percell": builds_percell,
        "shared_s": shared_s,
        "percell_s": percell_s,
        "speedup": percell_s / shared_s,
        "max_rel_dev_loss": dev_loss,
        "persistent_cache": bundle_cache_stats().persistent_cache,
        "wire_kb_per_step": {
            r.tag: r.measured["wire_kb_per_step"] for r in shared if r is not None
        },
    }
