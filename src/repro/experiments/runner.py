"""Batch execution of scenario lists on the simulation substrates.

Every run returns a :class:`ScenarioResult` carrying BOTH the measured
metrics from the substrate and the analytic cost-model prediction
(`repro.core.costmodel`) for the same cell, so sweep tables show
predicted-vs-measured side by side (the quantitative-survey methodology of
Shi et al., arXiv:2005.13247).

Substrates:

* ``timeline``  — :func:`repro.core.simulate.simulate_timeline` (Fig. 4 /
  Table II: throughput, staleness, idle, wire bytes under stragglers);
* ``training``  — :func:`repro.core.simulate.simulate_training_classbatch`
  (§VIII convergence: loss / consensus / upload bits). EVERY taxonomy cell —
  all sync schemes, all registered compressors, EF on/off — runs its replica
  seeds in ONE jitted ``lax.scan`` vmapped over the seed axis, and the sweep
  runner additionally groups cells into *shape classes*
  (:func:`training_shape_key`) so cells that differ only in traced values
  (lr, staleness, Local-H, compressor knobs, gradient noise) share one
  compiled program — a sweep compiles once per shape class, not once per
  cell.  Nothing falls back to the per-step Python loop
  (:func:`repro.core.simulate.simulate_training_reference` survives only as
  the equivalence/benchmark baseline);
* ``schedule``  — :func:`repro.core.schedule.simulate_schedule` (§VII
  WFBP / MG-WFBP iteration-time model);
* ``roofline``  — analytic per-scenario dry-run prediction reusing the
  roofline terms of :mod:`repro.launch.roofline` (no mesh, no compile):
  compute / HBM / collective seconds per iteration and the bottleneck.

The ``trainer`` substrate (real mesh execution of a Scenario through
``repro.train``) lives in :mod:`repro.experiments.trainer_substrate` because
it needs XLA host-device flags set before jax initializes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.core.costmodel import (
    Link,
    allreduce_cost,
    gossip_cost,
    ps_cost,
    round_wire_bytes,
    upload_bits,
)
from repro.core.schedule import LayerSpec, simulate_schedule
from repro.core.simulate import (
    PROBLEMS,
    SimCfg,
    TimelineCfg,
    engine_cache_clear,
    engine_cache_stats,
    shape_class_key,
    simulate_timeline,
    simulate_training_batch,
    simulate_training_classbatch,
    simulate_training_reference,
)
from repro.experiments.scenario import Scenario

f64 = float


@dataclass
class ScenarioResult:
    """One scenario executed on one substrate (replica-averaged)."""

    scenario: Scenario
    substrate: str
    measured: dict[str, float]
    predicted: dict[str, float]
    replicas: int = 1
    series: dict[str, np.ndarray] = field(default_factory=dict)

    @property
    def tag(self) -> str:
        return self.scenario.tag()

    def row(self) -> dict[str, Any]:
        out: dict[str, Any] = {"tag": self.tag, "substrate": self.substrate}
        out.update({f"measured_{k}": v for k, v in self.measured.items()})
        out.update({f"predicted_{k}": v for k, v in self.predicted.items()})
        return out


# ---------------------------------------------------------------------------
# Cost-model predictions (the "predicted" half of every result row).
# ---------------------------------------------------------------------------


#: registry-name -> Table IV compression family for the analytic bit model.
_QUANT_BITS = {
    "qsgd": lambda kw: math.log2(kw.get("levels", 16)) + 1,
    "natural": lambda kw: 9.0,
    "natural_dithering": lambda kw: 9.0,
    "terngrad": lambda kw: math.log2(3) + 1,
    "signsgd": lambda kw: 1.0,
    "signsgd_packed": lambda kw: 1.0,
    "onebit": lambda kw: 1.0,
}
_SPARSE = ("topk", "gtopk", "randomk", "stc", "sbc", "wangni", "threshold")


def estimated_wire_bytes(s: Scenario) -> float:
    """Effective bytes ONE worker uploads per communication round.

    Prefers the real compressor's analytic ``wire_bits``; falls back to the
    Table IV family model when the size is data-dependent (NaN).
    """
    n_elems = int(s.msg_bytes / 4)  # dense f32 elements
    if s.compressor is None:
        return s.msg_bytes
    comp = s.make_compressor()
    wb = comp.wire_bits(n_elems)
    if wb == wb:  # not NaN
        return wb / 8.0
    kw = s.kwargs_dict
    if s.compressor in _QUANT_BITS:
        return upload_bits("quant", n_elems, levels=int(2 ** (_QUANT_BITS[s.compressor](kw) - 1))) / 8.0
    if any(s.compressor.startswith(p) for p in _SPARSE):
        return upload_bits("spars", n_elems, ratio=kw.get("ratio", 0.01)) / 8.0
    return s.msg_bytes


def rounds_per_iter(s: Scenario) -> float:
    """Communication rounds per iteration under the sync scheme."""
    return 1.0 / s.local_steps if s.sync == "local" else 1.0


def _round_comm_time(s: Scenario, nbytes: float) -> float:
    link = Link(alpha=s.alpha, beta=s.beta)
    if s.arch == "ps":
        return ps_cost(s.n_workers, nbytes, link, congested=s.ps_congested)
    if s.arch == "allreduce":
        return allreduce_cost(s.allreduce_alg, s.n_workers, nbytes, link)
    if s.arch == "gossip":
        return gossip_cost(nbytes, peers=s.gossip_peers, link=link)
    raise ValueError(s.arch)


def _round_wire_bytes(s: Scenario, nbytes: float) -> float:
    return round_wire_bytes(s.arch, s.n_workers, nbytes, peers=s.gossip_peers)


def predict(s: Scenario, substrate: str) -> dict[str, float]:
    """Analytic cost-model prediction for the cell, keyed to match the
    substrate's measured metrics."""
    eff = estimated_wire_bytes(s)
    rounds = rounds_per_iter(s)
    comm_per_iter = _round_comm_time(s, eff) * rounds
    if substrate == "timeline":
        # straggler-free alpha-beta estimate; the simulator adds the
        # straggler/congestion dynamics on top.
        iter_time = s.compute_time + comm_per_iter
        out = {
            "iter_time": iter_time,
            "throughput": s.n_workers / iter_time,
            "comm_frac": comm_per_iter / iter_time,
            "bytes_per_worker": _round_wire_bytes(s, eff) * rounds * s.steps,
        }
        if s.churn:
            # expected churn overhead from the Bernoulli event stream the
            # timeline simulator draws: a rejoin at step t needs dead(t-1)
            # AND alive(t) — p(1-p) per in-window step pair, plus one
            # certain-alive transition when the window closes mid-run.
            start = min(max(s.churn_start, 0), s.steps)
            end = s.steps if s.churn_end == -1 else min(s.churn_end, s.steps)
            w = max(0, end - start)
            rates = (list(s.worker_dropout) if s.worker_dropout
                     else [s.dropout_rate] * s.n_workers)
            ev = sum(max(0, w - 1) * p * (1.0 - p)
                     + (p if end < s.steps and w > 0 else 0.0)
                     for p in rates)
            per_event_s = (s.alpha + s.beta * eff
                           if s.rejoin_policy == "pull_avg" else s.alpha)
            per_event_b = eff if s.rejoin_policy == "pull_avg" else 0.0
            out["resync_events"] = ev
            out["resync_seconds"] = per_event_s * ev
            out["resync_bytes"] = per_event_b * ev
            if s.corruption_rate > 0:
                # Bernoulli corruption over the live set in the same window:
                # each live worker's wire round is quarantined w.p. rate, and
                # the quarantined bytes moved but were booked undelivered.
                live = sum(1.0 - p for p in rates)
                qe = s.corruption_rate * live * w * rounds
                out["quarantine_events"] = qe
                out["quarantined_bytes"] = _round_wire_bytes(s, eff) * qe
        return out
    if substrate == "training":
        dim_bits = 32.0 * (eff / s.msg_bytes)  # effective bits per element
        return {
            "bits_per_element": dim_bits,
            "compression_x": s.msg_bytes / eff,
            "comm_time_per_step": comm_per_iter,
        }
    if substrate == "schedule":
        layers = layer_profile(s.layer_profile)
        link = Link(alpha=s.alpha, beta=s.beta)
        bwd = sum(l.backward_time for l in layers)
        per_layer = sum(
            allreduce_cost(s.allreduce_alg, s.n_workers, l.grad_bytes, link) for l in layers
        )
        return {
            "no_overlap_time": bwd + per_layer,
            "full_overlap_bound": max(bwd, per_layer),
        }
    if substrate == "roofline":
        # alpha-beta counterpart of the roofline terms: serial compute+comm.
        return {
            "iter_time": s.compute_time + comm_per_iter,
            "comm_frac": comm_per_iter / (s.compute_time + comm_per_iter),
        }
    raise ValueError(substrate)


# ---------------------------------------------------------------------------
# Layer profiles for the schedule substrate (shared with benchmarks).
# ---------------------------------------------------------------------------


def _resnet50_profile() -> list[LayerSpec]:
    # 161 gradient tensors, mostly small — the MG-WFBP motivation.
    layers = [
        LayerSpec(f"conv{i}", grad_bytes=25.5e6 * 4 / 160, backward_time=5e-3 / 160)
        for i in range(160)
    ]
    layers.append(LayerSpec("fc", grad_bytes=8e6, backward_time=5e-4))
    return layers


def _transformer32_profile() -> list[LayerSpec]:
    return [
        LayerSpec(f"block{i}", grad_bytes=12 * 4096 * 4096 * 2, backward_time=3e-3)
        for i in range(32)
    ]


def _uniform16_profile() -> list[LayerSpec]:
    return [
        LayerSpec(f"layer{i}", grad_bytes=4e6, backward_time=1e-3) for i in range(16)
    ]


LAYER_PROFILES = {
    "resnet50": _resnet50_profile,
    "transformer32": _transformer32_profile,
    "uniform16": _uniform16_profile,
}


def layer_profile(name: str) -> list[LayerSpec]:
    if name not in LAYER_PROFILES:
        raise KeyError(f"unknown layer profile {name!r}; known: {sorted(LAYER_PROFILES)}")
    return LAYER_PROFILES[name]()


# ---------------------------------------------------------------------------
# Substrate mappings.
# ---------------------------------------------------------------------------


def to_timeline_cfg(s: Scenario, seed: int | None = None) -> TimelineCfg:
    return TimelineCfg(
        n_workers=s.n_workers,
        iters=s.steps,
        compute_mean=s.compute_time,
        straggler_sigma=s.straggler_sigma,
        straggler_worker_slowdown=s.straggler_slowdown,
        alpha=s.alpha,
        beta=s.beta,
        msg_bytes=estimated_wire_bytes(s),
        server_bw_share=s.ps_congested,
        sync=s.sync,
        staleness=s.staleness,
        local_steps=s.local_steps,
        arch=s.arch,
        seed=s.seed if seed is None else seed,
        worker_speeds=s.worker_speeds,
        straggler_dist=s.straggler_dist,
        dropout_rate=s.dropout_rate,
        worker_dropout=s.worker_dropout,
        churn_start=s.churn_start,
        churn_end=s.churn_end,
        rejoin_policy=s.rejoin_policy,
        corruption_rate=s.corruption_rate,
        corruption_kind=s.corruption_kind,
        quarantine_limit=s.quarantine_limit,
    )


def to_sim_cfg(s: Scenario, seed: int | None = None) -> SimCfg:
    # In the exact-SGD simulator PS and all-reduce compute the same mean;
    # the architecture distinguishes them only in the cost model. Gossip
    # changes the dynamics (neighbor mixing instead of exact averaging).
    sync = "gossip" if s.arch == "gossip" else s.sync
    return SimCfg(
        n_workers=s.n_workers,
        sync=sync,
        staleness=s.staleness,
        local_steps=s.local_steps,
        compressor=s.make_compressor(),
        error_feedback=s.error_feedback,
        lr=s.lr,
        steps=s.steps,
        seed=s.seed if seed is None else seed,
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


# ---------------------------------------------------------------------------
# Roofline substrate: analytic dry-run prediction per scenario (no mesh).
# ---------------------------------------------------------------------------


def _hbm_passes(s: Scenario) -> float:
    """Gradient-sized HBM passes per iteration of the compression pipeline
    (the qsgd_ef kernel analysis, repro/kernels/qsgd_ef.py): dense SGD apply
    is 3 passes (read g, read x, write x); an unfused compress+EF adds 8, an
    unfused compress adds 2.5, and the fused EF kernel adds 4.25."""
    passes = 3.0
    if s.compressor is None:
        return passes
    comp = s.make_compressor()
    if s.error_feedback:
        return passes + (4.25 if hasattr(comp, "compress_decompress_ef") else 8.0)
    return passes + 2.5


def roofline_row(s: Scenario) -> dict[str, Any]:
    """Per-scenario roofline terms via :mod:`repro.launch.roofline` — the
    dry-run prediction the ROADMAP asked for, built from the scenario's
    analytic byte/flop model instead of a compiled artifact (no mesh needed).
    The declared ``compute_time`` is inverted to FLOPs at chip peak so the
    shared :class:`Roofline` term algebra applies unchanged."""
    from repro.launch import roofline as RL

    eff = estimated_wire_bytes(s)
    rl = RL.Roofline(
        arch=s.arch,
        shape=s.tag(),
        mesh=f"n{s.n_workers}",
        flops=s.compute_time * RL.peaks(RL.TARGET_KIND).flops,
        hbm_bytes=_hbm_passes(s) * s.msg_bytes,
        coll_bytes=_round_wire_bytes(s, eff) * rounds_per_iter(s),
        coll_bytes_hlo=0.0,
        coll_by_kind={},
        device_kind=RL.TARGET_KIND,
    )
    return {
        "t_compute": rl.t_compute,
        "t_memory": rl.t_memory,
        "t_collective": rl.t_collective,
        "iter_time_bound": max(rl.t_compute, rl.t_memory, rl.t_collective),
        "bottleneck": rl.bottleneck,
    }


# ---------------------------------------------------------------------------
# Engine-vs-reference speedup measurement (perf trajectory across PRs).
# ---------------------------------------------------------------------------

#: the fixed perf-tracking cell: 8 workers, 300 steps, 3 replicas, qsgd+EF.
REFERENCE_SPEEDUP_CELL = Scenario(
    sync="bsp", n_workers=8, steps=300, lr=0.05,
    compressor="qsgd", compressor_kwargs={"levels": 16}, error_feedback=True,
)


def measure_engine_speedup(s: Scenario = REFERENCE_SPEEDUP_CELL, *, replicas: int = 3) -> dict[str, float]:
    """Wall-clock of the jitted scan engine vs the Python-loop reference on
    one cell.  ``speedup_warm`` excludes the one-time jit compile (the repo's
    ``benchmarks.common.time_fn`` convention); ``speedup_cold`` includes it."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.simulate import _build_replica_fn

    problem = PROBLEMS[s.objective](n_workers=s.n_workers, noise=s.grad_noise, seed=s.seed)
    seeds = [s.seed + r for r in range(replicas)]
    cfg = to_sim_cfg(s)

    fn = jax.jit(jax.vmap(_build_replica_fn(cfg, problem)))
    keys = jnp.stack([jax.random.key(sd) for sd in seeds])
    t0 = time.perf_counter()
    jax.block_until_ready(fn(keys))
    cold = time.perf_counter() - t0  # includes the one-time jit compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn(keys))
    warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    for sd in seeds:
        simulate_training_reference(to_sim_cfg(s, seed=sd), problem=problem)
    ref = time.perf_counter() - t0
    return {
        "cell": s.tag(),
        "replicas": replicas,
        "steps": s.steps,
        "engine_s_cold": cold,
        "engine_s_warm": warm,
        "reference_s": ref,
        "speedup_cold": ref / cold,
        "speedup_warm": ref / warm,
    }


# ---------------------------------------------------------------------------
# The batch runner.
# ---------------------------------------------------------------------------


def _agg(vals: list[float]) -> float:
    return float(np.mean(vals))


# ---------------------------------------------------------------------------
# Training substrate: shape-class batched execution (one compile per class).
# ---------------------------------------------------------------------------


def training_shape_key(s: Scenario) -> tuple:
    """Hashable shape-class identity of a training-substrate cell.

    Two scenarios with equal keys execute in ONE compiled
    ``jit(vmap_cells(vmap_seeds(scan)))`` program: the key pins everything
    that changes program *structure* — the engine statics
    (:func:`repro.core.simulate.shape_class_key`: sync scheme, worker count,
    steps, EF flag, compressor family fingerprint) plus the objective
    *family* (its grad/loss code).  The problem's arrays (quadratic ``A``/
    ``b``, logistic ``X``/``y``, ``x*``) are traced per cell through the
    :class:`repro.core.simulate.Problem` data protocol, so cells differing
    only in problem seed share the compile; values like lr / staleness /
    Local-H / compressor knobs / gradient noise are traced too and equally
    absent."""
    return shape_class_key(to_sim_cfg(s)) + (s.objective,)


_PROBLEM_CACHE: dict[tuple, Any] = {}


def _training_problem(s: Scenario):
    """One problem instance per (objective, n_workers, seed) — shared across
    the cells of a shape class so they can bake the same arrays.  The
    factory noise is irrelevant here: the runner always traces each cell's
    ``grad_noise`` through the problem's ``noise`` keyword."""
    key = (s.objective, s.n_workers, s.seed)
    if key not in _PROBLEM_CACHE:
        if len(_PROBLEM_CACHE) > 32:
            _PROBLEM_CACHE.pop(next(iter(_PROBLEM_CACHE)))
        _PROBLEM_CACHE[key] = PROBLEMS[s.objective](
            n_workers=s.n_workers, noise=s.grad_noise, seed=s.seed)
    return _PROBLEM_CACHE[key]


def _run_training_scenarios(
    scenarios: list[Scenario], *, replicas: int = 1, cache: bool = True
) -> list[ScenarioResult]:
    """Group the cells into shape classes and run each class as ONE compiled
    program; results come back in input order.  ``cache=False`` forces a
    fresh trace per call — the per-cell PR 2 baseline the sweep benchmark
    measures against."""
    for s in scenarios:
        bad = s.violations("training")
        if bad:
            raise ValueError(f"invalid scenario {s.tag()} on training: {'; '.join(bad)}")
    groups: dict[tuple, list[int]] = {}
    for i, s in enumerate(scenarios):
        groups.setdefault(training_shape_key(s), []).append(i)
    results: list[ScenarioResult | None] = [None] * len(scenarios)
    for key, idxs in groups.items():
        cells = [scenarios[i] for i in idxs]
        outs = simulate_training_classbatch(
            [to_sim_cfg(s) for s in cells],
            problems=[_training_problem(s) for s in cells],
            seeds=[[s.seed + r for r in range(replicas)] for s in cells],
            grad_noise=[s.grad_noise for s in cells],
            cache=cache,
        )
        for i, s, cell in zip(idxs, cells, outs):
            measured = {
                "final_loss": _agg([float(o["loss"][-1]) for o in cell]),
                "x_star_err": _agg([o["x_star_err"] for o in cell]),
                "consensus": _agg([float(o["consensus"][-1]) for o in cell]),
                "gbits": _agg([float(o["bits"][-1]) for o in cell]) / 1e9,
            }
            if replicas > 1:
                measured["final_loss_std"] = float(
                    np.std([float(o["loss"][-1]) for o in cell]))
            if "quarantine_rounds" in cell[0]:
                # guarded cells book their integrity tallies: worker-rounds
                # quarantined, wire bits sent-but-undelivered, escalations
                measured["quarantine_rounds"] = _agg(
                    [float(o["quarantine_rounds"][-1]) for o in cell])
                measured["quarantined_gbits"] = _agg(
                    [float(o["quarantined_bits"][-1]) for o in cell]) / 1e9
                measured["escalations"] = _agg(
                    [float(o["escalations"][-1]) for o in cell])
            series = {
                "loss": np.stack([o["loss"] for o in cell]),
                "consensus": np.stack([o["consensus"] for o in cell]),
                "bits": np.stack([o["bits"] for o in cell]),
            }
            results[i] = ScenarioResult(s, "training", measured,
                                        predict(s, "training"),
                                        replicas=replicas, series=series)
    return results  # type: ignore[return-value]


def run_scenario(s: Scenario, substrate: str = "timeline", *, replicas: int = 1) -> ScenarioResult:
    """Execute one scenario; replica seeds are ``seed, seed+1, ...``."""
    bad = s.violations(substrate)
    if bad:
        raise ValueError(f"invalid scenario {s.tag()} on {substrate}: {'; '.join(bad)}")
    seeds = [s.seed + r for r in range(replicas)]
    pred = predict(s, substrate) if substrate != "trainer" else {}

    if substrate == "timeline":
        runs = [simulate_timeline(to_timeline_cfg(s, seed=sd)).row() for sd in seeds]
        measured = {k: _agg([r[k] for r in runs]) for k in runs[0]}
        # iter_time = makespan / iters = n_workers / throughput (global
        # throughput counts every worker's iterations).
        measured["iter_time"] = _agg([s.n_workers / r["throughput"] for r in runs])
        return ScenarioResult(s, substrate, measured, pred, replicas=replicas)

    if substrate == "training":
        # every cell — any sync scheme, any compressor, EF on/off — runs all
        # replica seeds in one jitted scan (no Python-loop fallback); sweeps
        # go through run_scenarios, which batches whole shape classes.
        return _run_training_scenarios([s], replicas=replicas)[0]

    if substrate == "schedule":
        r = simulate_schedule(
            layer_profile(s.layer_profile),
            n_workers=s.n_workers,
            link=Link(alpha=s.alpha, beta=s.beta),
            alg=s.allreduce_alg,
            mode=s.schedule,
            bucket_bytes=s.bucket_bytes,
            staleness=s.overlap_staleness,
        )
        measured = {k: float(v) for k, v in r.items()}
        return ScenarioResult(s, substrate, measured, pred, replicas=1)

    if substrate == "roofline":
        return ScenarioResult(s, substrate, roofline_row(s), pred, replicas=1)

    if substrate == "trainer":
        from repro.experiments.trainer_substrate import run_trainer_scenario

        return run_trainer_scenario(s)

    raise ValueError(f"unknown substrate {substrate!r}")


def run_scenarios(
    scenarios: list[Scenario],
    substrate: str = "timeline",
    *,
    replicas: int = 1,
) -> list[ScenarioResult]:
    """Run every scenario, preserving order. Invalid cells raise — filter
    with :func:`repro.experiments.scenario.expand` first.

    On the ``training`` substrate the list is grouped into shape classes
    (:func:`training_shape_key`) and each class executes as ONE compiled
    batched program — the sweep compiles once per class, not once per cell.
    The ``trainer`` substrate analogously routes through
    :func:`repro.experiments.trainer_substrate.run_trainer_sweep`, so cells
    sharing a static ``BundleSpec`` reuse one compiled bundle."""
    if substrate == "training":
        return _run_training_scenarios(list(scenarios), replicas=replicas)
    if substrate == "trainer":
        from repro.experiments.trainer_substrate import run_trainer_sweep

        scenarios = list(scenarios)
        for s in scenarios:
            bad = s.violations("trainer")
            if bad:
                raise ValueError(
                    f"invalid scenario {s.tag()} on trainer: {'; '.join(bad)}")
        results, skipped = run_trainer_sweep(scenarios)
        if skipped:
            why = "; ".join(f"{s.tag()}: {r}" for s, r in skipped)
            raise ValueError(f"trainer cells not runnable: {why}")
        return results  # type: ignore[return-value]
    return [run_scenario(s, substrate, replicas=replicas) for s in scenarios]


# ---------------------------------------------------------------------------
# Batched-sweep speedup measurement (the BENCH_sweep.json record).
# ---------------------------------------------------------------------------


def sweep_matrix_45(*, steps: int = 60, n_workers: int = 8, seed: int = 0,
                    problem_seeds: tuple[int, ...] = (0,)) -> list[Scenario]:
    """The fixed 45-cell perf-tracking sweep: 5 sync/topology schemes x
    3 quantization levels x 3 learning rates (qsgd+EF everywhere).  Exactly
    5 shape classes — within a scheme the cells differ only in traced
    values, so the batched engine compiles 5 programs where the per-cell
    path compiles 45.  ``problem_seeds`` replicates the matrix across
    problem instances (45 x len cells): because problem data is traced, the
    class count — and the compile count — stays 5."""
    cells = []
    for sync, arch in (("bsp", "allreduce"), ("local", "allreduce"),
                       ("ssp", "ps"), ("asp", "ps"), ("bsp", "gossip")):
        for levels in (4, 8, 16):
            for lr in (0.02, 0.05, 0.08):
                for ps in problem_seeds:
                    cells.append(Scenario(
                        sync=sync, arch=arch, n_workers=n_workers, steps=steps,
                        lr=lr, staleness=4, local_steps=8, compressor="qsgd",
                        compressor_kwargs={"levels": levels}, error_feedback=True,
                        seed=seed + ps))
    return cells


def measure_sweep_speedup(
    scenarios: list[Scenario] | None = None,
    *,
    replicas: int = 1,
    percell: bool = True,
) -> dict[str, Any]:
    """Wall-clock + compile count of the shape-class batched sweep vs the
    per-cell PR 2 path (one fresh ``jit(vmap(scan))`` trace per cell) on the
    same scenario list, plus the max deviation between the two result sets.
    The acceptance record behind ``BENCH_sweep.json``."""
    import time

    scenarios = sweep_matrix_45() if scenarios is None else list(scenarios)
    classes = {training_shape_key(s) for s in scenarios}
    # what the class count would be WITHOUT the traced-problem-data protocol:
    # the pre-data-threading key also pinned the problem instance (seed)
    classes_per_problem = {training_shape_key(s) + (s.seed,) for s in scenarios}

    engine_cache_clear()
    t0 = time.perf_counter()
    batched = _run_training_scenarios(scenarios, replicas=replicas)
    batched_s = time.perf_counter() - t0
    st = engine_cache_stats()
    compiles_batched = st.compiles

    out: dict[str, Any] = {
        "n_cells": len(scenarios),
        "n_shape_classes": len(classes),
        "n_problem_instances": len({(s.objective, s.n_workers, s.seed)
                                    for s in scenarios}),
        "n_classes_without_shared_problems": len(classes_per_problem),
        "replicas": replicas,
        "steps": scenarios[0].steps,
        "n_workers": scenarios[0].n_workers,
        "compiles_batched": compiles_batched,
        "batched_s": batched_s,
        "cells_per_s_batched": len(scenarios) / batched_s,
        "persistent_cache": st.persistent_cache,
    }
    if not percell:
        return out

    engine_cache_clear()
    t0 = time.perf_counter()
    percell_res = [
        _run_training_scenarios([s], replicas=replicas, cache=False)[0]
        for s in scenarios
    ]
    percell_s = time.perf_counter() - t0
    compiles_percell = engine_cache_stats().compiles  # counters were cleared

    dev_loss = max(
        float(np.max(np.abs(b.series["loss"] - p.series["loss"])
                     / np.maximum(np.abs(p.series["loss"]), 1e-6)))
        for b, p in zip(batched, percell_res)
    )
    dev_bits = max(
        float(np.max(np.abs(b.series["bits"] - p.series["bits"])
                     / np.maximum(np.abs(p.series["bits"]), 1.0)))
        for b, p in zip(batched, percell_res)
    )
    out.update({
        "compiles_percell": compiles_percell,
        "percell_s": percell_s,
        "speedup": percell_s / batched_s,
        "max_rel_dev_loss": dev_loss,
        "max_rel_dev_bits": dev_bits,
    })
    return out
