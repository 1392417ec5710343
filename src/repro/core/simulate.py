"""Discrete-event and multi-worker training simulators.

Two engines:

1. :func:`simulate_timeline` — discrete-event model of n workers with a
   straggler distribution under BSP / SSP(s) / ASP / Local-SGD(H) and a
   PS / All-Reduce / Gossip communication model (alpha-beta costs, PS
   congestion).  Regenerates the paper's Fig. 4 timelines and the
   Table II qualitative matrix quantitatively.

2. :func:`simulate_training` — an *exact* (not event-driven) multi-worker
   SGD simulator: one jitted ``lax.scan`` over steps whose carry holds
   ``(X, ef, delay_buf, key, total_bits, mask)``, vmapped over workers
   inside the step, over replica seeds outside it (:func:`simulate_training_batch`),
   and — new in PR 3 — over whole taxonomy *cells* outside that
   (:func:`simulate_training_classbatch`): a cell's config splits into a
   static :class:`EngineSpec` and a traced :class:`CellParams`, so every
   cell of one *shape class* (same sync scheme / worker count / steps /
   compressor family / EF flag) shares ONE compiled program regardless of
   its lr / staleness / Local-H / compressor-knob values.  Every sync scheme
   (bsp/local/ssp/asp/gossip) and every registered compressor (+EF,
   including the fused Pallas EF kernel) runs in the one compiled scan;
   wire bits are accumulated in-scan, *measured* from the realized support
   for data-dependent (threshold-style) compressors;
   :func:`simulate_training_reference` keeps the original per-step Python
   loop as the equivalence baseline.  Used for the convergence-rate
   benchmarks (paper §VIII, Table IV) on convex (quadratic/logistic)
   objectives — the substrate for validating the survey's convergence
   claims empirically.

Both engines are deliberately CPU-friendly (no mesh needed).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


# ---------------------------------------------------------------------------
# 1. Discrete-event timeline simulator (Fig. 4 / Table II).
# ---------------------------------------------------------------------------


@dataclass
class TimelineCfg:
    n_workers: int = 16
    iters: int = 200
    compute_mean: float = 1.0  # per-iteration compute time
    straggler_sigma: float = 0.2  # lognormal sigma
    straggler_worker_slowdown: float = 1.0  # multiplicative slowdown of worker 0
    # alpha-beta communication model (paper Table III)
    alpha: float = 1e-3  # per-message latency (s)
    beta: float = 1e-9  # per-byte time (s/B)  ~ 1 GB/s links
    msg_bytes: float = 4 * 25e6  # 25M-param f32 model/gradient
    server_bw_share: bool = True  # PS congestion: uploads share server link
    sync: str = "bsp"  # bsp | ssp | asp | local
    staleness: int = 3  # SSP bound
    local_steps: int = 8  # Local SGD H
    arch: str = "ps"  # ps | allreduce | gossip
    seed: int = 0
    # heterogeneity (churn axis): per-worker speed multipliers (1.0 =
    # nominal; empty = homogeneous) and the straggler draw family
    worker_speeds: tuple = ()
    straggler_dist: str = "lognormal"  # lognormal | uniform | none
    # churn as a timeline EVENT STREAM: per-iteration Bernoulli offline
    # draws inside the [churn_start, churn_end) window produce drop/rejoin
    # transitions; every rejoin charges a resync cost through the
    # alpha-beta model ("pull_avg": a full model pull, alpha + beta*N and
    # N wire bytes; "reset": a membership handshake, alpha only).
    dropout_rate: float = 0.0  # per-iteration P(worker offline)
    worker_dropout: tuple = ()  # per-worker override (length n_workers)
    churn_start: int = 0  # first iteration (inclusive) dropout applies
    churn_end: int = -1  # last iteration (exclusive); -1 = until the end
    rejoin_policy: str = "reset"  # reset | pull_avg
    # gradient-integrity axis: per-round P(a live worker's payload is
    # corrupted).  A corrupted round is QUARANTINED — the bytes moved but are
    # booked undelivered — and `quarantine_limit` consecutive quarantines
    # escalate to a forced rejoin (charging the policy's resync cost).
    corruption_rate: float = 0.0
    corruption_kind: str = "none"  # none | nan | inf | spike | bitflip
    quarantine_limit: int = 3


@dataclass
class TimelineResult:
    finish_times: np.ndarray  # (workers, iters) completion wall-clock
    throughput: float  # global iterations/sec
    idle_frac: float
    mean_staleness: float
    comm_frac: float
    bytes_per_worker: float = 0.0  # wire bytes each worker moved (up+down)
    # churn event accounting: rejoin transitions observed and the resync
    # cost they charged (seconds on the rejoiner's clock, bytes on the wire)
    resync_events: int = 0
    resync_seconds: float = 0.0
    resync_bytes: float = 0.0
    # gradient-integrity accounting: rounds whose payload was quarantined
    # (sent but not delivered), the wire bytes they moved, and bounded-
    # quarantine escalations to the rejoin protocol
    quarantine_events: int = 0
    quarantined_bytes: float = 0.0
    escalation_events: int = 0

    def row(self) -> dict:
        return {
            "throughput": self.throughput,
            "idle_frac": self.idle_frac,
            "mean_staleness": self.mean_staleness,
            "comm_frac": self.comm_frac,
            "bytes_per_worker": self.bytes_per_worker,
            "resync_events": self.resync_events,
            "resync_seconds": self.resync_seconds,
            "resync_bytes": self.resync_bytes,
            "quarantine_events": self.quarantine_events,
            "quarantined_bytes": self.quarantined_bytes,
            "escalation_events": self.escalation_events,
        }


def _comm_time(cfg: TimelineCfg, concurrent: int) -> float:
    """Per-iteration communication time under the architecture model."""
    a, b, N = cfg.alpha, cfg.beta, cfg.msg_bytes
    n = cfg.n_workers
    if cfg.arch == "ps":
        # upload + download; server link shared by `concurrent` workers
        share = max(1, concurrent) if cfg.server_bw_share else 1
        return 2 * (a + b * N * share)
    if cfg.arch == "allreduce":
        # ring: 2(n-1) alpha + 2 (n-1)/n beta N   (Table III)
        return 2 * (n - 1) * a + 2 * (n - 1) / n * b * N
    if cfg.arch == "gossip":
        return 2 * (a + b * N)  # exchange with 2 neighbors (parallel links)
    raise ValueError(cfg.arch)


def _comm_bytes(cfg: TimelineCfg) -> float:
    """Per-worker wire bytes of one round (shared costmodel formula)."""
    from repro.core.costmodel import round_wire_bytes

    return round_wire_bytes(cfg.arch, cfg.n_workers, cfg.msg_bytes)


def simulate_timeline(cfg: TimelineCfg) -> TimelineResult:
    rng = np.random.default_rng(cfg.seed)
    n, T = cfg.n_workers, cfg.iters
    if cfg.straggler_dist == "lognormal":
        compute = rng.lognormal(np.log(cfg.compute_mean), cfg.straggler_sigma, (n, T))
    elif cfg.straggler_dist == "uniform":
        # same sigma knob reinterpreted as the half-width fraction
        lo = cfg.compute_mean * max(1e-6, 1.0 - cfg.straggler_sigma)
        hi = cfg.compute_mean * (1.0 + cfg.straggler_sigma)
        compute = rng.uniform(lo, hi, (n, T))
    elif cfg.straggler_dist == "none":
        compute = np.full((n, T), cfg.compute_mean)
    else:
        raise ValueError(cfg.straggler_dist)
    compute[0] *= cfg.straggler_worker_slowdown
    if cfg.worker_speeds:
        if len(cfg.worker_speeds) != n:
            raise ValueError("worker_speeds length must equal n_workers")
        compute /= np.asarray(cfg.worker_speeds, dtype=float)[:, None]

    # churn event stream: Bernoulli offline draws inside the window become
    # drop/rejoin TRANSITIONS; a masked iteration contributes no compute and
    # moves no bytes, and every rejoin charges the policy's resync cost on
    # the rejoiner's clock.  Drawn after the compute draw so churn-free
    # cells reproduce the exact pre-churn trajectories.
    churn_on = bool(cfg.dropout_rate > 0 or any(cfg.worker_dropout))
    alive = np.ones((n, T), dtype=bool)
    rejoin = np.zeros((n, T), dtype=bool)
    resync_t = resync_b = 0.0
    if churn_on:
        if cfg.rejoin_policy not in ("reset", "pull_avg"):
            raise ValueError(
                f"unknown rejoin_policy {cfg.rejoin_policy!r} "
                "(expected 'reset' or 'pull_avg')")
        rates = (np.asarray(cfg.worker_dropout, dtype=float)
                 if cfg.worker_dropout else np.full(n, cfg.dropout_rate))
        if rates.shape[0] != n:
            raise ValueError("worker_dropout length must equal n_workers")
        start = min(max(int(cfg.churn_start), 0), T)
        end = T if cfg.churn_end < 0 else min(int(cfg.churn_end), T)
        if end > start:
            u = rng.uniform(size=(n, end - start))
            alive[:, start:end] = u >= rates[:, None]
        prev = np.concatenate([np.ones((n, 1), bool), alive[:, :-1]], axis=1)
        rejoin = alive & ~prev
        if cfg.rejoin_policy == "pull_avg":
            # a full model pull over the link
            resync_t = cfg.alpha + cfg.beta * cfg.msg_bytes
            resync_b = cfg.msg_bytes
        else:
            resync_t = cfg.alpha  # membership handshake only
        compute = compute * alive + resync_t * rejoin
    resync_events = int(rejoin.sum())
    resync_seconds_total = resync_t * resync_events
    resync_bytes_total = resync_b * resync_events

    # gradient-integrity event stream: per-round Bernoulli corruption draws
    # over the live set (same window as churn).  A corrupted WIRE round is
    # quarantined — the bytes moved but were not delivered — and
    # `quarantine_limit` consecutive quarantines escalate to a forced rejoin
    # that charges the policy's resync cost on the worker's clock.  Drawn
    # after the churn draws so corruption-free cells keep their trajectories.
    corrupt = np.zeros((n, T), dtype=bool)
    esc = np.zeros((n, T), dtype=bool)
    esc_t = esc_b = 0.0
    if cfg.corruption_rate > 0:
        if cfg.corruption_kind not in ("nan", "inf", "spike", "bitflip"):
            raise ValueError(
                f"corruption_rate > 0 needs a corruption_kind "
                f"(got {cfg.corruption_kind!r})")
        if cfg.rejoin_policy not in ("reset", "pull_avg"):
            raise ValueError(
                f"unknown rejoin_policy {cfg.rejoin_policy!r} "
                "(expected 'reset' or 'pull_avg')")
        start = min(max(int(cfg.churn_start), 0), T)
        end = T if cfg.churn_end < 0 else min(int(cfg.churn_end), T)
        if end > start:
            cu = rng.uniform(size=(n, end - start))
            corrupt[:, start:end] = ((cu < cfg.corruption_rate)
                                     & alive[:, start:end])
        # only wire rounds count (local syncs every H-th iteration)
        if cfg.sync == "local":
            wire_round = np.arange(T) % cfg.local_steps == cfg.local_steps - 1
        else:
            wire_round = np.ones(T, dtype=bool)
        corrupt &= wire_round[None, :]
        q = np.zeros(n, dtype=int)
        for t in range(T):
            if not wire_round[t]:
                continue
            q = np.where(alive[:, t] & corrupt[:, t], q + 1,
                         np.where(alive[:, t], 0, q))
            e = q >= cfg.quarantine_limit
            esc[:, t] = e
            q[e] = 0
        if cfg.rejoin_policy == "pull_avg":
            esc_t = cfg.alpha + cfg.beta * cfg.msg_bytes
            esc_b = cfg.msg_bytes
        else:
            esc_t = cfg.alpha  # membership handshake only
        compute = compute + esc_t * esc
    escalation_events = int(esc.sum())
    quarantine_events = int(corrupt.sum())
    # escalation resyncs are real (delivered) transfers — book them with the
    # rejoin resyncs so the per-sync bytes accounting below picks them up
    resync_seconds_total += esc_t * escalation_events
    resync_bytes_total += esc_b * escalation_events

    finish = np.zeros((n, T))
    t = np.zeros(n)  # current wall-clock per worker
    done = np.zeros(n, dtype=int)  # iterations completed
    comm_total = np.zeros(n)
    stale_samples = []
    bytes_per_worker = 0.0
    round_bytes = _comm_bytes(cfg)

    if cfg.sync == "bsp":
        # Vectorized: after every barrier all workers share one clock, so the
        # iteration time is the per-iteration max compute + comm — a single
        # cumulative sum over iterations instead of the per-step Python loop.
        c = _comm_time(cfg, concurrent=n)
        t_end = np.cumsum(compute.max(axis=0) + c)  # (T,) barrier+comm ends
        finish[:] = t_end[None, :]
        t_prev = np.concatenate([[0.0], t_end[:-1]])
        comm_total = (t_end[None, :] - (t_prev[None, :] + compute)).sum(axis=1)
        # masked workers move no payload that round; resync pulls are extra
        bytes_per_worker = (round_bytes * alive.sum() / n
                            + resync_bytes_total / n)
        stale_samples = [0.0]
    elif cfg.sync == "local":
        # Vectorized per H-step segment: workers run free inside a segment
        # (within-segment cumsum), then barrier on the segment max.
        H = cfg.local_steps
        c = _comm_time(cfg, concurrent=n)
        K, rem = divmod(T, H)
        seg_end = 0.0
        if K:
            seg_cum = compute[:, : K * H].reshape(n, K, H).cumsum(axis=2)
            seg_tot = seg_cum[:, :, -1]  # (n, K) per-worker segment compute
            incr = seg_tot.max(axis=0) + c  # (K,) barrier-to-barrier time
            seg_start = np.concatenate([[0.0], np.cumsum(incr)[:-1]])
            fin = seg_start[None, :, None] + seg_cum  # (n, K, H)
            sync_end = seg_start + incr
            fin[:, :, -1] = sync_end[None, :]
            finish[:, : K * H] = fin.reshape(n, K * H)
            comm_total = (sync_end[None, :] - (seg_start[None, :] + seg_tot)).sum(axis=1)
            # a worker masked at the sync point skips that round's exchange
            part = alive[:, H - 1 : K * H : H]  # (n, K) at-sync participation
            bytes_per_worker = round_bytes * part.sum() / n
            seg_end = sync_end[-1]
        if rem:  # trailing partial segment never reaches a sync point
            finish[:, K * H :] = seg_end + compute[:, K * H :].cumsum(axis=1)
        bytes_per_worker += resync_bytes_total / n
        stale_samples = [0.0]
    else:  # ssp / asp: event-driven per worker
        # each worker proceeds; SSP blocks if ahead of slowest by > s
        c_one = _comm_time(cfg, concurrent=max(1, n // 4))  # partial congestion
        for step in range(T * n):
            i = int(np.argmin(t + (done >= T) * 1e18))
            if done[i] >= T:
                break
            if cfg.sync == "ssp":
                lag = done[i] - done.min()
                if lag > cfg.staleness:
                    # wait until the slowest finishes one more iteration
                    j = int(np.argmin(done))
                    wait = max(0.0, t[j] + compute[j, min(done[j], T - 1)] - t[i])
                    t[i] += wait
            start = t[i]
            al = float(alive[i, done[i]])  # masked iter: no compute, no wire
            t[i] += compute[i, done[i]] + c_one * al
            comm_total[i] += c_one * al
            bytes_per_worker += (round_bytes * al
                                 + resync_b * rejoin[i, done[i]]
                                 + esc_b * esc[i, done[i]]) / n
            finish[i, done[i]] = t[i]
            stale_samples.append(done[i] - done.min())
            done[i] += 1

    makespan = finish.max()
    total_iters = (finish > 0).sum()
    busy = compute[:, : finish.shape[1]].sum()
    return TimelineResult(
        finish_times=finish,
        throughput=total_iters / makespan,
        idle_frac=float(1.0 - busy / (makespan * n)),
        mean_staleness=float(np.mean(stale_samples)),
        comm_frac=float(comm_total.sum() / (makespan * n)),
        bytes_per_worker=float(bytes_per_worker),
        resync_events=resync_events,
        resync_seconds=float(resync_seconds_total),
        resync_bytes=float(resync_bytes_total),
        quarantine_events=quarantine_events,
        quarantined_bytes=float(round_bytes * quarantine_events),
        escalation_events=escalation_events,
    )


# ---------------------------------------------------------------------------
# 2. Multi-worker SGD simulator (convergence studies, §VIII).
# ---------------------------------------------------------------------------


@dataclass
class SimCfg:
    n_workers: int = 8
    sync: str = "bsp"  # bsp | ssp | asp | local | gossip
    staleness: int = 4  # fixed delay for asp; max advance for ssp
    local_steps: int = 8
    compressor: Any = None  # repro.core.compression instance
    error_feedback: bool = False
    lr: float = 0.05
    steps: int = 300
    seed: int = 0
    gossip_w: float = 1.0 / 3.0
    # churn (elastic-worker) axis: the scan always draws a per-step
    # participation mask; the probabilities / window are traced values (at
    # dropout 0, or outside the window, the mask is all ones).
    dropout_rate: float = 0.0  # shared per-step P(worker offline)
    worker_dropout: tuple = ()  # per-worker override (length n_workers)
    churn_start: int = 0  # first step (inclusive) dropout applies
    churn_end: int = -1  # last step (exclusive); -1 = until the end
    #: rejoin protocol — "reset" resets compressor state (EF residual) on
    #: rejoin and lets parameters re-enter via the scheme's own averaging;
    #: "pull_avg" additionally pulls the live-set parameter average at the
    #: rejoin step (local/gossip schemes, where a rejoiner is actually
    #: stale; elsewhere it normalizes to "reset"), charging a dense model
    #: download per rejoin event.
    rejoin_policy: str = "reset"
    # gradient-integrity axis: per-round P(a live worker's wire payload is
    # corrupted in-domain).  The KIND is structural (naming one compiles the
    # guarded program, the identity at rate 0); the rate is traced.  A
    # detected-corrupt contribution is quarantined for one round;
    # `quarantine_limit` consecutive quarantines escalate to the rejoin
    # protocol above.
    corruption_rate: float = 0.0
    corruption_kind: str = "none"  # none | nan | inf | spike | bitflip
    quarantine_limit: int = 3


class Problem(tuple):
    """A ``(grad, loss, x0, x_star)`` 4-tuple (unpacks everywhere the plain
    tuple did) that additionally exposes its seed-dependent arrays as a
    *traced-data* pytree:

    * ``data`` — every array drawn from the problem seed (including
      ``x_star``); the batched engine passes it as a traced argument, so
      cells that differ ONLY in problem seed share one compiled program
      (``grad(..., data=...)`` / ``loss(x, data=...)`` read from it);
    * ``data_key`` — hashable structural identity (objective family +
      shapes) of the program the problem yields: the compiled-program cache
      key, replacing the old per-instance ``id(problem)`` pin;
    * ``noise`` — the factory's baked gradient-noise scale, part of the
      cache key when a cell does NOT trace ``grad_noise``.
    """

    data: dict | None
    data_key: tuple | None
    noise: float

    def __new__(cls, grad, loss, x0, x_star, *, data=None, data_key=None,
                noise=0.0):
        obj = super().__new__(cls, (grad, loss, x0, x_star))
        obj.data = data
        obj.data_key = data_key
        obj.noise = noise
        return obj


def quadratic_problem(dim: int = 64, n_workers: int = 8, noise: float = 0.1, seed: int = 0):
    """f_i(x) = 1/2 (x-b_i)^T A (x-b_i): strongly convex with worker
    heterogeneity; f* and x* known in closed form."""
    rng = np.random.default_rng(seed)
    evals = np.linspace(0.5, 5.0, dim)
    Q = np.linalg.qr(rng.normal(size=(dim, dim)))[0]
    A = jnp.asarray(Q @ np.diag(evals) @ Q.T, f32)
    b = jnp.asarray(rng.normal(size=(n_workers, dim)) * 1.0, f32)

    def grad(x, i, key, noise=noise, data=None):
        A_, b_ = (data["A"], data["b"]) if data is not None else (A, b)
        g = A_ @ (x - b_[i])
        return g + noise * jax.random.normal(key, x.shape)

    def loss(x, data=None):
        A_, b_ = (data["A"], data["b"]) if data is not None else (A, b)
        d = x[None, :] - b_
        return 0.5 * jnp.mean(jnp.einsum("nd,de,ne->n", d, A_, d))

    x_star = jnp.mean(b, axis=0)
    return Problem(grad, loss, jnp.zeros((dim,), f32), x_star,
                   data={"A": A, "b": b, "x_star": x_star},
                   data_key=("quadratic", dim, n_workers), noise=noise)


def logistic_problem(dim: int = 32, n_workers: int = 8, n_samples: int = 64,
                     noise: float = 0.05, seed: int = 0):
    """Worker-heterogeneous l2-regularized logistic regression: each worker
    holds its own sample shard (drawn around a shifted ground truth), the
    convex-but-not-quadratic testbed of the survey's §VIII experiments."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim,))
    feats = jnp.asarray(rng.normal(size=(n_workers, n_samples, dim)), f32)
    shift = rng.normal(size=(n_workers, dim)) * 0.3
    logits = np.einsum("nsd,nd->ns", np.asarray(feats), w_true[None] + shift)
    labels = jnp.asarray((logits + rng.logistic(size=logits.shape) > 0).astype(np.float32))
    lam = 1e-2

    def _loss_one(x, i, feats_, labels_):
        z = feats_[i] @ x
        return jnp.mean(jnp.logaddexp(0.0, z) - labels_[i] * z) + 0.5 * lam * jnp.sum(x * x)

    def grad(x, i, key, noise=noise, data=None):
        f_, l_ = (data["feats"], data["labels"]) if data is not None else (feats, labels)
        g = jax.grad(_loss_one)(x, i, f_, l_)
        return g + noise * jax.random.normal(key, x.shape)

    def loss(x, data=None):
        f_, l_ = (data["feats"], data["labels"]) if data is not None else (feats, labels)
        return jnp.mean(jnp.stack([_loss_one(x, i, f_, l_) for i in range(n_workers)]))

    x0 = jnp.zeros((dim,), f32)
    # x* has no closed form; report distance to the heterogeneity-free truth
    x_star = jnp.asarray(w_true, f32)
    return Problem(grad, loss, x0, x_star,
                   data={"feats": feats, "labels": labels, "x_star": x_star},
                   data_key=("logistic", dim, n_workers, n_samples), noise=noise)


PROBLEMS = {
    "quadratic": quadratic_problem,
    "logistic": logistic_problem,
}


# ---------------------------------------------------------------------------
# 2a. The shape-class batched scan engine (one compile per shape class).
# ---------------------------------------------------------------------------
#
# A taxonomy cell splits into
#
#   * EngineSpec   — the STATIC half: anything that changes XLA program
#     structure (sync scheme, worker count, step count, EF on/off, the
#     compressor *family* fingerprint, the delay-line depth);
#   * CellParams   — the TRACED half: anything that only changes values
#     (lr, Local-SGD H, staleness bound, gossip mixing weight, gradient
#     noise, compressor knobs such as quantization levels / top-k fraction /
#     threshold / powersgd rank).
#
# Cells with equal EngineSpec (and the same problem instance) form one
# *shape class* and run as ONE ``jit(vmap_cells(vmap_seeds(scan)))`` —
# a 45-cell sweep that spans 5 shape classes compiles 5 programs, not 45.


@dataclass(frozen=True)
class EngineSpec:
    """Static (program-structure) half of a cell."""

    sync: str
    n_workers: int
    steps: int
    error_feedback: bool
    comp_key: tuple  # compressor shape fingerprint (("dense",) for None)
    delay_slots: int = 1  # delay-line depth >= max staleness + 1 in the class
    traced_noise: bool = False  # grad noise passed as a traced CellParams value
    #: "reset" | "pull_avg" — structural (the pull program differs);
    #: normalized to "reset" outside local/gossip, which have no pull
    rejoin_policy: str = "reset"
    #: corruption kind (STRUCTURAL — the detect/quarantine program differs
    #: per kind; "none" = unguarded)
    corruption_kind: str = "none"


@dataclass
class CellParams:
    """Traced (values-only) half of a cell.  ``comp`` holds the compressor's
    knob values (``base.batch_param_values``); ``grad_noise`` is None when
    the problem's noise stays baked into the gradient closure."""

    lr: float = 0.05
    local_steps: int = 8
    staleness: int = 4
    gossip_w: float = 1.0 / 3.0
    grad_noise: float | None = None
    comp: dict[str, float] = field(default_factory=dict)
    # churn values (traced): per-worker dropout probabilities and the
    # [start, end) step window
    dropout: tuple = ()
    churn_start: float = 0.0
    churn_end: float = float("inf")
    # gradient-integrity values (traced; present only when the spec carries
    # the guarded program): corruption probability + escalation bound
    corruption: float | None = None
    quarantine_limit: float = 3.0

    def as_tree(self) -> dict:
        out = {
            "lr": jnp.asarray(self.lr, f32),
            "local_steps": jnp.asarray(self.local_steps, jnp.int32),
            "staleness": jnp.asarray(self.staleness, jnp.int32),
            "gossip_w": jnp.asarray(self.gossip_w, f32),
            "comp": {k: jnp.asarray(v, f32) for k, v in self.comp.items()},
            "dropout": jnp.asarray(self.dropout, f32),
            "churn_start": jnp.asarray(self.churn_start, f32),
            "churn_end": jnp.asarray(self.churn_end, f32),
        }
        if self.grad_noise is not None:
            out["grad_noise"] = jnp.asarray(self.grad_noise, f32)
        if self.corruption is not None:
            out["corruption"] = jnp.asarray(self.corruption, f32)
            out["quarantine_limit"] = jnp.asarray(self.quarantine_limit, f32)
        return out


def _grad_takes_noise(grad_fn) -> bool:
    import inspect

    try:
        return "noise" in inspect.signature(grad_fn).parameters
    except (TypeError, ValueError):
        return False


def _rejoin_policy(cfg: SimCfg) -> str:
    """The structural rejoin policy: only local/gossip pull a rejoiner (the
    PS schemes' global model makes rejoin implicit)."""
    return cfg.rejoin_policy if cfg.sync in ("local", "gossip") else "reset"


def split_cfg(cfg: SimCfg, *, grad_noise: float | None = None,
              dim: int | None = None) -> tuple[EngineSpec, CellParams]:
    """Decompose one :class:`SimCfg` into its static/traced halves.  ``dim``
    (the problem dimension) is required when the compressor has traced knobs
    — element-count knobs like top-k's ``k`` derive from it."""
    from repro.core.compression.base import batch_knobs, batch_param_values, shape_fingerprint

    if cfg.sync not in ("bsp", "local", "ssp", "asp", "gossip"):
        raise ValueError(cfg.sync)
    if dim is None and cfg.compressor is not None and batch_knobs(cfg.compressor):
        raise ValueError(
            f"split_cfg needs dim to derive {type(cfg.compressor).__name__} "
            f"knob values ({batch_knobs(cfg.compressor)})")
    if cfg.worker_dropout and len(cfg.worker_dropout) != cfg.n_workers:
        raise ValueError("worker_dropout length must equal n_workers")
    if cfg.rejoin_policy not in ("reset", "pull_avg"):
        raise ValueError(
            f"unknown rejoin_policy {cfg.rejoin_policy!r} "
            "(expected 'reset' or 'pull_avg')")
    if cfg.corruption_kind not in ("none", "nan", "inf", "spike", "bitflip"):
        raise ValueError(
            f"unknown corruption_kind {cfg.corruption_kind!r} "
            "(expected none|nan|inf|spike|bitflip)")
    if cfg.corruption_rate > 0 and cfg.corruption_kind == "none":
        raise ValueError("corruption_rate > 0 needs a corruption_kind")
    if not 0.0 <= cfg.corruption_rate < 1.0:
        raise ValueError("corruption_rate must be in [0, 1)")
    if cfg.quarantine_limit < 1:
        raise ValueError("quarantine_limit must be >= 1")
    kind = cfg.corruption_kind
    spec = EngineSpec(
        sync=cfg.sync,
        n_workers=cfg.n_workers,
        steps=cfg.steps,
        error_feedback=bool(cfg.error_feedback),
        comp_key=shape_fingerprint(cfg.compressor),
        delay_slots=cfg.staleness + 1 if cfg.sync in ("ssp", "asp") else 1,
        traced_noise=grad_noise is not None,
        rejoin_policy=_rejoin_policy(cfg),
        corruption_kind=kind,
    )
    dropout = (tuple(float(p) for p in cfg.worker_dropout)
               if cfg.worker_dropout
               else (float(cfg.dropout_rate),) * cfg.n_workers)
    params = CellParams(
        lr=cfg.lr,
        local_steps=cfg.local_steps,
        staleness=cfg.staleness,
        gossip_w=cfg.gossip_w,
        grad_noise=grad_noise,
        comp=batch_param_values(cfg.compressor, dim) if dim is not None else {},
        dropout=dropout,
        churn_start=float(cfg.churn_start),
        churn_end=float(cfg.churn_end) if cfg.churn_end >= 0 else float("inf"),
        corruption=float(cfg.corruption_rate) if kind != "none" else None,
        quarantine_limit=float(cfg.quarantine_limit),
    )
    return spec, params


def shape_class_key(cfg: SimCfg) -> tuple:
    """Hashable grouping key: cells with equal keys (and one shared problem)
    can run in one compiled sweep program.  Delay-line depth and structural
    knob envelopes (powersgd max rank) are *not* in the key — they are
    resolved to the class maximum after grouping."""
    from repro.core.compression.base import shape_fingerprint

    return (cfg.sync, cfg.n_workers, cfg.steps, bool(cfg.error_feedback),
            shape_fingerprint(cfg.compressor), _rejoin_policy(cfg),
            cfg.corruption_kind)


def _build_cell_replica_fn(spec: EngineSpec, comp, problem):
    """The parameterized scan: ``replica_fn(p, seed_key, data)`` where ``p``
    is a CellParams tree of *traced* scalars and ``data`` is the problem's
    traced-data pytree (``None`` for legacy problems, whose arrays stay
    baked into the trace).  Workers are vmapped inside the step; the caller
    vmaps replica seeds and (for a class batch) cells — with per-cell
    ``data``, cells differing only in problem seed share the program.
    The carry is ``(X, ef, delay_buf, key, total_bits, m_prev)`` — the last
    is the previous round's participation mask, for rejoin detection; wire
    bits are accumulated in-scan from the compressor roundtrip — data-dependent
    (threshold-style) payloads charge their *measured* size."""
    from repro.core import integrity
    from repro.core.compression.base import roundtrip_bits, roundtrip_bits_ef

    grad_fn, loss_fn, x0, x_star0 = problem
    has_data = getattr(problem, "data", None) is not None
    n, dim = spec.n_workers, x0.size
    sync = spec.sync
    corrupt = spec.corruption_kind != "none"
    widx = jnp.arange(n)
    if spec.traced_noise and not _grad_takes_noise(grad_fn):
        raise ValueError(
            "traced grad noise requires a problem whose grad accepts a "
            "`noise` keyword (both built-in problems do)")

    def replica_fn(p: dict, seed_key, data=None):
        lr = p["lr"]
        cp = p["comp"]
        loss_fn_ = (lambda x: loss_fn(x, data=data)) if has_data else loss_fn
        x_star = data["x_star"] if has_data else x_star0
        if sync == "gossip":
            from repro.core.gossip import masked_mixing_matrix, ring_mixing_matrix_traced

            W = ring_mixing_matrix_traced(n, p["gossip_w"])
        # SSP: workers alternate being ahead — worker i's gradient is delayed
        # i % (s+1) steps, read from the rolled delay line with one gather.
        d_idx = jnp.mod(widx, p["staleness"] + 1)

        def grad_all(X, gkeys):
            kw = {"data": data} if has_data else {}
            if spec.traced_noise:
                return jax.vmap(
                    lambda x, i, k: grad_fn(x, i, k, noise=p["grad_noise"], **kw)
                )(X, widx, gkeys)
            return jax.vmap(lambda x, i, k: grad_fn(x, i, k, **kw))(X, widx, gkeys)

        def apply_compression(ckeys, G, ef):
            """Compress every worker's (effective) gradient; returns the
            reconstruction, the new EF residual, and the PER-WORKER wire-bit
            vector of this round (callers sum it under the mask)."""
            if comp is None:
                return G, ef, jnp.full((n,), 32.0 * dim, f32)
            if spec.error_feedback:
                out, ef2, wb = jax.vmap(
                    lambda k, g, e: roundtrip_bits_ef(comp, k, g, e, cp)
                )(ckeys, G, ef)
                return out, ef2, wb
            out, wb = jax.vmap(lambda k, g: roundtrip_bits(comp, k, g, cp))(ckeys, G)
            return out, ef, wb

        def step(carry, t):
            X, ef, delay_buf, key, total_bits, m_prev = carry[:6]
            if corrupt:
                qc, qb, qr, qe = carry[6:]
            key, k1, k2 = jax.random.split(key, 3)
            gkeys = jax.random.split(k1, n)
            ckeys = jax.random.split(k2, n)
            # participation mask: folds out of the NEW carry key (the split
            # above is untouched), so the gradient/compressor key streams
            # do not depend on the dropout values; all ones at dropout 0 or
            # outside the window.  The window scales the rates instead of
            # gating the draw: with a gate, one executable rounded a closed
            # window 1 ulp apart from an open window at rate 0 on the CPU
            # (the loop-invariant gate lets the compiler specialize the
            # closed-window loop on a constant mask)
            tf = t.astype(f32)
            win = ((tf >= p["churn_start"]) & (tf < p["churn_end"])).astype(f32)
            u = jax.random.uniform(jax.random.fold_in(key, 0x6368), (n,))
            m = jnp.where(u < p["dropout"] * win, 0.0, 1.0)
            n_alive = jnp.maximum(jnp.sum(m), 1.0)
            # rejoin protocol: a worker alive now but masked last round
            # resets its compressor state at the END of its rejoin round
            # (the stale EF residual is garbage w.r.t. the moved model; it
            # is dropped rather than carried — the reset merges into the
            # post-compression freeze select below).  Under pull_avg
            # (local/gossip only, where a rejoiner is actually stale) it
            # also pulls the live-set parameter average.  All selections
            # are jnp.where on a rejoined bit that is identically 0 at
            # dropout 0.
            rejoined = m * (1.0 - m_prev)
            if spec.rejoin_policy == "pull_avg":
                donors = m * m_prev  # live both rounds: not stale
                n_don = jnp.sum(donors)
                xpull = (jnp.sum(X * donors[:, None], axis=0)
                         / jnp.maximum(n_don, 1.0))
                take = (rejoined[:, None] > 0) & (n_don > 0)
                X = jnp.where(take, xpull[None, :], X)
                # each pull is a dense model download (resync transfer)
                total_bits = total_bits + jnp.where(
                    n_don > 0, jnp.sum(rejoined) * 32.0 * dim, 0.0)
            if corrupt:
                # per-worker corruption flags: own fold tag off the carry
                # key, so the mask / gradient / compressor streams are
                # untouched; only live in-window workers send a payload
                cu = jax.random.uniform(
                    jax.random.fold_in(key, integrity.CORRUPT_FOLD), (n,))
                cflag = jnp.where((m > 0) & (cu < p["corruption"] * win),
                                  1.0, 0.0)
                valid_round = jnp.ones((n,), f32)
                qbits_step = jnp.zeros((), f32)
            G = grad_all(X, gkeys)

            if sync == "gossip":
                Ghat, ef2, wb = apply_compression(ckeys, G, ef)
                # dead rows are identity (frozen params), dead columns'
                # weight folds into each live row's self-weight — rows
                # still sum to 1 and W stays symmetric; a rejoiner's stale
                # residual is dropped (carry-out zero)
                ef = jnp.where(rejoined[:, None] > 0, jnp.zeros_like(ef),
                               jnp.where(m[:, None] > 0, ef2, ef))
                Y = X - lr * Ghat * m[:, None]
                m_eff = m
                if corrupt:
                    # the wire payload is the worker's mixed row: corrupt it
                    # in-domain, validate, and drop detected rows from the
                    # mixing (the quarantined worker keeps its own local
                    # update — quarantine is not death); an UNDETECTED
                    # corruption flows into the mix for real
                    Yw = integrity.corrupt_dense(spec.corruption_kind, Y,
                                                 cflag[:, None])
                    valid = integrity.dense_valid(Yw, per_row=True)
                    m_eff = m * valid
                    Y = jnp.where(valid[:, None] > 0, Yw, Y)
                    valid_round = valid
                    qbits_step = jnp.sum(wb * m * (1.0 - valid))
                Weff = masked_mixing_matrix(W, m_eff)
                X = Weff @ Y
                total_bits = total_bits + jnp.sum(wb * m)
            else:
                if sync == "asp":
                    delay_buf = jnp.roll(delay_buf, 1, axis=0).at[0].set(G)
                    G_eff = delay_buf[p["staleness"]]  # `staleness` steps old
                elif sync == "ssp":
                    delay_buf = jnp.roll(delay_buf, 1, axis=0).at[0].set(G)
                    G_eff = delay_buf[d_idx, widx]
                else:
                    G_eff = G
                Ghat, ef2, wb = apply_compression(ckeys, G_eff, ef)
                m_ef = m
                if corrupt and sync != "local":
                    # corrupt the post-compression reconstruction — the dense
                    # image of the worker's wire payload; a DETECTED row is
                    # zeroed via select (NaN * 0 would still poison the sum)
                    # and leaves the denominator; an undetected one flows in
                    Gw = integrity.corrupt_dense(spec.corruption_kind, Ghat,
                                                 cflag[:, None])
                    valid = integrity.dense_valid(Gw, per_row=True)
                    Ghat = jnp.where(valid[:, None] > 0, Gw,
                                     jnp.zeros_like(Gw))
                    m_ef = m * valid
                    valid_round = valid
                    qbits_step = jnp.sum(wb * m * (1.0 - valid))
                # EF residuals of masked-out workers freeze: they neither
                # sent nor accumulated this round; a rejoiner drops its
                # stale residual at the end of its rejoin round; a
                # QUARANTINED round freezes too — it was never delivered
                ef = jnp.where(rejoined[:, None] > 0, jnp.zeros_like(ef),
                               jnp.where(m_ef[:, None] > 0, ef2, ef))
                if sync == "local":
                    # Local SGD communicates only at sync steps
                    X = X - lr * Ghat * m[:, None]
                    is_sync = (t + 1) % p["local_steps"] == 0
                    if corrupt:
                        # the wire payload at a sync point is the params: a
                        # detected-corrupt row is dropped from the average
                        # (weight AND denominator) for one round
                        Xw = integrity.corrupt_dense(
                            spec.corruption_kind, X, cflag[:, None])
                        valid = integrity.dense_valid(Xw, per_row=True)
                        m_s = m * valid
                        xs = (jnp.sum(jnp.where(valid[:, None] > 0, Xw,
                                                jnp.zeros_like(Xw))
                                      * m[:, None], axis=0)
                              / jnp.maximum(jnp.sum(m_s), 1.0))
                        valid_round = jnp.where(is_sync, valid,
                                                jnp.ones_like(valid))
                        qbits_step = jnp.where(
                            is_sync, jnp.sum(wb * m * (1.0 - valid)), 0.0)
                    else:
                        xs = jnp.sum(X * m[:, None], axis=0) / n_alive
                    # only live workers adopt the (live-only) average; a
                    # dead worker rejoins by mixing back in later
                    X = jnp.where(is_sync & (m[:, None] > 0),
                                  jnp.broadcast_to(xs[None], X.shape), X)
                    total_bits = total_bits + jnp.where(
                        is_sync, jnp.sum(wb * m), 0.0)
                else:
                    # bsp / ssp / asp: masked mean with the denominator
                    # renormalized over the live set; the global model
                    # updates every row (PS semantics: a rejoining worker
                    # reads current params)
                    if corrupt:
                        # quarantined rows left the numerator above — the
                        # denominator renormalizes over the live-AND-valid set
                        gbar = (jnp.sum(Ghat * m[:, None], axis=0)
                                / jnp.maximum(jnp.sum(m_ef), 1.0))
                    else:
                        gbar = jnp.sum(Ghat * m[:, None], axis=0) / n_alive
                    X = X - lr * gbar[None, :]
                    total_bits = total_bits + jnp.sum(wb * m)
            if corrupt:
                # bounded quarantine: consecutive corrupted rounds escalate
                # into the rejoin protocol (EF reset; pull_avg additionally
                # pulls the live-valid parameter average where a worker is
                # actually stale) instead of retrying forever.  Every select
                # rides AFTER the compression reductions — identity at rate
                # 0.  m_prev keeps TRUE liveness: quarantine recovery is not
                # a rejoin.
                q_new = jnp.where(m > 0,
                                  jnp.where(valid_round > 0, 0.0, qc + 1.0),
                                  qc)
                esc = jnp.where(q_new >= p["quarantine_limit"], 1.0, 0.0)
                ef = jnp.where(esc[:, None] > 0, jnp.zeros_like(ef), ef)
                if spec.rejoin_policy == "pull_avg":
                    donors = m * valid_round * (1.0 - esc)
                    n_don = jnp.sum(donors)
                    xpull = (jnp.sum(X * donors[:, None], axis=0)
                             / jnp.maximum(n_don, 1.0))
                    take = (esc[:, None] > 0) & (n_don > 0)
                    X = jnp.where(take, xpull[None, :], X)
                    total_bits = total_bits + jnp.where(
                        n_don > 0, jnp.sum(esc) * 32.0 * dim, 0.0)
                qc = jnp.where(esc > 0, 0.0, q_new)
                qb = qb + qbits_step
                qr = qr + jnp.sum(m * (1.0 - valid_round))
                qe = qe + jnp.sum(esc)
            xbar = jnp.mean(X, axis=0)
            out = (
                loss_fn_(xbar),
                jnp.mean(jnp.linalg.norm(X - xbar[None], axis=1)),
                total_bits,
            )
            carry = (X, ef, delay_buf, key, total_bits, m)
            if corrupt:
                out = out + (qb, qr, qe)
                carry = carry + (qc, qb, qr, qe)
            return carry, out

        carry0 = (
            jnp.tile(x0[None], (n, 1)),
            jnp.zeros((n, dim), f32),
            jnp.zeros((spec.delay_slots, n, dim), f32),
            seed_key,
            jnp.zeros((), f32),
            jnp.ones((n,), f32),  # last round's mask: everyone was alive
        )
        if corrupt:
            carry0 = carry0 + (jnp.zeros((n,), f32), jnp.zeros((), f32),
                               jnp.zeros((), f32), jnp.zeros((), f32))
        carry_f, outs = jax.lax.scan(step, carry0, jnp.arange(spec.steps))
        Xf = carry_f[0]
        losses, cons, bits = outs[0], outs[1], outs[2]
        extras = {}
        if corrupt:
            # cumulative per-step integrity accounting: wire bits booked
            # quarantined (sent, not delivered), quarantined worker-rounds,
            # and escalations into the rejoin protocol
            extras = {"quarantined_bits": outs[3],
                      "quarantine_rounds": outs[4],
                      "escalations": outs[5]}
        return (losses, cons, bits,
                jnp.linalg.norm(jnp.mean(Xf, 0) - x_star), extras)

    return replica_fn


# --- compiled-program cache (one entry per shape class x batch extent) ------


@dataclass
class EngineStats:
    """Compile/hit counters for the class-program cache — the sweep
    benchmarks assert `compiles == #shape-classes`."""

    compiles: int = 0
    hits: int = 0

    @property
    def persistent_cache(self) -> dict:
        """On-disk cache effectiveness {hits, misses, dir} at shape-class
        granularity (repro.core.compilecache manifest)."""
        from repro.core import compilecache

        return compilecache.record("engine")


_ENGINE_STATS = EngineStats()
_ENGINE_CACHE: dict[tuple, tuple] = {}  # key -> (fn, problem, comp) (pinned)
_ENGINE_CACHE_CAP = 64


def engine_cache_stats() -> EngineStats:
    return _ENGINE_STATS


def engine_cache_clear() -> None:
    """Drop every cached class program and zero the counters."""
    _ENGINE_CACHE.clear()
    _ENGINE_STATS.compiles = 0
    _ENGINE_STATS.hits = 0


def simulate_training_classbatch(
    cfgs: list[SimCfg],
    problem=None,
    *,
    problems: list | None = None,
    seeds: list[list[int]] | None = None,
    grad_noise: list[float] | None = None,
    problem_key=None,
    cache: bool = True,
) -> list[list[dict[str, np.ndarray]]]:
    """Run EVERY cell of one shape class (x its replica seeds) in a single
    compiled program: ``jit(vmap_cells(vmap_seeds(scan)))``.

    All ``cfgs`` must share :func:`shape_class_key`; their value knobs are
    stacked into a CellParams tree and traced.  The problem comes in two
    forms:

    * ``problem`` — ONE instance for every cell.  :class:`Problem`
      instances thread their ``data`` pytree (A/b, X/y, x*) as a traced
      argument and cache the program under the structural ``data_key``;
      legacy 4-tuples bake their arrays and cache under ``problem_key``
      (default ``id(problem)``, pinned).
    * ``problems`` — one :class:`Problem` PER CELL (equal ``data_key``):
      each cell's data is stacked over the cell axis and traced, so cells
      that differ only in problem seed share the one compiled program.

    ``seeds`` is a per-cell list of replica seeds (equal length per cell;
    default ``[[cfg.seed]]``); ``grad_noise`` optionally traces a per-cell
    gradient-noise scale through the problem's ``noise`` keyword (required
    when per-cell problems were built with differing factory noise); pass
    ``cache=False`` to force a fresh trace (the per-cell PR 2 baseline the
    sweep benchmark compares against).

    Returns, per cfg, the per-seed result dicts of
    :func:`simulate_training_batch` — equal to running each cell alone
    within float tolerance (property-tested per shape class).
    """
    if not cfgs:
        return []
    keys = {shape_class_key(c) for c in cfgs}
    if len(keys) > 1:
        raise ValueError(
            f"cfgs span {len(keys)} shape classes ({sorted(map(str, keys))}); "
            "group with shape_class_key() first")
    if problems is not None:
        if len(problems) != len(cfgs):
            raise ValueError("problems must give one Problem per cfg")
        dkeys = {getattr(p, "data_key", None) for p in problems}
        if None in dkeys or len(dkeys) > 1:
            raise ValueError(
                "per-cell problems must be Problem instances sharing one "
                f"data_key (got {sorted(map(str, dkeys))})")
        problem = problems[0]
    if problem is None:
        problem = PROBLEMS["quadratic"](
            n_workers=cfgs[0].n_workers, seed=cfgs[0].seed)
    x0 = problem[2]
    seeds = [[c.seed] for c in cfgs] if seeds is None else [list(s) for s in seeds]
    if len(seeds) != len(cfgs) or len({len(s) for s in seeds}) != 1:
        raise ValueError("seeds must give every cfg the same replica count")
    noises = [None] * len(cfgs) if grad_noise is None else list(grad_noise)
    if any(nz is None for nz in noises) and any(nz is not None for nz in noises):
        raise ValueError("grad_noise must be set for every cell or for none")

    from repro.core.compression.base import merge_representative, structural_envelope

    split = [split_cfg(c, grad_noise=nz, dim=x0.size)
             for c, nz in zip(cfgs, noises)]
    spec = split[0][0]
    # structural envelopes of the class: delay depth and knob maxima
    spec = EngineSpec(**{**spec.__dict__,
                         "delay_slots": max(s.delay_slots for s, _ in split)})
    comp = merge_representative([c.compressor for c in cfgs])

    has_data = getattr(problem, "data", None) is not None
    if problems is not None and not spec.traced_noise:
        # the compiled grad closure bakes the REPRESENTATIVE problem's noise;
        # per-cell factory noise would be silently dropped
        if len({getattr(p, "noise", 0.0) for p in problems}) > 1:
            raise ValueError("per-cell problems with differing factory noise "
                             "need grad_noise traced")
    if has_data:
        # structural program identity; the arrays arrive traced — add the
        # baked factory noise only when the cells do not trace their own
        pkey = (problem.data_key,
                None if spec.traced_noise else getattr(problem, "noise", 0.0))
    else:
        # a legacy tuple bakes its arrays: fall back to pinned identity (an
        # ephemeral instance can never be re-identified — don't cache)
        if problem_key is None and problems is None and cache:
            problem_key = id(problem)
        pkey = problem_key
        if pkey is None:
            cache = False

    C, R = len(cfgs), len(seeds[0])
    cache_key = (spec, structural_envelope(comp), pkey, C, R)
    hit = cache and cache_key in _ENGINE_CACHE
    if hit:
        fn = _ENGINE_CACHE[cache_key][0]
        _ENGINE_STATS.hits += 1
    else:
        replica_fn = _build_cell_replica_fn(spec, comp, problem)
        fn = jax.jit(jax.vmap(jax.vmap(replica_fn, in_axes=(None, 0, None)),
                              in_axes=(0, 0, 0)))
        _ENGINE_STATS.compiles += 1
        if has_data:
            # manifest the fresh build: a stable pkey (data_key-based) means a
            # later process re-deriving this signature deserializes the XLA
            # executable from disk instead of compiling.  Legacy id(problem)
            # pkeys are process-local and never manifested.
            from repro.core import compilecache

            compilecache.record_compile("engine", cache_key)
        if cache:
            if len(_ENGINE_CACHE) >= _ENGINE_CACHE_CAP:
                _ENGINE_CACHE.pop(next(iter(_ENGINE_CACHE)))
            _ENGINE_CACHE[cache_key] = (fn, problem, comp)

    ptrees = [p.as_tree() for _, p in split]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ptrees)
    seed_keys = jnp.stack([
        jnp.stack([jax.random.key(sd) for sd in row]) for row in seeds])
    if has_data:
        cell_probs = problems if problems is not None else [problem] * C
        data = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[p.data for p in cell_probs])
    else:
        data = None
    losses, cons, bits, errs, extras = fn(stacked, seed_keys, data)
    return [
        [
            {
                "loss": np.asarray(losses[c, r]),
                "consensus": np.asarray(cons[c, r]),
                "bits": np.asarray(bits[c, r], dtype=np.float64),
                "x_star_err": float(errs[c, r]),
                **{k: np.asarray(v[c, r], dtype=np.float64)
                   for k, v in extras.items()},
            }
            for r in range(R)
        ]
        for c in range(C)
    ]


def _build_replica_fn(cfg: SimCfg, problem):
    """Single-cell view of the parameterized scan (knob values bound from
    ``cfg``): ``one_replica(seed_key)``.  Kept as the engine-speedup
    benchmark's entry point and the building block of
    :func:`simulate_training_classbatch`."""
    spec, params = split_cfg(cfg, dim=problem[2].size)
    replica_fn = _build_cell_replica_fn(spec, cfg.compressor, problem)
    ptree = params.as_tree()
    data = getattr(problem, "data", None)
    return lambda seed_key: replica_fn(ptree, seed_key, data)


def simulate_training_batch(
    cfg: SimCfg, problem=None, *, seeds: list[int] | None = None
) -> list[dict[str, np.ndarray]]:
    """Run every replica seed of one taxonomy cell in a single compiled
    program: ``jit(vmap(scan))`` over the seed axis.  The per-seed result
    dicts match :func:`simulate_training_reference` within float tolerance
    (property-tested for every sync scheme x registered compressor x EF).

    Custom ``problem`` tuples must provide a worker-vmappable ``grad``
    (traced worker index) — both built-in problems do.  Implemented as a
    one-cell :func:`simulate_training_classbatch`, so repeated runs of the
    same cell shape against the same problem instance reuse the compiled
    class program.
    """
    problem = problem or PROBLEMS["quadratic"](n_workers=cfg.n_workers, seed=cfg.seed)
    seeds = [cfg.seed] if seeds is None else list(seeds)
    return simulate_training_classbatch([cfg], problem, seeds=[seeds])[0]


def simulate_training(cfg: SimCfg, problem=None) -> dict[str, np.ndarray]:
    """Exact simulation of n workers under the chosen sync/topology/compressor.

    Returns {"loss": (steps,), "consensus": (steps,), "bits": (steps,)} —
    loss of the (mean) model, worker disagreement, cumulative upload bits.

    Runs on the jitted scan engine; :func:`simulate_training_reference` is the
    step-by-step Python loop it is equivalence-tested against.
    """
    return simulate_training_batch(cfg, problem)[0]


# ---------------------------------------------------------------------------
# 2b. Reference implementation (Python loop, kept for equivalence tests).
# ---------------------------------------------------------------------------


def simulate_training_reference(cfg: SimCfg, problem=None) -> dict[str, np.ndarray]:
    """The original per-step Python-loop simulator — O(steps x workers)
    dispatches and a host sync per step.  Kept as the semantic reference the
    scan engine is tested against (tests/test_scan_engine.py) and as the
    baseline for the engine-speedup benchmark."""
    grad_fn, loss_fn, x0, x_star = problem or quadratic_problem(n_workers=cfg.n_workers, seed=cfg.seed)
    n = cfg.n_workers
    dim = x0.size
    comp = cfg.compressor

    X = jnp.tile(x0[None], (n, 1))  # per-worker models
    ef = jnp.zeros((n, dim), f32)
    delay_buf = jnp.zeros((cfg.staleness + 1, n, dim), f32)  # asp delay line
    key = jax.random.key(cfg.seed)

    W = None
    if cfg.sync == "gossip":
        from repro.core.gossip import ring_mixing_matrix

        W = jnp.asarray(ring_mixing_matrix(n, cfg.gossip_w), f32)

    losses, consensus, bits = [], [], []
    total_bits = 0.0

    # Wire accounting: one upload per worker per COMMUNICATION round —
    # 32 bits/element dense, comp.wire_bits compressed, and the *measured*
    # 64 bits/transmitted-coordinate when the analytic size is data-dependent
    # (threshold-style methods return NaN).  Local SGD only communicates at
    # sync steps (the parameter average), so the realized round cost is
    # charged there and the per-step cost is 0.
    def compress_all(keys, G, ef):
        """Returns (reconstruction, new EF residual, realized round bits)."""
        if comp is None:
            return G, ef, 32.0 * dim * n
        a = G + ef if cfg.error_feedback else G
        out = []
        for i in range(n):
            c = comp.compress(keys[i], a[i])
            out.append(comp.decompress(c))
        out = jnp.stack(out)
        new_ef = (a - out) if cfg.error_feedback else ef
        wb = comp.wire_bits(dim)
        if wb != wb:  # NaN: measured from the realized support
            round_bits = 64.0 * sum(float(jnp.count_nonzero(out[i])) for i in range(n))
        else:
            round_bits = wb * n
        return out, new_ef, round_bits

    for t in range(cfg.steps):
        key, k1, k2 = jax.random.split(key, 3)
        gkeys = jax.random.split(k1, n)
        ckeys = jax.random.split(k2, n)
        G = jnp.stack([grad_fn(X[i], i, gkeys[i]) for i in range(n)])

        if cfg.sync in ("bsp", "local", "ssp", "asp"):
            if cfg.sync == "asp":
                # apply the gradient that is `staleness` steps old
                delay_buf = jnp.roll(delay_buf, 1, axis=0).at[0].set(G)
                G_eff = delay_buf[-1]
            elif cfg.sync == "ssp":
                # workers alternate being ahead: even workers' grads delayed 1..s
                delay_buf = jnp.roll(delay_buf, 1, axis=0).at[0].set(G)
                d = np.arange(n) % (cfg.staleness + 1)
                G_eff = jnp.stack([delay_buf[d[i], i] for i in range(n)])
            else:
                G_eff = G
            Ghat, ef, wb = compress_all(ckeys, G_eff, ef)
            if cfg.sync == "local":
                X = X - cfg.lr * Ghat
                if (t + 1) % cfg.local_steps == 0:
                    X = jnp.tile(jnp.mean(X, axis=0)[None], (n, 1))
                    total_bits += wb
            else:
                total_bits += wb
                gbar = jnp.mean(Ghat, axis=0)
                X = X - cfg.lr * gbar[None, :]
        elif cfg.sync == "gossip":
            Ghat, ef, wb = compress_all(ckeys, G, ef)
            total_bits += wb
            X = W @ (X - cfg.lr * Ghat)
        else:
            raise ValueError(cfg.sync)

        xbar = jnp.mean(X, axis=0)
        losses.append(float(loss_fn(xbar)))
        consensus.append(float(jnp.mean(jnp.linalg.norm(X - xbar[None], axis=1))))
        bits.append(total_bits)

    return {
        "loss": np.asarray(losses),
        "consensus": np.asarray(consensus),
        "bits": np.asarray(bits),
        "x_star_err": float(jnp.linalg.norm(jnp.mean(X, 0) - x_star)),
    }
