"""Communication configuration — one knob per taxonomy dimension (Table I).

:class:`CommConfig` is the user-facing cell description.  For the mesh
runtime it splits the same way the simulator's ``SimCfg`` split into
``EngineSpec``/``CellParams`` (PR 3):

* :class:`BundleSpec` — the STATIC half: everything that changes the
  structure of the compiled step programs (sync scheme, aggregator,
  collective schedule, EF / momentum-correction / clipping *flags*, the
  compressor *family* at the runtime layer, bucket-plan inputs, pod-local).
  Bundles with equal specs (same model/mesh/optimizer/shape) share one set
  of compiled ``train_step``/``sync_step``/``gossip_step`` programs — the
  bundle cache in :mod:`repro.train.steps` keys on it.
* :class:`CommKnobs` — the TRACED half: values that ride into the compiled
  programs as arguments (compressor knobs via the ``RUNTIME_KNOBS``
  protocol, EF decay, momentum-correction coefficient, clip thresholds,
  gossip step size / mixing weight, the pipelined-overlap stale-gradient
  scale, the stochastic-compression seed).
  ``lr`` was already a traced step argument; Local-SGD ``H`` and the
  post-local switch never enter a compiled program at all — the Trainer
  applies them as Python-level step-count comparisons (repro.core.sync), so
  they are deliberately absent from both halves.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass
class CommConfig:
    # --- compression (paper §V/§VI) ------------------------------------------
    compressor: str = "none"  # see repro.core.compression registry
    compressor_kwargs: dict[str, Any] = field(default_factory=dict)
    #: per-tensor rules: list of (substring, compressor_name|"none", kwargs);
    #: first match wins. Lets e.g. SSM decay params skip compression
    #: (DESIGN.md §Arch-applicability) or layers use different k [92].
    per_tensor_rules: list = field(default_factory=list)

    # --- auxiliary technologies (paper §IX) -----------------------------------
    error_feedback: bool = False  # §IX-A error accumulation
    ef_decay: float = 1.0  # 1.0 = classic EF; <1 decays residuals
    momentum_correction: float = 0.0  # §IX-B DGC momentum m (0 = off)
    local_clip: float = 0.0  # §IX-C local gradient clipping threshold (0 = off)
    warmup_steps: int = 0  # §IX-D sparsity warm-up (exponential ramp)

    # --- synchronization (paper §III) ------------------------------------------
    sync: str = "bsp"  # bsp | local | post_local
    local_steps: int = 1  # H for local SGD
    post_local_switch: int = 0  # step at which post-local switches bsp->local
    #: multi-pod: aggregate gradients only WITHIN each pod every step (BSP on
    #: ICI) and average parameters ACROSS pods every `local_steps` (local SGD
    #: on the slow DCN boundary) — the survey's §III-D at pod scale.
    pod_local: bool = False

    # --- architecture / collectives (paper §IV) ---------------------------------
    aggregator: str = "allreduce"  # allreduce | gossip
    collective: str = "xla"  # xla | ring | rhd (manual ppermute schedules)
    gossip_graph: str = "ring"  # ring | exp (exponential peers)
    gossip_compress: str = "none"  # choco | dcd | none
    gossip_step_size: float = 0.5  # CHOCO-SGD gamma (traced knob)
    gossip_mix_weight: float = 1.0 / 3.0  # ring mixing weight w (traced knob)

    # --- scheduling (paper §VII) -------------------------------------------------
    bucket_mb: float = 0.0  # 0 = per-tensor; >0 = MG-WFBP-style fused buckets
    agg_dtype: str = "float32"  # bucket dtype for the dense path ("bfloat16" halves wire)
    #: parallelism of communication and computing (§VII): "sequential"
    #: aggregates once after the full (accumulated) backward; "pipelined"
    #: issues each microbatch's bucket all-reduces inside the accumulation
    #: scan with no data dependency on the NEXT microbatch's forward/backward,
    #: so XLA's latency-hiding scheduler can overlap them.
    overlap: str = "sequential"  # sequential | pipelined
    #: pipelined only: 1 = double-buffered across the step boundary (the last
    #: microbatch's aggregation is consumed by the NEXT step — every
    #: collective fully overlappable, gradient staleness 1); 0 = flush the
    #: last microbatch after the scan (no staleness; the flush is exposed).
    overlap_staleness: int = 1
    #: weight applied to the stale (previous-step) microbatch contribution in
    #: the staleness-1 pipelined update (traced knob; 1.0 = plain average).
    stale_scale: float = 1.0

    # --- wire format (paper §V-§VII: compressed-domain collectives) ------------
    #: "dense"      — decompress to dense f32 before the reduce (fidelity
    #:                baseline; what every cell did before this axis existed);
    #: "compressed" — the wire carries the COMPRESSED payload and reduction
    #:                happens in (or near) the compressed domain via fused
    #:                Pallas unpack+accumulate kernels: 1-bit packed sign
    #:                majority vote, 2-bit packed ternary accumulate, int8
    #:                widening accumulate, or (compressor "none") a bf16 wire
    #:                with f32 widening accumulation.  STRUCTURAL: it swaps
    #:                psum for gather+kernel programs.
    wire_format: str = "dense"

    # --- churn / elastic workers (survey future directions) --------------------
    #: carry a per-round participation mask through aggregation/mixing —
    #: STRUCTURAL (the masked program renormalizes denominators); the
    #: probability/window values below are traced knobs, so 0/10/30%
    #: dropout cells share one compiled bundle.
    churn: bool = False
    dropout_rate: float = 0.0  # per-round P(worker masked out)
    #: per-worker dropout rates (one traced rate per shard); empty = use the
    #: scalar ``dropout_rate`` for every worker.  Values are traced — cells
    #: differing only in the vector share one compiled bundle.
    worker_dropout: tuple = ()
    churn_start: int = 0  # first step (inclusive) dropout applies
    churn_end: int = -1  # last step (exclusive); -1 = until the end
    #: how a worker re-enters after a masked round — STRUCTURAL:
    #: "reset"    — compressor state (EF residual, momentum) resets on
    #:              rejoin; parameters re-enter by the scheme's own
    #:              mixing/averaging (a rejoiner contributes its frozen
    #:              params to the next sync round);
    #: "pull_avg" — additionally the rejoiner pulls the live-set parameter
    #:              average (excluded as a donor while stale), charged as a
    #:              resync transfer in the wire/timeline accounting.
    rejoin_policy: str = "reset"

    # --- gradient integrity (fault injection + quarantine) ---------------------
    #: per-round P(a live worker's wire payload is corrupted) — traced, so
    #: corruption-rate siblings share one compiled bundle.
    corruption_rate: float = 0.0
    #: STRUCTURAL corruption family, injected post-compression so packed /
    #: int8 payloads are corrupted in-domain:
    #: "nan" | "inf"  — non-finite scales/norms (dense: poisoned values);
    #: "spike"        — magnitudes blown up by ~1e8 (encodes fine, detected
    #:                  by the receiver's range check);
    #: "bitflip"      — exponent-bit flips on dense f32 words, XORed int8
    #:                  codes, XORed packed sign words.
    corruption_kind: str = "none"
    #: consecutive quarantined rounds a worker tolerates before escalating
    #: to the rejoin protocol (reset/pull_avg) instead of retrying forever
    #: (traced knob).
    quarantine_limit: int = 3

    def with_updates(self, **kw) -> "CommConfig":
        return dataclasses.replace(self, **kw)


DENSE = CommConfig()


def carries_mask(cfg) -> bool:
    """The one churn rule: whether a cell's mesh program carries the
    per-round participation mask.  Explicit ``churn``, any dropout rate, or
    a named ``corruption_kind`` turns it on (a quarantined round IS a
    one-round drop).  A cell compiles the integrity ("guarded") program iff
    it names a kind; the rate is traced and the guard is the identity at
    rate 0.  Read by :func:`bundle_spec`, :mod:`repro.core.aggregate` and
    ``Scenario``; the convergence engine always carries the mask.  Takes
    any config with these fields (``CommConfig``, ``Scenario``)."""
    return bool(getattr(cfg, "churn", False) or cfg.dropout_rate > 0
                or any(r > 0 for r in cfg.worker_dropout)
                or cfg.corruption_kind != "none")


# ---------------------------------------------------------------------------
# The static / traced split of a CommConfig (runtime shape classes).
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BundleSpec:
    """Static (program-structure) half of a :class:`CommConfig`.

    Two configs with equal specs compile to identical step programs; their
    value differences travel through :class:`CommKnobs` as traced arguments.
    Compressor identity is the *runtime* fingerprint: the family plus every
    kwarg that sizes a payload array or specializes a kernel — value-only
    knobs (``RUNTIME_KNOBS``, e.g. qsgd levels) are excluded.
    """

    sync: str
    pod_local: bool
    aggregator: str
    collective: str
    gossip_graph: str
    gossip_compress: str
    error_feedback: bool
    momentum_correction: bool
    local_clip: bool
    warmup_steps: int
    comp_key: tuple
    rules_key: tuple
    bucket_mb: float
    agg_dtype: str
    overlap: str = "sequential"
    #: normalized to 0 for sequential cells so the inert knob never splits a
    #: shape class
    overlap_staleness: int = 0
    #: participation mask carried through aggregation/mixing (values traced)
    churn: bool = False
    #: rejoin protocol ("reset" | "pull_avg") — structural: "pull_avg" adds
    #: the live-set pull / donor-exclusion program; normalized to "reset"
    #: for churn-free cells so the inert knob never splits a class
    rejoin_policy: str = "reset"
    #: "compressed" swaps the aggregation psum for gather+fused-kernel
    #: programs (normalized to "dense" for gossip, which mixes parameters)
    wire_format: str = "dense"
    #: fault-injection family — STRUCTURAL (the integrity program adds the
    #: inject/validate/quarantine selects); the rate is traced, so corruption-
    #: rate siblings share one bundle (see :func:`carries_mask`).
    corruption_kind: str = "none"


def bundle_spec(comm: CommConfig) -> BundleSpec:
    """Project a :class:`CommConfig` onto its static half.

    Note what is absent: ``local_steps`` / ``post_local_switch`` (Python-side
    step-count comparisons in the Trainer, never compiled), ``lr`` (a traced
    step argument), and every knob listed in :class:`CommKnobs`.
    """
    from repro.core.compression.base import get_compressor, runtime_fingerprint

    if comm.overlap not in ("sequential", "pipelined"):
        raise ValueError(f"unknown overlap mode {comm.overlap!r}")
    if comm.overlap_staleness not in (0, 1):
        raise ValueError(f"overlap_staleness must be 0 or 1, got {comm.overlap_staleness!r}")
    if (comm.overlap == "pipelined" and comm.aggregator != "gossip"
            and comm.sync != "bsp"):
        # the double buffer is refilled only by the AGGREGATING step: under
        # local/post_local sync that fires every H steps, so the "staleness-1"
        # contribution would silently be H steps old
        raise ValueError(
            "pipelined overlap needs per-step aggregation (sync must be bsp, "
            f"got {comm.sync!r})")
    churn = carries_mask(comm)
    if comm.rejoin_policy not in ("reset", "pull_avg"):
        raise ValueError(
            f"unknown rejoin_policy {comm.rejoin_policy!r} "
            "(expected 'reset' or 'pull_avg')")
    if churn and not 0.0 <= comm.dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {comm.dropout_rate!r}")
    if churn and not all(0.0 <= r < 1.0 for r in comm.worker_dropout):
        raise ValueError(
            f"worker_dropout rates must be in [0, 1), got {comm.worker_dropout!r}")
    if comm.corruption_kind not in ("none", "nan", "inf", "spike", "bitflip"):
        raise ValueError(
            f"unknown corruption_kind {comm.corruption_kind!r} "
            "(expected 'none', 'nan', 'inf', 'spike' or 'bitflip')")
    if comm.corruption_rate > 0 and comm.corruption_kind == "none":
        raise ValueError("corruption_rate > 0 needs a corruption_kind")
    if not 0.0 <= comm.corruption_rate < 1.0:
        raise ValueError(
            f"corruption_rate must be in [0, 1), got {comm.corruption_rate!r}")
    if comm.quarantine_limit < 1:
        raise ValueError(
            f"quarantine_limit must be >= 1, got {comm.quarantine_limit!r}")
    comp = get_compressor(comm.compressor, **comm.compressor_kwargs)
    if comm.wire_format not in ("dense", "compressed"):
        raise ValueError(f"unknown wire_format {comm.wire_format!r}")
    wire_fmt = comm.wire_format if comm.aggregator != "gossip" else "dense"
    if wire_fmt == "compressed":
        # only families with a linear int-code payload (or dense -> bf16
        # wire) reduce in the compressed domain; reject, don't approximate
        if comp is not None and not getattr(comp, "wire_reduce", ""):
            raise ValueError(
                f"wire_format='compressed' is unsupported for compressor "
                f"{comm.compressor!r}: no compressed-domain reduction "
                "(supported: the sign/terngrad/qsgd families, or 'none' "
                "for a bf16 wire with f32 widening accumulation)")
        if comm.agg_dtype == "bfloat16" and comp is not None:
            raise ValueError(
                "agg_dtype='bfloat16' only shapes the dense aggregation "
                "path — meaningless combined with a compressed wire format")
    return BundleSpec(
        sync=comm.sync,
        pod_local=bool(comm.pod_local),
        aggregator=comm.aggregator,
        collective=comm.collective,
        gossip_graph=comm.gossip_graph,
        gossip_compress=comm.gossip_compress,
        error_feedback=bool(comm.error_feedback),
        momentum_correction=bool(comm.momentum_correction),
        local_clip=bool(comm.local_clip),
        warmup_steps=int(comm.warmup_steps),
        comp_key=runtime_fingerprint(comp),
        rules_key=tuple(
            (sub, name, tuple(sorted(dict(kw).items())))
            for sub, name, kw in comm.per_tensor_rules
        ),
        bucket_mb=float(comm.bucket_mb),
        agg_dtype=comm.agg_dtype,
        # overlap restructures gradient AGGREGATION: inert for gossip (which
        # mixes parameters) — normalized away so it never splits a class
        overlap=(comm.overlap if comm.aggregator != "gossip" else "sequential"),
        overlap_staleness=(int(comm.overlap_staleness)
                           if comm.overlap == "pipelined"
                           and comm.aggregator != "gossip" else 0),
        churn=churn,
        rejoin_policy=(comm.rejoin_policy if churn else "reset"),
        wire_format=wire_fmt,
        corruption_kind=comm.corruption_kind,
    )


@dataclass
class CommKnobs:
    """Traced (values-only) half of a :class:`CommConfig` + build args.

    ``comp`` holds one dict of runtime-traceable compressor knob values per
    bucket of the plan (``base.runtime_knob_values``); the rest are scalars.
    ``as_tree()`` is the pytree the step closures receive as an argument —
    every leaf rides into the compiled program traced, so cells that differ
    only here share one compiled bundle.
    """

    ef_decay: float = 1.0
    momentum: float = 0.0
    local_clip: float = 0.0
    gossip_gamma: float = 0.5
    gossip_w: float = 1.0 / 3.0
    clip_norm: float = 0.0
    stale_scale: float = 1.0
    #: churn: per-round P(worker masked out).  A scalar, or — when the build
    #: site passes the mesh's worker count — a per-worker tuple indexed by
    #: each shard's mask index in-program (the vector is traced, so cells
    #: differing only in rates share one compiled bundle).
    dropout: Any = 0.0
    churn_start: float = 0.0
    churn_end: float = float("inf")
    corruption: float = 0.0  # per-round P(live worker's payload corrupted)
    quarantine_limit: float = 3.0  # consecutive quarantines before rejoin
    seed: int = 0
    comp: tuple = ()  # per-bucket dict of traced compressor knob values

    @classmethod
    def from_comm(cls, comm: CommConfig, comp_per_bucket: tuple, *,
                  seed: int = 0, clip_norm: float = 0.0,
                  n_workers: int = 0) -> "CommKnobs":
        if comm.worker_dropout:
            if n_workers and len(comm.worker_dropout) != n_workers:
                raise ValueError(
                    f"worker_dropout has {len(comm.worker_dropout)} rates but "
                    f"the mesh has {n_workers} data shards")
            dropout = tuple(float(r) for r in comm.worker_dropout)
        elif n_workers:
            # normalize to a vector so scalar- and per-worker-rate cells
            # share one knob-tree structure (hence one compiled bundle)
            dropout = (float(comm.dropout_rate),) * n_workers
        else:
            dropout = comm.dropout_rate
        return cls(
            ef_decay=comm.ef_decay,
            momentum=comm.momentum_correction,
            local_clip=comm.local_clip,
            gossip_gamma=comm.gossip_step_size,
            gossip_w=comm.gossip_mix_weight,
            clip_norm=clip_norm,
            stale_scale=comm.stale_scale,
            dropout=dropout,
            churn_start=float(comm.churn_start),
            churn_end=(float(comm.churn_end) if comm.churn_end >= 0
                       else float("inf")),
            corruption=float(comm.corruption_rate),
            quarantine_limit=float(comm.quarantine_limit),
            seed=seed,
            comp=comp_per_bucket,
        )

    def as_tree(self) -> dict:
        import jax.numpy as jnp

        f32 = jnp.float32
        return {
            "ef_decay": jnp.asarray(self.ef_decay, f32),
            "momentum": jnp.asarray(self.momentum, f32),
            "local_clip": jnp.asarray(self.local_clip, f32),
            "gossip_gamma": jnp.asarray(self.gossip_gamma, f32),
            "gossip_w": jnp.asarray(self.gossip_w, f32),
            "clip_norm": jnp.asarray(self.clip_norm, f32),
            "stale_scale": jnp.asarray(self.stale_scale, f32),
            "dropout": jnp.asarray(self.dropout, f32),
            "churn_start": jnp.asarray(self.churn_start, f32),
            "churn_end": jnp.asarray(self.churn_end, f32),
            "corruption": jnp.asarray(self.corruption, f32),
            "quarantine_limit": jnp.asarray(self.quarantine_limit, f32),
            "seed": jnp.asarray(self.seed, jnp.int32),
            "comp": [
                {k: jnp.asarray(v, f32) for k, v in d.items()} for d in self.comp
            ],
        }
