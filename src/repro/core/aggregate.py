"""Compressed gradient aggregation (the paper's pipeline, §II summary eq.):

    u = momentum-correct(g);  a = clip(u) + e;  c = C(a);  e = a - C(a)
    agg = Aggregate(c_1..n; topology)

Runs inside shard_map, manual over the gradient axes (``data``[, ``pod``]).
Buckets: per-tensor by default, or MG-WFBP-style fused buckets [64] with
``bucket_mb > 0`` (fewer collectives -> smaller latency term, paper §VII).

Aggregation strategies by compressor ``reduce_mode``:
  * dense (no compressor): all-reduce with a selectable schedule (§IV-B).
  * "none": all_gather the compressed payload, decompress per worker
    (memory-bounded fori loop; (values,indices) payloads use one scatter-add).
  * "sum": payload is dense-masked; psum then average.
  * "majority": psum of int8 signs, then sign() — SignSGD majority vote [173].

``CommConfig.wire_format="compressed"`` overrides the above for families
with a ``wire_reduce`` attribute: the wire carries the PACKED payload
(1-bit sign bitmaps, 2-bit ternary codes, int8 quantizer codes — or bf16
for the dense path) and a fused Pallas unpack+accumulate kernel
(repro.kernels.wire_reduce) reduces all workers in one pass.  With EF and
a fused-capable compressor (qsgd_kernel), the EF add + quantize + residual
update collapse into ``compress_ef_p`` as well.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


from repro.core import collectives, comms, feedback, integrity
from repro.core.compression.base import (
    Compressed,
    compress_p,
    decompress_p,
    get_compressor,
    runtime_knob_values,
    runtime_knobs,
)
from repro.core.types import CommConfig, bundle_spec, carries_mask

f32 = jnp.float32


@dataclass(frozen=True)
class Bucket:
    name: str
    #: (leaf_index, size) segments concatenated into this bucket
    segments: tuple[tuple[int, int], ...]
    size: int
    compressor_name: str
    compressor_kwargs: tuple  # hashable kv pairs


@dataclass(frozen=True)
class BucketPlan:
    buckets: tuple[Bucket, ...]

    def compressor(self, b: Bucket):
        return get_compressor(b.compressor_name, **dict(b.compressor_kwargs))

    def knob_values(self) -> tuple[dict, ...]:
        """Per-bucket runtime-traceable compressor knob values — the ``comp``
        half of :class:`repro.core.types.CommKnobs`."""
        return tuple(runtime_knob_values(self.compressor(b)) for b in self.buckets)


def plan_signature(plan: BucketPlan) -> tuple:
    """Hashable structural identity of a plan: segment layout plus the
    compressor family per bucket with runtime-traceable knob values REMOVED.
    Part of the bundle-cache key — two cells whose plans differ only in
    traced knob values (qsgd levels, terngrad clip) share compiled steps."""
    out = []
    for b in plan.buckets:
        comp = plan.compressor(b)
        traced = set(runtime_knobs(comp))
        static_kw = tuple(kv for kv in b.compressor_kwargs if kv[0] not in traced)
        out.append((b.name, b.segments, b.size, b.compressor_name, static_kw))
    return tuple(out)


def _rule_for(comm: CommConfig, path: str) -> tuple[str, dict]:
    for sub, name, kwargs in comm.per_tensor_rules:
        if sub in path:
            return name, kwargs
    return comm.compressor, dict(comm.compressor_kwargs)


def make_bucket_plan(comm: CommConfig, grads_abstract: Any) -> BucketPlan:
    """Static bucketing decided from abstract (local) leaf shapes."""
    from repro.utils.tree import flatten_with_paths

    flat = flatten_with_paths(grads_abstract)
    items = sorted(flat.items())
    buckets: list[Bucket] = []
    if comm.bucket_mb <= 0:
        for i, (path, leaf) in enumerate(items):
            name, kw = _rule_for(comm, path)
            buckets.append(
                Bucket(path, ((i, int(np.prod(leaf.shape))),), int(np.prod(leaf.shape)), name, tuple(sorted(kw.items())))
            )
    else:
        cap = int(comm.bucket_mb * 1024 * 1024 / 4)
        cur: list[tuple[int, int]] = []
        cur_size = 0
        idx = 0
        for i, (path, leaf) in enumerate(items):
            n = int(np.prod(leaf.shape))
            if cur and cur_size + n > cap:
                buckets.append(
                    Bucket(f"bucket{idx}", tuple(cur), cur_size, comm.compressor, tuple(sorted(comm.compressor_kwargs.items())))
                )
                idx += 1
                cur, cur_size = [], 0
            cur.append((i, n))
            cur_size += n
        if cur:
            buckets.append(
                Bucket(f"bucket{idx}", tuple(cur), cur_size, comm.compressor, tuple(sorted(comm.compressor_kwargs.items())))
            )
    return BucketPlan(tuple(buckets))


def init_comm_state(comm: CommConfig, plan: BucketPlan) -> dict[str, Any]:
    """Every comm-state leaf of a cell, one shard's view: scalars are
    replicated, every other leaf is per shard."""
    spec = bundle_spec(comm)

    def per_bucket():
        return [jnp.zeros((b.size,), f32) for b in plan.buckets]

    state: dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
    if spec.churn:
        # previous round's participation bit — rejoin detection
        state["alive_prev"] = jnp.ones((1,), f32)
        if comm.pod_local:
            # pod-granularity liveness for the DCN sync round (derived from
            # the per-shard bits, carried so pod rejoins are detectable)
            state["pod_alive_prev"] = jnp.ones((1,), f32)
    if spec.corruption_kind != "none":
        # consecutive-quarantine counter + lifetime tallies of quarantined
        # rounds and rejoin escalations
        state["qcount"] = jnp.zeros((1,), f32)
        state["quarantine_total"] = jnp.zeros((1,), f32)
        state["escalation_total"] = jnp.zeros((1,), f32)
    if spec.overlap == "pipelined" and spec.overlap_staleness == 1:
        # the last microbatch's bucket grads, double-buffered across the
        # step boundary (aggregated by the NEXT step)
        state["overlap_pending"] = per_bucket()
    if comm.error_feedback:
        state["ef"] = per_bucket()
    if comm.momentum_correction:
        state["u"] = per_bucket()
    if plan_uses_powersgd(plan):
        qs = []
        for i, b in enumerate(plan.buckets):
            comp = plan.compressor(b)
            if getattr(comp, "reduce_mode", "") == "powersgd":
                # identical on every worker: fixed key per bucket
                qs.append(comp.init_q(b.size, jax.random.key(1000 + i)).reshape(-1))
            else:
                qs.append(jnp.zeros((0,), f32))
        state["psgd_q"] = qs
    if spec.aggregator == "gossip" and spec.gossip_compress == "choco":
        state["choco_xhat"] = per_bucket()
        state["choco_nbr"] = per_bucket()
    return state


def worker_index(axes) -> jax.Array:
    """This shard's row-major index over ``axes``."""
    widx = jnp.zeros((), jnp.int32)
    for axn in axes:
        widx = widx * jax.lax.axis_size(axn) + jax.lax.axis_index(axn)
    return widx


def draw_alive(step_key: jax.Array, step: jax.Array, knobs: dict[str, Any],
               mask_axes) -> tuple[jax.Array, jax.Array, jax.Array]:
    """This shard's participation bit for one round: ``(alive, in_window,
    mask_key)``.  The mask key folds the shard's index over ``mask_axes``
    into the round's step key; the bit is 0 with the shard's traced dropout
    rate inside the traced ``[churn_start, churn_end)`` window, else 1."""
    widx = worker_index(mask_axes)
    mkey = jax.random.fold_in(step_key, widx)
    drop = knobs["dropout"]
    if getattr(drop, "ndim", 0) == 1:
        # per-worker dropout vector: this shard's traced rate
        drop = jnp.take(drop, widx)
    u = jax.random.uniform(jax.random.fold_in(mkey, 0x6368), ())
    stepf = step.astype(f32)
    in_window = (stepf >= knobs["churn_start"]) & (stepf < knobs["churn_end"])
    alive = jnp.where(in_window & (u < drop), 0.0, 1.0)
    return alive, in_window, mkey


def rejoin(state: dict[str, Any], alive: jax.Array,
           slot: str = "alive_prev") -> jax.Array:
    """The rejoin bit (alive this round, masked out the last) of a shard;
    records this round's bit in ``state[slot]``."""
    rejoined = alive * (1.0 - state[slot].reshape(()))
    state[slot] = alive.reshape(1)
    return rejoined


def reset_comp_state(state: dict[str, Any], bit: jax.Array) -> None:
    """Zero the compressor state (EF residual, momentum) where ``bit`` is
    set: the frozen buffers describe a model that has since moved on.  A
    select, so a bit that is identically 0 leaves the state bitwise."""
    for k in ("ef", "u"):
        if k in state:
            state[k] = [jnp.where(bit > 0, jnp.zeros_like(e), e)
                        for e in state[k]]


def escalate(state: dict[str, Any], alive: jax.Array, valid: jax.Array,
             limit: jax.Array) -> jax.Array:
    """Bounded quarantine: a live shard's consecutive invalid rounds count
    up in ``qcount``; at the traced ``limit`` the shard escalates to the
    rejoin protocol's reset leg (its compressor state is stale-by-
    quarantine the way a rejoiner's is stale-by-death) instead of retrying
    forever.  Books the lifetime tallies and returns the escalation bit;
    every select is the identity at corruption rate 0."""
    q = state["qcount"].reshape(())
    q_new = jnp.where(alive > 0, jnp.where(valid > 0, 0.0, q + 1.0), q)
    esc = jnp.where(q_new >= limit, 1.0, 0.0)
    reset_comp_state(state, esc)
    state["qcount"] = jnp.where(esc > 0, 0.0, q_new).reshape(1)
    state["quarantine_total"] = (state["quarantine_total"]
                                 + (1.0 - valid).reshape(1))
    state["escalation_total"] = state["escalation_total"] + esc.reshape(1)
    return esc


def plan_uses_powersgd(plan: BucketPlan) -> bool:
    return any(b.compressor_name == "powersgd" for b in plan.buckets)


def _gather_buckets(plan: BucketPlan, leaves: list[jax.Array]) -> list[jax.Array]:
    out = []
    for b in plan.buckets:
        parts = [leaves[i].reshape(-1).astype(f32) for i, _ in b.segments]
        out.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return out


def _scatter_buckets(plan: BucketPlan, bucket_vals: list[jax.Array], leaves_like: list[jax.Array]) -> list[jax.Array]:
    new = list(leaves_like)
    for b, v in zip(plan.buckets, bucket_vals):
        off = 0
        for i, n in b.segments:
            new[i] = v[off : off + n].reshape(leaves_like[i].shape).astype(leaves_like[i].dtype)
            off += n
    return new


def _powersgd_aggregate(compressor, a, q_flat, axes, n_workers,
                        alive=None, n_eff=None):
    """PowerSGD round: psum-compatible low-rank factors (see
    compression/powersgd.py). Returns (agg, new_q_flat).

    Under churn (``alive``/``n_eff``) a dead worker's ``M`` contribution is
    zeroed before both factor psums and the denominators renormalize over
    the live set — the factor iteration runs on live gradients only.  The
    aggregated ``Qn`` is identical on every shard, so a rejoiner's ``Q``
    is re-warm-started from the live representative the moment it re-enters
    (its stale factor is overwritten by this round's live-set ``Qn``)."""
    from repro.core.compression.powersgd import orthonormalize, shape2d

    n = a.size
    aa, bb = shape2d(n)
    M = jnp.pad(a, (0, aa * bb - n)).reshape(aa, bb)
    if alive is not None:
        M = M * alive
    denom = n_workers if n_eff is None else n_eff
    Q = q_flat.reshape(bb, compressor.rank)
    P = comms.psum(M @ Q, axes) / denom
    P = orthonormalize(P)
    Qn = comms.psum(M.T @ P, axes) / denom
    agg = (P @ Qn.T).reshape(-1)[:n]
    return agg, Qn.reshape(-1)


def _gather_alive(alive: jax.Array | None, axes) -> jax.Array | None:
    """Churn participation bits of every worker, (W,) f32 (None when no churn)."""
    if alive is None:
        return None
    return comms.all_gather(alive.reshape(1), axes, axis=0).reshape(-1)


def _int8_code_reduce(compressor, c: Compressed, p, axes, alive_g, denom,
                      integ=None):
    """int8_acc wire reduction: all-gather the int8 codes as the quantize
    kernel's 32-bit words (four codes a word, n bytes a worker), fold each
    worker's decode scale norm_w/levels_w — and its churn mask — into the
    per-worker weight, and let one fused kernel sign-extend, accumulate and
    divide by ``denom``.  Neither the (W, n) codes nor their f32 decode are
    ever materialized, and no pass repacks the words around the gather.

    ``integ`` (gradient-integrity context, see :mod:`repro.core.integrity`):
    the shard's own payload is corrupted in-domain before the gather (XOR
    0x55 in every byte of a word is the int8 fault, code by code), every
    gathered row is validated (finite in-range norms/scales, codes within
    the level bound, read off words unpacked for the check), and an invalid
    row's weight + denominator share drop to zero — a one-round quarantine.
    Every select is an identity at corruption rate 0."""
    from repro.kernels import ops

    payload = dict(c.payload)
    if integ is not None:
        payload = integrity.corrupt_payload(integ["kind"], payload,
                                            integ["flag"])
    with comms.wire_format("int8"):
        cg = comms.all_gather(payload["code"], axes, axis=0)
    ng = comms.all_gather(payload["norm"], axes, axis=0).reshape(-1)
    if "s" in payload:
        sg = comms.all_gather(payload["s"], axes, axis=0).reshape(-1)
    else:
        sg = jnp.asarray((p or {}).get("levels", compressor.levels), f32)
    with jax.named_scope("decode"):
        w = ng / sg
        if alive_g is not None:
            w = w * alive_g
        if integ is not None:
            valid_g = (integrity.scale_valid(ng, sg)
                       * integrity.code_valid(ops.int8_unpack(cg, c.n), sg,
                                              per_row=True))
            w = jnp.where(valid_g > 0, w, 0.0)
            denom = jnp.maximum(jnp.sum(alive_g * valid_g), 1.0)
            own_s = (payload["s"].reshape(()) if "s" in payload else sg)
            integ["valid_bucket"] = (
                integrity.scale_valid(payload["norm"].reshape(()), own_s)
                * integrity.code_valid(ops.int8_unpack(payload["code"], c.n),
                                       own_s))
        return ops.int8_weighted_sum(cg, w, denom, n=c.n)


def _int8_wire(compressor, key, a, p):
    """(wire payload with the int8 codes as 32-bit words, self C(a)).  A
    compressor whose kernel writes the words hands them over as they are;
    one that makes int8 codes in jnp has them packed."""
    from repro.kernels import ops

    wire = getattr(compressor, "compress_wire_p", None)
    with jax.named_scope("encode"):
        if wire is None:
            c = compress_p(compressor, key, a, p)
            cw = Compressed({**c.payload, "code": ops.int8_pack(c.payload["code"])}, c.n)
        else:
            cw = wire(key, a, p)
            c = Compressed({**cw.payload, "code": ops.int8_unpack(cw.payload["code"], cw.n)},
                           cw.n)
    with jax.named_scope("decode"):
        self_hat = decompress_p(compressor, c, p)
    return cw, self_hat


def _compressed_reduce(compressor, key, a, axes, p, alive_g, denom,
                       integ=None):
    """Compressed-domain aggregation (``wire_format="compressed"``): the wire
    carries the PACKED/narrow payload and a fused Pallas kernel decodes and
    accumulates all workers in one pass.  Returns (aggregated mean,
    self decompressed C(a)).

    Exactness vs the composed dense path: sign majority is bit-identical to
    the unpacked int8 psum (both compare the same integer-valued f32 vote
    sums, ties -> +1); ternary accumulate is exact (every product has an
    exact {-1,0,+1} factor); int8_acc differs only by reassociating
    code/s*norm into code*(norm/s) (~1 ulp).

    ``integ``: in-domain fault injection + receiver-side validation.  The
    1-bit packed sign wire has NO redundancy (every bit pattern is a legal
    vote), so a flipped payload is undetectable by construction and the
    majority vote itself is the defense; the 2-bit ternary wire exposes the
    illegal crumb 2 plus its scale, and int8 codes expose range + norm."""
    from repro.kernels import ops

    wr = compressor.wire_reduce

    if wr in ("sign_vote", "sign_acc"):
        # pack straight from a — the int8 sign payload is never formed
        with jax.named_scope("encode"):
            packed = ops.sign_pack(a)
        if integ is not None:
            packed = integrity.corrupt_codes(integ["kind"], packed,
                                             integ["flag"])
        with comms.wire_format("packed1"):
            pg = comms.all_gather(packed, axes, axis=0)
        with jax.named_scope("decode"):
            w = jnp.ones((pg.shape[0],), f32) if alive_g is None else alive_g
            votes = ops.sign_vote(pg, w, n=a.size)
            self_hat = jnp.where(a >= 0, 1.0, -1.0).astype(f32)
            if wr == "sign_vote":  # majority: masked shards cast zero votes
                return jnp.where(votes >= 0, 1.0, -1.0).astype(f32), self_hat
            return votes / denom, self_hat  # mean of ±1 votes

    if wr == "int8_acc":
        c, self_hat = _int8_wire(compressor, key, a, p)
        return _int8_code_reduce(compressor, c, p, axes, alive_g, denom,
                                 integ=integ), self_hat

    with jax.named_scope("encode"):
        c = compress_p(compressor, key, a, p)
    with jax.named_scope("decode"):
        self_hat = decompress_p(compressor, c, p)
    if wr == "tern_acc":
        with jax.named_scope("encode"):
            packed = ops.tern_pack(c.payload["tern"])
        scale = c.payload["scale"]
        if integ is not None:
            packed = integrity.corrupt_codes(integ["kind"], packed,
                                             integ["flag"])
            scale = integrity.corrupt_dense(integ["kind"], scale,
                                            integ["flag"])
        with comms.wire_format("packed2"):
            pg = comms.all_gather(packed, axes, axis=0)
        sg = comms.all_gather(scale, axes, axis=0).reshape(-1)
        with jax.named_scope("decode"):
            w = sg if alive_g is None else sg * alive_g
            if integ is not None:
                valid_g = (integrity.packed2_valid(pg, per_row=True)
                           * integrity.scale_valid(sg))
                w = jnp.where(valid_g > 0, w, 0.0)
                denom = jnp.maximum(jnp.sum(alive_g * valid_g), 1.0)
                integ["valid_bucket"] = (
                    integrity.packed2_valid(packed)
                    * integrity.scale_valid(scale.reshape(())))
            return ops.tern_acc(pg, w, n=c.n) / denom, self_hat
    raise ValueError(f"unknown wire_reduce {wr!r} on {compressor!r}")


def _aggregate_one(
    comm: CommConfig,
    compressor,
    key: jax.Array,
    a: jax.Array,
    axes: tuple[str, ...],
    p: dict | None = None,
    alive: jax.Array | None = None,
    n_eff: jax.Array | None = None,
    integ: dict | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (aggregated mean, self decompressed C(a) for the EF update).
    ``p`` carries the bucket's *traced* runtime knob values (qsgd levels,
    terngrad clip, ...) so shape-class cells share one compiled program.
    ``alive``/``n_eff`` (churn): this shard's traced participation bit and
    the live-worker count — masked shards contribute zero and the mean
    renormalizes over the live set.
    ``integ`` (gradient integrity): the shard's outgoing wire payload is
    corrupted in-domain where its flag is set, validated with the format's
    own redundancy, and an invalid contribution is excluded + the
    denominator renormalized — a one-round quarantine.  On the psum paths
    validation is necessarily sender-side (a psum has no per-row receiver
    view); it computes the identical predicate a receiver would."""
    n_workers = 1
    for axn in axes:
        n_workers *= jax.lax.axis_size(axn)
    denom = n_workers if n_eff is None else n_eff

    wire_fmt = getattr(comm, "wire_format", "dense")

    if compressor is None:
        if integ is not None:
            a_w = integrity.corrupt_dense(integ["kind"], a, integ["flag"])
            valid = integrity.dense_valid(a_w)
            integ["valid_bucket"] = valid
            # select (not multiply): a quarantined payload may hold NaN/Inf
            # and NaN * 0 would still poison the psum
            a_m = jnp.where(valid > 0, a_w, jnp.zeros_like(a_w)) * alive
            denom = jnp.maximum(comms.psum(alive * valid, axes), 1.0)
        else:
            a_m = a if alive is None else a * alive
        if wire_fmt == "compressed":
            # bf16 wire format, f32 accumulation: half the wire bytes of the
            # dense path without the bf16-psum partial-sum rounding
            agg = comms.widening_psum(a_m.astype(jnp.bfloat16), axes) / denom
        elif comm.agg_dtype == "bfloat16":
            a16 = a_m.astype(jnp.bfloat16)
            agg = collectives.allreduce(a16, axes, impl=comm.collective).astype(f32) / denom
        else:
            agg = collectives.allreduce(a_m, axes, impl=comm.collective) / denom
        return agg, a

    if wire_fmt == "compressed" and getattr(compressor, "wire_reduce", ""):
        return _compressed_reduce(compressor, key, a, axes, p,
                                  _gather_alive(alive, axes), denom,
                                  integ=integ)

    with jax.named_scope("encode"):
        c = compress_p(compressor, key, a, p)
    with jax.named_scope("decode"):
        self_hat = decompress_p(compressor, c, p)
    mode = compressor.reduce_mode

    if mode == "majority":
        # int8 vote sum is exact for <=127 workers (our axes are <=32) and
        # keeps the wire at 1 byte/element (4x; bit-packed variant is 32x);
        # masked-out shards cast zero votes (ties resolve to +1 as before)
        sign = c.payload["sign"]
        if integ is not None:
            sign = integrity.corrupt_codes(integ["kind"], sign, integ["flag"])
            valid = integrity.code_valid(sign, 1.0)
            integ["valid_bucket"] = valid
            sign = sign * (alive * valid).astype(sign.dtype)
        elif alive is not None:
            sign = sign * alive.astype(sign.dtype)
        votes = comms.psum(sign, axes)
        with jax.named_scope("decode"):
            agg = jnp.where(votes >= 0, 1.0, -1.0).astype(f32)
    elif mode == "sum":
        dense = c.payload["dense"]
        if integ is not None:
            dense = integrity.corrupt_dense(integ["kind"], dense,
                                            integ["flag"])
            valid = integrity.dense_valid(dense)
            integ["valid_bucket"] = valid
            dense = jnp.where(valid > 0, dense, jnp.zeros_like(dense)) * alive
            denom = jnp.maximum(comms.psum(alive * valid, axes), 1.0)
        elif alive is not None:
            dense = dense * alive
        agg = comms.psum(dense, axes) / denom
    else:  # gather + decompress
        payload = c.payload
        if integ is not None:
            payload = integrity.corrupt_payload(integ["kind"], payload,
                                                integ["flag"])
            code_bound = (p or {}).get("levels",
                                       getattr(compressor, "levels", None))
            vb = jnp.ones((), f32)
            for k, v in payload.items():
                if jnp.issubdtype(v.dtype, jnp.floating):
                    vb = vb * integrity.dense_valid(v)
                elif k == "code" and code_bound is not None:
                    vb = vb * integrity.code_valid(v, code_bound)
            integ["valid_bucket"] = vb
        gathered = {k: comms.all_gather(v, axes, axis=0) for k, v in payload.items()}
        alive_g = None
        if alive is not None:
            alive_g = comms.all_gather(alive.reshape(1), axes, axis=0).reshape(-1)
        valid_g = None
        if integ is not None:
            valid_g = jnp.ones((n_workers,), f32)
            for k, v in gathered.items():
                if jnp.issubdtype(v.dtype, jnp.floating):
                    valid_g = valid_g * integrity.dense_valid(
                        v.reshape(n_workers, -1), per_row=True)
                elif k == "code" and code_bound is not None:
                    valid_g = valid_g * integrity.code_valid(
                        v.reshape(n_workers, -1), code_bound, per_row=True)
            denom = jnp.maximum(jnp.sum(alive_g * valid_g), 1.0)
        with jax.named_scope("decode"):
            if "indices" in gathered:  # sparse (values, indices): one scatter-add
                vals2d = gathered["values"].reshape(n_workers, -1)
                if valid_g is not None:
                    wrow = alive_g * valid_g
                    vals2d = jnp.where(wrow[:, None] > 0, vals2d, 0.0)
                elif alive_g is not None:
                    vals2d = vals2d * alive_g[:, None]
                vals = vals2d.reshape(-1)
                idx = gathered["indices"].reshape(-1)
                agg = jnp.zeros((c.n,), f32).at[idx].add(vals) / denom
            else:
                wrow_g = None if valid_g is None else alive_g * valid_g

                def body(w, acc):
                    pw = {k: jax.lax.dynamic_index_in_dim(v, w, 0, keepdims=False) for k, v in gathered.items()}
                    dec = decompress_p(compressor, Compressed(pw, c.n), p)
                    if wrow_g is not None:
                        return acc + jnp.where(wrow_g[w] > 0, dec,
                                               jnp.zeros_like(dec))
                    return acc + (dec if alive_g is None else alive_g[w] * dec)

                agg = jax.lax.fori_loop(0, n_workers, body, jnp.zeros((c.n,), f32)) / denom

    if getattr(compressor, "re_sparsify", False):  # gTop-k [191]
        kk = compressor.k or max(1, int(c.n * compressor.ratio))
        kk = min(kk, c.n)
        _, idx = jax.lax.top_k(jnp.abs(agg), kk)
        agg = jnp.zeros_like(agg).at[idx].set(agg[idx])
    return agg, self_hat


def aggregate_buckets(
    comm: CommConfig,
    plan: BucketPlan,
    bufs: list[jax.Array],
    comm_state: dict[str, Any],
    key: jax.Array,
    axes: tuple[str, ...],
    knobs: dict[str, Any],
    mask_axes: tuple[str, ...] | None = None,
    alive_info: tuple | None = None,
) -> tuple[list[jax.Array], dict[str, Any]]:
    """The §II pipeline over already-gathered flat bucket vectors.

    This is the granularity the pipelined-overlap step (§VII) works at: the
    microbatch scan carries bucket buffers and issues these collectives with
    no data dependency on the next microbatch's compute.  Functional state
    update; safe inside ``lax.scan`` (every shape is static).

    ``knobs`` is the cell's traced :class:`repro.core.types.CommKnobs` tree
    (``knobs["comp"][i]`` per bucket, plus the scalar knobs).

    ``mask_axes``: the axes the churn mask is drawn over — defaults to the
    aggregation axes.  ``pod_local`` passes ALL data axes here while
    aggregating only within the pod, so shards in different pods draw
    independent fates (the per-shard granularity of the dual-granularity
    liveness; the pod-sync granularity derives from ``alive_prev``).

    ``alive_info`` = (alive, rejoined, in_window): an externally-drawn mask
    for callers that must hold one mask across several aggregation calls
    (the pipelined staleness-1 microbatch scan).  The caller owns the
    ``alive_prev`` update; the rejoin reset still applies here."""
    n_workers = 1
    for axn in axes:
        n_workers *= jax.lax.axis_size(axn)
    if mask_axes is None:
        mask_axes = axes

    # distinct stochastic-compression keys per worker
    step_key = key
    key = jax.random.fold_in(key, worker_index(axes))

    state = dict(comm_state)
    for k in ("ef", "u", "psgd_q"):
        if k in state:
            state[k] = list(state[k])

    # churn: each shard draws its own participation bit for this round from
    # the per-worker key (probability/window traced via knobs); the live
    # count is one scalar psum — a real liveness round on the wire.  One
    # mask covers every bucket of the round.
    alive = n_eff = None
    if carries_mask(comm):
        if alive_info is None:
            alive, in_window, mkey = draw_alive(step_key, state["step"], knobs,
                                                mask_axes)
            rejoined = rejoin(state, alive)
        else:
            alive, rejoined, in_window = alive_info
            mkey = jax.random.fold_in(step_key, worker_index(mask_axes))
        n_eff = jnp.maximum(comms.psum(alive, axes), 1.0)
        # rejoin protocol: a shard alive this round but masked out last
        # round resets its compressor state.  The rejoined bit is
        # identically 0 at dropout 0 (alive_prev inits to 1), preserving the
        # bitwise churn-free equivalence; powersgd Q needs no reset because
        # the psum'd live-set Qn overwrites every shard's factor each round.
        reset_comp_state(state, rejoined)

    # gradient integrity: one corruption flag per worker per round, drawn
    # from the same per-worker key stream as the churn mask (its own fold
    # tag — the mask / compressor draws are untouched); only live in-window
    # workers have a payload on the wire to corrupt
    integ = round_valid = None
    if comm.corruption_kind != "none":
        flag = integrity.corruption_flag(mkey, knobs["corruption"],
                                         in_window & (alive > 0))
        integ = {"kind": comm.corruption_kind, "flag": flag,
                 "valid_bucket": jnp.ones((), f32)}
        round_valid = jnp.ones((), f32)

    wire_fmt = getattr(comm, "wire_format", "dense")
    out_bufs = []
    with comms.tag("grad_agg"):
        for i, (b, g) in enumerate(zip(plan.buckets, bufs)):
            compressor = plan.compressor(b)
            p_i = knobs["comp"][i]
            if integ is not None:
                integ["valid_bucket"] = jnp.ones((), f32)
            if (wire_fmt == "compressed" and comm.error_feedback
                    and not comm.momentum_correction and not comm.local_clip
                    and hasattr(compressor, "compress_ef_p")):
                # fused EF+quantize (kernels/qsgd_ef.py): one Pallas pass
                # yields the int8 WIRE codes and the residual update, so
                # pre/post_compress collapse into the kernel; same uniform
                # draw as the composed path (momentum correction or local
                # clipping would need the unfused arithmetic — excluded)
                decay = knobs["ef_decay"]
                ef_prev = state["ef"][i]
                with jax.named_scope("encode"):
                    c, e_new = compressor.compress_ef_p(
                        jax.random.fold_in(key, i), g, ef_prev, p_i, decay)
                denom = n_workers if n_eff is None else n_eff
                agg = _int8_code_reduce(
                    compressor, c, p_i, axes, _gather_alive(alive, axes),
                    denom, integ=integ)
                # quarantine freezes EF exactly like a masked round: the
                # round was dropped, so the residual must not absorb it
                gate_ef = alive
                if integ is not None:
                    gate_ef = alive * integ["valid_bucket"]
                state["ef"][i] = (e_new if alive is None
                                  else jnp.where(gate_ef > 0, e_new, ef_prev))
                if round_valid is not None:
                    round_valid = round_valid * integ["valid_bucket"]
                out_bufs.append(agg)
                continue
            u_prev = state["u"][i] if "u" in state else None
            with jax.named_scope("encode"):
                a = feedback.pre_compress(comm, g, state, i, n_workers,
                                          knobs=knobs, alive=alive)
            if getattr(compressor, "reduce_mode", "") == "powersgd":
                # powersgd's wire is a pair of factor psums — no per-worker
                # payload to corrupt in-domain (rejected at scenario level)
                agg, q_new = _powersgd_aggregate(
                    compressor, a, state["psgd_q"][i], axes, n_workers,
                    alive=alive, n_eff=n_eff,
                )
                state["psgd_q"][i] = q_new
                self_hat = agg  # per-worker EF vs the GLOBAL approximation
            else:
                agg, self_hat = _aggregate_one(
                    comm, compressor, jax.random.fold_in(key, i), a, axes,
                    p_i, alive=alive, n_eff=n_eff, integ=integ,
                )
            av = alive
            if integ is not None:
                av = alive * integ["valid_bucket"]
            if compressor is not None:
                with jax.named_scope("decode"):
                    feedback.post_compress(comm, a, self_hat, state, i, alive=av)
            if integ is not None and u_prev is not None:
                # momentum accumulated the quarantined round pre-compression;
                # undo — the freeze path for a state the validator gates late
                state["u"][i] = jnp.where(integ["valid_bucket"] > 0,
                                          state["u"][i], u_prev)
            if round_valid is not None:
                round_valid = round_valid * integ["valid_bucket"]
            out_bufs.append(agg)
    if round_valid is not None:
        escalate(state, alive, round_valid, knobs["quarantine_limit"])
    state["step"] = state["step"] + 1
    return out_bufs, state


def aggregate_gradients(
    comm: CommConfig,
    plan: BucketPlan,
    grads: Any,
    comm_state: dict[str, Any],
    key: jax.Array,
    axes: tuple[str, ...],
    knobs: dict[str, Any],
    mask_axes: tuple[str, ...] | None = None,
    alive_info: tuple | None = None,
) -> tuple[Any, dict[str, Any]]:
    """The full §II pipeline over a gradient pytree. Functional state update.

    ``knobs`` is the traced :class:`repro.core.types.CommKnobs` tree of the
    cell (``knobs["comp"][i]`` per bucket, plus ef_decay / momentum /
    local_clip scalars).  ``mask_axes``/``alive_info`` pass through to
    :func:`aggregate_buckets` (pod-granular churn masks / externally-held
    pipelined masks)."""
    leaves, treedef = jax.tree.flatten(grads)
    # the bucket packing and unpacking carry no collective, so tagging them
    # moves no wire bytes between tags; it names them in a device trace
    with comms.tag("grad_agg"):
        bufs = _gather_buckets(plan, leaves)
    out_bufs, state = aggregate_buckets(
        comm, plan, bufs, comm_state, key, axes, knobs=knobs,
        mask_axes=mask_axes, alive_info=alive_info,
    )
    with comms.tag("grad_agg"):
        new_leaves = _scatter_buckets(plan, out_bufs, leaves)
    return jax.tree.unflatten(treedef, new_leaves), state
