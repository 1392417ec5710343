"""Step builders: the glue between model, communication pipeline, optimizer
and the mesh.

Everything (forward, backward, tensor-parallel collectives, gradient
compression + aggregation, optimizer, Local-SGD parameter averaging, gossip
mixing, decode) runs inside ONE ``jax.shard_map`` that is manual over every
mesh axis — every byte on the wire is a collective this package placed
explicitly (see repro.core.comms).

Step functions produced (all jitted, AOT-lowerable):
  * ``train_step(state, batch, lr)``   — fwd+bwd+aggregate+update (BSP path)
  * ``inner_step``                     — same without gradient aggregation
                                          (Local SGD inner iterations)
  * ``sync_step(state)``               — Local-SGD model averaging (Eq. 9)
  * ``gossip_step(state, batch, lr)``  — D-PSGD / CHOCO-SGD parameter mixing
  * ``prefill_step(params, batch)``    — build decode caches
  * ``serve_step(params, cache, tok)`` — one token, context-parallel cache
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


from repro.configs.base import InputShape, ModelConfig
from repro.core import aggregate, comms, gossip, integrity, sync
from repro.core.compression.base import get_compressor
from repro.core.types import (
    BundleSpec,
    CommConfig,
    CommKnobs,
    bundle_spec,
)
from repro.launch import specs as SP
from repro.models import transformer as T
from repro.models.sharding import AxisCtx, make_plan, tree_specs
from repro.optim.optimizers import Optimizer, global_clip

f32 = jnp.float32


def local_abstract(tree: Any, pspecs: Any, mesh) -> Any:
    """Global abstract tree -> per-shard abstract tree under the mesh."""

    def f(x, s):
        shape = list(x.shape)
        for i, entry in enumerate(s):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for nm in names:
                assert shape[i] % mesh.shape[nm] == 0, (x.shape, s, nm)
                shape[i] //= mesh.shape[nm]
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

    return jax.tree.map(f, tree, pspecs, is_leaf=lambda l: isinstance(l, P))


def global_abstract(tree: Any, pspecs: Any, mesh) -> Any:
    def f(x, s):
        shape = list(x.shape)
        for i, entry in enumerate(s):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for nm in names:
                shape[i] *= mesh.shape[nm]
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

    return jax.tree.map(f, tree, pspecs, is_leaf=lambda l: isinstance(l, P))


def _mentions_model(spec: P) -> bool:
    for entry in spec:
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        if "model" in names:
            return True
    return False


def _fix_model_grads(grads: Any, specs: Any, model_axis: str) -> Any:
    """Gradient correction for ``check_vma=False`` AD semantics.

    Under the unreduced-cotangent convention (transpose(psum) = psum), raw
    shard_map gradients come out as
        * model-SHARDED params:   msize x the true local gradient slice,
        * model-REPLICATED params: msize x a per-shard *partial* gradient.
    So: sharded -> g/msize ; replicated -> psum(g)/msize.  Validated
    element-wise against single-device AD for all 10 architectures
    (tests/test_tp_equivalence.py).  The replicated-leaf psums are real wire
    traffic (tagged 'tp_grad_fixup' in the roofline accounting)."""

    msize = jax.lax.axis_size(model_axis)

    def fix(g, s):
        if _mentions_model(s):
            return g / msize
        with comms.tag("tp_grad_fixup"):
            return comms.psum(g, model_axis) / msize

    return jax.tree.map(fix, grads, specs, is_leaf=lambda l: isinstance(l, P))


@dataclass
class StepBundle:
    cfg: ModelConfig
    comm: CommConfig
    mesh: Any
    ax: AxisCtx
    param_abstract: Any  # global
    param_specs: Any
    state_specs: Any
    state_abstract: Any  # global
    bucket_plan: aggregate.BucketPlan
    opt: Optimizer
    init_state: Callable  # (params) -> state          [jitted shard_map]
    train_step: Callable  # (state, batch, lr) -> (state, metrics)
    inner_step: Callable | None
    sync_step: Callable | None
    gossip_step: Callable | None
    eval_step: Callable  # (state, batch) -> loss
    batch_specs: Any = None
    batch_pspecs: Any = None
    #: static half of the cell's CommConfig (the bundle-cache identity)
    spec: BundleSpec | None = None
    #: per-call wire bytes by tag, captured once at build time by tracing
    #: each step program abstractly: {"train"|"inner"|"sync"|"gossip":
    #: {tag: bytes}}.  Cache-reused bundles carry the same artifact, so wire
    #: accounting no longer depends on being the first trace of the program.
    wire: dict[str, dict[str, float]] | None = None

    def shardings(self, tree_pspecs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), tree_pspecs,
                            is_leaf=lambda l: isinstance(l, P))


class _PersistentStep:
    """A knob-threaded step program backed by the persistent executable
    cache.  Resolution is LAZY — nothing compiles until the first real call,
    so dry-run paths (``.lower`` only) stay trace-only:

    * first call, blob on disk (warm process): deserialize the whole XLA
      executable (``jax.experimental.serialize_executable``) — NO tracing,
      NO lowering, NO backend compile;
    * first call, no blob (cold): AOT-compile from the build-time avals and
      serialize for the next process — same work the jit path would do;
    * an unreadable or foreign blob is compiled afresh; a compile the
      backend refuses raises here, where it happens.

    If the first call happens under an open ``comms.capture()``, the
    deserialize shortcut is skipped and the step AOT-compiles from the
    avals instead: a capture's contract is that it observes the
    collectives of a first call it wraps, which requires tracing (jax's
    own persistent cache still skips the backend compile, so the capture
    costs trace time only).

    Calls coerce non-Array leaves (the trainer passes ``lr`` as a python
    float, which jit accepts as a weak-typed scalar but a compiled
    executable rejects); ``lower`` always forwards to the jitted function.
    """

    def __init__(self, jitted, avals: tuple, path: str):
        self._jit = jitted
        self._avals = avals
        self._path = path
        self._compiled = None
        self._resolved = False

    def _resolve(self) -> None:
        self._resolved = True
        import pickle

        from jax.experimental.serialize_executable import (
            deserialize_and_load,
            serialize,
        )

        if os.path.exists(self._path) and not comms.capturing():
            try:
                with open(self._path, "rb") as f:
                    payload, in_tree, out_tree = pickle.loads(f.read())
                self._compiled = deserialize_and_load(payload, in_tree, out_tree)
                return
            except (OSError, EOFError, pickle.PickleError,
                    jax.errors.JaxRuntimeError):
                pass  # unreadable or foreign blob: compile afresh below
        self._compiled = self._jit.lower(*self._avals).compile()
        if os.path.exists(self._path):
            return
        try:
            blob = pickle.dumps(serialize(self._compiled))
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            tmp = self._path + f".tmp{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._path)  # atomic: writers race benignly
        except (OSError, pickle.PickleError):
            pass  # the next process compiles again

    def __call__(self, *args):
        if not self._resolved:
            self._resolve()
        if self._compiled is not None:
            try:
                return self._compiled(*jax.tree.map(
                    lambda x: x if isinstance(x, jax.Array) else jnp.asarray(x),
                    args))
            except (TypeError, ValueError):
                # arg-form drift: aval/sharding mismatches are raised by
                # argument checking BEFORE any donation, so the jit retry
                # sees live buffers.  Anything else (a genuine runtime
                # failure mid-execution) may have consumed the donated
                # state, so it must propagate — a jit retry on deleted
                # arrays would only mask the original error.
                self._compiled = None
        return self._jit(*args)

    def lower(self, *args):
        return self._jit.lower(*args)


def _load_wire(exec_dir: str | None):
    """The build-time wire artifact persisted next to the executables —
    byte-for-byte the dict `_trace_wire` would re-derive, so warm builds
    skip the abstract traces."""
    if exec_dir is None:
        return None
    import json

    try:
        with open(os.path.join(exec_dir, "wire.json")) as f:
            return json.load(f)
    except Exception:
        return None


def _save_wire(exec_dir: str | None, wire: dict) -> None:
    if exec_dir is None:
        return
    import json

    try:
        os.makedirs(exec_dir, exist_ok=True)
        tmp = os.path.join(exec_dir, f"wire.json.tmp{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(wire, f)
        os.replace(tmp, os.path.join(exec_dir, "wire.json"))
    except OSError:  # pragma: no cover - unwritable cache dir
        pass


class BoundStep:
    """A compiled knob-threaded step, bound to one cell's traced knob values.

    ``fn(state, batch, lr, knobs)`` becomes the familiar
    ``step(state, batch, lr)``; ``lower(...)`` forwards to the underlying
    jitted function (the dry-run path) with the knobs appended."""

    def __init__(self, fn: Callable, knobs: Any, n_args: int):
        self._fn = fn
        self._knobs = knobs
        self._n_args = n_args

    def __call__(self, *args):
        assert len(args) == self._n_args, (len(args), self._n_args)
        return self._fn(*args, self._knobs)

    def lower(self, *args):
        return self._fn.lower(*args, self._knobs)


@dataclass
class _CompiledBundle:
    """The shape-class-shared half of a bundle: everything whose identity is
    (model, mesh, BundleSpec, plan signature, optimizer, shape) — compiled
    step programs take the cell's :class:`CommKnobs` tree as a traced
    trailing argument, so every cell of the class reuses them."""

    ax: AxisCtx
    param_abstract: Any
    param_specs: Any
    state_specs: Any
    state_abstract: Any
    batch_specs: Any
    batch_pspecs: Any
    init_state: Callable
    train_step_k: Callable  # (state, batch, lr, knobs)
    inner_step_k: Callable | None
    sync_step_k: Callable | None  # (state, knobs) — churn mask values traced
    gossip_step_k: Callable | None
    eval_step: Callable
    wire: dict[str, dict[str, float]]


@dataclass
class BundleCacheStats:
    """Build/hit counters for the bundle registry — the trainer-lane sweeps
    assert ``builds <= #shape-classes`` (mirrors ``engine_cache_stats``)."""

    builds: int = 0
    hits: int = 0

    @property
    def persistent_cache(self) -> dict:
        """On-disk cache effectiveness {hits, misses, dir} at bundle-key
        granularity (repro.core.compilecache manifest)."""
        from repro.core import compilecache

        return compilecache.record("bundle")


_BUNDLE_STATS = BundleCacheStats()
_BUNDLE_CACHE: dict[tuple, _CompiledBundle] = {}
_BUNDLE_CACHE_CAP = 32


def bundle_cache_stats() -> BundleCacheStats:
    return _BUNDLE_STATS


def bundle_cache_clear() -> None:
    """Drop every cached compiled bundle and zero the counters."""
    _BUNDLE_CACHE.clear()
    _BUNDLE_STATS.builds = 0
    _BUNDLE_STATS.hits = 0


def _mesh_key(mesh) -> tuple:
    return (
        tuple(mesh.axis_names),
        tuple(int(mesh.shape[a]) for a in mesh.axis_names),
        tuple(d.id for d in mesh.devices.flat),
    )


def bundle_cache_key(
    cfg: ModelConfig, mesh, spec: BundleSpec, plan: aggregate.BucketPlan,
    opt: Optimizer, shape: InputShape, *, clip_norm: float = 0.0,
    microbatch: int = 1,
) -> tuple:
    """The registry key: model-config fingerprint, mesh shape, static comm
    spec, bucket-plan signature, optimizer identity, input shape, and the
    structural build flags.  ``seed``, ``lr``, ``clip_norm``'s *value* and
    every CommKnobs value are deliberately absent — they are traced."""
    return (
        repr(cfg),  # dataclass repr = full field fingerprint
        _mesh_key(mesh),
        spec,
        aggregate.plan_signature(plan),
        (opt.name, opt.fingerprint),
        shape,
        bool(clip_norm),
        int(microbatch),
    )


def build_bundle(
    cfg: ModelConfig,
    mesh,
    comm: CommConfig,
    opt: Optimizer,
    shape: InputShape,
    *,
    clip_norm: float = 0.0,
    seed: int = 0,
    microbatch: int = 1,
    cache: bool = True,
) -> StepBundle:
    """Build (or fetch from the bundle registry) the step programs for one
    taxonomy cell.  Cells whose :func:`repro.core.types.bundle_spec` —
    plus model / mesh / plan signature / optimizer / shape — coincide share
    ONE set of compiled ``train_step``/``sync_step``/``gossip_step``
    programs; their value knobs (compressor levels/clip, EF decay, momentum
    coefficient, gossip weights, seed, clip threshold) ride along as a
    traced :class:`repro.core.types.CommKnobs` tree.  ``cache=False``
    forces a fresh build (the per-cell baseline the trainer sweep
    benchmark measures against)."""
    spec = bundle_spec(comm)
    msize = mesh.shape["model"]
    param_abs, param_specs, _ = T.abstract_params(cfg, msize)
    grads_local_abs = local_abstract(param_abs, param_specs, mesh)
    bplan = aggregate.make_bucket_plan(comm, grads_local_abs)

    key = bundle_cache_key(cfg, mesh, spec, bplan, opt, shape,
                           clip_norm=clip_norm, microbatch=microbatch)
    cb = _BUNDLE_CACHE.get(key) if cache else None
    if cb is None:
        from repro.core import compilecache

        # cache=False is the per-cell rebuild baseline the sweep benchmarks
        # time — it must pay the full build, so it never touches the
        # persistent executables either
        cb = _compile_bundle(cfg, mesh, comm, opt, shape, spec, bplan,
                             param_abs, param_specs,
                             clip_norm=clip_norm, microbatch=microbatch,
                             exec_dir=(compilecache.exec_dir("bundle", key)
                                       if cache else None))
        _BUNDLE_STATS.builds += 1
        if cache:
            # manifest the fresh build: every key component serializes stably
            # (repr-level) across processes, so a later process re-deriving
            # this bundle key pulls the XLA executables from the persistent
            # cache.  cache=False builds got exec_dir=None — no blobs on disk
            # — so manifesting them would let a later process claim a hit it
            # cannot serve (and inflate the hit/miss stats CI asserts on).
            compilecache.record_compile("bundle", key)
            if len(_BUNDLE_CACHE) >= _BUNDLE_CACHE_CAP:
                _BUNDLE_CACHE.pop(next(iter(_BUNDLE_CACHE)))
            _BUNDLE_CACHE[key] = cb
    else:
        _BUNDLE_STATS.hits += 1

    # the mask-unit count (shards over the DATA axes) normalizes the dropout
    # knob to a per-worker vector — scalar-rate and worker_dropout cells then
    # share one knob-tree structure, hence one compiled bundle
    n_data = int(np.prod([mesh.shape[a] for a in cb.ax.data]))
    knobs = CommKnobs.from_comm(
        comm, bplan.knob_values(), seed=seed, clip_norm=clip_norm,
        n_workers=n_data,
    ).as_tree()
    return StepBundle(
        cfg=cfg, comm=comm, mesh=mesh, ax=cb.ax,
        param_abstract=cb.param_abstract, param_specs=cb.param_specs,
        state_specs=cb.state_specs, state_abstract=cb.state_abstract,
        bucket_plan=bplan, opt=opt,
        init_state=cb.init_state,
        train_step=BoundStep(cb.train_step_k, knobs, 3),
        inner_step=(BoundStep(cb.inner_step_k, knobs, 3)
                    if cb.inner_step_k is not None else None),
        sync_step=(BoundStep(cb.sync_step_k, knobs, 1)
                   if cb.sync_step_k is not None else None),
        gossip_step=(BoundStep(cb.gossip_step_k, knobs, 3)
                     if cb.gossip_step_k is not None else None),
        eval_step=cb.eval_step,
        batch_specs=cb.batch_specs, batch_pspecs=cb.batch_pspecs,
        spec=spec, wire=cb.wire,
    )


def _compile_bundle(
    cfg: ModelConfig,
    mesh,
    comm: CommConfig,
    opt: Optimizer,
    shape: InputShape,
    spec: BundleSpec,
    bplan: aggregate.BucketPlan,
    param_abs: Any,
    param_specs: Any,
    *,
    clip_norm: float = 0.0,
    microbatch: int = 1,
    exec_dir: str | None = None,
) -> _CompiledBundle:
    ax = SP.make_axis_ctx(mesh)
    batch_abs, batch_pspecs = SP.train_inputs(cfg, shape, mesh)

    # pod-local mode: per-step gradient aggregation stays inside the pod
    # (fast ICI); the pod axis is synchronized by sync_step (slow DCN)
    agg_axes = ax.data
    sync_axes = ax.data
    if comm.pod_local and "pod" in mesh.axis_names:
        agg_axes = tuple(a for a in ax.data if a != "pod")
        sync_axes = ("pod",)
    # churn masks are drawn over ALL data axes even when aggregation is
    # pod-scoped, so shards in different pods draw independent fates (the
    # per-shard half of pod_local's dual-granularity liveness)
    mask_axes = ax.data if agg_axes != ax.data else None
    corruption_kind = spec.corruption_kind

    # ---- state specs ---------------------------------------------------------
    all_axes = ax.data + (ax.model,)
    if opt.name.startswith("zero1"):
        # optimizer state lives as per-shard slices over ALL axes
        leafspec = jax.tree.map(lambda _: P(all_axes), param_specs,
                                is_leaf=lambda l: isinstance(l, P))
        base = opt.name.split("_", 1)[1]
        inner = {
            "sgd": (),
            "adamw": {"m": leafspec, "v": leafspec, "t": P()},
        }.get(base, {"v": leafspec})
        opt_state_specs: Any = {"inner": inner}
    else:
        opt_state_specs = {
            "sgd": (),
            "momentum0.9": {"v": param_specs},
            "adamw": {"m": param_specs, "v": param_specs, "t": P()},
        }.get(opt.name, None)
        if opt_state_specs is None:  # momentum with other coefficient
            opt_state_specs = {"v": param_specs}
    # one declaration of the comm state (aggregate.init_comm_state):
    # scalars are replicated, every other leaf is per shard
    comm_state_specs = jax.tree.map(
        lambda x: P(all_axes) if x.ndim else P(),
        jax.eval_shape(lambda: aggregate.init_comm_state(comm, bplan)))
    state_specs = {
        "params": param_specs,
        "opt": opt_state_specs,
        "comm": comm_state_specs,
        "step": P(),
    }

    n_shards_total = int(np.prod([mesh.shape[a] for a in all_axes]))

    # ---- init ----------------------------------------------------------------
    def _init(params):
        opt_state = jax.tree.map(
            lambda x: comms.varying(x, all_axes) if hasattr(x, "shape") and x.ndim else x,
            opt.init(params),
        )
        cstate = jax.tree.map(
            lambda x: comms.varying(x, all_axes) if x.ndim else x,
            aggregate.init_comm_state(comm, bplan))
        return {"params": params, "opt": opt_state, "comm": cstate,
                "step": jnp.zeros((), jnp.int32)}

    init_state = jax.jit(
        jax.shard_map(_init, mesh=mesh, in_specs=(param_specs,), out_specs=state_specs,
                      check_vma=False)
    )

    # ---- traced knob tree -----------------------------------------------------
    # every step program takes the cell's CommKnobs tree as a trailing traced
    # argument; this representative (the compile cell's values) only fixes
    # the tree STRUCTURE — values are rebound per cell by build_bundle.
    knobs0 = CommKnobs.from_comm(
        comm, bplan.knob_values(), clip_norm=clip_norm,
        n_workers=int(np.prod([mesh.shape[a] for a in ax.data])),
    ).as_tree()
    knob_pspecs = jax.tree.map(lambda _: P(), knobs0)

    # ---- train steps -----------------------------------------------------------
    # the step's metrics: the loss and what forward_loss reports beside it
    metric_specs = {"loss": P(), **{k: P() for k in T.metric_names(cfg)}}

    def make_step(do_aggregate: bool):
        def _grads(params, batch):
            def loss_fn(p):
                # under AD the scope names the backward pass too:
                # transpose(jvp(forward)), its remat recompute inside it
                with jax.named_scope("forward"):
                    loss, metrics = T.forward_loss(cfg, p, batch, ax)
                return loss, metrics

            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def _microbatches(batch, n):
            return jax.tree.map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]), batch
            )

        def _sequential_grads(params, batch):
            """Post-hoc schedule (§VII "sequential"): accumulate every
            microbatch's raw gradient, aggregate once after the full
            backward — activation memory scales with B_local/microbatch."""
            if microbatch > 1:
                mb = _microbatches(batch, microbatch)

                def body(acc, b):
                    (l, m), g = _grads(params, b)
                    acc = jax.tree.map(lambda a, gg: a + gg.astype(f32), acc, g)
                    return acc, (l, m)

                acc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params)
                with comms.loop(microbatch):  # collective accounting
                    acc, (ls, ms) = jax.lax.scan(body, acc0, mb)
                grads = jax.tree.map(lambda a, p: (a / microbatch).astype(p.dtype), acc, params)
                loss = jnp.mean(ls)
                metrics = jax.tree.map(jnp.mean, ms)
            else:
                (loss, metrics), grads = _grads(params, batch)
            return _fix_model_grads(grads, param_specs, ax.model), loss, metrics

        def _pipelined_grads(state, batch, knobs):
            """Microbatch-pipelined bucketized aggregation (§VII overlap):
            inside the accumulation scan, iteration k issues the (compressed)
            all-reduce of the PREVIOUS microbatch's bucket grads — no data
            dependency on this iteration's forward/backward, so XLA's
            latency-hiding scheduler can overlap the collectives with
            compute.  Message granularity is the BucketPlan's.  With
            staleness 1 the last microbatch's buckets are double-buffered in
            ``comm["overlap_pending"]`` and aggregated by the NEXT step
            (every collective fully overlappable, the stale contribution
            scaled by the traced ``stale_scale`` knob); with staleness 0 the
            pipeline is primed with microbatch 0 and the last aggregation is
            flushed after the scan (no staleness, one exposed collective)."""
            params = state["params"]
            cstate = dict(state["comm"])
            key = jax.random.fold_in(jax.random.key(knobs["seed"]), state["step"])
            M = microbatch
            mb = _microbatches(batch, M)

            def mb_grads(b):
                (l, m), g = _grads(params, b)
                g = _fix_model_grads(g, param_specs, ax.model)
                leaves, _ = jax.tree.flatten(g)
                with comms.tag("grad_agg"):
                    bufs = aggregate._gather_buckets(bplan, leaves)
                return bufs, (l, m)

            acc0 = [jnp.zeros((b.size,), f32) for b in bplan.buckets]

            # churn under the staleness-1 double buffer: ONE mask per outer
            # step (drawn here, outside the scan) held across every
            # microbatch round — a dead worker's contributions all drop this
            # step, and a REJOINING worker's carried-over stale bucket (slot
            # 0, computed while it was out) is additionally gated off.  The
            # caller owns the alive_prev update; aggregate_buckets receives
            # the mask via ``alive_info`` so its per-call draw is skipped.
            alive_seq = rejoin_seq = in_window = None
            if spec.churn and spec.overlap_staleness == 1:
                alive, in_window, _ = aggregate.draw_alive(
                    key, state["step"], knobs,
                    mask_axes if mask_axes is not None else agg_axes)
                rejoined = aggregate.rejoin(cstate, alive)
                alive_seq = jnp.concatenate([
                    (alive * (1.0 - rejoined)).reshape(1),
                    jnp.broadcast_to(alive, (M - 1,)),
                ]) if M > 1 else (alive * (1.0 - rejoined)).reshape(1)
                rejoin_seq = jnp.concatenate([
                    rejoined.reshape(1), jnp.zeros((M - 1,), f32),
                ]) if M > 1 else rejoined.reshape(1)

            def body(carry, xs):
                acc, pending, cst = carry
                b, k, scale, a_k, r_k = xs
                ainfo = ((a_k, r_k, in_window) if alive_seq is not None
                         else None)
                agg, cst = aggregate.aggregate_buckets(
                    comm, bplan, pending, cst, jax.random.fold_in(key, k),
                    agg_axes, knobs=knobs, mask_axes=mask_axes,
                    alive_info=ainfo,
                )
                pending, (l, m) = mb_grads(b)
                acc = [a + scale * g for a, g in zip(acc, agg)]
                return (acc, pending, cst), (l, m)

            if spec.overlap_staleness == 1:
                pending0 = list(cstate.pop("overlap_pending"))
                scales = jnp.ones((M,), f32).at[0].set(knobs["stale_scale"])
                zero_seq = jnp.zeros((M,), f32)
                with comms.loop(M):  # collective accounting
                    (acc, pending, cst), (ls, ms) = jax.lax.scan(
                        body, (acc0, pending0, cstate),
                        (mb, jnp.arange(M), scales,
                         alive_seq if alive_seq is not None else zero_seq,
                         rejoin_seq if rejoin_seq is not None else zero_seq),
                    )
                cstate = dict(cst)
                cstate["overlap_pending"] = pending
                loss = jnp.mean(ls)
                metrics = jax.tree.map(jnp.mean, ms)
            else:
                pending, (l0, m0) = mb_grads(jax.tree.map(lambda x: x[0], mb))
                if M > 1:
                    with comms.loop(M - 1):
                        (acc, pending, cstate), (ls, ms) = jax.lax.scan(
                            body, (acc0, pending, cstate),
                            (jax.tree.map(lambda x: x[1:], mb),
                             jnp.arange(M - 1), jnp.ones((M - 1,), f32),
                             jnp.zeros((M - 1,), f32), jnp.zeros((M - 1,), f32)),
                        )
                    loss = (l0 + jnp.sum(ls)) / M
                    metrics = jax.tree.map(
                        lambda a, bs: (a + jnp.sum(bs, axis=0)) / M, m0, ms)
                else:
                    acc, loss, metrics = acc0, l0, m0
                agg, cstate = aggregate.aggregate_buckets(
                    comm, bplan, pending, cstate, jax.random.fold_in(key, M - 1),
                    agg_axes, knobs=knobs, mask_axes=mask_axes,
                )
                acc = [a + g for a, g in zip(acc, agg)]
                cstate = dict(cstate)
            leaves, treedef = jax.tree.flatten(params)
            with comms.tag("grad_agg"):
                new_leaves = aggregate._scatter_buckets(
                    bplan, [a / M for a in acc], leaves)
            return jax.tree.unflatten(treedef, new_leaves), cstate, loss, metrics

        def _step(state, batch, lr, knobs):
            params = state["params"]
            if do_aggregate and spec.overlap == "pipelined":
                grads, cstate, loss, metrics = _pipelined_grads(state, batch, knobs)
            else:
                grads, loss, metrics = _sequential_grads(params, batch)
                cstate = state["comm"]
                if do_aggregate:
                    key = jax.random.fold_in(jax.random.key(knobs["seed"]), state["step"])
                    grads, cstate = aggregate.aggregate_gradients(
                        comm, bplan, grads, cstate, key, agg_axes, knobs=knobs,
                        mask_axes=mask_axes,
                    )
            with jax.named_scope("optimizer"):
                if clip_norm:
                    grads = global_clip(grads, knobs["clip_norm"])
                new_params, opt_state = opt.update(grads, state["opt"], params, lr)
            loss = comms.pmean(loss, ax.data)
            out = {"loss": loss, **{k: comms.pmean(v, ax.data) for k, v in metrics.items()}}
            return (
                {"params": new_params, "opt": opt_state, "comm": cstate,
                 "step": state["step"] + 1},
                out,
            )

        raw = jax.shard_map(
            _step, mesh=mesh,
            in_specs=(state_specs, batch_pspecs, P(), knob_pspecs),
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        )
        return raw, jax.jit(raw, donate_argnums=(0,))

    raw_train, train_step = make_step(do_aggregate=True)
    raw_inner, inner_step = (
        make_step(do_aggregate=False)
        if comm.sync in ("local", "post_local") else (None, None)
    )

    # ---- local SGD sync ----------------------------------------------------------
    def _sync(state, knobs):
        params = state["params"]
        if spec.churn:
            # masked runtime parameter averaging: each shard draws its
            # participation bit for this SYNC ROUND (same key discipline as
            # aggregate_buckets — the mask key folds out of the per-worker
            # step key, so dropout 0 reproduces the unmasked round).  Dead
            # shards freeze; live shards adopt the live-set average; under
            # pull_avg a rejoiner adopts but is excluded as a donor (its
            # stale params never drag the average), and its compressor
            # state resets.
            cstate = dict(state["comm"])
            slot = "pod_alive_prev" if comm.pod_local else "alive_prev"
            prev = cstate[slot].reshape(())
            if comm.pod_local:
                # participation unit = the POD (every shard of a pod must
                # agree on the pod's alive bit or within-pod consistency
                # breaks).  The pod's bit DERIVES from the per-shard bits
                # the within-pod aggregation rounds drew (alive_prev): a pod
                # syncs iff any of its shards was live — the two liveness
                # granularities stay coherent by construction instead of
                # drawing independent fates.  One scalar psum on ICI.
                shard_bit = cstate["alive_prev"].reshape(())
                alive = jnp.where(comms.psum(shard_bit, agg_axes) > 0,
                                  1.0, 0.0)
            else:
                # participation unit = the data shard (sync_axes == ax.data)
                alive, in_window, mkey = aggregate.draw_alive(
                    jax.random.fold_in(jax.random.key(knobs["seed"]),
                                       state["step"]),
                    state["step"], knobs, sync_axes)
            rejoined = aggregate.rejoin(cstate, alive, slot)
            donor = (alive * prev if spec.rejoin_policy == "pull_avg"
                     else None)
            # gradient integrity on the sync wire: local/post_local cells
            # put their payload on the wire HERE (inner steps never
            # aggregate), so the corruption axis rides the parameter-
            # averaging payload — injected sender-side on a wire COPY (the
            # shard's own params stay clean; the fault is in transit), with
            # receiver-side finiteness/range validation folding into the
            # donor mask.  pod_local cells corrupt at the per-step
            # within-pod aggregation instead (aggregate_buckets), so the
            # DCN sync stays clean — one injection point per wire payload.
            payload = valid = None
            if corruption_kind != "none" and not comm.pod_local:
                cflag = integrity.corruption_flag(
                    mkey, knobs["corruption"], in_window & (alive > 0))
                payload = jax.tree.map(
                    lambda p: integrity.corrupt_dense(
                        corruption_kind, p.astype(f32), cflag),
                    params)
                vloc = jnp.ones((), f32)
                for leaf in jax.tree.leaves(payload):
                    vloc = vloc * integrity.dense_valid(leaf)
                # every shard of the participation unit must agree on
                # validity (a unit's payload spans the model axis): any
                # invalid slice anywhere invalidates the whole payload —
                # one scalar psum, the validation round on the wire
                unit_axes = tuple(a for a in all_axes if a not in sync_axes)
                if unit_axes:
                    bad = comms.psum(1.0 - vloc, unit_axes)
                else:
                    bad = 1.0 - vloc
                valid = jnp.where(bad > 0, 0.0, 1.0)
                base = donor if donor is not None else alive
                donor = base * valid
            params = sync.average_params(params, sync_axes,
                                         impl=comm.collective,
                                         alive=alive, donor=donor,
                                         payload=payload)
            aggregate.reset_comp_state(cstate, rejoined)
            if valid is not None:
                # bounded quarantine: the corrupted payload was discarded
                # (this shard adopted the clean live-set average — its own
                # params were never corrupted, the wire copy was), but
                # consecutive corrupted rounds escalate to the rejoin
                # protocol's compressor-state reset leg
                aggregate.escalate(cstate, alive, valid,
                                   knobs["quarantine_limit"])
            return {**state, "params": params, "comm": cstate}
        params = sync.average_params(params, sync_axes, impl=comm.collective)
        return {**state, "params": params}

    raw_sync = sync_step = None
    if comm.sync in ("local", "post_local") or comm.pod_local:
        raw_sync = jax.shard_map(_sync, mesh=mesh, in_specs=(state_specs, knob_pspecs),
                             out_specs=state_specs, check_vma=False)
        sync_step = jax.jit(raw_sync, donate_argnums=(0,))

    # ---- gossip step ----------------------------------------------------------
    raw_gossip = gossip_step = None
    if comm.aggregator == "gossip":
        compressor = get_compressor(comm.compressor, **comm.compressor_kwargs)

        def _gstep(state, batch, lr, knobs):
            params = state["params"]

            def loss_fn(p):
                with jax.named_scope("forward"):
                    loss, m = T.forward_loss(cfg, p, batch, ax)
                return loss, m

            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            grads = _fix_model_grads(grads, param_specs, ax.model)
            # grads are per-worker over the data axes (decentralized);
            # local SGD update then neighbor mixing (D-PSGD [51] / CHOCO [164])
            with jax.named_scope("optimizer"):
                new_params, opt_state = opt.update(grads, state["opt"], params, lr)
            leaves, treedef = jax.tree.flatten(new_params)
            bufs = aggregate._gather_buckets(bplan, leaves)
            cstate = dict(state["comm"])
            # churn: each shard draws its participation bit for this mixing
            # round (same key discipline as aggregate_buckets); a dead shard
            # drops out of the exchange, neighbors renormalize onto self
            key = jax.random.fold_in(jax.random.key(knobs["seed"]), state["step"])
            alive = rejoined = None
            if spec.churn:
                alive, _, _ = aggregate.draw_alive(key, state["step"], knobs,
                                                   ax.data)
                rejoined = aggregate.rejoin(cstate, alive)
            with comms.tag("gossip_mix"):
                if comm.gossip_compress == "choco" and compressor is not None:
                    st = gossip.ChocoState(list(cstate["choco_xhat"]), list(cstate["choco_nbr"]))
                    # churn: mirror snap + exact-delta resync (both rejoin
                    # policies — the mirror-drift invariant is mandatory)
                    bufs, st = gossip.choco_mix(
                        comm, compressor, key, bufs, st, ax.data,
                        w=knobs["gossip_w"], gamma=knobs["gossip_gamma"],
                        comp_knobs=knobs["comp"], alive=alive,
                        rejoined=rejoined,
                    )
                    cstate["choco_xhat"], cstate["choco_nbr"] = st.x_hat, st.x_hat_nbr
                else:
                    bufs = gossip.dpsgd_mix(
                        bufs, ax.data, w=knobs["gossip_w"], alive=alive,
                        rejoined=(rejoined
                                  if spec.rejoin_policy == "pull_avg" else None))
            new_leaves = aggregate._scatter_buckets(bplan, bufs, leaves)
            new_params = jax.tree.unflatten(treedef, new_leaves)
            cstate["step"] = cstate["step"] + 1
            out = {"loss": comms.pmean(loss, ax.data),
                   **{k: comms.pmean(v, ax.data) for k, v in metrics.items()}}
            return ({"params": new_params, "opt": opt_state, "comm": cstate,
                     "step": state["step"] + 1}, out)

        raw_gossip = jax.shard_map(
            _gstep, mesh=mesh,
            in_specs=(state_specs, batch_pspecs, P(), knob_pspecs),
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        )
        gossip_step = jax.jit(raw_gossip, donate_argnums=(0,))

    # ---- eval -----------------------------------------------------------------
    def _eval(state, batch):
        loss, _ = T.forward_loss(cfg, state["params"], batch, ax)
        return comms.pmean(loss, ax.data)

    eval_step = jax.jit(
        jax.shard_map(_eval, mesh=mesh, in_specs=(state_specs, batch_pspecs),
                      out_specs=P(), check_vma=False)
    )

    state_abstract = jax.eval_shape(init_state, param_abs)

    # ---- build-time wire accounting -------------------------------------------
    # Trace each (un-jitted) step program once, abstractly, under a private
    # capture: the per-call bytes-by-tag become a bundle artifact, so cached
    # reuse keeps exact accounting without re-tracing.  Wire bytes are
    # payload-shape quantities — identical for every cell of the class, so
    # a warm process loads the artifact from the executable cache instead of
    # paying the abstract traces again.
    lr_abs = jax.ShapeDtypeStruct((), f32)
    wire = _load_wire(exec_dir)
    if wire is None:
        wire = {}

        def _trace_wire(name, fn, *args):
            if fn is None:
                return
            with comms.capture() as wlog:
                # trace through a FRESH wrapper object: eval_shape on `fn`
                # itself would seed jax's shared trace cache for it, and the
                # jitted step's first real call would then skip tracing —
                # silencing any capture() an outer caller (dry-run, tests)
                # holds open around that call
                jax.eval_shape(lambda *a: fn(*a), *args)
            wire[name] = wlog.by_tag()
            # per-encoding breakdown rides along under "<name>_formats" so
            # wire columns can show WHAT the bytes were (f32 vs int8 vs
            # packed1/2); the dense churn_resync rejoin channel stays out of
            # it — it is a separate figure (trainer_wire_resync_per_step),
            # not payload
            wire[name + "_formats"] = wlog.by_wire_format(
                exclude_tags=("churn_resync",))

        _trace_wire("train", raw_train, state_abstract, batch_abs, lr_abs, knobs0)
        _trace_wire("inner", raw_inner, state_abstract, batch_abs, lr_abs, knobs0)
        _trace_wire("sync", raw_sync, state_abstract, knobs0)
        _trace_wire("gossip", raw_gossip, state_abstract, batch_abs, lr_abs, knobs0)
        _save_wire(exec_dir, wire)

    # ---- persistent executables ------------------------------------------------
    # Wrap each step program so its first call resolves against
    # <exec_dir>/<name>.pkl: a warm process deserializes the serialized XLA
    # executable (no tracing at all), a cold one AOT-compiles from these
    # avals and serializes it.  The lowering avals carry the REAL call-time
    # shardings (state from init_state's out_specs, batch from the
    # trainer's device_put) so the executable accepts the live arguments.
    if exec_dir is not None:
        def _sds(abs_tree, spec_tree):
            sh = jax.tree.map(lambda p: NamedSharding(mesh, p), spec_tree,
                              is_leaf=lambda l: isinstance(l, P))
            return jax.tree.map(
                lambda a, h: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=h),
                abs_tree, sh)

        state_sds = _sds(state_abstract, state_specs)
        batch_sds = _sds(batch_abs, batch_pspecs)
        knob_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), knobs0)
        step_avals = (state_sds, batch_sds, lr_abs, knob_sds)

        def _persist(name, fn, avals):
            if fn is None:
                return None
            return _PersistentStep(fn, avals, os.path.join(exec_dir, name + ".pkl"))

        train_step = _persist("train", train_step, step_avals)
        inner_step = _persist("inner", inner_step, step_avals)
        sync_step = _persist("sync", sync_step, (state_sds, knob_sds))
        gossip_step = _persist("gossip", gossip_step, step_avals)

    return _CompiledBundle(
        ax=ax, param_abstract=param_abs, param_specs=param_specs,
        state_specs=state_specs, state_abstract=state_abstract,
        batch_specs=batch_abs, batch_pspecs=batch_pspecs,
        init_state=init_state,
        train_step_k=train_step, inner_step_k=inner_step,
        sync_step_k=sync_step, gossip_step_k=gossip_step,
        eval_step=eval_step, wire=wire,
    )


# ---------------------------------------------------------------------------
# Serving steps.
# ---------------------------------------------------------------------------


@dataclass
class ServeBundle:
    cfg: ModelConfig
    mesh: Any
    ax: AxisCtx
    param_abstract: Any
    param_specs: Any
    cache_abstract: Any
    cache_pspecs: Any
    batch_specs: Any
    batch_pspecs: Any
    token_pspec: Any
    prefill_step: Callable
    serve_step: Callable


def build_serve(cfg: ModelConfig, mesh, shape: InputShape) -> ServeBundle:
    ax = SP.make_axis_ctx(mesh)
    msize = mesh.shape["model"]
    param_abs, param_specs, _ = T.abstract_params(cfg, msize)
    batch_abs, batch_pspecs = SP.train_inputs(cfg, shape, mesh)
    cache_abs, cache_pspecs = SP.serve_cache_specs(cfg, mesh, shape)
    baxes, saxes = SP.batch_sharding_plan(mesh, shape)
    tok_pspec = P(baxes, None)

    def _prefill(params, batch):
        last, cache = T.prefill(cfg, params, batch, ax)
        return last, cache

    prefill_step = jax.jit(
        jax.shard_map(_prefill, mesh=mesh, in_specs=(param_specs, batch_pspecs),
                      out_specs=(P(baxes), cache_pspecs), check_vma=False)
    )

    def _serve(params, cache, tok):
        return T.decode_step(
            cfg, params, cache, tok, ax, seq_axes=saxes, max_seq=shape.seq_len
        )

    serve_step = jax.jit(
        jax.shard_map(_serve, mesh=mesh,
                      in_specs=(param_specs, cache_pspecs, tok_pspec),
                      out_specs=(tok_pspec, cache_pspecs), check_vma=False),
        donate_argnums=(1,),
    )
    return ServeBundle(
        cfg=cfg, mesh=mesh, ax=ax, param_abstract=param_abs, param_specs=param_specs,
        cache_abstract=cache_abs, cache_pspecs=cache_pspecs,
        batch_specs=batch_abs, batch_pspecs=batch_pspecs, token_pspec=tok_pspec,
        prefill_step=prefill_step, serve_step=serve_step,
    )
