"""Plain float32 reference of a training cell's steps, for any model: the
gradient exchange between the workers and the momentum update, around the
loss of the configuration's reference model (``bench/reference/<name>.py``,
passed in as a module).

It imports nothing of the program under test:

* weights: the benchmark's own, from the seed (``bench/weights.py``), in the
  model's ``param_layout``: the same values the harness hands the program;
* each worker's loss and gradient on its share of the batch's rows, from the
  model's ``loss_fn``;
* the exchange: the mean of the workers' gradients, either exact (dense
  wire) or as the QSGD codes say (stochastic rounding of |g|/||g|| * levels
  per bucket of ``bucket_mb``, with the uniforms drawn from
  ``fold_in(fold_in(fold_in(key(comm_seed), step), worker), bucket)``, and
  the mean of the decoded codes);
* the optimizer: heavy-ball momentum, parameters rounded back to
  ``param_dtype`` after each update.

Every matrix product of the model goes through the ``einsum`` this file
hands its ``loss_fn``: float32 ``HIGHEST`` precision, or with
``precision="fp8"`` operands taken through float8_e4m3 with a per-tensor
scale, forward and backward: the control, one precision step below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools
import time
from types import ModuleType
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

f32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ------------------------------------------------------------------ weights


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(n, int) for n in x)


def leaf_paths(tree: Any) -> list[str]:
    """'a/b/c' names of a tree's leaves, in flatten order."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree, is_leaf=_is_shape)[0]:
        out.append("/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path))
    return out


def init_params(model: ModuleType, cfg: dict, seed: int) -> dict:
    """The cell's initial weights from ``seed`` (``bench/weights.py``) in the
    model's layout, held as float32; every value is exact in ``param_dtype``."""
    layout = model.param_layout(cfg)
    shapes, treedef = jax.tree.flatten(layout, is_leaf=_is_shape)
    made = weights.make(seed, dict(zip(leaf_paths(layout), shapes)),
                        jnp.dtype(cfg["param_dtype"]))
    return jax.tree.unflatten(treedef, [made[p].astype(f32) for p in leaf_paths(layout)])


# ------------------------------------------------------------------ products


def _scaled_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(f32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, _scaled_fp8(a), _scaled_fp8(b), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    return _fp8_einsum(spec, a, b), (a, b)


def _fp8_bwd(spec, res, g):
    a, b = res
    # the backward products take the same lower-precision operands
    _, vjp = jax.vjp(lambda a_, b_: jnp.einsum(spec, a_, b_, precision=HIGHEST),
                     _scaled_fp8(a), _scaled_fp8(b))
    return vjp(_scaled_fp8(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def make_einsum(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(f"unknown reference precision {precision!r}")


# ------------------------------------------------------------------ exchange


def bucket_plan(paths: list[str], sizes: list[int], bucket_mb: float) -> list[list[int]]:
    """Leaf indices per bucket: leaves in sorted-path order, packed greedily
    up to bucket_mb of float32 (a leaf larger than that is a bucket alone);
    bucket_mb 0 makes one bucket per leaf."""
    order = sorted(range(len(paths)), key=lambda i: paths[i])
    if bucket_mb <= 0:
        return [[i] for i in order]
    cap = int(bucket_mb * 1024 * 1024 / 4)
    out, cur, size = [], [], 0
    for i in order:
        if cur and size + sizes[i] > cap:
            out.append(cur)
            cur, size = [], 0
        cur.append(i)
        size += sizes[i]
    if cur:
        out.append(cur)
    return out


@jax.jit
def _qsgd_decode(x, u, levels):
    """Stochastically rounded QSGD codes of x, decoded: sign(x) * l * norm/s
    with l = floor(y) + [u < y - floor(y)], y = |x| / norm * s."""
    norm = jnp.maximum(jnp.sqrt(jnp.sum(x * x)), 1e-30)
    y = jnp.abs(x) * (1.0 / norm) * levels
    lvl = jnp.floor(y)
    lvl = lvl + (u < (y - lvl)).astype(f32)
    return jnp.sign(x) * lvl * (norm / levels)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=(0,))
def _mean(a, n):
    return jax.tree.map(lambda x: x / n, a)


def qsgd_uniforms(comm_seed: int, step: int, worker: int, bucket: int, n: int):
    key = jax.random.fold_in(jax.random.key(comm_seed), step)
    key = jax.random.fold_in(jax.random.fold_in(key, worker), bucket)
    return jax.random.uniform(key, (n,))


# ------------------------------------------------------------------ steps


class Reference:
    """Follows the program's first steps from the same seed and batches, with
    the loss of ``model`` (a reference model module, ``Cell.reference``).

    ``fault`` plants one fault in the reference, for reading what a broken
    program would give: ``half_batch`` (the second half of every batch's
    tokens carries no label, so each worker's loss is the mean over the rest)
    or ``no_exchange`` (each worker keeps its own gradient; worker 0's state
    is read)."""

    def __init__(self, cfg: dict, traffic: dict, model: ModuleType, *, workers: int,
                 precision: str = "f32", fault: str | None = None):
        self.cfg, self.traffic, self.model, self.fault = cfg, traffic, model, fault
        self.ein = make_einsum(precision)
        self.workers = workers
        comm = traffic["comm"]
        self.qsgd_levels = comm["levels"] if comm["compressor"] == "qsgd_kernel" else None
        self.bucket_mb = comm.get("bucket_mb", 0.0)
        self.lr = traffic["lr"]
        self.mom = traffic["optimizer"]["momentum"]
        self.store = jnp.dtype(cfg["param_dtype"])
        ein = self.ein
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, t, l: model.loss_fn(cfg, ein, p, t, l)))

        finfo = jnp.finfo(self.store)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def update(params, v, g, lr):
            v = jax.tree.map(lambda v_, g_: self.mom * v_ + g_, v, g)
            # round to the stored type explicitly: a convert there and back
            # is an identity XLA may drop under its excess-precision rule
            params = jax.tree.map(lambda p_, v_: jax.lax.reduce_precision(
                p_ - lr * v_, finfo.nexp, finfo.nmant), params, v)
            return params, v

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def first_update(params, g, lr):
            params = jax.tree.map(lambda p_, g_: jax.lax.reduce_precision(
                p_ - lr * g_, finfo.nexp, finfo.nmant), params, g)
            return params, g

        self._update, self._first_update = update, first_update

    def _worker_rows(self, batch: dict, w: int) -> tuple:
        rows = batch["tokens"].shape[0] // self.workers
        sl = slice(w * rows, (w + 1) * rows)
        return batch["tokens"][sl], batch["labels"][sl]

    def _wire(self, g, step: int, worker: int, comm_seed: int):
        """What worker ``worker`` puts on the wire, decoded: its gradient on
        the dense wire, or its QSGD codes times norm/levels per bucket."""
        if self.qsgd_levels is None:
            return g
        flat, treedef = jax.tree.flatten(g)
        sizes = [int(np.prod(x.shape)) for x in flat]
        out = [None] * len(flat)
        levels = jnp.asarray(self.qsgd_levels, f32)
        for b, idx in enumerate(bucket_plan(leaf_paths(g), sizes, self.bucket_mb)):
            x = jnp.concatenate([flat[i].reshape(-1) for i in idx])
            dec = _qsgd_decode(x, qsgd_uniforms(comm_seed, step, worker, b, x.size), levels)
            off = 0
            for i in idx:
                out[i] = dec[off: off + sizes[i]].reshape(flat[i].shape)
                off += sizes[i]
        return jax.tree.unflatten(treedef, out)

    def run(self, seed: int, comm_seed: int, batches: list[dict]) -> dict:
        """Steps 0..len(batches)-1 from the seed's weights.  Returns the loss
        of each step, the per-leaf norms of the first aggregated gradient and
        of the parameters' change over all the steps, and where the time went.

        Worker ``w`` computes on device ``w`` of as many as there are (each
        with its own copy of the parameters, as data-parallel workers hold
        them); the mean of the decoded payloads is taken on device 0."""
        t0 = time.perf_counter()
        devs = jax.devices()[:self.workers]
        params = init_params(self.model, self.cfg, seed)
        paths = leaf_paths(params)
        p0 = [np.asarray(x.astype(self.store)) for x in jax.tree.leaves(params)]
        v, losses, grad_norms = None, [], None
        lr = jnp.asarray(self.lr, f32)
        senders = 1 if self.fault == "no_exchange" else self.workers
        for step, batch in enumerate(batches):
            if self.fault == "half_batch":
                batch = dict(batch, labels=half_labels(batch["labels"]))
            copies = [params] + [jax.device_put(params, d) for d in devs[1:]]
            out = []
            for w in range(self.workers):
                k = w % len(devs)
                t, l = self._worker_rows(batch, w)
                with jax.default_device(devs[k]):
                    loss, g = self._grad(copies[k], jax.device_put(t, devs[k]),
                                         jax.device_put(l, devs[k]))
                    out.append((loss, self._wire(g, step, w, comm_seed) if w < senders else None))
                del g
            del copies
            acc = None
            for _, d in out[:senders]:  # the mean of the decoded payloads, in worker order
                d = jax.device_put(d, devs[0])
                acc = d if acc is None else _add(acc, d)
            losses.append(float(np.mean([float(loss) for loss, _ in out])))
            del out
            g = _mean(acc, jnp.asarray(senders, f32))
            del acc
            if v is None:
                params, v = self._first_update(params, g, lr)
                grad_norms = leaf_norms(paths, jax.tree.leaves(v))
            else:
                params, v = self._update(params, v, g, lr)
            del g
        del v
        upd = {p: float(n) for p, n in zip(paths, [
            diff_norm(x, jnp.asarray(a)) for x, a in zip(jax.tree.leaves(params), p0)])}
        return {"losses": losses, "grad_norms": grad_norms, "update_norms": upd,
                "seconds": time.perf_counter() - t0}


def half_labels(labels: np.ndarray) -> np.ndarray:
    """The batch's labels with the second half of its tokens (in row-major
    order: the second half of the rows, or of the positions of one row)
    masked out."""
    out = np.array(labels)
    flat = out.reshape(-1)
    flat[flat.size // 2:] = -1
    return out


@jax.jit
def diff_norm(a, b):
    d = a.astype(f32) - b.astype(f32)
    return jnp.sqrt(jnp.sum(d * d))


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(f32)))) for x in leaves]


def leaf_norms(paths: list[str], leaves: list) -> dict[str, float]:
    return {p: float(n) for p, n in zip(paths, _norms(leaves))}
