"""The system under test, built the way its launcher builds it.

``build`` makes the step bundle (``repro.train.steps.build_bundle``), the
``Trainer`` and the traffic feed from a cell's files; ``first_steps`` drives
that same trainer from the seed's weights through the cell's first steps and
reads what the correctness check compares.  The program is imported only
here and only after ``configure`` has pointed JAX's compile cache at the
checkout.
"""

from __future__ import annotations

import dataclasses
import os
import sys
from dataclasses import dataclass

import numpy as np

CHECK_STEPS = 3  # the steps the reference follows


@dataclass(frozen=True)
class Seeds:
    """31-bit seeds for each consumer, derived from the run's ``--seed``
    (any whole number): the program's keys and counters are 32-bit."""

    weights: int
    data: int
    comm: int

    @classmethod
    def derive(cls, seed: int) -> "Seeds":
        state = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(3)
        w, d, c = (int(x) & 0x7FFFFFFF for x in state)
        return cls(w, d, c)


def configure(root: str) -> None:
    """Before JAX is imported: the compile cache at a fixed path inside the
    checkout, the TPU runtime's logs off, and the program importable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Feed:
    """The trainer's data source: one generator call per step, each inside a
    ``bench.gen`` host span."""

    def __init__(self, source, rows: int, seq: int):
        self.source, self.rows, self.seq = source, rows, seq

    def batch(self, step: int) -> dict:
        import jax

        with jax.profiler.TraceAnnotation("bench.gen"):
            return self.source.batch(step, self.rows, self.seq)


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    for k in ("attn_pattern", "mrope_sections"):
        if k in kw:
            kw[k] = tuple(kw[k])
    return ModelConfig(**kw)


def comm_config(traffic: dict):
    from repro.launch.specs import COMM_PRESETS

    c = traffic["comm"]
    kwargs = {"levels": c["levels"]} if "levels" in c else {}
    return COMM_PRESETS[c["preset"]].with_updates(
        compressor=c["compressor"], compressor_kwargs=kwargs,
        bucket_mb=float(c.get("bucket_mb", 0.0)), wire_format=c["wire_format"])


def make_source(cfg: dict, traffic: dict, seed: int):
    from bench.data.bigram import BigramSource

    data = traffic["data"]
    if data["generator"] != "bigram":
        raise ValueError(f"unknown traffic generator {data['generator']!r}")
    return BigramSource(cfg["vocab"], seed, successors=data["successors"],
                        temperature=data["temperature"])


def build(cfg: dict, traffic: dict, seeds: Seeds, chips: int):
    """(bundle, trainer) for one cell on the first ``chips`` devices."""
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import InputShape
    from repro.optim.optimizers import momentum_sgd
    from repro.optim.schedules import constant
    from repro.train.steps import build_bundle
    from repro.train.trainer import Trainer

    opt_spec = traffic["optimizer"]
    if opt_spec["name"] != "momentum":
        raise ValueError(f"unknown optimizer {opt_spec['name']!r}")
    devs = np.array(jax.devices()[:chips]).reshape(chips, 1)
    mesh = Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rows = traffic["batch_per_chip"] * chips
    shape = InputShape("train", traffic["seq_len"], rows, "train")
    bundle = build_bundle(model_config(cfg), mesh, comm_config(traffic),
                          momentum_sgd(opt_spec["momentum"]), shape, seed=seeds.comm)
    feed = Feed(make_source(cfg, traffic, seeds.data), rows, traffic["seq_len"])
    return bundle, Trainer(bundle, feed, constant(traffic["lr"]), log_every=0)


def leaf_paths(tree) -> list[str]:
    import jax

    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(tree) -> dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                               for x in jax.tree.leaves(t)])(tree)
    return {p: float(n) for p, n in zip(leaf_paths(tree), norms)}


def init_state(bundle, seed: int):
    """The training state from the benchmark's weights (``bench/weights.py``),
    made on the device straight into the parameters' shardings and handed to
    the bundle's ``init_state``, where ``Trainer.init`` would hand its own."""
    import jax

    from bench import weights

    abstract = bundle.param_abstract
    paths = leaf_paths(abstract)
    leaves, treedef = jax.tree.flatten(abstract)
    shard = jax.tree.leaves(bundle.shardings(bundle.param_specs))
    made = weights.make(seed, {p: x.shape for p, x in zip(paths, leaves)},
                        bundle.cfg.pdtype, out_shardings=dict(zip(paths, shard)))
    return bundle.init_state(jax.tree.unflatten(treedef, [made[p] for p in paths]))


def first_steps(trainer, state):
    """Drive the trainer through its first ``CHECK_STEPS`` steps, through
    the window's own call (``Trainer.fit``) and feed, logging each loss.
    Returns (state, readings) where the readings hold the losses, the
    per-leaf norms of the momentum buffer after step one (the first
    aggregated gradient as the optimizer got it) and host copies of the
    parameters before and after, for the norms of their change."""
    import jax

    p0 = jax.device_get(state["params"])
    trainer.log_every = 1
    state = trainer.fit(state, 1, start_step=0)
    grad_norms = _norms(state["opt"]["v"])
    state = trainer.fit(state, CHECK_STEPS - 1, start_step=1)
    p_end = jax.device_get(state["params"])
    trainer.log_every = 0
    losses = [row["loss"] for row in trainer.history[-CHECK_STEPS:]]
    return state, {"losses": losses, "grad_norms": grad_norms, "p0": p0, "p_end": p_end}


def update_norms(readings: dict) -> dict[str, float]:
    """Per-leaf norms of the parameters' change, from the host copies, one
    leaf at a time on the device."""
    import jax

    from bench.reference.training import diff_norm

    flat0 = jax.tree.leaves(readings["p0"])
    flat1 = jax.tree.leaves(readings["p_end"])
    return {path: float(diff_norm(jax.numpy.asarray(b), jax.numpy.asarray(a)))
            for path, a, b in zip(leaf_paths(readings["p0"]), flat0, flat1)}
