"""Training loop: drives the step bundle per the CommConfig's sync scheme,
feeds the data pipeline, logs metrics, checkpoints."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.core import sync as sync_rules
from repro.train.steps import StepBundle


@dataclass
class Trainer:
    bundle: StepBundle
    data: Any  # .batch(step) -> dict of np arrays (global)
    lr_fn: Callable[[int], Any]
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    log_every: int = 10
    history: list[dict] = field(default_factory=list)

    def _put(self, batch: dict[str, np.ndarray]):
        b = self.bundle
        return {
            k: jax.device_put(v, NamedSharding(b.mesh, b.batch_pspecs[k]))
            for k, v in batch.items()
        }

    def init(self, seed: int = 0):
        """Initialize the parameters straight into their shardings (no full
        copy on one device first) and build the training state."""
        b = self.bundle
        from repro.models.transformer import init_params

        msize = b.mesh.shape["model"]
        make = jax.jit(lambda key: init_params(b.cfg, key, msize),
                       out_shardings=b.shardings(b.param_specs))
        return b.init_state(make(jax.random.key(seed)))

    def restore_rejoin(self, path: str):
        """Churn-aware restore for a process re-entering a run: pull params,
        optimizer state and the step counter from the checkpoint at ``path``
        (``partial=True`` — the checkpoint's comm state is stale by
        construction) and re-initialize communication state FRESH, so the
        rejoiner's compressor state (EF residual, momentum, PowerSGD factors,
        CHOCO mirrors) starts from the same zeros a never-compressed worker
        would carry.  The bundle's churn machinery then resynchronizes it on
        its first communication round per the spec's ``rejoin_policy``.

        Returns ``(state, step)`` ready to pass to
        ``fit(state, steps, start_step=step)``.
        """
        from repro.checkpoint import restore

        b = self.bundle
        like = {
            "params": b.state_abstract["params"],
            "opt": b.state_abstract["opt"],
            "step": b.state_abstract["step"],
        }
        shardings = b.shardings({
            "params": b.state_specs["params"],
            "opt": b.state_specs["opt"],
            "step": b.state_specs["step"],
        })
        restored, step = restore(path, like, shardings, partial=True)
        state = b.init_state(restored["params"])
        state["opt"] = restored["opt"]
        state["step"] = restored["step"]
        # distinct buffer: step programs donate the state, and donating one
        # buffer through two arguments is an XLA error
        state["comm"]["step"] = jax.numpy.copy(restored["step"])
        return state, step

    def fit(self, state, steps: int, start_step: int = 0):
        b = self.bundle
        comm = b.comm
        t0 = time.perf_counter()
        for t in range(start_step, start_step + steps):
            batch = self._put(self.data.batch(t))
            lr = self.lr_fn(t)
            if comm.aggregator == "gossip":
                state, m = b.gossip_step(state, batch, lr)
            elif sync_rules.grads_need_aggregation(comm, t):
                state, m = b.train_step(state, batch, lr)
            else:
                state, m = b.inner_step(state, batch, lr)
            if comm.aggregator != "gossip" and sync_rules.params_need_sync(comm, t):
                state = b.sync_step(state)
            if self.log_every and (t % self.log_every == 0 or t == start_step + steps - 1):
                row = {k: float(v) for k, v in m.items()}
                row.update(step=t, wall=time.perf_counter() - t0)
                self.history.append(row)
            if self.ckpt_dir and self.ckpt_every and (t + 1) % self.ckpt_every == 0:
                from repro.checkpoint import save

                save(f"{self.ckpt_dir}/step{t+1}", state, step=t + 1)
        return state
