"""Synchronization schemes (paper §III) — runtime side.

BSP aggregates gradients every step (``aggregate.aggregate_gradients``).
Local SGD [73] runs H local steps then averages *parameters*; post-local SGD
[121] switches from BSP to Local SGD at a given step.  On the multi-pod mesh
the ``pod`` axis can be designated the Local-SGD boundary (synchronous
inside a pod, periodic across pods) — the practical TPU realization of the
survey's loose-synchronization methods (DESIGN.md §2).

SSP/ASP cannot exist inside one SPMD program; they are modeled faithfully in
``repro.core.simulate`` and compared in the benchmarks.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


from repro.core import collectives, comms
from repro.core.types import CommConfig


def grads_need_aggregation(comm: CommConfig, step: int) -> bool:
    """Python-level decision: does this step's train_step aggregate grads?"""
    if comm.pod_local:
        return True  # BSP inside each pod every step
    if comm.sync == "bsp":
        return True
    if comm.sync == "post_local":
        return step < comm.post_local_switch or _is_sync_step(step, comm.local_steps)
    if comm.sync == "local":
        return False  # local SGD averages parameters, not gradients
    raise ValueError(comm.sync)


def params_need_sync(comm: CommConfig, step: int) -> bool:
    if comm.pod_local:
        return _is_sync_step(step, comm.local_steps)  # DCN boundary sync
    if comm.sync == "local":
        return _is_sync_step(step, comm.local_steps)
    if comm.sync == "post_local":
        return step >= comm.post_local_switch and _is_sync_step(step, comm.local_steps)
    return False


def _is_sync_step(step: int, H: int) -> bool:
    return H > 0 and (step + 1) % H == 0


def average_params(params: Any, axes: tuple[str, ...], impl: str = "xla",
                   alive: Any = None, donor: Any = None,
                   payload: Any = None) -> Any:
    """Model averaging for Local SGD (Eq. 9, sync branch).

    ``alive=None`` is the churn-free path (bitwise unchanged).  With churn,
    ``alive`` is this shard's traced participation bit for the sync round:
    the average is taken over the live set only, dead shards keep their
    parameters frozen, and live shards (including rejoiners) adopt the
    live-set average.  ``donor`` optionally narrows whose parameters feed
    the average — the ``pull_avg`` rejoin policy passes
    ``donor = alive * alive_prev`` so a rejoiner with stale parameters
    pulls the average without polluting it.  When nobody qualifies as a
    donor the round degrades to a freeze (everyone keeps their params).

    ``payload`` (gradient-integrity axis) is the wire COPY of ``params``
    that actually travels — possibly fault-injected.  Excluded payloads are
    SELECTED out (``jnp.where`` on the donor bit), never multiplied by 0:
    a NaN payload times zero would still poison the psum.  Adoption and the
    freeze fallback always use the clean local ``params``.
    """
    n = 1
    for axn in axes:
        n *= jax.lax.axis_size(axn)
    with comms.tag("local_sgd_sync"):
        if alive is None:
            return jax.tree.map(
                lambda p: (collectives.allreduce(p.astype(jnp.float32), axes, impl=impl) / n).astype(p.dtype),
                params,
            )
        w = alive if donor is None else donor
        n_don = comms.psum(w, axes)
        n_eff = jnp.maximum(n_don, 1.0)
        adopt = (alive > 0) & (n_don > 0)

        if payload is not None:
            def _avg_wire(p, wp):
                wire = jnp.where(w > 0, wp.astype(jnp.float32), 0.0)
                s = collectives.allreduce(wire, axes, impl=impl)
                return jnp.where(adopt, (s / n_eff).astype(p.dtype), p)

            return jax.tree.map(_avg_wire, params, payload)

        def _avg(p):
            s = collectives.allreduce((p.astype(jnp.float32) * w), axes, impl=impl)
            return jnp.where(adopt, (s / n_eff).astype(p.dtype), p)

        return jax.tree.map(_avg, params)
