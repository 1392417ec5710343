"""Drive one run of the harness on the CPU with a fault planted in the timed
path underneath it, and print the run's output.

    python -m bench.tests.faults <root> <workload> <fault> <seed> [devices]

Faults (``none`` plants nothing):

* ``unchanged_state``: the train step returns the state it was given;
* ``half_batch``: the second half of every batch's tokens carries no label,
  so the loss is the mean over the rest;
* ``no_exchange``: the all-gathers of the gradient exchange return the
  worker's own payload for every worker, so nothing crosses between chips.

``devices`` host CPU devices stand in for chips.  The harness's look for a
chip is skipped (``platform="cpu"``); everything after it runs as on a chip.
"""

from __future__ import annotations

import os
import sys


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import program

    build = program.build

    if fault == "unchanged_state":
        def build_frozen(*a, **k):
            bundle, trainer = build(*a, **k)
            evaluate = bundle.eval_step

            def frozen(state, batch, lr):
                loss = evaluate(state, batch)
                return state, {"loss": loss, "ce": loss, "aux": jnp.zeros(())}

            bundle.train_step = frozen
            return bundle, trainer

        program.build = build_frozen
    elif fault == "half_batch":
        from bench.reference.training import half_labels

        batch = program.Feed.batch

        def half(self, step):
            b = batch(self, step)
            return dict(b, labels=half_labels(b["labels"]))

        program.Feed.batch = half
    elif fault == "no_exchange":
        from repro.core import comms

        def own_only(x, axes, *, axis=0, tiled=False):
            n = jax.lax.axis_size(axes)
            if tiled:
                return jnp.concatenate([x] * n, axis=axis)
            return jnp.stack([x] * n, axis=axis)

        comms.all_gather = own_only
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")


def main(argv: list[str]) -> int:
    root, workload, fault, seed = argv[:4]
    devices = int(argv[4]) if len(argv) > 4 else 1
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count={devices}")
    sys.path.insert(0, root)
    os.chdir(root)
    from bench import program, run
    from bench.tests.helpers import CPU_PEAK

    program.configure(root)  # the program importable before planting
    plant(fault)
    return run.main(["--workload", workload, "--seed", seed, "--seconds", "1",
                     "--trace", "0"], platform="cpu", peak=CPU_PEAK)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
