"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-0.6b --reduced --steps 200 \
        --comm topk_ef --opt momentum --lr 0.1 \
        --data 4 --model 2 [--pod 2] [--microbatch 4] [--zero1] \
        [--ckpt-dir ckpts --ckpt-every 100]

On CPU development hosts pass --fake-devices N to simulate the mesh.
Comm presets come from repro.launch.specs.COMM_PRESETS; any preset can be
further tweaked with --local-steps / --bucket-mb / --pod-local /
--wire-format compressed / --overlap pipelined [--overlap-staleness 0|1]
(§VII microbatch-pipelined bucketized aggregation).  Compiled programs are
cached in $JAX_COMPILATION_CACHE_DIR, else in the checkout's .jax_cache.
Importing this module touches neither jax nor XLA_FLAGS.
"""

import argparse
import os
import statistics
import sys


def train(argv=None) -> dict:
    """Build the bundle for the command line ``argv``, train, and return
    ``{"history", "bundle", "state", "first_step_s", "median_step_s"}``.
    Every step is logged (and so synchronized) when ``--steps`` < 40; step
    times are the differences of those logged wall clocks, and the first
    one includes the compile."""
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true", help="reduced smoke-scale variant")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--global-batch", type=int, default=16)
    p.add_argument("--comm", default="dense_bsp")
    p.add_argument("--opt", default="momentum", choices=("sgd", "momentum", "adamw"))
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--data", type=int, default=1)
    p.add_argument("--model", type=int, default=1)
    p.add_argument("--pod", type=int, default=0)
    p.add_argument("--microbatch", type=int, default=1)
    p.add_argument("--zero1", action="store_true")
    p.add_argument("--pod-local", action="store_true")
    p.add_argument("--local-steps", type=int, default=0)
    p.add_argument("--bucket-mb", type=float, default=-1.0)
    p.add_argument("--wire-format", default="", choices=("", "dense", "compressed"),
                   help="compressed: the wire carries the packed/int8 payload "
                        "and a fused kernel decodes and reduces it")
    p.add_argument("--overlap", default="", choices=("", "sequential", "pipelined"),
                   help="§VII schedule: pipelined issues each microbatch's "
                        "bucket all-reduces inside the accumulation scan")
    p.add_argument("--overlap-staleness", type=int, default=1, choices=(0, 1))
    p.add_argument("--clip-norm", type=float, default=0.0)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--restore", default="")
    p.add_argument("--fake-devices", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        )

    import jax

    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.core import compilecache
    from repro.data.pipeline import BigramSource
    from repro.launch.mesh import make_test_mesh
    from repro.launch.specs import COMM_PRESETS
    from repro.optim.optimizers import adamw, momentum_sgd, sgd, zero1
    from repro.optim.schedules import warmup_cosine
    from repro.train.steps import build_bundle
    from repro.train.trainer import Trainer

    compilecache.configure()  # after XLA_FLAGS are settled

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    comm = COMM_PRESETS[args.comm]
    upd = {}
    if args.pod_local:
        upd["pod_local"] = True
    if args.local_steps:
        upd["local_steps"] = args.local_steps
    if args.bucket_mb >= 0:
        upd["bucket_mb"] = args.bucket_mb
    if args.wire_format:
        upd["wire_format"] = args.wire_format
    if args.overlap:
        upd["overlap"] = args.overlap
        upd["overlap_staleness"] = args.overlap_staleness
    if upd:
        comm = comm.with_updates(**upd)

    mesh = make_test_mesh(data=args.data, model=args.model, pod=args.pod)
    shape = InputShape("train", args.seq_len, args.global_batch, "train")
    opt = {"sgd": sgd, "momentum": momentum_sgd, "adamw": adamw}[args.opt]()
    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    if args.zero1:
        opt = zero1(opt, daxes)

    bundle = build_bundle(cfg, mesh, comm, opt, shape,
                          clip_norm=args.clip_norm, microbatch=args.microbatch,
                          seed=args.seed)
    src = BigramSource(cfg.vocab, seed=args.seed)

    class Data:
        def batch(self, step):
            return src.batch(step, shape.global_batch, shape.seq_len)

    trainer = Trainer(bundle, Data(), warmup_cosine(args.lr, args.warmup, args.steps),
                      ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every,
                      log_every=max(1, args.steps // 20))
    start = 0
    state = trainer.init(args.seed)
    if args.restore:
        from repro.checkpoint import restore

        state, start = restore(args.restore, state,
                               bundle.shardings(bundle.state_specs))
        print(f"restored step {start} from {args.restore}")
    state = jax.block_until_ready(trainer.fit(state, args.steps, start_step=start))
    walls = [row["wall"] for row in trainer.history]
    steps = [b - a for a, b in zip(walls, walls[1:])]
    return {
        "history": trainer.history, "bundle": bundle, "state": state,
        "first_step_s": walls[0] if walls else float("nan"),
        "median_step_s": statistics.median(steps) if steps else float("nan"),
    }


def main(argv=None) -> int:
    run = train(argv)
    for row in run["history"]:
        print(f"step {row['step']:5d} loss {row['loss']:.4f} "
              f"ce {row['ce']:.4f} wall {row['wall']:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
