"""Multi-pod dry-run: .lower().compile() every (arch x shape) on the
production mesh; print memory_analysis / cost_analysis; extract roofline
terms (see repro.launch.roofline) and write JSON records.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape train_4k [--multi-pod] [--comm topk_ef] [--out experiments/]

Shape kinds: train_4k -> train_step; prefill_32k -> prefill_step;
decode_32k / long_500k -> serve_step (1 new token, seq_len KV cache).

The programs compile for the host's CPU devices (``main`` forces 512 of
them before jax starts); the roofline terms are priced for the named target
chip, ``roofline.TARGET_KIND``, not for the CPU that compiled them.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.configs.base import INPUT_SHAPES
from repro.core import comms
from repro.core.types import CommConfig
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import COMM_PRESETS
from repro.optim.optimizers import adamw
from repro.train.steps import build_bundle, build_serve

def _lower_step(cfg, mesh, shape, comm_name: str):
    if shape.kind == "train":
        comm = COMM_PRESETS[comm_name]
        # cache=False: the dry-run derives its collective accounting from
        # tracing under the enclosing comms.capture(); a registry-served
        # bundle would reuse jax's trace cache and leave the log empty
        bundle = build_bundle(cfg, mesh, comm, adamw(), shape, cache=False)
        return bundle.train_step.lower(
            bundle.state_abstract, bundle.batch_specs, jax.ShapeDtypeStruct((), jnp.float32)
        ), 2.0  # AD twin collectives for TP (DESIGN/comms docs)
    if shape.kind == "prefill":
        sb = build_serve(cfg, mesh, shape)
        return sb.prefill_step.lower(sb.param_abstract, sb.batch_specs), 1.0
    sb = build_serve(cfg, mesh, shape)
    tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return sb.serve_step.lower(sb.param_abstract, sb.cache_abstract, tok_abs), 1.0


def dry_run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
                comm_name: str = "dense_bsp", swa_override: int = 0,
                unrolled_costs: bool = True, cfg_overrides: dict | None = None) -> dict:
    """Dual lowering:
      * scan-over-layers program -> memory_analysis (true live footprint),
        collective capture (loop-aware), HLO cross-check;
      * unrolled program -> cost_analysis (XLA counts while bodies ONCE, so
        per-step FLOPs/bytes need the unrolled module).
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if swa_override:
        cfg = cfg.with_updates(swa_override=swa_override)
    if cfg_overrides:
        cfg = cfg.with_updates(**cfg_overrides)

    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "comm": comm_name,
        "multi_pod": multi_pod, "swa_override": swa_override,
    }
    t0 = time.perf_counter()
    with comms.capture() as log:
        lowered, backward_factor = _lower_step(cfg, mesh, shape, comm_name)
    record["lower_s"] = round(time.perf_counter() - t0, 2)

    t1 = time.perf_counter()
    compiled = lowered.compile()
    record["compile_s"] = round(time.perf_counter() - t1, 2)

    mem = compiled.memory_analysis()
    record["memory_analysis"] = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                   "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    print("memory_analysis:", record["memory_analysis"])
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    record["cost_analysis_scanned"] = {
        k: float(v) for k, v in ca.items()
        if isinstance(v, (int, float)) and not k.startswith("utilization")
    }

    cost_compiled = compiled
    if unrolled_costs and cfg.scan_layers:
        t2 = time.perf_counter()
        lowered_u, _ = _lower_step(cfg.with_updates(scan_layers=False), mesh, shape, comm_name)
        cost_compiled = lowered_u.compile()
        record["unroll_compile_s"] = round(time.perf_counter() - t2, 2)
        cau = cost_compiled.cost_analysis()
        if isinstance(cau, list):
            cau = cau[0]
        record["cost_analysis"] = {
            k: float(v) for k, v in cau.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")
        }
    else:
        record["cost_analysis"] = record["cost_analysis_scanned"]
    print("cost_analysis(unrolled): flops=%.3e bytes=%.3e" % (
        record["cost_analysis"].get("flops", 0),
        record["cost_analysis"].get("bytes accessed", 0)))

    rl = RL.extract(arch, shape_name, mesh_name, cost_compiled, log,
                    device_kind=RL.TARGET_KIND,
                    backward_factor=backward_factor)
    # HLO collective cross-check from the scanned module (static count)
    rl.coll_bytes_hlo, _ = RL.hlo_collective_bytes(compiled.as_text())
    record["roofline"] = rl.row()
    print(f"roofline: compute={rl.t_compute*1e3:.2f}ms memory={rl.t_memory*1e3:.2f}ms "
          f"collective={rl.t_collective*1e3:.2f}ms -> {rl.bottleneck}-bound")
    return record


def main(argv=None) -> int:
    # the production mesh needs 512 devices: force host ones before jax's
    # CPU backend starts (nothing above has touched a device yet)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all", help=f"one of {ARCHS} or 'all'")
    p.add_argument("--shape", default="all", help=f"one of {tuple(INPUT_SHAPES)} or 'all'")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--comm", default="dense_bsp", choices=sorted(COMM_PRESETS))
    p.add_argument("--swa-override", type=int, default=0,
                   help="force global layers to this sliding window (long_500k variant)")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)

    archs = ARCHS if args.arch == "all" else (args.arch,)
    shapes = tuple(INPUT_SHAPES) if args.shape == "all" else (args.shape,)
    records, failures = [], []
    for arch in archs:
        for shape in shapes:
            # documented skip: enc-dec speech model has no 500k-token decode
            if arch == "seamless-m4t-large-v2" and shape == "long_500k":
                print(f"SKIP {arch} x {shape} (DESIGN.md: no 500k decode for enc-dec speech)")
                continue
            swa = args.swa_override
            if shape == "long_500k" and not swa:
                cfg = get_config(arch)
                subquadratic = cfg.family in ("ssm", "hybrid") or "local" in cfg.attn_pattern
                if not subquadratic:
                    swa = 4096  # documented SWA-variant (DESIGN.md §3)
            tag = f"{arch} x {shape} {'multi-pod' if args.multi_pod else 'single-pod'} [{args.comm}]"
            print(f"=== {tag} ===", flush=True)
            try:
                rec = dry_run_one(arch, shape, multi_pod=args.multi_pod,
                                  comm_name=args.comm, swa_override=swa)
                records.append(rec)
            except Exception as e:  # noqa: BLE001 — report and continue the sweep
                import traceback

                traceback.print_exc()
                failures.append((tag, repr(e)))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        suffix = "multipod" if args.multi_pod else "singlepod"
        fn = os.path.join(args.out, f"dryrun_{args.arch}_{args.shape}_{suffix}_{args.comm}.json")
        with open(fn, "w") as f:
            json.dump(records, f, indent=2, default=str)
        print("wrote", fn)
    if failures:
        print("FAILURES:")
        for tag, err in failures:
            print(" ", tag, err)
        return 1
    print(f"OK: {len(records)} dry-runs passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
