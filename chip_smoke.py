"""On-chip smoke test: drive the system's main paths once on a TPU and check
what comes out.

    python chip_smoke.py                # one chip: kernels, trainer, engine
    python chip_smoke.py --four-chips   # four chips: 4-way data parallel only

One chip (the default) runs three phases in this one process:

* kernels: every Pallas kernel of the trainer and engine paths, compiled
  for the chip, against its ``repro.kernels.ref`` oracle at a 4M-element
  bucket (W=4 gathered workers for the wire kernels), to the tolerances of
  tests/test_kernels.py and tests/test_wire_formats.py;
* trainer: Qwen3-0.6B at its published widths and full depth through
  ``repro.launch.train`` (``build_bundle`` + ``Trainer``), seq 2048 x
  global batch 4, in a dense-BSP cell and a compressed-wire int8 QSGD cell
  whose step program must hold a Pallas kernel (``tpu_custom_call``);
* engine: a class-batched convergence sweep through
  ``python -m repro.experiments.run --substrate training``, which must
  compile once per shape class and end with finite losses.

``--four-chips`` trains the same model 4-way data parallel (global batch
16): dense BSP, and int8 QSGD and packed-sign SignSGD each on the dense and
the compressed wire.  The compressed wire must reproduce the dense wire's
losses (sign bitwise, int8 within rtol 1e-6), and parameters and optimizer
state must sit on all four chips.

Every result line is JSON.  The last line is ``{"ok": true, "device":
{...}}``, printed only when every phase passed; the exit code is 0 then and
1 otherwise.  There is no CPU path: on any platform but a TPU the script
exits at once, naming the platform it found.  Compiled programs are cached
in $JAX_COMPILATION_CACHE_DIR, else in the checkout's .jax_cache."""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N = 4 * 1024 * 1024  # kernel bucket, elements
W = 4  # gathered workers for the wire kernels
SEQ, BATCH_PER_CHIP, STEPS = 2048, 4, 6
ENGINE_GRID = ("sync=bsp,local compressor=qsgd:levels=4,qsgd:levels=16,"
               "qsgd_kernel:levels=16 error_feedback=true lr=0.02,0.05")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device() -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devs[0].platform!r}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            self.total += secs


# ---------------------------------------------------------------- kernels


def kernels_phase() -> list[str]:
    """Each kernel against its oracle; returns the names that disagree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.key(0), 8)
    x = jax.random.normal(ks[0], (N,)) * 0.1
    u = jax.random.uniform(ks[1], (N,))
    e = jax.random.normal(ks[2], (N,)) * 0.05
    xs = jax.random.normal(ks[3], (W, N))
    tern = jax.random.randint(ks[4], (W, N), -1, 2).astype(jnp.int8)
    codes8 = jax.random.randint(ks[5], (W, N), -127, 128).astype(jnp.int8)
    a = np.asarray
    bad = []

    def check(name, fn):
        t0 = time.perf_counter()
        try:
            fn()
            status = "ok"
        except AssertionError as err:
            status = f"mismatch: {str(err).strip()[:400]}"
            bad.append(name)
        emit({"phase": "kernel", "kernel": name, "n": N, "status": status,
              "seconds": time.perf_counter() - t0})

    def qsgd():
        codes, norm = ops.qsgd_quantize(x, u, levels=16)
        np.testing.assert_array_equal(
            a(codes), a(ref.qsgd_ref(x, u, jnp.linalg.norm(x), 16)))
        np.testing.assert_allclose(float(norm[0]), float(jnp.linalg.norm(x)),
                                   rtol=1e-6)

    def qsgd_ef():
        codes, _, enew = ops.qsgd_ef_fused(x, e, u, levels=16, decay=0.9)
        cr, er = ref.qsgd_ef_ref(x, e, u, jnp.linalg.norm(e * 0.9 + x), 16, 0.9)
        np.testing.assert_array_equal(a(codes), a(cr))
        np.testing.assert_allclose(a(enew), a(er), rtol=1e-5, atol=1e-7)

    def terngrad():
        t, _ = ops.terngrad_quantize(x, u)
        np.testing.assert_array_equal(
            a(t), a(ref.terngrad_ref(x, u, jnp.max(jnp.abs(x)))))

    def sign_pack():
        packed = ops.sign_pack(x)
        np.testing.assert_array_equal(a(packed), a(ref.sign_pack_ref(x)))
        np.testing.assert_array_equal(a(ops.sign_unpack(packed, N)),
                                      np.where(a(x) >= 0, 1.0, -1.0))

    def sign_vote():
        packed = jnp.stack([ops.sign_pack(xs[w]) for w in range(W)])
        weights = jnp.asarray([0.0] + [1.0] * (W - 1))
        signs = jnp.where(xs >= 0, 1.0, -1.0)
        np.testing.assert_array_equal(
            a(ops.sign_vote(packed, weights, n=N)),
            a(ref.sign_vote_ref(signs, weights)))

    def tern_wire():
        packed = jnp.stack([ops.tern_pack(tern[w]) for w in range(W)])
        np.testing.assert_array_equal(a(packed[0]), a(ref.tern_pack_ref(tern[0])))
        weights = jnp.asarray([0.7, 0.0, 1.3, 0.9])
        np.testing.assert_array_equal(
            a(ops.tern_acc(packed, weights, n=N)),
            a(ref.weighted_sum_ref(tern.astype(jnp.float32), weights)))

    def int8_sum():
        weights = jnp.linspace(0.01, 0.05, W)
        np.testing.assert_allclose(
            a(ops.int8_weighted_sum(codes8, weights)),
            a(ref.weighted_sum_ref(codes8.astype(jnp.float32), weights)),
            rtol=1e-6, atol=1e-5)

    for name, fn in [("qsgd", qsgd), ("qsgd_ef", qsgd_ef),
                     ("terngrad", terngrad), ("sign_pack/sign_unpack", sign_pack),
                     ("sign_vote", sign_vote), ("tern_pack/tern_acc", tern_wire),
                     ("int8_weighted_sum", int8_sum)]:
        check(name, fn)
    return bad


# ---------------------------------------------------------------- trainer


def train_cell(name: str, comm: list[str], *, lr: float, data: int,
               clock: CompileClock, need_kernel: bool = False) -> dict:
    """Train Qwen3-0.6B through the launcher; emit and return the cell's
    record (``state`` included, for the caller to inspect and drop)."""
    import jax
    import jax.numpy as jnp

    from repro.launch import train as launcher

    argv = ["--arch", "qwen3-0.6b", "--steps", str(STEPS),
            "--seq-len", str(SEQ), "--global-batch", str(BATCH_PER_CHIP * data),
            "--data", str(data), "--opt", "momentum", "--lr", str(lr),
            "--warmup", "1", *comm]
    c0 = clock.total
    run = launcher.train(argv)
    compile_s = clock.total - c0
    bundle = run["bundle"]
    losses = [row["loss"] for row in run["history"]]
    rec = {"phase": "trainer", "cell": name, "chips": data,
           "arch": bundle.cfg.name, "n_layers": bundle.cfg.n_layers,
           "d_model": bundle.cfg.d_model, "vocab": bundle.cfg.vocab,
           "seq": SEQ, "global_batch": BATCH_PER_CHIP * data,
           "losses": losses, "compile_s": compile_s,
           "first_step_s": run["first_step_s"],
           "median_step_s": run["median_step_s"],
           "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
               "peak_bytes_in_use")}
    problems = []
    if len(losses) < STEPS or not all(math.isfinite(v) for v in losses):
        problems.append("losses missing or not finite")
    if need_kernel:
        state = jax.tree.map(
            lambda a_, s: jax.ShapeDtypeStruct(a_.shape, a_.dtype, sharding=s),
            bundle.state_abstract, bundle.shardings(bundle.state_specs))
        batch = jax.tree.map(
            lambda a_, s: jax.ShapeDtypeStruct(a_.shape, a_.dtype, sharding=s),
            bundle.batch_specs, bundle.shardings(bundle.batch_pspecs))
        text = bundle.train_step.lower(
            state, batch, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
        rec["tpu_custom_calls"] = text.count("tpu_custom_call")
        if not rec["tpu_custom_calls"]:
            problems.append("compiled step holds no Pallas kernel")
    rec["status"] = "; ".join(problems) or "ok"
    emit(rec)
    rec["state"] = run["state"]
    return rec


def one_chip_trainer(clock: CompileClock) -> list[str]:
    bad = []
    for name, comm, lr, kernel in [
            ("dense_bsp", ["--comm", "dense_bsp"], 0.05, False),
            ("qsgd_int8+cwire", ["--comm", "qsgd_int8", "--wire-format",
                                 "compressed"], 0.05, True)]:
        rec = train_cell(name, comm, lr=lr, data=1, clock=clock,
                         need_kernel=kernel)
        if rec["status"] != "ok":
            bad.append(name)
        del rec
        gc.collect()
    return bad


# ---------------------------------------------------------------- engine


def engine_phase() -> list[str]:
    from repro.experiments import run as cli

    out = os.path.join(ROOT, "chiprun_out", "chip_smoke_engine.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    t0 = time.perf_counter()
    rc = cli.main(["--substrate", "training", "--grid", ENGINE_GRID,
                   "--steps", "50", "--workers", "4", "--replicas", "2",
                   "--emit-json", out, "--no-speedup"])
    with open(out) as f:
        record = json.load(f)
    eng = record["engine"]
    losses = [c["measured"]["final_loss"] for c in record["cells"]]
    ok = (rc == 0 and eng["compiles"] == eng["n_shape_classes"]
          and all(math.isfinite(v) for v in losses))
    emit({"phase": "engine", "grid": ENGINE_GRID, "cells": record["n_cells"],
          "shape_classes": eng["n_shape_classes"], "compiles": eng["compiles"],
          "cells_per_s": eng["cells_per_s"], "final_losses": losses,
          "seconds": time.perf_counter() - t0,
          "status": "ok" if ok else "compiles != shape classes, or a loss "
                                    "is not finite"})
    return [] if ok else ["engine"]


# ---------------------------------------------------------------- four chips


def four_chip_phase(clock: CompileClock) -> list[str]:
    """4-way data parallel: dense BSP, then each compressor on the dense and
    the compressed wire, held to the compressed-wire equivalence contract."""
    import jax
    import numpy as np

    devices = set(jax.devices())
    bad = []
    losses = {}
    for name, comm, lr in [
            ("dense_bsp", ["--comm", "dense_bsp"], 0.05),
            ("qsgd_int8", ["--comm", "qsgd_int8"], 0.05),
            ("qsgd_int8+cwire", ["--comm", "qsgd_int8", "--wire-format",
                                 "compressed"], 0.05),
            ("signsgd_packed", ["--comm", "signsgd_packed"], 1e-4),
            ("signsgd_packed+cwire", ["--comm", "signsgd_packed",
                                      "--wire-format", "compressed"], 1e-4)]:
        rec = train_cell(name, comm, lr=lr, data=4, clock=clock,
                         need_kernel=name.endswith("+cwire"))
        placed = all(leaf.sharding.device_set == devices
                     for part in ("params", "opt")
                     for leaf in jax.tree.leaves(rec["state"][part]))
        emit({"phase": "placement", "cell": name, "on_all_4_chips": placed})
        if rec["status"] != "ok" or not placed:
            bad.append(name)
        losses[name] = np.asarray(rec["losses"])
        del rec
        gc.collect()
    for dense, comp, exact in [("signsgd_packed", "signsgd_packed+cwire", True),
                               ("qsgd_int8", "qsgd_int8+cwire", False)]:
        try:
            if exact:
                np.testing.assert_array_equal(losses[dense], losses[comp])
            else:
                np.testing.assert_allclose(losses[dense], losses[comp], rtol=1e-6)
            status = "ok"
        except AssertionError as err:
            status = f"mismatch: {str(err).strip()[:400]}"
            bad.append(f"{dense} vs {comp}")
        emit({"phase": "equivalence", "dense_wire": dense,
              "compressed_wire": comp, "contract": "bitwise" if exact
              else "rtol 1e-6", "status": status})
    return bad


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-way data-parallel phase (4 chips)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"chip_smoke: the repro package is not in {ROOT}/src")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    dev = device()
    if args.four_chips and dev["count"] != 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 chips; JAX found "
                 f"{dev['count']}")
    emit({"phase": "device", **dev})
    clock = CompileClock()
    phases = ([("four_chips", lambda: four_chip_phase(clock))]
              if args.four_chips else
              [("kernels", kernels_phase),
               ("trainer", lambda: one_chip_trainer(clock)),
               ("engine", engine_phase)])
    failed = []
    for name, phase in phases:
        try:
            failed += [f"{name}: {what}" for what in phase()]
        except Exception:  # report the phase, run the others, fail at the end
            traceback.print_exc()
            failed.append(f"{name}: raised")
    if failed:
        print("chip_smoke failed: " + "; ".join(failed), file=sys.stderr)
        return 1
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
