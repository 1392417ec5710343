"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process:

1. resolve the cell from BENCHMARK.json, its configuration and traffic files;
2. build the step bundle, the Trainer and its weights from the seed, as the
   launcher does (``bench/program.py``);
3. drive the first three steps from the seed (they compile or load every
   program the window runs, and are what the reference follows);
4. with ``--trace 0``, train for ``--seconds`` in chunks of steps, blocking
   only at chunk ends, and report the end-to-end metrics; with ``--trace 1``,
   trace a short window of its own with the profiler and report the
   per-layer metrics, read by ``bench/metrics/<metric>.py`` from the trace;
5. free the program's state, run the plain reference over the same first
   steps (``bench/reference/training.py`` around the configuration's
   reference model) and compare (``bench/correct.py``);
6. print the result as the last line of standard output, and each compared
   number beside its limit as the last lines of standard error.

There is no fallback: on any platform but a TPU, or with fewer chips than the
cell asks for, the run prints no result and exits with code 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import cell as cells  # noqa: E402
from bench import correct, program  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
GIB = float(1 << 30)


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling, from its
    monitoring events (as the program's on-chip smoke reads them)."""

    def __init__(self):
        from jax import monitoring

        self.total, self.events = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.total += secs
            self.events += 1


def device_info(chips: int, platform: str) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"needs a {platform.upper()}; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def peaks(kind: str) -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def run_steps(trainer, state, start: int, chunk: int, seconds: float):
    """Train in chunks of ``chunk`` steps until ``seconds`` have passed at a
    chunk end.  Returns (state, steps, elapsed, chunk_seconds)."""
    import jax

    step, chunks = start, []
    t0 = time.perf_counter()
    while True:
        c0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.chunk"):
            state = trainer.fit(state, chunk, start_step=step)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(state)
        step += chunk
        chunks.append(time.perf_counter() - c0)
        if time.perf_counter() - t0 >= seconds:
            break
    return state, step - start, time.perf_counter() - t0, chunks


def memory_peak(chips: int) -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])


def work(c: cells.Cell) -> dict:
    """The work the metrics divide by, from the configuration's reference
    model: FLOPs per trained token and the gradient's elements."""
    return {"flops_per_token": c.reference.flops_per_token(c.config, c.traffic["seq_len"]),
            "grad_elements": c.reference.n_params(c.config)}


def per_layer(c: cells.Cell, tr, run: dict) -> dict:
    out = {}
    for m in c.metrics(trace=True):
        value = cells.load_module("metrics", m["name"], c.root).read(tr, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def main(argv=None, *, platform: str = "tpu", peak: dict | None = None) -> int:
    """``platform`` and ``peak`` let the tests drive a run on the CPU; a run
    from the command line always needs a TPU and its row of peaks.json."""
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--chips", type=int, default=0,
                   help="run the cell on this many chips instead (rehearsal only)")
    p.add_argument("--keep-trace", default="", help="copy the trace directory here")
    args = p.parse_args(argv)

    c = cells.resolve(args.workload, ROOT)
    chips = args.chips or c.chips
    program.configure(c.root)
    phases = {"start": time.perf_counter() - T_START}
    try:
        import jax

        phases["import"] = time.perf_counter() - T_START
        dev = device_info(chips, platform)
        phases["backend"] = time.perf_counter() - T_START
        peak = peak or peaks(dev["kind"])
    except (NoChip, RuntimeError) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 3
    clock = CompileClock()
    seeds = program.Seeds.derive(args.seed)
    t = c.traffic
    cfg = c.config

    bundle, trainer = program.build(cfg, t, seeds, chips)
    phases["build"] = time.perf_counter() - T_START
    state = program.init_state(bundle, seeds.weights)
    phases["weights"] = time.perf_counter() - T_START
    state, prog = program.first_steps(trainer, state)
    setup_s = time.perf_counter() - T_START
    phases["first_steps"] = setup_s
    emit({"setup": {"setup_s": setup_s, "seconds_since_start": phases, "compile_s": clock.total,
                    "compile_events": clock.events, "first_losses": prog["losses"]},
          "wire_bytes_per_step": (bundle.wire or {}).get("train"), "device": dev})

    tokens_per_step = t["seq_len"] * t["batch_per_chip"] * chips
    events0 = clock.events
    start = program.CHECK_STEPS
    trace_dir = os.path.join(c.root, "bench_out", "trace", c.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            state, steps, elapsed, chunks = run_steps(
                trainer, state, start, t["trace_steps"], 0.0)
        jax.profiler.stop_trace()
    else:
        state, steps, elapsed, chunks = run_steps(
            trainer, state, start, t["chunk_steps"], args.seconds)
    window_compiles = clock.events - events0
    emit({"device": dev, "window": {"steps": steps, "seconds": elapsed, "chunk_seconds": chunks,
                     "step_seconds": [s / (t["trace_steps"] if args.trace else t["chunk_steps"])
                                      for s in chunks],
                     "compile_events": window_compiles}})
    if window_compiles:
        print(f"bench: {window_compiles} compile events inside the window", file=sys.stderr)
        return 4
    mem = memory_peak(chips)
    del state, trainer, bundle
    gc.collect()

    tokens_per_s = steps * tokens_per_step / elapsed
    device = dict(dev, memory_peak_bytes=mem)
    result: dict = {}
    if args.trace:
        from bench import trace as traces

        tr = traces.load(trace_dir)
        run = {"root": c.root, "chips": chips, "steps": steps, "tokens_per_s": tokens_per_s,
               "peak": peak, "compile_s": clock.total, "workers": chips, **work(c)}
        metrics = per_layer(c, tr, run)
        device.update(busy_s=traces.busy_s(tr), window_s=tr.window_s)
        result["breakdown"] = traces.breakdown(tr)
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"tokens_per_s": tokens_per_s, "peak_hbm_gib": mem / GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c.metrics(trace=False)}

    # the reference follows the same first steps, once the program is gone
    from bench.reference.training import Reference

    t_ref = time.perf_counter()
    batches = [program.make_source(cfg, t, seeds.data).batch(
        s, t["batch_per_chip"] * chips, t["seq_len"]) for s in range(program.CHECK_STEPS)]
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    ref = Reference(cfg, t, c.reference, workers=chips).run(seeds.weights, seeds.comm, batches)
    prog["update_norms"] = program.update_norms(prog)
    values = correct.readings(prog, ref)
    ok, checks = correct.judge(values, c.limits)
    emit({"device": dev, "reference": {"seconds": time.perf_counter() - t_ref, "bytes_in_use_before": in_use,
                        "losses": ref["losses"],
                        "program_losses": prog["losses"]}})

    line = {"correct": ok, "attempted": steps, "failed": 0 if ok else steps,
            "metrics": metrics, "device": device, **result, "checks": checks}
    emit(line)
    for name, chk in checks.items():
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
