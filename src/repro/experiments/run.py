"""Scenario-matrix sweep CLI.

    PYTHONPATH=src python -m repro.experiments.run \
        --substrate timeline \
        --grid "sync=bsp,local,asp arch=ps,allreduce,gossip compressor=none,qsgd:levels=16" \
        --workers 16 --steps 120 --replicas 1

``--grid`` is a space-separated list of ``field=v1,v2,...`` axes (any
Scenario field). Compressor values may carry kwargs after colons:
``topk:ratio=0.05``. Invalid taxonomy cells (e.g. all-reduce x ASP) are
dropped and reported on stderr. The default grid sweeps the paper's
sync x architecture x compression matrix (16 valid cells) and prints a
Table II-style comparison of measured vs cost-model-predicted time/bytes.

``--substrate training`` batches the sweep by shape class — one compiled
program per (sync x compressor-family x EF) class, however many cells vary
the traced values (lr, staleness, H, compressor knobs, problem seed);
``--emit-json`` records the compile count next to the cells/sec.
``--substrate trainer`` runs the cells on the REAL mesh runtime with
automated device-count selection (the largest valid data-parallel mesh that
fits the available devices; cells that cannot run are skipped with the
reason on stderr) — jax is imported lazily so the lane can force host
devices first, and the sweep is grouped by trainer shape class so cells
sharing a static ``BundleSpec`` reuse ONE compiled bundle (``--emit-json``
gains the ``bundle`` build/hit record).  The overlap axis runs here too:
``--grid "... overlap=sequential,pipelined microbatch=4"`` sweeps
microbatch-pipelined vs post-hoc aggregation, and pipelined cells carry
predicted (``simulate_schedule``) and, when their sequential twin is in the
sweep, measured overlap saving.

``--substrate roofline`` emits the analytic per-cell dry-run prediction
(compute/memory/collective roofline terms); ``--emit-json PATH`` records
measured metrics, predictions, relative error, and sweep wall-clock — on the
training substrate it also benchmarks the scan engine against the
Python-loop reference (see BENCH_convergence.json at the repo root).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

# NOTE: repro.experiments.runner (and through it jax) is imported lazily
# inside main(): the trainer lane must be able to set XLA_FLAGS to force
# host devices BEFORE jax initializes.
from repro.experiments.scenario import Scenario, expand, grid

DEFAULT_GRID = "sync=bsp,local,asp arch=ps,allreduce,gossip compressor=none,qsgd:levels=16"

_FIELD_TYPES = {f.name: f.type for f in dataclasses.fields(Scenario)}


def _coerce(field: str, raw: str):
    t = _FIELD_TYPES.get(field, "str")
    if field == "compressor":
        if raw in ("none", ""):
            return None, ()
        name, _, rest = raw.partition(":")
        kwargs = []
        for part in rest.split(":") if rest else []:
            k, _, v = part.partition("=")
            kwargs.append((k, _num(v)))
        return name, tuple(kwargs)
    if raw in ("true", "True"):
        return True
    if raw in ("false", "False"):
        return False
    if "int" in str(t):
        return int(raw)
    if "float" in str(t):
        return float(raw)
    return raw


def _num(v: str):
    try:
        return int(v)
    except ValueError:
        try:
            return float(v)
        except ValueError:
            return v


def parse_grid(spec: str, **base) -> list[Scenario]:
    """``"sync=bsp,local arch=ps"`` -> raw scenario cross-product."""
    axes: dict[str, list] = {}
    comp_pairs: list[tuple] | None = None
    for part in spec.split():
        field, _, vals = part.partition("=")
        if not vals:
            raise ValueError(f"malformed grid axis {part!r} (want field=v1,v2)")
        if field == "compressor":
            comp_pairs = [_coerce("compressor", v) for v in vals.split(",")]
        else:
            axes[field] = [_coerce(field, v) for v in vals.split(",")]
    scenarios = grid(**{**{k: [v] for k, v in base.items()}, **axes})
    if comp_pairs is not None:
        # each (name, kwargs) pair is ONE axis value — the same compressor
        # may appear twice with different kwargs (e.g. qsgd:levels=4 and
        # qsgd:levels=16 are distinct cells)
        scenarios = [
            s.replace(compressor=name, compressor_kwargs=kw)
            for s in scenarios
            for name, kw in comp_pairs
        ]
    return scenarios


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.experiments.run",
        description="sweep the survey's taxonomy matrix and emit a comparison table",
    )
    p.add_argument("--grid", default=DEFAULT_GRID, help=f"axis spec (default: {DEFAULT_GRID!r})")
    p.add_argument("--substrate", default="timeline",
                   choices=("timeline", "training", "schedule", "roofline", "trainer"))
    p.add_argument("--workers", type=int, default=16)
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--replicas", type=int, default=1,
                   help="seeds per scenario (every cell vmaps them in one scan)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--straggler", type=float, default=1.0,
                   help="multiplicative slowdown of worker 0 (timeline)")
    p.add_argument("--msg-mb", type=float, default=100.0, help="dense gradient size (MB)")
    p.add_argument("--alpha", type=float, default=1e-3, help="link latency (s)")
    p.add_argument("--beta", type=float, default=1e-9, help="link s/byte")
    p.add_argument("--format", default="table", choices=("table", "csv"))
    p.add_argument("--out", default="", help="write the table here as well as stdout")
    p.add_argument("--emit-json", default="", metavar="PATH",
                   help="write a perf-tracking JSON record: per-cell measured "
                        "metrics, cost-model predictions, relative error, sweep "
                        "wall-clock, and (training substrate) the scan-engine "
                        "vs Python-loop-reference speedup")
    p.add_argument("--no-speedup", action="store_true",
                   help="skip the engine-vs-reference speedup benchmark in "
                        "--emit-json (it runs the 300-step reference loop, "
                        "~10s+ — too heavy for smoke checks)")
    p.add_argument("--calibration", default="", metavar="PATH",
                   help="machine-fitted cost-model profile (core.calibrate) "
                        "for the predicted columns; empty = auto-adopt "
                        "calibration.json of the compile cache directory "
                        "when present; 'none' = "
                        "force the uncalibrated datasheet constants")
    args = p.parse_args(argv)

    base = dict(
        n_workers=args.workers,
        steps=args.steps,
        seed=args.seed,
        lr=args.lr,
        straggler_slowdown=args.straggler,
        msg_bytes=args.msg_mb * 1e6,
        alpha=args.alpha,
        beta=args.beta,
    )
    raw = parse_grid(args.grid, **base)
    scenarios = expand(raw, substrate=args.substrate)
    dropped = [s for s in raw if s not in scenarios]
    for s in dropped:
        print(f"# dropped invalid cell {s.tag()}: {'; '.join(s.violations(args.substrate))}",
              file=sys.stderr)
    if not scenarios:
        print("no valid scenarios in the grid", file=sys.stderr)
        return 1
    print(f"# sweeping {len(scenarios)} scenarios on the {args.substrate} substrate "
          f"({len(dropped)} invalid cells dropped)", file=sys.stderr)

    if args.substrate == "trainer":
        return _trainer_sweep(args, scenarios)

    from repro.experiments.runner import (
        measure_engine_speedup,
        run_scenarios,
        training_shape_key,
    )
    from repro.core.simulate import engine_cache_stats
    from repro.experiments.tables import format_csv, format_table

    _configure_cache_and_calibration(args)  # jax is imported by now
    st0 = dataclasses.replace(engine_cache_stats())
    t0 = time.perf_counter()
    results = run_scenarios(scenarios, args.substrate, replicas=args.replicas)
    sweep_s = time.perf_counter() - t0
    title = (f"{args.substrate} sweep: {len(results)} cells, "
             f"n={args.workers}, steps={args.steps}")
    text = format_table(results, title=title) if args.format == "table" else format_csv(results)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.emit_json:
        record = emit_json_record(results, sweep_s)
        if args.substrate == "training":
            st1 = engine_cache_stats()
            record["engine"] = {
                "n_shape_classes": len({training_shape_key(s) for s in scenarios}),
                "compiles": st1.compiles - st0.compiles,
                "cache_hits": st1.hits - st0.hits,
                "cells_per_s": len(results) / sweep_s,
                "persistent_cache": st1.persistent_cache,
            }
            if not args.no_speedup:
                record["engine_speedup"] = measure_engine_speedup()
        with open(args.emit_json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.emit_json}", file=sys.stderr)
    return 0


def _configure_cache_and_calibration(args) -> None:
    """Open the persistent compile cache and apply ``--calibration``.
    Imports jax (through ``compilecache.configure``), so it must run only
    after the lane's XLA_FLAGS setup — i.e. after ``_ensure_host_devices``
    in the trainer lane — to preserve the set-flags-before-jax contract."""
    from repro.core import calibrate, compilecache

    compilecache.configure()
    if args.calibration == "none":
        calibrate.set_active(None)
    elif args.calibration:
        calibrate.set_active(calibrate.CalibrationProfile.load(args.calibration))
    else:
        profile = calibrate.load_default()
        if profile is not None:
            print(f"# calibration: adopted {calibrate.default_path()}",
                  file=sys.stderr)
            calibrate.set_active(profile)


def _ensure_host_devices(n: int) -> int:
    """Force ``n`` host-platform devices if (and only if) jax has not been
    imported yet; returns the device count actually available."""
    if "jax" not in sys.modules and n > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}".strip())
    import jax

    return len(jax.devices())


def _trainer_sweep(args, scenarios) -> int:
    """The ``--substrate trainer`` lane: real mesh runtime with automated
    device-count selection, routed through the shape-class-grouped
    ``run_trainer_sweep`` — cells whose CommConfig shares a static
    ``BundleSpec`` reuse ONE compiled bundle (``bundle_cache_stats`` lands
    in the ``--emit-json`` record).  Cells whose largest valid mesh cannot
    fit the available devices are skipped with the reason on stderr."""
    want = min(max(s.n_workers for s in scenarios), 8)  # bound host-dev cost
    ndev = _ensure_host_devices(want)
    _configure_cache_and_calibration(args)  # after XLA_FLAGS are settled

    from repro.experiments.tables import format_csv, format_table
    from repro.experiments.trainer_substrate import (
        run_trainer_sweep,
        select_trainer_device_count,
        trainer_shape_key,
    )
    from repro.train.steps import bundle_cache_stats

    st0 = dataclasses.replace(bundle_cache_stats())
    t0 = time.perf_counter()
    all_results, skip_reasons = run_trainer_sweep(
        scenarios, n_devices=ndev, verbose=True)
    sweep_s = time.perf_counter() - t0
    for s, why in skip_reasons:
        print(f"# skip {s.tag()}: {why}", file=sys.stderr)
    results = [r for r in all_results if r is not None]
    skipped = len(skip_reasons)
    if not results:
        print(f"# no trainer cells runnable ({skipped} skipped)", file=sys.stderr)
        return 0
    st1 = bundle_cache_stats()
    builds, hits = st1.builds - st0.builds, st1.hits - st0.hits
    ran = [r.scenario for r in results]
    n_classes = len({
        trainer_shape_key(s, data_par=select_trainer_device_count(s, ndev)[0])
        for s in ran
    })
    print(f"# bundle cache: {len(results)} cells, {builds} builds, "
          f"{hits} hits", file=sys.stderr)
    title = (f"trainer sweep: {len(results)} cells ({skipped} skipped), "
             f"{ndev} devices, steps={args.steps}, {builds} bundle builds")
    text = format_table(results, title=title) if args.format == "table" else format_csv(results)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    if args.emit_json:
        record = emit_json_record(results, sweep_s)
        record["bundle"] = {
            "n_shape_classes": n_classes,
            "builds": builds,
            "cache_hits": hits,
            "cells_per_s": len(results) / sweep_s,
            "persistent_cache": st1.persistent_cache,
        }
        with open(args.emit_json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.emit_json}", file=sys.stderr)
    return 0


def emit_json_record(results, sweep_s: float) -> dict:
    """Measured vs predicted per cell (+ relative error on shared keys) and
    the sweep wall-clock — the across-PR perf/accuracy trajectory record."""
    cells = []
    for r in results:
        rel_err = {
            k: abs(r.measured[k] - r.predicted[k]) / max(abs(r.predicted[k]), 1e-30)
            for k in r.measured
            if k in r.predicted
            and isinstance(r.measured[k], (int, float))
            and isinstance(r.predicted[k], (int, float))
        }
        cells.append({
            "tag": r.tag,
            "replicas": r.replicas,
            "measured": {k: v for k, v in r.measured.items()},
            "predicted": {k: v for k, v in r.predicted.items()},
            "rel_err": rel_err,
        })
    from repro.core import calibrate, compilecache

    return {
        "substrate": results[0].substrate if results else "",
        "n_cells": len(results),
        "sweep_wall_clock_s": sweep_s,
        # uniform across every lane: on-disk cache effectiveness at each
        # compilation layer's own key granularity, and whether the predicted
        # columns used machine-fitted constants
        "persistent_cache": {
            "engine": compilecache.record("engine"),
            "bundle": compilecache.record("bundle"),
        },
        "calibrated": calibrate.get_active() is not None,
        "cells": cells,
    }


if __name__ == "__main__":
    sys.exit(main())
