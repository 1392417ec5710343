"""Readings that set a cell's correctness limits (``bench/limits/<cell>.json``).

    python bench/calibrate.py --workload <cell> --seeds 12 --faulty-seeds 3 \
        --out bench_out/calibrate.<cell>.json

For each of ``--seeds`` seeds, the program's first steps against the plain
reference (the lower readings: sound runs).  For the first ``--faulty-seeds``
of them, the reference's own readings against the control (the reference in
fp8, one precision below the configuration's bfloat16) and against each
fault the cell can have, planted in the reference put in the program's place:
half of the batch left out, and on more than one chip the exchange between
chips left out (the upper readings).  A state left unchanged reads 1 on the
gradient and update numbers by their definition and needs no run.

Everything runs in this one process at the cell's own size; it needs no
measured window, since training's readings come from the first steps.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import cell as cells  # noqa: E402
from bench import correct, program  # noqa: E402

FIRST_SEED = 1_000_003


def program_readings(c, seeds, chips: int) -> dict:
    """The program's first steps from ``seeds``, with the update norms."""
    bundle, trainer = program.build(c.config, c.traffic, seeds, chips)
    state = program.init_state(bundle, seeds.weights)
    state, prog = program.first_steps(trainer, state)
    del state, trainer, bundle
    gc.collect()
    prog["update_norms"] = program.update_norms(prog)
    return {k: prog[k] for k in ("losses", "grad_norms", "update_norms")}


def worst(prog: dict, ref: dict, n: int = 3) -> dict:
    """The leaves that read the largest gaps, with the norms behind them."""
    out = {}
    for key, keep in (("grad_norms", None), ("update_norms", correct.moved(ref))):
        gaps = correct.leaf_gaps(prog[key], ref[key], keep)
        top = sorted(gaps, key=gaps.get, reverse=True)[:n]
        out[key] = [[k, gaps[k], prog[key][k], ref[key][k]] for k in top]
    return out


def main(argv=None, *, platform: str = "tpu") -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--faulty-seeds", type=int, default=3)
    p.add_argument("--chips", type=int, default=0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)

    c = cells.resolve(args.workload, ROOT)
    chips = args.chips or c.chips
    program.configure(c.root)
    import jax  # noqa: F401

    from bench.reference.training import Reference
    from bench.run import NoChip, device_info

    try:
        dev = device_info(chips, platform)
    except NoChip as err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 3
    t = c.traffic
    rows = t["batch_per_chip"] * chips
    faults = ["half_batch"] + (["no_exchange"] if chips > 1 else [])
    record = {"workload": c.name, "device": dev, "runs": []}
    for i in range(args.seeds):
        seed = FIRST_SEED + 7919 * i
        seeds = program.Seeds.derive(seed)
        t0 = time.perf_counter()
        prog = program_readings(c, seeds, chips)
        batches = [program.make_source(c.config, t, seeds.data).batch(s, rows, t["seq_len"])
                   for s in range(program.CHECK_STEPS)]
        ref = Reference(c.config, t, c.reference, workers=chips).run(
            seeds.weights, seeds.comm, batches)
        run = {"seed": seed, "program": correct.readings(prog, ref),
               "losses": {"program": prog["losses"], "reference": ref["losses"]},
               "worst_leaves": worst(prog, ref)}
        if i < args.faulty_seeds:
            for name, kw in [("control", {"precision": "fp8"})] + [
                    (f, {"fault": f}) for f in faults]:
                other = Reference(c.config, t, c.reference, workers=chips, **kw).run(
                    seeds.weights, seeds.comm, batches)
                run[name] = correct.readings(other, ref)
        run["seconds"] = time.perf_counter() - t0
        record["runs"].append(run)
        print(json.dumps(run), flush=True)
    summary = {}
    for name in correct.NAMES:
        lower = max(r["program"][name] for r in record["runs"])
        upper = {k: min(r[k][name] for r in record["runs"] if k in r)
                 for k in ["control"] + faults}
        summary[name] = {"lower": lower, "upper": upper}
    record["summary"] = summary
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
