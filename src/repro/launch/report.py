"""Turn dry-run JSON records into the EXPERIMENTS.md roofline tables.

    PYTHONPATH=src python -m repro.launch.report experiments/*.json
"""

from __future__ import annotations

import glob
import json
import sys

from repro.configs import get_config
from repro.configs.base import INPUT_SHAPES, active_params
HBM_PER_CHIP = 16e9  # v5e


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic MODEL_FLOPS (global): 6*N_active*D train, 2*N_active*D
    prefill, 2*N_active*B decode-step."""
    cfg = get_config(arch)
    n = active_params(cfg)
    sh = INPUT_SHAPES[shape_name]
    if sh.kind == "train":
        return 6.0 * n * sh.seq_len * sh.global_batch
    if sh.kind == "prefill":
        return 2.0 * n * sh.seq_len * sh.global_batch
    return 2.0 * n * sh.global_batch  # one decode step


def chips(mesh: str) -> int:
    out = 1
    for p in mesh.split("x"):
        out *= int(p)
    return out


def load(patterns: list[str]) -> list[dict]:
    recs = []
    for pat in patterns:
        for fn in glob.glob(pat):
            with open(fn) as f:
                recs.extend(json.load(f))
    return recs


def table(recs: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | t_compute | t_memory | t_coll | bound | "
           "MODEL/HLO flops | HBM/chip | fits |")
    sep = "|" + "---|" * 10
    lines = [hdr, sep]
    for r in sorted(recs, key=lambda r: (r["mesh"], r["arch"], r["shape"])):
        rl = r["roofline"]
        n_chips = chips(r["mesh"])
        mf = model_flops(r["arch"], r["shape"])
        ratio = mf / (rl["flops"] * n_chips) if rl["flops"] else float("nan")
        mem = r.get("memory_analysis", {})
        resident = (mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
                    + mem.get("output_size_in_bytes", 0))
        fits = "Y" if resident < HBM_PER_CHIP else f"N({resident/1e9:.0f}GB)"
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {rl['t_compute']*1e3:.2f}ms | {rl['t_memory']*1e3:.2f}ms "
            f"| {rl['t_collective']*1e3:.2f}ms | {rl['bottleneck']} "
            f"| {ratio:.2f} | {resident/1e9:.1f}GB | {fits} |"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    recs = load(args or ["experiments/dryrun_*.json"])
    print(table(recs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
