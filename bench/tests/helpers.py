"""Constants and the function that builds a checkout, shared by the benchmark's tests."""

from __future__ import annotations

import json
import os
import shutil


REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "bench", "tests", "data")
TINY_CELLS = {
    # cell: (config, traffic, chips)
    "tiny-glm.dense": ("tiny-glm", "tiny-dense.c1", 1),
    "tiny-qwen.qsgd": ("tiny-qwen", "tiny-qsgd-cwire.c4", 4),
}
# stand-in peaks for runs on the CPU: no device number is read from them
CPU_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(path: str) -> str:
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(path, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell, (config, traffic, chips) in TINY_CELLS.items():
        shutil.copy(os.path.join(DATA, config + ".json"),
                    os.path.join(path, "bench", "configs", config + ".json"))
        shutil.copy(os.path.join(DATA, traffic + ".json"),
                    os.path.join(path, "bench", "traffic", traffic + ".json"))
        shutil.copy(os.path.join(DATA, "limits", cell + ".json"),
                    os.path.join(path, "bench", "limits", cell + ".json"))
        spec["configs"].append({"name": config, "source": "bench/tests/data",
                                "file": f"bench/configs/{config}.json", "reduced": [],
                                "why": "a small stand-in for tests"})
        spec["workloads"].append({"name": cell, "config": config, "traffic": traffic,
                                  "chips": chips, "why": "a small stand-in for tests"})
        for m in spec["per_layer"]:
            m["workloads"].append(cell)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return path
