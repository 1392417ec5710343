"""Cells are found by name; a run refuses any platform but a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from bench import cell as cells
from bench.tests.helpers import REPO


def test_committed_cells_resolve():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        c = cells.resolve(w["name"], REPO)
        assert c.traffic["chips"] == c.chips
        assert set(c.limits) >= {"loss_gap", "grad_gap", "update_gap"}
        for f in cells.REFERENCE_API:
            assert callable(getattr(c.reference, f)), (c.reference_name, f)
        for m in c.metrics(trace=True):
            cells.load_module("metrics", m["name"], REPO).read  # noqa: B018


def test_cell_metric_and_kernel_added_as_new_files(tiny_root):
    """A new cell, metric and kernel are new files plus entries: nothing
    that exists is edited, and the harness finds each by its name."""
    with open(os.path.join(tiny_root, "bench", "kernels", "noop_kernel.py"), "w") as f:
        f.write("PATTERN = r'^%noop'\n"
                "def work(elements, workers):\n    return 1.0 * elements, 2.0 * elements\n")
    with open(os.path.join(tiny_root, "bench", "metrics", "roofline.noop_kernel.py"), "w") as f:
        f.write("from bench import roofline\n"
                "def read(tr, run):\n    return roofline.share(tr, run, 'noop_kernel')\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "roofline.noop_kernel", "unit": "%", "better": "higher",
                              "source": "device_trace", "layer": "kernels",
                              "moves": "tokens_per_s", "workloads": ["tiny-glm.dense"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    c = cells.resolve("tiny-glm.dense", tiny_root)
    assert c.config["name"] == "tiny-glm" and c.traffic["seq_len"] == 64
    assert "roofline.noop_kernel" in [m["name"] for m in c.metrics(trace=True)]
    assert "roofline.noop_kernel" not in [
        m["name"] for m in cells.resolve("tiny-qwen.qsgd", tiny_root).metrics(trace=True)]
    reader = cells.load_module("metrics", "roofline.noop_kernel", tiny_root)
    from bench.trace import Op, Trace

    tr = Trace(devices={0: [Op("%noop.1", 0.0, 1000.0, "custom-call")]}, window=(0.0, 2000.0))
    run = {"root": tiny_root, "steps": 1, "grad_elements": 100, "workers": 1,
           "peak": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}
    # 200 bytes at 1e11 B/s = 2 ns over 1000 ns of kernel time
    assert abs(reader.read(tr, run) - 0.2) < 1e-12


def test_refuses_a_non_tpu_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "glm4-9b.dense-bsp.c1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr and "cpu" in p.stderr
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ runs nothing."""
    import shutil

    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "glm4-9b.dense-bsp.c1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
