"""``correct`` comes out true for a sound run and false for the control and
for each fault a training cell can have, planted under the harness."""

from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench import cell as cells
from bench import correct
from bench.tests.helpers import REPO


def run_with(root: str, workload: str, fault: str, seed: int, devices: int) -> dict:
    p = subprocess.run([sys.executable, "-m", "bench.tests.faults", root, workload, fault,
                        str(seed), str(devices)],
                       cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    # every compared number stands beside its limit, last on stderr too
    tail = p.stderr.strip().splitlines()[-len(correct.NAMES):]
    assert [line.split()[1] for line in tail] == list(correct.NAMES)
    assert list(result)[-1] == "checks"
    return result


@pytest.mark.parametrize("workload, fault, devices, expect", [
    ("tiny-glm.dense", "none", 1, True),
    ("tiny-glm.dense", "unchanged_state", 1, False),
    ("tiny-glm.dense", "half_batch", 1, False),
    ("tiny-qwen.qsgd", "none", 4, True),
    ("tiny-qwen.qsgd", "half_batch", 4, False),
    ("tiny-qwen.qsgd", "no_exchange", 4, False),
])
def test_correct_decides(tiny_root, workload, fault, devices, expect):
    result = run_with(tiny_root, workload, fault, 2_500_000_017, devices)
    assert result["correct"] is expect, result["checks"]
    assert result["device"]["count"] == devices


@pytest.mark.parametrize("workload", ["tiny-glm.dense", "tiny-qwen.qsgd"])
def test_control_is_not_correct(tiny_root, workload):
    """The reference in fp8 (one step below bfloat16), put in the program's
    place, fails the cell's limits on every seed tried."""
    from bench import program
    from bench.reference.training import Reference

    c = cells.resolve(workload, tiny_root)
    t = c.traffic
    for seed in (11, 2_000_000_023, 4_294_967_311):
        seeds = program.Seeds.derive(seed)
        batches = [program.make_source(c.config, t, seeds.data).batch(
            s, t["batch_per_chip"] * t["chips"], t["seq_len"]) for s in range(program.CHECK_STEPS)]
        ref = Reference(c.config, t, c.reference, workers=c.chips).run(
            seeds.weights, seeds.comm, batches)
        ctl = Reference(c.config, t, c.reference, workers=c.chips, precision="fp8").run(
            seeds.weights, seeds.comm, batches)
        ok, checks = correct.judge(correct.readings(ctl, ref), c.limits)
        assert not ok, checks
