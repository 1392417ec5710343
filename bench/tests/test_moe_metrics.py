"""The held-experts layer's readers (``moe_ms``, ``moe_dispatch_ms``,
``roofline.expert_matmul``): their scope predicates on the paths AD and
remat give, nothing to read on the traces of programs that open no ``moe``
scope, and the logical work of the held experts' matmuls and of the
DeepSeek-V2-Lite cell."""

from __future__ import annotations

import gzip
import json
import os

import pytest

from bench import cell as cells
from bench import trace
from bench.tests.helpers import DATA, REPO

CELL = "deepseek-v2-lite.dense-bsp.c1"
RECORDED = ["v5e_glm4_9b_c1_flash", "v5e_glm4_9b_c1_scoped", "v5e_tiny_qsgd_c1_scoped",
            "v5e_tiny_qsgd_c4"]
READERS = ("moe_ms", "moe_dispatch_ms", "roofline.expert_matmul")


def _reader(name):
    return cells.load_module("metrics", name, REPO)


def _p(s: str) -> tuple[str, ...]:
    return tuple(s.split("/"))


FWD = "jit(_step)/shard_map/jvp(forward)/while/body/closed_call/checkpoint/moe"
BWD = "jit(_step)/transpose(jvp(forward))/while/body/transpose(jvp(moe))"
REMAT = ("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
         "rematted_computation/moe")


@pytest.mark.parametrize("path, moe, movement, experts", [
    (f"{FWD}/router/dot_general:", True, False, False),
    (f"{FWD}/dispatch/sort:", True, True, False),
    (f"{FWD}/experts/ragged_dot_general:", True, False, True),
    (f"{FWD}/combine/gather:", True, True, False),
    (f"{FWD}/shared/dot_general:", True, False, False),
    (f"{REMAT}/dispatch/gather:", True, True, False),
    (f"{BWD}/transpose(jvp(combine))/scatter-add:", True, True, False),
    (f"{BWD}/transpose(jvp(experts))/ragged_dot_general:", True, False, True),
    ("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/attention/"
     "jit(flash_attention)/flash_attention_dq", False, False, False),
    ("jit(_step)/forward/dispatch/sort:", False, False, False),
    ("jit(_step)/forward/experts/dot_general:", False, False, False),
    ("jit(_step)/optimizer/add:", False, False, False),
])
def test_predicates(path, moe, movement, experts):
    assert _reader("moe_ms").moe(_p(path)) is moe
    assert _reader("moe_dispatch_ms").row_movement(_p(path)) is movement
    assert _reader("roofline.expert_matmul").experts(_p(path)) is experts


@pytest.mark.parametrize("recorded", RECORDED)
def test_traces_without_the_layer_read_nothing(recorded, tmp_path):
    """Programs with no ``moe`` scope (the dense cells, and the parent of
    the held-experts layer): every reader reads None and raises nothing."""
    path = tmp_path / "bench_out" / "trace" / "cell" / "plugins" / f"{recorded}.xplane.pb"
    path.parent.mkdir(parents=True)
    with open(os.path.join(DATA, f"{recorded}.xplane.pb.gz"), "rb") as f:
        path.write_bytes(gzip.decompress(f.read()))
    tr = trace.load(str(path))
    run = {"root": str(tmp_path), "chips": len(tr.devices), "steps": 2,
           "workers": len(tr.devices), "peak": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}}
    for name in READERS:
        assert _reader(name).read(tr, run) is None, name


def _config() -> dict:
    with open(os.path.join(REPO, "bench", "configs", "deepseek-v2-lite.json")) as f:
        return json.load(f)


def test_expert_matmul_work():
    """16,384 tokens x top-6 x 8 held / 64 = 12,288 rows a layer; 3 products
    of 2 x rows x 2048 x 1408 FLOPs, 4 passes, 6 expert layers."""
    k = cells.load_module("kernels", "expert_matmul", REPO)
    rows = 16384 * 6 * 8 / 64
    assert k.rows(_config(), 16384) == rows == 12288
    flops, nbytes = k.work(_config(), 16384)
    assert flops == 6 * 4 * 3 * 2 * rows * 2048 * 1408
    per_pass = 2 * (3 * 8 * 2048 * 1408 + 2 * rows * (2048 + 1408) + rows * (1408 + 2048))
    assert nbytes == 6 * 4 * per_pass
    # compute-bound on a v5e: 197 TFLOP/s against 819 GB/s
    assert flops / 197e12 > nbytes / 819e9


def test_cell_work():
    """The cell's FLOPs per token (the held experts' expected share, 0.75
    experts a token) and gradient elements, through the run."""
    from bench import run

    c = cells.resolve(CELL, REPO)
    assert c.reference_name == "deepseek_v2"
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    moe = 2048 * 64 + 2.75 * 3 * 2048 * 1408
    matmul = 7 * attn + 3 * 2048 * 10944 + 6 * moe + 12800 * 2048
    # matrices at their held sizes (8 experts, not 0.75), the norms: ln1,
    # ln2 and kv_norm of each layer, ln_f
    elements = (7 * attn + 3 * 2048 * 10944 + 6 * (2048 * 64 + 10 * 3 * 2048 * 1408)
                + 12800 * 2048 + 7 * (2 * 2048 + 512) + 2048)
    assert run.work(c) == {"flops_per_token": 6 * matmul + 6 * 7 * 16 * 320 * 4096,
                           "grad_elements": elements}
    assert elements == 709_658_112


def test_recorded_cell_trace(tiny_root):
    """A ``--trace 1`` run of the cell on a TPU v5e (4 steps, seed
    3000002301): each reader reads what that run printed, the grouped
    matmuls sit under ``moe`` / ``experts`` in forward, remat and backward,
    and the roofline share is below 100%."""
    from bench import program_trace

    path = os.path.join(tiny_root, "bench_out", "trace", CELL, "plugins", "profile", "run",
                        "runsc.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(os.path.join(DATA, "v5e_deepseek_v2_lite_c1.xplane.pb.gz"), "rb") as f:
        data = gzip.decompress(f.read())
    with open(path, "wb") as f:
        f.write(data)
    tr = trace.load(path)
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    run = {"root": tiny_root, "chips": 1, "steps": 4, "workers": 1, "peak": peak}
    assert _reader("moe_ms").read(tr, run) == pytest.approx(406.98995675, rel=1e-9)
    assert _reader("moe_dispatch_ms").read(tr, run) == pytest.approx(237.456748, rel=1e-9)
    share = _reader("roofline.expert_matmul").read(tr, run)
    assert share == pytest.approx(26.75112346259787, rel=1e-9) and share < 100
    phases = set()
    for o in program_trace.load(path).ops(0):
        if _reader("roofline.expert_matmul").experts(o.path):
            phases.add("remat" if program_trace.remat(o.path) else
                       "backward" if program_trace.backward(o.path) else
                       "forward" if program_trace.forward(o.path) else "none")
    # (and a few index computations XLA hoists out of the step's phases)
    assert phases >= {"forward", "remat", "backward"}
