"""Hand counts of the model FLOPs per token and the kernels' logical work."""

from __future__ import annotations

import json
import os

import pytest

from bench import cell as cells
from bench.reference.model import flops_per_token, matmul_params
from bench.tests.helpers import REPO


def config(name: str) -> dict:
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, seq, matmul, per_token", [
    # GLM-4-9B cut to 4 layers and a 18944-row vocab slice:
    # layer = wq 4096*32*128 + wk,wv 2*4096*2*128 + wo 32*128*4096
    #       + MLP 3*4096*13696 = 203,948,032; head 18944*4096 = 77,594,624
    ("glm4-9b", 4096, 4 * 203_948_032 + 77_594_624,
     6 * (4 * 203_948_032 + 77_594_624) + 12 * 4 * 32 * 128 * 4096),
    # Qwen3-0.6B: layer = 2*1024*16*128 + 2*1024*8*128 + 3*1024*3072
    #           = 15,728,640; head 151936*1024 = 155,582,464
    ("qwen3-0.6b", 2048, 28 * 15_728_640 + 155_582_464,
     6 * (28 * 15_728_640 + 155_582_464) + 12 * 28 * 16 * 128 * 2048),
])
def test_flops_per_token(name, seq, matmul, per_token):
    cfg = config(name)
    assert matmul_params(cfg) == matmul
    assert flops_per_token(cfg, seq) == per_token


@pytest.mark.parametrize("name, total", [
    # matrices + head as above, plus the norms and GLM's q/k/v biases:
    # 4*(2*4096 + 36*128) + 4096; Qwen3: 28*(2*1024 + 2*128) + 1024
    ("glm4-9b", 893_386_752 + 55_296),
    ("qwen3-0.6b", 595_984_384 + 65_536),
])
def test_parameter_count(name, total):
    from bench.reference.model import n_params

    assert n_params(config(name)) == total


@pytest.mark.parametrize("cell, per_token, total", [
    # the numbers pinned above, read through the run from each committed
    # cell's reference model
    ("glm4-9b.dense-bsp.c1", 6 * (4 * 203_948_032 + 77_594_624) + 12 * 4 * 32 * 128 * 4096,
     893_386_752 + 55_296),
    ("qwen3-0.6b.dense-bsp.c1", 6 * (28 * 15_728_640 + 155_582_464) + 12 * 28 * 16 * 128 * 2048,
     595_984_384 + 65_536),
    ("qwen3-0.6b.qsgd-int8-cwire.c4",
     6 * (28 * 15_728_640 + 155_582_464) + 12 * 28 * 16 * 128 * 2048, 595_984_384 + 65_536),
])
def test_cell_work(cell, per_token, total):
    from bench import run

    c = cells.resolve(cell, REPO)
    assert c.reference_name == "model"
    assert run.work(c) == {"flops_per_token": per_token, "grad_elements": total}


@pytest.mark.parametrize("kernel, elements, workers, flops, nbytes", [
    # f32 in, int8 code out; about six element operations
    ("qsgd_quantize", 1000, 4, 6000.0, 5000.0),
    # W int8 codes in, one f32 out; a multiply and an add per worker
    ("int8_weighted_sum", 1000, 4, 8000.0, 8000.0),
    ("int8_weighted_sum", 10, 2, 40.0, 60.0),
])
def test_kernel_work(kernel, elements, workers, flops, nbytes):
    k = cells.load_module("kernels", kernel, REPO)
    assert k.work(elements, workers) == (flops, nbytes)
