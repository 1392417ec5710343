"""The ``attention_ms`` reader (``bench/metrics/attention_ms.py``): its scope
predicate on the paths AD and remat give the attention core; nothing to read
on the traces recorded before the program opened that scope; and on a
``glm4-9b.dense-bsp.c1`` trace recorded on a TPU v5e with the fused kernel
(8 steps), the kernel's events in every phase."""

from __future__ import annotations

import gzip
import os

import pytest

from bench import cell as cells
from bench import program_trace, trace
from bench.tests.helpers import DATA, REPO

FLASH = "v5e_glm4_9b_c1_flash"
RECORDED = ["v5e_glm4_9b_c1_scoped", "v5e_tiny_qsgd_c1_scoped", "v5e_tiny_qsgd_c1",
            "v5e_tiny_qsgd_c4"]


def _reader():
    return cells.load_module("metrics", "attention_ms", REPO)


def _p(s: str) -> tuple[str, ...]:
    return tuple(s.split("/"))


@pytest.mark.parametrize("path, inside", [
    ("jit(_step)/shard_map/jvp(forward)/while/body/closed_call/checkpoint/attention/"
     "jit(flash_attention)/flash_attention_fwd", True),
    ("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/attention/"
     "jit(flash_attention)/flash_attention_dq", True),
    ("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/jit(flash_attention)/flash_attention_fwd", True),
    ("jit(_step)/transpose(jvp(forward))/while/body/transpose(jvp(attention))/dot_general:", True),
    ("jit(_step)/forward/attention/while/body/dot_general:", True),
    ("jit(_step)/shard_map/jvp(forward)/while/body/closed_call/checkpoint/dot_general:", False),
    ("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
     "rematted_computation/mul:", False),
    ("jit(_step)/optimizer/add:", False),
    ("jit(_step)/grad_agg/encode/jit(qsgd_quantize)/pallas_call:", False),
    ("jit(_step)/forward/kv_allgather/all-gather:", False),
    ("jit(_step)/forward/attention_seqpar/mul:", False),
    ("jit(_step)/forward/jit(flash_attention)/add:", False),
])
def test_predicate(path, inside):
    assert _reader().attention(_p(path)) is inside


def _load(recorded: str, root) -> tuple[str, trace.Trace]:
    """The recorded trace unpacked where a traced run in ``root`` leaves its own."""
    path = root / "bench_out" / "trace" / "cell" / "plugins" / f"{recorded}.xplane.pb"
    path.parent.mkdir(parents=True)
    with open(os.path.join(DATA, f"{recorded}.xplane.pb.gz"), "rb") as f:
        path.write_bytes(gzip.decompress(f.read()))
    return str(path), trace.load(str(path))


@pytest.mark.parametrize("recorded", RECORDED)
def test_recorded_traces_read_nothing(recorded, tmp_path):
    """The program of these chip traces had no ``attention`` scope (the
    parent of the fused kernel): the reader reads None and raises nothing."""
    _, tr = _load(recorded, tmp_path)
    run = {"root": str(tmp_path), "chips": len(tr.devices), "steps": 2,
           "workers": len(tr.devices)}
    assert _reader().read(tr, run) is None


def test_fused_kernel_trace(tmp_path):
    """With the kernel, ``attention_ms`` reads the kernels and the little
    around them, and each kernel sits in the phase it belongs to."""
    path, tr = _load(FLASH, tmp_path)
    run = {"root": str(tmp_path), "chips": 1, "steps": 8, "workers": 1}
    ms = _reader().read(tr, run)
    assert ms == pytest.approx(26.7952615, rel=1e-6)
    phases = {"flash_attention_fwd": set(), "flash_attention_dq": set(),
              "flash_attention_dkv": set()}
    kernel_ms = 0.0
    for o in program_trace.load(path).ops(0):
        for name in phases.keys() & set(o.path):
            assert _reader().attention(o.path)
            phases[name].add("remat" if program_trace.remat(o.path) else
                             "backward" if program_trace.backward(o.path) else
                             "forward" if program_trace.forward(o.path) else "none")
            kernel_ms += (o.end - o.start) * 1e-6 / 8
    assert phases == {"flash_attention_fwd": {"forward", "remat"},
                      "flash_attention_dq": {"backward"},
                      "flash_attention_dkv": {"backward"}}
    assert 0.9 * ms <= kernel_ms <= ms
