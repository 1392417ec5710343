"""End-to-end system tests: the full taxonomy trains a small LM on a 4x2
mesh (subprocess, 8 fake devices); a small dry-run (lower+compile+roofline)
runs on the same mesh for a train, prefill and decode shape."""

import pytest

from tests.helpers import run_subprocess_devices

TRAIN_SCRIPT = r"""
import numpy as np, jax
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.types import CommConfig
from repro.launch.mesh import make_test_mesh
from repro.optim.optimizers import momentum_sgd
from repro.optim.schedules import constant
from repro.train.steps import build_bundle
from repro.train.trainer import Trainer
from repro.data.pipeline import BigramSource

cfg = get_config("qwen3-0.6b").reduced().with_updates(
    vocab=64, n_layers=2, d_ff=128, d_model=128, head_dim=32)
shape = InputShape("t", 32, 8, "train")
mesh = make_test_mesh(data=4, model=2)

class Src:
    def __init__(s, vocab): s.b = BigramSource(vocab, seed=3)
    def batch(s, step): return s.b.batch(step, shape.global_batch, shape.seq_len)

def run(comm, opt=None, lr=0.3, steps=20):
    bundle = build_bundle(cfg, mesh, comm, opt or momentum_sgd(), shape)
    tr = Trainer(bundle, Src(cfg.vocab), constant(lr), log_every=4)
    state = tr.fit(tr.init(), steps)
    first, last = tr.history[0]["loss"], tr.history[-1]["loss"]
    assert np.isfinite(last) and last < first, (comm, first, last)
    print(f"ok {first:.3f}->{last:.3f}")

run(CommConfig())
run(CommConfig(collective="ring"))
run(CommConfig(compressor="topk", compressor_kwargs={"ratio": 0.05},
               error_feedback=True, momentum_correction=0.9),
    opt=momentum_sgd(0.0), lr=0.05)
run(CommConfig(compressor="qsgd", compressor_kwargs={"levels": 16}))
run(CommConfig(compressor="signsgd"), opt=momentum_sgd(0.0), lr=0.02)
run(CommConfig(sync="local", local_steps=4), opt=momentum_sgd(0.0), lr=0.1)
run(CommConfig(aggregator="gossip"))
run(CommConfig(aggregator="gossip", gossip_compress="choco",
               compressor="topk", compressor_kwargs={"ratio": 0.1}))
print("SYSTEM-TRAIN OK")
"""

DRYRUN_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import comms
from repro.core.types import CommConfig
from repro.launch.mesh import make_test_mesh
from repro.launch import roofline as RL
from repro.optim.optimizers import adamw
from repro.train.steps import build_bundle, build_serve

mesh = make_test_mesh(data=4, model=2)
cfg = get_config("gemma3-12b").reduced()
for shape in (InputShape("t", 64, 8, "train"), InputShape("p", 64, 8, "prefill"),
              InputShape("d", 64, 8, "decode")):
    with comms.capture() as log:
        if shape.kind == "train":
            b = build_bundle(cfg, mesh, CommConfig(compressor="topk",
                 compressor_kwargs={"ratio": 0.01}, error_feedback=True), adamw(), shape)
            low = b.train_step.lower(b.state_abstract, b.batch_specs,
                                     jax.ShapeDtypeStruct((), jnp.float32))
        elif shape.kind == "prefill":
            sb = build_serve(cfg, mesh, shape)
            low = sb.prefill_step.lower(sb.param_abstract, sb.batch_specs)
        else:
            sb = build_serve(cfg, mesh, shape)
            low = sb.serve_step.lower(sb.param_abstract, sb.cache_abstract,
                                      jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32))
    compiled = low.compile()
    rl = RL.extract(cfg.name, shape.name, "4x2", compiled, log,
                    device_kind=RL.TARGET_KIND)
    assert rl.flops > 0 and rl.hbm_bytes > 0
    assert compiled.memory_analysis() is not None
    hlo_bytes, kinds = RL.hlo_collective_bytes(compiled.as_text())
    print(shape.kind, "flops=%.2e" % rl.flops, "coll=%.1fKB" % (rl.coll_bytes/1e3),
          "hlo_coll=%.1fKB" % (hlo_bytes/1e3), "bottleneck=" + rl.bottleneck)
print("SYSTEM-DRYRUN OK")
"""


@pytest.mark.slow
def test_system_training_taxonomy():
    out = run_subprocess_devices(TRAIN_SCRIPT, n_devices=8, timeout=2400)
    assert "SYSTEM-TRAIN OK" in out


@pytest.mark.slow
def test_system_dryrun_and_roofline():
    out = run_subprocess_devices(DRYRUN_SCRIPT, n_devices=8, timeout=1200)
    assert "SYSTEM-DRYRUN OK" in out
