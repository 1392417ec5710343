"""The program names its own phases for a profiler.

Device side: ``jax.named_scope`` puts ``forward`` (and, through AD, its
backward ``transpose(jvp(forward))`` with the remat recompute inside it),
``optimizer``, ``grad_agg`` and the exchange's ``encode`` / ``decode`` stages
into every operation's ``op_name``, which a device trace carries as
``tf_op``.  Host side: ``Trainer.fit`` opens a ``trainer.step`` span per
step, with ``trainer.batch`` and ``trainer.put`` children, whose stats say
which programs ran and how many wire bytes they move."""

import json
import re

import pytest

from tests.helpers import run_subprocess_devices


def _tiny_qsgd_bundle(mesh):
    from repro.experiments.trainer_substrate import make_tiny_workload
    from repro.launch.specs import COMM_PRESETS
    from repro.optim.optimizers import momentum_sgd
    from repro.train.steps import build_bundle

    cfg, shape, data = make_tiny_workload(batch=4, seq=16)
    comm = COMM_PRESETS["qsgd_int8"].with_updates(wire_format="compressed",
                                                  bucket_mb=0.25)
    return build_bundle(cfg, mesh, comm, momentum_sgd(0.9), shape), data


def test_train_step_op_names_carry_the_phase_scopes():
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_test_mesh

    bundle, _ = _tiny_qsgd_bundle(make_test_mesh(1, 1))
    assert bundle.cfg.remat == "full"
    lowered = bundle.train_step.lower(bundle.state_abstract, bundle.batch_specs,
                                      jax.ShapeDtypeStruct((), jnp.float32))
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    paths = [n.split("/") for n in names]

    def some(pred):
        return any(pred(p) for p in paths)

    forward = some(lambda p: "jvp(forward)" in p
                   and not any(c.startswith("transpose(") for c in p))
    backward = some(lambda p: "transpose(jvp(forward))" in p)
    remat = some(lambda p: "transpose(jvp(forward))" in p
                 and "rematted_computation" in p)
    assert forward and backward and remat
    assert some(lambda p: "optimizer" in p)
    assert some(lambda p: "grad_agg" in p)
    for stage in ("encode", "decode"):
        assert some(lambda p: "grad_agg" in p and stage in p[p.index("grad_agg"):]), stage
    # the kernels sit inside their stages, under their wrappers' names
    assert some(lambda p: "encode" in p and "jit(qsgd_quantize)" in p)
    assert some(lambda p: "decode" in p and "jit(int8_weighted_sum)" in p)


# run with PRESET and EXTRA (CommConfig updates) defined before it
TRACE_SCRIPT = r"""
import glob, json, tempfile
import jax
from jax.profiler import ProfileData
from repro.experiments.trainer_substrate import make_tiny_workload
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import COMM_PRESETS
from repro.optim.optimizers import momentum_sgd
from repro.optim.schedules import constant
from repro.train.steps import build_bundle
from repro.train.trainer import Trainer

cfg, shape, data = make_tiny_workload(batch=4, seq=16)
comm = COMM_PRESETS[PRESET].with_updates(bucket_mb=0.25, **EXTRA)
bundle = build_bundle(cfg, make_test_mesh(2, 1), comm, momentum_sgd(0.9), shape)
trainer = Trainer(bundle, data, constant(0.05), log_every=0)
state = trainer.fit(trainer.init(0), 2)  # compiles outside the trace
jax.block_until_ready(state)
out = tempfile.mkdtemp()
jax.profiler.start_trace(out)
state = trainer.fit(state, 2, start_step=2)
jax.block_until_ready(state)
jax.profiler.stop_trace()
(path,) = glob.glob(out + "/**/*.xplane.pb", recursive=True)
spans = [{"name": e.name, "start": e.start_ns, "end": e.end_ns, "stats": dict(e.stats)}
         for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events if e.name.startswith("trainer.")]
print(json.dumps({"wire": bundle.wire, "spans": spans}))
"""


@pytest.mark.parametrize("preset, extra, programs", [
    ("qsgd_int8", {"wire_format": "compressed"}, ["train", "train"]),
    ("local_sgd", {"local_steps": 2}, ["inner", "inner+sync"]),
])
def test_fit_leaves_step_spans_with_their_programs_and_wire_bytes(preset, extra, programs):
    script = f"PRESET, EXTRA = {preset!r}, {extra!r}\n" + TRACE_SCRIPT
    out = run_subprocess_devices(script, n_devices=2, timeout=600)
    got = json.loads(out.strip().splitlines()[-1])
    wire, spans = got["wire"], got["spans"]
    steps = sorted((s for s in spans if s["name"] == "trainer.step"),
                   key=lambda s: s["start"])
    assert [s["stats"]["step"] for s in steps] == [2, 3]
    assert [s["stats"]["program"] for s in steps] == programs
    for s in steps:
        expect = sum(sum(wire[p].values()) for p in s["stats"]["program"].split("+"))
        assert expect > 0 and s["stats"]["wire_bytes"] == expect
        for child in ("trainer.batch", "trainer.put"):
            inside = [c for c in spans if c["name"] == child
                      and s["start"] <= c["start"] and c["end"] <= s["end"]]
            assert len(inside) == 1, (child, s)
