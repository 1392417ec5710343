"""The program's names in a trace (``bench/program_trace.py``): the metadata
decoder, the scope predicates, and the readers of the phase, stage, input
stall and wire-byte metrics, on traces recorded on a TPU v5e chip with the
scoped program: the tiny Qwen-like cell of ``bench/tests/data`` on one chip
(int8 QSGD on the compressed wire, so the exchange's kernels run though
nothing crosses; two steps in the traced window) and the
``glm4-9b.dense-bsp.c1`` cell at its real size (eight steps); and on the two
traces recorded before the program had scopes, where every one of those
readers reads nothing."""

from __future__ import annotations

import gzip
import os

import pytest

from bench import cell as cells
from bench import program_trace, trace
from bench.reference.model import n_params
from bench.tests.helpers import DATA, REPO

SCOPED = os.path.join(DATA, "v5e_tiny_qsgd_c1_scoped.xplane.pb.gz")
GLM_C1 = os.path.join(DATA, "v5e_glm4_9b_c1_scoped.xplane.pb.gz")
UNSCOPED = [os.path.join(DATA, f"v5e_tiny_qsgd_{c}.xplane.pb.gz") for c in ("c1", "c4")]
NEW = ("forward_ms", "backward_ms", "remat_ms", "optimizer_ms", "grad_agg_ms",
       "encode_ms", "decode_ms", "input_stall_ms", "wire_mb")


def _root(recorded: str, tmp_path_factory) -> tuple[str, str]:
    """A checkout-like directory holding the recorded trace where a traced
    run leaves its own; returns (root, path of the trace)."""
    root = tmp_path_factory.mktemp("root")
    path = root / "bench_out" / "trace" / "cell" / "plugins" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    with open(recorded, "rb") as f:
        path.write_bytes(gzip.decompress(f.read()))
    return str(root), str(path)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    root, path = _root(SCOPED, tmp_path_factory)
    return root, trace.load(path), program_trace.load(path)


def _read(names, tr, root, **run):
    run = {"root": root, "chips": len(tr.devices), "steps": 2, "workers": len(tr.devices),
           **run}
    return {m: cells.load_module("metrics", m, REPO).read(tr, run) for m in names}


def test_scope_predicates():
    def p(s):
        return tuple(s.split("/"))

    fwd = p("jit(_step)/shard_map/jvp(forward)/while/body/closed_call/dot_general:")
    bwd = p("jit(_step)/shard_map/transpose(jvp(forward))/while/body/mul:")
    rem = p("jit(_step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
            "rematted_computation/reduce_sum:")
    # an unscanned stack recomputes its forward inside the backward
    rem2 = p("jit(_step)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
             "rematted_computation/mul:")
    enc = p("jit(_step)/shard_map/grad_agg/encode/jit(qsgd_quantize)/pallas_call:")
    dec = p("jit(_step)/grad_agg/decode/jit(int8_weighted_sum)/pallas_call:")
    opt = p("jit(_step)/optimizer/add:")
    old = p("jit(_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
            "rematted_computation/mul:")
    assert [program_trace.forward(x) for x in (fwd, bwd, rem, rem2, old)] == [
        True, False, False, False, False]
    assert [program_trace.backward(x) for x in (fwd, bwd, rem, rem2, old)] == [
        False, True, True, True, False]
    assert [program_trace.remat(x) for x in (bwd, rem, rem2, old)] == [False, True, True, False]
    assert program_trace.encode(enc) and not program_trace.decode(enc)
    assert program_trace.decode(dec) and program_trace.grad_agg(dec)
    assert program_trace.optimizer(opt) and not program_trace.grad_agg(opt)
    # a stage outside the exchange is no stage of it
    assert not program_trace.encode(p("jit(_step)/encode/add:"))


@pytest.mark.parametrize("recorded", UNSCOPED, ids=["c1", "c4"])
def test_unscoped_traces_read_nothing(recorded, tmp_path_factory):
    """A program without scopes or trainer spans (the parent of the scoped
    one) gives each new reader nothing to read, and none raises."""
    root, path = _root(recorded, tmp_path_factory)
    tr = trace.load(path)
    assert _read(NEW, tr, root) == {m: None for m in NEW}


def test_decoder_names_every_device_op(scoped):
    """Every operation event of every chip finds its metadata by its full
    name, and the kernels sit where the program scopes them."""
    root, tr, pt = scoped
    path = [p for p in (os.path.join(dp, f) for dp, _, fs in os.walk(root) for f in fs)][0]
    with open(path, "rb") as f:
        paths = program_trace._scope_paths(f.read())
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        names = {e.name for line in plane.lines if line.name == trace.OPS_LINE
                 for e in line.events}
        assert names and names <= set(paths[plane.name])
    assert sorted(pt.devices) == sorted(tr.devices) == [0]
    kernels = {"%qsgd_quantize.": ("encode", "jit(qsgd_quantize)"),
               "%int8_weighted_sum.": ("decode", "jit(int8_weighted_sum)")}
    for plane, table in paths.items():
        for name, p in table.items():
            for prefix, scopes in kernels.items():
                if name.startswith(prefix):
                    assert p is not None and all(s in p for s in scopes), (name, p)
                    assert "grad_agg" in p


def test_scoped_trace_metrics(scoped):
    root, tr, pt = scoped
    read = _read(NEW, tr, root)
    assert read.pop("wire_mb") == 0.0  # one chip: nothing crosses
    assert all(v is not None and v > 0 for v in read.values()), read
    busy_ms = trace.busy_s(tr) / 2 * 1e3  # per step and chip
    phases = sum(read[m] for m in ("forward_ms", "backward_ms", "optimizer_ms", "grad_agg_ms"))
    assert phases <= busy_ms
    assert read["encode_ms"] + read["decode_ms"] <= read["grad_agg_ms"]
    assert read["remat_ms"] <= read["backward_ms"]
    idle_ms = (tr.window_s - trace.busy_s(tr)) / 2 * 1e3
    assert read["input_stall_ms"] <= idle_ms
    steps = [s for s in pt.spans if s.name == "trainer.step"]
    assert [(s.stats["step"], s.stats["program"]) for s in steps] == [(3, "train"), (4, "train")]
    for s in steps:
        inside = [c.name for c in pt.spans if s.start <= c.start and c.end <= s.end]
        assert sorted(inside) == ["trainer.batch", "trainer.put", "trainer.step"]


def test_rooflines_still_find_the_kernels(scoped):
    """The kernels' custom-calls keep the names the roofline readers match."""
    _, tr, _ = scoped
    import json

    with open(os.path.join(DATA, "tiny-qwen.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    read = _read(("roofline.qsgd_quantize", "roofline.int8_weighted_sum"), tr, REPO,
                 peak=peak, grad_elements=n_params(cfg))
    assert all(v is not None and v > 0 for v in read.values()), read


def test_real_size_phases_cover_the_busy_time(tmp_path_factory):
    """At the real size the phases account for the step: forward, backward
    and optimizer cover at least 90% of the chip's busy time and never more
    than all of it.  At one chip XLA folds the dense exchange's bucket
    packing into the optimizer, so nothing is left under ``grad_agg``."""
    root, path = _root(GLM_C1, tmp_path_factory)
    tr = trace.load(path)
    read = _read(NEW, tr, root, steps=8)
    assert read["grad_agg_ms"] is None and read["encode_ms"] is None
    assert read["wire_mb"] == 0.0  # one chip puts nothing on the wire
    busy_ms = trace.busy_s(tr) / 8 * 1e3
    phases = read["forward_ms"] + read["backward_ms"] + read["optimizer_ms"]
    assert 0.9 * busy_ms <= phases <= busy_ms
    assert 0 < read["remat_ms"] <= read["backward_ms"]
    idle_ms = (tr.window_s - trace.busy_s(tr)) / 8 * 1e3
    assert 0 < read["input_stall_ms"] <= idle_ms
    assert read["forward_ms"] == pytest.approx(88.750373, rel=1e-6)
    assert read["backward_ms"] == pytest.approx(351.779423375, rel=1e-6)
    assert read["remat_ms"] == pytest.approx(110.455842375, rel=1e-6)
    assert read["optimizer_ms"] == pytest.approx(19.153403875, rel=1e-6)
