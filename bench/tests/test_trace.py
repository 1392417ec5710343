"""The trace reduction, on intervals made by hand and on a small trace
recorded on a TPU v5e: the tiny Qwen-like cell of ``bench/tests/data`` with
the compressed int8 wire on one chip, two steps in the traced window."""

from __future__ import annotations

import gzip
import json
import os

import pytest

from bench import cell as cells
from bench import trace
from bench.reference.model import n_params
from bench.tests.helpers import DATA, REPO
from bench.trace import Op, Trace

RECORDED = os.path.join(DATA, "v5e_tiny_qsgd_c1.xplane.pb.gz")
RECORDED_C4 = os.path.join(DATA, "v5e_tiny_qsgd_c4.xplane.pb.gz")


def test_interval_algebra():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.length([(0, 3), (5, 8)]) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert trace.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]


def test_collectives_join_async_pairs_and_exposure():
    ops = [Op("%while.1", 0, 100, "while"),  # a loop body's span, not compute
           Op("%fusion.1", 0, 40, "fusion"),
           Op("%all-gather-start.3", 30, 32, "all-gather-start"),
           Op("%all-gather-done.3", 58, 60, "all-gather-done"),
           Op("%all-reduce.2", 70, 80, "all-reduce"),
           Op("%fusion.2", 50, 75, "fusion")]
    tr = Trace(devices={0: ops}, window=(0, 100))
    assert trace.collectives(tr, 0) == [(30, 60), (70, 80)]
    # in flight 30-60 and 70-80; other work covers 30-40 and 50-75
    exposed = trace.subtract(trace.collectives(tr, 0), trace.compute(tr, 0))
    assert exposed == [(40, 50), (75, 80)]
    run = {"steps": 1}
    coll = cells.load_module("metrics", "collective_ms", REPO).read(tr, run)
    expo = cells.load_module("metrics", "collective_exposed_ms", REPO).read(tr, run)
    assert coll == pytest.approx(40e-6) and expo == pytest.approx(15e-6)


def _load(recorded: str, tmp_path_factory) -> Trace:
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with open(recorded, "rb") as f:
        path.write_bytes(gzip.decompress(f.read()))
    return trace.load(str(path))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    return _load(RECORDED, tmp_path_factory)


def test_recorded_four_chip_collectives(tmp_path_factory):
    """The same cell on four chips (2 steps): each chip's exchange shows as
    all-gathers and all-reduces, read per step and chip."""
    tr = _load(RECORDED_C4, tmp_path_factory)
    assert sorted(tr.devices) == [0, 1, 2, 3]
    for d in tr.devices:
        kinds = [o.kind for o in tr.ops(d) if trace.is_collective(o)]
        assert kinds.count("all-gather") == 8 and kinds.count("all-reduce") == 4
    run = {"steps": 2}
    coll = cells.load_module("metrics", "collective_ms", REPO).read(tr, run)
    expo = cells.load_module("metrics", "collective_exposed_ms", REPO).read(tr, run)
    per_chip = [trace.length(trace.collectives(tr, d)) for d in tr.devices]
    assert coll == pytest.approx(sum(per_chip) / 4 / 2 * 1e-6)
    assert 0 < expo <= coll


def test_recorded_trace_reduces(recorded):
    tr = recorded
    assert list(tr.devices) == [0]
    gens = [s for s in tr.spans if s.name == "bench.gen"]
    assert len(gens) == 2 and all(tr.window[0] <= s.start < s.end <= tr.window[1] for s in gens)
    assert tr.window_s == pytest.approx(0.007190929)
    assert 0 < trace.busy_s(tr) < tr.window_s
    names = [o.name for o in tr.ops(0) if o.kind == "custom-call"]
    assert sum(n.startswith("%qsgd_quantize.") for n in names) == 14
    assert sum(n.startswith("%int8_weighted_sum.") for n in names) == 14
    assert trace.collectives(tr, 0) == []  # one chip: nothing crosses
    b = trace.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1]
    assert json.loads(json.dumps(b)) == b


def test_recorded_trace_metrics(recorded):
    with open(os.path.join(DATA, "tiny-qwen.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    run = {"root": REPO, "chips": 1, "steps": 2, "tokens_per_s": 64 * 2 / recorded.window_s,
           "flops_per_token": 1e6, "peak": peak, "compile_s": 1.5,
           "grad_elements": n_params(cfg), "workers": 1}
    read = {m: cells.load_module("metrics", m, REPO).read(recorded, run)
            for m in ("gen_ms", "idle_share", "step_mfu", "collective_ms",
                      "collective_exposed_ms", "roofline.qsgd_quantize",
                      "roofline.int8_weighted_sum", "compile_s")}
    assert read["gen_ms"] == pytest.approx((0.80502 + 0.77686) / 2, rel=1e-4)
    assert 0 < read["idle_share"] < 100
    assert read["collective_ms"] is None and read["collective_exposed_ms"] is None
    # at these sizes XLA keeps the kernels' operands in on-chip memory (S(1)
    # in the trace's layouts), so a share of the HBM roofline can pass 100%
    # here; the cells run the kernels on buckets of 32 MB and more
    assert read["roofline.qsgd_quantize"] == pytest.approx(
        100 * 5 * n_params(cfg) / 819e9 / (7.852e-6 / 2), rel=1e-3)
    assert read["roofline.int8_weighted_sum"] > 0
    assert read["compile_s"] == 1.5
    assert read["step_mfu"] == pytest.approx(100 * 1e6 * run["tokens_per_s"] / 197e12)
