"""A configuration names its reference model, ``bench/reference/<name>.py``,
and the harness runs that file: a new model is new files plus entries, with
no edit to anything that exists."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from bench import cell as cells
from bench import run
from bench.tests.helpers import DATA
from bench.tests.test_correct import run_with

MODELS = {
    # the dense model with its logits scaled by 1.5: another model, which the
    # unchanged program does not train; its counts differ from the dense
    # file's, so a test sees where the run took them
    "tiny_alt": '''
from bench.reference import model as dense
from bench.reference.model import param_layout  # noqa: F401


def loss_fn(cfg, einsum, params, tokens, labels):
    def scaled(spec, a, b):
        out = einsum(spec, a, b)
        return 1.5 * out if spec == "bsd,vd->bsv" else out

    return dense.loss_fn(cfg, scaled, params, tokens, labels)


def n_params(cfg):
    return dense.n_params(cfg) + 1


def flops_per_token(cfg, seq_len):
    return 2.0 * dense.flops_per_token(cfg, seq_len)
''',
    # the dense model under another name
    "tiny_same": '''
from bench.reference.model import flops_per_token, loss_fn, n_params, param_layout  # noqa: F401
''',
    # a model file that lacks a function the harness needs
    "tiny_partial": '''
from bench.reference.model import loss_fn, n_params, param_layout  # noqa: F401
''',
}


def add_model(root: str, reference: str) -> str:
    """A reference model file, a configuration that names it (tiny-glm's
    sizes) and a cell of it, as a later change adds them; returns the cell."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "reference", reference + ".py"), "w") as f:
        f.write(MODELS[reference])
    config = reference.replace("_", "-")
    with open(os.path.join(DATA, "tiny-glm.json")) as f:
        cfg = dict(json.load(f), name=config, reference=reference)
    with open(os.path.join(bench, "configs", config + ".json"), "w") as f:
        json.dump(cfg, f)
    cell = config + ".dense"
    shutil.copy(os.path.join(DATA, "limits", "tiny-glm.dense.json"),
                os.path.join(bench, "limits", cell + ".json"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": config, "source": "bench/tests/data",
                            "file": f"bench/configs/{config}.json", "reduced": [],
                            "why": "a small stand-in for tests"})
    spec["workloads"].append({"name": cell, "config": config, "traffic": "tiny-dense.c1",
                              "chips": 1, "why": "a small stand-in for tests"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return cell


def test_named_model_is_resolved(tiny_root):
    c = cells.resolve(add_model(tiny_root, "tiny_alt"), tiny_root)
    assert c.reference_name == "tiny_alt"
    assert c.reference.__file__ == os.path.join(tiny_root, "bench", "reference", "tiny_alt.py")
    dense = cells.resolve("tiny-glm.dense", tiny_root)
    assert dense.reference_name == "model"
    assert run.work(c) == {
        "flops_per_token": 2.0 * dense.reference.flops_per_token(dense.config, 64),
        "grad_elements": dense.reference.n_params(dense.config) + 1}


def test_incomplete_model_is_refused(tiny_root):
    c = cells.resolve(add_model(tiny_root, "tiny_partial"), tiny_root)
    with pytest.raises(AttributeError, match="tiny_partial.py.*flops_per_token"):
        c.reference  # noqa: B018


def test_missing_model_is_refused(tiny_root):
    cell = add_model(tiny_root, "tiny_same")
    os.remove(os.path.join(tiny_root, "bench", "reference", "tiny_same.py"))
    with pytest.raises(FileNotFoundError, match="tiny_same.py"):
        cells.resolve(cell, tiny_root)


@pytest.mark.parametrize("reference, expect", [("tiny_alt", False), ("tiny_same", True)])
def test_correct_follows_the_named_model(tiny_root, reference, expect):
    """The unchanged program against another model is not correct, and
    against the dense model under another name it is: the run compares with
    the file the configuration names."""
    result = run_with(tiny_root, add_model(tiny_root, reference), "none", 3_100_000_019, 1)
    assert result["correct"] is expect, result["checks"]
