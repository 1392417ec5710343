"""Resolve a benchmark cell from ``BENCHMARK.json`` and the files found by
name beside it.

A cell names a configuration and a traffic mix.  Everything else is a file:

* ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives),
* ``bench/traffic/<traffic>.json``,
* ``bench/limits/<cell>.json`` (the limits that decide ``correct``),
* ``bench/reference/<reference>.py`` (the configuration's reference model:
  its ``"reference"`` key, ``model`` without it),
* ``bench/metrics/<metric>.py`` (one reader per per-layer metric),
* ``bench/kernels/<kernel>.py`` (a kernel's trace names and logical work).

So a later change adds a cell, a configuration with its reference model, a
metric or a kernel as new files plus an entry, and edits nothing that is
here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# what a reference model file supplies (bench/reference/model.py says what each is)
REFERENCE_API = ("param_layout", "loss_fn", "n_params", "flops_per_token")


@dataclass
class Cell:
    root: str  # the checkout: the directory that holds BENCHMARK.json
    spec: dict  # the whole BENCHMARK.json
    workload: dict  # this cell's entry of "workloads"
    config: dict  # the configuration file
    traffic: dict  # the traffic file
    limits: dict  # the limits file

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def reference_name(self) -> str:
        return self.config.get("reference", "model")

    @functools.cached_property
    def reference(self) -> ModuleType:
        """The configuration's reference model, loaded on first use: it
        imports JAX, which has to wait for ``program.configure``."""
        mod = load_module("reference", self.reference_name, self.root)
        missing = [f for f in REFERENCE_API if not callable(getattr(mod, f, None))]
        if missing:
            raise AttributeError(f"bench/reference/{self.reference_name}.py, the reference "
                                 f"model of {self.workload['config']!r}, lacks {missing}")
        return mod

    def metrics(self, trace: bool) -> list[dict]:
        """The metrics this cell reports: end-to-end without the trace,
        per-layer with it (a metric with ``workloads`` only in those cells)."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(name: str, root: str | None = None) -> Cell:
    """The cell called ``name``; raises KeyError or FileNotFoundError when a
    part of it is missing."""
    root = root or os.path.dirname(BENCH_DIR)
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(workloads)}")
    w = workloads[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    bench = os.path.join(root, "bench")
    traffic = _load_json(os.path.join(bench, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bench, "limits", name + ".json"))
    c = Cell(root, spec, w, config, traffic, limits)
    ref = os.path.join(bench, "reference", c.reference_name + ".py")
    if not os.path.isfile(ref):
        raise FileNotFoundError(f"no reference model {ref} for {w['config']!r}")
    return c


def load_module(kind: str, name: str, root: str | None = None) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    root = root or os.path.dirname(BENCH_DIR)
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
