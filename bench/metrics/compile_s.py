"""compile_s: seconds the run spent tracing, lowering and compiling, from
JAX's monitoring events (jaxpr trace, MLIR lowering, backend compile).  A
run that finds its programs in the compile cache reads little here."""

from __future__ import annotations


def read(tr, run):
    return run["compile_s"]
