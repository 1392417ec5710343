"""The numbers that decide ``correct`` for a training cell, and their limits.

Both the program and the reference (``bench/reference``) run the cell's first
three steps from the same seed on the same batches.  Three numbers compare
them:

* ``loss_gap``: the largest relative gap of the three steps' losses;
* ``grad_gap``: over the leaves, the largest gap between the program's and
  the reference's norm of the first aggregated gradient (read from the
  momentum buffer after step one), over the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``update_gap``: the same for the norm of the parameters' change over the
  three steps.  Leaves whose reference gradient is under a thousandth of the
  median leaf's (a key bias under softmax has none but rounding) are left
  out of it: such a leaf moves by round-off alone.

Each number has a limit in ``bench/limits/<cell>.json``, set between the
largest reading of sound runs and the smallest reading of the control and of
the planted faults (PERF.md gives the readings).
"""

from __future__ import annotations

import math
import statistics

NAMES = ("loss_gap", "grad_gap", "update_gap")
TINY = 1e-3  # a leaf whose reference gradient is under this share of the median's


def leaf_gaps(prog: dict[str, float], ref: dict[str, float],
              keep: set[str] | None = None) -> dict[str, float]:
    """Per leaf: |program's norm - reference's| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - r) / max(r, med) for k, r in ref.items()
            if keep is None or k in keep}


def moved(ref: dict) -> set[str]:
    """Leaves whose reference gradient is not nought to rounding."""
    med = statistics.median(ref["grad_norms"].values())
    return {k for k, g in ref["grad_norms"].items() if g >= TINY * med}


def readings(prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses``, ``grad_norms`` and
    ``update_norms`` (per-leaf, keyed by path)."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference disagree on the parameter leaves: "
                         f"{sorted(set(prog['grad_norms']) ^ set(ref['grad_norms']))}")
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    update = leaf_gaps(prog["update_norms"], ref["update_norms"], moved(ref))
    return {"loss_gap": loss, "grad_gap": max(grad.values(), default=0.0),
            "update_gap": max(update.values(), default=0.0)}


def judge(values: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a missing or non-finite value
    is not correct."""
    checks, ok = {}, True
    for name in NAMES:
        v, lim = values.get(name), limits[name]
        good = v is not None and math.isfinite(v) and v <= lim
        ok &= good
        checks[name] = {"value": v, "limit": lim}
    return ok, checks
