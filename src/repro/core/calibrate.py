"""Profile-calibrated cost-model constants (Shi et al. arXiv:2005.13247,
Wei et al. arXiv:2403.07585: fit the alpha-beta model from measured traces,
not datasheet numbers).

The analytic predictions in :mod:`repro.core.schedule` /
``experiments.trainer_substrate.predict_overlap_saving`` default to datasheet
constants (``Link(alpha=1e-5, beta=1/50e9)``, ``Scenario.compute_time = 1.0``)
that no machine running the sweeps has ever exhibited — which is exactly why
the predicted columns in BENCH_overlap/BENCH_trainer carried large rel-err.
This module measures the machine instead:

* **collective rounds** — timed ``pmap``-psum rounds over the available
  devices across a ladder of payload sizes, least-squares fitted to
  ``t = alpha + beta * bytes`` (the alpha-beta model the whole cost layer
  is built on);
* **launch overhead** — median warm wall-clock of a trivial jitted dispatch:
  the fixed per-message cost a host-device runtime pays on top of the wire
  terms, threaded into the new ``launch=`` term of
  :func:`repro.core.schedule.simulate_schedule`;
* **the dense step** — one measured real train step of the tiny trainer
  workload (dense BSP), the compute term for trainer-lane step-time
  predictions.

The fitted
:class:`CalibrationProfile` persists as JSON next to the persistent
compilation cache (``<cache_dir>/calibration.json``,
:mod:`repro.core.compilecache`) and threads into predictions through the
module-level ACTIVE profile: ``set_active(profile)`` makes
``predict_overlap_saving`` / ``run_trainer_scenario`` use the fitted link,
launch, and compute constants; with no active profile every prediction is
bit-identical to the uncalibrated repo.

CLI: ``python -m repro.core.calibrate [--out PATH]``.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field

from repro.core import compilecache
from repro.core.costmodel import Link

DEFAULT_PROFILE_NAME = "calibration.json"


@dataclass
class CalibrationProfile:
    """Machine-fitted cost-model constants + the measurements behind them."""

    alpha: float  # per-message latency (s), fitted intercept
    beta: float  # seconds per payload byte, fitted slope
    t_launch: float  # fixed dispatch overhead of one warm jitted call (s)
    t_step_dense: float | None  # measured dense-BSP trainer step (s); None
    #                             when fitted on a <2-device process
    meta: dict = field(default_factory=dict)

    def link(self) -> Link:
        return Link(alpha=self.alpha, beta=self.beta)

    def as_dict(self) -> dict:
        return {"alpha": self.alpha, "beta": self.beta,
                "t_launch": self.t_launch, "t_step_dense": self.t_step_dense,
                "meta": self.meta}

    @classmethod
    def from_dict(cls, d: dict) -> "CalibrationProfile":
        return cls(alpha=float(d["alpha"]), beta=float(d["beta"]),
                   t_launch=float(d["t_launch"]),
                   t_step_dense=(None if d.get("t_step_dense") is None
                                 else float(d["t_step_dense"])),
                   meta=dict(d.get("meta", {})))

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.as_dict(), f, indent=1)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path: str) -> "CalibrationProfile":
        with open(path) as f:
            return cls.from_dict(json.load(f))


# --- active-profile registry ------------------------------------------------

_ACTIVE: CalibrationProfile | None = None


def set_active(profile: CalibrationProfile | None) -> CalibrationProfile | None:
    """Install ``profile`` as the process-wide calibration (None = revert to
    the uncalibrated datasheet constants).  Returns the previous profile."""
    global _ACTIVE
    prev, _ACTIVE = _ACTIVE, profile
    return prev


def get_active() -> CalibrationProfile | None:
    return _ACTIVE


def active_link(default: Link) -> Link:
    return _ACTIVE.link() if _ACTIVE is not None else default


def active_launch(default: float = 0.0) -> float:
    return _ACTIVE.t_launch if _ACTIVE is not None else default


def default_path() -> str:
    """Where the profile persists: next to the persistent compilation cache."""
    return os.path.join(compilecache.cache_dir(), DEFAULT_PROFILE_NAME)


def load_default() -> CalibrationProfile | None:
    """The profile saved next to the configured cache dir, if any.

    A profile fitted under a different :func:`compilecache.cache_fingerprint`
    (jax version, platform, device kind/count — e.g. a lane forcing a
    different ``xla_force_host_platform_device_count``, or a shared cache
    dir) is skipped with a stderr note: run.py auto-adopts this file, and a
    foreign machine's constants would silently miscalibrate every predicted
    column.  Explicit ``CalibrationProfile.load`` / ``--calibration PATH``
    stays unchecked — naming a file is opting in."""
    import sys

    path = default_path()
    if not os.path.exists(path):
        return None
    profile = CalibrationProfile.load(path)
    stored = profile.meta.get("fingerprint")
    current = list(compilecache.cache_fingerprint())
    if stored is not None and list(stored) != current:
        print(f"# calibration: ignoring {path} "
              f"(fitted on fingerprint {stored}, this process is {current})",
              file=sys.stderr)
        return None
    return profile


# --- measurement ------------------------------------------------------------


def fit_alpha_beta(nbytes, times) -> tuple[float, float]:
    """Least-squares fit of ``t = alpha + beta * bytes`` (clamped
    non-negative: a negative latency or bandwidth term is measurement noise,
    not physics)."""
    import numpy as np

    x = np.asarray(nbytes, dtype=float)
    y = np.asarray(times, dtype=float)
    if x.size < 2:
        raise ValueError("need >= 2 (bytes, time) points to fit alpha-beta")
    beta, alpha = np.polyfit(x, y, 1)
    return float(max(alpha, 1e-9)), float(max(beta, 1e-15))


def measure_collective_times(
    sizes_bytes=(1 << 12, 1 << 15, 1 << 18, 1 << 20, 1 << 22),
    repeats: int = 5,
) -> tuple[list[float], list[float]]:
    """Best-of-``repeats`` wall-clock of one psum round per payload size
    (per-device payload bytes, f32), over every available device."""
    import jax
    import numpy as np

    n = jax.device_count()
    f = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    out_b, out_t = [], []
    for nbytes in sizes_bytes:
        elems = max(1, int(nbytes) // 4)
        x = np.zeros((n, elems), np.float32)
        jax.block_until_ready(f(x))  # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            best = min(best, time.perf_counter() - t0)
        out_b.append(float(elems * 4))
        out_t.append(best)
    return out_b, out_t


def measure_launch_overhead(repeats: int = 20) -> float:
    """Median warm wall-clock of a trivial jitted dispatch — the per-message
    fixed runtime cost (python -> runtime -> device and back)."""
    import jax
    import numpy as np

    f = jax.jit(lambda x: x + 1.0)
    x = np.zeros((8,), np.float32)
    jax.block_until_ready(f(x))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measure_dense_step(*, steps: int = 6) -> float | None:
    """Measured per-step wall-clock of the dense-BSP tiny trainer workload —
    the compute term of trainer step-time predictions.  None on a <2-device
    process (the mesh trainer needs a data axis)."""
    import jax

    if jax.device_count() < 2:
        return None
    from repro.experiments.scenario import Scenario
    from repro.experiments.trainer_substrate import (
        run_trainer_scenario, select_trainer_device_count)

    s = Scenario(arch="allreduce", sync="bsp", compressor=None,
                 steps=steps, n_workers=2, lr=0.05)
    dp, _why = select_trainer_device_count(s, jax.device_count())
    if dp is None:  # pragma: no cover - dense bsp always schedulable on >=2
        return None
    prev = set_active(None)  # measurement must not depend on a stale profile
    try:
        res = run_trainer_scenario(s, data_par=dp)
    finally:
        set_active(prev)
    return float(res.measured["step_time_s"])


def calibrate(
    out: str | None = None,
    *,
    steps: int = 6,
    repeats: int = 5,
) -> CalibrationProfile:
    """Measure this machine, fit the constants, optionally persist.

    ``out``: profile path (defaults to ``<cache_dir>/calibration.json``)."""
    sizes, times = measure_collective_times(repeats=repeats)
    alpha, beta = fit_alpha_beta(sizes, times)
    t_launch = measure_launch_overhead()
    t_step = measure_dense_step(steps=steps)
    profile = CalibrationProfile(
        alpha=alpha, beta=beta, t_launch=t_launch, t_step_dense=t_step,
        meta={
            "fingerprint": list(compilecache.cache_fingerprint()),
            "sizes_bytes": sizes,
            "times_s": times,
            "dense_steps": steps,
            "fitted_unix": time.time(),
        })
    path = out or default_path()
    profile.save(path)
    profile.meta["path"] = path
    return profile


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="profile JSON path (default: calibration.json in the "
                         "compile cache directory)")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    compilecache.configure()
    profile = calibrate(args.out or None, steps=args.steps,
                        repeats=args.repeats)
    print(json.dumps(profile.as_dict(), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
