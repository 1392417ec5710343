"""Cold-start: the persistent compilation cache + calibration acceptance
bench (BENCH_coldstart.json).

Every number that matters here is a COLD-PROCESS number, so each leg runs in
a subprocess with its own interpreter, its own forced host-device topology
(``JAX_PLATFORMS=cpu``: the children never ask for an accelerator), and a
shared on-disk cache directory — the fixed ``coldstart`` subdirectory of the
compile cache directory, emptied before the cold legs:

* **cold-process / cold-cache** — fresh interpreter, empty cache dir: the
  full XLA compile bill every process used to pay;
* **cold-process / warm-cache** — fresh interpreter, the SAME cache dir: jax
  deserializes the executables some previous process compiled (the repo
  manifest confirms 0 persistent misses);
* **warm-process** — the second sweep inside one process: the in-memory
  registry bound (engine program cache / bundle registry), unchanged by
  this PR and reported for scale.

Legs run for both compilation layers: the engine 90-cell sweep
(``sweep_matrix_45`` x 2 problem seeds) and the 16-cell trainer matrix
(``trainer_matrix_16`` on 4 forced host devices).  Asserts the acceptance
criterion: warm-disk-cache cold-process trainer sweep >= 3x faster than
cold-cache.

The calibration leg then fits this machine's profile
(:mod:`repro.core.calibrate`: psum alpha-beta ladder, launch overhead,
dense-step compute) inside a 4-device subprocess and re-runs a trainer
sweep twice — once predicting with the uncalibrated datasheet constants,
once with the fitted profile — recording mean predicted-vs-measured
step-time rel-err before/after (asserted to strictly improve) and the
noisier overlap-saving rel-err (recorded, not asserted: forced host
devices have no real NIC to overlap).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

from benchmarks.common import Row

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_PATH = os.path.join(REPO, "BENCH_coldstart.json")

ENGINE_STEPS = 20
TRAINER_STEPS = 6

_ENGINE_CHILD = f"""
import json, os, time
from repro.core import compilecache
compilecache.configure()
from repro.core.simulate import engine_cache_stats
from repro.experiments.runner import _run_training_scenarios, sweep_matrix_45

cells = sweep_matrix_45(steps={ENGINE_STEPS}, problem_seeds=(0, 1))
t0 = time.perf_counter(); _run_training_scenarios(cells, replicas=1)
first_s = time.perf_counter() - t0
t0 = time.perf_counter(); _run_training_scenarios(cells, replicas=1)
warm_process_s = time.perf_counter() - t0
st = engine_cache_stats()
print("RESULT " + json.dumps({{
    "n_cells": len(cells), "first_s": first_s,
    "warm_process_s": warm_process_s, "compiles": st.compiles,
    "persistent": st.persistent_cache}}))
"""

_TRAINER_CHILD = f"""
import json, os, time
from repro.core import compilecache
compilecache.configure()
from repro.experiments.trainer_substrate import run_trainer_sweep, trainer_matrix_16
from repro.train.steps import bundle_cache_stats

cells = trainer_matrix_16(steps={TRAINER_STEPS})
t0 = time.perf_counter()
results, skipped = run_trainer_sweep(cells)
first_s = time.perf_counter() - t0
assert not skipped, skipped
t0 = time.perf_counter(); run_trainer_sweep(cells)
warm_process_s = time.perf_counter() - t0
st = bundle_cache_stats()
print("RESULT " + json.dumps({{
    "n_cells": len(cells), "first_s": first_s,
    "warm_process_s": warm_process_s, "builds": st.builds, "hits": st.hits,
    "persistent": st.persistent_cache}}))
"""

_CALIBRATE_CHILD = f"""
import json, os
from repro.core import calibrate, compilecache
compilecache.configure()
from repro.experiments.scenario import Scenario
from repro.experiments.trainer_substrate import run_trainer_sweep, trainer_matrix_16

profile = calibrate.calibrate(steps={TRAINER_STEPS})  # saves <cache>/calibration.json

cells = trainer_matrix_16(steps={TRAINER_STEPS})
for overlap in ("sequential", "pipelined"):  # an overlap twin pair for the
    cells.append(Scenario(                   # overlap-saving rel-err leg
        sync="bsp", n_workers=4, steps={TRAINER_STEPS}, lr=0.05,
        compressor="qsgd", compressor_kwargs={{"levels": 16}},
        overlap=overlap, microbatch=2))

def relerrs(results):
    step, save = [], []
    for r in results:
        if r is None:
            continue
        m, p = r.measured, r.predicted
        step.append(abs(p["step_time_s"] - m["step_time_s"]) / m["step_time_s"])
        if "overlap_saving_s" in m and "overlap_saving_s" in p:
            save.append(abs(p["overlap_saving_s"] - m["overlap_saving_s"])
                        / max(abs(m["overlap_saving_s"]), 1e-9))
    mean = lambda xs: sum(xs) / len(xs) if xs else None
    return {{"step_time": mean(step), "overlap_saving": mean(save),
             "n_cells": len(step)}}

calibrate.set_active(None)
before, skipped = run_trainer_sweep(cells)
assert not skipped, skipped
calibrate.set_active(profile)
after, _ = run_trainer_sweep(cells)
print("RESULT " + json.dumps({{
    "profile": profile.as_dict(),
    "before": relerrs(before), "after": relerrs(after)}}))
"""


def _run_child(code: str, cache_dir: str, *, ndev: int, timeout: int = 900) -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_PLATFORMS"] = "cpu"
    if ndev > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    else:
        env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"coldstart child failed:\n{out.stderr[-4000:]}")
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def run() -> list[Row]:
    from repro.core import compilecache

    t_all = time.perf_counter()
    cache_dir = os.path.join(compilecache.cache_dir(), "coldstart")
    shutil.rmtree(cache_dir, ignore_errors=True)  # the cold legs start empty
    eng_cold = _run_child(_ENGINE_CHILD, cache_dir, ndev=1)
    eng_warm = _run_child(_ENGINE_CHILD, cache_dir, ndev=1)
    tr_cold = _run_child(_TRAINER_CHILD, cache_dir, ndev=4)
    tr_warm = _run_child(_TRAINER_CHILD, cache_dir, ndev=4)
    cal = _run_child(_CALIBRATE_CHILD, cache_dir, ndev=4)

    # manifest accounting: a cold cache misses every build, a warm cache
    # misses NONE (0 fresh XLA compiles on the second process)
    assert eng_cold["persistent"]["misses"] == eng_cold["compiles"], eng_cold
    assert eng_warm["persistent"]["misses"] == 0, eng_warm
    assert eng_warm["persistent"]["hits"] == eng_warm["compiles"], eng_warm
    assert tr_cold["persistent"]["misses"] == tr_cold["builds"], tr_cold
    assert tr_warm["persistent"]["misses"] == 0, tr_warm
    assert tr_warm["persistent"]["hits"] == tr_warm["builds"], tr_warm

    trainer_disk_speedup = tr_cold["first_s"] / tr_warm["first_s"]
    engine_disk_speedup = eng_cold["first_s"] / eng_warm["first_s"]
    # the acceptance criterion: warm-disk-cache cold-process trainer
    # sweep >= 3x faster than cold-cache
    assert trainer_disk_speedup >= 3.0, (trainer_disk_speedup, tr_cold, tr_warm)

    # calibration strictly improves the step-time prediction; the
    # overlap-saving leg is recorded without an assert (host-device noise)
    rel_before = cal["before"]["step_time"]
    rel_after = cal["after"]["step_time"]
    assert rel_after < rel_before, cal

    record = {
        "engine": {
            "n_cells": eng_cold["n_cells"],
            "steps": ENGINE_STEPS,
            "compiles": eng_cold["compiles"],
            "cold_cache_s": eng_cold["first_s"],
            "warm_cache_s": eng_warm["first_s"],
            "warm_process_s": eng_warm["warm_process_s"],
            "disk_speedup": engine_disk_speedup,
            "persistent_cold": eng_cold["persistent"],
            "persistent_warm": eng_warm["persistent"],
        },
        "trainer": {
            "n_cells": tr_cold["n_cells"],
            "steps": TRAINER_STEPS,
            "builds": tr_cold["builds"],
            "cache_hits": tr_cold["hits"],
            "cold_cache_s": tr_cold["first_s"],
            "warm_cache_s": tr_warm["first_s"],
            "warm_process_s": tr_warm["warm_process_s"],
            "disk_speedup": trainer_disk_speedup,
            "persistent_cold": tr_cold["persistent"],
            "persistent_warm": tr_warm["persistent"],
        },
        "calibration": {
            "profile": cal["profile"],
            "relerr_step_time_before": rel_before,
            "relerr_step_time_after": rel_after,
            "relerr_overlap_saving_before": cal["before"]["overlap_saving"],
            "relerr_overlap_saving_after": cal["after"]["overlap_saving"],
            "n_cells": cal["before"]["n_cells"],
        },
        "bench_wall_clock_s": time.perf_counter() - t_all,
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(record, f, indent=2)

    return [
        Row("coldstart/engine_disk", eng_warm["first_s"] * 1e6,
            f"cold {eng_cold['first_s']:.1f}s -> warm-disk "
            f"{eng_warm['first_s']:.1f}s ({engine_disk_speedup:.2f}x, "
            f"{eng_cold['compiles']} programs)"),
        Row("coldstart/trainer_disk", tr_warm["first_s"] * 1e6,
            f"cold {tr_cold['first_s']:.1f}s -> warm-disk "
            f"{tr_warm['first_s']:.1f}s ({trainer_disk_speedup:.2f}x >= 3x, "
            f"{tr_cold['builds']} bundles)"),
        Row("coldstart/calibration", 0.0,
            f"step-time rel-err {rel_before:.2f} -> {rel_after:.2f} "
            f"(alpha={cal['profile']['alpha']:.2e}, "
            f"beta={cal['profile']['beta']:.2e})"),
        Row("coldstart/claims_validated", 0.0, True),
    ]
