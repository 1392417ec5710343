"""Test helpers shared by in-process (1-device) and subprocess (N-device)
tests."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess_devices(script: str, n_devices: int = 8, timeout: int = 900) -> str:
    """Run a python snippet in a subprocess with N fake host devices (on
    the CPU platform: the child never competes for an accelerator)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def tiny_batch(cfg, B=4, S=32, seed=0):
    k = jax.random.key(seed)
    batch = {
        "tokens": jax.random.randint(jax.random.fold_in(k, 1), (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(k, 2), (B, S), 0, cfg.vocab),
    }
    if cfg.modality == "vision":
        S_vis = int(S * cfg.vision_fraction / (1 - cfg.vision_fraction))
        batch["patches"] = jax.random.normal(jax.random.fold_in(k, 3), (B, S_vis, cfg.d_model))
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(jax.random.fold_in(k, 4), (B, max(1, S // cfg.encoder_ratio), cfg.d_model))
    return batch


def batch_pspecs(batch):
    return {k: P(("data",), *(None,) * (v.ndim - 1)) for k, v in batch.items()}
