"""The cells' initial weights, made from the seed by the benchmark itself.

Every leaf, named by its path in the parameter tree ("blocks/0/attn/wq"),
gets its own key, ``fold_in(key(seed), crc32(path))``, so a leaf's values do
not depend on which other leaves exist.  The rule is the published
initialization of these models (``initializer_range`` 0.02 in their
configurations): matrices and the embedding normal with standard deviation
0.02, biases zero, norm weights one.  Values are made in float32 and stored
in the configuration's parameter type, on the device, in one jitted call.
"""

from __future__ import annotations

import re
import zlib

import jax
import jax.numpy as jnp

STD = 0.02
ONES = re.compile(r"^(ln\w*|\w*_norm)$")
ZEROS = re.compile(r"^b[a-z]$")


def kind(path: str) -> str:
    leaf = path.rsplit("/", 1)[-1]
    if ONES.match(leaf):
        return "ones"
    if ZEROS.match(leaf):
        return "zeros"
    return "normal"


def make(seed: int, shapes: dict[str, tuple], dtype, out_shardings=None) -> dict:
    """``{path: array}`` for ``{path: shape}``, stored as ``dtype``."""
    paths = sorted(shapes)

    def build(key):
        out = {}
        for p in paths:
            k = kind(p)
            if k == "ones":
                x = jnp.ones(shapes[p], jnp.float32)
            elif k == "zeros":
                x = jnp.zeros(shapes[p], jnp.float32)
            else:
                sub = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
                x = jax.random.normal(sub, shapes[p], jnp.float32) * STD
            out[p] = x.astype(dtype)
        return out

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.key(seed))
