"""Gradient-integrity fault injection and payload validation.

Real fleets do not only drop cleanly (the churn axis) — they *misbehave*:
fp overflow turns a gradient into NaN/Inf, a flipped DRAM/NIC bit turns a
packed payload into a different valid-looking payload, and a magnitude
spike encodes perfectly well and then dominates every denominator.  This
module is the shared vocabulary of both substrates (scan engine + mesh
trainer) for the detection -> quarantine -> recover pipeline:

* **injection** is sender-side and post-compression: the payload leaves the
  worker corrupted *in its wire domain* (f32 words for the dense path, int8
  codes and f32 scales/norms for the quantized families, packed uint32
  words for the 1/2-bit wires).  The sender keeps its clean copy — error feedback
  always accumulates against what the worker actually compressed.
* **validation** is receiver-side and only uses the redundancy the wire
  format actually has: finiteness and range of scales/norms, code-range
  checks for int8/2-bit codes.  A 1-bit packed sign wire has no redundancy
  — every bit pattern is a legal vote — so a flipped sign payload is
  *undetectable* by construction and the majority vote itself is the
  defense (documented, tested).
* every select is a ``jnp.where`` whose predicate is identically true at
  ``corruption_rate == 0``, so an integrity-program cell with the rate
  traced to zero reproduces the churn-free trajectory bitwise (the PR 8
  reduction-refusion lesson: the guards ride the post-compression values,
  never the pre-compression arithmetic).

Corruption kinds (STRUCTURAL; the rate is traced):

========  ==================================================================
kind      wire-domain effect
========  ==================================================================
nan       float payloads (dense words, scales, norms) become NaN
inf       float payloads become +Inf
spike     float magnitudes multiplied by ``SPIKE_FACTOR`` (encodes fine;
          caught by the receiver's range check)
bitflip   dense f32 words get an exponent bit flipped; int8 codes and
          packed uint32 words are XORed with ``0x55`` in every byte
========  ==================================================================
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32

KINDS = ("nan", "inf", "spike", "bitflip")

#: magnitude multiplier of the "spike" fault — far above any sane gradient
SPIKE_FACTOR = 1e8
#: receiver-side ceiling on |dense word| / scale / norm: clean values sit
#: many orders of magnitude below, a spiked or exponent-flipped one above
VALID_MAX = 1e6

#: fold_in tag for the corruption uniform draw — distinct from the churn
#: mask tag (0x6368) so corruption draws never perturb the mask / gradient /
#: compressor key streams ("corr")
CORRUPT_FOLD = 0x636F72


def corruption_flag(key: jax.Array, rate, gate) -> jax.Array:
    """Per-worker per-round corruption bit: 1.0 where this worker's payload
    is corrupted this round.  ``key`` must already be folded to the worker
    (the same per-worker key the churn mask draws from); ``gate`` is the
    alive-and-in-window predicate — dead workers send nothing to corrupt."""
    u = jax.random.uniform(jax.random.fold_in(key, CORRUPT_FOLD), ())
    return jnp.where(gate & (u < rate), 1.0, 0.0)


def _flip_f32(x: jax.Array) -> jax.Array:
    """Flip the top exponent bit of every f32 word: magnitudes below 2 blow
    up towards ~2**127 (or Inf/NaN), the in-domain image of a memory/NIC
    bit flip on a dense wire."""
    bits = jax.lax.bitcast_convert_type(x.astype(f32), jnp.int32)
    return jax.lax.bitcast_convert_type(bits ^ (1 << 30), f32)


def corrupt_dense(kind: str, x: jax.Array, flag) -> jax.Array:
    """Corrupt a dense float payload where ``flag`` is set (sender-side).
    ``flag`` is a traced 0/1 scalar (or broadcastable vector)."""
    if kind == "nan":
        bad = jnp.full_like(x, jnp.nan)
    elif kind == "inf":
        bad = jnp.full_like(x, jnp.inf)
    elif kind == "spike":
        bad = x * SPIKE_FACTOR
    elif kind == "bitflip":
        bad = _flip_f32(x)
    else:
        raise ValueError(f"unknown corruption kind {kind!r}")
    return jnp.where(flag > 0, bad, x)


def corrupt_codes(kind: str, codes: jax.Array, flag) -> jax.Array:
    """Corrupt an integer code payload (int8 quantizer codes, packed uint32
    sign/ternary words).  Only ``bitflip`` has an integer-domain image; the
    float-born faults (nan/inf/spike) live in the scales/norms that
    accompany the codes and leave the codes themselves alone."""
    if kind != "bitflip":
        return codes
    # 0x55 in every byte: one flipped bit per 2-bit slot at any word width
    mask = int.from_bytes(b"\x55" * jnp.dtype(codes.dtype).itemsize, "little")
    bad = codes ^ jnp.asarray(mask, codes.dtype)
    return jnp.where(flag > 0, bad, codes)


def corrupt_payload(kind: str, payload: dict, flag) -> dict:
    """Corrupt a compressed payload dict in-domain: float leaves get the
    float fault, integer leaves the XOR fault.  ``flag`` broadcasts over
    each leaf (scalar for a single worker's payload)."""
    out = {}
    for k, v in payload.items():
        if jnp.issubdtype(v.dtype, jnp.floating):
            out[k] = corrupt_dense(kind, v, flag)
        elif k == "indices":
            # corrupting sparse indices models a different fault (addressing)
            # with scheme-dependent scatter semantics — out of scope
            out[k] = v
        else:
            out[k] = corrupt_codes(kind, v, flag)
    return out


def _reduce_all(ok: jax.Array, per_row: bool) -> jax.Array:
    if per_row:
        return jnp.all(ok.reshape(ok.shape[0], -1), axis=1).astype(f32)
    return jnp.all(ok).astype(f32)


def dense_valid(x: jax.Array, *, per_row: bool = False) -> jax.Array:
    """Receiver-side validity of a dense float payload: every word finite
    and within ``VALID_MAX``.  Returns a 0/1 f32 scalar, or one bit per
    leading-axis row with ``per_row=True`` (gathered (W, ...) payloads)."""
    ok = jnp.isfinite(x) & (jnp.abs(x) <= VALID_MAX)
    return _reduce_all(ok, per_row)


def scale_valid(*scales: jax.Array) -> jax.Array:
    """Validity of per-worker scale/norm scalars (each (W,) or scalar):
    finite and within range.  Returns the AND as 0/1 f32."""
    ok = None
    for s in scales:
        o = jnp.isfinite(s) & (jnp.abs(s) <= VALID_MAX)
        ok = o if ok is None else (ok & o)
    return ok.astype(f32)


def code_valid(codes: jax.Array, bound, *, per_row: bool = False) -> jax.Array:
    """Validity of an int8 code payload: every |code| within the quantizer's
    level bound.  ``bound`` may be traced (per-worker (W,) or scalar)."""
    mag = jnp.abs(codes.astype(f32))
    if per_row and jnp.ndim(bound) == 1:
        bound = bound.reshape((-1,) + (1,) * (codes.ndim - 1))
    ok = mag <= bound
    return _reduce_all(ok, per_row)


def packed2_valid(words: jax.Array, *, per_row: bool = False) -> jax.Array:
    """Validity of a 2-bit packed ternary wire (crumbs: 0=zero, 1=+1, 3=-1):
    the crumb value 2 is not a legal code, so an XOR fault is visible
    whenever it produces one.  (The 1-bit packed sign wire has no such
    redundancy — no validator exists for it, by design.)"""
    ok = None
    for shift in range(0, 8 * jnp.dtype(words.dtype).itemsize, 2):
        crumb = (words >> shift) & 3
        o = crumb != 2
        ok = o if ok is None else (ok & o)
    return _reduce_all(ok, per_row)
