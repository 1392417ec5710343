"""Pallas kernel micro-benchmarks + fused-vs-composed compressed-collective
roofline rows.  Writes ``BENCH_kernels.json`` at the repo root.

Interpret-mode caveat: off-TPU every kernel here runs with ``interpret=True``
(`repro.kernels.ops._interpret`), so the ``us_per_call`` column is a
correctness-level CPU timing — the Pallas interpreter evaluates kernel bodies
with jnp ops, and a fused kernel can even time *slower* than the composed jnp
path it replaces.  The TPU-relevant figure is the ``derived`` HBM-traffic
model: bytes the fused single-pass kernel moves vs the composed multi-pass
path (which round-trips every intermediate through HBM).  Both numbers are
recorded; rank kernels by traffic, not by interpret-mode wall time.

The qsgd resweep row addresses the traced-knob discipline end-to-end: it
times levels 4/8/16 through ONE compiled executable and asserts the jit
cache did not grow (``0 recompiles`` — levels is a traced value, not a jit
specialization constant).
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import Row, time_fn
from repro.kernels import ops

N = 262_144  # modest for interpret-mode timing
W = 8        # gathered worker count for the collective-reduce rows

BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_kernels.json")


def _traffic(name: str, fused_bytes: float, composed_bytes: float) -> str:
    return f"hbm_{composed_bytes / fused_bytes:.1f}x_less_than_composed"


def run() -> list[Row]:
    rows: list[Row] = []
    record: dict = {"n": N, "workers": W, "interpret_mode": True,
                    "families": {}}
    key = jax.random.key(0)
    x = jax.random.normal(key, (N,)) * 0.1
    e = jax.random.normal(jax.random.fold_in(key, 1), (N,)) * 0.05
    u = jax.random.uniform(jax.random.fold_in(key, 2), (N,))

    # ---- single-kernel rows (continuity with earlier BENCH history) ----
    us = time_fn(lambda: ops.qsgd_quantize(x, u, levels=16))
    rows.append(Row("kernels/qsgd", us, f"{4*N/1e6:.1f}MB_read_1.0MB_write"))
    us = time_fn(lambda: ops.terngrad_quantize(x, u))
    rows.append(Row("kernels/terngrad", us, "int8_payload"))
    us = time_fn(lambda: ops.sign_pack(x))
    rows.append(Row("kernels/sign_pack", us, "32x_wire"))
    us = time_fn(lambda: ops.threshold_sparsify(x, 0.05))
    rows.append(Row("kernels/threshold", us, "fused_mask+count"))

    # ---- fused vs composed: sign pack -> vote (majority collective) ----
    # fused: the wire carries the 1-bit bitmap; sign_vote decodes and
    # weight-accumulates W payloads in one pass (no unpacked intermediate).
    # composed: unpack each worker's payload to f32 signs, stack, reduce.
    packed_w = [ops.sign_pack(jax.random.normal(jax.random.fold_in(key, 10 + w),
                                                (N,))) for w in range(W)]
    packed = jnp.stack(packed_w)                      # the gathered wire tensor
    weights = jnp.ones((W,), jnp.float32)
    fused_sign = jax.jit(lambda p, wt: jnp.sign(ops.sign_vote(p, wt, n=N)))
    composed_sign = jax.jit(lambda p, wt: jnp.sign(
        sum(wt[w] * ops.sign_unpack(p[w], N) for w in range(W))))
    assert bool(jnp.array_equal(fused_sign(packed, weights),
                                composed_sign(packed, weights)))
    us_f = time_fn(fused_sign, packed, weights)
    us_c = time_fn(composed_sign, packed, weights)
    # fused reads W*N/8 packed bytes, writes 4N f32 votes; composed also
    # round-trips W unpacked f32 tensors (write + re-read = 8*4N each)
    tf, tc = N * (W / 8 + 4), N * (W / 8 + 8 * W + 4)
    rows.append(Row("kernels/sign_vote_fused", us_f, _traffic("sign", tf, tc)))
    rows.append(Row("kernels/sign_vote_composed", us_c,
                    f"materializes_{W}x{4*N/1e6:.1f}MB_unpacked"))
    record["families"]["sign_vote"] = {
        "fused_us": us_f, "composed_us": us_c,
        "fused_bytes": tf, "composed_bytes": tc, "bitwise_equal": True}

    # ---- fused vs composed: ternary 2-bit pack -> accumulate ----
    tern = jnp.sign(jax.random.normal(jax.random.fold_in(key, 30),
                                      (N,))).astype(jnp.int8) * \
        (jax.random.uniform(jax.random.fold_in(key, 31), (N,)) < 0.5)
    tpacked = jnp.stack([ops.tern_pack(tern) for _ in range(W)])
    scales = jnp.linspace(0.5, 1.5, W)
    us_pack = time_fn(lambda: ops.tern_pack(tern))
    rows.append(Row("kernels/tern_pack", us_pack, "16x_wire_vs_f32"))
    fused_tern = jax.jit(lambda p, s: ops.tern_acc(p, s, n=N))
    composed_tern = jax.jit(lambda t, s: sum(
        s[w] * t.astype(jnp.float32) for w in range(W)))
    us_f = time_fn(fused_tern, tpacked, scales)
    us_c = time_fn(composed_tern, tern, scales)
    # fused reads W*N/4 packed; composed reads the W*N int8 decode + the
    # same f32 round-trips the unfused reduce chain implies
    tf, tc = N * (W / 4 + 4), N * (W + 8 * W + 4)
    rows.append(Row("kernels/tern_acc_fused", us_f, _traffic("tern", tf, tc)))
    rows.append(Row("kernels/tern_acc_composed", us_c, "int8_decode_per_worker"))
    record["families"]["tern_acc"] = {
        "fused_us": us_f, "composed_us": us_c,
        "fused_bytes": tf, "composed_bytes": tc}

    # ---- fused vs composed: int8 widening weighted sum (qsgd wire) ----
    codes = jnp.stack([ops.qsgd_quantize(
        jax.random.normal(jax.random.fold_in(key, 40 + w), (N,)), u,
        levels=16)[0] for w in range(W)])
    dec_w = jnp.linspace(0.01, 0.02, W)
    fused_i8 = jax.jit(lambda c, wt: ops.int8_weighted_sum(c, wt, W, n=N))
    composed_i8 = jax.jit(
        lambda c, wt: (c.astype(jnp.float32) * wt[:, None]).sum(axis=0) / W)
    us_f = time_fn(fused_i8, codes, dec_w)
    us_c = time_fn(composed_i8, ops.int8_unpack(codes, N), dec_w)
    tf, tc = N * (W + 4), N * (W + 8 * W + 4)
    rows.append(Row("kernels/int8_acc_fused", us_f, _traffic("int8", tf, tc)))
    rows.append(Row("kernels/int8_acc_composed", us_c,
                    f"widens_to_{W}x{4*N/1e6:.1f}MB_f32"))
    record["families"]["int8_acc"] = {
        "fused_us": us_f, "composed_us": us_c,
        "fused_bytes": tf, "composed_bytes": tc}

    # ---- fused vs composed: EF + quantize in the bucketized pipeline ----
    fused_ef = jax.jit(lambda g, ee, uu: ops.qsgd_ef_fused(g, ee, uu, levels=16))
    def _composed_ef(g, ee, uu):
        a = ee * 1.0 + g                       # pass 1: accumulate EF
        words, norm = ops.qsgd_quantize(a, uu, levels=16)   # pass 2
        codes = ops.int8_unpack(words, a.size)
        e_new = a - ops.qsgd_dequantize(codes, norm, levels=16)  # pass 3
        return codes, norm, e_new
    composed_ef = jax.jit(_composed_ef)
    us_f = time_fn(fused_ef, x, e, u)
    us_c = time_fn(composed_ef, x, e, u)
    tf, tc = (3 * 4 + 1 + 4) * N, 8 * 4 * N
    rows.append(Row("kernels/qsgd_ef_fused", us_f, _traffic("qsgd_ef", tf, tc)))
    rows.append(Row("kernels/qsgd_ef_composed", us_c, "3_passes_over_4N"))
    record["families"]["qsgd_ef"] = {
        "fused_us": us_f, "composed_us": us_c,
        "fused_bytes": tf, "composed_bytes": tc}

    # ---- traced-knob resweep: levels is a VALUE, not a compile constant ----
    ops.qsgd_quantize(x, u, levels=16)  # ensure compiled
    before = ops.qsgd_quantize._cache_size()
    sweep_us = {lv: time_fn(lambda lv=lv: ops.qsgd_quantize(x, u, levels=lv))
                for lv in (4, 8, 16)}
    recompiles = ops.qsgd_quantize._cache_size() - before
    assert recompiles == 0, f"levels resweep recompiled {recompiles}x"
    rows.append(Row("kernels/qsgd_levels_resweep",
                    sum(sweep_us.values()) / len(sweep_us),
                    f"levels=4,8,16_{recompiles}_recompiles"))
    record["qsgd_levels_resweep"] = {
        "us_per_level": {str(k): v for k, v in sweep_us.items()},
        "recompiles": recompiles}

    # ---- wkv6 (continuity) ----
    B, S, H, hd = 1, 256, 4, 64
    r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd)) * 0.3
               for i in range(3, 6))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 6),
                                         (B, S, H, hd))) * 0.5 + 0.4
    uu = jax.random.normal(jax.random.fold_in(key, 7), (H, hd)) * 0.1
    s0 = jnp.zeros((B, H, hd, hd))
    us = time_fn(lambda: ops.wkv6(r, k, v, w, uu, s0, chunk=64), reps=3)
    flops = 4 * B * S * H * hd * hd * 2
    rows.append(Row("kernels/wkv6_chunked", us,
                    f"{flops/1e6:.0f}MFLOP_vmem_resident_state"))

    record["rows"] = [{"name": r.name, "us_per_call": r.us_per_call,
                       "derived": str(r.derived)} for r in rows]
    with open(BENCH_PATH, "w") as f:
        json.dump(record, f, indent=2)
    rows.append(Row("kernels/claims_validated", 0.0, True))
    return rows
