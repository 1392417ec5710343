"""Compressed-domain collectives (the ``wire_format`` axis): the Pallas
pack->reduce->unpack kernels vs jnp references (odd/even worker counts,
churn masks, vote ties), end-to-end compressed-vs-dense equivalence across
every registry family with a ``wire_reduce``, the static/traced discipline
(knob-siblings share one bundle while wire_format splits the class), the
fused EF+quantize path inside the pipelined microbatch scan, structural
validation errors, and the packed-sign payload accounting (~32x under
dense f32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comms
from repro.core.types import CommConfig, bundle_spec
from repro.experiments import Scenario
from repro.experiments.trainer_substrate import (
    run_trainer_scenario,
    trainer_shape_key,
)
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.train.steps import bundle_cache_clear, bundle_cache_stats


# ---------------------------------------------------------------------------
# Kernels vs jnp references.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_w", [3, 4])  # odd AND even voter counts
@pytest.mark.parametrize("n", [1000, 8192, 20_003])
def test_sign_vote_matches_reference(n_w, n):
    key = jax.random.key(n_w * 1000 + n)
    xs = jax.random.normal(key, (n_w, n))
    signs = jnp.where(xs >= 0, 1.0, -1.0)
    packed = jnp.stack([ops.sign_pack(xs[w]) for w in range(n_w)])
    # churn-style weights: one worker masked out entirely
    weights = jnp.asarray([0.0] + [1.0] * (n_w - 1))
    votes = ops.sign_vote(packed, weights, n=n)
    np.testing.assert_array_equal(
        np.asarray(votes), np.asarray(kref.sign_vote_ref(signs, weights)))
    # majority decode (ties -> +1) matches the unpacked-int8 reference path
    maj = jnp.where(votes >= 0, 1.0, -1.0)
    ref_votes = (signs * weights[:, None]).sum(axis=0)
    np.testing.assert_array_equal(
        np.asarray(maj), np.asarray(jnp.where(ref_votes >= 0, 1.0, -1.0)))


def test_sign_vote_tie_breaks_positive():
    """An even split votes to exactly 0.0 and decodes +1 — bit-identical to
    the dense reference's ``where(sum >= 0)``."""
    n = 4096
    x = jax.random.normal(jax.random.key(7), (n,))
    packed = jnp.stack([ops.sign_pack(x), ops.sign_pack(-x)])
    votes = ops.sign_vote(packed, jnp.ones((2,)), n=n)
    np.testing.assert_array_equal(np.asarray(votes), np.zeros(n, np.float32))
    assert bool(jnp.all(jnp.where(votes >= 0, 1.0, -1.0) == 1.0))


@pytest.mark.parametrize("n_w", [3, 4])
def test_tern_pack_acc_matches_reference(n_w):
    n = 10_007  # not a tile multiple: exercises zero-pad accumulation safety
    key = jax.random.key(n_w)
    tern = (jax.random.randint(key, (n_w, n), -1, 2)).astype(jnp.int8)
    packed = jnp.stack([ops.tern_pack(tern[w]) for w in range(n_w)])
    # scale x churn weights, one worker dead
    weights = jnp.asarray([0.7, 0.0, 1.3, 0.9][:n_w])
    acc = ops.tern_acc(packed, weights, n=n)
    expect = kref.weighted_sum_ref(tern.astype(jnp.float32), weights)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(expect))
    # the wire words match the oracle's tile layout, and the roundtrip
    # through the 2-bit payload is lossless
    np.testing.assert_array_equal(np.asarray(packed[0]),
                                  np.asarray(kref.tern_pack_ref(tern[0])))
    un = kref.tern_unpack_ref(packed[0], n)
    np.testing.assert_array_equal(np.asarray(un),
                                  np.asarray(tern[0], dtype=np.float32))


@pytest.mark.parametrize("n_w", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("n", [9000, 32768])  # padded, and one whole block
def test_int8_weighted_sum_matches_reference(n_w, n):
    """The word-reading widening sum equals the jnp sum of the unpacked
    codes times the weights, in worker order, over denom, bit for bit."""
    key = jax.random.key(40 + n_w)
    codes = jax.random.randint(key, (n_w, n), -128, 128).astype(jnp.int8)
    words = jnp.stack([jax.lax.bitcast_convert_type(kref.int8_pack_ref(codes[w]),
                                                    jnp.int32).reshape(-1, 128)
                       for w in range(n_w)])
    weights = jnp.linspace(0.01, 0.05, n_w)
    denom = 3.0
    got = ops.int8_weighted_sum(words, weights, denom, n=n)
    expect = jax.jit(lambda c, w, d: kref.weighted_sum_ref(c.astype(jnp.float32), w) / d)(
        codes, weights, denom)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


# ---------------------------------------------------------------------------
# Structural validation: bundle_spec and Scenario.violations.
# ---------------------------------------------------------------------------


def test_bundle_spec_wire_format_validation():
    with pytest.raises(ValueError, match="wire_format"):
        bundle_spec(CommConfig(wire_format="packed"))
    # families without a compressed-domain reduction are structural errors
    with pytest.raises(ValueError, match="wire_reduce|sign|terngrad|qsgd"):
        bundle_spec(CommConfig(compressor="topk",
                               compressor_kwargs={"ratio": 0.01},
                               wire_format="compressed"))
    # bf16-on-the-wire + compressed payloads is contradictory
    with pytest.raises(ValueError, match="bfloat16"):
        bundle_spec(CommConfig(compressor="qsgd", agg_dtype="bfloat16",
                               wire_format="compressed"))
    # gossip mixes parameters, not gradients: normalized to dense
    assert bundle_spec(CommConfig(aggregator="gossip",
                                  wire_format="compressed")).wire_format == "dense"
    spec_c = bundle_spec(CommConfig(compressor="signsgd",
                                    wire_format="compressed"))
    assert spec_c.wire_format == "compressed"
    assert spec_c != bundle_spec(CommConfig(compressor="signsgd"))


def test_scenario_wire_format_tag_and_violations():
    s = Scenario(compressor="signsgd", wire_format="compressed")
    assert "+cwire" in s.tag()
    assert s.violations("trainer") == []
    # runtime-only: the simulators model wire width analytically
    assert any("runtime-only" in v for v in s.violations("training"))
    assert any("gossip" in v
               for v in Scenario(arch="gossip",
                                 wire_format="compressed").violations())
    assert any(v for v in Scenario(compressor="topk",
                                   wire_format="compressed").violations())
    assert any("wire_format" in v
               for v in Scenario(wire_format="zip").violations())


# ---------------------------------------------------------------------------
# End-to-end: compressed wire reproduces the dense (decompress-then-reduce)
# path for every registry family that supports it.
# ---------------------------------------------------------------------------

_BASE = dict(sync="bsp", n_workers=2, steps=3, lr=0.05, bucket_bytes=4e6)


@pytest.mark.parametrize("family,kwargs,exact", [
    ("signsgd", (), True),           # integer vote sums: bitwise
    ("signsgd_packed", (), True),
    ("terngrad", (), True),          # exact {-1,0,+1} factors
    ("terngrad_kernel", (), True),
    ("qsgd", (("levels", 16),), False),   # ~1 ulp: reassociated decode scale
    ("qsgd_kernel", (("levels", 16),), False),
])
def test_compressed_wire_matches_dense_reduce(family, kwargs, exact):
    dense = run_trainer_scenario(
        Scenario(compressor=family, compressor_kwargs=kwargs, **_BASE),
        data_par=1)
    comp = run_trainer_scenario(
        Scenario(compressor=family, compressor_kwargs=kwargs,
                 wire_format="compressed", **_BASE), data_par=1)
    if exact:
        np.testing.assert_array_equal(dense.series["loss_full"],
                                      comp.series["loss_full"])
    else:
        np.testing.assert_allclose(dense.series["loss_full"],
                                   comp.series["loss_full"], rtol=1e-6)


def test_dense_compressor_none_compressed_uses_bf16_widening():
    """compressor=None + compressed wire = bf16 payload with f32 widening
    accumulate (lossy but finite; the wire artifact shows bf16)."""
    r = run_trainer_scenario(Scenario(wire_format="compressed", **_BASE),
                             data_par=1)
    assert np.isfinite(r.series["loss_full"]).all()


def test_fused_ef_pipelined_microbatch_matches_composed():
    """The fused qsgd+EF kernel inside the pipelined bucketized microbatch
    scan (staleness 0 = flush mode) reproduces the composed
    pre_compress -> quantize -> post_compress path within 1e-6."""
    base = dict(sync="bsp", n_workers=2, steps=4, lr=0.05, bucket_bytes=4e6,
                compressor="qsgd_kernel", compressor_kwargs=(("levels", 16),),
                error_feedback=True, overlap="pipelined", overlap_staleness=0,
                microbatch=2)
    composed = run_trainer_scenario(Scenario(**base), data_par=1)
    fused = run_trainer_scenario(Scenario(wire_format="compressed", **base),
                                 data_par=1)
    np.testing.assert_allclose(composed.series["loss_full"],
                               fused.series["loss_full"], rtol=1e-6)


def test_compressed_churn_ef_freezes_and_stays_finite():
    r = run_trainer_scenario(
        Scenario(compressor="qsgd_kernel", error_feedback=True,
                 wire_format="compressed", churn=True, dropout_rate=0.3,
                 **_BASE), data_par=1)
    assert np.isfinite(r.series["loss_full"]).all()


# ---------------------------------------------------------------------------
# Shape-class discipline + wire accounting.
# ---------------------------------------------------------------------------


def test_wire_format_splits_class_but_knob_siblings_share():
    s4 = Scenario(compressor="qsgd", compressor_kwargs=(("levels", 4),),
                  wire_format="compressed", **_BASE)
    s16 = Scenario(compressor="qsgd", compressor_kwargs=(("levels", 16),),
                   wire_format="compressed", **_BASE)
    dense = Scenario(compressor="qsgd", compressor_kwargs=(("levels", 16),),
                     **_BASE)
    assert trainer_shape_key(s4, data_par=1) == trainer_shape_key(s16,
                                                                  data_par=1)
    assert trainer_shape_key(dense, data_par=1) != trainer_shape_key(
        s16, data_par=1)
    bundle_cache_clear()
    b0, h0 = bundle_cache_stats().builds, bundle_cache_stats().hits
    r4 = run_trainer_scenario(s4, data_par=1)
    r16 = run_trainer_scenario(s16, data_par=1)
    st = bundle_cache_stats()
    assert (st.builds - b0, st.hits - h0) == (1, 1)
    # the traced knob still bites through the shared compile
    assert abs(r4.measured["final_loss"] - r16.measured["final_loss"]) > 1e-7


def test_packed_sign_payload_is_32x_under_dense():
    """Payload accounting (mesh-size independent): the 1-bit sign bitmap on
    the wire is ~32x smaller than the dense f32 gradient payload, modulo
    the <1-tile pack padding."""
    from repro.optim.optimizers import momentum_sgd
    from repro.optim.schedules import constant
    from repro.experiments.trainer_substrate import make_tiny_workload
    from repro.launch.mesh import make_test_mesh
    from repro.train.steps import build_bundle
    from repro.train.trainer import Trainer

    cfg, shape, src = make_tiny_workload()
    mesh = make_test_mesh(1, 1)

    def grad_payload(comm, fmt):
        bundle_cache_clear()
        with comms.capture() as log:
            bundle = build_bundle(cfg, mesh, comm, momentum_sgd(0.0), shape)
            tr = Trainer(bundle, src, constant(0.05), log_every=1)
            tr.fit(tr.init(), 1)
        assert fmt in log.by_wire_format(payload=True), (
            fmt, log.by_wire_format(payload=True))
        return sum(r.payload_bytes * r.mult for r in log.records
                   if r.tag == "grad_agg" and r.wire_format == fmt)

    dense = grad_payload(CommConfig(bucket_mb=4.0), "f32")
    packed = grad_payload(CommConfig(compressor="signsgd", bucket_mb=4.0,
                                     wire_format="compressed"), "packed1")
    assert dense > 0 and packed > 0
    ratio = dense / packed
    assert 24.0 < ratio <= 32.0, ratio


# ---------------------------------------------------------------------------
# The compressed int8 wire carries the quantize kernel's 32-bit words: the
# aggregate equals, bit for bit, the path that gathered the int8 codes
# themselves and summed them widened (4 fake devices, subprocess).
# ---------------------------------------------------------------------------

INT8_WIRE_SCRIPT = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax import make_mesh, shard_map
from jax.sharding import AxisType, PartitionSpec as P
from repro.core import aggregate, comms
from repro.core.types import CommConfig, CommKnobs
from repro.kernels import ops
from repro.kernels import ref as kref

W = 4
mesh = make_mesh((W,), ("data",), axis_types=(AxisType.Auto,))
# three buckets: one whole (256, 128) block, and two that are padded
shapes = {"a": (256, 128), "b": (1000,), "w": (300, 130)}
keys = jax.random.split(jax.random.key(0), len(shapes))
grads = {k: jax.random.normal(kk, (W,) + s) * 0.1
         for kk, (k, s) in zip(keys, sorted(shapes.items()))}
comms_by_mode = {
    "plain": CommConfig(),
    "ef": CommConfig(error_feedback=True, ef_decay=0.9),
    "churn": CommConfig(churn=True, dropout_rate=0.5),
}


def old_reduce(compressor, c, p, axes, alive_g, denom, integ=None):
    # the int8 codes gathered as int8 and summed widened in worker order,
    # then divided: what the wire did before it carried the words
    codes = ops.int8_unpack(c.payload["code"], c.n)
    cg = comms.all_gather(codes, axes, axis=0)
    ng = comms.all_gather(c.payload["norm"], axes, axis=0).reshape(-1)
    w = ng / jnp.asarray((p or {}).get("levels", compressor.levels), jnp.float32)
    if alive_g is not None:
        w = w * alive_g
    return kref.weighted_sum_ref(cg.astype(jnp.float32), w) / denom


def run(comm):
    plan = aggregate.make_bucket_plan(
        comm, {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in grads.items()})
    assert len(plan.buckets) == 3

    def f(g, ef):
        g = {k: v[0] for k, v in g.items()}
        state = aggregate.init_comm_state(comm, plan)
        if "ef" in state:
            state["ef"] = [e[0] for e in ef]
        knobs = CommKnobs.from_comm(comm, plan.knob_values(), n_workers=W).as_tree()
        out, st = aggregate.aggregate_gradients(comm, plan, g, state,
                                                jax.random.key(7), ("data",), knobs)
        return out, [e[None] for e in st.get("ef", [])]

    ef0 = [jax.random.normal(jax.random.key(9 + i), (W, b.size)) * 0.05
           for i, b in enumerate(plan.buckets)]
    step = jax.jit(shard_map(f, mesh=mesh, in_specs=({k: P("data") for k in grads}, P("data")),
                             out_specs=({k: P() for k in grads}, P("data")), check_vma=False))
    return step(grads, ef0)


out = {}
for mode, extra in comms_by_mode.items():
    comm = extra.with_updates(compressor="qsgd_kernel", compressor_kwargs={"levels": 127},
                              wire_format="compressed", bucket_mb=0.1)
    new = run(comm)
    words_reduce = aggregate._int8_code_reduce
    aggregate._int8_code_reduce = old_reduce
    try:
        old = run(comm)
    finally:
        aggregate._int8_code_reduce = words_reduce
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)))
    moved = all(float(jnp.max(jnp.abs(v))) > 0 for v in new[0].values())
    if mode == "plain":
        plain = new[0]
    # churn drops a worker, so its mean differs from the plain one
    differs = not all(np.array_equal(np.asarray(plain[k]), np.asarray(new[0][k]))
                      for k in grads)
    out[mode] = {"same": same, "nonzero": moved, "ef_leaves": len(new[1]),
                 "differs_from_plain": differs}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def int8_wire_results():
    import json

    from tests.helpers import run_subprocess_devices

    out = run_subprocess_devices(INT8_WIRE_SCRIPT, n_devices=4, timeout=600)
    return json.loads(out.split("RESULT ", 1)[1])


@pytest.mark.parametrize("mode", ["plain", "ef", "churn"])
def test_int8_word_wire_matches_int8_code_gather(int8_wire_results, mode):
    """aggregate_gradients on the compressed int8 wire (words from the
    quantize kernel, or the fused EF kernel, straight into the widening
    sum) gives the identical bits of the composed path: int8 codes ->
    int8 all-gather -> widening sum -> / denom."""
    got = int8_wire_results[mode]
    assert got["same"] and got["nonzero"], got
    assert got["ef_leaves"] == (3 if mode == "ef" else 0)
    assert got["differs_from_plain"] == (mode != "plain")
