"""Multi-device collective/aggregation semantics (8 fake devices,
subprocess): manual ring/RHD == psum, compressed aggregation invariants,
gossip mixing conservation, CHOCO consensus."""

import pytest

from tests.helpers import run_subprocess_devices

SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import make_mesh, shard_map
from jax.sharding import AxisType
from repro.core import collectives, comms, aggregate, gossip
from repro.core.types import CommConfig
from repro.core.compression import get_compressor

mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.key(0), (8, 1000))

# --- manual schedules == psum (exact) --------------------------------------
for impl in ("ring", "rhd"):
    def f(v):
        return collectives.allreduce(v[0], ("data",), impl=impl)
    got = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                                check_vma=False))(x)
    want = jnp.tile(x.sum(0)[None], (8, 1))
    np.testing.assert_allclose(np.asarray(got).reshape(8, -1), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print(impl, "== psum OK")

# --- byte accounting: ring moves 2N(n-1)/n ---------------------------------
with comms.capture() as log:
    jax.jit(shard_map(lambda v: collectives.allreduce(v[0], ("data",), impl="ring"),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
           ).lower(jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
byts = log.total_bytes()
expect = 2 * (8 - 1) / 8 * 1024 * 4
assert abs(byts - expect) < 1e-6, (byts, expect)
print("ring bytes OK:", byts)

# --- compressed aggregation: topk with k=n equals dense mean ----------------
grads = {"w": jax.random.normal(jax.random.key(1), (8, 64, 4)),
         "b": jax.random.normal(jax.random.key(2), (8, 16))}
def agg_with(comm):
    plan = aggregate.make_bucket_plan(comm, {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in grads.items()})
    def f(g):
        g = {k: v[0] for k, v in g.items()}
        state = aggregate.init_comm_state(comm, plan)
        out, _ = aggregate.aggregate_gradients(comm, plan, g, state, jax.random.key(0), ("data",))
        return out
    return jax.jit(shard_map(f, mesh=mesh,
        in_specs=({k: P("data") for k in grads},), out_specs={"w": P(), "b": P()},
        check_vma=False))(grads)

dense = agg_with(CommConfig())
topk_full = agg_with(CommConfig(compressor="topk", compressor_kwargs={"ratio": 1.0}))
for k in grads:
    np.testing.assert_allclose(np.asarray(dense[k]), np.asarray(grads[k].mean(0)), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(topk_full[k]), np.asarray(dense[k]), rtol=1e-5, atol=1e-6)
print("topk(k=n) == dense mean OK")

# majority vote == sign of sum of signs
sv = agg_with(CommConfig(compressor="signsgd"))
for k in grads:
    want = np.where(np.sign(np.asarray(grads[k])).sum(0) >= 0, 1.0, -1.0)
    np.testing.assert_array_equal(np.asarray(sv[k]), want)
print("signsgd majority OK")

# unbiased quantizer mean error shrinks with levels
err = {}
for lv in (2, 64):
    q = agg_with(CommConfig(compressor="qsgd", compressor_kwargs={"levels": lv}))
    err[lv] = float(sum(jnp.linalg.norm(q[k] - dense[k]) for k in grads))
assert err[64] < err[2], err
print("qsgd level scaling OK", err)

# --- gossip: mixing preserves the global mean; CHOCO reaches consensus ------
params = [jax.random.normal(jax.random.key(3), (8, 128))]
def mix(v):
    out = gossip.dpsgd_mix([v[0][0]], ("data",))
    return out[0]
mixed = jax.jit(shard_map(lambda v: mix([v]), mesh=mesh, in_specs=P("data"),
                out_specs=P("data"), check_vma=False))(params[0])
np.testing.assert_allclose(np.asarray(mixed.reshape(8, -1).mean(0)),
                           np.asarray(params[0].mean(0)), rtol=1e-5, atol=1e-6)
print("dpsgd mean conservation OK")

comp = get_compressor("topk", ratio=0.25)
comm = CommConfig(gossip_step_size=0.8)
def choco_rounds(v):
    xs = [v[0]]
    st = gossip.choco_init(xs)
    for t in range(60):
        xs, st = gossip.choco_mix(comm, comp, jax.random.fold_in(jax.random.key(9), t), xs, st, ("data",))
    return xs[0]
out = jax.jit(shard_map(choco_rounds, mesh=mesh, in_specs=P("data"),
              out_specs=P("data"), check_vma=False))(params[0])
out = np.asarray(out).reshape(8, -1)
spread0 = np.linalg.norm(np.asarray(params[0]) - np.asarray(params[0]).mean(0), axis=1).mean()
spread1 = np.linalg.norm(out - out.mean(0), axis=1).mean()
assert spread1 < spread0 * 0.5, (spread0, spread1)
print("choco consensus OK", spread0, "->", spread1)
print("MD-COLLECTIVES OK")
"""


@pytest.mark.slow
def test_collectives_multidevice():
    out = run_subprocess_devices(SCRIPT, n_devices=8, timeout=1200)
    assert "MD-COLLECTIVES OK" in out
