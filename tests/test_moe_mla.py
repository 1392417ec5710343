"""DeepSeek-V2's block in the program against the benchmark's plain reference
(``bench/reference/deepseek_v2.py``), at a small size on the CPU: latent
attention with YaRN, the dropless held-experts layer and its shares of an
expert-parallel layer, the sequence-wise balance loss."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bench import program, weights
from bench.reference import deepseek_v2 as R
from bench.reference.training import _is_shape, leaf_paths, make_einsum
from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.sharding import AxisCtx, make_plan, tree_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EIN = make_einsum("f32")
B, S = 2, 32


def small_config(**kw) -> dict:
    """The benchmark's configuration file at a small size: 1 dense and 2
    expert layers, a router of 16, 4 experts held, top-3, 2 shared, MLA
    32/16/16/16, YaRN as published, float32."""
    with open(os.path.join(REPO, "bench", "configs", "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    cfg.update(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96, vocab=256,
               kv_lora=32, qk_nope_dim=16, qk_rope_dim=16, v_head_dim=16, n_experts=4,
               router_experts=16, experts_per_token=3, n_shared_experts=2, d_ff_expert=32,
               param_dtype="float32", compute_dtype="float32", router_aux_coef=0.1)
    cfg.update(kw)
    return cfg


def seeded_params(cfg: dict, seed: int = 0) -> dict:
    layout = R.param_layout(cfg)
    shapes, treedef = jax.tree.flatten(layout, is_leaf=_is_shape)
    paths = leaf_paths(layout)
    made = weights.make(seed, dict(zip(paths, shapes)), jnp.float32)
    return jax.tree.unflatten(treedef, [made[p] for p in paths])


def program_loss_and_grad(cfg: dict, params, tokens, labels):
    mc = program.model_config(cfg)
    specs = tree_specs(T.build_defs(mc, make_plan(mc, 1)))
    bsp = {"tokens": P(), "labels": P()}
    f = jax.value_and_grad(lambda p, b: T.forward_loss(mc, p, b, AxisCtx()), has_aux=True)
    fn = jax.jit(jax.shard_map(f, mesh=make_test_mesh(1, 1), in_specs=(specs, bsp),
                               out_specs=((P(), P()), specs), check_vma=False))
    (loss, metrics), grad = fn(params, {"tokens": tokens, "labels": labels})
    return loss, metrics, grad


def _batch(cfg):
    key = jax.random.key(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg["vocab"])
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg["vocab"])
    return tokens, labels


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@pytest.mark.parametrize("variant", [
    {},  # as published: softmax top-k gates not renormalized, sequence-wise loss
    {"norm_topk_prob": True, "seq_aux": False},  # renormalized gates, batch-level loss
])
def test_program_matches_reference(variant):
    """Loss and every leaf's gradient, on the benchmark's seeded weights in
    the reference's layout, which is the program's."""
    cfg = small_config(**variant)
    mc = program.model_config(cfg)
    abstract = T.abstract_params(mc, 1)[0]
    params = seeded_params(cfg)
    assert program.leaf_paths(abstract) == leaf_paths(R.param_layout(cfg))
    assert [a.shape for a in jax.tree.leaves(abstract)] == [
        x.shape for x in jax.tree.leaves(params)]
    tokens, labels = _batch(cfg)
    loss, metrics, grad = program_loss_and_grad(cfg, params, tokens, labels)
    ref_loss, ref_grad = jax.value_and_grad(
        lambda p: R.loss_fn(cfg, EIN, p, tokens, labels))(params)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * abs(float(ref_loss))
    assert float(metrics["aux"]) > 0
    # rows the held experts computed, summed over the 2 expert layers
    assert 0 < float(metrics["moe_routed_rows"]) < 2 * B * S * cfg["experts_per_token"]
    # f32 both, summed in another order: a few f32 ulps of the largest entry
    for path, a, b in zip(leaf_paths(R.param_layout(cfg)), jax.tree.leaves(grad),
                          jax.tree.leaves(ref_grad)):
        assert _rel(a, b) < 1e-5, (path, _rel(a, b))


def _moe_params(cfg: dict, seed: int = 3) -> dict:
    """One expert layer's parameters in the program's layout (unstacked)."""
    p = seeded_params(cfg, seed)["blocks"]["0"]["moe"]
    return jax.tree.map(lambda x: x[0], p)


def _program_moe(cfg: dict, p: dict, x, shards: int = 1):
    """``moe_ffn`` as ``shards`` chips run it: each holds its slice of the
    experts and of the shared experts' width, and ``psum`` over the model
    axis combines them (here a vmapped axis)."""
    mc = program.model_config(cfg)
    E_l = p["wi"].shape[0] // shards

    def one(ps):
        return L.moe_ffn(mc, ps, x, AxisCtx())

    part = {"router": jnp.broadcast_to(p["router"], (shards, *p["router"].shape)),
            **{n: p[n].reshape(shards, E_l, *p[n].shape[1:]) for n in ("wi", "wg", "wo")}}
    if "shared" in p:
        s = p["shared"]
        part["shared"] = {
            "wi": s["wi"].reshape(s["wi"].shape[0], shards, -1).swapaxes(0, 1),
            "wg": s["wg"].reshape(s["wg"].shape[0], shards, -1).swapaxes(0, 1),
            "wo": s["wo"].reshape(shards, -1, s["wo"].shape[1])}
    y, stats = jax.vmap(one, axis_name="model")(part)
    return y[0], stats  # the output combined; each shard's own stats


def _hidden(cfg, seed=5):
    return jax.random.normal(jax.random.key(seed), (B, S, cfg["d_model"]), jnp.float32)


def test_expert_shares_sum_to_whole_layer():
    """Four disjoint shares of a 16-expert layer (4 experts held each, as
    four chips of an expert-parallel layer hold them): their routed parts,
    plus the shared experts once, give the uncut reference layer."""
    whole = small_config(n_experts=16)
    p = _moe_params(whole)
    x = _hidden(whole)
    ref, ref_aux = R._moe(whole, EIN, p, x)
    routed, stats = _program_moe(small_config(), {k: v for k, v in p.items() if k != "shared"},
                                 x, shards=4)
    shared = R._swiglu(EIN, p["shared"], x.reshape(B * S, -1)).reshape(x.shape)
    assert _rel(routed + shared, ref) < 1e-5
    np.testing.assert_allclose(stats["aux"], float(ref_aux), rtol=1e-6)
    # each share computes only the rows routed to it: the T*k rows once in all
    assert float(jnp.sum(stats["moe_routed_rows"])) == B * S * whole["experts_per_token"]
    y, _ = _program_moe(small_config(), p, x, shards=4)  # shared experts row-parallel
    assert _rel(y, ref) < 1e-5


def test_one_share_matches_the_reference_share():
    """The layer with 4 of the router's 16 experts held (one chip's share)
    against the reference given the same share."""
    cfg = small_config()
    p = _moe_params(cfg)
    x = _hidden(cfg)
    y, stats = _program_moe(cfg, p, x)
    ref, ref_aux = R._moe(cfg, EIN, p, x)
    assert _rel(y, ref) < 1e-5
    assert abs(float(stats["aux"][0]) - float(ref_aux)) < 1e-6


def test_dropless_when_every_token_picks_the_same_experts():
    """A router forced to send every token to the same k held experts: each
    of them gets all T rows, far past any capacity, and the layer still
    matches the reference, which has none."""
    cfg = small_config()
    k, T_ = cfg["experts_per_token"], B * S
    p = _moe_params(cfg)
    x = _hidden(cfg).at[..., 0].set(8.0)  # one large shared feature...
    p["router"] = p["router"].at[0, :k].set(4.0)  # ...that experts 0..k-1 score highly
    y, stats = _program_moe(cfg, p, x)
    gates, _ = R._gates(cfg, EIN, p["router"], x.reshape(T_, -1), B)
    assert bool(jnp.all(gates[:, :k] > 0)) and bool(jnp.all(gates[:, k:] == 0))
    ref, _ = R._moe(cfg, EIN, p, x)
    assert _rel(y, ref) < 1e-5
    assert float(stats["moe_routed_rows"][0]) == T_ * k
    assert float(stats["moe_max_expert_rows"][0]) == T_


@pytest.mark.parametrize("sizes", [(100, 0, 300, 50), (256, 256, 256, 256), (0, 0, 0, 0)])
def test_grouped_matmul_kernel(sizes):
    """The TPU's grouped-matmul kernel (interpreted) against ``ragged_dot``:
    values and both gradients, zero past the groups (an empty group, every
    row grouped, no row grouped)."""
    from repro.kernels import ops

    R, a, b = 1024, 256, 384
    x = jax.random.normal(jax.random.key(0), (R, a), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (4, a, b)) * 0.1).astype(jnp.bfloat16)
    dy = jax.random.normal(jax.random.key(2), (R, b), jnp.float32).astype(jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    grouped = int(jnp.sum(sizes))
    y, vjp = jax.vjp(lambda x, w: ops.grouped_matmul(x, w, sizes), x, w)
    ry, rvjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)
    for name, u, v in zip(("y", "dx", "dw"), (y, *vjp(dy)), (ry, *rvjp(dy))):
        assert u.shape == v.shape and u.dtype == v.dtype, name
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        # both accumulate in f32 and round once to bf16
        assert np.abs(u - v).max() <= 2e-2 * max(np.abs(v).max(), 1.0), name
    assert not np.asarray(y[grouped:], np.float32).any()
    assert not np.asarray(vjp(dy)[0][grouped:], np.float32).any()


def test_yarn_published_numbers():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096 positions, beta 32/1,
    mscale = mscale_all_dim = 0.707) on its 64 rope dims."""
    cfg = get_config("deepseek-v2-lite-16b")
    assert L.yarn_mscale(40, 0.707) == pytest.approx(1.26080, abs=5e-6)
    assert L.softmax_scale(cfg, 192) == pytest.approx(0.114721, abs=5e-7)
    assert L.softmax_scale(cfg.with_updates(rope_type="rope"), 192) == 192**-0.5
    inv = L.yarn_inv_freq(cfg, 64)
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: floor(10.47) = 10 and ceil(22.51) = 23; extrapolated
    # below, interpolated (divided by 40) above, a linear ramp between
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    assert inv[16] == pytest.approx(extra[16] * (ramp / 40 + 1 - ramp), rel=1e-6)
    ref_cfg = {"rope_theta": 10000.0, "rope_factor": 40, "rope_original_len": 4096,
               "yarn_beta_fast": 32, "yarn_beta_slow": 1}
    np.testing.assert_allclose(inv, R._yarn_inv_freq(ref_cfg, 64), rtol=1e-6)
    # cos and sin are scaled by mscale(40, 0.707) / mscale(40, 0.707) = 1
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 64))
    pos = jnp.broadcast_to(jnp.arange(8), (3, 1, 8))
    y = L.apply_rope(cfg, x, pos)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1), np.linalg.norm(x, axis=-1),
                               rtol=1e-5)
    assert math.isclose(float(L.apply_rope(cfg, x, pos * 0)[0, 0, 0, 0]), float(x[0, 0, 0, 0]),
                        rel_tol=1e-6)


def test_flops_count_the_held_experts_expected_share():
    """The benchmark configuration's FLOPs per token count 6 x 8 / 64 = 0.75
    held experts a token, not k = 6: 2.88 GFLOP a token at 4,096 positions."""
    with open(os.path.join(REPO, "bench", "configs", "deepseek-v2-lite.json")) as f:
        cfg = json.load(f)
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + (2 + 0.75) * expert
    matmul = 7 * attn + 3 * 2048 * 10944 + 6 * moe + 12800 * 2048
    assert R.matmul_params(cfg) == matmul
    per_token = 6 * matmul + 6 * 7 * 16 * 320 * 4096
    assert R.flops_per_token(cfg, 4096) == per_token
    assert 2.87e9 < per_token < 2.89e9
