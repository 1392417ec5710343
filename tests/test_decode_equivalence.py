"""Decode-cache correctness: prefill(S tokens) + decode_step must produce the
same next-token distribution as a full forward pass over S+1 tokens.

This validates the ring-buffer cache layout, rope-at-absolute-position
storage, windowed masking, RWKV/SSM state carry and MLA latent caching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.models.sharding import AxisCtx, make_plan, tree_specs
from repro.models.transformer import build_defs
from repro.launch import specs as SP

ARCHS_TO_CHECK = [
    "qwen3-0.6b",        # dense GQA + qk-norm
    "glm4-9b",           # partial rope, kv=2
    "gemma3-12b",        # sliding-window ring cache
    "deepseek-v2-lite-16b",  # MLA latent cache + MoE
    "rwkv6-3b",          # recurrent state
    "hymba-1.5b",        # hybrid attn+ssm state
    "seamless-m4t-large-v2",  # enc-dec cross attention
]


@pytest.mark.parametrize("arch", ARCHS_TO_CHECK)
def test_decode_matches_full_forward(arch):
    # MoE layers are dropless, so a token's expert output does not depend
    # on how many tokens share the batch (T=B in decode, B*(S+1) in the
    # full forward)
    cfg = get_config(arch).reduced().with_updates(compute_dtype="float32", param_dtype="float32")
    mesh = make_test_mesh(1, 1)
    ax = AxisCtx()
    params = T.init_params(cfg, jax.random.key(0), 1)
    S = 24
    B = 2
    k = jax.random.key(1)
    toks = jax.random.randint(jax.random.fold_in(k, 1), (B, S + 1), 0, cfg.vocab)
    extras = {}
    if cfg.modality == "vision":
        extras["patches"] = jax.random.normal(jax.random.fold_in(k, 2), (B, 8, cfg.d_model))
    if cfg.is_encoder_decoder:
        extras["frames"] = jax.random.normal(jax.random.fold_in(k, 3), (B, 8, cfg.d_model))

    # cache capacity S+1: decoding token index S must not evict position 0
    # (the production ring is steady-state — at capacity it drops the oldest)
    shape = InputShape("t", S + 1, B, "decode")
    cache_abs, cps = SP.serve_cache_specs(cfg.with_updates(compute_dtype="float32"), mesh, shape)
    baxes, saxes = SP.batch_sharding_plan(mesh, shape)

    specs = tree_specs(build_defs(cfg, make_plan(cfg, 1)))
    bsp = {"tokens": P(("data",)), **{kk: P(("data",)) for kk in extras}}

    def prefill_fn(p, b):
        return T.prefill(cfg, p, b, ax, max_seq=S + 1)

    pf = jax.jit(shard_map(prefill_fn, mesh=mesh, in_specs=(specs, bsp),
                               out_specs=(P(baxes), cps), check_vma=False))
    _, cache = pf(params, {"tokens": toks[:, :S], **extras})

    def decode_fn(p, c, t):
        return T.decode_step(cfg, p, c, t, ax, seq_axes=saxes, max_seq=S + 1)

    df = jax.jit(shard_map(decode_fn, mesh=mesh, in_specs=(specs, cps, P(baxes)),
                               out_specs=(P(baxes), cps), check_vma=False))
    next_tok, _ = df(params, cache, toks[:, S:S + 1])

    # reference: full forward over S+1 tokens, argmax at the last position
    def full_fn(p, b):
        x = T._embed_inputs(cfg, p, b, ax)
        Bf, Sf, _ = x.shape
        pos = T.make_positions(cfg, Bf, Sf)
        enc = T._encode(cfg, p, b, ax) if cfg.is_encoder_decoder else None
        pat = cfg.attn_pattern
        for pp in p["prefix"]:
            x, _, _ = T._run_block(cfg, pp, x, ax, attn_type=pat[0], seq_len=Sf,
                                   positions=pos, enc_out=enc, collect_cache=False)
        for grp in (p["blocks"] if not cfg.scan_layers else []):
            pass
        def super_block(x, pgroup):
            for i, at in enumerate(pat):
                x, _, _ = T._run_block(cfg, pgroup[str(i)], x, ax, attn_type=at,
                                       seq_len=Sf, positions=pos, enc_out=enc,
                                       collect_cache=False)
            return x, ()
        if cfg.scan_layers:
            x, _ = jax.lax.scan(super_block, x, p["blocks"])
        else:
            for pgroup in p["blocks"]:
                x, _ = super_block(x, pgroup)
        from repro.models import layers as L
        x = L.rmsnorm(p["ln_f"], x)
        logits = L.logits_local(p["embed"], x[:, -1:], ax)
        return jnp.argmax(logits, -1)

    ff = jax.jit(shard_map(full_fn, mesh=mesh, in_specs=(specs, bsp),
                               out_specs=P(baxes), check_vma=False))
    expected = ff(params, {"tokens": toks, **extras})
    np.testing.assert_array_equal(np.asarray(next_tok), np.asarray(expected)), arch
