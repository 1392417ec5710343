"""Every Pallas kernel compiles for a TPU v5e chip at a real bucket size.

The interpret-mode tests (test_kernels.py, test_wire_formats.py) check what
the kernels compute; they cannot see what the TPU compiler refuses: blocks
not aligned to the native tiles, casts and reductions Mosaic does not
lower, shape casts it cannot lay out.  These tests compile each kernel with
``interpret=False`` for a described ``v5e:2x2`` topology (no chip needed)
at a 4M-element bucket, W=4 gathered workers for the wire kernels, and the
attention kernels at the benchmark cells' shapes (DeepSeek-V2-Lite's MLA
with its q/k head dim zero-padded to 256).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file."""

import pytest

N = 4 * 1024 * 1024  # elements per bucket
W = 4  # gathered workers for the wire-reduce kernels
LANES = 128
ROWS = N // LANES


@pytest.fixture(scope="module")
def v5e():
    """The described ``v5e:2x2`` topology (four chips)."""
    import os

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


def _cases():
    """name -> (kernel call, [(shape, dtype name)]) at the shapes the
    ``kernels.ops`` wrappers hand the kernels for an N-element bucket."""
    import functools

    from repro.kernels import (flash_attention, qsgd, qsgd_ef, sign_pack, terngrad,
                               threshold_sparsify, wire_reduce, wkv6)

    x2, s11 = ((ROWS, LANES), "float32"), ((1, 1), "float32")
    words = ROWS // sign_pack.BITS  # 32 signs per 32-bit word
    tern_words = ROWS // wire_reduce.TERN_SLOTS  # 16 two-bit slots per word
    int8_words = ROWS // qsgd.CODES  # 4 int8 codes per word
    weights = ((W, LANES), "float32")
    bh, seq, hd = 64, 4096, 64  # wkv6: B*H rows of a 4096-token sequence
    cases = {
        "qsgd_2d": (qsgd.qsgd_2d, [x2, x2, s11, s11]),
        "qsgd_ef_2d": (qsgd_ef.qsgd_ef_2d, [x2, x2, x2, s11, s11, s11]),
        "terngrad_2d": (terngrad.terngrad_2d, [x2, x2, s11]),
        "sign_pack_2d": (sign_pack.sign_pack_2d, [x2]),
        "sign_unpack_2d": (sign_pack.sign_unpack_2d, [((words, LANES), "int32")]),
        "sign_vote_3d": (wire_reduce.sign_vote_3d,
                         [((W, words, LANES), "int32"), weights]),
        "tern_pack_2d": (wire_reduce.tern_pack_2d, [((ROWS, LANES), "int8")]),
        "tern_acc_3d": (wire_reduce.tern_acc_3d,
                        [((W, tern_words, LANES), "int32"), weights]),
        "int8_acc_3d": (wire_reduce.int8_acc_3d,
                        [((W, int8_words, LANES), "int32"), weights, s11]),
        "threshold_2d": (threshold_sparsify.threshold_2d, [x2, s11]),
        "wkv6_chunked": (wkv6.wkv6_chunked,
                         [((bh, seq, hd), "float32")] * 4
                         + [((bh, 1, hd), "float32"), ((bh, hd, hd), "float32")]),
    }
    # the attention kernels at the benchmark's cells: GLM-4-9B (4096 tokens,
    # 32 query heads on 2 KV heads) and Qwen3-0.6B (2048, 16 on 8), hd 128
    for cell, (S, H, KV) in {"glm4": (4096, 32, 2), "qwen3": (2048, 16, 8)}.items():
        q, kv = ((1, S, H, 128), "bfloat16"), ((1, S, KV, 128), "bfloat16")
        row = ((1, H, 1, S), "float32")
        bq, bk = flash_attention.block_sizes(S, S)
        kw = dict(window=S, block_q=bq, block_k=bk)
        fwd = [q, kv, kv, ((1,), "int32")]
        cases[f"flash_fwd_{cell}"] = (functools.partial(flash_attention.flash_fwd, **kw), fwd)
        for k in ("dq", "dkv"):
            cases[f"flash_{k}_{cell}"] = (
                functools.partial(getattr(flash_attention, f"flash_bwd_{k}"), **kw),
                fwd + [q, row, row])
    # DeepSeek-V2-Lite's MLA: 4 rows of 4096 tokens, 16 heads, q/k zero-padded
    # from 192 to 256, v 128, and YaRN's softmax scale
    q, v = ((4, 4096, 16, 256), "bfloat16"), ((4, 4096, 16, 128), "bfloat16")
    row = ((4, 16, 1, 4096), "float32")
    kw = dict(window=4096, block_q=1024, block_k=1024, scale=192**-0.5 * 1.5896)
    cases["flash_fwd_mla"] = (functools.partial(flash_attention.flash_fwd, **kw),
                              [q, q, v, ((1,), "int32")])
    for k in ("dq", "dkv"):
        bwd = getattr(flash_attention, f"flash_bwd_{k}")
        cases[f"flash_{k}_mla"] = (functools.partial(bwd, **kw),
                                   [q, q, v, ((1,), "int32"), v, row, row])
    return cases


# kernel -> the ``kernels.ops`` wrapper that calls it: each kernel's
# custom-call carries that name, which a device trace reads
KERNELS = {"qsgd_2d": "qsgd_quantize", "qsgd_ef_2d": "qsgd_ef_fused",
           "terngrad_2d": "terngrad_quantize", "sign_pack_2d": "sign_pack",
           "sign_unpack_2d": "sign_unpack", "sign_vote_3d": "sign_vote",
           "tern_pack_2d": "tern_pack", "tern_acc_3d": "tern_acc",
           "int8_acc_3d": "int8_weighted_sum",
           "threshold_2d": "threshold_sparsify", "wkv6_chunked": "wkv6",
           **{f"flash_{k}_{cell}": f"flash_attention_{k}"
              for k in ("fwd", "dq", "dkv") for cell in ("glm4", "qwen3", "mla")}}


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    import re

    import jax

    kernel, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(lambda *a: kernel(*a, interpret=False)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    calls = re.findall(
        r"(%[\w.-]+) = [^\n]*custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert calls and all(re.fullmatch(rf"%{KERNELS[name]}\.\d+", c) for c in calls), calls


@pytest.mark.parametrize("n", [N, N - 1000])  # a whole number of tiles, and not
def test_int8_exchange_carries_words_without_byte_copies(v5e, n):
    """One bucket's compressed int8 exchange, compiled under shard_map for
    the four described chips: the codes go from the quantize kernel over
    the all-gather into the widening sum as 32-bit words, so no fusion of
    the optimized entry computation writes an 8-bit array of the bucket's
    size or W times it (the byte planes unpacked from the gathered words,
    or the codes re-tiled for the sum)."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import aggregate
    from repro.core.types import CommConfig, CommKnobs

    mesh = Mesh(np.asarray(v5e.devices).reshape(W), ("data",))
    comm = CommConfig(compressor="qsgd_kernel", compressor_kwargs={"levels": 127},
                      wire_format="compressed", bucket_mb=4 * n / 2**20)
    plan = aggregate.make_bucket_plan(comm, {"g": jax.ShapeDtypeStruct((n,), jnp.float32)})
    assert len(plan.buckets) == 1

    def exchange(g):
        state = aggregate.init_comm_state(comm, plan)
        knobs = CommKnobs.from_comm(comm, plan.knob_values(), n_workers=W).as_tree()
        out, _ = aggregate.aggregate_gradients(comm, plan, {"g": g}, state,
                                               jax.random.key(0), ("data",), knobs)
        return out["g"]

    step = jax.jit(shard_map(exchange, mesh=mesh, in_specs=P("data"), out_specs=P(),
                             check_vma=False))
    g = jax.ShapeDtypeStruct((W * n,), jnp.float32, sharding=NamedSharding(mesh, P("data")))
    text = step.lower(g).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert "int8_weighted_sum" in entry and "all-gather" in entry
    tile = 256 * LANES
    sizes = {n, W * n, n + (-n) % tile, W * (n + (-n) % tile)}
    narrow = []
    for name, out in re.findall(r"^\s*(%\S+) = (.*?) fusion\(", entry, re.M):
        for dims in re.findall(r"\b[su]8\[([\d,]*)\]", out):
            if int(np.prod([int(d) for d in dims.split(",") if d])) in sizes:
                narrow.append((name, out))
    assert not narrow, narrow
